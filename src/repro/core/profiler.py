"""Offline profiling (CMM §3.4).

Runs each task family over a grid of operand sizes on the actual machine,
times it, and fits the Table-1 interpolation equations by least squares
(non-negative coefficients, ``PolyModel.fit``).  The fitted
``TimeModel`` is persisted to JSON and reused by the scheduler/simulator —
profiling is *offline*, scheduling uses only the model (as in the paper).
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .timemodel import PolyModel, TimeModel


def _time_call(fn, reps: int = 3) -> float:
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def profile_matmul(sizes: Sequence[int], reps: int = 3,
                   rng: Optional[np.random.Generator] = None
                   ) -> Tuple[List[Tuple[int, int, int]], List[float]]:
    rng = rng or np.random.default_rng(0)
    dims_list, times = [], []
    for m in sizes:
        # k = 1: the mat-vec tiles of a chain ordered for the fewest flops
        for k in (*sizes, 1):
            a = rng.standard_normal((m, m))
            b = rng.standard_normal((m, k))
            c = np.zeros((m, k))

            def run(a=a, b=b, c=c):
                np.add(c, a @ b, out=c)  # addmul: C += A @ B

            times.append(_time_call(run, reps))
            dims_list.append((m, m, k))
    return dims_list, times


def profile_ewise(sizes: Sequence[int], reps: int = 3,
                  rng: Optional[np.random.Generator] = None
                  ) -> Tuple[List[Tuple[int, int]], List[float]]:
    rng = rng or np.random.default_rng(1)
    dims_list, times = [], []
    for m in sizes:
        for n in sizes:
            a = rng.standard_normal((m, n))
            b = rng.standard_normal((m, n))

            def run(a=a, b=b):
                np.add(a, b)

            times.append(_time_call(run, reps))
            dims_list.append((m, n))
    return dims_list, times


def profile_fill(sizes: Sequence[int], reps: int = 3
                 ) -> Tuple[List[Tuple[int, int]], List[float]]:
    """Data-generation (fill) cost: RNG-bound, much slower than memcpy.

    Times the executor's actual per-tile path (``lazy.random_slice``, the
    canonical block RNG) so the model prices what FILL tasks really do.
    """
    from .lazy import random_slice
    dims_list, times = [], []
    for m in sizes:
        for n in sizes:

            def run(m=m, n=n):
                random_slice(m * n, (m, n), np.float64, 0, m, 0, n)

            times.append(_time_call(run, reps))
            dims_list.append((m, n))
    return dims_list, times


def profile_machine(sizes: Sequence[int] = (64, 128, 256, 384, 512),
                    reps: int = 3) -> TimeModel:
    """Full offline profile -> fitted TimeModel (compute families)."""
    tm = TimeModel()
    dims, times = profile_matmul(sizes, reps)
    tm.models["matmul"] = PolyModel.fit("matmul", dims, times)
    dims_e, times_e = profile_ewise(sizes, reps)
    tm.models["ewise"] = PolyModel.fit("ewise", dims_e, times_e)
    dims_f, times_f = profile_fill(sizes, reps)
    tm.models["fill"] = PolyModel.fit("ewise", dims_f, times_f)
    calibrate_contention(tm)
    calibrate_dispatch(tm)
    calibrate_batch_dispatch(tm)
    calibrate_ipc(tm)
    calibrate_compression(tm)
    return tm


def calibrate_contention(tm: TimeModel, n: int = 768, tile: int = 384,
                         reps: int = 2) -> float:
    """Fit the concurrent-worker throughput scale (§3.4 observed-time fit).

    The family models are profiled one call at a time, but the executor runs
    ``worker_procs`` tasks concurrently, each inside multi-threaded BLAS —
    on an oversubscribed or shared host the effective per-task throughput is
    lower.  Run a GEMM-bound tiled program for real and scale the model by
    the observed wall / simulated makespan (clamped to [1, 8])."""
    import time as _time

    from .engine import CMMEngine
    from .lazy import ClusteredMatrix as CM
    from .machine import local_spec

    tm.contention = 1.0          # fit against the uncalibrated model
    eng = CMMEngine(local_spec(1), tm, tile=tile)
    P = CM.rand(n, n, seed=0)
    expr = P @ P
    plan = eng.plan(expr)
    best = float("inf")
    for _ in range(reps):
        t0 = _time.perf_counter()
        eng.run(expr, plan=plan, workers=eng.spec.worker_procs)
        best = min(best, _time.perf_counter() - t0)
    scale = best / max(plan.predicted_makespan, 1e-12)
    tm.contention = min(max(scale, 1.0), 8.0)
    return tm.contention


def calibrate_dispatch(tm: TimeModel, n: int = 256, tile: int = 64,
                       workers: int = 3) -> float:
    """Fit the per-task dispatch overhead (threadpool/GIL cost dominates
    sub-ms tiles): run a small tiled program for real and attribute the
    wall-time excess over the simulated makespan to per-task overhead."""
    import time as _time

    from .engine import CMMEngine
    from .lazy import ClusteredMatrix as CM
    from .machine import c5_9xlarge

    eng = CMMEngine(c5_9xlarge(1), tm, tile=tile)
    A = CM.rand(n, n, seed=0)
    expr = A @ A
    plan = eng.plan(expr)
    t0 = _time.perf_counter()
    eng.run(expr, plan=plan, workers=workers)
    wall = _time.perf_counter() - t0
    n_tasks = len(plan.program.graph)
    # overhead per task, serialised over `workers` lanes
    over = max(0.0, (wall - plan.predicted_makespan) * workers / n_tasks)
    tm.dispatch_overhead = min(over, 5e-3)
    return tm.dispatch_overhead


def calibrate_batch_dispatch(tm: TimeModel, tile: int = 64,
                             reps: int = 3) -> float:
    """Fit the per-*batched-launch* overhead (wave executor cost model).

    One stacked kernel call pays a fixed Python/NumPy entry cost that is
    independent of how many tiles are stacked.  Time stacked launches
    across group sizes and take the OLS intercept — that intercept is what
    a wave group costs on top of its arithmetic, and what the strategy
    selector weighs against ``dispatch_overhead`` x tasks."""
    xs, ys = [], []
    rng = np.random.default_rng(0)
    for g in (1, 2, 8, 32):
        a = rng.standard_normal((g, tile, tile))
        b = rng.standard_normal((g, tile, tile))

        def run(a=a, b=b):
            np.matmul(a, b)

        ys.append(_time_call(run, reps))
        xs.append([1.0, float(g)])
    coef, *_ = np.linalg.lstsq(np.asarray(xs), np.asarray(ys), rcond=None)
    tm.batch_dispatch_overhead = float(min(max(coef[0], 1e-6), 5e-3))
    return tm.batch_dispatch_overhead


def _ipc_echo(inq, outq):                      # pragma: no cover - subprocess
    while True:
        msg = inq.get()
        if msg is None:
            break
        outq.put(msg)


def calibrate_ipc(tm: TimeModel, nbytes: int = 1 << 22,
                  reps: int = 5) -> Tuple[float, float]:
    """Fit the cluster executor's cost terms (§3.4 applied to processes):

    * ``process_dispatch_overhead`` / ``ipc_latency`` — one dispatch-queue
      round trip to a worker process (pickle + pipe + wakeup + ack), which
      the multi-process executor pays per task (and per XFER message);
    * ``ipc_bandwidth`` — throughput of a tile copy between two
      ``SharedMemory`` arenas, the executor's actual XFER data path.
    """
    import multiprocessing as mp
    from multiprocessing import shared_memory

    ctx = mp.get_context()
    inq, outq = ctx.Queue(), ctx.Queue()
    p = ctx.Process(target=_ipc_echo, args=(inq, outq), daemon=True)
    p.start()
    try:
        inq.put(0)                    # warm the queues / process
        outq.get(timeout=30)
        best = float("inf")
        for i in range(reps):
            t0 = time.perf_counter()
            inq.put(i)
            outq.get(timeout=30)
            best = min(best, time.perf_counter() - t0)
    finally:
        inq.put(None)
        p.join(timeout=10)
        if p.is_alive():              # pragma: no cover
            p.terminate()
    tm.process_dispatch_overhead = min(max(best, 1e-6), 5e-2)
    tm.ipc_latency = tm.process_dispatch_overhead

    src = shared_memory.SharedMemory(create=True, size=nbytes)
    dst = shared_memory.SharedMemory(create=True, size=nbytes)
    try:
        a = np.ndarray((nbytes // 8,), dtype=np.float64, buffer=src.buf)
        b = np.ndarray((nbytes // 8,), dtype=np.float64, buffer=dst.buf)
        a[:] = 1.0
        copy = _time_call(lambda: np.copyto(b, a), reps)
        tm.ipc_bandwidth = float(min(max(nbytes / max(copy, 1e-9), 1e8),
                                     1e12))
    finally:
        for s in (src, dst):
            s.close()
            s.unlink()
    return tm.process_dispatch_overhead, tm.ipc_bandwidth


def calibrate_compression(tm: TimeModel, nbytes: int = 1 << 22,
                          reps: int = 3) -> Tuple[float, float]:
    """Fit the wire-codec terms the per-edge XFER pricing runs on:

    * ``compress_bandwidth`` — raw bytes/s the codec encodes at on this
      host (the ``compress_cpu`` term of the pricing inequality);
    * ``compression_ratio_prior`` — expected raw/compressed ratio.

    The probe tile is *structured* (a low-rank f64 outer product — the
    shape of persisted intermediates and generated operands), not pure
    noise: the prior should reflect payloads where the codec can win at
    all.  On incompressible data the per-edge rule still falls back to
    ``"raw"`` because the measured wire bytes, not the prior, are what
    the executors count.
    """
    from ..runtime.wire import encode_tile

    side = max(int(np.sqrt(nbytes / 8)), 16)
    col = np.linspace(0.0, 1.0, side)
    probe = np.outer(col, np.ones(side))          # rank-1: compressible
    raw = probe.nbytes
    enc = _time_call(lambda: encode_tile(probe, "zlib"), reps)
    payload = encode_tile(probe, "zlib")
    tm.compress_bandwidth = float(min(max(raw / max(enc, 1e-9), 1e6), 1e11))
    tm.compression_ratio_prior = float(
        min(max(raw / max(len(payload), 1), 1.0), 64.0))
    return tm.compress_bandwidth, tm.compression_ratio_prior


def profile_comm_synthetic(spec, sizes_bytes: Sequence[int] = None,
                           noise: float = 0.03, seed: int = 0):
    """Synthesise comm-profile observations from the machine model.

    On the real cluster this function would round-trip buffers between node
    pairs; offline here, we sample the parametric link model with noise and
    refit — exercising the same per-pair regression path the paper describes
    (§3.4: "additionally taking the connection speeds between two nodes into
    account").  Returns {(a, b): (latency, bandwidth)} fitted per pair.
    """
    rng = np.random.default_rng(seed)
    sizes_bytes = sizes_bytes or [2 ** p for p in range(12, 27, 2)]
    fitted = {}
    for a in range(spec.n_nodes):
        for b in range(spec.n_nodes):
            if a == b:
                continue
            xs, ys = [], []
            for s in sizes_bytes:
                true = spec.comm_time(s, a, b)
                obs = true * (1.0 + noise * rng.standard_normal())
                xs.append([1.0, float(s)])
                ys.append(max(obs, 0.0))
            coef, *_ = np.linalg.lstsq(np.asarray(xs), np.asarray(ys),
                                       rcond=None)
            lat = max(float(coef[0]), 0.0)
            bw = 1.0 / max(float(coef[1]), 1e-30)
            fitted[(a, b)] = (lat, bw)
    return fitted
