"""Local executor: runs a planned tiled task graph with NumPy and
materialises the result.

Executes tasks in HEFT-priority order with a worker pool sized from the
plan's machine model (``ClusterSpec.worker_procs`` x nodes, falling back to
``os.cpu_count()`` — NumPy/BLAS releases the GIL inside GEMM, so tiles
genuinely overlap).  This is both the single-node execution path of the
framework and the correctness oracle for the scheduler: whatever HEFT
decided, the data dependencies enforced here must reproduce
``ClusteredMatrix.eager()`` exactly.

Zero-copy tile runtime:

* FILL generates **only its own tile** — INPUT tiles are views into the user
  array, RANDOM tiles come from the counter-based canonical block RNG
  (``lazy.random_slice``), ZEROS/EYE build just the tile.  No full leaf is
  ever materialised.
* CALLOC allocates in the expression dtype (``TiledProgram.dtypes``).
* Buffers are reference-counted: a tile is freed as soon as its last reader
  finishes, so peak memory is bounded by *live* tiles, not all tiles.
  ``self.stats`` records the observed peak.
* No global buffer lock: each buffer has exactly one writer at a time (the
  dependency edges guarantee it), so writes go straight into the dict;
  only the tiny refcount/scheduler bookkeeping is serialised.
"""
from __future__ import annotations

import os
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, Optional

import numpy as np

from ..core.fusion import eval_fused
from ..core.graph import (Task, TaskGraph, TaskKind, TileRef,
                          matmul_epilogue, matmul_flags)
from ..core.lazy import EWISE_FNS, apply_scale, leaf_slice
from ..core.tiling import assemble, result_sets_of, tile_slices
from ..runtime.telemetry import Tracer


class LocalExecutor:
    def __init__(self, workers: Optional[int] = None,
                 free_buffers: bool = True, trace: bool = True):
        self.workers = workers
        self.free_buffers = free_buffers
        #: flight recorder: EXEC spans per task (node 0, one lane per
        #: pool thread); ``spans`` holds the last run's timeline
        self.trace = trace
        self.spans: list = []
        #: filled by execute(): peak_buffer_bytes, tasks_run, buffers_freed
        self.stats: Dict[str, int] = {}

    def _nworkers(self, plan) -> int:
        if self.workers:
            return self.workers
        spec = getattr(plan, "spec", None)
        if spec is not None:
            return max(1, spec.total_workers())
        return os.cpu_count() or 4

    def execute(self, plan) -> np.ndarray:
        g: TaskGraph = plan.program.graph
        tile = plan.tile
        leaf_nodes = plan.program.leaf_nodes
        dtypes = plan.program.dtypes
        residency = getattr(plan, "residency", None)
        rsets = result_sets_of(g)
        buffers: Dict[TileRef, np.ndarray] = {}

        # readers per tile buffer (+1 keeps every result tile alive for
        # final assembly, and every persisted tile alive for session
        # retention — retained output tiles are thereby excluded from
        # refcount freeing); freed at zero by the last reader
        refcnt: Dict[TileRef, int] = {}
        for t in g:
            for r in t.ins:
                refcnt[r] = refcnt.get(r, 0) + 1
        for rs in rsets:
            for r in rs.tiles:
                refcnt[r] = refcnt.get(r, 0) + 1
        mem = {"cur": 0, "peak": 0, "freed": 0}
        #: bytes currently accounted per tile ref — a task that REBINDS
        #: ``buffers[t.out]`` over an earlier allocation (ufunc output over
        #: a CALLOC'd tile, a fused epilogue's result) must release the old
        #: allocation's bytes, or ``peak_buffer_bytes`` drifts upward
        owned: Dict[TileRef, int] = {}

        def run_task(t: Task):
            if t.kind is TaskKind.CALLOC:
                dt = dtypes.get(t.payload, np.float64)
                buffers[t.out] = np.zeros(t.out.shape, dtype=dt)
                return
            if t.kind is TaskKind.FILL:
                node = leaf_nodes[t.payload]
                rs = tile_slices(node.shape[0], tile[0])[t.out.i]
                cs = tile_slices(node.shape[1], tile[1])[t.out.j]
                buffers[t.out] = leaf_slice(node, rs[0], rs[1], cs[0], cs[1])
                return
            if t.kind is TaskKind.RESIDENT:
                # zero-copy: alias the session-resident tile into this
                # run's buffer namespace (read-only downstream)
                buffers[t.out] = residency.tile(t.payload, t.out.i, t.out.j)
                return
            if t.kind is TaskKind.ADDMUL:
                ta, tb = matmul_flags(t.payload)
                epi = matmul_epilogue(t.payload)
                a = buffers[t.ins[0]]
                b = buffers[t.ins[1]]
                a = a.T if ta else a
                b = b.T if tb else b
                c = buffers[t.out]
                c += a @ b
                if epi is not None:
                    # last task of the k-chain: apply the fused
                    # elementwise epilogue over the accumulated tile
                    buffers[t.out] = eval_fused(
                        epi, [c] + [buffers[r] for r in t.ins[2:]])
                return
            if t.kind is TaskKind.ADD:
                buffers[t.out] = buffers[t.ins[0]] + buffers[t.ins[1]]
                return
            if t.kind is TaskKind.SUB:
                buffers[t.out] = buffers[t.ins[0]] - buffers[t.ins[1]]
                return
            if t.kind is TaskKind.EWMUL:
                buffers[t.out] = buffers[t.ins[0]] * buffers[t.ins[1]]
                return
            if t.kind is TaskKind.SCALE:
                kind, s = t.payload
                buffers[t.out] = apply_scale(kind, buffers[t.ins[0]], s)
                return
            if t.kind is TaskKind.EWISE:
                buffers[t.out] = EWISE_FNS[t.payload](buffers[t.ins[0]])
                return
            if t.kind is TaskKind.FUSED:
                buffers[t.out] = eval_fused(
                    t.payload, [buffers[r] for r in t.ins])
                return
            if t.kind is TaskKind.TRANSPOSE:
                buffers[t.out] = np.ascontiguousarray(buffers[t.ins[0]].T)
                return
            if t.kind is TaskKind.TAKECOPY:
                # gather to master: locally a no-op (buffer already present)
                return
            raise ValueError(t.kind)  # pragma: no cover

        # dependency-driven execution in schedule priority order
        prio = {tid: i for i, tid in enumerate(plan.schedule.order)}
        deps_left = {t.tid: len(t.preds) for t in g}
        import heapq
        ready = [(prio[t.tid], t.tid) for t in g.sources()]
        heapq.heapify(ready)
        done_lock = threading.Lock()
        cv = threading.Condition(done_lock)
        inflight = [0]

        nworkers = self._nworkers(plan)

        def account(t: Task):
            """Memory bookkeeping after a task ran (under cv)."""
            if t.out is not None and t.kind not in (TaskKind.TAKECOPY,
                                                    TaskKind.RESIDENT):
                # RESIDENT tiles are session-owned (not this run's memory)
                buf = buffers.get(t.out)
                if buf is not None:
                    # views (zero-copy INPUT slices) own no memory
                    new = buf.nbytes if buf.base is None else 0
                    old = owned.get(t.out, 0)
                    if new != old:
                        mem["cur"] += new - old
                        if new:
                            owned[t.out] = new
                        else:
                            owned.pop(t.out, None)
                    mem["peak"] = max(mem["peak"], mem["cur"])
            if not self.free_buffers:
                return
            for r in t.ins:
                refcnt[r] -= 1
                if refcnt[r] == 0:
                    buf = buffers.pop(r, None)
                    if buf is not None:
                        mem["cur"] -= owned.pop(r, 0)
                        mem["freed"] += 1

        def worker_done(tid: int):
            with cv:
                account(g.tasks[tid])
                for s in g.tasks[tid].succs:
                    deps_left[s] -= 1
                    if deps_left[s] == 0:
                        heapq.heappush(ready, (prio[s], s))
                inflight[0] -= 1
                cv.notify_all()

        errors: list = []
        # flight recorder: one EXEC span per task on node 0, lanes keyed
        # by pool thread — the in-process equivalent of the cluster
        # workers' piggybacked spans
        tracer = Tracer(node=0, enabled=self.trace)
        with ThreadPoolExecutor(max_workers=nworkers) as pool:
            submitted = 0
            total = len(g)
            with cv:
                while submitted < total and not errors:
                    while not ready and not errors:
                        cv.wait()
                    if errors:
                        break
                    _, tid = heapq.heappop(ready)
                    inflight[0] += 1
                    submitted += 1

                    def job(tid=tid):
                        try:
                            t = g.tasks[tid]
                            with tracer.span(t.kind.name, cat="EXEC",
                                             tid=tid, kind=t.kind.name):
                                run_task(t)
                        except BaseException as e:  # surface task failures
                            errors.append(e)
                        finally:
                            worker_done(tid)

                    pool.submit(job)
                while inflight[0] > 0:
                    cv.wait()
        if errors:
            raise errors[0]

        # retention: persisted roots' tiles move to the session store.
        # Computed tiles transfer zero-copy (the run's array becomes the
        # resident tile); VIEW-backed tiles (INPUT leaf slices into the
        # user's array) are copied out — a resident handle must be a
        # snapshot that owns its memory, not an alias the caller can
        # mutate from under the session.
        retained = 0
        outs = []
        gather_bytes = 0
        for rs in rsets:
            if rs.gather:
                vals = {r: buffers[r] for r in rs.tiles}
                gather_bytes += sum(r.bytes for r in rs.tiles)
                outs.append(assemble(vals, rs.shape, tile, rs.uid))
            else:
                for r in rs.tiles:
                    buf = buffers[r]
                    if buf.base is not None:
                        buf = np.ascontiguousarray(buf)
                    residency.retain_local(rs.uid, r.i, r.j, buf)
                    retained += 1

        self.spans = tracer.drain()
        self.stats = {"peak_buffer_bytes": mem["peak"],
                      "cur_buffer_bytes": mem["cur"],
                      "buffers_freed": mem["freed"],
                      "tasks_run": len(g),
                      "workers": nworkers,
                      "gather_bytes": gather_bytes,
                      "retained_tiles": retained}
        if not outs:
            return None
        return outs[0] if len(outs) == 1 else outs
