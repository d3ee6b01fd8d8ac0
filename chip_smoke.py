"""Bring-up smoke of the CMM engine's device path on a TPU.

    python chip_smoke.py              # one chip: the engine's device path
    python chip_smoke.py --chips 4    # only the sharded GEMMs, on four chips

One chip, in order:

1. device check: print what JAX reports; anything but a TPU exits non-zero
   before any result (there is no CPU fallback);
2. kernel check: the wave executor's ADDMUL group call lowers to a Mosaic
   ``tpu_custom_call`` (compiled, not interpreted);
3. the paper's Markov, K-Means and Synth programs (``benchmarks/cmm_suite.py``)
   at n = 8192, float32, tile 1024 through ``CMMEngine.run`` on the
   ``batched-pallas`` executor, Markov also on ``kernel``; each result is
   compared with ``expr.eager()`` (NumPy from the same seeds) by its
   norm-wise relative error;
4. K-Means again at ``precision="mixed"`` (bf16 store, the 2e-2 tier);
5. a ``CMMSession`` power iteration: persist P, then ``u = persist(P @ u)``
   five times, one gather at the end, compared with NumPy.

``--chips 4`` runs only SUMMA (``matmul_2d``), Cannon and the reduce-scatter
GEMM of ``exec/sharded.py`` at n = 16384 float32, each compared with a
one-device ``jnp.dot`` at HIGHEST precision, and checks that each output is
spread over the four chips, a quarter on each.

Each phase prints its seconds and the compile count so far on its own line;
any failed check raises.  The last line of standard output is one JSON
object naming the device.  Everything runs in this one process: nothing
forks (the time model is ``analytic_time_model()``, not the profiler), and
JAX is first touched after the host-side imports.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

from benchmarks.cmm_suite import kmeans, markov, synth  # noqa: E402
from repro.core import (ClusteredMatrix as CM, CMMEngine,  # noqa: E402
                        CMMSession, analytic_time_model)

N, TILE, DTYPE = 8192, 1024, np.float32
N_SHARDED = 16384
SESSION_STEPS = 5
F32_BOUND = 1e-4        # norm-wise relative error of the float32 legs
MIXED_BOUND = 2e-2      # bf16-store tier (TESTING.md, numerics tiers)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Phases:
    """Times named phases and counts backend compiles while they run."""

    def __init__(self):
        self.compiles = 0
        self.seconds: dict = {}

    def on_event(self, event: str, duration: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            self.compiles += 1

    def run(self, name: str, fn, *args, **kw):
        t0 = time.perf_counter()
        out = fn(*args, **kw)
        self.seconds[name] = time.perf_counter() - t0
        print(f"phase {name}: {self.seconds[name]!r} s, "
              f"compiles so far {self.compiles}", flush=True)
        return out


def check(leg: str, out, ref, bound: float) -> float:
    """Norm-wise relative error of ``out`` against ``ref``; raises above
    ``bound`` or on a wrong shape or non-finite value."""
    dtype = np.asarray(out).dtype
    out = np.asarray(out, np.float64)
    ref = np.asarray(ref, np.float64)
    if out.shape != ref.shape:
        raise AssertionError(f"{leg}: shape {out.shape} != {ref.shape}")
    if not np.isfinite(out).all():
        raise AssertionError(f"{leg}: non-finite values")
    err = float(np.linalg.norm(out - ref) / np.linalg.norm(ref))
    print(f"leg {leg}: dtype {dtype}, rel_err {err!r}, bound {bound}",
          flush=True)
    if not err <= bound:
        raise AssertionError(f"{leg}: relative error {err} > {bound}")
    return err


def device_check(chips: int) -> dict:
    import jax
    devs = jax.devices()
    d = devs[0]
    print(f"device platform {d.platform}, kind {d.device_kind}, "
          f"count {len(devs)}, jax {jax.__version__}", flush=True)
    if d.platform != "tpu":
        sys.exit(f"chip_smoke: JAX found {d.platform!r}, not a TPU; "
                 f"there is no CPU fallback")
    if len(devs) < chips:
        sys.exit(f"chip_smoke: {chips} chips asked for, {len(devs)} found")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def kernel_check(tile: int) -> None:
    """Lower one ADDMUL group call exactly as ``exec/batched.py`` makes it
    and require the Mosaic custom call in its HLO."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops as kops
    blocks = kops._resolve_blocks(None, None, None, tile, tile, tile)
    fn = kops._addmul_batched_fn(*blocks, kops._interpret_default())
    x = jax.ShapeDtypeStruct((8, tile, tile), jnp.float32)
    if "tpu_custom_call" not in fn.lower(x, x, x).as_text():
        raise AssertionError("the ADDMUL group call has no tpu_custom_call")


def paper_legs(ph: Phases, engine: CMMEngine, n: int, tile: int,
               dtype) -> dict:
    """Markov, K-Means (strict and mixed) and Synth through the engine,
    each against its ``eager()`` reference; returns leg -> error."""
    errs = {}

    def leg(name, expr, ref, bound, executor, **kw):
        out = ph.run(name, engine.run, expr, tile=tile, executor=executor,
                     **kw)
        errs[name] = check(name, out, ref, bound)

    expr = markov(n, dtype=dtype)
    ref = ph.run("markov/reference", expr.eager)
    leg("markov/batched-pallas", expr, ref, F32_BOUND, "batched-pallas")
    leg("markov/kernel", expr, ref, F32_BOUND, "kernel")

    expr = kmeans(n, dtype=dtype)
    ref = ph.run("kmeans/reference", expr.eager)
    leg("kmeans/batched-pallas", expr, ref, F32_BOUND, "batched-pallas")
    leg("kmeans/mixed", expr, ref, MIXED_BOUND, "batched-pallas",
        precision="mixed")

    expr = synth(n, dtype=dtype)
    ref = ph.run("synth/reference", expr.eager)
    leg("synth/batched-pallas", expr, ref, F32_BOUND, "batched-pallas")
    return errs


def session_leg(engine: CMMEngine, n: int, tile: int, dtype,
                steps: int = SESSION_STEPS) -> float:
    """Resident power iteration ``u <- P @ u`` in a device-executor session,
    gathered once at the end and compared with NumPy."""
    P_expr = CM.rand(n, n, seed=10, dtype=dtype, name="P")
    u_expr = CM.rand(n, 1, seed=11, dtype=dtype, name="u")
    with CMMSession(engine, executor="batched-pallas", tile=tile) as s:
        P = s.persist(P_expr)
        u = s.persist(u_expr)
        for _ in range(steps):
            u = s.persist(P @ u)
        out = u.to_numpy()
    P_np, ref = P_expr.eager(), u_expr.eager()
    for _ in range(steps):
        ref = P_np @ ref
    return check("session/power-iteration", out, ref, F32_BOUND)


def sharded_legs(n: int, seed: int = 0) -> dict:
    """SUMMA, Cannon and reduce-scatter GEMMs over four devices against a
    one-device HIGHEST-precision ``jnp.dot``; returns leg -> error."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding
    from repro.exec.sharded import (matmul_2d, matmul_cannon,
                                    reduce_scatter_matmul)
    from repro.launch.mesh import auto_mesh

    devs = jax.devices()
    if len(devs) != 4:
        raise AssertionError(f"the sharded legs need 4 devices, got "
                             f"{len(devs)}")
    one = SingleDeviceSharding(devs[0])
    ka, kb = jax.random.split(jax.random.PRNGKey(seed))
    normal = jax.jit(lambda k: jax.random.normal(k, (n, n), jnp.float32),
                     out_shardings=one)
    a, b = normal(ka), normal(kb)
    ref = jax.jit(lambda x, y: jnp.dot(x, y, precision="highest"))(a, b)
    err_fn = jax.jit(lambda x, r: jnp.linalg.norm(x - r)
                     / jnp.linalg.norm(r))

    grid = auto_mesh((2, 2), ("x", "y"))
    line = auto_mesh((4,), ("model",))
    legs = {
        "summa": (grid, P("x", "y"), P("x", "y"),
                  lambda x, y: matmul_2d(x, y, grid)),
        "cannon": (grid, P("x", "y"), P("x", "y"),
                   lambda x, y: matmul_cannon(x, y, grid)),
        "reduce-scatter": (line, P(None, "model"), P("model", None),
                           lambda x, y: reduce_scatter_matmul(x, y, line)),
    }
    errs = {}
    for name, (mesh, spec_a, spec_b, fn) in legs.items():
        xa = jax.device_put(a, NamedSharding(mesh, spec_a))
        xb = jax.device_put(b, NamedSharding(mesh, spec_b))
        with jax.default_matmul_precision("highest"):
            out = jax.jit(fn)(xa, xb)
        shards = out.addressable_shards
        if {s.device for s in shards} != set(devs) or any(
                s.data.size * 4 != n * n for s in shards):
            raise AssertionError(
                f"sharded/{name}: output is not a quarter on each of 4 "
                f"devices: {[(s.device, s.data.shape) for s in shards]}")
        err = float(err_fn(jax.device_put(out, one), ref))
        print(f"leg sharded/{name}: shards "
              f"{[tuple(s.data.shape) for s in shards]}, rel_err {err!r}, "
              f"bound {F32_BOUND}", flush=True)
        if not err <= F32_BOUND:
            raise AssertionError(f"sharded/{name}: relative error {err} > "
                                 f"{F32_BOUND}")
        errs[name] = err
    return errs


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded GEMMs on a 2x2 mesh")
    args = ap.parse_args()

    import jax
    from repro.kernels.ops import enable_compile_cache
    print(f"compile cache {enable_compile_cache()}", flush=True)
    ph = Phases()
    jax.monitoring.register_event_duration_secs_listener(ph.on_event)
    device = ph.run("device", device_check, args.chips)

    if args.chips == 4:
        ph.run("sharded", sharded_legs, N_SHARDED)
    else:
        ph.run("kernel", kernel_check, TILE)
        engine = CMMEngine(timemodel=analytic_time_model())
        print(f"programs: n {N}, dtype {np.dtype(DTYPE).name}, tile {TILE}",
              flush=True)
        paper_legs(ph, engine, N, TILE, DTYPE)
        ph.run("session", session_leg, engine, N, TILE, DTYPE)
    print(f"total {sum(ph.seconds.values())!r} s, compiles {ph.compiles}",
          flush=True)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
