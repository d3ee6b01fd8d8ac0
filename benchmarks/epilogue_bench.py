"""Fused-epilogue benchmark + smoke gate -> BENCH_epilogue.json.

Measures what matmul-epilogue fusion (``fusion.fuse_matmul_epilogues``)
buys and guarantees on the paper chain ``relu((A @ B) + C)``:

* **fusion leg** — the same expression planned with and without
  epilogue fusion.  GATED (always): the fused plan executes *strictly
  fewer* tasks, and the fused output is bit-identical to the unfused
  executor on every numpy backend (local / batched / cluster) for both
  f64 and f32 — the strict-precision tier of TESTING.md.  GATED (full
  runs): best-of-reps wall-clock speedup > 1.0x on the wave-batched
  executor at tile 16, where fusion eliminates a whole stacked-FUSED
  dispatch per wave.  Smoke runs record the ratio informationally —
  sub-second runs on shared CI hosts cannot resolve small deltas.
  Per-wave planned roofline fractions ride along informationally.
* **mixed leg** — opt-in mixed precision
  (``WaveExecutor(precision="mixed")``: f32 accumulate, bf16 store).
  GATED: output dtype is bfloat16 and values match the f64 eager oracle
  within the documented bf16 tolerance (rtol=atol=2e-2).
* **roofline leg** — a chaos-throttled node on the cluster executor
  must show up in the analytic roofline report
  (``core/roofline.py``): the throttled node is the ONLY below-band
  outlier (planned heterogeneity cancels in per-node peaks), and the
  run stays bit-identical to the local oracle.

Exit status is non-zero on any failed gate — wired into CI as the
``kernel-smoke`` job (``--smoke``: small inputs, writes
``BENCH_epilogue_smoke.json`` so the committed artifact is never
clobbered, per repo convention).

    PYTHONPATH=src python benchmarks/epilogue_bench.py [--smoke] [--out PATH]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

from repro.core import ClusteredMatrix as CM, CMMEngine, analytic_time_model
from repro.core.machine import c5_9xlarge, hetero_spec
from repro.core.roofline import roofline_report
from repro.exec.batched import WaveExecutor
from repro.exec.elastic import ChaosEvent, ClusterExecutor
from repro.exec.local import LocalExecutor
from repro.runtime.membership import MembershipConfig

TM = analytic_time_model()
FAST_NET = dict(link_bw=1e12, latency=1e-6)

SPEEDUP_GATE = 1.0                   # fused must not be slower (full runs)
BF16_TOL = 2e-2                      # documented bf16 tier (TESTING.md)


def _chain(n, dtype=np.float64):
    A = CM.rand(n, n, seed=0, dtype=dtype)
    B = CM.rand(n, n, seed=1, dtype=dtype)
    C = CM.rand(n, n, seed=2, dtype=dtype)
    return ((A @ B) + C).relu()


def _plan(expr, tile, fuse_epilogue, spec=None):
    eng = CMMEngine(spec or c5_9xlarge(2), TM, plan_cache=False,
                    fuse_epilogue=fuse_epilogue)
    return eng.plan(expr, tile=tile)


_BACKENDS = {
    "local": lambda: LocalExecutor(),
    "batched": lambda: WaveExecutor(backend="numpy"),
    "cluster": lambda: ClusterExecutor(),
}


def run_fusion(n: int, tile: int, reps: int, gate_speedup: bool) -> dict:
    """Task-count + bit-identity + wall-clock legs on relu((A@B)+C)."""
    res = {"case": "epilogue_fusion", "n": n, "tile": tile, "reps": reps}

    plan_f = _plan(_chain(n), tile, fuse_epilogue=True)
    plan_u = _plan(_chain(n), tile, fuse_epilogue=False)
    res["tasks_fused"] = len(plan_f.program.graph)
    res["tasks_unfused"] = len(plan_u.program.graph)
    res["ok_strictly_fewer_tasks"] = bool(
        res["tasks_fused"] < res["tasks_unfused"])

    # strict-precision tier: fused == unfused bitwise on numpy backends
    for dtype in (np.float64, np.float32):
        pf = _plan(_chain(n, dtype), tile, True)
        pu = _plan(_chain(n, dtype), tile, False)
        for name, mk in _BACKENDS.items():
            out_f = mk().execute(pf)
            out_u = mk().execute(pu)
            key = f"ok_bitident_{name}_{np.dtype(dtype).name}"
            res[key] = bool(np.array_equal(out_f, out_u)
                            and out_f.dtype == out_u.dtype == dtype)

    # wall-clock: paired unfused/fused wave-batched runs, back-to-back so
    # machine drift hits both legs alike; best-of-reps is the speedup
    t_f, t_u = [], []
    for _ in range(reps):
        t0 = time.perf_counter()
        WaveExecutor(backend="numpy").execute(plan_u)
        t_u.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        WaveExecutor(backend="numpy").execute(plan_f)
        t_f.append(time.perf_counter() - t0)
    res["fused_best_s"] = min(t_f)
    res["unfused_best_s"] = min(t_u)
    res["fused_all_s"] = t_f
    res["unfused_all_s"] = t_u
    res["speedup_x"] = min(t_u) / max(min(t_f), 1e-9)
    res["speedup_gated"] = bool(gate_speedup)
    if gate_speedup:
        res["ok_fused_not_slower"] = bool(res["speedup_x"] > SPEEDUP_GATE)

    # planned roofline fraction per wave (informational)
    waves = plan_f.roofline_waves(TM)
    fracs = [w["fraction"] for w in waves if w["fraction"] is not None]
    res["waves"] = len(waves)
    res["wave_fraction_median"] = (
        float(np.median(fracs)) if fracs else None)
    return res


def run_mixed(n: int, tile: int) -> dict:
    """Opt-in mixed precision: f32 accumulate, bf16 store, 2e-2 tier."""
    expr = _chain(n)
    plan = _plan(expr, tile, fuse_epilogue=True)
    out = WaveExecutor(backend="numpy", precision="mixed").execute(plan)
    ref = expr.eager()
    err = np.abs(np.asarray(out, dtype=np.float64) - ref)
    scale = np.maximum(np.abs(ref), 1.0)
    return {
        "case": "mixed_precision", "n": n, "tile": tile,
        "out_dtype": out.dtype.name,
        "tolerance": BF16_TOL,
        "max_rel_err": float((err / scale).max()),
        "ok_bf16_dtype": bool(out.dtype.name == "bfloat16"),
        "ok_within_bf16_tol": bool(np.allclose(
            np.asarray(out, dtype=np.float64), ref,
            rtol=BF16_TOL, atol=BF16_TOL)),
    }


def run_roofline_chaos(n: int, tile: int, throttle_node: int = 3,
                       throttle_seconds: float = 0.4) -> dict:
    """Throttled-node chaos run: the analytic roofline report must flag
    exactly the slowed node as the below-band outlier.  The spec plans
    nodes 2,3 as 2x slower — that *planned* heterogeneity cancels in the
    per-node peaks, so only the *unplanned* chaos throttle may flag."""
    spec = hetero_spec((2, 2, 1, 1), slowdown=(1.0, 1.0, 2.0, 2.0),
                       **FAST_NET)
    plan = _plan(_chain(n), tile, fuse_epilogue=True, spec=spec)
    ref = LocalExecutor().execute(plan)
    exe = ClusterExecutor(
        timemodel=TM,
        membership=MembershipConfig(heartbeat_interval_s=0.05),
        chaos=[ChaosEvent(after_done=0, throttle_node=throttle_node,
                          throttle_seconds=throttle_seconds)])
    out = exe.execute(plan)
    rep = roofline_report(exe.spans, plan, tm=TM, band=2.0)
    return {
        "case": "roofline_chaos", "n": n, "tile": tile,
        "throttle_node": throttle_node,
        "throttle_seconds": throttle_seconds,
        "below_band": list(rep.below_band),
        "fleet_fraction": rep.fleet_fraction,
        "node_fractions": {str(nr.node): nr.fraction for nr in rep.nodes},
        "node_samples": {str(nr.node): nr.samples for nr in rep.nodes},
        "summary": rep.summary(),
        "ok_throttled_node_flagged": bool(
            throttle_node in rep.below_band),
        "ok_only_throttled_flagged": bool(
            rep.below_band == [throttle_node]),
        "ok_bitident_chaos": bool(np.array_equal(ref, out)),
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small inputs (the CI kernel-smoke gate)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    if args.out is None:
        name = ("BENCH_epilogue_smoke.json" if args.smoke
                else "BENCH_epilogue.json")
        args.out = os.path.join(os.path.dirname(__file__), "..", name)

    if args.smoke:
        cases = [run_fusion(96, 16, reps=2, gate_speedup=False),
                 run_mixed(64, 16),
                 run_roofline_chaos(96, 32)]
    else:
        cases = [run_fusion(256, 16, reps=3, gate_speedup=True),
                 run_mixed(128, 16),
                 run_roofline_chaos(128, 32)]

    ok = True
    for c in cases:
        checks = {k: v for k, v in c.items() if k.startswith("ok_")}
        ok &= all(checks.values())
        line = " ".join(f"{k}={v}" for k, v in checks.items())
        if c["case"] == "epilogue_fusion":
            print(f"[epi] fusion n={c['n']} tile={c['tile']} "
                  f"tasks {c['tasks_unfused']}->{c['tasks_fused']} "
                  f"fused={c['fused_best_s']:.3f}s "
                  f"unfused={c['unfused_best_s']:.3f}s "
                  f"({c['speedup_x']:.3f}x, "
                  f"{'gated' if c['speedup_gated'] else 'informational'}) "
                  f"{line}")
        elif c["case"] == "mixed_precision":
            print(f"[epi] mixed n={c['n']} dtype={c['out_dtype']} "
                  f"max_rel_err={c['max_rel_err']:.2e} {line}")
        else:
            print(f"[epi] roofline n={c['n']} "
                  f"below_band={c['below_band']} "
                  f"fractions={ {k: (None if v is None else round(v, 3)) for k, v in c['node_fractions'].items()} } "
                  f"{line}")
        if not all(checks.values()):
            print(f"[epi] CHECK FAILED: {c['case']}", file=sys.stderr)

    with open(args.out, "w") as f:
        json.dump({"cases": cases}, f, indent=2)
    print(f"[epi] wrote {os.path.abspath(args.out)}")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
