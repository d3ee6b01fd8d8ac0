"""Observability benchmark + smoke gate -> BENCH_obs.json.

Measures what the flight recorder (``runtime/telemetry.py`` +
``core/drift.py``) costs and guarantees:

* **overhead leg** — the same cluster plan run back-to-back with
  tracing on and off, repeated; the best-of-reps wall-clock ratio is
  the tracing overhead.  GATED (full runs): overhead < 5%, which is
  the policy that justifies tracing-on-by-default.  GATED (always):
  the traced run is bit-identical to the untraced run, the trace
  carries exactly one EXEC span per scheduled task, and it exports as
  valid Chrome-trace JSON.  Smoke runs record the ratio
  informationally — sub-second runs on shared CI hosts cannot resolve
  a 5% wall-clock delta.
* **drift leg** — a chaos-throttled node on the cluster executor must
  show up in the drift report: per-node residual rows for EVERY node
  of the spec, the throttled node flagged as a straggler prior, and
  the run still bit-identical to the local oracle.  The recovered
  priors are then fed back through
  ``ClusterExecutor(straggler_priors=...)`` (round-trip
  recorded informationally).

Exit status is non-zero on any failed gate — wired into CI as the
``obs-smoke`` job (``--smoke``: small inputs, writes
``BENCH_obs_smoke.json`` so the committed artifact is never clobbered,
per repo convention).

    PYTHONPATH=src python benchmarks/obs_bench.py [--smoke] [--out PATH]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

from repro.core import ClusteredMatrix as CM, CMMEngine, analytic_time_model
from repro.core.drift import drift_report
from repro.core.machine import hetero_spec
from repro.exec.elastic import ChaosEvent, ClusterExecutor
from repro.exec.local import LocalExecutor
from repro.runtime.membership import MembershipConfig
from repro.runtime.telemetry import chrome_trace

TM = analytic_time_model()
FAST_NET = dict(link_bw=1e12, latency=1e-6)

OVERHEAD_GATE = 1.05                 # tracing-on-by-default policy: < 5%


def _spec(nodes=(3, 2, 1)):
    return hetero_spec(nodes, **FAST_NET)


def _expr(n):
    A = CM.rand(n, n, seed=0)
    B = CM.rand(n, n, seed=1)
    return (A @ B) + A


def _plan(expr, tile, spec):
    eng = CMMEngine(spec, TM, plan_cache=False)
    return eng.plan(expr, tile=tile)


def _valid_chrome_trace(spans) -> bool:
    doc = chrome_trace(spans)
    try:
        json.dumps(doc)              # must be JSON-serializable end to end
    except (TypeError, ValueError):
        return False
    xs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    return bool(xs) and all(
        e.get("ph") in ("X", "M")
        and isinstance(e.get("pid"), int) and isinstance(e.get("tid"), int)
        and (e["ph"] != "X" or (e["ts"] >= 0.0 and e["dur"] >= 0.0))
        for e in doc["traceEvents"])


def run_overhead(n: int, tile: int, reps: int, gate: bool) -> dict:
    """Paired tracing-on/off cluster runs on one plan; best-of-reps
    ratio is the overhead.  Pairs run back-to-back so machine drift
    (thermal, noisy neighbours) hits both legs alike."""
    spec = _spec()
    plan = _plan(_expr(n), tile, spec)
    t_on, t_off = [], []
    out_on = out_off = None
    spans = None
    for _ in range(reps):
        t0 = time.perf_counter()
        off = ClusterExecutor(trace=False)
        out_off = off.execute(plan)
        t_off.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        on = ClusterExecutor(trace=True)
        out_on = on.execute(plan)
        t_on.append(time.perf_counter() - t0)
        spans = on.spans
    ratio = min(t_on) / max(min(t_off), 1e-9)
    exec_tids = sorted(s.args["tid"] for s in spans if s.cat == "EXEC")
    res = {
        "case": "tracing_overhead", "n": n, "tile": tile, "reps": reps,
        "traced_best_s": min(t_on),
        "untraced_best_s": min(t_off),
        "traced_all_s": t_on,
        "untraced_all_s": t_off,
        "overhead_x": ratio,
        "overhead_gate_x": OVERHEAD_GATE,
        "overhead_gated": bool(gate),
        "spans": len(spans),
        "exec_spans": len(exec_tids),
        "ok_bitident_traced": bool(np.array_equal(out_on, out_off)),
        "ok_exec_span_per_task": bool(
            exec_tids == sorted(plan.schedule.placements)),
        "ok_valid_chrome_trace": _valid_chrome_trace(spans),
    }
    if gate:
        res["ok_overhead_lt_5pct"] = bool(ratio < OVERHEAD_GATE)
    return res


def run_drift_chaos(n: int, tile: int, throttle_node: int = 3,
                    throttle_seconds: float = 0.4) -> dict:
    """Throttled-node chaos run: the drift report must flag exactly the
    slowed node as a straggler prior, with residual rows for every node
    of the spec, and the run must stay bit-identical to the local
    oracle.  The priors then seed a fresh cluster run's membership
    detector (round-trip recorded informationally)."""
    spec = _spec((2, 2, 1, 1))
    plan = _plan(_expr(n), tile, spec)
    ref = LocalExecutor().execute(plan)
    exe = ClusterExecutor(
        timemodel=TM,
        membership=MembershipConfig(heartbeat_interval_s=0.05),
        chaos=[ChaosEvent(after_done=0, throttle_node=throttle_node,
                          throttle_seconds=throttle_seconds)])
    out = exe.execute(plan)
    rep = drift_report(exe.spans, plan, tm=TM)
    rows = {nd.node: nd for nd in rep.nodes}
    flagged = rep.straggler_priors

    # round-trip: feed the recovered priors into a fresh run's detector
    rt = ClusterExecutor(
        timemodel=TM,
        membership=MembershipConfig(heartbeat_interval_s=0.05),
        straggler_priors=flagged,
        chaos=[ChaosEvent(after_done=0, throttle_node=throttle_node,
                          throttle_seconds=throttle_seconds)])
    out_rt = rt.execute(plan)
    return {
        "case": "drift_chaos", "n": n, "tile": tile,
        "throttle_node": throttle_node,
        "throttle_seconds": throttle_seconds,
        "straggler_priors": list(flagged),
        "fleet_ratio": rep.fleet_ratio,
        "node_residuals": {str(nd.node): nd.ratio for nd in rep.nodes},
        "node_samples": {str(nd.node): nd.samples for nd in rep.nodes},
        "roundtrip_straggles": rt.stats["straggles"],
        "roundtrip_speculated": rt.stats["speculated"],
        "ok_throttled_node_flagged": bool(throttle_node in flagged),
        "ok_only_throttled_flagged": bool(flagged == [throttle_node]),
        "ok_row_per_spec_node": bool(
            set(rows) >= set(range(spec.n_nodes))),
        "ok_bitident_chaos": bool(np.array_equal(ref, out)),
        "ok_bitident_roundtrip": bool(np.array_equal(ref, out_rt)),
        "ok_valid_chrome_trace": _valid_chrome_trace(exe.spans),
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small inputs (the CI obs-smoke gate)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    if args.out is None:
        name = "BENCH_obs_smoke.json" if args.smoke else "BENCH_obs.json"
        args.out = os.path.join(os.path.dirname(__file__), "..", name)

    if args.smoke:
        cases = [run_overhead(96, 16, reps=2, gate=False),
                 run_drift_chaos(96, 32)]
    else:
        cases = [run_overhead(384, 48, reps=3, gate=True),
                 run_drift_chaos(128, 32)]

    ok = True
    for c in cases:
        checks = {k: v for k, v in c.items() if k.startswith("ok_")}
        ok &= all(checks.values())
        line = " ".join(f"{k}={v}" for k, v in checks.items())
        if c["case"] == "tracing_overhead":
            print(f"[obs] overhead n={c['n']} "
                  f"traced={c['traced_best_s']:.3f}s "
                  f"untraced={c['untraced_best_s']:.3f}s "
                  f"({c['overhead_x']:.3f}x, "
                  f"{'gated' if c['overhead_gated'] else 'informational'}) "
                  f"{c['spans']} spans {line}")
        else:
            print(f"[obs] drift n={c['n']} "
                  f"priors={c['straggler_priors']} "
                  f"residuals={ {k: round(v, 2) for k, v in c['node_residuals'].items() if v is not None} } "
                  f"{line}")
        if not all(checks.values()):
            print(f"[obs] CHECK FAILED: {c['case']}", file=sys.stderr)

    with open(args.out, "w") as f:
        json.dump({"cases": cases}, f, indent=2)
    print(f"[obs] wrote {os.path.abspath(args.out)}")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
