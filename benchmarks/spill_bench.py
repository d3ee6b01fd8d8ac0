"""Bounded-arena spill benchmark + smoke gate -> BENCH_spill.json.

Measures what the tiered spill store (``runtime/spill.py`` + the bounded
``_NodeArena``) costs when idle and guarantees when active:

* **bit-identity leg** — the conformance program run with per-node
  ``mem_bytes`` at a third of its working set (footprint >= 3x budget)
  must spill for real (``spill_writes > 0``) and still produce the
  **exact bytes** of the unbounded oracle at the same tile size on the
  cluster executor.  GATED.
* **overhead leg** — the same program with a budget generous enough to
  never spill, so what is measured is pure bounded-arena bookkeeping
  (locked gets, LRU touches, byte accounting) against the unbounded
  fast path.  Paired back-to-back reps, best RATIO over reps; gated
  **< 10 %** at full size, informational in ``--smoke`` (small inputs
  cannot amortise fixed per-run costs).  Skipped, per the repo's
  wall-clock policy, while the 1-minute load average exceeds 1.25/cpu.
* **chaos leg** — ``mem_squeeze`` (shrink a node's budget mid-run) and
  ``alloc_fail`` (fail the Nth allocation) fired against the cluster
  executor: the run must complete bit-identically — the failures are
  absorbed by eviction and bounded retry, never a crash.  GATED.

Exit status is non-zero on any failed gate — wired into CI as the
``oom-smoke`` job (``--smoke``: small inputs, writes
``BENCH_spill_smoke.json`` so the committed artifact is never
clobbered, per repo convention).

    PYTHONPATH=src python benchmarks/spill_bench.py [--smoke] [--out PATH]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

from repro.core import ClusteredMatrix as CM, CMMEngine, analytic_time_model
from repro.core.machine import hetero_spec
from repro.exec.elastic import ChaosEvent, ClusterExecutor

REPS = 3          # best-of-N wall clocks (load spikes inflate, never deflate)
LOAD_BAR = 1.25   # loadavg/cpu above which wall gates are skipped

TM = analytic_time_model()
FAST_NET = dict(link_bw=1e12, latency=1e-6)


def _host_load_per_cpu() -> float:
    try:
        return os.getloadavg()[0] / max(1, os.cpu_count() or 1)
    except OSError:                     # pragma: no cover — non-POSIX
        return 0.0


def _spec(budget=None):
    return hetero_spec((3, 2, 1), mem_bytes=budget, **FAST_NET)


def _expr(n):
    A = CM.rand(n, n, seed=0)
    B = CM.rand(n, n, seed=1)
    return (A @ B) + A


def _plan(n, tile, budget=None):
    eng = CMMEngine(_spec(budget), TM, plan_cache=False)
    return eng.plan(_expr(n), tile=tile)


def _ws(n):
    return 3 * n * n * 8


def run_bit_identity(n: int, tile: int) -> dict:
    """Bounded (budget = ws/3) vs unbounded, bitwise."""
    budget = float(_ws(n) // 3)
    ref = ClusterExecutor(timemodel=TM).execute(_plan(n, tile))
    ex = ClusterExecutor(timemodel=TM)
    got = ex.execute(_plan(n, tile, budget))
    return {
        "case": "spill_bit_identity", "n": n, "tile": tile,
        "budget_bytes": budget, "working_set_bytes": _ws(n),
        "cluster_spill_writes": ex.stats["spill_writes"],
        "cluster_faults": ex.stats["faults"],
        "ok_spilled_for_real": bool(ex.stats["spill_writes"] > 0),
        "ok_bitident_cluster": bool(np.array_equal(ref, got)),
        "ok_no_leaked_spill_files": bool(
            ex.stats["leaked_spill_files"] == 0),
    }


def run_overhead(n: int, tile: int, gate: bool = True) -> dict:
    """Bounded-arena bookkeeping cost on a fits-in-RAM workload: the
    budget is 4x the working set, so the spill path is armed but never
    taken — the ratio isolates accounting/locking overhead.  Paired
    back-to-back reps; the rep's RATIO is what matters (wall noise on a
    shared host inflates both legs of a pair together)."""
    budget = float(4 * _ws(n))
    pairs = []
    ref = got = None
    spilled = 0
    for _ in range(REPS):
        t0 = time.perf_counter()
        ref = ClusterExecutor().execute(_plan(n, tile))
        wp = time.perf_counter() - t0
        ex = ClusterExecutor()
        t0 = time.perf_counter()
        got = ex.execute(_plan(n, tile, budget))
        wb = time.perf_counter() - t0
        spilled += ex.stats["spill_writes"]
        pairs.append((wb / wp, wp, wb))
    ratio, wall_unbounded, wall_bounded = min(pairs)
    overhead = ratio - 1.0
    load = _host_load_per_cpu()
    skipped = (not gate) or (overhead >= 0.10 and load > LOAD_BAR)
    if not gate:
        note = "overhead gate not enforced in --smoke (workload too " \
               "small to amortise fixed per-run costs); see the " \
               "committed BENCH_spill.json"
    elif skipped:
        note = (f"overhead gate SKIPPED: host load {load:.2f}/cpu > "
                f"{LOAD_BAR} (wall-clock policy)")
    else:
        note = "gated < 10%"
    return {
        "case": "bounded_arena_overhead", "n": n, "tile": tile,
        "reps": REPS,
        "budget_bytes": budget,
        "wall_unbounded_s": wall_unbounded,
        "wall_bounded_s": wall_bounded,
        "overhead_pct": 100.0 * overhead,
        "load_per_cpu": load,
        "ok_never_spilled": bool(spilled == 0),
        "ok_bitident_bounded": bool(np.array_equal(ref, got)),
        "ok_overhead_lt_10pct": True if skipped else bool(overhead < 0.10),
        "_note": note,
    }


def run_chaos(n: int, tile: int) -> dict:
    """mem_squeeze + alloc_fail against the cluster executor under a
    budget: graceful degradation (evict/retry), bit-identical result."""
    budget = float(_ws(n) // 2)
    ref = ClusterExecutor(timemodel=TM).execute(_plan(n, tile))
    ex = ClusterExecutor(
        timemodel=TM,
        chaos=(ChaosEvent(after_done=3, alloc_fail=0, alloc_fail_nth=2),
               ChaosEvent(after_done=5, mem_squeeze=1,
                          squeeze_bytes=int(_ws(n) // 6))))
    got = ex.execute(_plan(n, tile, budget))
    return {
        "case": "chaos_graceful_degradation", "n": n, "tile": tile,
        "budget_bytes": budget,
        "squeezes": ex.stats["squeezes"],
        "evictions": ex.stats["evictions"],
        "task_retries": ex.stats["task_retries"],
        "xfer_retries": ex.stats["xfer_retries"],
        "tiles_lost": ex.stats["tiles_lost"],
        "ok_squeeze_fired": bool(ex.stats["squeezes"] == 1),
        "ok_bitident_chaos": bool(np.array_equal(ref, got)),
        "ok_no_leaked_spill_files": bool(
            ex.stats["leaked_spill_files"] == 0),
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small inputs (the CI oom-smoke gate)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    if args.out is None:
        name = "BENCH_spill_smoke.json" if args.smoke \
            else "BENCH_spill.json"
        args.out = os.path.join(os.path.dirname(__file__), "..", name)

    if args.smoke:
        cases = [run_bit_identity(96, 16),
                 run_overhead(96, 16, gate=False),
                 run_chaos(96, 16)]
    else:
        # full size: big enough that per-tile compute dwarfs the
        # bounded-arena bookkeeping the overhead leg isolates
        cases = [run_bit_identity(256, 32),
                 run_overhead(512, 64),
                 run_chaos(256, 32)]

    ok = True
    for c in cases:
        checks = {k: v for k, v in c.items() if k.startswith("ok_")}
        ok &= all(checks.values())
        line = " ".join(f"{k}={v}" for k, v in checks.items())
        if c["case"] == "spill_bit_identity":
            print(f"[spill] bit-identity n={c['n']} "
                  f"budget={c['budget_bytes']:.0f}B "
                  f"({c['cluster_spill_writes']} writes/"
                  f"{c['cluster_faults']} faults) {line}")
        elif c["case"] == "bounded_arena_overhead":
            print(f"[spill] overhead n={c['n']} wall "
                  f"{c['wall_unbounded_s']:.3f}s->"
                  f"{c['wall_bounded_s']:.3f}s "
                  f"(+{c['overhead_pct']:.1f}%) {line}")
        else:
            print(f"[spill] chaos n={c['n']} squeezes={c['squeezes']} "
                  f"evictions={c['evictions']} "
                  f"retries={c['task_retries']}+{c['xfer_retries']} "
                  f"{line}")
        if not all(checks.values()):
            print(f"[spill] CHECK FAILED: {c['case']}", file=sys.stderr)

    with open(args.out, "w") as f:
        json.dump({"cases": cases}, f, indent=2)
    print(f"[spill] wrote {os.path.abspath(args.out)}")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
