"""Multi-process cluster executor benchmark + smoke gate -> BENCH_cluster.json.

Runs a tiled GEMM workload on a 2-node spec through the executor registry:
the multi-process ``cluster`` backend (one worker process per node, real
shared-memory XFERs) against the in-process ``local`` backend on the SAME
plan, checking:

* **oracle**: cluster output is bit-identical to the per-task executor and
  within tolerance of ``eager()`` (multi-k-tile reduction order);
* **placement**: every task ran in the worker process of its HEFT-assigned
  node (``exec_nodes`` vs ``Schedule.placements``);
* **transfers**: the schedule's cross-node edges produced real XFERs;
* **drift**: predicted-vs-actual makespan error is recorded per run for
  both backends (time-model drift tracking across PRs).

``--elastic`` switches to the chaos leg (-> BENCH_elastic.json): one run
SIGKILLs a worker node mid-bench (oracle-gated lineage recovery, recovery
overhead reported vs an unperturbed elastic run), and one run joins a
fresh node mid-bench and must strictly reduce the measured makespan
versus not joining.

Exit status is non-zero on any mismatch — wired into CI as the
cluster-executor smoke gate and the chaos-smoke gate (``--smoke``:
small inputs, ``BENCH_*_smoke.json`` outputs so committed artifacts are
never clobbered).

    PYTHONPATH=src python benchmarks/cluster_bench.py \\
        [--smoke] [--elastic] [--out PATH]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

from repro.core import CMMEngine, ClusteredMatrix as CM, analytic_time_model
from repro.core.machine import hetero_spec
from repro.exec import make_executor


def build_gemm(n: int, seed: int = 0) -> CM:
    A = CM.rand(n, n, seed=seed, name="A")
    B = CM.rand(n, n, seed=seed + 1, name="B")
    C = CM.rand(n, n, seed=seed + 2, name="C")
    return (A @ B) + C


def run_case(n: int, tile: int, node_workers, reps: int = 1) -> dict:
    from repro.core.profiler import calibrate_ipc
    spec = hetero_spec(node_workers, link_bw=1e12, latency=1e-6)
    tm = analytic_time_model()
    calibrate_ipc(tm)     # measured queue round-trip + shm copy bandwidth
    eng = CMMEngine(spec, tm, plan_cache=False)
    expr = build_gemm(n)
    plan = eng.plan(expr, tile=tile)

    results = {}
    walls = {}
    stats = {}
    for backend in ("local", "cluster"):
        ex = make_executor(backend)
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            out = ex.execute(plan)
            best = min(best, time.perf_counter() - t0)
        results[backend] = out
        walls[backend] = best
        stats[backend] = ex.stats

    ok_bitident = bool(np.array_equal(results["local"], results["cluster"]))
    ok_oracle = bool(np.allclose(results["cluster"], expr.eager(),
                                 rtol=1e-8, atol=1e-10))
    sched_nodes = {tid: p.node
                   for tid, p in plan.schedule.placements.items()}
    ok_placement = stats["cluster"]["exec_nodes"] == sched_nodes
    n_xfer_sched = len(plan.schedule.xfers(plan.program.graph))
    pred_local = plan.predicted_makespan
    pred_cluster = plan.cluster_makespan
    return {
        "n": n, "tile": tile, "node_workers": list(node_workers),
        "tasks": len(plan.program.graph),
        "wall_local_s": walls["local"],
        "wall_cluster_s": walls["cluster"],
        "predicted_local_s": pred_local,
        "predicted_cluster_s": pred_cluster,
        # signed relative drift of the time model: (actual - predicted)
        # / predicted, tracked per run so recalibration needs are visible
        "makespan_err_local": (walls["local"] - pred_local)
        / max(pred_local, 1e-12),
        "makespan_err_cluster": (walls["cluster"] - pred_cluster)
        / max(pred_cluster, 1e-12),
        "xfers": stats["cluster"]["xfers"],
        "xfers_scheduled": n_xfer_sched,
        "xfer_bytes": stats["cluster"]["xfer_bytes"],
        "peak_buffer_bytes": stats["cluster"]["peak_buffer_bytes"],
        "nodes_used": len(set(stats["cluster"]["exec_nodes"].values())),
        "ok_bitident": ok_bitident,
        "ok_oracle": ok_oracle,
        "ok_placement": ok_placement,
    }


def _elastic_wall(plan, tm, chaos=(), reps: int = 2,
                  blas_threads=None):
    """Best-of-``reps`` elastic wall clock + the last run's stats/output."""
    from repro.exec.elastic import ClusterExecutor
    best, out, stats = float("inf"), None, None
    for _ in range(reps):
        ex = ClusterExecutor(timemodel=tm, chaos=chaos,
                             blas_threads=blas_threads)
        t0 = time.perf_counter()
        out = ex.execute(plan)
        best = min(best, time.perf_counter() - t0)
        stats = ex.stats
    return best, out, stats


def run_elastic_kill_case(n: int, tile: int, node_workers,
                          reps: int = 2) -> dict:
    """SIGKILL one worker node mid-run: lineage recovery must keep the
    result bit-identical to the per-task executor; recovery overhead is
    the chaos wall minus the unperturbed elastic wall."""
    from repro.exec.elastic import ChaosEvent
    spec = hetero_spec(node_workers, link_bw=1e12, latency=1e-6)
    tm = analytic_time_model()
    eng = CMMEngine(spec, tm, plan_cache=False)
    expr = build_gemm(n)
    plan = eng.plan(expr, tile=tile)
    ref = make_executor("local").execute(plan)

    wall_plain, out_plain, _ = _elastic_wall(plan, tm, reps=reps)
    victim = 1
    kill_at = max(1, len(plan.program.graph) // 3)
    chaos = (ChaosEvent(after_done=kill_at, kill_node=victim),)
    wall_chaos, out_chaos, st = _elastic_wall(plan, tm, chaos, reps=reps)

    return {
        "case": "elastic_kill", "n": n, "tile": tile,
        "node_workers": list(node_workers),
        "tasks": len(plan.program.graph),
        "killed_node": victim, "killed_after_done": kill_at,
        "wall_elastic_s": wall_plain,
        "wall_elastic_chaos_s": wall_chaos,
        "recovery_overhead_s": wall_chaos - wall_plain,
        "recovered_tasks": st["recovered_tasks"],
        "replans": st["replans"],
        "deaths": st["deaths"],
        "recovery_seconds": st["recovery_seconds"],
        "ok_bitident": bool(np.array_equal(ref, out_chaos)
                            and np.array_equal(ref, out_plain)),
        "ok_oracle": bool(np.allclose(out_chaos, expr.eager(),
                                      rtol=1e-8, atol=1e-10)),
        "ok_death_detected": st["deaths"] == 1,
    }


def run_elastic_join_case(n: int, tile: int, join_workers: int = 2,
                          reps: int = 2,
                          floor_s: float = 0.03) -> dict:
    """A node joining mid-run must strictly reduce the measured makespan
    versus not joining (the frontier is re-planned onto it).

    The starting node is *weak*: its machine model carries a large
    compute slowdown and fault injection enforces a matching per-task
    service-time floor (``throttle``, a sleep — deliberately not
    CPU-bound, so the signal survives CPU-starved/shared CI runners
    where two busy processes do not actually run in parallel).  When a
    fast node joins, ``replan_frontier`` prices the weak node's slowdown
    and migrates the not-yet-dispatched frontier onto the joiner, which
    must strictly beat the no-join wall clock.  Both legs run the same
    throttle; the two legs are interleaved so host drift hits both.
    """
    from repro.exec.elastic import ChaosEvent
    spec = hetero_spec((1,), slowdown=(8.0,), link_bw=2e9, latency=2e-4)
    tm = analytic_time_model()
    eng = CMMEngine(spec, tm, plan_cache=False)
    expr = build_gemm(n)
    plan = eng.plan(expr, tile=tile)
    ref = make_executor("local").execute(plan)

    throttle = ChaosEvent(after_done=0, throttle_node=0,
                          throttle_seconds=floor_s)
    chaos_nojoin = (throttle,)
    chaos_join = (throttle,
                  ChaosEvent(after_done=5, join_workers=join_workers))
    wall_nojoin, wall_join = float("inf"), float("inf")
    out_nojoin = out_join = st = None
    for _ in range(reps):
        w, out_nojoin, _st = _elastic_wall(plan, tm, chaos_nojoin, reps=1,
                                           blas_threads=1)
        wall_nojoin = min(wall_nojoin, w)
        w, out_join, st = _elastic_wall(plan, tm, chaos_join, reps=1,
                                        blas_threads=1)
        wall_join = min(wall_join, w)

    return {
        "case": "elastic_join", "n": n, "tile": tile,
        "join_workers": join_workers,
        "tasks": len(plan.program.graph),
        "wall_nojoin_s": wall_nojoin,
        "wall_join_s": wall_join,
        "join_speedup": wall_nojoin / max(wall_join, 1e-12),
        "joined_node_tasks": sum(
            1 for node in st["exec_nodes"].values() if node == 1),
        "replans": st["replans"],
        "ok_bitident": bool(np.array_equal(ref, out_join)
                            and np.array_equal(ref, out_nojoin)),
        "ok_join_used": 1 in set(st["exec_nodes"].values()),
        "ok_join_speedup": wall_join < wall_nojoin,
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small inputs, oracle-checked (the CI gates)")
    ap.add_argument("--elastic", action="store_true",
                    help="chaos leg: mid-run node kill + mid-run join "
                         "through the cluster executor under chaos")
    ap.add_argument("--out", default=None,
                    help="output JSON path (default: BENCH_cluster.json / "
                         "BENCH_elastic.json, with a _smoke suffix under "
                         "--smoke so gates never clobber published "
                         "artifacts)")
    args = ap.parse_args()
    if args.out is None:
        base = "BENCH_elastic" if args.elastic else "BENCH_cluster"
        name = f"{base}_smoke.json" if args.smoke else f"{base}.json"
        args.out = os.path.join(os.path.dirname(__file__), "..", name)

    if args.elastic:
        if args.smoke:
            cases = [run_elastic_kill_case(192, 48, (2, 2)),
                     run_elastic_join_case(512, 256)]
        else:
            cases = [run_elastic_kill_case(384, 96, (2, 2), reps=3),
                     run_elastic_join_case(768, 256, reps=3)]
        ok = True
        for c in cases:
            checks = [v for k, v in c.items() if k.startswith("ok_")]
            ok &= all(checks)
            if c["case"] == "elastic_kill":
                print(f"[elastic] kill n={c['n']} tile={c['tile']} "
                      f"tasks={c['tasks']} "
                      f"plain={c['wall_elastic_s']:.3f}s "
                      f"chaos={c['wall_elastic_chaos_s']:.3f}s "
                      f"recovered={c['recovered_tasks']} "
                      f"replans={c['replans']} "
                      f"bitident={c['ok_bitident']} "
                      f"oracle={c['ok_oracle']}")
            else:
                print(f"[elastic] join n={c['n']} tile={c['tile']} "
                      f"tasks={c['tasks']} "
                      f"nojoin={c['wall_nojoin_s']:.3f}s "
                      f"join={c['wall_join_s']:.3f}s "
                      f"speedup={c['join_speedup']:.2f}x "
                      f"joined_tasks={c['joined_node_tasks']} "
                      f"bitident={c['ok_bitident']} "
                      f"speedup_ok={c['ok_join_speedup']}")
            if not all(checks):
                print(f"[elastic] CHECK FAILED: {c['case']}",
                      file=sys.stderr)
        with open(args.out, "w") as f:
            json.dump({"cases": cases}, f, indent=2)
        print(f"[elastic] wrote {os.path.abspath(args.out)}")
        return 0 if ok else 1

    if args.smoke:
        cases = [run_case(96, 32, (2, 1))]
    else:
        cases = [run_case(256, 64, (2, 1), reps=2),
                 run_case(384, 96, (3, 2, 1), reps=2)]

    ok = True
    for c in cases:
        ok &= c["ok_bitident"] and c["ok_oracle"] and c["ok_placement"]
        print(f"[cluster] n={c['n']} tile={c['tile']} "
              f"nodes={c['node_workers']} tasks={c['tasks']} "
              f"xfers={c['xfers']}/{c['xfers_scheduled']} "
              f"nodes_used={c['nodes_used']} "
              f"local={c['wall_local_s']:.3f}s "
              f"cluster={c['wall_cluster_s']:.3f}s "
              f"(err {c['makespan_err_cluster']:+.2f}) "
              f"bitident={c['ok_bitident']} oracle={c['ok_oracle']} "
              f"placement={c['ok_placement']}")
        if not (c["ok_bitident"] and c["ok_oracle"] and c["ok_placement"]):
            print(f"[cluster] CHECK FAILED at n={c['n']} tile={c['tile']}",
                  file=sys.stderr)

    with open(args.out, "w") as f:
        json.dump({"cases": cases}, f, indent=2)
    print(f"[cluster] wrote {os.path.abspath(args.out)}")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
