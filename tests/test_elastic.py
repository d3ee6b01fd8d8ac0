"""Fault-injection tier: the elastic runtime under membership churn.

Determinism contract (see TESTING.md): every task kind is a deterministic
NumPy call, so duplicated, resurrected and re-routed executions produce
bit-identical tiles — results under kill/join/straggle chaos must equal
``LocalExecutor`` exactly.  These tests SIGKILL real worker processes and
spawn real joiners; they are marked ``chaos`` (and the paper-suite sweep
additionally ``slow``).
"""
import numpy as np
import pytest

from repro.core import (ClusteredMatrix as CM, CMMEngine, TimeModel,
                        analytic_time_model, c5_9xlarge)
from repro.core.heft import Placement, replan_frontier
from repro.core.machine import hetero_spec
from repro.core.simulator import (churn_adjusted_makespan,
                                  predict_recovery_cost)
from repro.exec import EXECUTORS, make_executor
from repro.exec.elastic import ChaosEvent, ClusterExecutor
from repro.exec.local import LocalExecutor
from repro.runtime.membership import (DEATH, RECOVER, STRAGGLE,
                                      MembershipConfig, MembershipService)

TM = analytic_time_model()
FAST_NET = dict(link_bw=1e12, latency=1e-6)


def _plan(expr, tile, spec):
    eng = CMMEngine(spec, TM, plan_cache=False)
    return eng.plan(expr, tile=tile)


def _synth(n=64):
    A = CM.rand(n, n, seed=0)
    B = CM.rand(n, n, seed=1)
    C = CM.rand(n, n, seed=2)
    D = CM.rand(n, n, seed=3)
    return (A @ B) + (C @ D)


# -- ClusterSpec membership deltas ------------------------------------------

def test_spec_without_node_drains_in_place():
    spec = hetero_spec((3, 2, 1))
    dead = spec.without_node(1)
    assert dead.n_nodes == 3                      # indices stay stable
    assert dead.workers_at(1) == 0
    assert dead.workers_at(0) == 3 and dead.workers_at(2) == 1
    assert dead.alive_nodes() == (0, 2)
    assert dead.total_workers() == 4
    with pytest.raises(ValueError, match="master"):
        spec.without_node(spec.master)
    with pytest.raises(ValueError, match="no node"):
        spec.without_node(7)


def test_spec_with_node_appends():
    spec = hetero_spec((2, 1), slowdown=(1.0, 1.5))
    grown = spec.with_node(4, slowdown=2.0)
    assert grown.n_nodes == 3
    assert grown.workers_at(2) == 4
    assert grown.node_slowdown(2) == 2.0
    assert grown.node_slowdown(1) == 1.5          # existing entries kept
    assert grown.alive_nodes() == (0, 1, 2)
    with pytest.raises(ValueError):
        spec.with_node(0)
    # homogeneous specs materialise their per-node tuples on first delta
    homog = c5_9xlarge(2).with_node()
    assert homog.workers_at(2) == homog.worker_procs


def test_spec_with_slowdown_replaces_one_entry():
    spec = hetero_spec((2, 2))
    slow = spec.with_slowdown(1, 3.0)
    assert slow.node_slowdown(1) == 3.0
    assert slow.node_slowdown(0) == 1.0
    assert slow.workers_at(1) == 2


# -- membership service ------------------------------------------------------

class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_membership_heartbeat_timeout_death_once():
    clk = _Clock()
    cfg = MembershipConfig(heartbeat_timeout_s=1.0)
    ms = MembershipService(range(3), cfg=cfg, clock=clk)
    clk.t = 0.5
    ms.heartbeat(0)
    ms.heartbeat(1)
    clk.t = 1.2
    evs = ms.poll()
    assert {e.node for e in evs if e.kind == DEATH} == {2}
    assert ms.alive_nodes() == [0, 1]
    assert ms.poll() == []                        # DEATH fires exactly once
    clk.t = 3.0
    with pytest.raises(RuntimeError, match="master"):
        ms.poll()                                 # master staleness is fatal


def test_membership_process_exit_beats_heartbeat():
    ms = MembershipService(range(2), clock=_Clock())
    evs = ms.poll({0: True, 1: False})
    assert [e.node for e in evs] == [1]
    assert "exited" in evs[0].reason


def test_membership_master_death_is_fatal():
    ms = MembershipService(range(2), master=0, clock=_Clock())
    with pytest.raises(RuntimeError, match="master"):
        ms.mark_dead(0)


def test_membership_straggler_patience_and_rearm():
    clk = _Clock()
    cfg = MembershipConfig(straggler_factor=2.0, straggler_patience=3,
                           straggler_poll_interval_s=1.0,
                           straggler_min_tasks=1)
    ms = MembershipService(range(3), cfg=cfg, clock=clk)
    for _ in range(8):
        ms.record_task(0, 0.01)
        ms.record_task(1, 0.01)
        ms.record_task(2, 0.10)                   # 10x the median
    evs = []
    for i in range(4):
        clk.t += 1.0
        evs += ms.poll()
    stragglers = [e for e in evs if e.kind == STRAGGLE]
    assert [e.node for e in stragglers] == [2]    # patience, then fire once
    # recovery emits RECOVER (lifts the re-planning penalty) + re-arms
    for _ in range(40):
        ms.record_task(2, 0.01)
    clk.t += 1.0
    rec = ms.poll()
    assert [e.node for e in rec if e.kind == RECOVER] == [2]
    assert [e for e in rec if e.kind == STRAGGLE] == []
    for _ in range(40):
        ms.record_task(2, 0.5)
    evs = []
    for i in range(4):
        clk.t += 1.0
        evs += ms.poll()
    assert [e.node for e in evs if e.kind == STRAGGLE] == [2]


def test_membership_straggler_detected_on_two_node_fleet():
    """Lower-middle median: on 2 nodes the straggler must be compared
    against the healthy node, not against itself."""
    clk = _Clock()
    cfg = MembershipConfig(straggler_factor=2.0, straggler_patience=2,
                           straggler_poll_interval_s=1.0,
                           straggler_min_tasks=1)
    ms = MembershipService(range(2), cfg=cfg, clock=clk)
    for _ in range(8):
        ms.record_task(0, 0.01)
        ms.record_task(1, 0.20)
    evs = []
    for _ in range(3):
        clk.t += 1.0
        evs += ms.poll()
    assert [e.node for e in evs if e.kind == STRAGGLE] == [1]


def test_membership_straggler_needs_min_tasks():
    clk = _Clock()
    cfg = MembershipConfig(straggler_factor=2.0, straggler_patience=1,
                           straggler_poll_interval_s=0.1,
                           straggler_min_tasks=5)
    ms = MembershipService(range(2), cfg=cfg, clock=clk)
    ms.record_task(0, 0.01)
    ms.record_task(1, 1.0)                        # one noisy sample
    clk.t += 1.0
    assert ms.poll() == []


def test_membership_join():
    ms = MembershipService(range(2), clock=_Clock())
    ev = ms.add_node(2)
    assert ev.kind == "join" and ev.node == 2
    assert ms.alive_nodes() == [0, 1, 2]


# -- replan_frontier ---------------------------------------------------------

def _split_by_start(sched, frac=0.4):
    """First ``frac`` of tasks (by scheduled start) as the done set."""
    order = sorted(sched.placements, key=lambda t: (sched.placements[t].start,
                                                    t))
    cut = max(1, int(len(order) * frac))
    done = {tid: sched.placements[tid] for tid in order[:cut]}
    frontier = order[cut:]
    return done, frontier


def test_replan_frontier_death_keeps_done_and_avoids_dead_node():
    spec = hetero_spec((3, 2, 1), **FAST_NET)
    plan = _plan(_synth(), tile=16, spec=spec)
    g, sched = plan.program.graph, plan.schedule
    done, frontier = _split_by_start(sched)
    drained = spec.without_node(1)
    new = replan_frontier(g, drained, TM, done, frontier)
    # completed placements are immutable
    for tid, p in done.items():
        assert new.placements[tid] == p
    # every frontier task re-placed, never on the dead node
    for tid in frontier:
        assert new.placements[tid].node != 1
        assert new.placements[tid].node in drained.alive_nodes()
    assert set(new.placements) == set(sched.placements)


def test_replan_frontier_join_can_use_new_node():
    spec = hetero_spec((1, 1), **FAST_NET)
    plan = _plan(_synth(), tile=16, spec=spec)
    g, sched = plan.program.graph, plan.schedule
    done, frontier = _split_by_start(sched, frac=0.2)
    grown = spec.with_node(3)
    new = replan_frontier(g, grown, TM, done, frontier)
    nodes_used = {new.placements[tid].node for tid in frontier}
    assert 2 in nodes_used, "a fat joining node should attract work"
    for tid, p in done.items():
        assert new.placements[tid] == p


def test_replan_frontier_rejects_overlap_and_drained_master():
    spec = hetero_spec((2, 1), **FAST_NET)
    plan = _plan(_synth(48), tile=16, spec=spec)
    g, sched = plan.program.graph, plan.schedule
    done, frontier = _split_by_start(sched)
    some_done = next(iter(done))
    with pytest.raises(ValueError, match="both done and in the frontier"):
        replan_frontier(g, spec, TM, done, frontier + [some_done])
    import dataclasses
    all_drained = dataclasses.replace(spec, node_workers=(0, 1), master=0)
    with pytest.raises(ValueError, match="master"):
        replan_frontier(g, all_drained, TM, done, frontier)


# -- churn pricing -----------------------------------------------------------

def test_predict_recovery_cost_scales_with_lost_work():
    spec = hetero_spec((2, 2), **FAST_NET)
    plan = _plan(_synth(), tile=16, spec=spec)
    g, sched = plan.program.graph, plan.schedule
    c1 = predict_recovery_cost(g, sched, spec, TM, 1)
    assert c1 >= TM.respawn_overhead
    lone = hetero_spec((2,), **FAST_NET)
    assert predict_recovery_cost(g, sched, lone, TM, 0) == float("inf")


def test_churn_adjusted_makespan_prices_mtbf():
    spec = hetero_spec((2, 2), **FAST_NET)
    plan = _plan(_synth(), tile=16, spec=spec)
    g, sched = plan.program.graph, plan.schedule
    base = sched.makespan
    assert churn_adjusted_makespan(g, sched, spec, TM) == base  # mtbf=inf
    risky = TimeModel.from_json(TM.to_json())
    risky.node_mtbf = base                       # ~certain failure
    adj = churn_adjusted_makespan(g, sched, spec, risky)
    assert adj > base
    safer = TimeModel.from_json(TM.to_json())
    safer.node_mtbf = base * 1e6
    assert base < churn_adjusted_makespan(g, sched, spec, safer) < adj


def test_timemodel_json_roundtrips_churn_terms():
    tm = TimeModel.from_json(TM.to_json())
    tm.node_mtbf = 3600.0
    tm.respawn_overhead = 0.25
    rt = TimeModel.from_json(tm.to_json())
    assert rt.node_mtbf == 3600.0
    assert rt.respawn_overhead == 0.25
    assert TimeModel.from_json(TM.to_json()).node_mtbf == float("inf")


# -- satellite: memoized predictions must track TimeModel recalibration -----

def test_cluster_prediction_tracks_timemodel_mutation():
    """``plan.cluster_makespan`` must not return a stale verdict after
    ``calibrate_ipc``-style in-place mutation of the TimeModel."""
    tm = TimeModel.from_json(TM.to_json())
    tm.process_dispatch_overhead = 1e-6
    eng = CMMEngine(hetero_spec((2, 1), **FAST_NET), tm, plan_cache=False)
    plan = eng.plan(_synth(48), tile=16)
    cheap = plan.cluster_makespan
    tm.process_dispatch_overhead = 5e-2          # what calibrate_ipc does
    dear = plan.cluster_makespan
    assert dear > cheap, "memo must invalidate on TimeModel change"
    assert plan.elastic_makespan == dear         # mtbf=inf: same number


def test_plan_cache_invalidated_by_recalibration():
    tm = TimeModel.from_json(TM.to_json())
    eng = CMMEngine(hetero_spec((2, 1), **FAST_NET), tm)
    expr = _synth(48)
    eng.plan(expr, tile=16)
    p2 = eng.plan(expr, tile=16)
    assert p2.cache_hit
    tm.ipc_bandwidth *= 2                        # recalibration
    p3 = eng.plan(expr, tile=16)
    assert not p3.cache_hit, "recalibrated TimeModel must miss the cache"


# -- engine integration ------------------------------------------------------

def test_elastic_registered_and_engine_runs_it():
    assert "cluster" in EXECUTORS
    assert isinstance(make_executor("cluster"), ClusterExecutor)
    spec = hetero_spec((2, 1), **FAST_NET)
    eng = CMMEngine(spec, TM, plan_cache=False)
    expr = _synth(48)
    out = eng.run(expr, tile=16, executor="cluster")
    plan = eng.plan(expr, tile=16)
    assert np.array_equal(out, LocalExecutor().execute(plan))
    assert eng.last_exec_stats["deaths"] == 0
    assert eng.last_exec_stats["executor"] == "cluster"


def test_engine_elastic_auto_prices_churn():
    expr = _synth(48)
    tm = TimeModel.from_json(TM.to_json())
    tm.dispatch_overhead = 5e-3                  # in-process is expensive
    tm.batch_dispatch_overhead = 10.0
    tm.process_dispatch_overhead = 1e-7
    tm.ipc_bandwidth = 1e12
    tm.ipc_latency = 1e-7
    spec = hetero_spec((2, 1), **FAST_NET)
    eng = CMMEngine(spec, tm, plan_cache=False, elastic=True)
    plan = eng.plan(expr, tile=16)
    # reliable cluster: the churn-priced cluster strategy wins and runs
    assert eng.choose_executor(plan) == "cluster"
    out = eng.run(expr, plan=plan, executor="auto", validate=True)
    assert eng.last_exec_stats["executor"] == "cluster"
    assert out.shape == (48, 48)
    # an unreliable cluster tips auto back to an in-process strategy
    tm.node_mtbf = 1e-3
    tm.respawn_overhead = 1e3
    plan2 = eng.plan(expr, tile=16)
    assert plan2.elastic_makespan > plan2.cluster_makespan
    assert eng.choose_executor(plan2) != "cluster"


# -- fault-injected execution: the acceptance bar ---------------------------

HET_SPEC = hetero_spec((3, 2, 1), slowdown=(1.0, 1.2, 1.5), **FAST_NET)


@pytest.mark.chaos
def test_kill_one_node_mid_run_bitwise():
    plan = _plan(_synth(), tile=16, spec=HET_SPEC)
    ref = LocalExecutor().execute(plan)
    kill_at = len(plan.program.graph) // 3
    ex = ClusterExecutor(
        timemodel=TM, chaos=[ChaosEvent(after_done=kill_at, kill_node=1)])
    out = ex.execute(plan)
    assert out.dtype == ref.dtype
    assert np.array_equal(ref, out)
    st = ex.stats
    assert st["deaths"] == 1
    assert st["replans"] >= 1
    assert st["nodes_final"] == 2
    # every task has exactly one winning completion node and the run
    # finished without node 1's worker
    assert set(st["exec_nodes"]) == set(plan.program.graph.tasks)


def test_chaos_kill_node_must_be_in_range():
    plan = _plan(_synth(48), tile=16, spec=hetero_spec((2, 1), **FAST_NET))
    ex = ClusterExecutor(
        timemodel=TM, chaos=[ChaosEvent(after_done=1, kill_node=7)])
    with pytest.raises(ValueError, match="kill_node=7"):
        ex.execute(plan)
    with pytest.raises(ValueError, match="master"):
        ClusterExecutor(
            timemodel=TM,
            chaos=[ChaosEvent(after_done=1, kill_node=0)]).execute(plan)


@pytest.mark.chaos
def test_kill_of_later_joining_node_is_deferred_not_dropped():
    """A kill aimed at a node that only exists after a join must stay
    armed until the join has spawned it, then actually fire."""
    spec = hetero_spec((1, 1), **FAST_NET)
    plan = _plan(_synth(), tile=8, spec=spec)
    ref = LocalExecutor().execute(plan)
    ex = ClusterExecutor(
        timemodel=TM,
        chaos=[ChaosEvent(after_done=1, kill_node=2),     # before the join
               ChaosEvent(after_done=6, join_workers=2)])
    out = ex.execute(plan)
    assert np.array_equal(ref, out)
    assert ex.stats["joins"] == 1
    assert ex.stats["deaths"] == 1, \
        "the deferred kill must fire once its target exists"


@pytest.mark.chaos
def test_min_nodes_floor_aborts_run():
    plan = _plan(_synth(48), tile=16, spec=hetero_spec((2, 2), **FAST_NET))
    ex = ClusterExecutor(
        timemodel=TM, timeout=60,
        membership=MembershipConfig(min_nodes=2),
        chaos=[ChaosEvent(after_done=5, kill_node=1)])
    with pytest.raises(RuntimeError, match="min_nodes=2"):
        ex.execute(plan)


@pytest.mark.chaos
def test_stall_watchdog_fires_despite_heartbeats():
    """A wedged run (here: an unsatisfiable dependency cycle spliced into
    the graph) must trip the stall timeout even though idle-but-alive
    workers keep heartbeating — heartbeats are liveness, not progress."""
    from repro.core.graph import TaskKind
    from repro.core.heft import Placement
    spec = hetero_spec((2, 1), **FAST_NET)
    plan = _plan(_synth(48), tile=16, spec=spec)
    g = plan.program.graph
    some = next(iter(g.tasks.values()))
    t1 = g.add(TaskKind.ADD, (some.out, some.out), some.out)
    t2 = g.add(TaskKind.ADD, (some.out, some.out), some.out)
    g.add_edge(t1.tid, t2.tid)
    g.add_edge(t2.tid, t1.tid)           # cycle: neither can ever start
    plan.schedule.placements[t1.tid] = Placement(0, 0, 1e9, 1e9)
    plan.schedule.placements[t2.tid] = Placement(0, 0, 1e9, 1e9)
    ex = ClusterExecutor(
        timemodel=TM, timeout=3.0,
        # straggler detection stays off: on a loaded host the idle wedged
        # run can trip a STRAGGLE sweep first, and the resulting replan
        # chokes on the deliberately-cyclic graph before the watchdog
        membership=MembershipConfig(heartbeat_interval_s=0.05,
                                    straggler_min_tasks=1 << 30))
    with pytest.raises(RuntimeError, match="stalled"):
        ex.execute(plan)


@pytest.mark.chaos
def test_kill_respawn_readmits_node():
    plan = _plan(_synth(), tile=16, spec=HET_SPEC)
    ref = LocalExecutor().execute(plan)
    ex = ClusterExecutor(
        timemodel=TM, respawn_dead=True,
        chaos=[ChaosEvent(after_done=12, kill_node=2)])
    out = ex.execute(plan)
    assert np.array_equal(ref, out)
    assert ex.stats["deaths"] == 1
    assert ex.stats["respawns"] == 1
    assert ex.stats["nodes_final"] == 3


@pytest.mark.chaos
def test_join_node_mid_run_bitwise_and_used():
    spec = hetero_spec((1, 1), **FAST_NET)
    plan = _plan(_synth(), tile=8, spec=spec)   # 8x8 grid: plenty of work
    ref = LocalExecutor().execute(plan)
    ex = ClusterExecutor(
        timemodel=TM,
        chaos=[ChaosEvent(after_done=10, join_workers=3)])
    out = ex.execute(plan)
    assert np.array_equal(ref, out)
    st = ex.stats
    assert st["joins"] == 1
    assert st["nodes_final"] == 3
    assert 2 in set(st["exec_nodes"].values()), \
        "the joining node must actually execute re-planned work"


@pytest.mark.chaos
def test_straggler_speculation_bitwise():
    plan = _plan(_synth(), tile=16, spec=HET_SPEC)
    ref = LocalExecutor().execute(plan)
    ex = ClusterExecutor(
        timemodel=TM,
        chaos=[ChaosEvent(after_done=3, throttle_node=1,
                          throttle_seconds=0.05),
               ChaosEvent(after_done=10, flag_straggler=1)])
    out = ex.execute(plan)
    assert np.array_equal(ref, out)
    st = ex.stats
    assert st["straggles"] >= 1
    assert st["replans"] >= 1
    # first-writer-wins: duplicates may or may not land, but every task
    # completed exactly once in the winner bookkeeping
    assert len(st["exec_nodes"]) == len(plan.program.graph)


# -- hypothesis properties: churn never changes bits -------------------------

try:
    from hypothesis import given, settings, strategies as st
    from test_batched import _rand_expr          # FUSED / transposed-matmul
    HAVE_HYP = True                              # / f32-f64 strategies
except ImportError:                     # pragma: no cover
    HAVE_HYP = False

if HAVE_HYP:
    @pytest.mark.chaos
    @given(st.data())
    @settings(max_examples=5, deadline=None)
    def test_kill_mid_run_bit_identical_property(data):
        """Over randomized expression DAGs (the paper-suite strategies
        reused from tests/test_cluster.py), SIGKILLing one worker process
        mid-run leaves the result bit-identical to ``LocalExecutor``."""
        dtype = data.draw(st.sampled_from([np.float64, np.float32]))
        tile = data.draw(st.integers(4, 12))
        m = data.draw(st.integers(2, 16))
        n = data.draw(st.integers(2, 16))
        depth = data.draw(st.integers(1, 2))
        expr = _rand_expr(data.draw, depth, m, n, dtype, max_inner=tile)
        plan = _plan(expr, tile=tile, spec=HET_SPEC)
        total = len(plan.program.graph)
        kill_at = data.draw(st.integers(1, max(1, total - 2)))
        ref = LocalExecutor().execute(plan)
        ex = ClusterExecutor(
            timemodel=TM,
            chaos=[ChaosEvent(after_done=kill_at, kill_node=1)])
        out = ex.execute(plan)
        assert out.dtype == ref.dtype
        assert np.array_equal(ref, out), \
            "elastic executor diverged after mid-run node death"
        assert ex.stats["deaths"] == 1

    @pytest.mark.chaos
    @given(st.data())
    @settings(max_examples=4, deadline=None)
    def test_join_mid_run_bit_identical_property(data):
        dtype = data.draw(st.sampled_from([np.float64, np.float32]))
        tile = data.draw(st.integers(4, 12))
        m = data.draw(st.integers(2, 16))
        n = data.draw(st.integers(2, 16))
        depth = data.draw(st.integers(1, 2))
        expr = _rand_expr(data.draw, depth, m, n, dtype, max_inner=tile)
        spec = hetero_spec((2, 1), **FAST_NET)
        plan = _plan(expr, tile=tile, spec=spec)
        total = len(plan.program.graph)
        join_at = data.draw(st.integers(0, max(0, total - 2)))
        ref = LocalExecutor().execute(plan)
        ex = ClusterExecutor(
            timemodel=TM,
            chaos=[ChaosEvent(after_done=join_at, join_workers=2)])
        out = ex.execute(plan)
        assert out.dtype == ref.dtype
        assert np.array_equal(ref, out), \
            "elastic executor diverged after mid-run node join"
        assert ex.stats["joins"] == 1

    @pytest.mark.chaos
    @given(st.data())
    @settings(max_examples=3, deadline=None)
    def test_kill_with_long_k_chains_property(data):
        """Accumulate chains that migrate across nodes mid-chain survive
        a node death: bitwise vs per-task executor, oracle at the
        documented multi-k-tile tolerance."""
        dtype = data.draw(st.sampled_from([np.float64, np.float32]))
        tile = data.draw(st.integers(3, 6))
        k = data.draw(st.integers(tile + 1, 3 * tile))
        m = data.draw(st.integers(2, 10))
        n = data.draw(st.integers(2, 10))
        expr = (CM.rand(m, k, seed=0, dtype=dtype) @
                CM.rand(k, n, seed=1, dtype=dtype)).relu() + \
            CM.rand(m, n, seed=2, dtype=dtype)
        plan = _plan(expr, tile=tile, spec=HET_SPEC)
        total = len(plan.program.graph)
        kill_at = data.draw(st.integers(1, max(1, total - 2)))
        ref = LocalExecutor().execute(plan)
        ex = ClusterExecutor(
            timemodel=TM,
            chaos=[ChaosEvent(after_done=kill_at, kill_node=1)])
        out = ex.execute(plan)
        assert np.array_equal(ref, out)
        tol = 1e-4 if dtype == np.float32 else 1e-9
        np.testing.assert_allclose(out, expr.eager(), rtol=tol, atol=tol)


# -- acceptance: every paper workload survives a mid-run SIGKILL -------------

@pytest.mark.slow
@pytest.mark.chaos
def test_paper_suite_kill_one_node_bitwise():
    """On the heterogeneous 3-node spec, killing a node mid-run yields
    results bitwise-identical to ``LocalExecutor`` for every paper-suite
    workload (the PR's acceptance criterion)."""
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "benchmarks"))
    from cmm_suite import BENCHMARKS
    spec = hetero_spec((3, 2, 1), **FAST_NET)
    eng = CMMEngine(spec, TM, plan_cache=False)
    for name in sorted(BENCHMARKS):
        expr = BENCHMARKS[name](48)
        plan = eng.plan(expr, tile=16)
        ref = LocalExecutor().execute(plan)
        kill_at = max(1, len(plan.program.graph) // 3)
        ex = ClusterExecutor(
            timemodel=TM,
            chaos=[ChaosEvent(after_done=kill_at, kill_node=1)])
        out = ex.execute(plan)
        assert out.dtype == ref.dtype, name
        assert np.array_equal(ref, out), \
            f"{name}: elastic result diverged after node death"
        assert ex.stats["deaths"] == 1, name
