"""Multi-process ClusterExecutor: placement fidelity, bit-identity vs the
per-task executor, XFER endpoints, strategy selection, IPC calibration."""
import numpy as np
import pytest

from repro.core import (ClusteredMatrix as CM, CMMEngine, TimeModel,
                        analytic_time_model, c5_9xlarge)
from repro.core.machine import hetero_spec
from repro.exec import EXECUTORS, make_executor
from repro.exec.cluster import ClusterExecutor, predict_cluster_makespan
from repro.exec.local import LocalExecutor

TM = analytic_time_model()

#: fat links + tiny latency make HEFT spread work across nodes (comm is
#: nearly free), so placements genuinely exercise the multi-node path
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


# -- heterogeneous placement: the acceptance-criteria test ------------------

def test_hetero_3node_placement_is_executed_for_real():
    """On a heterogeneous >=3-node spec (unequal worker counts and speeds),
    every task must run in the worker process of its HEFT-assigned node,
    with real inter-process tile transfers."""
    spec = hetero_spec((3, 2, 1), slowdown=(1.0, 1.2, 1.5), **FAST_NET)
    plan = _plan(_synth(), tile=16, spec=spec)
    nodes_used = {p.node for p in plan.schedule.placements.values()}
    assert len(nodes_used) >= 2, "HEFT should spread this plan across nodes"

    out_local = LocalExecutor().execute(plan)
    ex = ClusterExecutor()
    out_cluster = ex.execute(plan)
    assert out_cluster.dtype == out_local.dtype
    assert np.array_equal(out_local, out_cluster)

    sched_nodes = {tid: p.node for tid, p in plan.schedule.placements.items()}
    assert ex.stats["exec_nodes"] == sched_nodes, \
        "every task must execute on its HEFT-assigned node process"
    assert len(set(ex.stats["node_pids"].values())) == 3, \
        "one distinct worker process per node"
    assert ex.stats["xfers"] > 0 and ex.stats["xfer_bytes"] > 0
    assert ex.stats["workers"] == 3 + 2 + 1


def test_cluster_refcounting_frees_all_buffers():
    spec = hetero_spec((2, 1), **FAST_NET)
    plan = _plan(_synth(48), tile=16, spec=spec)
    ex = ClusterExecutor()
    out = ex.execute(plan)
    ref = LocalExecutor().execute(plan)
    assert np.array_equal(out, ref)
    # every segment was freed: result tiles are released after the gather
    assert ex.stats["cur_buffer_bytes"] == 0
    assert ex.stats["buffers_freed"] > 0
    assert ex.stats["peak_buffer_bytes"] > 0

    ex_keep = ClusterExecutor(free_buffers=False)
    out_keep = ex_keep.execute(plan)
    assert np.array_equal(out, out_keep)
    assert ex_keep.stats["cur_buffer_bytes"] > 0


def test_cluster_input_leaves_and_plan_cache_rebind():
    """INPUT data is shipped to the worker processes; a plan-cache hit must
    rebind fresh leaves (different data) through the same schedule."""
    rng = np.random.default_rng(0)
    spec = hetero_spec((2, 1), **FAST_NET)
    eng = CMMEngine(spec, TM)
    a1, b1 = rng.standard_normal((48, 48)), rng.standard_normal((48, 48))
    e1 = (CM.from_array(a1) @ CM.from_array(b1)) + CM.from_array(a1)
    out1 = eng.run(e1, tile=16, executor="cluster")
    np.testing.assert_allclose(out1, a1 @ b1 + a1, rtol=1e-12, atol=1e-12)

    a2, b2 = rng.standard_normal((48, 48)), rng.standard_normal((48, 48))
    e2 = (CM.from_array(a2) @ CM.from_array(b2)) + CM.from_array(a2)
    plan2 = eng.plan(e2, tile=16)
    assert plan2.cache_hit
    out2 = ClusterExecutor().execute(plan2)
    np.testing.assert_allclose(out2, a2 @ b2 + a2, rtol=1e-12, atol=1e-12)


# -- schedule endpoints exposed to executors --------------------------------

def test_schedule_node_tasks_and_xfer_endpoints():
    spec = hetero_spec((3, 2, 1), **FAST_NET)
    plan = _plan(_synth(), tile=16, spec=spec)
    g = plan.program.graph
    sched = plan.schedule

    by_node = sched.node_tasks()
    flat = [tid for tids in by_node.values() for tid in tids]
    assert sorted(flat) == sorted(sched.placements)          # exact partition
    for n, tids in by_node.items():
        assert all(sched.placements[t].node == n for t in tids)
        starts = [sched.placements[t].start for t in tids]
        assert starts == sorted(starts)                      # dispatch order

    xfers = sched.xfers(g)
    assert xfers, "multi-node synth must move tiles across nodes"
    seen = set()
    for (p, src, dst, nbytes) in xfers:
        assert sched.placements[p].node == src
        assert src != dst and nbytes > 0
        assert (p, dst) not in seen, "one XFER per version per destination"
        seen.add((p, dst))


# -- executor registry (satellite fix) --------------------------------------

def test_executor_registry_single_source_of_truth():
    assert {"local", "kernel", "batched", "batched-pallas",
            "cluster"} <= set(EXECUTORS)
    assert isinstance(make_executor("cluster"), ClusterExecutor)
    assert isinstance(make_executor("local"), LocalExecutor)
    with pytest.raises(ValueError, match="unknown executor"):
        make_executor("no-such-backend")
    eng = CMMEngine(c5_9xlarge(1), TM)
    with pytest.raises(ValueError, match="unknown executor"):
        eng.run(_synth(16), tile=8, executor="no-such-backend")


# -- strategy selection: process-dispatch/IPC terms -------------------------

def test_predict_cluster_makespan_prices_ipc_terms():
    spec = hetero_spec((2, 1), **FAST_NET)
    plan = _plan(_synth(48), tile=16, spec=spec)
    cheap = TimeModel.from_json(TM.to_json())
    cheap.process_dispatch_overhead = 1e-6
    dear = TimeModel.from_json(TM.to_json())
    dear.process_dispatch_overhead = 5e-3
    g, sched = plan.program.graph, plan.schedule
    t_cheap = predict_cluster_makespan(g, sched, spec, cheap)
    t_dear = predict_cluster_makespan(g, sched, spec, dear)
    assert t_dear > t_cheap


def test_predict_wave_makespan_uses_hetero_worker_counts():
    """A hetero spec with 1-worker nodes must not be priced at the
    ClusterSpec default ``worker_procs=3`` (auto-selection mispricing)."""
    from repro.exec.batched import predict_wave_makespan
    spec1 = hetero_spec((1, 1), **FAST_NET)
    spec3 = hetero_spec((3, 3), **FAST_NET)
    plan = _plan(_synth(48), tile=16, spec=spec1)
    g = plan.program.graph
    t1 = predict_wave_makespan(g, spec1, TM, waves=plan.waves,
                               dtypes=plan.program.dtypes)
    t3 = predict_wave_makespan(g, spec3, TM, waves=plan.waves,
                               dtypes=plan.program.dtypes)
    assert t1 > t3


def test_engine_auto_can_select_cluster():
    expr = _synth(48)
    # expensive in-process dispatch + slow network model, near-free process
    # dispatch and fat IPC -> the cluster strategy wins
    tm_c = TimeModel.from_json(TM.to_json())
    tm_c.dispatch_overhead = 5e-3
    tm_c.batch_dispatch_overhead = 10.0
    tm_c.process_dispatch_overhead = 1e-7
    tm_c.ipc_bandwidth = 1e12
    tm_c.ipc_latency = 1e-7
    eng = CMMEngine(hetero_spec((2, 1), **FAST_NET), tm_c, plan_cache=False)
    plan = eng.plan(expr, tile=16)
    assert plan.cluster_makespan is not None
    assert plan.cluster_makespan < plan.sim.makespan
    assert plan.best_executor == "cluster"
    assert plan.best_predicted_makespan == plan.cluster_makespan
    out = eng.run(expr, plan=plan, executor="auto", validate=True)
    assert eng.last_exec_stats["executor"] == "cluster"
    assert out.shape == (48, 48)

    # prohibitive process dispatch -> cluster never chosen
    tm_l = TimeModel.from_json(TM.to_json())
    tm_l.process_dispatch_overhead = 10.0
    eng_l = CMMEngine(hetero_spec((2, 1), **FAST_NET), tm_l,
                      plan_cache=False)
    plan_l = eng_l.plan(expr, tile=16)
    assert plan_l.best_executor != "cluster"


def test_single_node_plans_skip_cluster_prediction():
    eng = CMMEngine(c5_9xlarge(1), TM, plan_cache=False)
    plan = eng.plan(_synth(32), tile=16)
    assert plan.cluster_makespan is None
    assert plan.best_executor in ("local", "batched")


def test_timemodel_json_roundtrip_ipc_terms():
    tm = TimeModel.from_json(TM.to_json())
    tm.process_dispatch_overhead = 1.5e-4
    tm.ipc_bandwidth = 3e9
    tm.ipc_latency = 7e-5
    rt = TimeModel.from_json(tm.to_json())
    assert rt.process_dispatch_overhead == 1.5e-4
    assert rt.ipc_bandwidth == 3e9
    assert rt.ipc_latency == 7e-5


def test_calibrate_ipc_fits_positive_terms():
    from repro.core.profiler import calibrate_ipc
    tm = TimeModel.from_json(TM.to_json())
    disp, bw = calibrate_ipc(tm, nbytes=1 << 20, reps=2)
    assert 1e-6 <= disp <= 5e-2
    assert 1e8 <= bw <= 1e12
    assert tm.process_dispatch_overhead == disp
    assert tm.ipc_latency == disp
    assert tm.ipc_bandwidth == bw


# -- hypothesis property: cluster <-> local bit-identity --------------------

try:
    from hypothesis import given, settings, strategies as st
    from test_batched import _rand_expr          # FUSED / transposed-matmul
    HAVE_HYP = True                              # / f32-f64 strategies
except ImportError:                     # pragma: no cover
    HAVE_HYP = False

if HAVE_HYP:
    HET_SPEC = hetero_spec((2, 1), **FAST_NET)

    @given(st.data())
    @settings(max_examples=8, deadline=None)
    def test_cluster_bit_identical_property(data):
        """Over randomized expression DAGs (FUSED regions, transposed
        matmuls, f32/f64), the multi-process executor is bit-identical to
        the per-task executor, and — when every matmul k-chain fits one
        tile — to ``eager()`` too (same policy as the batched property)."""
        dtype = data.draw(st.sampled_from([np.float64, np.float32]))
        tile = data.draw(st.integers(4, 12))
        m = data.draw(st.integers(2, 16))
        n = data.draw(st.integers(2, 16))
        depth = data.draw(st.integers(1, 2))
        expr = _rand_expr(data.draw, depth, m, n, dtype, max_inner=tile)
        plan = _plan(expr, tile=tile, spec=HET_SPEC)
        out_local = LocalExecutor().execute(plan)
        ex = ClusterExecutor()
        out_cluster = ex.execute(plan)
        assert out_cluster.dtype == out_local.dtype
        assert np.array_equal(out_local, out_cluster), \
            "cluster executor diverged from per-task executor"
        assert np.array_equal(out_cluster, expr.eager()), \
            "cluster executor diverged from the eager oracle"
        sched_nodes = {tid: p.node
                       for tid, p in plan.schedule.placements.items()}
        assert ex.stats["exec_nodes"] == sched_nodes

    @given(st.data())
    @settings(max_examples=4, deadline=None)
    def test_cluster_matches_per_task_with_long_k_chains(data):
        """Multi-k-tile accumulate chains (possibly migrating between
        nodes mid-chain): still bitwise vs the per-task executor, oracle
        at tolerance (tiling re-associates the GEMM reduction)."""
        dtype = data.draw(st.sampled_from([np.float64, np.float32]))
        tile = data.draw(st.integers(3, 6))
        k = data.draw(st.integers(tile + 1, 3 * tile))
        m = data.draw(st.integers(2, 10))
        n = data.draw(st.integers(2, 10))
        expr = (CM.rand(m, k, seed=0, dtype=dtype) @
                CM.rand(k, n, seed=1, dtype=dtype)).relu() + \
            CM.rand(m, n, seed=2, dtype=dtype)
        plan = _plan(expr, tile=tile, spec=HET_SPEC)
        out_local = LocalExecutor().execute(plan)
        out_cluster = ClusterExecutor().execute(plan)
        assert np.array_equal(out_local, out_cluster)
        tol = 1e-4 if dtype == np.float32 else 1e-9
        np.testing.assert_allclose(out_cluster, expr.eager(),
                                   rtol=tol, atol=tol)


def test_host_workers_never_import_jax():
    """The cluster and elastic worker processes and the calibrate_ipc echo
    child run on the host only: with every JAX import refused (the forked
    children inherit the refusal), all three still run to the right
    answer.  A process that drives the chip must still not fork them —
    the chip belongs to one process."""
    import os
    import subprocess
    import sys
    import textwrap
    code = textwrap.dedent("""
        import sys

        class RefuseJax:
            def find_spec(self, name, path=None, target=None):
                if name.split(".")[0] in ("jax", "jaxlib"):
                    raise ImportError(f"{name} imported on a host path")

        sys.meta_path.insert(0, RefuseJax())
        import numpy as np
        from repro.core import ClusteredMatrix as CM, CMMEngine
        from repro.core import analytic_time_model
        from repro.core.machine import hetero_spec
        from repro.core.profiler import calibrate_ipc

        tm = analytic_time_model()
        spec = hetero_spec((2, 1), link_bw=1e12, latency=1e-6)
        A, B = CM.rand(48, 48, seed=0), CM.rand(48, 48, seed=1)
        expr = ((A @ B) - 0.5).relu() @ A.T
        ref = expr.eager()
        eng = CMMEngine(spec, tm, plan_cache=False)
        for name in ("cluster", "elastic"):
            out = eng.run(expr, tile=16, executor=name)
            np.testing.assert_allclose(out, ref, rtol=1e-10, atol=1e-10)
        calibrate_ipc(tm, nbytes=1 << 16, reps=1)
        assert "jax" not in sys.modules
        print("ok")
    """)
    env = dict(os.environ)
    root = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.path.abspath(root)
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stdout + p.stderr
    assert p.stdout.strip().endswith("ok")
