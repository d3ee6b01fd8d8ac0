"""Wave-batched executor: wave invariants, bit-identity, arena, selection."""
import numpy as np
import pytest

from repro.core import (ClusteredMatrix as CM, CMMEngine, TimeModel,
                        analytic_time_model, c5_9xlarge, optimize)
from repro.core.graph import TaskGraph, TaskKind, TileRef
from repro.core.machine import local_spec
from repro.exec.batched import (StagingPool, WaveExecutor, build_waves,
                                predict_wave_makespan, stack_into)
from repro.exec.local import LocalExecutor

TM = analytic_time_model()


def _plan(expr, tile, nodes=1, fuse=True):
    eng = CMMEngine(c5_9xlarge(nodes), TM, plan_cache=False, fuse=fuse)
    return eng.plan(expr, tile=tile)


def _in_planned_order(expr):
    """``expr`` as the planner associates its products (identity folds,
    CSE and matrix-chain order; no fusion): ``eager()`` of it is the
    bitwise oracle of a plan whose matmul k-chains fit one tile.  The
    chain order re-associates products, so the written expression's
    ``eager()`` differs from such a plan in the last bits."""
    return optimize(expr, fuse=False)[0]


def _mixed_expr(n=96, dtype=np.float64):
    A = CM.rand(n, n, seed=0, dtype=dtype)
    B = CM.rand(n, n, seed=1, dtype=dtype)
    C = CM.rand(n, n, seed=2, dtype=dtype)
    return ((A @ B).relu() * 2.0 + C).hadamard(C) - A


# -- wave partition ---------------------------------------------------------

def test_waves_partition_and_are_antichains():
    plan = _plan(_mixed_expr(), tile=16)
    g = plan.program.graph
    waves = build_waves(g)
    seen = [tid for w in waves for tid in w]
    assert sorted(seen) == sorted(g.tasks)          # exact partition
    wave_of = {tid: i for i, w in enumerate(waves) for tid in w}
    for t in g:
        for s in t.succs:
            assert wave_of[s] > wave_of[t.tid], \
                "dependency must cross waves (mutual independence)"


def test_plan_carries_waves():
    plan = _plan(_mixed_expr(), tile=32)
    assert plan.waves is not None
    assert sorted(t for w in plan.waves for t in w) == \
        sorted(plan.program.graph.tasks)
    assert plan.batched_makespan is not None and plan.batched_makespan > 0


# -- bit-identity vs the per-task executor & the eager oracle ---------------

@pytest.mark.parametrize("dtype", [np.float64, np.float32])
@pytest.mark.parametrize("tile", [16, 24, 96])
def test_batched_bit_identical_to_per_task(dtype, tile):
    plan = _plan(_mixed_expr(dtype=dtype), tile=tile)
    out_local = LocalExecutor().execute(plan)
    out_wave = WaveExecutor().execute(plan)
    assert out_local.dtype == out_wave.dtype
    assert np.array_equal(out_local, out_wave)


def test_batched_transposed_matmul_paths():
    A = CM.rand(64, 48, seed=3)
    B = CM.rand(64, 80, seed=4)
    C = CM.rand(48, 80, seed=5)
    expr = (A.T @ B) + C
    plan = _plan(expr, tile=16)
    # the optimizer folded the transpose into ADDMUL flags
    kinds = plan.program.graph.counts()
    assert "transpose" not in kinds
    out_local = LocalExecutor().execute(plan)
    out_wave = WaveExecutor().execute(plan)
    assert np.array_equal(out_local, out_wave)
    np.testing.assert_allclose(out_wave, expr.eager(), rtol=1e-9, atol=1e-9)


def test_batched_explicit_transpose_kind():
    A = CM.rand(40, 24, seed=9)
    expr = A.T + CM.rand(24, 40, seed=10)
    plan = _plan(expr, tile=8, fuse=False)    # keep the TRANSPOSE task kind
    assert "transpose" in plan.program.graph.counts()
    out_local = LocalExecutor().execute(plan)
    out_wave = WaveExecutor().execute(plan)
    assert np.array_equal(out_local, out_wave)


def test_batched_ragged_tiles():
    expr = _mixed_expr(n=100)
    plan = _plan(expr, tile=24)               # 100 = 4x24 + ragged 4
    out_local = LocalExecutor().execute(plan)
    out_wave = WaveExecutor().execute(plan)
    assert np.array_equal(out_local, out_wave)


def test_batched_input_leaves():
    rng = np.random.default_rng(0)
    a = rng.standard_normal((48, 48))
    b = rng.standard_normal((48, 48))
    expr = (CM.from_array(a) @ CM.from_array(b)) + CM.from_array(a)
    plan = _plan(expr, tile=16)
    out_local = LocalExecutor().execute(plan)
    out_wave = WaveExecutor().execute(plan)
    assert np.array_equal(out_local, out_wave)


# -- arena / memory ---------------------------------------------------------

def test_arena_zero_copy_and_freeing():
    plan = _plan(_mixed_expr(n=128), tile=16)
    ex = WaveExecutor()
    ex.execute(plan)
    assert ex.stats["zero_copy_gathers"] > 0
    assert ex.stats["buffers_freed"] > 0
    assert ex.stats["tasks_run"] == len(plan.program.graph)
    assert ex.stats["cur_buffer_bytes"] <= ex.stats["peak_buffer_bytes"]

    # refcounted slab freeing bounds the peak by LIVE slabs: on a deep
    # unfused elementwise chain (one slab per step, freed as the next
    # step consumes it) the peak stays far below the keep-everything run
    e = CM.rand(64, 64, seed=0)
    for i in range(12):
        e = (e * 1.01 + 0.1).relu()
    plan_chain = _plan(e, tile=32, fuse=False)
    ex_free = WaveExecutor()
    out_free = ex_free.execute(plan_chain)
    ex_keep = WaveExecutor(free_buffers=False)
    out_keep = ex_keep.execute(plan_chain)
    assert np.array_equal(out_free, out_keep)
    assert ex_free.stats["peak_buffer_bytes"] < \
        ex_keep.stats["peak_buffer_bytes"]
    assert ex_free.stats["buffers_freed"] > 0


def test_arena_survives_duplicate_producers_from_regen_fills():
    """HEFT's §3.3 regeneration pass clones FILL tasks that share the
    original task's ``out`` TileRef on multi-node plans.  A ref must hold
    exactly one slab slot alive, or regenerated fills strand their slabs
    at live > 0 forever (slab-leak regression)."""
    A = CM.rand(256, 256, seed=0)
    B = CM.rand(256, 256, seed=1)
    expr = (A @ B) + CM.rand(256, 256, seed=2)
    eng = CMMEngine(c5_9xlarge(4), TM, plan_cache=False)
    plan = eng.plan(expr, tile=32)
    producers = {}
    for t in plan.program.graph:
        if t.kind is TaskKind.FILL:
            producers[t.out] = producers.get(t.out, 0) + 1
    assert max(producers.values()) > 1, \
        "expected regen-clone fills (duplicate producers) in this plan"
    ex = WaveExecutor()
    out = ex.execute(plan)
    np.testing.assert_allclose(out, expr.eager(), rtol=1e-8, atol=1e-8)
    # live at end: exactly the result tiles' slab (+ nothing stranded)
    assert ex.stats["cur_buffer_bytes"] == 256 * 256 * 8
    assert ex.stats["buffers_freed"] >= ex.stats["slabs_alloc"] - 1


# -- engine integration -----------------------------------------------------

def test_engine_batched_executor_validates():
    eng = CMMEngine(local_spec(1), TM)
    expr = _mixed_expr(n=64)
    out = eng.run(expr, tile=16, executor="batched", validate=True)
    assert eng.last_exec_stats["executor"] == "batched"
    assert out.shape == (64, 64)


def test_engine_auto_selects_by_predicted_makespan():
    expr = _mixed_expr(n=64)
    # heavy per-task dispatch, cheap batched launches -> batched wins
    tm_b = TimeModel.from_json(TM.to_json())
    tm_b.dispatch_overhead = 5e-3
    tm_b.batch_dispatch_overhead = 1e-5
    eng_b = CMMEngine(local_spec(1), tm_b, plan_cache=False)
    plan_b = eng_b.plan(expr, tile=16)
    assert plan_b.batched_makespan < plan_b.sim.makespan
    assert eng_b.choose_executor(plan_b) == "batched"
    out = eng_b.run(expr, plan=plan_b, executor="auto", validate=True)
    assert eng_b.last_exec_stats["executor"] == "batched"
    assert out.shape == (64, 64)

    # free per-task dispatch, expensive batched launches -> per-task wins
    tm_l = TimeModel.from_json(TM.to_json())
    tm_l.dispatch_overhead = 0.0
    tm_l.batch_dispatch_overhead = 10.0
    eng_l = CMMEngine(local_spec(1), tm_l, plan_cache=False)
    plan_l = eng_l.plan(expr, tile=16)
    assert eng_l.choose_executor(plan_l) == "local"
    assert plan_l.best_predicted_makespan == plan_l.sim.makespan


def test_predict_wave_makespan_prices_batch_dispatch():
    plan = _plan(_mixed_expr(n=64), tile=16)
    g = plan.program.graph
    cheap = TimeModel.from_json(TM.to_json())
    cheap.batch_dispatch_overhead = 1e-6
    dear = TimeModel.from_json(TM.to_json())
    dear.batch_dispatch_overhead = 1e-2
    spec = c5_9xlarge(1)
    t_cheap = predict_wave_makespan(g, spec, cheap, waves=plan.waves,
                                    dtypes=plan.program.dtypes)
    t_dear = predict_wave_makespan(g, spec, dear, waves=plan.waves,
                                   dtypes=plan.program.dtypes)
    assert t_dear > t_cheap


def test_batched_pallas_backend_matches_at_tolerance():
    """vmap-over-Pallas ADDMUL groups (interpret mode on CPU): float32 VMEM
    accumulation, so validated at tolerance rather than bitwise."""
    expr = (CM.rand(32, 32, seed=0) @ CM.rand(32, 32, seed=1)) + \
        CM.rand(32, 32, seed=2)
    eng = CMMEngine(local_spec(1), TM, plan_cache=False)
    out = eng.run(expr, tile=16, executor="batched-pallas")
    np.testing.assert_allclose(out, expr.eager(), rtol=1e-4, atol=1e-4)


# -- device-path staging buffers --------------------------------------------

@pytest.mark.parametrize("tm,tn", [(24, 40), (40, 24), (32, 32)])
@pytest.mark.parametrize("transpose", [True, False])
def test_stack_into_a_reused_buffer_is_one_bitwise_copy(tm, tn, transpose):
    """The device gather's one copy into a reused staging buffer equals the
    stack, transpose view and ``ascontiguousarray`` it replaces, for
    contiguous and strided tiles alike."""
    rng = np.random.default_rng(7)
    whole = rng.standard_normal((3 * tm, 2 * tn)).astype(np.float32)
    tiles = [whole[i * tm:(i + 1) * tm, j * tn:(j + 1) * tn]   # strided
             for i in range(3) for j in range(2)]
    tiles += [rng.standard_normal((tm, tn)).astype(np.float32)]
    shape = (len(tiles),) + ((tn, tm) if transpose else (tm, tn))
    pool = StagingPool()
    buf, reused = pool.take(shape, np.float32)
    assert not reused
    buf[...] = np.nan                                # dirty it, give back
    pool.give([buf])
    again, reused = pool.take(shape, np.float32)
    assert reused and again is buf
    out = stack_into(again, tiles, transpose)
    ref = np.stack(tiles)
    if transpose:
        ref = np.ascontiguousarray(ref.transpose(0, 2, 1))
    assert out is buf and out.flags.c_contiguous
    assert out.shape == ref.shape and out.tobytes() == ref.tobytes()


def test_stack_into_casts_like_astype():
    """Mixed precision stages float64 or bfloat16 tiles straight into a
    float32 buffer: the bytes ``astype`` of the stack would give."""
    import ml_dtypes
    rng = np.random.default_rng(3)
    for dt in (np.float64, ml_dtypes.bfloat16):
        tiles = [rng.standard_normal((16, 24)).astype(dt) for _ in range(4)]
        out = stack_into(np.empty((4, 24, 16), np.float32), tiles, True)
        ref = np.ascontiguousarray(
            np.stack(tiles).transpose(0, 2, 1)).astype(np.float32)
        assert out.tobytes() == ref.tobytes()


def test_staging_pool_keys_bounds_and_retain():
    pool = StagingPool()
    a, _ = pool.take((2, 8, 8), np.float32)
    b, _ = pool.take((2, 8, 8), np.float32)
    c, _ = pool.take((2, 8, 8), np.float64)
    pool.give([a, b, c])
    held = pool.held_bytes()
    assert held == {((2, 8, 8), np.dtype(np.float32)): 2 * a.nbytes,
                    ((2, 8, 8), np.dtype(np.float64)): c.nbytes}
    # each shape's free list is what one taker held at once, no more
    for _ in range(3):
        x, reused = pool.take((2, 8, 8), np.float32)
        assert reused
        pool.give([x])
    assert pool.held_bytes() == held
    assert pool.take((2, 8, 4), np.float32)[1] is False   # another shape
    pool.retain({((2, 8, 8), np.dtype(np.float64))})
    assert pool.held_bytes() == {((2, 8, 8), np.dtype(np.float64)):
                                 c.nbytes}


def test_staging_pool_is_thread_safe():
    """Many threads taking and giving back never share a buffer."""
    import sys
    import threading
    pool, errors = StagingPool(), []

    def worker(k):
        try:
            for _ in range(200):
                buf, _ = pool.take((4,), np.int64)
                buf[:] = k
                if not (buf == k).all():     # pragma: no cover
                    errors.append(k)
                pool.give([buf])
        except Exception as e:               # pragma: no cover
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=worker, args=(k,))
                   for k in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads) and not errors
    assert pool.held_bytes()[((4,), np.dtype(np.int64))] <= 16 * 32


# -- per-task executor accounting (satellite fix) ---------------------------

def test_local_executor_rebind_accounting():
    """Rebinding ``buffers[t.out]`` over a CALLOC'd allocation must release
    the old allocation's bytes (the peak_buffer_bytes drift fix)."""
    from types import SimpleNamespace

    leaf_a = CM.rand(8, 8, seed=1)
    leaf_b = CM.rand(8, 8, seed=2)
    r = TileRef(10_000, 0, 0, (8, 8))
    a = TileRef(leaf_a.uid, 0, 0, (8, 8))
    b = TileRef(leaf_b.uid, 0, 0, (8, 8))
    g = TaskGraph()
    t0 = g.add(TaskKind.CALLOC, (), r, payload=10_000)
    t1 = g.add(TaskKind.FILL, (), a, payload=leaf_a.uid)
    t2 = g.add(TaskKind.FILL, (), b, payload=leaf_b.uid)
    t3 = g.add(TaskKind.ADD, (a, b), r,          # rebinds over the CALLOC
               deps=(t0.tid, t1.tid, t2.tid))
    g.add(TaskKind.TAKECOPY, (r,), r, deps=(t3.tid,))
    g.result_tiles = [r]
    g.result_grid = (1, 1)
    g.result_shape = (8, 8)

    plan = SimpleNamespace(
        program=SimpleNamespace(graph=g, leaf_nodes={leaf_a.uid: leaf_a,
                                                     leaf_b.uid: leaf_b},
                                dtypes={10_000: np.float64}),
        tile=(8, 8),
        schedule=SimpleNamespace(order=[t.tid for t in g.topo()]),
        spec=None)
    ex = LocalExecutor(workers=1)
    out = ex.execute(plan)
    np.testing.assert_allclose(out, leaf_a.eager() + leaf_b.eager())
    tile_bytes = 8 * 8 * 8
    # live at end: just the (rebound) result tile
    assert ex.stats["cur_buffer_bytes"] == tile_bytes
    # peak: calloc + two fills (the ADD rebind nets to zero)
    assert ex.stats["peak_buffer_bytes"] == 3 * tile_bytes


# -- hypothesis property: bit-identical over randomized DAGs ----------------

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYP = True
except ImportError:                     # pragma: no cover
    HAVE_HYP = False

if HAVE_HYP:
    SAFE_EWISE = ["sin", "cos", "tanh", "abs", "relu"]

    def _rand_expr(draw, depth, m, n, dtype, max_inner):
        if depth == 0:
            return CM.rand(m, n, seed=draw(st.integers(0, 50)), dtype=dtype)
        kind = draw(st.sampled_from(
            ["add", "sub", "ewmul", "matmul", "matmul_t", "scale", "ewise"]))
        if kind in ("matmul", "matmul_t"):
            k = draw(st.integers(1, max_inner))
            if kind == "matmul_t":
                # A.T @ B with A ~ (k, m): the optimizer folds the
                # transpose into ADDMUL operand flags
                a = _rand_expr(draw, depth - 1, k, m, dtype, max_inner)
                b = _rand_expr(draw, depth - 1, k, n, dtype, max_inner)
                return a.T @ b
            a = _rand_expr(draw, depth - 1, m, k, dtype, max_inner)
            b = _rand_expr(draw, depth - 1, k, n, dtype, max_inner)
            return a @ b
        if kind in ("add", "sub", "ewmul"):
            a = _rand_expr(draw, depth - 1, m, n, dtype, max_inner)
            b = _rand_expr(draw, depth - 1, m, n, dtype, max_inner)
            return {"add": a + b, "sub": a - b,
                    "ewmul": a.hadamard(b)}[kind]
        if kind == "scale":
            return _rand_expr(draw, depth - 1, m, n, dtype, max_inner) * \
                draw(st.sampled_from([0.5, 1.5, -2.0]))
        return _rand_expr(draw, depth - 1, m, n, dtype, max_inner).ewise(
            draw(st.sampled_from(SAFE_EWISE)))

    @given(st.data())
    @settings(max_examples=25, deadline=None)
    def test_batched_bit_identical_property(data):
        """Satellite: over randomized expression DAGs, tile sizes and
        dtypes (incl. FUSED regions and transposed matmuls), the batched
        executor is bit-identical to the per-task executor, and — when
        every matmul k-chain fits one tile, so tiling itself does not
        re-associate the GEMM reduction — bit-identical to
        ``ClusteredMatrix.eager()`` of the expression in the planner's
        order of its products too.  Matrix-chain order re-associates
        products, so against ``eager()`` of the expression as written,
        the planner-independent oracle, it holds at the tolerance of a
        re-associated reduction."""
        dtype = data.draw(st.sampled_from([np.float64, np.float32]))
        tile = data.draw(st.integers(4, 16))
        m = data.draw(st.integers(2, 20))
        n = data.draw(st.integers(2, 20))
        depth = data.draw(st.integers(1, 3))
        # inner dims <= tile: single-k-tile GEMMs keep the reduction
        # order of the eager oracle (multi-k-tile accumulation is a
        # different float summation order by construction)
        expr = _rand_expr(data.draw, depth, m, n, dtype, max_inner=tile)
        plan = _plan(expr, tile=tile)
        out_local = LocalExecutor().execute(plan)
        out_wave = WaveExecutor().execute(plan)
        assert out_wave.dtype == out_local.dtype
        assert np.array_equal(out_local, out_wave), \
            "batched executor diverged from per-task executor"
        tol = 1e-4 if dtype == np.float32 else 1e-9
        np.testing.assert_allclose(out_wave, expr.eager(),
                                   rtol=tol, atol=tol)
        eager = _in_planned_order(expr).eager()
        assert np.array_equal(out_wave, eager), \
            "batched executor diverged from the eager oracle"

    @given(st.data())
    @settings(max_examples=10, deadline=None)
    def test_batched_matches_per_task_with_long_k_chains(data):
        """Multi-k-tile matmuls (tiled reduction order differs from one
        big GEMM): batched must still match the per-task executor
        bitwise, and the oracle at tolerance."""
        dtype = data.draw(st.sampled_from([np.float64, np.float32]))
        tile = data.draw(st.integers(3, 8))
        k = data.draw(st.integers(tile + 1, 3 * tile))   # forces kt > 1
        m = data.draw(st.integers(2, 12))
        n = data.draw(st.integers(2, 12))
        expr = (CM.rand(m, k, seed=0, dtype=dtype) @
                CM.rand(k, n, seed=1, dtype=dtype)).relu() + \
            CM.rand(m, n, seed=2, dtype=dtype)
        plan = _plan(expr, tile=tile)
        out_local = LocalExecutor().execute(plan)
        out_wave = WaveExecutor().execute(plan)
        assert np.array_equal(out_local, out_wave)
        tol = 1e-4 if dtype == np.float32 else 1e-9
        np.testing.assert_allclose(out_wave, expr.eager(),
                                   rtol=tol, atol=tol)
