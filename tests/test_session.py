"""Session engine: resident distributed tiles across compute() calls.

Bit-identity contract (TESTING.md): a persisted k-step chain — persist
each step, feed the handle forward — must be **bitwise identical** to the
equivalent one-shot program of each step on the same backend, and (when
every matmul k-chain fits one tile) to the eager oracle of each step in
the planner's order of its products; it matches the one-shot program of
the whole chain, and the eager oracle of the chain as written, at the
tolerance of a re-associated reduction, across the
``local``/``batched``/``cluster`` executors on the heterogeneous 3-node
spec.  Residency changes *where data lives between runs*, never what is
computed.
"""
import numpy as np
import pytest

from repro.core import ClusteredMatrix as CM, CMMEngine, analytic_time_model
from repro.core.graph import TaskKind
from repro.core.machine import hetero_spec, local_spec
from repro.core.session import CMMSession, ResidentMatrix

TM = analytic_time_model()
#: the conformance spec: unequal worker counts, near-free links so HEFT
#: spreads placements and resident tiles genuinely live on several nodes
SPEC3 = hetero_spec((3, 2, 1), link_bw=1e12, latency=1e-6)


def _engine(spec=None, **kw):
    return CMMEngine(spec or local_spec(1), TM, **kw)


def _power_iter_oneshot(n, k, tile, eng, executor="local"):
    P = CM.rand(n, n, seed=0)
    u = CM.rand(n, 1, seed=1)
    e = u
    for _ in range(k):
        e = P @ e
    return eng.run(e, tile=tile, executor=executor)


# -- basics -----------------------------------------------------------------

def test_persist_returns_resident_leaf():
    with CMMSession(_engine(), tile=16) as s:
        A = s.persist(CM.rand(32, 32, seed=0), name="A")
        assert isinstance(A, ResidentMatrix)
        assert A.shape == (32, 32)
        assert A.handle.grid == (2, 2)
        assert set(A.handle.home.values()) == {0}
        np.testing.assert_array_equal(A.to_numpy(),
                                      CM.rand(32, 32, seed=0).eager())


def test_session_power_iteration_bitwise_vs_oneshot():
    n, k, tile = 48, 4, 16
    eng = _engine()
    with CMMSession(eng, tile=tile) as s:
        P = s.persist(CM.rand(n, n, seed=0))
        u = s.persist(CM.rand(n, 1, seed=1))
        for _ in range(k):
            u = s.persist(P @ u)
        got = u.to_numpy()
    ref = _power_iter_oneshot(n, k, tile, _engine())
    assert np.array_equal(got, ref)


def test_resident_graph_has_no_fill_or_takecopy_for_residents():
    eng = _engine()
    with CMMSession(eng, tile=16) as s:
        P = s.persist(CM.rand(32, 32, seed=0))
        u = s.persist(CM.rand(32, 1, seed=1))
        s.persist(P @ u)
        st = s.stats["last_exec"]
        # the persisted step ran RESIDENT binds instead of FILLs, and no
        # TAKECOPY gather at all
        plan = eng.plan_many([P @ u], tile=16, persist=(0,))
        counts = plan.program.graph.counts()
        assert counts.get("resident", 0) == 4 + 2   # P (2x2) + u (2x1) tiles
        assert "fill" not in counts
        assert "takecopy" not in counts
        assert st["gather_bytes"] == 0


def test_session_fewer_tasks_and_zero_gather_than_oneshot():
    n, tile = 48, 16
    eng = _engine()
    P1 = CM.rand(n, n, seed=0)
    u1 = CM.rand(n, 1, seed=1)
    oneshot_plan = eng.plan(P1 @ u1, tile=tile)
    oneshot_tasks = len(oneshot_plan.program.graph)
    with CMMSession(eng, tile=tile) as s:
        P = s.persist(CM.rand(n, n, seed=0))
        u = s.persist(CM.rand(n, 1, seed=1))
        s.persist(P @ u)
        step_tasks = s.stats["last_exec"]["tasks_run"]
        assert step_tasks < oneshot_tasks
        assert s.stats["last_exec"]["gather_bytes"] == 0


def test_session_plan_cache_hits_across_steps():
    """Each persisted step has the same structure + residency layout, so
    the second and later steps must hit the structural plan cache."""
    eng = _engine()
    with CMMSession(eng, tile=16) as s:
        P = s.persist(CM.rand(48, 48, seed=0))
        u = s.persist(CM.rand(48, 1, seed=1))
        u = s.persist(P @ u)
        misses0 = eng.plan_cache_misses
        hits0 = eng.plan_cache_hits
        for _ in range(3):
            u = s.persist(P @ u)
        assert eng.plan_cache_misses == misses0
        assert eng.plan_cache_hits == hits0 + 3


def test_compute_many_shared_cse():
    """Two roots sharing a subexpression plan as ONE program: the shared
    matmul is computed once (shared CSE), and both results are exact."""
    A = CM.rand(32, 32, seed=0)
    B = CM.rand(32, 32, seed=1)
    AB = A @ B
    r1 = AB + A
    r2 = AB - B
    eng = _engine()
    with CMMSession(eng, tile=16) as s:
        out1, out2 = s.compute_many([r1, r2])
    np.testing.assert_allclose(out1, r1.eager(), rtol=1e-8, atol=1e-8)
    np.testing.assert_allclose(out2, r2.eager(), rtol=1e-8, atol=1e-8)
    plan = eng.plan_many([r1, r2], tile=16)
    merged = len(plan.program.graph)
    sep = len(eng.plan(r1, tile=16).program.graph) + \
        len(eng.plan(r2, tile=16).program.graph)
    assert merged < sep


def test_tile_mismatch_falls_back_to_gather():
    """A handle persisted at one tile size re-enters a differently-tiled
    program as a gathered INPUT leaf — correct, just not zero-cost."""
    with CMMSession(_engine(), tile=16) as s:
        A = s.persist(CM.rand(32, 32, seed=0))
        out = s.compute(A + A, tile=8)
    ref = CM.rand(32, 32, seed=0).eager()
    np.testing.assert_array_equal(out, ref + ref)


@pytest.mark.parametrize("executor", ["local", "batched"])
def test_persisted_handle_is_a_snapshot(executor):
    """A resident handle owns its memory: mutating the user array after
    persisting an INPUT-rooted expression must not change the handle
    (view-backed tiles are copied at retention)."""
    eng = _engine()
    with CMMSession(eng, executor=executor, tile=16) as s:
        a = np.ones((32, 32))
        P = s.persist(CM.from_array(a))
        a[:] = 99.0
        assert np.all(P.to_numpy() == 1.0)


def test_free_and_foreign_handle_errors():
    s1 = CMMSession(_engine(), tile=16)
    s2 = CMMSession(_engine(), tile=16)
    A = s1.persist(CM.rand(16, 16, seed=0))
    with pytest.raises(ValueError, match="does not belong"):
        s2.compute(A + 1.0)
    A.free()
    with pytest.raises(ValueError, match="freed"):
        s1.compute(A + 1.0)
    s1.close()
    s2.close()


def test_engine_run_unchanged_one_shot():
    """compute() stays a thin one-shot wrapper: no session, no residency."""
    expr = (CM.rand(32, 32, seed=0) @ CM.rand(32, 32, seed=1)) * 0.5
    out = _engine().run(expr, tile=16)
    np.testing.assert_allclose(out, expr.eager(), rtol=1e-8, atol=1e-8)


# -- batched backend --------------------------------------------------------

def test_session_batched_bitwise_vs_oneshot():
    n, k, tile = 48, 3, 16
    eng = _engine()
    with CMMSession(eng, executor="batched", tile=tile) as s:
        P = s.persist(CM.rand(n, n, seed=0))
        u = s.persist(CM.rand(n, 1, seed=1))
        for _ in range(k):
            u = s.persist(P @ u)
        got = u.to_numpy()
    ref = _power_iter_oneshot(n, k, tile, _engine(), executor="batched")
    assert np.array_equal(got, ref)


# -- cluster backend: resident tiles in worker shm arenas -------------------

@pytest.mark.slow
def test_session_cluster_three_runs_no_arena_leaks():
    """Acceptance: the long-lived cluster executor survives >= 3
    consecutive session runs; after every run the worker arenas hold
    exactly the retained tiles (refcount audit), and close() audits
    clean."""
    eng = _engine(SPEC3)
    s = CMMSession(eng, executor="cluster", tile=16)
    P = s.persist(CM.rand(48, 48, seed=0))
    u = s.persist(CM.rand(48, 1, seed=1))
    for _ in range(3):
        u = s.persist(P @ u)
        st = s.stats["last_exec"]
        assert st["live_buffers"] == 0, "arena leak: stray run buffers"
        assert st["cur_buffer_bytes"] == 0
        assert st["retained_tiles"] == 3       # this step's u tiles (3x1)
    got = u.to_numpy()
    ref = _power_iter_oneshot(48, 3, 16, _engine(SPEC3))
    assert np.array_equal(got, ref)
    audit = s.close()
    for node, st in audit["arena"].items():
        assert st["live_buffers"] == 0, f"node {node} leaked buffers"
        assert st["retained"] == 0, f"node {node} leaked retained tiles"


@pytest.mark.slow
def test_session_cluster_resident_tiles_stay_remote():
    """Resident tiles of a spread computation live on several nodes and
    re-enter pinned there — consuming them gathers nothing to master."""
    eng = _engine(SPEC3)
    with CMMSession(eng, executor="cluster", tile=16) as s:
        A = s.persist(CM.rand(96, 96, seed=0) @ CM.rand(96, 96, seed=1))
        assert len(set(A.handle.home.values())) > 1, \
            "expected resident tiles spread across nodes"
        out = s.compute(A + A)
        a = (CM.rand(96, 96, seed=0) @ CM.rand(96, 96, seed=1))
        ref = eng.run(a + a, tile=16)
        assert np.array_equal(out, ref)


# -- hypothesis: persisted chains vs one-shot vs oracle ---------------------

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYP = True
except ImportError:                     # pragma: no cover
    HAVE_HYP = False

if HAVE_HYP:
    # reuse the randomized-DAG strategies from the wave-executor tests
    from test_batched import _in_planned_order, _rand_expr, SAFE_EWISE

    def _chain_steps(draw, k, m, dtype, max_inner):
        """k step-builders f_i: each combines the fed-forward matrix with
        a fresh random sub-DAG (drawn with test_batched's strategy)."""
        steps = []
        for i in range(k):
            kind = draw(st.sampled_from(
                ["matmul_l", "matmul_r", "add", "ewmul", "scale", "ewise"]))
            sub = _rand_expr(draw, draw(st.integers(0, 1)), m, m, dtype,
                             max_inner)
            if kind == "matmul_l":
                steps.append(lambda x, s=sub: s @ x)
            elif kind == "matmul_r":
                steps.append(lambda x, s=sub: x @ s)
            elif kind == "add":
                steps.append(lambda x, s=sub: x + s)
            elif kind == "ewmul":
                steps.append(lambda x, s=sub: x.hadamard(s))
            elif kind == "scale":
                c = draw(st.sampled_from([0.5, -1.5, 2.0]))
                steps.append(lambda x, c=c: x * c)
            else:
                fn = draw(st.sampled_from(SAFE_EWISE))
                steps.append(lambda x, fn=fn: x.ewise(fn))
        return steps

    def _run_chain_property(data, executor, spec):
        dtype = data.draw(st.sampled_from([np.float64, np.float32]))
        m = data.draw(st.integers(2, 12))
        tile = data.draw(st.integers(m, 16))   # single-k-tile matmuls:
        k = data.draw(st.integers(2, 3))       # oracle stays bitwise
        steps = _chain_steps(data.draw, k, m, dtype, max_inner=tile)
        x0 = CM.rand(m, m, seed=data.draw(st.integers(0, 50)), dtype=dtype)

        # the whole chain as one one-shot program on the same backend
        e = x0
        for f in steps:
            e = f(e)
        whole = _engine(spec).run(e, tile=tile, executor=executor)

        # one-shot equivalent on the same backend, a program per step: a
        # persisted step is a root of its own plan, so the planner's
        # matrix-chain order never reaches across it
        eng_ref = _engine(spec)
        ref = eng_ref.run(x0, tile=tile, executor=executor)
        eager = x0.eager()
        for f in steps:
            ref = eng_ref.run(f(CM.from_array(ref)), tile=tile,
                              executor=executor)
            eager = _in_planned_order(f(CM.from_array(eager))).eager()

        eng = _engine(spec)
        with CMMSession(eng, executor=executor, tile=tile) as s:
            cur = s.persist(x0)
            for f in steps:
                cur = s.persist(f(cur))
            got = cur.to_numpy()
        assert got.dtype == ref.dtype
        assert np.array_equal(got, ref), \
            f"persisted chain diverged from one-shot on {executor}"
        assert np.array_equal(got, eager), \
            f"persisted chain diverged from the eager oracle on {executor}"
        # the one-shot program of the whole chain may re-associate products
        # across steps: it, and eager() as written, hold at the tolerance
        # of a re-associated reduction
        tol = 1e-4 if dtype == np.float32 else 1e-9
        np.testing.assert_allclose(got, whole, rtol=tol, atol=tol)
        np.testing.assert_allclose(got, e.eager(), rtol=tol, atol=tol)

    @given(st.data())
    @settings(max_examples=15, deadline=None)
    def test_chain_bitwise_local(data):
        _run_chain_property(data, "local", SPEC3)

    @given(st.data())
    @settings(max_examples=15, deadline=None)
    def test_chain_bitwise_batched(data):
        _run_chain_property(data, "batched", SPEC3)

    @pytest.mark.slow
    @given(st.data())
    @settings(max_examples=5, deadline=None)
    def test_chain_bitwise_cluster(data):
        _run_chain_property(data, "cluster", SPEC3)


# -- elastic backend: lost resident tiles recompute from lineage ------------

@pytest.mark.chaos
def test_elastic_session_recomputes_lost_resident_from_lineage():
    """Acceptance: SIGKILL the node holding resident tiles mid-run; the
    session re-derives the handle from lineage on the survivors and the
    retried run is bit-identical."""
    from repro.exec.elastic import ChaosEvent
    spec = hetero_spec((2, 2), link_bw=1e12, latency=1e-6)
    eng = _engine(spec)
    s = CMMSession(eng, executor="cluster", tile=16)
    try:
        A = s.persist(CM.rand(96, 96, seed=0) @ CM.rand(96, 96, seed=1),
                      name="A")
        assert 1 in set(A.handle.home.values()), \
            "expected resident tiles on the victim node"
        s._exec.chaos = (ChaosEvent(after_done=3, kill_node=1),)
        out = s.compute(A @ A)
        s._exec.chaos = ()
        assert s.stats.get("recomputed_handles", 0) >= 1
        assert eng.spec.alive_nodes() == (0,)      # membership synced
        assert set(A.handle.home.values()) == {0}  # re-homed on survivor
        a = CM.rand(96, 96, seed=0) @ CM.rand(96, 96, seed=1)
        ref = _engine(spec).run(a @ a, tile=16)
        assert np.array_equal(out, ref)
        # the session keeps working after recovery
        out2 = s.compute(A + A)
        ref2 = _engine(spec).run(a + a, tile=16)
        assert np.array_equal(out2, ref2)
    finally:
        audit = s.close()
    for node, stx in (audit.get("arena") or {}).items():
        assert stx["live_buffers"] == 0
        assert stx["retained"] == 0


@pytest.mark.chaos
def test_elastic_session_marks_unused_handles_lost():
    """A handle NOT referenced by the failing run still loses its tiles
    when its home node dies; the session marks it lost after the run and
    the next use re-derives it from lineage."""
    from repro.exec.elastic import ChaosEvent
    spec = hetero_spec((2, 2), link_bw=1e12, latency=1e-6)
    eng = _engine(spec)
    with CMMSession(eng, executor="cluster", tile=16) as s:
        Q = s.persist(CM.rand(96, 96, seed=2) @ CM.rand(96, 96, seed=3),
                      name="Q")
        assert 1 in set(Q.handle.home.values())
        R = s.persist(CM.rand(48, 48, seed=4))
        s._exec.chaos = (ChaosEvent(after_done=2, kill_node=1),)
        s.compute(R + R)                   # does not read Q
        s._exec.chaos = ()
        assert Q.handle.lost
        q = Q.to_numpy()                   # lineage recompute on survivors
        ref = _engine(spec).run(
            CM.rand(96, 96, seed=2) @ CM.rand(96, 96, seed=3), tile=16)
        assert np.array_equal(q, ref)


@pytest.mark.chaos
def test_elastic_session_three_runs_bitwise():
    """Elastic session without churn: >= 3 consecutive runs over resident
    tiles, bitwise vs the one-shot path, clean audit."""
    spec = hetero_spec((2, 2), link_bw=1e12, latency=1e-6)
    eng = _engine(spec)
    with CMMSession(eng, executor="cluster", tile=16) as s:
        P = s.persist(CM.rand(48, 48, seed=0))
        u = s.persist(CM.rand(48, 1, seed=1))
        for _ in range(3):
            u = s.persist(P @ u)
        got = u.to_numpy()
    ref = _power_iter_oneshot(48, 3, 16, _engine(spec))
    assert np.array_equal(got, ref)
