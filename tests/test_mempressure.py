"""Memory-pressure tier: bounded arenas, tiered spill, admission control.

The contract (ISSUE: out-of-core tiles): give every node a byte budget
(``ClusterSpec.mem_bytes`` / ``node_mem``) and every memory-consuming
path must *survive* it — cold tiles spill to a CRC-checked disk tier and
fault back in transparently, so a bounded run is **bitwise identical**
to the unbounded oracle at the same tile size.  Plans whose minimum
working set cannot fit are re-planned at a smaller tile or rejected with
a structured ``MemoryBudgetExceeded`` naming the offending node — never
an OOM kill.  ``mem_squeeze``/``alloc_fail`` chaos drives the recovery
path on real worker processes.
"""
import numpy as np
import pytest

from repro.core import (ClusteredMatrix as CM, CMMEngine, TimeModel,
                        analytic_time_model)
from repro.core.cache import NodeCache
from repro.core.heft import min_resident_floor, peak_node_bytes
from repro.core.machine import MemoryBudgetExceeded, hetero_spec
from repro.core.session import CMMSession
from repro.core.simulator import predict_spill_seconds
from repro.exec.elastic import ChaosEvent, ClusterExecutor
from repro.runtime.spill import (SpillCorrupt, SpillMiss, TileSpillStore,
                                 run_spill_dir)

TM = analytic_time_model()
FAST_NET = dict(link_bw=1e12, latency=1e-6)
SPEC3 = hetero_spec((3, 2, 1), **FAST_NET)

#: working set of the standard (A @ B) + A conformance program below
N = 96
WS = 3 * N * N * 8


def _expr(n=N):
    A = CM.rand(n, n, seed=0)
    B = CM.rand(n, n, seed=1)
    return (A @ B) + A


def _plan(spec, tile=16, expr=None):
    eng = CMMEngine(spec, TM, plan_cache=False)
    return eng.plan(expr if expr is not None else _expr(), tile=tile)


def _bounded_spec(budget=WS // 3):
    return hetero_spec((3, 2, 1), mem_bytes=float(budget), **FAST_NET)


# -- ClusterSpec budget accessors -------------------------------------------

def test_spec_mem_accessors():
    s = hetero_spec((2, 1), **FAST_NET)
    assert s.mem_at(0) is None
    b = _bounded_spec(1 << 20)
    assert b.mem_at(0) == 1 << 20 and b.mem_at(2) == 1 << 20
    sq = b.with_mem(1, 4096)
    assert sq.mem_at(1) == 4096 and sq.mem_at(0) == 1 << 20
    lifted = sq.with_mem(1, None)
    assert lifted.mem_at(1) == 1 << 20   # falls back to mem_bytes
    with pytest.raises(ValueError):
        b.with_mem(7, 1)
    # a joined node falls beyond node_mem and inherits mem_bytes
    j = sq.with_node(2)
    assert j.mem_at(j.n_nodes - 1) == 1 << 20


def test_memory_budget_exceeded_is_structured():
    e = MemoryBudgetExceeded(2, 4096, 1024)
    assert e.node == 2 and e.needed_bytes == 4096 and e.budget_bytes == 1024
    assert "node 2" in str(e) and "4096" in str(e)


# -- NodeCache: incremental byte totals + pinning ---------------------------

def test_nodecache_running_totals_match_recount():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    op = st.tuples(st.sampled_from(["put", "invalidate", "pin", "unpin"]),
                   st.integers(0, 2),          # node
                   st.integers(0, 7),          # key
                   st.integers(0, 4096))       # nbytes

    @given(ops=st.lists(op, max_size=60),
           cap=st.one_of(st.none(), st.integers(1, 8192)))
    @settings(max_examples=60, deadline=None)
    def run(ops, cap):
        c = NodeCache(3, capacity_bytes=cap)
        for (kind, node, key, nbytes) in ops:
            if kind == "put":
                c.put(node, key, nbytes)
            elif kind == "invalidate":
                c.invalidate(key)
            elif kind == "pin":
                c.pin(key)
            else:
                c.unpin(key)
            for n in range(3):
                assert c.bytes_at(n) == sum(c._c[n].values()), \
                    "running total drifted from the table"
                if cap is not None:
                    # over-capacity is only allowed for pinned entries or
                    # a single (fresh) entry that alone exceeds capacity
                    if c.bytes_at(n) > cap:
                        unpinned = [k for k in c._c[n] if not c.pinned(k)]
                        assert len(unpinned) <= 1 or all(
                            c.pinned(k) for k in list(c._c[n])[:-1])
        cl = c.clone()
        for n in range(3):
            assert cl.bytes_at(n) == c.bytes_at(n)

    run()


def test_nodecache_pin_exempts_from_eviction():
    c = NodeCache(1, capacity_bytes=100)
    c.put(0, "keep", 60)
    c.pin("keep")
    for i in range(8):
        c.put(0, f"junk{i}", 60)
    assert c.peek(0, "keep"), "pinned entry was evicted"
    c.unpin("keep")
    c.put(0, "more", 60)
    assert not c.peek(0, "keep"), "unpinned cold entry should evict"
    assert c.bytes_at(0) == sum(c._c[0].values())


# -- spill store: CRC round-trip --------------------------------------------

def test_spill_store_roundtrip_bitwise(tmp_path):
    st_ = TileSpillStore(str(tmp_path / "s"), "t")
    rng = np.random.default_rng(0)
    a = rng.standard_normal((17, 13))
    st_.spill("k", a)
    assert "k" in st_ and st_.live_files == 1
    back = st_.fault_in("k")
    assert np.array_equal(a, back) and a.dtype == back.dtype
    assert "k" not in st_          # fault-in consumes the entry
    with pytest.raises(SpillMiss):
        st_.fault_in("k")
    assert st_.destroy() == 0


def test_spill_store_crc_detects_corruption(tmp_path):
    st_ = TileSpillStore(str(tmp_path / "s"), "t")
    st_.spill("k", np.arange(64, dtype=np.float64))
    st_.corrupt("k")
    with pytest.raises(SpillCorrupt):
        st_.fault_in("k")


# -- pricing: TimeModel + simulator + admission -----------------------------

def test_timemodel_spill_write_bandwidth_roundtrips():
    import json
    tm = TimeModel.from_json(TM.to_json())
    assert tm.spill_write_bandwidth == TM.spill_write_bandwidth
    d = json.loads(TM.to_json())
    del d["spill_write_bandwidth"]     # legacy calibration files
    assert TimeModel.from_json(json.dumps(d)).spill_write_bandwidth == 1e9


def test_predict_spill_seconds_monotone():
    assert predict_spill_seconds(0, TM) == 0.0
    a = predict_spill_seconds(1 << 20, TM)
    b = predict_spill_seconds(1 << 24, TM)
    assert 0.0 < a < b


def test_peak_node_bytes_sanity():
    plan = _plan(hetero_spec((3, 2, 1), **FAST_NET))
    peaks = peak_node_bytes(plan.program.graph, plan.schedule)
    assert peaks and all(v >= 0 for v in peaks.values())
    tile_bytes = 16 * 16 * 8
    assert max(peaks.values()) >= tile_bytes
    for n in peaks:
        floor = min_resident_floor(plan.program.graph, plan.schedule, n)
        assert 0 <= floor <= peaks[n]


def test_admission_annotates_spill_price():
    eng = CMMEngine(_bounded_spec(WS // 3), TM, plan_cache=False)
    plan = eng.plan(_expr(), tile=16)
    assert plan.peak_bytes, "admission must record per-node peaks"
    assert plan.spill_bytes > 0 and plan.spill_seconds > 0.0
    # a generous budget prices to zero spill
    eng2 = CMMEngine(_bounded_spec(1 << 30), TM, plan_cache=False)
    plan2 = eng2.plan(_expr(), tile=16)
    assert plan2.spill_bytes == 0 and plan2.spill_seconds == 0.0


def test_admission_rejects_unsatisfiable_budget():
    eng = CMMEngine(_bounded_spec(10), TM, plan_cache=False)
    with pytest.raises(MemoryBudgetExceeded) as ei:
        eng.plan(_expr(32), tile=16)
    e = ei.value
    assert isinstance(e.node, int) and 0 <= e.node < 3
    assert e.needed_bytes > e.budget_bytes == 10


def test_admission_replans_smaller_tile_out_of_core():
    # one ADDMUL working set at tile 16 is 3*2048 = 6144 bytes > 4000,
    # so the plan must shrink until its floor fits the budget
    eng = CMMEngine(_bounded_spec(4000), TM, plan_cache=False)
    plan = eng.plan(_expr(64), tile=16)
    assert eng.plan_shrinks >= 1
    assert plan.tile < (16, 16)
    # bit-identity holds at the CHOSEN tile (a different tile size has a
    # different FP accumulation order, so eager is compared approximately)
    out = eng.run(_expr(64), tile=16)
    oracle = CMMEngine(SPEC3, TM, plan_cache=False)
    assert np.array_equal(out, oracle.run(_expr(64), tile=plan.tile))
    np.testing.assert_allclose(out, _expr(64).eager())


# -- bounded-arena bit-identity on real worker processes --------------------

@pytest.mark.slow
@pytest.mark.mempressure
@pytest.mark.parametrize("tm", [None, TM], ids=["own-tm", "engine-tm"])
def test_cluster_bounded_bitwise_vs_unbounded(tm):
    """Acceptance: footprint >= 2x per-node budget completes bitwise
    equal to the unbounded oracle on the cluster executor, with its own
    model or one handed in."""
    ref = ClusterExecutor(timemodel=tm).execute(_plan(SPEC3))
    ex = ClusterExecutor(timemodel=tm)
    out = ex.execute(_plan(_bounded_spec(WS // 3)))
    assert np.array_equal(ref, out)
    assert ex.stats["spill_writes"] > 0, "budget never exercised the spill"
    assert ex.stats["faults"] > 0
    assert ex.stats["leaked_spill_files"] == 0
    assert ex.stats["live_buffers"] == 0
    assert ex.stats["tiles_lost"] == 0


@pytest.mark.mempressure
def test_cluster_bounded_xfer_heavy_chain_bitwise():
    """Regression: two matmul chains sharing a leaf plus a fused
    elementwise tail generate enough cross-node XFER traffic that,
    under a ws/3 budget, the source arenas cycle their whole LRU inside
    the master->consumer dispatch window.  Without source-side
    hold/release leases the name-based XFER retries livelock (the acked
    segment is re-evicted before the destination attaches, every
    time)."""
    A = CM.rand(N, N, seed=2)
    B = CM.rand(N, N, seed=3)
    expr = (A @ B + A.T @ B) * 2.0 - B
    ref = ClusterExecutor().execute(_plan(SPEC3, expr=expr))
    ex = ClusterExecutor()
    out = ex.execute(_plan(_bounded_spec(WS // 3), expr=expr))
    assert np.array_equal(ref, out)
    assert ex.stats["spill_writes"] > 0, "budget never exercised the spill"
    assert ex.stats["leaked_spill_files"] == 0
    assert ex.stats["live_buffers"] == 0


@pytest.mark.slow
@pytest.mark.chaos
@pytest.mark.mempressure
def test_elastic_mem_squeeze_midrun_bitwise():
    """Shrinking a node's budget mid-run forces eviction; the run stays
    bitwise correct and current_spec reflects the squeeze."""
    ref = ClusterExecutor(timemodel=TM).execute(_plan(SPEC3))
    ex = ClusterExecutor(
        timemodel=TM,
        chaos=(ChaosEvent(after_done=5, mem_squeeze=1,
                          squeeze_bytes=WS // 6),))
    out = ex.execute(_plan(SPEC3))
    assert np.array_equal(ref, out)
    assert ex.stats["squeezes"] == 1
    assert ex.stats["evictions"] > 0
    assert ex.current_spec.mem_at(1) == WS // 6


@pytest.mark.slow
@pytest.mark.chaos
@pytest.mark.mempressure
def test_elastic_alloc_fail_retries_bitwise():
    """An injected allocation failure rides the bounded task/XFER retry
    path — the master recovers, never crashes."""
    ref = ClusterExecutor(timemodel=TM).execute(_plan(SPEC3))
    ex = ClusterExecutor(
        timemodel=TM,
        chaos=(ChaosEvent(after_done=3, alloc_fail=0, alloc_fail_nth=2),))
    out = ex.execute(_plan(SPEC3))
    assert np.array_equal(ref, out)
    assert ex.stats["task_retries"] + ex.stats["xfer_retries"] >= 1


@pytest.mark.slow
@pytest.mark.chaos
@pytest.mark.mempressure
def test_elastic_squeeze_to_nothing_is_structured_error():
    """A squeeze below one tile's working set can never be survived —
    the run must fail with MemoryBudgetExceeded naming the node, not an
    OOM kill or a hang."""
    ex = ClusterExecutor(
        timemodel=TM, timeout=120.0,
        chaos=(ChaosEvent(after_done=2, mem_squeeze=1,
                          squeeze_bytes=64),))
    with pytest.raises(MemoryBudgetExceeded) as ei:
        ex.execute(_plan(SPEC3))
    assert ei.value.node == 1


@pytest.mark.slow
@pytest.mark.chaos
@pytest.mark.mempressure
def test_elastic_kill_composes_with_bounded_arena():
    """Spill/fault-in composes with the existing kill chaos: lineage
    recovery under a budget stays bitwise."""
    ref = ClusterExecutor(timemodel=TM).execute(_plan(SPEC3))
    ex = ClusterExecutor(
        timemodel=TM, chaos=(ChaosEvent(after_done=6, kill_node=2),))
    out = ex.execute(_plan(_bounded_spec(WS // 2)))
    assert np.array_equal(ref, out)
    assert ex.stats["deaths"] == 1


# -- sessions: persisted tiles under a budget -------------------------------

def _power_refs(n, k, tile):
    P = CM.rand(n, n, seed=0)
    u = CM.rand(n, 1, seed=1)
    e = u
    for _ in range(k):
        e = P @ e
    eng = CMMEngine(SPEC3, TM)
    return eng.run(e, tile=tile)


@pytest.mark.slow
@pytest.mark.mempressure
def test_session_cluster_bounded_bitwise_and_clean_close():
    ref = _power_refs(64, 3, 16)
    eng = CMMEngine(_bounded_spec(WS // 3), TM)
    s = CMMSession(eng, executor="cluster", tile=16)
    P = s.persist(CM.rand(64, 64, seed=0))
    u = s.persist(CM.rand(64, 1, seed=1))
    for _ in range(3):
        u = s.persist(P @ u)
    got = u.to_numpy()
    assert np.array_equal(got, ref)
    audit = s.close()
    assert audit["spill"]["leaked_spill_files"] == 0
    for node, st_ in audit["arena"].items():
        assert st_["live_buffers"] == 0
        assert st_["retained"] == 0
        assert st_.get("spill_files", 0) == 0


@pytest.mark.slow
@pytest.mark.chaos
@pytest.mark.mempressure
def test_session_elastic_squeeze_persisted_workload():
    """A mid-run squeeze in session mode: results stay bitwise, the
    session re-plans follow-up runs against the squeezed current_spec,
    and close() audits clean (no leaked spill files)."""
    ref = _power_refs(64, 3, 16)
    eng = CMMEngine(_bounded_spec(WS), TM)
    s = CMMSession(eng, executor="cluster", tile=16)
    s._exec.chaos = (ChaosEvent(after_done=4, mem_squeeze=1,
                                squeeze_bytes=WS // 4),)
    P = s.persist(CM.rand(64, 64, seed=0))
    u = s.persist(CM.rand(64, 1, seed=1))
    for _ in range(3):
        u = s.persist(P @ u)
    got = u.to_numpy()
    assert np.array_equal(got, ref)
    audit = s.close()
    assert audit["spill"]["leaked_spill_files"] == 0
    for node, st_ in audit["arena"].items():
        assert st_["live_buffers"] == 0
        assert st_.get("spill_files", 0) == 0


@pytest.mark.slow
@pytest.mark.mempressure
def test_spill_dir_removed_after_oneshot_run():
    import os
    ex = ClusterExecutor()
    ex.execute(_plan(_bounded_spec(WS // 3)))
    assert ex.stats["spill_writes"] > 0
    assert ex.stats["leaked_spill_files"] == 0
    # the run-scoped spill directory itself is reaped
    root = os.path.dirname(run_spill_dir("probe"))
    if os.path.isdir(root):
        leftovers = [d for d in os.listdir(root)
                     if os.listdir(os.path.join(root, d))]
        assert not any(f"cmm{os.getpid()}_" in d for d in leftovers)
