"""Network-path tier: lossless wire codecs, priced per-edge compression,
relay-tree broadcast, streaming gather, and the transfer-path leases.

Contract (ISSUE: network path overhaul): every byte that crosses the
wire on the TILE path is losslessly coded — a compressed run is bitwise
identical to the raw run and to the eager oracle, with compression on
and off, on every executor, under churn.  Lossy codecs (int8 gradient
quantisation) are allowed on the OPTIMIZER path only and never touch
tiles.  Leases keep bounded-arena sources pinned for exactly the life
of each copy: a consumer dying mid-copy must release, not strand, the
source pin.
"""
import numpy as np
import pytest

from repro.core import (ClusteredMatrix as CM, CMMEngine, TimeModel,
                        analytic_time_model)
from repro.core.machine import hetero_spec
from repro.core.simulator import _relay_tree
from repro.core.timemodel import TimeModel as TM_cls
from repro.exec.elastic import ChaosEvent, ClusterExecutor
from repro.exec.local import LocalExecutor
from repro.runtime.membership import MembershipConfig
from repro.runtime.wire import (CODECS, choose_wire_codec, decode_tile,
                                encode_tile)

TM = analytic_time_model()
FAST_NET = dict(link_bw=1e12, latency=1e-6)


def _plan(expr, tile, spec):
    eng = CMMEngine(spec, TM, plan_cache=False)
    return eng.plan(expr, tile=tile)


def _synth(n=64):
    A = CM.rand(n, n, seed=0)
    B = CM.rand(n, n, seed=1)
    return (A @ B) + A


def _fanout_expr(n=96):
    """One operand feeds every output tile column — a fan-out-heavy
    program whose XFER pattern exercises relay trees for real."""
    A = CM.rand(n, n, seed=0)
    B = CM.rand(n, n, seed=1)
    return A @ B


# -- codec round trips -------------------------------------------------------

def test_codec_registry():
    assert set(CODECS) >= {"raw", "zlib"}
    with pytest.raises(ValueError, match="unknown wire codec"):
        from repro.runtime.wire import get_codec
        get_codec("lz9")


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
@pytest.mark.parametrize("codec", ["raw", "zlib"])
def test_roundtrip_bit_identity_random(dtype, codec):
    rng = np.random.default_rng(0)
    a = rng.standard_normal((37, 23)).astype(dtype)
    payload = encode_tile(a, codec)
    b = decode_tile(payload, a.shape, a.dtype, codec)
    assert b.dtype == a.dtype and b.shape == a.shape
    assert a.tobytes() == b.tobytes()       # bitwise, not allclose


@pytest.mark.parametrize("codec", ["raw", "zlib"])
def test_roundtrip_bit_identity_special_values(codec):
    a = np.array([[0.0, -0.0, np.inf, -np.inf],
                  [np.nan, 1e-308, -1e308, 2.0 ** -1074]])
    payload = encode_tile(a, codec)
    b = decode_tile(payload, a.shape, a.dtype, codec)
    assert a.tobytes() == b.tobytes()


def test_zlib_compresses_structured_tiles():
    col = np.linspace(0.0, 1.0, 256)
    structured = np.outer(col, np.ones(256))       # rank 1
    payload = encode_tile(structured, "zlib")
    assert len(payload) < structured.nbytes / 2
    back = decode_tile(payload, structured.shape, structured.dtype, "zlib")
    assert structured.tobytes() == back.tobytes()


def test_noncontiguous_input_encodes_correctly():
    rng = np.random.default_rng(1)
    base = rng.standard_normal((64, 64))
    view = base[::2, ::2]                          # non-contiguous
    payload = encode_tile(view, "zlib")
    back = decode_tile(payload, view.shape, view.dtype, "zlib")
    assert np.ascontiguousarray(view).tobytes() == back.tobytes()


# hypothesis property sweep (skipped where hypothesis is unavailable)
try:
    from hypothesis import given, settings, strategies as st
    _HYP = True
except ImportError:                                # pragma: no cover
    _HYP = False

if _HYP:
    @given(st.integers(1, 40), st.integers(1, 40),
           st.sampled_from(["<f4", "<f8"]),
           st.sampled_from(["raw", "zlib"]),
           st.booleans(), st.integers(0, 2 ** 31 - 1))
    @settings(max_examples=40, deadline=None)
    def test_roundtrip_property(rows, cols, dts, codec, structured, seed):
        rng = np.random.default_rng(seed)
        if structured:
            a = np.outer(np.arange(rows), np.ones(cols)).astype(dts)
        else:
            a = rng.standard_normal((rows, cols)).astype(dts)
        payload = encode_tile(a, codec)
        b = decode_tile(payload, a.shape, np.dtype(dts), codec)
        assert a.tobytes() == b.tobytes()


# -- per-edge pricing --------------------------------------------------------

def test_choose_codec_defaults_to_raw():
    # an unprofiled model (compress terms at their defaults) never
    # compresses — the pre-overhaul behaviour is the fallback
    assert choose_wire_codec(1 << 20, 1e9, TM) == "raw"


def test_choose_codec_prices_the_inequality():
    tm = TM_cls(compress_bandwidth=1e9, compression_ratio_prior=4.0)
    # slow link: encode at 1 GB/s then ship a quarter of the bytes wins
    assert choose_wire_codec(1 << 22, 1e8, tm) == "zlib"
    # near-infinite link: raw transfer is already free, encoding only adds
    assert choose_wire_codec(1 << 22, 1e13, tm) == "raw"


def test_wire_time_is_min_of_raw_and_compressed():
    spec = hetero_spec((1, 1), link_bw=1e8, latency=0.0)
    tm = TM_cls(compress_bandwidth=1e9, compression_ratio_prior=4.0)
    nb = 1 << 22
    raw = spec.comm_time(nb, 0, 1)
    comp = nb / 1e9 + spec.comm_time(nb // 4, 0, 1)
    assert np.isclose(tm.wire_time(nb, 0, 1, spec), min(raw, comp))
    assert tm.wire_time(nb, 0, 0, spec) == 0.0     # same node: no wire
    # unprofiled terms leave the pricing untouched
    assert TM.wire_time(nb, 0, 1, spec) == spec.comm_time(nb, 0, 1)


def test_timemodel_json_roundtrips_compression_terms():
    import json
    tm = TM_cls(compress_bandwidth=2.5e9, compression_ratio_prior=3.5)
    d = json.loads(tm.to_json())
    assert d["compress_bandwidth"] == 2.5e9
    assert d["compression_ratio_prior"] == 3.5
    back = TM_cls.from_json(tm.to_json())
    assert back.compress_bandwidth == 2.5e9
    assert back.compression_ratio_prior == 3.5
    # plan caches key on to_json(): fitted terms must change the key
    assert TM_cls().to_json() != d


def test_calibrate_compression_fits_sane_terms():
    from repro.core.profiler import calibrate_compression
    tm = TM_cls()
    cbw, ratio = calibrate_compression(tm, nbytes=1 << 18, reps=1)
    assert tm.compress_bandwidth == cbw and cbw > 1e5
    assert tm.compression_ratio_prior == ratio and ratio > 1.0


# -- the simulator's relay tree shape -----------------------------------------

def test_broadcast_tree_structure():
    tree = _relay_tree(0, [1, 2, 3, 4, 5])
    # every destination appears exactly once as a child
    kids = [c for cs in tree.values() for c in cs]
    assert sorted(kids) == [1, 2, 3, 4, 5]
    # binary: nobody relays to more than 2 children; depth is log-ish
    assert all(len(cs) <= 2 for cs in tree.values())
    assert 0 in tree                                 # source is the root


def test_broadcast_tree_excludes_source_from_dsts():
    tree = _relay_tree(2, [0, 1, 2])
    kids = [c for cs in tree.values() for c in cs]
    assert 2 not in kids and sorted(kids) == [0, 1]


# -- executor conformance: compression on the real transfer path ------------

@pytest.mark.parametrize(
    "codec,tm", [("raw", None), ("zlib", None), ("raw", TM), ("zlib", TM)],
    ids=["raw", "zlib", "raw-engine-tm", "zlib-engine-tm"])
def test_cluster_forced_codec_bit_identical(codec, tm):
    """A forced codec is used on every cross-node edge, whether the
    executor falls back to its own model or is handed one, as the engine
    hands it its TimeModel."""
    spec = hetero_spec((2, 2, 1), **FAST_NET)
    plan = _plan(_synth(), tile=16, spec=spec)
    ref = LocalExecutor().execute(plan)
    ex = ClusterExecutor(timemodel=tm, wire_codec=codec)
    out = ex.execute(plan)
    assert np.array_equal(ref, out)
    if codec == "zlib" and ex.stats["xfers"] > 0:
        assert ex.stats["xfers_compressed"] > 0
        assert ex.stats["wire_bytes"] < ex.stats["xfer_bytes"]
    assert ex.stats["stale_leases"] == 0
    assert ex.stats["stale_retry_entries"] == 0


def test_auto_pricing_compresses_on_slow_links_only():
    tm = analytic_time_model()
    tm.compress_bandwidth = 1e9
    tm.compression_ratio_prior = 4.0
    # a painfully slow link: the priced rule must choose zlib per edge
    slow = hetero_spec((2, 1), link_bw=1e4, latency=1e-6)
    plan = _plan(_synth(48), tile=16, spec=slow)
    ref = LocalExecutor().execute(plan)
    ex = ClusterExecutor(timemodel=tm)
    out = ex.execute(plan)
    assert np.array_equal(ref, out)
    if ex.stats["xfers"] > 0:
        assert ex.stats["xfers_compressed"] > 0
    # fat link, same model: raw wins the inequality
    fat = hetero_spec((2, 1), **FAST_NET)
    plan2 = _plan(_synth(48), tile=16, spec=fat)
    ex2 = ClusterExecutor(timemodel=tm)
    out2 = ex2.execute(plan2)
    assert np.array_equal(LocalExecutor().execute(plan2), out2)
    assert ex2.stats["xfers_compressed"] == 0


def test_engine_and_session_price_cluster_wire_with_their_timemodel():
    """The engine hands its TimeModel to a one-shot cluster run, and a
    cluster session defaults to it too: with fitted compression terms on
    a slow link every cross-node XFER is priced into zlib."""
    from repro.core.session import CMMSession
    tm = analytic_time_model()
    tm.compress_bandwidth = 1e9
    tm.compression_ratio_prior = 4.0
    eng = CMMEngine(hetero_spec((2, 1), link_bw=1e4, latency=1e-6), tm,
                    plan_cache=False)
    A, B = CM.rand(48, 48, seed=0), CM.rand(48, 48, seed=1)
    out = eng.run((A @ B) + A, tile=16, executor="cluster")
    st = eng.last_exec_stats
    assert st["xfers"] > 0 and st["xfers_compressed"] == st["xfers"]
    assert np.array_equal(out, eng.run((A @ B) + A, tile=16))

    with CMMSession(eng, executor="cluster", tile=16) as s:
        assert s._exec.timemodel is tm
        P = s.persist((A @ B) + A)
        got = s.compute(P @ A)
        st = s.stats["last_exec"]
        assert st["xfers"] > 0 and st["xfers_compressed"] == st["xfers"]
    assert np.array_equal(got, eng.run(((A @ B) + A) @ A, tile=16))


# -- broadcast + streaming gather on executors ------------------------------

def test_cluster_broadcast_relays_and_matches():
    spec = hetero_spec((1, 1, 1, 1, 1, 1), **FAST_NET)
    plan = _plan(_fanout_expr(), tile=16, spec=spec)
    ref = LocalExecutor().execute(plan)
    ex = ClusterExecutor(broadcast=True)
    out = ex.execute(plan)
    assert np.array_equal(ref, out)
    ex2 = ClusterExecutor(broadcast=False)
    out2 = ex2.execute(plan)
    assert np.array_equal(ref, out2)
    assert ex2.stats["relay_hops"] == 0


def test_cluster_fanout_relays_without_chaos():
    """With no churn, a fan-out edge still drains through relays: landed
    copies serve later consumers (relay hops), bitwise, every lease
    closed.  Two workers a node keep several copies of a tile in flight
    at once, so holders reach their relay cap."""
    spec = hetero_spec((2, 2, 2, 2, 2, 2), **FAST_NET)
    plan = _plan(_fanout_expr(), tile=16, spec=spec)
    ref = LocalExecutor().execute(plan)
    ex = ClusterExecutor(broadcast=True)
    out = ex.execute(plan)
    assert np.array_equal(ref, out)
    assert ex.stats["deaths"] == 0
    assert ex.stats["relay_hops"] > 0
    assert ex.stats["stale_leases"] == 0


@pytest.mark.parametrize("tm", [None, TM], ids=["own-tm", "engine-tm"])
def test_cluster_stream_gather_bit_identical(tm):
    """Streamed and barrier gathers agree bitwise, with the executor's own
    model or one handed in."""
    spec = hetero_spec((2, 2), **FAST_NET)
    plan = _plan(_synth(96), tile=16, spec=spec)
    ref = LocalExecutor().execute(plan)
    on = ClusterExecutor(timemodel=tm, stream_gather=True)
    out_on = on.execute(plan)
    off = ClusterExecutor(timemodel=tm, stream_gather=False)
    out_off = off.execute(plan)
    assert np.array_equal(ref, out_on) and np.array_equal(ref, out_off)
    assert on.stats["gather_streamed_tiles"] > 0
    assert off.stats["gather_streamed_tiles"] == 0
    assert on.stats["gather_first_tile_s"] is not None
    assert on.stats["gather_full_result_s"] >= on.stats["gather_first_tile_s"]


# -- chaos: leases and relays under churn -----------------------------------

@pytest.mark.chaos
def test_consumer_death_mid_copy_releases_source_leases():
    """Kill a throttled consumer while leased XFERs are in flight to it
    (bounded arenas force the lease path; the throttle keeps each copy
    in its hold-ack -> copy-land window).  The master must release the
    dead consumer's source pins — the run then completes bit-identically
    on the survivors with every lease closed.  Regression: the pins used
    to leak, leaving source tiles unevictable on bounded arenas."""
    n = 96
    ws = 4 * n * n * 8
    spec = hetero_spec((2, 2, 1, 1), mem_bytes=float(ws), **FAST_NET)
    plan = _plan(_fanout_expr(n), tile=16, spec=spec)
    ref = LocalExecutor().execute(plan)
    ex = ClusterExecutor(
        timemodel=TM,
        membership=MembershipConfig(heartbeat_interval_s=0.05),
        chaos=[ChaosEvent(after_done=0, throttle_node=3,
                          throttle_seconds=0.4),
               ChaosEvent(after_done=10, kill_node=3)])
    out = ex.execute(plan)
    assert np.array_equal(ref, out)
    assert ex.stats["deaths"] == 1
    assert ex.stats["leases"] > 0, "bounded arenas must take leases"
    assert ex.stats["stale_leases"] == 0, "a dead consumer stranded a pin"
    assert ex.stats["stale_retry_entries"] == 0


@pytest.mark.chaos
def test_relay_node_death_rebuilds_broadcast_tree():
    """Kill a node mid-run on a fan-out-heavy workload with relaying on:
    consumers that were routed through the dead relay must re-route to a
    surviving holder (or the resurrected producer) bit-identically."""
    spec = hetero_spec((1, 1, 1, 1, 1, 1), **FAST_NET)
    plan = _plan(_fanout_expr(), tile=16, spec=spec)
    ref = LocalExecutor().execute(plan)
    ex = ClusterExecutor(
        timemodel=TM, broadcast=True,
        chaos=[ChaosEvent(after_done=14, kill_node=4)])
    out = ex.execute(plan)
    assert np.array_equal(ref, out)
    assert ex.stats["deaths"] == 1
    assert ex.stats["stale_leases"] == 0


@pytest.mark.chaos
def test_compressed_xfers_survive_chaos_drops():
    """Poisoned compressed transfers must retry through a fresh lease
    (release old pin, re-pack, re-copy) and land bit-identically — and
    the recovered edges' retry budgets must reset on success."""
    spec = hetero_spec((2, 2, 1), **FAST_NET)
    plan = _plan(_synth(), tile=16, spec=spec)
    ref = LocalExecutor().execute(plan)
    ex = ClusterExecutor(
        timemodel=TM, wire_codec="zlib",
        chaos=[ChaosEvent(after_done=4, drop_xfer=3)])
    out = ex.execute(plan)
    assert np.array_equal(ref, out)
    assert ex.stats["xfer_retries"] >= 1
    assert ex.stats["stale_leases"] == 0
    assert ex.stats["stale_retry_entries"] == 0, \
        "successful retries must clear their failure counts"


@pytest.mark.chaos
def test_retry_budget_resets_after_successful_retry():
    """With a retry budget of 1 per edge, more dropped XFERs than the
    budget only survive if each successful recovery resets its edge's
    count — the stale-count bug failed this run spuriously."""
    spec = hetero_spec((2, 2, 1), **FAST_NET)
    plan = _plan(_synth(), tile=16, spec=spec)
    ref = LocalExecutor().execute(plan)
    ex = ClusterExecutor(
        timemodel=TM,
        membership=MembershipConfig(xfer_max_retries=2),
        chaos=[ChaosEvent(after_done=2, drop_xfer=2),
               ChaosEvent(after_done=8, drop_xfer=2)])
    out = ex.execute(plan)
    assert np.array_equal(ref, out)
    assert ex.stats["stale_retry_entries"] == 0
