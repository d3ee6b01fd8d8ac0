"""Device-resident session tiles on the ``batched-pallas`` executor.

A session over the Pallas wave backend keeps each persisted tile in HBM
as a ``jax.Array`` wherever the device holds its dtype exactly, and a
device ADDMUL group reads it there: stacked on the device, never staged
on the host or uploaded again.  Every other reader pulls it to the host,
counted in ``d2h_bytes``.  The kernel gets the same float32 values as on
the host path, so a persisted step is bitwise the one-shot program of
that step.  On the CPU the Pallas kernels run in interpret mode.
"""
import gc

import jax
import numpy as np
import pytest

from repro.core import ClusteredMatrix as CM, CMMEngine, analytic_time_model
from repro.core.session import CMMSession

TM = analytic_time_model()
N, TILE, STEPS = 256, 64, 3
NT = N // TILE
F32_TOL = 1e-4                       # TESTING.md: f32 re-associated bound


def _arrays(dtype=np.float32, seed=0):
    """Standard normal entries over sqrt(N): products stay of order one."""
    rng = np.random.default_rng(seed)
    return {k: (rng.standard_normal(shape) / np.sqrt(N)).astype(dtype)
            for k, shape in (("P", (N, N)), ("Q", (N, N)), ("R", (N, N)),
                             ("X", (N, N)), ("u", (N, 1)))}


def _on_device(t) -> bool:
    return isinstance(t, jax.Array)


def _session_tiles(s, rm):
    return [s._tiles[(rm.handle.hid, i, j)] for (i, j) in rm.handle.tiles()]


def _chain(dtype):
    """P persisted, then u and STEPS persisted steps ``u <- P @ u``; each
    step's output, stats and spans, and the one-shot program of that step
    on the same arrays (the host path)."""
    arrs = _arrays(dtype)
    eng = CMMEngine(timemodel=TM)
    with CMMSession(eng, executor="batched-pallas", tile=TILE) as s:
        P = s.persist(CM.from_array(arrs["P"], name="P"))
        p_tiles = _session_tiles(s, P)
        u = s.persist(CM.from_array(arrs["u"], name="u"))
        steps = []
        for _ in range(STEPS):
            prev = u.to_numpy()
            nxt = s.persist(P @ u)
            steps.append({"stats": dict(eng.last_exec_stats),
                          "spans": list(eng.last_spans),
                          "out": nxt.to_numpy(), "prev": prev,
                          "device_bytes": s.resident_device_bytes})
            u.free()
            u = nxt
        for st in steps:
            st["oneshot"] = CMMEngine(timemodel=TM).run(
                CM.from_array(arrs["P"]) @ CM.from_array(st["prev"]),
                tile=TILE, executor="batched-pallas")
    return arrs, p_tiles, steps


@pytest.fixture(scope="module")
def f32_chain():
    return _chain(np.float32)


def test_each_step_is_bitwise_its_one_shot_program(f32_chain):
    _, _, steps = f32_chain
    for st in steps:
        np.testing.assert_array_equal(st["out"], st["oneshot"])


def test_the_chain_matches_eager(f32_chain):
    arrs, _, steps = f32_chain
    e = CM.from_array(arrs["u"])
    for _ in range(STEPS):
        e = CM.from_array(arrs["P"]) @ e
    np.testing.assert_allclose(steps[-1]["out"], e.eager(),
                               rtol=F32_TOL, atol=F32_TOL)


def test_persisted_tiles_live_in_device_memory(f32_chain):
    _, p_tiles, steps = f32_chain
    assert len(p_tiles) == NT ** 2 and all(_on_device(t) for t in p_tiles)
    # P and two vectors (the step's input u and its output) at once
    assert all(st["device_bytes"] == (N * N + 2 * N) * 4 for st in steps)


def test_step_counters_match_the_formula(f32_chain):
    """A step runs NT k-groups of NT mat-vec tasks: only C goes up and
    comes down; P's and u's tiles are read where they lie; the step's
    result goes up once at retention."""
    _, _, steps = f32_chain
    c_bytes = NT ** 2 * TILE * 4
    for st in steps:
        s = st["stats"]
        assert s["h2d_bytes"] == c_bytes + N * 4
        assert s["d2h_bytes"] == c_bytes
        assert s["device_operand_tiles"] == 2 * NT ** 2
        assert s["device_operand_bytes"] == NT ** 2 * (TILE ** 2 + TILE) * 4
        assert s["staging_allocs"] + s["staging_reuses"] == 0
        gathers = [sp for sp in st["spans"] if sp.cat == "GATHER"]
        assert len(gathers) == NT
        assert sum(sp.args["device_operand_tiles"] for sp in gathers) == \
            s["device_operand_tiles"]
        assert sum(sp.args["nbytes"] for sp in st["spans"]
                   if sp.cat == "H2D") == c_bytes


def test_one_shot_programs_read_no_device_tiles():
    """A one-shot program has no RESIDENT tiles: every operand takes the
    host path and the H2D spans hold every byte that went up."""
    eng = CMMEngine(timemodel=TM)
    a = _arrays()
    X, Ct = CM.from_array(a["X"]), CM.from_array(a["Q"])
    eng.run(((X @ Ct) - 0.5).relu().T @ X, tile=TILE,
            executor="batched-pallas", trace=True)
    st = eng.last_exec_stats
    assert (st["device_operand_tiles"], st["device_operand_bytes"]) == (0, 0)
    assert st["h2d_bytes"] == sum(s.args["nbytes"] for s in eng.last_spans
                                  if s.cat == "H2D") > 0
    assert all(s.args["device_operand_tiles"] == 0
               for s in eng.last_spans if s.cat == "GATHER")


M = N * N * 4                        # bytes of one persisted N x N matrix

#: (name, roots over the leaves, bytes pulled from the device by host
#: readers, bytes of host tiles put up outside the H2D spans, device tiles
#: read in place)
READERS = [
    ("elementwise", lambda L: [L["P"] + L["Q"]], 2 * M, 0, 0),
    ("transpose", lambda L: [L["P"].T], M, 0, 0),
    ("fused", lambda L: [(L["P"] * 2.0 - L["Q"]).relu()], 2 * M, 0, 0),
    ("gather", lambda L: [L["P"]], M, 0, 0),
    # A and B at every k-step, R's tiles as the epilogue's extra operand
    ("epilogued_gemm",
     lambda L: [((L["P"] @ L["Q"]).hadamard(L["R"]) - 0.5).relu()], 0, 0,
     2 * NT ** 3 + NT ** 2),
    # each group's A operand holds P's device tiles and X's host tiles
    ("mixed_operand", lambda L: [L["P"] @ L["u"], L["X"] @ L["u"]], 0, M,
     NT ** 2),
]


@pytest.mark.parametrize("name,build,pulls,ups,in_place", READERS,
                         ids=[r[0] for r in READERS])
def test_every_reader_of_device_tiles_gets_eagers_answer(name, build, pulls,
                                                         ups, in_place):
    arrs = _arrays()
    eng = CMMEngine(timemodel=TM)
    with CMMSession(eng, executor="batched-pallas", tile=TILE) as s:
        leaves = {k: s.persist(CM.from_array(arrs[k], name=k))
                  for k in ("P", "Q", "R")}
        assert all(_on_device(t) for t in _session_tiles(s, leaves["P"]))
        leaves.update({k: CM.from_array(arrs[k]) for k in ("X", "u")})
        outs = s.compute_many(build(leaves))
        st, spans = eng.last_exec_stats, eng.last_spans
    refs = build({k: CM.from_array(a) for k, a in arrs.items()})
    for out, ref in zip(outs, refs, strict=True):
        np.testing.assert_allclose(out, ref.eager(), rtol=F32_TOL,
                                   atol=F32_TOL)
    assert st["d2h_bytes"] == pulls + sum(sp.args["nbytes"] for sp in spans
                                          if sp.cat == "D2H")
    assert st["h2d_bytes"] == ups + sum(sp.args["nbytes"] for sp in spans
                                        if sp.cat == "H2D")
    assert st["device_operand_tiles"] == in_place


def test_float64_sessions_keep_host_tiles_and_todays_output():
    arrs, p_tiles, steps = _chain(np.float64)
    assert all(isinstance(t, np.ndarray) for t in p_tiles)
    for st in steps:
        assert st["device_bytes"] == 0
        assert st["stats"]["device_operand_tiles"] == 0
        assert st["out"].dtype == np.float64
        np.testing.assert_array_equal(st["out"], st["oneshot"])


def test_mixed_precision_casts_device_tiles_on_the_device():
    """``precision="mixed"`` stores an epilogued GEMM as bfloat16, which the
    device holds exactly; a later product casts those tiles to float32 on
    the device, bitwise as the host path casts them."""
    arrs = _arrays()
    eng = CMMEngine(timemodel=TM)
    with CMMSession(eng, executor="batched-pallas", tile=TILE,
                    precision="mixed") as s:
        P, Q = (s.persist(CM.from_array(arrs[k])) for k in ("P", "Q"))
        E = s.persist((P @ Q - 0.5).relu())
        e_tiles = _session_tiles(s, E)
        got = s.compute(E @ CM.from_array(arrs["u"]))
        st = dict(eng.last_exec_stats)
        e_host = E.to_numpy()
    assert all(_on_device(t) and t.dtype.name == "bfloat16"
               for t in e_tiles)
    assert st["device_operand_tiles"] == NT ** 2
    want = CMMEngine(timemodel=TM).run(
        CM.from_array(e_host) @ CM.from_array(arrs["u"]), tile=TILE,
        executor="batched-pallas", precision="mixed")
    np.testing.assert_array_equal(got, want)


def test_checkpoint_then_resume_round_trips_bitwise(tmp_path):
    arrs = _arrays()
    eng = CMMEngine(timemodel=TM)
    with CMMSession(eng, executor="batched-pallas", tile=TILE,
                    checkpoint_dir=str(tmp_path)) as s:
        P = s.persist(CM.from_array(arrs["P"]), name="P")
        v = s.persist(P @ CM.from_array(arrs["u"]), name="v")
        s.flush_checkpoints()
        want = {"P": P.to_numpy(), "v": v.to_numpy()}
    r = CMMSession.resume(str(tmp_path), CMMEngine(timemodel=TM),
                          executor="batched-pallas", tile=TILE,
                          policy="reload")
    try:
        for k, a in want.items():
            h = r.resident(k)
            assert all(_on_device(t) for t in _session_tiles(r, h))
            np.testing.assert_array_equal(h.to_numpy(), a)
    finally:
        r.close()


def _tile_arrays():
    gc.collect()
    return [a for a in jax.live_arrays()
            if a.shape in ((TILE, TILE), (TILE, 1))]


def test_free_and_close_release_device_memory():
    before = len(_tile_arrays())
    arrs = _arrays()
    s = CMMSession(CMMEngine(timemodel=TM), executor="batched-pallas",
                   tile=TILE)
    P = s.persist(CM.from_array(arrs["P"]))
    u = s.persist(CM.from_array(arrs["u"]))
    v = s.persist(P @ u)
    assert s.resident_device_bytes == M + 2 * N * 4
    assert len(_tile_arrays()) == before + NT ** 2 + 2 * NT
    u.free()
    assert s.resident_device_bytes == M + N * 4
    assert len(_tile_arrays()) == before + NT ** 2 + NT
    del u, v
    audit = s.close()
    assert audit["local_tiles_leaked"] == audit["handles_leaked"] == 0
    assert s.resident_device_bytes == 0
    assert len(_tile_arrays()) == before
