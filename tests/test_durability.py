"""Durable sessions: tile checkpoint store, crash recovery, resume.

The oracle for every resume test is the bit-identity contract: whatever
mix of reload-from-disk and recompute-from-lineage the restore chooses,
the resumed session's matrices are bitwise equal to the uninterrupted
run — including after SIGKILL of the master and every worker
mid-``compute()`` (the ``chaos``-marked subprocess test).
"""
import glob
import os
import signal
import subprocess
import sys

import numpy as np
import pytest

from repro.core.engine import CMMEngine
from repro.core.lazy import ClusteredMatrix as CM
from repro.core.machine import hetero_spec, local_spec
from repro.core.session import (CMMSession, ResidentTilesLost,
                                SessionUnrecoverable)
from repro.core.simulator import (predict_checkpoint_overhead,
                                  predict_recovery_cost,
                                  predict_reload_seconds)
from repro.core.timemodel import TimeModel, analytic_time_model
from repro.runtime.durability import (ShardCorrupt, TileCheckpointStore,
                                      pickle_expr, unpickle_expr)

TM = analytic_time_model()
SPEC3 = hetero_spec((3, 2, 1), link_bw=1e12, latency=1e-6)
SPEC2 = hetero_spec((2, 2), link_bw=1e12, latency=1e-6)


def _engine(spec=None, **kw):
    return CMMEngine(spec or local_spec(1), TM, **kw)


def _fresh(hid, arr, tile=(2, 2), lineage=None):
    """A minimal fresh-entry dict for TileCheckpointStore.save."""
    from repro.core.tiling import grid_of, tile_slices
    gm, gn = grid_of(arr.shape, tile)
    rows, cols = tile_slices(arr.shape[0], tile[0]), \
        tile_slices(arr.shape[1], tile[1])
    tiles = {(i, j): arr[rows[i][0]:rows[i][1], cols[j][0]:cols[j][1]]
             for i in range(gm) for j in range(gn)}
    return {"shape": arr.shape, "dtype": arr.dtype, "tile": tile,
            "grid": (gm, gn), "name": f"h{hid}", "lineage": lineage,
            "tiles": tiles}


# -- store unit tests --------------------------------------------------------

def test_store_roundtrip(tmp_path):
    st = TileCheckpointStore(str(tmp_path))
    a = np.arange(16, dtype=np.float64).reshape(4, 4)
    man = st.save(1, {7: _fresh(7, a)})
    assert st.snaps() == [1]
    got = np.empty_like(a)
    for i in range(2):
        for j in range(2):
            got[2 * i:2 * i + 2, 2 * j:2 * j + 2] = st.load_tile(man, 7, i, j)
    np.testing.assert_array_equal(got, a)
    assert st.handle_bytes(man, 7) == a.nbytes


def test_store_incremental_carry(tmp_path):
    """A carried handle's shards stay in the older snap_ directory —
    nothing is rewritten, the new manifest references across."""
    st = TileCheckpointStore(str(tmp_path))
    a = np.ones((4, 4))
    b = np.full((4, 4), 2.0)
    st.save(1, {1: _fresh(1, a)})
    man2 = st.save(2, {2: _fresh(2, b)}, carry=[1])
    assert man2["handles"]["1"]["tiles"]["0,0"]["path"].startswith("snap_1/")
    assert man2["handles"]["2"]["tiles"]["0,0"]["path"].startswith("snap_2/")
    np.testing.assert_array_equal(
        st.load_tile(man2, 1, 0, 0), np.ones((2, 2)))
    with pytest.raises(KeyError):
        st.save(3, {}, carry=[99])


def test_store_rotate_keeps_referenced_dirs(tmp_path):
    st = TileCheckpointStore(str(tmp_path))
    st.save(1, {1: _fresh(1, np.ones((4, 4)))})
    for s in (2, 3, 4, 5):
        st.save(s, {}, carry=[1])       # all carry from snap_1
    st.rotate(keep=2)
    assert 1 in st.snaps()              # still referenced by kept manifests
    assert 2 not in st.snaps() and 3 not in st.snaps()
    man = st.latest_intact()
    assert man["step"] == 5
    np.testing.assert_array_equal(st.load_tile(man, 1, 0, 0), np.ones((2, 2)))


def test_store_tmp_dir_invisible_and_fallback(tmp_path):
    """A crash mid-save leaves a .tmp dir readers never look at; a
    manifest referencing missing shards is not intact either way."""
    st = TileCheckpointStore(str(tmp_path))
    st.save(1, {1: _fresh(1, np.ones((4, 4)))})
    os.makedirs(tmp_path / "snap_2.tmp")
    (tmp_path / "snap_2.tmp" / "manifest.json").write_text("{trunc")
    assert st.snaps() == [1]
    # a published-looking snap with a torn shard set: skipped by intact
    st.save(3, {2: _fresh(2, np.zeros((4, 4)))}, carry=[1])
    os.unlink(glob.glob(str(tmp_path / "snap_3" / "h2_*.npy"))[0])
    assert st.latest_intact()["step"] == 1


def test_store_crc_detects_corruption(tmp_path):
    st = TileCheckpointStore(str(tmp_path))
    man = st.save(1, {1: _fresh(1, np.ones((4, 4)))})
    path = st.corrupt_shard(1)
    assert os.path.exists(path)
    with pytest.raises(ShardCorrupt):
        st.load_tile(man, 1, 0, 0)


def test_store_async_write_error_is_swallowed(tmp_path, monkeypatch):
    """A failed async write never raises into the compute path: it lands
    in write_errors and the previous snapshot stays the newest intact."""
    st = TileCheckpointStore(str(tmp_path))
    st.save(1, {1: _fresh(1, np.ones((4, 4)))})
    monkeypatch.setattr(np, "save",
                        lambda *a, **k: (_ for _ in ()).throw(OSError("disk")))
    st.save_async(2, {2: _fresh(2, np.zeros((4, 4)))})
    st.wait()
    assert st.write_errors
    assert st.latest_intact()["step"] == 1


def test_lineage_pickle_helpers_roundtrip():
    expr = CM.rand(8, 8, seed=0) @ CM.rand(8, 8, seed=1)
    back = unpickle_expr(pickle_expr(expr))
    assert back.shape == expr.shape and back.op is expr.op


# -- session durability (fast, local backend) --------------------------------

def test_durable_session_resume_bitwise(tmp_path):
    """Persist a chain, flush, resume in a fresh session: bit-identical
    under every restore policy."""
    with CMMSession(_engine(), tile=16,
                    checkpoint_dir=str(tmp_path)) as s:
        A = s.persist(CM.rand(48, 48, seed=0), name="A")
        u = s.persist(CM.rand(48, 1, seed=1), name="u")
        for i in range(3):
            u = s.persist(A @ u, name=f"u{i}")
        ref = u.to_numpy()
        s.flush_checkpoints()
    for policy in ("price", "reload", "recompute"):
        with CMMSession.resume(str(tmp_path), _engine(), tile=16,
                               policy=policy) as s2:
            got = s2.resident("u2").to_numpy()
            assert np.array_equal(got, ref), policy
            rep = s2.stats["resume"]
            assert sorted(rep["reloaded"] + rep["recomputed"]) == \
                sorted(int(h) for h in rep["reloaded"] + rep["recomputed"])
            if policy == "reload":
                assert not rep["recomputed"]
            if policy == "recompute":
                assert not rep["reloaded"]


def test_resumed_session_continues_computing(tmp_path):
    """A resumed session is a full session: the restored handles re-enter
    new expressions and further persists checkpoint again."""
    with CMMSession(_engine(), tile=16,
                    checkpoint_dir=str(tmp_path)) as s:
        s.persist(CM.rand(32, 32, seed=0), name="P")
        s.flush_checkpoints()
    with CMMSession.resume(str(tmp_path), _engine(), tile=16) as s2:
        P = s2.resident("P")
        Q = s2.persist(P @ P, name="Q")
        ref = Q.to_numpy()
        s2.flush_checkpoints()
    with CMMSession.resume(str(tmp_path), _engine(), tile=16) as s3:
        assert np.array_equal(s3.resident("Q").to_numpy(), ref)


def test_freed_handle_does_not_resurrect(tmp_path):
    with CMMSession(_engine(), tile=16,
                    checkpoint_dir=str(tmp_path)) as s:
        P = s.persist(CM.rand(32, 32, seed=0), name="P")
        s.persist(CM.rand(32, 32, seed=1), name="Q")
        P.free()                       # publishes a snapshot without P
        s.flush_checkpoints()
    with CMMSession.resume(str(tmp_path), _engine(), tile=16) as s2:
        with pytest.raises(KeyError):
            s2.resident("P")
        s2.resident("Q")


def test_corrupt_shard_degrades_to_lineage_recompute(tmp_path):
    with CMMSession(_engine(), tile=16,
                    checkpoint_dir=str(tmp_path)) as s:
        A = s.persist(CM.rand(48, 48, seed=0), name="A")
        u = s.persist(A @ CM.rand(48, 1, seed=1), name="u0")
        ref = u.to_numpy()
        hid = u.handle.hid
        s.flush_checkpoints()
    TileCheckpointStore(str(tmp_path)).corrupt_shard(hid)
    with CMMSession.resume(str(tmp_path), _engine(), tile=16,
                           policy="reload") as s2:
        rep = s2.stats["resume"]
        assert rep["corrupt_shards"] >= 1
        assert hid in rep["recomputed"]         # degraded, not failed
        assert np.array_equal(s2.resident("u0").to_numpy(), ref)


def test_corrupt_shard_without_lineage_is_unrecoverable(tmp_path):
    st = TileCheckpointStore(str(tmp_path))
    st.save(1, {1: _fresh(1, np.ones((4, 4)), lineage=None)})
    st.corrupt_shard(1)
    with pytest.raises(SessionUnrecoverable) as ei:
        CMMSession.resume(str(tmp_path), _engine(), tile=2)
    assert ei.value.hids == (1,)


def test_resume_without_checkpoint_raises(tmp_path):
    with pytest.raises(RuntimeError, match="no intact checkpoint"):
        CMMSession.resume(str(tmp_path), _engine(), tile=16)
    with pytest.raises(ValueError, match="policy"):
        CMMSession.resume(str(tmp_path), _engine(), tile=16, policy="bogus")


def test_resume_falls_back_to_prior_intact_snapshot(tmp_path):
    """A torn newest snapshot (crash mid-save) is skipped: resume restores
    the previous intact one and the session continues from there."""
    with CMMSession(_engine(), tile=16,
                    checkpoint_dir=str(tmp_path)) as s:
        s.persist(CM.rand(32, 32, seed=0), name="P")
        s.flush_checkpoints()
        ref = s.resident("P").to_numpy()
        s.persist(CM.rand(32, 32, seed=1), name="R")
        s.flush_checkpoints()
    st = TileCheckpointStore(str(tmp_path))
    newest = st.snaps()[-1]
    for f in glob.glob(str(tmp_path / f"snap_{newest}" / "*.npy")):
        os.unlink(f)                   # tear the newest snapshot
    with CMMSession.resume(str(tmp_path), _engine(), tile=16) as s2:
        assert s2.stats["resume"]["step"] < newest
        assert np.array_equal(s2.resident("P").to_numpy(), ref)


def test_checkpoint_every_batches_snapshots(tmp_path):
    with CMMSession(_engine(), tile=16, checkpoint_dir=str(tmp_path),
                    checkpoint_every=3) as s:
        for i in range(3):
            s.persist(CM.rand(16, 16, seed=i), name=f"m{i}")
        s.flush_checkpoints()
    st = TileCheckpointStore(str(tmp_path))
    # one snapshot from the batch of 3 persists (+ the explicit flush)
    assert len(st.snaps()) <= 2
    man = st.latest_intact()
    assert len(man["handles"]) == 3


def test_bounded_retry_raises_session_unrecoverable(monkeypatch):
    """Satellite: the lost-tiles retry loop is bounded — a loss the
    executor can never repair surfaces as SessionUnrecoverable carrying
    the lost hids, after max_retries + 1 attempts with backoff."""
    s = CMMSession(_engine(), tile=16, max_retries=2, retry_backoff_s=0.0)
    try:
        attempts = []

        def boom(*a, **k):
            attempts.append(1)
            raise ResidentTilesLost((41,), "injected loss")

        monkeypatch.setattr(s.engine, "execute_plan", boom)
        with pytest.raises(SessionUnrecoverable) as ei:
            s.compute(CM.rand(16, 16, seed=0))
        assert ei.value.hids == (41,)
        assert isinstance(ei.value.__cause__, ResidentTilesLost)
        assert len(attempts) == 3          # max_retries + 1
    finally:
        monkeypatch.undo()
        s.close()


# -- pricing: TimeModel fields and simulator legs ----------------------------

def test_timemodel_durability_fields_roundtrip():
    tm = TimeModel.from_json(TM.to_json())
    tm.spill_read_bandwidth = 123.0
    tm.checkpoint_write_overhead = 0.25
    rt = TimeModel.from_json(tm.to_json())
    assert rt.spill_read_bandwidth == 123.0
    assert rt.checkpoint_write_overhead == 0.25
    # old serialized models (without the fields) still load
    import json
    d = json.loads(TM.to_json())
    d.pop("spill_read_bandwidth"), d.pop("checkpoint_write_overhead")
    old = TimeModel.from_json(json.dumps(d))
    assert old.spill_read_bandwidth > 0


def test_predict_reload_and_overhead():
    tm = TimeModel.from_json(TM.to_json())
    tm.spill_read_bandwidth = 1e6
    assert predict_reload_seconds(2e6, tm) == pytest.approx(2.0)
    assert predict_checkpoint_overhead(2e6, tm) == \
        pytest.approx(2.0 + tm.checkpoint_write_overhead)


def test_predict_recovery_cost_caps_at_reload(tmp_path):
    """With checkpointed bytes available the recovery estimate is capped
    by the reload leg — recompute is only charged when it is cheaper."""
    eng = _engine(hetero_spec((2, 2), link_bw=1e9, latency=1e-4))
    plan = eng.plan_many([CM.rand(96, 96, seed=0) @ CM.rand(96, 96, seed=1)],
                         tile=32)
    g, sched, spec = plan.program.graph, plan.schedule, eng.spec
    tm = TimeModel.from_json(TM.to_json())
    slow = predict_recovery_cost(g, sched, spec, tm, 1)
    tm.spill_read_bandwidth = 1e30          # reload is ~free
    fast = predict_recovery_cost(g, sched, spec, tm, 1,
                                 checkpoint_bytes=96 * 96 * 8)
    assert fast <= slow
    assert fast >= tm.respawn_overhead


# -- chaos tier: cluster backends, full-cluster kill -------------------------

_CHILD = r"""
import sys
from repro.core.session import CMMSession
from repro.core.lazy import ClusteredMatrix as CM
from repro.core.engine import CMMEngine
from repro.core.timemodel import analytic_time_model
from repro.core.machine import hetero_spec
from repro.exec.elastic import ChaosEvent

d = sys.argv[1]
spec = hetero_spec((3, 2, 1), link_bw=1e12, latency=1e-6)
s = CMMSession(CMMEngine(spec, analytic_time_model()), executor="cluster",
               tile=16, checkpoint_dir=d)
A = s.persist(CM.rand(48, 48, seed=0), name="A")
u = s.persist(CM.rand(48, 1, seed=1), name="u")
u = s.persist(A @ u, name="u0")
u = s.persist(A @ u, name="u1")
s.flush_checkpoints()
print("flushed", flush=True)
s._exec.chaos = [ChaosEvent(after_done=2, kill_master=True)]
s.persist(A @ u, name="u2")     # SIGKILLed mid-compute, never returns
print("UNREACHABLE", flush=True)
"""


@pytest.mark.chaos
def test_full_cluster_sigkill_then_resume_bitwise(tmp_path):
    """Acceptance oracle: SIGKILL master + every worker mid-compute()
    (ChaosEvent(kill_master=True)), resume() on a DIFFERENT ClusterSpec,
    continue the chain — bitwise equal to the uninterrupted run."""
    child = tmp_path / "child.py"
    child.write_text(_CHILD)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    p = subprocess.Popen([sys.executable, str(child), str(tmp_path)],
                         env=env, stdout=subprocess.PIPE,
                         stderr=subprocess.PIPE, text=True)
    try:
        stdout, stderr = p.communicate(timeout=240)
    finally:
        if p.poll() is None:            # pragma: no cover — timed out
            p.kill()
            p.communicate()
    assert p.returncode == -signal.SIGKILL, (p.returncode, stderr[-800:])
    assert "flushed" in stdout
    assert "UNREACHABLE" not in stdout
    # reap shared memory the killed cluster may have stranded: only its
    # own segments (named after the killed master's pid) — other
    # processes' clusters may be running alongside
    for f in glob.glob(f"/dev/shm/cmm{p.pid}_*"):
        try:
            os.unlink(f)
        except OSError:
            pass

    with CMMSession.resume(str(tmp_path), _engine(SPEC2),
                           executor="cluster", tile=16) as s:
        rep = s.stats["resume"]
        assert sorted(rep["reloaded"] + rep["recomputed"])
        got = s.compute(s.resident("A") @ s.resident("u1"))
    ref = _power_chain_ref(48, 3)
    assert np.array_equal(got, ref)


def _power_chain_ref(n, k):
    P, v = CM.rand(n, n, seed=0), CM.rand(n, 1, seed=1)
    e = v
    for _ in range(k):
        e = P @ e
    return _engine().run(e, tile=16)


@pytest.mark.chaos
def test_elastic_resume_onto_different_spec_bitwise(tmp_path):
    """Durable elastic session on a 3-node cluster, resumed onto a 2-node
    cluster: tiles re-home into the new arenas, bytes unchanged."""
    with CMMSession(_engine(SPEC3), executor="cluster", tile=16,
                    checkpoint_dir=str(tmp_path)) as s:
        A = s.persist(CM.rand(48, 48, seed=0), name="A")
        u = s.persist(CM.rand(48, 1, seed=1), name="u")
        u = s.persist(A @ u, name="u0")
        ref = u.to_numpy()
        s.flush_checkpoints()
    with CMMSession.resume(str(tmp_path), _engine(SPEC2),
                           executor="cluster", tile=16,
                           policy="reload") as s2:
        h = s2.resident("u0").handle
        assert set(h.home.values()) <= set(SPEC2.alive_nodes())
        assert np.array_equal(s2.resident("u0").to_numpy(), ref)


@pytest.mark.chaos
def test_chaos_corrupt_tile_degrades_on_resume(tmp_path):
    """ChaosEvent(corrupt_tile=hid) flips a byte in the newest on-disk
    shard mid-run; the next resume detects the CRC mismatch and degrades
    that handle to lineage recompute — no wrong bytes survive."""
    from repro.exec.elastic import ChaosEvent
    with CMMSession(_engine(SPEC2), executor="cluster", tile=16,
                    checkpoint_dir=str(tmp_path)) as s:
        A = s.persist(CM.rand(48, 48, seed=0), name="A")
        u = s.persist(A @ CM.rand(48, 1, seed=1), name="u0")
        ref = u.to_numpy()
        s.flush_checkpoints()
        s._exec.chaos = [ChaosEvent(after_done=1,
                                    corrupt_tile=u.handle.hid)]
        s.compute(A + A)               # fires the corruption mid-run
        s._exec.chaos = ()
        hid = u.handle.hid
    with CMMSession.resume(str(tmp_path), _engine(), tile=16,
                           policy="reload") as s2:
        rep = s2.stats["resume"]
        assert rep["corrupt_shards"] >= 1 and hid in rep["recomputed"]
        assert np.array_equal(s2.resident("u0").to_numpy(), ref)


def test_chaos_corrupt_tile_requires_durable_session():
    from repro.exec.elastic import ChaosEvent
    with CMMSession(_engine(SPEC2), executor="cluster", tile=16) as s:
        s._exec.chaos = [ChaosEvent(after_done=0, corrupt_tile=1)]
        with pytest.raises(ValueError, match="durable session"):
            s.compute(CM.rand(32, 32, seed=0))
        s._exec.chaos = ()


@pytest.mark.chaos
def test_chaos_dropped_xfer_retries_and_stays_bitwise():
    """ChaosEvent(drop_xfer=N) poisons the next N transfer dispatches;
    the hardened path retries with backoff (possibly from another
    holder) and the result is still bitwise correct — no hang, no wrong
    bytes."""
    from repro.exec.elastic import ChaosEvent
    with CMMSession(_engine(SPEC3), executor="cluster", tile=16) as s:
        s._exec.chaos = [ChaosEvent(after_done=1, drop_xfer=2)]
        A = s.persist(CM.rand(96, 96, seed=0), name="A")
        B = s.persist(CM.rand(96, 96, seed=2), name="B")
        got = s.compute(A @ B)
        s._exec.chaos = ()
        st = s.stats["last_exec"]
        assert st["chaos_dropped_xfers"] >= 1
        assert st["xfer_retries"] >= 1
    ref = _engine().run(CM.rand(96, 96, seed=0) @ CM.rand(96, 96, seed=2),
                        tile=16)
    assert np.array_equal(got, ref)


@pytest.mark.chaos
def test_durable_session_survives_node_death_and_checkpoints(tmp_path):
    """Node death inside a durable session: lineage recompute re-homes
    the handle AND the next snapshot captures the re-homed tiles, so a
    later resume sees the post-recovery state."""
    from repro.exec.elastic import ChaosEvent
    with CMMSession(_engine(SPEC2), executor="cluster", tile=16,
                    checkpoint_dir=str(tmp_path)) as s:
        A = s.persist(CM.rand(96, 96, seed=0) @ CM.rand(96, 96, seed=1),
                      name="A")
        s.flush_checkpoints()
        s._exec.chaos = (ChaosEvent(after_done=3, kill_node=1),)
        out = s.compute(A @ A)
        s._exec.chaos = ()
        ref_handle = A.to_numpy()
        s.flush_checkpoints()
    a = CM.rand(96, 96, seed=0) @ CM.rand(96, 96, seed=1)
    assert np.array_equal(out, _engine(SPEC2).run(a @ a, tile=16))
    with CMMSession.resume(str(tmp_path), _engine(), tile=16) as s2:
        assert np.array_equal(s2.resident("A").to_numpy(), ref_handle)
