"""CMMSession: resident distributed tiles across ``compute()`` calls.

The one-shot engine path (``CMMEngine.run``) re-fills every leaf, executes,
gathers the full ndarray to the master and discards all executor state —
so iterative workloads (power iteration, the paper's Markov chain) pay
scatter/gather and re-fill on every step that a resident cluster never
pays.  numpywren keeps intermediates in remote storage between stages and
DistStat.jl's distributed arrays stay resident across calls; this module
brings that to CMM:

* :class:`CMMSession` owns a **long-lived executor** (worker processes and
  their shared-memory arenas survive across runs for the cluster
  backend) and a **residency table** mapping handles to live tiles;
* :meth:`CMMSession.persist` computes an expression and leaves the result
  **tiled in the executor's arenas** (local slab / per-node SharedMemory;
  HBM on the ``batched-pallas`` executor, wherever the device holds the
  dtype exactly), returning a :class:`ResidentMatrix`;
* a ``ResidentMatrix`` re-enters later expressions as a zero-cost,
  location-pinned leaf: tiling maps its tiles one-for-one onto RESIDENT
  tasks (no FILL, no gather), HEFT pins each RESIDENT task to the node
  whose arena holds the tile, and the simulator prices it at ~0 so
  ``auto`` verdicts stay honest;
* :meth:`ResidentMatrix.to_numpy` gathers on demand;
* on the **cluster** backend a resident tile lost to a node death is a
  *recomputable root*: every handle carries the expression (lineage) that
  produced it, and the session transparently re-derives lost handles from
  lineage — numpywren-style recovery extended across runs.

Bit-identity contract: a persisted k-step chain is bitwise identical to
the equivalent one-shot expression on every backend, because each step
executes the same tiled kernels on the same bits and tile movement is
bit-copying (asserted in ``tests/test_session.py``).

**Durable sessions**: constructed with ``checkpoint_dir=...`` the session
snapshots every persisted handle's tiles to disk (asynchronously,
incremental per handle — see ``runtime/durability.py``) together with its
pickled lineage, and :meth:`CMMSession.resume` rebuilds the residency
table from the newest intact snapshot after a full-cluster crash —
SIGKILL of master and every worker mid-``compute()`` included.  Restore
chooses reload-from-disk vs recompute-from-lineage per handle, priced
through the ``TimeModel`` (``spill_read_bandwidth``); a corrupt shard
degrades to lineage recompute instead of resurrecting wrong bytes.
"""
from __future__ import annotations

import itertools
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

from .engine import CMMEngine, Plan
from .lazy import ClusteredMatrix, Op, topo_order_many
from .simulator import predict_reload_seconds
from .tiling import (normalize_tile, grid_of, tile_slices, result_sets_of,
                     to_host)

_hid_counter = itertools.count(1)
_hid_lock = threading.Lock()


def _next_hid() -> int:
    with _hid_lock:
        return next(_hid_counter)


def _ensure_hid_floor(n: int) -> None:
    """Advance the handle-id counter to at least ``n`` — resume() restores
    handles under their checkpointed hids, and new handles made afterwards
    must not collide with them."""
    global _hid_counter
    with _hid_lock:
        cur = next(_hid_counter)
        _hid_counter = itertools.count(max(cur, n))


class ResidentTilesLost(RuntimeError):
    """Raised by the cluster executor when tiles of a resident handle were
    on a node that died (and no live copy remains).  The session catches
    it, re-derives the named handles from lineage and retries the run."""

    def __init__(self, hids: Sequence[int], msg: str = ""):
        self.hids = tuple(sorted(set(hids)))
        super().__init__(msg or f"resident tiles lost for handles "
                                f"{self.hids}")


class SessionUnrecoverable(RuntimeError):
    """The session exhausted its bounded retry budget (``max_retries``)
    re-deriving lost resident tiles, or a restore found a handle with
    neither intact shards nor lineage.  Carries the lost handle ids."""

    def __init__(self, hids: Sequence[int], msg: str = ""):
        self.hids = tuple(sorted(set(hids)))
        super().__init__(msg or f"resident handles {self.hids} are "
                                f"unrecoverable")


@dataclass
class ResidentHandle:
    """Identity + location of one persisted result's tiles.

    Pure data (no session/executor references) so it can cross a process
    boundary if it ever needs to; all tile *storage* lives in the session
    (ndarrays for in-process backends, or ``jax.Array``s in HBM that the
    ``batched-pallas`` executor put there; (node, segment, dtype) triples
    for the multi-process ones).
    """

    hid: int
    shape: Tuple[int, int]
    dtype: "np.dtype"
    tile: Tuple[int, int]
    grid: Tuple[int, int]
    #: (i, j) -> node whose arena holds that tile (0 for in-process)
    home: Dict[Tuple[int, int], int] = field(default_factory=dict)
    name: str = ""
    #: the expression that produced this handle — the recompute lineage.
    #: May itself reference other ResidentMatrix leaves (lineage chains).
    lineage: Optional[ClusteredMatrix] = None
    alive: bool = True
    #: tiles lost to a node death; next use re-derives from lineage
    lost: bool = False

    def tiles(self):
        gm, gn = self.grid
        for i in range(gm):
            for j in range(gn):
                yield (i, j)


class ResidentMatrix(ClusteredMatrix):
    """A persisted result as a lazy leaf: composes with every
    ``ClusteredMatrix`` operator, but its tiles are already resident in
    the session executor's arenas — re-entering an expression costs no
    FILL and no gather."""

    def __init__(self, handle: ResidentHandle, session: "CMMSession",
                 name: str = ""):
        super().__init__(Op.RESIDENT, handle.shape, handle.dtype,
                         payload=handle, name=name or handle.name)
        self._session = session

    @property
    def handle(self) -> ResidentHandle:
        return self.payload

    def to_numpy(self) -> np.ndarray:
        """Gather the resident tiles into one ndarray (on demand — the
        only point where resident data crosses back to the master)."""
        return self._session.gather(self.handle)

    def free(self) -> None:
        """Release this handle's tiles from the executor arenas."""
        self._session.free(self.handle)


class SessionResidency:
    """Per-run residency view handed to the executor via ``plan.residency``:
    read access to resident input tiles and retention sinks for persisted
    outputs.  All storage lives on the session; this object scopes one run's
    leaf-uid / root-uid namespaces onto it."""

    def __init__(self, session: "CMMSession",
                 handles: Dict[int, ResidentHandle],
                 retain: Dict[int, ResidentHandle]):
        self._session = session
        #: leaf expr uid -> handle (resident INPUTS of this run)
        self.handles = handles
        #: root expr uid -> handle (persisted OUTPUTS of this run)
        self.retain = retain

    # -- executor read path (in-process backends: a host ndarray, or a
    # device array on batched-pallas) ----------------------------------------
    def tile(self, leaf_uid: int, i: int, j: int):
        h = self.handles[leaf_uid]
        return self._session._tiles[(h.hid, i, j)]

    # -- executor read path (multi-process backends) ------------------------
    def seg(self, leaf_uid: int, i: int, j: int) -> Tuple[int, str, str]:
        h = self.handles[leaf_uid]
        return self._session._segs[(h.hid, i, j)]

    def resident_ids(self) -> Dict[int, int]:
        """leaf uid -> handle id (what cluster workers need to resolve a
        RESIDENT task against their retained arena store)."""
        return {uid: h.hid for uid, h in self.handles.items()}

    # -- executor retention sinks -------------------------------------------
    def retain_local(self, root_uid: int, i: int, j: int, arr) -> None:
        h = self.retain[root_uid]
        self._session._tiles[(h.hid, i, j)] = arr
        h.home[(i, j)] = 0

    def retain_seg(self, root_uid: int, i: int, j: int, node: int,
                   segname: str, dtype_str: str) -> None:
        h = self.retain[root_uid]
        self._session._segs[(h.hid, i, j)] = (node, segname, dtype_str)
        h.home[(i, j)] = node


#: executor registry names that run inside the master process (tile storage
#: is plain ndarrays owned by the session)
_INPROC = ("local", "batched", "batched-pallas")


class CMMSession:
    """The session engine: plan-cache-backed compute over a long-lived
    executor whose arenas persist between calls.

    ::

        with CMMSession(engine, executor="cluster", tile=32) as s:
            P = s.persist(CM.rand(n, n, seed=0))      # tiles stay remote
            u = s.persist(CM.rand(n, 1, seed=1))
            for _ in range(k):
                u = s.persist(P @ u)                  # no gather, no refill
            result = u.to_numpy()                     # one gather, at the end

    ``executor`` is a registry name; for ``"cluster"`` the
    worker processes are spawned once and survive across runs, and
    persisted tiles live in the workers' shared-memory arenas.  ``close()``
    frees every live handle, audits the worker arenas for leaks (refcount
    audit) and shuts the workers down; the session is also a context
    manager.
    """

    def __init__(self, engine: Optional[CMMEngine] = None,
                 executor: str = "local", tile=None,
                 checkpoint_dir: Optional[str] = None,
                 checkpoint_every: int = 1,
                 max_retries: int = 3,
                 retry_backoff_s: float = 0.05, **exec_kw):
        self.engine = engine or CMMEngine()
        self.executor = executor
        self.tile = tile if tile is not None else self.engine.tile
        #: in-process tiles: host ndarrays, or device arrays (HBM)
        self._tiles: Dict[Tuple[int, int, int], object] = {}
        self._segs: Dict[Tuple[int, int, int], Tuple[int, str, str]] = {}
        self._handles: Dict[int, ResidentHandle] = {}
        self._closed = False
        self._closing = False
        self.stats: Dict[str, object] = {}
        #: bounded-retry policy for lost resident tiles (satellite of the
        #: durability work: the old path recursed without backoff)
        self.max_retries = max(0, int(max_retries))
        self.retry_backoff_s = float(retry_backoff_s)
        #: durability (None -> plain in-memory session, as before)
        self._store = None
        self.checkpoint_every = max(1, int(checkpoint_every))
        self._dirty: Set[int] = set()
        self._persists_since_ckpt = 0
        self._ckpt_step = 0
        #: flight recorder: spans accumulated across every run of this
        #: session (master lanes + ingested worker lanes), plus a
        #: master-side tracer for CHECKPOINT spans; ``dump_trace`` exports
        #: the whole session timeline
        from ..runtime.telemetry import Tracer
        self._trace_spans: List = []
        self._tracer = Tracer(node=-1,
                              enabled=bool(exec_kw.get("trace", True)))
        self._last_plan: Optional[Plan] = None
        if checkpoint_dir is not None:
            from ..runtime.durability import TileCheckpointStore
            self._store = TileCheckpointStore(checkpoint_dir)
            self._store.tracer = self._tracer
            # never renumber over snapshots an earlier incarnation left:
            # snap_<N> publication rmtree's an existing snap_<N>, which
            # would tear shards still referenced by newer manifests
            self._ckpt_step = max(self._store.snaps(), default=0)
        if executor in _INPROC:
            from ..exec import make_executor
            self._exec = make_executor(executor, **exec_kw)
        elif executor == "cluster":
            from ..exec.elastic import ClusterExecutor
            exec_kw.setdefault("timemodel", self.engine.timemodel)
            self._exec = ClusterExecutor(session=True, **exec_kw)
        else:
            raise ValueError(f"unknown session executor {executor!r}")
        if self._store is not None \
                and hasattr(self._exec, "corrupt_tile_hook"):
            self._exec.corrupt_tile_hook = self._corrupt_shard

    # -- public API ----------------------------------------------------------
    def compute(self, expr: ClusteredMatrix, tile=None) -> np.ndarray:
        """Materialise one expression (resident leaves enter at zero cost)."""
        return self._run([expr], persist=(), tile=tile)[0]

    def compute_many(self, exprs: Sequence[ClusteredMatrix],
                     tile=None) -> List[np.ndarray]:
        """Materialise several roots as ONE program: subexpressions shared
        across roots are planned and executed once (shared CSE)."""
        return self._run(list(exprs), persist=(), tile=tile)

    def persist(self, expr: ClusteredMatrix, name: str = "",
                tile=None) -> ResidentMatrix:
        """Compute ``expr`` and keep the result tiled in the executor's
        arenas; returns the handle as a reusable lazy leaf."""
        if isinstance(expr, ResidentMatrix) and expr._session is self \
                and expr.handle.alive and not expr.handle.lost:
            return expr                     # already resident here
        (rm,) = self._run([expr], persist=(0,), tile=tile, names=(name,))
        return rm

    def gather(self, handle: ResidentHandle) -> np.ndarray:
        """Assemble a resident handle's tiles into one master ndarray.

        Streaming assembly: each tile is copied exactly once, straight
        from its arena segment into its slice of the output — never via
        a tile-sized staging copy (halves gather traffic and keeps peak
        memory at output + one segment mapping)."""
        self._check_handle(handle)
        if handle.lost:
            self._recompute(handle)
        rows = tile_slices(handle.shape[0], handle.tile[0])
        cols = tile_slices(handle.shape[1], handle.tile[1])
        out = np.empty(handle.shape, dtype=handle.dtype)
        for (i, j) in handle.tiles():
            key = (handle.hid, i, j)
            (r0, r1), (c0, c1) = rows[i], cols[j]
            if key in self._tiles:
                out[r0:r1, c0:c1] = to_host(self._tiles[key])
            else:
                self._attach_tile(key, out=out[r0:r1, c0:c1])
        return out

    @property
    def resident_device_bytes(self) -> int:
        """Bytes of persisted tiles held in device memory (HBM)."""
        return sum(t.nbytes for t in self._tiles.values()
                   if not isinstance(t, np.ndarray))

    def free(self, handle: ResidentHandle) -> None:
        """Drop a handle's tiles from the arenas (its ResidentMatrix
        leaves become unusable; dependents lose their recompute lineage).
        Device tiles are released with the session's references."""
        if not handle.alive:
            return
        handle.alive = False
        registered = self._handles.pop(handle.hid, None) is not None
        for (i, j) in handle.tiles():
            self._tiles.pop((handle.hid, i, j), None)
            ent = self._segs.pop((handle.hid, i, j), None)
            if ent is not None:
                self._drop_seg(handle.hid, i, j, ent)
        if registered and self._store is not None and not self._closing:
            # a freed handle must not resurrect on resume: publish a
            # snapshot without it (cheap — survivors carry over).  Only
            # for handles that made it into the table: abandoning a
            # half-retained run's outputs is not a durability event.
            self.checkpoint()

    # -- flight recorder ------------------------------------------------------
    @property
    def spans(self) -> List:
        """Every span recorded so far this session: executor spans of all
        runs plus master-side CHECKPOINT spans (async writes drained in)."""
        return list(self._trace_spans) + self._tracer.snapshot()

    def dump_trace(self, path: str, include_predicted: bool = False) -> int:
        """Export the session's accumulated timeline as Chrome-trace JSON
        (``chrome://tracing`` / https://ui.perfetto.dev).  With
        ``include_predicted`` the LAST run's simulated timeline is
        overlaid on shifted lanes.  Returns the number of events."""
        spans = self.spans
        if include_predicted and self._last_plan is not None \
                and self._last_plan.sim is not None:
            spans += self._last_plan.sim.predicted_spans()
        from ..runtime.telemetry import export_chrome_trace
        return len(export_chrome_trace(spans, path)["traceEvents"])

    def drift_report(self, **kw):
        """Predicted-vs-actual drift of the LAST run in this session
        (:func:`repro.core.drift.drift_report`): per-node residual ratios
        and TimeModel terms flagged for recalibration."""
        if self._last_plan is None:
            raise RuntimeError("no executed plan to analyse — "
                               "compute()/persist() first")
        from .drift import drift_report
        return drift_report(self.engine.last_spans, self._last_plan,
                            tm=self.engine.timemodel, **kw)

    def close(self) -> Dict[str, object]:
        """Free every live handle, audit the executor arenas for leaks and
        shut down the long-lived executor.  Raises ``RuntimeError`` if the
        refcount audit finds stranded buffers (a retained tile the session
        no longer tracks, or a run that leaked arena segments)."""
        if self._closed:
            return self.stats
        self._closing = True          # an orderly close keeps the last
        if self._store is not None:   # snapshot resumable: free() must
            self._store.wait()        # not republish without the handles
        for h in list(self._handles.values()):
            self.free(h)
        audit: Dict[str, object] = {"handles_leaked": len(self._handles),
                                    "local_tiles_leaked": len(self._tiles)}
        if hasattr(self._exec, "close_session"):
            arena_audit = self._exec.close_session()
            # the executor's spill-file sweep rides along under a string
            # key; split it out so arena stays strictly per-node
            audit["spill"] = arena_audit.pop("spill",
                                             {"leaked_spill_files": 0})
            audit["arena"] = arena_audit
        self._closed = True
        self.stats["audit"] = audit
        leaked = audit["local_tiles_leaked"] or audit["handles_leaked"]
        arena = audit.get("arena") or {}
        for node, st in arena.items():
            leaked = leaked or st.get("live_buffers", 0) \
                or st.get("retained", 0) or st.get("spill_files", 0)
        spill = audit.get("spill") or {}
        leaked = leaked or spill.get("leaked_spill_files", 0)
        if leaked:
            raise RuntimeError(f"session arena audit failed: {audit}")
        return audit

    def __enter__(self) -> "CMMSession":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc_type is None:
            self.close()
        else:                    # don't mask the original error
            try:
                self.close()
            except Exception:
                pass

    # -- internals -----------------------------------------------------------
    def _sync_spec(self) -> None:
        """After a cluster run, membership may have changed (deaths drain
        nodes, joins append them).  Future plans must target the executor's
        current spec, or they would place tasks on nodes that left — and
        EVERY handle with tiles homed on a departed node is lost, not just
        the ones the failed run happened to read (their next use
        re-derives them from lineage)."""
        cur = getattr(self._exec, "current_spec", None)
        if cur is None:
            return
        if cur != self.engine.spec:
            self.engine.spec = cur
        alive = set(cur.alive_nodes())
        for h in self._handles.values():
            if not h.lost and any(n not in alive for n in h.home.values()):
                h.lost = True

    def _check_handle(self, handle: ResidentHandle) -> None:
        if not handle.alive:
            raise ValueError(f"resident handle #{handle.hid} "
                             f"({handle.name!r}) was freed")
        if handle.hid not in self._handles:
            raise ValueError(f"resident handle #{handle.hid} does not "
                             f"belong to this session")

    def _attach_tile(self, key, out: Optional[np.ndarray] = None
                     ) -> np.ndarray:
        """Read one tile out of a worker arena segment (cluster backends).
        With ``out`` the segment streams straight into the caller's
        buffer (one copy); without it a fresh tile-sized copy returns."""
        node, sname, dt = self._segs[key]
        from ..exec.cluster import _attach_shm
        hid, i, j = key
        h = self._handles[hid]
        from .tiling import tile_shape
        shp = tile_shape(h.shape, h.tile, i, j)
        seg = _attach_shm(sname)
        try:
            view = np.ndarray(shp, dtype=np.dtype(dt), buffer=seg.buf)
            if out is not None:
                np.copyto(out, view)
                return out
            return view.copy()
        finally:
            seg.close()

    def _drop_seg(self, hid: int, i: int, j: int, ent) -> None:
        """Tell the owning worker to drop a retained segment."""
        drop = getattr(self._exec, "drop_retained", None)
        if drop is not None:
            drop(ent[0], (hid, i, j))

    def _prepare(self, roots: Sequence[ClusteredMatrix], tile
                 ) -> List[ClusteredMatrix]:
        """Validate/normalise resident leaves for this run: foreign or
        freed handles are errors; lost handles are re-derived from lineage;
        a handle persisted at a different tile size is transparently
        gathered and re-enters as an INPUT leaf (correct, just not
        zero-cost)."""
        t = normalize_tile(tile)
        subst: Dict[int, ClusteredMatrix] = {}
        for node in topo_order_many(roots):
            if node.op is not Op.RESIDENT:
                continue
            if not isinstance(node, ResidentMatrix) or node._session is not \
                    self:
                raise ValueError(
                    f"resident leaf #{node.uid} does not belong to this "
                    f"session (persist() it here first)")
            h = node.handle
            self._check_handle(h)
            if h.lost:
                self._recompute(h)
            if tuple(h.tile) != t:
                subst[node.uid] = ClusteredMatrix.from_array(
                    self.gather(h), name=h.name or node.name)
        if not subst:
            return list(roots)
        new: Dict[int, ClusteredMatrix] = {}
        for node in topo_order_many(roots):
            if node.uid in subst:
                new[node.uid] = subst[node.uid]
                continue
            parents = tuple(new[p.uid] for p in node.parents)
            new[node.uid] = node if parents == node.parents else \
                ClusteredMatrix(node.op, node.shape, node.dtype,
                                parents=parents, payload=node.payload,
                                name=node.name)
        return [new[r.uid] for r in roots]

    def _tile_for(self, roots: Sequence[ClusteredMatrix], tile):
        if tile is not None:
            return normalize_tile(tile)
        if self.tile is not None:
            return normalize_tile(self.tile)
        return normalize_tile(self.engine._default_tile(roots))

    def _run(self, roots: List[ClusteredMatrix], persist: Sequence[int],
             tile=None, names: Sequence[str] = ()):
        """Bounded-retry driver around :meth:`_run_once`: each attempt that
        fails with ``ResidentTilesLost`` marks the named handles lost (the
        next attempt re-derives them from lineage inside ``_prepare``) and
        backs off exponentially; after ``max_retries + 1`` attempts the
        loss is declared :class:`SessionUnrecoverable`."""
        if self._closed:
            raise RuntimeError("session is closed")
        last: Optional[ResidentTilesLost] = None
        for attempt in range(self.max_retries + 1):
            if attempt:
                time.sleep(min(self.retry_backoff_s * (2 ** (attempt - 1)),
                               2.0))
            try:
                return self._run_once(roots, persist, tile, names)
            except ResidentTilesLost as e:
                self._sync_spec()
                for hid in e.hids:
                    h = self._handles.get(hid)
                    if h is not None:
                        h.lost = True
                last = e
        raise SessionUnrecoverable(
            last.hids,
            f"resident tiles for handles {last.hids} could not be "
            f"restored after {self.max_retries + 1} attempts: "
            f"{last}") from last

    def _run_once(self, roots: List[ClusteredMatrix],
                  persist: Sequence[int], tile=None,
                  names: Sequence[str] = ()):
        t = self._tile_for(roots, tile)
        prepared = self._prepare(roots, t)
        plan = self.engine.plan_many(prepared, tile=t, persist=persist)
        prog = plan.program

        handles: Dict[int, ResidentHandle] = {
            uid: n.payload for uid, n in prog.leaf_nodes.items()
            if n.op is Op.RESIDENT}
        retain: Dict[int, ResidentHandle] = {}
        new_handles: List[Tuple[int, ResidentHandle]] = []
        rsets = result_sets_of(prog.graph)
        for rs in rsets:
            if rs.gather:
                continue
            name = names[rs.index] if rs.index < len(names) else ""
            h = ResidentHandle(_next_hid(), rs.shape,
                               np.dtype(prog.dtypes.get(rs.uid, np.float64)),
                               t, rs.grid, name=name,
                               lineage=roots[rs.index])
            retain[rs.uid] = h
            new_handles.append((rs.index, h))

        plan.residency = SessionResidency(self, handles, retain)
        try:
            gathered = self.engine.execute_plan(plan, executor=self.executor,
                                                executor_obj=self._exec)
        except ResidentTilesLost:
            # a node died holding resident input tiles: abandon this
            # attempt's half-retained outputs and let the bounded _run
            # loop mark + re-derive the lost handles (deterministic
            # tasks -> the retry is bit-identical)
            for (_idx, h) in new_handles:
                self.free(h)
            raise
        self._sync_spec()
        self.stats["last_exec"] = dict(self._exec.stats)
        self._trace_spans.extend(self.engine.last_spans)
        self._last_plan = plan

        for (_idx, h) in new_handles:
            missing = [ij for ij in h.tiles()
                       if (h.hid,) + ij not in self._tiles
                       and (h.hid,) + ij not in self._segs]
            if missing:                       # pragma: no cover — defensive
                raise RuntimeError(f"executor retained no tile for "
                                   f"{missing[:4]} of handle #{h.hid}")
            self._handles[h.hid] = h
        if new_handles and self._store is not None:
            self._note_persisted([h.hid for _i, h in new_handles])

        # outputs in root order: gathered ndarrays for computed roots,
        # ResidentMatrix for persisted ones
        n_gather = sum(1 for rs in rsets if rs.gather)
        if gathered is None:
            garr: List[np.ndarray] = []
        elif isinstance(gathered, list):
            garr = gathered
        else:
            garr = [gathered]
        if len(garr) != n_gather:             # pragma: no cover — defensive
            raise RuntimeError(f"executor returned {len(garr)} results for "
                               f"{n_gather} gathered roots")
        out: List[object] = [None] * len(roots)
        gi = iter(garr)
        by_index = {idx: h for idx, h in new_handles}
        for rs in rsets:
            if rs.gather:
                out[rs.index] = next(gi)
            else:
                out[rs.index] = ResidentMatrix(by_index[rs.index], self)
        return out

    def _persist_into(self, handle: ResidentHandle,
                      expr: ClusteredMatrix) -> None:
        """Execute ``expr`` and retain its tiles under ``handle``'s
        EXISTING hid, rebinding residency into the current executor's
        arenas — the shared machinery of lineage recompute and
        checkpoint reload (both re-home a known handle, possibly onto a
        differently-shaped cluster).

        Drops stale locations first: surviving nodes may still hold
        retained segments of the old incarnation — tell them to release
        (a dead node's queue is gone and its segments were reaped with
        it)."""
        for (i, j) in handle.tiles():
            self._tiles.pop((handle.hid, i, j), None)
            ent = self._segs.pop((handle.hid, i, j), None)
            if ent is not None:
                self._drop_seg(handle.hid, i, j, ent)
        handle.home.clear()
        handle.lost = False                  # set before the run so nested
        prepared = self._prepare([expr], handle.tile)
        plan = self.engine.plan_many(prepared, tile=handle.tile,
                                     persist=(0,))
        prog = plan.program
        handles = {uid: n.payload for uid, n in prog.leaf_nodes.items()
                   if n.op is Op.RESIDENT}
        rs = next(r for r in result_sets_of(prog.graph) if not r.gather)
        plan.residency = SessionResidency(self, handles, {rs.uid: handle})
        self.engine.execute_plan(plan, executor=self.executor,
                                 executor_obj=self._exec)
        self._sync_spec()
        self._trace_spans.extend(self.engine.last_spans)
        self._last_plan = plan

    def _recompute(self, handle: ResidentHandle) -> None:
        """Re-derive a lost handle's tiles from its lineage expression,
        writing them back under the SAME hid so existing ResidentMatrix
        leaves stay valid."""
        if handle.lineage is None:
            raise ResidentTilesLost(
                (handle.hid,),
                f"resident handle #{handle.hid} lost its tiles and has no "
                f"lineage to recompute from")
        self._persist_into(handle, handle.lineage)
        self.stats["recomputed_handles"] = \
            self.stats.get("recomputed_handles", 0) + 1

    # -- durability ----------------------------------------------------------
    def _note_persisted(self, hids: Sequence[int]) -> None:
        """New handles entered the residency table: mark them dirty and
        snapshot once every ``checkpoint_every`` persists."""
        self._dirty.update(hids)
        self._persists_since_ckpt += 1
        if self._persists_since_ckpt >= self.checkpoint_every:
            self.checkpoint(wait=False)

    def checkpoint(self, wait: bool = True) -> None:
        """Snapshot the current residency table (asynchronously).

        Dirty or never-checkpointed handles are written fresh; clean
        handles carry over by reference.  A handle whose tiles cannot be
        read (its node died between the run and this call) is marked lost
        and skipped — durability degrades, the session keeps computing.

        ``wait=False`` (the steady-state path) never blocks on the
        writer: if the previous snapshot is still being written, this one
        is skipped and the dirty handles COALESCE into the next — the
        durability lag is bounded by one disk write, and a slow disk
        costs throughput of snapshots, not of compute."""
        if self._store is None or self._closed:
            return
        if not wait and self._store.busy():
            return                       # coalesce: dirty set stays dirty
        self._store.wait()                   # baseline = last real write
        if self._store.write_errors:
            errs = self.stats.setdefault("checkpoint_errors", [])
            errs.extend(self._store.write_errors)
            del self._store.write_errors[:]
        fresh: Dict[int, dict] = {}
        carry: List[int] = []
        for hid in sorted(self._handles):
            h = self._handles[hid]
            if h.lost:
                continue
            if hid not in self._dirty and self._store.has_entry(hid):
                carry.append(hid)
                continue
            try:
                tiles = {(i, j): self._read_tile(hid, i, j)
                         for (i, j) in h.tiles()}
            except Exception:
                h.lost = True                # next use re-derives it
                continue
            fresh[hid] = {"shape": h.shape, "dtype": h.dtype,
                          "tile": h.tile, "grid": h.grid, "name": h.name,
                          "lineage": self._pickle_lineage(h),
                          "tiles": tiles}
        if not fresh and set(carry) == self._store.baseline_hids():
            return                       # nothing changed since last snap
        self._ckpt_step += 1
        self._store.save_async(self._ckpt_step, fresh, carry)
        self._dirty.clear()
        self._persists_since_ckpt = 0

    def flush_checkpoints(self) -> None:
        """Force a snapshot of the current residency table and block until
        it is durably published; raises if the write failed."""
        if self._store is None:
            return
        self.checkpoint()
        self._store.wait()
        if self._store.write_errors:
            errs = list(self._store.write_errors)
            del self._store.write_errors[:]
            raise RuntimeError(f"checkpoint write failed:\n{errs[0]}")

    def _read_tile(self, hid: int, i: int, j: int) -> np.ndarray:
        """One resident tile as a master-side host array (checkpoint
        source).  In-process host tiles are handed to the writer WITHOUT a
        copy: a registered handle's tiles are immutable for its lifetime
        (``_persist_into`` replaces the dict entries, executors allocate
        fresh outputs, ``to_numpy`` assembles into a new array) and the
        writer's reference keeps the array alive past ``free()``.  Device
        tiles are pulled to the host; cluster tiles are assembled from
        arena segments — both already fresh arrays."""
        key = (hid, i, j)
        if key in self._tiles:
            return to_host(self._tiles[key])
        return self._attach_tile(key)

    def _pickle_lineage(self, h: ResidentHandle) -> Optional[bytes]:
        """Session-free pickle of a handle's lineage (ResidentMatrix
        leaves carry the session — strip them down to their hid); None if
        the expression is unpicklable (the handle is then reload-only)."""
        if h.lineage is None:
            return None
        from ..runtime.durability import pickle_expr
        try:
            return pickle_expr(self._strip_lineage(h.lineage))
        except Exception:
            return None

    def _strip_lineage(self, expr: ClusteredMatrix) -> ClusteredMatrix:
        new: Dict[int, ClusteredMatrix] = {}
        for node in topo_order_many([expr]):
            if node.op is Op.RESIDENT:
                new[node.uid] = ClusteredMatrix(
                    Op.RESIDENT, node.shape, node.dtype,
                    payload=int(node.payload.hid), name=node.name)
                continue
            parents = tuple(new[p.uid] for p in node.parents)
            new[node.uid] = node if parents == node.parents else \
                ClusteredMatrix(node.op, node.shape, node.dtype,
                                parents=parents, payload=node.payload,
                                name=node.name)
        return new[expr.uid]

    def _rebuild_lineage(self, raw: bytes) -> Optional[ClusteredMatrix]:
        """Inverse of :meth:`_strip_lineage` against THIS session's
        restored handles.  Every node is rebuilt (fresh uids — unpickled
        uids could collide with this process's counter); None if a
        referenced handle did not survive the restore."""
        from ..runtime.durability import unpickle_expr
        expr = unpickle_expr(raw)
        new: Dict[int, ClusteredMatrix] = {}
        for node in topo_order_many([expr]):
            if node.op is Op.RESIDENT:
                h = self._handles.get(int(node.payload))
                if h is None or not h.alive:
                    return None
                new[node.uid] = ResidentMatrix(h, self, name=node.name)
                continue
            parents = tuple(new[p.uid] for p in node.parents)
            new[node.uid] = ClusteredMatrix(
                node.op, node.shape, node.dtype, parents=parents,
                payload=node.payload, name=node.name)
        return new[expr.uid]

    def _corrupt_shard(self, hid: int) -> str:
        """Fault-injection hook for ``ChaosEvent(corrupt_tile=hid)``:
        flips one byte in the newest on-disk shard of ``hid``."""
        if self._store is None:              # pragma: no cover — guarded
            raise RuntimeError("corrupt_tile chaos needs a durable "
                               "session (checkpoint_dir=...)")
        self._store.wait()
        return self._store.corrupt_shard(hid)

    # -- resume ---------------------------------------------------------------
    def resident(self, name: str) -> ResidentMatrix:
        """Look up a live handle by its persist-time name (how resumed
        sessions re-acquire their matrices); newest wins on duplicates."""
        matches = [h for h in self._handles.values()
                   if h.alive and h.name == name]
        if not matches:
            raise KeyError(f"no resident handle named {name!r}")
        return ResidentMatrix(max(matches, key=lambda h: h.hid), self)

    @classmethod
    def resume(cls, checkpoint_dir: str,
               engine: Optional[CMMEngine] = None,
               executor: str = "local", tile=None,
               policy: str = "price", **exec_kw) -> "CMMSession":
        """Rebuild a session from the newest intact snapshot under
        ``checkpoint_dir`` — after a crash (SIGKILL of the whole cluster
        included) or an orderly close.

        The new session may target a completely different cluster shape:
        tiles are re-homed into the fresh executor's arenas.  Per handle
        the restore chooses reload-from-disk vs recompute-from-lineage:

        * ``policy="price"`` (default) — cheaper leg per the TimeModel
          (``spill_read_bandwidth`` vs the lineage plan's simulated
          makespan);
        * ``policy="reload"`` / ``policy="recompute"`` — forced.

        A corrupt shard degrades to lineage recompute; corrupt shards of
        a lineage-less handle raise :class:`SessionUnrecoverable`.
        Restored bytes are bit-identical to what was persisted."""
        if policy not in ("price", "reload", "recompute"):
            raise ValueError(f"unknown resume policy {policy!r}")
        s = cls(engine, executor=executor, tile=tile,
                checkpoint_dir=checkpoint_dir, **exec_kw)
        try:
            s._resume_from(policy)
        except BaseException:
            try:
                s.close()
            except Exception:
                pass
            raise
        return s

    def _resume_from(self, policy: str) -> None:
        from ..runtime.durability import ShardCorrupt
        man = self._store.latest_intact()
        if man is None:
            raise RuntimeError(
                f"no intact checkpoint under {self._store.dir!r}")
        entries = {int(hid): e for hid, e in man["handles"].items()}
        # restored handles keep their checkpointed hids; hids are
        # monotonic, so lineage only references EARLIER hids — restoring
        # in sorted order makes every reference resolvable
        _ensure_hid_floor(max(entries, default=0) + 1)
        report: Dict[str, object] = {"step": int(man["step"]),
                                     "reloaded": [], "recomputed": [],
                                     "corrupt_shards": 0}
        for hid in sorted(entries):
            e = entries[hid]
            h = ResidentHandle(hid, tuple(e["shape"]),
                               np.dtype(e["dtype"]), tuple(e["tile"]),
                               tuple(e["grid"]), name=e.get("name", ""))
            self._handles[hid] = h
            lineage = self._load_lineage(man, hid)
            mode = policy
            if mode == "price" and lineage is not None:
                reload_s = predict_reload_seconds(
                    self._store.handle_bytes(man, hid),
                    self.engine.timemodel)
                recompute_s = self.engine.predict_recompute_seconds(
                    [lineage], tile=h.tile)
                mode = "reload" if reload_s <= recompute_s \
                    else "recompute"
            if lineage is None:
                mode = "reload"              # no recompute leg exists
            if mode == "reload":
                try:
                    arr = self._assemble_shards(man, hid, h)
                except ShardCorrupt as exc:
                    report["corrupt_shards"] += 1
                    if lineage is None:
                        raise SessionUnrecoverable(
                            (hid,),
                            f"checkpoint shard of handle #{hid} "
                            f"({h.name!r}) is corrupt and it has no "
                            f"lineage: {exc}") from exc
                    mode = "recompute"       # graceful degradation
                else:
                    h.lineage = lineage if lineage is not None else \
                        ClusteredMatrix.from_array(arr, name=h.name)
                    self._persist_into(
                        h, ClusteredMatrix.from_array(arr, name=h.name))
                    report["reloaded"].append(hid)
            if mode == "recompute":
                h.lineage = lineage
                self._persist_into(h, lineage)
                report["recomputed"].append(hid)
        # recomputed tiles are bit-identical to the checkpointed ones
        # (deterministic tasks), so the on-disk shards remain valid
        # carry-over references for this session's own snapshots
        self._store.adopt(man)
        self._ckpt_step = max(self._ckpt_step, int(man["step"]))
        self.stats["resume"] = report

    def _load_lineage(self, man: dict, hid: int
                      ) -> Optional[ClusteredMatrix]:
        from ..runtime.durability import ShardCorrupt
        try:
            raw = self._store.load_lineage(man, hid)
        except ShardCorrupt:
            return None                      # reload leg may still work
        if raw is None:
            return None
        try:
            return self._rebuild_lineage(raw)
        except Exception:
            return None

    def _assemble_shards(self, man: dict, hid: int,
                         h: ResidentHandle) -> np.ndarray:
        """The full checkpointed ndarray of one handle, every shard
        CRC-validated (ShardCorrupt on any mismatch)."""
        rows = tile_slices(h.shape[0], h.tile[0])
        cols = tile_slices(h.shape[1], h.tile[1])
        out = np.empty(h.shape, dtype=h.dtype)
        for (i, j) in h.tiles():
            t = self._store.load_tile(man, hid, i, j)
            (r0, r1), (c0, c1) = rows[i], cols[j]
            out[r0:r1, c0:c1] = t
        return out
