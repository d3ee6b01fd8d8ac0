"""Worker runtime of the multi-process cluster executor.

The master loop lives in ``exec/elastic.py`` (``ClusterExecutor``); this
module holds what runs inside each node's process and what the master
needs to talk to it:

* ``_node_worker`` — one process per ClusterSpec node, running
  ``spec.workers_at(node)`` compute threads fed by that node's dispatch
  queue, so heterogeneous specs spawn unequal pools and HEFT's placement
  decisions are executed, not just simulated;
* ``_NodeArena`` — the node's tile arena: one
  ``multiprocessing.SharedMemory`` segment per live tile buffer,
  reference-counted, optionally bounded with a spill tier;
* XFER copies — the consumer node attaches the producer node's segment
  (``_attach_shm``) and copies the tile into its own arena, on the same
  thread pool as compute, so transfers overlap in-flight tasks.

Numerics: every task executes the same NumPy calls as ``LocalExecutor``
and tile movement is bit-copying, so results are **bit-identical** to the
per-task executor (asserted across the paper suite in
``tests/test_cmm_suite.py``).  The Pallas tile kernel is not routed
through this backend.

``predict_cluster_makespan`` is the executor-strategy leg for ``"auto"``:
it re-simulates the schedule under the profiler-calibrated process
dispatch + IPC terms (``TimeModel.process_dispatch_overhead`` /
``ipc_bandwidth`` / ``ipc_latency``, see ``profiler.calibrate_ipc``) so
the engine can weigh the multi-process strategy against the in-process
ones per plan.
"""
from __future__ import annotations

import itertools
import os
import queue as _queue
import threading
import time
import traceback
import zlib
from concurrent.futures import ThreadPoolExecutor
from dataclasses import replace
from typing import Dict, Optional, Tuple

import numpy as np

from ..core.fusion import eval_fused
from ..core.graph import (TaskGraph, TaskKind, TileRef, matmul_epilogue,
                          matmul_flags)
from ..core.heft import Schedule
from ..core.lazy import EWISE_FNS, apply_scale, leaf_slice
from ..core.machine import ClusterSpec
from ..core.timemodel import TimeModel

from ..core.tiling import tile_slices
from ..runtime.spill import (AllocFailInjected, ArenaOverflow, SpillCorrupt,
                             SpillDataLost, SpillMiss, TileSpillStore,
                             run_spill_dir)
from ..runtime.telemetry import Tracer
from ..runtime.wire import decode_tile, encode_tile

#: task kinds that accumulate into their output tile in place (the chain
#: holds the buffer alive without listing it in ``ins`` — same bookkeeping
#: as the wave executor's slab refcounts)
_CHAIN_KINDS = (TaskKind.ADDMUL, TaskKind.MATMUL)


#: serialises SharedMemory create/attach so the attach-time tracker patch
#: below can never swallow a concurrent create's registration
_TRACK_LOCK = threading.Lock()


def _attach_shm(name: str):
    """Attach an existing segment WITHOUT registering it with the resource
    tracker (bpo-39959: attaches register too, but the tracker's cache is a
    set — the owner's create+unlink pair then unbalances and the tracker
    raises KeyError / warns about leaks at shutdown).  Only the creating
    node registers a segment; crash cleanup still covers every segment."""
    from multiprocessing import resource_tracker, shared_memory
    with _TRACK_LOCK:
        orig = resource_tracker.register
        resource_tracker.register = lambda *a, **kw: None
        try:
            return shared_memory.SharedMemory(name=name)
        finally:
            resource_tracker.register = orig


def _release_seg(seg, unlink: bool = True) -> None:
    """Close (+unlink) a segment.  ``close()`` unmaps the memory even when
    ndarray views over ``seg.buf`` are still alive — a subsequent read
    through such a view hits unmapped (or, worse, remapped-to-another-
    segment) pages.  Callers must guarantee no live reader exists (the
    arena's pin protocol) or defer the close via ``_NodeArena._limbo``."""
    try:
        seg.close()
    except BufferError:                 # pragma: no cover
        pass
    if unlink:
        try:
            seg.unlink()
        except FileNotFoundError:       # pragma: no cover
            pass


class _NodeArena:
    """One node's shared-memory tile arena: a segment per live buffer,
    with exec/local.py-style owned-bytes accounting.

    Session residency adds two orthogonal states to a binding:

    * **retained** — a segment moved out of the per-run ref namespace into
      the session store (keyed by ``(handle id, i, j)``); it survives
      end-of-run freeing and later runs, until the session drops it;
    * **alias** — a ref bound onto a retained segment by a RESIDENT task
      (zero-copy re-entry).  Freeing or rebinding an alias drops only the
      binding, never the underlying retained segment.
    """

    def __init__(self, prefix: str, node: int,
                 mem_bytes: Optional[int] = None,
                 spill_dir: Optional[str] = None,
                 on_spill=None, on_unspill=None):
        # reentrant: budgeted allocation (_new_seg -> _ensure -> _evict)
        # happens both outside and inside lock-holding paths (retain)
        self._lock = threading.RLock()
        self._segs: Dict[TileRef, object] = {}
        #: ref -> ndarray in LRU order (oldest first); touched by get/adopt
        self._arrs: Dict[TileRef, np.ndarray] = {}
        #: session-retained segments: (hid, i, j) -> (seg, arr)
        self._retained: Dict[Tuple[int, int, int], Tuple[object, object]] = {}
        #: refs whose binding aliases a retained segment (not owned)
        self._alias: set = set()
        #: in-flight pin refcounts: a pinned ref is never evicted (task
        #: inputs/outputs for the duration of the task, XFER destinations)
        self._pinned: Dict[TileRef, int] = {}
        #: frees that arrived while the ref was pinned — honoured at the
        #: last unpin.  Releasing a segment unmaps it even under live
        #: ndarray views (close() invalidates them), so freeing a pinned
        #: ref would hand its in-flight reader unmapped (or worse,
        #: remapped-to-another-tile) pages.
        self._free_pending: set = set()
        #: superseded segments whose name is already unlinked but whose
        #: mapping may still back a pinned reader's view; closed when the
        #: arena is quiescent (no pins)
        self._limbo: list = []
        self._count = itertools.count()
        self._prefix = f"{prefix}n{node}"
        #: byte budget for cur + retained; None = unbounded (legacy mode)
        self.budget = None if mem_bytes is None else int(mem_bytes)
        self._spill_dir = spill_dir
        self._spill: Optional[TileSpillStore] = None
        self._on_spill = on_spill
        self._on_unspill = on_unspill
        #: chaos: fail the Nth fresh allocation (-1 = disarmed)
        self._alloc_fail_after = -1
        self.cur = 0
        self.peak = 0
        self.freed = 0
        self.allocs = 0
        self.retained_bytes = 0
        self.evictions = 0
        self.faults = 0
        #: flight-recorder hook: the worker sets this once at startup so
        #: the lazily-created spill store records SPILL/FAULTIN spans
        self.tracer = None

    def _store(self) -> TileSpillStore:
        if self._spill is None:
            d = self._spill_dir or run_spill_dir(self._prefix)
            self._spill = TileSpillStore(d, self._prefix)
            self._spill.tracer = self.tracer
        return self._spill

    def _evictable(self) -> Optional[TileRef]:
        """Coldest unpinned non-alias ref, or None (LRU = dict order)."""
        for ref in self._arrs:
            if ref in self._alias or self._pinned.get(ref):
                continue
            return ref
        return None

    def _evict(self, ref: TileRef) -> None:
        """Move ``ref``'s tile to the spill tier (lock held).  The spill
        write completes before the segment is released, and existing
        mappings (a reader that already ``get``-ed the array) stay valid
        until dropped — eviction changes where bytes live, never values."""
        seg = self._segs.pop(ref)
        arr = self._arrs.pop(ref)
        self._store().spill(ref, arr)
        self.cur -= seg.size
        self.evictions += 1
        del arr
        _release_seg(seg)
        if self._on_spill is not None:
            self._on_spill(ref)

    def _ensure(self, nbytes: int, strict: bool = True) -> None:
        """Evict cold tiles until ``nbytes`` more fit the budget (lock
        held).  ``strict`` raises ArenaOverflow when nothing evictable
        remains; non-strict (mid-run squeeze) evicts best-effort."""
        if self.budget is None:
            return
        while self.cur + self.retained_bytes + nbytes > self.budget:
            victim = self._evictable()
            if victim is None:
                if strict:
                    raise ArenaOverflow(
                        f"arena {self._prefix}: need {nbytes} bytes but "
                        f"{self.cur} allocated + {self.retained_bytes} "
                        f"retained of budget {self.budget} are pinned or "
                        f"retained — nothing left to evict")
                return
            self._evict(victim)

    def _maybe_inject_alloc_fail(self) -> None:
        with self._lock:
            if self._alloc_fail_after > 0:
                self._alloc_fail_after -= 1
                if self._alloc_fail_after == 0:
                    self._alloc_fail_after = -1
                    raise AllocFailInjected(
                        f"arena {self._prefix}: chaos-injected allocation "
                        f"failure")

    def _new_seg(self, nbytes: int):
        from multiprocessing import shared_memory
        with self._lock:
            self._ensure(int(nbytes))
            with _TRACK_LOCK:
                seg = shared_memory.SharedMemory(
                    create=True, size=max(int(nbytes), 1),
                    name=f"{self._prefix}_{next(self._count)}")
            # pre-charge so concurrent allocations see the reservation
            # before the (lock-free) copy completes and _adopt binds it
            self.cur += seg.size
            self.peak = max(self.peak, self.cur)
            return seg

    def alloc(self, ref: TileRef, shape, dtype) -> np.ndarray:
        """A fresh zeroed buffer for ``ref`` (CALLOC — shm is zero-filled
        by the OS, matching ``np.zeros``)."""
        self._maybe_inject_alloc_fail()
        dtype = np.dtype(dtype)
        n = int(np.prod(shape)) * dtype.itemsize
        seg = self._new_seg(n)
        arr = np.ndarray(shape, dtype=dtype, buffer=seg.buf)
        arr[...] = 0
        self._adopt(ref, seg, arr)
        return arr

    def store(self, ref: TileRef, value: np.ndarray) -> np.ndarray:
        """Copy ``value`` into a new segment bound to ``ref``."""
        self._maybe_inject_alloc_fail()
        value = np.asarray(value)
        seg = self._new_seg(value.nbytes)
        arr = np.ndarray(value.shape, dtype=value.dtype, buffer=seg.buf)
        arr[...] = value
        self._adopt(ref, seg, arr)
        return arr

    def _adopt(self, ref: TileRef, seg, arr: np.ndarray) -> None:
        with self._lock:
            # replace in place — the unbounded ``get`` fast path is
            # lock-free, so the key must never be absent during a rebind
            # (a reader racing a duplicate-producer rebind sees the old or
            # new buffer, both holding the same tile value)
            old = self._segs.get(ref)
            was_alias = ref in self._alias
            self._alias.discard(ref)
            self._segs[ref] = seg
            self._arrs.pop(ref, None)       # rebind lands at the LRU tail
            self._arrs[ref] = arr
            if self._spill is not None:
                # a spilled older version is superseded by this rebind
                self._spill.drop(ref)
            if old is not None and not was_alias:
                # rebind over a superseded version: release the old
                # allocation's bytes (the exec/local.py drift fix).
                # An alias binding owned neither bytes nor the segment.
                self.cur -= old.size
                self.freed += 1
                if self._pinned.get(ref):
                    # a pinned reader may still map the superseded
                    # segment: unlink the name now, close only once the
                    # arena is quiescent (close unmaps under live views)
                    try:
                        old.unlink()
                    except FileNotFoundError:   # pragma: no cover
                        pass
                    self._limbo.append(old)
                else:
                    _release_seg(old)
            self.allocs += 1
            # bytes were pre-charged by _new_seg

    def _fault_in(self, ref: TileRef) -> np.ndarray:
        """Reload a spilled tile into a fresh segment (lock held).  A
        missing or corrupt spill file surfaces as SpillDataLost carrying
        the ref, so the master can degrade to lineage recompute.  The
        disk entry is dropped only after the hot binding exists — if
        ``_new_seg`` overflows, the sole copy stays on disk for the
        retry."""
        try:
            data = self._store().fault_in(ref, keep=True)
        except (SpillMiss, SpillCorrupt) as e:
            self._store().drop(ref)
            raise SpillDataLost(ref, str(e))
        seg = self._new_seg(data.nbytes)    # may evict other cold tiles
        arr = np.ndarray(data.shape, dtype=data.dtype, buffer=seg.buf)
        arr[...] = data
        self._segs[ref] = seg
        self._arrs[ref] = arr
        self._store().drop(ref)
        self.allocs += 1
        self.faults += 1
        if self._on_unspill is not None:
            self._on_unspill(ref, seg.name, arr.dtype.str)
        return arr

    def get(self, ref: TileRef) -> np.ndarray:
        if self.budget is None:
            return self._arrs[ref]          # unbounded: lock-free fast path
        with self._lock:
            arr = self._arrs.get(ref)
            if arr is not None:
                self._arrs[ref] = self._arrs.pop(ref)      # LRU touch
                return arr
            if self._spill is not None and ref in self._spill:
                return self._fault_in(ref)
            raise KeyError(ref)

    def pin_all(self, refs) -> None:
        """Exempt ``refs`` from eviction while a task/XFER uses them."""
        with self._lock:
            for r in refs:
                self._pinned[r] = self._pinned.get(r, 0) + 1

    def unpin_all(self, refs) -> None:
        with self._lock:
            for r in refs:
                n = self._pinned.get(r, 0) - 1
                if n <= 0:
                    self._pinned.pop(r, None)
                    if r in self._free_pending:
                        # the master freed this ref mid-flight; honour it
                        # now that no reader maps its buffer (reentrant)
                        self._free_pending.discard(r)
                        self.free(r)
                else:
                    self._pinned[r] = n
            if not self._pinned and self._limbo:
                for seg in self._limbo:
                    _release_seg(seg, unlink=False)
                self._limbo.clear()

    def set_budget(self, nbytes: Optional[int]) -> None:
        """Shrink (or lift) the byte budget mid-run (``mem_squeeze``
        chaos / elastic re-admission); evicts down best-effort."""
        with self._lock:
            self.budget = None if nbytes is None else int(nbytes)
            self._ensure(0, strict=False)

    def arm_alloc_fail(self, nth: int) -> None:
        with self._lock:
            self._alloc_fail_after = max(1, int(nth))

    def seg_of(self, ref: TileRef) -> Tuple[str, str]:
        with self._lock:
            if ref not in self._segs and self._spill is not None \
                    and ref in self._spill:
                self._fault_in(ref)
            return self._segs[ref].name, self._arrs[ref].dtype.str

    def free(self, ref: TileRef) -> None:
        with self._lock:
            if self._pinned.get(ref):
                # an in-flight task/XFER still reads this buffer: defer
                # the release to its last unpin (see _free_pending)
                self._free_pending.add(ref)
                return
            self._free_pending.discard(ref)
            if self._spill is not None and ref in self._spill:
                # freeing a spilled ref: drop the cold copy
                self._spill.drop(ref)
                self.freed += 1
                return
            seg = self._segs.pop(ref, None)
            self._arrs.pop(ref, None)
            if ref in self._alias:
                # alias of a retained segment: drop the binding only
                self._alias.discard(ref)
                return
            if seg is not None:
                self.cur -= seg.size
                self.freed += 1
                _release_seg(seg)

    # -- session residency ---------------------------------------------------
    def retain(self, key: Tuple[int, int, int], ref: TileRef) -> None:
        """Move ``ref``'s segment into the retained (session) store under
        ``key`` — it leaves this run's byte accounting and survives until
        ``drop_retained``.  An alias binding (persist of an expression that
        folded to a resident leaf) is deep-copied so every retained key
        owns its segment exclusively."""
        with self._lock:
            if ref not in self._segs and self._spill is not None \
                    and ref in self._spill:
                self._fault_in(ref)     # retained tiles live in the hot tier
            seg = self._segs.pop(ref, None)
            arr = self._arrs.pop(ref, None)
            if seg is None:
                raise KeyError(f"retain of unbound ref {ref}")
            if ref in self._alias:
                self._alias.discard(ref)
                src = arr
                seg = self._new_seg(src.nbytes)
                arr = np.ndarray(src.shape, dtype=src.dtype, buffer=seg.buf)
                arr[...] = src
            self.cur -= seg.size        # moves to the retained accounting
            old = self._retained.get(key)
            if old is not None:         # re-retention under the same key
                self.retained_bytes -= old[0].size
                _release_seg(old[0])
            self._retained[key] = (seg, arr)
            self.retained_bytes += seg.size

    def bind_retained(self, ref: TileRef,
                      key: Tuple[int, int, int]) -> None:
        """Alias ``ref`` onto a retained segment (RESIDENT task): zero-copy
        re-entry of a session tile into this run's namespace."""
        with self._lock:
            ent = self._retained.get(key)
            if ent is None:
                raise KeyError(f"no retained tile {key} in this arena "
                               f"(resident tile lost?)")
            old = self._segs.get(ref)
            if old is not None and ref not in self._alias:
                self.cur -= old.size
                self.freed += 1
                _release_seg(old)
            self._segs[ref], self._arrs[ref] = ent
            self._alias.add(ref)

    def drop_retained(self, key: Tuple[int, int, int]) -> None:
        with self._lock:
            ent = self._retained.pop(key, None)
            if ent is not None:
                self.retained_bytes -= ent[0].size
                _release_seg(ent[0])

    def retained_seg(self, key: Tuple[int, int, int]) -> Tuple[str, str]:
        """Authoritative (segment name, dtype) of a retained tile — the
        retain-ack payload (a retain may have faulted the tile in first,
        renaming its segment, so the master must not trust a stale name)."""
        with self._lock:
            seg, arr = self._retained[key]
            return seg.name, arr.dtype.str

    def stats(self) -> Dict[str, int]:
        with self._lock:
            sp = self._spill
            return {"peak_buffer_bytes": self.peak,
                    "cur_buffer_bytes": self.cur,
                    "buffers_freed": self.freed,
                    "buffers_alloc": self.allocs,
                    "live_buffers": len(self._segs),
                    "retained": len(self._retained),
                    "retained_bytes": self.retained_bytes,
                    "mem_budget": 0 if self.budget is None else self.budget,
                    "evictions": self.evictions,
                    "faults": self.faults,
                    "spill_writes": 0 if sp is None else sp.writes,
                    "spill_reads": 0 if sp is None else sp.reads,
                    "spill_files": 0 if sp is None else sp.live_files,
                    "spilled_bytes": 0 if sp is None else sp.live_bytes}

    def destroy(self) -> None:
        with self._lock:
            for ref, seg in self._segs.items():
                if ref not in self._alias:
                    _release_seg(seg)
            self._segs.clear()
            self._arrs.clear()
            self._alias.clear()
            self._pinned.clear()
            self._free_pending.clear()
            for seg in self._limbo:
                _release_seg(seg, unlink=False)
            self._limbo.clear()
            for (seg, _arr) in self._retained.values():
                _release_seg(seg)
            self._retained.clear()
            if self._spill is not None:
                self._spill.destroy()


def _execute_task(t, arena: _NodeArena, leaf_nodes, dtypes,
                  tile, resident_ids=None
                  ) -> Tuple[Optional[str], Optional[str]]:
    """Run one task against the node arena; mirrors the per-task executor's
    kernels exactly (bit-identity contract).  Returns the output buffer's
    (segment name, dtype str)."""
    k = t.kind
    if k is TaskKind.CALLOC:
        arena.alloc(t.out, t.out.shape, dtypes.get(t.payload, np.float64))
        return arena.seg_of(t.out)
    if k is TaskKind.TAKECOPY:
        # gather to master: the tile already sits in the master node's
        # arena (produced here or XFER'd in) — nothing to compute
        return arena.seg_of(t.out)
    if k is TaskKind.RESIDENT:
        # session-resident tile: alias the retained segment into this
        # run's ref namespace (zero-copy, this node is the tile's home)
        hid = (resident_ids or {})[t.payload]
        arena.bind_retained(t.out, (hid, t.out.i, t.out.j))
        return arena.seg_of(t.out)
    if k in _CHAIN_KINDS:
        ta, tb = matmul_flags(t.payload)
        a = arena.get(t.ins[0])
        b = arena.get(t.ins[1])
        a = a.T if ta else a
        b = b.T if tb else b
        c = arena.get(t.out)
        c += a @ b
        epi = matmul_epilogue(t.payload)
        if epi is not None:
            # last task of the k-chain: fused elementwise epilogue over
            # the accumulated tile (rebinds the output segment — store
            # runs before seg_of so the master sees the new segment)
            arena.store(t.out, eval_fused(
                epi, [c] + [arena.get(r) for r in t.ins[2:]]))
        return arena.seg_of(t.out)
    if k is TaskKind.FILL:
        node = leaf_nodes[t.payload]
        rs = tile_slices(node.shape[0], tile[0])[t.out.i]
        cs = tile_slices(node.shape[1], tile[1])[t.out.j]
        val = leaf_slice(node, rs[0], rs[1], cs[0], cs[1])
    elif k is TaskKind.ADD:
        val = arena.get(t.ins[0]) + arena.get(t.ins[1])
    elif k is TaskKind.SUB:
        val = arena.get(t.ins[0]) - arena.get(t.ins[1])
    elif k is TaskKind.EWMUL:
        val = arena.get(t.ins[0]) * arena.get(t.ins[1])
    elif k is TaskKind.SCALE:
        kind, s = t.payload
        val = apply_scale(kind, arena.get(t.ins[0]), s)
    elif k is TaskKind.EWISE:
        val = EWISE_FNS[t.payload](arena.get(t.ins[0]))
    elif k is TaskKind.FUSED:
        val = eval_fused(t.payload, [arena.get(r) for r in t.ins])
    elif k is TaskKind.TRANSPOSE:
        val = np.ascontiguousarray(arena.get(t.ins[0]).T)
    else:       # pragma: no cover
        raise ValueError(t.kind)
    arena.store(t.out, val)
    return arena.seg_of(t.out)


def _node_worker(node: int, inq, outq, g: TaskGraph, tile, leaf_nodes,
                 dtypes, nthreads: int, prefix: str,
                 hb_interval: float = 0.0,
                 blas_threads: Optional[int] = None,
                 mem_bytes: Optional[int] = None,
                 spill_dir: Optional[str] = None,
                 trace: bool = True) -> None:
    """One cluster node: a dispatch-queue loop feeding a thread pool of
    ``nthreads`` compute slots, with tiles in this node's shm arena.
    XFER copies run on the same pool, so they overlap in-flight compute.

    ``hb_interval > 0`` enables the elastic runtime's liveness protocol:
    the worker emits ``("hb", node, pid)`` whenever the dispatch queue is
    idle for that long, reports per-task service time in its ``done``
    messages (straggler EWMA input), and honours a ``("throttle", s)``
    op (fault-injection: make this node artificially slow).  XFER
    failures are reported as recoverable ``xfer_fail`` events — a dead
    source node's segment vanishing mid-copy must re-route, not crash.

    ``blas_threads`` caps this process's BLAS pool (the machine model's
    ``threads_per_worker``): without it one worker process can saturate
    every host core through OpenBLAS threading, which hides the
    process-level scaling the cluster model is about.

    Session mode spawns the worker with ``g=None`` and ships the run
    context (graph, tile, leaves, dtypes, resident-leaf handle ids) per
    run via a ``("run", ...)`` op — the process and its arena (with the
    session's retained tiles) survive across runs.

    ``mem_bytes`` bounds the arena (``ClusterSpec.mem_at``): on pressure
    cold unpinned tiles spill to ``spill_dir`` and fault back in on read.
    Every eviction posts ``("spill", node, ref)`` and every fault-in posts
    ``("unspill", node, ref, segname, dtype)`` so the master's segment-name
    maps track where tiles live; a lost spill file posts
    ``("tile_lost", node, ref, tb)`` for lineage recompute.

    A bounded arena also serves XFER/gather *leases*: ``("hold", ref)``
    pins the tile (faulting it hot if cold) and acks ``("held", node,
    ref, segname, dtype)``; ``("release", ref)`` drops the pin.
    Without the lease, a reader attaching the acked segment name races
    eviction — under pressure the LRU can cycle the whole arena inside
    the master→consumer round trip, so name-based retries livelock.

    The compressed wire path generalises the lease: ``("pack", ref,
    codec)`` pins the tile AND stages its encoded payload in a transient
    wire segment (outside the arena budget), acking ``("packed", node,
    ref, segname, dtype, codec, comp_nbytes, raw_crc)``; the consumer
    attaches the staging segment, decodes, and CRC-checks the *decoded*
    bytes against ``raw_crc`` — bit-identity end to end.  ``("unpack",
    ref)`` drops one pack lease; the staging segment is destroyed when
    the last lease on it drops.
    """
    if blas_threads:
        try:
            import threadpoolctl
            threadpoolctl.threadpool_limits(blas_threads)
        except ImportError:             # pragma: no cover
            pass

    def _on_spill(ref: TileRef) -> None:
        outq.put(("spill", node, ref))

    def _on_unspill(ref: TileRef, segname: str, dtype_str: str) -> None:
        outq.put(("unspill", node, ref, segname, dtype_str))

    arena = _NodeArena(prefix, node, mem_bytes=mem_bytes,
                       spill_dir=spill_dir,
                       on_spill=_on_spill, on_unspill=_on_unspill)
    #: flight recorder: spans buffer here and piggyback on the done /
    #: xfer_done / hb / stats messages already flowing to the master —
    #: tracing adds no queue traffic of its own
    tracer = Tracer(node=node, enabled=trace)
    arena.tracer = tracer
    pid = os.getpid()
    throttle = [0.0]
    #: refs the master released this run — a ("fault", ref) op that pool-
    #: schedules AFTER the inline ("free", ref) is obsolete, not a lost tile
    freed_refs: set = set()
    ctx = {"g": g, "tile": tile, "leaf_nodes": leaf_nodes,
           "dtypes": dtypes, "resident_ids": {}}

    def run_task(tid: int) -> None:
        t = ctx["g"].tasks[tid]
        # pin the working set: in-flight inputs and the (possibly mutated
        # in place) output must stay in the hot tier for the task's whole
        # duration — eviction mid-mutation would spill a partial value
        pins = list(t.ins) + ([t.out] if t.out is not None else [])
        arena.pin_all(pins)
        try:
            t0 = time.perf_counter()
            with tracer.span(t.kind.name, cat="EXEC", tid=tid,
                             kind=t.kind.name):
                if throttle[0] > 0.0:
                    time.sleep(throttle[0])
                seg, dt = _execute_task(t, arena,
                                        ctx["leaf_nodes"], ctx["dtypes"],
                                        ctx["tile"], ctx["resident_ids"])
            outq.put(("done", node, tid, seg, dt, pid,
                      time.perf_counter() - t0, tracer.drain()))
        except BaseException as e:
            if isinstance(e, SpillDataLost):
                # the master must drop this holding BEFORE retrying the
                # task (per-worker FIFO guarantees the ordering)
                outq.put(("tile_lost", node, e.ref, traceback.format_exc()))
            outq.put(("error", node, tid, traceback.format_exc()))
        finally:
            arena.unpin_all(pins)

    def run_xfer(version: int, ref: TileRef, src_name: str,
                 dtype_str: str, codec: str = "raw",
                 comp_nbytes: int = 0, raw_crc=None) -> None:
        arena.pin_all((ref,))
        try:
            nbytes = (int(np.prod(ref.shape))
                      * np.dtype(dtype_str).itemsize)
            with tracer.span("XFER", nbytes=nbytes, codec=codec,
                             comp_nbytes=comp_nbytes, version=version):
                if throttle[0] > 0.0:
                    # a slow node is slow at moving bytes too (straggler
                    # modelling; also gives chaos tests a deterministic
                    # in-flight window)
                    time.sleep(throttle[0])
                remote = _attach_shm(src_name)
                try:
                    if codec != "raw":
                        # compressed wire path: the staging segment holds
                        # the encoded payload; decode locally and verify
                        # the CRC of the *decoded* bytes against the
                        # source's stamp — torn reads and codec faults
                        # both land as recoverable xfer_fail, never as
                        # wrong bytes
                        payload = bytes(remote.buf[:comp_nbytes])
                        src = decode_tile(payload, ref.shape,
                                          np.dtype(dtype_str), codec)
                        want = zlib.crc32(src.data) & 0xFFFFFFFF
                        if raw_crc is not None and want != raw_crc:
                            raise RuntimeError(
                                f"XFER decoded-payload CRC32 mismatch for "
                                f"{ref}: {want:#010x} != {raw_crc:#010x}")
                    else:
                        src = np.ndarray(ref.shape,
                                         dtype=np.dtype(dtype_str),
                                         buffer=remote.buf)
                        # CRC32 over the payload before and after the
                        # copy: a source segment vanishing or being
                        # rebound mid-copy (a torn read) lands here as a
                        # recoverable xfer_fail — the elastic master
                        # retries from a live holder — instead of
                        # silently propagating wrong bytes
                        want = zlib.crc32(src.data) & 0xFFFFFFFF
                    copied = arena.store(ref, src)
                    got = zlib.crc32(copied.data) & 0xFFFFFFFF
                    if got != want:
                        raise RuntimeError(
                            f"XFER payload CRC32 mismatch for {ref}: "
                            f"copied {got:#010x} != source {want:#010x}")
                finally:
                    remote.close()
            seg, dt = arena.seg_of(ref)
            outq.put(("xfer_done", node, version, ref, seg, dt,
                      tracer.drain()))
        except BaseException:
            outq.put(("xfer_fail", node, version, ref,
                      traceback.format_exc()))
        finally:
            arena.unpin_all((ref,))

    #: ref -> [staging seg, lease count, codec, comp_nbytes, raw_crc,
    #: dtype_str] — wire payloads staged for outgoing compressed XFERs.
    #: Transient buffers outside the arena budget; each "pack" lease
    #: also pins the source tile, so the staged bytes stay authoritative.
    packs: Dict[TileRef, list] = {}
    pack_ids = itertools.count()

    def run_pack(ref: TileRef, codec: str) -> None:
        from multiprocessing import shared_memory
        arena.pin_all((ref,))
        try:
            ent = packs.get(ref)
            if ent is None:
                arr = arena.get(ref)     # faults the tile hot if cold
                with tracer.span("PACK", nbytes=int(arr.nbytes),
                                 codec=codec) as psp:
                    payload = encode_tile(arr, codec)
                    raw_crc = zlib.crc32(np.ascontiguousarray(arr).data) \
                        & 0xFFFFFFFF
                    with _TRACK_LOCK:
                        seg = shared_memory.SharedMemory(
                            create=True, size=max(len(payload), 1),
                            name=f"{prefix}w{node}_{next(pack_ids)}")
                    seg.buf[:len(payload)] = payload
                    if tracer.enabled:
                        psp.args["comp_nbytes"] = len(payload)
                ent = packs[ref] = [seg, 0, codec, len(payload), raw_crc,
                                    arr.dtype.str]
            ent[1] += 1
            outq.put(("packed", node, ref, ent[0].name, ent[5], ent[2],
                      ent[3], ent[4]))
        except KeyError:
            arena.unpin_all((ref,))
            if ref not in freed_refs:
                outq.put(("tile_lost", node, ref, traceback.format_exc()))
        except SpillDataLost:
            arena.unpin_all((ref,))
            outq.put(("tile_lost", node, ref, traceback.format_exc()))
        except ArenaOverflow:
            # transient, like "hold": the master re-sends (bounded)
            arena.unpin_all((ref,))
            outq.put(("hold_fail", node, ref))
        except BaseException:
            arena.unpin_all((ref,))
            outq.put(("error", node, -1, traceback.format_exc()))

    def drop_pack(ref: TileRef) -> None:
        ent = packs.get(ref)
        if ent is None:                 # pragma: no cover - defensive
            return
        ent[1] -= 1
        arena.unpin_all((ref,))
        if ent[1] <= 0:
            _release_seg(ent[0])
            del packs[ref]

    def run_fault(ref: TileRef) -> None:
        """Master-requested fault-in of a spilled tile (it wants to XFER
        from or gather this node).  Always acks with the current segment
        name — the tile may have been faulted back in locally already."""
        arena.pin_all((ref,))
        try:
            arena.get(ref)              # faults the tile hot
            seg, dt = arena.seg_of(ref)
            outq.put(("unspill", node, ref, seg, dt))
        except KeyError:
            if ref in freed_refs:
                # the master freed this ref after requesting the fault
                # (its last reader finished first); the request is stale
                return
            outq.put(("tile_lost", node, ref, traceback.format_exc()))
        except SpillDataLost:
            outq.put(("tile_lost", node, ref, traceback.format_exc()))
        except BaseException:
            outq.put(("error", node, -1, traceback.format_exc()))
        finally:
            arena.unpin_all((ref,))

    with ThreadPoolExecutor(max_workers=max(1, nthreads)) as pool:
        while True:
            if hb_interval > 0.0:
                try:
                    msg = inq.get(timeout=hb_interval)
                except _queue.Empty:
                    outq.put(("hb", node, pid, tracer.drain()))
                    continue
            else:
                msg = inq.get()
            op = msg[0]
            if op == "task":
                pool.submit(run_task, msg[1])
            elif op == "xfer":
                pool.submit(run_xfer, msg[1], msg[2], msg[3], msg[4],
                            *msg[5:])
            elif op == "free":
                freed_refs.add(msg[1])
                arena.free(msg[1])
            elif op == "hold":
                # lease this tile as an XFER/gather source: pin it so
                # neither eviction nor a rebind can invalidate the acked
                # segment name before the consumer attaches (under
                # pressure the LRU can cycle the whole arena in the
                # master->consumer round-trip window, livelocking the
                # name-based retry).  The pin is released by "release"
                # once the copy lands.
                ref = msg[1]
                arena.pin_all((ref,))
                try:
                    arena.get(ref)          # faults the tile hot if cold
                    seg, dt = arena.seg_of(ref)
                    outq.put(("held", node, ref, seg, dt))
                except KeyError:
                    arena.unpin_all((ref,))
                    if ref not in freed_refs:
                        outq.put(("tile_lost", node, ref,
                                  traceback.format_exc()))
                except SpillDataLost:
                    arena.unpin_all((ref,))
                    outq.put(("tile_lost", node, ref,
                              traceback.format_exc()))
                except ArenaOverflow:
                    # transient: concurrent tasks' pins drain as they
                    # finish — the master re-sends the hold (bounded)
                    arena.unpin_all((ref,))
                    outq.put(("hold_fail", node, ref))
                except BaseException:
                    arena.unpin_all((ref,))
                    outq.put(("error", node, -1, traceback.format_exc()))
            elif op == "release":
                arena.unpin_all((msg[1],))
            elif op == "pack":
                # compressed-wire lease: pin + stage encoded payload.
                # Runs inline (like "hold") so concurrent pack requests
                # for one ref can't race the staging-segment create.
                run_pack(msg[1], msg[2])
            elif op == "unpack":
                drop_pack(msg[1])
            elif op == "fault":
                # master needs a spilled tile hot (XFER source / gather)
                pool.submit(run_fault, msg[1])
            elif op == "run":
                # session mode: (re)bind this worker to a new run's
                # graph/leaves — the arena (incl. retained tiles) persists
                ctx["g"], ctx["tile"] = msg[1], msg[2]
                ctx["leaf_nodes"], ctx["dtypes"] = msg[3], msg[4]
                ctx["resident_ids"] = msg[5]
                freed_refs.clear()      # ref names recur across runs
            elif op == "retain":
                # move a persisted output tile into the session store;
                # ack with the authoritative segment name (retain may
                # fault the tile in, renaming its segment)
                try:
                    arena.retain(msg[2], msg[1])
                    sname, dt = arena.retained_seg(msg[2])
                    outq.put(("retained", node, msg[2], sname, dt))
                except BaseException:
                    outq.put(("error", node, -1, traceback.format_exc()))
            elif op == "drop":
                arena.drop_retained(msg[1])
            elif op == "audit":
                outq.put(("audit", node, arena.stats()))
            elif op == "throttle":
                throttle[0] = float(msg[1])
            elif op == "squeeze":
                # chaos mem_squeeze: shrink the budget mid-run
                arena.set_budget(msg[1])
            elif op == "alloc_fail":
                # chaos: fail the Nth upcoming fresh allocation
                arena.arm_alloc_fail(msg[1])
            elif op == "cal":
                # clock calibration: echo the master's send stamp with
                # this process's monotonic clock (NTP-style midpoint,
                # see telemetry.estimate_clock_offset)
                outq.put(("cal", node, msg[1], time.perf_counter()))
            elif op == "stop":
                break
    for ent in packs.values():          # transient wire buffers
        _release_seg(ent[0])
    packs.clear()
    stats = arena.stats()
    arena.destroy()
    outq.put(("stats", node, stats, pid, tracer.drain()))


#: unique per-execute() shm namespace within this master process
_RUN_IDS = itertools.count()


def predict_cluster_makespan(g: TaskGraph, sched: Schedule,
                             spec: ClusterSpec, tm: TimeModel) -> float:
    """Predicted wall-clock of the multi-process cluster executor.

    Re-simulates the schedule with the machine model swapped to what this
    backend actually pays: per-task process dispatch
    (``tm.process_dispatch_overhead``) and shared-memory XFER transfers
    (``tm.ipc_latency + bytes / tm.ipc_bandwidth``) instead of the network
    link model.  The engine compares this against the per-task and
    wave-batched predictions to pick ``executor="auto"``'s strategy.
    """
    from ..core.simulator import simulate
    ipc_spec = replace(spec, link_bw=max(tm.ipc_bandwidth, 1.0),
                       latency=max(tm.ipc_latency, 0.0), pair_bw=())
    tm_proc = replace(tm, dispatch_overhead=tm.process_dispatch_overhead)
    return simulate(g, sched, ipc_spec, tm_proc).makespan
