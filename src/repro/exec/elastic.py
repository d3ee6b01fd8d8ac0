"""Multi-process cluster executor: HEFT placements run for real, and
survive membership churn (plan -> observe -> re-plan).

The paper's claim is that CMM "automatically configures communication and
worker processes" so the schedule produced for a multi-node cluster
actually executes across nodes, and "automatically scale[s] up for
clusters of heterogeneous nodes".  ``ClusterExecutor`` runs one worker
process per ClusterSpec node (the runtime in ``exec/cluster.py``): every
task runs on the process of its HEFT-assigned node, tiles live in per-node
shared-memory arenas, and cross-node edges are real inter-process XFER
copies.  The master is a control loop over three mechanisms:

* **membership** (``runtime/membership.py``): workers heartbeat over
  their queues; process exit or heartbeat staleness raises a DEATH
  event, per-task service-time EWMAs raise STRAGGLE events (the
  ``runtime/fault.py`` policy shapes applied at node granularity).

* **lineage recovery** (numpywren-style): no tile data is ever
  checkpointed.  Every tile is a deterministic function of the task
  graph, so when a node dies the master resurrects exactly the completed
  tasks whose output values were lost with it and are still needed —
  computed as a closure over the producer subgraph — and re-executes
  them on the survivors.

* **incremental frontier re-planning** (``heft.replan_frontier``): on
  death/join/straggle the not-yet-dispatched frontier is re-HEFTed
  against the surviving (or augmented) ``ClusterSpec`` — completed and
  in-flight placements stay fixed, dead nodes are drained
  (``spec.without_node``), joined nodes appended (``spec.with_node``).

Stragglers additionally get **speculative duplicate execution**: their
in-flight tasks are duplicated onto healthy nodes, and the master's
first-writer-wins bookkeeping keeps exactly one completion per task.
Because every task kind is deterministic (same NumPy call on the same
bits), duplicate and resurrected executions produce bit-identical
tiles, so results under any failure/join/straggle interleaving are
**bit-identical to** ``LocalExecutor`` — the repo's conformance bar —
which the fault-injection tier (tests/test_elastic.py) asserts.

The master routes data dynamically: it tracks which *version* (producer
task id) of each tile ref is bound in each node's arena, and requests a
shared-memory XFER from a live holder whenever a dispatch-ready task is
missing an input at its assigned node.  Writes to one ``(node, ref)``
arena slot are serialised by a master-side write lock, so in-place
accumulate chains can never race a transfer reading the same buffer.

Fault injection for tests/benchmarks is first-class: ``ChaosEvent``\\ s
fire on task-completion counts — SIGKILL a worker process, join a new
node, throttle a node into a straggler — so churn is reproducible.
"""
from __future__ import annotations

import os
import queue as _queue
import shutil
import signal
import time
from collections import defaultdict
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

from ..core.graph import TaskGraph, TaskKind, TileRef
from ..core.heft import Placement, Schedule, replan_frontier
from ..core.lazy import Op
from ..core.machine import ClusterSpec, MemoryBudgetExceeded
from ..core.session import ResidentTilesLost
from ..core.timemodel import CostCache, TimeModel, analytic_time_model
from ..core.tiling import assemble, result_sets_of
from ..runtime.membership import (DEATH, RECOVER, STRAGGLE,
                                  MembershipConfig, MembershipService)
from ..runtime.spill import run_spill_dir
from ..runtime.telemetry import (MetricsRegistry, Span, Tracer,
                                 estimate_clock_offset)
from ..runtime.wire import BCAST_MIN_FANOUT, choose_wire_codec
from .cluster import _CHAIN_KINDS, _RUN_IDS, _attach_shm, _node_worker


@dataclass(frozen=True)
class ChaosEvent:
    """One injected membership change, fired when the master's completed-
    task count first reaches ``after_done`` (deterministic trigger)."""

    after_done: int
    #: SIGKILL this node's worker process (master refuses its own node)
    kill_node: Optional[int] = None
    #: spawn + join a fresh node with this many worker threads
    join_workers: Optional[int] = None
    join_slowdown: float = 1.0
    #: make this node sleep this long per task (manufactures a straggler)
    throttle_node: Optional[int] = None
    throttle_seconds: float = 0.0
    #: bypass EWMA detection latency: raise STRAGGLE for this node now
    flag_straggler: Optional[int] = None
    #: poison the next N master->worker XFER requests (each is sent with
    #: an unattachable source segment, so the destination worker reports
    #: ``xfer_fail`` — exercising the bounded-backoff retry path
    #: end-to-end on real queues)
    drop_xfer: Optional[int] = None
    #: flip a byte in the newest checkpoint shard of this resident handle
    #: id (needs a durable session — ``CMMSession(checkpoint_dir=...)``
    #: wires ``corrupt_tile_hook``): the next resume fails that shard's
    #: CRC and must degrade to lineage recompute
    corrupt_tile: Optional[int] = None
    #: SIGKILL the ENTIRE cluster — every worker, then the master process
    #: itself.  Nothing survives to clean up (that is the point: the
    #: durable session's ``resume()`` is what recovers) — only subprocess
    #: test harnesses should arm this
    kill_master: bool = False
    #: shrink this node's arena memory budget to ``squeeze_bytes``
    #: mid-run: the worker evicts cold tiles to the spill tier until it
    #: fits, and the membership-adjusted spec (``current_spec``) reflects
    #: the new budget for subsequent session plans
    mem_squeeze: Optional[int] = None
    squeeze_bytes: int = 0
    #: fail this node's Nth subsequent arena allocation with an injected
    #: ``AllocFailInjected`` — the task fails, the master retries it with
    #: backoff (the counter is consumed, so the retry allocates for real)
    alloc_fail: Optional[int] = None
    alloc_fail_nth: int = 1


class ClusterExecutor:
    """Executes a planned tiled program across one process per cluster
    node, honouring the HEFT schedule's per-task node placement, under
    the control plane described in the module docstring.  ``timemodel``
    drives frontier re-planning (``CMMEngine.run`` injects its own);
    ``membership`` tunes detection latency; ``chaos`` injects
    failures/joins/stragglers for tests and the chaos benchmark;
    ``respawn_dead=True`` additionally respawns a dead node's worker
    (fresh process, empty arena) and re-admits it instead of draining
    its slots.
    """

    def __init__(self, workers_per_node: Optional[int] = None,
                 free_buffers: bool = True,
                 mp_context: Optional[str] = None,
                 timeout: float = 300.0,
                 timemodel: Optional[TimeModel] = None,
                 membership: Optional[MembershipConfig] = None,
                 chaos: Sequence[ChaosEvent] = (),
                 respawn_dead: bool = False,
                 speculate: bool = True,
                 gc_interval: int = 64,
                 blas_threads: Optional[int] = None,
                 session: bool = False,
                 wire_codec: Optional[str] = None,
                 broadcast: bool = True,
                 stream_gather: bool = True,
                 trace: bool = True,
                 straggler_priors: Sequence[int] = ()):
        self.workers_per_node = workers_per_node
        self.free_buffers = free_buffers
        self.mp_context = mp_context
        self.timeout = timeout
        self.timemodel = timemodel
        self.membership_cfg = membership
        self.chaos = tuple(sorted(chaos, key=lambda c: c.after_done))
        self.respawn_dead = respawn_dead
        self.speculate = speculate
        self.gc_interval = max(1, gc_interval)
        #: wire codec policy: None prices each cross-node edge with the
        #: TimeModel's compression terms; "raw"/"zlib" force it (tests,
        #: benchmarks).  Compressed XFERs ride the worker's pack/unpack
        #: lease, so the staged payload stays authoritative end to end.
        self.wire_codec = wire_codec
        #: cap each (holder, tile) fan-out so wide consumer sets drain as
        #: a dynamic relay tree: landed copies become sources themselves
        #: (and re-root for free when a relay node dies — the routing is
        #: re-evaluated per dispatch scan)
        self.broadcast = broadcast
        #: copy gathered result tiles off the master arena the moment
        #: their TAKECOPY lands (overlapped with the remaining compute)
        #: instead of in one barrier pass after the run
        self.stream_gather = stream_gather
        #: per-worker BLAS thread cap (machine model: threads_per_worker);
        #: None leaves the BLAS pool at its library default
        self.blas_threads = blas_threads
        #: session mode: workers + arenas + membership survive across
        #: ``execute()`` calls; resident tiles lost to churn raise
        #: ``ResidentTilesLost`` for the session's lineage-recompute path
        self.session = session
        if session and respawn_dead:
            # a respawned worker returns with an EMPTY arena but an
            # unchanged spec, which would hide retained-tile loss from
            # the session's home-vs-alive-nodes check
            raise ValueError("respawn_dead is not supported in session "
                             "mode; lost resident tiles recompute from "
                             "lineage on the survivors instead")
        #: set by a durable session (CMMSession with checkpoint_dir):
        #: called with a handle id when ChaosEvent(corrupt_tile=...) fires
        self.corrupt_tile_hook = None
        #: flight recorder: on by default (obs_bench gates the paired
        #: overhead under 5%); ``spans`` holds the last run's timeline
        #: (master + ingested worker spans) after execute()
        self.trace = trace
        #: nodes a previous run's drift report flagged slow: seeded into
        #: the membership detector so its straggler check fires on the
        #: first confirming sweep instead of waiting out the patience
        #: budget (drift_report(...).straggler_priors)
        self.straggler_priors = tuple(straggler_priors)
        self.spans: List = []
        #: per-node clock offsets from the cal handshake — persistent
        #: across runs/joins (each _spawn calibrates its incarnation)
        self._clock_offsets: Dict[int, float] = {}
        self._started = False
        self._broken = False
        self._run_msg = None
        self._ms: Optional[MembershipService] = None
        self._cur_spec: Optional[ClusterSpec] = None
        self.stats: Dict[str, object] = {}

    @property
    def current_spec(self) -> Optional[ClusterSpec]:
        """The membership-adjusted spec after the last run (session mode):
        dead nodes drained, joined nodes appended — what the session's
        engine must plan the NEXT run against."""
        return self._cur_spec

    # -- setup helpers --------------------------------------------------------
    def _derive_fill_origin(self, prog) -> Dict[int, str]:
        """INPUT leaves live on the master, generated leaves fill locally
        (mirrors ``CMMEngine._fill_origins`` without needing the root)."""
        return {uid: ("master" if n.op is Op.INPUT else "local")
                for uid, n in prog.leaf_nodes.items()}

    def _spawn(self, node: int, nthreads: int,
               mem_bytes: Optional[float] = None):
        """(Re)spawn the worker process for ``node`` under a fresh
        incarnation: fresh queues (a SIGKILLed predecessor may have died
        holding queue locks or with stale dispatches enqueued) and a
        fresh arena namespace (so leftover segments of the dead
        incarnation can never collide with new allocations)."""
        inc = next(self._incarnations)
        prefix = f"{self._prefix}i{inc}_"
        inq, outq = self._ctx.Queue(), self._ctx.Queue()
        p = self._ctx.Process(
            target=_node_worker,
            args=(node, inq, outq, self._g, self._tile, self._leaf_nodes,
                  self._dtypes, nthreads, prefix,
                  self._mcfg.heartbeat_interval_s, self.blas_threads,
                  mem_bytes, self._spill_dir, self.trace),
            daemon=True)
        p.start()
        self._procs[node] = p
        self._inqs[node] = inq
        self._outqs[node] = outq
        if self.trace:
            # calibrate this incarnation's clock against the master's
            # (the echo lands in handle()'s "cal" branch)
            inq.put(("cal", time.perf_counter()))
        if self._run_msg is not None:
            # session mode: hand the newcomer the CURRENT run's context
            # (graph + resident-leaf handle ids) — fork-inherited state
            # may predate it
            inq.put(self._run_msg)

    # -- the run --------------------------------------------------------------
    def execute(self, plan) -> np.ndarray:
        import multiprocessing as mp

        g: TaskGraph = plan.program.graph
        spec: Optional[ClusterSpec] = getattr(plan, "spec", None)
        if spec is None:
            raise ValueError("ClusterExecutor needs plan.spec")
        if self.session and self._broken:
            raise RuntimeError("session cluster executor is broken "
                               "(a previous run failed); open a new session")
        if self.session and self._started and spec != self._cur_spec:
            raise ValueError(
                "session cluster executor: the plan's spec does not match "
                "the membership-adjusted current_spec; re-plan against "
                "executor.current_spec")
        residency = getattr(plan, "residency", None)
        rsets = result_sets_of(g)
        #: RESIDENT task tid -> home node (pinned placement for replans)
        #: and home coverage per handle (loss detection on node death)
        resident_pins: Dict[int, int] = {}
        if residency is not None:
            for t in g:
                if t.kind is TaskKind.RESIDENT:
                    h = residency.handles[t.payload]
                    resident_pins[t.tid] = h.home.get(
                        (t.out.i, t.out.j), 0)
        sched: Schedule = plan.schedule
        n_joins = sum(1 for c in self.chaos if c.join_workers is not None)
        for c in self.chaos:
            if c.kill_node is not None:
                if c.kill_node == spec.master:
                    raise ValueError("cannot kill the master node")
                if not 0 <= c.kill_node < spec.n_nodes + n_joins:
                    raise ValueError(
                        f"kill_node={c.kill_node} is outside the "
                        f"{spec.n_nodes}-node spec (+{n_joins} joins)")
            if c.join_workers is not None and c.join_workers <= 0:
                raise ValueError("join needs at least one worker")
            if c.mem_squeeze is not None:
                if not 0 <= c.mem_squeeze < spec.n_nodes + n_joins:
                    raise ValueError(
                        f"mem_squeeze={c.mem_squeeze} is outside the "
                        f"{spec.n_nodes}-node spec (+{n_joins} joins)")
                if c.squeeze_bytes <= 0:
                    raise ValueError("mem_squeeze needs squeeze_bytes > 0")
            if c.alloc_fail is not None:
                if not 0 <= c.alloc_fail < spec.n_nodes + n_joins:
                    raise ValueError(
                        f"alloc_fail={c.alloc_fail} is outside the "
                        f"{spec.n_nodes}-node spec (+{n_joins} joins)")
                if c.alloc_fail_nth < 1:
                    raise ValueError("alloc_fail_nth must be >= 1")
            if c.corrupt_tile is not None and self.corrupt_tile_hook is None:
                raise ValueError(
                    "ChaosEvent(corrupt_tile=...) needs a durable session "
                    "(CMMSession(checkpoint_dir=...)) whose shards it can "
                    "corrupt")

        tm = self.timemodel or analytic_time_model()
        self._mcfg = self.membership_cfg or MembershipConfig()
        if not (self.session and self._started):
            method = self.mp_context or (
                "fork" if "fork" in mp.get_all_start_methods() else "spawn")
            self._ctx = mp.get_context(method)
            self._prefix = f"cmm{os.getpid()}_{next(_RUN_IDS)}e"
            self._incarnations = iter(range(1 << 30))
            self._spill_dir = run_spill_dir(self._prefix)
        self._g, self._tile = g, plan.tile
        # RESIDENT leaves stay master-side (workers resolve them against
        # their retained arena store via handle ids)
        self._leaf_nodes = {uid: n for uid, n in
                            plan.program.leaf_nodes.items()
                            if n.op is not Op.RESIDENT}
        self._dtypes = plan.program.dtypes
        if self.session:
            self._run_msg = ("run", g, plan.tile, self._leaf_nodes,
                             self._dtypes,
                             residency.resident_ids()
                             if residency is not None else {})
        origin = self._derive_fill_origin(plan.program)

        # -- value-version canonicalisation ---------------------------------
        # the scheduler may splice in regenerated-fill clones: several FILL
        # task ids producing the SAME tile from the same leaf payload.
        # Their outputs are bit-identical by construction, so version
        # bookkeeping treats each group as one canonical version (else a
        # value-equal rebind looks like a lost value and triggers a
        # needless lineage recovery).
        canon: Dict[int, int] = {}
        vgroup: Dict[int, Tuple[int, ...]] = {}
        fill_groups: Dict[Tuple[object, TileRef], List[int]] = \
            defaultdict(list)
        for t in g:
            if t.kind is TaskKind.FILL and t.out is not None:
                fill_groups[(t.payload, t.out)].append(t.tid)
        for members in fill_groups.values():
            c = min(members)
            for m in members:
                canon[m] = c
            vgroup[c] = tuple(sorted(members))

        def canon_of(tid: int) -> int:
            return canon.get(tid, tid)

        # -- static dataflow: data needs (ref, producer-version) per task --
        needs: Dict[int, List[Tuple[TileRef, int]]] = defaultdict(list)
        for t in g:
            for p in sorted(t.preds):
                po = g.tasks[p].out
                if po is None:
                    continue
                if po in t.ins or (t.out is not None and po == t.out):
                    needs[t.tid].append((po, canon_of(p)))

        # -- mutable control-plane state ------------------------------------
        cur_spec = spec
        master = spec.master
        #: persisted output tiles of this run: ref -> owning root uid.
        #: They are kept by the GC sweep and moved into the session store
        #: (worker ``retain`` op) at the end of the run.
        retained_refs: Dict[TileRef, int] = {}
        for _rs in rsets:
            if not _rs.gather:
                for _r in _rs.tiles:
                    retained_refs[_r] = _rs.uid
        #: a resident-input loss pends an orderly abort (session retries
        #: after lineage recompute); never set outside session mode
        pending_abort: List[Optional[ResidentTilesLost]] = [None]
        assigned = {tid: p.node for tid, p in sched.placements.items()}
        missing = [tid for tid in g.tasks if tid not in assigned]
        if missing:
            raise ValueError(f"schedule misses placements for "
                             f"{missing[:5]}")
        cur_place: Dict[int, Placement] = dict(sched.placements)
        deps_left = {t.tid: len(t.preds) for t in g}
        completed: Set[int] = set()
        dispatched: Dict[int, Set[int]] = defaultdict(set)
        exec_nodes: Dict[int, int] = {}
        node_pids: Dict[int, int] = {}
        #: (node, ref) -> (version tid, segment name, dtype str): the
        #: master's view of every worker arena binding
        avail: Dict[Tuple[int, TileRef], Tuple[int, str, str]] = {}
        write_busy: Set[Tuple[int, TileRef]] = set()
        src_busy: Dict[Tuple[int, TileRef], int] = defaultdict(int)
        xfer_inflight: Dict[Tuple[int, TileRef], Tuple[int, int]] = {}
        xfer_retries: Dict[Tuple[int, int], int] = defaultdict(int)
        #: bounded retry-with-backoff for the hardened transfer path:
        #: (node, ref) / tid -> monotonic time before which no new
        #: attempt is issued (exponential in the attempt count, capped)
        xfer_retry_at: Dict[Tuple[int, TileRef], float] = {}
        task_retry_at: Dict[int, float] = {}
        task_retries: Dict[int, int] = defaultdict(int)
        #: leased transfer path (bounded-arena sources and every
        #: compressed edge): the holder pins the tile ("hold") or pins +
        #: stages the encoded payload ("pack") until the master releases
        #: it.  pending_lease holds consumers waiting on the holder's
        #: ack — one entry per request, each ack dispatches exactly one
        #: (acks and worker-side pins are one-to-one, so the release
        #: count always balances).  leases maps a dispatched XFER's
        #: destination back to the (holder, codec) pin it must release.
        pending_lease: Dict[Tuple[int, TileRef],
                            List[Tuple[int, int, str]]] = defaultdict(list)
        leases: Dict[Tuple[int, TileRef], Tuple[int, str]] = {}
        #: per-(holder, tile) concurrent-reader cap — beyond it, waiting
        #: consumers defer until a landed copy can serve as a relay source
        relay_cap = (BCAST_MIN_FANOUT - 1) if self.broadcast else (1 << 30)
        #: streamed-gather staging: result tiles copied off the master
        #: arena as their TAKECOPY lands (master arena must be unbounded —
        #: a bounded one could evict the segment mid-attach)
        gather_refs: Dict[TileRef, int] = {}
        for _rs in rsets:
            if _rs.gather:
                for _r in _rs.tiles:
                    gather_refs[_r] = _rs.uid
        gstreamed: Dict[TileRef, np.ndarray] = {}
        gather_t_first: List[Optional[float]] = [None]
        t_exec0 = time.perf_counter()
        #: remaining XFER requests to poison (ChaosEvent.drop_xfer)
        chaos_drop = [0]
        spec_pending: Dict[int, int] = {}        # speculative node per tid
        #: (node, ref) slots whose segment was evicted to the spill tier:
        #: the binding stays in ``avail`` (the VALUE is still secured by
        #: that node) but cannot serve as an XFER source until the master
        #: faults it back in
        spilled: Set[Tuple[int, TileRef]] = set()
        fault_pending: Set[Tuple[int, TileRef]] = set()
        #: retention acks in flight: (hid, i, j) -> (root uid, ref) — the
        #: worker's retain may fault the tile in from spill (fresh segment
        #: name), so the session store is only updated from the ack
        pending_retain: Dict[Tuple[int, int, int], Tuple[int, TileRef]] = {}
        ready: Set[int] = {t.tid for t in g.sources()}
        #: the sweep is O(tasks), so its cadence scales with graph size:
        #: at most ~8 periodic sweeps per run (replans add their own) —
        #: peak arena memory traded against master-loop dispatch latency
        gc_every = max(self.gc_interval, len(g) // 8)
        #: dispatched-not-done instances per node: dispatch is LATE-BOUND
        #: (a node's queue holds at most ~2x its slots) so most of the
        #: graph stays in the replannable frontier — a flooded queue
        #: would pin work to a node the moment it became ready and leave
        #: a joining node nothing to take over
        inflight: Dict[int, int] = defaultdict(int)

        def depth_cap(node: int) -> int:
            return 2 * max(1, cur_spec.workers_at(node)) + 1
        fired = [False] * len(self.chaos)
        # unified metrics registry (replaces the ad-hoc defaultdict):
        # inc() is the atomic increment path, frozen_view() the read-only
        # dict the stats consumers have always read
        cnt = MetricsRegistry()
        for _k in ("deaths", "joins", "respawns", "straggles",
                   "recoveries", "replans", "recovered_tasks",
                   "speculated", "spec_wins", "dup_done", "xfers",
                   "xfer_bytes", "wire_bytes", "xfers_compressed",
                   "relay_hops", "leases", "leases_released_on_death",
                   "xfer_retries", "task_retries", "chaos_dropped_xfers",
                   "gather_streamed_tiles", "squeezes", "tiles_lost",
                   "frees", "dup_errors", "alloc_fails_armed"):
            cnt.inc(_k, 0)
        # flight recorder: master tracer (node -1) + the persistent
        # per-incarnation clock offsets the cal handshake maintains
        tracer = Tracer(node=-1, enabled=self.trace)
        offsets = self._clock_offsets
        recovery_seconds = [0.0]
        total = len(g)

        if self.session and self._started:
            ms = self._ms
            # hand every surviving worker the new run's context; drain
            # idle-period heartbeats so they don't count as progress
            for n in ms.alive_nodes():
                if self._inqs.get(n) is not None:
                    self._inqs[n].put(self._run_msg)
            for n in ms.alive_nodes():
                q = self._outqs.get(n)
                while q is not None:
                    try:
                        msg = q.get_nowait()
                    except _queue.Empty:
                        break
                    if msg[0] == "hb":
                        ms.heartbeat(msg[1])
        else:
            ms = MembershipService(range(spec.n_nodes), master=master,
                                   cfg=self._mcfg)
            # start the resource tracker BEFORE forking workers so every
            # worker shares this process's tracker: a SIGKILLed worker's
            # segment registrations then land where the master's
            # post-mortem unregister (see _reap_segments) can retract them
            # — otherwise each worker lazily spawns its own tracker, which
            # outlives the kill and warns about "leaked" segments the
            # master already reaped
            from multiprocessing import resource_tracker
            resource_tracker.ensure_running()
            self._procs: Dict[int, object] = {}
            self._inqs: Dict[int, object] = {}
            self._outqs: Dict[int, object] = {}
            for n in range(spec.n_nodes):
                self._spawn(n, self.workers_per_node or spec.workers_at(n),
                            spec.mem_at(n))
            if self.straggler_priors:
                # a previous run's drift report flagged these nodes slow:
                # arm the detector so one confirming sweep fires STRAGGLE
                ms.seed_straggler_priors(self.straggler_priors)
            self._ms = ms
            self._started = True

        # -- control-plane actions ------------------------------------------
        def alive(n: int) -> bool:
            return ms.is_alive(n)

        def pick_holder(version: int, ref: TileRef) -> Optional[int]:
            """Deterministic live holder of this tile version whose copy
            is safe to read (no in-progress write on that arena slot and
            not currently evicted to the spill tier).  Among candidates
            the least-read one wins, so wide fan-outs spread over landed
            copies — the dynamic half of the relay tree."""
            best = None
            for k in ms.alive_nodes():
                ent = avail.get((k, ref))
                if ent is not None and ent[0] == version \
                        and (k, ref) not in write_busy \
                        and (k, ref) not in spilled:
                    load = src_busy.get((k, ref), 0)
                    if best is None or load < best[0]:
                        best = (load, k)
            return None if best is None else best[1]

        def wire_codec_for(nbytes: int, src_n: int, dst_n: int) -> str:
            """Per-edge codec choice: forced by ``wire_codec``, else
            priced against the TimeModel's fitted compression terms
            (raw unless the model predicts encode + smaller-payload
            transfer beats the raw transfer on this link)."""
            if src_n == dst_n:
                return "raw"
            if self.wire_codec is not None:
                return self.wire_codec
            return choose_wire_codec(
                nbytes, cur_spec.bandwidth(src_n, dst_n), tm)

        def release_pin(holder: int, ref: TileRef, codec: str) -> None:
            """Drop one worker-side lease pin (hold or staged pack)."""
            if ms.is_alive(holder) and self._inqs.get(holder) is not None:
                self._inqs[holder].put(("release", ref) if codec == "raw"
                                       else ("unpack", ref))

        def dispatch_leased(holder: int, ref: TileRef, ver: int,
                            dstn: int, codec: str, sname: str, sdt: str,
                            comp_nbytes: int, raw_crc) -> None:
            """The holder acked one lease pin: forward the XFER to its
            consumer — or release the pin right away if the consumer
            departed (or was re-routed) while the ack was in flight.
            That immediate release is the mid-copy-death fix: a dead
            consumer must never strand a source pin on a bounded arena."""
            if not alive(dstn) \
                    or xfer_inflight.get((dstn, ref)) != (ver, holder):
                release_pin(holder, ref, codec)
                cnt.inc("leases_released_on_death")
                return
            if chaos_drop[0] > 0:
                chaos_drop[0] -= 1
                cnt.inc("chaos_dropped_xfers")
                sname = f"{self._prefix}chaos_dropped"
            leases[(dstn, ref)] = (holder, codec)
            if codec == "raw":
                cnt.inc("wire_bytes", ref.bytes)
                self._inqs[dstn].put(("xfer", ver, ref, sname, sdt))
            else:
                cnt.inc("wire_bytes", comp_nbytes)
                cnt.inc("xfers_compressed")
                self._inqs[dstn].put(("xfer", ver, ref, sname, sdt,
                                      codec, comp_nbytes, raw_crc))

        def fail_pending_lease(n: int, ref: TileRef,
                               bump_retries: bool) -> None:
            """The holder cannot serve (hold_fail / tile_lost / death):
            un-book every waiting consumer so the dispatch scan re-routes
            them — no xfer_fail will ever arrive for these."""
            for (ver, dstn, _c) in pending_lease.pop((n, ref), []):
                write_busy.discard((dstn, ref))
                ent = xfer_inflight.get((dstn, ref))
                if ent is not None and ent[1] == n:
                    del xfer_inflight[(dstn, ref)]
                if src_busy.get((n, ref), 0) > 0:
                    src_busy[(n, ref)] -= 1
                if bump_retries:
                    xfer_retries[(ver, dstn)] += 1
                    tries = xfer_retries[(ver, dstn)]
                    cnt.inc("xfer_retries")
                    if tries > self._mcfg.xfer_max_retries:
                        raise MemoryBudgetExceeded(
                            n, 0, cur_spec.mem_at(n) or 0,
                            msg=f"node {n} could not pin {ref} for an "
                                f"XFER lease after {tries} attempts "
                                f"(arena too tight to hold the source)")
                    xfer_retry_at[(dstn, ref)] = time.monotonic() + min(
                        self._mcfg.retry_backoff_s * (2 ** (tries - 1)),
                        2.0)

        def request_fault(n: int, ref: TileRef) -> None:
            """Ask node ``n`` to fault a spilled tile back into its hot
            tier; the ``unspill`` ack restores the fresh segment name."""
            if (n, ref) not in fault_pending \
                    and self._inqs.get(n) is not None:
                fault_pending.add((n, ref))
                self._inqs[n].put(("fault", ref))

        def value_secured(v: int) -> bool:
            """Is canonical version ``v`` guaranteed to (re)appear without
            intervention?  Bound in a live arena (even mid-write), being
            produced by a live in-flight instance, or owed by a group
            member that has not run yet."""
            ref = g.tasks[v].out
            if ref is None:
                return True
            if any(avail.get((k, ref), (None,))[0] == v
                   for k in ms.alive_nodes()):
                return True
            for m in vgroup.get(v, (v,)):
                if m not in completed:
                    return True
                if any(alive(k) for k in dispatched[m]):
                    return True
            return False

        def try_dispatch(tid: int, node: int,
                         prefetch_only: bool = False) -> bool:
            """Dispatch one instance of ``tid`` on ``node`` if its inputs
            are bound there; otherwise request the missing XFERs.  Every
            write to a (node, ref) arena slot is exclusive.
            ``prefetch_only`` stages inputs without dispatching (used for
            tasks beyond the node's in-flight depth cap)."""
            t = g.tasks[tid]
            waiting = False
            for (ref, p) in needs[tid]:
                ent = avail.get((node, ref))
                if ent is not None and ent[0] == p:
                    continue
                waiting = True
                if (node, ref) in write_busy:
                    continue                  # a write is already in flight
                if time.monotonic() < xfer_retry_at.get((node, ref), 0.0):
                    continue                  # backing off a failed XFER
                holder = pick_holder(p, ref)
                if holder is None or holder == node:
                    if not value_secured(p):
                        # value lost outside a death event (defensive):
                        # recover it through the normal lineage path.
                        # (a merely write-busy holder is NOT lost — the
                        # copy becomes readable when its write completes)
                        replan({p})
                        return False
                    if holder is None:
                        # every live copy may be cold in the spill tier —
                        # fault one back in so a later scan can route it
                        for k in ms.alive_nodes():
                            e2 = avail.get((k, ref))
                            if e2 is not None and e2[0] == p \
                                    and (k, ref) in spilled:
                                request_fault(k, ref)
                                break
                    continue                  # value not yet obtainable
                if src_busy.get((holder, ref), 0) >= relay_cap:
                    # relay fan-out cap: every landed copy becomes a
                    # source, so deferring here turns an N-wide unicast
                    # burst into a tree that widens each scan
                    continue
                codec = wire_codec_for(ref.bytes, holder, node)
                if exec_nodes.get(p) not in (None, holder):
                    cnt.inc("relay_hops")
                if codec != "raw" or cur_spec.mem_at(holder) is not None:
                    # leased path: the holder pins the source (and, when
                    # compressed, stages the encoded payload) before the
                    # consumer is told where to copy from — a bounded
                    # arena can then never evict it mid-copy
                    pending_lease[(holder, ref)].append((p, node, codec))
                    self._inqs[holder].put(
                        ("pack", ref, codec) if codec != "raw"
                        else ("hold", ref))
                    cnt.inc("leases")
                else:
                    sname = avail[(holder, ref)][1]
                    sdt = avail[(holder, ref)][2]
                    if chaos_drop[0] > 0:
                        # fault injection: poison the request's source
                        # segment so the destination worker reports
                        # xfer_fail and the bounded-backoff retry
                        # re-issues it for real
                        chaos_drop[0] -= 1
                        cnt.inc("chaos_dropped_xfers")
                        sname = f"{self._prefix}chaos_dropped"
                    self._inqs[node].put(("xfer", p, ref, sname, sdt))
                    cnt.inc("wire_bytes", ref.bytes)
                write_busy.add((node, ref))
                xfer_inflight[(node, ref)] = (p, holder)
                src_busy[(holder, ref)] += 1
                cnt.inc("xfers")
                cnt.inc("xfer_bytes", ref.bytes)
            if waiting or prefetch_only:
                return False
            if t.out is not None:
                if (node, t.out) in write_busy:
                    return False
                if t.kind in _CHAIN_KINDS and \
                        src_busy.get((node, t.out), 0) > 0:
                    return False              # an XFER is reading the chain
                write_busy.add((node, t.out))
            self._inqs[node].put(("task", tid))
            dispatched[tid].add(node)
            inflight[node] += 1
            return True

        def scan_dispatch() -> None:
            for tid in sorted(ready):
                if tid in completed or dispatched[tid]:
                    ready.discard(tid)        # an instance beat us to it
                    continue
                node = assigned[tid]
                if not alive(node):
                    continue                  # replan is imminent
                if time.monotonic() < task_retry_at.get(tid, 0.0):
                    continue                  # backing off a failed dispatch
                over = inflight[node] >= depth_cap(node)
                if try_dispatch(tid, node, prefetch_only=over):
                    ready.discard(tid)
            for tid in sorted(spec_pending):
                node = spec_pending[tid]
                if tid in completed or not alive(node):
                    spec_pending.pop(tid, None)
                    continue
                if node in dispatched[tid]:
                    continue
                if inflight[node] >= depth_cap(node):
                    continue
                if try_dispatch(tid, node):
                    cnt.inc("speculated")

        def run_gc() -> None:
            """Mark-and-sweep over arena bindings: a (node, ref) binding
            stays only while some not-completed task still needs that
            version, a write/XFER is touching it, or it backs an
            ungathered result tile.  Lineage makes over-freeing safe but
            expensive — this never frees a value the current plan reads."""
            if not self.free_buffers:
                return
            live_nodes = set(ms.alive_nodes())
            keep: Set[Tuple[int, TileRef]] = set(write_busy)
            for (dst, ref), (_v, src) in xfer_inflight.items():
                keep.add((dst, ref))
                keep.add((src, ref))
            for t in g:
                # a completed task may still have a LOSING duplicate
                # instance in flight (first-writer-wins): its inputs at
                # that node must survive until the instance reports, or
                # the worker's arena lookup explodes mid-execution
                if t.tid in completed and not dispatched[t.tid]:
                    continue
                for (ref, p) in needs[t.tid]:
                    for k in live_nodes:
                        ent = avail.get((k, ref))
                        if ent is not None and ent[0] == p:
                            keep.add((k, ref))
                for n in dispatched[t.tid]:
                    if t.out is not None:
                        keep.add((n, t.out))
            # gather holds must cover EVERY gathered root of a multi-root
            # program (g.result_tiles is only the first one)
            for rs_ in rsets:
                if not rs_.gather:
                    continue
                for r in rs_.tiles:
                    for k in live_nodes:
                        if (k, r) in avail:
                            keep.add((k, r))
            for r in retained_refs:
                # persisted outputs: every live copy survives until the
                # end-of-run retention picks its home
                for k in live_nodes:
                    if (k, r) in avail:
                        keep.add((k, r))
            for key in [k for k in avail if k not in keep]:
                n, ref = key
                del avail[key]
                if alive(n):
                    self._inqs[n].put(("free", ref))
                    cnt.inc("frees")

        def replan(resurrect_seed: Set[int] = frozenset()) -> None:
            """Resurrection closure + incremental frontier re-plan —
            the observe->re-plan half of the loop, run on every
            membership event (and on a detected lost value)."""
            t0 = time.perf_counter()
            resurrected: Set[int] = set()

            def ensure(v: int) -> None:
                """Canonical version ``v`` must be obtainable: if every
                producer ran and no live copy/instance remains, the
                canonical producer is resurrected — and its own inputs
                secured transitively (the lineage closure)."""
                if v in resurrected or value_secured(v):
                    return
                completed.discard(v)
                resurrected.add(v)
                for (_ref, q) in needs[v]:
                    ensure(q)

            for v in sorted(resurrect_seed):
                ensure(v)
            for tid in [t.tid for t in g if t.tid not in completed]:
                for (_ref, p) in needs[tid]:
                    ensure(p)
            cnt.inc("recovered_tasks", len(resurrected))

            for tid in g.tasks:
                if tid not in completed:
                    deps_left[tid] = sum(1 for p in g.tasks[tid].preds
                                         if p not in completed)
            live_disp = {tid for tid, insts in dispatched.items()
                         if tid not in completed
                         and any(alive(k) for k in insts)}
            frontier = [tid for tid in g.tasks
                        if tid not in completed and tid not in live_disp]
            done_pl: Dict[int, Placement] = {}
            for tid in g.tasks:
                if tid in completed or tid in live_disp:
                    p = cur_place[tid]
                    out = g.tasks[tid].out
                    if tid in completed and out is not None \
                            and not alive(p.node):
                        holder = pick_holder(canon_of(tid), out)
                        if holder is not None:
                            p = Placement(holder, 0, p.start, p.finish)
                    done_pl[tid] = p
            if frontier:
                new_sched = replan_frontier(
                    g, cur_spec, tm, done_pl, frontier,
                    fill_origin=origin, cost=CostCache(tm, cur_spec),
                    pinned=resident_pins or None)
                for tid in frontier:
                    cur_place[tid] = new_sched.placements[tid]
                    assigned[tid] = new_sched.placements[tid].node
            ready.clear()
            ready.update(tid for tid in frontier if deps_left[tid] == 0)
            cnt.inc("replans")
            run_gc()
            recovery_seconds[0] += time.perf_counter() - t0
            if self.trace:
                tracer.add(Span("REPLAN", "REPLAN", -1, 0, t0,
                                time.perf_counter() - t0,
                                {"resurrected": len(resurrected),
                                 "frontier": len(frontier)}))

        def on_death(n: int) -> None:
            nonlocal cur_spec
            cnt.inc("deaths")
            survivors = ms.alive_nodes()
            if not self.respawn_dead and \
                    len(survivors) < self._mcfg.min_nodes:
                raise RuntimeError(
                    f"node {n} died leaving {len(survivors)} node(s), "
                    f"below the configured floor "
                    f"min_nodes={self._mcfg.min_nodes}; aborting the run")
            proc = self._procs.get(n)
            if proc is not None and proc.is_alive():
                proc.terminate()
            # the master's view of node n is gone: arena bindings, write
            # locks, transfers to/from it
            for key in [k for k in avail if k[0] == n]:
                del avail[key]
            for key in [k for k in write_busy if k[0] == n]:
                write_busy.discard(key)
            for key in [k for k in src_busy if k[0] == n]:
                del src_busy[key]
            for (dst, ref) in list(xfer_inflight):
                ver, src = xfer_inflight[(dst, ref)]
                if dst == n:
                    del xfer_inflight[(dst, ref)]
                    if (src, ref) in src_busy:
                        src_busy[(src, ref)] -= 1
                # src == n: the destination worker reports xfer_fail and
                # the retry path re-routes from a surviving holder
            # the dead consumer's leased XFERs will never ack: release
            # their source pins NOW or the holders' bounded arenas keep
            # the tiles unevictable forever (the mid-copy-death leak)
            for (dst, ref) in [k for k in leases if k[0] == n]:
                holder, codec = leases.pop((dst, ref))
                if alive(holder):
                    release_pin(holder, ref, codec)
                    cnt.inc("leases_released_on_death")
            for key in [k for k in leases if leases[k][0] == n]:
                del leases[key]   # holder died: its pins died with it
            # pending leases ON the dead holder get no ack and no
            # xfer_fail — un-book their waiters so the scan re-routes
            for (hn, ref) in [k for k in pending_lease if k[0] == n]:
                fail_pending_lease(hn, ref, bump_retries=False)
            for tid in list(dispatched):
                dispatched[tid].discard(n)
            inflight[n] = 0
            for tid in [t for t, k in spec_pending.items() if k == n]:
                del spec_pending[tid]
            for key in [k for k in spilled if k[0] == n]:
                spilled.discard(key)
            for key in [k for k in fault_pending if k[0] == n]:
                fault_pending.discard(key)
            # the dead node's failure episodes end with it: drop its
            # retry counts/backoffs (no future attempt targets it)
            for key in [k for k in xfer_retries if k[1] == n]:
                del xfer_retries[key]
            for key in [k for k in xfer_retry_at if k[0] == n]:
                del xfer_retry_at[key]
            self._reap_segments(n)
            self._procs[n] = None
            self._inqs[n] = None
            self._outqs[n] = None
            if self.respawn_dead:
                self._spawn(n, self.workers_per_node
                            or cur_spec.workers_at(n), cur_spec.mem_at(n))
                ms.add_node(n)
                cnt.inc("respawns")
            else:
                cur_spec = cur_spec.without_node(n)
            # resident-input tiles homed on the dead node are gone (a
            # respawned worker comes back with an EMPTY arena): they are
            # not recomputable within THIS graph — they are its *inputs* —
            # but they ARE recomputable roots of their own lineage.  Abort
            # the run in an orderly way; the session re-derives the lost
            # handles from lineage and retries (bit-identical, tasks are
            # deterministic).
            if residency is not None:
                lost = {h.hid for h in residency.handles.values()
                        if any(home == n for home in h.home.values())}
                if pending_abort[0] is not None:
                    lost |= set(pending_abort[0].hids)
                if lost:
                    pending_abort[0] = ResidentTilesLost(
                        sorted(lost),
                        f"node {n} died holding resident tiles of "
                        f"handles {sorted(lost)}")
                    return
            replan()

        def on_join(workers: int, slowdown: float) -> None:
            nonlocal cur_spec
            node = cur_spec.n_nodes
            cur_spec = cur_spec.with_node(workers, slowdown)
            base_slowdown[node] = float(slowdown)
            self._spawn(node, self.workers_per_node or workers,
                        cur_spec.mem_at(node))
            ms.add_node(node)
            cnt.inc("joins")
            replan()

        #: each node's un-penalised slowdown, for idempotent straggler
        #: re-pricing (bump to base*factor, restore to base on recovery
        #: — never compound across repeated STRAGGLE events)
        base_slowdown = {n: spec.node_slowdown(n)
                         for n in range(spec.n_nodes)}

        def on_straggle(n: int) -> None:
            nonlocal cur_spec
            cnt.inc("straggles")
            if self.speculate:
                others = [k for k in ms.alive_nodes() if k != n]
                if others:
                    load = {k: sum(1 for s in dispatched.values()
                                   if k in s) for k in others}
                    for tid in sorted(t for t, insts in dispatched.items()
                                      if n in insts and t not in completed):
                        if g.tasks[tid].kind is TaskKind.TAKECOPY:
                            continue          # pinned to the master
                        tgt = min(others, key=lambda k: (load[k], k))
                        spec_pending[tid] = tgt
                        load[tgt] += 1
            # reprice the straggler so the frontier drains away from it
            cur_spec = cur_spec.with_slowdown(
                n, base_slowdown.get(n, 1.0) * self._mcfg.straggler_factor)
            replan()

        def on_recover(n: int) -> None:
            nonlocal cur_spec
            if not alive(n):
                return
            cnt.inc("recoveries")
            cur_spec = cur_spec.with_slowdown(n, base_slowdown.get(n, 1.0))
            replan()

        def fire_chaos() -> None:
            nonlocal cur_spec
            for i, c in enumerate(self.chaos):
                if fired[i] or len(completed) < c.after_done:
                    continue
                if c.kill_node is not None:
                    proc = self._procs.get(c.kill_node)
                    if proc is None or not proc.pid:
                        # target not spawned yet (kill of a node whose
                        # join has not fired) — stay armed, retry on the
                        # next completion instead of dropping the kill
                        continue
                fired[i] = True
                if c.kill_node is not None:
                    proc = self._procs.get(c.kill_node)
                    if proc is not None and proc.pid:
                        os.kill(proc.pid, signal.SIGKILL)
                if c.throttle_node is not None \
                        and alive(c.throttle_node):
                    self._inqs[c.throttle_node].put(
                        ("throttle", c.throttle_seconds))
                if c.join_workers is not None:
                    on_join(c.join_workers, c.join_slowdown)
                if c.flag_straggler is not None \
                        and alive(c.flag_straggler):
                    on_straggle(c.flag_straggler)
                if c.drop_xfer is not None:
                    # poison the source segment name of the next N
                    # cross-node transfers: the destination worker fails
                    # to attach, reports xfer_fail, and the bounded
                    # retry path re-requests the tile for real
                    chaos_drop[0] += int(c.drop_xfer)
                if c.mem_squeeze is not None and alive(c.mem_squeeze):
                    # shrink the node's arena budget mid-run: the worker
                    # evicts down to it; the spec change flows to the
                    # session's next plan via current_spec
                    self._inqs[c.mem_squeeze].put(
                        ("squeeze", int(c.squeeze_bytes)))
                    cur_spec = cur_spec.with_mem(
                        c.mem_squeeze, float(c.squeeze_bytes))
                    cnt.inc("squeezes")
                if c.alloc_fail is not None and alive(c.alloc_fail):
                    self._inqs[c.alloc_fail].put(
                        ("alloc_fail", int(c.alloc_fail_nth)))
                    cnt.inc("alloc_fails_armed")
                if c.corrupt_tile is not None:
                    self.corrupt_tile_hook(c.corrupt_tile)
                if c.kill_master:
                    # full-cluster crash: SIGKILL every worker FIRST
                    # (they are daemonic children — a parent SIGKILL
                    # alone leaves them running), then the master
                    # itself; nothing gets to flush or clean up, which
                    # is exactly the failure durable sessions recover
                    for proc in self._procs.values():
                        if proc is not None and proc.pid \
                                and proc.is_alive():
                            os.kill(proc.pid, signal.SIGKILL)
                    os.kill(os.getpid(), signal.SIGKILL)

        def handle(msg) -> bool:
            """Process one worker message; returns True when it counts
            as forward progress (heartbeats do NOT — a wedged run with
            idle-but-alive workers must still trip the stall watchdog)."""
            kind = msg[0]
            if kind == "done":
                _, n, tid, seg, dt, pid, dur, spans = msg
                tracer.ingest(spans, offsets.get(n, 0.0))
                cnt.observe("task_seconds", dur)
                ms.record_task(n, dur)
                node_pids[n] = pid
                t = g.tasks[tid]
                if t.out is not None:
                    write_busy.discard((n, t.out))
                    if seg is not None:
                        avail[(n, t.out)] = (canon_of(tid), seg, dt)
                dispatched[tid].discard(n)
                inflight[n] -= 1
                if tid in completed:
                    cnt.inc("dup_done")      # first-writer-wins: a late
                    return True               # duplicate only adds a copy
                completed.add(tid)
                exec_nodes[tid] = n
                # a successful completion ends this task's failure
                # episode: reset its retry budget so a LATER unrelated
                # fault gets the full allowance again
                task_retries.pop(tid, None)
                task_retry_at.pop(tid, None)
                if t.kind is TaskKind.TAKECOPY and n == master \
                        and seg is not None and t.out in gather_refs \
                        and t.out not in gstreamed and self.stream_gather \
                        and cur_spec.mem_at(master) is None \
                        and (master, t.out) not in spilled:
                    # streamed gather: assemble the result while the rest
                    # of the run still computes (unbounded master arena
                    # only — a bounded one could evict mid-attach)
                    try:
                        sh = _attach_shm(seg)
                        try:
                            view = np.ndarray(t.out.shape,
                                              dtype=np.dtype(dt),
                                              buffer=sh.buf)
                            gstreamed[t.out] = view.copy()
                        finally:
                            sh.close()
                        cnt.inc("gather_streamed_tiles")
                        if gather_t_first[0] is None:
                            gather_t_first[0] = \
                                time.perf_counter() - t_exec0
                    except FileNotFoundError:  # pragma: no cover — the
                        pass                   # barrier pass still runs
                if spec_pending.pop(tid, None) == n:
                    cnt.inc("spec_wins")
                for s in sorted(t.succs):
                    deps_left[s] -= 1
                    if deps_left[s] == 0 and s not in completed \
                            and not dispatched[s]:
                        ready.add(s)
                if len(completed) % gc_every == 0:
                    run_gc()
                fire_chaos()
            elif kind == "xfer_done":
                _, n, version, ref, seg, dt, spans = msg
                tracer.ingest(spans, offsets.get(n, 0.0))
                write_busy.discard((n, ref))
                ent = xfer_inflight.pop((n, ref), None)
                if ent is not None and (ent[1], ref) in src_busy:
                    src_busy[(ent[1], ref)] -= 1
                lease = leases.pop((n, ref), None)
                if lease is not None:
                    release_pin(lease[0], ref, lease[1])
                # the copy landed: close this edge's failure episode so
                # the NEXT fault on it starts from a fresh retry budget
                xfer_retries.pop((version, n), None)
                xfer_retry_at.pop((n, ref), None)
                avail[(n, ref)] = (version, seg, dt)
            elif kind == "xfer_fail":
                _, n, version, ref, tb = msg
                write_busy.discard((n, ref))
                ent = xfer_inflight.pop((n, ref), None)
                if ent is not None and (ent[1], ref) in src_busy:
                    src_busy[(ent[1], ref)] -= 1
                lease = leases.pop((n, ref), None)
                if lease is not None:
                    # drop the pin BEFORE the retry re-requests: the
                    # redispatch takes a fresh lease (possibly from a
                    # different holder), so the old one must not linger
                    release_pin(lease[0], ref, lease[1])
                xfer_retries[(version, n)] += 1
                tries = xfer_retries[(version, n)]
                cnt.inc("xfer_retries")
                if tries > self._mcfg.xfer_max_retries:
                    if "ArenaOverflow" in tb:
                        raise MemoryBudgetExceeded(
                            n, 0, cur_spec.mem_at(n) or 0,
                            msg=f"node {n} arena overflow receiving XFER "
                                f"of {ref} after {tries} attempts:\n{tb}")
                    raise RuntimeError(
                        f"XFER of {ref} (version {version}) to node {n} "
                        f"failed {tries} times (xfer_max_retries="
                        f"{self._mcfg.xfer_max_retries}):\n{tb}")
                # bounded exponential backoff before the dispatch scan
                # re-requests the tile — from ANY live holder, so a
                # vanished or corrupted source re-routes instead of
                # hammering the same copy
                xfer_retry_at[(n, ref)] = time.monotonic() + min(
                    self._mcfg.retry_backoff_s * (2 ** (tries - 1)), 2.0)
            elif kind == "held":
                # one lease pin granted (the hold may have faulted the
                # tile hot under a fresh segment name — rebind it)
                _, n, ref, seg, dt = msg
                ent0 = avail.get((n, ref))
                if ent0 is not None:
                    avail[(n, ref)] = (ent0[0], seg, dt)
                spilled.discard((n, ref))
                entries = pending_lease.get((n, ref))
                if entries:
                    ver, dstn, _c = entries.pop(0)
                    if not entries:
                        del pending_lease[(n, ref)]
                    dispatch_leased(n, ref, ver, dstn, "raw", seg, dt,
                                    0, None)
                else:
                    # every waiter failed over while this ack was in
                    # flight — the pin has no consumer, drop it
                    release_pin(n, ref, "raw")
            elif kind == "packed":
                _, n, ref, sname, sdt, codec, comp_nbytes, raw_crc = msg
                entries = pending_lease.get((n, ref))
                if entries:
                    ver, dstn, _c = entries.pop(0)
                    if not entries:
                        del pending_lease[(n, ref)]
                    dispatch_leased(n, ref, ver, dstn, codec, sname, sdt,
                                    comp_nbytes, raw_crc)
                else:
                    release_pin(n, ref, codec)
            elif kind == "hold_fail":
                # the holder's arena is too tight to pin the source right
                # now (no pin was taken): back the waiters off and let
                # the dispatch scan re-route them
                _, n, ref = msg
                fail_pending_lease(n, ref, bump_retries=True)
            elif kind == "spill":
                spilled.add((msg[1], msg[2]))
            elif kind == "unspill":
                _, n, ref, sname, dt = msg
                ent = avail.get((n, ref))
                if ent is not None:
                    # the fault-in rebinds under a fresh segment name;
                    # the version is unchanged (spill is bit-copying)
                    avail[(n, ref)] = (ent[0], sname, dt)
                spilled.discard((n, ref))
                fault_pending.discard((n, ref))
            elif kind == "tile_lost":
                # a spill-tier miss or CRC failure destroyed this copy;
                # degrade to lineage recompute instead of failing the run
                _, n, ref, tb = msg
                spilled.discard((n, ref))
                fault_pending.discard((n, ref))
                fail_pending_lease(n, ref, bump_retries=False)
                ent = avail.pop((n, ref), None)
                cnt.inc("tiles_lost")
                if ent is not None and not value_secured(ent[0]):
                    replan({ent[0]})
            elif kind == "retained":
                _, n, key, sname, dt = msg
                ent = pending_retain.pop(key, None)
                if ent is not None and residency is not None:
                    uid, r = ent
                    residency.retain_seg(uid, r.i, r.j, n, sname, dt)
            elif kind == "hb":
                ms.heartbeat(msg[1])
                node_pids.setdefault(msg[1], msg[2])
                if len(msg) > 3:
                    # idle-period span flush piggybacked on the heartbeat
                    tracer.ingest(msg[3], offsets.get(msg[1], 0.0))
                return False
            elif kind == "cal":
                # worker clock echo: NTP-style midpoint offset, mapping
                # that incarnation's span timestamps onto the master clock
                offsets[msg[1]] = estimate_clock_offset(
                    msg[2], msg[3], time.perf_counter())
                return False
            elif kind == "error":
                if msg[2] in completed:
                    # a losing duplicate instance crashed after the
                    # winner already produced the value — the run does
                    # not depend on it
                    lt = g.tasks[msg[2]]
                    if lt.out is not None:
                        write_busy.discard((msg[1], lt.out))
                    dispatched[msg[2]].discard(msg[1])
                    inflight[msg[1]] -= 1
                    cnt.inc("dup_errors")
                    return True
                tid = msg[2]
                t = g.tasks.get(tid)
                task_retries[tid] += 1
                tries = task_retries[tid]
                # in-place accumulate chains (ADDMUL/...) mutate their
                # output buffer as they run: a crashed instance may have
                # landed a partial update, so blindly re-running would
                # double-accumulate — those stay fatal; pure tasks are
                # retried with bounded exponential backoff.  SpillDataLost
                # and ArenaOverflow are the chain-safe exceptions: both
                # can only be raised while *fetching/allocating* inputs,
                # strictly before the in-place update touches the output
                # buffer (an overflow is often transient — concurrent
                # tasks' pinned inputs drain — so it retries too)
                retryable = t is not None and (
                    t.kind not in _CHAIN_KINDS
                    or "SpillDataLost" in msg[3]
                    or "ArenaOverflow" in msg[3])
                if not retryable or tries > self._mcfg.task_max_retries:
                    if "ArenaOverflow" in msg[3]:
                        # nothing left to evict under the budget even
                        # after backoff: structured failure naming the
                        # node, never an OOM kill
                        raise MemoryBudgetExceeded(
                            msg[1], 0, cur_spec.mem_at(msg[1]) or 0,
                            msg=f"node {msg[1]} arena overflow (budget "
                                f"{cur_spec.mem_at(msg[1])} bytes, "
                                f"nothing left to evict) running task "
                                f"{tid}, attempt {tries}:\n{msg[3]}")
                    raise RuntimeError(
                        f"cluster task failed on node {msg[1]} "
                        f"(task {tid}, attempt {tries}):\n{msg[3]}")
                if t.out is not None:
                    write_busy.discard((msg[1], t.out))
                dispatched[tid].discard(msg[1])
                inflight[msg[1]] -= 1
                cnt.inc("task_retries")
                task_retry_at[tid] = time.monotonic() + min(
                    self._mcfg.retry_backoff_s * (2 ** (tries - 1)), 2.0)
                if deps_left[tid] == 0 and not dispatched[tid]:
                    ready.add(tid)
            elif kind in ("stats", "audit"):
                self._node_stats[msg[1]] = msg[2]
                if len(msg) > 4:
                    tracer.ingest(msg[4], offsets.get(msg[1], 0.0))
            return True

        # -- master event loop ----------------------------------------------
        self._node_stats: Dict[int, Dict[str, int]] = {}
        last_progress = time.monotonic()

        def wait_for_events(timeout: float) -> None:
            """Block on the live workers' queue pipes (not a sleep poll —
            a timer-sleeping master loses its sleeper credit and gets
            starved for 100ms+ once workers oversubscribe the host,
            which turns every dispatch round trip into idle worker
            time).  Falls back to a short sleep if the queue internals
            are unavailable."""
            conns = []
            for n in ms.alive_nodes():
                q = self._outqs.get(n)
                r = getattr(q, "_reader", None) if q is not None else None
                if r is not None:
                    conns.append(r)
            if not conns:
                time.sleep(0.002)
                return
            try:
                from multiprocessing.connection import wait as conn_wait
                conn_wait(conns, timeout)
            except OSError:             # pragma: no cover — racing a death
                time.sleep(0.002)

        def abandon_run() -> None:
            """Orderly abort for a resident-tile loss: drain in-flight
            worker activity (so stale `done` messages can't corrupt the
            session's NEXT run), release this run's arena bindings, then
            raise — workers stay alive for the retry."""
            exc = pending_abort[0]
            deadline = time.monotonic() + min(self.timeout, 30.0)
            while (sum(inflight[k] for k in ms.alive_nodes())
                   or any(k[0] in ms.alive_nodes()
                          for k in xfer_inflight)) \
                    and time.monotonic() < deadline:
                moved = False
                for n in list(ms.alive_nodes()):
                    q = self._outqs.get(n)
                    if q is None:
                        continue
                    try:
                        msg = q.get_nowait()
                    except _queue.Empty:
                        continue
                    moved = True
                    k = msg[0]
                    if k == "done":
                        t = g.tasks[msg[2]]
                        if t.out is not None and msg[3] is not None:
                            avail[(msg[1], t.out)] = \
                                (canon_of(msg[2]), msg[3], msg[4])
                        dispatched[msg[2]].discard(msg[1])
                        inflight[msg[1]] -= 1
                    elif k in ("xfer_done", "xfer_fail"):
                        xfer_inflight.pop((msg[1], msg[3]), None)
                        lease = leases.pop((msg[1], msg[3]), None)
                        if lease is not None:
                            release_pin(lease[0], msg[3], lease[1])
                        if k == "xfer_done":
                            avail[(msg[1], msg[3])] = \
                                (msg[2], msg[4], msg[5])
                    elif k in ("held", "packed"):
                        # aborting: each ack is one pin — drop it and
                        # un-book its waiters (the retry run takes fresh
                        # leases of its own)
                        fail_pending_lease(msg[1], msg[2],
                                           bump_retries=False)
                        release_pin(msg[1], msg[2],
                                    "raw" if k == "held" else msg[5])
                    elif k == "hb":
                        ms.heartbeat(msg[1])
                    elif k == "error":
                        t = g.tasks[msg[2]] if msg[2] in g.tasks else None
                        dispatched[msg[2]].discard(msg[1])
                        inflight[msg[1]] -= 1
                if not moved:
                    liveness = {n: self._procs[n].is_alive()
                                for n in ms.alive_nodes()
                                if self._procs.get(n) is not None}
                    for ev in ms.poll(liveness):
                        if ev.kind == DEATH:
                            inflight[ev.node] = 0
                            for (dst, ref) in list(xfer_inflight):
                                if dst == ev.node:
                                    del xfer_inflight[(dst, ref)]
                            for key in [key for key in avail
                                        if key[0] == ev.node]:
                                del avail[key]
                    wait_for_events(0.02)
            # any lease still open past the drain deadline must not
            # outlive this run (workers survive for the session retry)
            for (_dstn, ref), (holder, codec) in list(leases.items()):
                release_pin(holder, ref, codec)
            leases.clear()
            if self.free_buffers:
                for (n, ref) in list(avail):
                    del avail[(n, ref)]
                    if ms.is_alive(n) and self._inqs.get(n) is not None:
                        self._inqs[n].put(("free", ref))
            raise exc

        try:
            fire_chaos()                      # after_done=0 chaos
            scan_dispatch()
            while len(completed) < total:
                processed = 0
                for n in list(ms.alive_nodes()):
                    q = self._outqs.get(n)
                    if q is None:
                        continue
                    for _ in range(256):
                        try:
                            msg = q.get_nowait()
                        except _queue.Empty:
                            break
                        if handle(msg):
                            processed += 1
                liveness = {n: self._procs[n].is_alive()
                            for n in ms.alive_nodes()
                            if self._procs.get(n) is not None}
                for ev in ms.poll(liveness):
                    processed += 1
                    if ev.kind == DEATH:
                        on_death(ev.node)
                    elif ev.kind == STRAGGLE:
                        on_straggle(ev.node)
                    elif ev.kind == RECOVER:
                        on_recover(ev.node)
                if pending_abort[0] is not None:
                    abandon_run()
                scan_dispatch()
                now = time.monotonic()
                if processed:
                    last_progress = now
                elif now - last_progress > self.timeout:
                    raise RuntimeError(
                        f"cluster execution stalled: no progress within "
                        f"timeout={self.timeout}s "
                        f"({len(completed)}/{total} tasks, "
                        f"ready={sorted(ready)[:8]})")
                else:
                    wait_for_events(0.05)

            def pump_until(pred, what: str) -> None:
                """Drain worker messages through ``handle`` until ``pred``
                holds (used post-run: gather fault-ins, retention acks)."""
                deadline = time.monotonic() + min(self.timeout, 30.0)
                while not pred():
                    got = False
                    for n2 in list(ms.alive_nodes()):
                        q2 = self._outqs.get(n2)
                        if q2 is None:
                            continue
                        try:
                            m2 = q2.get_nowait()
                        except _queue.Empty:
                            continue
                        handle(m2)
                        got = True
                    if pred():
                        return
                    if time.monotonic() > deadline:
                        raise RuntimeError(f"timed out waiting for {what}")
                    if not got:
                        wait_for_events(0.02)

            # -- gather result tiles of non-persisted roots -----------------
            outs: List[np.ndarray] = []
            gather_bytes = 0
            gather_span_t0 = time.perf_counter()
            for rs in rsets:
                if not rs.gather:
                    continue
                vals: Dict[TileRef, np.ndarray] = {}
                for r in rs.tiles:
                    streamed = gstreamed.pop(r, None)
                    if streamed is not None:
                        # already copied out when its TAKECOPY landed
                        vals[r] = streamed
                        gather_bytes += r.bytes
                        continue
                    for _attempt in range(5):
                        ent = avail.get((master, r))
                        if ent is None:  # pragma: no cover — takecopy pins
                            raise RuntimeError(f"result tile {r} missing "
                                               f"from the master arena")
                        if (master, r) in spilled:
                            request_fault(master, r)
                            pump_until(
                                lambda: (master, r) not in spilled,
                                f"fault-in of result tile {r}")
                            ent = avail.get((master, r))
                            if ent is None:   # lost + lineage recompute
                                raise RuntimeError(
                                    f"result tile {r} lost from the "
                                    f"spill tier during gather")
                        try:
                            seg = _attach_shm(ent[1])
                        except FileNotFoundError:
                            # evicted between unspill and attach — retry
                            spilled.add((master, r))
                            continue
                        try:
                            view = np.ndarray(r.shape,
                                              dtype=np.dtype(ent[2]),
                                              buffer=seg.buf)
                            vals[r] = view.copy()
                            if gather_t_first[0] is None:
                                gather_t_first[0] = \
                                    time.perf_counter() - t_exec0
                        finally:
                            seg.close()
                        break
                    else:
                        raise RuntimeError(
                            f"could not gather result tile {r}: segment "
                            f"kept vanishing under memory pressure")
                    gather_bytes += r.bytes
                outs.append(assemble(vals, rs.shape, plan.tile, rs.uid))
            gather_t_full = time.perf_counter() - t_exec0
            if self.trace:
                tracer.add(Span("GATHER", "GATHER", -1, 0, gather_span_t0,
                                time.perf_counter() - gather_span_t0,
                                {"bytes": gather_bytes}))

            # -- retention: persisted tiles into the session store ----------
            # a tile's home is wherever its (canonical) value actually
            # lives — under churn that may differ from the planned node.
            # The worker's retain op faults a spilled tile back in (fresh
            # segment name), so the session store is updated from the ack
            retained_count = 0
            for rs in rsets:
                if rs.gather:
                    continue
                h = residency.retain[rs.uid]
                for r in rs.tiles:
                    v = canon_of(rs.producers[r])
                    holder = exec_nodes.get(rs.producers[r])
                    if holder is None or not alive(holder) or \
                            avail.get((holder, r), (None,))[0] != v:
                        holder = next(
                            (k for k in ms.alive_nodes()
                             if avail.get((k, r), (None,))[0] == v), None)
                    if holder is None:  # pragma: no cover — defensive
                        raise RuntimeError(
                            f"retention: no live holder for {r} "
                            f"(version {v})")
                    avail.pop((holder, r))
                    spilled.discard((holder, r))
                    pending_retain[(h.hid, r.i, r.j)] = (rs.uid, r)
                    self._inqs[holder].put(("retain", r,
                                            (h.hid, r.i, r.j)))
                    retained_count += 1
            if pending_retain:
                pump_until(lambda: not pending_retain, "retention acks")

            # -- release every remaining binding before shutdown ------------
            if self.free_buffers:
                for (n, ref) in list(avail):
                    del avail[(n, ref)]
                    if alive(n) and self._inqs.get(n) is not None:
                        self._inqs[n].put(("free", ref))

            # -- orderly shutdown + per-node stats (one-shot mode only) -----
            if not self.session:
                expect = [n for n in ms.alive_nodes()
                          if self._inqs.get(n) is not None]
                for n in expect:
                    self._inqs[n].put(("stop",))
                deadline = time.monotonic() + min(self.timeout, 30.0)
                while len(self._node_stats) < len(expect) \
                        and time.monotonic() < deadline:
                    got = False
                    for n in expect:
                        try:
                            msg = self._outqs[n].get_nowait()
                        except _queue.Empty:
                            continue
                        if msg[0] == "stats":
                            self._node_stats[msg[1]] = msg[2]
                            node_pids.setdefault(msg[1], msg[3])
                            if len(msg) > 4:
                                tracer.ingest(msg[4],
                                              offsets.get(msg[1], 0.0))
                        got = True
                    if not got:
                        time.sleep(0.005)
                for n in expect:
                    p = self._procs.get(n)
                    if p is not None:
                        p.join(timeout=5)
            else:
                # workers survive: audit their arenas instead (per-worker
                # FIFO: each reply follows this run's frees and retains)
                expect = [n for n in ms.alive_nodes()
                          if self._inqs.get(n) is not None]
                for n in expect:
                    self._inqs[n].put(("audit",))
                pump_until(lambda: all(n in self._node_stats
                                       or not alive(n) for n in expect),
                           "arena audits")
        except ResidentTilesLost:
            # orderly abort: workers (and their retained arenas) survive
            # for the session's lineage recompute + retry
            if not self.session:        # pragma: no cover — defensive
                self._terminate_all()
            raise
        except BaseException:
            self._broken = True
            self._terminate_all()
            shutil.rmtree(self._spill_dir, ignore_errors=True)
            raise
        finally:
            self._cur_spec = cur_spec
            if not self.session or self._broken:
                for p in self._procs.values():
                    if p is not None and p.is_alive():  # pragma: no cover
                        p.terminate()
                        p.join(timeout=5)

        leaked_spill = 0
        if not self.session:
            # after a clean one-shot run every spill file must be gone;
            # leftovers are leaks (counted, then reaped)
            try:
                leaked_spill = len(os.listdir(self._spill_dir))
            except OSError:
                pass
            shutil.rmtree(self._spill_dir, ignore_errors=True)
        # the registry's frozen_view IS the stats dict consumers always
        # read — counters stay inside the registry, run-shaped facts ride
        # along as extras
        self.spans = tracer.drain()
        self.stats = cnt.frozen_view({
            "tasks_run": total,
            # node arenas the audit sums (alive at the end of the run)
            "nodes": len(self._node_stats),
            "nodes_initial": spec.n_nodes,
            "nodes_final": len(ms.alive_nodes()),
            "workers": sum(cur_spec.workers_at(n)
                           for n in cur_spec.alive_nodes()),
            "exec_nodes": exec_nodes,
            "node_pids": node_pids,
            "recovery_seconds": recovery_seconds[0],
            # hygiene audits — both must be 0 after a clean run: an open
            # lease is a stranded source pin; a surviving retry entry
            # means a recovered edge/task kept its failure count and
            # would exhaust its budget early on the NEXT fault
            "stale_leases": len(leases) + sum(len(v) for v
                                              in pending_lease.values()),
            "stale_retry_entries": len(xfer_retries) + len(task_retries),
            "gather_bytes": gather_bytes,
            "gather_first_tile_s": gather_t_first[0],
            "gather_full_result_s": gather_t_full,
            "retained_tiles": retained_count,
            "buffers_freed": sum(s["buffers_freed"]
                                 for s in self._node_stats.values()),
            "peak_buffer_bytes": sum(s["peak_buffer_bytes"]
                                     for s in self._node_stats.values()),
            "cur_buffer_bytes": sum(s["cur_buffer_bytes"]
                                    for s in self._node_stats.values()),
            # both 0 after a clean one-shot run; in session mode
            # retained_total counts the session's tiles in the arenas
            "live_buffers": sum(s.get("live_buffers", 0)
                                for s in self._node_stats.values()),
            "retained_total": sum(s.get("retained", 0)
                                  for s in self._node_stats.values()),
            "evictions": sum(s.get("evictions", 0)
                             for s in self._node_stats.values()),
            "faults": sum(s.get("faults", 0)
                          for s in self._node_stats.values()),
            "spill_writes": sum(s.get("spill_writes", 0)
                                for s in self._node_stats.values()),
            "spill_reads": sum(s.get("spill_reads", 0)
                               for s in self._node_stats.values()),
            "spilled_bytes": sum(s.get("spilled_bytes", 0)
                                 for s in self._node_stats.values()),
            "leaked_spill_files": leaked_spill,
        })
        if not outs:
            return None
        return outs[0] if len(outs) == 1 else outs

    # -- session lifecycle ----------------------------------------------------
    def drop_retained(self, node: int, key) -> None:
        """Session free path: drop one retained tile from ``node``'s
        arena (no-op for nodes that already left the cluster)."""
        if self._broken or self._ms is None:
            return
        if self._ms.is_alive(node) and self._inqs.get(node) is not None:
            self._inqs[node].put(("drop", key))

    def close_session(self) -> Dict[int, Dict[str, int]]:
        """Stop the long-lived workers; returns per-node arena stats
        collected at shutdown (live/retained counts — the refcount-audit
        input; dead nodes are absent)."""
        audit: Dict[int, Dict[str, int]] = {}
        if not self._started:
            return audit
        if not self._broken and self._ms is not None:
            expect = [n for n in self._ms.alive_nodes()
                      if self._inqs.get(n) is not None]
            for n in expect:
                self._inqs[n].put(("stop",))
            deadline = time.monotonic() + min(self.timeout, 30.0)
            while len(audit) < len(expect) and \
                    time.monotonic() < deadline:
                got = False
                for n in expect:
                    q = self._outqs.get(n)
                    if q is None:
                        continue
                    try:
                        msg = q.get_nowait()
                    except _queue.Empty:
                        continue
                    got = True
                    if msg[0] == "stats":
                        audit[msg[1]] = msg[2]
                if not got:
                    time.sleep(0.005)
        self._terminate_all()
        self._started = False
        # spill-file leak sweep: a clean shutdown leaves the run's spill
        # directory empty — report leftovers so the session audit can fail
        sd = getattr(self, "_spill_dir", None)
        if sd:
            try:
                leaked = len(os.listdir(sd))
            except OSError:
                leaked = 0
            shutil.rmtree(sd, ignore_errors=True)
            audit["spill"] = {"leaked_spill_files": leaked}
        return audit

    # -- cleanup --------------------------------------------------------------
    def _reap_segments(self, node: Optional[int] = None) -> None:
        """Best-effort unlink of shm segments left behind by dead
        incarnations (a SIGKILLed worker never unlinks its arena),
        found via the run-scoped name prefix."""
        from multiprocessing import resource_tracker
        if not os.path.isdir("/dev/shm"):       # pragma: no cover
            return
        reaped = []
        for f in os.listdir("/dev/shm"):
            if not f.startswith(self._prefix):
                continue
            if node is not None and f"n{node}_" not in f:
                continue
            try:
                # plain unlink (= shm_unlink): attaching would fail on a
                # segment whose creator was SIGKILLed mid-create (zero
                # size), and existing mappings survive the unlink anyway
                os.unlink(os.path.join("/dev/shm", f))
                reaped.append(f)
            except OSError:
                pass
        # the dead worker registered its creates with the (shared) tracker
        # process and died before unlinking; retract the stale entries or
        # the tracker warns about leaks at exit.  register-then-unregister
        # nets to removal whether or not the registration arrived before
        # the SIGKILL (the tracker cache is a set — bpo-39959)
        for f in reaped:
            try:
                resource_tracker.register("/" + f, "shared_memory")
                resource_tracker.unregister("/" + f, "shared_memory")
            except Exception:       # pragma: no cover
                pass
        # a SIGKILLed worker also strands its spill-tier files; same
        # per-node sweep over the run's spill directory.  The node=None
        # (terminate-all) case deliberately leaves files in place so the
        # close_session leak audit can count them first
        sd = getattr(self, "_spill_dir", None)
        if node is not None and sd and os.path.isdir(sd):
            for f in os.listdir(sd):
                if f"n{node}_" not in f:
                    continue
                try:
                    os.unlink(os.path.join(sd, f))
                except OSError:     # pragma: no cover
                    pass

    def _terminate_all(self) -> None:
        for p in self._procs.values():
            if p is not None and p.is_alive():
                p.terminate()
        for p in self._procs.values():
            if p is not None:
                p.join(timeout=5)
        self._reap_segments()
