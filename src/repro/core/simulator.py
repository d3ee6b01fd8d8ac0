"""Discrete-event schedule simulator (CMM §3.3, §4.2).

Simulates a HEFT schedule under the profiled time model, with the machine
model's resources made explicit:

* each node has ``worker_procs`` compute slots (a task occupies one);
* each node has ``comm_procs`` communication slots — a cross-node transfer
  occupies one slot at the sender *and* one at the receiver for its duration
  (the paper's dedicated communication processes; the master has more);
* ``calloc`` is asynchronous: it does not occupy a worker slot (§3.3);
* the node-level cache absorbs repeated transfers of the same tile version
  (§3.5) — transfers in flight are joined, not duplicated;
* ``zero_comm=True`` makes communication instantaneous, which is exactly the
  paper's *theoretical speedup* condition (§5.1).

The simulator is what the engine uses for tile-size auto-selection (§3.3) and
what `benchmarks/` uses for Table 3/4 and Fig. 3.
"""
from __future__ import annotations

import heapq
import itertools
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Set, Tuple

from .cache import NodeCache
from .graph import Task, TaskGraph, TaskKind
from .heft import Schedule, edge_bytes
from .machine import ClusterSpec
from .timemodel import CostCache, TimeModel
from ..runtime.wire import BCAST_MIN_FANOUT


def _relay_tree(src: int, dsts) -> Dict[int, List[int]]:
    """Binary relay tree for one fan-out edge: each relay node mapped to
    its children over ``[src] + sorted(dsts)`` (position ``i`` feeds
    ``2i+1`` and ``2i+2``).  How the simulator prices an edge the cluster
    executor serves through relays (each holder serves at most
    ``BCAST_MIN_FANOUT - 1`` readers at a time; landed copies serve the
    rest)."""
    order = [src] + sorted(set(int(d) for d in dsts) - {src})
    tree: Dict[int, List[int]] = {}
    for i, parent in enumerate(order):
        kids = order[2 * i + 1: 2 * i + 3]
        if kids:
            tree[parent] = kids
    return tree


@dataclass
class Interval:
    tid: int
    kind: str
    node: int
    slot: int
    start: float
    end: float


@dataclass
class Transfer:
    key: Tuple[int, int]
    src: int
    dst: int
    nbytes: int
    start: float = 0.0
    end: float = 0.0


@dataclass
class SimResult:
    makespan: float
    intervals: List[Interval]
    transfers: List[Transfer]
    cache_hits: int
    cache_misses: int
    spec: ClusterSpec
    #: predicted peak arena bytes per node (``heft.peak_node_bytes``);
    #: filled in by the engine's admission check when any node carries a
    #: ``mem_bytes`` budget, empty otherwise
    peak_bytes: Dict[int, int] = field(default_factory=dict)

    def stats_by_kind(self) -> Dict[str, Tuple[int, float]]:
        acc: Dict[str, List[float]] = defaultdict(list)
        for iv in self.intervals:
            acc[iv.kind].append(iv.end - iv.start)
        return {k: (len(v), sum(v)) for k, v in acc.items()}

    def node_busy_fraction(self) -> Dict[int, float]:
        busy = defaultdict(float)
        for iv in self.intervals:
            busy[iv.node] += iv.end - iv.start
        ms = max(self.makespan, 1e-12)
        return {n: busy[n] / (max(1, self.spec.workers_at(n)) * ms)
                for n in range(self.spec.n_nodes)}

    def comm_busy_seconds(self) -> float:
        return sum(t.end - t.start for t in self.transfers)

    def predicted_spans(self, lane_offset: int = 100) -> list:
        """The predicted timeline as flight-recorder spans.

        One ``PRED_EXEC`` span per simulated task interval and one
        ``PRED_XFER`` per simulated transfer, on the same node (pid)
        lanes as the measured trace but with worker-slot lanes shifted
        by ``lane_offset`` — exporting measured + predicted spans into
        one Chrome trace puts prediction directly under reality for
        eyeball drift checks, and ``core/drift.py`` computes the
        residuals the same join implies.
        """
        from ..runtime.telemetry import Span
        out = []
        for iv in self.intervals:
            out.append(Span(f"PRED {iv.kind}", "PRED_EXEC", iv.node,
                            lane_offset + iv.slot, iv.start,
                            iv.end - iv.start,
                            {"tid": iv.tid, "kind": iv.kind}))
        for t in self.transfers:
            out.append(Span("PRED xfer", "PRED_XFER", t.dst,
                            lane_offset, t.start, t.end - t.start,
                            {"tid": t.key[0], "src": t.src,
                             "nbytes": t.nbytes}))
        return out

    def gantt(self, width: int = 100) -> str:
        """ASCII Gantt chart per (node, slot) lane — the Fig. 3 artefact."""
        if not self.intervals:
            return "(empty)"
        scale = width / max(self.makespan, 1e-12)
        lanes: Dict[Tuple[int, int], List[Interval]] = defaultdict(list)
        for iv in self.intervals:
            lanes[(iv.node, iv.slot)].append(iv)
        sym = {"addmul": "#", "matmul": "#", "add": "+", "sub": "-",
               "ewmul": "*", "scale": "*", "ewise": "~", "transpose": "t",
               "fused": "F",
               "fill": "f", "calloc": ".", "takecopy": "c"}
        out = []
        for (node, slot) in sorted(lanes):
            row = [" "] * width
            for iv in lanes[(node, slot)]:
                a = min(int(iv.start * scale), width - 1)
                b = min(max(int(iv.end * scale), a + 1), width)
                for x in range(a, b):
                    row[x] = sym.get(iv.kind, "?")
            out.append(f"n{node}.w{slot} |{''.join(row)}|")
        for t in sorted(self.transfers, key=lambda t: (t.src, t.start)):
            a = min(int(t.start * scale), width - 1)
            b = min(max(int(t.end * scale), a + 1), width)
            row = [" "] * width
            for x in range(a, b):
                row[x] = ">"
            out.append(f"n{t.src}>n{t.dst} |{''.join(row)}|")
        return "\n".join(out)


def simulate(g: TaskGraph, sched: Schedule, spec: ClusterSpec, tm: TimeModel,
             zero_comm: bool = False, use_cache: bool = True,
             cost: Optional[CostCache] = None) -> SimResult:
    """``use_cache=False`` disables the node-level cache in the MACHINE
    (every consumer transfer is re-sent) — the §3.5 mechanism ablation.

    ``cost`` optionally shares a memoized :class:`CostCache` (e.g. the one
    the scheduler already filled) so task durations are not re-derived from
    the interpolation polynomials task-by-task on large graphs."""
    if zero_comm:
        spec = spec.zero_comm()
        cost = None          # cached durations were built for the real spec
    if cost is None:
        cost = CostCache(tm, spec)
    prio = {tid: i for i, tid in enumerate(sched.order)}
    node_of = {tid: p.node for tid, p in sched.placements.items()}

    cache = NodeCache(spec.n_nodes)
    free_workers = {n: spec.workers_at(n) for n in range(spec.n_nodes)}
    free_slots = {n: list(range(spec.workers_at(n)))
                  for n in range(spec.n_nodes)}
    free_comm = {n: spec.comm_procs(n) for n in range(spec.n_nodes)}

    deps_left = {t.tid: len(t.preds) for t in g}
    # (key, dst) -> list of task ids waiting for that arrival
    waiting_data: Dict[Tuple[Tuple[int, int], int], List[int]] = defaultdict(list)
    data_left = {t.tid: 0 for t in g}
    ready: Dict[int, List[Tuple[int, int]]] = {n: [] for n in range(spec.n_nodes)}
    # startable transfers as a priority heap; a transfer blocked on an
    # exhausted comm endpoint is PARKED on that node and only returns to the
    # heap when the node frees a slot — so dispatch never rescans the whole
    # pending set (the naive rescan is O(events x pending) on big graphs)
    pending_xfers: List[Tuple[int, int, Transfer]] = []  # (prio, seq, tr)
    parked_xfers: Dict[int, List[Tuple[int, int, Transfer]]] = \
        defaultdict(list)
    xseq = itertools.count()
    in_flight: Set[Tuple[Tuple[int, int], int]] = set()
    # relay plan for fan-out edges: (key, relay node) -> child nodes whose
    # hop starts when the relay's own copy lands (same deterministic tree
    # shape as the executors' broadcast path, so tree depth is priced)
    relay_children: Dict[Tuple[Tuple[int, int], int], List[int]] = {}
    relay_prio: Dict[Tuple[Tuple[int, int], int], int] = {}

    events: List[Tuple[float, int, str, object]] = []
    seq = itertools.count()
    intervals: List[Interval] = []
    transfers_done: List[Transfer] = []
    now = 0.0

    def push(t, kind, payload):
        heapq.heappush(events, (t, next(seq), kind, payload))

    def task_ready(tid: int):
        n = node_of[tid]
        heapq.heappush(ready[n], (prio[tid], tid))

    def finish_producer(tid: int):
        """Producer done: release deps, create transfers for cross-node data."""
        t = g.tasks[tid]
        src = node_of[tid]
        if t.out is not None:
            cache.put(src, (tid, t.out.tensor), t.out.bytes)
        new_dsts: List[Tuple[int, int, Tuple]] = []   # (dst, nbytes, key)
        for s in sorted(t.succs, key=lambda x: prio[x]):
            st = g.tasks[s]
            nbytes = edge_bytes(g, t, st)
            dst = node_of[s]
            if nbytes and dst != src:
                key = (tid, t.out.tensor) if use_cache \
                    else (tid, t.out.tensor, s)   # unique -> never cached
                if use_cache and cache.peek(dst, key):
                    cache.hits += 1
                else:
                    data_left[s] += 1
                    waiting_data[(key, dst)].append(s)
                    if (key, dst) not in in_flight:
                        cache.misses += 1
                        in_flight.add((key, dst))
                        # succs iterate in prio order -> first waiter is
                        # the most urgent consumer at this destination
                        relay_prio[(key, dst)] = prio[s]
                        new_dsts.append((dst, nbytes, key))
            deps_left[s] -= 1
            if deps_left[s] == 0 and data_left[s] == 0:
                task_ready(s)
        if not new_dsts:
            return
        if use_cache and len(new_dsts) >= BCAST_MIN_FANOUT:
            # fan-out edge: relay tree instead of N unicasts — only the
            # root's hops start now; deeper hops start as relays land
            key = new_dsts[0][2]
            nbytes = new_dsts[0][1]
            tree = _relay_tree(src, [d for d, _, _ in new_dsts])
            for parent, kids in tree.items():
                if parent != src:
                    relay_children[(key, parent)] = kids
            for child in tree.get(src, []):
                heapq.heappush(
                    pending_xfers,
                    (relay_prio[(key, child)], next(xseq),
                     Transfer(key, src, child, nbytes)))
        else:
            for dst, nbytes, key in new_dsts:
                heapq.heappush(
                    pending_xfers,
                    (relay_prio[(key, dst)], next(xseq),
                     Transfer(key, src, dst, nbytes)))

    def dispatch(now: float):
        # start feasible transfers in priority order.  Starting a transfer
        # only CONSUMES comm slots, so a blocked transfer stays blocked for
        # the rest of this dispatch: it parks on its exhausted endpoint and
        # is only reconsidered once that node frees a slot.  Candidates are
        # k-way-merged in global priority order from the fresh-transfer heap
        # and the parked heaps of nodes that currently have free slots —
        # exactly the feasible subset the naive full rescan would start, at
        # O(starts + moves) instead of O(pending) per event.
        while True:
            best = pending_xfers[0] if pending_xfers else None
            best_node = -1
            for n, h in parked_xfers.items():
                if h and free_comm[n] > 0 and \
                        (best is None or h[0] < best):
                    best = h[0]
                    best_node = n
            if best is None:
                break
            src_heap = pending_xfers if best_node < 0 \
                else parked_xfers[best_node]
            item = heapq.heappop(src_heap)
            tr = item[2]
            if free_comm[tr.src] <= 0:
                heapq.heappush(parked_xfers[tr.src], item)
                continue
            if free_comm[tr.dst] <= 0:
                heapq.heappush(parked_xfers[tr.dst], item)
                continue
            free_comm[tr.src] -= 1
            free_comm[tr.dst] -= 1
            tr.start = now
            # per-edge codec-aware pricing (degrades to spec.comm_time
            # while the TimeModel's codec priors are unfitted)
            tr.end = now + tm.wire_time(tr.nbytes, tr.src, tr.dst, spec)
            push(tr.end, "xfer_done", tr)
        # start ready compute tasks
        for n in range(spec.n_nodes):
            while ready[n]:
                _, tid = ready[n][0]
                t = g.tasks[tid]
                if t.kind in (TaskKind.CALLOC, TaskKind.RESIDENT):
                    heapq.heappop(ready[n])
                    # CALLOC is async (§3.3); RESIDENT binds an
                    # already-materialised session tile — zero-cost
                    # inputs, so `auto` verdicts stay honest
                    dur = 1e-6  # no worker slot occupied
                    intervals.append(Interval(tid, t.kind.value, n, -1,
                                              now, now + dur))
                    push(now + dur, "task_done", tid)
                    continue
                if free_workers[n] <= 0:
                    break
                heapq.heappop(ready[n])
                free_workers[n] -= 1
                slot = free_slots[n].pop()
                dur = cost.time(t, n)
                intervals.append(Interval(tid, t.kind.value, n, slot,
                                          now, now + dur))
                push(now + dur, "task_done", (tid, slot))

    # seed: source tasks are immediately ready
    for t in g.sources():
        task_ready(t.tid)
    dispatch(0.0)

    while events:
        now, _, kind, payload = heapq.heappop(events)
        if kind == "task_done":
            if isinstance(payload, tuple):
                tid, slot = payload
                n = node_of[tid]
                free_workers[n] += 1
                free_slots[n].append(slot)
            else:
                tid = payload
            finish_producer(tid)
        elif kind == "xfer_done":
            tr: Transfer = payload
            free_comm[tr.src] += 1
            free_comm[tr.dst] += 1
            cache.put(tr.dst, tr.key, tr.nbytes)
            transfers_done.append(tr)
            in_flight.discard((tr.key, tr.dst))
            for s in waiting_data.pop((tr.key, tr.dst), []):
                data_left[s] -= 1
                if deps_left[s] == 0 and data_left[s] == 0:
                    task_ready(s)
            # the landed copy relays onward to its broadcast children
            for child in relay_children.pop((tr.key, tr.dst), []):
                heapq.heappush(
                    pending_xfers,
                    (relay_prio.get((tr.key, child), 0), next(xseq),
                     Transfer(tr.key, tr.dst, child, tr.nbytes)))
        dispatch(now)

    makespan = max((iv.end for iv in intervals), default=0.0)
    return SimResult(makespan, intervals, transfers_done,
                     cache.hits, cache.misses, spec)


# -- churn pricing (elastic runtime) ----------------------------------------

def predict_reload_seconds(nbytes: int, tm: TimeModel) -> float:
    """Wall-clock to reload ``nbytes`` of checkpointed tiles from disk —
    the reload leg of the durable session's per-handle reload-vs-recompute
    choice (the recompute leg is ``CMMEngine.predict_recompute_seconds``,
    simulated with the same TimeModel)."""
    return float(nbytes) / max(tm.spill_read_bandwidth, 1.0)


def predict_spill_seconds(excess_bytes: int, tm: TimeModel) -> float:
    """Wall-clock cost of running a plan out-of-core: every byte above
    the arena budget is written to the spill tier once and faulted back
    at least once, priced at the TimeModel's spill bandwidths.  Used by
    the engine's admission check to annotate spill-executable plans so
    degradation is chosen with its price known, not suffered."""
    b = float(max(0, excess_bytes))
    return (b / max(tm.spill_write_bandwidth, 1.0)
            + b / max(tm.spill_read_bandwidth, 1.0))


def predict_checkpoint_overhead(nbytes: int, tm: TimeModel) -> float:
    """Steady-state cost one asynchronous tile snapshot adds to the
    session path: the fixed writer handoff plus the host-side copy of the
    dirty tiles, priced at the spill bandwidth (the disk write itself
    overlaps the next compute)."""
    return tm.checkpoint_write_overhead + predict_reload_seconds(nbytes, tm)


def predict_recovery_cost(g: TaskGraph, sched: Schedule, spec: ClusterSpec,
                          tm: TimeModel, node: int,
                          cost: Optional[CostCache] = None,
                          checkpoint_bytes: Optional[int] = None) -> float:
    """Predicted wall-clock cost of losing ``node`` mid-run.

    The elastic runtime recovers by lineage: every tile the dead node held
    is recomputed from its producer subgraph on the survivors (no tile
    data is checkpointed), so the dominant term is re-executing the tasks
    HEFT had placed on ``node``.  A uniformly random failure time loses
    half of that work in expectation; recomputation spreads over the
    surviving compute slots.  ``tm.respawn_overhead`` adds the fixed
    detection + re-plan + rewire cost of one recovery event.

    ``checkpoint_bytes`` is the durable-session extension: when the lost
    tiles also exist as checkpoint shards of that many bytes, recovery
    takes the *cheaper* of lineage recompute and reload-from-disk — the
    same per-tile choice ``CMMSession.resume`` makes.
    """
    surv = sum(spec.workers_at(k) for k in spec.alive_nodes() if k != node)
    if surv <= 0:
        return float("inf")
    if cost is None:
        cost = CostCache(tm, spec)
    lost = sum(cost.time(g.tasks[tid], node)
               for tid, p in sched.placements.items() if p.node == node)
    recompute = 0.5 * lost / surv
    if checkpoint_bytes is not None:
        recompute = min(recompute,
                        predict_reload_seconds(checkpoint_bytes, tm))
    return tm.respawn_overhead + recompute


def churn_adjusted_makespan(g: TaskGraph, sched: Schedule, spec: ClusterSpec,
                            tm: TimeModel, base: Optional[float] = None,
                            cost: Optional[CostCache] = None) -> float:
    """Expected makespan once node-failure risk is priced in.

    ``base`` (default: the schedule's makespan) is inflated by, per
    non-master node, the probability of losing that node during the run
    (``base / tm.node_mtbf``, capped at 1) times its predicted recovery
    cost.  With the default ``node_mtbf = inf`` this is exactly ``base``,
    so pristine-cluster auto-selection is unchanged.
    """
    import math
    base = sched.makespan if base is None else base
    if not math.isfinite(tm.node_mtbf) or tm.node_mtbf <= 0:
        return base
    if cost is None:
        cost = CostCache(tm, spec)
    total = base
    for node in spec.alive_nodes():
        if node == spec.master:
            continue
        p_fail = min(1.0, base / tm.node_mtbf)
        total += p_fail * predict_recovery_cost(g, sched, spec, tm, node,
                                                cost=cost)
    return total
