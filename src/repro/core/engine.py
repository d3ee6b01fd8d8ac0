"""CMM engine: expression -> optimize -> tiled DAG -> HEFT -> sim -> run.

This is the user-facing orchestration layer (Fig. 1 of the paper): a
``ClusteredMatrix.compute()`` lands here.  The engine

1. optimizes the expression DAG (``fusion.optimize``: CSE, identity folding,
   transpose-into-matmul folding, elementwise-chain fusion — the paper's
   "optimize matrix operations on the fly" step),
2. tiles the optimized expression (``tiling.tile_expression``) at the
   configured or auto-selected tile size (§3.3),
3. schedules with cache-aware HEFT under the offline-profiled time model,
4. simulates the schedule (the ~0.1 s check the paper runs before execution),
5. executes with the selected executor (local threaded / Pallas-kernel /
   sharded SUMMA) and returns the materialised ndarray.

Repeated ``compute()`` calls with the same *structure* (iterative workloads:
power iteration, the Markov example) hit a structural **plan cache** — the
tiled program + HEFT schedule are reused with the leaves rebound to the new
data, so planning is paid once per structure.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, Dict, Optional, Sequence, Tuple

import numpy as np

from .fusion import (FusionReport, leaves_in_order_many, optimize_many,
                     residency_layout, structural_signature_many)
from .roofline import audit_timemodel
from .graph import TaskGraph, TaskKind
from .heft import DirectCost, Schedule, heft_schedule
from .lazy import ClusteredMatrix, Op, topo_order, topo_order_many
from .machine import ClusterSpec, MemoryBudgetExceeded, c5_9xlarge
from .simulator import SimResult, simulate
from .tiling import (TiledProgram, normalize_tile, tile_expression,
                     tile_expression_many)
from .timemodel import CostCache, TimeModel, analytic_time_model
from ..runtime.telemetry import Tracer


@dataclass
class Plan:
    program: TiledProgram
    schedule: Schedule
    sim: SimResult
    tile: Tuple[int, int]
    plan_seconds: float
    spec: Optional[ClusterSpec] = None
    fusion: Optional[FusionReport] = None
    cache_hit: bool = False
    #: per-run session residency view (``core.session.SessionResidency``):
    #: resident-leaf tile lookups + retention sinks.  Set by the session
    #: right before execution, never cached.
    residency: Optional[object] = None
    #: dependency levels of the task graph (wave-batched execution order)
    waves: Optional[list] = None
    #: predicted wall-clock of the wave-batched executor strategy
    batched_makespan: Optional[float] = None
    #: lazy, memoized predictor for the multi-process cluster strategy
    #: (None on single-node specs).  Pricing it re-simulates the whole
    #: schedule under the process/IPC terms, so it only runs when the
    #: prediction is actually consulted (``auto`` / ``best_*``) — plain
    #: ``plan()`` keeps the fast-path planning time.
    _cluster_pred: Optional[Callable[[], float]] = None
    #: lazy churn-priced predictor for the elastic strategy (cluster
    #: prediction + expected recovery cost under ``tm.node_mtbf``)
    _elastic_pred: Optional[Callable[[], float]] = None
    #: predicted peak arena bytes per node (admission check; None when no
    #: node carries a ``mem_bytes`` budget)
    peak_bytes: Optional[Dict[int, int]] = None
    #: predicted bytes that must round-trip the spill tier to run this
    #: plan within budget (0 = fits in RAM)
    spill_bytes: int = 0
    #: those bytes priced through the TimeModel's spill bandwidths
    spill_seconds: float = 0.0
    #: flight recorder: the planner's spans (a PLAN span around the call,
    #: FUSE (with CHAIN inside)/CACHE/TILE/HEFT/SIM/WAVES children; node
    #: -1, the master)
    spans: list = field(default_factory=list)

    @property
    def cluster_makespan(self) -> Optional[float]:
        """Predicted wall-clock of the multi-process cluster executor
        (None on single-node specs; computed on first access)."""
        return self._cluster_pred() if self._cluster_pred else None

    @property
    def elastic_makespan(self) -> Optional[float]:
        """Expected wall-clock of the elastic cluster strategy once
        node-failure risk is priced in (``churn_adjusted_makespan``;
        equals ``cluster_makespan`` at the default ``node_mtbf=inf``)."""
        return self._elastic_pred() if self._elastic_pred else None

    @property
    def predicted_makespan(self) -> float:
        """Per-task (HEFT-simulated) makespan — the paper's §4.2 number."""
        return self.sim.makespan

    @property
    def best_predicted_makespan(self) -> float:
        """Cheapest predicted strategy: per-task simulation vs wave-batched
        vs multi-process cluster execution (the simulation-driven selection
        extended to executor strategy)."""
        cands = [self.sim.makespan, self.batched_makespan,
                 self.cluster_makespan]
        return min(c for c in cands if c is not None)

    @property
    def best_executor(self) -> str:
        best, t = "local", self.sim.makespan
        if self.batched_makespan is not None and self.batched_makespan < t:
            best, t = "batched", self.batched_makespan
        if self.cluster_makespan is not None and self.cluster_makespan < t:
            best, t = "cluster", self.cluster_makespan
        return best

    def roofline_waves(self, tm, **kw) -> list:
        """Per-wave roofline fractions of this plan (how close each
        wave's predicted compute sits to the analytic machine ceiling —
        :func:`repro.core.roofline.wave_roofline`)."""
        from .roofline import wave_roofline
        from ..exec.batched import build_waves
        waves = self.waves or build_waves(self.program.graph)
        return wave_roofline(self.program.graph, waves, tm,
                             spec=self.spec, **kw)


def _memo_cluster_pred(g, sched, spec, tm) -> Callable[[], float]:
    """Memoized cluster-strategy predictor, shared by a cached plan and
    every cache-hit copy so the extra simulation runs at most once per
    planned structure — **keyed on the TimeModel state + spec**, so
    recalibration (``profiler.calibrate_ipc`` mutates ``tm`` in place)
    invalidates the cached verdict instead of returning a stale
    makespan."""
    memo: Dict[str, object] = {}

    def pred() -> float:
        key = (tm.to_json(), spec)
        if memo.get("k") != key:
            from ..exec.cluster import predict_cluster_makespan
            memo["k"] = key
            memo["v"] = predict_cluster_makespan(g, sched, spec, tm)
        return memo["v"]

    return pred


def _memo_elastic_pred(g, sched, spec, tm,
                       cluster_pred: Callable[[], float]
                       ) -> Callable[[], float]:
    """Churn-priced twin of ``_memo_cluster_pred``: the cluster
    prediction inflated by expected lineage-recovery cost under
    ``tm.node_mtbf`` (same TimeModel-keyed invalidation)."""
    memo: Dict[str, object] = {}

    def pred() -> float:
        key = (tm.to_json(), spec)
        if memo.get("k") != key:
            from .simulator import churn_adjusted_makespan
            memo["k"] = key
            memo["v"] = churn_adjusted_makespan(g, sched, spec, tm,
                                                base=cluster_pred())
        return memo["v"]

    return pred


class CMMEngine:
    _default: Optional["CMMEngine"] = None

    def __init__(self, spec: Optional[ClusterSpec] = None,
                 timemodel: Optional[TimeModel] = None,
                 tile: Optional[int] = None,
                 cache_aware: bool = True,
                 fuse: bool = True,
                 fuse_epilogue: bool = True,
                 plan_cache: bool = True,
                 fast_planning: bool = True,
                 elastic: bool = False):
        self.spec = spec or c5_9xlarge(1)
        self.timemodel = timemodel or analytic_time_model()
        self.tile = tile
        self.cache_aware = cache_aware
        self.fuse = fuse
        #: fold single-consumer elementwise chains into their matmul as an
        #: epilogue program (``fusion.fuse_matmul_epilogues_many``); off =
        #: the unfused baseline (standalone FUSED tasks per tile)
        self.fuse_epilogue = fuse_epilogue
        self.plan_cache = plan_cache
        #: elastic runtime mode: ``auto`` selection prices churn risk
        #: (``tm.node_mtbf``) into the multi-process ``"cluster"`` strategy
        self.elastic = elastic
        #: memoized-cost + gap-timeline HEFT (identical schedules; see
        #: ``heft.heft_schedule(fast=...)``).  ``False`` restores the
        #: pre-fast-path planner for benchmarking.
        self.fast_planning = fast_planning
        #: structural signature + tile -> (Plan, leaf uid order)
        self._plans: Dict[tuple, Plan] = {}
        self.plan_cache_hits = 0
        self.plan_cache_misses = 0
        #: how many times admission re-planned a too-big plan out-of-core
        #: at a smaller tile
        self.plan_shrinks = 0
        #: flight recorder: spans + plan of the last ``execute_plan`` call
        #: (``dump_trace`` / ``drift_report`` consume them)
        self.last_spans: list = []
        self.last_plan: Optional[Plan] = None
        self.last_exec_stats: Dict[str, object] = {}
        #: host staging buffers of the device wave executors that
        #: ``execute_plan`` builds, kept from one program to the next
        from ..exec.batched import StagingPool
        self.staging = StagingPool()

    @classmethod
    def default(cls) -> "CMMEngine":
        if cls._default is None:
            cls._default = CMMEngine()
        return cls._default

    # -- planning -----------------------------------------------------------
    def _fill_origins(self, roots: Sequence[ClusteredMatrix]
                      ) -> Dict[int, str]:
        out = {}
        for node in topo_order_many(roots):
            if node.op is Op.INPUT:
                out[node.uid] = "master"     # user data lives on the master
            elif node.op in (Op.RANDOM, Op.ZEROS, Op.EYE):
                out[node.uid] = "local"      # generated in place (§3.3)
        return out

    @staticmethod
    def _resident_pins(prog: TiledProgram) -> Optional[Dict[int, int]]:
        """RESIDENT task -> node whose arena holds that tile (the handle's
        per-tile home) — location-pinned placement for the scheduler."""
        pins: Dict[int, int] = {}
        for t in prog.graph:
            if t.kind is TaskKind.RESIDENT:
                h = prog.leaf_nodes[t.payload].payload
                pins[t.tid] = h.home.get((t.out.i, t.out.j), 0)
        return pins or None

    def plan(self, root: ClusteredMatrix, tile=None,
             fuse: Optional[bool] = None,
             fast: Optional[bool] = None) -> Plan:
        """Plan one root (the one-shot ``compute()`` path) — a thin wrapper
        over the multi-root session planner."""
        return self.plan_many((root,), tile=tile, fuse=fuse, fast=fast)

    def plan_many(self, roots: Sequence[ClusteredMatrix], tile=None,
                  fuse: Optional[bool] = None,
                  fast: Optional[bool] = None,
                  persist: Sequence[int] = ()) -> Plan:
        """Plan a multi-root program with shared CSE.

        ``persist`` lists root *positions* whose results stay resident in
        the executor arenas (no takecopy gather); the session layer turns
        them into ``ResidentMatrix`` handles.  The plan cache key covers
        the union structure, the persist set and the **residency layout**
        (tile size + per-tile home node of every resident leaf), so an
        iterative workload re-planning the same step structure hits the
        cache even though each step consumes fresh handles.

        The plan carries the planner's spans (``Plan.spans``); a cache
        hit records only FUSE (with CHAIN inside) and CACHE under its PLAN
        span.
        """
        tracer = Tracer(node=-1)
        with tracer.span("PLAN"):
            plan = self._plan_many(roots, tile, fuse, fast, persist, tracer)
        plan.spans = tracer.drain()
        return plan

    def _plan_many(self, roots, tile, fuse, fast, persist,
                   tracer: Tracer) -> Plan:
        t0 = time.perf_counter()
        roots = list(roots)
        orig_roots = roots  # pre-optimization view, for admission re-plans
        tile = normalize_tile(tile or self.tile or self._default_tile(roots))
        fuse = self.fuse if fuse is None else fuse
        fast = self.fast_planning if fast is None else fast
        persist_idx = frozenset(int(i) for i in persist)
        bad = [i for i in persist_idx if not 0 <= i < len(roots)]
        if bad:
            raise ValueError(f"persist indices {bad} out of range for "
                             f"{len(roots)} roots")
        report = None
        if fuse:
            # transposed-operand tile indexing needs a square tile on
            # ragged grids; keep explicit TRANSPOSE nodes otherwise
            with tracer.span("FUSE"):
                roots, report = optimize_many(
                    roots, fold_transpose=tile[0] == tile[1],
                    fuse_epilogue=self.fuse_epilogue, tracer=tracer)

        key = None
        if self.plan_cache:
            with tracer.span("CACHE"):
                # the TimeModel fingerprint keys the cache too: in-place
                # recalibration (calibrate_ipc/contention/...) must invalidate
                # cached schedules + auto-selection verdicts, not replay them
                key = (structural_signature_many(roots), tile, self.spec,
                       self.cache_aware, fuse, self.timemodel.to_json(),
                       persist_idx, residency_layout(roots))
                hit = self._plans.get(key)
                if hit is not None:
                    self.plan_cache_hits += 1
                    prog = hit.program.rebound(leaves_in_order_many(roots))
                    # the cached copy dropped its roots (they would pin user
                    # data); a served plan carries the CALLER's roots
                    prog.roots = list(roots)
                    prog.root = roots[0]
                    return Plan(prog, hit.schedule, hit.sim, hit.tile,
                                time.perf_counter() - t0, spec=self.spec,
                                fusion=report, cache_hit=True, waves=hit.waves,
                                batched_makespan=hit.batched_makespan,
                                _cluster_pred=hit._cluster_pred,
                                _elastic_pred=hit._elastic_pred,
                                peak_bytes=hit.peak_bytes,
                                spill_bytes=hit.spill_bytes,
                                spill_seconds=hit.spill_seconds)
                self.plan_cache_misses += 1

        with tracer.span("TILE"):
            prog = tile_expression_many(roots, tile, persist_idx)
        # one cost object shared by scheduling, simulation and wave costing:
        # memoized on the fast path, direct (naive-baseline) otherwise
        cost = CostCache(self.timemodel, self.spec) if fast \
            else DirectCost(self.timemodel, self.spec)
        with tracer.span("HEFT"):
            sched = heft_schedule(prog.graph, self.spec, self.timemodel,
                                  cache_aware=self.cache_aware,
                                  fill_origin=self._fill_origins(roots),
                                  fast=fast, cost=cost,
                                  pinned=self._resident_pins(prog))
        with tracer.span("SIM"):
            sim = simulate(prog.graph, sched, self.spec, self.timemodel,
                           cost=cost)
        from ..exec.batched import build_waves, predict_wave_makespan
        with tracer.span("WAVES"):
            waves = build_waves(prog.graph)
            batched = predict_wave_makespan(prog.graph, self.spec,
                                            self.timemodel, waves=waves,
                                            dtypes=prog.dtypes, cost=cost)
        cluster_pred = None
        elastic_pred = None
        if self.spec.n_nodes > 1:
            # the multi-process strategy only exists for multi-node specs
            cluster_pred = _memo_cluster_pred(prog.graph, sched, self.spec,
                                              self.timemodel)
            elastic_pred = _memo_elastic_pred(prog.graph, sched, self.spec,
                                              self.timemodel, cluster_pred)
        plan = Plan(prog, sched, sim, tile, time.perf_counter() - t0,
                    spec=self.spec, fusion=report, waves=waves,
                    batched_makespan=batched, _cluster_pred=cluster_pred,
                    _elastic_pred=elastic_pred)

        # -- admission: price the plan's peak footprint against mem_bytes.
        # A plan that overflows a node's budget but whose minimum working
        # set fits is ACCEPTED as spill-executable (the arena runs it
        # out-of-core bit-identically, at the annotated spill price); a
        # plan whose floor overflows is re-planned at a smaller tile, or
        # rejected with a structured MemoryBudgetExceeded — never an OOM.
        budgets = {n: self.spec.mem_at(n) for n in self.spec.alive_nodes()
                   if self.spec.mem_at(n) is not None}
        if budgets:
            from .heft import min_resident_floor, peak_node_bytes
            from .simulator import predict_spill_seconds
            peaks = peak_node_bytes(prog.graph, sched)
            spill_excess = 0
            for n, b in sorted(budgets.items()):
                p = peaks.get(n, 0)
                if p <= b:
                    continue
                floor = min_resident_floor(prog.graph, sched, n)
                if floor > b:
                    # spilling cannot help: one task's working set (or the
                    # retained baseline) alone overflows.  Resident-leaf
                    # programs are tile-locked to their handles, so only
                    # fresh-leaf programs can shrink.
                    has_resident = any(t.kind is TaskKind.RESIDENT
                                       for t in prog.graph.tasks.values())
                    if tile > (1, 1) and not has_resident:
                        self.plan_shrinks += 1
                        return self._plan_many(
                            orig_roots,
                            (max(1, tile[0] // 2), max(1, tile[1] // 2)),
                            fuse, fast, persist_idx, tracer)
                    raise MemoryBudgetExceeded(n, floor, b)
                spill_excess += p - b
            sim.peak_bytes.update(peaks)
            plan.peak_bytes = peaks
            plan.spill_bytes = spill_excess
            plan.spill_seconds = predict_spill_seconds(spill_excess,
                                                       self.timemodel)
        if key is not None:
            if len(self._plans) >= 128:      # bound cache growth (FIFO)
                self._plans.pop(next(iter(self._plans)))
            self._plans[key] = self._cache_copy(plan)
        return plan

    @staticmethod
    def _cache_copy(plan: Plan) -> Plan:
        """The cached entry must not pin user data: INPUT leaf payloads,
        RESIDENT handles (they pin arena tiles) and the expression root are
        dropped — a hit rebinds fresh leaves."""
        prog = plan.program
        stripped = []
        for uid in prog.leaf_order:
            n = prog.leaf_nodes[uid]
            if n.op in (Op.INPUT, Op.RESIDENT):
                n = ClusteredMatrix(n.op, n.shape, n.dtype, payload=None,
                                    name=n.name)
            stripped.append(n)
        p = prog.rebound(stripped)
        p.root = None
        p.roots = []
        return Plan(p, plan.schedule, plan.sim, plan.tile, plan.plan_seconds,
                    spec=plan.spec, waves=plan.waves,
                    batched_makespan=plan.batched_makespan,
                    _cluster_pred=plan._cluster_pred,
                    _elastic_pred=plan._elastic_pred,
                    peak_bytes=plan.peak_bytes,
                    spill_bytes=plan.spill_bytes,
                    spill_seconds=plan.spill_seconds)

    def _default_tile(self, roots: Sequence[ClusteredMatrix]) -> int:
        # paper finding: tile ~ n/2 is best for n=10k on 8 nodes (§3.3);
        # fall back to half the largest dimension.
        if isinstance(roots, ClusteredMatrix):
            roots = (roots,)
        dim = max(max(n.shape) for n in topo_order_many(roots))
        return max(1, dim // 2)

    def predict_recompute_seconds(self, roots: Sequence[ClusteredMatrix],
                                  tile=None) -> float:
        """Simulated wall-clock of re-deriving ``roots`` from scratch on
        the current spec — the lineage-recompute leg of the durable
        session's per-handle reload-vs-recompute pricing (the reload leg
        is ``simulator.predict_reload_seconds`` on the same TimeModel)."""
        roots = list(roots)
        plan = self.plan_many(roots, tile=tile,
                              persist=tuple(range(len(roots))))
        return plan.sim.makespan

    def autotune_tile(self, root: ClusteredMatrix,
                      candidates: Sequence[int]) -> Tuple[int, Dict[int, float]]:
        """§3.3: pick the tile size with the best *simulated* makespan,
        costing each candidate at its cheapest executor strategy."""
        scores: Dict[int, float] = {}
        for c in candidates:
            scores[c] = self.plan(root, tile=c).best_predicted_makespan
        best = min(scores, key=lambda k: (scores[k], k))
        return best, scores

    # -- execution ------------------------------------------------------------
    def run(self, root: ClusteredMatrix, tile=None, executor: str = "local",
            validate: bool = False, plan: Optional[Plan] = None,
            **exec_kw) -> np.ndarray:
        """Execute through a backend from the ``repro.exec.EXECUTORS``
        registry:

        * ``"local"``          — per-task threaded NumPy executor;
        * ``"batched"``        — wave-batched stacked-kernel executor;
        * ``"batched-pallas"`` — wave-batched, ADDMUL groups through
          ``jax.vmap`` over the Pallas blocked GEMM (the device path);
        * ``"cluster"``        — one worker process per cluster node,
          HEFT node placements executed for real under the elastic
          control plane (membership, lineage recovery, re-planning);
        * ``"auto"``           — simulation-driven choice between the
          per-task, wave-batched and cluster strategies for this plan
          (churn-priced when the engine runs with ``elastic=True``).

        ``run`` is the thin ONE-SHOT wrapper over the session execution
        path (``execute_plan``): plan, execute with an ephemeral executor,
        gather everything to the master and discard all executor state.
        Iterative workloads that want results to stay resident between
        calls should use :class:`repro.core.session.CMMSession` instead.
        """
        plan = plan or self.plan(root, tile=tile)
        out = self.execute_plan(plan, executor=executor, **exec_kw)
        if validate:
            ref = root.eager()
            np.testing.assert_allclose(out, ref, rtol=1e-8, atol=1e-8)
        return out

    def execute_plan(self, plan: Plan, executor: str = "local",
                     executor_obj=None, **exec_kw):
        """Execute a prepared plan — the engine half shared by one-shot
        ``run()`` and the session engine.  ``executor_obj`` lets a session
        pass its *long-lived* executor instance (resident arenas survive
        across calls); otherwise an ephemeral backend is built from the
        registry."""
        if executor == "auto":
            executor = self.choose_executor(plan)
        if executor == "cluster" and executor_obj is None \
                and "timemodel" not in exec_kw:
            # frontier re-planning inside the executor must price nodes
            # with the same model the original schedule used; it also
            # prices per-edge wire codecs against it
            exec_kw["timemodel"] = self.timemodel
        if executor_obj is None:
            from ..exec import make_executor
            if executor == "batched-pallas":
                exec_kw.setdefault("staging", self.staging)
            executor_obj = make_executor(executor, **exec_kw)
        out = executor_obj.execute(plan)
        self.last_exec_stats = dict(executor_obj.stats)
        self.last_exec_stats["executor"] = executor
        self.last_spans = list(getattr(executor_obj, "spans", []) or [])
        self.last_plan = plan
        return out

    # -- flight recorder ----------------------------------------------------
    def dump_trace(self, path: str, include_predicted: bool = False) -> int:
        """Export the last run's planner and executor spans as
        Chrome-trace JSON (load in
        ``chrome://tracing`` or https://ui.perfetto.dev).  With
        ``include_predicted`` the simulator's predicted timeline is
        overlaid on shifted lanes, so drift is visible in the viewer.
        Returns the number of events written."""
        spans = list(self.last_spans)
        if self.last_plan is not None:
            spans = self.last_plan.spans + spans
        if include_predicted and self.last_plan is not None \
                and self.last_plan.sim is not None:
            spans += self.last_plan.sim.predicted_spans()
        from ..runtime.telemetry import export_chrome_trace
        return len(export_chrome_trace(spans, path)["traceEvents"])

    def drift_report(self, **kw):
        """Predicted-vs-actual drift over the last run's spans
        (:func:`repro.core.drift.drift_report` against the last plan)."""
        if self.last_plan is None:
            raise RuntimeError("no executed plan to analyse — "
                               "run execute_plan() first")
        from .drift import drift_report
        return drift_report(self.last_spans, self.last_plan,
                            tm=self.timemodel, **kw)

    def roofline_report(self, **kw):
        """Achieved-vs-roofline analysis over the last run's spans
        (:func:`repro.core.roofline.roofline_report` against the last
        plan) — nodes far below the analytic ceiling are straggler
        priors even when the fitted model has absorbed their slowdown."""
        if self.last_plan is None:
            raise RuntimeError("no executed plan to analyse — "
                               "run execute_plan() first")
        from .roofline import roofline_report
        return roofline_report(self.last_spans, self.last_plan,
                               tm=self.timemodel, **kw)

    def roofline_audit(self, plan: Optional[Plan] = None, **kw):
        """Audit the TimeModel against the analytic roofline for a plan's
        task signatures (:func:`repro.core.roofline.audit_timemodel`)."""
        plan = plan or self.last_plan
        if plan is None:
            raise RuntimeError("no plan to audit — plan() or run() first")
        return audit_timemodel(plan.program.graph, self.timemodel,
                               spec=plan.spec, **kw)

    def choose_executor(self, plan: Plan) -> str:
        """Per-plan executor strategy from predicted makespans (§3.3's
        simulation-driven selection, extended to execution strategy).

        Under ``elastic=True`` the multi-process strategy is priced at
        its churn-adjusted makespan (expected lineage-recovery cost under
        ``tm.node_mtbf``) — an unreliable cluster can tip ``auto`` back
        to an in-process strategy even when the pristine cluster
        prediction wins.
        """
        if not self.elastic:
            return plan.best_executor
        best, t = "local", plan.sim.makespan
        if plan.batched_makespan is not None and plan.batched_makespan < t:
            best, t = "batched", plan.batched_makespan
        em = plan.elastic_makespan
        if em is not None and em < t:
            best, t = "cluster", em
        return best

    def theoretical_speedup(self, root: ClusteredMatrix, tile=None,
                            n_nodes: Optional[int] = None) -> float:
        """Table 4: zero-communication simulated speedup vs one node."""
        spec_n = self.spec if n_nodes is None else self.spec.with_nodes(n_nodes)
        eng_n = CMMEngine(spec_n, self.timemodel, cache_aware=self.cache_aware)
        plan_n = eng_n.plan(root, tile=tile)
        zc = simulate(plan_n.program.graph, plan_n.schedule, spec_n,
                      self.timemodel, zero_comm=True)
        eng_1 = CMMEngine(self.spec.with_nodes(1), self.timemodel,
                          cache_aware=self.cache_aware)
        plan_1 = eng_1.plan(root, tile=tile)
        return plan_1.sim.makespan / max(zc.makespan, 1e-12)
