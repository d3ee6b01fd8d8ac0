"""Predicted-vs-actual drift analysis over flight-recorder spans.

The planner commits to a tile size and a schedule because the
simulator, driven by the fitted :class:`~repro.core.timemodel.TimeModel`,
predicted they would win (the paper's §3.4–3.6 loop).  This module
closes that loop: it joins the spans a real run recorded
(``runtime/telemetry.py``) against the HEFT/simulator predicted
timeline and answers two questions —

* **which nodes drifted?**  Per-node residual ratios
  (``median(actual / predicted)`` over that node's EXEC spans,
  normalized by the fleet median so a uniformly mis-fitted model does
  not flag everyone).  Nodes outside a configurable band become
  **straggler priors**: feed them to
  ``MembershipService.seed_straggler_priors`` and the next run's
  detector fires on its first confirming sweep instead of waiting out
  its patience budget (ROADMAP item 3).

* **which model terms drifted?**  EXEC spans evidence ``kernel_time``,
  raw XFER spans evidence ``ipc_bandwidth``, PACK (encode) spans
  ``compress_bandwidth``, SPILL / FAULTIN spans the spill write/read
  bandwidths.  A term whose pooled residual leaves the band is flagged
  for recalibration, with ``TimeModel.recalibrated(term, ratio)`` as
  the one-line fix.

The join is replanning-safe: a task that ran on its *planned* node
compares against its simulated interval; a task the elastic runtime
re-routed (death/join/straggle) is re-priced on the node it actually
ran on through :class:`~repro.core.timemodel.CostCache`, so churned
runs still produce meaningful residuals.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional

from ..runtime.wire import predicted_xfer_seconds
from .timemodel import CostCache, TimeModel

__all__ = ["NodeDrift", "TermDrift", "DriftReport", "drift_report"]

#: predicted durations below this floor are noise, not evidence — a
#: ratio against a ~0 prediction would dominate every median
_MIN_PREDICTED_S = 1e-7


def _median(xs: List[float]) -> float:
    s = sorted(xs)
    n = len(s)
    return s[n // 2] if n % 2 else 0.5 * (s[n // 2 - 1] + s[n // 2])


@dataclass
class NodeDrift:
    """One node's residual summary over its EXEC spans."""

    node: int
    samples: int
    actual_s: float
    predicted_s: float
    #: median(actual / predicted) over this node's tasks; None without
    #: samples
    ratio: Optional[float]
    #: ratio normalized by the fleet median ratio — the drift signal
    rel: Optional[float]
    #: outside the band (either direction) with enough samples
    flagged: bool

    def as_dict(self) -> dict:
        return {"node": self.node, "samples": self.samples,
                "actual_s": self.actual_s,
                "predicted_s": self.predicted_s,
                "ratio": self.ratio, "rel": self.rel,
                "flagged": self.flagged}


@dataclass
class TermDrift:
    """One TimeModel term's pooled residual across all its spans."""

    term: str
    samples: int
    #: median(actual / predicted) under the current term value
    ratio: Optional[float]
    flagged: bool
    #: the recalibrated value ``TimeModel.recalibrated(term, ratio)``
    #: would set (None for kernel_time, whose fix is a coefficient
    #: scale, and for unflagged/unsampled terms)
    suggested: Optional[float] = None

    def as_dict(self) -> dict:
        return {"term": self.term, "samples": self.samples,
                "ratio": self.ratio, "flagged": self.flagged,
                "suggested": self.suggested}


@dataclass
class DriftReport:
    nodes: List[NodeDrift]
    terms: List[TermDrift]
    #: nodes whose relative residual exceeded the band on the slow side
    #: — feed to ``MembershipService.seed_straggler_priors`` /
    #: ``ClusterExecutor(straggler_priors=...)``
    straggler_priors: List[int]
    band: float
    #: fleet-median actual/predicted ratio (the model's uniform bias)
    fleet_ratio: Optional[float] = None

    def node(self, n: int) -> Optional[NodeDrift]:
        for nd in self.nodes:
            if nd.node == n:
                return nd
        return None

    def term(self, name: str) -> Optional[TermDrift]:
        for td in self.terms:
            if td.term == name:
                return td
        return None

    def as_dict(self) -> dict:
        return {"band": self.band,
                "fleet_ratio": self.fleet_ratio,
                "straggler_priors": list(self.straggler_priors),
                "nodes": [nd.as_dict() for nd in self.nodes],
                "terms": [td.as_dict() for td in self.terms]}

    def summary(self) -> str:
        lines = [f"drift report (band {self.band}x, fleet ratio "
                 f"{self.fleet_ratio if self.fleet_ratio is None else round(self.fleet_ratio, 3)})"]
        for nd in self.nodes:
            mark = " <-- STRAGGLER PRIOR" if nd.node in \
                self.straggler_priors else (" <-- drifted"
                                            if nd.flagged else "")
            r = "n/a" if nd.ratio is None else f"{nd.ratio:.2f}x"
            lines.append(f"  node {nd.node}: {nd.samples} tasks, "
                         f"residual {r}{mark}")
        for td in self.terms:
            if td.ratio is None:
                continue
            mark = " <-- recalibrate" if td.flagged else ""
            lines.append(f"  term {td.term}: {td.samples} samples, "
                         f"residual {td.ratio:.2f}x{mark}")
        return "\n".join(lines)


def _ratio_rows(spans, plan, tm) -> Dict[str, List[float]]:
    """actual/predicted ratio samples per evidence stream."""
    g = plan.program.graph
    spec = plan.spec
    pred_iv = {iv.tid: iv for iv in plan.sim.intervals} \
        if plan.sim is not None else {}
    cost = CostCache(tm, spec)
    rows: Dict[str, List[float]] = {
        "kernel_time": [], "ipc_bandwidth": [],
        "compress_bandwidth": [], "spill_write_bandwidth": [],
        "spill_read_bandwidth": [],
    }
    per_node: Dict[int, List[float]] = {}
    per_node_sum: Dict[int, List[float]] = {}
    for sp in spans:
        if sp.cat == "EXEC":
            tid = sp.args.get("tid")
            t = g.tasks.get(tid) if tid is not None else None
            if t is None:
                continue
            iv = pred_iv.get(tid)
            if iv is not None and iv.node == sp.node:
                p = iv.end - iv.start
            elif spec is not None and 0 <= sp.node < spec.n_nodes:
                # re-routed under churn: price on the actual node
                p = cost.time(t, sp.node)
            elif spec is not None:
                p = cost.avg(t)       # joined node outside the spec
            else:
                continue
            if p < _MIN_PREDICTED_S:
                continue
            r = sp.dur / p
            rows["kernel_time"].append(r)
            per_node.setdefault(sp.node, []).append(r)
            per_node_sum.setdefault(sp.node, []).append((sp.dur, p))
        elif sp.cat == "XFER":
            nbytes = sp.args.get("nbytes", 0)
            codec = sp.args.get("codec", "raw")
            p = predicted_xfer_seconds(
                nbytes, tm, codec, sp.args.get("comp_nbytes", 0))
            if p < _MIN_PREDICTED_S:
                continue
            term = ("ipc_bandwidth" if codec == "raw"
                    else "compress_bandwidth")
            rows[term].append(sp.dur / p)
        elif sp.cat == "PACK":
            nbytes = sp.args.get("nbytes", 0)
            cbw = getattr(tm, "compress_bandwidth", 0.0)
            if nbytes and cbw > 0:
                p = nbytes / cbw
                if p >= _MIN_PREDICTED_S:
                    rows["compress_bandwidth"].append(sp.dur / p)
        elif sp.cat == "SPILL":
            nbytes = sp.args.get("nbytes", 0)
            bw = getattr(tm, "spill_write_bandwidth", 0.0)
            if nbytes and bw > 0:
                p = nbytes / bw
                if p >= _MIN_PREDICTED_S:
                    rows["spill_write_bandwidth"].append(sp.dur / p)
        elif sp.cat == "FAULTIN":
            nbytes = sp.args.get("nbytes", 0)
            bw = getattr(tm, "spill_read_bandwidth", 0.0)
            if nbytes and bw > 0:
                p = nbytes / bw
                if p >= _MIN_PREDICTED_S:
                    rows["spill_read_bandwidth"].append(sp.dur / p)
    rows["__per_node__"] = per_node            # type: ignore[assignment]
    rows["__per_node_sum__"] = per_node_sum    # type: ignore[assignment]
    return rows


def drift_report(spans: Iterable, plan, tm: Optional[TimeModel] = None,
                 band: float = 1.5, min_samples: int = 3,
                 nodes: Optional[Iterable[int]] = None) -> DriftReport:
    """Join measured spans against the plan's predicted timeline.

    ``band`` is the residual tolerance: a node (or term) whose
    normalized residual ratio leaves ``[1/band, band]`` with at least
    ``min_samples`` samples is flagged.  ``nodes`` forces a row for
    every listed node even without samples (default: every node of
    ``plan.spec``), so the report always answers "what about node k?".
    """
    if tm is None:
        tm = getattr(plan, "timemodel", None)
    if tm is None:
        from .timemodel import analytic_time_model
        tm = analytic_time_model()
    spans = list(spans)
    rows = _ratio_rows(spans, plan, tm)
    per_node: Dict[int, List[float]] = rows.pop("__per_node__")
    per_node_sum = rows.pop("__per_node_sum__")

    if nodes is None:
        spec = plan.spec
        nodes = range(spec.n_nodes) if spec is not None else []
    all_nodes = sorted(set(int(n) for n in nodes) | set(per_node))

    node_ratio = {n: _median(per_node[n]) for n in per_node}
    fleet = _median(list(node_ratio.values())) if node_ratio else None
    node_rows: List[NodeDrift] = []
    priors: List[int] = []
    for n in all_nodes:
        samples = per_node.get(n, [])
        ratio = node_ratio.get(n)
        rel = None
        flagged = False
        if ratio is not None and fleet and fleet > 0:
            rel = ratio / fleet
            flagged = (len(samples) >= min_samples
                       and (rel > band or rel < 1.0 / band))
            if flagged and rel > band:
                priors.append(n)
        sums = per_node_sum.get(n, [])
        node_rows.append(NodeDrift(
            node=n, samples=len(samples),
            actual_s=sum(a for a, _ in sums),
            predicted_s=sum(p for _, p in sums),
            ratio=ratio, rel=rel, flagged=flagged))

    term_rows: List[TermDrift] = []
    for term, samples in rows.items():
        ratio = _median(samples) if samples else None
        flagged = (ratio is not None and len(samples) >= min_samples
                   and (ratio > band or ratio < 1.0 / band))
        suggested = None
        if flagged and term != "kernel_time":
            cur = getattr(tm, term, 0.0)
            if cur > 0:
                suggested = cur / ratio
        term_rows.append(TermDrift(term=term, samples=len(samples),
                                   ratio=ratio, flagged=flagged,
                                   suggested=suggested))

    return DriftReport(nodes=node_rows, terms=term_rows,
                       straggler_priors=priors, band=band,
                       fleet_ratio=fleet)
