"""Flight recorder: spans, clock alignment, and a unified metrics registry.

The paper's thesis is that a fitted time model predicts cluster capacity
well enough to drive tile-size and schedule simulation — but the repo
had no way to *see* whether a real run matched its predicted timeline.
This module is the observability substrate every executor shares:

* :class:`Tracer` — per-process span recorder over the monotonic clock.
  A span is one timed region (task EXEC, wire XFER, arena SPILL /
  FAULTIN, checkpoint write, frontier REPLAN, result GATHER) tagged
  with its node and a per-thread lane.  Worker processes buffer spans
  locally and piggyback them on the done/heartbeat/stats messages they
  already send — tracing adds **no new queues and no extra wakeups**,
  which is what keeps it cheap enough to stay on by default (the
  ``obs_bench`` gate holds the paired overhead under 5%).

* **clock-offset calibration** — master and worker timestamps come from
  each process's ``time.perf_counter``.  At worker handshake the master
  sends a ``("cal", t_send)`` op and the worker echoes its own clock;
  :func:`estimate_clock_offset` is the NTP-style midpoint estimate
  ``offset = t_worker - (t_send + t_recv) / 2`` under which
  ``t_master = t_worker - offset``.  (On Linux ``perf_counter`` is the
  system-wide CLOCK_MONOTONIC, so measured offsets are ~0 — the
  machinery matters on platforms with per-process clocks, and it makes
  the alignment unit-testable with fake clocks.)

* :class:`MetricsRegistry` — counters, gauges, and bounded log-bucket
  histograms behind one lock, replacing the executors' ad-hoc ``stats``
  dicts.  ``inc`` is the *atomic* increment path every non-master-thread
  stat update must take (bare ``dict[k] += 1`` is a lost-update bug the
  moment two threads race it); ``frozen_view`` hands tests/benchmarks
  the read-only dict they always consumed.

* **the profiler's clock** — where JAX is already loaded, every span an
  enabled tracer records also opens a ``jax.profiler.TraceAnnotation``
  named ``cmm.<span name>`` over the same region, so a device trace
  puts each idle gap down to the host phase around it.  A process that
  has not imported JAX (cluster workers, NumPy-only paths) never does.

* :func:`chrome_trace` / :func:`export_chrome_trace` — Chrome
  trace-event JSON (loads in ``chrome://tracing`` and Perfetto) with
  one process lane per node and one thread lane per worker slot, so
  compute/XFER overlap is visible exactly as numpywren's profile
  timelines render serverless runs.

The drift consumer (``core/drift.py``) joins these spans against the
HEFT/simulator predicted timeline.
"""
from __future__ import annotations

import json
import math
import sys
import threading
import time
from types import MappingProxyType
from typing import Dict, Iterable, List, Mapping, Optional

__all__ = [
    "Span", "Tracer", "NULL_TRACER", "MetricsRegistry",
    "estimate_clock_offset", "chrome_trace", "export_chrome_trace",
]


# -- spans --------------------------------------------------------------------
class Span:
    """One timed region: ``[t0, t0 + dur)`` on ``node``/``lane``.

    ``cat`` is the span's category (EXEC/XFER/SPILL/...), the join key
    for every consumer; ``name`` is the display label; ``args`` carries
    the category-specific payload (task id, bytes, codec, ...).
    Timestamps are seconds on the recording process's monotonic clock
    until the master ingests them through :meth:`Tracer.ingest`, which
    shifts them onto the master timeline.
    """

    __slots__ = ("name", "cat", "node", "lane", "t0", "dur", "args")

    def __init__(self, name: str, cat: str, node: int, lane: int,
                 t0: float, dur: float, args: Optional[dict] = None):
        self.name = name
        self.cat = cat
        self.node = node
        self.lane = lane
        self.t0 = t0
        self.dur = dur
        self.args = args or {}

    def __reduce__(self):  # __slots__ classes need explicit pickling
        return (Span, (self.name, self.cat, self.node, self.lane,
                       self.t0, self.dur, self.args))

    def __repr__(self):  # pragma: no cover — debugging aid
        return (f"Span({self.cat} {self.name!r} node={self.node} "
                f"lane={self.lane} t0={self.t0:.6f} dur={self.dur:.6f})")


#: span name -> its profiler annotation's name, built once per name (span
#: names are task kinds and category constants, never per-call strings)
_ANNOTATION_NAMES: Dict[str, str] = {}


def _profiler_annotation(name: str):
    """A ``jax.profiler.TraceAnnotation`` named ``cmm.<name>``, or None
    where the process has not imported JAX (it is never imported here)."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    label = _ANNOTATION_NAMES.get(name)
    if label is None:
        label = _ANNOTATION_NAMES.setdefault(name, "cmm." + name)
    return jax.profiler.TraceAnnotation(label)


class _SpanCtx:
    """Context manager recording one span on ``__exit__`` (kept as a
    tiny slotted class instead of ``contextlib`` to stay off the hot
    path's allocation budget).  The span's profiler annotation encloses
    its clock readings."""

    __slots__ = ("tr", "name", "cat", "lane", "args", "t0", "ann")

    def __init__(self, tr: "Tracer", name: str, cat: str,
                 lane: Optional[int], args: dict):
        self.tr = tr
        self.name = name
        self.cat = cat
        self.lane = lane
        self.args = args

    def __enter__(self):
        self.ann = _profiler_annotation(self.name)
        if self.ann is not None:
            self.ann.__enter__()
        self.t0 = self.tr.clock()
        return self

    def __exit__(self, exc_type, exc, tb):
        tr = self.tr
        t1 = tr.clock()
        lane = self.lane if self.lane is not None else tr.lane()
        sp = Span(self.name, self.cat, tr.node, lane,
                  self.t0, t1 - self.t0, self.args)
        with tr._lock:
            tr._spans.append(sp)
        if self.ann is not None:
            self.ann.__exit__(exc_type, exc, tb)
        return False


class _NullSpanCtx:
    """Shared no-op context for a disabled tracer (zero allocation)."""

    __slots__ = ()

    @property
    def args(self) -> dict:
        """A fresh dict: what a caller writes into a disabled span's
        payload goes nowhere."""
        return {}

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False


_NULL_CTX = _NullSpanCtx()


class Tracer:
    """Per-process span buffer over a monotonic clock.

    Thread-safe: worker pool threads record concurrently; ``drain``
    hands the buffered spans to whoever serializes them over the
    message path.  ``enabled=False`` turns every ``span()`` into a
    shared no-op context (the tracing-off leg of the overhead gate).
    """

    def __init__(self, node: int = 0, enabled: bool = True,
                 clock=time.perf_counter):
        self.node = node
        self.enabled = enabled
        self.clock = clock
        self._lock = threading.Lock()
        self._spans: List[Span] = []
        self._lanes: Dict[int, int] = {}

    # -- recording ----------------------------------------------------------
    def lane(self) -> int:
        """Small stable lane id for the calling thread (worker slot)."""
        ident = threading.get_ident()
        lane = self._lanes.get(ident)
        if lane is None:
            with self._lock:
                lane = self._lanes.setdefault(ident, len(self._lanes))
        return lane

    def span(self, name: str, cat: Optional[str] = None,
             lane: Optional[int] = None, **args):
        """``with tracer.span("EXEC", tid=7): ...`` — records on exit."""
        if not self.enabled:
            return _NULL_CTX
        return _SpanCtx(self, name, cat or name, lane, args)

    def add(self, span: Span) -> None:
        if self.enabled:
            with self._lock:
                self._spans.append(span)

    # -- transport ----------------------------------------------------------
    def drain(self) -> List[Span]:
        """Take and clear the buffered spans (piggybacked on the next
        outgoing done/heartbeat/stats message)."""
        if not self._spans:
            return []
        with self._lock:
            out, self._spans = self._spans, []
        return out

    def ingest(self, spans: Optional[Iterable[Span]],
               offset: float = 0.0) -> None:
        """Master side: adopt worker spans, shifting their timestamps
        onto this process's clock (``t_master = t_worker - offset``
        with ``offset`` from :func:`estimate_clock_offset`)."""
        if not spans:
            return
        if offset:
            for sp in spans:
                sp.t0 -= offset
        with self._lock:
            self._spans.extend(spans)

    def snapshot(self) -> List[Span]:
        with self._lock:
            return list(self._spans)


#: module-level disabled tracer for call sites without a wired recorder
NULL_TRACER = Tracer(enabled=False)


def estimate_clock_offset(t_send: float, t_worker: float,
                          t_recv: float) -> float:
    """NTP-style midpoint offset of a worker clock from the master's.

    The master stamps ``t_send``, the worker echoes its clock
    ``t_worker``, the master receives at ``t_recv``; assuming the
    one-way delays are symmetric, the worker read its clock at master
    time ``(t_send + t_recv) / 2``, so

        ``offset = t_worker - (t_send + t_recv) / 2``

    and a worker timestamp maps to the master timeline as
    ``t_master = t_worker - offset``.
    """
    return t_worker - 0.5 * (t_send + t_recv)


# -- metrics ------------------------------------------------------------------
class _Histogram:
    """Bounded log2-bucket histogram (64 buckets from 0.1µs up).

    Constant memory regardless of sample count, mergeable across
    processes, and quantile-queryable to within a 2x bucket width —
    all a drift/latency summary needs.
    """

    __slots__ = ("count", "total", "vmin", "vmax", "buckets")

    NBUCKETS = 64
    FLOOR = 1e-7

    def __init__(self):
        self.count = 0
        self.total = 0.0
        self.vmin = math.inf
        self.vmax = -math.inf
        self.buckets = [0] * self.NBUCKETS

    def _index(self, value: float) -> int:
        if value <= self.FLOOR:
            return 0
        return min(self.NBUCKETS - 1,
                   1 + int(math.log2(value / self.FLOOR)))

    def observe(self, value: float) -> None:
        self.count += 1
        self.total += value
        if value < self.vmin:
            self.vmin = value
        if value > self.vmax:
            self.vmax = value
        self.buckets[self._index(value)] += 1

    def merge(self, other: "_Histogram") -> None:
        self.count += other.count
        self.total += other.total
        self.vmin = min(self.vmin, other.vmin)
        self.vmax = max(self.vmax, other.vmax)
        for i, c in enumerate(other.buckets):
            self.buckets[i] += c

    def quantile(self, q: float) -> float:
        """Upper edge of the bucket holding the q-quantile sample."""
        if self.count == 0:
            return 0.0
        rank = max(1, math.ceil(q * self.count))
        seen = 0
        for i, c in enumerate(self.buckets):
            seen += c
            if seen >= rank:
                return self.FLOOR * (2.0 ** i)
        return self.vmax          # pragma: no cover — rank <= count

    def summary(self) -> dict:
        return {
            "count": self.count,
            "total": self.total,
            "mean": (self.total / self.count) if self.count else 0.0,
            "min": self.vmin if self.count else 0.0,
            "max": self.vmax if self.count else 0.0,
            "p50": self.quantile(0.5),
            "p99": self.quantile(0.99),
        }


class MetricsRegistry:
    """Counters + gauges + bounded histograms behind one lock.

    ``inc`` is the atomic increment path: unlike ``d[k] += 1`` on a
    shared dict (a read-modify-write that loses updates under thread
    interleaving), every mutation here holds the registry lock.
    ``frozen_view`` materializes the read-only dict the executors'
    ``.stats`` consumers have always read.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._counters: Dict[str, float] = {}
        self._gauges: Dict[str, object] = {}
        self._hists: Dict[str, _Histogram] = {}

    # -- counters -----------------------------------------------------------
    def inc(self, key: str, n=1) -> None:
        with self._lock:
            self._counters[key] = self._counters.get(key, 0) + n

    def get(self, key: str, default=0):
        with self._lock:
            if key in self._counters:
                return self._counters[key]
            return self._gauges.get(key, default)

    # -- gauges -------------------------------------------------------------
    def gauge(self, key: str, value) -> None:
        with self._lock:
            self._gauges[key] = value

    # -- histograms ---------------------------------------------------------
    def observe(self, key: str, value: float) -> None:
        with self._lock:
            h = self._hists.get(key)
            if h is None:
                h = self._hists[key] = _Histogram()
            h.observe(value)

    def histogram(self, key: str) -> Optional[dict]:
        with self._lock:
            h = self._hists.get(key)
            return None if h is None else h.summary()

    # -- aggregation --------------------------------------------------------
    def merge(self, other: "MetricsRegistry") -> None:
        with other._lock:
            counters = dict(other._counters)
            gauges = dict(other._gauges)
            hists = dict(other._hists)
        with self._lock:
            for k, v in counters.items():
                self._counters[k] = self._counters.get(k, 0) + v
            self._gauges.update(gauges)
            for k, h in hists.items():
                mine = self._hists.get(k)
                if mine is None:
                    mine = self._hists[k] = _Histogram()
                mine.merge(h)

    def snapshot(self) -> Dict[str, object]:
        """Plain-dict copy of counters + gauges (histograms summarized
        under ``hist:<key>``)."""
        with self._lock:
            out: Dict[str, object] = dict(self._counters)
            out.update(self._gauges)
            for k, h in self._hists.items():
                out[f"hist:{k}"] = h.summary()
        return out

    def frozen_view(self, extra: Optional[Mapping] = None) -> Mapping:
        """Read-only dict view (supports ``[]``, ``.get``, ``dict()``,
        iteration) of the current snapshot plus ``extra`` overrides —
        what an executor publishes as ``.stats`` so existing tests and
        benchmarks keep working unchanged while writes are rejected."""
        snap = self.snapshot()
        if extra:
            snap.update(extra)
        return MappingProxyType(snap)


# -- Chrome trace export ------------------------------------------------------
def chrome_trace(spans: Iterable[Span], normalize: bool = True) -> dict:
    """Chrome trace-event JSON object (``chrome://tracing`` / Perfetto).

    One *process* lane per node (pid), one *thread* lane per worker
    slot (tid); "X" complete events carry microsecond ts/dur.  With
    ``normalize`` the earliest span starts at ts=0 so the viewer opens
    at the run rather than at hours of monotonic-clock uptime.
    """
    spans = list(spans)
    base = min((sp.t0 for sp in spans), default=0.0) if normalize else 0.0
    events: List[dict] = []
    lanes = set()
    for sp in spans:
        lanes.add((sp.node, sp.lane))
        events.append({
            "name": sp.name,
            "cat": sp.cat,
            "ph": "X",
            "pid": sp.node,
            "tid": sp.lane,
            "ts": (sp.t0 - base) * 1e6,
            "dur": max(sp.dur, 0.0) * 1e6,
            "args": sp.args,
        })
    for node in sorted({n for n, _ in lanes}):
        events.append({
            "name": "process_name", "ph": "M", "pid": node, "tid": 0,
            "args": {"name": ("master" if node < 0 else f"node {node}")},
        })
    for node, lane in sorted(lanes):
        events.append({
            "name": "thread_name", "ph": "M", "pid": node, "tid": lane,
            "args": {"name": f"worker {lane}"},
        })
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def export_chrome_trace(spans: Iterable[Span], path: str,
                        normalize: bool = True) -> dict:
    """Write :func:`chrome_trace` JSON to ``path``; returns the object."""
    doc = chrome_trace(spans, normalize=normalize)
    with open(path, "w") as f:
        json.dump(doc, f)
    return doc
