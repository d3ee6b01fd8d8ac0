"""Reduction of a profiler trace to the benchmark's device numbers.

Two steps, kept apart so that the second can be tested on made-up events:

* :func:`load_events` reads the ``.xplane.pb`` that ``jax.profiler`` wrote
  and returns plain tuples ``(name, start_ns, end_ns)``: the operations on
  each TPU device plane (line ``XLA Ops``, named by their HLO instruction
  without its operands), and the host annotations whose
  names start with ``bench.`` (the benchmark's own ``TraceAnnotation``s)
  or ``cmm.`` (the program's phase spans).
* :func:`reduce_trace` turns those tuples into busy time, the idle share,
  the device operations that took most time and the idle time by what the
  host was doing (the innermost annotation around it).

Busy time is the union of the operation intervals, so operations that
overlap count once.  The traced window runs from the start of the first
unit annotation (``bench.program`` / ``bench.query``) to the end of the
last one; the per-unit numbers count only the time inside unit
annotations.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

Interval = Tuple[str, float, float]

DEVICE_PLANE = re.compile(r"/device:TPU:(\d+)")
DEVICE_OPS_LINE = "XLA Ops"
ANNOTATION_PREFIXES = ("bench.", "cmm.")
NO_ANNOTATION = "(none)"


def op_name(hlo: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``: the device
    line names each operation by its HLO instruction."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def load_events(trace_dir: str, n_devices: int
                ) -> Tuple[Dict[str, List[Interval]], List[Interval]]:
    """Device operations of the first ``n_devices`` TPU planes and the
    ``bench.`` and ``cmm.`` host annotations of the newest trace under
    ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    ops: Dict[str, List[Interval]] = {}
    anns: List[Interval] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.fullmatch(plane.name)
        if m:
            if int(m.group(1)) >= n_devices:
                continue
            evs = ops.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == DEVICE_OPS_LINE:
                    evs.extend((op_name(e.name), e.start_ns, e.end_ns)
                               for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                anns.extend((e.name, e.start_ns, e.end_ns)
                            for e in line.events
                            if e.name.startswith(ANNOTATION_PREFIXES))
    return ops, anns


def _union(iv: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(iv):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


class _Busy:
    """Merged busy intervals with overlap queries."""

    def __init__(self, iv):
        self.iv = _union(iv)
        self.starts = [s for s, _ in self.iv]

    def overlap(self, a: float, b: float) -> float:
        if b <= a:
            return 0.0
        tot = 0.0
        k = max(0, bisect.bisect_right(self.starts, a) - 1)
        while k < len(self.iv) and self.iv[k][0] < b:
            s, e = self.iv[k]
            tot += max(0.0, min(e, b) - max(s, a))
            k += 1
        return tot


def _innermost_segments(anns: Sequence[Interval], lo: float, hi: float
                        ) -> List[Tuple[str, float, float]]:
    """Split ``[lo, hi]`` at every annotation boundary and label each piece
    with the innermost annotation around it (the latest to start, then the
    earliest to end), or ``NO_ANNOTATION``.  One sweep: an annotation
    joins the open ones at its start and leaves once it ends before a
    piece does, since the pieces only move later."""
    pts = sorted({lo, hi} | {t for _, s, e in anns for t in (s, e)
                             if lo < t < hi})
    by_start = sorted(anns, key=lambda x: x[1])
    k, open_, segs = 0, [], []
    for a, b in zip(pts, pts[1:]):
        while k < len(by_start) and by_start[k][1] <= a:
            open_.append(by_start[k])
            k += 1
        open_ = [x for x in open_ if x[2] >= b]
        around = [(s, -e, name) for name, s, e in open_]
        segs.append((max(around)[2] if around else NO_ANNOTATION, a, b))
    return segs


def reduce_trace(ops: Dict[str, List[Interval]], anns: Sequence[Interval],
                 unit: str, top: int = 10) -> dict:
    """Busy and idle time of the traced window, averaged over the device
    planes in ``ops``; returns seconds.

    ``unit`` names the annotation that wraps one unit of work.  Keys:
    ``window_s``/``busy_s`` (the whole traced window), ``units``,
    ``unit_s``/``unit_busy_s`` (inside unit annotations only),
    ``device_ops`` (top operations by summed time) and ``idle_gaps``
    (idle time by innermost annotation), both as ``[[name, seconds]]``.
    """
    units = [(s, e) for name, s, e in anns if name == unit]
    if not units or not ops:
        return {}
    lo = min(s for s, _ in units)
    hi = max(e for _, e in units)
    unit_iv = _union(units)
    segs = _innermost_segments([a for a in anns if a[2] > lo and a[1] < hi],
                               lo, hi)
    n_dev = len(ops)
    busy = unit_busy = 0.0
    by_op: Dict[str, float] = {}
    idle_by: Dict[str, float] = {}
    for evs in ops.values():
        b = _Busy([(s, e) for _, s, e in evs])
        busy += b.overlap(lo, hi)
        unit_busy += sum(b.overlap(s, e) for s, e in unit_iv)
        for name, s, e in evs:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                by_op[name] = by_op.get(name, 0.0) + d
        for name, a, c in segs:
            idle_by[name] = idle_by.get(name, 0.0) + (c - a) - b.overlap(a, c)
    ns = 1e-9

    def ranked(d):
        return [[k, v * ns / n_dev] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {
        "window_s": (hi - lo) * ns,
        "busy_s": busy * ns / n_dev,
        "units": len(units),
        "unit_s": sum(e - s for s, e in unit_iv) * ns,
        "unit_busy_s": unit_busy * ns / n_dev,
        "device_ops": ranked(by_op),
        "idle_gaps": ranked(idle_by),
    }
