"""Where a unit's time goes, by the program's own spans.

    python3 bench/phases.py --workload <cell> --seed <n> --units <k> \
        [--untraced-units <u>] [--profile-units <p>] [--set key=value] \
        [--out <file>]

from the root of a checkout, on the chip.  It runs the cell's loop as
``run.py`` does (set-up, one warm-up unit), then ``k`` units with the wave
executor's tracer on (the default) and, in a one-shot cell, ``u`` (default
``k``) with it off, alternating while both last, then ``p`` units with the
tracer on under the JAX profiler.  A session keeps its executor's tracer
on, as in ``run.py``, so a session cell runs traced units only.  Every
traced unit is split by the spans that the planner (``Plan.spans``) and
the wave executor (``engine.last_spans``) recorded, and by the executor's
counters (``engine.last_exec_stats``); a session query takes together
those of all its persists.  ``--set`` replaces a size of the
configuration (a JSON value), to probe other sizes than the cell's:

* ``planner_ms``: the ``PLAN`` span;
* ``xfer_ms``: the ``H2D`` and ``D2H`` spans;
* ``kernel_ms``: the ``KERNEL`` spans (launch to result ready);
* ``host_ms``: the executor's host work: ``GATHER``, ``STORE``,
  ``ASSEMBLE`` and the ``EXEC`` spans of groups that run no kernel;
* ``other_ms``: the unit's time outside ``PLAN``, ``EXEC`` and
  ``ASSEMBLE`` (building the expression, the executor's set-up);
* ``xfer_mib``: ``(h2d_bytes + d2h_bytes) / 2**20`` of the executor's
  counters; ``host_copy_mib`` likewise of ``host_copy_bytes``;
* ``spans``: ``[category, name, wave, start_ms, ms]`` of every span, the
  start counted from the unit's first span.

The profiled units' trace is reduced by ``trace_reduce.reduce_trace``, so
its idle time goes to the innermost ``cmm.`` or ``bench.`` annotation.
One JSON object goes to ``--out`` and a summary to standard output.  Nothing here is read by ``run.py``.
"""
from __future__ import annotations

import argparse
import json
import shutil
import statistics
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]

from bench.harness import (OneShot, Session, UNITS,  # noqa: E402
                           UNIT_ANNOTATION, WARMUP, load_json, make_env,
                           resolve, rng)

HOST_PHASES = ("GATHER", "STORE", "ASSEMBLE")
STATS = ("h2d_bytes", "d2h_bytes", "host_copy_bytes")


def _inside(child, parent) -> bool:
    return parent.t0 <= child.t0 and \
        child.t0 + child.dur <= parent.t0 + parent.dur


def unit_phases(plan_spans, exec_spans, stats,
                dur_s: float) -> Dict[str, object]:
    """One program's phase numbers (milliseconds, MiB) from its spans,
    the executor's counters and its duration."""
    spans = sorted(list(plan_spans) + list(exec_spans), key=lambda s: s.t0)
    by_cat: Dict[str, float] = {}
    for s in spans:
        by_cat[s.cat] = by_cat.get(s.cat, 0.0) + s.dur
    kernels = [s for s in exec_spans if s.cat == "KERNEL"]
    host_groups = sum(s.dur for s in exec_spans if s.cat == "EXEC" and
                      not any(_inside(k, s) for k in kernels))
    ms = 1e3
    t0 = spans[0].t0 if spans else 0.0
    return {
        "planner_ms": by_cat.get("PLAN", 0.0) * ms,
        "xfer_ms": (by_cat.get("H2D", 0.0) + by_cat.get("D2H", 0.0)) * ms,
        "kernel_ms": by_cat.get("KERNEL", 0.0) * ms,
        "host_ms": (host_groups +
                    sum(by_cat.get(c, 0.0) for c in HOST_PHASES)) * ms,
        "xfer_mib": (stats["h2d_bytes"] + stats["d2h_bytes"]) / 2 ** 20,
        "host_copy_mib": stats["host_copy_bytes"] / 2 ** 20,
        "other_ms": (dur_s - sum(by_cat.get(c, 0.0) for c in
                                 ("PLAN", "EXEC", "ASSEMBLE"))) * ms,
        "phase_ms": {c: v * ms for c, v in sorted(by_cat.items())},
        "spans": [[s.cat, s.name, s.args.get("wave"), (s.t0 - t0) * ms,
                   s.dur * ms] for s in spans],
    }


class PhasedOneShot(OneShot):
    """The cell's one-shot loop, with the executor's tracer on or off
    (``trace``) and each traced unit's phases in its record."""

    trace = True

    def one(self, r):
        cfg, engine = self.cfg, self.engine
        arrays = self.inputs(r)
        t0 = time.perf_counter()
        with self.ann("bench.program"):
            expr = self.prog.build(arrays, cfg)
            with self.ann("bench.plan"):
                plan = engine.plan(expr, tile=cfg["tile"])
            with self.ann("bench.execute"):
                out = engine.execute_plan(plan, executor=cfg["executor"],
                                          precision=cfg["precision"],
                                          trace=self.trace)
        rec = {"dur_s": time.perf_counter() - t0, "traced": self.trace,
               "profiled": self.tracing}
        if self.trace:
            rec.update(unit_phases(plan.spans, engine.last_spans,
                                   engine.last_exec_stats, rec["dur_s"]))
        return rec, out


class PhasedSession(Session):
    """The cell's session loop, each query's phases in its record: the
    spans and counters of the query's persists taken together."""

    def __init__(self, env: dict):
        self.parts = self._empty()
        super().__init__(env)

    @staticmethod
    def _empty() -> dict:
        return {"plan": [], "exec": [], "stats": dict.fromkeys(STATS, 0)}

    def persist(self, expr):
        h = super().persist(expr)
        e = self.engine
        self.parts["plan"] += e.last_plan.spans
        self.parts["exec"] += e.last_spans
        for k in STATS:
            self.parts["stats"][k] += e.last_exec_stats[k]
        return h

    def one(self, r):
        self.parts = self._empty()
        rec, out = super().one(r)
        rec.update(traced=True, profiled=self.tracing)
        p = self.parts
        rec.update(unit_phases(p["plan"], p["exec"], p["stats"],
                               rec["dur_s"]))
        return rec, out


PHASED = {"oneshot": PhasedOneShot, "session": PhasedSession}


def _median_of(records: List[dict], key: str) -> Optional[float]:
    vals = [r[key] for r in records if key in r]
    return statistics.median(vals) if vals else None


def summarize(records: List[dict]) -> dict:
    """Medians of the phase numbers over traced, unprofiled units; the
    slowest such unit beside the median one; and the median unit's
    seconds with the executor's tracer on, off and under the profiler."""
    plain = [r for r in records if r["traced"] and not r["profiled"]]
    keys = ("dur_s", "planner_ms", "xfer_ms", "kernel_ms", "host_ms",
            "other_ms", "xfer_mib", "host_copy_mib")
    out = {"median": {k: _median_of(plain, k) for k in keys}}
    if plain:
        by_dur = sorted(plain, key=lambda r: r["dur_s"])
        out["median_unit"] = by_dur[(len(by_dur) - 1) // 2]
        out["slowest_unit"] = by_dur[-1]
    for name, sel in (("traced", plain),
                      ("untraced", [r for r in records if not r["traced"]]),
                      ("profiled", [r for r in records if r["profiled"]])):
        out[f"unit_s.{name}"] = _median_of(sel, "dur_s")
    return out


def run_phases(cell: str, seed: int, units: int,
               untraced_units: Optional[int], profile_units: int, *,
               spec: Optional[dict] = None, overrides: Optional[dict] = None,
               trace_dir: Optional[Path] = None, n_devices: int = 1,
               log=print) -> dict:
    """Run ``units`` traced and ``untraced_units`` untraced units of a
    cell, alternating while both last, then ``profile_units`` profiled
    ones (``untraced_units`` None: as many as ``units`` in a one-shot
    cell, none in a session); returns the unit records, their summary
    and (with ``profile_units``) the reduction of the profiled units'
    trace, which is empty where the trace holds no device plane."""
    import jax
    from bench.trace_reduce import load_events, reduce_trace
    spec = spec if spec is not None else load_json(ROOT / "BENCHMARK.json")
    files = resolve(spec, cell)
    loop = files["traffic"]["loop"]
    if untraced_units is None:
        untraced_units = units if loop == "oneshot" else 0
    if loop == "session" and untraced_units:
        raise ValueError(f"{cell}: a session runs traced units only")
    lp = PHASED[loop](make_env(files, seed, overrides))
    lp.one(rng(seed, WARMUP))
    order = [True, False] * min(units, untraced_units) + \
        [units > untraced_units] * abs(units - untraced_units)
    records = []
    for i, traced in enumerate(order):
        lp.trace = traced
        records.append(lp.one(rng(seed, UNITS, i))[0])
    reduction = None
    if profile_units:
        trace_dir = str(trace_dir or ROOT / ".bench_cache" / "phases_trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
        lp.tracing, lp.trace = True, True
        try:
            for i in range(profile_units):
                records.append(lp.one(rng(seed, UNITS, len(order) + i))[0])
        finally:
            jax.profiler.stop_trace()
            lp.tracing = False
        ops, anns = load_events(trace_dir, n_devices)
        reduction = reduce_trace(ops, anns, UNIT_ANNOTATION[loop], top=40)
        reduction["annotations"] = sorted({a[0] for a in anns})
    lp.close()
    summary = summarize(records)
    if "slowest_unit" in summary:
        for which in ("median_unit", "slowest_unit"):
            r = summary[which]
            log(f"{which}: {r['dur_s']!r} s, phases ms "
                f"{json.dumps(r['phase_ms'])}")
    return {"workload": cell, "seed": seed,
            "overrides": overrides or {}, "records": records,
            "summary": summary, "reduction": reduction}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--units", type=int, required=True)
    ap.add_argument("--untraced-units", type=int, default=None)
    ap.add_argument("--profile-units", type=int, default=3)
    ap.add_argument("--set", action="append", default=[],
                    metavar="KEY=VALUE",
                    help="replace a size of the configuration")
    ap.add_argument("--out", default=str(ROOT / "chiprun_out" /
                                         "phases.json"))
    args = ap.parse_args()

    from bench.harness import configure_jax
    jax = configure_jax()
    if jax.devices()[0].platform != "tpu":
        sys.exit("phases: JAX found no TPU")
    overrides = {k: json.loads(v) for k, v in
                 (kv.split("=", 1) for kv in args.set)}
    res = run_phases(args.workload, args.seed, args.units,
                     args.untraced_units, args.profile_units,
                     overrides=overrides)
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(res, f)
    print(json.dumps({"summary": {k: v for k, v in res["summary"].items()
                                  if not k.endswith("_unit")},
                      "idle_gaps": (res["reduction"] or {}).get(
                          "idle_gaps"),
                      "device_ops": (res["reduction"] or {}).get(
                          "device_ops")}), flush=True)


if __name__ == "__main__":
    main()
