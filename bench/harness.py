"""The general part of the benchmark: runs one cell of ``BENCHMARK.json``.

Everything that belongs to one configuration, traffic mix, cell or metric
is found by name:

* ``<config file>`` (from ``BENCHMARK.json``): the sizes, as JSON; its
  ``program`` key names ``configs/<program>.py`` (the program as a user of
  the engine writes it) and ``configs/<program>_ref.py`` (state and inputs
  from the seed, the plain reference, the least work of one unit);
* ``traffic/<traffic>.json``: the loop (``oneshot`` or ``session``) and its
  parameters;
* ``limits/<cell>.json``: the limit of each number that decides
  ``correct``;
* ``metrics/<metric>.py``: a ``read(ctx)`` that returns the metric or
  ``None`` when the run has nothing for it to read.

A unit of work is one program (one-shot loop) or one query (session loop).
Units run back to back from one caller.  State and inputs are made from
``numpy.random.default_rng([seed, stream, index])``, so a seed gives the
same data in every run.  Set-up ends with one warm-up unit of the cell's
exact shapes; the window then runs units until ``seconds`` have passed (the
last one started finishes).  With ``trace`` the same loop runs under the
JAX profiler for ``trace_units`` units (or ``seconds``, if that is sooner).
After the window, with the program's state freed, every unit's output is
compared with the plain reference.
"""
from __future__ import annotations

import gc
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time
import traceback
from contextlib import nullcontext
from pathlib import Path
from typing import Callable, List, Optional

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
UNIT_ANNOTATION = {"oneshot": "bench.program", "session": "bench.query"}

#: ``default_rng([seed, stream, index])`` streams
WARMUP, UNITS, STATE = 0, 1, 2


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import a benchmark file by path (metric names hold dots)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _for_cell(metrics: List[dict], cell: str) -> List[dict]:
    return [m for m in metrics if cell in m.get("workloads", (cell,))]


def resolve(spec: dict, cell: str) -> dict:
    """The files and metric entries of ``cell``; raises ``KeyError`` for a
    cell that ``spec`` does not hold."""
    work = {w["name"]: w for w in spec["workloads"]}[cell]
    conf = {c["name"]: c for c in spec["configs"]}[work["config"]]
    cfg = load_json(ROOT / conf["file"])
    return {
        "workload": work,
        "cfg": cfg,
        "traffic": load_json(BENCH / "traffic" / f"{work['traffic']}.json"),
        "limits": load_json(BENCH / "limits" / f"{cell}.json"),
        "program": f"bench.configs.{cfg['program']}",
        "reference": f"bench.configs.{cfg['program']}_ref",
        "end_to_end": _for_cell(spec["end_to_end"], cell),
        "per_layer": _for_cell(spec["per_layer"], cell),
    }


def make_env(files: dict, seed: int,
             overrides: Optional[dict] = None) -> dict:
    """What a loop is built from: the configuration (with ``overrides``),
    the traffic mix, the seed and the program's and reference's modules."""
    return {"cfg": dict(files["cfg"], **(overrides or {})),
            "traffic": files["traffic"], "seed": seed,
            "prog_mod": importlib.import_module(files["program"]),
            "ref_mod": importlib.import_module(files["reference"])}


def configure_jax():
    """Keep JAX's persistent compilation cache where
    ``JAX_COMPILATION_CACHE_DIR`` says, else at ``.jax_cache/`` in the
    checkout (a fixed path: the path is part of the cache's key), and
    cache every compile, the sub-second Pallas ones too.  The TPU
    runtime's logs go to ``.bench_cache/tpu_logs`` in the checkout unless
    ``TPU_LOG_DIR`` says otherwise.  Call before JAX touches a device."""
    os.environ.setdefault("TPU_LOG_DIR",
                          str(ROOT / ".bench_cache" / "tpu_logs"))

    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return jax


def metric_path(name: str) -> Path:
    return BENCH / "metrics" / f"{name}.py"


def rng(seed: int, stream: int, index: int = 0) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream, index])


def rel_err(out, ref) -> float:
    """Norm-wise relative error in float64; inf for a missing output, a
    wrong shape or a value that is not finite."""
    if out is None:
        return float("inf")
    out = np.asarray(out, np.float64)
    ref = np.asarray(ref, np.float64)
    if out.shape != ref.shape or not np.isfinite(out).all():
        return float("inf")
    return float(np.linalg.norm(out - ref) / np.linalg.norm(ref))


class CompileCounter:
    """Counts, through ``jax.monitoring``, the programs that XLA built
    (``n``): JAX reports each one's compile event whether it was compiled
    or loaded from the persistent cache, so ``cache_hits`` counts the
    loads apart."""

    def __init__(self):
        self.n = 0
        self.cache_hits = 0

    def __call__(self, event: str, duration: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            self.n += 1

    def hit(self, event: str, **_kw) -> None:
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1

    def __str__(self) -> str:
        return f"{self.n} ({self.cache_hits} from the persistent cache)"


class Loop:
    """What every loop shares: the state from the seed, annotations, unit
    records and outputs, and the comparison with the plain reference.

    A loop makes its program state in ``__init__`` (set-up), runs one
    unit per ``unit(i)`` call, frees the program state in ``close()`` and
    compares the outputs with the plain reference in ``check()``.
    """

    def __init__(self, env: dict):
        self.env = env
        self.cfg, self.traffic = env["cfg"], env["traffic"]
        self.prog, self.ref = env["prog_mod"], env["ref_mod"]
        self.seed = env["seed"]
        self.state = self.ref.make_state(self.cfg, rng(self.seed, STATE))
        self.tracing = False
        self.records: List[dict] = []
        self.outputs: List[Optional[np.ndarray]] = []
        self.inputs_s = 0.0

    def ann(self, name: str):
        if not self.tracing:
            return nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def inputs(self, r) -> dict:
        with self.ann("bench.inputs"):
            t = time.perf_counter()
            arrays = self.ref.make_inputs(self.cfg, self.traffic, r)
            self.inputs_s += time.perf_counter() - t
        return arrays

    def warm(self) -> dict:
        """One unit of the window's exact shapes, outside the records."""
        return self.one(rng(self.seed, WARMUP))[0]

    def unit(self, i: int) -> None:
        """Run unit ``i``; a unit that raises counts as failed."""
        try:
            rec, out = self.one(rng(self.seed, UNITS, i))
        except Exception:  # one failed unit must not end the run
            traceback.print_exc(file=sys.stderr)
            rec, out = {"failed": True}, None
        self.records.append(rec)
        self.outputs.append(out)

    def close(self) -> None:
        pass

    def check(self) -> List[float]:
        """Each unit's error against the plain reference, on inputs made
        again from the seed."""
        n = len(self.outputs)
        inputs = [self.ref.make_inputs(self.cfg, self.traffic,
                                       rng(self.seed, UNITS, i))
                  for i in range(n)]
        refs = self.ref.reference(self.state, inputs, self.cfg, self.traffic)
        return [rel_err(o, r) for o, r in zip(self.outputs, refs)]


class OneShot(Loop):
    """Closed loop of one-shot programs over fresh inputs."""

    def __init__(self, env: dict):
        super().__init__(env)
        from repro.core import CMMEngine, analytic_time_model
        self.engine = CMMEngine(timemodel=analytic_time_model())

    def one(self, r):
        cfg, engine = self.cfg, self.engine
        arrays = self.inputs(r)
        t0 = time.perf_counter()
        with self.ann("bench.program"):
            expr = self.prog.build(arrays, cfg)
            with self.ann("bench.plan"):
                tp = time.perf_counter()
                plan = engine.plan(expr, tile=cfg["tile"])
                plan_s = time.perf_counter() - tp
            with self.ann("bench.execute"):
                out = engine.execute_plan(plan, executor=cfg["executor"],
                                          precision=cfg["precision"])
        dur = time.perf_counter() - t0
        return {"dur_s": dur, "plan_s": plan_s,
                "group_calls": engine.last_exec_stats["batched_calls"]}, out

    def close(self) -> None:
        self.engine = None


class Session(Loop):
    """Closed loop of queries against operands persisted in set-up."""

    def __init__(self, env: dict):
        super().__init__(env)
        from repro.core import ClusteredMatrix as CM, CMMEngine, CMMSession
        from repro.core import analytic_time_model
        self.engine = CMMEngine(timemodel=analytic_time_model())
        self.session = CMMSession(self.engine, executor=self.cfg["executor"],
                                  tile=self.cfg["tile"],
                                  precision=self.cfg["precision"])
        self.acc = self.zero()
        self.resident = {k: self.persist(CM.from_array(a, name=k))
                         for k, a in self.state.items()}

    def persist(self, expr):
        with self.ann("bench.persist"):
            h = self.session.persist(expr)
        st = self.engine.last_exec_stats
        self.acc["plan_s"] += self.engine.last_plan.plan_seconds
        self.acc["group_calls"] += st["batched_calls"]
        self.acc["xfer_bytes"] += st["h2d_bytes"] + st["d2h_bytes"]
        return h

    @staticmethod
    def zero() -> dict:
        return {"plan_s": 0.0, "group_calls": 0, "xfer_bytes": 0}

    def gather(self, h):
        with self.ann("bench.gather"):
            return h.to_numpy()

    def one(self, r):
        self.acc = self.zero()
        t0 = time.perf_counter()
        with self.ann("bench.query"):
            arrays = self.inputs(r)
            out = self.prog.query(self.persist, self.gather, self.resident,
                                  arrays, self.cfg, self.traffic)
        dur = time.perf_counter() - t0
        return {"dur_s": dur, **self.acc}, out

    def close(self) -> None:
        self.session.close()
        self.session = self.engine = self.resident = None


class Control(Loop):
    """The control: the plain reference one precision step below the
    configuration's, put in the program's place.  Its outputs go through
    the same comparison as the program's, which has to find them not
    correct."""

    def one(self, r):
        arrays = self.inputs(r)
        out = self.ref.reference(self.state, [arrays], self.cfg,
                                 self.traffic, control=True)[0]
        return {"dur_s": 0.0, "plan_s": 0.0, "group_calls": 0}, out


LOOPS = {"oneshot": OneShot, "session": Session}


def _device_info(jax) -> dict:
    devs = jax.devices()
    peak = 0
    for d in devs:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def run_cell(cell: str, seed: int, seconds: float, trace: bool,
             t_start: float, *, spec: Optional[dict] = None,
             overrides: Optional[dict] = None, control: bool = False,
             units: Optional[int] = None, trace_dir: Optional[Path] = None,
             log: Callable[[str], None] = print) -> dict:
    """Run one cell and return the result object (the last line that
    ``run.py`` prints).

    ``overrides`` replaces configuration sizes (tests run tiny cells on the
    CPU); ``control`` puts the control in the program's place; ``units``
    runs that many units in place of a window of ``seconds``.  None of
    them is used by ``run.py``.
    """
    import jax
    spec = spec if spec is not None else load_json(ROOT / "BENCHMARK.json")
    files = resolve(spec, cell)
    env = make_env(files, seed, overrides)
    loop_kind = files["traffic"]["loop"]
    compiles = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    jax.monitoring.register_event_listener(compiles.hit)
    try:
        return _run(files, env, loop_kind, seconds, trace, t_start,
                    control, units, trace_dir, compiles, log)
    finally:
        jax.monitoring.unregister_event_duration_listener(compiles)
        jax.monitoring.unregister_event_listener(compiles.hit)


def _run(files, env, loop_kind, seconds, trace, t_start, control, units,
         trace_dir, compiles, log) -> dict:
    import jax
    cfg, traffic = env["cfg"], env["traffic"]
    lp = (Control if control else LOOPS[loop_kind])(env)
    rec = lp.warm()
    log(f"warm-up unit {rec['dur_s']!r} s, programs built so far "
        f"{compiles}")

    n_trace = int(traffic["trace_units"])
    if trace:
        trace_dir = Path(trace_dir or ROOT / ".bench_cache" / "trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
        lp.tracing = True
    c0, h0 = compiles.n, compiles.cache_hits
    lp.inputs_s = 0.0
    t_win = time.perf_counter()
    setup_s = t_win - t_start
    t_end = t_win + seconds
    i = 0
    while True:
        lp.unit(i)
        i += 1
        if units is not None:
            if i >= units:
                break
        elif time.perf_counter() >= t_end or (trace and i >= n_trace):
            break
    window_s = time.perf_counter() - t_win
    if trace:
        jax.profiler.stop_trace()
        lp.tracing = False
    log(f"window {window_s!r} s, units {i}, programs built in window "
        f"{compiles.n - c0} ({compiles.cache_hits - h0} from the persistent "
        f"cache), input making {lp.inputs_s!r} s")
    durs = [r["dur_s"] for r in lp.records if not r.get("failed")]
    if durs:
        log("unit seconds min, quartiles, max "
            f"{np.percentile(durs, [0, 25, 50, 75, 100]).tolist()!r}")

    device = _device_info(jax)
    lp.close()
    gc.collect()
    t_ref = time.perf_counter()
    errs = lp.check()
    log(f"reference and comparison {time.perf_counter() - t_ref!r} s")

    limits = files["limits"]["limits"]
    readings = {"rel_err_max": max(errs)}
    failed = sum(1 for r, e in zip(lp.records, errs)
                 if r.get("failed") or not e <= limits["rel_err_max"])
    correct = failed == 0 and all(readings[k] <= v
                                  for k, v in limits.items())

    records = [r for r in lp.records if not r.get("failed")]
    ctx = {"loop": loop_kind, "units": records, "window_s": window_s,
           "setup_s": setup_s, "trace": None, "device_kind": device["kind"],
           "work": env["ref_mod"].least_work(cfg, traffic)}
    breakdown = None
    if trace:
        from .peaks import least_seconds
        from .trace_reduce import load_events, reduce_trace
        ops, anns = load_events(str(trace_dir),
                                files["workload"]["chips"])
        red = reduce_trace(ops, anns, UNIT_ANNOTATION[loop_kind])
        ctx["trace"] = red or None
        if red:
            w = ctx["work"]
            ctx["least_s"] = least_seconds(w["flops"], w["bytes"],
                                           device["kind"])
            device.update(busy_s=red["busy_s"], window_s=red["window_s"])
            breakdown = {"device_ops": red["device_ops"],
                         "idle_gaps": red["idle_gaps"]}
    metrics = {}
    for m in files["per_layer" if trace else "end_to_end"]:
        v = load_module(metric_path(m["name"])).read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    checks = {k: {"value": readings[k], "limit": v}
              for k, v in limits.items()}
    for k, c in checks.items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    out = {"correct": bool(correct), "attempted": i, "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out
