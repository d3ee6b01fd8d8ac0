"""Observability tier: flight recorder, metrics registry, drift report.

Contract (ISSUE: flight recorder): tracing is cheap enough to stay on by
default and NEVER perturbs numerics — a traced run is bit-identical to
an untraced one.  The recorded timeline is complete (one EXEC span per
scheduled task, one XFER span per planned cross-node movement), aligns
worker clocks onto the master timeline, exports as valid Chrome-trace
JSON, and the drift report joins it against the simulator's predicted
timeline to flag straggler nodes and mis-fitted TimeModel terms.
"""
import json
import threading

import numpy as np
import pytest

from repro.core import ClusteredMatrix as CM, CMMEngine, analytic_time_model
from repro.core.drift import drift_report
from repro.core.graph import TaskKind
from repro.core.machine import hetero_spec
from repro.core.session import CMMSession
from repro.runtime.telemetry import (MetricsRegistry, Span, Tracer,
                                     chrome_trace, estimate_clock_offset,
                                     export_chrome_trace, _Histogram)

TM = analytic_time_model()


def _synth(n=64):
    A = CM.rand(n, n, seed=0)
    B = CM.rand(n, n, seed=1)
    return (A @ B) + A


def _plan(expr, tile, spec):
    eng = CMMEngine(spec, TM, plan_cache=False)
    return eng.plan(expr, tile=tile), eng


# -- tracer units ------------------------------------------------------------

class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


def test_span_records_interval():
    clk = FakeClock(10.0)
    tr = Tracer(node=3, enabled=True, clock=clk)
    with tr.span("GEMM", cat="EXEC", tid=7):
        clk.t = 10.5
    (sp,) = tr.drain()
    assert (sp.name, sp.cat, sp.node) == ("GEMM", "EXEC", 3)
    assert sp.t0 == 10.0 and sp.dur == pytest.approx(0.5)
    assert sp.args == {"tid": 7}
    assert tr.drain() == []          # drain took everything


def test_span_nesting_containment():
    clk = FakeClock(0.0)
    tr = Tracer(node=0, clock=clk)
    with tr.span("outer", cat="A"):
        clk.t = 1.0
        with tr.span("inner", cat="B"):
            clk.t = 2.0
        clk.t = 3.0
    spans = {s.name: s for s in tr.drain()}
    out, inn = spans["outer"], spans["inner"]
    # children exit (and record) first; the parent interval contains them
    assert out.t0 <= inn.t0
    assert inn.t0 + inn.dur <= out.t0 + out.dur
    assert out.lane == inn.lane      # same thread -> same lane


def test_span_recorded_on_exception():
    clk = FakeClock(0.0)
    tr = Tracer(clock=clk)
    with pytest.raises(ValueError):
        with tr.span("boom", cat="EXEC"):
            clk.t = 0.25
            raise ValueError("x")
    (sp,) = tr.drain()
    assert sp.dur == pytest.approx(0.25)


def test_disabled_tracer_records_nothing():
    tr = Tracer(enabled=False)
    with tr.span("x", cat="EXEC", tid=1):
        pass
    tr.add(Span("y", "EXEC", 0, 0, 0.0, 1.0))
    assert tr.drain() == []
    # disabled span() returns one shared context: zero per-call allocation
    assert tr.span("a") is tr.span("b")


def test_lanes_stable_per_thread():
    tr = Tracer()
    lanes = {}
    barrier = threading.Barrier(4)     # keep all threads alive at once —
    # exited thread idents (and so lanes) are legitimately reusable

    def work(k):
        barrier.wait()
        with tr.span(f"t{k}", cat="EXEC"):
            pass
        lanes[k] = tr.lane()
        barrier.wait()

    ths = [threading.Thread(target=work, args=(k,)) for k in range(4)]
    for t in ths:
        t.start()
    for t in ths:
        t.join()
    spans = {s.name: s for s in tr.drain()}
    assert len(spans) == 4
    # concurrent threads get small, dense, distinct lanes, and a span
    # records on its own thread's lane
    assert sorted(lanes.values()) == [0, 1, 2, 3]
    for k in range(4):
        assert spans[f"t{k}"].lane == lanes[k]


# -- clock-offset calibration -------------------------------------------------

def test_estimate_clock_offset_symmetric_delay():
    # worker clock runs 100s ahead; 10ms symmetric one-way delay
    ahead = 100.0
    t_send = 50.0
    t_worker = (t_send + 0.01) + ahead   # worker echoes mid-flight
    t_recv = t_send + 0.02
    off = estimate_clock_offset(t_send, t_worker, t_recv)
    assert off == pytest.approx(ahead, abs=1e-9)


def test_ingest_shifts_onto_master_timeline():
    master = Tracer(node=-1)
    # a worker whose clock is 7s ahead recorded t0=107; the event
    # happened at master time 100
    sp = Span("EXEC", "EXEC", 2, 0, 107.0, 0.5, {"tid": 1})
    master.ingest([sp], offset=7.0)
    (got,) = master.drain()
    assert got.t0 == pytest.approx(100.0)
    assert got.dur == pytest.approx(0.5)


def test_calibration_roundtrip_aligns_two_clocks():
    # two fake processes with skewed clocks; the cal handshake recovers
    # the skew exactly under symmetric delays
    skew = 3.0
    t_send = 1.0                       # master stamps
    t_worker = (t_send + 0.005) + skew  # worker echoes its clock
    t_recv = 1.01                      # master receives
    off = estimate_clock_offset(t_send, t_worker, t_recv)
    worker = Tracer(node=1, clock=FakeClock(0.0))
    worker.add(Span("EXEC", "EXEC", 1, 0, 5.0 + skew, 0.1))
    master = Tracer(node=-1)
    master.ingest(worker.drain(), off)
    (sp,) = master.drain()
    assert sp.t0 == pytest.approx(5.0, abs=1e-9)


# -- histogram ----------------------------------------------------------------

def test_histogram_summary_basics():
    h = _Histogram()
    for v in (0.001, 0.002, 0.004, 0.1):
        h.observe(v)
    s = h.summary()
    assert s["count"] == 4
    assert s["total"] == pytest.approx(0.107)
    assert s["min"] == 0.001 and s["max"] == 0.1
    # quantile returns a bucket upper edge within 2x of the true value
    assert 0.002 <= s["p50"] <= 0.008
    assert s["p99"] >= 0.1


# hypothesis property sweep (skipped where hypothesis is unavailable)
try:
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=60, deadline=None)
    @given(xs=st.lists(st.floats(min_value=0.0, max_value=1e4,
                                 allow_nan=False), max_size=40),
           ys=st.lists(st.floats(min_value=0.0, max_value=1e4,
                                 allow_nan=False), max_size=40))
    def test_histogram_merge_property(xs, ys):
        """merge(A, B) is indistinguishable from observing A+B directly."""
        ha, hb, hall = _Histogram(), _Histogram(), _Histogram()
        for v in xs:
            ha.observe(v)
            hall.observe(v)
        for v in ys:
            hb.observe(v)
            hall.observe(v)
        ha.merge(hb)
        assert ha.buckets == hall.buckets
        assert ha.count == hall.count
        assert ha.total == pytest.approx(hall.total)
        sa, sall = ha.summary(), hall.summary()
        for k in ("min", "max", "p50", "p99"):
            assert sa[k] == pytest.approx(sall[k])
except ImportError:                    # pragma: no cover
    pass


# -- metrics registry ---------------------------------------------------------

def test_registry_inc_is_atomic_across_threads():
    reg = MetricsRegistry()
    N, T = 2000, 8

    def bump():
        for _ in range(N):
            reg.inc("hits")

    ths = [threading.Thread(target=bump) for _ in range(T)]
    for t in ths:
        t.start()
    for t in ths:
        t.join()
    assert reg.get("hits") == N * T    # bare dict += would lose updates


def test_registry_frozen_view_is_readonly_dict():
    reg = MetricsRegistry()
    reg.inc("xfers", 3)
    reg.gauge("nodes", [0, 1])
    reg.observe("task_seconds", 0.5)
    view = reg.frozen_view({"extra": 7})
    assert view["xfers"] == 3 and view["extra"] == 7
    assert view.get("missing", "d") == "d"
    assert dict(view)["nodes"] == [0, 1]
    assert view["hist:task_seconds"]["count"] == 1
    with pytest.raises(TypeError):
        view["xfers"] = 9
    # the view is a snapshot: later increments don't leak into it
    reg.inc("xfers")
    assert view["xfers"] == 3


def test_registry_merge():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.inc("n", 2)
    b.inc("n", 5)
    b.observe("lat", 0.1)
    a.merge(b)
    assert a.get("n") == 7
    assert a.histogram("lat")["count"] == 1


# -- Chrome trace export ------------------------------------------------------

def _schema_check(doc):
    assert set(doc) >= {"traceEvents"}
    for ev in doc["traceEvents"]:
        assert ev["ph"] in ("X", "M")
        assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
        if ev["ph"] == "X":
            assert ev["ts"] >= 0.0 and ev["dur"] >= 0.0
            assert isinstance(ev["name"], str) and isinstance(ev["cat"], str)
            json.dumps(ev["args"])     # args must be JSON-serializable


def test_chrome_trace_schema_and_normalization():
    spans = [Span("GEMM", "EXEC", 0, 1, 100.0, 0.5, {"tid": 3}),
             Span("XFER", "XFER", 1, 0, 100.2, 0.1, {"nbytes": 64}),
             Span("GATHER", "GATHER", -1, 0, 101.0, 0.2)]
    doc = chrome_trace(spans)
    _schema_check(doc)
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert min(e["ts"] for e in xs) == 0.0        # normalized to run start
    names = {(e["pid"], e["args"]["name"]) for e in doc["traceEvents"]
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert (-1, "master") in names and (0, "node 0") in names


def test_export_chrome_trace_roundtrip(tmp_path):
    spans = [Span("E", "EXEC", 0, 0, 1.0, 0.5)]
    p = tmp_path / "trace.json"
    export_chrome_trace(spans, str(p))
    _schema_check(json.load(open(p)))


# -- executor integration -----------------------------------------------------

def test_cluster_trace_covers_schedule():
    """One EXEC span per scheduled task; XFER spans match the plan's
    cross-node movement table exactly (the ``xfer_index`` oracle)."""
    spec = hetero_spec((2, 2, 1))
    plan, eng = _plan(_synth(64), 32, spec)
    g = plan.program.graph
    eng.run(_synth(64), executor="cluster", plan=plan, validate=True)
    ex = [s for s in eng.last_spans if s.cat == "EXEC"]
    tids = [s.args["tid"] for s in ex]
    assert sorted(tids) == sorted(plan.schedule.placements)  # exactly once
    # every EXEC span ran on its scheduled node
    for s in ex:
        assert s.node == plan.schedule.placements[s.args["tid"]].node
    idx = plan.schedule.xfer_index(g)
    got = {(s.args["version"], s.node): s.args["nbytes"]
           for s in eng.last_spans if s.cat == "XFER"}
    assert set(got) == set(idx)
    for key, nbytes in got.items():
        assert nbytes == idx[key][1]
    assert any(s.cat == "GATHER" for s in eng.last_spans)


def test_tracing_off_is_bit_identical_and_silent():
    spec = hetero_spec((2, 2, 1))
    expr = _synth(64)
    plan, eng = _plan(expr, 32, spec)
    on = eng.run(expr, executor="cluster", plan=plan)
    assert eng.last_spans
    plan2, eng2 = _plan(expr, 32, spec)
    off = eng2.run(expr, executor="cluster", plan=plan2, trace=False)
    assert eng2.last_spans == []
    np.testing.assert_array_equal(on, off)
    # stats survive the registry migration on both legs (dict view)
    for st in (eng.last_exec_stats, eng2.last_exec_stats):
        assert st["tasks_run"] == len(plan.program.graph)
        assert "xfers" in st and "wire_bytes" in st


def test_session_trace_accumulates_and_exports(tmp_path):
    spec = hetero_spec((2, 2, 1))
    A = CM.rand(48, 48, seed=0)
    with CMMSession(CMMEngine(spec, TM), executor="cluster", tile=24) as s:
        P = s.persist(A @ A)
        one = len(s.spans)
        assert one > 0
        s.compute(P + A)
        assert len(s.spans) > one      # spans accumulate across runs
        p = tmp_path / "session_trace.json"
        n = s.dump_trace(str(p), include_predicted=True)
        doc = json.load(open(p))
        _schema_check(doc)
        assert n == len(doc["traceEvents"])
        cats = {e["cat"] for e in doc["traceEvents"] if e["ph"] == "X"}
        assert "EXEC" in cats and "PRED_EXEC" in cats
        rep = s.drift_report()
        assert {nd.node for nd in rep.nodes} >= set(range(spec.n_nodes))


def test_local_and_batched_spans():
    expr = _synth(48)
    eng = CMMEngine(tile=24)
    out1 = eng.run(expr, executor="local")
    ex = [s for s in eng.last_spans if s.cat == "EXEC"]
    assert sorted(s.args["tid"] for s in ex) == sorted(
        eng.last_plan.program.graph.tasks)
    out2 = eng.run(expr, executor="batched")
    ex = [s for s in eng.last_spans if s.cat == "EXEC"]
    assert ex and all(s.args.get("batched") for s in ex)
    assert [s.cat for s in eng.last_spans if s.cat != "EXEC"] == \
        ["ASSEMBLE"]
    np.testing.assert_array_equal(out1, out2)


# -- device path phases (batched-pallas, interpret mode on the CPU) ----------

PHASES = ["GATHER", "H2D", "KERNEL", "D2H", "STORE"]
KM_N, KM_TILE = 256, 128


def _kmeans_arrays(seed=0):
    rng = np.random.default_rng(seed)
    return {k: rng.random((KM_N, KM_N), dtype=np.float32) * 2 - 1
            for k in ("X", "Ct")}


def _kmeans(arrays):
    """The paper's K-Means core: relu(X @ Ct - 0.5) fused into the first
    GEMM's last ADDMUL group, a transposed operand in the second."""
    X = CM.from_array(arrays["X"], name="X")
    Ct = CM.from_array(arrays["Ct"], name="Ct")
    return ((X @ Ct) - 0.5).relu().T @ X


def _device_run(eng, trace=True, seed=0):
    plan = eng.plan(_kmeans(_kmeans_arrays(seed)), tile=KM_TILE)
    out = eng.execute_plan(plan, executor="batched-pallas", trace=trace)
    return plan, out, dict(eng.last_exec_stats), list(eng.last_spans)


@pytest.fixture(scope="module")
def device_runs():
    """One traced and one untraced K-Means program on one engine (the
    second plan is a plan-cache hit)."""
    eng = CMMEngine(timemodel=TM)
    return _device_run(eng), _device_run(eng, trace=False), eng


def _inside(child, parent):
    return parent.t0 <= child.t0 and \
        child.t0 + child.dur <= parent.t0 + parent.dur


def test_device_groups_record_their_phases(device_runs):
    (_, _, stats, spans), _, _ = device_runs
    groups = [s for s in spans if s.cat == "EXEC" and s.name == "ADDMUL"]
    nt = KM_N // KM_TILE
    assert len(groups) == 2 * nt          # one group per k-step per GEMM
    for g in groups:
        kids = [s for s in spans if s.cat in PHASES and _inside(s, g)]
        assert [s.cat for s in sorted(kids, key=lambda s: s.t0)] == PHASES
    assert all(any(_inside(s, g) for g in groups)
               for s in spans if s.cat in PHASES)
    others = [s for s in spans if s.cat == "EXEC" and s.name != "ADDMUL"]
    assert not any(_inside(s, g) for s in spans if s.cat in PHASES
                   for g in others)
    (asm,) = [s for s in spans if s.cat == "ASSEMBLE"]
    assert asm.t0 >= max(s.t0 + s.dur for s in spans if s.cat == "EXEC")
    assert stats["batched_calls"] == len(groups) + 3  # CALLOC, FILL, TAKECOPY


def test_device_byte_counters_match_spans_and_the_count(device_runs):
    (_, _, stats, spans), _, _ = device_runs
    nt, tile_bytes = KM_N // KM_TILE, KM_TILE * KM_TILE * 4
    # per GEMM, nt groups of nt^2 tasks; each sends C, A and B tiles and
    # receives C (the relu threshold is a constant of the epilogue)
    assert stats["h2d_bytes"] == 2 * nt * 3 * nt ** 2 * tile_bytes
    assert stats["d2h_bytes"] == 2 * nt * nt ** 2 * tile_bytes
    assert stats["h2d_bytes"] == sum(s.args["nbytes"] for s in spans
                                     if s.cat == "H2D")
    assert stats["d2h_bytes"] == sum(s.args["nbytes"] for s in spans
                                     if s.cat == "D2H")
    # stacked A and B of the first GEMM; A stacked as its tiles' .T (one
    # copy) and stacked B of the second; every store but the epilogue's
    assert stats["host_copy_bytes"] == (6 * nt ** 3 - nt ** 2) * tile_bytes


def test_device_path_tracing_off_is_bit_identical_and_silent(device_runs):
    (_, on, stats_on, _), (_, off, stats_off, spans_off), _ = device_runs
    assert spans_off == []
    np.testing.assert_array_equal(on, off)
    for k in ("batched_calls", "h2d_bytes", "d2h_bytes", "host_copy_bytes"):
        assert stats_on[k] == stats_off[k]


def test_staging_buffers_are_reused_within_and_across_programs(device_runs):
    """Each ADDMUL group stages A and B (a C slab run is sent as it is):
    the engine's first program allocates one group's two buffers and
    reuses them in every later group; a later program allocates none."""
    (_, _, first, spans), (_, _, second, _), eng = device_runs
    nt, tile_bytes = KM_N // KM_TILE, KM_TILE * KM_TILE * 4
    assert (first["staging_allocs"], first["staging_reuses"]) == \
        (2, 4 * nt - 2)
    assert (second["staging_allocs"], second["staging_reuses"]) == \
        (0, 4 * nt)
    gathers = [s for s in spans if s.cat == "GATHER"]
    assert len(gathers) == 2 * nt
    for k in ("staging_allocs", "staging_reuses"):
        assert sum(s.args[k] for s in gathers) == first[k]
    # the pool holds what one group took at once, of the one shape in use
    assert eng.staging.held_bytes() == {
        ((nt ** 2, KM_TILE, KM_TILE), np.dtype(np.float32)):
            2 * nt ** 2 * tile_bytes}


def test_a_dirty_staging_pool_changes_no_output(device_runs):
    """A program on an engine whose pool another program, on other data,
    left dirty is bitwise the same program on a fresh engine."""
    (_, fresh, _, _), _, _ = device_runs
    eng = CMMEngine(timemodel=TM)
    _, other, _, _ = _device_run(eng, seed=1)
    _, out, stats, _ = _device_run(eng)
    assert stats["staging_allocs"] == 0
    assert not np.array_equal(other, out)
    np.testing.assert_array_equal(out, fresh)


def test_numpy_wave_backend_uses_no_staging():
    """The NumPy ``batched`` backend keeps its own stacks: it takes nothing
    from the engine's pool and stays bit-identical to ``local``."""
    eng = CMMEngine(timemodel=TM)
    expr = _kmeans(_kmeans_arrays())
    out = eng.run(expr, tile=KM_TILE, executor="batched")
    stats = eng.last_exec_stats
    assert (stats["staging_allocs"], stats["staging_reuses"]) == (0, 0)
    assert eng.staging.held_bytes() == {}
    np.testing.assert_array_equal(
        out, eng.run(expr, tile=KM_TILE, executor="local"))


def test_drift_and_roofline_read_the_spans_they_read_before(device_runs):
    (plan, _, _, spans), _, eng = device_runs
    assert {s.cat for s in spans} > {"EXEC"}
    exec_only = [s for s in spans if s.cat == "EXEC"]
    from repro.core.roofline import roofline_report
    for report in (drift_report, roofline_report):
        assert report(spans, plan, tm=TM).as_dict() == \
            report(exec_only, plan, tm=TM).as_dict()


def test_planner_spans_nest_under_plan(device_runs, tmp_path):
    (miss, _, _, _), (hit, _, _, _), eng = device_runs
    assert not miss.cache_hit and hit.cache_hit
    for plan, kids in ((miss, ["FUSE", "CACHE", "TILE", "HEFT", "SIM",
                               "WAVES"]),
                       (hit, ["FUSE", "CACHE"])):
        (top,) = [s for s in plan.spans if s.cat == "PLAN"]
        inner = sorted((s for s in plan.spans if s is not top),
                       key=lambda s: s.t0)
        assert [s.cat for s in inner] == kids
        assert all(_inside(s, top) and s.node == -1 for s in inner)
        assert plan.plan_seconds <= top.dur
    n = eng.dump_trace(str(tmp_path / "trace.json"))
    doc = json.load(open(tmp_path / "trace.json"))
    assert n == len(doc["traceEvents"])
    assert {e["cat"] for e in doc["traceEvents"] if e["ph"] == "X"} == \
        {"PLAN", "FUSE", "CACHE"}            # the untraced run: plan only


def test_spans_reach_the_profiler_as_cmm_annotations(tmp_path):
    """Under ``jax.profiler`` each span is a ``cmm.<name>`` host
    annotation on the profiler's clock, nested where the span is."""
    import glob
    import jax
    from jax.profiler import ProfileData
    eng = CMMEngine(timemodel=TM)
    _device_run(eng)                                 # compile outside
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.program"):
            _device_run(eng)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    anns = [(e.name, e.start_ns, e.end_ns)
            for p in ProfileData.from_file(path).planes
            if p.name.startswith("/host:") for line in p.lines
            for e in line.events
            if e.name.startswith(("cmm.", "bench.program"))]
    (prog,) = [a for a in anns if a[0] == "bench.program"]
    cmm = [a for a in anns if a[0].startswith("cmm.")]
    names = {a[0] for a in cmm}
    assert names >= {"cmm.PLAN", "cmm.FUSE", "cmm.ADDMUL", "cmm.ASSEMBLE"} | \
        {"cmm." + p for p in PHASES}
    assert all(prog[1] <= s and e <= prog[2] for _, s, e in cmm)
    for _, s, e in (a for a in cmm if a[0] == "cmm.H2D"):
        assert any(gs <= s and e <= ge for n, gs, ge in cmm
                   if n == "cmm.ADDMUL")


def _markov(n=KM_N, seed=0):
    """The paper's Markov as written, ``(P @ P @ P) @ u``: one chain,
    which the planner orders as three mat-vecs."""
    P = CM.rand(n, n, seed=seed, dtype=np.float32)
    u = CM.rand(n, 1, seed=seed + 1, dtype=np.float32)
    return (P @ P @ P) @ u


def test_chain_span_nests_in_plan_on_a_miss_and_a_hit():
    eng = CMMEngine(timemodel=TM)
    miss = eng.plan(_markov(seed=0), tile=KM_TILE)
    hit = eng.plan(_markov(seed=5), tile=KM_TILE)
    assert not miss.cache_hit and hit.cache_hit
    for plan in (miss, hit):
        (top,) = [s for s in plan.spans if s.cat == "PLAN"]
        (fuse,) = [s for s in plan.spans if s.cat == "FUSE"]
        (chain,) = [s for s in plan.spans if s.cat == "CHAIN"]
        assert _inside(chain, fuse) and _inside(fuse, top)
        assert chain.node == -1
    # K-Means has no chain of three operands: no CHAIN span
    km = eng.plan(_kmeans(_kmeans_arrays()), tile=KM_TILE)
    assert "CHAIN" not in {s.cat for s in km.spans}


@pytest.mark.parametrize("program", ["markov", "kmeans"])
def test_fusion_report_counts_the_chain_pass(program):
    n = KM_N
    expr = _markov() if program == "markov" else \
        _kmeans(_kmeans_arrays())
    rep = CMMEngine(timemodel=TM).plan(expr, tile=KM_TILE).fusion.as_dict()
    want = {"markov": (1, 2 * 2 * n ** 3 + 2 * n ** 2, 3 * 2 * n ** 2),
            "kmeans": (0, 2 * 2 * n ** 3, 2 * 2 * n ** 3)}[program]
    assert (rep["chains_reordered"], rep["matmul_flops_written"],
            rep["matmul_flops_planned"]) == want


def test_chain_span_reaches_the_profiler_under_plan(tmp_path):
    import glob
    import jax
    from jax.profiler import ProfileData
    eng = CMMEngine(timemodel=TM)
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.plan(_markov(), tile=KM_TILE)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    anns = [(e.name, e.start_ns, e.end_ns)
            for p in ProfileData.from_file(path).planes
            if p.name.startswith("/host:") for line in p.lines
            for e in line.events if e.name.startswith("cmm.")]
    (plan,) = [a for a in anns if a[0] == "cmm.PLAN"]
    (chain,) = [a for a in anns if a[0] == "cmm.CHAIN"]
    assert plan[1] <= chain[1] and chain[2] <= plan[2]


def test_disabled_and_jax_free_tracers_annotate_nothing(monkeypatch):
    import sys
    from repro.runtime import telemetry
    made = []
    monkeypatch.setattr(telemetry, "_profiler_annotation",
                        lambda name: made.append(name))
    with Tracer(enabled=False).span("H2D"):
        pass
    assert made == []
    with Tracer().span("H2D"):
        pass
    assert made == ["H2D"]
    monkeypatch.undo()
    monkeypatch.delitem(sys.modules, "jax")
    assert telemetry._profiler_annotation("H2D") is None


# -- drift report -------------------------------------------------------------

def _spans_from_sim(plan, slow_node=None, factor=5.0):
    """Synthesize a measured timeline from the simulated one, inflating
    ``slow_node``'s task durations by ``factor``."""
    out = []
    for iv in plan.sim.intervals:
        dur = iv.end - iv.start
        if iv.node == slow_node:
            dur *= factor
        out.append(Span(iv.kind, "EXEC", iv.node, iv.slot, iv.start, dur,
                        {"tid": iv.tid, "kind": iv.kind}))
    return out


def test_drift_flags_synthetically_slow_node():
    spec = hetero_spec((2, 2, 2))
    plan, _ = _plan(_synth(96), 32, spec)
    rep = drift_report(_spans_from_sim(plan, slow_node=1), plan, tm=TM)
    nd = rep.node(1)
    assert nd.flagged and nd.samples >= 3
    assert rep.straggler_priors == [1]
    assert nd.rel == pytest.approx(5.0, rel=0.01)
    for n in (0, 2):
        assert not rep.node(n).flagged
    # a perfectly-matching run flags nothing
    clean = drift_report(_spans_from_sim(plan), plan, tm=TM)
    assert clean.straggler_priors == []
    assert not any(nd.flagged for nd in clean.nodes)
    assert clean.fleet_ratio == pytest.approx(1.0)
    # kernel_time matched the simulator exactly -> unflagged
    assert not clean.term("kernel_time").flagged


def test_drift_reports_every_requested_node():
    spec = hetero_spec((2, 2, 1))
    plan, _ = _plan(_synth(64), 32, spec)
    rep = drift_report([], plan, tm=TM)      # no spans at all
    assert [nd.node for nd in rep.nodes] == list(range(spec.n_nodes))
    assert all(nd.samples == 0 and not nd.flagged for nd in rep.nodes)


def test_drift_term_recalibration_suggestion():
    spec = hetero_spec((2, 2, 1))
    plan, _ = _plan(_synth(64), 32, spec)
    # XFERs took 4x the predicted ipc time -> bandwidth is ~4x optimistic
    from repro.runtime.wire import predicted_xfer_seconds
    spans = []
    nbytes = 1 << 25                   # bandwidth-dominated payload
    for _ in range(4):
        p = predicted_xfer_seconds(nbytes, TM)
        spans.append(Span("XFER", "XFER", 1, 0, 0.0, 4.0 * p,
                          {"nbytes": nbytes, "codec": "raw"}))
    rep = drift_report(spans, plan, tm=TM, min_samples=3)
    td = rep.term("ipc_bandwidth")
    assert td.flagged and td.ratio == pytest.approx(4.0)
    assert td.suggested == pytest.approx(TM.ipc_bandwidth / 4.0)
    # applying the suggestion collapses the drift into the band (the
    # fixed ipc_latency term keeps the residual from being exactly 1.0)
    tm2 = TM.recalibrated("ipc_bandwidth", td.ratio)
    rep2 = drift_report(spans, plan, tm=tm2, min_samples=3)
    assert rep2.term("ipc_bandwidth").ratio == pytest.approx(1.0, rel=0.05)
    assert not rep2.term("ipc_bandwidth").flagged


def test_drift_report_as_dict_json():
    spec = hetero_spec((2, 2, 1))
    plan, _ = _plan(_synth(64), 32, spec)
    rep = drift_report(_spans_from_sim(plan, slow_node=0), plan, tm=TM)
    d = json.loads(json.dumps(rep.as_dict()))
    assert d["band"] == 1.5
    assert len(d["nodes"]) == spec.n_nodes
    assert rep.summary()                     # renders without raising
