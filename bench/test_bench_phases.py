"""The program's spans in the benchmark's terms: the trace reduction with
the program's ``cmm.`` annotations beside the benchmark's own (made-up
events), and ``phases.py`` on the tiny K-Means and Markov cells on the
CPU."""
import pytest

from bench.phases import run_phases, unit_phases
from bench.trace_reduce import load_events, reduce_trace

MS = 1_000_000  # ns
TINY = {"n": 256, "tile": 128}
SEED = 2 ** 31 + 12345
UNCHANGED = ("window_s", "busy_s", "units", "unit_s", "unit_busy_s",
             "device_ops")


def test_program_annotations_split_only_the_idle_time_of_bench_execute():
    bench = [
        ("bench.program", 0, 100 * MS),
        ("bench.plan", 0, 10 * MS),
        ("bench.execute", 10 * MS, 90 * MS),
        ("bench.inputs", 100 * MS, 120 * MS),
        ("bench.program", 130 * MS, 150 * MS),
    ]
    cmm = [
        ("cmm.PLAN", 1 * MS, 9 * MS),
        ("cmm.ADDMUL", 15 * MS, 60 * MS),
        ("cmm.GATHER", 15 * MS, 20 * MS),
        ("cmm.H2D", 20 * MS, 25 * MS),
        ("cmm.KERNEL", 25 * MS, 50 * MS),
        ("cmm.D2H", 50 * MS, 55 * MS),
        ("cmm.STORE", 55 * MS, 60 * MS),
        ("cmm.ASSEMBLE", 80 * MS, 88 * MS),
    ]
    ops = {"/device:TPU:0": [("cmm_addmul.1", 26 * MS, 48 * MS),
                             ("copy", 140 * MS, 145 * MS)]}
    before = reduce_trace(ops, bench, unit="bench.program", top=20)
    after = reduce_trace(ops, bench + cmm, unit="bench.program", top=20)
    for k in UNCHANGED:
        assert after[k] == before[k], k
    gaps_before, gaps_after = dict(before["idle_gaps"]), \
        dict(after["idle_gaps"])
    # bench.execute held 80 ms, 22 of them busy: of its 58 ms of idle time
    # 31 go to the program's phases (cmm.ADDMUL's children cover it
    # whole), 27 stay with bench.execute itself
    assert gaps_before["bench.execute"] == pytest.approx(0.058)
    assert gaps_after == pytest.approx({
        "bench.execute": 0.027, "cmm.ASSEMBLE": 0.008, "cmm.GATHER": 0.005,
        "cmm.H2D": 0.005, "cmm.KERNEL": 0.003, "cmm.D2H": 0.005,
        "cmm.STORE": 0.005, "cmm.PLAN": 0.008, "bench.plan": 0.002,
        "bench.program": 0.025, "bench.inputs": 0.020, "(none)": 0.010})
    assert sum(gaps_after.values()) == pytest.approx(
        sum(gaps_before.values()))


@pytest.fixture(scope="module")
def tiny_phases(tmp_path_factory):
    trace_dir = tmp_path_factory.mktemp("phases")
    return run_phases("kmeans.oneshot", SEED, 1, 1, 1, overrides=TINY,
                      log=lambda s: None, trace_dir=trace_dir), trace_dir


def test_phases_of_the_tiny_kmeans_cell(tiny_phases):
    res, _ = tiny_phases
    traced, untraced, profiled = res["records"]
    assert traced["traced"] and not untraced["traced"] and profiled["traced"]
    assert "xfer_mib" not in untraced
    n, tile = TINY["n"], TINY["tile"]
    nt = n // tile
    for r in (traced, profiled):
        # 2 GEMMs of nt groups (k-steps) of nt^2 tile tasks; each group
        # sends C, A and B and receives C: 32 n^2 (n / tile) bytes
        assert r["xfer_mib"] * 2 ** 20 == 32 * n * n * nt
        # each operand tile stacked once, GEMM 2's transposed A too
        assert r["host_copy_mib"] * 2 ** 20 == \
            (6 * nt ** 3 - nt ** 2) * tile * tile * 4
        assert r["planner_ms"] > 0 and r["xfer_ms"] > 0
        assert r["kernel_ms"] > 0 and r["host_ms"] > 0
        assert r["planner_ms"] + r["xfer_ms"] + r["kernel_ms"] + \
            r["host_ms"] <= 1e3 * r["dur_s"]
        assert r["other_ms"] > 0
        waves = {sp[2] for sp in r["spans"] if sp[0] == "EXEC"}
        assert waves == set(range(len(waves)))
    s = res["summary"]
    assert s["unit_s.traced"] == traced["dur_s"]
    assert s["unit_s.untraced"] == untraced["dur_s"]
    assert s["slowest_unit"] is traced


def test_profiled_unit_nests_program_annotations_in_bench_ones(tiny_phases):
    res, trace_dir = tiny_phases
    # no TPU plane in a CPU trace: nothing to reduce, annotations listed
    assert set(res["reduction"]) == {"annotations"}
    assert {"cmm.PLAN", "cmm.ADDMUL", "cmm.GATHER", "cmm.H2D",
            "cmm.KERNEL", "cmm.D2H", "cmm.STORE",
            "cmm.ASSEMBLE"} <= set(res["reduction"]["annotations"])
    _, anns = load_events(str(trace_dir), 1)
    (prog,) = [a for a in anns if a[0] == "bench.program"]
    (plan,) = [a for a in anns if a[0] == "bench.plan"]
    (ex,) = [a for a in anns if a[0] == "bench.execute"]
    cmm = [a for a in anns if a[0].startswith("cmm.")]
    assert cmm and all(prog[1] <= s and e <= prog[2] for _, s, e in cmm)
    for name, s, e in cmm:
        outer = plan if name in ("cmm.PLAN", "cmm.FUSE", "cmm.CACHE") else ex
        assert outer[1] <= s and e <= outer[2], name


def test_phases_of_the_tiny_markov_session_sum_its_persists(tmp_path):
    res = run_phases("markov.session", SEED, 2, None, 1, overrides=TINY,
                     log=lambda s: None, trace_dir=tmp_path)
    n, tile = TINY["n"], TINY["tile"]
    nt = n // tile
    recs = res["records"]
    assert [(r["traced"], r["profiled"]) for r in recs] == \
        [(True, False), (True, False), (True, True)]
    for r in recs:
        # 3 steps of nt^2 mat-vec tasks: P, u and C tiles up, C down
        assert r["xfer_mib"] * 2 ** 20 == \
            3 * nt ** 2 * (tile ** 2 + 3 * tile) * 4
        # the u persist and each of the 3 steps plan once
        assert [sp[0] for sp in r["spans"]].count("PLAN") == 4
        assert r["kernel_ms"] > 0 and r["xfer_ms"] > 0
        assert r["planner_ms"] + r["xfer_ms"] + r["kernel_ms"] + \
            r["host_ms"] <= 1e3 * r["dur_s"]
    assert res["summary"]["unit_s.untraced"] is None
    with pytest.raises(ValueError, match="traced units only"):
        run_phases("markov.session", SEED, 1, 1, 0, overrides=TINY)


def test_unit_phases_counts_host_groups_and_skips_device_ones():
    from repro.runtime.telemetry import Span
    plan = [Span("PLAN", "PLAN", -1, 0, 0.0, 0.001)]
    ex = [Span("FILL", "EXEC", 0, 0, 0.0, 0.002),
          Span("ADDMUL", "EXEC", 0, 0, 0.010, 0.010),
          Span("GATHER", "GATHER", 0, 0, 0.010, 0.002),
          Span("H2D", "H2D", 0, 0, 0.012, 0.002),
          Span("KERNEL", "KERNEL", 0, 0, 0.014, 0.002),
          Span("D2H", "D2H", 0, 0, 0.016, 0.002),
          Span("STORE", "STORE", 0, 0, 0.018, 0.001),
          Span("ASSEMBLE", "ASSEMBLE", 0, 0, 0.030, 0.003)]
    r = unit_phases(plan, ex, {"h2d_bytes": 3 * 2 ** 20,
                               "d2h_bytes": 2 ** 20, "host_copy_bytes": 0},
                    dur_s=0.040)
    assert r["planner_ms"] == pytest.approx(1.0)
    assert r["xfer_ms"] == pytest.approx(4.0)
    assert r["kernel_ms"] == pytest.approx(2.0)
    assert r["host_ms"] == pytest.approx(2.0 + 2.0 + 1.0 + 3.0)
    assert r["xfer_mib"] == 4.0 and r["host_copy_mib"] == 0.0
    assert r["other_ms"] == pytest.approx(40.0 - 1.0 - 2.0 - 10.0 - 3.0)
    assert [sp[0] for sp in r["spans"]] == [
        "PLAN", "EXEC", "EXEC", "GATHER", "H2D", "KERNEL", "D2H", "STORE",
        "ASSEMBLE"]
    assert r["spans"][3][3:] == pytest.approx([10.0, 2.0])
