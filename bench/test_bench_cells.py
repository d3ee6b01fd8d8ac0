"""The benchmark's cells, end to end at a tiny size on the CPU (the Pallas
kernels in interpret mode), and the files that ``BENCHMARK.json`` names.

Each cell must agree with its plain reference; its control (the reference
one precision step lower, in the program's place) and each fault planted
in the timed path must come out not correct under the cell's own limits.
"""
import importlib
import json
import os
import re
import subprocess
import sys
import time

import numpy as np
import pytest

from bench.harness import (ROOT, UNITS, Session, load_json, load_module,
                           make_env, metric_path, resolve, rng, run_cell)
from bench.peaks import least_seconds, peaks

SPEC = load_json(ROOT / "BENCHMARK.json")
CELLS = [w["name"] for w in SPEC["workloads"]]
TINY = {"n": 256, "tile": 128}
SEED = 2 ** 31 + 12345  # over 32 signed bits: a seed may be that large
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")


def _run(cell, **kw):
    return run_cell(cell, SEED, 0.0, False, time.perf_counter(),
                    overrides=TINY, spec=SPEC, log=lambda s: None, **kw)


def _e2e_of(cell):
    return {m["name"] for m in resolve(SPEC, cell)["end_to_end"]}


# -- BENCHMARK.json and the files it names ---------------------------------

def test_benchmark_json_keeps_to_its_schema():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"] and SPEC["command"][1] == "bench/run.py"
    assert 1 <= SPEC["run_seconds"] <= 51
    named = SPEC["configs"] + SPEC["workloads"] + SPEC["end_to_end"] \
        + SPEC["per_layer"]
    for entry in named:
        assert NAME.fullmatch(entry["name"]), entry["name"]
        for k in ("why", "layer", "source"):
            assert len(entry.get(k, "x")) <= 200
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in SPEC[group]]
        assert len(names) == len(set(names))
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert m["better"] in ("lower", "higher")
        assert re.fullmatch(r"[A-Za-z0-9_/%.-]{1,16}", m["unit"])
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert {w["chips"] for w in SPEC["workloads"]} <= {1, 4}


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    files = resolve(SPEC, cell)
    conf = {c["name"]: c for c in SPEC["configs"]}[files["workload"]["config"]]
    assert conf["file"].startswith("bench/configs/")
    assert sorted(conf["reduced"]) == sorted(files["cfg"]["reduced"])
    for mod in (files["program"], files["reference"]):
        assert (ROOT / (mod.replace(".", "/") + ".py")).is_file()
    assert files["traffic"]["loop"] in ("oneshot", "session")
    assert "rel_err_max" in files["limits"]["limits"]
    e2e = _e2e_of(cell)
    assert "setup_s" in e2e and len(e2e) >= 2 and files["per_layer"]
    for m in files["end_to_end"] + files["per_layer"]:
        assert metric_path(m["name"]).is_file(), m["name"]


@pytest.mark.parametrize("conf", SPEC["configs"], ids=lambda c: c["name"])
def test_configuration_file_names_its_source_and_its_cuts(conf):
    cfg = load_json(ROOT / conf["file"])
    assert cfg["source"].startswith(conf["source"].split(" ")[0])
    assert sorted(cfg["reduced"]) == sorted(conf["reduced"])
    assert set(cfg["reduced"]) <= set(cfg["published"])
    assert isinstance(cfg["assumed"], list) and cfg["assumed"]
    assert cfg["n"] % cfg["tile"] == 0


@pytest.mark.parametrize("cell", CELLS)
def test_a_seed_gives_the_same_data_in_every_run(cell):
    from bench.harness import STATE, UNITS, rng
    files = resolve(SPEC, cell)
    ref = importlib.import_module(files["reference"])
    cfg, traffic = dict(files["cfg"], **TINY), files["traffic"]

    def data(seed, i):
        state = ref.make_state(cfg, rng(seed, STATE))
        inputs = ref.make_inputs(cfg, traffic, rng(seed, UNITS, i))
        return [a for d in (state, inputs) for _, a in sorted(d.items())]

    same = zip(data(SEED, 3), data(SEED, 3))
    assert all(np.array_equal(a, b) for a, b in same)
    other = data(SEED, 4)[-1]
    assert not np.array_equal(data(SEED, 3)[-1], other)


@pytest.mark.parametrize("metric", [m["name"] for m in SPEC["per_layer"]])
def test_per_layer_metric_moves_an_e2e_metric_of_its_cells(metric):
    m = {x["name"]: x for x in SPEC["per_layer"]}[metric]
    assert m["workloads"]
    for cell in m["workloads"]:
        assert m["moves"] in _e2e_of(cell), (metric, cell)


@pytest.mark.parametrize("metric", [m["name"] for m in
                                    SPEC["end_to_end"] + SPEC["per_layer"]
                                    if m["name"] != "setup_s"])
def test_metric_reader_finds_nothing_in_an_empty_run(metric):
    for loop in ("oneshot", "session"):
        ctx = {"loop": loop, "units": [], "window_s": 1.0, "setup_s": 1.0,
               "trace": None}
        assert load_module(metric_path(metric)).read(ctx) is None


# -- least work and peaks -------------------------------------------------

def test_least_work_of_the_configurations():
    km = resolve(SPEC, "kmeans.oneshot")
    w = importlib.import_module(km["reference"]).least_work(
        km["cfg"], km["traffic"])
    assert w == {"flops": 4 * 4096 ** 3 + 2 * 4096 ** 2,
                 "bytes": 3 * 4096 ** 2 * 4}
    # compute-bound: 1.40 ms at 197 TFLOP/s
    assert least_seconds(w["flops"], w["bytes"], "TPU v5 lite") == \
        pytest.approx(1.39549e-3, rel=1e-4)

    mk = resolve(SPEC, "markov.session")
    w = importlib.import_module(mk["reference"]).least_work(
        mk["cfg"], mk["traffic"])
    assert w == {"flops": 3 * 2 * 10240 ** 2,
                 "bytes": (10240 ** 2 + 2 * 10240) * 4}
    # memory-bound: P read once, 512 us at 819 GB/s
    assert least_seconds(w["flops"], w["bytes"], "TPU v5 lite") == \
        pytest.approx(512.22e-6, rel=1e-4)


def test_an_unknown_device_has_no_peaks():
    assert peaks("TPU v5 lite")["flops"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        least_seconds(1.0, 1.0, "cpu")


# -- the cells, end to end on the CPU --------------------------------------

def test_session_query_counts_its_host_device_bytes():
    # each of the 3 steps runs nt k-groups of nt mat-vec tasks; a task
    # sends its P tile, u tile and C tile up and its C tile down
    n, tile = TINY["n"], TINY["tile"]
    nt = n // tile
    want = 3 * nt ** 2 * (tile ** 2 + 3 * tile) * 4
    env = make_env(resolve(SPEC, "markov.session"), SEED, TINY)
    lp = Session(env)
    try:
        recs = [lp.one(rng(SEED, UNITS, i))[0] for i in range(2)]
    finally:
        lp.close()
    assert [r["xfer_bytes"] for r in recs] == [want, want]
    read = load_module(metric_path("xfer_mib.session")).read
    assert read({"loop": "session", "units": recs}) == want / 2 ** 20
    assert read({"loop": "oneshot", "units": recs}) is None


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_and_agrees_with_its_reference(cell):
    res = _run(cell)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1
    assert set(res["metrics"]) == _e2e_of(cell)
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert set(res["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert list(res)[-1] == "checks"
    assert json.loads(json.dumps(res)) == res


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_reads_host_metrics_and_no_device_on_the_cpu(cell,
                                                                 tmp_path):
    res = run_cell(cell, SEED, 0.0, True, time.perf_counter(),
                   overrides=TINY, spec=SPEC, trace_dir=tmp_path,
                   log=lambda s: None)
    assert res["correct"] is True
    names = set(res["metrics"])
    assert any(n.startswith("plan_ms.") for n in names)
    assert any(n.startswith("group_calls.") for n in names)
    # no TPU plane in a CPU trace: the device metrics stay out, never 0
    assert not any(n.startswith(("kernels_roofline", "device_idle"))
                   for n in names)
    assert "breakdown" not in res and "busy_s" not in res["device"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    res = _run(cell, control=True, units=2)
    assert res["correct"] is False
    r = res["checks"]["rel_err_max"]
    assert r["value"] > r["limit"]


def _fault(kind):
    from repro.kernels import ops as kops
    real = kops.addmul_batched

    def addmul_batched(c, a, b, **kw):
        out = np.array(real(c, a, b, **kw))
        if kind == "state_unchanged":
            return np.array(c, dtype=out.dtype)
        if kind == "half_batch_left_out":
            h = (len(out) + 1) // 2
            out[h:] = c[h:]
        elif kind == "answer_altered":
            out[0] *= 1 + 1e-3
        return out
    return addmul_batched


@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch_left_out",
                                  "answer_altered"])
@pytest.mark.parametrize("cell", CELLS)
def test_fault_in_the_timed_path_is_not_correct(cell, kind, monkeypatch):
    from repro.kernels import ops as kops
    monkeypatch.setattr(kops, "addmul_batched", _fault(kind))
    res = _run(cell)
    assert res["correct"] is False
    assert res["checks"]["rel_err_max"]["value"] > \
        res["checks"]["rel_err_max"]["limit"]


def test_run_py_without_a_tpu_exits_nonzero_with_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "kmeans.oneshot", "--seed", str(SEED), "--seconds",
                        "1", "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
    assert "not a TPU" in p.stderr
