"""The ``markov.oneshot`` cell's own files: its least work, and the plan
of its program at a tiny size on the CPU, which holds no n x n product.
Its run, control and planted faults are in ``test_bench_cells.py``."""
import importlib

import pytest

from bench.harness import ROOT, UNITS, load_json, make_env, resolve, rng
from bench.peaks import least_seconds

SPEC = load_json(ROOT / "BENCHMARK.json")
CELL = "markov.oneshot"
SEED = 2 ** 31 + 12345


def test_least_work_of_the_markov_chain_configuration():
    files = resolve(SPEC, CELL)
    assert files["workload"]["config"] == "markov-chain-n8192-f32"
    w = importlib.import_module(files["reference"]).least_work(
        files["cfg"], files["traffic"])
    assert w == {"flops": 3 * 2 * 8192 ** 2,
                 "bytes": (8192 ** 2 + 2 * 8192) * 4}
    # memory-bound: P read once, 327.84 us at 819 GB/s
    assert least_seconds(w["flops"], w["bytes"], "TPU v5 lite") == \
        pytest.approx(327.84e-6, rel=1e-4)


def test_tiny_cell_plans_no_n_by_n_product():
    from repro.core import CMMEngine, analytic_time_model
    from repro.core.lazy import Op, topo_order_many
    n, tile = 256, 128
    env = make_env(resolve(SPEC, CELL), SEED, {"n": n, "tile": tile})
    arrays = env["ref_mod"].make_inputs(env["cfg"], env["traffic"],
                                        rng(SEED, UNITS, 0))
    expr = env["prog_mod"].build(arrays, env["cfg"])
    plan = CMMEngine(timemodel=analytic_time_model()).plan(expr, tile=tile)
    products = [m for m in topo_order_many(plan.program.roots)
                if m.op is Op.MATMUL]
    assert [m.shape for m in products] == [(n, 1)] * 3
    assert plan.fusion.chains_reordered == 1
    assert plan.fusion.matmul_flops_planned == 3 * 2 * n ** 2
