"""Matrix-chain order in the planner (``core/fusion.py``).

Each chain of MATMULs is re-associated for the fewest flops; a written
order among the cheapest, a shared or root product and a transposed
operand are kept as they are.  Re-association changes rounding only: the
result agrees with ``eager()`` of the written expression.
"""
import numpy as np
import pytest

from benchmarks.cmm_suite import BENCHMARKS
from repro.core import (ClusteredMatrix as CM, CMMEngine,
                        analytic_time_model, structural_signature)
from repro.core import fusion
from repro.core.fusion import optimize, optimize_many
from repro.core.lazy import Op, topo_order_many

TM = analytic_time_model()


def _engine():
    return CMMEngine(timemodel=TM)


def _matmuls(roots):
    return [n for n in topo_order_many(roots) if n.op is Op.MATMUL]


def _counts(plan):
    c = plan.program.graph.counts()
    return c.get("addmul", 0), c.get("calloc", 0)


def test_paper_markov_is_planned_as_three_matvecs():
    n, tile = 1024, 128
    plan = _engine().plan(BENCHMARKS["Markov"](n), tile=tile)
    assert _counts(plan) == (192, 24)           # 3 x (nt^2, nt), nt = 8
    assert all(m.shape == (n, 1) for m in _matmuls(plan.program.roots))
    rep = plan.fusion
    assert rep.chains_reordered == 1
    assert rep.matmul_flops_written == 2 * 2 * n ** 3 + 2 * n ** 2
    assert rep.matmul_flops_planned == 3 * 2 * n ** 2


def _square_chain(n):
    X = CM.rand(n, n, seed=0)
    return (X @ X) @ X


def _session_step(n):
    return CM.rand(n, n, seed=0) @ CM.rand(n, 1, seed=1)


@pytest.mark.parametrize("build", [BENCHMARKS["Kmeans"], BENCHMARKS["Hill"],
                                   BENCHMARKS["DFT"], BENCHMARKS["Hits"],
                                   _square_chain, _session_step],
                         ids=["kmeans", "hill", "dft", "hits",
                              "square_chain", "session_step"])
def test_cheapest_written_order_keeps_its_plan(build, monkeypatch):
    """Plans of programs whose written order is among the cheapest are
    those of a planner without the pass: the same nodes, structural
    signature and task counts."""
    n, tile = 96, 32
    expr = build(n)
    _, rep = optimize_many([expr], fuse=False)
    assert rep.chains_reordered == 0
    assert rep.matmul_flops_planned == rep.matmul_flops_written
    plan = _engine().plan(expr, tile=tile)
    monkeypatch.setattr(fusion, "order_matmul_chains_many",
                        lambda roots, report, tracer=None: list(roots))
    bare = _engine().plan(expr, tile=tile)
    assert structural_signature(plan.program.roots[0]) == \
        structural_signature(bare.program.roots[0])
    assert plan.program.graph.counts() == bare.program.graph.counts()
    assert plan.fusion.chains_reordered == 0


def test_iterated_expression_is_reordered_and_still_hits_the_cache():
    def expr(seed):
        X = CM.rand(24, 24, seed=seed)
        v = CM.rand(24, 1, seed=seed + 1)
        return (X @ X) @ v + v
    eng = _engine()
    p1, p2 = eng.plan(expr(0), tile=8), eng.plan(expr(100), tile=8)
    assert p1.fusion.chains_reordered == 1
    assert not p1.cache_hit and p2.cache_hit
    assert all(m.shape == (24, 1) for m in _matmuls(p1.program.roots))


def test_leontief_keeps_a2_shared_and_shares_a_d_once():
    n = 64
    expr = BENCHMARKS["Leontief"](n)
    root, rep = optimize(expr, fuse=False)
    assert rep.chains_reordered == 1 and rep.cse_merged == 1
    mms = _matmuls([root])
    squares = [m for m in mms if m.shape == (n, n)]
    assert len(squares) == 1                       # A2, computed once
    (a2,) = squares
    assert sum(a2 in m.parents for m in mms) == 2  # ... and used twice
    a = a2.parents[0]
    a_d = [m for m in mms if m.parents[0] is a and m.shape == (n, 1)]
    assert len(a_d) == 1                           # A @ d, once
    assert sum(a_d[0] in m.parents for m in topo_order_many([root])) == 2
    assert rep.matmul_flops_planned == 2 * n ** 3 + 3 * 2 * n ** 2


def test_transposed_operand_keeps_its_flag():
    rng = np.random.default_rng(7)
    A = CM.from_array(rng.standard_normal((40, 30)))
    B = CM.from_array(rng.standard_normal((40, 40)))
    v = CM.from_array(rng.standard_normal((40, 1)))
    expr = A.T @ B @ v
    root = optimize(expr, fuse=False)[0]
    assert root.parents[0] is A and root.payload == (True, False)
    inner = root.parents[1]
    assert inner.parents == (B, v) and inner.payload is None
    out = _engine().run(expr, tile=16)
    ref = expr.eager()
    np.testing.assert_allclose(out, ref, rtol=1e-12,
                               atol=1e-12 * np.abs(ref).max())


def test_transposed_or_root_product_bounds_a_chain():
    A = CM.rand(32, 32, seed=0)
    B = CM.rand(32, 32, seed=1)
    v = CM.rand(32, 1, seed=2)
    M = A @ B
    _, rep = optimize_many([M.T @ v])
    assert rep.chains_reordered == 0               # transposed product
    _, rep = optimize_many([M, M @ v])
    assert rep.chains_reordered == 0               # a root: persisted
    _, rep = optimize_many([M @ v])
    assert rep.chains_reordered == 1


def _mixed_left():
    P = CM.rand(32, 32, seed=0, dtype=np.float32)
    Q = CM.rand(32, 32, seed=1, dtype=np.float64)
    u = CM.rand(32, 1, seed=2, dtype=np.float64)
    return (Q @ P) @ u


def _mixed_both_sides():
    """``(A @ B) @ (C @ D)`` with B and C in float32: ``B @ C`` would be
    a float32 product where the written order rounds in float64."""
    A = CM.rand(32, 32, seed=0, dtype=np.float64)
    B = CM.rand(32, 32, seed=1, dtype=np.float32)
    C = CM.rand(32, 1, seed=2, dtype=np.float32)
    D = CM.rand(1, 1, seed=3, dtype=np.float64)
    return (A @ B) @ (C @ D)


@pytest.mark.parametrize("build", [_mixed_left, _mixed_both_sides],
                         ids=["left", "both_sides"])
def test_chain_of_mixed_dtypes_keeps_its_written_order(build):
    expr = build()
    root, rep = optimize(expr, fuse=False)
    assert rep.chains_reordered == 0 and root is expr


def _brute_least_flops(dims):
    """Least 2·m·k·n over every parenthesisation, by exhaustion."""
    def best(i, j):
        if i == j:
            return 0
        return min(best(i, s) + best(s + 1, j)
                   + 2 * dims[i] * dims[s + 1] * dims[j + 1]
                   for s in range(i, j))
    return best(0, len(dims) - 2)


@pytest.mark.parametrize("seed", range(6))
def test_random_chains_take_the_least_flops_and_agree_with_eager(seed):
    rng = np.random.default_rng(seed)
    k = int(rng.integers(3, 6))
    dims = [int(d) for d in rng.integers(1, 24, size=k + 1)]
    mats = [CM.from_array(rng.standard_normal((dims[i], dims[i + 1])))
            for i in range(k)]
    expr = mats[0]
    for m in mats[1:]:                             # left to right, as read
        expr = expr @ m
    root, rep = optimize(expr, fuse=False)
    assert rep.matmul_flops_planned == _brute_least_flops(dims)
    assert rep.chains_reordered == \
        int(rep.matmul_flops_planned < rep.matmul_flops_written)
    out = _engine().run(expr, tile=8)
    ref = expr.eager()
    np.testing.assert_allclose(out, ref, rtol=1e-12,
                               atol=1e-12 * np.abs(ref).max())


@pytest.mark.parametrize("name", list(BENCHMARKS))
def test_suite_program_agrees_with_eager_as_written(name):
    """Every paper program, re-associated or not, against ``eager()`` of
    the expression as written, in float64."""
    expr = BENCHMARKS[name](64)
    out = _engine().run(expr, tile=24)
    ref = expr.eager()
    np.testing.assert_allclose(out, ref, rtol=1e-12,
                               atol=1e-12 * np.abs(ref).max())

