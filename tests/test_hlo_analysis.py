"""The roofline analyzer itself: trip counts, dot flops, collective math."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.launch.hlo_analysis import analyze, parse_shape
from repro.launch.roofline import V5E, roofline_terms


def test_parse_shape():
    assert parse_shape("bf16[16,512]") == (8192, 16384)
    assert parse_shape("f32[2,3,4]{2,1,0}") == (24, 96)
    assert parse_shape("(f32[4], s32[2])")[0] == 6
    assert parse_shape("pred[]") == (1, 1)


def test_scan_trip_counts_in_flops():
    """cost_analysis misses scan trips; our analyzer must not."""
    def f(x, w):
        def body(c, wi):
            return jnp.tanh(c @ wi), None
        c, _ = jax.lax.scan(body, x, w)
        return c

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((10, 64, 64), jnp.float32)
    compiled = jax.jit(f).lower(x, w).compile()
    xla_flops = compiled.cost_analysis()["flops"]
    ours = analyze(compiled.as_text()).flops
    want = 10 * 2 * 64 ** 3
    assert abs(ours - want) / want < 0.01
    assert xla_flops < ours / 5  # XLA counted the body once


def test_nested_scan_multipliers():
    def f(x):
        def outer(c, _):
            def inner(c2, _):
                return jnp.tanh(c2 @ c2), None
            c2, _ = jax.lax.scan(inner, c, None, length=5)
            return c2, None
        c, _ = jax.lax.scan(outer, x, None, length=3)
        return c

    x = jax.ShapeDtypeStruct((32, 32), jnp.float32)
    st = analyze(jax.jit(f).lower(x).compile().as_text())
    want = 3 * 5 * 2 * 32 ** 3
    assert abs(st.flops - want) / want < 0.01


def test_sliced_param_access_not_overcounted():
    """dynamic-slice of stacked params inside a scan must count slice
    bytes, not the whole (L, ...) array per iteration."""
    L, D = 20, 64

    def f(x, w):
        def body(c, wi):
            return c @ wi, None
        c, _ = jax.lax.scan(body, x, w)
        return c

    x = jax.ShapeDtypeStruct((D, D), jnp.float32)
    w = jax.ShapeDtypeStruct((L, D, D), jnp.float32)
    st = analyze(jax.jit(f).lower(x, w).compile().as_text())
    # upper bound: L x (one slice r/w + carry traffic + dot operands)
    per_iter_ub = 8 * D * D * 4
    assert st.hbm_bytes < L * per_iter_ub, st.hbm_bytes


def test_roofline_terms_and_bound():
    class S:
        flops = 197e12          # exactly 1 s of compute
        hbm_bytes = 819e9 / 2   # 0.5 s
        collective_bytes = 50e9 * 2  # 2 s
    t = roofline_terms(S, 256, V5E)
    assert t["bound"] == "collective"
    assert t["compute_s"] == pytest.approx(1.0)
    assert t["roofline_fraction"] == pytest.approx(0.5)
    assert t["step_lower_bound_s"] == pytest.approx(2.0)


def test_dryrun_artifacts_have_corrected_collectives():
    from benchmarks.roofline_table import load_cells
    cells = load_cells("single_pod_16x16")
    if not cells:
        pytest.skip("no dry-run artifacts")
    for c in cells:
        raw = c["hlo"].get("collective_bytes_raw", 0)
        cor = c["hlo"]["collective_bytes"]
        if raw:
            assert cor <= raw + 1e-6
