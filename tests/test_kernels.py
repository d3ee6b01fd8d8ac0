"""Pallas kernels vs pure-jnp oracles, interpret mode, shape/dtype sweep."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref

MM_SHAPES = [(128, 128, 128), (256, 384, 128), (100, 70, 130),
             (257, 129, 255), (64, 512, 192), (1, 128, 128)]
DTYPES = [jnp.float32, jnp.bfloat16]


def _tol(dt):
    return dict(rtol=5e-2, atol=5e-2) if dt == jnp.bfloat16 \
        else dict(rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize("m,k,n", MM_SHAPES)
@pytest.mark.parametrize("dt", DTYPES)
def test_matmul_kernel(m, k, n, dt):
    rng = np.random.default_rng(m * 7 + n)
    a = jnp.asarray(rng.standard_normal((m, k)), dt)
    b = jnp.asarray(rng.standard_normal((k, n)), dt)
    out = ops.matmul(a, b)
    want = ref.matmul(a, b)
    assert out.shape == (m, n) and out.dtype == want.dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dt))


@pytest.mark.parametrize("m,k,n", MM_SHAPES[:4])
@pytest.mark.parametrize("dt", DTYPES)
def test_addmul_kernel(m, k, n, dt):
    rng = np.random.default_rng(m + n)
    a = jnp.asarray(rng.standard_normal((m, k)), dt)
    b = jnp.asarray(rng.standard_normal((k, n)), dt)
    c = jnp.asarray(rng.standard_normal((m, n)), dt)
    out = ops.addmul(c, a, b)
    want = ref.addmul(c, a, b)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dt))


@pytest.mark.parametrize("block", [(64, 64, 64), (128, 128, 256)])
def test_matmul_block_sweep(block):
    bm, bn, bk = block
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.standard_normal((192, 320)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((320, 224)), jnp.float32)
    out = ops.matmul(a, b, block_m=bm, block_n=bn, block_k=bk)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref.matmul(a, b)),
                               rtol=1e-3, atol=1e-4)


def test_addmul_matches_cmm_task_semantics():
    """The kernel implements the paper's addmul: C += A @ B."""
    rng = np.random.default_rng(3)
    c0 = rng.standard_normal((64, 64)).astype(np.float32)
    a = rng.standard_normal((64, 64)).astype(np.float32)
    b = rng.standard_normal((64, 64)).astype(np.float32)
    out = ops.addmul(jnp.asarray(c0), jnp.asarray(a), jnp.asarray(b))
    np.testing.assert_allclose(np.asarray(out), c0 + a @ b,
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("s,d", [(256, 64), (128, 32), (384, 128)])
def test_flash_attention(causal, s, d):
    rng = np.random.default_rng(s + d)
    q = jnp.asarray(rng.standard_normal((2, 3, s, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 3, s, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 3, s, d)), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=causal, block_q=128,
                              block_k=128)
    want = ref.flash_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_flash_attention_bf16():
    rng = np.random.default_rng(9)
    q = jnp.asarray(rng.standard_normal((1, 2, 256, 64)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((1, 2, 256, 64)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((1, 2, 256, 64)), jnp.bfloat16)
    out = ops.flash_attention(q, k, v)
    want = ref.flash_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=5e-2, atol=5e-2)


def test_kernel_used_by_executor():
    """Device executor path: tiled CMM execution through the Pallas
    addmul, batched per wave."""
    from repro.core import CMMEngine, ClusteredMatrix as CM, c5_9xlarge
    from repro.core import analytic_time_model
    rng = np.random.default_rng(5)
    a = rng.standard_normal((96, 96))
    A = CM.from_array(a)
    eng = CMMEngine(c5_9xlarge(1), analytic_time_model(), tile=48)
    out = eng.run(A @ A, executor="batched-pallas")
    np.testing.assert_allclose(out, a @ a, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("chunk", [16, 32])
@pytest.mark.parametrize("normalize", [True, False])
def test_gla_kernel_vs_oracle(chunk, normalize):
    """Pallas chunkwise-GLA kernel vs the jnp chunkwise oracle (which is
    itself validated against the naive recurrence in test_properties)."""
    from repro.kernels.gla import gla
    from repro.models.ssm import chunkwise_gla
    rng = np.random.default_rng(chunk)
    B, S, H, dk, dv = 2, 64, 3, 8, 16
    q = jnp.asarray(rng.standard_normal((B, S, H, dk)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, H, dk)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, H, dv)), jnp.float32)
    la = jnp.asarray(-np.abs(rng.standard_normal((B, S, H))) * 0.1,
                     jnp.float32)
    y_k = gla(q, k, v, la, chunk=chunk, normalize=normalize, interpret=True)
    y_r, _ = chunkwise_gla(q, k, v, la, chunk=chunk, normalize=normalize)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                               rtol=3e-4, atol=3e-4)


def test_gla_kernel_bf16():
    from repro.kernels.gla import gla
    from repro.models.ssm import chunkwise_gla
    rng = np.random.default_rng(7)
    B, S, H, dk, dv = 1, 32, 2, 8, 8
    q = jnp.asarray(rng.standard_normal((B, S, H, dk)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((B, S, H, dk)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((B, S, H, dv)), jnp.bfloat16)
    la = jnp.asarray(-np.abs(rng.standard_normal((B, S, H))) * 0.1,
                     jnp.float32)
    y_k = gla(q, k, v, la, chunk=16, interpret=True)
    y_r, _ = chunkwise_gla(q, k, v, la, chunk=16)
    np.testing.assert_allclose(np.asarray(y_k, np.float32),
                               np.asarray(y_r, np.float32),
                               rtol=5e-2, atol=5e-2)


@pytest.mark.parametrize("backend,interpret", [("cpu", True), ("tpu", False),
                                               ("gpu", None)])
def test_interpret_mode_only_on_cpu(monkeypatch, backend, interpret):
    """Interpret mode is for the CPU; any other non-TPU backend raises
    instead of quietly interpreting the kernels."""
    monkeypatch.setattr(ops.jax, "default_backend", lambda: backend)
    if interpret is None:
        with pytest.raises(RuntimeError, match=backend):
            ops._interpret_default()
    else:
        assert ops._interpret_default() is interpret


def test_compile_cache_dir(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins and is left alone; otherwise the
    cache goes to the checkout's fixed .jax_cache/."""
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert ops.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = ops.COMPILE_CACHE_DIR
        assert want.name == ".jax_cache"
        assert (want.parent / "src" / "repro" / "kernels" / "ops.py").exists()
        assert ops.enable_compile_cache() == str(want)
        assert jax.config.jax_compilation_cache_dir == str(want)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
