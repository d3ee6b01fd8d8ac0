"""Jit'd public wrappers for the Pallas kernels.

On the CPU backend the kernels execute in ``interpret=True`` mode — the
kernel body runs as traced Python, validating the exact TPU tiling logic; on
a TPU backend the same calls compile to Mosaic.  Any other backend is an
error: a GPU or a misconfigured accelerator must not fall back to the
interpreter without a word.
"""
from __future__ import annotations

import functools
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from . import matmul as _mm
from . import flash_attention as _fa
from . import gla as _gla
from . import ref


def _interpret_default() -> bool:
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(f"the Pallas kernels target TPU (compiled) or CPU "
                       f"(interpret mode); backend {backend!r} is neither")


#: fixed, checkout-relative home of the persistent compilation cache (the
#: directory is part of JAX's cache key, so it must not move between runs)
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its cache
    there and nothing is changed.  Otherwise the cache goes to
    ``.jax_cache/`` at the root of the checkout.  Entry points call this
    before their first compile; nothing calls it at import.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)


def _resolve_blocks(block_m, block_n, block_k, m, n, k):
    """Autotuned-by-shape block defaults: clamp to the actual tile dims.

    A 16x16 CMM tile must not be padded out to 128-blocks — at tile sizes
    below the MXU-aligned default the padding would dominate the launch
    (64x the FLOPs for a 16x16 tile).  Explicitly passed block sizes are
    honoured as-is (the core/autotune.py candidates loop sets them).
    """
    if block_m is None:
        block_m = min(128, m)
    if block_n is None:
        block_n = min(128, n)
    if block_k is None:
        block_k = min(128, k)
    return block_m, block_n, block_k


def matmul(a, b, *, block_m: int | None = None, block_n: int | None = None,
           block_k: int | None = None, interpret: bool | None = None):
    interpret = _interpret_default() if interpret is None else interpret
    block_m, block_n, block_k = _resolve_blocks(
        block_m, block_n, block_k, a.shape[0], b.shape[1], a.shape[1])
    return _mm.matmul(a, b, block_m=block_m, block_n=block_n,
                      block_k=block_k, interpret=interpret)


def addmul(c, a, b, *, block_m: int | None = None, block_n: int | None = None,
           block_k: int | None = None, interpret: bool | None = None,
           epilogue=None, extras=(), out_dtype=None):
    """GEMM-accumulate ``c + a @ b``; with ``epilogue`` a FUSED tile
    program, the elementwise chain is fused into the same kernel launch
    (applied to the f32 accumulator before the store)."""
    interpret = _interpret_default() if interpret is None else interpret
    block_m, block_n, block_k = _resolve_blocks(
        block_m, block_n, block_k, a.shape[0], b.shape[1], a.shape[1])
    if epilogue is None:
        return _mm.addmul(c, a, b, block_m=block_m, block_n=block_n,
                          block_k=block_k, interpret=interpret)
    return _mm.addmul_epilogue(
        c, a, b, *extras, prog=tuple(epilogue), block_m=block_m,
        block_n=block_n, block_k=block_k, out_dtype=out_dtype,
        interpret=interpret)


@functools.lru_cache(maxsize=128)
def _addmul_batched_fn(block_m: int, block_n: int, block_k: int,
                       interpret: bool, prog=None, nextra: int = 0,
                       out_dtype=None):
    """One jitted ``vmap`` of the Pallas addmul per block/backend signature.

    The wave executor calls this once per ``(tile shape, dtype)`` group;
    jax's jit cache then specialises per stacked operand shape, so repeated
    waves of the same group signature reuse the compiled executable.
    Epilogued groups key additionally on (program, extra count, store
    dtype) — each distinct fused chain is its own executable.
    """
    if prog is None:
        fn = functools.partial(_mm.addmul, block_m=block_m, block_n=block_n,
                               block_k=block_k, interpret=interpret)
    else:
        def fn(c, a, b, *extras):
            return _mm.addmul_epilogue(
                c, a, b, *extras, prog=prog, block_m=block_m,
                block_n=block_n, block_k=block_k, out_dtype=out_dtype,
                interpret=interpret)
    return jax.jit(jax.vmap(fn))


def addmul_batched(c, a, b, *, block_m: int | None = None,
                   block_n: int | None = None, block_k: int | None = None,
                   interpret: bool | None = None,
                   epilogue=None, extras=(), out_dtype=None):
    """Stacked GEMM-accumulate: ``out[i] = c[i] + a[i] @ b[i]``.

    ``jax.vmap`` over the blocked Pallas kernel — the wave-batched
    executor's ADDMUL group call (one launch per group instead of one per
    tile task).  With ``epilogue``, the group's fused elementwise chain
    runs inside the same launch (``extras`` are the stacked chain
    operands beyond the accumulator; ``out_dtype`` is the mixed-precision
    store override).
    """
    interpret = _interpret_default() if interpret is None else interpret
    block_m, block_n, block_k = _resolve_blocks(
        block_m, block_n, block_k, a.shape[1], b.shape[2], a.shape[2])
    if out_dtype is not None:
        out_dtype = np.dtype(out_dtype)
    fn = _addmul_batched_fn(
        block_m, block_n, block_k, interpret,
        prog=None if epilogue is None else tuple(epilogue),
        nextra=len(extras), out_dtype=out_dtype)
    return fn(jnp.asarray(c), jnp.asarray(a), jnp.asarray(b),
              *[jnp.asarray(e) for e in extras])


def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool | None = None):
    interpret = _interpret_default() if interpret is None else interpret
    return _fa.flash_attention(q, k, v, causal=causal, block_q=block_q,
                               block_k=block_k, interpret=interpret)


def gla(q, k, v, log_a, *, chunk: int = 128, normalize: bool = True,
        interpret: bool | None = None):
    interpret = _interpret_default() if interpret is None else interpret
    return _gla.gla(q, k, v, log_a, chunk=chunk, normalize=normalize,
                    interpret=interpret)


__all__ = ["matmul", "addmul", "addmul_batched", "flash_attention", "gla",
           "ref", "enable_compile_cache"]
