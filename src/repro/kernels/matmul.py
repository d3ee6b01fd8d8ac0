"""Pallas blocked GEMM / GEMM-accumulate — CMM's ``addmul`` task on TPU.

The paper's hot task is ``C_ij += A_ik @ B_kj`` on an L3-cache-tiled CPU
BLAS.  The TPU adaptation re-tiles for the memory hierarchy HBM -> VMEM ->
MXU: the ``pallas_call`` grid walks (i, j, k) output/contraction blocks, each
step streaming one (bm, bk) A-block and one (bk, bn) B-block into VMEM,
feeding the 128x128 systolic MXU, and accumulating into a float32 VMEM
scratch that is written back to HBM once per (i, j) block (on the last k
step).  Block sizes default to MXU-aligned 128 multiples; the CMM autotuner
(core/autotune.py) selects them with the same simulate-candidates loop the
paper uses for tile sizes.

Kernels:
  * ``matmul_kernel``  — C = A @ B
  * ``addmul_kernel``  — C = C_in + A @ B   (the paper's addmul, fused)
  * ``addmul_epilogue`` — C_in + A @ B followed by a fused elementwise
    epilogue program (the FUSED tile-program encoding from core/fusion),
    applied to the float32 VMEM accumulator on the last k step, before the
    single HBM store.  This is the true-fusion leg of the matmul-epilogue
    optimization: the elementwise chain never round-trips through HBM.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _dot_f32(a: jax.Array, b: jax.Array) -> jax.Array:
    """``a @ b`` accumulated in float32.  Float32 operands contract at full
    float32 precision: Mosaic's default contraction rounds them to bf16
    (on a TPU v5e that gave the n = 8192 float32 Markov chain a norm-wise
    relative error of 4.0e-3, against 6.6e-7 at full precision)."""
    prec = (jax.lax.Precision.HIGHEST
            if jnp.float32 in (a.dtype, b.dtype) else None)
    return jnp.dot(a, b, precision=prec, preferred_element_type=jnp.float32)


def _mm_kernel(a_ref, b_ref, o_ref, acc_ref, *, nk: int):
    """Grid (i, j, k); k is the minor-most (fastest) dimension."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _dot_f32(a_ref[...], b_ref[...])

    @pl.when(k == nk - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _addmul_kernel(c_ref, a_ref, b_ref, o_ref, acc_ref, *, nk: int):
    """o = c + a @ b ; accumulator seeded from the C block (fused addmul)."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = c_ref[...].astype(jnp.float32)

    acc_ref[...] += _dot_f32(a_ref[...], b_ref[...])

    @pl.when(k == nk - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


# -- fused epilogue -------------------------------------------------------
# jnp translation of the FUSED tile-program vocabulary (core/fusion).
# The program runs on the float32 accumulator inside the kernel, so every
# op maps to a VPU-friendly jnp primitive.

_EPI_UNARY = {
    "sin": jnp.sin,
    "cos": jnp.cos,
    "exp": jnp.exp,
    "tanh": jnp.tanh,
    "abs": jnp.abs,
    "relu": lambda x: jnp.maximum(x, 0.0),
    "sqrt": jnp.sqrt,
    "sign": jnp.sign,
}


def _epi_scale(kind: str, x: jax.Array, s: float) -> jax.Array:
    if kind == "add":
        return x + s
    if kind == "sub":
        return x - s
    if kind == "rsub":
        return s - x
    if kind in ("scale", "mul", "ewmul"):
        return x * s
    if kind == "div":
        return x / s
    if kind == "rdiv":
        return s / x
    raise ValueError(f"unknown scalar op {kind}")


def eval_epilogue_jnp(prog, inputs) -> jax.Array:
    """Interpret a FUSED tile program over jnp values (last instr = out).

    Mirrors ``fusion.eval_fused`` semantics; used inside the Pallas kernel
    (on VMEM blocks) and directly for testing the translation.
    """
    vals = []
    for ins in prog:
        kind = ins[0]
        if kind == "in":
            vals.append(inputs[ins[1]])
        elif kind == "ewise":
            vals.append(_EPI_UNARY[ins[1]](vals[ins[2]]))
        elif kind == "scale":
            vals.append(_epi_scale(ins[1], vals[ins[3]], ins[2]))
        elif kind == "add":
            vals.append(vals[ins[1]] + vals[ins[2]])
        elif kind == "sub":
            vals.append(vals[ins[1]] - vals[ins[2]])
        elif kind == "ewmul":
            vals.append(vals[ins[1]] * vals[ins[2]])
        else:  # pragma: no cover
            raise ValueError(f"unknown epilogue instr {kind}")
    return vals[-1]


def _addmul_epi_kernel(*refs, nk: int, prog, nextra: int):
    """o = epilogue(c + a @ b, extras...) — epilogue on the f32 accumulator
    at the last k step, fused before the single store to HBM."""
    c_ref, a_ref, b_ref = refs[:3]
    extra_refs = refs[3:3 + nextra]
    o_ref = refs[3 + nextra]
    acc_ref = refs[4 + nextra]
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = c_ref[...].astype(jnp.float32)

    acc_ref[...] += _dot_f32(a_ref[...], b_ref[...])

    @pl.when(k == nk - 1)
    def _done():
        ins = [acc_ref[...]] + [r[...].astype(jnp.float32)
                                for r in extra_refs]
        o_ref[...] = eval_epilogue_jnp(prog, ins).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("prog", "block_m", "block_n", "block_k", "out_dtype",
                     "interpret"))
def addmul_epilogue(c: jax.Array, a: jax.Array, b: jax.Array, *extras,
                    prog, block_m: int = 128, block_n: int = 128,
                    block_k: int = 128, out_dtype=None,
                    interpret: bool = False) -> jax.Array:
    """Fused ``epilogue(C + A @ B, extras...)`` in one Pallas launch.

    ``prog`` is the FUSED tile program (hashable tuple; in-slot 0 is the
    accumulated C, slots 1.. are ``extras`` in order).  The accumulator
    lives in float32 VMEM, so this leg is validated at tolerance against
    the NumPy path, like the plain Pallas addmul.  ``out_dtype`` overrides
    the store dtype (the mixed-precision bf16 gate); default is the NumPy
    promotion over C and extras.
    """
    m, kdim = a.shape
    _, n = b.shape
    if c.shape != (m, n):
        raise ValueError(f"bad addmul shapes {c.shape} + {a.shape}@{b.shape}")
    for e in extras:
        if e.shape != (m, n):
            raise ValueError(f"bad epilogue extra shape {e.shape} != {(m, n)}")
    if out_dtype is None:
        out_dtype = functools.reduce(
            jnp.promote_types, [e.dtype for e in extras], c.dtype)
    ap = _pad_to(a, (block_m, block_k))
    bp = _pad_to(b, (block_k, block_n))
    cp = _pad_to(c, (block_m, block_n))
    eps = [_pad_to(e, (block_m, block_n)) for e in extras]
    gm, gn, gk = (_blocks(m, block_m), _blocks(n, block_n),
                  _blocks(kdim, block_k))
    ij_spec = pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j))
    out = pl.pallas_call(
        functools.partial(_addmul_epi_kernel, nk=gk, prog=prog,
                          nextra=len(extras)),
        grid=(gm, gn, gk),
        in_specs=[
            ij_spec,
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),
        ] + [ij_spec] * len(extras),
        out_specs=ij_spec,
        out_shape=jax.ShapeDtypeStruct((gm * block_m, gn * block_n),
                                       out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        interpret=interpret,
    )(cp, ap, bp, *eps)
    return out[:m, :n]


def _pad_to(x: jax.Array, mult: Tuple[int, int]) -> jax.Array:
    m, n = x.shape
    pm = (-m) % mult[0]
    pn = (-n) % mult[1]
    if pm or pn:
        x = jnp.pad(x, ((0, pm), (0, pn)))
    return x


def _blocks(dim: int, blk: int) -> int:
    return -(-dim // blk)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "interpret"))
def matmul(a: jax.Array, b: jax.Array, *, block_m: int = 128,
           block_n: int = 128, block_k: int = 128,
           interpret: bool = False) -> jax.Array:
    """C = A @ B via the blocked Pallas kernel.  Ragged shapes are padded to
    block multiples and the result sliced back (edge-tile handling)."""
    if a.ndim != 2 or b.ndim != 2 or a.shape[1] != b.shape[0]:
        raise ValueError(f"bad matmul shapes {a.shape} @ {b.shape}")
    m, kdim = a.shape
    _, n = b.shape
    out_dtype = jnp.promote_types(a.dtype, b.dtype)
    ap = _pad_to(a, (block_m, block_k))
    bp = _pad_to(b, (block_k, block_n))
    gm, gn, gk = (_blocks(m, block_m), _blocks(n, block_n),
                  _blocks(kdim, block_k))
    out = pl.pallas_call(
        functools.partial(_mm_kernel, nk=gk),
        grid=(gm, gn, gk),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((gm * block_m, gn * block_n),
                                       out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        interpret=interpret,
    )(ap, bp)
    return out[:m, :n]


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "interpret"))
def addmul(c: jax.Array, a: jax.Array, b: jax.Array, *, block_m: int = 128,
           block_n: int = 128, block_k: int = 128,
           interpret: bool = False) -> jax.Array:
    """CMM's addmul: C + A @ B, fused (C is read block-wise into the VMEM
    accumulator — no separate add pass over HBM)."""
    m, kdim = a.shape
    _, n = b.shape
    if c.shape != (m, n):
        raise ValueError(f"bad addmul shapes {c.shape} + {a.shape}@{b.shape}")
    out_dtype = c.dtype
    ap = _pad_to(a, (block_m, block_k))
    bp = _pad_to(b, (block_k, block_n))
    cp = _pad_to(c, (block_m, block_n))
    gm, gn, gk = (_blocks(m, block_m), _blocks(n, block_n),
                  _blocks(kdim, block_k))
    out = pl.pallas_call(
        functools.partial(_addmul_kernel, nk=gk),
        grid=(gm, gn, gk),
        in_specs=[
            pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((gm * block_m, gn * block_n),
                                       out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        interpret=interpret,
    )(cp, ap, bp)
    return out[:m, :n]
