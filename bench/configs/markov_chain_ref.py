"""Inputs, plain reference and least work of the paper's Markov chain,
u' = P^3 u, run once over a fresh P and u.

Imports nothing of the program under test.  The reference is NumPy in
float64 on the host, ``P @ (P @ (P @ u))`` with P converted once a
program; the control is the same order in float32 one precision step
below the configuration's, ``jax.numpy`` at ``Precision.HIGH`` (see
``precision.py``).
"""
import functools

import jax
import numpy as np

from .precision import dot


def make_state(cfg: dict, rng: np.random.Generator) -> dict:
    """Nothing outlives one program."""
    return {}


def make_inputs(cfg: dict, traffic: dict, rng: np.random.Generator) -> dict:
    """P (n x n) and u (n x 1), standard normal, as the paper's program
    (``benchmarks/cmm_suite.py:markov``) draws its random leaves."""
    n, dt = cfg["n"], np.dtype(cfg["dtype"])
    return {"P": rng.standard_normal((n, n), dtype=dt),
            "u": rng.standard_normal((n, 1), dtype=dt)}


@functools.lru_cache(maxsize=None)
def _control():
    def f(p, u):
        for _ in range(3):
            u = dot(p, u, control=True)
        return u
    return jax.jit(f)


def reference(state: dict, inputs: list, cfg: dict, traffic: dict,
              control: bool = False) -> list:
    """P^3 u for each program's P and u."""
    out = []
    for a in inputs:
        if control:
            out.append(np.asarray(_control()(a["P"], a["u"])))
            continue
        p, u = a["P"].astype(np.float64), a["u"].astype(np.float64)
        for _ in range(3):
            u = p @ u
        out.append(u)
    return out


def least_work(cfg: dict, traffic: dict) -> dict:
    """One program at the least: three mat-vecs (2 n^2 each), the order
    that reads P once per step and never forms an n x n product; P read
    once, u read once, P^3 u written once."""
    n, w = cfg["n"], np.dtype(cfg["dtype"]).itemsize
    return {"flops": 3 * 2 * n * n, "bytes": (n * n + 2 * n) * w}
