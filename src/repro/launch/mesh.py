"""Production mesh builders.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axis types on every axis.

    Newer JAX defaults to Explicit axes, which the GSPMD-style step
    functions (``with_sharding_constraint`` on named axes) reject.
    """
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 two-pod (512 chips).

    Axes: `data` = batch/FSDP, `model` = tensor/expert parallel; `pod`
    (multi-pod) is additional data parallelism across the DCN/ICI-linked
    pods.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh(data: int = 2, model: int = 2):
    """Tiny mesh over host devices for tests (requires
    xla_force_host_platform_device_count >= data*model in the test env)."""
    return auto_mesh((data, model), ("data", "model"))
