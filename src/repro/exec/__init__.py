"""Executor backends + the single name -> factory registry.

``EXECUTORS`` is the one place a backend is named: ``CMMEngine.run``,
benchmarks and tests all resolve executor strings through
``make_executor``, so adding a backend is one registry line.
"""
from typing import Callable, Dict

from .local import LocalExecutor                                # noqa: F401
from .batched import (WaveExecutor, build_waves,                # noqa: F401
                      predict_wave_makespan)
from .cluster import predict_cluster_makespan                  # noqa: F401
from .elastic import ChaosEvent, ClusterExecutor                # noqa: F401

#: executor name -> zero-arg-capable factory (kwargs forwarded verbatim)
EXECUTORS: Dict[str, Callable] = {
    # per-task threaded NumPy executor (the correctness oracle's twin)
    "local": LocalExecutor,
    # wave-batched stacked-kernel executor
    "batched": lambda **kw: WaveExecutor(backend="numpy", **kw),
    # wave-batched, ADDMUL groups through jax.vmap over the Pallas GEMM
    "batched-pallas": lambda **kw: WaveExecutor(backend="pallas", **kw),
    # one process per ClusterSpec node, HEFT placements executed for
    # real, under membership churn: heartbeats, lineage recovery,
    # frontier re-planning, speculative straggler duplicates
    "cluster": ClusterExecutor,
}


def make_executor(name: str, **kw):
    """Instantiate a registered executor backend by name."""
    try:
        factory = EXECUTORS[name]
    except KeyError:
        raise ValueError(
            f"unknown executor {name!r}; known: {sorted(EXECUTORS)}"
        ) from None
    return factory(**kw)
