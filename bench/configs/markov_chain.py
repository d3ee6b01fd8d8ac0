"""The paper's Markov chain (Fig. 2), u' = P^3 u, as one one-shot program
written as the paper writes it: ``(P @ P @ P) @ u``.

The same expression as ``benchmarks/cmm_suite.py:markov``, over leaves
made from the benchmark's own arrays instead of the engine's RANDOM
leaves.  The order in which the products run is the planner's to choose.
"""
from repro.core import ClusteredMatrix as CM


def build(arrays: dict, cfg: dict) -> CM:
    P = CM.from_array(arrays["P"], name="P")
    u = CM.from_array(arrays["u"], name="u")
    return (P @ P @ P) @ u
