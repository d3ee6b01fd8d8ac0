"""Network-path benchmark + smoke gate -> BENCH_net.json.

Measures what the priced transfer layer (``runtime/wire.py`` + the
executor lease/relay/streaming machinery) buys and guarantees:

* **bytes-on-wire leg** — a compressible fan-out workload (structured
  operands: low-rank tiles, the shape of persisted intermediates) run
  with the zlib wire codec forced vs raw.  GATED: >= 1.3x wire-byte
  reduction AND bitwise identity to the eager oracle, compression on
  and off — the tile path admits lossless codecs only, so compression
  must never show up in the numbers.
* **streamed-gather leg** — time-to-first-tile with streaming on
  (result tiles copied off the master arena as their TAKECOPY lands,
  overlapped with compute) vs the barrier gather.  GATED: the streamed
  first tile lands strictly earlier than the barrier one, with
  identical bytes.
* **broadcast leg** — fan-out with each holder capped at
  ``BCAST_MIN_FANOUT - 1`` concurrent readers vs uncapped, on the same
  plan: makespans + relay hops recorded (informational — wall-clock
  ratios are not gated on shared hosts; uncapped runs still read from
  landed copies when the producer is busier).  GATED: the capped run
  relays, and both are bit-identical.
* **chaos leg** — killing a relay node mid-broadcast and killing a
  throttled consumer mid-copy (leased XFERs in flight) must both
  recover bit-identically on the cluster executor with every source
  lease released.  GATED.

Exit status is non-zero on any failed gate — wired into CI as the
``net-smoke`` job (``--smoke``: small inputs, writes
``BENCH_net_smoke.json`` so the committed artifact is never clobbered,
per repo convention).

    PYTHONPATH=src python benchmarks/net_bench.py [--smoke] [--out PATH]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

from repro.core import ClusteredMatrix as CM, CMMEngine, analytic_time_model
from repro.core.machine import hetero_spec
from repro.exec.elastic import ChaosEvent, ClusterExecutor
from repro.exec.local import LocalExecutor
from repro.runtime.membership import MembershipConfig

TM = analytic_time_model()
FAST_NET = dict(link_bw=1e12, latency=1e-6)


def _spec(nodes=(3, 2, 1), budget=None):
    return hetero_spec(nodes, mem_bytes=budget, **FAST_NET)


def _structured_expr(n):
    """Compressible fan-out workload: low-rank operands (an outer
    product and a banded ramp) whose tiles — and whose product's tiles —
    zlib can actually shrink, unlike f64 noise.  A @ B fans every A-tile
    out across the output row: the broadcast shape."""
    col = np.linspace(0.0, 1.0, n)
    a = np.outer(col, np.ones(n))
    b = np.add.outer(col, col)
    A = CM.from_array(a, name="A")
    B = CM.from_array(b, name="B")
    return (A @ B) + A


def _plan(expr, tile, spec):
    eng = CMMEngine(spec, TM, plan_cache=False)
    return eng.plan(expr, tile=tile)


def run_bytes_on_wire(n: int, tile: int) -> dict:
    """Forced zlib vs forced raw on the same plan: wire bytes down
    >= 1.3x, bits identical to the eager oracle on every leg."""
    expr = _structured_expr(n)
    spec = _spec()
    plan = _plan(expr, tile, spec)
    oracle = expr.eager()

    legs = {}
    outs = {}
    for codec in ("raw", "zlib"):
        ex = ClusterExecutor(timemodel=TM, wire_codec=codec)
        outs[codec] = ex.execute(plan)
        legs[codec] = ex.stats

    ok_bit = all(
        np.array_equal(np.asarray(oracle, dtype=out.dtype), out)
        or bool(np.allclose(oracle, out, rtol=1e-8, atol=1e-10))
        and np.array_equal(outs["raw"], out)
        for out in outs.values())
    # bitwise across codecs (the eager oracle itself is allclose-only:
    # k-chain re-association)
    ok_bitwise_x = np.array_equal(outs["raw"], outs["zlib"])
    raw_wire = legs["raw"]["wire_bytes"]
    zlib_wire = legs["zlib"]["wire_bytes"]
    ratio = raw_wire / max(zlib_wire, 1)
    return {
        "case": "bytes_on_wire", "n": n, "tile": tile,
        "xfers": legs["raw"]["xfers"],
        "raw_wire_bytes": int(raw_wire),
        "zlib_wire_bytes": int(zlib_wire),
        "xfers_compressed": legs["zlib"]["xfers_compressed"],
        "wire_reduction_x": ratio,
        "ok_xfers_happened": bool(raw_wire > 0),
        "ok_reduction_ge_1_3x": bool(ratio >= 1.3),
        "ok_bitident_all_legs": bool(ok_bit and ok_bitwise_x),
        "ok_no_stale_leases": bool(
            all(st["stale_leases"] == 0 for st in legs.values())),
    }


def run_stream_gather(n: int, tile: int) -> dict:
    """Streamed vs barrier gather on the same plan: first tile strictly
    earlier, full result identical."""
    expr = _structured_expr(n)
    plan = _plan(expr, tile, _spec())
    on = ClusterExecutor(stream_gather=True)
    out_on = on.execute(plan)
    off = ClusterExecutor(stream_gather=False)
    out_off = off.execute(plan)
    t_on, t_off = (on.stats["gather_first_tile_s"],
                   off.stats["gather_first_tile_s"])
    return {
        "case": "stream_gather", "n": n, "tile": tile,
        "streamed_tiles": on.stats["gather_streamed_tiles"],
        "ttft_streamed_s": t_on,
        "ttft_barrier_s": t_off,
        "full_result_streamed_s": on.stats["gather_full_result_s"],
        "full_result_barrier_s": off.stats["gather_full_result_s"],
        "ok_streamed_tiles": bool(on.stats["gather_streamed_tiles"] > 0
                                  and off.stats["gather_streamed_tiles"]
                                  == 0),
        "ok_ttft_strictly_earlier": bool(
            t_on is not None and t_off is not None and t_on < t_off),
        "ok_bitident_stream": bool(np.array_equal(out_on, out_off)),
    }


def run_broadcast(n: int, tile: int) -> dict:
    """Capped vs uncapped fan-out on a fan-out-heavy plan across six
    1-worker nodes (fan-out is widest when every tile is remote)."""
    expr = _structured_expr(n)
    plan = _plan(expr, tile, _spec((1, 1, 1, 1, 1, 1)))
    t0 = time.perf_counter()
    tree = ClusterExecutor(broadcast=True)
    out_t = tree.execute(plan)
    wall_tree = time.perf_counter() - t0
    t0 = time.perf_counter()
    star = ClusterExecutor(broadcast=False)
    out_s = star.execute(plan)
    wall_star = time.perf_counter() - t0
    return {
        "case": "broadcast_vs_unicast", "n": n, "tile": tile,
        "relay_hops_tree": tree.stats["relay_hops"],
        "relay_hops_star": star.stats["relay_hops"],
        "wall_tree_s": wall_tree,
        "wall_star_s": wall_star,
        "ok_tree_relays": bool(tree.stats["relay_hops"] > 0),
        "ok_bitident_broadcast": bool(np.array_equal(out_t, out_s)),
    }


def run_chaos(n: int, tile: int) -> dict:
    """Relay-node death + consumer death mid-copy, both bit-identical
    with every lease closed (the transfer-path bugfixes)."""
    expr = _structured_expr(n)
    ws = 4 * n * n * 8

    plan_r = _plan(expr, tile, _spec((1, 1, 1, 1, 1, 1)))
    ref_r = LocalExecutor().execute(plan_r)
    relay = ClusterExecutor(
        timemodel=TM, broadcast=True,
        chaos=[ChaosEvent(after_done=14, kill_node=4)])
    out_r = relay.execute(plan_r)

    plan_c = _plan(expr, tile, _spec((2, 2, 1, 1), budget=float(ws)))
    ref_c = LocalExecutor().execute(plan_c)
    mid = ClusterExecutor(
        timemodel=TM,
        membership=MembershipConfig(heartbeat_interval_s=0.05),
        chaos=[ChaosEvent(after_done=0, throttle_node=3,
                          throttle_seconds=0.4),
               ChaosEvent(after_done=10, kill_node=3)])
    out_c = mid.execute(plan_c)
    return {
        "case": "chaos_recovery", "n": n, "tile": tile,
        "relay_deaths": relay.stats["deaths"],
        "midcopy_deaths": mid.stats["deaths"],
        "leases_taken": mid.stats["leases"],
        "leases_released_on_death": mid.stats["leases_released_on_death"],
        "ok_relay_death_bitident": bool(np.array_equal(ref_r, out_r)),
        "ok_midcopy_death_bitident": bool(np.array_equal(ref_c, out_c)),
        "ok_leases_taken": bool(mid.stats["leases"] > 0),
        "ok_no_stale_leases": bool(relay.stats["stale_leases"] == 0
                                   and mid.stats["stale_leases"] == 0),
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small inputs (the CI net-smoke gate)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    if args.out is None:
        name = "BENCH_net_smoke.json" if args.smoke else "BENCH_net.json"
        args.out = os.path.join(os.path.dirname(__file__), "..", name)

    if args.smoke:
        cases = [run_bytes_on_wire(96, 16),
                 run_stream_gather(96, 16),
                 run_broadcast(96, 16),
                 run_chaos(96, 16)]
    else:
        cases = [run_bytes_on_wire(256, 32),
                 run_stream_gather(256, 32),
                 run_broadcast(192, 16),
                 run_chaos(128, 16)]

    ok = True
    for c in cases:
        checks = {k: v for k, v in c.items() if k.startswith("ok_")}
        ok &= all(checks.values())
        line = " ".join(f"{k}={v}" for k, v in checks.items())
        if c["case"] == "bytes_on_wire":
            print(f"[net] wire n={c['n']} raw={c['raw_wire_bytes']}B "
                  f"zlib={c['zlib_wire_bytes']}B "
                  f"({c['wire_reduction_x']:.2f}x) {line}")
        elif c["case"] == "stream_gather":
            print(f"[net] gather n={c['n']} "
                  f"ttft {c['ttft_streamed_s']:.4f}s vs "
                  f"{c['ttft_barrier_s']:.4f}s barrier "
                  f"({c['streamed_tiles']} streamed) {line}")
        elif c["case"] == "broadcast_vs_unicast":
            print(f"[net] bcast n={c['n']} "
                  f"tree={c['wall_tree_s']:.3f}s "
                  f"({c['relay_hops_tree']} hops) "
                  f"star={c['wall_star_s']:.3f}s {line}")
        else:
            print(f"[net] chaos n={c['n']} "
                  f"leases={c['leases_taken']} "
                  f"released_on_death={c['leases_released_on_death']} "
                  f"{line}")
        if not all(checks.values()):
            print(f"[net] CHECK FAILED: {c['case']}", file=sys.stderr)

    with open(args.out, "w") as f:
        json.dump({"cases": cases}, f, indent=2)
    print(f"[net] wrote {os.path.abspath(args.out)}")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
