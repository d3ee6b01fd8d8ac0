"""Wire codecs + per-edge transfer pricing for the XFER path.

Every byte that crosses a node boundary flows through one priced,
instrumented path (ROADMAP item 5).  Three pieces live here:

* **Lossless wire codecs** — byte-level compression applied per-XFER.
  The repo's bitwise-identity policy is absolute on the tile path, so
  only *lossless* codecs are admissible here (``zlib`` from the stdlib;
  the lossy int8 quantizer in ``optim/compress.py`` stays
  optimizer-only and never touches tile bytes).  ``decode_tile(
  encode_tile(a)) == a`` bit-for-bit, always.

* **Per-edge pricing** — a codec is worth using on edge ``(src, dst)``
  exactly when the TimeModel predicts

      compress_cpu + compressed_bytes/bw  <  raw_bytes/bw

  with ``compress_cpu = nbytes / tm.compress_bandwidth`` and
  ``compressed_bytes = nbytes / tm.compression_ratio_prior``.  Both
  terms are fitted by the profiler (``calibrate_compression``) and
  serialized in ``TimeModel.to_json()`` so plan caches invalidate on
  recalibration.  With the default priors (``compress_bandwidth == 0``)
  the codec is disabled and every decision degrades to ``"raw"``.

* **Broadcast relays** — one-producer-many-consumer edges (common after
  ``persist()``) of at least ``BCAST_MIN_FANOUT`` destinations are
  served by relays instead of N unicasts: the cluster executor lets each
  holder serve at most ``BCAST_MIN_FANOUT - 1`` readers at a time, so
  landed copies serve the rest, and the simulator prices that as a
  binary relay tree.
"""
from __future__ import annotations

import zlib
from typing import Dict, Tuple

import numpy as np

__all__ = [
    "WireCodec", "RawCodec", "ZlibCodec", "CODECS", "get_codec",
    "encode_tile", "decode_tile", "choose_wire_codec", "wire_seconds",
    "predicted_xfer_seconds", "BCAST_MIN_FANOUT",
]

#: minimum cross-node destination count before a relay tree beats
#: N unicasts (at 2 destinations the tree *is* two unicasts).
BCAST_MIN_FANOUT = 3


class WireCodec:
    """Lossless byte codec interface for the tile wire path.

    ``decode(encode(b)) == b`` must hold bit-for-bit for arbitrary
    ``bytes`` — codecs that cannot guarantee that (lossy quantizers,
    float truncation) are not admissible here.
    """

    name: str = "?"

    def encode(self, raw: bytes) -> bytes:
        raise NotImplementedError

    def decode(self, payload: bytes) -> bytes:
        raise NotImplementedError


class RawCodec(WireCodec):
    """Identity codec: the uncompressed point-to-point path."""

    name = "raw"

    def encode(self, raw: bytes) -> bytes:
        return bytes(raw)

    def decode(self, payload: bytes) -> bytes:
        return bytes(payload)


class ZlibCodec(WireCodec):
    """stdlib zlib at level 1 — the speed-over-ratio end of DEFLATE,
    the right trade for a 10 Gbps-class link (lz4 is not vendored; the
    interface is the point, the codec is a plug)."""

    name = "zlib"
    level = 1

    def encode(self, raw: bytes) -> bytes:
        return zlib.compress(raw, self.level)

    def decode(self, payload: bytes) -> bytes:
        return zlib.decompress(payload)


#: codec registry — one place a wire codec is named; executors, the
#: profiler and the benchmarks resolve codec strings through here.
CODECS: Dict[str, WireCodec] = {
    RawCodec.name: RawCodec(),
    ZlibCodec.name: ZlibCodec(),
}


def get_codec(name: str) -> WireCodec:
    try:
        return CODECS[name]
    except KeyError:
        raise ValueError(
            f"unknown wire codec {name!r}; known: {sorted(CODECS)}"
        ) from None


def encode_tile(arr: np.ndarray, codec: str) -> bytes:
    """Encode a tile's raw bytes for the wire.  Lossless by contract."""
    a = np.ascontiguousarray(arr)
    return get_codec(codec).encode(a.tobytes())


def decode_tile(payload: bytes, shape: Tuple[int, int], dtype,
                codec: str) -> np.ndarray:
    """Decode a wire payload back to the exact tile that was encoded."""
    raw = get_codec(codec).decode(payload)
    return np.frombuffer(raw, dtype=np.dtype(dtype)).reshape(shape)


def choose_wire_codec(nbytes: int, bw: float, tm) -> str:
    """Pick the codec for one edge by the TimeModel's pricing rule.

    Returns ``"zlib"`` when ``compress_cpu + compressed_bytes/bw <
    raw_bytes/bw`` under the fitted priors, else ``"raw"``.  With
    unfitted priors (``compress_bandwidth <= 0`` or ratio <= 1) the
    codec can never win and the choice is always ``"raw"`` — existing
    plans and transfers are byte-for-byte unchanged by default.
    """
    if nbytes <= 0 or bw <= 0:
        return "raw"
    cbw = getattr(tm, "compress_bandwidth", 0.0)
    ratio = getattr(tm, "compression_ratio_prior", 1.0)
    if cbw <= 0.0 or ratio <= 1.0:
        return "raw"
    raw_s = nbytes / bw
    comp_s = nbytes / cbw + (nbytes / ratio) / bw
    return "zlib" if comp_s < raw_s else "raw"


def wire_seconds(nbytes: int, src: int, dst: int, spec, tm) -> float:
    """Codec-aware seconds for ``nbytes`` over edge ``(src, dst)``.

    The single pricing helper shared by HEFT (``heft_schedule`` *and*
    ``replan_frontier`` — the two EFT policies must stay mirrored), the
    discrete-event simulator and ``predict_cluster_makespan``, so
    ``auto`` executor selection prices exactly the transfer path the
    executors run.  Identical to ``spec.comm_time`` when the codec
    priors are unfitted.
    """
    base = spec.comm_time(nbytes, src, dst)
    if src == dst or nbytes <= 0 or tm is None:
        return base
    cbw = getattr(tm, "compress_bandwidth", 0.0)
    ratio = getattr(tm, "compression_ratio_prior", 1.0)
    if cbw <= 0.0 or ratio <= 1.0:
        return base
    comp = nbytes / cbw + spec.comm_time(int(nbytes / ratio), src, dst)
    return min(base, comp)


def predicted_xfer_seconds(nbytes: int, tm, codec: str = "raw",
                           comp_nbytes: int = 0) -> float:
    """Model-predicted wall seconds for one *materialized* XFER leg.

    Unlike :func:`wire_seconds` — which prices the codec *choice*
    against the planning-level link model — this prices what the
    destination worker actually does: a shared-memory attach + copy
    (``ipc_latency + bytes / ipc_bandwidth``, the terms
    ``profiler.calibrate_ipc`` fits), plus a decode pass priced at the
    codec throughput prior when the payload came compressed.  The
    drift report compares measured XFER spans against this, so a raw
    leg evidences ``ipc_bandwidth`` and a compressed one
    ``compress_bandwidth``.
    """
    if nbytes <= 0 or tm is None:
        return 0.0
    lat = getattr(tm, "ipc_latency", 0.0)
    bw = getattr(tm, "ipc_bandwidth", 0.0)
    if codec == "raw":
        return lat + (nbytes / bw if bw > 0 else 0.0)
    cbw = getattr(tm, "compress_bandwidth", 0.0)
    payload = comp_nbytes or nbytes
    t = lat + (payload / bw if bw > 0 else 0.0)
    if cbw > 0:
        t += nbytes / cbw
    return t
