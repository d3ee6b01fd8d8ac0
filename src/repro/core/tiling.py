"""Automatic tiling: expression DAG -> tiled task graph (CMM §3.2, Listing 1).

A single tile size ``t`` (or ``(tm, tn)`` tuple) is applied to every matrix in
the expression, exactly like the paper (10 k matrices, 5 k tiles -> 2x2 grid;
edge tiles are ragged via ``min`` bounds as in Listing 1).  The expression DAG
is expanded node-by-node into per-tile tasks while preserving the task
dependencies; tiled matmul introduces the ``calloc`` + ``addmul``-chain
structure of Fig. 2.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from .graph import (Task, TaskGraph, TaskKind, TileRef, matmul_epilogue,
                    matmul_flags)
from .lazy import ClusteredMatrix, Op, topo_order, topo_order_many


def cld(a: int, b: int) -> int:
    """Ceiling division (Julia's ``cld`` used in Listing 1)."""
    return -(-a // b)


def tile_slices(dim: int, tile: int) -> List[Tuple[int, int]]:
    """Listing 1 row/col bounds: [(start, end)] with ragged final tile."""
    n = cld(dim, tile)
    return [(tile * i, min(tile * (i + 1), dim)) for i in range(n)]


def grid_of(shape: Tuple[int, int], tile: Tuple[int, int]) -> Tuple[int, int]:
    return (cld(shape[0], tile[0]), cld(shape[1], tile[1]))


def tile_shape(shape: Tuple[int, int], tile: Tuple[int, int],
               i: int, j: int) -> Tuple[int, int]:
    rs = tile_slices(shape[0], tile[0])[i]
    cs = tile_slices(shape[1], tile[1])[j]
    return (rs[1] - rs[0], cs[1] - cs[0])


def normalize_tile(tile) -> Tuple[int, int]:
    if isinstance(tile, int):
        return (tile, tile)
    tm, tn = tile
    return (int(tm), int(tn))


@dataclass
class ResultSet:
    """One root's output tiles in the (possibly multi-root) tiled program.

    ``gather=True`` roots get TAKECOPY tasks and are assembled on the
    master; ``gather=False`` roots are session-persisted — their tiles
    stay in the executor arenas (``producers`` maps each tile to the task
    that writes its final value, whose placement is the tile's home)."""

    uid: int                              # root expr-node uid
    index: int                            # position in the roots list
    shape: Tuple[int, int]
    grid: Tuple[int, int]
    tiles: List[TileRef] = field(default_factory=list)
    producers: Dict[TileRef, int] = field(default_factory=dict)
    gather: bool = True


class TiledProgram:
    """Result of tiling: the task graph plus tile bookkeeping for execution."""

    def __init__(self, graph: TaskGraph, tile: Tuple[int, int],
                 root: ClusteredMatrix,
                 leaf_nodes: Dict[int, ClusteredMatrix],
                 dtypes: Optional[Dict[int, "object"]] = None,
                 roots: Optional[Sequence[ClusteredMatrix]] = None):
        self.graph = graph
        self.tile = tile
        self.root = root
        #: every root of the (multi-root) program, in caller order
        self.roots = list(roots) if roots is not None else [root]
        #: expr-node uid -> leaf ClusteredMatrix (for FILL materialisation)
        self.leaf_nodes = leaf_nodes
        #: expr-node uid -> np.dtype (CALLOC must allocate in the expression
        #: dtype, not float64)
        self.dtypes = dtypes or {}
        #: canonical leaf-uid order (plan-cache leaf rebinding contract)
        self.leaf_order = list(leaf_nodes)

    def rebound(self, new_leaves) -> "TiledProgram":
        """A shallow copy with FILL leaves rebound to ``new_leaves`` (same
        canonical order) — how a plan-cache hit serves a structurally equal
        DAG holding different data."""
        if len(new_leaves) != len(self.leaf_order):
            raise ValueError("leaf count mismatch on plan-cache rebind")
        leaf_nodes = dict(zip(self.leaf_order, new_leaves))
        p = TiledProgram(self.graph, self.tile, self.root, leaf_nodes,
                         self.dtypes, roots=self.roots)
        p.leaf_order = list(self.leaf_order)
        return p


def tile_expression(root: ClusteredMatrix, tile) -> TiledProgram:
    """Expand one expression DAG into a tiled TaskGraph (single-root
    wrapper over :func:`tile_expression_many`)."""
    return tile_expression_many((root,), tile)


def tile_expression_many(roots: Sequence[ClusteredMatrix], tile,
                         persist_idx: frozenset = frozenset()
                         ) -> TiledProgram:
    """Expand one or more expression DAGs into ONE tiled TaskGraph.

    Per node we keep ``producer[(i, j)]`` — the task id that last wrote tile
    ``(i, j)`` of that node's output — so consumers depend on exactly the
    right task (for matmul that is the *last* addmul of the k-chain).

    Roots whose *position* is in ``persist_idx`` are session-persisted:
    they get NO takecopy tasks — their tiles stay wherever their final
    producers ran (the ``ResultSet.producers`` map records which task that
    is per tile).  RESIDENT leaves expand to one zero-cost RESIDENT task
    per tile instead of FILLs: the tile is already bound in an executor
    arena and just re-enters this run's buffer namespace.
    """
    t = normalize_tile(tile)
    g = TaskGraph()
    # node uid -> {(i,j): (TileRef, producer_tid)}
    tiles: Dict[int, Dict[Tuple[int, int], Tuple[TileRef, int]]] = {}
    leaf_nodes: Dict[int, ClusteredMatrix] = {}
    dtypes: Dict[int, "object"] = {}

    def ref(node: ClusteredMatrix, i: int, j: int) -> TileRef:
        return TileRef(node.uid, i, j, tile_shape(node.shape, t, i, j))

    for node in topo_order_many(roots):
        gm, gn = grid_of(node.shape, t)
        entry: Dict[Tuple[int, int], Tuple[TileRef, int]] = {}
        dtypes[node.uid] = node.dtype

        if node.op is Op.RESIDENT:
            h = node.payload
            if h is None or tuple(h.tile) != t:
                raise ValueError(
                    f"resident leaf #{node.uid} holds tiles of size "
                    f"{None if h is None else h.tile}, but this program "
                    f"tiles at {t}; gather + re-ingest (the session does "
                    f"this automatically) or re-plan at the handle's tile")
            leaf_nodes[node.uid] = node
            for i in range(gm):
                for j in range(gn):
                    r = ref(node, i, j)
                    task = g.add(TaskKind.RESIDENT, (), r, payload=node.uid)
                    entry[(i, j)] = (r, task.tid)

        elif node.op in (Op.INPUT, Op.RANDOM, Op.ZEROS, Op.EYE):
            leaf_nodes[node.uid] = node
            for i in range(gm):
                for j in range(gn):
                    r = ref(node, i, j)
                    # fill = data materialisation for an input tile; the
                    # engine/scheduler delays it until just before first use
                    # (§3.3) — structurally it is a source task.
                    task = g.add(TaskKind.FILL, (), r, payload=node.uid)
                    entry[(i, j)] = (r, task.tid)

        elif node.op is Op.MATMUL:
            a, b = node.parents[:2]
            extras = node.parents[2:]      # epilogue operands
            epi = matmul_epilogue(node.payload)
            ga = tiles[a.uid]
            gb = tiles[b.uid]
            # transposed-operand flags folded in by the fusion optimizer:
            # operand tiles are indexed through the transpose instead of a
            # materialised TRANSPOSE pass (requires a square tile for ragged
            # grids to line up; the engine guarantees that)
            ta, tb = matmul_flags(node.payload)
            if (ta or tb) and t[0] != t[1]:
                raise ValueError("transposed matmul needs a square tile")
            # the inner dimension is tiled by tn on A but by tm on B; a
            # non-square tile misaligns the k-chains (silent wrong results)
            # unless the inner dim fits in a single tile both ways
            n_inner = a.shape[0] if ta else a.shape[1]
            if t[0] != t[1] and max(cld(n_inner, t[0]),
                                    cld(n_inner, t[1])) > 1:
                raise ValueError(
                    f"MATMUL inner dim {n_inner} needs a square tile, "
                    f"got {t}; use an int tile size")
            kt = grid_of(a.shape, t)[0 if ta else 1]  # inner tile count
            flags = (ta, tb) if ta or tb else None
            if epi is not None:
                # the k-chain accumulates in the *matmul* dtype; the
                # epilogue's own output dtype emerges when the last chain
                # task rebinds the tile (bit-identity with the unfused
                # CALLOC-in-matmul-dtype + separate-FUSED-task path)
                import numpy as _np
                dtypes[node.uid] = _np.promote_types(a.dtype, b.dtype)
            for i in range(gm):
                for j in range(gn):
                    r = ref(node, i, j)
                    calloc = g.add(TaskKind.CALLOC, (), r, payload=node.uid)
                    prev = calloc.tid
                    for k in range(kt):
                        ra, pa = ga[(k, i) if ta else (i, k)]
                        rb, pb = gb[(j, k) if tb else (k, j)]
                        m_ = ra.shape[1] if ta else ra.shape[0]
                        n_ = ra.shape[0] if ta else ra.shape[1]
                        k_ = rb.shape[0] if tb else rb.shape[1]
                        ins = (ra, rb)
                        deps = (prev, pa, pb)
                        payload = flags
                        flops = 2 * m_ * n_ * k_
                        if epi is not None and k == kt - 1:
                            # the LAST chain task applies the epilogue to
                            # the accumulated C tile in the same pass: its
                            # extra ins are the (i, j) tiles of the
                            # epilogue operands, its flops include the
                            # elementwise work (priced into ADDMUL)
                            from .fusion import fused_flops
                            eins = [tiles[e.uid][(i, j)] for e in extras]
                            ins += tuple(er for er, _ in eins)
                            deps += tuple(ep for _, ep in eins)
                            payload = node.payload
                            flops += fused_flops(epi, *r.shape)
                        task = g.add(TaskKind.ADDMUL, ins, r,
                                     payload=payload, flops=flops,
                                     deps=deps)
                        prev = task.tid
                    entry[(i, j)] = (r, prev)

        elif node.op in (Op.ADD, Op.SUB, Op.EWMUL):
            kind = {Op.ADD: TaskKind.ADD, Op.SUB: TaskKind.SUB,
                    Op.EWMUL: TaskKind.EWMUL}[node.op]
            a, b = node.parents
            for i in range(gm):
                for j in range(gn):
                    ra, pa = tiles[a.uid][(i, j)]
                    rb, pb = tiles[b.uid][(i, j)]
                    r = ref(node, i, j)
                    m_, n_ = r.shape
                    task = g.add(kind, (ra, rb), r, flops=m_ * n_,
                                 deps=(pa, pb))
                    entry[(i, j)] = (r, task.tid)

        elif node.op is Op.SCALE:
            (kindstr, s) = node.payload
            a = node.parents[0]
            for i in range(gm):
                for j in range(gn):
                    ra, pa = tiles[a.uid][(i, j)]
                    r = ref(node, i, j)
                    task = g.add(TaskKind.SCALE, (ra,), r,
                                 payload=(kindstr, s),
                                 flops=r.shape[0] * r.shape[1], deps=(pa,))
                    entry[(i, j)] = (r, task.tid)

        elif node.op is Op.EWISE:
            a = node.parents[0]
            for i in range(gm):
                for j in range(gn):
                    ra, pa = tiles[a.uid][(i, j)]
                    r = ref(node, i, j)
                    task = g.add(TaskKind.EWISE, (ra,), r, payload=node.payload,
                                 flops=4 * r.shape[0] * r.shape[1], deps=(pa,))
                    entry[(i, j)] = (r, task.tid)

        elif node.op is Op.FUSED:
            # one task per tile for the whole elementwise region: inputs are
            # the (i, j) tiles of every external parent
            from .fusion import fused_flops
            for i in range(gm):
                for j in range(gn):
                    ins, deps = [], []
                    for p in node.parents:
                        rp, pp = tiles[p.uid][(i, j)]
                        ins.append(rp)
                        deps.append(pp)
                    r = ref(node, i, j)
                    task = g.add(TaskKind.FUSED, ins, r, payload=node.payload,
                                 flops=fused_flops(node.payload, *r.shape),
                                 deps=deps)
                    entry[(i, j)] = (r, task.tid)

        elif node.op is Op.TRANSPOSE:
            # tile (i, j) of the transpose is the transpose of parent tile
            # (j, i) — which only lines up when the tile is square (the
            # single-tile-size design; ragged edges break otherwise)
            if t[0] != t[1]:
                raise ValueError(
                    f"TRANSPOSE needs a square tile, got {t}; "
                    f"use an int tile size")
            a = node.parents[0]
            for i in range(gm):
                for j in range(gn):
                    ra, pa = tiles[a.uid][(j, i)]
                    r = ref(node, i, j)
                    task = g.add(TaskKind.TRANSPOSE, (ra,), r,
                                 flops=r.shape[0] * r.shape[1], deps=(pa,))
                    entry[(i, j)] = (r, task.tid)

        else:  # pragma: no cover
            raise ValueError(node.op)

        tiles[node.uid] = entry

    # takecopy: gather every result tile of a non-persisted root to the
    # master node.  Each takecopy depends only on its own producer chain
    # (§3.3 optimisation: originally serialised behind *all* jobs; CMM made
    # it depend only on its subtree).  Persisted roots skip the gather —
    # their tiles are retained in place by the executor.
    g.result_sets = []
    for idx, root in enumerate(roots):
        gm, gn = grid_of(root.shape, t)
        rs = ResultSet(root.uid, idx, root.shape, (gm, gn),
                       gather=idx not in persist_idx)
        for i in range(gm):
            for j in range(gn):
                r, p = tiles[root.uid][(i, j)]
                rs.tiles.append(r)
                rs.producers[r] = p
                if rs.gather:
                    g.add(TaskKind.TAKECOPY, (r,), r, deps=(p,))
        g.result_sets.append(rs)
    # backward-compatible single-root view: the first gathered root
    first = next((rs for rs in g.result_sets if rs.gather),
                 g.result_sets[0] if g.result_sets else None)
    if first is not None:
        g.result_tiles = list(first.tiles)
        g.result_grid = first.grid
        g.result_shape = first.shape
    return TiledProgram(g, t, roots[0], leaf_nodes, dtypes, roots=roots)


def result_sets_of(g) -> List[ResultSet]:
    """The graph's per-root output sets, synthesizing the legacy single
    ``result_tiles`` view for hand-built graphs (tests, benchmarks)."""
    rs = getattr(g, "result_sets", None)
    if rs:
        return rs
    tiles = list(g.result_tiles)
    uid = tiles[0].tensor if tiles else -1
    return [ResultSet(uid, 0, g.result_shape, g.result_grid, tiles,
                      {}, True)]


def to_host(t):
    """A tile as a host ndarray: ``t`` itself where it is one, else a copy
    pulled from the device (a ``jax.Array`` that a device session keeps)."""
    import numpy as np

    return t if isinstance(t, np.ndarray) else np.asarray(t)


def assemble(tile_values: Dict[TileRef, "object"],
             shape: Tuple[int, int], tile: Tuple[int, int],
             tensor_uid: int):
    """Reassemble a full matrix from its tile values (inverse of tiling)."""
    import numpy as np

    rows = tile_slices(shape[0], tile[0])
    cols = tile_slices(shape[1], tile[1])
    first = next(iter(tile_values.values()))
    out = np.empty(shape, dtype=np.asarray(first).dtype)
    for i, (r0, r1) in enumerate(rows):
        for j, (c0, c1) in enumerate(cols):
            key = TileRef(tensor_uid, i, j, (r1 - r0, c1 - c0))
            out[r0:r1, c0:c1] = np.asarray(tile_values[key])
    return out
