"""Expression-graph optimizer (CMM §3.1: "optimize matrix operations on the
fly" before tiling and scheduling).

The engine runs these rewrite passes over the lazy expression DAG *before*
``tile_expression``, so tiling / HEFT / simulation all see the reduced graph:

* **identity folding** — ``A + zeros``, ``A - zeros``, ``A @ eye``,
  ``eye @ A``, ``A * 1.0``, ``A / 1.0``, ``(A.T).T`` collapse to ``A``
  (only when the fold preserves the result dtype);
* **transpose folding** — a ``TRANSPOSE`` operand of a ``MATMUL`` becomes a
  transposed-operand flag ``(ta, tb)`` on the MATMUL node, so no transposed
  intermediate is ever materialised (BLAS consumes the transposed view
  directly);
* **CSE** — structurally identical subexpressions (same op, canonicalised
  parents and value-relevant payload) are merged, so a shared subexpression
  is computed once;
* **matrix-chain order** — each chain of MATMULs whose interior products
  have one consumer is re-associated for the fewest flops (the classic
  O(k³) dynamic programme over the operand shapes), so the paper's Markov
  ``(P @ P @ P) @ u`` runs as three mat-vecs ``P @ (P @ (P @ u))``.  A
  written order that is already among the cheapest is kept as it is;
* **elementwise fusion** — maximal connected regions of
  EWISE/SCALE/ADD/SUB/EWMUL nodes whose interior nodes have a single
  consumer collapse into one FUSED node.  A FUSED node executes as *one*
  task per tile, eliminating every interior tile buffer of the chain.
  Multi-consumer nodes are never inlined (their value is needed elsewhere);
  they can still root their own region.
* **matmul-epilogue fusion** — an elementwise node or FUSED region whose
  only use of a single-consumer MATMUL is as a same-shaped operand is
  folded INTO that matmul as an **epilogue program** on its payload
  (``graph.epilogue_payload``).  The hot shape ``relu(A@B + C)`` then
  executes as the addmul k-chain alone: the last chain task applies the
  epilogue to the accumulated ``C`` tile in one pass — no FUSED task, no
  materialised matmul intermediate.  The epilogue reuses the FUSED
  tile-program encoding with input slot 0 = the accumulator and slots
  ``1..`` = the extra operands appended to the MATMUL's parents.

The FUSED payload is a small hashable tile program — a tuple of
instructions in topological order::

    ("in", k)                   # tile of the k-th parent
    ("ewise", fn, i)            # EWISE_FNS[fn](vals[i])
    ("scale", kind, s, i)       # apply_scale(kind, vals[i], s)
    ("add"|"sub"|"ewmul", i, j) # binary elementwise

The last instruction is the output.  ``eval_fused`` interprets it over full
tiles, reusing dead interior buffers in place (``out=``) so a fused chain of
N ops allocates O(1) scratch instead of N intermediates.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

from ..runtime.telemetry import NULL_TRACER, Tracer
from .graph import epilogue_payload, matmul_epilogue, matmul_flags
from .lazy import (ClusteredMatrix, EWISE_FNS, Op, apply_scale, topo_order,
                   topo_order_many)

#: expression ops that are elementwise over same-shaped operands
ELEMENTWISE_OPS = {Op.ADD, Op.SUB, Op.EWMUL, Op.SCALE, Op.EWISE}

LEAF_OPS = {Op.INPUT, Op.RANDOM, Op.ZEROS, Op.EYE, Op.RESIDENT}


@dataclass
class FusionReport:
    """What the optimizer did — surfaced on the Plan for benchmarks/tests."""

    nodes_before: int = 0
    nodes_after: int = 0
    cse_merged: int = 0
    identities_folded: int = 0
    transposes_folded: int = 0
    fused_regions: int = 0
    fused_ops: int = 0          # elementwise nodes swallowed by FUSED regions
    epilogues_fused: int = 0    # FUSED/elementwise nodes folded into a MATMUL
    epilogue_ops: int = 0       # arithmetic instrs now running as epilogues
    chains_reordered: int = 0   # matmul chains re-associated for fewer flops
    matmul_flops_written: int = 0   # 2·m·k·n over the MATMULs as written
    matmul_flops_planned: int = 0   # ... and as planned

    def as_dict(self) -> Dict[str, int]:
        return dict(self.__dict__)


# ---------------------------------------------------------------------------
# pass 1: identity + transpose folding (single bottom-up rebuild)
# ---------------------------------------------------------------------------

def _is_zeros(n: ClusteredMatrix) -> bool:
    return n.op is Op.ZEROS


def _is_eye(n: ClusteredMatrix) -> bool:
    return n.op is Op.EYE


def fold_identities(root: ClusteredMatrix, report: FusionReport,
                    fold_transpose: bool = True) -> ClusteredMatrix:
    """Algebraic identity folding + transpose-into-matmul flag folding."""
    return fold_identities_many((root,), report,
                                fold_transpose=fold_transpose)[0]


def fold_identities_many(roots: Sequence[ClusteredMatrix],
                         report: FusionReport,
                         fold_transpose: bool = True
                         ) -> List[ClusteredMatrix]:
    """Multi-root twin of :func:`fold_identities` (shared subexpressions
    are rewritten once)."""
    new: Dict[int, ClusteredMatrix] = {}

    def rewritten(node: ClusteredMatrix) -> ClusteredMatrix:
        return new[node.uid]

    for node in topo_order_many(roots):
        parents = tuple(rewritten(p) for p in node.parents)
        out: Optional[ClusteredMatrix] = None

        if node.op is Op.ADD:
            a, b = parents
            if _is_zeros(b) and a.dtype == node.dtype:
                out = a
            elif _is_zeros(a) and b.dtype == node.dtype:
                out = b
        elif node.op is Op.SUB:
            a, b = parents
            if _is_zeros(b) and a.dtype == node.dtype:
                out = a
        elif node.op is Op.SCALE:
            kind, s = node.payload
            a = parents[0]
            if a.dtype == node.dtype and (
                    (kind in ("scale", "mul", "ewmul", "div") and s == 1.0)
                    or (kind in ("add", "sub") and s == 0.0)):
                out = a
        elif node.op is Op.TRANSPOSE:
            a = parents[0]
            if a.op is Op.TRANSPOSE:          # (A.T).T -> A
                out = a.parents[0]
        elif node.op is Op.MATMUL:
            a, b = parents[:2]
            extras = parents[2:]           # epilogue operands (re-optimize)
            epi = matmul_epilogue(node.payload)
            if epi is None and not extras and _is_eye(b) \
                    and a.dtype == node.dtype:
                out = a
            elif epi is None and not extras and _is_eye(a) \
                    and b.dtype == node.dtype:
                out = b
            else:
                flags0 = matmul_flags(node.payload)
                ta, tb = flags0
                while fold_transpose and a.op is Op.TRANSPOSE:
                    a, ta = a.parents[0], not ta
                    report.transposes_folded += 1
                while fold_transpose and b.op is Op.TRANSPOSE:
                    b, tb = b.parents[0], not tb
                    report.transposes_folded += 1
                if (a, b) != parents[:2] or (ta, tb) != flags0:
                    if epi is not None:
                        payload = epilogue_payload((ta, tb), epi)
                    else:
                        payload = (ta, tb) if ta or tb else None
                    out = ClusteredMatrix(Op.MATMUL, node.shape, node.dtype,
                                          parents=(a, b) + extras,
                                          payload=payload,
                                          name=node.name)

        if out is not None and out.op is not Op.MATMUL:
            report.identities_folded += 1
        if out is None:
            out = node if parents == node.parents else \
                ClusteredMatrix(node.op, node.shape, node.dtype,
                                parents=parents, payload=node.payload,
                                name=node.name)
        new[node.uid] = out
    return [new[r.uid] for r in roots]


# ---------------------------------------------------------------------------
# pass 2: common-subexpression elimination
# ---------------------------------------------------------------------------

def _value_payload_key(node: ClusteredMatrix):
    """Payload component of the CSE key — must distinguish different VALUES.

    INPUT data is keyed by array object identity; RANDOM by its seed.
    """
    if node.op is Op.INPUT:
        return ("input", id(node.payload))
    if node.op is Op.RANDOM:
        return ("seed", node.payload)
    if node.op is Op.RESIDENT:
        # a resident leaf's value is its handle: two uses of one handle
        # are the same tiles, two handles are distinct values
        return ("resident", node.payload.hid)
    if node.op is Op.FUSED:
        return node.payload
    if isinstance(node.payload, (str, int, float, tuple, type(None))):
        return node.payload
    return id(node.payload)


def cse(root: ClusteredMatrix, report: FusionReport) -> ClusteredMatrix:
    """Merge structurally identical subexpressions (structural hashing of
    ``(op, parents, payload)``)."""
    return cse_many((root,), report)[0]


def cse_many(roots: Sequence[ClusteredMatrix],
             report: FusionReport) -> List[ClusteredMatrix]:
    """CSE over the union DAG of several roots — the shared-CSE half of
    ``compute_many``: a subexpression common to two roots is computed
    once in the merged program."""
    canon: Dict[tuple, ClusteredMatrix] = {}
    new: Dict[int, ClusteredMatrix] = {}

    for node in topo_order_many(roots):
        parents = tuple(new[p.uid] for p in node.parents)
        key = (node.op, node.shape, str(node.dtype),
               _value_payload_key(node), tuple(p.uid for p in parents))
        hit = canon.get(key)
        if hit is not None:
            report.cse_merged += 1
            new[node.uid] = hit
            continue
        out = node if parents == node.parents else \
            ClusteredMatrix(node.op, node.shape, node.dtype, parents=parents,
                            payload=node.payload, name=node.name)
        canon[key] = out
        new[node.uid] = out
    return [new[r.uid] for r in roots]


# ---------------------------------------------------------------------------
# pass 3: matrix-chain order
# ---------------------------------------------------------------------------

def _chain_link(node: ClusteredMatrix) -> bool:
    """A MATMUL with no epilogue (its value is the bare product) whose
    operands have its dtype: a link of a matmul chain."""
    return node.op is Op.MATMUL and len(node.parents) == 2 \
        and matmul_epilogue(node.payload) is None \
        and all(p.dtype == node.dtype for p in node.parents)


def _matmul_flops(node: ClusteredMatrix) -> int:
    """2·m·k·n of one MATMUL, k read through its transposed-operand flag."""
    a = node.parents[0]
    k = a.shape[0] if matmul_flags(node.payload)[0] else a.shape[1]
    return 2 * node.shape[0] * k * node.shape[1]


def order_matmul_chains_many(roots: Sequence[ClusteredMatrix],
                             report: FusionReport,
                             tracer: Tracer = NULL_TRACER
                             ) -> List[ClusteredMatrix]:
    """Re-associate every matmul chain for the fewest flops.

    A chain is a maximal tree of plain MATMULs whose operands all have
    the product's dtype, in which every interior product has exactly one
    use, as an untransposed operand, and is no root.  A shared, transposed
    or root product, and a product of mixed dtypes, bounds the chain as
    one of its operands and is never duplicated.  So every chain has one
    dtype, and no order makes a product round narrower than the written
    one did.  Operands keep their transposed-operand flags.  The order is
    the one of least ``2·m·k·n`` summed, and the written split of a range
    wins a tie, so a written order among the cheapest (every chain of
    square operands) comes back as the very same nodes.  Each chain of
    three or more operands is ordered under a ``CHAIN`` span on
    ``tracer``.
    """
    order = topo_order_many(roots)
    root_uids = {r.uid for r in roots}
    uses: Dict[int, int] = {}
    linked: Set[int] = set()
    for node in order:
        if node.op is Op.MATMUL:
            report.matmul_flops_written += _matmul_flops(node)
        link = _chain_link(node)
        flags = matmul_flags(node.payload)
        for slot, p in enumerate(node.parents):
            uses[p.uid] = uses.get(p.uid, 0) + 1
            if link and not flags[slot]:
                linked.add(p.uid)
    interior = {n.uid for n in order
                if n.uid in linked and uses[n.uid] == 1
                and n.uid not in root_uids and _chain_link(n)}
    if not interior:
        return list(roots)

    new: Dict[int, ClusteredMatrix] = {}
    for node in order:
        if node.uid in interior:
            continue                    # rebuilt by its chain's top
        if any(p.uid in interior for p in node.parents):
            with tracer.span("CHAIN"):
                new[node.uid] = _order_chain(node, interior, new, report)
            continue
        parents = tuple(new[p.uid] for p in node.parents)
        new[node.uid] = node if parents == node.parents else \
            ClusteredMatrix(node.op, node.shape, node.dtype, parents=parents,
                            payload=node.payload, name=node.name)
    return [new[r.uid] for r in roots]


def _order_chain(top: ClusteredMatrix, interior: Set[int],
                 new: Dict[int, ClusteredMatrix],
                 report: FusionReport) -> ClusteredMatrix:
    """Rebuild the chain under ``top`` in its cheapest association."""
    leaves: List[Tuple[ClusteredMatrix, bool]] = []   # (operand, transposed)
    # written products by the leaf range they cover -> (node, split)
    written: Dict[Tuple[int, int], Tuple[ClusteredMatrix, int]] = {}

    def operand(p: ClusteredMatrix, t: bool) -> None:
        if p.uid in interior:           # used untransposed, so t is False
            walk(p)
        else:
            leaves.append((new[p.uid], t))

    def walk(node: ClusteredMatrix) -> None:
        first = len(leaves)
        (a, b), (ta, tb) = node.parents, matmul_flags(node.payload)
        operand(a, ta)
        split = len(leaves) - 1
        operand(b, tb)
        written[(first, len(leaves) - 1)] = (node, split)

    walk(top)
    k = len(leaves)
    dims = [(a.shape[1] if t else a.shape[0]) for a, t in leaves]
    a, t = leaves[-1]
    dims.append(a.shape[0] if t else a.shape[1])

    cost = [[0] * k for _ in range(k)]
    best = [[0] * k for _ in range(k)]
    for length in range(2, k + 1):
        for i in range(k - length + 1):
            j = i + length - 1
            w = written.get((i, j))
            splits = ([w[1]] if w is not None else []) + list(range(i, j))
            # min() keeps the first of equals: the written split
            cost[i][j], best[i][j] = min(
                ((cost[i][s] + cost[s + 1][j]
                  + 2 * dims[i] * dims[s + 1] * dims[j + 1], s)
                 for s in splits), key=lambda cs: cs[0])

    written_cost = sum(2 * dims[i] * dims[s + 1] * dims[j + 1]
                       for (i, j), (_, s) in written.items())
    if cost[0][k - 1] < written_cost:
        report.chains_reordered += 1

    def build(i: int, j: int) -> Tuple[ClusteredMatrix, bool]:
        if i == j:
            return leaves[i]
        s = best[i][j]
        a, ta = build(i, s)
        b, tb = build(s + 1, j)
        w = written.get((i, j))
        if w is not None and w[1] == s:
            node = w[0]
            if node.parents == (a, b) and \
                    matmul_flags(node.payload) == (ta, tb):
                return node, False
        name = w[0].name if w is not None else ""
        shape = (a.shape[1] if ta else a.shape[0],
                 b.shape[0] if tb else b.shape[1])
        return ClusteredMatrix(Op.MATMUL, shape, top.dtype,
                               parents=(a, b),
                               payload=(ta, tb) if ta or tb else None,
                               name=name), False

    return build(0, k - 1)[0]


# ---------------------------------------------------------------------------
# pass 4: elementwise-chain fusion
# ---------------------------------------------------------------------------

def _consumers(roots: Sequence[ClusteredMatrix]) -> Dict[int, Set[int]]:
    cons: Dict[int, Set[int]] = {r.uid: set() for r in roots}
    for node in topo_order_many(roots):
        cons.setdefault(node.uid, set())
        for p in node.parents:
            cons.setdefault(p.uid, set()).add(node.uid)
    return cons


def fuse_elementwise(root: ClusteredMatrix,
                     report: FusionReport) -> ClusteredMatrix:
    """Collapse single-consumer elementwise chains into FUSED nodes."""
    return fuse_elementwise_many((root,), report)[0]


def fuse_elementwise_many(roots: Sequence[ClusteredMatrix],
                          report: FusionReport) -> List[ClusteredMatrix]:
    """Multi-root elementwise fusion.  A root's value is an OUTPUT of the
    merged program, so a root node is never inlined into a consumer's
    region (it may still root its own region and swallow its upstream
    chain)."""
    order = topo_order_many(roots)
    by_uid = {n.uid: n for n in order}
    cons = _consumers(roots)
    root_uids = {r.uid for r in roots}

    # region_of[uid] = uid of the region root this node is inlined into
    region_of: Dict[int, int] = {}
    for node in reversed(order):            # root first
        if node.op not in ELEMENTWISE_OPS:
            continue
        cs = cons[node.uid]
        if len(cs) == 1 and node.uid not in root_uids:
            (c,) = cs
            if by_uid[c].op in ELEMENTWISE_OPS:
                # inline into the consumer's region
                region_of[node.uid] = region_of.get(c, c)
                continue
        region_of[node.uid] = node.uid      # roots its own region

    members: Dict[int, List[ClusteredMatrix]] = {}
    for node in order:                      # topological member order
        r = region_of.get(node.uid)
        if r is not None:
            members.setdefault(r, []).append(node)

    new: Dict[int, ClusteredMatrix] = {}
    for node in order:
        r = region_of.get(node.uid)
        if r is not None and r != node.uid:
            continue                        # interior node: no standalone copy
        if r is None or len(members[r]) == 1:
            parents = tuple(new[p.uid] for p in node.parents)
            new[node.uid] = node if parents == node.parents else \
                ClusteredMatrix(node.op, node.shape, node.dtype,
                                parents=parents, payload=node.payload,
                                name=node.name)
            continue

        # build the FUSED node for this region
        region = members[r]
        region_uids = {m.uid for m in region}
        externals: List[ClusteredMatrix] = []
        ext_slot: Dict[int, int] = {}       # resolved-external uid -> slot
        instrs: List[tuple] = []
        instr_of: Dict[int, int] = {}       # member/external uid -> instr idx

        def operand(p: ClusteredMatrix) -> int:
            if p.uid in region_uids:
                return instr_of[p.uid]
            q = new[p.uid]
            if q.uid not in ext_slot:
                ext_slot[q.uid] = len(externals)
                externals.append(q)
                instrs.append(("in", ext_slot[q.uid]))
                instr_of[q.uid] = len(instrs) - 1
            return instr_of[q.uid]

        for m in region:
            if m.op is Op.EWISE:
                ins = ("ewise", m.payload, operand(m.parents[0]))
            elif m.op is Op.SCALE:
                kind, s = m.payload
                ins = ("scale", kind, s, operand(m.parents[0]))
            else:
                opname = {Op.ADD: "add", Op.SUB: "sub",
                          Op.EWMUL: "ewmul"}[m.op]
                ins = (opname, operand(m.parents[0]), operand(m.parents[1]))
            instrs.append(ins)
            instr_of[m.uid] = len(instrs) - 1

        fused = ClusteredMatrix(Op.FUSED, node.shape, node.dtype,
                                parents=tuple(externals),
                                payload=tuple(instrs), name=node.name)
        report.fused_regions += 1
        report.fused_ops += len(region)
        new[node.uid] = fused

    return [new[r.uid] for r in roots]


# ---------------------------------------------------------------------------
# pass 5: matmul-epilogue fusion
# ---------------------------------------------------------------------------

def _as_epilogue_prog(node: ClusteredMatrix,
                      slot_of: Dict[int, int]) -> tuple:
    """Rewrite ``node`` (a FUSED region or a single elementwise op) as an
    epilogue program whose ``("in", k)`` slots follow ``slot_of`` —
    parent uid -> epilogue input slot (0 = the matmul accumulator)."""
    if node.op is Op.FUSED:
        out = []
        for ins in node.payload:
            if ins[0] == "in":
                out.append(("in", slot_of[node.parents[ins[1]].uid]))
            else:
                out.append(ins)
        return tuple(out)
    # single elementwise node: synthesize the minimal program
    slots = [slot_of[p.uid] for p in node.parents]
    instrs: List[tuple] = []
    idx_of: Dict[int, int] = {}          # input slot -> instruction index
    for s in slots:
        if s not in idx_of:
            instrs.append(("in", s))
            idx_of[s] = len(instrs) - 1
    ops = [idx_of[s] for s in slots]
    if node.op is Op.EWISE:
        instrs.append(("ewise", node.payload, ops[0]))
    elif node.op is Op.SCALE:
        kind, s = node.payload
        instrs.append(("scale", kind, s, ops[0]))
    else:
        opname = {Op.ADD: "add", Op.SUB: "sub", Op.EWMUL: "ewmul"}[node.op]
        instrs.append((opname, ops[0], ops[1]))
    return tuple(instrs)


def fuse_matmul_epilogues(root: ClusteredMatrix,
                          report: FusionReport) -> ClusteredMatrix:
    """Single-root wrapper over :func:`fuse_matmul_epilogues_many`."""
    return fuse_matmul_epilogues_many((root,), report)[0]


def fuse_matmul_epilogues_many(roots: Sequence[ClusteredMatrix],
                               report: FusionReport
                               ) -> List[ClusteredMatrix]:
    """Fold elementwise consumers of single-consumer MATMULs into the
    matmul as an epilogue program (runs after elementwise fusion, so a
    whole chain like ``relu(A@B + C)`` arrives as ONE FUSED node).

    Candidate anchor: a MATMUL parent of an elementwise/FUSED node that
    (a) has no epilogue yet, (b) is consumed ONLY by this node, (c) is not
    itself a program root, and (d) has the consumer's shape (elementwise
    ops preserve shape, so this always holds for direct operands).  The
    consumer is rewritten into the matmul: parents become
    ``(A, B, *other_operands)`` and the payload carries the epilogue
    program with slot 0 bound to the accumulated ``C`` tile.  Only ONE
    matmul is absorbed per region — other matmul operands stay
    materialised inputs (epilogue extras)."""
    order = topo_order_many(roots)
    cons = _consumers(roots)
    root_uids = {r.uid for r in roots}
    new: Dict[int, ClusteredMatrix] = {}

    for node in order:
        parents = tuple(new[p.uid] for p in node.parents)
        out: Optional[ClusteredMatrix] = None

        mi = None
        if node.op is Op.FUSED or node.op in ELEMENTWISE_OPS:
            for i, (po, pn) in enumerate(zip(node.parents, parents)):
                if (pn.op is Op.MATMUL
                        and matmul_epilogue(pn.payload) is None
                        and po.uid not in root_uids
                        and cons.get(po.uid) == {node.uid}
                        and pn.shape == node.shape):
                    mi = i
                    break
        if mi is not None:
            anchor = parents[mi]
            # epilogue input slots: 0 = accumulator; 1.. = the region's
            # other external operands, in first-use order.  Keyed by the
            # PRE-pass parent uid so a CSE-duplicated anchor operand
            # (e.g. ``M + M``) maps every occurrence to slot 0.
            extras: List[ClusteredMatrix] = []
            slot_of: Dict[int, int] = {node.parents[mi].uid: 0}
            for po, pn in zip(node.parents, parents):
                if po.uid not in slot_of:
                    slot_of[po.uid] = 1 + len(extras)
                    extras.append(pn)
            prog = _as_epilogue_prog(node, slot_of)
            out = ClusteredMatrix(
                Op.MATMUL, node.shape, node.dtype,
                parents=tuple(anchor.parents) + tuple(extras),
                payload=epilogue_payload(matmul_flags(anchor.payload), prog),
                name=node.name)
            report.epilogues_fused += 1
            report.epilogue_ops += fused_op_count(prog)

        if out is None:
            out = node if parents == node.parents else \
                ClusteredMatrix(node.op, node.shape, node.dtype,
                                parents=parents, payload=node.payload,
                                name=node.name)
        new[node.uid] = out

    return [new[r.uid] for r in roots]


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def optimize(root: ClusteredMatrix, fold_transpose: bool = True,
             fuse: bool = True, fuse_epilogue: bool = True
             ) -> Tuple[ClusteredMatrix, FusionReport]:
    """Run all rewrite passes; returns (optimized root, report).

    ``fold_transpose=False`` keeps explicit TRANSPOSE nodes (needed when the
    tile is non-square, where transposed tile indexing is ill-defined on
    ragged grids).  ``fuse_epilogue=False`` keeps elementwise consumers of
    matmuls as standalone FUSED tasks (the unfused oracle baseline).
    """
    roots, report = optimize_many((root,), fold_transpose=fold_transpose,
                                  fuse=fuse, fuse_epilogue=fuse_epilogue)
    return roots[0], report


def optimize_many(roots: Sequence[ClusteredMatrix],
                  fold_transpose: bool = True, fuse: bool = True,
                  fuse_epilogue: bool = True, tracer: Tracer = NULL_TRACER
                  ) -> Tuple[List[ClusteredMatrix], FusionReport]:
    """Optimize several roots as ONE program: every pass (identity folds,
    CSE, matrix-chain order, elementwise fusion, matmul-epilogue fusion)
    runs over the union DAG, so subexpressions shared *across* roots are
    merged — the ``compute_many`` shared-CSE contract.  Chain order runs
    after CSE, so that a product used twice stays one, and CSE runs again
    after a re-association, so that a product it exposes is shared; each
    chain it orders is a ``CHAIN`` span on ``tracer``."""
    report = FusionReport(nodes_before=len(topo_order_many(roots)))
    roots = fold_identities_many(roots, report,
                                 fold_transpose=fold_transpose)
    roots = cse_many(roots, report)
    roots = order_matmul_chains_many(roots, report, tracer)
    if report.chains_reordered:
        roots = cse_many(roots, report)
        report.matmul_flops_planned = sum(
            _matmul_flops(n) for n in topo_order_many(roots)
            if n.op is Op.MATMUL)
    else:
        report.matmul_flops_planned = report.matmul_flops_written
    if fuse:
        roots = fuse_elementwise_many(roots, report)
        if fuse_epilogue:
            roots = fuse_matmul_epilogues_many(roots, report)
    report.nodes_after = len(topo_order_many(roots))
    return list(roots), report


# ---------------------------------------------------------------------------
# FUSED program interpreter (shared by executor + eager oracle)
# ---------------------------------------------------------------------------

_UNARY_OUT = {
    "sin": np.sin, "cos": np.cos, "exp": np.exp, "tanh": np.tanh,
    "abs": np.abs, "sqrt": np.sqrt, "sign": np.sign,
}
_BIN_OUT = {"add": np.add, "sub": np.subtract, "ewmul": np.multiply}
_SCALE_OUT = {"add": np.add, "sub": np.subtract, "scale": np.multiply,
              "mul": np.multiply, "ewmul": np.multiply,
              "div": np.true_divide}


def fused_op_count(prog: Sequence[tuple]) -> int:
    """Number of arithmetic instructions in a FUSED program."""
    return sum(1 for ins in prog if ins[0] != "in")


def fused_flops(prog: Sequence[tuple], m: int, n: int) -> int:
    """Flop estimate matching the unfused per-kind accounting."""
    f = 0
    for ins in prog:
        if ins[0] == "in":
            continue
        f += (4 if ins[0] == "ewise" else 1) * m * n
    return f


def eval_fused(prog: Sequence[tuple], inputs: Sequence[np.ndarray]
               ) -> np.ndarray:
    """Interpret a FUSED tile program.

    Interior temporaries whose last use has passed are recycled as ``out=``
    buffers, so the chain runs with O(1) scratch regardless of length.
    Input buffers are never written.
    """
    n = len(prog)
    last_use = [0] * n
    is_input = [ins[0] == "in" for ins in prog]
    for idx, ins in enumerate(prog):
        for ref in ins[2:] if ins[0] == "scale" else ins[1:]:
            if isinstance(ref, int):
                last_use[ref] = idx

    vals: List[Optional[np.ndarray]] = [None] * n
    free: List[np.ndarray] = []
    # buffer recycling is only safe when ufunc output dtype == operand dtype,
    # which holds for floating inputs (ints would promote under sin/div/...)
    reuse = all(np.asarray(x).dtype.kind == "f" for x in inputs)

    def take_out(shape, dtype) -> Optional[np.ndarray]:
        if not reuse:
            return None
        for i, buf in enumerate(free):
            if buf.shape == shape and buf.dtype == dtype:
                return free.pop(i)
        return None

    def release(idx: int, at: int):
        if not is_input[idx] and last_use[idx] <= at:
            buf = vals[idx]
            if buf is not None:
                free.append(buf)
            vals[idx] = None

    for idx, ins in enumerate(prog):
        kind = ins[0]
        if kind == "in":
            vals[idx] = np.asarray(inputs[ins[1]])
            continue
        if kind == "ewise":
            fn, i = ins[1], ins[2]
            x = vals[i]
            if fn == "relu":
                rd = np.result_type(x.dtype)
                out = take_out(x.shape, rd)
                vals[idx] = np.maximum(x, 0.0, out=out) if out is not None \
                    else np.maximum(x, 0.0)
            else:
                uf = _UNARY_OUT.get(fn)
                if uf is None:              # EWISE_FNS entry without a ufunc
                    vals[idx] = EWISE_FNS[fn](x)
                else:
                    out = take_out(x.shape, np.result_type(x.dtype))
                    vals[idx] = uf(x, out=out) if out is not None else uf(x)
            release(i, idx)
        elif kind == "scale":
            sk, s, i = ins[1], ins[2], ins[3]
            x = vals[i]
            out = take_out(x.shape, x.dtype)
            uf = _SCALE_OUT.get(sk)
            if uf is not None and out is not None and \
                    np.result_type(x.dtype) == out.dtype:
                vals[idx] = uf(x, x.dtype.type(s), out=out)
            else:
                if out is not None:
                    free.append(out)
                vals[idx] = apply_scale(sk, x, s)
            release(i, idx)
        else:
            i, j = ins[1], ins[2]
            a, b = vals[i], vals[j]
            rd = np.result_type(a.dtype, b.dtype)
            out = take_out(a.shape, rd)
            uf = _BIN_OUT[kind]
            vals[idx] = uf(a, b, out=out) if out is not None else uf(a, b)
            release(i, idx)
            release(j, idx)

    return vals[n - 1]


# ---------------------------------------------------------------------------
# structural signature (plan-cache key)
# ---------------------------------------------------------------------------

def _structure_payload_key(node: ClusteredMatrix):
    """Payload component of the *structural* signature.

    Unlike the CSE key this deliberately ignores leaf VALUES (input array
    identity, random seed): the tiled program and schedule depend only on
    structure and shapes, and a cache hit rebinds the leaves.  RESIDENT
    leaves ignore the handle identity too — the *layout* (tile grid +
    per-tile home nodes) is keyed separately (``residency_layout``), so a
    power-iteration step hits the cache even though each step holds a
    fresh handle.
    """
    if node.op in (Op.INPUT, Op.RANDOM, Op.RESIDENT):
        return None
    if isinstance(node.payload, (str, int, float, tuple, type(None))):
        return node.payload
    return str(node.payload)


def structural_signature(root: ClusteredMatrix) -> tuple:
    """Canonical hashable description of the DAG's structure + shapes."""
    return structural_signature_many((root,))


def structural_signature_many(roots: Sequence[ClusteredMatrix]) -> tuple:
    """Structural signature of a multi-root program: the union DAG's
    node signature plus each root's index into it (two programs match
    only if they compute the same outputs of the same structure)."""
    index: Dict[int, int] = {}
    sig: List[tuple] = []
    for i, node in enumerate(topo_order_many(roots)):
        index[node.uid] = i
        sig.append((node.op.value, node.shape, str(node.dtype),
                    _structure_payload_key(node),
                    tuple(index[p.uid] for p in node.parents)))
    return tuple(sig) + (("roots",) + tuple(index[r.uid] for r in roots),)


def residency_layout(roots: Sequence[ClusteredMatrix]) -> tuple:
    """The plan-cache key component for resident leaves: per RESIDENT leaf
    (in topo order), its handle's tile size and per-tile home nodes.  Two
    structurally equal programs share a schedule only when their resident
    tiles sit on the same nodes — pinned placements depend on it."""
    out: List[tuple] = []
    for i, node in enumerate(topo_order_many(roots)):
        if node.op is Op.RESIDENT:
            h = node.payload
            out.append((i, h.tile, tuple(sorted(h.home.items()))))
    return tuple(out)


def leaves_in_order(root: ClusteredMatrix) -> List[ClusteredMatrix]:
    """Leaves in canonical topo order — the rebinding contract between two
    DAGs with equal structural signatures."""
    return leaves_in_order_many((root,))


def leaves_in_order_many(roots: Sequence[ClusteredMatrix]
                         ) -> List[ClusteredMatrix]:
    return [n for n in topo_order_many(roots) if n.op in LEAF_OPS]
