"""Wave-batched executor: one stacked kernel call per wave group.

At the tile sizes the autotuner favors, the per-task executor
(``exec/local.py``) pays one Python dispatch — heap pop, closure, lock
round-trip — per tile task; at 10k+ tasks that overhead dominates the BLAS
time the plan was optimized for (the numpywren fine-grained-task wall).
This backend removes it by *batching*:

1. the scheduled task graph is partitioned into **waves** — antichains of
   mutually independent tasks (longest-path levels, so every dependency
   crosses waves);
2. each wave is grouped by ``(kind, tile shape, dtype, payload class)``;
3. each group executes as ONE stacked call — ``np.matmul`` over 3-D stacked
   operands for ADDMUL/MATMUL, one vectorized ufunc application over a
   stacked slab for ADD/SUB/EWMUL/SCALE/EWISE, ``fusion.eval_fused`` over
   stacked inputs for FUSED — or, with ``backend="pallas"``, a
   ``jax.vmap``-over-Pallas blocked GEMM (``kernels/matmul.py``) jit-cached
   per group signature.

Flight recorder: one EXEC span per group call.  A device ADDMUL group's
span holds one child span per phase — GATHER (stack and make contiguous
on the host, or on the device for device tiles), H2D, KERNEL, D2H,
STORE (into the arena) — each ended by the data it moved being ready;
an ASSEMBLE span covers the result assembly after the last wave.
``stats`` counts ``h2d_bytes``, ``d2h_bytes``, ``host_copy_bytes``
(stacked gathers, contiguity and dtype copies, device-path stores),
``staging_allocs`` / ``staging_reuses`` (device-path staging takes; also
each GATHER span's ``args``) and ``device_operand_tiles`` /
``device_operand_bytes`` (device tiles a device group consumed where they
lie; the tile count is in each GATHER span's ``args`` too).

Device tiles (Pallas backend, in a session): a persisted root's tiles are
kept in HBM as ``jax.Array``s, put up once at retention, wherever the
device holds their dtype exactly (float32, bfloat16; not float64 under
JAX's default x64-off, whose tiles stay host arrays).  A later run's
RESIDENT tasks alias them, and a device ADDMUL group stacks an operand
that holds any of them on the device, with no staging buffer and no
upload of those tiles.  Every other reader pulls a device tile to the
host on demand (``core.tiling.to_host``), counted in ``d2h_bytes``.

Buffer arena: every group's output tiles live in ONE stacked slab
``(group, tm, tn)``; each tile buffer is a zero-copy view ``slab[i]``.
When a later group's inputs are exactly a contiguous run of a slab, the
gather is a zero-copy slice (the common case for elementwise chains and
the C-accumulator of addmul k-chains); otherwise tiles are stacked into a
scratch copy.  Slabs are reference-counted like the per-task runtime: a
slab is freed when the last reader of its last live tile finishes, so peak
memory stays bounded by live *slabs* (wave-granular, vs tile-granular for
the per-task executor — the throughput/peak trade-off of batching).

Staging buffers (device path only): a device ADDMUL group writes every
operand it must copy — stacked A and B, a C that is no contiguous run, the
epilogue's extras, a transposed operand as each tile's ``.T`` in the same
single copy — into host buffers taken from a ``StagingPool`` keyed on
``(shape, dtype)``, and gives them back when the group ends, after STORE,
once no device array made from them is referenced (the CPU backend's
``device_put`` may alias a NumPy buffer).  A fresh 64 MiB buffer costs
first-touch page faults that dwarf the copy into it, so the pool, which
the engine hands to every device executor it builds, outlives programs.
It holds at most what one group took at once per shape, and only the
shapes of the last program that took any.  Arena slabs and the NumPy
backend's stacks stay fresh allocations: they outlive their group (a slab,
``eval_fused``'s result may alias its inputs).

Numerics: the NumPy backend is bit-identical to ``LocalExecutor`` — a 3-D
``np.matmul`` issues the same BLAS GEMM per slice as the per-task ``@``,
and NumPy ufuncs are elementwise-deterministic under stacking.  The Pallas
backend accumulates in float32 VMEM on TPU and is validated at tolerance
instead.

``predict_wave_makespan`` is the executor-strategy leg of the paper's
simulation-driven selection: the engine compares it against the per-task
simulated makespan (which prices ``TimeModel.dispatch_overhead`` per task)
and picks the cheaper strategy per plan.
"""
from __future__ import annotations

import functools
import threading
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from ..core.fusion import eval_fused
from ..core.graph import (Task, TaskGraph, TaskKind, TileRef,
                          matmul_epilogue, matmul_flags)
from ..core.lazy import EWISE_FNS, Op, apply_scale, leaf_slice
from ..core.machine import ClusterSpec
from ..core.timemodel import CostCache, TimeModel
from ..core.tiling import assemble, result_sets_of, tile_slices, to_host
from ..runtime.telemetry import NULL_TRACER, Tracer


def build_waves(g: TaskGraph) -> List[List[int]]:
    """Partition ``g`` into dependency levels (waves).

    ``wave[t] = 1 + max(wave[p] for p in preds)`` — tasks in one wave are
    mutually independent (any edge strictly increases the level), so a wave
    can execute as a set of batched kernels with no intra-wave ordering.
    Within a wave, tasks are ordered by output tile ``(tensor, i, j)`` so
    group gathers line up with slab layout (maximising zero-copy runs).
    """
    level: Dict[int, int] = {}
    for t in g.topo():
        level[t.tid] = 1 + max((level[p] for p in t.preds), default=-1)
    n_waves = max(level.values(), default=-1) + 1
    waves: List[List[int]] = [[] for _ in range(n_waves)]
    for tid, lv in level.items():
        waves[lv].append(tid)

    def order_key(tid: int):
        t = g.tasks[tid]
        if t.out is not None:
            return (0, t.out.tensor, t.out.i, t.out.j, tid)
        return (1, 0, 0, 0, tid)

    for wave in waves:
        wave.sort(key=order_key)
    return waves


def _group_key(t: Task, dtypes: Dict[int, object]) -> tuple:
    """Batching signature: tasks with equal keys stack into one kernel."""
    dt = lambda ref: str(dtypes.get(ref.tensor, np.float64))  # noqa: E731
    k = t.kind
    if k in (TaskKind.ADDMUL, TaskKind.MATMUL):
        key = (k, matmul_flags(t.payload), t.ins[0].shape, t.ins[1].shape,
               t.out.shape, dt(t.ins[0]), dt(t.ins[1]), dt(t.out))
        epi = matmul_epilogue(t.payload)
        if epi is not None:
            # epilogued chain tails batch separately from plain chain
            # steps: the stacked eval_fused needs matching programs and
            # matching extra-operand shapes/dtypes across the group
            key += (epi, tuple(r.shape for r in t.ins[2:]),
                    tuple(dt(r) for r in t.ins[2:]))
        return key
    if k is TaskKind.CALLOC:
        return (k, t.out.shape, dt(t.out))
    if k is TaskKind.FILL:
        return (k, t.out.shape, dt(t.out))
    if k is TaskKind.RESIDENT:
        return (k, t.out.shape, dt(t.out))
    if k in (TaskKind.ADD, TaskKind.SUB, TaskKind.EWMUL):
        return (k, t.out.shape, dt(t.ins[0]), dt(t.ins[1]))
    if k in (TaskKind.SCALE, TaskKind.EWISE):
        return (k, t.payload, t.out.shape, dt(t.ins[0]))
    if k is TaskKind.FUSED:
        return (k, t.payload, tuple(r.shape for r in t.ins),
                tuple(dt(r) for r in t.ins))
    if k is TaskKind.TRANSPOSE:
        return (k, t.ins[0].shape, dt(t.ins[0]))
    if k is TaskKind.TAKECOPY:
        return (k,)
    raise ValueError(k)  # pragma: no cover


def group_wave(g: TaskGraph, wave: Sequence[int],
               dtypes: Dict[int, object]) -> List[Tuple[tuple, List[Task]]]:
    """Group one wave's tasks by batching signature (insertion-ordered)."""
    groups: Dict[tuple, List[Task]] = {}
    for tid in wave:
        t = g.tasks[tid]
        groups.setdefault(_group_key(t, dtypes), []).append(t)
    return list(groups.items())


class _Slab:
    """One stacked allocation holding a wave group's output tiles."""

    __slots__ = ("arr", "live", "nbytes")

    def __init__(self, arr: np.ndarray, live: int):
        self.arr = arr
        self.live = live
        self.nbytes = arr.nbytes


class WaveArena:
    """Stacked tile storage with slab-granular refcounted freeing."""

    def __init__(self):
        #: TileRef -> (slab, index within slab)
        self._of: Dict[TileRef, Tuple[_Slab, int]] = {}
        self.cur_bytes = 0
        self.peak_bytes = 0
        self.slabs_alloc = 0
        self.slabs_freed = 0

    def register(self, refs: Sequence[TileRef], arr: np.ndarray,
                 extra_live: int = 0) -> _Slab:
        """Adopt ``arr`` (leading axis = tiles in ``refs`` order) as a slab.

        A tile ref can be produced twice — HEFT's §3.3 regeneration pass
        clones a FILL onto another node, and both tasks share the original
        ``out`` ref.  A ref holds exactly ONE slab slot alive at a time:
        re-registering releases the previous hold, so duplicate producers
        cannot strand a slab at ``live > 0`` forever.
        """
        slab = _Slab(arr, live=len(refs) + extra_live)
        self.cur_bytes += slab.nbytes
        self.peak_bytes = max(self.peak_bytes, self.cur_bytes)
        self.slabs_alloc += 1
        for i, r in enumerate(refs):
            if r in self._of:
                self.release_tile(r)
            self._of[r] = (slab, i)
        return slab

    def contiguous_run(self, refs: Sequence[TileRef]) -> Optional[np.ndarray]:
        """Zero-copy stacked view if ``refs`` are one ascending slab run."""
        first = self._of.get(refs[0])
        if first is None:
            return None
        slab, start = first
        for k, r in enumerate(refs[1:], 1):
            ent = self._of.get(r)
            if ent is None or ent[0] is not slab or ent[1] != start + k:
                return None
        return slab.arr[start:start + len(refs)]

    def release_tile(self, ref: TileRef) -> bool:
        """Drop one live count of the tile's slab; True if the slab died."""
        ent = self._of.get(ref)
        if ent is None:
            return False
        slab, _ = ent
        slab.live -= 1
        if slab.live == 0:
            self.cur_bytes -= slab.nbytes
            self.slabs_freed += 1
            slab.arr = None
            return True
        return False


def stack_into(buf: np.ndarray, tiles: Sequence[np.ndarray],
               transpose: bool = False) -> np.ndarray:
    """Stack 2-D ``tiles`` into ``buf`` in one copy — each tile's ``.T``
    where ``transpose``, casting to ``buf``'s dtype; returns ``buf``.

    Bitwise equal to ``np.stack(tiles)`` (``.transpose(0, 2, 1)`` made
    contiguous, ``.astype(buf.dtype)``), without their fresh buffers.
    """
    for slot, t in zip(buf, tiles):
        np.copyto(slot, t.T if transpose else t, casting="same_kind")
    return buf


def device_holds(dtype) -> bool:
    """Whether the device keeps ``dtype`` exactly: float32 and bfloat16
    do, float64 under JAX's default x64-off does not."""
    import jax
    return jax.dtypes.canonicalize_dtype(dtype) == np.dtype(dtype)


@functools.lru_cache(maxsize=None)
def _device_stack_fn():
    """``stack(tiles, transpose, dtype)``: one stacked device operand made
    from device tiles in one jitted copy, each tile's ``.T`` where
    ``transpose``, cast to ``dtype``; jit keeps one program per
    (count, shape, dtype)."""
    import jax
    import jax.numpy as jnp

    def stack(tiles, transpose, dtype):
        out = jnp.stack(tiles)
        if transpose:
            out = out.transpose(0, 2, 1)
        return out.astype(dtype)
    return jax.jit(stack, static_argnames=("transpose", "dtype"))


class StagingPool:
    """Free host staging buffers of the device path, keyed on
    ``(shape, dtype)``; thread-safe.

    Groups run one at a time, take their buffers and give them all back,
    so each shape's free list grows to the most buffers one group held at
    once, and no further.  ``retain`` drops the free buffers of shapes a
    program no longer uses.
    """

    def __init__(self):
        self._free: Dict[tuple, List[np.ndarray]] = {}
        self._lock = threading.Lock()

    def take(self, shape: tuple, dtype) -> Tuple[np.ndarray, bool]:
        """A buffer of ``shape`` and ``dtype``, uninitialised; and whether
        it was reused rather than allocated."""
        key = (tuple(shape), np.dtype(dtype))
        with self._lock:
            free = self._free.get(key)
            if free:
                return free.pop(), True
        return np.empty(key[0], key[1]), False

    def give(self, bufs: Iterable[np.ndarray]) -> None:
        """Return buffers taken from this pool; nothing may refer to them
        or to device arrays made from them any more."""
        with self._lock:
            for b in bufs:
                self._free.setdefault((b.shape, b.dtype), []).append(b)

    def retain(self, keys: Iterable[tuple]) -> None:
        """Drop the free buffers of every ``(shape, dtype)`` not in
        ``keys``."""
        keys = set(keys)
        with self._lock:
            self._free = {k: v for k, v in self._free.items() if k in keys}

    def held_bytes(self) -> Dict[tuple, int]:
        """Bytes of free buffers per ``(shape, dtype)``."""
        with self._lock:
            return {k: sum(b.nbytes for b in v)
                    for k, v in self._free.items()}


class WaveExecutor:
    """Executes a planned tiled program wave-by-wave with batched kernels.

    ``backend="numpy"`` (default) issues stacked BLAS/ufunc calls and is
    bit-identical to ``LocalExecutor``; ``backend="pallas"`` routes ADDMUL
    groups through ``jax.vmap`` over the Pallas blocked GEMM (interpret
    mode on CPU, compiled on TPU), jit-cached per group signature.

    ``free_buffers=False`` keeps every slab alive (debugging / benchmarks).
    ``staging`` is the device path's ``StagingPool``; the engine passes its
    own so that the buffers outlive this executor, otherwise the executor
    makes one.
    """

    def __init__(self, backend: str = "numpy", free_buffers: bool = True,
                 trace: bool = True, precision: str = "strict",
                 staging: Optional[StagingPool] = None):
        if backend not in ("numpy", "pallas"):
            raise ValueError(f"unknown wave backend {backend!r}")
        if precision not in ("strict", "mixed"):
            raise ValueError(f"unknown precision mode {precision!r}")
        self.backend = backend
        #: ``"strict"`` (default) keeps the bit-identity contract with
        #: LocalExecutor.  ``"mixed"`` is the opt-in numerics gate: matmul
        #: accumulators CALLOC in float32, operands are cast to float32
        #: for the multiply, and epilogued chain outputs are stored as
        #: bfloat16 — validated by allclose tolerance, never bitwise
        #: (see TESTING.md, numerics tiers).
        self.precision = precision
        self.free_buffers = free_buffers
        self.staging = staging if staging is not None else StagingPool()
        #: staging keys taken in the current run
        self._staged_keys: set = set()
        #: flight recorder: one EXEC span per batched group call, phase
        #: spans inside device groups (node 0, lane 0 — waves are
        #: sequential in this process)
        self.trace = trace
        self._tracer = NULL_TRACER
        self.spans: List = []
        self.stats: Dict[str, int] = {}

    # -- gather helpers ----------------------------------------------------
    def _counted(self, src: np.ndarray, dst: np.ndarray) -> np.ndarray:
        """``dst``, counting its bytes as a host copy unless it is ``src``
        itself (``ascontiguousarray``/``astype`` that had nothing to do)."""
        if dst is not src:
            self.stats["host_copy_bytes"] += dst.nbytes
        return dst

    def _stack(self, arrays) -> np.ndarray:
        out = np.stack(arrays)
        self.stats["host_copy_bytes"] += out.nbytes
        return out

    def _host(self, t) -> np.ndarray:
        """Tile ``t`` as a host array; a pull of a device tile counts in
        ``d2h_bytes``."""
        if isinstance(t, np.ndarray):
            return t
        t = to_host(t)
        self.stats["d2h_bytes"] += t.nbytes
        return t

    def _gather(self, refs, buffers, arena) -> np.ndarray:
        if len(refs) == 1:
            self.stats["zero_copy_gathers"] += 1
            return self._host(buffers[refs[0]])[None]
        run = arena.contiguous_run(refs)
        if run is not None and run.shape[0] == len(refs):
            self.stats["zero_copy_gathers"] += 1
            return run
        self.stats["copied_gathers"] += 1
        return self._stack([self._host(buffers[r]) for r in refs])

    def _matmul_operands(self, tasks, buffers, arena):
        """Stacked A and B of a matmul group, transposed as the payload's
        flags say (a transposed operand is a strided view)."""
        ta, tb = matmul_flags(tasks[0].payload)
        a3 = self._gather([t.ins[0] for t in tasks], buffers, arena)
        b3 = self._gather([t.ins[1] for t in tasks], buffers, arena)
        if ta:
            a3 = a3.transpose(0, 2, 1)
        if tb:
            b3 = b3.transpose(0, 2, 1)
        if self.precision == "mixed":
            a3 = self._counted(a3, a3.astype(np.float32, copy=False))
            b3 = self._counted(b3, b3.astype(np.float32, copy=False))
        return a3, b3

    # -- group kernels -----------------------------------------------------
    def _run_group(self, kind: TaskKind, tasks: List[Task], buffers, arena,
                   leaf_nodes, dtypes, tile, residency=None) -> None:
        self.stats["batched_calls"] += 1
        outs = [t.out for t in tasks]

        if kind is TaskKind.TAKECOPY:
            return

        if kind is TaskKind.RESIDENT:
            # session-resident tiles: zero-copy aliases into this run's
            # buffer namespace; NOT registered in the arena (session-owned)
            for t in tasks:
                buffers[t.out] = residency.tile(t.payload, t.out.i, t.out.j)
            return

        if kind is TaskKind.CALLOC:
            dt = dtypes.get(tasks[0].payload, np.float64)
            if self.precision == "mixed":
                # CALLOCs are matmul accumulators: f32 accumulate
                dt = np.float32
            slab = np.zeros((len(tasks),) + outs[0].shape, dtype=dt)
            arena.register(outs, slab)
            for i, t in enumerate(tasks):
                buffers[t.out] = slab[i]
            return

        if kind is TaskKind.FILL:
            self._run_fill(tasks, buffers, arena, leaf_nodes, tile)
            return

        if kind in (TaskKind.ADDMUL, TaskKind.MATMUL):
            self._run_matmul(kind, tasks, buffers, arena, dtypes)
            return

        # elementwise families: one vectorized call over stacked operands
        if kind is not TaskKind.FUSED:
            ins0 = self._gather([t.ins[0] for t in tasks], buffers, arena)
        if kind in (TaskKind.ADD, TaskKind.SUB, TaskKind.EWMUL):
            ins1 = self._gather([t.ins[1] for t in tasks], buffers, arena)
            ufunc = {TaskKind.ADD: np.add, TaskKind.SUB: np.subtract,
                     TaskKind.EWMUL: np.multiply}[kind]
            slab = ufunc(ins0, ins1)
        elif kind is TaskKind.SCALE:
            skind, s = tasks[0].payload
            slab = apply_scale(skind, ins0, s)
        elif kind is TaskKind.EWISE:
            slab = EWISE_FNS[tasks[0].payload](ins0)
        elif kind is TaskKind.FUSED:
            stacks = [self._gather([t.ins[j] for t in tasks], buffers, arena)
                      for j in range(len(tasks[0].ins))]
            slab = eval_fused(tasks[0].payload, stacks)
        elif kind is TaskKind.TRANSPOSE:
            slab = np.ascontiguousarray(ins0.transpose(0, 2, 1))
        else:  # pragma: no cover
            raise ValueError(kind)
        arena.register(outs, slab)
        for i, t in enumerate(tasks):
            buffers[t.out] = slab[i]

    def _run_fill(self, tasks, buffers, arena, leaf_nodes, tile) -> None:
        node = leaf_nodes[tasks[0].payload]
        if node.op is Op.INPUT and \
                all(leaf_nodes[t.payload].op is Op.INPUT for t in tasks):
            # zero-copy views into the user array, exactly like exec/local
            for t in tasks:
                n = leaf_nodes[t.payload]
                rs = tile_slices(n.shape[0], tile[0])[t.out.i]
                cs = tile_slices(n.shape[1], tile[1])[t.out.j]
                buffers[t.out] = leaf_slice(n, rs[0], rs[1], cs[0], cs[1])
            return
        slab = np.empty((len(tasks),) + tasks[0].out.shape, dtype=node.dtype)
        for i, t in enumerate(tasks):
            n = leaf_nodes[t.payload]
            rs = tile_slices(n.shape[0], tile[0])[t.out.i]
            cs = tile_slices(n.shape[1], tile[1])[t.out.j]
            slab[i] = leaf_slice(n, rs[0], rs[1], cs[0], cs[1])
            buffers[t.out] = slab[i]
        arena.register([t.out for t in tasks], slab)

    def _epilogue_store_dtype(self):
        if self.precision != "mixed":
            return None
        import ml_dtypes
        return np.dtype(ml_dtypes.bfloat16)

    def _apply_epilogue(self, epi, tasks, c3, buffers, arena) -> None:
        """Stacked epilogue over the accumulated C slab; rebinds outputs.

        Runs the same ``eval_fused`` program as the unfused FUSED task
        would, on the same accumulated values, so strict-precision wave
        execution stays bit-identical to the per-task executors.
        """
        nin = len(tasks[0].ins)
        stacks = [self._gather([t.ins[j] for t in tasks], buffers, arena)
                  for j in range(2, nin)]
        slab = eval_fused(epi, [c3] + stacks)
        store_dt = self._epilogue_store_dtype()
        if store_dt is not None:
            slab = slab.astype(store_dt)
        outs = [t.out for t in tasks]
        arena.register(outs, slab)
        for i, t in enumerate(tasks):
            buffers[t.out] = slab[i]

    def _run_matmul(self, kind, tasks, buffers, arena, dtypes) -> None:
        if kind is TaskKind.ADDMUL and self.backend == "pallas":
            self._run_device_addmul(tasks, buffers, arena)
            return
        epi = matmul_epilogue(tasks[0].payload)
        a3, b3 = self._matmul_operands(tasks, buffers, arena)

        if kind is TaskKind.MATMUL:
            slab = np.matmul(a3, b3)
            if epi is not None:
                self._apply_epilogue(epi, tasks, slab, buffers, arena)
                return
            arena.register([t.out for t in tasks], slab)
            for i, t in enumerate(tasks):
                buffers[t.out] = slab[i]
            return

        # ADDMUL: C += A @ B, accumulating into the CALLOC'd tile buffers
        outs = [t.out for t in tasks]
        crun = arena.contiguous_run(outs) if len(outs) > 1 else None
        prod = np.matmul(a3, b3)
        if crun is not None:
            crun += prod
        else:
            for i, t in enumerate(tasks):
                buffers[t.out] += prod[i]
        if epi is not None:
            # tail of the k-chain: apply the fused epilogue over the
            # fully-accumulated C tiles in one stacked pass
            c3 = crun if crun is not None else \
                self._stack([buffers[t.out] for t in tasks])
            self._apply_epilogue(epi, tasks, c3, buffers, arena)

    def _device_operand(self, refs, buffers, arena, taken, transpose=False,
                        dtype=None):
        """One stacked operand of a device group, C-contiguous and of
        ``dtype`` (default: the tiles'), each tile's ``.T`` where
        ``transpose``.  Where any tile is a device array, a device array
        stacked where the tiles lie (:meth:`_device_stack`); otherwise a
        host array: a zero-copy view where the tiles already lie so, else
        one copy into a staging buffer, which is appended to ``taken``."""
        tiles = [buffers[r] for r in refs]
        on_dev = [not isinstance(t, np.ndarray) for t in tiles]
        if any(on_dev):
            return self._device_stack(tiles, on_dev, transpose, dtype)
        dtype = tiles[0].dtype if dtype is None else np.dtype(dtype)
        if not transpose:
            run = tiles[0][None] if len(refs) == 1 else \
                arena.contiguous_run(refs)
            if run is not None and run.shape[0] == len(refs) and \
                    run.flags.c_contiguous and run.dtype == dtype:
                self.stats["zero_copy_gathers"] += 1
                return run
        self.stats["copied_gathers"] += 1
        shape = tiles[0].shape[::-1] if transpose else tiles[0].shape
        buf, reused = self.staging.take((len(tiles),) + shape, dtype)
        taken.append(buf)
        self._staged_keys.add((buf.shape, buf.dtype))
        self.stats["staging_reuses" if reused else "staging_allocs"] += 1
        self.stats["host_copy_bytes"] += buf.nbytes
        return stack_into(buf, tiles, transpose)

    def _device_stack(self, tiles, on_dev, transpose, dtype):
        """A device operand stacked on the device in one jitted copy; the
        host tiles among ``tiles`` go up first (counted in ``h2d_bytes``),
        the device ones are read where they lie (counted in
        ``device_operand_tiles`` / ``device_operand_bytes``)."""
        import jax
        st = self.stats
        up = [t for t, d in zip(tiles, on_dev) if not d]
        if up:
            put = iter(jax.device_put(up))
            tiles = [t if d else next(put) for t, d in zip(tiles, on_dev)]
            st["h2d_bytes"] += sum(t.nbytes for t, d in zip(tiles, on_dev)
                                   if not d)
        st["device_operand_tiles"] += sum(on_dev)
        st["device_operand_bytes"] += sum(t.nbytes for t, d in
                                          zip(tiles, on_dev) if d)
        dtype = tiles[0].dtype if dtype is None else np.dtype(dtype)
        return _device_stack_fn()(tiles, transpose=transpose, dtype=dtype)

    def _run_device_addmul(self, tasks, buffers, arena) -> None:
        """ADDMUL group through the Pallas GEMM: C += A @ B, with a fused
        epilogue (accumulator -> epilogue -> store) in the same launch."""
        import jax
        from ..kernels import ops as kops
        tr, st = self._tracer, self.stats
        epi = matmul_epilogue(tasks[0].payload)
        ta, tb = matmul_flags(tasks[0].payload)
        ab_dt = np.float32 if self.precision == "mixed" else None
        outs = [t.out for t in tasks]
        crun = arena.contiguous_run(outs) if len(outs) > 1 else None
        taken: List[np.ndarray] = []

        def operand(j, transpose=False, dtype=None):
            return self._device_operand([t.ins[j] for t in tasks], buffers,
                                        arena, taken, transpose, dtype)

        with tr.span("GATHER") as sp:
            allocs, reuses = st["staging_allocs"], st["staging_reuses"]
            dev_tiles = st["device_operand_tiles"]
            ops = [self._device_operand(outs, buffers, arena, taken),
                   operand(0, ta, ab_dt), operand(1, tb, ab_dt)]
            if epi is not None:
                ops += [operand(j) for j in range(2, len(tasks[0].ins))]
            is_host = [isinstance(x, np.ndarray) for x in ops]
            jax.block_until_ready([x for x, h in zip(ops, is_host)
                                   if not h])
            sp.args["staging_allocs"] = st["staging_allocs"] - allocs
            sp.args["staging_reuses"] = st["staging_reuses"] - reuses
            sp.args["device_operand_tiles"] = \
                st["device_operand_tiles"] - dev_tiles
        with tr.span("H2D") as sp:
            put = jax.block_until_ready(jax.device_put(
                [x for x, h in zip(ops, is_host) if h]))
            sp.args["nbytes"] = nbytes = sum(x.nbytes for x in put)
            put = iter(put)
            dev = [next(put) if h else x for x, h in zip(ops, is_host)]
        st["h2d_bytes"] += nbytes
        store_dt = None if epi is None else self._epilogue_store_dtype()
        with tr.span("KERNEL"):
            res = jax.block_until_ready(kops.addmul_batched(
                *dev[:3], epilogue=epi, extras=dev[3:], out_dtype=store_dt))
        with tr.span("D2H") as sp:
            res = np.asarray(res)
            sp.args["nbytes"] = res.nbytes
        st["d2h_bytes"] += res.nbytes
        with tr.span("STORE"):
            if epi is None:
                if crun is not None:
                    np.copyto(crun, res)
                    st["host_copy_bytes"] += crun.nbytes
                else:
                    for i, t in enumerate(tasks):
                        np.copyto(buffers[t.out], res[i])
                        st["host_copy_bytes"] += buffers[t.out].nbytes
            else:
                if store_dt is None:
                    # strict mode: keep the wave pipeline's dtype contract
                    # (jax may compute in f32; the plain path casts back
                    # to the accumulator dtype the same way)
                    res = self._counted(res, res.astype(np.result_type(
                        *[x.dtype for x in [ops[0]] + ops[3:]]),
                        copy=False))
                arena.register(outs, res)
                for i, t in enumerate(tasks):
                    buffers[t.out] = res[i]
        # nothing refers to the device arrays made from the staging
        # buffers any more, so the next group may overwrite them (after an
        # error they are dropped instead: a transfer may still hold them)
        del dev, put
        self.staging.give(taken)

    def _retained(self, bufs) -> list:
        """The tiles a session keeps of one persisted root.

        The Pallas backend keeps in HBM every host tile whose dtype the
        device holds exactly, put up in one ``device_put`` and counted in
        ``h2d_bytes``; a device tile stays as it is.  Every other tile
        stays a host array.  Wave tiles are views into per-wave SLABS —
        retaining the view would pin the whole slab (every same-wave tile)
        for the handle's lifetime, and INPUT-leaf views alias the user's
        array — so a view-backed host tile is copied out; only standalone
        arrays transfer zero-copy.
        """
        if self.backend == "pallas":
            up = [k for k, b in enumerate(bufs)
                  if isinstance(b, np.ndarray) and device_holds(b.dtype)]
            if up:
                import jax
                put = jax.block_until_ready(
                    jax.device_put([bufs[k] for k in up]))
                self.stats["h2d_bytes"] += sum(x.nbytes for x in put)
                bufs = list(bufs)
                for k, x in zip(up, put):
                    bufs[k] = x
        return [np.ascontiguousarray(b)
                if isinstance(b, np.ndarray) and b.base is not None else b
                for b in bufs]

    # -- driver ------------------------------------------------------------
    def execute(self, plan) -> np.ndarray:
        g: TaskGraph = plan.program.graph
        tile = plan.tile
        leaf_nodes = plan.program.leaf_nodes
        dtypes = plan.program.dtypes
        residency = getattr(plan, "residency", None)
        rsets = result_sets_of(g)
        waves = getattr(plan, "waves", None) or build_waves(g)

        buffers: Dict[TileRef, np.ndarray] = {}
        arena = WaveArena()
        self.stats = {"zero_copy_gathers": 0, "copied_gathers": 0,
                      "batched_calls": 0, "h2d_bytes": 0, "d2h_bytes": 0,
                      "host_copy_bytes": 0, "staging_allocs": 0,
                      "staging_reuses": 0, "device_operand_tiles": 0,
                      "device_operand_bytes": 0}
        self._staged_keys = set()

        # readers per tile (+1 keeps result tiles alive for assembly and
        # persisted tiles alive for session retention — retained tiles are
        # excluded from slab refcount freeing)
        refcnt: Dict[TileRef, int] = {}
        for t in g:
            for r in t.ins:
                refcnt[r] = refcnt.get(r, 0) + 1
        for rs in rsets:
            for r in rs.tiles:
                refcnt[r] = refcnt.get(r, 0) + 1
        # an ADDMUL chain rewrites its C tile: every chain step after the
        # slab's CALLOC holds the tile alive even though it is not in `ins`
        for t in g:
            if t.kind in (TaskKind.ADDMUL, TaskKind.MATMUL) and \
                    t.out is not None:
                refcnt[t.out] = refcnt.get(t.out, 0) + 1

        tracer = Tracer(node=0, enabled=self.trace) if self.trace \
            else NULL_TRACER
        self._tracer = tracer
        tasks_run = 0
        for wi, wave in enumerate(waves):
            for (key, tasks) in group_wave(g, wave, dtypes):
                with tracer.span(key[0].name, cat="EXEC", wave=wi,
                                 tasks=len(tasks), batched=True):
                    self._run_group(key[0], tasks, buffers, arena,
                                    leaf_nodes, dtypes, tile,
                                    residency=residency)
                tasks_run += len(tasks)
                if not self.free_buffers:
                    continue
                for t in tasks:
                    reads = list(t.ins)
                    if t.kind in (TaskKind.ADDMUL, TaskKind.MATMUL):
                        reads.append(t.out)   # release the chain's hold
                    for r in reads:
                        refcnt[r] -= 1
                        if refcnt[r] == 0:
                            # result tiles hold an extra assembly ref, so
                            # they can never reach zero here
                            arena.release_tile(r)
                            buffers.pop(r, None)

        # retention: persisted roots' tiles move to the session store
        # (``_retained``)
        retained = 0
        outs = []
        gather_bytes = 0
        with tracer.span("ASSEMBLE"):
            for rs in rsets:
                if rs.gather:
                    vals = {r: self._host(buffers[r]) for r in rs.tiles}
                    gather_bytes += sum(r.bytes for r in rs.tiles)
                    outs.append(assemble(vals, rs.shape, tile, rs.uid))
                else:
                    kept = self._retained([buffers[r] for r in rs.tiles])
                    for r, buf in zip(rs.tiles, kept):
                        residency.retain_local(rs.uid, r.i, r.j, buf)
                    retained += len(kept)

        if self._staged_keys:
            self.staging.retain(self._staged_keys)
        self.spans = tracer.drain()
        self.stats.update({
            "peak_buffer_bytes": arena.peak_bytes,
            "cur_buffer_bytes": arena.cur_bytes,
            "slabs_alloc": arena.slabs_alloc,
            "buffers_freed": arena.slabs_freed,
            "tasks_run": tasks_run,
            "waves": len(waves),
            "gather_bytes": gather_bytes,
            "retained_tiles": retained,
        })
        if not outs:
            return None
        return outs[0] if len(outs) == 1 else outs


def predict_wave_makespan(g: TaskGraph, spec: ClusterSpec, tm: TimeModel,
                          waves: Optional[List[List[int]]] = None,
                          dtypes: Optional[Dict[int, object]] = None,
                          cost: Optional[CostCache] = None) -> float:
    """Predicted wall-clock of wave-batched execution under ``tm``.

    Waves run back-to-back; each group costs one
    ``tm.batch_dispatch_overhead`` plus its summed per-slice kernel time
    spread over the node's worker parallelism (stacked BLAS keeps every
    core busy).  Compare with the per-task simulated makespan — which pays
    ``tm.dispatch_overhead`` per task — to pick an executor strategy.
    """
    waves = waves or build_waves(g)
    dtypes = dtypes or {}
    cost = cost or CostCache(tm, spec)
    # the wave executor runs in ONE process: its parallelism is the widest
    # node's worker count (equals ``worker_procs`` on homogeneous specs;
    # heterogeneous specs must not be priced at the default 3)
    par = max(1, max(spec.workers_at(n) for n in range(spec.n_nodes)))
    total = 0.0
    for wave in waves:
        for (key, tasks) in group_wave(g, wave, dtypes):
            kind = key[0]
            if kind is TaskKind.TAKECOPY:
                continue
            if kind in (TaskKind.CALLOC, TaskKind.RESIDENT):
                total += 1e-6      # calloc slab / resident bind: near-free
                continue
            kern = sum(cost.kernel(t) for t in tasks)
            total += tm.batch_dispatch_overhead + kern / par
    return total
