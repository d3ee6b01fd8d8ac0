"""Compile the device path's Pallas kernels for a described TPU v5e.

No chip is needed: the TPU compiler compiles for a topology that is
described, not attached, and refuses what the chip's compiler would refuse
(unaligned blocks, too much VMEM) — which interpret mode cannot show.  The
cases are the calls ``chip_smoke.py`` makes at its real tile (1024):

* the wave executor's vmapped ADDMUL group;
* the 1024 x 1 mat-vec group (``block_n = 1``);
* the fused-epilogue group with ``relu`` and a bfloat16 store (the mixed
  precision tier).

The wrappers would see this process's CPU backend and pick interpret mode,
so every call passes ``interpret=False`` itself.  The topology is described
inside a fixture, never at import: only one process at a time may load the
TPU library.
"""
import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops as kops

TILE = 1024
GROUP = 8


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # no compiler logs in /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _stack(sharding, *shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct((GROUP,) + shape, dtype, sharding=sharding)


# (case, (block_m, block_n, block_k), (c, a, b) tile shapes, epilogue,
#  store dtype) — blocks as ops._resolve_blocks picks them for the tile
CASES = [
    ("addmul_group", (128, 128, 128),
     ((TILE, TILE), (TILE, TILE), (TILE, TILE)), None, None),
    ("matvec_group", (128, 1, 128),
     ((TILE, 1), (TILE, TILE), (TILE, 1)), None, None),
    ("relu_bf16_epilogue_group", (128, 128, 128),
     ((TILE, TILE), (TILE, TILE), (TILE, TILE)),
     (("in", 0), ("ewise", "relu", 0)), np.dtype(ml_dtypes.bfloat16)),
]


@pytest.mark.parametrize("name,blocks,shapes,prog,store", CASES,
                         ids=[c[0] for c in CASES])
def test_group_call_compiles_for_v5e(one_chip, name, blocks, shapes, prog,
                                     store):
    assert blocks == kops._resolve_blocks(None, None, None, shapes[1][0],
                                          shapes[2][1], shapes[1][1])
    fn = kops._addmul_batched_fn(*blocks, False, prog=prog,
                                 out_dtype=store)
    args = [_stack(one_chip, *s) for s in shapes]
    compiled = fn.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    (out,) = jax.tree.leaves(compiled.out_info)
    assert out.shape == (GROUP,) + shapes[0]
    assert out.dtype == (store if store is not None else jnp.float32)
