"""Pallas TPU kernel: dynamic embedding-row gather (HBM-PS ``get``/pull).

The hot device-side op of the paper's HBM-PS: fetch the rows of the working
parameter table referenced by a mini-batch. The table stays in HBM; rows
stream through VMEM one (row, d-tile) block per grid step. Row ids arrive via
scalar prefetch so the BlockSpec ``index_map`` can address HBM blocks
directly — the Pallas pipeline turns this into async HBM->VMEM DMAs that
overlap with the copy of the previous block (the TPU analogue of the paper's
NVLink peer-to-peer ``get``).

Grid: (B, D // block_d). Block (1, block_d) of the table at row ids[i]; the
table is viewed as ``[N, 1, D]`` so that the row is a whole trailing
block, which Mosaic accepts for lane-aligned ``block_d``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_D = 2048


def _gather_kernel(ids_ref, table_ref, out_ref):
    # the pipeline already fetched the right (row, tile) block; pure copy.
    out_ref[...] = table_ref[...]


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def embedding_lookup_pallas(
    table: jax.Array,  # [N, D] float32/bf16, D multiple of 128
    ids: jax.Array,  # [B] int32
    *,
    block_d: int = DEFAULT_BLOCK_D,
    interpret: bool = False,
) -> jax.Array:
    N, D = table.shape
    (B,) = ids.shape
    bd = min(block_d, D)
    assert D % bd == 0, f"D={D} must tile by block_d={bd}"
    grid = (B, D // bd)
    return pl.pallas_call(
        _gather_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[pl.BlockSpec((None, 1, bd), lambda i, j, ids: (ids[i], 0, j))],
            out_specs=pl.BlockSpec((None, 1, bd), lambda i, j, ids: (i, 0, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((B, 1, D), table.dtype),
        interpret=interpret,
    )(ids.astype(jnp.int32), table.reshape(N, 1, D)).reshape(B, D)
