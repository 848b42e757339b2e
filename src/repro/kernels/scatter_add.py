"""Pallas TPU kernel: scatter-accumulate into the working table (``accumulate``).

The push half of the paper's HBM-PS hash-table ``accumulate``: gradient rows
are added into their working-table rows. GPUs use atomics; TPUs have no
global atomics, so we make collisions *structurally* race-free instead:

* the wrapper sorts ids (duplicates become consecutive grid steps);
* the TPU grid is sequential, and Pallas keeps an output block resident in
  VMEM while its block index is unchanged — consecutive duplicate rows
  accumulate in VMEM and write back to HBM once;
* ``input_output_aliases`` makes the update in-place in HBM.

Grid: (B, D // block_d); out block = table row ids[i], d-tile j. Table
and grads are viewed as ``[rows, 1, D]`` so that one row is a whole
trailing ``(1, block_d)`` block, which Mosaic accepts for lane-aligned
``block_d``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_D = 2048


def _scatter_kernel(ids_ref, grad_ref, table_ref, out_ref):
    i = pl.program_id(0)
    prev = ids_ref[jnp.maximum(i - 1, 0)]
    first_visit = jnp.logical_or(i == 0, ids_ref[i] != prev)

    @pl.when(first_visit)
    def _():
        out_ref[...] = table_ref[...] + grad_ref[...].astype(table_ref.dtype)

    @pl.when(jnp.logical_not(first_visit))
    def _():
        out_ref[...] = out_ref[...] + grad_ref[...].astype(table_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def scatter_add_pallas(
    table: jax.Array,  # [N, D]
    ids: jax.Array,  # [B] int32 — MUST be sorted (wrapper sorts)
    grads: jax.Array,  # [B, D]
    *,
    block_d: int = DEFAULT_BLOCK_D,
    interpret: bool = False,
) -> jax.Array:
    N, D = table.shape
    B = ids.shape[0]
    bd = min(block_d, D)
    assert D % bd == 0, f"D={D} must tile by block_d={bd}"
    grid = (B, D // bd)
    return pl.pallas_call(
        _scatter_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((None, 1, bd), lambda i, j, ids: (i, 0, j)),  # grads
                pl.BlockSpec((None, 1, bd), lambda i, j, ids: (ids[i], 0, j)),  # table in
            ],
            out_specs=pl.BlockSpec((None, 1, bd), lambda i, j, ids: (ids[i], 0, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((N, 1, D), table.dtype),
        input_output_aliases={2: 0},
        interpret=interpret,
    )(ids.astype(jnp.int32), grads.reshape(B, 1, D), table.reshape(N, 1, D)).reshape(N, D)
