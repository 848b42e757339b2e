"""Pallas TPU kernel: fused embedding-bag — row gather + per-slot sum-pool.

The device hot op of the paper's CTR network: every example gathers its
``nnz`` working-table rows and sum-pools them into per-feature-slot buckets.
Unfused (the seed path) this materializes a ``[B, nnz, emb]`` gather *and* a
dense ``[B, nnz, n_slots]`` one-hot, then pools with an einsum — a dense
matmul doing a segment-sum's job, with ``B*nnz*n_slots*emb`` MACs and three
HBM-sized intermediates. Fused, neither intermediate ever exists:

* ids / slot_of / valid arrive via **scalar prefetch**, so the BlockSpec
  ``index_map`` addresses the HBM table row directly — the Pallas pipeline
  turns the gather into async HBM->VMEM DMAs overlapped with compute. The
  table is viewed as ``[N, 1, D]`` so that one row is a whole trailing
  ``(1, block_d)`` block: Mosaic refuses a ``(1, block_d)`` block of a 2-D
  array, whose second-minor block dim must be a multiple of 8;
* each grid step adds one (row, d-tile) into its example's pooled
  ``[n_slots, block_d]`` output tile via a VPU masked add (iota == slot);
* the output tile stays **VMEM-resident** across an example's ``nnz`` steps
  (the grid revisits the same output block consecutively — the same
  residency contract the scatter_add kernel uses) and is written back to
  HBM once per (example, d-tile).

Cost: ``B*nnz*emb`` adds and ``(B*nnz + B*n_slots) * emb`` HBM bytes — vs
the seed path's dense ``B*nnz*n_slots*emb`` matmul.

Grid: (B, D // block_d, nnz) — nnz innermost so the pooled tile for
(example i, d-tile j) is revisited consecutively.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_D = 512


def _bag_kernel(ids_ref, slot_ref, valid_ref, row_ref, out_ref, *, nnz, n_slots):
    i = pl.program_id(0)
    n = pl.program_id(2)
    t = i * nnz + n
    s = slot_ref[t]
    v = valid_ref[t]
    row = row_ref[0, :].astype(jnp.float32) * v.astype(jnp.float32)
    # VPU masked add: route the row into its slot without a one-hot matmul.
    # The pooled tile is f32 regardless of table dtype — nnz-step partial
    # sums must not round to bf16 (the wrapper casts once at the end).
    sel = jax.lax.broadcasted_iota(jnp.int32, (n_slots, 1), 0) == s
    contrib = jnp.where(sel, row[None, :], 0.0)

    @pl.when(n == 0)
    def _():
        out_ref[0] = contrib

    @pl.when(n > 0)
    def _():
        out_ref[0] = out_ref[0] + contrib


@functools.partial(jax.jit, static_argnames=("n_slots", "block_d", "interpret"))
def embedding_bag_pallas(
    table: jax.Array,  # [N, D] float32/bf16 working table
    slot_ids: jax.Array,  # [B, nnz] int32 — working-slot row ids
    slot_of: jax.Array,  # [B, nnz] int32 — pooling bucket per nonzero
    valid: jax.Array,  # [B, nnz] padding mask (non-bool treated as != 0)
    *,
    n_slots: int,
    block_d: int = DEFAULT_BLOCK_D,
    interpret: bool = False,
) -> jax.Array:
    """Fused gather + per-(example, slot) sum-pool -> [B, n_slots, D]."""
    N, D = table.shape
    B, nnz = slot_ids.shape
    bd = math.gcd(D, block_d)  # largest tile that both divides D and fits
    grid = (B, D // bd, nnz)
    kernel = functools.partial(_bag_kernel, nnz=nnz, n_slots=n_slots)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                # table row for (example i, nonzero n), d-tile j
                pl.BlockSpec(
                    (None, 1, bd), lambda i, j, n, ids, slots, vals: (ids[i * nnz + n], 0, j)
                ),
            ],
            # pooled tile: constant over the innermost nnz axis -> resident
            out_specs=pl.BlockSpec((1, n_slots, bd), lambda i, j, n, ids, slots, vals: (i, 0, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((B, n_slots, D), jnp.float32),
        interpret=interpret,
    )(
        slot_ids.reshape(-1).astype(jnp.int32),
        slot_of.reshape(-1).astype(jnp.int32),
        # mask semantics, not weights: != 0 keeps float masks from silently
        # truncating differently than the ref/portable paths
        (valid.reshape(-1) != 0).astype(jnp.int32),
        table.reshape(N, 1, D),
    )
    return out.astype(table.dtype)
