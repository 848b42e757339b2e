"""Pallas TPU kernel: fused row-wise Adagrad on working-set rows.

The paper's CTR optimizer applies a per-row adaptive update to every pulled
working row. Unfused this is 4 HBM round-trips (read p, read a, write p,
write a) plus 3 elementwise kernels; fused it is a single VMEM pass:

    a' = a + g*g ;  p' = p - lr * g / (sqrt(a') + eps)

Grid tiles rows x d; params/accum are aliased in-place.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_ROWS = 256
DEFAULT_BLOCK_D = 512


def tiles(B: int, D: int) -> bool:
    """Whether a [B, D] operand tiles by the kernel's default blocks and by
    the TPU's (8, 128) vreg tile."""
    br, bd = min(DEFAULT_BLOCK_ROWS, B), min(DEFAULT_BLOCK_D, D)
    return B % 8 == 0 and D % 128 == 0 and B % br == 0 and D % bd == 0


def _adagrad_kernel(p_ref, a_ref, g_ref, lr_ref, po_ref, ao_ref, *, eps):
    g = g_ref[...].astype(jnp.float32)
    a = a_ref[...] + g * g
    ao_ref[...] = a
    lr = lr_ref[0, 0]
    po_ref[...] = (p_ref[...].astype(jnp.float32) - lr * g / (jnp.sqrt(a) + eps)).astype(po_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "block_d", "eps", "interpret"))
def adagrad_pallas(
    params: jax.Array,  # [B, D]
    accum: jax.Array,  # [B, D] float32
    grads: jax.Array,  # [B, D]
    lr: jax.Array | float,
    *,
    eps: float = 1e-8,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    block_d: int = DEFAULT_BLOCK_D,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    B, D = params.shape
    br = min(block_rows, B)
    bd = min(block_d, D)
    assert B % br == 0 and D % bd == 0, f"({B},{D}) must tile by ({br},{bd})"
    lr_arr = jnp.asarray(lr, dtype=jnp.float32).reshape(1, 1)
    grid = (B // br, D // bd)
    blk = pl.BlockSpec((br, bd), lambda i, j: (i, j))
    p_new, a_new = pl.pallas_call(
        functools.partial(_adagrad_kernel, eps=eps),
        grid=grid,
        in_specs=[
            blk,
            blk,
            blk,
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),  # lr: replicated scalar
        ],
        out_specs=[blk, blk],
        out_shape=[
            jax.ShapeDtypeStruct((B, D), params.dtype),
            jax.ShapeDtypeStruct((B, D), jnp.float32),
        ],
        input_output_aliases={0: 0, 1: 1},
        interpret=interpret,
    )(params, accum, grads, lr_arr)
    return p_new, a_new
