"""Public jit'd wrappers over the Pallas kernels, with portable fallbacks.

Dispatch policy: the TPU kernels are the *target*. Off the TPU they run
only where a test asks for ``interpret=True``; production code paths there
call the portable implementations, which lower on any backend with the
same math:

* ``embedding_lookup`` / ``scatter_add`` / ``adagrad_update`` — jnp gather /
  sorted-segment add / fused arithmetic (XLA fuses these well on TPU too;
  the Pallas versions additionally avoid touching non-working rows).
* ``embedding_bag`` — fused gather + per-(example, slot) sum-pool with a
  custom VJP (backward goes straight through ``scatter_add``); the portable
  path is a segment-sum, never the dense one-hot/einsum chain.
* ``attention`` — ``impl='flash'`` (Pallas kernel, recompute-vjp),
  ``'blockwise'`` (lax.scan streaming softmax: O(S*block) memory, compiles
  everywhere — what the multi-pod dry-run lowers), ``'naive'`` (materializes
  scores; small shapes / decode).

The row kernels (``embedding_lookup``, ``embedding_bag``, ``scatter_add``)
take Pallas on the TPU only where Mosaic accepts them: see
:func:`row_kernel_is_pallas`.
"""

from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp

import numpy as np

from repro.kernels import fused_adagrad
from repro.kernels import ref as _ref
from repro.kernels.embedding_bag import embedding_bag_pallas
from repro.kernels.embedding_lookup import embedding_lookup_pallas
from repro.kernels.feature_extract import (
    feature_extract_pallas,
    feature_extract_portable,
)
from repro.kernels.fused_adagrad import adagrad_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.scatter_add import scatter_add_pallas
from repro.kernels.topk_mips import topk_mips_pallas
from repro.metrics import Counters


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


LANES = 128  # one vreg lane tile: the narrowest row block Mosaic accepts
# Scalar-prefetched id streams live in SMEM, 1 MiB on v5e; the kernel's own
# scalars need room too, so the ids may take half of it.
SMEM_ID_BYTES = 1 << 19

# Process-wide dispatch counts. The row-kernel choice is made in Python, so
# under jit it counts once per trace, not once per call.
COUNTERS = Counters("row_kernel_xla")


def row_kernel_is_pallas(width: int, prefetch_bytes: int) -> bool:
    """Whether a row kernel (``embedding_lookup``, ``embedding_bag``,
    ``scatter_add``) runs as Pallas for ``width``-wide rows and
    ``prefetch_bytes`` of scalar-prefetched ids.

    The rule: Pallas on the TPU when the rows are a whole number of
    128-lane tiles and the ids fit in ``SMEM_ID_BYTES``; the XLA
    formulation otherwise, on every backend. Mosaic refuses row blocks
    narrower than a lane tile — every CTR table is 8 wide — and refuses a
    prefetch larger than SMEM. Each XLA pick that this rule forces counts
    under ``row_kernel_xla`` in :data:`COUNTERS`.
    """
    if width % LANES or prefetch_bytes > SMEM_ID_BYTES:
        COUNTERS.inc("row_kernel_xla")
        return False
    return _on_tpu()


# §Perf toggles (beyond-paper optimizations; see EXPERIMENTS.md).
# RECOMPUTE_ATTN: recompute-vjp attention — backward re-runs the streaming
#   softmax instead of storing per-KV-block (s, p) scan residuals. Dominant
#   memory-term win for long-sequence training.
# BANDED_WINDOW: sliding-window attention as banded chunks (q chunk attends
#   its [2W] neighborhood) instead of masking every KV block — cuts window
#   attention FLOPs and bytes by ~S/(2W).
RECOMPUTE_ATTN = True
BANDED_WINDOW = True


# --------------------------------------------------------------------------
# embedding lookup / scatter / optimizer
# --------------------------------------------------------------------------


def embedding_lookup(table, ids, *, use_pallas: bool | None = None, interpret: bool | None = None):
    if use_pallas is None:
        use_pallas = row_kernel_is_pallas(table.shape[1], 4 * ids.size)
    if use_pallas:
        return embedding_lookup_pallas(table, ids, interpret=not _on_tpu() if interpret is None else interpret)
    return _ref.embedding_lookup_ref(table, ids)


def scatter_add(
    table, ids, grads, *,
    assume_sorted: bool = False,
    use_pallas: bool | None = None,
    interpret: bool | None = None,
):
    """``table[ids[i]] += grads[i]`` with duplicates accumulating.

    The Pallas kernel needs duplicate ids consecutive, so the wrapper sorts
    by default. Callers whose ids are already sorted (the MEM-PS emits
    sorted-unique working sets; the embedding-bag VJP sorts once itself)
    pass ``assume_sorted=True`` to skip the redundant argsort+gathers.
    """
    if use_pallas is None:
        use_pallas = row_kernel_is_pallas(table.shape[1], 4 * ids.size)
    if use_pallas:
        if not assume_sorted:
            order = jnp.argsort(ids)  # duplicates must be consecutive for the kernel
            ids, grads = ids[order], grads[order]
        return scatter_add_pallas(
            table, ids, grads,
            interpret=not _on_tpu() if interpret is None else interpret,
        )
    return _ref.scatter_add_ref(table, ids, grads)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def adagrad_update(params, accum, grads, lr, *, eps: float = 1e-8, use_pallas: bool | None = None, interpret: bool | None = None):
    """Fused row-Adagrad on the pulled working set.

    Working sets are sized by the batch's unique keys, so their shapes
    rarely tile by the kernel's blocks. The update is purely elementwise, so
    the wrapper runs any other [B, D] shape on its transposed view [D, B],
    padded to whole blocks: lane-dense along the working rows, and within
    one block of padding per dimension. Every shape takes the fused Pallas
    path. The view is transposed because a row-major flat repack to
    [B*D/128, 128] makes XLA materialize the 8-wide CTR tables 16x
    lane-padded inside the train step's scan (4.7 GB of temporaries and a
    140 s compile for a v5e at model C's working set).
    """
    if use_pallas is None:
        use_pallas = _on_tpu()
    if not use_pallas:
        return _ref.adagrad_ref(params, accum, grads, lr, eps)
    interpret = not _on_tpu() if interpret is None else interpret
    B, D = params.shape
    if fused_adagrad.tiles(B, D):
        return adagrad_pallas(params, accum, grads, lr, eps=eps, interpret=interpret)
    block_rows = fused_adagrad.DEFAULT_BLOCK_ROWS
    rows = _round_up(D, 8)
    if rows > block_rows:
        rows = _round_up(rows, block_rows)
    # keep the kernel's default block size in elements
    block_elems = block_rows * fused_adagrad.DEFAULT_BLOCK_D
    block_d = min(max(LANES, block_elems // rows // LANES * LANES), _round_up(B, LANES))
    cols = _round_up(B, block_d)
    view = lambda x: jnp.pad(x.T, ((0, rows - D), (0, cols - B)))
    p_new, a_new = adagrad_pallas(
        view(params), view(accum), view(grads), lr,
        eps=eps, block_d=block_d, interpret=interpret,
    )
    unview = lambda x: x[:D, :B].T
    return unview(p_new), unview(a_new)


# --------------------------------------------------------------------------
# fused embedding-bag: gather + per-(example, slot) sum-pool, custom VJP
# --------------------------------------------------------------------------


def _embedding_bag_segment(table, slot_ids, slot_of, valid, n_slots):
    """Portable fallback: flat gather + segment-sum over (example, slot)
    buckets. No ``[B, nnz, n_slots]`` one-hot, no dense pooling matmul —
    XLA lowers this to a gather fused into a segment reduction on any
    backend."""
    B, nnz = slot_ids.shape
    # f32 partial sums regardless of table dtype — matches the Pallas
    # kernel's accumulator so TPU and portable runs pool identically
    rows = jnp.take(table, slot_ids.reshape(-1), axis=0).astype(jnp.float32)
    rows = rows * valid.reshape(-1, 1).astype(jnp.float32)
    seg = (jnp.arange(B, dtype=jnp.int32)[:, None] * n_slots + slot_of).reshape(-1)
    pooled = jax.ops.segment_sum(rows, seg, num_segments=B * n_slots)
    return pooled.reshape(B, n_slots, table.shape[1]).astype(table.dtype)


@jax.named_scope("emb_bag_fwd")
def _embedding_bag_impl(table, slot_ids, slot_of, valid, n_slots, use_pallas, interpret):
    if use_pallas:
        return embedding_bag_pallas(
            table, slot_ids, slot_of, valid, n_slots=n_slots, interpret=interpret
        )
    return _embedding_bag_segment(table, slot_ids, slot_of, valid, n_slots)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _embedding_bag(table, slot_ids, slot_of, valid, n_slots, use_pallas, interpret):
    return _embedding_bag_impl(table, slot_ids, slot_of, valid, n_slots, use_pallas, interpret)


def _embedding_bag_fwd(table, slot_ids, slot_of, valid, n_slots, use_pallas, interpret):
    out = _embedding_bag_impl(table, slot_ids, slot_of, valid, n_slots, use_pallas, interpret)
    return out, (table, slot_ids, slot_of, valid)


@jax.named_scope("emb_bag_bwd")
def _embedding_bag_bwd(n_slots, use_pallas, interpret, res, g):
    """Working-table cotangent without autodiff's dense intermediate chain:
    route each nonzero's pooled gradient back to its row (a [B, nnz, emb]
    take_along_axis instead of a one-hot matmul transpose) and scatter-add
    into the table. The kernel path sorts at this boundary and passes
    ``assume_sorted=True`` — same work as the wrapper's default sort, but
    the backward owns its ids ordering (batch ids are never pre-sorted) and
    the portable path skips sorting entirely."""
    table, slot_ids, slot_of, valid = res
    grad_rows = jnp.take_along_axis(g, slot_of[:, :, None].astype(jnp.int32), axis=1)
    grad_rows = grad_rows * valid[..., None].astype(g.dtype)
    flat_ids = slot_ids.reshape(-1)
    flat_grads = grad_rows.reshape(-1, table.shape[1])
    zeros = jnp.zeros_like(table)
    if use_pallas:
        order = jnp.argsort(flat_ids)  # one sort; kernel needs dups adjacent
        d_table = scatter_add(
            zeros, flat_ids[order], flat_grads[order],
            assume_sorted=True, use_pallas=True, interpret=interpret,
        )
    else:
        d_table = _ref.scatter_add_ref(zeros, flat_ids, flat_grads)
    f0 = lambda x: np.zeros(x.shape, jax.dtypes.float0)  # int/bool cotangents
    return d_table, f0(slot_ids), f0(slot_of), f0(valid)


_embedding_bag.defvjp(_embedding_bag_fwd, _embedding_bag_bwd)


def embedding_bag(
    table,  # [N, emb] working table
    slot_ids,  # [B, nnz] int32 working-slot row ids
    slot_of,  # [B, nnz] int32 pooling bucket per nonzero
    valid,  # [B, nnz] padding mask (cast to bool: mask semantics, not weights)
    n_slots: int,
    *,
    use_pallas: bool | None = None,
    interpret: bool | None = None,
):
    """Fused gather + per-(example, slot) sum-pool -> [B, n_slots, emb].

    THE device lookup+pool primitive for CTR training and serving: the
    Pallas kernel (one VMEM pass, nothing materialized) where
    :func:`row_kernel_is_pallas` allows it, elsewhere the segment-sum
    formulation — both under a custom VJP whose backward emits
    working-table cotangents straight through ``scatter_add``. The forward
    prefetches three id streams (ids, slots, mask).
    """
    if use_pallas is None:
        use_pallas = row_kernel_is_pallas(table.shape[1], 3 * 4 * slot_ids.size)
    if interpret is None:
        interpret = not _on_tpu()
    valid = valid.astype(jnp.bool_)  # all three impls see identical mask math
    return _embedding_bag(
        table, slot_ids, slot_of, valid, int(n_slots), bool(use_pallas), bool(interpret)
    )


# --------------------------------------------------------------------------
# streaming feature extraction (ingest subsystem, DESIGN.md §11)
# --------------------------------------------------------------------------


def feature_extract(
    raw_lo,  # [B, P] uint32 — low half of the unhashed raw feature ids
    raw_hi,  # [B, P] uint32 — high half
    valid,  # [B, P] padding mask (cast to bool)
    *,
    n_keys: int,
    n_slots: int,
    key_seed: int = 17,
    slot_seed: int = 31,
    use_pallas: bool | None = None,
    interpret: bool | None = None,
):
    """Device feature extraction:
    raw ids -> (keys_hi u32, keys_lo u32, slot_of i32).

    The ingest pipeline's hot op: two rounds of splitmix64 (as u32-pair
    math — TPUs have no 64-bit lanes) plus a modulo each, bitwise-equal to
    the host feeder's ``hash_keys(raw) % n_keys`` / ``% n_slots`` numpy
    path. Keys come back as a u32 pair (``hi << 32 | lo`` on host) so
    ``n_keys`` may exceed 2^32 — paper-scale 1e11-key spaces; for small
    key spaces the hi plane is identically zero. Padded positions come
    back as key 0 / slot 0.
    """
    if use_pallas is None:
        use_pallas = _on_tpu()
    if use_pallas:
        return feature_extract_pallas(
            raw_lo, raw_hi, valid,
            n_keys=n_keys, n_slots=n_slots,
            key_seed=key_seed, slot_seed=slot_seed,
            interpret=not _on_tpu() if interpret is None else interpret,
        )
    return feature_extract_portable(
        raw_lo, raw_hi, valid,
        n_keys=n_keys, n_slots=n_slots,
        key_seed=key_seed, slot_seed=slot_seed,
    )


# --------------------------------------------------------------------------
# blocked top-k MIPS (retrieval subsystem, DESIGN.md §12)
# --------------------------------------------------------------------------


def topk_mips(
    queries,  # [Q, D] f32 query vectors
    corpus,  # [N, D] f32 corpus rows (row i = corpus id i)
    k: int,
    *,
    n_valid: int | None = None,
    block_q: int = 128,
    block_n: int = 512,
    use_pallas: bool | None = None,
    interpret: bool | None = None,
):
    """Top-k maximum-inner-product search -> (scores [Q, k], indices [Q, k]).

    The retrieval subsystem's scoring op: on TPU the blocked Pallas kernel
    (corpus streams through the MXU, running top-k stays VMEM-resident),
    elsewhere the full-score-matrix oracle. Both follow the same contract:
    descending score, ties by ascending corpus index, positions past the
    live corpus (``n_valid``, default all of ``corpus``) come back as
    (-inf, -1).
    """
    if use_pallas is None:
        use_pallas = _on_tpu()
    if use_pallas:
        return topk_mips_pallas(
            queries, corpus, int(k),
            n_valid=None if n_valid is None else int(n_valid),
            block_q=block_q, block_n=block_n,
            interpret=not _on_tpu() if interpret is None else interpret,
        )
    return _ref.topk_mips_ref(queries, corpus, int(k), n_valid=n_valid)


# --------------------------------------------------------------------------
# grouped matmul (MoE expert compute)
# --------------------------------------------------------------------------


def gmm(x, w, group_sizes, *, block_t: int = 128, use_pallas: bool | None = None, interpret: bool | None = None):
    """Grouped matmul: rows of ``x`` are contiguous groups (sorted by
    expert); row t multiplies ``w[group_of(t)]``.

    The Pallas path pads each group to a ``block_t`` multiple (tiles never
    straddle experts) and streams only the weights each tile needs.
    """
    if use_pallas is None:
        use_pallas = _on_tpu()
    if not use_pallas:
        return _ref.gmm_ref(x, w, group_sizes)
    from repro.kernels.moe_gmm import gmm_pallas

    T, K = x.shape
    E = w.shape[0]
    padded = ((group_sizes + block_t - 1) // block_t) * block_t  # per group
    offs = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(padded)[:-1].astype(jnp.int32)])
    starts = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(group_sizes)[:-1].astype(jnp.int32)])
    Tp = int(T + E * (block_t - 1) + block_t - 1) // block_t * block_t  # static bound
    # scatter rows into their padded positions
    gid_of_row = jnp.searchsorted(jnp.cumsum(group_sizes), jnp.arange(T), side="right")
    dst = offs[gid_of_row] + (jnp.arange(T) - starts[gid_of_row])
    xp = jnp.zeros((Tp, K), x.dtype).at[dst].set(x)
    tile_gid = jnp.clip(
        jnp.searchsorted(jnp.cumsum(padded), jnp.arange(Tp // block_t) * block_t, side="right"),
        0, E - 1,
    )
    out_p = gmm_pallas(
        xp, w, tile_gid,
        block_t=block_t,
        interpret=not _on_tpu() if interpret is None else interpret,
    )
    return jnp.take(out_p, dst, axis=0)


# --------------------------------------------------------------------------
# attention
# --------------------------------------------------------------------------


def attention_blockwise(
    q: jax.Array,  # [B, H, Sq, Dh]
    k: jax.Array,  # [B, Hkv, Skv, Dh]
    v: jax.Array,  # [B, Hkv, Skv, Dh]
    *,
    causal: bool = True,
    window: int = 0,
    q_offset: int | jax.Array = 0,
    kv_len: int | jax.Array | None = None,
    block_k: int = 512,
) -> jax.Array:
    """Streaming-softmax attention via lax.scan over KV blocks.

    Memory O(Sq * block_k) instead of O(Sq * Skv); differentiable; lowers on
    any backend. GQA handled without materializing repeated KV.
    """
    B, H, Sq, Dh = q.shape
    _, Hkv, Skv, _ = k.shape
    rep = H // Hkv
    bk = min(block_k, Skv)
    if Skv % bk != 0:  # pad K/V to a block multiple; padded keys masked out
        pad = bk - Skv % bk
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        if kv_len is None:
            kv_len = Skv
        Skv = Skv + pad
    nk = Skv // bk
    scale = 1.0 / (Dh**0.5)

    qf = q.astype(jnp.float32).reshape(B, Hkv, rep, Sq, Dh)
    kb = k.astype(jnp.float32).reshape(B, Hkv, nk, bk, Dh).transpose(2, 0, 1, 3, 4)
    vb = v.astype(jnp.float32).reshape(B, Hkv, nk, bk, Dh).transpose(2, 0, 1, 3, 4)
    q_pos = q_offset + jnp.arange(Sq)

    def step(carry, inputs):
        m, l, acc = carry
        kblk, vblk, jk = inputs  # [B,Hkv,bk,Dh], [B,Hkv,bk,Dh], scalar
        s = jnp.einsum("bgrqd,bgkd->bgrqk", qf, kblk) * scale
        k_pos = jk * bk + jnp.arange(bk)
        mask = jnp.ones((Sq, bk), dtype=bool)
        if causal:
            mask = jnp.logical_and(mask, k_pos[None, :] <= q_pos[:, None])
        if window > 0:
            mask = jnp.logical_and(mask, k_pos[None, :] > q_pos[:, None] - window)
        if kv_len is not None:
            mask = jnp.logical_and(mask, (k_pos[None, :] < kv_len))
        s = jnp.where(mask[None, None, None], s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(axis=-1))
        # guard -inf - -inf for fully masked rows
        m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(mask[None, None, None], p, 0.0)
        corr = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - m_safe))
        l_new = l * corr + p.sum(axis=-1)
        pv = jnp.einsum("bgrqk,bgkd->bgrqd", p, vblk)
        acc_new = acc * corr[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, Hkv, rep, Sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, Hkv, rep, Sq), jnp.float32)
    acc0 = jnp.zeros((B, Hkv, rep, Sq, Dh), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, acc0), (kb, vb, jnp.arange(nk)))
    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = (acc / l_safe[..., None]).reshape(B, H, Sq, Dh)
    return out.astype(q.dtype)


def attention_banded(
    q: jax.Array,  # [B, H, S, Dh] — self-attention (Sq == Skv)
    k: jax.Array,
    v: jax.Array,
    *,
    window: int,
    block_k: int = 512,  # unused; kept for API parity
) -> jax.Array:
    """Causal sliding-window attention via banded chunks.

    q is split into chunks of size W=window; chunk i attends only keys in
    chunks [i-1, i] (exactly covers the (p-W, p] window), so compute and
    memory are O(S * 2W) instead of O(S^2) with masking — the TPU-native
    form of SWA (contiguous MXU tiles, no wasted masked blocks).
    """
    B, H, S, Dh = q.shape
    Hkv = k.shape[1]
    W = window
    if S % W != 0:  # pad sequence to a chunk multiple (tail masked)
        pad = W - S % W
        qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
        kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        return attention_banded(qp, kp, vp, window=W)[:, :, :S]
    n = S // W
    rep = H // Hkv
    qf = q.astype(jnp.float32).reshape(B, Hkv, rep, n, W, Dh)
    kc = k.astype(jnp.float32).reshape(B, Hkv, n, W, Dh)
    vc = v.astype(jnp.float32).reshape(B, Hkv, n, W, Dh)
    # neighborhood [i-1, i]: prepend a zero chunk for i = 0
    zeros = jnp.zeros_like(kc[:, :, :1])
    k2 = jnp.concatenate([jnp.concatenate([zeros, kc[:, :, :-1]], axis=2), kc], axis=3)
    v2 = jnp.concatenate([jnp.concatenate([zeros, vc[:, :, :-1]], axis=2), vc], axis=3)
    scale = 1.0 / (Dh**0.5)
    # NOTE(§Perf): a lax.scan over q chunks (one [W,2W] band live at a time)
    # was measured WORSE here — hymba t_memory 66.7 -> 81.3s, peak temp ~flat
    # (the peak is the global-attention layers, and the scan blocks fusion of
    # the band softmax). Kept as one einsum; the Pallas flash kernel with
    # window block-skipping is the real-TPU form with no HBM intermediates.
    s = jnp.einsum("bgrnqd,bgnkd->bgrnqk", qf, k2) * scale  # [.., W, 2W]
    qpos = jnp.arange(W)[:, None] + W  # position within the 2W band
    kpos = jnp.arange(2 * W)[None, :]
    first = jnp.arange(n) == 0
    mask = (kpos <= qpos) & (kpos > qpos - W)  # causal + window
    valid_prev = ~first[:, None, None]  # chunk 0 has no left neighbor
    mask = mask[None, :, :] & (valid_prev | (kpos[None] >= W))
    s = jnp.where(mask[None, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bgrnqk,bgnkd->bgrnqd", p, v2)
    return out.reshape(B, H, S, Dh).astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, window, q_offset, block_q, block_k):
    return flash_attention_pallas(
        q, k, v, causal=causal, window=window, q_offset=q_offset,
        block_q=block_q, block_k=block_k, interpret=not _on_tpu(),
    )


def _flash_fwd(q, k, v, causal, window, q_offset, block_q, block_k):
    return _flash(q, k, v, causal, window, q_offset, block_q, block_k), (q, k, v)


def _flash_bwd(causal, window, q_offset, block_q, block_k, res, g):
    q, k, v = res  # recompute blockwise (flash-style remat backward)
    _, vjp = jax.vjp(
        lambda q, k, v: attention_blockwise(
            q, k, v, causal=causal, window=window, q_offset=q_offset, block_k=block_k
        ),
        q, k, v,
    )
    return vjp(g)


_flash.defvjp(_flash_fwd, _flash_bwd)


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    q_offset: int | jax.Array = 0,
    kv_len: int | jax.Array | None = None,
    impl: Literal["auto", "naive", "blockwise", "flash"] = "auto",
    block_q: int = 128,
    block_k: int = 512,
) -> jax.Array:
    """Fused attention with GQA + causal/sliding-window masks.

    ``q_offset``/``kv_len`` may be traced scalars except under impl='flash'
    (the Pallas kernel specializes them statically).
    """
    Sq, Skv = q.shape[2], k.shape[2]
    if impl == "auto":
        if _on_tpu() and Sq >= 128 and isinstance(q_offset, int) and kv_len is None:
            impl = "flash"
        elif Sq * Skv > 2048 * 2048:
            impl = "blockwise"
        else:
            impl = "naive"
    if impl == "flash":
        assert kv_len is None and isinstance(q_offset, int), "flash needs static bounds"
        return _flash(q, k, v, causal, window, q_offset, block_q, min(block_k, 128))
    # banded fast path for full-sequence sliding-window self-attention
    if (
        BANDED_WINDOW
        and window > 0
        and causal
        and Sq == Skv
        and Sq > window
        and kv_len is None
        and isinstance(q_offset, int)
        and q_offset == 0
    ):
        fn = lambda q, k, v: attention_banded(q, k, v, window=window)
        if RECOMPUTE_ATTN:
            fn = jax.checkpoint(fn)
        return fn(q, k, v)
    if impl == "blockwise":
        fn = lambda q, k, v: attention_blockwise(
            q, k, v, causal=causal, window=window, q_offset=q_offset,
            kv_len=kv_len, block_k=block_k,
        )
        if RECOMPUTE_ATTN:
            # recompute-vjp: backward re-streams KV blocks instead of storing
            # per-block (s, p) residuals — the flash-attention memory trade
            fn = jax.checkpoint(fn)
        return fn(q, k, v)
    return _ref.attention_ref(
        q, k, v, causal=causal, window=window, q_offset=q_offset, kv_len=kv_len
    )
