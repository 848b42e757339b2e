"""The paper's CTR prediction network (Figure 1) + the LR baseline.

Sparse one/multi-hot features -> embedding rows (through the hierarchical
PS working table) -> per-slot sum pooling -> fully-connected tower ->
sigmoid CTR. The embedding rows are the "sparse parameters" managed by
HBM/MEM/SSD-PS; the tower is the small dense part pinned in HBM.

Inputs are padded sparse rows (per table/slot group):
  slots_ids  int32 [B, nnz]  — working-slot ids (renumbered keys)
  slot_of    int32 [B, nnz]  — which feature slot each nonzero belongs to
  valid      bool  [B, nnz]

Heterogeneous embedding widths (``CTRConfig.slot_groups``): each slot
group is backed by its own named PS table (its own working table at its
own ``emb_dim``); ``forward_grouped`` pools every group at its native
width and concatenates into the tower — the multi-table co-hosting layout
of production ads systems.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.ctr_models import CTRConfig
from repro.kernels import ops as kops
from repro.models.common import ParamSpec, init_params


def tower_schema(cfg: CTRConfig) -> dict:
    dims = (cfg.pooled_dim,) + tuple(cfg.mlp_hidden) + (1,)
    out = {}
    for i, (a, b) in enumerate(zip(dims[:-1], dims[1:])):
        out[f"w{i}"] = ParamSpec((a, b), ("embed", "mlp"), fan_axis=0)
        out[f"b{i}"] = ParamSpec((b,), (None,), init="zeros")
    return out


def init_tower(cfg: CTRConfig, rng: jax.Array):
    return init_params(tower_schema(cfg), rng)


def embed_pool(
    working_table: jax.Array,  # [n_working, emb_dim]
    slot_ids: jax.Array,  # [B, nnz]
    slot_of: jax.Array,  # [B, nnz]
    valid: jax.Array,  # [B, nnz]
    n_slots: int,
) -> jax.Array:
    """Sum-pool embedding rows into per-slot buckets -> [B, n_slots*emb].

    One fused embedding-bag op (``kernels.ops.embedding_bag``): gather and
    per-slot pooling in a single pass, custom VJP through ``scatter_add``.
    The semantic contract is ``kernels.ref.embedding_bag_ref`` (the seed's
    one-hot/einsum math)."""
    B = slot_ids.shape[0]
    pooled = kops.embedding_bag(working_table, slot_ids, slot_of, valid, n_slots)
    return pooled.reshape(B, -1)


def _tower_mlp(tower, h: jax.Array) -> jax.Array:
    """The shared fully-connected tower: pooled features -> logits [B]."""
    n = len([k for k in tower if k.startswith("w")])
    with jax.named_scope("tower"):
        for i in range(n):
            h = h @ tower[f"w{i}"] + tower[f"b{i}"]
            if i < n - 1:
                h = jax.nn.relu(h)
        return h[:, 0]


def _bce_with_logits(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Numerically-stable mean binary cross-entropy."""
    return jnp.mean(
        jnp.maximum(logits, 0) - logits * labels + jnp.log1p(jnp.exp(-jnp.abs(logits)))
    )


def forward(
    cfg: CTRConfig,
    tower,
    working_table: jax.Array,
    slot_ids: jax.Array,
    slot_of: jax.Array,
    valid: jax.Array,
) -> jax.Array:
    """Returns CTR logits [B]."""
    return _tower_mlp(tower, embed_pool(working_table, slot_ids, slot_of, valid, cfg.n_slots))


def loss_fn(cfg, tower, working_table, slot_ids, slot_of, valid, labels) -> jax.Array:
    """Mean BCE-with-logits."""
    return _bce_with_logits(
        forward(cfg, tower, working_table, slot_ids, slot_of, valid), labels
    )


# --------------------------------------------------------------------------
# heterogeneous slot groups: one working table per group, own emb width
# --------------------------------------------------------------------------


def forward_grouped(cfg, tower, tables: dict, inputs: dict) -> jax.Array:
    """Multi-table forward: ``tables[g.name]`` is that group's working
    table [n_working_g, emb_g]; ``inputs[g.name]`` holds the group's padded
    sparse triple ``{"slot_ids", "slot_of", "valid"}`` (slot_of indexes
    *within* the group). Pools each group at its native width, concatenates
    across groups, then runs the shared tower. Returns CTR logits [B]."""
    pooled = []
    for g in cfg.groups:
        inp = inputs[g.name]
        pooled.append(
            embed_pool(
                tables[g.name], inp["slot_ids"], inp["slot_of"], inp["valid"], g.n_slots
            )
        )
    return _tower_mlp(tower, jnp.concatenate(pooled, axis=-1))


def loss_fn_grouped(cfg, tower, tables: dict, inputs: dict, labels) -> jax.Array:
    """Mean BCE-with-logits over the grouped forward."""
    return _bce_with_logits(forward_grouped(cfg, tower, tables, inputs), labels)


# --------------------------------------------------------------------------
# LR baseline (Tables 1-2): one weight per sparse feature, same PS machinery
# --------------------------------------------------------------------------


def lr_forward(working_table: jax.Array, slot_ids: jax.Array, valid: jax.Array, bias: jax.Array) -> jax.Array:
    """working_table: [n_working, 1] per-feature weights. Returns logits [B].

    An embedding bag with one slot of width 1: the pooled [B, 1, 1] sum of
    active feature weights IS the linear score."""
    pooled = kops.embedding_bag(working_table, slot_ids, jnp.zeros_like(slot_ids), valid, 1)
    return pooled[:, 0, 0] + bias


def lr_loss_fn(working_table, slot_ids, valid, labels, bias) -> jax.Array:
    return _bce_with_logits(lr_forward(working_table, slot_ids, valid, bias), labels)
