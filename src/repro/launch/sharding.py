"""Logical-axis -> mesh-axis sharding rules (MaxText-style, config-aware).

``build_rules(cfg, mesh)`` decides, per logical axis name, which mesh axes
shard it — honoring divisibility (an axis that doesn't divide is replicated)
and never assigning one mesh axis to two dims of the same tensor
(``pspec`` drops repeats, first dim wins).

The strategy encoded here:
  * weights: tensor-parallel over ``model`` (heads/mlp/vocab/experts/ssm) +
    FSDP over (``pod``, ``data``) on the d_model dim -> every large tensor is
    2-D sharded and optimizer state scales to 512 chips;
  * activations: batch over (``pod``, ``data``); moe buffers over ``model``;
  * decode KV caches: kv-heads over ``model`` when divisible, else the cache
    *sequence* dim goes over ``model`` (flash-decode style sharded softmax —
    how a 5 TB nemotron cache fits).
"""

from __future__ import annotations

import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


from repro.configs import ArchConfig
from repro.models.common import (
    ParamSpec,
    set_embed_gather_fn,
    set_logical_constraint_fn,
)


def _axes_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    axes = (axes,) if isinstance(axes, str) else axes
    return math.prod(mesh.shape[a] for a in axes)


def data_axes(mesh: Mesh):
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def build_rules(cfg: ArchConfig, mesh: Mesh) -> dict[str, Any]:
    dp = data_axes(mesh)
    model = "model" if "model" in mesh.axis_names else None
    msize = mesh.shape.get("model", 1)
    Hkv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    kv_on_model = model and Hkv % msize == 0
    rules: dict[str, Any] = {
        "layers": None,
        "embed": dp or None,  # FSDP dim of weight matrices
        "vocab": model,
        "vocab_rep": None,  # input-embedding rows replicated (gather local)
        "embed_tp": model if cfg.d_model % msize == 0 else None,
        "heads": model,
        "kv_heads": model if kv_on_model else None,
        "mlp": model,
        "experts": model,
        "ssm": model,
        # activations
        "batch": dp or None,
        "embed_act": None,
        # sequence parallelism hook (§Perf): setting this to `model` shards
        # block outputs on the seq dim (Megatron-SP pattern). REFUTED on this
        # XLA version: the partitioner keeps the full-activation all-reduce
        # and adds resharding all-to-alls on top (nemotron t_mem +43%,
        # t_coll +8%) instead of folding the psum into a reduce-scatter.
        # Left off; revisit with explicit shard_map blocks.
        "seq_act": None,
        "vocab_act": model,
        "mlp_act": model,
        "ssm_act": model,
        "experts_act": model,
        "heads_sep": model if cfg.n_heads % msize == 0 else None,
        # decode caches
        "kv_heads_cache": model if kv_on_model else None,
        "kv_seq": None if kv_on_model else model,
        "working_rows": None,  # working-table rows stay host-ordered
        "working_dim": model if cfg.d_model % msize == 0 else None,
    }
    return rules


def pspec(shape: tuple[int, ...], logical: tuple[Optional[str], ...], rules: dict, mesh: Mesh) -> P:
    """Build a PartitionSpec honoring divisibility + no-axis-reuse."""
    used: set[str] = set()
    parts = []
    for dim, name in zip(shape, logical):
        ax = rules.get(name) if name else None
        if ax is None:
            parts.append(None)
            continue
        ax_t = (ax,) if isinstance(ax, str) else tuple(ax)
        ax_t = tuple(a for a in ax_t if a not in used)
        if not ax_t or dim % _axes_size(mesh, ax_t) != 0:
            parts.append(None)
            continue
        used.update(ax_t)
        parts.append(ax_t if len(ax_t) > 1 else ax_t[0])
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def schema_shardings(schema: dict, rules: dict, mesh: Mesh):
    """Pytree of NamedSharding matching a param schema."""

    def go(node):
        if isinstance(node, ParamSpec):
            return NamedSharding(mesh, pspec(node.shape, node.logical, rules, mesh))
        return {k: go(v) for k, v in node.items()}

    return go(schema)


def like_tree(tree, spec_fn):
    """Map leaves (ShapeDtypeStruct or arrays) -> NamedSharding via spec_fn(leaf)."""
    return jax.tree.map(spec_fn, tree)


def install_constraints(mesh: Mesh, rules: dict) -> None:
    """Route models' with_logical_constraint() through this mesh's rules and
    install the explicit shard_map HBM-PS row gather."""

    def fn(x, logical):
        spec = pspec(x.shape, tuple(logical), rules, mesh)
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    set_logical_constraint_fn(fn)

    def gather(table, ids):
        # table: rows replicated, d tensor-parallel; ids: batch over data
        # axes. Local take per shard — the paper's hash-table ``get`` with
        # zero collectives (and no generic-gather partitioner involvement).
        tspec = pspec(table.shape, ("vocab_rep", "embed_tp"), rules, mesh)
        ispec = pspec(ids.shape, ("batch",) + (None,) * (ids.ndim - 1), rules, mesh)
        b_part = tuple(ispec)[0] if tuple(ispec) else None
        d_part = tuple(tspec)[1] if len(tuple(tspec)) > 1 else None
        ospec = P(*((b_part,) + (None,) * (ids.ndim - 1) + (d_part,)))

        def body(tbl, tok):
            return jnp.take(tbl, tok, axis=0)

        return jax.shard_map(
            body, mesh=mesh, in_specs=(tspec, ispec), out_specs=ospec, check_vma=False
        )(table, ids)

    set_embed_gather_fn(gather)


def clear_constraints() -> None:
    from repro.models.common import set_param_constraint_fn

    set_logical_constraint_fn(None)
    set_embed_gather_fn(None)
    set_param_constraint_fn(None)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
