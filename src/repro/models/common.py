"""Shared model substrate: schema-based params, norms, RoPE, attention, MLPs.

Parameters are declared as a *schema* (nested dict of ParamSpec). One schema
drives both initialization (``init_params``) and sharding
(``logical_specs`` -> launch/sharding.py maps logical axis names to mesh
axes), so init shapes and partition specs can never drift apart.

Logical axis vocabulary (mapped to mesh axes by launch/sharding.py):
  layers   — stacked scan dim (never sharded)
  embed    — d_model dim (FSDP-sharded over the data axes)
  vocab    — vocabulary dim (tensor-parallel)
  heads    — attention query heads x head_dim, flattened (tensor-parallel)
  kv_heads — kv heads x head_dim, flattened (tensor-parallel if divisible)
  mlp      — feed-forward hidden (tensor-parallel)
  experts  — MoE expert dim (expert-parallel)
  ssm      — SSM inner channels (tensor-parallel)
"""

from __future__ import annotations

import math
import zlib
from dataclasses import dataclass, field
from typing import Any, Optional

import jax
import jax.numpy as jnp

Params = Any  # nested dict of arrays


@dataclass(frozen=True)
class ParamSpec:
    shape: tuple[int, ...]
    logical: tuple[Optional[str], ...]
    init: str = "normal"  # normal | zeros | ones
    scale: Optional[float] = None  # stddev; default 1/sqrt(shape[fan_axis])
    fan_axis: int = 0
    dtype: Any = jnp.float32

    def __post_init__(self):
        assert len(self.shape) == len(self.logical), (self.shape, self.logical)


def init_params(schema: dict, rng: jax.Array) -> Params:
    """Materialize a schema into arrays; per-leaf rng folded in by path."""

    def go(node, path):
        if isinstance(node, ParamSpec):
            if node.init == "zeros":
                return jnp.zeros(node.shape, node.dtype)
            if node.init == "ones":
                return jnp.ones(node.shape, node.dtype)
            key = rng
            for p in path:
                # crc32, not hash(): str hashes change from process to process
                key = jax.random.fold_in(key, zlib.crc32(str(p).encode()) & 0x7FFFFFFF)
            fan = node.shape[node.fan_axis] if node.shape else 1
            scale = node.scale if node.scale is not None else 1.0 / math.sqrt(max(1, fan))
            return (jax.random.normal(key, node.shape, jnp.float32) * scale).astype(node.dtype)
        return {k: go(v, path + (k,)) for k, v in node.items()}

    return go(schema, ())


def abstract_params(schema: dict) -> Params:
    """ShapeDtypeStruct pytree (dry-run: no allocation)."""

    def go(node):
        if isinstance(node, ParamSpec):
            return jax.ShapeDtypeStruct(node.shape, node.dtype)
        return {k: go(v) for k, v in node.items()}

    return go(schema)


def logical_specs(schema: dict) -> Any:
    """Pytree of logical-axis tuples matching the schema structure."""

    def go(node):
        if isinstance(node, ParamSpec):
            return node.logical
        return {k: go(v) for k, v in node.items()}

    return go(schema)


def param_count(schema: dict) -> int:
    total = 0

    def go(node):
        nonlocal total
        if isinstance(node, ParamSpec):
            total += math.prod(node.shape) if node.shape else 1
        else:
            for v in node.values():
                go(v)

    go(schema)
    return total


# --------------------------------------------------------------------------
# normalization / activations / RoPE
# --------------------------------------------------------------------------


def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * weight).astype(x.dtype)


def layer_norm(x: jax.Array, weight: jax.Array, bias: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps) * weight + bias).astype(x.dtype)


def mlp_activation(kind: str, h: jax.Array, gate: jax.Array | None = None) -> jax.Array:
    if kind == "swiglu":
        assert gate is not None
        return jax.nn.silu(gate) * h
    if kind == "squared_relu":
        r = jax.nn.relu(h)
        return r * r
    if kind == "gelu":
        return jax.nn.gelu(h)
    raise ValueError(kind)


def rope_tables(positions: jax.Array, head_dim: int, theta: float) -> tuple[jax.Array, jax.Array]:
    """cos/sin tables for rotate-half RoPE. positions: [...,] int."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    angles = positions.astype(jnp.float32)[..., None] * freqs  # [..., half]
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: [B, H, S, Dh]; cos/sin: [S, Dh/2] (or broadcastable)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[None, None].astype(jnp.float32)
    s = sin[None, None].astype(jnp.float32)
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate([x1f * c - x2f * s, x2f * c + x1f * s], axis=-1).astype(x.dtype)


# --------------------------------------------------------------------------
# sharding-annotation hooks (populated by launch/sharding.py at trace time)
# --------------------------------------------------------------------------

_LOGICAL_CONSTRAINT_FN = None
_EMBED_GATHER_FN = None


def set_logical_constraint_fn(fn) -> None:
    """Install a fn(x, logical_axes) -> x applying sharding constraints."""
    global _LOGICAL_CONSTRAINT_FN
    _LOGICAL_CONSTRAINT_FN = fn


def with_logical_constraint(x: jax.Array, *logical: Optional[str]) -> jax.Array:
    if _LOGICAL_CONSTRAINT_FN is None:
        return x
    return _LOGICAL_CONSTRAINT_FN(x, logical)


_PARAM_CONSTRAINT_FN = None


def set_param_constraint_fn(fn) -> None:
    """Install fn(param_like_pytree) -> pytree applying the parameter
    shardings to a matching pytree (gradients). Forcing per-microbatch
    gradients onto the FSDP param sharding makes XLA reduce-scatter each
    contribution instead of all-reducing full gradients inside the
    accumulation loop (§Perf: the dominant collective win on large dense
    models)."""
    global _PARAM_CONSTRAINT_FN
    _PARAM_CONSTRAINT_FN = fn


def constrain_like_params(grads):
    if _PARAM_CONSTRAINT_FN is None:
        return grads
    return _PARAM_CONSTRAINT_FN(grads)


def set_embed_gather_fn(fn) -> None:
    """Install the distributed HBM-PS row gather (shard_map local take).

    The launcher installs a mesh-aware version: table d-dim is tensor-
    parallel, rows replicated, so each shard takes its d-slice locally with
    ZERO collectives — the explicit form of the paper's hash-table ``get``
    (XLA's generic gather partitioner mis-handles this pattern inside
    scans; see launch/sharding.py).
    """
    global _EMBED_GATHER_FN
    _EMBED_GATHER_FN = fn


def embed_gather(table: jax.Array, ids: jax.Array) -> jax.Array:
    if _EMBED_GATHER_FN is None:
        # single-host default: the kernel-layer lookup (Pallas row-gather on
        # TPU, jnp.take-equivalent reference elsewhere — bitwise identical)
        from repro.kernels import ops as kops

        flat = kops.embedding_lookup(table, ids.reshape(-1))
        return flat.reshape(*ids.shape, table.shape[-1])
    return _EMBED_GATHER_FN(table, ids)
