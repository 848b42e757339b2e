"""HBM-PS: the device-resident working-parameter table (paper Section 4).

TPU adaptation of the multi-GPU distributed hash table (see DESIGN.md §3):
the MEM-PS renumbers the batch's unique keys to contiguous *working slots*
[0, n_working); the device table is then a dense ``[n_working, dim]`` matrix
and the hash-table ops become:

  get(slots)               -> gather              (Pallas embedding_lookup)
  accumulate(slots, vals)  -> scatter-add         (Pallas scatter_add)
  insert(slots, vals)      -> scatter-write

Distribution across the ``model`` mesh axis mirrors the paper's per-GPU
modulo partition: slot s lives on shard ``s % n_shards`` at local row
``s // n_shards``. Two exchange strategies are provided:

* ``get_psum`` — each shard contributes its owned rows, one ``psum``
  assembles the full row set on every shard (paper's all-reduce-style sync;
  2(S-1)/S * B * dim bytes per link).
* ``get_a2a`` — requests routed to owners and rows routed back with two
  ``all_to_all`` ops (paper's NVLink p2p ``get``; B * dim * (S-1)/S bytes);
  requires per-shard request lists of equal size, which the host pads via
  :func:`plan_a2a`. Output is requester-sharded: shard r ends holding the
  rows for its B/S slice of the batch, exactly the paper's per-GPU pattern.

``accumulate`` in the distributed setting reduces gradient rows across the
data axis (``psum``) and each shard applies only its owned rows — the same
"synchronize after every mini-batch" semantics as Algorithm 1 line 14.

On top of the per-batch table sits :class:`DeviceWorkingSet` — the paper's
HBM-PS caching behaviour across batches: rows whose keys repeat in the next
batch stay device-resident and are *slot-remapped* (a device gather), so the
host only transfers the delta rows. On skewed (zipfian) CTR streams adjacent
batches share most of their hot keys, making this the dominant PCIe/host
traffic win.

:class:`DeviceHotSet` generalizes the same mechanism to the *serving* path
(DESIGN.md §7): instead of "previous batch only", it keeps a
frequency-ranked resident set of the hottest rows on device across decode
steps. Because serving rows are immutable within a snapshot version, any
device-resident copy equals the host copy bit-for-bit — residency is keyed
by version and resets on a roll-forward.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Literal

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.keys import member_sorted
from repro.kernels import ops as kops


# --------------------------------------------------------------------------
# single-device working table (used inside one jitted train step)
# --------------------------------------------------------------------------


class WorkingTable:
    """Dense device working table with hash-table semantics."""

    @staticmethod
    def get(table: jax.Array, slots: jax.Array) -> jax.Array:
        return kops.embedding_lookup(table, slots)

    @staticmethod
    def accumulate(
        table: jax.Array, slots: jax.Array, values: jax.Array,
        *, assume_sorted: bool = False,
    ) -> jax.Array:
        return kops.scatter_add(table, slots, values, assume_sorted=assume_sorted)

    @staticmethod
    def insert(table: jax.Array, slots: jax.Array, values: jax.Array) -> jax.Array:
        return table.at[slots].set(values.astype(table.dtype))


# --------------------------------------------------------------------------
# sharded working table over the `model` mesh axis
# --------------------------------------------------------------------------


def shard_layout(n_working: int, n_shards: int) -> int:
    """Rows per shard after padding (slot s -> shard s % S, row s // S)."""
    return (n_working + n_shards - 1) // n_shards


def to_sharded_rows(values: np.ndarray, n_shards: int) -> np.ndarray:
    """Host-side: [n_working, d] -> [S * rows_per_shard, d] padded, where the
    shard-major layout matches the device partition (shard = slot % S)."""
    n, d = values.shape
    rps = shard_layout(n, n_shards)
    out = np.zeros((n_shards * rps, d), dtype=values.dtype)
    for s in range(n_shards):
        rows = values[s::n_shards]
        out[s * rps : s * rps + len(rows)] = rows
    return out


def from_sharded_rows(sharded: np.ndarray, n_working: int, n_shards: int) -> np.ndarray:
    n, d = n_working, sharded.shape[1]
    rps = shard_layout(n, n_shards)
    out = np.zeros((n, d), dtype=sharded.dtype)
    for s in range(n_shards):
        take = len(out[s::n_shards])
        out[s::n_shards] = sharded[s * rps : s * rps + take]
    return out


class ShardedWorkingTable:
    """Working table sharded over a mesh axis with explicit collectives."""

    def __init__(self, mesh: Mesh, axis: str = "model"):
        self.mesh = mesh
        self.axis = axis
        self.n_shards = mesh.shape[axis]
        self.table_spec = P(axis, None)  # [S * rows_per_shard, d] row-sharded

    def sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, self.table_spec)

    # -- psum exchange: every shard ends with all requested rows -----------
    def get_psum(self, table: jax.Array, slots: jax.Array) -> jax.Array:
        """table: [S*rps, d] sharded on axis; slots: [B] replicated ->
        [B, d] replicated."""
        S = self.n_shards
        rps = table.shape[0] // S

        def body(tbl, sl):
            # tbl: local [rps, d]; sl: [B] (replicated)
            me = jax.lax.axis_index(self.axis)
            owned = (sl % S) == me
            local_row = jnp.where(owned, sl // S, 0)
            rows = kops.embedding_lookup(tbl, local_row.astype(jnp.int32))
            rows = jnp.where(owned[:, None], rows, 0.0)
            return jax.lax.psum(rows, self.axis)

        return jax.shard_map(
            body,
            mesh=self.mesh,
            in_specs=(self.table_spec, P()),
            out_specs=P(),
            check_vma=False,
        )(table, slots)

    # -- accumulate: grads for all B slots -> owned rows only --------------
    def accumulate(
        self, table: jax.Array, slots: jax.Array, grads: jax.Array,
        *, assume_sorted: bool = False,
    ) -> jax.Array:
        """grads: [B, d] replicated (already summed over data axis);
        each shard applies its owned rows.

        ``assume_sorted=True`` when ``slots`` is ascending (the MEM-PS emits
        sorted-unique working sets): every slot maps to local row
        ``slot // S`` — non-decreasing — so the Pallas scatter kernel skips
        its argsort. Non-owned entries scatter zero grads into their (valid)
        ``slot // S`` row, which is harmless and keeps the order sorted."""
        S = self.n_shards

        def body(tbl, sl, g):
            me = jax.lax.axis_index(self.axis)
            owned = (sl % S) == me
            g = jnp.where(owned[:, None], g, 0.0)
            return kops.scatter_add(
                tbl, (sl // S).astype(jnp.int32), g, assume_sorted=assume_sorted
            )

        return jax.shard_map(
            body,
            mesh=self.mesh,
            in_specs=(self.table_spec, P(), P()),
            out_specs=self.table_spec,
            check_vma=False,
        )(table, slots, grads)

    # -- all_to_all exchange: requests to owners, rows back (p2p ``get``) --
    def get_a2a(self, table: jax.Array, req: jax.Array, restore: jax.Array) -> jax.Array:
        """Two-``all_to_all`` row exchange (paper's NVLink p2p pattern).

        ``req``/``restore`` come from :func:`plan_a2a`: ``req[r, o]`` lists
        the (padded, equal-length) slots requester shard r asks owner shard
        o for, and ``restore[r]`` maps r's batch positions back into its
        received rows. Returns the [B, d] rows requester-sharded over the
        axis (shard r holds rows for its contiguous B/S slice of slots)."""
        S = self.n_shards

        def body(tbl, req_r, restore_r):
            d = tbl.shape[-1]
            m = req_r.shape[-1]
            # a2a #1: route each requester's per-owner slot lists to owners
            got = jax.lax.all_to_all(req_r, self.axis, split_axis=1, concat_axis=0, tiled=True)
            local_rows = (got.reshape(S, m) // S).astype(jnp.int32)
            rows = kops.embedding_lookup(tbl, local_rows.reshape(-1)).reshape(S, m, d)
            # a2a #2: route the gathered rows back to their requesters
            back = jax.lax.all_to_all(rows, self.axis, split_axis=0, concat_axis=0, tiled=True)
            return back.reshape(S * m, d)[restore_r[0]]

        return jax.shard_map(
            body,
            mesh=self.mesh,
            in_specs=(self.table_spec, P(self.axis, None, None), P(self.axis, None)),
            out_specs=P(self.axis, None),
            check_vma=False,
        )(table, req, restore)


def plan_a2a(slots: np.ndarray, n_shards: int) -> tuple[np.ndarray, np.ndarray]:
    """Host-side routing plan for :meth:`ShardedWorkingTable.get_a2a`.

    Splits the batch into one contiguous chunk per requester shard and
    groups each chunk's slots by owner shard, padding every (requester,
    owner) request list to the same length m (pad entries request slot
    ``o`` — owner o's local row 0 — and are dropped by ``restore``).

    Returns (req [S, S, m] int32, restore [S, B//S] int32) with
    ``restore[r, j]`` indexing into the [S*m] rows shard r receives.
    """
    slots = np.asarray(slots, dtype=np.int64)
    S = n_shards
    B = len(slots)
    assert B % S == 0, f"batch {B} must pad to a multiple of {S} requesters"
    chunk = B // S
    # group by (requester, owner) in a few vectorized passes: a stable
    # argsort on the pair id keeps each group's request order, cumsum gives
    # group starts, and positions within a group follow by subtraction
    owners = slots % S
    pair = np.repeat(np.arange(S, dtype=np.int64), chunk) * S + owners
    order = np.argsort(pair, kind="stable")
    counts = np.bincount(pair, minlength=S * S)
    m = max(1, int(counts.max()))
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    rank = np.arange(B, dtype=np.int64) - np.repeat(starts, counts)
    req = np.tile(np.arange(S, dtype=np.int32), (S, 1))[:, :, None].repeat(m, axis=2)
    req.reshape(S * S, m)[pair[order], rank] = slots[order]
    restore = np.empty(B, dtype=np.int32)
    restore[order] = owners[order] * m + rank
    return req, restore.reshape(S, chunk)


# --------------------------------------------------------------------------
# cross-batch device working-set reuse (HBM-PS caching across batches)
# --------------------------------------------------------------------------


def assemble_rows(
    prev_table: jax.Array | None,
    fresh_rows: jax.Array,
    reuse_src: np.ndarray,
    reuse_dst: np.ndarray,
    fresh_dst: np.ndarray,
    n_working: int,
) -> jax.Array:
    """Build a [n_working, d] device table from already-resident rows plus
    the freshly-transferred delta: gather of ``prev_table[reuse_src]`` into
    ``reuse_dst`` + scatter of ``fresh_rows`` into ``fresh_dst``. Pure data
    movement — bitwise. Shared by the training :class:`DeviceWorkingSet`
    (previous-batch residency) and the serving :class:`DeviceHotSet`
    (frequency-ranked residency)."""
    if len(reuse_src) == 0:
        return fresh_rows  # fresh_dst is the identity permutation
    out = jnp.zeros((n_working, fresh_rows.shape[-1]), dtype=fresh_rows.dtype)
    out = out.at[jnp.asarray(reuse_dst)].set(prev_table[jnp.asarray(reuse_src)])
    return out.at[jnp.asarray(fresh_dst)].set(fresh_rows)


@dataclass
class ReusePlan:
    """How to assemble one batch's device table from the previous one."""

    n_working: int
    seq: int  # device-table generation this plan expects to remap from
    reuse_src: np.ndarray  # int32 — row in the PREVIOUS device table
    reuse_dst: np.ndarray  # int32 — row in the new table (same key)
    fresh_dst: np.ndarray  # int32 — new-table rows transferred from host

    @property
    def n_reused(self) -> int:
        return len(self.reuse_src)


@dataclass
class ReuseStats:
    batches: int = 0
    rows_reused: int = 0
    rows_transferred: int = 0
    bytes_saved: int = 0  # host->device bytes avoided by on-device remap
    bytes_transferred: int = 0


class DeviceWorkingSet:
    """Keeps consecutive batches' shared rows device-resident.

    The MEM-PS renumbers each batch's keys to fresh contiguous slots, so a
    key shared by batches i and i+1 lands at a *different* slot — but its
    post-train value already lives in batch i's final device table. ``plan``
    matches the new batch's (sorted, unique) keys against the previous
    batch's and emits a slot remap; ``assemble`` builds the new table on
    device from the remapped rows plus only the freshly-transferred delta.
    Values are bitwise-identical to a full host pull because the final
    device rows are exactly what the host push wrote back.
    """

    def __init__(self, row_bytes: int):
        self.row_bytes = int(row_bytes)
        self.stats = ReuseStats()
        self._prev_keys: np.ndarray | None = None
        self._seq = 0
        self._last_ext_id: int | None = None
        self._last_plan: ReusePlan | None = None

    def reset(self) -> None:
        """Invalidate residency (resume/restore or an aborted pipeline)."""
        self._prev_keys = None
        self._last_ext_id = None
        self._last_plan = None

    def plan(self, keys: np.ndarray, batch_id: int | None = None) -> ReusePlan:
        """keys: sorted unique uint64 of the new batch. Updates state.

        ``batch_id`` dedups a retried transfer stage: re-planning the same
        batch would diff its keys against themselves (and skew the device
        generation), so an immediate re-plan returns the original plan."""
        if batch_id is not None and batch_id == self._last_ext_id:
            return self._last_plan
        n = len(keys)
        prev = self._prev_keys
        self._prev_keys = keys
        self._seq += 1
        self._last_ext_id = batch_id
        self.stats.batches += 1
        if prev is None or len(prev) == 0:
            fresh = np.arange(n, dtype=np.int32)
            empty = np.empty(0, dtype=np.int32)
            self.stats.rows_transferred += n
            self.stats.bytes_transferred += n * self.row_bytes
            self._last_plan = ReusePlan(n, self._seq, empty, empty, fresh)
            return self._last_plan
        hit, pos_c = member_sorted(prev, keys)
        reuse_dst = np.nonzero(hit)[0].astype(np.int32)
        reuse_src = pos_c[hit].astype(np.int32)
        fresh_dst = np.nonzero(~hit)[0].astype(np.int32)
        self.stats.rows_reused += len(reuse_dst)
        self.stats.rows_transferred += len(fresh_dst)
        self.stats.bytes_saved += len(reuse_dst) * self.row_bytes
        self.stats.bytes_transferred += len(fresh_dst) * self.row_bytes
        self._last_plan = ReusePlan(n, self._seq, reuse_src, reuse_dst, fresh_dst)
        return self._last_plan

    @staticmethod
    def assemble(prev_table: jax.Array | None, fresh_rows: jax.Array, plan: ReusePlan) -> jax.Array:
        """Build the [n_working, d] table: device gather of reused rows +
        scatter of the transferred delta. Pure data movement — bitwise."""
        with jax.named_scope("ws_assemble"):
            return assemble_rows(
                prev_table, fresh_rows,
                plan.reuse_src, plan.reuse_dst, plan.fresh_dst, plan.n_working,
            )


# --------------------------------------------------------------------------
# serving-path device residency: hottest rows stay on device across steps
# --------------------------------------------------------------------------


@dataclass
class HotPlan:
    """How to assemble one lookup's device table from the hot resident set."""

    n_working: int
    version: int
    keys: np.ndarray  # uint64 — the lookup's sorted unique keys
    reuse_src: np.ndarray  # int32 — row in the RESIDENT device table
    reuse_dst: np.ndarray  # int32 — row in the lookup's table (same key)
    fresh_dst: np.ndarray  # int32 — lookup rows transferred from host

    @property
    def n_reused(self) -> int:
        return len(self.reuse_src)


@dataclass
class HotSetStats:
    steps: int = 0
    rows_reused: int = 0
    rows_transferred: int = 0
    bytes_saved: int = 0  # host->device bytes avoided by residency
    bytes_transferred: int = 0

    @property
    def device_hit_rate(self) -> float:
        return self.rows_reused / max(1, self.rows_reused + self.rows_transferred)


class DeviceHotSet:
    """Keeps the hottest serving rows device-resident across decode steps.

    :class:`DeviceWorkingSet` exploits *adjacency* (training batch i+1
    shares keys with batch i); serving streams instead revisit a skewed hot
    set over many steps, so this class ranks keys by visit frequency and
    keeps the top ``capacity`` resident. Per lookup:

      1. ``plan``      — match the lookup's unique keys against the resident
                         set (one ``member_sorted`` pass); only the misses
                         need a host row.
      2. ``assemble``  — build the lookup's dense [n_working, d] table on
                         device: gather of resident rows + scatter of the
                         transferred delta (same primitive as training).
      3. ``admit``     — fold the lookup's keys into the frequency ranking
                         and refresh the resident table, sourcing rows from
                         the just-built lookup table and the old resident
                         table (both bitwise-correct: a version's rows are
                         immutable, so every copy of a key's row is equal).

    Residency is **version-keyed**: ``plan`` with a different snapshot
    version resets the set, so a roll-forward can never serve a stale row.
    """

    def __init__(self, capacity: int, row_bytes: int):
        self.capacity = int(capacity)
        self.row_bytes = int(row_bytes)
        self.stats = HotSetStats()
        self.generation = 0  # bumped on every resident-set mutation; lets
        # callers release their lock across the host pull and detect a
        # concurrent admit/reset before assembling against a stale plan
        self._version: int | None = None
        self._keys: np.ndarray | None = None  # sorted unique resident keys
        self._freq: np.ndarray | None = None  # int64, aligned with _keys
        self._table: jax.Array | None = None  # [len(_keys), d] resident rows

    @property
    def n_resident(self) -> int:
        return 0 if self._keys is None else len(self._keys)

    def reset(self) -> None:
        self.generation += 1
        self._version = None
        self._keys = None
        self._freq = None
        self._table = None

    def plan(self, keys: np.ndarray, version: int) -> HotPlan:
        """keys: sorted unique uint64 of one lookup; version: the snapshot
        version the caller's rows come from."""
        if version != self._version:
            self.reset()
            self._version = version
        n = len(keys)
        self.stats.steps += 1
        if self._keys is None or len(self._keys) == 0:
            fresh = np.arange(n, dtype=np.int32)
            empty = np.empty(0, dtype=np.int32)
            self.stats.rows_transferred += n
            self.stats.bytes_transferred += n * self.row_bytes
            return HotPlan(n, version, keys, empty, empty, fresh)
        hit, pos = member_sorted(self._keys, keys)
        reuse_dst = np.nonzero(hit)[0].astype(np.int32)
        reuse_src = pos[hit].astype(np.int32)
        fresh_dst = np.nonzero(~hit)[0].astype(np.int32)
        self.stats.rows_reused += len(reuse_dst)
        self.stats.rows_transferred += len(fresh_dst)
        self.stats.bytes_saved += len(reuse_dst) * self.row_bytes
        self.stats.bytes_transferred += len(fresh_dst) * self.row_bytes
        return HotPlan(n, version, keys, reuse_src, reuse_dst, fresh_dst)

    def assemble(self, fresh_rows: jax.Array, plan: HotPlan) -> jax.Array:
        """Lookup table from resident rows + transferred delta (device-side
        data movement only)."""
        return assemble_rows(
            self._table, fresh_rows,
            plan.reuse_src, plan.reuse_dst, plan.fresh_dst, plan.n_working,
        )

    def admit(self, batch_table: jax.Array, plan: HotPlan) -> None:
        """Update the frequency ranking with this lookup and refresh the
        resident set to the top-``capacity`` keys."""
        if plan.version != self._version:
            return  # raced with a reset; next plan() rebuilds
        keys = plan.keys
        if self._keys is None or len(self._keys) == 0:
            cand, freq = keys, np.ones(len(keys), dtype=np.int64)
        else:
            cand = np.union1d(self._keys, keys)  # sorted unique
            m_old, p_old = member_sorted(self._keys, cand)
            freq = np.where(m_old, self._freq[np.minimum(p_old, len(self._freq) - 1)], 0)
            m_new, _ = member_sorted(keys, cand)
            freq = freq + m_new
        if len(cand) > self.capacity:
            keep = np.zeros(len(cand), dtype=bool)
            keep[np.argsort(-freq, kind="stable")[: self.capacity]] = True
            cand, freq = cand[keep], freq[keep]  # mask keeps the sort order
        in_batch, pos_b = member_sorted(keys, cand)
        tbl = jnp.zeros((len(cand), batch_table.shape[-1]), dtype=batch_table.dtype)
        b_idx = np.nonzero(in_batch)[0]
        if b_idx.size:
            tbl = tbl.at[jnp.asarray(b_idx)].set(batch_table[jnp.asarray(pos_b[in_batch])])
        if self._keys is not None and len(self._keys):
            rest = ~in_batch
            if rest.any():
                m_old, p_old = member_sorted(self._keys, cand[rest])
                # every kept non-batch key came from the old resident set
                r_idx = np.nonzero(rest)[0]
                tbl = tbl.at[jnp.asarray(r_idx)].set(self._table[jnp.asarray(p_old)])
        self._keys, self._freq, self._table = cand, freq, tbl
        self.generation += 1

    def assemble_and_admit(self, fresh_rows: jax.Array, plan: HotPlan) -> jax.Array:
        table = self.assemble(fresh_rows, plan)
        self.admit(table, plan)
        return table
