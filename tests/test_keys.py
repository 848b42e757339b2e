"""Key hashing/partitioning invariants (property-based)."""

import numpy as np

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.keys import (
    deterministic_init,
    hash_keys,
    key_to_node,
    key_to_shard,
    partition_by_owner,
    splitmix64,
)

keys_arrays = st.lists(st.integers(0, 2**63 - 1), min_size=1, max_size=200).map(
    lambda xs: np.asarray(xs, dtype=np.uint64)
)


@given(keys_arrays)
def test_hash_deterministic(keys):
    assert (hash_keys(keys) == hash_keys(keys)).all()


@given(keys_arrays, st.integers(1, 16))
def test_owner_in_range(keys, n):
    owners = key_to_node(keys, n)
    assert ((owners >= 0) & (owners < n)).all()


@given(keys_arrays)
def test_node_and_shard_maps_independent(keys):
    # different seeds -> a key's node does not determine its device shard
    n = key_to_node(keys, 4)
    s = key_to_shard(keys, 4)
    assert n.shape == s.shape


def test_splitmix_bijective_on_sample():
    xs = np.arange(100_000, dtype=np.uint64)
    assert len(np.unique(splitmix64(xs))) == len(xs)


def test_partition_balance():
    keys = np.arange(100_000, dtype=np.uint64)
    counts = np.bincount(key_to_node(keys, 8), minlength=8)
    assert counts.min() > 0.9 * counts.mean()
    assert counts.max() < 1.1 * counts.mean()


@given(keys_arrays, st.integers(1, 8), st.integers(1, 16))
def test_deterministic_init_is_per_key(keys, dim, seed_unused):
    a = deterministic_init(keys, dim)
    b = deterministic_init(keys[::-1].copy(), dim)[::-1]
    np.testing.assert_array_equal(a, b)
    assert (np.abs(a) <= 0.01 + 1e-9).all()


@given(keys_arrays, st.integers(1, 7))
def test_partition_by_owner_roundtrip(keys, n):
    owners = key_to_node(keys, n)
    order, splits = partition_by_owner(keys, owners, n)
    parts = np.split(keys[order], splits)
    assert sum(len(p) for p in parts) == len(keys)
    for i, p in enumerate(parts):
        assert (key_to_node(p, n) == i).all() if len(p) else True
    # scatter-back property
    rebuilt = np.empty_like(keys)
    rebuilt[order] = keys[order]
    np.testing.assert_array_equal(rebuilt, keys)
