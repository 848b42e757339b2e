"""Seeded CTR traffic for the benchmark: raw log records in HDFS batches.

A copy, kept here so that no change to the program can move the yardstick,
of the program's synthetic click log (``repro.data.synthetic_ctr``:
``SyntheticCTRStream.next_raw``, ``extract_host`` and the planted labels) and
of its key hash (``repro.core.keys.splitmix64``).

One change from the original. A batch's *structure* -- which zipf rank sits at
each position, and how many ids each example carries -- comes from the traffic
file's fixed ``structure_seed``; ``--seed`` picks the keys the ranks stand for
(a bijection of ranks onto ``[1, n_keys)``), the labels, and the order of the
examples inside each batch. Every seed therefore trains the same number of
working rows per batch, shares the same number of rows between consecutive
batches, and compiles the same shapes, while the keys, their owners, their
slots, their files and the labels all change with the seed.

Raw ids are chosen so that the program's extraction (``key = splitmix64(raw ^
17) % n_keys``) maps them onto exactly the chosen keys: ``raw =
splitmix64^-1(key) ^ 17``.
"""

from __future__ import annotations

import math
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass

import numpy as np

_U64 = np.uint64
_GOLDEN = _U64(0x9E3779B97F4A7C15)
_MIX1 = _U64(0xBF58476D1CE4E5B9)
_MIX2 = _U64(0x94D049BB133111EB)
_INV1 = _U64(pow(0xBF58476D1CE4E5B9, -1, 1 << 64))
_INV2 = _U64(pow(0x94D049BB133111EB, -1, 1 << 64))

KEY_SEED = 17  # raw id -> key hash seed of the program's extraction
SLOT_SEED = 31  # key -> feature slot
LABEL_SEED = 23  # key -> planted ground-truth weight
INIT_SEED = 3  # key -> initial row of an unseen key (the PS's init rule)


def splitmix64(x: np.ndarray) -> np.ndarray:
    x = np.asarray(x, dtype=_U64)
    with np.errstate(over="ignore"):
        z = x + _GOLDEN
        z = (z ^ (z >> _U64(30))) * _MIX1
        z = (z ^ (z >> _U64(27))) * _MIX2
        return z ^ (z >> _U64(31))


def _unxorshift(y: np.ndarray, s: int) -> np.ndarray:
    x = y
    for _ in range(math.ceil(64 / s)):
        x = y ^ (x >> _U64(s))
    return x


def splitmix64_inv(z: np.ndarray) -> np.ndarray:
    z = np.asarray(z, dtype=_U64)
    with np.errstate(over="ignore"):
        x = _unxorshift(z, 31) * _INV2
        x = _unxorshift(x, 27) * _INV1
        return _unxorshift(x, 30) - _GOLDEN


def hash_keys(keys: np.ndarray, seed: int) -> np.ndarray:
    return splitmix64(np.asarray(keys, dtype=_U64) ^ _U64(seed))


def key_of_raw(raw: np.ndarray, n_keys: int) -> np.ndarray:
    return hash_keys(raw, KEY_SEED) % _U64(n_keys)


def slot_of_key(keys: np.ndarray, n_slots: int) -> np.ndarray:
    return (hash_keys(keys, SLOT_SEED) % _U64(n_slots)).astype(np.int32)


def init_rows(keys: np.ndarray, dim: int, scale: float) -> np.ndarray:
    """The PS's documented row for a key it has never seen: ``scale *
    U(-1, 1)`` per column, a function of the key alone."""
    keys = np.asarray(keys, dtype=_U64)
    cols = np.arange(dim, dtype=_U64)
    with np.errstate(over="ignore"):
        grid = hash_keys(keys, INIT_SEED)[:, None] * _GOLDEN + cols[None, :] * _MIX1
        bits = splitmix64(grid)
    u = (bits >> _U64(11)).astype(np.float64) * (1.0 / (1 << 53))
    return ((u * 2.0 - 1.0) * scale).astype(np.float32)


class KeyMap:
    """Seeded bijection of zipf ranks ``[0, n_keys - 1)`` onto keys
    ``[1, n_keys)``: ``key = 1 + (a * rank + b) mod (n_keys - 1)``."""

    def __init__(self, n_keys: int, rng: np.random.Generator):
        self.m = n_keys - 1
        # a * rank stays below 2**63 for every rank < 2**38 (n_keys <= 2.7e11)
        assert self.m < 1 << 38, "key space too large for the 64-bit affine map"
        while True:
            a = int(rng.integers(1 << 20, 1 << 24)) | 1
            if math.gcd(a, self.m) == 1:
                break
        self.a, self.b = _U64(a), _U64(int(rng.integers(0, self.m)))

    def keys(self, ranks: np.ndarray) -> np.ndarray:
        r = np.asarray(ranks, dtype=_U64)
        return _U64(1) + (self.a * r + self.b) % _U64(self.m)


@dataclass
class Batch:
    """One HDFS batch as the benchmark hands it over, with what the
    harness and the reference need to know about it."""

    raw_ids: np.ndarray  # uint64 [B, nnz]; zero past each example's length
    lengths: np.ndarray  # int32 [B]
    labels: np.ndarray  # float32 [B]
    keys: np.ndarray  # uint64 [B, nnz]; the extraction's keys, 0 when padding
    n_valid: int  # ids that are real
    working_keys: np.ndarray  # sorted unique keys of the batch, 0 included


def _structure(traffic: dict, cfg: dict, b: int):
    """Batch b's ranks and lengths: the same for every --seed."""
    rng = np.random.default_rng([int(traffic["structure_seed"]), b])
    B, nnz = int(cfg["batch_size"]), int(cfg["nnz_per_example"])
    lengths = rng.integers(int(traffic["min_nnz"]), nnz + 1, B).astype(np.int32)
    z = rng.zipf(float(cfg["zipf_a"]), int(lengths.sum()))
    return (z - 1) % (int(cfg["n_sparse_keys"]) - 1), lengths


def _labels(keys: np.ndarray, valid: np.ndarray, rng: np.random.Generator) -> np.ndarray:
    """Planted sparse-logistic ground truth, as the program's stream plants
    it (noise 1.0)."""
    h = hash_keys(keys, LABEL_SEED)
    w = ((h >> _U64(11)).astype(np.float64) / (1 << 53) - 0.5) * 2.0
    w = np.sign(w) * (np.abs(w) ** 3) * 4.0
    logit = (w * valid).sum(axis=1)
    logit = (logit - logit.mean()) / (logit.std() + 1e-6) * 2.0
    p = 1.0 / (1.0 + np.exp(-(logit + rng.normal(0, 1.0, keys.shape[0]))))
    return (rng.random(keys.shape[0]) < p).astype(np.float32)


def make_batch(cfg: dict, traffic: dict, seed: int, b: int, keymap: KeyMap) -> Batch:
    ranks, lengths = _structure(traffic, cfg, b)
    rng = np.random.default_rng([int(seed), 1, b])
    B, nnz = int(cfg["batch_size"]), int(cfg["nnz_per_example"])
    valid = np.arange(nnz, dtype=np.int32)[None, :] < lengths[:, None]
    keys = np.zeros((B, nnz), dtype=_U64)
    keys[valid] = keymap.keys(ranks)
    raw = np.zeros((B, nnz), dtype=_U64)
    raw[valid] = splitmix64_inv(keys[valid]) ^ _U64(KEY_SEED)
    # the seed reorders the examples; the sets of ids per batch stay
    perm = rng.permutation(B)
    keys, raw, lengths, valid = keys[perm], raw[perm], lengths[perm], valid[perm]
    labels = _labels(keys, valid, rng)
    wk = np.unique(keys[valid])
    if not valid.all():
        wk = np.concatenate([np.zeros(1, dtype=_U64), wk])
    return Batch(raw, lengths, labels, keys, int(valid.sum()), wk)


def make_batches(cfg: dict, traffic: dict, seed: int, n: int, threads: int = 4):
    """Batches 0..n-1 of one run, built on a few host threads."""
    keymap = KeyMap(int(cfg["n_sparse_keys"]), np.random.default_rng([int(seed), 0]))
    with ThreadPoolExecutor(max(1, threads)) as ex:
        return list(ex.map(lambda b: make_batch(cfg, traffic, seed, b, keymap), range(n)))


def reuse_counts(batches: list[Batch]) -> list[tuple[int, int, int]]:
    """Per batch: (working rows, rows shared with the previous batch,
    previous batch's working rows). The program keeps the shared rows on
    the device and sends the rest, so these fix every shape a batch
    compiles."""
    out, prev = [], None
    for bt in batches:
        n = len(bt.working_keys)
        if prev is None:
            out.append((n, 0, 0))
        else:
            shared = len(np.intersect1d(prev, bt.working_keys, assume_unique=True))
            out.append((n, shared, len(prev)))
        prev = bt.working_keys
    return out

