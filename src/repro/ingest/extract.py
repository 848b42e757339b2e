"""Device-side feature extraction over staged raw records (DESIGN.md §11).

:class:`DeviceIngestor` is the ingest pipeline stage's engine: it takes a
:class:`~repro.data.synthetic_ctr.RawRecordBatch` (unhashed uint64 feature-id
surrogates, ragged per-example nnz), stages the raw planes through the
:class:`~repro.ingest.staging.StagingRing`, and runs the fused hash +
slot-bucket kernel (:func:`repro.kernels.ops.feature_extract`) on device —
emitting the exact ``(keys, slot_of, valid)`` layout the embedding-bag
kernel consumes.

Parity contract: for any raw batch, the produced planes are **bitwise
equal** to the host feeder's numpy extraction
(:func:`repro.data.synthetic_ctr.extract_host`) at the same pack width —
keys hashed with the same splitmix64 mix (u32-pair emulated on device),
slots hashed over the finished key, padding pinned to key 0 / slot 0.
Pinned in tests/test_ingest.py.

The pull/push stage still needs the batch's keys on host (the PS hierarchy
is a host subsystem), so the extracted key pair planes make one device→host
hop — also modelled through the NIC so staging benches account for it (two
u32 planes = the same 8 bytes/key a u64 plane would be). Everything
else (slot_of, valid, labels) stays device-resident: the transfer stage
reshapes device arrays instead of re-uploading host ones.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import numpy as np

from repro import tracing
from repro.data.synthetic_ctr import KEY_SEED, SLOT_SEED, RawRecordBatch
from repro.ingest.staging import StagedBatch, StagingRing
from repro.kernels import ops as kops

_MASK32 = np.uint64(0xFFFFFFFF)


@dataclass
class IngestedBatch:
    """A train-ready batch whose planes live on device.

    Duck-types ``CTRBatch`` for the trainer's pull/transfer/train stages:
    ``keys`` is host uint64 (the PS pull needs host keys); ``slot_of`` /
    ``valid`` / ``labels`` are device arrays from the staging slot. The
    train stage releases ``staged`` when the batch's step commits.
    """

    keys: np.ndarray  # uint64 [B, P] — host, for the PS pull
    slot_of: Any  # int32 [B, P] — device
    valid: Any  # bool [B, P] — device
    labels: Any  # float32 [B] — device
    batch_id: int
    staged: StagedBatch | None = None


class DeviceIngestor:
    """Raw records → staged, device-extracted batches."""

    def __init__(
        self,
        *,
        n_keys: int,
        n_slots: int,
        pack_width: int,
        network=None,
        deps=None,
        counters=None,
        depth: int = 2,
        key_seed: int = KEY_SEED,
        slot_seed: int = SLOT_SEED,
        use_pallas: bool | None = None,
        interpret: bool | None = None,
    ):
        self.n_keys = n_keys
        self.n_slots = n_slots
        self.pack_width = pack_width
        self.key_seed = key_seed
        self.slot_seed = slot_seed
        self.use_pallas = use_pallas
        self.interpret = interpret
        self.network = network
        self.ring = StagingRing(
            depth=depth, network=network, deps=deps, counters=counters
        )
        self.counters = self.ring.counters

    def ingest(self, raw: RawRecordBatch) -> IngestedBatch:
        """Stage one raw batch and extract its features on device."""
        B, L = raw.raw_ids.shape
        P = self.pack_width
        ids = np.asarray(raw.raw_ids, dtype=np.uint64)[:, :P]
        if L < P:  # reader rows narrower than the pack width: pad (invalid)
            ids = np.pad(ids, ((0, 0), (0, P - L)))
        lengths = np.asarray(raw.lengths, dtype=np.int32)
        valid = np.arange(P, dtype=np.int32)[None, :] < lengths[:, None]
        staged = self.ring.stage(
            raw.batch_id,
            {
                # u64 raw ids travel as two u32 planes (no u64 on device)
                "raw_lo": (ids & _MASK32).astype(np.uint32),
                "raw_hi": (ids >> np.uint64(32)).astype(np.uint32),
                "valid": valid,
                "labels": np.asarray(raw.labels, dtype=np.float32),
            },
        )
        with tracing.span("ingest.extract", batch=raw.batch_id):
            hi_dev, lo_dev, slot_dev = kops.feature_extract(
                staged.tensors["raw_lo"],
                staged.tensors["raw_hi"],
                staged.tensors["valid"],
                n_keys=self.n_keys,
                n_slots=self.n_slots,
                key_seed=self.key_seed,
                slot_seed=self.slot_seed,
                use_pallas=self.use_pallas,
                interpret=self.interpret,
            )
            # the one device->host hop: the PS pull wants host u64 keys, so
            # the two u32 planes (8 bytes/key, same wire cost as before the
            # key space widened past 2^32) recombine here. np.asarray blocks
            # until the extraction is done, so downstream stages never see a
            # half-written plane.
            keys = (
                np.asarray(hi_dev).astype(np.uint64) << np.uint64(32)
            ) | np.asarray(lo_dev).astype(np.uint64)
        if self.network is not None:
            self.network.transfer(int(keys.nbytes))
        self.counters.inc("ingest_examples", B)
        return IngestedBatch(
            keys=keys,
            slot_of=slot_dev,
            valid=staged.tensors["valid"],
            labels=staged.tensors["labels"],
            batch_id=raw.batch_id,
            staged=staged,
        )

    def release(self, batch: IngestedBatch) -> None:
        if batch.staged is not None:
            self.ring.release(batch.staged)

    def reset(self) -> None:
        self.ring.reset()
