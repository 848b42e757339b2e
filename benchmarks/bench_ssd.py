"""Fig 5a: SSD-PS I/O time per batch, with compaction kicking in.

Paper: I/O time hikes once the disk-usage threshold triggers file
compaction (batch ~54 for model E) and fluctuates thereafter. We drive
update churn until stale fractions trip the compactor and report the I/O +
compaction time series and the space bound.
"""

from __future__ import annotations

import tempfile
import time

import numpy as np

from benchmarks.common import QUICK, emit, note
from repro.core.ssd_ps import SSDParameterServer


def main() -> None:
    note("Fig 5a: SSD I/O time per batch with compaction (log-structured files)")
    n_keys = 60_000 if QUICK else 200_000
    n_batches = 20 if QUICK else 40
    rng = np.random.default_rng(0)
    with tempfile.TemporaryDirectory() as tmp:
        ssd = SSDParameterServer(tmp, dim=16, file_capacity=4096)
        keys = np.arange(n_keys, dtype=np.uint64)
        ssd.write_batch(keys, rng.random((n_keys, 16)).astype(np.float32))
        marks = set(range(0, n_batches, max(1, n_batches // 8)))
        for i in range(n_batches):
            sub = rng.choice(keys, size=n_keys // 8, replace=False).astype(np.uint64)
            vals = rng.random((len(sub), 16)).astype(np.float32)
            t0 = time.perf_counter()
            ssd.read_batch(sub[: len(sub) // 4])
            ssd.write_batch(sub, vals)
            dt = time.perf_counter() - t0
            if i in marks or i == n_batches - 1:
                emit(
                    f"fig5a.batch{i:03d}",
                    dt * 1e6,
                    f"compactions={ssd.stats.compactions} space_amp={ssd.space_amplification():.2f} "
                    f"read_amp={ssd.stats.read_amplification:.2f}",
                )
        assert ssd.space_amplification() <= 2.5
        note(f"space amplification bounded: {ssd.space_amplification():.2f} <= 2x + in-flight")


if __name__ == "__main__":
    main()
