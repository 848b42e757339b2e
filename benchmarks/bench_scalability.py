"""Fig 5b: training throughput speedup over 1/2/4 nodes.

On one host, N logical nodes share the CPU, so wall-clock scaling is
meaningless; we reproduce the paper's *model* of scaling instead: per-batch
virtual time = max over nodes of (local SSD/cache work of its key shard) +
NIC transfer time for remote rows, with each node processing 1/N of the
global batch. The derived column reports speedup vs 1 node (paper: 3.57/4).
"""

from __future__ import annotations

import tempfile
import time

import numpy as np

from benchmarks.common import QUICK, emit, note
from repro.core.node import Cluster, NetworkModel
from repro.data.synthetic_ctr import SyntheticCTRStream


def run(n_nodes: int, tmp: str) -> float:
    """Virtual seconds per global batch."""
    n_keys, nnz, global_batch = 200_000, 100, 4096
    n_batches = 3 if QUICK else 6
    cl = Cluster(n_nodes, f"{tmp}/n{n_nodes}", dim=16, cache_capacity=50_000 // n_nodes,
                 file_capacity=4096, network=NetworkModel())
    stream = SyntheticCTRStream(n_keys, nnz, 32, global_batch, seed=0)
    virtual = 0.0
    for _ in range(n_batches):
        b = stream.next_batch()
        per_node = np.array_split(np.unique(b.keys), n_nodes)
        node_times = []
        for req, shard_keys in enumerate(per_node):
            t0 = time.perf_counter()
            nic0 = cl.network.virtual_time
            cl.pull(shard_keys.astype(np.uint64), requester=req, pin=False)
            host = time.perf_counter() - t0
            nic = cl.network.virtual_time - nic0
            node_times.append(host + nic)
        virtual += max(node_times)  # nodes run in parallel
    return virtual / n_batches


def main() -> None:
    note("Fig 5b: scalability 1/2/4 nodes (virtual-time model, shared-host)")
    with tempfile.TemporaryDirectory() as tmp:
        base = run(1, tmp)
        emit("fig5b.nodes1", base * 1e6, "speedup=1.00x")
        for n in (2, 4):
            t = run(n, tmp)
            emit(f"fig5b.nodes{n}", t * 1e6, f"speedup={base / t:.2f}x ideal={n}.0x")


if __name__ == "__main__":
    main()
