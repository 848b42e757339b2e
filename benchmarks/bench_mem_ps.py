"""MEM-PS benchmarks: Fig 4b pull split + the batch hot-path trajectory.

Two parts:

* ``main()`` — the paper's Fig 4b observation (local vs remote pull time
  stays roughly flat with node count), unchanged harness contract.
* ``bench_throughput()`` — pull/push rows-per-second of one MEM-PS at
  10k/100k unique keys plus a Zipf hit-rate sweep, written to
  ``BENCH_mem_ps.json`` at the repo root. This file is the perf
  trajectory: future PRs compare against it before touching the hot path
  (`python benchmarks/run.py --smoke` regenerates it in <60s).

``SEED_BASELINE_ROWS_PER_S`` pins the pre-vectorization (per-key
OrderedDict loop) numbers measured in this container, so the recorded
speedup is against a fixed reference rather than a moving one.
"""

from __future__ import annotations

import json
import os
import tempfile
import time

import numpy as np

from benchmarks.common import QUICK, emit, note
from repro.core.mem_ps import MemParameterServer
from repro.core.node import Cluster, NetworkModel
from repro.core.ssd_ps import SSDParameterServer
from repro.data.synthetic_ctr import SyntheticCTRStream

BENCH_JSON = os.path.join(os.path.dirname(__file__), "..", "BENCH_mem_ps.json")

# rows/s of the seed's per-key-loop MEM-PS, measured in this container
# (dim=16, warm cache, 2x-capacity, sorted unique keys) before the
# vectorized rewrite — the fixed reference for the perf trajectory.
SEED_BASELINE_ROWS_PER_S = {
    "10000": {"pull_hit": 381_199, "push": 697_528},
    "100000": {"pull_hit": 403_495, "push": 727_060},
}


def _best(fn, repeats: int, warmup: int = 6) -> float:
    for _ in range(warmup):  # page-fault / frequency-scaling warmup
        fn()
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def bench_throughput(out_path: str = BENCH_JSON) -> dict:
    note("MEM-PS batch hot path: pull/push rows-per-second (perf trajectory)")
    repeats = 5 if QUICK else 9
    dim = 16
    results: dict = {
        "bench": "mem_ps",
        "dim": dim,
        "quick": QUICK,
        "seed_baseline_rows_per_s": SEED_BASELINE_ROWS_PER_S,
        "throughput": {},
        "hit_rate_sweep": [],
    }
    for n in (10_000, 100_000):
        with tempfile.TemporaryDirectory() as tmp:
            ssd = SSDParameterServer(tmp, dim=dim, file_capacity=4096)
            mem = MemParameterServer(ssd, capacity=2 * n)
            keys = np.sort(
                np.random.default_rng(0).permutation(np.arange(3 * n, dtype=np.uint64))[:n]
            )
            t0 = time.perf_counter()
            mem.pull(keys, pin=False)  # cold: SSD-miss path
            t_cold = time.perf_counter() - t0
            rows = mem.pull(keys, pin=True)
            mem.push(keys, rows)  # warm both paths
            t_pull = _best(lambda: mem.pull(keys, pin=True), repeats)
            t_push = _best(lambda: mem.push(keys, rows), repeats)
            entry = {
                "pull_cold_rows_per_s": round(n / t_cold),
                "pull_hit_rows_per_s": round(n / t_pull),
                "push_rows_per_s": round(n / t_push),
                "pull_push_cycle_ms": round((t_pull + t_push) * 1e3, 3),
            }
            results["throughput"][str(n)] = entry
            emit(f"mem_ps.pull_hit.{n}", t_pull * 1e6,
                 f"rows_per_s={entry['pull_hit_rows_per_s']}")
            emit(f"mem_ps.push.{n}", t_push * 1e6,
                 f"rows_per_s={entry['push_rows_per_s']}")
            base = SEED_BASELINE_ROWS_PER_S[str(n)]
            seed_cycle = n / base["pull_hit"] + n / base["push"]
            speed = {
                "pull_hit": round(entry["pull_hit_rows_per_s"] / base["pull_hit"], 2),
                "push": round(entry["push_rows_per_s"] / base["push"], 2),
                # the headline gate: combined pull+push cycle time vs seed
                "pull_push_cycle": round(seed_cycle / (t_pull + t_push), 2),
            }
            results["throughput"][str(n)]["speedup_vs_seed"] = speed
            note(
                f"n={n}: {speed['pull_hit']}x pull, {speed['push']}x push, "
                f"{speed['pull_push_cycle']}x pull+push cycle vs seed"
            )
    # Zipf hit-rate sweep (Fig 4c flavour): capacity vs achieved hit rate
    n_hot, batches = 4096, (10 if QUICK else 50)
    for capacity in (256, 512, 1024, 2048):
        with tempfile.TemporaryDirectory() as tmp:
            ssd = SSDParameterServer(tmp, dim=dim, file_capacity=1024)
            mem = MemParameterServer(ssd, capacity=capacity)
            rng = np.random.default_rng(1)
            for _ in range(batches):
                ranks = (rng.zipf(1.2, size=256) - 1) % n_hot
                mem.pull(np.unique(ranks.astype(np.uint64)), pin=False)
            results["hit_rate_sweep"].append(
                {"capacity": capacity, "key_space": n_hot,
                 "hit_rate": round(mem.stats.hit_rate, 4)}
            )
            emit(f"mem_ps.hit_rate.cap{capacity}", 0.0,
                 f"hit_rate={mem.stats.hit_rate:.3f}")
    with open(out_path, "w") as f:
        json.dump(results, f, indent=2, sort_keys=True)
        f.write("\n")
    note(f"perf trajectory written to {os.path.abspath(out_path)}")
    return results


def main() -> None:
    note("Fig 4b: local/remote pull split vs node count (model E scaled)")
    n_keys, nnz, batch = 400_000, 100, 2048
    n_batches = 4 if QUICK else 8
    for n_nodes in (1, 2, 4):
        with tempfile.TemporaryDirectory() as tmp:
            cl = Cluster(
                n_nodes, tmp, dim=16,
                cache_capacity=60_000, file_capacity=4096,
                network=NetworkModel(),
            )
            stream = SyntheticCTRStream(n_keys, nnz, 32, batch, seed=0)
            pull_s = 0.0
            for _ in range(n_batches):
                b = stream.next_batch()
                uniq = np.unique(b.keys)
                t0 = time.perf_counter()
                cl.pull(uniq, requester=0, pin=False)
                pull_s += time.perf_counter() - t0
            total = pull_s + cl.network.virtual_time
            emit(
                f"fig4b.nodes{n_nodes}",
                total / n_batches * 1e6,
                f"nic_virtual_s={cl.network.virtual_time:.4f}",
            )
    bench_throughput()


if __name__ == "__main__":
    main()
