"""Quickstart: the hierarchical parameter server in ~60 lines.

Builds a 2-node PS cluster (MEM-PS cache over SSD-PS files), opens a named
table on it, pulls a batch session's working set, trains k mini-batches on
device, commits the updates back — Algorithm 1 of the paper, end to end,
through the multi-table client API (PSClient / TableSpec / BatchSession).

Run:  PYTHONPATH=src python examples/quickstart.py
"""

import tempfile

import jax
import numpy as np

from repro.configs.ctr_models import TINY
from repro.core.client import PSClient
from repro.core.node import Cluster
from repro.core.tables import RowSchema, TableSpec
from repro.data.synthetic_ctr import SyntheticCTRStream
from repro.launch.cache import enable_compile_cache
from repro.models import ctr as ctr_model
from repro.train.optim import AdamW
from repro.train.train_step import make_ctr_train_step


def main():
    enable_compile_cache()
    cfg = TINY
    tmp = tempfile.mkdtemp(prefix="hps_quickstart_")

    # 3-tier PS: SSD files <- DRAM cache <- device working table. The
    # cluster hosts one named table whose rows pack [emb | adagrad accum].
    cluster = Cluster(
        n_nodes=2, base_dir=tmp, dim=cfg.emb_dim * 2,
        cache_capacity=4096, file_capacity=128,
    )
    client = PSClient(cluster, [TableSpec("ctr", RowSchema.with_adagrad(cfg.emb_dim))])

    tower = ctr_model.init_tower(cfg, jax.random.PRNGKey(0))
    opt = AdamW(lr=1e-3)
    opt_state = opt.init(tower)
    step = jax.jit(make_ctr_train_step(cfg, row_lr=0.05, tower_opt=opt))

    stream = SyntheticCTRStream(
        cfg.n_sparse_keys, cfg.nnz_per_example, cfg.n_slots, cfg.batch_size, seed=0
    )
    for i in range(10):
        batch = stream.next_batch()
        # session = pull + dedup + renumber (pinned); commit = push + unpin
        with client.session("ctr", batch.keys) as s:
            k = cfg.minibatches_per_batch
            mb = cfg.batch_size // k
            stack = lambda a: jax.numpy.asarray(a.reshape((k, mb) + a.shape[1:]))
            minibatches = {
                "slot_ids": stack(s.slots),
                "slot_of": stack(batch.slot_of),
                "valid": stack(batch.valid),
                "labels": stack(batch.labels),
            }
            tower, opt_state, table, accum, metrics = step(
                tower, opt_state, jax.numpy.asarray(s.params),
                jax.numpy.asarray(s.opt_state), minibatches
            )
            s.commit(np.asarray(table), np.asarray(accum))
        print(f"batch {i}: loss={float(metrics['loss']):.4f} working_set={s.n_working}")

    hits = sum(n.mem.stats.hits for n in cluster.nodes)
    misses = sum(n.mem.stats.misses for n in cluster.nodes)
    print(f"MEM-PS hit rate: {hits / (hits + misses):.1%}; "
          f"remote bytes: {cluster.network.bytes_moved:,}")
    cluster.destroy()


if __name__ == "__main__":
    main()
