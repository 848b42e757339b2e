"""Production training launcher: any assigned arch, hier-PS embeddings.

Trains ``--arch`` on this host's devices (``--model-parallel`` splits a
model axis off the host mesh) with the paper's embedding path: token rows
pulled per batch from a PS cluster (MEM-PS/SSD-PS), row-Adagrad state on
the rows, AdamW on the backbone, async checkpoints, deterministic resume.

At production scale the same step function lowers against
``make_production_mesh()`` — that path is exercised by
``python -m repro.launch.dryrun``; this launcher is the runnable driver.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch yi-9b --scale smoke \
      --steps 50 --batch 8 --seq 128 [--ckpt-dir /tmp/ck] [--resume]
"""

from __future__ import annotations

import argparse
import os
import tempfile
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.core.client import PSClient
from repro.core.node import Cluster
from repro.core.tables import RowSchema, TableSpec
from repro.data.tokens import TokenStream
from repro.launch import sharding as shd
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import get_model
from repro.train import checkpoint as ckpt
from repro.train.optim import AdamW
from repro.train.train_step import TrainSettings, make_lm_train_step_hier


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="yi-9b")
    ap.add_argument("--scale", choices=["smoke", "full"], default="smoke")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--nodes", type=int, default=2)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()

    cfg = get_smoke_config(args.arch) if args.scale == "smoke" else get_config(args.arch)
    model = get_model(cfg)
    mesh = make_host_mesh(model=args.model_parallel)
    rules = shd.build_rules(cfg, mesh)
    shd.install_constraints(mesh, rules)

    params = model.init(cfg, jax.random.PRNGKey(0))
    settings = TrainSettings(optimizer=AdamW(lr=args.lr), microbatches=1)
    opt = settings.optimizer
    opt_state = opt.init(params)
    step = jax.jit(make_lm_train_step_hier(cfg, settings))

    base = args.ckpt_dir or tempfile.mkdtemp(prefix=f"train_{args.arch.replace('/', '_')}_")
    tok_table = TableSpec("tok_emb", RowSchema.with_adagrad(cfg.d_model))
    cluster = Cluster(
        args.nodes, os.path.join(base, "ps"), dim=cfg.d_model * 2,
        cache_capacity=max(4096, 4 * args.batch * args.seq),
        file_capacity=1024, init_scale=0.02,
    )
    client = PSClient(cluster, [tok_table])
    checkpointer = ckpt.AsyncCheckpointer(os.path.join(base, "ckpt"))

    start = 0
    if args.resume:
        tree, start, extra, manifest = ckpt.restore(
            os.path.join(base, "ckpt"), {"params": params, "opt": opt_state}
        )
        params, opt_state = tree["params"], tree["opt"]
        if manifest is not None:
            cluster = Cluster.restore(manifest, cluster.base_dir, **{
                **cluster.ctor_kwargs(), "tables": None,  # manifest's specs win
            })
            client = PSClient(cluster, [tok_table])
        print(f"resumed from step {start}")

    stream = TokenStream(cfg.vocab_size, args.batch, args.seq, seed=start)
    losses = []
    t0 = time.perf_counter()
    with mesh:
        for i in range(start, start + args.steps):
            toks = stream.next_batch()
            inputs, targets = toks[:, :-1], toks[:, 1:]
            with client.session("tok_emb", inputs.astype(np.uint64)) as s:
                batch = {"tokens": jnp.asarray(s.slots), "targets": jnp.asarray(targets)}
                if cfg.family == "audio":
                    batch["frames"] = jnp.zeros((args.batch, cfg.n_frames, cfg.d_model), jnp.bfloat16)
                if cfg.family == "vlm":
                    batch["image_embeds"] = jnp.zeros((args.batch, cfg.n_image_tokens, cfg.d_model), jnp.bfloat16)
                params, opt_state, metrics, new_t, new_acc = step(
                    params, opt_state, batch, jnp.asarray(s.params), jnp.asarray(s.opt_state)
                )
                s.commit(np.asarray(new_t), np.asarray(new_acc))
            losses.append(float(metrics["loss"]))
            if (i + 1) % 10 == 0:
                print(f"step {i+1}: loss {np.mean(losses[-10:]):.4f}")
            if args.ckpt_every and (i + 1) % args.ckpt_every == 0:
                checkpointer.save(
                    i + 1, {"params": params, "opt": opt_state},
                    ps_manifest=cluster.manifest(),
                )
    checkpointer.wait()
    shd.clear_constraints()
    dt = time.perf_counter() - t0
    tok_s = args.steps * args.batch * args.seq / dt
    print(f"{args.steps} steps in {dt:.0f}s ({tok_s:,.0f} tok/s); "
          f"loss {losses[0]:.3f} -> {np.mean(losses[-5:]):.3f}")
    hits = sum(n.mem.stats.hits for n in cluster.nodes)
    misses = sum(n.mem.stats.misses for n in cluster.nodes)
    print(f"embedding cache hit rate {hits/max(1,hits+misses):.1%}; "
          f"checkpoints in {base}/ckpt")


if __name__ == "__main__":
    main()
