#!/usr/bin/env python3
"""One-chip smoke run of the online-learning loop: train -> publish -> serve
-> retrieve, at the paper's model-C widths, through the user entry points.

Phases, in one process:

1. device   — JAX version and devices; exits 2 before any work unless JAX
              runs on a TPU.
2. cache    — the persistent compilation cache (``repro.launch.cache``).
3. kernels  — every main-path ``kernels.ops`` op at model C's widths
              against its reference on the same chip; prints the
              implementation each op took (Pallas or XLA).
4. train    — ``CTRTrainer`` with device ingest, pipelined: 4 HDFS batches of
              16,384 examples, 4 mini-batches of 4,096 each (the paper's 4M /
              1,000), 4 PS nodes, a MEM-PS smaller than the touched key set.
              Every loss must be finite, and batch 0's loss must match the
              same step computed with the reference pooling.
5. serve    — ``publish``, then ``ServingEngine.lookup_device`` must equal
              the host ``lookup`` bitwise.
6. retrieve — ``RetrievalEngine.search`` on the published version must equal
              ``kernels.ref.topk_mips_ref`` on the same chip, scores and
              indices.

A failed check does not stop the later phases; any failure exits 1. The last
line of stdout, printed only when every check passed, is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Times and examples/s printed here are smoke figures, not benchmarks.

Run from the checkout root: ``python3 chip_smoke.py [--seed N]``.
"""

from __future__ import annotations

import argparse
import dataclasses
import itertools
import json
import sys
import tempfile
import threading
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Scale of one smoke run; the model's widths are always model C's."""

    batch: int = 16_384  # examples per HDFS batch
    minibatches: int = 4  # per HDFS batch: 4,096 examples each
    # pipelined trainer.run calls, batches each. A run drains its pins when
    # it ends, so two runs of two hold at most two batches' rows pinned, and
    # the MEM-PS can be smaller than the four batches' touched key set
    runs: tuple[int, ...] = (2, 2)
    nodes: int = 4
    cache_rows: int = 1_400_000  # MEM-PS rows per node
    file_rows: int = 65_536  # rows per SSD-PS file
    bag_rows: int = 1_000_003  # kernel parity: working-table rows
    adagrad_rows: int = 2_200_003
    extract_examples: int = 16_384
    topk_shape: tuple[int, int, int] = (128, 65_536, 64)  # queries, corpus rows, k
    serve_keys: int = 512
    retrieve_queries: int = 8
    retrieve_k: int = 16


FULL = Sizes()

# absolute tolerances of the kernel parity checks
TOL_BAG = 1e-4  # f32 sums of up to 500 unit-scale terms in another order
TOL_ADAGRAD = 1e-6  # elementwise; at most one rounding of the division apart
# batch 0's first mini-batch, before any update: the poolings differ only in
# summation order, and the tower's matmuls run at default precision
TOL_LOSS_MB0 = 1e-5
# batch 0's mean over its mini-batches: Adam's first step moves every tower
# weight by +-lr, so rounding-level gradient differences between the two
# poolings flip some updates and move the later mini-batches' losses
TOL_LOSS = 1e-3


class Checks:
    """Records each check's verdict, so one failure does not hide the rest."""

    def __init__(self):
        self.failed: list[str] = []

    def record(self, name: str, ok: bool, detail: str) -> None:
        print(f"[{'PASS' if ok else 'FAIL'}] {name}: {detail}", flush=True)
        if not ok:
            self.failed.append(name)

    def close(self, name: str, impl: str, got, want, tol: float) -> None:
        got, want = np.asarray(got), np.asarray(want)
        if got.shape != want.shape:
            self.record(name, False, f"{impl}; shape {got.shape} != {want.shape}")
            return
        diff = float(np.max(np.abs(got.astype(np.float64) - want.astype(np.float64)), initial=0.0))
        finite = bool(np.isfinite(got).all())
        self.record(name, finite and diff <= tol, f"{impl}; max abs diff {diff!r} (tol {tol!r})")


def device_info() -> dict:
    import jax

    devs = jax.devices()
    d0 = devs[0]
    print(f"jax {jax.__version__}; devices {devs}")
    print(f"platform {d0.platform}; kind {d0.device_kind}; count {len(devs)}", flush=True)
    return {"platform": d0.platform, "kind": d0.device_kind, "count": len(devs)}


def _compiled(fn, *args):
    """Compile ``fn`` for ``args`` and name the implementation it took."""
    import jax

    exe = jax.jit(fn).lower(*args).compile()
    return exe, "pallas" if "tpu_custom_call" in exe.as_text() else "xla"


def _model_c(s: Sizes):
    from repro.configs.ctr_models import PAPER

    return dataclasses.replace(
        PAPER["C"], name="ctr-C-smoke", batch_size=s.batch, minibatches_per_batch=s.minibatches
    )


# ----------------------------------------------------------------- kernels
def phase_kernels(checks: Checks, s: Sizes, seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro.data.synthetic_ctr import extract_host
    from repro.kernels import ops as kops
    from repro.kernels import ref as kref
    from repro.kernels.feature_extract import feature_extract_portable

    cfg = _model_c(s)
    mb, nnz, n_slots, emb = s.batch // s.minibatches, cfg.nnz_per_example, cfg.n_slots, cfg.emb_dim
    ks = jax.random.split(jax.random.PRNGKey(seed), 12)

    # embedding_bag forward + backward (the custom VJP) vs autodiff through
    # the one-hot reference, which runs at full f32 matmul precision
    table = jax.random.normal(ks[0], (s.bag_rows, emb), jnp.float32)
    ids = jax.random.randint(ks[1], (mb, nnz), 0, s.bag_rows)
    slot_of = jax.random.randint(ks[2], (mb, nnz), 0, n_slots)
    valid = jax.random.bernoulli(ks[3], 0.5, (mb, nnz))
    cot = jax.random.normal(ks[4], (mb, n_slots, emb), jnp.float32)

    def fwd_bwd(bag):
        def f(t, i, so, v, g):
            out, vjp = jax.vjp(lambda t: bag(t, i, so, v, n_slots), t)
            return out, vjp(g)[0]

        return f

    args = (table, ids, slot_of, valid, cot)
    exe, impl = _compiled(fwd_bwd(kops.embedding_bag), *args)
    out, grad = exe(*args)
    with jax.default_matmul_precision("highest"):
        ref_out, ref_grad = jax.jit(fwd_bwd(kref.embedding_bag_ref))(*args)
    checks.close("embedding_bag fwd", impl, out, ref_out, TOL_BAG)
    checks.close("embedding_bag bwd", impl, grad, ref_grad, TOL_BAG)
    del table, out, grad, ref_out, ref_grad

    shape = (s.adagrad_rows, emb)
    p = jax.random.normal(ks[5], shape, jnp.float32)
    a = jnp.abs(jax.random.normal(ks[6], shape, jnp.float32))
    g = jax.random.normal(ks[7], shape, jnp.float32)
    exe, impl = _compiled(lambda p, a, g: kops.adagrad_update(p, a, g, 0.05), p, a, g)
    p1, a1 = exe(p, a, g)
    p2, a2 = jax.jit(lambda p, a, g: kref.adagrad_ref(p, a, g, 0.05))(p, a, g)
    checks.close("adagrad_update params", impl, p1, p2, TOL_ADAGRAD)
    checks.close("adagrad_update accum", impl, a1, a2, TOL_ADAGRAD)
    del p, a, g, p1, a1, p2, a2

    # feature_extract: against its jnp twin on the chip and the numpy feeder
    # on the host, bitwise
    rng = np.random.default_rng(seed)
    raw = rng.integers(0, 2**63, (s.extract_examples, nnz), dtype=np.uint64)
    lengths = rng.integers(1, nnz + 1, s.extract_examples).astype(np.int32)
    keys_h, slot_h, valid_h = extract_host(raw, lengths, cfg.n_sparse_keys, n_slots, pack_width=nnz)
    planes = (
        jnp.asarray((raw & np.uint64(0xFFFFFFFF)).astype(np.uint32)),
        jnp.asarray((raw >> np.uint64(32)).astype(np.uint32)),
        jnp.asarray(valid_h),
    )
    kw = dict(n_keys=cfg.n_sparse_keys, n_slots=n_slots)
    exe, impl = _compiled(lambda lo, hi, v: kops.feature_extract(lo, hi, v, **kw), *planes)
    got = [np.asarray(x) for x in exe(*planes)]
    twin = [np.asarray(x) for x in feature_extract_portable(*planes, **kw)]
    keys_d = (got[0].astype(np.uint64) << np.uint64(32)) | got[1].astype(np.uint64)
    same = all(np.array_equal(x, y) for x, y in zip(got, twin))
    same_host = np.array_equal(keys_d, keys_h) and np.array_equal(got[2], slot_h)
    checks.record(
        "feature_extract", same and same_host,
        f"{impl}; equal to jnp twin {same}, to host extraction {same_host} (tol 0)",
    )

    # topk_mips on dyadic inputs: every product and sum is exact in f32 at
    # any matmul precision, so scores and the tie-broken indices must be equal
    nq, n, k = s.topk_shape
    q = jax.random.randint(ks[8], (nq, 128), -8, 9).astype(jnp.float32) / 8
    c = jax.random.randint(ks[9], (n, 128), -8, 9).astype(jnp.float32) / 8
    exe, impl = _compiled(lambda q, c: kops.topk_mips(q, c, k), q, c)
    v1, i1 = (np.asarray(x) for x in exe(q, c))
    v2, i2 = (np.asarray(x) for x in kref.topk_mips_ref(q, c, k))
    checks.record(
        "topk_mips", np.array_equal(v1, v2) and np.array_equal(i1, i2),
        f"{impl}; scores equal {np.array_equal(v1, v2)}, indices equal "
        f"{np.array_equal(i1, i2)} (tol 0)",
    )


# ------------------------------------------------------------------- train
class CompileLog:
    """Backend compilations and persistent-cache hits, with their thread."""

    def __init__(self):
        import jax.monitoring as mon

        self.events: list[tuple[int, float]] = []  # (thread, seconds)
        self.cache_hits: list[int] = []  # thread of each hit
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append((threading.get_ident(), float(duration)))

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits.append(threading.get_ident())

    def close(self) -> None:
        import jax.monitoring as mon

        mon.unregister_event_duration_listener(self._on_duration)
        mon.unregister_event_listener(self._on_event)


class StepProbe:
    """Wraps the trainer's jitted step: keeps batch 0's inputs for the
    reference check and times each step with the compiles made inside it."""

    def __init__(self, step_fn, log: CompileLog):
        self.step_fn, self.log = step_fn, log
        self.first_args = None
        self.steps: list[dict] = []

    def __call__(self, *args):
        import jax

        if self.first_args is None:
            self.first_args = args
        tid, t0 = threading.get_ident(), time.perf_counter()
        n0, h0 = len(self.log.events), len(self.log.cache_hits)
        out = jax.block_until_ready(self.step_fn(*args))
        t1 = time.perf_counter()
        mine = [d for t, d in self.log.events[n0:] if t == tid]
        hits = sum(t == tid for t in self.log.cache_hits[h0:])
        self.steps.append({
            "step_s": t1 - t0, "compiles": len(mine), "compile_s": sum(mine),
            "cache_hits": hits, "end": t1,
        })
        return out


def reference_loss(cfg, trainer, args):
    """Batch 0's mean loss from the same k mini-batch updates, pooled by
    ``kernels.ref.embedding_bag_ref`` at full f32 matmul precision."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ref as kref
    from repro.models import ctr as ctr_model

    opt, row_lr = trainer.opt, trainer.tcfg.row_lr

    def one(carry, mb):
        tower, opt_state, table, accum = carry

        def loss(tw, tb):
            with jax.default_matmul_precision("highest"):
                pooled = kref.embedding_bag_ref(
                    tb, mb["slot_ids"], mb["slot_of"], mb["valid"], cfg.n_slots
                )
            logits = ctr_model._tower_mlp(tw, pooled.reshape(pooled.shape[0], -1))
            return ctr_model._bce_with_logits(logits, mb["labels"])

        val, (g_tower, g_table) = jax.value_and_grad(loss, argnums=(0, 1))(tower, table)
        tower, opt_state = opt.update(g_tower, opt_state, tower)
        table, accum = kref.adagrad_ref(table, accum, g_table, row_lr)
        return (tower, opt_state, table, accum), val

    def step(tower, opt_state, table, accum, mbs):
        _, losses = jax.lax.scan(one, (tower, opt_state, table, accum), mbs)
        return jnp.mean(losses)

    return float(jax.jit(step)(*args))


def phase_train(checks: Checks, s: Sizes, seed: int, workdir: str):
    from repro.core.node import Cluster
    from repro.data.synthetic_ctr import SyntheticCTRStream, extract_host
    from repro.train.trainer import CTRTrainer, TrainerConfig

    cfg = _model_c(s)
    print(
        f"model {cfg.name}: nnz {cfg.nnz_per_example}, slots {cfg.n_slots}, emb {cfg.emb_dim}, "
        f"tower {cfg.mlp_hidden}, key space {cfg.n_sparse_keys:.3g}; batch {s.batch} examples "
        f"in {s.minibatches} mini-batches (no cut); runs {s.runs}",
        flush=True,
    )
    cluster = Cluster(
        s.nodes, f"{workdir}/ps", dim=2 * cfg.emb_dim, cache_capacity=s.cache_rows,
        file_capacity=s.file_rows, init_cols=cfg.emb_dim,
    )
    trainer = CTRTrainer(
        cfg, cluster, TrainerConfig(ingest=True, publish_dir=f"{workdir}/snap"), seed=seed
    )
    log = CompileLog()
    probe = trainer.step_fn = StepProbe(trainer.step_fn, log)
    raw = SyntheticCTRStream(
        cfg.n_sparse_keys, cfg.nnz_per_example, cfg.n_slots, cfg.batch_size, seed=seed
    ).raw_records()
    first = next(raw)
    src = itertools.chain([first], raw)
    results, t0 = [], time.perf_counter()
    for n in s.runs:
        results += trainer.run(src, n)
    wall = time.perf_counter() - t0
    prev = t0
    for r, st in zip(results, probe.steps):
        print(
            f"batch {r['batch_id']}: loss {r['loss']!r}, working rows {r['n_working']}, "
            f"since previous {st['end'] - prev:.2f}s, step {st['step_s']:.2f}s with "
            f"{st['compiles']} compile requests ({st['compile_s']:.2f}s), "
            f"{st['cache_hits']} served by the persistent cache",
            flush=True,
        )
        prev = st["end"]
    n_ex = s.batch * len(results)
    print(
        f"trained {n_ex} examples in {wall:.1f}s: {n_ex / wall:.0f} examples/s "
        f"(smoke figure, compiles included; not a benchmark); all compile requests "
        f"{len(log.events)} ({sum(d for _, d in log.events):.1f}s), "
        f"{len(log.cache_hits)} served by the persistent cache",
        flush=True,
    )
    log.close()
    hits = sum(nd.mem.stats.hits for nd in cluster.nodes)
    misses = sum(nd.mem.stats.misses for nd in cluster.nodes)
    ssd = [nd.ssd.stats for nd in cluster.nodes]
    touched = sum(nd.ssd.n_live_rows for nd in cluster.nodes)
    print(
        f"MEM-PS {s.nodes} x {s.cache_rows} rows, hit rate {hits / max(1, hits + misses):.3f}; "
        f"SSD-PS live rows {touched}, files written {sum(x.files_written for x in ssd)}, "
        f"files read {sum(x.files_read for x in ssd)}, bytes read {sum(x.bytes_read for x in ssd)}",
        flush=True,
    )

    losses = np.array([r["loss"] for r in results])
    checks.record(
        "losses finite", len(results) == sum(s.runs) and bool(np.isfinite(losses).all()),
        f"{len(results)} batches, losses {losses.tolist()}",
    )
    args = probe.first_args
    mb0 = (*args[:4], {k: v[:1] for k, v in args[4].items()})
    for name, got, want, tol in (
        ("batch 0 mini-batch 0 loss vs reference pooling",
         float(probe.step_fn(*mb0)[4]["loss"]), reference_loss(cfg, trainer, mb0), TOL_LOSS_MB0),
        ("batch 0 loss vs reference pooling",
         results[0]["loss"], reference_loss(cfg, trainer, args), TOL_LOSS),
    ):
        checks.record(
            name, abs(got - want) <= tol,
            f"{got!r} vs {want!r}, diff {abs(got - want)!r} (tol {tol!r})",
        )
    keys, _, valid = extract_host(
        first.raw_ids, first.lengths, cfg.n_sparse_keys, cfg.n_slots,
        pack_width=cfg.nnz_per_example,
    )
    return trainer, np.unique(keys[valid])


# ------------------------------------------------------------------- serve
def phase_serve(checks: Checks, s: Sizes, seed: int, trainer, trained_keys):
    version = trainer.publish()
    engine = trainer.client.serving_view(
        snapshots=trainer.publisher, device_hot_rows=4 * s.serve_keys
    )
    rng = np.random.default_rng(seed)
    keys = np.sort(rng.choice(trained_keys, size=s.serve_keys, replace=False))
    half = s.serve_keys // 2
    # the second lookup overlaps the first, so it reads device-resident rows
    for name, ks in (("cold", keys[: half + half // 2]), ("warm", keys[half // 2 :])):
        slots, table_dev = engine.lookup_device(trainer.table, ks)
        dev_rows = np.asarray(table_dev)[slots]
        host_rows = engine.lookup(trainer.table, ks)
        same = dev_rows.shape == host_rows.shape and np.array_equal(
            dev_rows.view(np.uint32), host_rows.view(np.uint32)
        )
        checks.record(
            f"lookup_device {name} == lookup", same,
            f"version {version}, {len(ks)} keys, device rows reused so far "
            f"{engine.counters['device_rows_reused']} (bitwise)",
        )
    return engine, keys


# ---------------------------------------------------------------- retrieve
def phase_retrieve(checks: Checks, s: Sizes, trainer, engine, keys) -> None:
    import jax.numpy as jnp

    from repro.kernels import ref as kref
    from repro.retrieval import RetrievalEngine

    t0 = time.perf_counter()
    retr = RetrievalEngine(engine, trainer.table)
    queries = engine.lookup(trainer.table, keys[: s.retrieve_queries])
    res = retr.search(queries, s.retrieve_k)
    idx = res.index
    print(
        f"retrieval index: {idx.n_rows} rows, corpus {tuple(idx.corpus.shape)}, "
        f"version {idx.version}, built and searched in {time.perf_counter() - t0:.1f}s",
        flush=True,
    )
    qp = jnp.asarray(np.pad(queries, ((0, 0), (0, idx.corpus.shape[1] - idx.dim))))
    v, i = (np.asarray(x) for x in kref.topk_mips_ref(qp, idx.corpus, s.retrieve_k, n_valid=idx.n_rows))
    same_v, same_i = np.array_equal(res.scores, v), np.array_equal(res.indices, i)
    checks.record(
        "retrieval search == topk_mips_ref", same_v and same_i,
        f"{len(queries)} queries, k {s.retrieve_k}: scores equal {same_v}, indices equal "
        f"{same_i}, max score diff {float(np.max(np.abs(res.scores - v))):.3g} (tol 0)",
    )
    retr.close()


def run(s: Sizes, seed: int) -> list[str]:
    """Every phase after the device check; returns the failed checks."""
    checks = Checks()
    t0 = time.perf_counter()
    phase_kernels(checks, s, seed)
    print(f"kernels phase {time.perf_counter() - t0:.1f}s", flush=True)
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as workdir:
        t0 = time.perf_counter()
        trainer, trained = phase_train(checks, s, seed, workdir)
        print(f"train phase {time.perf_counter() - t0:.1f}s", flush=True)
        t0 = time.perf_counter()
        engine, keys = phase_serve(checks, s, seed, trainer, trained)
        print(f"serve phase {time.perf_counter() - t0:.1f}s", flush=True)
        t0 = time.perf_counter()
        phase_retrieve(checks, s, trainer, engine, keys)
        print(f"retrieve phase {time.perf_counter() - t0:.1f}s", flush=True)
    return checks.failed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0, help="seed of the data and the weights")
    args = ap.parse_args(argv)
    t0 = time.perf_counter()
    device = device_info()
    if device["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {device['platform']}", file=sys.stderr)
        return 2
    from repro.launch.cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    failed = run(FULL, args.seed)
    print(f"total {time.perf_counter() - t0:.1f}s", flush=True)
    if failed:
        print(f"chip_smoke: failed checks: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
