"""Training cells: ``CTRTrainer.run`` pipelined with device ingest, through
the whole hierarchy (MEM-PS, SSD-PS, device working set, train step).

One ``run`` call carries both the warm-up batches and the window, so the
window holds neither the pipeline's fill nor its drain:

* set-up: data from the seed (host threads, started before JAX), the
  cluster, the trainer with the benchmark's own seeded tower, and, during
  the first step, every shape the later batches will use (the train step
  for each working-set size, and the device gathers and scatters that
  assemble each working set from the previous one);
* the window opens when the last warm-up batch's train stage completes and
  closes at the first train-stage completion at or after ``--seconds``;
  examples trained in it over its length is ``train_examples_per_s``;
* then the feed stops, the batches in flight finish, and the run ends;
* the check: the first ``warmup_batches`` steps went through the window's
  own call and feed; the reference follows them from the same weights and
  init rows, and ``benchlib.compare`` sets the two side by side.

The traffic file gives ``warmup_batches``, ``max_window_batches`` (the
window closes early if they run out), ``structure_seed``, ``min_nnz``, the
MEM-PS size of each node (``mem_ps_rows_per_node``) and ``file_rows``.
"""

from __future__ import annotations

import gc
import math
import shutil
import tempfile
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchlib import compare, gen
from benchlib.compiles import CompileLog
from benchlib.trace import SPAN_PREFIX, WINDOW_SPAN, Tracer

LEAD = 3  # batches handed to the pipeline ahead of the train stage: ring depth + 1


class WindowClosed(Exception):
    """Raised by the feed once the window has closed and every batch handed
    over has trained: ends the trainer's run."""


def prepare(cfg: dict, traffic: dict, seed: int):
    """Starts building the run's batches on host threads; no JAX here."""
    n = int(traffic["warmup_batches"]) + int(traffic["max_window_batches"])
    pool = ThreadPoolExecutor(1)
    fut = pool.submit(gen.make_batches, cfg, traffic, seed, n)
    pool.shutdown(wait=False)
    return fut


def make_tower(cfg: dict, seed: int):
    """The tower's weights from the seed, on the device in one jitted call:
    normal / sqrt(fan_in) weights, zero biases."""
    import jax
    import jax.numpy as jnp

    dims = [int(cfg["n_slots"]) * int(cfg["emb_dim"]), *map(int, cfg["mlp_hidden"]), 1]
    words = np.random.SeedSequence([int(seed), 4]).generate_state(2)

    @jax.jit
    def build(key):
        out = {}
        for i, (a, b) in enumerate(zip(dims[:-1], dims[1:])):
            out[f"w{i}"] = jax.random.normal(jax.random.fold_in(key, i), (a, b), jnp.float32) / np.sqrt(a)
            out[f"b{i}"] = jnp.zeros((b,), jnp.float32)
        return out

    return build(jax.random.PRNGKey(int(words[0]) & 0x7FFFFFFF))


class Feed:
    """The stream ``CTRTrainer.run`` reads: hands batch b over once fewer
    than LEAD earlier batches are untrained; after the window closes, waits
    for the batches in flight and ends the run."""

    def __init__(self, batches):
        self.batches = batches
        self.cv = threading.Condition()
        self.trained = 0
        self.closed = False

    def __iter__(self):
        from repro.data.synthetic_ctr import RawRecordBatch

        for b, bt in enumerate(self.batches):
            with self.cv:
                self.cv.wait_for(lambda: self.closed or b < self.trained + LEAD)
                if self.closed:
                    self.cv.wait_for(lambda: self.trained >= b)
                    raise WindowClosed
            yield RawRecordBatch(bt.raw_ids, bt.lengths, bt.labels, b)

    def done(self, closed: bool) -> None:
        with self.cv:
            self.trained += 1
            self.closed = self.closed or closed
            self.cv.notify_all()


class Probe:
    """Wraps the trainer's step and stages: keeps the first steps' inputs
    and outputs for the check, warms every later shape at the first step,
    records the harness's spans, and opens and closes the window."""

    def __init__(self, trainer, batches, warmup: int, seconds: float, trace: bool, feed: Feed):
        import jax

        self.jax = jax
        self.trainer, self.batches, self.warmup = trainer, batches, warmup
        self.seconds, self.feed = seconds, feed
        self.tracer = Tracer() if trace else None
        self.calls: list[dict] = []  # what the check reads of the first warmup+1 steps
        self.keys: dict[int, np.ndarray] = {}  # batch -> the program's working keys
        self.spans: dict[str, list[tuple[float, float]]] = {}
        self.ends: list[float] = []  # train-stage completion times
        self.t_open = self.t_close = None
        self.snap_open = self.snap_close = None
        self.compiles = CompileLog()
        self.warm_s = 0.0
        self.step_fn = trainer.step_fn
        trainer.step_fn = self._step
        for stage in ("ingest", "pull", "transfer", "train"):
            orig = getattr(trainer, f"_stage_{stage}")
            setattr(trainer, f"_stage_{stage}", self._span(stage, orig))
        self._window_ann = None

    # ------------------------------------------------------------ spans
    def _span(self, name, fn):
        ann = self.jax.profiler.TraceAnnotation
        label = SPAN_PREFIX + ("pull_push" if name == "pull" else name)

        def wrapped(item):
            t0 = time.perf_counter()
            with ann(label):
                out = fn(item)
            t1 = time.perf_counter()
            self.spans.setdefault(label[len(SPAN_PREFIX):], []).append((t0, t1))
            if name == "transfer" and out[0].batch_id <= self.warmup:
                self.keys[out[0].batch_id] = np.asarray(out[1].keys)
            if name == "train":
                self._trained(t1)
            return out

        return wrapped

    # ------------------------------------------------------------- steps
    def _step(self, *args):
        """Keeps, without waiting for the device, what the check reads:
        each warm-up step's loss, the first step's Adam state and row
        accumulator growth, and the tower and rows the next step receives."""
        jnp = self.jax.numpy
        i = len(self.calls)
        if i == 0:
            t0 = time.perf_counter()
            self._warm_shapes(args)
            self.warm_s = time.perf_counter() - t0
        out = self.step_fn(*args)
        if i < self.warmup:
            keep = {"loss": out[4]["loss"]}
            if i == 0:
                keep["m"] = out[1].m
                keep["rows_grow"] = jnp.sum(out[3] - args[3])
            self.calls.append(keep)
        elif i == self.warmup:
            self.calls.append({"tower": args[0], "table": args[2]})
        return out

    def _warm_shapes(self, args) -> None:
        """Compiles the step for every later working-set size and runs the
        device assembly of every later working set once on zeros, so that
        nothing compiles inside the window."""
        jax, jnp = self.jax, self.jax.numpy
        from repro.core.hbm_ps import DeviceWorkingSet, ReusePlan

        counts = gen.reuse_counts(self.batches)
        struct = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
        tower, opt, _, _, mbs = jax.tree.map(struct, args)
        emb = args[2].shape[1]
        for n in sorted({c[0] for c in counts[1:]}):
            rows = jax.ShapeDtypeStruct((n, emb), args[2].dtype)
            self.step_fn.lower(tower, opt, rows, rows, mbs).compile()
        for n, shared, n_prev in counts[1:]:
            idx = lambda m: np.arange(m, dtype=np.int32)
            plan = ReusePlan(n, 0, idx(shared), idx(shared), np.arange(shared, n, dtype=np.int32))
            out = DeviceWorkingSet.assemble(
                jnp.zeros((n_prev, emb), args[2].dtype), jnp.zeros((n - shared, emb), args[2].dtype), plan
            )
            out.block_until_ready()

    # ------------------------------------------------------------ window
    def _snapshot(self, t: float) -> dict:
        nodes = self.trainer.cluster.nodes
        ws = self.trainer.dev_ws.stats
        return {
            "t": t,
            "mem_hits": sum(nd.mem.stats.hits for nd in nodes),
            "mem_misses": sum(nd.mem.stats.misses for nd in nodes),
            "ssd_bytes_read": sum(nd.ssd.stats.bytes_read for nd in nodes),
            "ssd_files_read": sum(nd.ssd.stats.files_read for nd in nodes),
            "ssd_bytes_written": sum(nd.ssd.stats.bytes_written for nd in nodes),
            "rows_reused": ws.rows_reused,
            "rows_transferred": ws.rows_transferred,
        }

    def _trained(self, t: float) -> None:
        self.ends.append(t)
        done = len(self.ends)
        close = False
        if done == self.warmup:
            self.t_open = t
            self.snap_open = self._snapshot(t)
            if self.tracer is not None:
                self.tracer.start()
                self._window_ann = self.jax.profiler.TraceAnnotation(WINDOW_SPAN)
                self._window_ann.__enter__()
            self.t_open = time.perf_counter()
        elif self.t_open is not None and self.t_close is None and (
            t - self.t_open >= self.seconds or done == len(self.batches)
        ):
            self.t_close = t
            self.snap_close = self._snapshot(t)
            close = True
            if self.tracer is not None:
                self._window_ann.__exit__(None, None, None)
                self.trace = self.tracer.stop()
        self.feed.done(close)


def _program_readings(probe: Probe, cfg: dict, warmup: int) -> dict:
    """The program's side of ``benchlib.compare.train_numbers``."""
    import jax.numpy as jnp

    calls = probe.calls
    losses = [float(c["loss"]) for c in calls[:warmup]]
    grad = {n: float(jnp.linalg.norm(x)) for n, x in calls[0]["m"].items()}
    grad["rows"] = math.sqrt(max(0.0, float(calls[0]["rows_grow"])))
    tower_next = {n: np.asarray(x) for n, x in calls[warmup]["tower"].items()}
    table_next = np.asarray(calls[warmup]["table"])
    return {"losses": losses, "grad": grad, "tower_next": tower_next, "table_next": table_next}


def run(ctx) -> dict:
    """One run of a training cell; ``ctx`` has cfg, traffic, seed, seconds,
    trace, limits, t_start, prepared, peaks."""
    import jax

    from repro.configs.ctr_models import CTRConfig
    from repro.core.node import Cluster
    from repro.core.pipeline import PipelineError
    from repro.train.trainer import CTRTrainer, TrainerConfig

    cfg, tr = ctx["cfg"], ctx["traffic"]
    warmup = int(tr["warmup_batches"])
    batches = ctx["prepared"].result()
    ctr = CTRConfig(
        cfg["name"], int(cfg["n_sparse_keys"]), int(cfg["nnz_per_example"]), int(cfg["emb_dim"]),
        int(cfg["n_slots"]), tuple(cfg["mlp_hidden"]), int(cfg["batch_size"]),
        int(cfg["minibatches_per_batch"]), float(cfg["zipf_a"]),
    )
    cache_rows = int(tr["mem_ps_rows_per_node"])
    workdir = tempfile.mkdtemp(prefix="bench_ps_")
    try:
        cluster = Cluster(
            int(cfg["ps_nodes"]), f"{workdir}/ps", dim=2 * ctr.emb_dim, cache_capacity=cache_rows,
            file_capacity=int(tr["file_rows"]), init_cols=ctr.emb_dim,
            init_scale=float(cfg["row_init_scale"]),
        )
        o, r = cfg["tower_optimizer"], cfg["row_optimizer"]
        trainer = CTRTrainer(
            ctr, cluster, TrainerConfig(ingest=True, row_lr=r["lr"], tower_lr=o["lr"]), seed=0
        )
        opt = trainer.opt
        want = (o["b1"], o["b2"], o["eps"], o["clip_norm"], 0.0)
        have = (opt.b1, opt.b2, opt.eps, opt.clip_norm, opt.weight_decay)
        if want != have:
            raise RuntimeError(f"trainer's AdamW {have} is not the configuration's {want}")
        tower0 = make_tower(cfg, ctx["seed"])
        trainer.tower = tower0
        trainer.opt_state = opt.init(tower0)
        feed = Feed(batches)
        probe = Probe(trainer, batches, warmup, ctx["seconds"], ctx["trace"], feed)
        failed, err = 0, None
        try:
            trainer.run(iter(feed), len(batches))
        except PipelineError as e:
            if not isinstance(e.__cause__, WindowClosed):
                failed, err = 1, e
        if probe.t_close is None:
            failed, err = 1, err or RuntimeError("the window never closed")
        memory_peak = ctx["memory_peak"]()
        t_open, t_close = probe.t_open, probe.t_close
        in_window = [i for i, t in enumerate(probe.ends) if t_open < t <= (t_close or -1)]
        n_win = len(in_window)
        examples = n_win * ctr.batch_size
        window_s = (t_close - t_open) if t_close else math.nan
        prog = _program_readings(probe, cfg, warmup) if len(probe.calls) > warmup else None
        keys_next = probe.keys.get(warmup)
        compiles = probe.compiles.between(t_open, t_close) if t_close else {}
        probe.compiles.close()
        layer = {
            "cfg": cfg, "peaks": ctx["peaks"], "window_s": window_s, "examples": examples,
            "valid_ids": sum(batches[warmup + i].n_valid for i in range(n_win)),
            "window_batches": n_win,
            "working_rows": [len(batches[warmup + i].working_keys) for i in range(n_win)],
            "open": probe.snap_open, "close": probe.snap_close,
            "spans": probe.spans, "t_open": t_open, "t_close": t_close,
            "compiles": compiles, "trace": getattr(probe, "trace", None),
            "minibatches": ctr.minibatches_per_batch,
        }
        setup_s = t_open - ctx["t_start"] if t_open else math.nan
        info = {
            "warm_shapes_s": probe.warm_s, "cache_rows": cache_rows, "window_batches": n_win,
            "ssd_bytes_written": sum(nd.ssd.stats.bytes_written for nd in cluster.nodes),
        }
        del trainer, cluster, probe, feed
        gc.collect()
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    checks, readings = _check(ctx, batches, tower0, prog, keys_next, warmup)
    info["readings"] = readings
    del tower0
    return {
        "e2e": {"train_examples_per_s": examples / window_s, "setup_s": setup_s},
        "layer": layer, "checks": checks, "attempted": n_win + warmup, "failed": failed,
        "memory_peak_bytes": memory_peak, "error": err, "info": info,
    }


def _check(ctx, batches, tower0, prog, keys_next, warmup: int) -> dict:
    cfg, lim = ctx["cfg"], ctx["limits"]["limits"]
    readings = None
    if prog is None or keys_next is None:
        nums = {k: math.inf for k in lim}
    else:
        ref_mod = ctx["reference"]
        tower0_np = {n: np.asarray(x) for n, x in tower0.items()}
        ref = ref_mod.readings(cfg, batches, tower0_np, warmup, keys_next)
        change = {
            n: float(np.linalg.norm(np.asarray(prog["tower_next"][n], np.float64) - tower0_np[n]))
            for n in tower0_np
        }
        init = gen.init_rows(keys_next, int(cfg["emb_dim"]), float(cfg["row_init_scale"]))
        change["rows"] = float(np.linalg.norm(prog["table_next"].astype(np.float64) - init))
        mine = {"losses": prog["losses"], "grad": prog["grad"], "change": change}
        nums = compare.train_numbers(mine, ref)
        readings = {"program": mine, "reference": ref}
    return {k: {"value": nums[k], "limit": lim[k]} for k in lim}, readings
