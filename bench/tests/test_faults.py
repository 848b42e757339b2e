"""The comparison that decides ``correct`` catches a broken timed path.

Each test skips the harness's look for a chip, runs the rest of a cell at a
small size on the CPU with the program broken underneath it, and sees
``correct`` come out false; an unbroken run of the same size comes out
true. Faults: a step that returns its state unchanged; half of each batch
left out, the mean taken over the rest; an answer altered where it is
produced (a tower weight moved after the step). The exchange between chips
does not exist in these one-chip cells. The control, the cell's reference computed in bfloat16 and held to
the same limits in the program's place, fails one of them too.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""

from __future__ import annotations

import sys
import time
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import run as bench_run  # noqa: E402
from benchlib import compare, gen, spec  # noqa: E402

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def small(workload: str):
    """The cell's configuration and traffic at a size the CPU runs in
    seconds; limits as the cell has them."""
    bm = spec.benchmark()
    cell = spec.workload(bm, workload)
    cfg, tr = spec.config(cell["config"]), spec.traffic(cell["traffic"])
    # the CPU backend computes a float32 matmul at full precision whatever
    # the requested precision, so the reference does too
    cfg["matmul_precision"] = "highest"
    cfg.update(n_sparse_keys=200_000, nnz_per_example=32, n_slots=8, mlp_hidden=[32, 16],
               batch_size=256, minibatches_per_batch=4)
    tr.update(max_window_batches=2, file_rows=256, mem_ps_rows_per_node=2500)
    return bm, cell, cfg, tr


def correct(workload: str, seed: int = 2**33 + 3) -> tuple[bool, dict]:
    bm, cell, cfg, tr = small(workload)
    drv = spec.runner(tr["runner"])
    ctx = {
        "cfg": cfg, "traffic": tr, "seed": seed, "seconds": 0.5, "trace": False,
        "limits": spec.limits(cell["name"]), "reference": spec.reference(cfg["reference"][tr["runner"]]),
        "t_start": time.perf_counter(), "prepared": drv.prepare(cfg, tr, seed),
        "peaks": PEAKS, "memory_peak": lambda: None, "workload": cell["name"],
    }
    out = drv.run(ctx)
    line = bench_run.result_line(bm, cell, out, CPU, False)
    return line["correct"], line["checks"]


def train_cells():
    return [w["name"] for w in spec.benchmark()["workloads"]
            if spec.traffic(w["traffic"])["runner"] == "train"]


def _patch_step(monkeypatch, broken):
    import repro.train.trainer as trainer_mod

    real = trainer_mod.make_ctr_train_step

    def factory(*a, **kw):
        return broken(real(*a, **kw))

    monkeypatch.setattr(trainer_mod, "make_ctr_train_step", factory)


def _unchanged(step):
    def f(tower, opt, table, accum, mbs):
        out = step(tower, opt, table, accum, mbs)
        return tower, opt, table, accum, out[4]

    return f


def _half_batch(step):
    def f(tower, opt, table, accum, mbs):
        half = {k: v[:, : v.shape[1] // 2] for k, v in mbs.items()}
        return step(tower, opt, table, accum, half)

    return f


def _altered(step):
    def f(tower, opt, table, accum, mbs):
        t, o, tb, ac, m = step(tower, opt, table, accum, mbs)
        t = dict(t, w0=t["w0"].at[0, 0].add(1.0))
        return t, o, tb, ac, m

    return f


@pytest.mark.parametrize("workload", train_cells())
def test_train_sound_run_is_correct(workload):
    ok, checks = correct(workload)
    assert ok, checks


@pytest.mark.parametrize("fault", [_unchanged, _half_batch, _altered], ids=["unchanged", "half", "altered"])
@pytest.mark.parametrize("workload", train_cells())
def test_train_fault_is_not_correct(monkeypatch, workload, fault):
    _patch_step(monkeypatch, fault)
    ok, checks = correct(workload)
    assert not ok, checks


def _over_a_limit(workload: str, nums: dict) -> bool:
    lim = spec.limits(workload)["limits"]
    return any(not nums[k] <= v for k, v in lim.items())


@pytest.mark.parametrize("workload", train_cells())
def test_train_control_is_not_correct(workload, seed=2**33 + 5):
    """The reference in bfloat16, put in the program's place, fails a limit."""
    _, cell, cfg, tr = small(workload)
    drv, ref = spec.runner(tr["runner"]), spec.reference(cfg["reference"]["train"])
    w = int(tr["warmup_batches"])
    batches = gen.make_batches(cfg, tr, seed, w + 1)
    tower0 = {n: np.asarray(x) for n, x in drv.make_tower(cfg, seed).items()}
    r32 = ref.readings(cfg, batches, tower0, w, batches[w].working_keys)
    r16 = ref.readings(cfg, batches, tower0, w, batches[w].working_keys, dtype="bfloat16")
    assert _over_a_limit(cell["name"], compare.train_numbers(r16, r32))


@pytest.mark.parametrize("workload", train_cells())
def test_train_half_batch_reference_is_not_correct(workload, seed=2**33 + 7):
    """The half-batch fault as the chip readings plant it, in the reference
    put in the program's place (``readings.halved``), fails a limit."""
    from readings import halved

    _, cell, cfg, tr = small(workload)
    drv, ref = spec.runner(tr["runner"]), spec.reference(cfg["reference"]["train"])
    w = int(tr["warmup_batches"])
    batches = gen.make_batches(cfg, tr, seed, w + 1)
    tower0 = {n: np.asarray(x) for n, x in drv.make_tower(cfg, seed).items()}
    keys = batches[w].working_keys
    full = ref.readings(cfg, batches, tower0, w, keys)
    half = ref.readings(cfg, halved(batches[:w], int(cfg["minibatches_per_batch"])), tower0, w, keys)
    assert _over_a_limit(cell["name"], compare.train_numbers(half, full))
