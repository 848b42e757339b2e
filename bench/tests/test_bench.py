"""Checks of the benchmark's own arithmetic, discovery and refusal, on the CPU.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""

from __future__ import annotations

import json
import os
import statistics
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

from benchlib import flops, gen, spec, stats, trace  # noqa: E402
from benchlib.peaks import peaks  # noqa: E402

DATA = BENCH / "tests" / "data"


# ------------------------------------------------------------------ trace
def _toy_record():
    # window 0..100 ns; ops at 10-30, 20-40 (overlap), 60-70, and 95-120
    # (clipped at 100); spans say what the host did
    return {
        "window": [0.0, 100.0],
        "device_ops": [
            ["%fusion.1 = f32[8] fusion(f32[8] %p)", 10.0, 20.0],
            ["%adagrad_pallas.2 = f32[8,8] custom-call(f32[8,8] %a)", 20.0, 20.0],
            ["%fusion.1 = f32[8] fusion(f32[8] %p)", 60.0, 10.0],
            ["%copy.3 = f32[8] copy(f32[8] %q)", 95.0, 25.0],
            ["%while.4 = (f32[8]) while((f32[8]) %t)", 10.0, 30.0],
        ],
        "spans": [
            ["bench:window", 0.0, 100.0],
            ["bench:pull_push", 0.0, 100.0],
            ["bench:train", 40.0, 15.0],
        ],
    }


def test_busy_union_and_idle_share():
    rec = _toy_record()
    # union: 10-40 (30) + 60-70 (10) + 95-100 (5) = 45 ns
    assert trace.busy_s(rec) == pytest.approx(45e-9)
    assert trace.window_s(rec) == pytest.approx(100e-9)
    assert trace.idle_share(rec) == pytest.approx(0.55)


def test_kernel_time_by_name_and_detail():
    rec = _toy_record()
    assert trace.kernel_s(rec, "adagrad_pallas") == (pytest.approx(20e-9), 1)
    assert trace.kernel_s(rec, "fusion") == (pytest.approx(30e-9), 2)
    assert trace.kernel_s(rec, "copy") == (pytest.approx(5e-9), 1)
    assert trace.kernel_s(rec, "adagrad") == (0.0, 0)  # whole names only


def test_top_ops_and_idle_gaps():
    rec = _toy_record()
    top = trace.top_ops(rec)
    assert top[0] == ["%fusion.1", pytest.approx(30e-9)]
    assert all(not name.startswith("%while") for name, _ in top)
    gaps = trace.idle_gaps(rec)
    # gaps 0-10 (pull_push), 40-60 (train covers the middle 50), 70-95
    assert gaps[0] == ["pull_push", pytest.approx(25e-9)]
    assert ["pull_push+train", pytest.approx(20e-9)] in gaps
    assert ["pull_push", pytest.approx(10e-9)] in gaps


def test_recorded_chip_trace():
    """A window recorded on the chip, cut down: the reduction gives numbers
    inside their bounds and finds the kernels by the names they carry."""
    path = DATA / "trace_ctr-C.train-ssd.json"
    rec = json.loads(path.read_text())
    busy, win = trace.busy_s(rec), trace.window_s(rec)
    assert 0 < busy <= win
    assert 0 <= trace.idle_share(rec) < 1
    assert trace.kernel_s(rec, "adagrad_pallas")[1] == 4  # one per mini-batch
    assert trace.kernel_s(rec, "feature_extract_pallas")[1] >= 1
    gaps = trace.idle_gaps(rec)
    assert all(g[1] > 0 for g in gaps) and sum(g[1] for g in gaps) <= win - busy + 1e-9


# ------------------------------------------------------------------ flops
def test_tower_flops_hand_count():
    cfg = {"n_slots": 2, "emb_dim": 3, "mlp_hidden": [4, 5]}
    # layers 6x4, 4x5, 5x1: 24 + 20 + 5 = 49 multiply-adds; x2 fwd, x3 fwd+2 bwd
    assert flops.tower_dims(cfg) == [6, 4, 5, 1]
    assert flops.tower_train_flops_per_example(cfg) == 6 * 49
    assert flops.pooling_flops(10, 3) == 60
    assert flops.train_flops(cfg, 2, 10) == 2 * 294 + 60


def test_roofline_share_bound_choice():
    pk = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.roofline_share(100.0, 5.0, 2.0, pk) == pytest.approx(50.0)  # compute bound
    assert flops.roofline_share(1.0, 40.0, 8.0, pk) == pytest.approx(50.0)  # memory bound
    assert flops.roofline_share(1.0, 1.0, 0.0, pk) is None


def test_peaks_table():
    assert peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks("TPU v99")


# ------------------------------------------------------------------ stats
def test_spread():
    xs = list(range(1, 101))
    q1, q2, q3 = statistics.quantiles(xs, n=4)
    assert stats.spread(xs) == pytest.approx((q3 - q1) / q2)


def test_union_and_gaps():
    iv = [(0, 2), (1, 3), (5, 6)]
    assert stats.union_seconds(iv, 0, 10) == 4
    assert stats.union_seconds(iv, 2.5, 5.5) == pytest.approx(1.0)
    assert stats.gaps(iv, 0, 10) == [(3, 5), (6, 10)]
    assert stats.gaps([], 0, 1) == [(0, 1)]


# -------------------------------------------------------------- discovery
def test_every_name_resolves():
    bm = spec.benchmark()
    for c in bm["configs"]:
        cfg = spec.config(c["name"])
        assert (ROOT / c["file"]).is_file() and cfg["name"] == c["name"]
        assert all(spec.reference(r) for r in cfg["reference"].values())
    for w in bm["workloads"]:
        tr = spec.traffic(w["traffic"])
        assert spec.runner(tr["runner"])
        lim = spec.limits(w["name"])["limits"]
        assert lim and all(v > 0 for v in lim.values())
    for m in bm["per_layer"]:
        reader = spec.metric_reader(m["name"])
        assert reader.read({}) is None  # nothing to read: no number, never 0


def test_cell_metrics_follow_workloads_keys():
    bm = spec.benchmark()
    for w in bm["workloads"]:
        e2e = {m["name"] for m in spec.cell_metrics(bm, w["name"], trace=False)}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert spec.cell_metrics(bm, w["name"], trace=True)


def test_names_are_refused_when_not_names():
    with pytest.raises(ValueError):
        spec.config("../x")


# -------------------------------------------------------------- generator
def test_generated_keys_are_the_programs_and_shapes_do_not_move():
    from repro.data.synthetic_ctr import extract_host

    cfg = {"batch_size": 64, "nnz_per_example": 16, "zipf_a": 1.05, "n_sparse_keys": 6 * 10**10}
    tr = {"structure_seed": 5, "min_nnz": 1}
    a = gen.make_batches(cfg, tr, 2**33 + 7, 3)
    b = gen.make_batches(cfg, tr, 11, 3)
    for bt in a:
        keys, _, valid = extract_host(bt.raw_ids, bt.lengths, cfg["n_sparse_keys"], 8, pack_width=16)
        assert np.array_equal(keys, bt.keys)
    assert gen.reuse_counts(a) == gen.reuse_counts(b)
    assert not np.array_equal(a[0].working_keys, b[0].working_keys)
    x = np.random.default_rng(0).integers(0, 2**63, 1000, dtype=np.uint64)
    assert np.array_equal(gen.splitmix64_inv(gen.splitmix64(x)), x)


# ----------------------------------------------------------------- refusal
def test_run_exits_nonzero_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    bm = spec.benchmark()
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", bm["workloads"][0]["name"],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300,
    )
    assert p.returncode != 0
    assert p.stdout.strip() == ""
