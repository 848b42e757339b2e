"""Checks of the readers of the program's own spans (``benchlib.program``
and the metrics that use it), on the CPU.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

from benchlib import program, spec, stats, trace  # noqa: E402

DATA = BENCH / "tests" / "data"
SHARES = {
    "hier.push_share": "ps.push", "hier.pull_share": "ps.pull",
    "hier.ssd_read_share": "ssd.read", "hier.ssd_write_share": "ssd.write",
    "hier.conflict_wait_share": "ps.conflict_wait",
    "ingest.ring_wait_share": "ingest.ring_wait", "train.readback_share": "train.readback",
}
COUNTS = ("hier.ssd_compaction_read_bytes_per_example", "hier.ssd_rows_initialized_share")


def _read(name, ctx):
    return spec.metric_reader(name).read(ctx)


def _toy_ctx():
    # window 1..2 s on the program's clock (ns in the spans); 4 examples
    s = 1e9
    return {
        "t_open": 1.0, "t_close": 2.0, "examples": 4,
        "program_spans": [
            ["hps:ps.push", 0.5 * s, 0.7 * s, "stage.pull_push", {"batch": 1}],  # 1.0-1.2 inside
            ["hps:ps.push", 1.1 * s, 0.2 * s, "stage.pull_push", {"batch": 2}],  # overlaps: to 1.3
            ["hps:ps.pull", 1.9 * s, 0.5 * s, "stage.pull_push", {"batch": 2}],  # 1.9-2.0 inside
            ["hps:ssd.compact", 0.2 * s, 0.3 * s, "stage.pull_push", {"bytes_read": 1000}],  # ends before
            ["hps:ssd.compact", 1.2 * s, 0.1 * s, "stage.pull_push", {"bytes_read": 40}],
            ["hps:ssd.compact", 1.9 * s, 0.2 * s, "stage.pull_push", {"bytes_read": 7}],  # ends after
            ["hps:ssd.read", 1.4 * s, 0.1 * s, "stage.pull_push", {"rows": 30}],
            ["hps:ssd.init", 1.5 * s, 0.1 * s, "stage.pull_push", {"rows": 10}],
        ],
    }


def test_shares_are_unions_clipped_to_the_window():
    ctx = _toy_ctx()
    assert _read("hier.push_share", ctx) == pytest.approx(30.0)
    assert _read("hier.pull_share", ctx) == pytest.approx(10.0)
    assert _read("hier.ssd_read_share", ctx) == pytest.approx(10.0)
    assert _read("hier.conflict_wait_share", ctx) == 0.0  # none ran: a reading, not a gap


def test_counts_take_spans_that_end_in_the_window():
    ctx = _toy_ctx()
    assert _read("hier.ssd_compaction_read_bytes_per_example", ctx) == pytest.approx(10.0)
    assert _read("hier.ssd_rows_initialized_share", ctx) == pytest.approx(25.0)


def test_a_program_without_spans_gives_no_reading(monkeypatch):
    import repro

    monkeypatch.setitem(sys.modules, "repro.tracing", None)
    monkeypatch.delattr(repro, "tracing", raising=False)
    ctx = {"t_open": 1.0, "t_close": 2.0, "examples": 4}
    assert program.spans(ctx) is None
    for name in [*SHARES, *COUNTS]:
        assert _read(name, ctx) is None


def test_readers_take_the_programs_record_when_the_context_has_none():
    from repro import tracing

    tracing.clear()
    with tracing.span("ps.push", batch=0):
        pass
    with tracing.span("ssd.init", rows=3):
        pass
    rec = tracing.recorded()
    lo, hi = rec[0][1] / 1e9 - 1.0, rec[-1][1] / 1e9 + 1.0
    ctx = {"t_open": lo, "t_close": hi, "examples": 1}
    assert 0.0 < _read("hier.push_share", ctx) < 100.0
    assert _read("hier.ssd_rows_initialized_share", ctx) == 100.0
    tracing.clear()


def test_every_new_metric_is_in_the_benchmark_with_its_span():
    bm = spec.benchmark()
    entries = {m["name"]: m for m in bm["per_layer"]}
    for name in [*SHARES, *COUNTS]:
        m = entries[name]
        assert m["moves"] == "train_examples_per_s" and m["workloads"] == ["ctr-C.train-ssd"]
        assert m["source"] == ("program_span" if name in SHARES else "program_counter")
    for name, span in SHARES.items():
        src = (BENCH / "metrics" / f"{name}.py").read_text()
        assert f'"{span}"' in src and f"hps:{span}" in src


def test_old_recorded_trace_reads_as_before():
    """The existing trace readers are untouched: the recorded chip trace
    gives the numbers it gave when it was recorded."""
    rec = json.loads((DATA / "trace_ctr-C.train-ssd.json").read_text())
    ctx = {"trace": rec}
    assert _read("device.idle_share.train", ctx) == pytest.approx(83.38876185, rel=1e-9)
    assert trace.top_ops(rec)[0] == ["%fusion.109", pytest.approx(0.492723093)]
    assert trace.idle_gaps(rec)[0] == ["ingest", pytest.approx(5.191947795)]


def test_traced_run_reports_every_span_metric():
    """A traced cell at a small size on the CPU (as ``test_faults`` runs
    one): each span metric reads a number from the runner's context."""
    import time

    import run as bench_run
    from test_faults import CPU, PEAKS, small

    bm, cell, cfg, tr = small("ctr-C.train-ssd")
    drv = spec.runner(tr["runner"])
    seed = 2**33 + 5
    ctx = {
        "cfg": cfg, "traffic": tr, "seed": seed, "seconds": 0.5, "trace": True,
        "limits": spec.limits(cell["name"]), "reference": spec.reference(cfg["reference"][tr["runner"]]),
        "t_start": time.perf_counter(), "prepared": drv.prepare(cfg, tr, seed),
        "peaks": PEAKS, "memory_peak": lambda: None, "workload": cell["name"],
    }
    line = bench_run.result_line(bm, cell, drv.run(ctx), CPU, True)
    for name in [*SHARES, *COUNTS]:
        v = line["metrics"][name]["value"]
        assert v is not None and 0.0 <= v and (name in COUNTS or v <= 100.0), (name, v)
    assert line["metrics"]["hier.push_share"]["value"] > 0.0


# what the traced chip run that recorded the excerpt reported (TPU v5 lite,
# seed 2200000017, 51-s window)
CHIP = {
    "hier.push_share": 10.67553750732818, "hier.pull_share": 65.00039875718637,
    "hier.ssd_read_share": 4.288433953731318, "hier.ssd_write_share": 14.360120912343726,
    "hier.conflict_wait_share": 0.0, "hier.ssd_compaction_read_bytes_per_example": 0.0,
    "hier.ssd_rows_initialized_share": 96.59663331010394,
    "ingest.ring_wait_share": 94.66908883013467, "train.readback_share": 18.021175596080383,
}
CHIP_PULL_PUSH_BUSY = 99.99831286254339  # pipeline.pull_push_busy_share, same run


def _chip():
    return json.loads((DATA / "program_spans_ctr-C.train-ssd.json").read_text())


@pytest.mark.parametrize("name", sorted(CHIP))
def test_recorded_chip_spans_read_as_on_the_chip(name):
    assert _read(name, _chip()) == pytest.approx(CHIP[name], rel=1e-9, abs=1e-12)


def test_recorded_chip_spans_split_the_pull_push_stage():
    """In the recorded window the program's spans account for the pull/push
    stage: its children cover nearly all of it, the four PS spans add up to
    the harness's busy share, and a batch makes a few dozen spans."""
    ctx = _chip()
    rec, lo, hi = ctx["program_spans"], ctx["t_open"] * 1e9, ctx["t_close"] * 1e9
    stage_ns = covered_ns = 0.0
    for st in (s for s in rec if s[0] == "hps:stage.pull_push"):
        s0, e0 = max(st[1], lo), min(st[1] + st[2], hi)
        kids = [(s[1], s[1] + s[2]) for s in rec if s is not st and s[3] == st[3]
                and st[1] <= s[1] and s[1] + s[2] <= st[1] + st[2]]
        stage_ns += e0 - s0
        covered_ns += stats.union_seconds(kids, s0, e0)
    assert covered_ns >= 0.9 * stage_ns
    top = [(s[1], s[1] + s[2]) for s in rec
           if s[0] in ("hps:ps.push", "hps:ps.pull", "hps:ps.keys", "hps:ps.conflict_wait")]
    assert abs(100 * stats.union_seconds(top, lo, hi) / (hi - lo) - CHIP_PULL_PUSH_BUSY) <= 10
    assert len(rec) <= 100 * ctx["examples"] / 16_384
