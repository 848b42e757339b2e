"""Checks of ``hier.pull_node_parallelism``, the reader of the PS nodes'
pull segments (``hps:node.pull``) against the cluster's pulls
(``hps:ps.pull``), on the CPU.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_pull_node_parallelism.py
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

from benchlib import spec  # noqa: E402

NAME = "hier.pull_node_parallelism"


def _read(ctx):
    return spec.metric_reader(NAME).read(ctx)


def test_the_benchmark_lists_it_in_the_ssd_cell():
    m = {m["name"]: m for m in spec.benchmark()["per_layer"]}[NAME]
    assert m == {"name": NAME, "unit": "x", "better": "higher", "source": "program_span",
                 "layer": "host hierarchy", "moves": "train_examples_per_s",
                 "workloads": ["ctr-C.train-ssd"]}


def test_a_record_without_node_spans_gives_no_reading():
    """The chip excerpt recorded before the nodes pulled at once has
    ``ps.pull`` spans and no ``node.pull``: no reading, and no error."""
    ctx = json.loads((BENCH / "tests" / "data" / "program_spans_ctr-C.train-ssd.json").read_text())
    assert any(s[0] == "hps:ps.pull" for s in ctx["program_spans"])
    assert _read(ctx) is None
    assert _read({}) is None
    assert _read({"t_open": 1.0, "t_close": 2.0, "program_spans": []}) is None


def test_overlapping_node_spans_over_the_pulls_union():
    s = 1e9
    pull = lambda t0, t1: ["hps:ps.pull", t0 * s, (t1 - t0) * s, "stage.pull_push", {"batch": 0}]  # noqa: E731
    node = lambda n, t0, t1: ["hps:node.pull", t0 * s, (t1 - t0) * s, f"ps.node_{n}",  # noqa: E731
                              {"node": n, "rows": 10}]
    spans = [
        pull(1.0, 1.4), pull(1.6, 2.2),  # 0.4 + 0.4 s of pulling inside the 1..2 s window
        *[node(n, 1.0, 1.3) for n in range(4)],  # 1.2 node-seconds
        node(0, 1.6, 1.9), node(1, 1.6, 2.1), node(2, 1.7, 2.0),  # 0.3 + 0.4 (clipped) + 0.3
        node(3, 0.5, 0.6),  # before the window: nothing
    ]
    ctx = {"t_open": 1.0, "t_close": 2.0, "examples": 4, "program_spans": spans}
    assert _read(ctx) == pytest.approx(2.2 / 0.8)
    # node spans but no pull in the window: no reading, never a division by 0
    ctx["program_spans"] = [s for s in spans if s[0] == "hps:node.pull"]
    assert _read(ctx) is None


def test_reads_a_cluster_pull_from_the_programs_record(tmp_path):
    """A pull above the program's threshold, in a ``ps.pull`` span as the
    engine makes it: between one node at a time and all four at once."""
    from repro import tracing
    from repro.core.node import CONCURRENT_MIN_KEYS, Cluster

    cl = Cluster(4, str(tmp_path / "c"), dim=4, cache_capacity=20_000, file_capacity=256)
    keys = np.arange(1, CONCURRENT_MIN_KEYS + 4097, dtype=np.uint64)
    tracing.clear()
    t0 = time.perf_counter()
    with tracing.span("ps.pull", batch=0):
        cl.pull(keys, pin=False)
    v = _read({"t_open": t0, "t_close": time.perf_counter(), "examples": 1})
    tracing.clear()
    assert v is not None and 0.0 < v <= 4.0
