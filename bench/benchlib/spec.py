"""Finds everything by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix; each is a file of its own:

* ``bench/configs/<config>.json``   sizes, source, and ``reference``: for
  each kind of traffic run on it, the module under ``bench/reference/``
  that computes the same thing plainly;
* ``bench/traffic/<traffic>.json``  parameters of the mix, and ``runner``:
  the module under ``bench/runners/`` that runs that kind of traffic;
* ``bench/limits/<workload>.json``  the limits of the numbers that decide
  ``correct`` in that cell, with the readings they were set from;
* ``bench/metrics/<metric>.py``     one reader per per-layer metric.

A new cell, mix, configuration or metric is a new file and a new entry in
``BENCHMARK.json``; nothing here changes.
"""

from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def _name(name: str) -> str:
    if not _NAME.match(name or ""):
        raise ValueError(f"not a benchmark name: {name!r}")
    return name


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return _json(ROOT / "BENCHMARK.json")


def workload(bm: dict, name: str) -> dict:
    for w in bm["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(name: str) -> dict:
    return _json(BENCH / "configs" / f"{_name(name)}.json")


def traffic(name: str) -> dict:
    return _json(BENCH / "traffic" / f"{_name(name)}.json")


def limits(workload_name: str) -> dict:
    return _json(BENCH / "limits" / f"{_name(workload_name)}.json")


def _module(kind: str, name: str):
    path = BENCH / kind / f"{_name(name)}.py"
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def runner(name: str):
    return _module("runners", name)


def reference(name: str):
    return _module("reference", name)


def metric_reader(name: str):
    return _module("metrics", name)


def applies(metric: dict, workload_name: str) -> bool:
    """Whether a metric entry is reported in a cell (no ``workloads`` key:
    in every cell)."""
    return workload_name in metric.get("workloads", [workload_name])


def cell_metrics(bm: dict, workload_name: str, trace: bool) -> list[dict]:
    return [m for m in bm["per_layer" if trace else "end_to_end"] if applies(m, workload_name)]
