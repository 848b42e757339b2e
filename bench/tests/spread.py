#!/usr/bin/env python3
"""Spreads of a cell's end-to-end metrics over two sets of runs, and the
bounds they call for:

    python3 bench/tests/spread.py RESULTS.jsonl [RESULTS.jsonl ...]

Each input line is ``{"workload", "set", "seed", "result": <the run's last
line>}``. For each cell and metric it prints, per set, the median and the
spread (first to third quartile over the median,
``statistics.quantiles(values, n=4)``), the mean of the two sets' spreads
with each set's run farthest from its median left out, and five times the
wider of the two spreads, floored at 1%.
"""

from __future__ import annotations

import json
import statistics
import sys
from collections import defaultdict
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchlib.stats import spread  # noqa: E402


def trimmed(xs: list[float]) -> list[float]:
    med = statistics.median(xs)
    far = max(range(len(xs)), key=lambda i: abs(xs[i] - med))
    return [x for i, x in enumerate(xs) if i != far]


def main(paths: list[str]) -> int:
    runs = defaultdict(lambda: defaultdict(lambda: defaultdict(list)))
    for p in paths:
        for line in open(p):
            rec = json.loads(line)
            res = rec["result"]
            if res is None or rec["set"] == "traced":
                continue
            if not res.get("correct"):
                print(f"not correct: {rec['workload']} set {rec['set']} seed {rec['seed']}")
            for name, m in res["metrics"].items():
                runs[rec["workload"]][name][rec["set"]].append(m["value"])
    for cell, metrics in sorted(runs.items()):
        for name, sets in sorted(metrics.items()):
            row = {}
            for s, xs in sorted(sets.items()):
                row[s] = {"n": len(xs), "median": statistics.median(xs), "spread": spread(xs) if len(xs) >= 2 else None}
            sp = [r["spread"] for r in row.values() if r["spread"] is not None]
            tight = [spread(trimmed(xs)) for xs in sets.values() if len(xs) >= 3]
            out = {
                "cell": cell, "metric": name, "sets": row,
                "widest_spread": max(sp) if sp else None,
                "mean_trimmed_spread": sum(tight) / len(tight) if tight else None,
                "bound_5x": max(0.01, 5 * max(sp)) if sp else None,
            }
            print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
