#!/usr/bin/env python3
"""Runs one cell traced, as ``bench/run.py --trace 1`` does, and keeps what
the program-span readers read of it:

    python3 bench/tests/record_spans.py --workload W --seed N --seconds 51 --out FILE.json

The result line is printed as ``bench/run.py`` prints it. ``FILE.json`` holds
the window (``t_open``, ``t_close``, on the program's clock), the examples
trained in it, every program span that overlaps it (``benchlib.program``),
and the run's end-to-end numbers (``e2e``), which a traced result line
leaves out.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import run as bench_run  # noqa: E402
from benchlib import program  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    args, rest = ap.parse_known_args(argv)
    kept = {}
    result_line = bench_run.result_line

    def keep(bm, cell, out, dev, traced):
        kept.update(program.excerpt(out["layer"]), e2e=out["e2e"])
        return result_line(bm, cell, out, dev, traced)

    bench_run.result_line = keep
    rc = bench_run.main(rest + ["--trace", "1"])
    if kept:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(kept, f)
    return rc


if __name__ == "__main__":
    sys.exit(main())
