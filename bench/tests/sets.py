#!/usr/bin/env python3
"""Runs a cell the way a check does, one process per run, and keeps every
result line:

    python3 bench/tests/sets.py --workload W --seeds S1 ... S6 [--sets 2] \
        [--traced-seeds T1 T2 T3] [--seconds 51] --out RESULTS.jsonl

Traced runs come first (they also warm the compile cache), then each set of
untraced runs over the same seeds. Each output line is ``{"workload",
"set", "seed", "trace", "rc", "wall_s", "result"}``; ``bench/tests/spread.py``
reads them. Each run's standard error goes to ``<out>.<set>.<seed>.err``.
This process never imports JAX: each run holds the chip alone.
"""

from __future__ import annotations

import argparse
import json
import subprocess
import sys
import time
from pathlib import Path

RUN = Path(__file__).resolve().parents[1] / "run.py"


def one(workload: str, seed: int, seconds: float, trace: int, err_path: str, timeout: float):
    cmd = [sys.executable, str(RUN), "--workload", workload, "--seed", str(seed),
           "--seconds", str(seconds), "--trace", str(trace)]
    t0 = time.perf_counter()
    with open(err_path, "w") as err:
        try:
            p = subprocess.run(cmd, stdout=subprocess.PIPE, stderr=err, text=True, timeout=timeout)
            rc, out = p.returncode, p.stdout
        except subprocess.TimeoutExpired as e:
            rc, out = 124, (e.stdout or b"").decode() if isinstance(e.stdout, bytes) else (e.stdout or "")
    lines = [ln for ln in out.strip().splitlines() if ln.startswith("{")]
    result = json.loads(lines[-1]) if lines else None
    return rc, time.perf_counter() - t0, result


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--traced-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=float, default=51)
    ap.add_argument("--timeout", type=float, default=1300)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    plan = [("traced", s, 1) for s in args.traced_seeds]
    plan += [(str(k + 1), s, 0) for k in range(args.sets) for s in args.seeds]
    with open(args.out, "a") as f:
        for set_name, seed, trace in plan:
            rc, wall, res = one(args.workload, seed, args.seconds, trace,
                                f"{args.out}.{set_name}.{seed}.err", args.timeout)
            rec = {"workload": args.workload, "set": set_name, "seed": seed, "trace": trace,
                   "rc": rc, "wall_s": wall, "result": res}
            line = json.dumps(rec)
            f.write(line + "\n")
            f.flush()
            short = {k: v for k, v in (res or {}).items() if k != "breakdown"}
            print(json.dumps({"set": set_name, "seed": seed, "rc": rc, "wall_s": round(wall, 1), "result": short}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
