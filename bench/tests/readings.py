#!/usr/bin/env python3
"""Readings that limits are set from, on the chip, for one cell:

    python3 bench/tests/readings.py --workload <name> --seeds 11 12 13 ... \
        [--control] [--half-batch] [--seconds S] [--out FILE]

For each seed, one run of the cell as ``bench/run.py`` makes it (a short
window, since the check reads the first steps), then, with ``--control``,
the control: the cell's reference computed in the nearest precision below
the configuration's, put in the program's place and held to the same
numbers; with ``--half-batch``, the reference with half of each mini-batch
left out, in the program's place. Prints and writes one JSON line per
seed. The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import run as bench_run  # noqa: E402
from benchlib import compare, device, gen, spec  # noqa: E402
from benchlib.peaks import peaks  # noqa: E402


def halved(batches, k: int):
    """Each batch with the second half of each of its k mini-batches left
    out, so that every step takes its mean over the rest: the fault planted
    in the reference put in the program's place."""
    out = []
    for bt in batches:
        mb = bt.raw_ids.shape[0] // k
        keep = np.concatenate([np.arange(i * mb, i * mb + mb // 2) for i in range(k)])
        out.append(dataclasses.replace(
            bt, raw_ids=bt.raw_ids[keep], lengths=bt.lengths[keep], labels=bt.labels[keep], keys=bt.keys[keep]
        ))
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--half-batch", action="store_true")
    ap.add_argument("--max-window-batches", type=int, default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    bm = spec.benchmark()
    cell = spec.workload(bm, args.workload)
    cfg, traffic = spec.config(cell["config"]), spec.traffic(cell["traffic"])
    if args.max_window_batches is not None:
        traffic["max_window_batches"] = args.max_window_batches
    drv = spec.runner(traffic["runner"])
    ref = spec.reference(cfg["reference"][traffic["runner"]])
    dev = device.require(int(cell["chips"]))
    bench_run._enable_cache()
    out_f = open(args.out, "a") if args.out else None
    for seed in args.seeds:
        t0 = time.perf_counter()
        ctx = {
            "cfg": cfg, "traffic": traffic, "seed": seed, "seconds": args.seconds,
            "trace": False, "limits": spec.limits(cell["name"]), "reference": ref,
            "t_start": t0, "prepared": drv.prepare(cfg, traffic, seed),
            "peaks": peaks(dev["kind"]), "memory_peak": device.memory_peak_bytes,
            "workload": cell["name"],
        }
        out = drv.run(ctx)
        rec = {
            "seed": seed, "workload": cell["name"],
            "program": {k: c["value"] for k, c in out["checks"].items()},
            "failed": out["failed"], "error": repr(out.get("error")),
            "e2e": out["e2e"], "info": out.get("info"),
        }
        if args.control or args.half_batch:
            t1 = time.perf_counter()
            w = int(traffic["warmup_batches"])
            batches = gen.make_batches(cfg, traffic, seed, w + 1)
            tower0 = {n: np.asarray(x) for n, x in drv.make_tower(cfg, seed).items()}
            keys_next = batches[w].working_keys
            r32 = ref.readings(cfg, batches, tower0, w, keys_next)
            rec["reference_readings"] = {"losses": r32["losses"], "grad": r32["grad"], "change": r32["change"]}
            if args.control:
                r16 = ref.readings(cfg, batches, tower0, w, keys_next, dtype="bfloat16")
                rec["control"] = compare.train_numbers(r16, r32)
                rec["control_readings"] = {"losses": r16["losses"], "grad": r16["grad"], "change": r16["change"]}
            if args.half_batch:
                half = halved(batches[:w], int(cfg["minibatches_per_batch"]))
                rh = ref.readings(cfg, half, tower0, w, keys_next)
                rec["half_batch"] = compare.train_numbers(rh, r32)
            rec["control_s"] = time.perf_counter() - t1
        rec["seconds"] = time.perf_counter() - t0
        line = json.dumps(rec, default=float)
        print(line, flush=True)
        if out_f:
            out_f.write(line + "\n")
            out_f.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
