#!/usr/bin/env python3
"""Runs one benchmark cell once and prints its result as the last line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout. The cell, its configuration, its traffic mix,
its limits and its per-layer metrics are found by name (``benchlib.spec``).
With ``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` the window is traced and the result carries its per-layer
metrics, the device's busy time and a breakdown. Exits 3, printing no
result, unless JAX sees the TPU chips the cell asks for.

The last lines of standard error, and the ``checks`` key that comes last in
the result, give each number that decides ``correct`` beside its limit.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

from benchlib import device, spec, trace  # noqa: E402
from benchlib.peaks import peaks  # noqa: E402

# JAX's persistent compilation cache, at one fixed place inside the checkout
CACHE_DIR = BENCH.parent / ".jax_cache"


def _enable_cache() -> None:
    import jax

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    # every program, however small, so that a second run compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()


def _finite(x):
    return x if isinstance(x, (int, float)) and math.isfinite(x) else None


def result_line(bm: dict, cell: dict, out: dict, dev: dict, traced: bool) -> dict:
    checks = out["checks"]
    ok = (
        out["failed"] == 0
        and out["attempted"] > 0
        and all(_finite(c["value"]) is not None and c["value"] <= c["limit"] for c in checks.values())
    )
    metrics = {}
    if traced:
        ctx = dict(out["layer"], workload=cell["name"])
        for m in spec.cell_metrics(bm, cell["name"], trace=True):
            v = spec.metric_reader(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in spec.cell_metrics(bm, cell["name"], trace=False):
            metrics[m["name"]] = {"value": out["e2e"][m["name"]], "unit": m["unit"]}
    device_out = dict(dev, memory_peak_bytes=out["memory_peak_bytes"])
    line = {
        "correct": bool(ok),
        "attempted": int(out["attempted"]),
        "failed": int(out["failed"]),
        "metrics": metrics,
        "device": device_out,
    }
    rec = out["layer"].get("trace")
    if traced and rec is not None:
        device_out["busy_s"] = trace.busy_s(rec)
        device_out["window_s"] = trace.window_s(rec)
        line["breakdown"] = {"device_ops": trace.top_ops(rec), "idle_gaps": trace.idle_gaps(rec)}
    line["checks"] = {k: {"value": c["value"], "limit": c["limit"]} for k, c in checks.items()}
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump", default=None, help="also write the traced window's record here (JSON)")
    args = ap.parse_args(argv)

    bm = spec.benchmark()
    cell = spec.workload(bm, args.workload)
    cfg, traffic = spec.config(cell["config"]), spec.traffic(cell["traffic"])
    drv = spec.runner(traffic["runner"])
    prepared = drv.prepare(cfg, traffic, args.seed)  # host data, before JAX
    try:
        dev = device.require(int(cell["chips"]))
    except device.NoChip as e:
        return int(e.code)
    _enable_cache()
    ctx = {
        "cfg": cfg, "traffic": traffic, "seed": args.seed, "seconds": args.seconds,
        "trace": bool(args.trace), "limits": spec.limits(cell["name"]),
        "reference": spec.reference(cfg["reference"][traffic["runner"]]), "t_start": T_START,
        "prepared": prepared, "peaks": peaks(dev["kind"]),
        "memory_peak": device.memory_peak_bytes, "workload": cell["name"],
    }
    out = drv.run(ctx)
    line = result_line(bm, cell, out, dev, bool(args.trace))
    if args.dump and out["layer"].get("trace") is not None:
        Path(args.dump).parent.mkdir(parents=True, exist_ok=True)
        with open(args.dump, "w") as f:
            json.dump(trace.excerpt(out["layer"]["trace"]), f)
    if out.get("error") is not None:
        print(f"bench: run failed: {out['error']!r}", file=sys.stderr)
    print(f"bench: {json.dumps(out.get('info', {}))}", file=sys.stderr)
    for k, c in line["checks"].items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
