"""Profiler trace of the measured window, and its reduction to numbers.

``Tracer`` records the window with JAX's profiler (Python tracer off, so the
trace holds the device's operations and the harness's own spans). ``load``
turns the ``.xplane.pb`` into a small, plain record::

    {"window": [t0_ns, t1_ns],
     "device_ops": [[name, start_ns, dur_ns], ...],
     "spans": [[name, start_ns, dur_ns], ...]}

A device operation's name is its HLO instruction as the trace gives it
(``%fusion.99 = f32[...] fusion(...)``); a Pallas kernel's instruction takes
the name of the function that calls ``pallas_call``. The reductions below
read only that record, so a recorded one (``bench/tests/data``) checks them
without a chip.
"""

from __future__ import annotations

import glob
import os
import shutil
import tempfile

from benchlib import stats

SPAN_PREFIX = "bench:"  # harness spans, written with jax.profiler.TraceAnnotation
WINDOW_SPAN = SPAN_PREFIX + "window"
OP_LINES = ("XLA Ops",)  # the device line that holds one event per operation


class Tracer:
    def __init__(self):
        self.dir = None

    def start(self) -> None:
        import jax

        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self) -> dict:
        import jax

        jax.profiler.stop_trace()
        try:
            paths = glob.glob(os.path.join(self.dir, "plugins", "profile", "*", "*.xplane.pb"))
            return load(paths[0])
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def load(path: str) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, spans, inventory = [], [], {}
    for plane in pd.planes:
        lines = list(plane.lines)
        inventory[plane.name] = [ln.name for ln in lines]
        if plane.name.startswith("/device:") and not plane.name.startswith("/device:CPU"):
            chosen = [ln for ln in lines if ln.name in OP_LINES] or lines
            for ln in chosen:
                for ev in ln.events:
                    ops.append([ev.name, float(ev.start_ns), float(ev.duration_ns)])
        elif plane.name.startswith("/host:"):
            for ln in lines:
                for ev in ln.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append([ev.name, float(ev.start_ns), float(ev.duration_ns)])
    win = [s for s in spans if s[0] == WINDOW_SPAN]
    if win:
        window = [win[0][1], win[0][1] + win[0][2]]
    else:  # no window span: the extent of everything recorded
        ends = [o[1] + o[2] for o in ops] + [s[1] + s[2] for s in spans]
        starts = [o[1] for o in ops] + [s[1] for s in spans]
        window = [min(starts, default=0.0), max(ends, default=0.0)]
    return {"window": window, "device_ops": ops, "spans": spans, "planes": inventory}


def window_s(rec: dict) -> float:
    t0, t1 = rec["window"]
    return (t1 - t0) / 1e9


def busy_s(rec: dict) -> float:
    """Seconds of the window in which some operation ran on the device."""
    t0, t1 = rec["window"]
    return stats.union_seconds(((o[1], o[1] + o[2]) for o in rec["device_ops"]), t0, t1) / 1e9


def idle_share(rec: dict) -> float | None:
    w = window_s(rec)
    return None if w <= 0 else 1.0 - busy_s(rec) / w


def kernel_s(rec: dict, name: str) -> tuple[float, int]:
    """Device seconds and event count, inside the window, of one kernel's
    operations: those whose instruction is named ``%<name>`` or
    ``%<name>.<n>``."""
    t0, t1 = rec["window"]
    total, n = 0.0, 0
    for op in rec["device_ops"]:
        base = op_name(op[0]).lstrip("%")
        if base != name and base.rsplit(".", 1)[0] != name:
            continue
        s, e = max(op[1], t0), min(op[1] + op[2], t1)
        if e > s:
            total += e - s
            n += 1
    return total / 1e9, n


CONTAINERS = ("%while", "%conditional", "%call")  # ops that hold other ops' events


def op_name(event_name: str) -> str:
    """The HLO instruction's name, without the text the trace carries
    after it (``%fusion.99 = f32[...] fusion(...)`` -> ``%fusion.99``)."""
    return event_name.split(" = ", 1)[0]


def top_ops(rec: dict, n: int = 10) -> list[list]:
    """The device operations that took most time in the window, by
    instruction name, loops and calls left out (their bodies' operations
    are there on their own)."""
    t0, t1 = rec["window"]
    acc: dict[str, float] = {}
    for name, s, d in rec["device_ops"]:
        name = op_name(name)
        if name.startswith(CONTAINERS):
            continue
        x = min(s + d, t1) - max(s, t0)
        if x > 0:
            acc[name] = acc.get(name, 0.0) + x / 1e9
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(rec: dict, n: int = 10) -> list[list]:
    """The longest stretches of the window with nothing on the device, each
    named by the harness spans that covered its middle (what the host was
    doing: ``+`` joins stages that ran at once), or "none"."""
    t0, t1 = rec["window"]
    out = []
    for s, e in stats.gaps(((o[1], o[1] + o[2]) for o in rec["device_ops"]), t0, t1):
        mid = (s + e) / 2
        cover = {sp[0][len(SPAN_PREFIX):] for sp in rec["spans"]
                 if sp[0] != WINDOW_SPAN and sp[1] <= mid <= sp[1] + sp[2]}
        out.append(["+".join(sorted(cover)) or "none", (e - s) / 1e9])
    return sorted(out, key=lambda g: -g[1])[:n]


def excerpt(rec: dict, ops: int = 4000) -> dict:
    """A smaller record for keeping: the window, every span, and the device
    operations of the first ``ops`` events in it."""
    t0, t1 = rec["window"]
    inside = [o for o in rec["device_ops"] if o[1] + o[2] > t0 and o[1] < t1]
    return {"window": rec["window"], "device_ops": inside[:ops], "spans": rec["spans"],
            "planes": rec.get("planes", {})}
