"""The program's own spans (``repro.tracing``), for the per-layer metrics
that read them.

The program keeps each span it ends in an in-process record of ``[name,
start_ns, dur_ns, thread, attrs]`` on the ``time.perf_counter`` clock, the
clock of the runner's ``t_open`` and ``t_close``; a span that began before
the window opened is there whole. ``spans`` takes them from
``ctx["program_spans"]`` where the context carries them (a recorded excerpt,
``bench/tests/data``), else from the program's record, and gives None where
the program keeps none (a program older than its spans): the readers then
give None too.
"""

from __future__ import annotations

from benchlib import stats

PREFIX = "hps:"  # repro.tracing.PREFIX


def spans(ctx) -> list | None:
    if "program_spans" in ctx:
        return ctx["program_spans"]
    try:
        from repro import tracing
    except ImportError:
        return None
    return [list(s) for s in tracing.recorded()]


def _window_ns(ctx):
    t0, t1 = ctx.get("t_open"), ctx.get("t_close")
    if t0 is None or t1 is None or t1 <= t0:
        return None
    return t0 * 1e9, t1 * 1e9


def share(ctx, name: str) -> float | None:
    """Union of the spans named ``hps:<name>``, clipped to the window, over
    the window, in percent (0 where none ran in it)."""
    rec, win = spans(ctx), _window_ns(ctx)
    if not rec or win is None:
        return None
    iv = [(s[1], s[1] + s[2]) for s in rec if s[0] == PREFIX + name]
    return 100.0 * stats.union_seconds(iv, *win) / (win[1] - win[0])


def total(ctx, name: str, attr: str) -> float | None:
    """Sum of the count ``attr`` over the spans named ``hps:<name>`` that
    ended inside the window (0 where none did)."""
    rec, win = spans(ctx), _window_ns(ctx)
    if not rec or win is None:
        return None
    return float(sum(s[4].get(attr, 0) for s in rec
                     if s[0] == PREFIX + name and win[0] < s[1] + s[2] <= win[1]))


def excerpt(ctx) -> dict:
    """What the span readers read of one run, for keeping: the window, the
    examples trained in it, and every program span that overlaps it."""
    win = _window_ns(ctx)
    rec = spans(ctx) or []
    keep = [s for s in rec if win is not None and s[1] < win[1] and s[1] + s[2] > win[0]]
    return {"t_open": ctx.get("t_open"), "t_close": ctx.get("t_close"),
            "examples": ctx.get("examples"), "program_spans": keep}
