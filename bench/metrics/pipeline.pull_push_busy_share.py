"""Share of the window in which the trainer's pull/push stage was working,
from the harness's spans around the stage, in percent."""

from benchlib import stats


def read(ctx):
    spans = ctx.get("spans", {}).get("pull_push")
    t0, t1 = ctx.get("t_open"), ctx.get("t_close")
    if not spans or t0 is None or t1 is None or t1 <= t0:
        return None
    return 100.0 * stats.union_seconds(spans, t0, t1) / (t1 - t0)
