"""How many PS nodes pull at once while the cluster pulls: the summed time
of the nodes' pull segments (``hps:node.pull``), each clipped to the window,
over the union of the cluster's pulls (``hps:ps.pull``) clipped to the
window. 1.0 when the nodes pull one after another, the node count when all
of them pull the whole time. None for a program whose pulls keep no
per-node spans, or where no pull ran in the window."""

from benchlib import program, stats


def read(ctx):
    rec, t0, t1 = program.spans(ctx), ctx.get("t_open"), ctx.get("t_close")
    if not rec or t0 is None or t1 is None or t1 <= t0:
        return None
    lo, hi = t0 * 1e9, t1 * 1e9

    def intervals(name):
        return [(s[1], s[1] + s[2]) for s in rec if s[0] == program.PREFIX + name]

    nodes = intervals("node.pull")
    pulling = stats.union_seconds(intervals("ps.pull"), lo, hi)
    if not nodes or pulling <= 0:
        return None
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in nodes) / pulling
