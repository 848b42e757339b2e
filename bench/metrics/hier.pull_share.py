"""Share of the window in which the pull/push stage pulled a batch's fresh
rows from the cluster (``hps:ps.pull``: MEM-PS lookup, eviction, SSD
reads and fresh init): the union of the program's spans, clipped to the
window, over the window, in percent."""

from benchlib import program


def read(ctx):
    return program.share(ctx, "ps.pull")
