"""Share of the window in which the pull/push stage applied a trained
batch's deferred push (``hps:ps.push``: MEM-PS push, eviction, SSD flush
and compaction): the union of the program's spans, clipped to the
window, over the window, in percent."""

from benchlib import program


def read(ctx):
    return program.share(ctx, "ps.push")
