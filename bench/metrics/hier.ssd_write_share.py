"""Share of the window in which the SSD-PS wrote flushed rows as new files,
compaction included (``hps:ssd.write``, any node): the union of the
program's spans, clipped to the window, over the window, in percent."""

from benchlib import program


def read(ctx):
    return program.share(ctx, "ssd.write")
