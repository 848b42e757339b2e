"""Share of the window in which the ingest stage waited for a free staging-
ring slot (``hps:ingest.ring_wait``): the union of the program's spans,
clipped to the window, over the window, in percent."""

from benchlib import program


def read(ctx):
    return program.share(ctx, "ingest.ring_wait")
