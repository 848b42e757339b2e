"""Share of the window in which a pull waited for an older in-flight batch
to train before forwarding its rows (``hps:ps.conflict_wait``): the
union of the program's spans, clipped to the window, over the window, in
percent."""

from benchlib import program


def read(ctx):
    return program.share(ctx, "ps.conflict_wait")
