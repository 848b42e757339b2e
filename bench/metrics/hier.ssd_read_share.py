"""Share of the window in which an SSD-PS gather read whole parameter files
(``hps:ssd.read``, any node): the union of the program's spans, clipped
to the window, over the window, in percent."""

from benchlib import program


def read(ctx):
    return program.share(ctx, "ssd.read")
