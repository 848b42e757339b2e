"""Share of the window in which the train stage waited for a step's rows
and loss to reach the host (``hps:train.readback``): the union of the
program's spans, clipped to the window, over the window, in percent."""

from benchlib import program


def read(ctx):
    return program.share(ctx, "train.readback")
