"""Rows the SSD-PS was asked for in the window that no file held, so that
they took fresh init (``hps:ssd.init``'s ``rows``, the count
``SSDStats.rows_initialized`` keeps), over all rows asked for (those rows
plus the rows read from files, ``hps:ssd.read``'s ``rows``), in percent."""

from benchlib import program


def read(ctx):
    init = program.total(ctx, "ssd.init", "rows")
    found = program.total(ctx, "ssd.read", "rows")
    if init is None or found is None or init + found <= 0:
        return None
    return 100.0 * init / (init + found)
