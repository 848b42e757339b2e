"""Bytes that SSD-PS compactions read in the window (``hps:ssd.compact``'s
``bytes_read``, the count ``SSDStats.compaction_bytes_read`` keeps; part of
``hier.ssd_read_bytes_per_example``), per example trained in it."""

from benchlib import program


def read(ctx):
    n = program.total(ctx, "ssd.compact", "bytes_read")
    if n is None or not ctx.get("examples"):
        return None
    return n / ctx["examples"]
