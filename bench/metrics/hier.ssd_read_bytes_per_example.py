"""Bytes the SSD-PS read in the window (``SSDStats.bytes_read``, all
nodes) per example trained in it."""


def read(ctx):
    a, b = ctx.get("open"), ctx.get("close")
    if not a or not b or not ctx.get("examples"):
        return None
    return (b["ssd_bytes_read"] - a["ssd_bytes_read"]) / ctx["examples"]
