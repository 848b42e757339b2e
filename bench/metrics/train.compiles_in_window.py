"""Compile requests inside the window, backend compiles and loads from the
persistent cache alike (``benchlib.compiles``)."""


def read(ctx):
    c = ctx.get("compiles")
    return None if not c else float(c["compiles"])
