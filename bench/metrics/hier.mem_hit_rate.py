"""MEM-PS hits over lookups in the window, summed over the nodes
(``MemStats``), in percent."""


def read(ctx):
    a, b = ctx.get("open"), ctx.get("close")
    if not a or not b:
        return None
    hits = b["mem_hits"] - a["mem_hits"]
    total = hits + b["mem_misses"] - a["mem_misses"]
    return 100.0 * hits / total if total > 0 else None
