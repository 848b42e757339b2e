"""Working rows the device kept from the previous batch over all working
rows, in the window (``hbm_ps.ReuseStats``), in percent."""


def read(ctx):
    a, b = ctx.get("open"), ctx.get("close")
    if not a or not b:
        return None
    reused = b["rows_reused"] - a["rows_reused"]
    total = reused + b["rows_transferred"] - a["rows_transferred"]
    return 100.0 * reused / total if total > 0 else None
