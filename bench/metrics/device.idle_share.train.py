"""1 - (union of device operations) / (traced window), in a training cell,
in percent."""

from benchlib import trace


def read(ctx):
    rec = ctx.get("trace")
    if rec is None or not rec["device_ops"]:
        return None
    s = trace.idle_share(rec)
    return None if s is None else 100.0 * s
