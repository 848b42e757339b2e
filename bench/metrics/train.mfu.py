"""The train step's share of the chip's bf16 peak: tower forward and
backward matmul FLOPs plus pooling adds of the examples trained in the
window, over the window and the chips (``benchlib.flops``), in percent."""

from benchlib import flops


def read(ctx):
    w, ex = ctx.get("window_s"), ctx.get("examples")
    if not w or not ex or w <= 0:
        return None
    f = flops.train_flops(ctx["cfg"], ex, ctx["valid_ids"])
    return 100.0 * f / w / (ctx.get("chips", 1) * ctx["peaks"]["bf16_flops_per_s"])
