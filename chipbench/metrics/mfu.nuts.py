"""Useful gradient FLOPs per second over the chips' peak: active leaf
lanes times ``grads_per_leaf`` times one chain's gradient FLOPs, over the
traced calls, divided by the traced window and by chips times the peak at
the configuration's matrix-product precision (``cell.peak_flops``)."""
LAYER = "whole step"
MOVES = "draws_per_s"


def read(ctx):
    t = ctx.trace
    active = sum(c["grad_active"] for c in ctx.traced)
    if t is None or ctx.peaks is None or not active or t.window_ps <= 0:
        return None
    flops = active * ctx.grads_per_leaf * ctx.work(1)["grad_flops"]
    return 100.0 * flops / (t.window_ps / 1e12) / (
        ctx.chips * ctx.peak_flops)
