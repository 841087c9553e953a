"""Share of its roofline that the target's density reaches on the device.

The work is fixed by shapes: each leaf execution evaluates, for every
chain the device holds (active or not), ``grads_per_leaf`` gradients and
one value (the leaf's joint density).  The configuration's ``work``
function gives their FLOPs and bytes.  The least time is the larger of
FLOPs over the chip's peak at the configuration's matrix-product
precision (``cell.peak_flops``: at 'highest' a product takes six bfloat16
passes, so the peak is a sixth of the bfloat16 one) and bytes over its
bandwidth; the time taken is the device time under ``bench.logp`` on the
fullest device.  The value a trajectory starts from is also under that
scope and is not counted, so the share reads low by that much, never
high.
"""
LAYER = "kernel: the target's gradient"
MOVES = "draws_per_s"
SCOPE = "bench.logp"


def _bounds(ctx):
    execs = sum(c["grad_execs"] for c in ctx.traced)
    w = ctx.work(ctx.chains // ctx.chips)
    g = ctx.grads_per_leaf
    flops = execs * (g * w["grad_flops"] + w["value_flops"])
    nbytes = execs * (g * w["grad_bytes"] + w["value_bytes"])
    return (flops / ctx.peak_flops,
            nbytes / ctx.peaks["hbm_bytes_per_s"])


def read(ctx):
    t = ctx.trace
    if t is None or ctx.peaks is None:
        return None
    taken = t.fullest().scope_ps(SCOPE) / 1e12
    least = max(_bounds(ctx))
    if taken <= 0 or least <= 0:
        return None
    return 100.0 * least / taken


def note(ctx):
    flops_s, bytes_s = _bounds(ctx)
    return "bound by FLOPs" if flops_s >= bytes_s else "bound by bytes"
