"""Share of the traced window in which the fullest device ran no
operation (control-flow operations, which only hold others, left out)."""
LAYER = "device"
MOVES = "draws_per_s"


def read(ctx):
    t = ctx.trace
    if t is None or t.window_ps <= 0:
        return None
    return 100.0 * (1.0 - t.fullest().busy_ps / t.window_ps)
