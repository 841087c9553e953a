"""VM dispatches (loop iterations, ``last_result.steps``) per trajectory,
over every call of the window."""
LAYER = "VM dispatch loop: core/pc_vm.py"
MOVES = "draws_per_s"


def read(ctx):
    if not ctx.calls:
        return None
    return sum(c["steps"] for c in ctx.calls) / (len(ctx.calls) * ctx.traj)
