"""Share of the lanes of the leapfrog leaf (tag ``grad``) that were active
when it ran: ``tag_stats`` active over executions times chains, over
every call of the window."""
LAYER = "batched primitive: the leapfrog leaf"
MOVES = "draws_per_s"


def read(ctx):
    execs = sum(c["grad_execs"] for c in ctx.calls)
    if not execs:
        return None
    active = sum(c["grad_active"] for c in ctx.calls)
    return 100.0 * active / (execs * ctx.chains)
