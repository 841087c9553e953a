"""Device-idle time inside the VM loop's program per VM dispatch, on the
fullest device, over the traced calls: the gaps between the loop's own
operations, while the host waits for the loop in ``pcvm.wait``.  With
``host_gap_ms.nuts`` it makes up the window's idle time.  The loop's
program is the one that holds the VM's ``pcvm.block`` operations,
whatever it is named."""
import tracereduce

LAYER = "VM dispatch loop: core/pc_vm.py"
MOVES = "draws_per_s"
VM_SCOPE = "pcvm.block"


def read(ctx):
    t = ctx.trace
    steps = sum(c["steps"] for c in ctx.traced)
    if t is None or not steps:
        return None
    d = t.fullest()
    loop = d.module_of(VM_SCOPE)
    spans = tracereduce.clip(d.modules[loop], *t.window)
    return (tracereduce.length(spans) - d.busy_in(loop)) / 1e6 / steps
