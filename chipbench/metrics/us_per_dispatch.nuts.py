"""Device busy time inside the VM loop's program per VM dispatch, on the
fullest device, over the traced calls.  The loop's program is the one
that holds the VM's ``pcvm.block`` operations, whatever it is named."""
LAYER = "VM dispatch loop: core/pc_vm.py"
MOVES = "draws_per_s"
VM_SCOPE = "pcvm.block"


def read(ctx):
    t = ctx.trace
    steps = sum(c["steps"] for c in ctx.traced)
    if t is None or not steps:
        return None
    d = t.fullest()
    return d.busy_in(d.module_of(VM_SCOPE)) / 1e6 / steps
