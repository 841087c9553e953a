"""Device-idle time per call outside the program's VM loop, on the fullest
device: the gap between calls that the harness's ``bench.call`` and the
program's ``pcvm.run`` host code leave (set-up of a run, its result
syncs, the next call's keys).  The loop's program is the one that holds
the VM's ``pcvm.block`` operations, whatever it is named."""
import tracereduce

LAYER = "entry: core/batching.py executor call"
MOVES = "draws_per_s"
VM_SCOPE = "pcvm.block"


def read(ctx):
    t = ctx.trace
    if t is None or not ctx.traced:
        return None
    d = t.fullest()
    loop = d.module_of(VM_SCOPE)
    spans = tracereduce.clip(d.modules[loop], *t.window)
    idle_outside = (t.window_ps - tracereduce.length(spans)) - (
        d.busy_ps - d.busy_in(loop))
    return idle_outside / 1e9 / len(ctx.traced)
