"""Profiler names of a VM run: the host phases (``autobatch.*`` /
``pcvm.*`` spans, with their seconds and blocking reads on
``SchedulerStats``) and the device scopes inside the loop body.

The scopes are metadata: a program compiled with them is the program
compiled without them, instruction for instruction.
"""
import contextlib
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ast_frontend
from repro.core.batching import Batched, autobatch
from repro.core.frontend import I32
from repro.mcmc import nuts, targets

#: Device scopes the loop body adds beside ``pcvm.block<i>``.
NEW_SCOPES = ("pcvm.pick", "pcvm.stats", "pcvm.cond", "pcvm.compact",
              "pcvm.switch", "pcvm.write", "pcvm.push", "pcvm.pop",
              "pcvm.prim.grad")
#: Host phases of one pc-backend call.
PHASES = ("autobatch.call", "autobatch.bind", "pcvm.run", "pcvm.start",
          "pcvm.launch", "pcvm.wait", "pcvm.result", "autobatch.check")


def _nuts_kernel(**kw):
    target = targets.correlated_gaussian(dim=4)
    settings = nuts.NutsSettings(max_tree_depth=3, num_steps=1,
                                 steps_per_leaf=2)
    return nuts.make_nuts_kernel(target, settings, **kw)


def _nuts_args(chains=8):
    return (jnp.zeros((chains, 4), jnp.float32), jnp.float32(0.1),
            jnp.arange(2 * chains, dtype=jnp.uint32).reshape(chains, 2))


def _fib(**kw):
    @autobatch(in_specs=(Batched(I32),), out_spec=I32, max_depth=24,
               registry=ast_frontend.Namespace(), **kw)
    def fib(n):
        if n < 2:
            return n
        return fib(n - 1) + fib(n - 2)

    return fib


@contextlib.contextmanager
def _without_new_scopes(monkeypatch):
    """``jax.named_scope`` with the new scope names made no-ops."""
    real = jax.named_scope
    new = re.compile(r"pcvm\.(pick|stats|cond|compact|switch|write|push|pop"
                     r"|prim\.)")

    def scope(name):
        return contextlib.nullcontext() if new.match(name) else real(name)

    with monkeypatch.context() as m:
        m.setattr(jax, "named_scope", scope)
        yield


DEBUG_TABLES = ("FileNames", "FunctionNames", "FileLocations",
                "StackFrames")


def _instructions(hlo: str):
    """Optimized HLO text without metadata (each instruction's and the
    module's tables of source locations), and for each instruction
    whether its op name lies under a ``pcvm.block`` scope."""
    lines, blocks = [], []
    table = False
    for ln in hlo.splitlines():
        table = ln in DEBUG_TABLES or (table and ln != "")
        if table:
            continue
        meta = re.search(r", metadata=\{[^}]*\}", ln)
        lines.append(re.sub(r", metadata=\{[^}]*\}", "", ln))
        blocks.append(bool(meta and "pcvm.block" in meta.group(0)))
    return lines, blocks


@pytest.mark.parametrize("kw", [{}, {"compact_every": 2},
                                {"use_kernel": True, "compact_every": 2},
                                {"schedule": "sweep"}],
                         ids=["earliest", "compact", "kernel", "sweep"])
def test_scopes_are_metadata_only(monkeypatch, kw):
    """The lowered program's HLO holds every new scope the configuration
    runs, no new scope holds ``pcvm.block``, and the compiled program is
    the one compiled without the new scopes, instruction for
    instruction."""
    args = _nuts_args()
    scoped = _nuts_kernel(**kw).lower(*args).compile().as_text()
    with _without_new_scopes(monkeypatch):
        plain = _nuts_kernel(**kw).lower(*args).compile().as_text()
    runs = set(NEW_SCOPES)
    if "compact_every" not in kw:
        runs.discard("pcvm.compact")
    if kw.get("schedule") == "sweep":
        runs -= {"pcvm.pick", "pcvm.switch"}
    else:
        # The block bodies keep their own scope, inside pcvm.switch.
        assert re.search(r"pcvm\.switch/\S*pcvm\.block\d+/pcvm\.write",
                         scoped)
    for scope in NEW_SCOPES:
        assert (scope in scoped) == (scope in runs), scope
        assert "pcvm.block" not in scope
        assert scope not in plain
    got, got_blocks = _instructions(scoped)
    want, want_blocks = _instructions(plain)
    assert got == want
    # What lies under pcvm.block is what lay there before.
    assert got_blocks == want_blocks


@pytest.mark.parametrize("kw, check", [
    ({}, True),
    ({"collect_stats": False}, True),
    ({"trace": True}, True),
    ({"on_fault": "quarantine"}, False),
    ({"detect_nonfinite": True}, True),
], ids=["stats", "no-stats", "trace", "quarantine", "nonfinite"])
def test_host_phases_and_syncs(monkeypatch, kw, check):
    fib = _fib(**kw)
    n = np.array([0, 1, 5, 9, 12, 3], np.int32)
    jax.block_until_ready(fib(n))  # compile outside the counted call
    reads = []
    real = jax.device_get

    def counted(x):
        reads.append(x)
        return real(x)

    monkeypatch.setattr(jax, "device_get", counted)
    t0 = time.perf_counter()
    out = fib(n)
    wall = time.perf_counter() - t0
    monkeypatch.setattr(jax, "device_get", real)
    np.testing.assert_array_equal(np.asarray(out), [0, 1, 5, 34, 144, 2])
    sched = fib.scheduler_stats
    assert sched.host_syncs == len(reads) > 0
    phases = sched.host_phases
    want = set(PHASES) if check else set(PHASES) - {"autobatch.check"}
    assert set(phases) == want
    assert all(v >= 0 for v in phases.values())
    assert sum(phases.values()) <= wall


def test_vm_run_records_its_own_phases():
    fib = _fib()
    n = np.array([3, 7], np.int32)
    fib(n)
    vm = fib._last_executor.vm
    res = vm.run({"fib/n": jnp.asarray(n)})
    assert set(res.sched.host_phases) == {
        "pcvm.run", "pcvm.start", "pcvm.launch", "pcvm.wait",
        "pcvm.result"}
    assert res.sched.host_syncs == 4  # block_exec, block_active, tile, steps
