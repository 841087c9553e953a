"""The call lowering's save rule for a recursive callee's parameters.

An argument goes into a recursive callee by a push (burying the value an
outer frame still reads) only where some frame can read the buried value:
at a self-call, the parameters the caller reads after the call; across
mutual recursion, every parameter; from a function the callee cannot
re-enter, none.  Every other argument overwrites the parameter's top
(``argset``).  Each case pins the exact pushes of every call site and the
pops of its return site, then runs on the ``pc`` backend bit for bit
against ``local``.
"""
import jax
import numpy as np
import pytest

from repro.core import batching, frontend, ir, lowering, passes
from repro.core.frontend import BOOL, I32
from repro.mcmc import nuts, targets

from tests.test_core import build_mutual


def _count_down(fb, out_value):
    """``if n <= 0: out = <out_value>; return``."""
    c = fb.prim(lambda n: n <= 0, ["n"])
    with fb.if_(c):
        fb.copy(out_value, out="out")
        fb.return_()
    return fb.prim(lambda n: n - 1, ["n"])


def build_live_param():
    """``f(n) = f(n - 1) + n``: ``n`` is read after the self-call."""
    pb = frontend.ProgramBuilder()
    fb = pb.function("f", ["n"], ["out"], {"n": I32}, {"out": I32})
    t = _count_down(fb, "n")
    fb.call("f", [t], out="a")
    fb.assign("out", lambda a, n: a + n, ["a", "n"])
    fb.return_()
    pb.add(fb)
    return pb.build()


def build_dead_params():
    """``f(n, acc) = f(n - 1, acc + n) * 3``: no param is read after the
    self-call, so the recursion needs no stacks at all."""
    pb = frontend.ProgramBuilder()
    fb = pb.function(
        "f", ["n", "acc"], ["out"], {"n": I32, "acc": I32}, {"out": I32}
    )
    t = _count_down(fb, "acc")
    u = fb.prim(lambda acc, n: acc + n, ["acc", "n"])
    fb.call("f", [t, u], out="a")
    fb.assign("out", lambda a: a * 3, ["a"])
    fb.return_()
    pb.add(fb)
    return pb.build()


def build_outer_caller():
    """``g(n) = f(n) + n`` with ``f`` from :func:`build_live_param`: ``g``
    is not reachable from ``f``, so no frame of ``f`` lies below ``g``."""
    pb = frontend.ProgramBuilder(main="g")
    fb = pb.function("f", ["n"], ["out"], {"n": I32}, {"out": I32})
    t = _count_down(fb, "n")
    fb.call("f", [t], out="a")
    fb.assign("out", lambda a, n: a + n, ["a", "n"])
    fb.return_()
    pb.add(fb)
    gb = pb.function("g", ["n"], ["out"], {"n": I32}, {"out": I32})
    gb.call("f", ["n"], out="y")
    gb.assign("out", lambda y, n: y + n, ["y", "n"])
    gb.return_()
    pb.add(gb)
    return pb.build()


def build_mutual_from_outer():
    """``h(n) = is_even(n)`` over :func:`tests.test_core.build_mutual`."""
    prog = build_mutual()
    pb = frontend.ProgramBuilder(main="h")
    for f in prog.functions.values():
        pb.functions[f.name] = f
    hb = pb.function("h", ["n"], ["out"], {"n": I32}, {"out": BOOL})
    hb.call("is_even", ["n"], out="out")
    hb.return_()
    pb.add(hb)
    return pb.build()


def call_sites(low):
    """``{"caller->callee": (pushes (var, src), return-site pops)}``, for
    programs with one call site per pair of functions."""
    entry_of = {e: f for f, e in low.func_entries.items()}
    sites = {}
    for blk in low.blocks:
        if isinstance(blk.term, ir.LPushJump):
            pushes = tuple(
                (op.var, op.src) for op in blk.ops if isinstance(op, ir.LPush)
            )
            ret = low.blocks[blk.term.ret]
            pops = tuple(op.var for op in ret.ops if isinstance(op, ir.LPop))
            site = f"{blk.label.split('.')[0]}->{entry_of[blk.term.target]}"
            assert site not in sites
            sites[site] = (pushes, pops)
    return sites


def argsets(low):
    return sorted(
        op.outs[0]
        for blk in low.blocks
        for op in blk.ops
        if isinstance(op, ir.LPrim) and op.name == "argset"
    )


N = np.array([0, 1, 2, 5, 7, 3, 11, 4], np.int32)

# (builder, inputs, {call site: (pushed vars, popped vars)}, argset params,
#  stack_vars, param pushes elided)
CASES = {
    "self_call_param_live": (
        build_live_param, (N,),
        {"f->f": (("f/n",), ("f/n",))},
        [], {"f/n"}, 0,
    ),
    "self_call_params_dead": (
        build_dead_params, (N, N * 2),
        {"f->f": ((), ())},
        ["f/acc", "f/n"], set(), 2,
    ),
    "from_unreachable_caller": (
        build_outer_caller, (N,),
        {"f->f": (("f/n",), ("f/n",)), "g->f": ((), ())},
        ["f/n"], {"f/n"}, 1,
    ),
    "mutual_recursion": (
        build_mutual, (N,),
        {"is_even->is_odd": (("is_odd/n",), ("is_odd/n",)),
         "is_odd->is_even": (("is_even/n",), ("is_even/n",))},
        [], {"is_even/n", "is_odd/n"}, 0,
    ),
    "mutual_from_unreachable_caller": (
        build_mutual_from_outer, (N,),
        {"is_even->is_odd": (("is_odd/n",), ("is_odd/n",)),
         "is_odd->is_even": (("is_even/n",), ("is_even/n",)),
         "h->is_even": ((), ())},
        ["is_even/n"], {"is_even/n", "is_odd/n"}, 1,
    ),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_pushes_per_call_site(case):
    build, _, sites, sets, stacked, elided = CASES[case]
    low = lowering.lower(build(), verify=True)
    got = {
        label: (tuple(v for v, _ in pushes), pops)
        for label, (pushes, pops) in call_sites(low).items()
    }
    assert got == sites
    assert argsets(low) == sets
    assert low.stack_vars == stacked
    d = passes.diagnose(low)
    assert d.verified, d.verification_error
    assert d.param_pushes_elided == elided


@pytest.mark.parametrize("case", sorted(CASES))
def test_pc_matches_local_bit_for_bit(case):
    build, args, *_ = CASES[case]
    prog = build()
    pc = batching.autobatch(prog, backend="pc", max_depth=16, verify=True)
    local = batching.autobatch(prog, backend="local")
    want = jax.tree.leaves(local(*args))
    got = jax.tree.leaves(pc(*args))
    assert len(got) == len(want)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_self_call_pushes_the_argument_itself():
    """A live param's push carries the argument (a fresh ``argcopy`` temp),
    so it is the param's save and the argument passing in one op."""
    low = lowering.lower(build_live_param())
    (pushes, _), = call_sites(low).values()
    (var, src), = pushes
    assert var == "f/n" and src.startswith("f/%arg")


NUTS_STACK_VARS = {
    "build_tree/" + v
    for v in ("tm", "rm", "tp", "rp", "th1", "log_u", "v", "eps",
              "jm1", "k3", "n1", "key_out")
}


@pytest.mark.parametrize("fuse", [False, True])
def test_nuts_stacks_and_counters(fuse):
    """The benchmark's NUTS program (dim 100, depth 10): 3 of 35 param
    pushes remain, 12 stack variables, 2,036 B of stack per lane and row."""
    t = targets.correlated_gaussian(100, rho=0.95)
    low = lowering.lower(
        nuts.build_nuts_program(t, nuts.NutsSettings(max_tree_depth=10))
    )
    if fuse:
        low = passes.PassPipeline(passes.fusion_passes()).run(low)
    assert low.stack_vars == NUTS_STACK_VARS
    d = passes.diagnose(low)
    assert d.param_pushes_elided == 32
    # five [100] f32, log_u/v/eps f32, jm1/n1 i32, k3/key_out [2] u32
    assert d.stack_bytes_per_row == 5 * 100 * 4 + 3 * 4 + 2 * 4 + 2 * 8
    assert d.stack_bytes_per_row == 2036
    assert "2036 B per lane per depth row (32 param pushes elided)" in (
        d.pretty()
    )
    param_pushes = sum(
        1
        for blk in low.blocks
        for op in blk.ops
        if isinstance(op, ir.LPush) and op.src != op.var
    )
    assert param_pushes == 3


def test_recursion_free_program_elides_nothing():
    """A recursion-free program elides nothing and carries no stacks."""
    pb = frontend.ProgramBuilder(main="top")
    leaf = pb.function("leaf", ["n"], ["out"], {"n": I32}, {"out": I32})
    leaf.assign("out", lambda n: n + 1, ["n"])
    leaf.return_()
    pb.add(leaf)
    top = pb.function("top", ["n"], ["out"], {"n": I32}, {"out": I32})
    top.call("leaf", ["n"], out="out")
    top.return_()
    pb.add(top)
    d = passes.diagnose(lowering.lower(pb.build()))
    assert d.param_pushes_elided == 0
    assert d.stack_bytes_per_row == 0
