"""Unit tests for the autobatching core: IR, lowering, both runtimes."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import api, frontend, ir, lowering, reference
from repro.core.frontend import BOOL, F32, I32


def build_fib():
    pb = frontend.ProgramBuilder()
    fb = pb.function(
        "fib", ["n"], ["out"], {"n": I32}, {"out": I32}
    )
    c = fb.prim(lambda n: n < 2, ["n"], name="lt2")
    with fb.if_(c):
        fb.copy("n", out="out")
        fb.return_()
    t1 = fb.prim(lambda n: n - 1, ["n"])
    fb.call("fib", [t1], out="a")
    t2 = fb.prim(lambda n: n - 2, ["n"])
    fb.call("fib", [t2], out="b")
    fb.assign("out", lambda a, b: a + b, ["a", "b"])
    fb.return_()
    pb.add(fb)
    return pb.build()


def build_pow_loop():
    """pow(x, k) via a while loop — no recursion, control flow only."""
    pb = frontend.ProgramBuilder()
    fb = pb.function(
        "powi",
        ["x", "k"],
        ["out"],
        {"x": F32, "k": I32},
        {"out": F32},
    )
    fb.const(1.0, jnp.float32, out="out")
    fb.copy("k", out="i")
    with fb.while_(lambda i: i > 0, ["i"]):
        fb.assign("out", lambda o, x: o * x, ["out", "x"])
        fb.assign("i", lambda i: i - 1, ["i"])
    fb.return_()
    pb.add(fb)
    return pb.build()


def build_mutual():
    """Mutual recursion: is_even/is_odd on non-negative ints."""
    pb = frontend.ProgramBuilder()
    ev = pb.function("is_even", ["n"], ["out"], {"n": I32}, {"out": BOOL})
    c = ev.prim(lambda n: n == 0, ["n"])
    with ev.if_(c):
        ev.const(True, jnp.bool_, out="out")
        ev.return_()
    t = ev.prim(lambda n: n - 1, ["n"])
    ev.call("is_odd", [t], out="out")
    ev.return_()
    pb.add(ev)
    od = pb.function("is_odd", ["n"], ["out"], {"n": I32}, {"out": BOOL})
    c = od.prim(lambda n: n == 0, ["n"])
    with od.if_(c):
        od.const(False, jnp.bool_, out="out")
        od.return_()
    t = od.prim(lambda n: n - 1, ["n"])
    od.call("is_even", [t], out="out")
    od.return_()
    pb.add(od)
    return ir.Program(functions=pb.functions, main="is_even")


FIB = np.array([0, 1, 1, 2, 3, 5, 8, 13, 21, 34, 55, 89, 144], np.int64)


class TestLowering:
    def test_fib_stack_assignment(self):
        """Paper opts (ii)/(iii): n, a stacked; b top-only; temps elided."""
        low = lowering.lower(build_fib())
        assert low.stack_vars == {"fib/n", "fib/a"}
        assert "fib/b" in low.temp_vars or "fib/b" not in low.stack_vars
        assert "fib/out" not in low.stack_vars
        # temporaries never appear in VM state
        assert all(v.startswith("fib/%") or v == "fib/b" for v in low.temp_vars)

    def test_nonrecursive_has_no_stacks(self):
        """A recursion-free program needs no data stacks at all (paper §3)."""
        low = lowering.lower(build_pow_loop())
        assert low.stack_vars == frozenset()

    def test_popush_elimination(self):
        """Adjacent sibling calls cancel the pop/push on the param stack."""
        pb = frontend.ProgramBuilder()
        fb = pb.function("f", ["n"], ["out"], {"n": I32}, {"out": I32})
        c = fb.prim(lambda n: n <= 0, ["n"])
        with fb.if_(c):
            fb.const(0, jnp.int32, out="out")
            fb.return_()
        t = fb.prim(lambda n: n - 1, ["n"])
        fb.call("f", [t], out="a")
        # Second sibling call with an argument that does NOT read n:
        fb.call("f", ["a"], out="b")
        # n stays live across both calls, so both push it (a param dead
        # after a self-call is written, not pushed).
        fb.assign("out", lambda a, b, n: a + b + n, ["a", "b", "n"])
        fb.return_()
        pb.add(fb)
        low = lowering.lower(pb.build())
        names = [
            op.name
            for blk in low.blocks
            for op in blk.ops
            if isinstance(op, ir.LPrim)
        ]
        assert "popush" in names  # the peephole fired

    def test_exit_index(self):
        low = lowering.lower(build_fib())
        assert low.exit_index == len(low.blocks)


class TestBackendAgreement:
    @pytest.mark.parametrize("backend", ["pc", "local", "local_eager"])
    def test_fib(self, backend):
        prog = build_fib()
        n = np.array([0, 1, 5, 9, 12, 3, 7, 2], np.int32)
        out = api.autobatch(prog, 8, backend=backend, max_depth=20)({"n": n})
        np.testing.assert_array_equal(np.asarray(out["out"]), FIB[n])

    @pytest.mark.parametrize("backend", ["pc", "local", "local_eager"])
    def test_loop(self, backend):
        prog = build_pow_loop()
        x = np.array([1.5, 2.0, 0.5, 3.0], np.float32)
        k = np.array([3, 0, 4, 2], np.int32)
        out = api.autobatch(prog, 4, backend=backend)({"x": x, "k": k})
        np.testing.assert_allclose(
            np.asarray(out["out"]), x.astype(np.float64) ** k, rtol=1e-6
        )

    @pytest.mark.parametrize("backend", ["pc", "local"])
    def test_mutual_recursion(self, backend):
        prog = build_mutual()
        n = np.array([0, 1, 2, 7, 10, 13], np.int32)
        out = api.autobatch(prog, 6, backend=backend, max_depth=20)({"n": n})
        np.testing.assert_array_equal(np.asarray(out["out"]), n % 2 == 0)

    def test_reference_matches(self):
        prog = build_fib()
        n = np.array([4, 6], np.int32)
        ref = reference.run_reference_batch(prog, {"n": n})
        np.testing.assert_array_equal(ref["out"], FIB[n])


class TestVMBehavior:
    def test_vector_state(self):
        """Per-member values may be vectors (NUTS carries [dim] positions)."""
        pb = frontend.ProgramBuilder()
        vec = frontend.spec((4,), jnp.float32)
        fb = pb.function(
            "scale", ["v", "k"], ["out"], {"v": vec, "k": I32}, {"out": vec}
        )
        fb.copy("v", out="out")
        fb.copy("k", out="i")
        with fb.while_(lambda i: i > 0, ["i"]):
            fb.assign("out", lambda o: o * 2.0, ["out"])
            fb.assign("i", lambda i: i - 1, ["i"])
        fb.return_()
        pb.add(fb)
        prog = pb.build()
        v = np.arange(12, dtype=np.float32).reshape(3, 4)
        k = np.array([1, 0, 3], np.int32)
        for backend in ("pc", "local"):
            out = api.autobatch(prog, 3, backend=backend)({"v": v, "k": k})
            np.testing.assert_allclose(
                np.asarray(out["out"]), v * (2.0 ** k)[:, None]
            )

    def test_non_convergence_flag(self):
        pb = frontend.ProgramBuilder()
        fb = pb.function("spin", ["n"], ["out"], {"n": I32}, {"out": I32})
        fb.copy("n", out="out")
        with fb.while_(lambda o: o >= 0, ["out"]):  # never exits for n >= 0
            fb.assign("out", lambda o: o, ["out"])
        fb.return_()
        pb.add(fb)
        bp = api.autobatch(pb.build(), 2, backend="pc", max_steps=50)
        bp({"n": np.array([1, 2], np.int32)})
        assert not bool(bp.last_result.converged)

    def test_divergence_and_reconvergence(self):
        """Members taking different branches re-converge at the join block."""
        pb = frontend.ProgramBuilder()
        fb = pb.function("f", ["x"], ["out"], {"x": F32}, {"out": F32})
        c = fb.prim(lambda x: x > 0, ["x"])
        with fb.if_(c):
            fb.assign("y", lambda x: x * 2.0, ["x"])
        with fb.orelse():
            fb.assign("y", lambda x: -x, ["x"])
        fb.assign("out", lambda y: y + 1.0, ["y"])
        fb.return_()
        pb.add(fb)
        prog = pb.build()
        x = np.array([1.0, -2.0, 3.0, -4.0], np.float32)
        expect = np.where(x > 0, x * 2 + 1, -x + 1)
        for backend in ("pc", "local", "local_eager"):
            out = api.autobatch(prog, 4, backend=backend)({"x": x})
            np.testing.assert_allclose(np.asarray(out["out"]), expect)

    def test_batching_across_depth_beats_local(self):
        """The paper's headline property (Fig. 1 vs Fig. 3, Fig. 6): because
        the PC VM batches members at *different stack depths*, it executes the
        expensive leaf primitive far fewer times (at higher utilization) than
        the host-recursive local-static runtime, which can only batch members
        whose Python call stacks coincide."""
        pb = frontend.ProgramBuilder()
        fb = pb.function("fib", ["n"], ["out"], {"n": I32}, {"out": I32})
        c = fb.prim(lambda n: n < 2, ["n"], name="lt2")
        with fb.if_(c):
            fb.prim(lambda n: n, ["n"], out="out", name="leaf", tag="leaf")
            fb.return_()
        t1 = fb.prim(lambda n: n - 1, ["n"])
        fb.call("fib", [t1], out="a")
        t2 = fb.prim(lambda n: n - 2, ["n"])
        fb.call("fib", [t2], out="b")
        fb.assign("out", lambda a, b: a + b, ["a", "b"])
        fb.return_()
        pb.add(fb)
        prog = pb.build()

        rng = np.random.default_rng(0)
        n = rng.integers(8, 13, 32).astype(np.int32)
        bp = api.autobatch(prog, 32, backend="pc", max_depth=24)
        bp({"n": n})
        pc_execs, pc_active = bp.last_result.tag_stats["leaf"]
        loc = api.autobatch(prog, 32, backend="local")
        loc({"n": n})
        loc_execs = loc.batcher.stats.tag_execs["leaf"]
        loc_active = loc.batcher.stats.tag_active["leaf"]
        assert pc_execs < loc_execs  # fewer expensive-primitive launches
        pc_util = pc_active / (pc_execs * 32)
        loc_util = loc_active / (loc_execs * 32)
        assert pc_util > loc_util  # at strictly better batch utilization

    def test_utilization_stats(self):
        prog = build_fib()
        bp = api.autobatch(prog, 4, backend="pc", max_depth=16)
        bp({"n": np.array([8, 8, 8, 8], np.int32)})
        res = bp.last_result
        assert int(res.steps) > 0
        assert res.block_exec.sum() == res.steps
        # Identical inputs => every step fully active.
        util = res.block_active.sum() / (res.block_exec.sum() * 4)
        assert util == pytest.approx(1.0)


class TestTypeInference:
    def test_conflicting_merge_raises(self):
        pb = frontend.ProgramBuilder()
        fb = pb.function("f", ["x"], ["out"], {"x": F32}, {"out": F32})
        c = fb.prim(lambda x: x > 0, ["x"])
        with fb.if_(c):
            fb.assign("y", lambda x: x, ["x"])
        with fb.orelse():
            fb.assign("y", lambda x: x.astype(jnp.int32), ["x"])
        fb.assign("out", lambda y: y * 1.0, ["y"])
        fb.return_()
        pb.add(fb)
        with pytest.raises(TypeError, match="conflicting"):
            lowering.lower(pb.build())

    def test_missing_output_spec_raises(self):
        with pytest.raises(ValueError, match="missing output spec"):
            fb = frontend.FunctionBuilder("f", ["x"], ["out"], {"x": F32}, {})
            fb.copy("x", out="out")
            fb.return_()
            fb.build().validate()
