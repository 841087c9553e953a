"""Tests for the NUTS workload: backend agreement, moments, baselines.

NUTS runs entirely on the decorator-first pytree API: the kernel takes
positional ``(theta0, eps, key)`` arguments (``eps`` is a ``Shared``
scalar) and returns the pytree state ``{"theta", "sum_theta", "sum_sq"}``.
"""
import numpy as np
import pytest

from repro.core import lowering
from repro.mcmc import iterative, nuts, targets


@pytest.fixture(scope="module")
def small_nuts():
    t = targets.isotropic_gaussian(3)
    s = nuts.NutsSettings(max_tree_depth=5, num_steps=4, steps_per_leaf=2)
    args = nuts.initial_state(t, 4, eps=0.4, seed=2)
    return t, s, args


STATE_KEYS = ("theta", "sum_theta", "sum_sq")


class TestNutsProgram:
    def test_lowering_structure(self, small_nuts):
        """The recursion forces stacks exactly on the build_tree frame state
        that a frame still reads after a self-call returns."""
        t, s, _ = small_nuts
        low = lowering.lower(nuts.build_nuts_program(t, s))
        assert low.stack_vars == {
            "build_tree/" + v
            for v in ("tm", "rm", "tp", "rp", "th1", "log_u", "v", "eps",
                      "jm1", "k3", "n1", "key_out")
        }
        # Params no frame reads after a self-call take their argument by a
        # plain write: the first self-call reads theta/r/j/key only before.
        for v in ["theta", "r", "j", "key"]:
            assert "build_tree/" + v not in low.stack_vars
        # Chain-level accumulators never cross a recursive call.
        assert "nuts_chain/sum_theta" not in low.stack_vars
        assert "nuts_chain/sum_sq" not in low.stack_vars

    @pytest.mark.parametrize("backend", ["pc", "local"])
    def test_agrees_with_reference(self, small_nuts, backend):
        """Batched NUTS must equal the unbatched oracle member-by-member.

        On an elementwise target the primitives are bitwise-stable under
        vmap, so whole chaotic trajectories must coincide."""
        t, s, args = small_nuts
        ref = nuts.make_nuts_kernel(t, s, backend="reference")(*args)
        out = nuts.make_nuts_kernel(t, s, backend=backend,
                                    max_steps=50_000)(*args)
        assert set(out) == set(STATE_KEYS)
        for k in STATE_KEYS:
            np.testing.assert_allclose(
                np.asarray(out[k]), ref[k], rtol=1e-4, atol=1e-4
            )

    def test_moments_correlated_gaussian(self):
        """Sampled marginal moments match the target (paper §4.2 problem)."""
        t = targets.correlated_gaussian(8, rho=0.9)
        s = nuts.NutsSettings(max_tree_depth=8, num_steps=60, steps_per_leaf=4)
        z = 64
        kern = nuts.make_nuts_kernel(t, s, max_steps=200_000)
        state = kern(*nuts.initial_state(t, z, eps=0.25, seed=3))
        assert bool(kern.last_result.converged)
        n = z * s.num_steps
        mean = np.asarray(state["sum_theta"]).sum(0) / n
        ex2 = np.asarray(state["sum_sq"]).sum(0) / n
        std = np.sqrt(ex2 - mean**2)
        np.testing.assert_allclose(mean, 0.0, atol=0.12)
        np.testing.assert_allclose(std, 1.0, atol=0.12)

    def test_divergent_chains_have_low_utilization(self):
        """Different chains pick different tree depths => util < 1 (Fig. 6)."""
        t = targets.correlated_gaussian(8, rho=0.9)
        s = nuts.NutsSettings(max_tree_depth=8, num_steps=10, steps_per_leaf=4)
        kern = nuts.make_nuts_kernel(t, s, max_steps=100_000)
        assert kern.utilization == {}  # unified semantics: {} before any run
        kern(*nuts.initial_state(t, 16, eps=0.25, seed=4))
        util = kern.utilization["grad"]
        assert 0.0 < util < 1.0

    def test_logistic_regression_target_runs(self):
        t = targets.logistic_regression(num_data=200, dim=8, seed=0)
        s = nuts.NutsSettings(max_tree_depth=6, num_steps=3, steps_per_leaf=2)
        kern = nuts.make_nuts_kernel(t, s, max_steps=50_000)
        state = kern(*nuts.initial_state(t, 4, eps=0.05, seed=5))
        assert bool(kern.last_result.converged)
        assert np.all(np.isfinite(np.asarray(state["theta"])))

    def test_kernel_cache_shared_across_batch_sizes(self):
        """One NUTS kernel serves several chain counts; the stack-explicit
        lowering happens exactly once (the decorator API's cache contract)."""
        t = targets.isotropic_gaussian(2)
        s = nuts.NutsSettings(max_tree_depth=4, num_steps=2, steps_per_leaf=2)
        kern = nuts.make_nuts_kernel(t, s, max_steps=50_000)
        kern(*nuts.initial_state(t, 2, eps=0.4, seed=0))
        kern(*nuts.initial_state(t, 5, eps=0.4, seed=0))
        info = kern.cache_info()
        assert info.lowerings == 1 and info.misses == 2


class TestIterativeBaseline:
    def test_moments(self):
        """The hand-batched iterative rewrite samples the same distribution."""
        t = targets.correlated_gaussian(8, rho=0.9)
        s = nuts.NutsSettings(max_tree_depth=8, num_steps=60, steps_per_leaf=4)
        z = 64
        theta0, eps, keys = nuts.initial_state(t, z, eps=0.25, seed=3)
        out = iterative.run_batched(t, s, theta0, eps, keys)
        n = z * s.num_steps
        mean = np.asarray(out["sum_theta"]).sum(0) / n
        ex2 = np.asarray(out["sum_sq"]).sum(0) / n
        std = np.sqrt(ex2 - mean**2)
        np.testing.assert_allclose(mean, 0.0, atol=0.12)
        np.testing.assert_allclose(std, 1.0, atol=0.12)
        assert int(out["grads"].sum()) > 0

    def test_matches_autobatched_grad_count_scale(self):
        """Grad-eval counts of the two implementations are the same order:
        both run the same doubling procedure over the same trajectories."""
        t = targets.isotropic_gaussian(4)
        s = nuts.NutsSettings(max_tree_depth=6, num_steps=5, steps_per_leaf=2)
        theta0, eps, keys = nuts.initial_state(t, 8, eps=0.3, seed=7)
        kern = nuts.make_nuts_kernel(t, s, max_steps=50_000)
        kern(theta0, eps, keys)
        execs, active = kern.tag_stats["grad"]
        vm_grads = active * s.grads_per_leaf  # member-leaf evals
        out = iterative.run_batched(t, s, theta0, eps, keys)
        it_grads = int(out["grads"].sum())
        assert 0.2 < vm_grads / it_grads < 5.0
