"""The plain reference walks the same trees as the program: on the CPU,
where float32 is computed in full, they agree to rounding."""
import jax
import numpy as np
import pytest

import cell
import nutsref

BENCH = cell.benchmark()


@pytest.mark.parametrize("name,over", [
    ("nuts-corrgauss-100", {"dim": 10}),
    ("nuts-logreg-10k", {"num_data": 300, "dim": 6}),
])
def test_reference_matches_program(name, over):
    from repro.mcmc import nuts

    cfg, mod = cell.config(BENCH, name)
    cfg = {**cfg, **over, "max_tree_depth": 5}
    settings = dict(max_tree_depth=5, steps_per_leaf=cfg["steps_per_leaf"],
                    num_steps=2)
    theta = mod.start_states(cfg, 8, np.random.default_rng(1))
    keys = np.random.default_rng(2).integers(0, 2**32, (8, 2), np.uint32)
    eps = np.float32(cfg["step_size"])
    kernel = nuts.make_nuts_kernel(
        mod.program_target(cfg), nuts.NutsSettings(**settings), backend="pc")
    got = jax.device_get(kernel(theta, eps, keys))
    logp, grad = mod.reference(cfg)
    ref = jax.device_get(nutsref.make_runner(logp, grad, cfg["dim"],
                                             **settings)(theta, eps, keys))
    gaps = cell.chain_gaps(got, {k: np.asarray(v) for k, v in ref.items()})
    assert gaps.max() < 1e-4, gaps


def test_logreg_reference_uses_the_programs_data():
    cfg, mod = cell.config(BENCH, "nuts-logreg-10k")
    cfg = {**cfg, "num_data": 400, "dim": 5}
    w = np.random.default_rng(3).normal(size=5).astype(np.float32) * 0.3
    prog = mod.program_target(cfg)
    logp, grad = mod.reference(cfg)
    np.testing.assert_allclose(float(logp(w)), float(prog.logp(w)),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(grad(w)),
                               np.asarray(jax.grad(prog.logp)(w)),
                               rtol=1e-4, atol=1e-3)


def test_start_states_are_near_the_posterior_mode():
    cfg, mod = cell.config(BENCH, "nuts-logreg-10k")
    cfg = {**cfg, "num_data": 2000, "dim": 5}
    x, y = mod.data(cfg)
    draws = mod.start_states(cfg, 4000, np.random.default_rng(0))
    w_map, chol = mod._laplace(cfg["num_data"], cfg["dim"], cfg["data_seed"])
    # The MAP zeroes the gradient; the draws centre on it with the
    # Laplace covariance.
    z = y * (x @ w_map)
    g = x.T @ (y / (1 + np.exp(z))) - w_map
    assert np.abs(g).max() < 1e-6
    np.testing.assert_allclose(draws.mean(0), w_map,
                               atol=4 * np.sqrt(np.diag(chol @ chol.T) / 4000).max())


def test_corrgauss_start_states_have_the_target_covariance():
    cfg, mod = cell.config(BENCH, "nuts-corrgauss-100")
    draws = mod.start_states({**cfg, "dim": 4}, 20000,
                             np.random.default_rng(0)).astype(np.float64)
    idx = np.arange(4)
    want = cfg["rho"] ** np.abs(idx[:, None] - idx[None, :])
    np.testing.assert_allclose(np.cov(draws.T), want, atol=0.05)
