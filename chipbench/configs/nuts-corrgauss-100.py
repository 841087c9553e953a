"""AR(1) correlated Gaussian (arXiv:1910.11141, Sec. 4): the density as the
sampler's user writes it, its plain reference, its start states and the
work of one gradient.

N(0, Sigma) with Sigma_ij = rho^|i - j|: every marginal has variance 1 and
the precision matrix is tridiagonal.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np


def program_target(cfg):
    from repro.mcmc import targets

    return targets.correlated_gaussian(cfg["dim"], cfg["rho"])


def precision(cfg):
    """Main and off diagonal of the tridiagonal precision matrix."""
    d, rho = cfg["dim"], cfg["rho"]
    s = 1.0 / (1.0 - rho * rho)
    main = np.full((d,), s * (1.0 + rho * rho))
    main[0] = main[-1] = s
    return main.astype(np.float32), np.full((d - 1,), -s * rho, np.float32)


def reference(cfg, control: bool = False):
    """``(logp, grad)`` of one chain's position: -x'Px/2 and -Px.

    The control computes both in bfloat16, the next precision below.
    """
    dt = jnp.bfloat16 if control else jnp.float32
    main, off = (jnp.asarray(a, dt) for a in precision(cfg))

    def logp(x):
        x = x.astype(dt)
        quad = jnp.sum(main * x * x) + 2.0 * jnp.sum(off * x[:-1] * x[1:])
        return (-0.5 * quad).astype(jnp.float32)

    def grad(x):
        x = x.astype(dt)
        px = main * x
        px = px.at[:-1].add(off * x[1:]).at[1:].add(off * x[:-1])
        return (-px).astype(jnp.float32)

    return logp, grad


def start_states(cfg, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` exact draws from N(0, Sigma), by the AR(1) recursion."""
    d, rho = cfg["dim"], cfg["rho"]
    e = rng.standard_normal((n, d))
    x = np.empty((n, d))
    x[:, 0] = e[:, 0]
    for i in range(1, d):
        x[:, i] = rho * x[:, i - 1] + np.sqrt(1.0 - rho * rho) * e[:, i]
    return x.astype(np.float32)


def work(cfg, chains: int) -> dict:
    """FLOPs and bytes one evaluation needs for ``chains`` chains.

    A gradient is the tridiagonal product Px: D + 2(D - 1) multiplies and
    2(D - 1) adds, 5D - 4.  A value is x'Px/2 as the density writes it:
    2D multiplies and D - 1 adds for the diagonal term, 2(D - 1)
    multiplies and D - 2 adds for the neighbour term, and 3 to combine
    them, 6D - 2.  The bytes are the two diagonals, the positions in and
    the results out.
    """
    d = cfg["dim"]
    consts = 4 * (2 * d - 1)
    return {
        "grad_flops": (5 * d - 4) * chains,
        "grad_bytes": consts + 2 * 4 * d * chains,
        "value_flops": (6 * d - 2) * chains,
        "value_bytes": consts + 4 * d * chains + 4 * chains,
    }
