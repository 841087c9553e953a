"""Bayesian logistic regression (arXiv:1910.11141, Sec. 4): the density as
the sampler's user writes it, its plain reference, its start states and the
work of one gradient.

The data belong to the configuration: they come from the fixed
``data_seed``, by the same draws as ``repro.mcmc.targets``, so that a run's
``--seed`` moves the chains and never the executable.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


@functools.lru_cache(maxsize=None)
def _data(num_data: int, dim: int, data_seed: int):
    rng = np.random.default_rng(data_seed)
    x = rng.normal(size=(num_data, dim)).astype(np.float32)
    w_true = (rng.normal(size=(dim,)) / np.sqrt(dim)).astype(np.float32)
    p = 1.0 / (1.0 + np.exp(-(x @ w_true)))
    y = (rng.uniform(size=(num_data,)) < p).astype(np.float32)
    return x, (2.0 * y - 1.0).astype(np.float32)


def data(cfg):
    """``(x [N, D], y in {-1, +1} [N])``, float32."""
    return _data(cfg["num_data"], cfg["dim"], cfg["data_seed"])


def program_target(cfg):
    """The sampler's target, built by the program from the same data seed."""
    from repro.mcmc import targets

    return targets.logistic_regression(
        num_data=cfg["num_data"], dim=cfg["dim"], seed=cfg["data_seed"])


def _dot3(a, b):
    """``a @ b`` in three bfloat16 products accumulated in float32: what
    'high' means on a TPU, written out for platforms that compute every
    float32 product in full whatever precision is asked for."""
    def split(v):
        hi = v.astype(jnp.bfloat16)
        return hi, (v - hi.astype(jnp.float32)).astype(jnp.bfloat16)

    (ah, al), (bh, bl) = split(a), split(b)

    def dot(x, y):
        return jnp.dot(x, y, preferred_element_type=jnp.float32)

    return dot(ah, bh) + dot(ah, bl) + dot(al, bh)


def reference(cfg, control: bool = False):
    """``(logp, grad)`` of one chain's weights: the log-likelihood of the
    labels under the logistic model plus the standard-normal prior, and
    its gradient by ``jax.grad``.

    The products with the data run at the configuration's precision.  The
    control runs them one precision lower, 'high' for 'highest': on a TPU
    by its own three-pass product, elsewhere by ``_dot3``, in the forward
    and the backward product alike.
    """
    x, y = data(cfg)
    xj, yj = jnp.asarray(x), jnp.asarray(y)

    if control and jax.default_backend() != "tpu":
        @jax.custom_vjp
        def logits(w):
            return _dot3(xj, w)

        logits.defvjp(lambda w: (_dot3(xj, w), None),
                      lambda _, ct: (_dot3(ct, xj),))
    else:
        precision = "high" if control else cfg["matmul_precision"]

        def logits(w):
            return jnp.dot(xj, w, precision=precision)

    def logp(w):
        return jnp.sum(jax.nn.log_sigmoid(yj * logits(w))) - 0.5 * jnp.sum(w * w)

    return logp, jax.grad(logp)


@functools.lru_cache(maxsize=None)
def _laplace(num_data: int, dim: int, data_seed: int):
    """MAP and Cholesky factor of the posterior covariance at the MAP, by
    Newton's method in float64."""
    x, y = _data(num_data, dim, data_seed)
    x = x.astype(np.float64)
    y = y.astype(np.float64)
    w = np.zeros(dim)
    for _ in range(12):
        z = y * (x @ w)
        s = 1.0 / (1.0 + np.exp(z))  # sigmoid(-z)
        g = x.T @ (y * s) - w
        h = (x * (s * (1.0 - s))[:, None]).T @ x + np.eye(dim)
        step = np.linalg.solve(h, g)
        w = w + step
        if np.max(np.abs(step)) < 1e-12:
            break
    return w, np.linalg.cholesky(np.linalg.inv(h))


def start_states(cfg, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` draws from the Laplace approximation at the MAP."""
    w, chol = _laplace(cfg["num_data"], cfg["dim"], cfg["data_seed"])
    return (w + rng.standard_normal((n, cfg["dim"])) @ chol.T).astype(
        np.float32)


def work(cfg, chains: int) -> dict:
    """FLOPs and bytes one evaluation needs for ``chains`` chains.

    A gradient is X w (2ND) and X^T r (2ND); a value is X w alone.  The
    bytes are X, read once, plus the weights in and the result out.
    """
    n, d = cfg["num_data"], cfg["dim"]
    x_bytes = 4 * n * d
    return {
        "grad_flops": 4 * n * d * chains,
        "grad_bytes": x_bytes + 2 * 4 * d * chains,
        "value_flops": 2 * n * d * chains,
        "value_bytes": x_bytes + 4 * d * chains + 4 * chains,
    }
