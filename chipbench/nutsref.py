"""Plain reference NUTS: the recursive slice sampler written out in JAX.

This is the yardstick that decides ``correct``.  It imports nothing of the
program under test.  It follows Hoffman & Gelman (2014), Algorithm 3, with
``steps_per_leaf`` leapfrog steps per tree leaf (arXiv:1910.11141, Sec. 4),
and it draws its randomness exactly as the sampler it checks does:

* a trajectory splits its key into (momentum, slice, rest); every doubling
  splits the rest into (direction, tree, accept, rest);
* an internal tree node splits its key into (left, right, out); a leaf
  passes its key through; a node accepts the right half's proposal with
  ``uniform(right.key_out) * (n_left + n_right) < n_right``.

So, given the same start, step size and key, it walks the same trees and
returns the same draws, up to the rounding of the density.

The recursion has a static depth at each doubling, so it is traced once
per level: ``build_tree(j)`` runs its two halves as a loop of at most two
passes over one traced ``build_tree(j - 1)``.  Every loop also carries an
``active`` mask, so chains that have stopped do not keep a batched loop
running.  One chain is written here; ``run`` maps it over the chains.
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

DELTA_MAX = 1000.0  # divergence threshold of the slice (standard)


class Tree(NamedTuple):
    tm: jax.Array
    rm: jax.Array
    tp: jax.Array
    rp: jax.Array
    th1: jax.Array
    n: jax.Array
    s: jax.Array
    key: jax.Array


def make_chain(logp: Callable, grad: Callable, dim: int, *,
               max_tree_depth: int, steps_per_leaf: int, num_steps: int):
    """``chain(theta0, eps, key) -> (theta, sum_theta, sum_sq)`` for one
    chain, given the density's value ``logp`` and gradient ``grad``."""

    def leapfrog(theta, r, step):
        def body(_, carry):
            theta, r, g = carry
            r_half = r + 0.5 * step * g
            theta = theta + step * r_half
            g = grad(theta)
            r = r_half + 0.5 * step * g
            return theta, r, g

        theta, r, _ = lax.fori_loop(0, steps_per_leaf, body,
                                    (theta, r, grad(theta)))
        return theta, r

    def joint(theta, r):
        return logp(theta) - 0.5 * jnp.sum(r * r)

    def no_uturn(tm, rm, tp, rp):
        d = tp - tm
        ok = jnp.logical_and(jnp.dot(d, rm) >= 0.0, jnp.dot(d, rp) >= 0.0)
        return ok.astype(jnp.int32)

    def split3(key):
        ks = jax.random.split(key, 3)
        return ks[0], ks[1], ks[2]

    def leaf(theta, r, log_u, v, eps, key, active):
        th, rr = leapfrog(theta, r, v * eps)
        jnt = joint(th, rr)
        return Tree(th, rr, th, rr, th,
                    (log_u <= jnt).astype(jnp.int32),
                    (jnt > log_u - DELTA_MAX).astype(jnp.int32), key)

    def make_node(child):
        def node(theta, r, log_u, v, eps, key, active):
            k_left, k_right, key_out = split3(key)
            neg = v < 0.0

            def half(carry):
                i, t, pending = carry
                first = i == 0
                start_t = jnp.where(first, theta, jnp.where(neg, t.tm, t.tp))
                start_r = jnp.where(first, r, jnp.where(neg, t.rm, t.rp))
                sub = child(start_t, start_r, log_u, v, eps,
                            jnp.where(first, k_left, k_right), pending)
                # Second half: extend the trajectory's edge in direction v,
                # accept its proposal with prob n_right / (n_left + n_right).
                tm = jnp.where(neg, sub.tm, t.tm)
                rm = jnp.where(neg, sub.rm, t.rm)
                tp = jnp.where(neg, t.tp, sub.tp)
                rp = jnp.where(neg, t.rp, sub.rp)
                n = t.n + sub.n
                acc = jax.random.uniform(sub.key) * n < sub.n
                merged = Tree(
                    tm, rm, tp, rp, jnp.where(acc, sub.th1, t.th1), n,
                    sub.s * no_uturn(tm, rm, tp, rp), key_out,
                )
                out = jax.tree_util.tree_map(
                    lambda a, b: jnp.where(first, a, b),
                    sub._replace(key=key_out), merged,
                )
                return i + 1, out, jnp.logical_and(first, out.s == 1)

            blank = Tree(theta, r, theta, r, theta, jnp.int32(0),
                         jnp.int32(0), key_out)
            _, t, _ = lax.while_loop(lambda c: c[2], half,
                                     (jnp.int32(0), blank, active))
            return t

        return node

    builders = [leaf]
    for _ in range(max_tree_depth - 1):
        builders.append(make_node(builders[-1]))

    def trajectory(theta, eps, key):
        ks = jax.random.split(key, 3)
        k_mom, k_slice, key = ks[0], ks[1], ks[2]
        r0 = jax.random.normal(k_mom, (dim,), jnp.float32)
        log_u = joint(theta, r0) + jnp.log1p(-jax.random.uniform(k_slice))
        st = dict(tm=theta, rm=r0, tp=theta, rp=r0, out=theta,
                  n=jnp.int32(1), s=jnp.int32(1), key=key)
        for build in builders:
            def doubling(c, build=build):
                st, pending = c
                ks = jax.random.split(st["key"], 4)
                k_dir, k_tree, k_acc, key = ks[0], ks[1], ks[2], ks[3]
                v = jnp.where(jax.random.bernoulli(k_dir), 1.0, -1.0).astype(
                    jnp.float32)
                neg = v < 0.0
                sub = build(jnp.where(neg, st["tm"], st["tp"]),
                            jnp.where(neg, st["rm"], st["rp"]),
                            log_u, v, eps, k_tree, pending)
                tm = jnp.where(neg, sub.tm, st["tm"])
                rm = jnp.where(neg, sub.rm, st["rm"])
                tp = jnp.where(neg, st["tp"], sub.tp)
                rp = jnp.where(neg, st["rp"], sub.rp)
                acc = jnp.logical_and(
                    sub.s == 1, jax.random.uniform(k_acc) * st["n"] < sub.n)
                new = dict(tm=tm, rm=rm, tp=tp, rp=rp,
                           out=jnp.where(acc, sub.th1, st["out"]),
                           n=st["n"] + sub.n,
                           s=sub.s * no_uturn(tm, rm, tp, rp), key=key)
                return new, jnp.bool_(False)

            st, _ = lax.while_loop(lambda c: c[1], doubling,
                                   (st, st["s"] == 1))
        return st["out"], st["key"]

    def chain(theta0, eps, key):
        def body(_, carry):
            theta, key, s1, s2 = carry
            theta, key = trajectory(theta, eps, key)
            return theta, key, s1 + theta, s2 + theta * theta

        zero = jnp.zeros((dim,), jnp.float32)
        theta, _, s1, s2 = lax.fori_loop(0, num_steps, body,
                                         (theta0, key, zero, zero))
        return theta, s1, s2

    return chain


def make_runner(logp: Callable, grad: Callable, dim: int, **settings):
    """Jitted ``run(theta0, eps, keys) -> {"theta", "sum_theta", "sum_sq"}``
    over a leading chain axis of ``theta0`` and ``keys``."""
    chain = jax.jit(jax.vmap(make_chain(logp, grad, dim, **settings),
                             in_axes=(0, None, 0)))

    def run(theta0, eps, keys):
        theta, s1, s2 = chain(theta0, eps, keys)
        return {"theta": theta, "sum_theta": s1, "sum_sq": s2}

    return run
