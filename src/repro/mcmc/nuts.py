"""The No-U-Turn Sampler as an autobatchable program (paper Section 4).

This is the paper's headline workload: NUTS's standard presentation is a
*recursive* tree-building procedure (Hoffman & Gelman 2014, Algorithm 3)
with data-dependent control flow at every level — "prohibitively difficult
to batch by hand".  Here it is written against the Fig-2 IR exactly as a
user would write it: plain recursion (``build_tree`` calls itself), plain
``if``/``while`` control flow, and per-member primitives.  The autobatching
backends in :mod:`repro.core` then execute thousands of chains in lockstep.

Per the paper's experimental setup, each leaf of the NUTS tree takes
``steps_per_leaf`` (default 4) leapfrog steps, to amortize control overhead;
this does not affect soundness.

The leaf integrator primitive is tagged ``"grad"`` so the runtimes report
gradient-evaluation counts and batch utilization (paper Figs. 5 & 6).
Each leaf execution costs ``steps_per_leaf + 1`` gradient evaluations.

Public entry point: :func:`make_nuts_kernel` — the decorator-first pytree
API.  ``kernel(theta0, eps, key)`` takes per-chain ``theta0``/``key`` and a
``Shared`` scalar step size, and returns the pytree state ``{"theta",
"sum_theta", "sum_sq"}``; one kernel object serves every chain count
(compiled executors are cached per batch size over a shared lowering).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import batching, frontend, ir
from repro.core.batching import Batched, Shared
from repro.core.frontend import spec

from .targets import Target

KEY = spec((2,), jnp.uint32)
F32 = spec((), jnp.float32)
I32 = spec((), jnp.int32)

DELTA_MAX = 1000.0  # divergence threshold (standard)


@dataclass(frozen=True)
class NutsSettings:
    max_tree_depth: int = 10
    num_steps: int = 10  # Markov-chain length (trajectories per chain)
    steps_per_leaf: int = 4  # leapfrog steps per tree leaf (paper: 4)

    @property
    def grads_per_leaf(self) -> int:
        return self.steps_per_leaf + 1


def make_primitives(target: Target, settings: NutsSettings):
    """Per-member JAX functions used as IR primitives."""
    logp = target.logp
    grad = jax.grad(logp)
    spl = settings.steps_per_leaf

    def leapfrog(theta, r, v, eps):
        """``steps_per_leaf`` leapfrog steps with step size ``v * eps``."""
        step = v * eps

        def body(_, carry):
            theta, r, g = carry
            r_half = r + 0.5 * step * g
            theta = theta + step * r_half
            g = grad(theta)
            r = r_half + 0.5 * step * g
            return theta, r, g

        theta, r, _ = jax.lax.fori_loop(0, spl, body, (theta, r, grad(theta)))
        return theta, r

    def joint(theta, r):
        return logp(theta) - 0.5 * jnp.sum(r * r)

    def uturn_ok(tm, rm, tp, rp):
        """1 if the (tm..tp) trajectory has NOT made a U-turn."""
        d = tp - tm
        ok = jnp.logical_and(jnp.dot(d, rm) >= 0.0, jnp.dot(d, rp) >= 0.0)
        return ok.astype(jnp.int32)

    def split3(key):
        ks = jax.random.split(key, 3)
        return ks[0], ks[1], ks[2]

    def split4(key):
        ks = jax.random.split(key, 4)
        return ks[0], ks[1], ks[2], ks[3]

    def momentum(key):
        return jax.random.normal(key, (target.dim,), jnp.float32)

    def slice_log_u(key, joint0):
        # log of the slice variable u ~ Uniform(0, exp(joint0)).
        return joint0 + jnp.log1p(-jax.random.uniform(key))

    def direction(key):
        return jnp.where(jax.random.bernoulli(key), 1.0, -1.0).astype(
            jnp.float32
        )

    return dict(
        leapfrog=leapfrog,
        joint=joint,
        uturn_ok=uturn_ok,
        split3=split3,
        split4=split4,
        momentum=momentum,
        slice_log_u=slice_log_u,
        direction=direction,
    )


def build_nuts_program(
    target: Target, settings: NutsSettings = NutsSettings()
) -> ir.Program:
    """The full multi-trajectory NUTS chain as a Fig-2 IR program.

    Functions:
      * ``build_tree(theta, r, log_u, v, j, eps, key)`` — the recursive
        doubling procedure (Hoffman & Gelman Algorithm 3's BuildTree);
      * ``nuts_step(theta, eps, key)`` — one trajectory (one draw);
      * ``nuts_chain(theta0, eps, key)`` — ``num_steps`` draws, accumulating
        running first/second moments (main function).
    """
    p = make_primitives(target, settings)
    vec = spec((target.dim,), jnp.float32)
    pb = frontend.ProgramBuilder(main="nuts_chain")

    # ------------------------------------------------------------------
    # build_tree — the recursive core
    # ------------------------------------------------------------------
    bt = pb.function(
        "build_tree",
        params=["theta", "r", "log_u", "v", "j", "eps", "key"],
        outputs=["tm", "rm", "tp", "rp", "th1", "n1", "s1", "key_out"],
        param_specs={
            "theta": vec, "r": vec, "log_u": F32, "v": F32,
            "j": I32, "eps": F32, "key": KEY,
        },
        output_specs={
            "tm": vec, "rm": vec, "tp": vec, "rp": vec,
            "th1": vec, "n1": I32, "s1": I32, "key_out": KEY,
        },
    )
    is_leaf = bt.prim(lambda j: j == 0, ["j"], name="is_leaf")
    with bt.if_(is_leaf):
        # Base case: one leaf = steps_per_leaf leapfrog steps (tag: grad).
        bt.prim(
            p["leapfrog"], ["theta", "r", "v", "eps"],
            out=("th_new", "r_new"), n_out=2, name="leapfrog", tag="grad",
        )
        bt.prim(p["joint"], ["th_new", "r_new"], out="jnt", name="joint")
        bt.assign(
            "n1",
            lambda lu, jt: (lu <= jt).astype(jnp.int32),
            ["log_u", "jnt"], name="slice_ind",
        )
        bt.assign(
            "s1",
            lambda lu, jt: (jt > lu - DELTA_MAX).astype(jnp.int32),
            ["log_u", "jnt"], name="not_divergent",
        )
        bt.copy("th_new", out="tm")
        bt.copy("r_new", out="rm")
        bt.copy("th_new", out="tp")
        bt.copy("r_new", out="rp")
        bt.copy("th_new", out="th1")
        bt.copy("key", out="key_out")
        bt.return_()
    # Recursive case: build left half, then (if still going) the right half.
    bt.assign("jm1", lambda j: j - 1, ["j"])
    bt.prim(p["split3"], ["key"], out=("k2", "k3", "key_out"), n_out=3,
            name="split3")
    bt.call(
        "build_tree",
        ["theta", "r", "log_u", "v", "jm1", "eps", "k2"],
        out=("tm", "rm", "tp", "rp", "th1", "n1", "s1", "kd0"), n_out=8,
    )
    going = bt.prim(lambda s: s == 1, ["s1"], name="still_going")
    with bt.if_(going):
        is_neg = bt.prim(lambda v: v < 0.0, ["v"], name="is_neg")
        with bt.if_(is_neg):
            bt.call(
                "build_tree",
                ["tm", "rm", "log_u", "v", "jm1", "eps", "k3"],
                out=("tm", "rm", "d0", "d1", "th2", "n2", "s2", "kd1"),
                n_out=8,
            )
        with bt.orelse():
            bt.call(
                "build_tree",
                ["tp", "rp", "log_u", "v", "jm1", "eps", "k3"],
                out=("d0", "d1", "tp", "rp", "th2", "n2", "s2", "kd1"),
                n_out=8,
            )
        # Accept the right-half proposal with prob n2 / (n1 + n2).
        bt.prim(
            lambda k, n1, n2: jax.random.uniform(k) * (n1 + n2) < n2,
            ["kd1", "n1", "n2"], out="acc", name="subtree_accept",
        )
        bt.assign(
            "th1",
            lambda a, t1, t2: jnp.where(a, t2, t1),
            ["acc", "th1", "th2"], name="select_proposal",
        )
        bt.prim(p["uturn_ok"], ["tm", "rm", "tp", "rp"], out="ut",
                name="uturn_ok")
        bt.assign("s1", lambda s2, ut: s2 * ut, ["s2", "ut"])
        bt.assign("n1", lambda n1, n2: n1 + n2, ["n1", "n2"])
    bt.return_()
    pb.add(bt)

    # ------------------------------------------------------------------
    # nuts_step — one trajectory (the doubling loop)
    # ------------------------------------------------------------------
    st = pb.function(
        "nuts_step",
        params=["theta", "eps", "key"],
        outputs=["theta_out", "key_run"],
        param_specs={"theta": vec, "eps": F32, "key": KEY},
        output_specs={"theta_out": vec, "key_run": KEY},
    )
    st.prim(p["split3"], ["key"], out=("k_mom", "k_slice", "key_run"),
            n_out=3, name="split3")
    st.prim(p["momentum"], ["k_mom"], out="r0", name="momentum")
    st.prim(p["joint"], ["theta", "r0"], out="joint0", name="joint0")
    st.prim(p["slice_log_u"], ["k_slice", "joint0"], out="log_u",
            name="slice_log_u")
    st.copy("theta", out="tm")
    st.copy("r0", out="rm")
    st.copy("theta", out="tp")
    st.copy("r0", out="rp")
    st.copy("theta", out="theta_out")
    st.const(1, jnp.int32, out="n")
    st.const(1, jnp.int32, out="s")
    st.const(0, jnp.int32, out="j")
    with st.while_(
        lambda s, j: jnp.logical_and(s == 1, j < settings.max_tree_depth),
        ["s", "j"],
    ):
        st.prim(p["split4"], ["key_run"],
                out=("k_dir", "k_tree", "k_acc", "key_run"), n_out=4,
                name="split4")
        st.prim(p["direction"], ["k_dir"], out="v", name="direction")
        is_neg = st.prim(lambda v: v < 0.0, ["v"], name="is_neg")
        with st.if_(is_neg):
            st.call(
                "build_tree",
                ["tm", "rm", "log_u", "v", "j", "eps", "k_tree"],
                out=("tm", "rm", "d0", "d1", "th1", "n1", "s1", "kd"),
                n_out=8,
            )
        with st.orelse():
            st.call(
                "build_tree",
                ["tp", "rp", "log_u", "v", "j", "eps", "k_tree"],
                out=("d0", "d1", "tp", "rp", "th1", "n1", "s1", "kd"),
                n_out=8,
            )
        # Metropolis-within-slice: accept with prob min(1, n1/n).
        st.prim(
            lambda k, s1, n1, n: jnp.logical_and(
                s1 == 1, jax.random.uniform(k) * n < n1
            ),
            ["k_acc", "s1", "n1", "n"], out="acc", name="trajectory_accept",
        )
        st.assign(
            "theta_out",
            lambda a, to, t1: jnp.where(a, t1, to),
            ["acc", "theta_out", "th1"], name="select_sample",
        )
        st.prim(p["uturn_ok"], ["tm", "rm", "tp", "rp"], out="ut",
                name="uturn_ok")
        st.assign("s", lambda s1, ut: s1 * ut, ["s1", "ut"])
        st.assign("n", lambda n, n1: n + n1, ["n", "n1"])
        st.assign("j", lambda j: j + 1, ["j"])
    st.return_()
    pb.add(st)

    # ------------------------------------------------------------------
    # nuts_chain — num_steps trajectories with running moments (main)
    # ------------------------------------------------------------------
    ch = pb.function(
        "nuts_chain",
        params=["theta0", "eps", "key"],
        outputs=["theta", "sum_theta", "sum_sq"],
        param_specs={"theta0": vec, "eps": F32, "key": KEY},
        output_specs={"theta": vec, "sum_theta": vec, "sum_sq": vec},
    )
    ch.copy("theta0", out="theta")
    ch.copy("key", out="key_run")
    ch.const(np.zeros(target.dim, np.float32), out="sum_theta")
    ch.const(np.zeros(target.dim, np.float32), out="sum_sq")
    ch.const(0, jnp.int32, out="it")
    with ch.while_(lambda it: it < settings.num_steps, ["it"]):
        ch.call("nuts_step", ["theta", "eps", "key_run"],
                out=("theta", "key_run"), n_out=2)
        ch.assign("sum_theta", lambda s, t: s + t, ["sum_theta", "theta"])
        ch.assign("sum_sq", lambda s, t: s + t * t, ["sum_sq", "theta"])
        ch.assign("it", lambda i: i + 1, ["it"])
    ch.return_()
    pb.add(ch)

    return pb.build()


def make_nuts_kernel(
    target: Target,
    settings: NutsSettings = NutsSettings(),
    *,
    backend: str = "pc",
    batch_size: Optional[int] = None,
    max_steps: int = 1_000_000,
    use_kernel: bool = False,
    schedule: str = "earliest",
    fuse: bool = True,
    mesh=None,
    verify: bool = False,
    compact_every: Optional[int] = None,
    pgo=None,
) -> batching.AutobatchedFunction:
    """The public NUTS entry point, on the decorator-first pytree API.

    Returns a batched callable ``kernel(theta0, eps, key) -> state`` where

    * ``theta0`` is per-chain (``Batched``): ``[chains, dim]`` float32,
    * ``eps`` is the step size shared by every chain (``Shared``): a scalar,
    * ``key`` is per-chain (``Batched``): ``[chains, 2]`` uint32,

    and ``state`` is the pytree ``{"theta": [chains, dim], "sum_theta":
    [chains, dim], "sum_sq": [chains, dim]}`` of final positions and running
    moments.  With ``batch_size=None`` the chain count is inferred from
    ``theta0`` on each call; compiled artifacts are cached per batch size
    (the stack-explicit lowering is shared across all of them).

    ``schedule`` and ``fuse`` are the pc backend's dispatch knobs (see
    :mod:`repro.core.pc_vm` / :mod:`repro.core.fusion`); both are bit-exact,
    so every combination samples identical chains.  ``mesh`` (``None``, a
    device count, or a 1-D ``jax.sharding.Mesh``) shards the chain axis
    across devices — chains are embarrassingly parallel, so the only
    cross-device traffic is the VM's scalar dispatch reductions, and the
    sampled chains are bit-identical to the unsharded run wherever XLA
    compiles the target's gradient the same at the per-device batch size
    (on TPU, not for a contraction it tiles by batch size, such as
    logistic regression over many data points).
    ``compact_every=k`` turns on occupancy-aware lane compaction every
    ``k`` VM dispatches — tree-depth divergence between chains is exactly
    the fragmentation compaction recovers; chains stay bit-identical.
    ``pgo=`` re-lowers through the profile-guided pipeline from a
    :class:`repro.obs.blockprof.BlockProfile` (or a saved profile path)
    collected on a traced run of the same kernel — still bit-exact, fewer
    dispatches (see ``tools/pgo.py``).
    """
    program = build_nuts_program(target, settings)
    vec = spec((target.dim,), jnp.float32)
    return batching.autobatch(
        program,
        in_specs=(Batched(vec), Shared(F32), Batched(KEY)),
        out_spec={"theta": "theta", "sum_theta": "sum_theta", "sum_sq": "sum_sq"},
        backend=backend,
        batch_size=batch_size,
        max_depth=recommended_max_depth(settings),
        max_steps=max_steps,
        use_kernel=use_kernel,
        schedule=schedule,
        fuse=fuse,
        mesh=mesh,
        verify=verify,
        compact_every=compact_every,
        pgo=pgo,
    )


def initial_state(
    target: Target, batch_size: int, *, eps: float, seed: int = 0
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Positional ``(theta0, eps, key)`` arguments for the NUTS kernel.

    ``eps`` is a scalar (a ``Shared`` argument of the kernel); ``theta0``
    and ``key`` carry the leading chain axis.
    """
    rng = np.random.default_rng(seed)
    theta0 = 0.1 * rng.normal(size=(batch_size, target.dim)).astype(np.float32)
    keys = jax.vmap(jax.random.PRNGKey)(
        jnp.arange(seed * 100_000, seed * 100_000 + batch_size)
    )
    return jnp.asarray(theta0), jnp.float32(eps), keys


def recommended_max_depth(settings: NutsSettings) -> int:
    """Stack slots needed: chain -> step -> tree_depth nested build_trees,
    plus one slot for the exit sentinel and one of headroom."""
    return settings.max_tree_depth + 4
