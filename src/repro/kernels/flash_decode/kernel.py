"""Decode attention (one query token vs a long KV cache), Pallas TPU.

Decode is bandwidth-bound: the whole KV cache streams HBM -> VMEM once per
step while compute is a [G, bk] matvec-like product per group.  The kernel
therefore tiles over ``(B, Hkv, nk)`` — all ``G`` query heads of one KV
group ride along in a single ``[G, Dh]`` tile so each KV byte is read
exactly once per group — and carries the online-softmax running (max, sum,
acc) in VMEM scratch across the kv-block axis.  Per-sequence cache
validity (``count``) arrives via scalar prefetch and masks the tail block.

On a real pod, the ``W`` axis additionally shards over the ``model`` mesh
axis (split-K); partial (m, l, acc) triples then combine with one small
all-gather — the lowering used by ``long_500k``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(count_ref, q_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, bk: int, nk: int, scale: float):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    count = count_ref[b]
    # Skip blocks entirely past the valid region.
    @pl.when(j * bk < count)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # [G, dh]
        k = k_ref[0, 0].astype(jnp.float32)  # [bk, dh]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [G, bk]
        cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(cols < count, s, NEG_INF)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = jnp.broadcast_to(
            l_ref[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True),
            l_ref.shape,
        )
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
            p.astype(jnp.float32), v, preferred_element_type=jnp.float32
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(j == nk - 1)
    def _finish():
        o_ref[0, 0] = (
            acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-30)
        ).astype(o_ref.dtype)


def decode_attention(
    q: jax.Array,  # [B, Hkv, G, Dh]
    k: jax.Array,  # [B, Hkv, W, Dh]
    v: jax.Array,
    count: jax.Array,  # [B] int32
    *,
    block_k: int = 256,
    interpret: bool,
) -> jax.Array:
    b, hk, g, dh = q.shape
    w = k.shape[2]
    bk = min(block_k, w)
    assert w % bk == 0, (w, bk)
    nk = w // bk
    scale = 1.0 / np.sqrt(dh)
    kernel = functools.partial(_decode_kernel, bk=bk, nk=nk, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # count
        grid=(b, hk, nk),
        in_specs=[
            pl.BlockSpec((1, 1, g, dh), lambda b_, h_, j, c: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, bk, dh), lambda b_, h_, j, c: (b_, h_, j, 0)),
            pl.BlockSpec((1, 1, bk, dh), lambda b_, h_, j, c: (b_, h_, j, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, g, dh), lambda b_, h_, j, c: (b_, h_, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((g, 128), jnp.float32),
            pltpu.VMEM((g, 128), jnp.float32),
            pltpu.VMEM((g, dh), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hk, g, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(count.astype(jnp.int32), q, k, v)
