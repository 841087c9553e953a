"""Causal GQA flash attention, Pallas TPU.

Tiling: grid ``(B, H, nq, nk)`` with the kv axis innermost ("arbitrary"
semantics — it carries the online-softmax recurrence through VMEM
scratch).  Each step loads a ``[bq, Dh]`` query tile and a ``[bk, Dh]``
key/value tile into VMEM, runs the ``[bq, bk]`` logit matmul on the MXU in
f32, and maintains running (max, sum, acc) per query row.  GQA is handled
structurally: the key/value ``BlockSpec`` index_map divides the query-head
index by the group size, so KV tiles are fetched once per group from HBM.

Causality is exploited two ways: fully-masked tiles are skipped
(``pl.when`` on the tile coordinates), and the diagonal tile applies the
triangular mask only where needed.  Block sizes default to 128 x 128 —
MXU-aligned (multiples of 128 in both contraction and lane dims).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  bq: int, bk: int, nk: int, scale: float, causal: bool):
    iq = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Skip tiles strictly above the diagonal (no valid positions).
    live = jnp.logical_or(not causal, j * bk < (iq + 1) * bq)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # [bq, dh]
        k = k_ref[0, 0].astype(jnp.float32)  # [bk, dh]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [bq, bk]
        if causal:
            rows = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        m_prev = m_ref[:, :1]  # [bq, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)  # [bq, bk]
        alpha = jnp.exp(m_prev - m_new)  # [bq, 1]
        l_new = l_ref[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == nk - 1)
    def _finish():
        l = l_ref[:, :1]
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l, 1e-30)).astype(
            o_ref.dtype
        )


def flash_attention(
    q: jax.Array,  # [B, H, S, Dh]
    k: jax.Array,  # [B, Hkv, T, Dh]
    v: jax.Array,
    *,
    causal: bool = True,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool,
) -> jax.Array:
    b, h, s, dh = q.shape
    hk, t = k.shape[1], k.shape[2]
    g = h // hk
    bq = min(block_q, s)
    bk = min(block_k, t)
    assert s % bq == 0 and t % bk == 0, (s, t, bq, bk)
    nq, nk = s // bq, t // bk
    scale = 1.0 / np.sqrt(dh)

    kernel = functools.partial(
        _flash_kernel, bq=bq, bk=bk, nk=nk, scale=scale, causal=causal
    )
    return pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, dh), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec(
                (1, 1, bk, dh), lambda b_, h_, i, j: (b_, h_ // g, j, 0)
            ),
            pl.BlockSpec(
                (1, 1, bk, dh), lambda b_, h_, i, j: (b_, h_ // g, j, 0)
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, bq, dh), lambda b_, h_, i, j: (b_, h_, i, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, s, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),  # running max
            pltpu.VMEM((bq, 128), jnp.float32),  # running sum
            pltpu.VMEM((bq, dh), jnp.float32),  # accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
