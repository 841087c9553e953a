"""Jitted wrapper for decode attention ([B, H, Dh] query layout;
interpreted on CPU)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..platform import interpret_mode
from . import kernel, ref


def decode_attention(
    q: jax.Array,  # [B, H, Dh]
    k: jax.Array,  # [B, W, Hkv, Dh]
    v: jax.Array,
    count: jax.Array,  # [B]
    *,
    block_k: int = 256,
) -> jax.Array:
    b, h, dh = q.shape
    hk = k.shape[2]
    g = h // hk
    qg = q.reshape(b, hk, g, dh)
    kt = jnp.swapaxes(k, 1, 2)  # [B, Hkv, W, Dh]
    vt = jnp.swapaxes(v, 1, 2)
    out = kernel.decode_attention(
        qg, kt, vt, count, block_k=block_k, interpret=interpret_mode()
    )
    return out.reshape(b, h, dh)
