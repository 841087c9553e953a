"""Jitted wrapper: [B, S, H, Dh] layout in/out (interpreted on CPU)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..platform import interpret_mode
from . import kernel, ref


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True,
                    block_q: int = 128, block_k: int = 128) -> jax.Array:
    """q: [B, S, H, Dh]; k, v: [B, T, Hkv, Dh] -> [B, S, H, Dh]."""
    qt = jnp.swapaxes(q, 1, 2)  # [B, H, S, Dh]
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out = kernel.flash_attention(
        qt, kt, vt, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret_mode(),
    )
    return jnp.swapaxes(out, 1, 2)
