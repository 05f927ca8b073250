"""jit'd wrapper exposing the flash kernel in model layout (b, s, h, d)."""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.flash_attention import flash_attention


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                              "block_k", "interpret"))
def flash_attention_bshd(
    q: jnp.ndarray,          # (b, s, H, d) — model layout
    k: jnp.ndarray,          # (b, s, KV, d)
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out = flash_attention(qt, kt, vt, causal=causal, window=window,
                          block_q=block_q, block_k=block_k, interpret=interpret)
    return jnp.swapaxes(out, 1, 2)
