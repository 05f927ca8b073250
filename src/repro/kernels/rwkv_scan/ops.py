"""jit'd wrapper: WKV6 kernel in model layout (b, s, n_h, hs)."""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.rwkv_scan.rwkv_scan import wkv6_pallas


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6_bsnh(r, k, v, w, u, *, chunk: int = 128, interpret: Optional[bool] = None):
    """r,k,v,w: (b, s, n_h, hs); u: (n_h, hs).

    Returns (y (b, s, n_h, hs), state (b, n_h, hs, hs)) — drop-in for
    :func:`repro.nn.ssm.wkv6_scan` with zero initial state.
    """
    b, s, n_h, hs = r.shape

    def fold(x):
        return jnp.swapaxes(x, 1, 2).reshape(b * n_h, s, hs)

    uf = jnp.broadcast_to(u[None], (b, n_h, hs)).reshape(b * n_h, hs)
    y, state = wkv6_pallas(fold(r), fold(k), fold(v), fold(w), uf,
                           chunk=chunk, interpret=interpret)
    y = jnp.swapaxes(y.reshape(b, n_h, s, hs), 1, 2)
    return y, state.reshape(b, n_h, hs, hs)
