"""Blockwise (flash) attention — Pallas TPU kernel for the prefill path.

Grid layout ``(batch*heads, q_blocks, kv_blocks)`` with the KV dimension
innermost: TPU grids execute sequentially minor-to-major, so the f32
running-max / running-sum / accumulator live in VMEM scratch and persist
across the KV sweep of one q block; the output tile is written once, on
the final KV step.  HBM traffic per q block is therefore
``O(S_kv * (bk x d))`` reads + one ``(bq x d)`` write — the flash
property — instead of materializing the ``(S_q x S_kv)`` score matrix.

Masking (causal and/or sliding window) is computed from global index
iotas against the block offsets; fully-masked positions are excluded from
the probability mass explicitly (`p *= allowed`) so a fully-masked KV
block cannot poison the running max.

GQA: the KV block index map folds the query-head index onto its KV group,
so no KV repetition is materialized.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, window: Optional[int],
                  bq: int, bk: int, n_kv: int):
    ki = pl.program_id(2)
    qi = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    q = q_ref[0].astype(jnp.float32) * scale            # (bq, d)
    k = k_ref[0].astype(jnp.float32)                    # (bk, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))   # (bq, bk)

    rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    allowed = jnp.ones((bq, bk), dtype=bool)
    if causal:
        allowed &= cols <= rows
    if window is not None:
        allowed &= cols > rows - window
    s = jnp.where(allowed, s, NEG_INF)

    m_prev = m_scr[...]                                 # (bq, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new) * allowed.astype(jnp.float32)
    corr = jnp.exp(m_prev - m_new)                      # (bq, 1)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    m_scr[...] = m_new
    v = v_ref[0].astype(jnp.float32)                    # (bk, d)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot(p, v)

    @pl.when(ki == n_kv - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def flash_attention(
    q: jnp.ndarray,          # (B, H, Sq, D)
    k: jnp.ndarray,          # (B, KV, Sk, D)
    v: jnp.ndarray,          # (B, KV, Sk, D)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    b, h, sq, d = q.shape
    kv, sk = k.shape[1], k.shape[2]
    g = h // kv
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    if sq % bq or sk % bk:
        raise ValueError(f"seq lens ({sq},{sk}) must divide blocks ({bq},{bk})")
    n_q, n_kv = sq // bq, sk // bk

    qf = q.reshape(b * h, sq, d)
    kf = k.reshape(b * kv, sk, d)
    vf = v.reshape(b * kv, sk, d)

    def kv_index(bh, qi, ki):
        return ((bh // h) * kv + (bh % h) // g, ki, 0)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        bq=bq, bk=bk, n_kv=n_kv)

    out = pl.pallas_call(
        kernel,
        grid=(b * h, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, d), kv_index),
            pl.BlockSpec((1, bk, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),    # running max
            pltpu.VMEM((bq, 1), jnp.float32),    # running sum
            pltpu.VMEM((bq, d), jnp.float32),    # output accumulator
        ],
        interpret=resolve_interpret(interpret),
    )(qf, kf, vf)
    return out.reshape(b, h, sq, d)
