"""Chunked WKV6 recurrence — Pallas TPU kernel.

The RWKV6 time-mix recurrence (per batch x head, head size ``hs``):

    y_t  = r_t . (S_t + (u * k_t) v_t^T)
    S_t+1 = diag(w_t) S_t + k_t v_t^T

is sequential over time, but the working set per step is tiny (an
``hs x hs`` f32 state).  The TPU-native formulation processes the sequence
in VMEM-resident chunks: grid ``(batch*heads, n_chunks)`` with the chunk
dimension innermost, the state matrix living in VMEM scratch across the
chunk sweep, and each grid step streaming one ``(chunk, hs)`` tile of
r/k/v/w from HBM.  HBM traffic is exactly one read of the inputs and one
write of the outputs — the recurrence state never round-trips to HBM
(the pure-jnp ``lax.scan`` version re-materializes the carry per step).

Validated in ``interpret=True`` against :func:`repro.nn.ssm.wkv6_scan`.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels import resolve_interpret


def _wkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, s_out_ref, s_scr,
                 *, chunk: int, hs: int, n_chunks: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_scr[...] = jnp.zeros((hs, hs), jnp.float32)

    r = r_ref[0].astype(jnp.float32)     # (chunk, hs)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    w = w_ref[0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)     # (hs,)

    def body(t, carry):
        s, ybuf = carry
        rt = jax.lax.dynamic_slice_in_dim(r, t, 1, 0)[0]
        kt = jax.lax.dynamic_slice_in_dim(k, t, 1, 0)[0]
        vt = jax.lax.dynamic_slice_in_dim(v, t, 1, 0)[0]
        wt = jax.lax.dynamic_slice_in_dim(w, t, 1, 0)[0]
        kv = kt[:, None] * vt[None, :]                    # (hs, hs)
        y = jnp.einsum("i,ij->j", rt, s + u[:, None] * kv)
        s_new = wt[:, None] * s + kv
        ybuf = jax.lax.dynamic_update_slice_in_dim(ybuf, y[None], t, 0)
        return s_new, ybuf

    s0 = s_scr[...]
    y0 = jnp.zeros((chunk, hs), jnp.float32)
    s_fin, ybuf = jax.lax.fori_loop(0, chunk, body, (s0, y0))
    s_scr[...] = s_fin
    o_ref[0] = ybuf.astype(o_ref.dtype)

    @pl.when(ci == n_chunks - 1)
    def _emit_state():
        s_out_ref[0] = s_fin.astype(s_out_ref.dtype)


def wkv6_pallas(
    r: jnp.ndarray,          # (BH, S, hs) — batch*heads folded
    k: jnp.ndarray,
    v: jnp.ndarray,
    w: jnp.ndarray,          # data-dependent decay in (0,1)
    u: jnp.ndarray,          # (BH, hs) per-head bonus (broadcast over batch)
    *,
    chunk: int = 128,
    interpret: Optional[bool] = None,
):
    """Returns (y (BH, S, hs), final_state (BH, hs, hs))."""
    bh, s, hs = r.shape
    chunk = min(chunk, s)
    if s % chunk:
        raise ValueError(f"seq {s} must divide chunk {chunk}")
    n_chunks = s // chunk

    kernel = functools.partial(_wkv6_kernel, chunk=chunk, hs=hs, n_chunks=n_chunks)
    seq_spec = pl.BlockSpec((1, chunk, hs), lambda bhi, ci: (bhi, ci, 0))
    y, s_fin = pl.pallas_call(
        kernel,
        grid=(bh, n_chunks),
        in_specs=[
            seq_spec, seq_spec, seq_spec, seq_spec,
            pl.BlockSpec((1, hs), lambda bhi, ci: (bhi, 0)),
        ],
        out_specs=(
            seq_spec,
            pl.BlockSpec((1, hs, hs), lambda bhi, ci: (bhi, 0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((bh, s, hs), r.dtype),
            jax.ShapeDtypeStruct((bh, hs, hs), jnp.float32),
        ),
        scratch_shapes=[pltpu.VMEM((hs, hs), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(r, k, v, w, u)
    return y, s_fin
