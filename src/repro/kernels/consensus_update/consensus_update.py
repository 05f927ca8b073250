"""Fused consensus-SGD update kernel (paper eq. 5) — Pallas TPU.

Per optimization step, every agent computes

    x' = sum_s w_s * neighbor_s  -  alpha * g          (CDSGD)
    v' = mu v - alpha g ; x' = sum_s w_s * neighbor_s + v'   (CDMSGD)

over the *entire* parameter vector.  Unfused, that is >= deg+2 separate
HBM sweeps (one per neighbor buffer, one for the gradient, one write);
on TPU the op is purely memory-bound, so fusing mixing + momentum + update
into a single pass halves-to-thirds the HBM traffic of the optimizer step.

Layout: parameters are flattened to 2-D ``(rows, 128)`` tiles (lane dim
128-aligned for the VPU); neighbors are stacked ``(S, rows, 128)``.  The
grid walks row-blocks; each grid step loads one ``(block_rows, 128)`` tile
of self/neighbors/grad into VMEM, accumulates in f32, and writes the
updated tile.  ``S`` (the neighbor-stencil size = topology degree + self)
is static — for a ring it is 3, for a 2-D torus 5.

Quantized neighbor exchange
---------------------------
The neighbor stack may arrive **quantized** (int8 or fp8-e4m3, one f32
scale per 128-lane row: ``scales (S, rows, 1)``) — the form produced by
:func:`sr_quantize_2d` before the circulant ``ppermute`` so each shift
moves ~4x fewer bytes.  Passing ``scales`` (plus the native-precision
``self_buf``, which never crossed the wire and therefore pays no
quantization noise — ``weights[0]`` applies to it, ``weights[1:]`` to the
wire payloads) to any ``*_update_2d`` wrapper dequantizes **in-register**
during the mixing accumulation (one extra VPU multiply per element); the
dequantized neighbor tiles are never materialized in HBM.

Quantization uses stochastic rounding — unbiased, so consensus averaging
stays centered — via ``pltpu.prng_random_bits`` on TPU and a
``jax.random``-based fallback under interpret mode (the TPU PRNG
primitives have no CPU lowering).

Sparse (top-k wire) operand form
--------------------------------
The neighbor stack may also arrive **top-k compressed** — the
:class:`repro.core.consensus.TopKWire` compact fields (int8 ``values
(S, k_rows, 128)``, int32 flat ``indices (S, k_rows, 128)``, f32 ``scales
(S, k_rows, 1)``) — consumed directly by the ``*_update_sparse_2d`` entry
points: the kernel scatter-accumulates ``w[s+1] * scale * dequant(value)``
into the self-separated f32 accumulator, so the neighbor mix reads
``k_rows * 128`` elements per neighbor instead of ``rows * 128`` and the
dense decompressed buffer is never materialized in HBM.  The compact
operands stay resident across the row-block grid (constant index_map);
each grid step masks the flat indices into its own block's element range
``[row0 * 128, (row0 + block_rows) * 128)`` with ``row0`` from
``pl.program_id(0)`` (the row-block index also under the stacked mode's
vmap over agents, which prepends a vmapped grid dim that program_id
skips).  The in-kernel scatter
is a value-level ``.at[].add`` on the flattened VMEM tile: exact under
interpret mode, and refused on a TPU (Mosaic has no scatter-add lowering),
where the entry points raise instead of falling back.  The dense
gather-dequant path
(:func:`repro.kernels.consensus_update.topk.topk_decompress_2d` + the
dense kernels) stays exported as the reference oracle; the two paths
agree bit-for-bit at f32 accumulation (tested).

In-place updates
----------------
Every fused kernel threads ``input_output_aliases``: the gradient operand
donates its buffer to the updated params and each optimizer-state operand
(momentum / Adam moments) donates to its successor, so the whole update
allocates no extra HBM output copy per model/slot (``alias=False`` opts
out, e.g. when a caller reuses the gradient afterwards).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

LANE = 128
DEFAULT_BLOCK_ROWS = 256

_QMAX = {"int8": 127.0, "fp8": 448.0}          # fp8 = float8_e4m3fn
_QDTYPE = {"int8": jnp.int8, "fp8": jnp.float8_e4m3fn}


# --------------------------------------------------------------------------
# quantize stage (runs before the ppermute exchange)
# --------------------------------------------------------------------------


# decorrelates the PRNG streams of adjacent row blocks: block i seeds with
# ``seed + STRIDE * i``.  ``pl.program_id(0)`` is the row-block index also
# when the pallas_call is vmapped over agents (the batching rule prepends the
# batch axis to the grid as a vmapped dim, which program_id skips).
_SEED_BLOCK_STRIDE = 15485863


def _smem_scalars(x, dtype=jnp.float32):
    """A few scalars as one ``(1, n)`` SMEM operand: ``(BlockSpec, array)``.

    Scalars belong in SMEM, and the 2-D shape keeps the block's last two
    dims equal to the array's once vmap prepends the agent axis — Mosaic
    refuses a rank-1 block that is neither the whole array nor a multiple
    of 128, and a squeezed agent dim over an ``(A, n)`` array.
    """
    a = jnp.asarray(x, dtype).reshape(1, -1)
    return (pl.BlockSpec(a.shape, lambda i: (0, 0),
                         memory_space=pltpu.SMEM), a)


def _quantize_math(xf, u, qmax: float, qdtype):
    """Shared per-row scale + rounding math of both sr_quantize_2d paths.

    ``u`` is the uniform-[0,1) stochastic-rounding draw, or None for
    deterministic nearest rounding (fp8).  One definition keeps the TPU
    kernel and the CPU-interpret fallback from drifting apart.
    """
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    scale = jnp.where(amax > 0, amax / qmax, 1.0)
    scaled = xf / scale
    if u is not None:
        scaled = jnp.clip(jnp.floor(scaled + u), -qmax, qmax)
    return scaled.astype(qdtype), scale


def _sr_quantize_kernel(seed_ref, x_ref, q_ref, scale_ref, *, qmax: float,
                        stochastic: bool):
    """Per-row (128-lane block) scaled quantization with stochastic rounding."""
    u = None
    if stochastic:
        pltpu.prng_seed(seed_ref[0, 0] + _SEED_BLOCK_STRIDE * pl.program_id(0))
        bits = pltpu.prng_random_bits(x_ref.shape)
        # top 24 bits (a logical shift of the int32 draw): exactly
        # representable in f32, so u stays strictly < 1 (a raw 2^-32 scaling
        # rounds the largest draws up to u == 1.0, which would bias
        # floor(x + u) upward by a full quantization step)
        u = jax.lax.shift_right_logical(bits, 8).astype(jnp.float32) \
            * (1.0 / 16777216.0)
    q, scale = _quantize_math(x_ref[...].astype(jnp.float32), u, qmax,
                              q_ref.dtype)
    q_ref[...] = q
    scale_ref[...] = scale


def sr_quantize_2d(
    x: jnp.ndarray,               # (rows, 128) — one packed flat bucket
    seed,                         # int32 scalar (traced ok); per-step seed
    *,
    exchange: str = "int8",       # "int8" (stochastic) | "fp8" (nearest)
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: Optional[bool] = None,
) -> tuple:
    """Quantize a flat bucket for the wire: ``(q, scales)``.

    ``q`` is ``(rows, 128)`` int8 / float8_e4m3fn, ``scales`` is
    ``(rows, 1)`` f32 — one scale per 128-element row block, so a transfer
    costs ``rows * (128 + 4)`` bytes instead of ``rows * 512`` (f32).

    int8 uses stochastic rounding (unbiased: ``E[q * scale] = x``); fp8
    e4m3 uses nearest rounding (its 3-bit mantissa makes SR needless for
    consensus averaging).  On CPU/interpret the TPU PRNG primitives do not
    lower, so the stochastic path draws its uniforms from ``jax.random``
    with the same per-``seed`` determinism.
    """
    rows, lane = x.shape
    assert lane == LANE, x.shape
    qmax = _QMAX[exchange]
    qdtype = _QDTYPE[exchange]
    stochastic = exchange == "int8"
    if resolve_interpret(interpret):
        u = None
        if stochastic:
            key = jax.random.PRNGKey(jnp.asarray(seed, jnp.int32))
            u = jax.random.uniform(key, x.shape, jnp.float32)
        return _quantize_math(x.astype(jnp.float32), u, qmax, qdtype)
    block_rows = min(block_rows, rows)
    n_blocks = pl.cdiv(rows, block_rows)
    kernel = functools.partial(_sr_quantize_kernel, qmax=qmax,
                               stochastic=stochastic)
    seed_spec, seed_arg = _smem_scalars(seed, jnp.int32)
    return pl.pallas_call(
        kernel,
        grid=(n_blocks,),
        in_specs=[
            seed_spec,                                          # step seed
            pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
        ],
        out_specs=(
            pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((rows, lane), qdtype),
            jax.ShapeDtypeStruct((rows, 1), jnp.float32),
        ),
        interpret=False,
    )(seed_arg, x)


def sr_dequantize_2d(q: jnp.ndarray, scales: jnp.ndarray,
                     dtype=jnp.float32) -> jnp.ndarray:
    """Reference inverse of :func:`sr_quantize_2d` (tests / oracle only —
    the fused kernels dequantize in-register and never materialize this)."""
    return (q.astype(jnp.float32) * scales).astype(dtype)


# --------------------------------------------------------------------------
# fused update kernels
# --------------------------------------------------------------------------


def _mix_stencil(w_ref, nbrs_ref, scales_ref, self_ref, n_stencil: int, shape):
    """f32 mixing accumulation.

    Unquantized (``scales_ref is None``): ``neighbors`` includes self and
    ``weights`` is the full ``(S,)`` stencil row.  Quantized: the self
    buffer stays in native precision (it never crosses the wire) at
    ``weights[0]``; ``neighbors`` holds the ``n_stencil`` int8/fp8 wire
    payloads which are dequantized in-register with their per-row scales
    at ``weights[1:]``.
    """
    if scales_ref is None:
        acc = jnp.zeros(shape, jnp.float32)
        for s in range(n_stencil):
            acc += w_ref[0, s] * nbrs_ref[s].astype(jnp.float32)
        return acc
    acc = w_ref[0, 0] * self_ref[...].astype(jnp.float32)
    for s in range(n_stencil):
        acc += w_ref[0, s + 1] * (nbrs_ref[s].astype(jnp.float32) * scales_ref[s])
    return acc


def _sparse_stencil(w_ref, vals_ref, idx_ref, sc_ref, self_ref,
                    n_stencil: int, shape):
    """f32 mixing accumulation over top-k compact neighbor payloads.

    The self tile stays dense at ``weights[0]`` exactly like the quantized
    form; each neighbor contributes ``w[s+1] * scale * dequant(value)``
    scatter-accumulated at its flat dense indices.  This grid step owns the
    dense rows from ``program_id(0) * block_rows`` (the row-block index also
    under the stacked mode's vmap — see the quantize-seed comment above):
    indices outside the block's element range are masked to contribute 0.0
    at position 0, so a compact element lands in exactly one grid step.
    Per element the accumulation order matches the dense oracle
    (stencil-major, f32), so the two forms agree bit-for-bit.
    """
    block_elems = shape[0] * shape[1]
    acc = (w_ref[0, 0] * self_ref[...].astype(jnp.float32)).reshape(block_elems)
    base = pl.program_id(0) * block_elems
    for s in range(n_stencil):
        deq = vals_ref[s].astype(jnp.float32) * sc_ref[s]   # (k_rows, 128)
        li = idx_ref[s].reshape(-1) - base
        ok = (li >= 0) & (li < block_elems)
        contrib = jnp.where(ok, w_ref[0, s + 1] * deq.reshape(-1), 0.0)
        acc = acc.at[jnp.where(ok, li, 0)].add(contrib)
    return acc.reshape(shape)


def _cdsgd_body(w_ref, alpha_ref, nbrs_ref, scales_ref, self_ref, grad_ref,
                out_ref, *, n_stencil: int):
    acc = _mix_stencil(w_ref, nbrs_ref, scales_ref, self_ref, n_stencil,
                       out_ref.shape)
    acc -= alpha_ref[0, 0] * grad_ref[...].astype(jnp.float32)
    out_ref[...] = acc.astype(out_ref.dtype)


def _cdsgd_kernel(w, a, nbrs, grad, out, *, n_stencil):
    _cdsgd_body(w, a, nbrs, None, None, grad, out, n_stencil=n_stencil)


def _cdsgd_kernel_q(w, a, slf, nbrs, scales, grad, out, *, n_stencil):
    _cdsgd_body(w, a, nbrs, scales, slf, grad, out, n_stencil=n_stencil)


def _cdmsgd_body(w_ref, alpha_ref, mu_ref, nbrs_ref, scales_ref, self_ref,
                 grad_ref, mom_ref, out_ref, new_mom_ref, *, n_stencil: int):
    v = mu_ref[0, 0] * mom_ref[...].astype(jnp.float32) \
        - alpha_ref[0, 0] * grad_ref[...].astype(jnp.float32)
    acc = _mix_stencil(w_ref, nbrs_ref, scales_ref, self_ref, n_stencil,
                       out_ref.shape)
    out_ref[...] = (acc + v).astype(out_ref.dtype)
    new_mom_ref[...] = v.astype(new_mom_ref.dtype)


def _cdmsgd_kernel(w, a, m, nbrs, grad, mom, out, nmom, *, n_stencil):
    _cdmsgd_body(w, a, m, nbrs, None, None, grad, mom, out, nmom,
                 n_stencil=n_stencil)


def _cdmsgd_kernel_q(w, a, m, slf, nbrs, scales, grad, mom, out, nmom,
                     *, n_stencil):
    _cdmsgd_body(w, a, m, nbrs, scales, slf, grad, mom, out, nmom,
                 n_stencil=n_stencil)


def _cdmsgd_kernel_qm(w, a, m, slf, nbrs, scales, vnbrs, vscales, grad, mom,
                      out, nmom, *, n_stencil):
    """Mixed-momentum CDMSGD: ``v' = mu (Pi v) - a g ; x' = Pi x + v'``.

    The momentum buffer rode the wire next to the params, so both mixing
    sums share the same self-separated weights; the local momentum operand
    ``mom`` is the momentum SELF tile (fresh, full precision — it never
    crossed the wire), mixed at ``weights[0]`` exactly like the params.
    """
    vmix = _mix_stencil(w, vnbrs, vscales, mom, n_stencil, out.shape)
    v = m[0, 0] * vmix - a[0, 0] * grad[...].astype(jnp.float32)
    acc = _mix_stencil(w, nbrs, scales, slf, n_stencil, out.shape)
    out[...] = (acc + v).astype(out.dtype)
    nmom[...] = v.astype(nmom.dtype)


def _cdmsgd_nesterov_body(w_ref, alpha_ref, mu_ref, nbrs_ref, scales_ref,
                          self_ref, grad_ref, mom_ref, out_ref, new_mom_ref,
                          look_ref, *, n_stencil: int):
    """CDMSGD + the *next* step's Nesterov lookahead point in the same sweep.

    ``look = x' + mu v'`` is where Algorithm 3 evaluates the next gradient;
    emitting it here saves the separate ``tree_axpy`` HBM pass the unfused
    path pays before every backward.
    """
    mu = mu_ref[0, 0]
    v = mu * mom_ref[...].astype(jnp.float32) \
        - alpha_ref[0, 0] * grad_ref[...].astype(jnp.float32)
    acc = _mix_stencil(w_ref, nbrs_ref, scales_ref, self_ref, n_stencil,
                       out_ref.shape)
    x = acc + v
    out_ref[...] = x.astype(out_ref.dtype)
    new_mom_ref[...] = v.astype(new_mom_ref.dtype)
    look_ref[...] = (x + mu * v).astype(look_ref.dtype)


def _cdmsgd_nesterov_kernel(w, a, m, nbrs, grad, mom, out, nmom, look,
                            *, n_stencil):
    _cdmsgd_nesterov_body(w, a, m, nbrs, None, None, grad, mom, out, nmom,
                          look, n_stencil=n_stencil)


def _cdmsgd_nesterov_kernel_q(w, a, m, slf, nbrs, scales, grad, mom, out,
                              nmom, look, *, n_stencil):
    _cdmsgd_nesterov_body(w, a, m, nbrs, scales, slf, grad, mom, out, nmom,
                          look, n_stencil=n_stencil)


def _cdmsgd_nesterov_kernel_qm(w, a, m, slf, nbrs, scales, vnbrs, vscales,
                               grad, mom, out, nmom, look, *, n_stencil):
    """Mixed-momentum Nesterov: the momentum mix feeds both the update and
    the emitted lookahead ``x' + mu v'`` in the same sweep."""
    mu = m[0, 0]
    vmix = _mix_stencil(w, vnbrs, vscales, mom, n_stencil, out.shape)
    v = mu * vmix - a[0, 0] * grad[...].astype(jnp.float32)
    acc = _mix_stencil(w, nbrs, scales, slf, n_stencil, out.shape)
    x = acc + v
    out[...] = x.astype(out.dtype)
    nmom[...] = v.astype(nmom.dtype)
    look[...] = (x + mu * v).astype(look.dtype)


def _cdadam_body(w_ref, scal_ref, nbrs_ref, scales_ref, self_ref, grad_ref,
                 m_ref, v_ref, out_ref, new_m_ref, new_v_ref,
                 *, n_stencil: int):
    """Consensus mixing + local Adam moments, one f32-accumulated pass.

    ``scal_ref`` packs [alpha, b1, b2, eps, bc1, bc2] — the bias corrections
    ``bc = 1 - beta^t`` depend on the (traced) step and are computed outside.
    """
    alpha, b1, b2, eps, bc1, bc2 = (scal_ref[0, i] for i in range(6))
    g = grad_ref[...].astype(jnp.float32)
    m = b1 * m_ref[...].astype(jnp.float32) + (1.0 - b1) * g
    v = b2 * v_ref[...].astype(jnp.float32) + (1.0 - b2) * g * g
    acc = _mix_stencil(w_ref, nbrs_ref, scales_ref, self_ref, n_stencil,
                       out_ref.shape)
    step_dir = (m / bc1) / (jnp.sqrt(v / bc2) + eps)
    out_ref[...] = (acc - alpha * step_dir).astype(out_ref.dtype)
    new_m_ref[...] = m.astype(new_m_ref.dtype)
    new_v_ref[...] = v.astype(new_v_ref.dtype)


def _cdadam_kernel(w, sc, nbrs, grad, m, v, out, nm, nv, *, n_stencil):
    _cdadam_body(w, sc, nbrs, None, None, grad, m, v, out, nm, nv,
                 n_stencil=n_stencil)


def _cdadam_kernel_q(w, sc, slf, nbrs, scales, grad, m, v, out, nm, nv,
                     *, n_stencil):
    _cdadam_body(w, sc, nbrs, scales, slf, grad, m, v, out, nm, nv,
                 n_stencil=n_stencil)


def _cdadam_kernel_qm(w, scal, slf, nbrs, scales, mnbrs, mscales, grad, m, v,
                      out, nm, nv, *, n_stencil):
    """Mixed-momentum CDAdam: ``m' = b1 (Pi m) + (1-b1) g``; the second
    moment stays local (a positive scale, not a direction)."""
    alpha, b1, b2, eps, bc1, bc2 = (scal[0, i] for i in range(6))
    g = grad[...].astype(jnp.float32)
    mmix = _mix_stencil(w, mnbrs, mscales, m, n_stencil, out.shape)
    new_m = b1 * mmix + (1.0 - b1) * g
    new_v = b2 * v[...].astype(jnp.float32) + (1.0 - b2) * g * g
    acc = _mix_stencil(w, nbrs, scales, slf, n_stencil, out.shape)
    step_dir = (new_m / bc1) / (jnp.sqrt(new_v / bc2) + eps)
    out[...] = (acc - alpha * step_dir).astype(out.dtype)
    nm[...] = new_m.astype(nm.dtype)
    nv[...] = new_v.astype(nv.dtype)


def _cdsgd_kernel_s(w, a, slf, vals, idx, sc, grad, out, *, n_stencil):
    acc = _sparse_stencil(w, vals, idx, sc, slf, n_stencil, out.shape)
    acc -= a[0, 0] * grad[...].astype(jnp.float32)
    out[...] = acc.astype(out.dtype)


def _cdmsgd_kernel_s(w, a, m, slf, vals, idx, sc, grad, mom, out, nmom,
                     *, n_stencil):
    v = m[0, 0] * mom[...].astype(jnp.float32) \
        - a[0, 0] * grad[...].astype(jnp.float32)
    acc = _sparse_stencil(w, vals, idx, sc, slf, n_stencil, out.shape)
    out[...] = (acc + v).astype(out.dtype)
    nmom[...] = v.astype(nmom.dtype)


def _cdmsgd_nesterov_kernel_s(w, a, m, slf, vals, idx, sc, grad, mom,
                              out, nmom, look, *, n_stencil):
    mu = m[0, 0]
    v = mu * mom[...].astype(jnp.float32) \
        - a[0, 0] * grad[...].astype(jnp.float32)
    acc = _sparse_stencil(w, vals, idx, sc, slf, n_stencil, out.shape)
    x = acc + v
    out[...] = x.astype(out.dtype)
    nmom[...] = v.astype(nmom.dtype)
    look[...] = (x + mu * v).astype(look.dtype)


def _cdadam_kernel_s(w, scal, slf, vals, idx, sc, grad, m, v, out, nm,
                     nv, *, n_stencil):
    alpha, b1, b2, eps, bc1, bc2 = (scal[0, i] for i in range(6))
    g = grad[...].astype(jnp.float32)
    new_m = b1 * m[...].astype(jnp.float32) + (1.0 - b1) * g
    new_v = b2 * v[...].astype(jnp.float32) + (1.0 - b2) * g * g
    acc = _sparse_stencil(w, vals, idx, sc, slf, n_stencil, out.shape)
    step_dir = (new_m / bc1) / (jnp.sqrt(new_v / bc2) + eps)
    out[...] = (acc - alpha * step_dir).astype(out.dtype)
    nm[...] = new_m.astype(nm.dtype)
    nv[...] = new_v.astype(nv.dtype)


def _scalar_operands(*groups):
    """``(specs, args)`` of the kernels' leading SMEM scalar operands."""
    specs, args = zip(*(_smem_scalars(g) for g in groups))
    return list(specs), list(args)


def _grid_and_specs(rows: int, block_rows: int, n_stencil: int):
    grid = (pl.cdiv(rows, block_rows),)
    nbr_spec = pl.BlockSpec((n_stencil, block_rows, LANE), lambda i: (0, i, 0))
    scale_spec = pl.BlockSpec((n_stencil, block_rows, 1), lambda i: (0, i, 0))
    mat_spec = pl.BlockSpec((block_rows, LANE), lambda i: (i, 0))
    return grid, nbr_spec, scale_spec, mat_spec


def _aliases(enabled: bool, pairs):
    """input_output_aliases dict; ``pairs`` is ((input_idx, output_idx), ...)."""
    return dict(pairs) if enabled else {}


def _mix_operands(quantized, s, nbr_spec, scale_spec, mat_spec,
                  neighbors, scales, self_buf,
                  mom_neighbors=None, mom_scales=None):
    """Mixing operand group: ``[self,] neighbors [, scales] [, momentum]``.

    Quantized form: ``neighbors (S, rows, 128)`` int8/fp8 are the wire
    payloads only; the native-precision ``self_buf`` rides separately at
    ``weights[0]`` (it never crossed the wire, so it is never quantized).
    Unquantized form: ``neighbors`` includes the self tile, no extras.
    Mixed-momentum form (``mom_neighbors`` given — always the wire-operand
    form, since the staged engine carries unit scales even for f32 wires):
    the momentum payload's ``(S, rows, 128)`` stack + scales follow the
    params'; the momentum SELF tile is the kernels' existing ``momentum``
    operand, so it adds no operand here.  Returns ``(in_specs, args,
    n_weights)``.
    """
    if mom_neighbors is not None:
        assert quantized and self_buf is not None and scales.shape[0] == s
        assert mom_neighbors.shape == neighbors.shape
        return ([mat_spec, nbr_spec, scale_spec, nbr_spec, scale_spec],
                [self_buf, neighbors, scales, mom_neighbors, mom_scales],
                s + 1)
    if not quantized:
        return [nbr_spec], [neighbors], s
    assert self_buf is not None and scales.shape[0] == s
    return ([mat_spec, nbr_spec, scale_spec],
            [self_buf, neighbors, scales], s + 1)


def _sparse_operands(values, indices, scales, self_buf, grad,
                     block_rows: int, interpret: Optional[bool]):
    """Shared setup of the ``*_update_sparse_2d`` entry points.

    Validates the compact-field shapes, builds the grid over the DENSE row
    blocks (the outputs/self/grad are dense — only the neighbor operands
    shrink), and returns ``(grid, mat_spec, sparse_specs, sparse_args,
    s)``: the compact stacks get whole-array BlockSpecs (constant
    index_map — they stay resident across grid steps); each step finds the
    dense element range it owns from ``pl.program_id(0)``.
    """
    if not resolve_interpret(interpret):
        raise NotImplementedError(
            "the sparse top-k update scatter-adds inside the kernel, and "
            "Mosaic has no TPU lowering for scatter-add; run the top-k "
            "compressor with sparse_update=False (the dense "
            "decompress-then-update kernels) on a TPU")
    s, k_rows, lane = values.shape
    assert lane == LANE, values.shape
    assert indices.shape == (s, k_rows, LANE), (indices.shape, values.shape)
    assert scales.shape == (s, k_rows, 1), (scales.shape, values.shape)
    assert self_buf is not None, "sparse operand form needs the self buffer"
    rows, lane2 = self_buf.shape
    assert lane2 == LANE and grad.shape == (rows, LANE)
    assert k_rows <= rows, (k_rows, rows)
    block_rows = min(block_rows, rows)
    grid = (pl.cdiv(rows, block_rows),)
    mat_spec = pl.BlockSpec((block_rows, LANE), lambda i: (i, 0))
    sparse_specs = [
        mat_spec,                                              # self tile
        pl.BlockSpec((s, k_rows, LANE), lambda i: (0, 0, 0)),  # values
        pl.BlockSpec((s, k_rows, LANE), lambda i: (0, 0, 0)),  # indices
        pl.BlockSpec((s, k_rows, 1), lambda i: (0, 0, 0)),     # scales
    ]
    sparse_args = [self_buf, values, indices.astype(jnp.int32), scales]
    return grid, mat_spec, sparse_specs, sparse_args, s


def cdsgd_update_sparse_2d(
    values: jnp.ndarray,          # (S, k_rows, 128) int8 compact values
    indices: jnp.ndarray,         # (S, k_rows, 128) int32 flat dense indices
    scales: jnp.ndarray,          # (S, k_rows, 1) f32 per-compact-row scales
    weights: jnp.ndarray,         # (S+1,) f32 self-separated weights
    grad: jnp.ndarray,            # (rows, 128) — donated to out
    alpha,
    *,
    self_buf: jnp.ndarray,        # (rows, 128) native self tile
    block_rows: int = DEFAULT_BLOCK_ROWS,
    alias: bool = True,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """CDSGD update consuming the top-k wire directly (see module docs)."""
    grid, mat_spec, sp_specs, sp_args, s = _sparse_operands(
        values, indices, scales, self_buf, grad, block_rows, interpret)
    assert weights.shape == (s + 1,), (weights.shape, s)
    kernel = functools.partial(_cdsgd_kernel_s, n_stencil=s)
    sc_specs, sc_args = _scalar_operands(weights, alpha)
    in_specs = [
        *sc_specs,                         # weights, alpha
        *sp_specs,
        mat_spec,                                  # grad
    ]
    args = [*sc_args, *sp_args, grad]
    grad_idx = len(args) - 1
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=mat_spec,
        out_shape=jax.ShapeDtypeStruct(grad.shape, grad.dtype),
        input_output_aliases=_aliases(alias, ((grad_idx, 0),)),
        interpret=resolve_interpret(interpret),
    )(*args)


def cdmsgd_update_sparse_2d(
    values: jnp.ndarray,
    indices: jnp.ndarray,
    scales: jnp.ndarray,
    weights: jnp.ndarray,         # (S+1,)
    grad: jnp.ndarray,            # donated to params out
    momentum: jnp.ndarray,        # donated to new momentum
    alpha,
    mu,
    *,
    self_buf: jnp.ndarray,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    alias: bool = True,
    interpret: Optional[bool] = None,
):
    """CDMSGD update on the sparse operand form (local momentum only — the
    top-k programs exclude ``momentum_mixing`` at config time)."""
    grid, mat_spec, sp_specs, sp_args, s = _sparse_operands(
        values, indices, scales, self_buf, grad, block_rows, interpret)
    assert weights.shape == (s + 1,), (weights.shape, s)
    kernel = functools.partial(_cdmsgd_kernel_s, n_stencil=s)
    sc_specs, sc_args = _scalar_operands(weights, alpha, mu)
    in_specs = [
        *sc_specs,                         # weights, alpha, mu
        *sp_specs,
        mat_spec, mat_spec,                        # grad, momentum
    ]
    args = [*sc_args, *sp_args, grad, momentum]
    g_idx = len(args) - 2
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=(mat_spec, mat_spec),
        out_shape=(
            jax.ShapeDtypeStruct(grad.shape, grad.dtype),
            jax.ShapeDtypeStruct(momentum.shape, momentum.dtype),
        ),
        input_output_aliases=_aliases(alias, ((g_idx, 0), (g_idx + 1, 1))),
        interpret=resolve_interpret(interpret),
    )(*args)


def cdmsgd_nesterov_update_sparse_2d(
    values: jnp.ndarray,
    indices: jnp.ndarray,
    scales: jnp.ndarray,
    weights: jnp.ndarray,
    grad: jnp.ndarray,
    momentum: jnp.ndarray,
    alpha,
    mu,
    *,
    self_buf: jnp.ndarray,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    alias: bool = True,
    interpret: Optional[bool] = None,
):
    """Returns ``(x', v', x' + mu v')`` like the dense Nesterov form, with
    the neighbor mix on the sparse operands."""
    grid, mat_spec, sp_specs, sp_args, s = _sparse_operands(
        values, indices, scales, self_buf, grad, block_rows, interpret)
    assert weights.shape == (s + 1,), (weights.shape, s)
    kernel = functools.partial(_cdmsgd_nesterov_kernel_s, n_stencil=s)
    sc_specs, sc_args = _scalar_operands(weights, alpha, mu)
    in_specs = [
        *sc_specs,                         # weights, alpha, mu
        *sp_specs,
        mat_spec, mat_spec,                        # grad, momentum
    ]
    args = [*sc_args, *sp_args, grad, momentum]
    g_idx = len(args) - 2
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=(mat_spec, mat_spec, mat_spec),
        out_shape=(
            jax.ShapeDtypeStruct(grad.shape, grad.dtype),
            jax.ShapeDtypeStruct(momentum.shape, momentum.dtype),
            jax.ShapeDtypeStruct(grad.shape, grad.dtype),
        ),
        input_output_aliases=_aliases(alias, ((g_idx, 0), (g_idx + 1, 1))),
        interpret=resolve_interpret(interpret),
    )(*args)


def cdadam_update_sparse_2d(
    values: jnp.ndarray,
    indices: jnp.ndarray,
    scales: jnp.ndarray,
    weights: jnp.ndarray,
    grad: jnp.ndarray,
    m: jnp.ndarray,
    v: jnp.ndarray,
    alpha,
    b1,
    b2,
    eps,
    bc1,
    bc2,
    *,
    self_buf: jnp.ndarray,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    alias: bool = True,
    interpret: Optional[bool] = None,
):
    """Returns ``(x', m', v')`` — local Adam moments, sparse neighbor mix."""
    grid, mat_spec, sp_specs, sp_args, s = _sparse_operands(
        values, indices, scales, self_buf, grad, block_rows, interpret)
    assert weights.shape == (s + 1,), (weights.shape, s)
    kernel = functools.partial(_cdadam_kernel_s, n_stencil=s)
    scal = jnp.stack([jnp.asarray(x, jnp.float32) for x in
                      (alpha, b1, b2, eps, bc1, bc2)])
    sc_specs, sc_args = _scalar_operands(weights, scal)
    in_specs = [
        *sc_specs,                         # weights, packed scalars
        *sp_specs,
        mat_spec, mat_spec, mat_spec,              # grad, m, v
    ]
    args = [*sc_args, *sp_args, grad, m, v]
    g_idx = len(args) - 3
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=(mat_spec, mat_spec, mat_spec),
        out_shape=(
            jax.ShapeDtypeStruct(grad.shape, grad.dtype),
            jax.ShapeDtypeStruct(m.shape, m.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ),
        input_output_aliases=_aliases(
            alias, ((g_idx, 0), (g_idx + 1, 1), (g_idx + 2, 2))),
        interpret=resolve_interpret(interpret),
    )(*args)


def cdsgd_update_2d(
    neighbors: jnp.ndarray,       # (S, rows, 128) — neighbor tiles (see below)
    weights: jnp.ndarray,         # (S,) f32 — Pi row restricted to the stencil
    grad: jnp.ndarray,            # (rows, 128) — bucket dtype; donated to out
    alpha,                        # scalar
    *,
    scales: jnp.ndarray = None,   # (S, rows, 1) f32 when neighbors quantized
    self_buf: jnp.ndarray = None, # (rows, 128) native self tile (quantized form)
    block_rows: int = DEFAULT_BLOCK_ROWS,
    alias: bool = True,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    s, rows, lane = neighbors.shape
    assert lane == LANE and grad.shape == (rows, lane)
    block_rows = min(block_rows, rows)
    grid, nbr_spec, scale_spec, mat_spec = _grid_and_specs(rows, block_rows, s)
    quantized = scales is not None
    kernel = functools.partial(
        _cdsgd_kernel_q if quantized else _cdsgd_kernel, n_stencil=s)
    mix_specs, mix_args, n_w = _mix_operands(
        quantized, s, nbr_spec, scale_spec, mat_spec, neighbors, scales, self_buf)
    assert weights.shape == (n_w,)
    sc_specs, sc_args = _scalar_operands(weights, alpha)
    in_specs = [
        *sc_specs,                         # weights, alpha
        *mix_specs,
        mat_spec,                                  # grad
    ]
    args = [*sc_args, *mix_args, grad]
    grad_idx = len(args) - 1
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=mat_spec,
        out_shape=jax.ShapeDtypeStruct((rows, lane), grad.dtype),
        input_output_aliases=_aliases(alias, ((grad_idx, 0),)),
        name="cdsgd_update",
        interpret=resolve_interpret(interpret),
    )(*args)


def cdmsgd_update_2d(
    neighbors: jnp.ndarray,       # (S, rows, 128)
    weights: jnp.ndarray,         # (S,)
    grad: jnp.ndarray,            # (rows, 128) — donated to params out
    momentum: jnp.ndarray,        # (rows, 128) — donated to new momentum
    alpha,
    mu,
    *,
    scales: jnp.ndarray = None,
    self_buf: jnp.ndarray = None,
    mom_neighbors: jnp.ndarray = None,   # (S, rows, 128) momentum wire payloads
    mom_scales: jnp.ndarray = None,      # (S, rows, 1) momentum row scales
    block_rows: int = DEFAULT_BLOCK_ROWS,
    alias: bool = True,
    interpret: Optional[bool] = None,
):
    """``mom_neighbors`` (+ ``mom_scales``) selects the mixed-momentum form
    ``v' = mu (Pi v) - a g``: the momentum buffer crossed the wire like the
    params and ``momentum`` becomes its fresh full-precision self tile."""
    s, rows, lane = neighbors.shape
    block_rows = min(block_rows, rows)
    grid, nbr_spec, scale_spec, mat_spec = _grid_and_specs(rows, block_rows, s)
    quantized = scales is not None
    mixed = mom_neighbors is not None
    kernel = functools.partial(
        _cdmsgd_kernel_qm if mixed else
        _cdmsgd_kernel_q if quantized else _cdmsgd_kernel, n_stencil=s)
    mix_specs, mix_args, n_w = _mix_operands(
        quantized, s, nbr_spec, scale_spec, mat_spec, neighbors, scales,
        self_buf, mom_neighbors, mom_scales)
    sc_specs, sc_args = _scalar_operands(weights, alpha, mu)
    in_specs = [
        *sc_specs,                         # weights, alpha, mu
        *mix_specs,
        mat_spec, mat_spec,                        # grad, momentum
    ]
    args = [*sc_args, *mix_args, grad, momentum]
    g_idx = len(args) - 2
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=(mat_spec, mat_spec),
        out_shape=(
            jax.ShapeDtypeStruct((rows, lane), grad.dtype),
            jax.ShapeDtypeStruct((rows, lane), momentum.dtype),
        ),
        input_output_aliases=_aliases(alias, ((g_idx, 0), (g_idx + 1, 1))),
        name="cdmsgd_update",
        interpret=resolve_interpret(interpret),
    )(*args)


def cdmsgd_nesterov_update_2d(
    neighbors: jnp.ndarray,       # (S, rows, 128)
    weights: jnp.ndarray,         # (S,)
    grad: jnp.ndarray,            # (rows, 128) — evaluated at the lookahead
    momentum: jnp.ndarray,        # (rows, 128)
    alpha,
    mu,
    *,
    scales: jnp.ndarray = None,
    self_buf: jnp.ndarray = None,
    mom_neighbors: jnp.ndarray = None,
    mom_scales: jnp.ndarray = None,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    alias: bool = True,
    interpret: Optional[bool] = None,
):
    """Returns ``(x', v', x' + mu v')`` — params, momentum, next lookahead.

    ``grad`` donates to ``x'`` and ``momentum`` to ``v'``; the lookahead is
    the one genuinely new buffer of the step.  ``mom_neighbors`` selects
    the mixed-momentum form (see :func:`cdmsgd_update_2d`).
    """
    s, rows, lane = neighbors.shape
    block_rows = min(block_rows, rows)
    grid, nbr_spec, scale_spec, mat_spec = _grid_and_specs(rows, block_rows, s)
    quantized = scales is not None
    mixed = mom_neighbors is not None
    kernel = functools.partial(
        _cdmsgd_nesterov_kernel_qm if mixed else
        _cdmsgd_nesterov_kernel_q if quantized else _cdmsgd_nesterov_kernel,
        n_stencil=s)
    mix_specs, mix_args, n_w = _mix_operands(
        quantized, s, nbr_spec, scale_spec, mat_spec, neighbors, scales,
        self_buf, mom_neighbors, mom_scales)
    sc_specs, sc_args = _scalar_operands(weights, alpha, mu)
    in_specs = [
        *sc_specs,                         # weights, alpha, mu
        *mix_specs,
        mat_spec, mat_spec,                        # grad, momentum
    ]
    args = [*sc_args, *mix_args, grad, momentum]
    g_idx = len(args) - 2
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=(mat_spec, mat_spec, mat_spec),
        out_shape=(
            jax.ShapeDtypeStruct((rows, lane), grad.dtype),
            jax.ShapeDtypeStruct((rows, lane), momentum.dtype),
            jax.ShapeDtypeStruct((rows, lane), grad.dtype),
        ),
        input_output_aliases=_aliases(alias, ((g_idx, 0), (g_idx + 1, 1))),
        name="cdmsgd_nesterov_update",
        interpret=resolve_interpret(interpret),
    )(*args)


def cdadam_update_2d(
    neighbors: jnp.ndarray,       # (S, rows, 128)
    weights: jnp.ndarray,         # (S,)
    grad: jnp.ndarray,            # (rows, 128) — donated to params out
    m: jnp.ndarray,               # (rows, 128) first moment; donated to m'
    v: jnp.ndarray,               # (rows, 128) second moment; donated to v'
    alpha,
    b1,
    b2,
    eps,
    bc1,                          # 1 - b1**t (traced; computed by the caller)
    bc2,                          # 1 - b2**t
    *,
    scales: jnp.ndarray = None,
    self_buf: jnp.ndarray = None,
    mom_neighbors: jnp.ndarray = None,   # first-moment wire payloads (mixed)
    mom_scales: jnp.ndarray = None,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    alias: bool = True,
    interpret: Optional[bool] = None,
):
    """Returns ``(x', m', v')`` — mixed params with a local-Adam step.
    ``mom_neighbors`` mixes the first moment over the wire too
    (``m' = b1 (Pi m) + (1-b1) g``); ``m`` is then its fresh self tile."""
    s, rows, lane = neighbors.shape
    block_rows = min(block_rows, rows)
    grid, nbr_spec, scale_spec, mat_spec = _grid_and_specs(rows, block_rows, s)
    quantized = scales is not None
    mixed = mom_neighbors is not None
    kernel = functools.partial(
        _cdadam_kernel_qm if mixed else
        _cdadam_kernel_q if quantized else _cdadam_kernel, n_stencil=s)
    scal = jnp.stack([jnp.asarray(x, jnp.float32) for x in
                      (alpha, b1, b2, eps, bc1, bc2)])
    mix_specs, mix_args, n_w = _mix_operands(
        quantized, s, nbr_spec, scale_spec, mat_spec, neighbors, scales,
        self_buf, mom_neighbors, mom_scales)
    sc_specs, sc_args = _scalar_operands(weights, scal)
    in_specs = [
        *sc_specs,                         # weights, packed scalars
        *mix_specs,
        mat_spec, mat_spec, mat_spec,              # grad, m, v
    ]
    args = [*sc_args, *mix_args, grad, m, v]
    g_idx = len(args) - 3
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=(mat_spec, mat_spec, mat_spec),
        out_shape=(
            jax.ShapeDtypeStruct((rows, lane), grad.dtype),
            jax.ShapeDtypeStruct((rows, lane), m.dtype),
            jax.ShapeDtypeStruct((rows, lane), v.dtype),
        ),
        input_output_aliases=_aliases(
            alias, ((g_idx, 0), (g_idx + 1, 1), (g_idx + 2, 2))),
        name="cdadam_update",
        interpret=resolve_interpret(interpret),
    )(*args)
