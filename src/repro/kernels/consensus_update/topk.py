"""Top-k sparse + rank-r low-rank wire compressors for the flat buckets.

These are the two *biased* compressors of the ``MixingProgram`` compressor
axis (``compressor="topk:p" | "rank:r"``, see
:mod:`repro.core.consensus`).  Both operate on one packed flat bucket at a
time — the ``(rows, 128)`` layout of :mod:`repro.core.flatbuf` — and both
ride the error-feedback rail (``OptState.residual``): a biased compressor
without EF diverges, which ``make_mixing_program`` rejects at config time.

Top-k (``topk:p``)
------------------
Keep the ``K = k_rows * 128`` largest-magnitude elements of the bucket
(``k_rows = ceil(ceil(p * rows * 128) / 128)`` — the ragged ``ceil(p*n)``
count rounded up to a lane-aligned static shape), ship them as a compact
``(k_rows, 128)`` tile:

* ``values``  — int8, SR-quantized via the existing
  :func:`~repro.kernels.consensus_update.consensus_update.sr_quantize_2d`
  Pallas kernel on the *compact* layout (one f32 scale per compact row);
* ``indices`` — int32 flat positions ``row * 128 + lane`` into the dense
  bucket;
* ``scales``  — the ``(k_rows, 1)`` f32 row scales of the values.

Selection itself is ``jax.lax.top_k`` over the flattened magnitudes:
element-granular gather/scatter has no efficient direct TPU-Pallas
lowering (the TPU vector unit has no scatter; XLA owns those HLOs), so
the exact select/gather/scatter legs go through XLA while the Pallas
surface here is :func:`topk_threshold_2d` — a single-HBM-sweep
magnitude-threshold kernel that brackets the k-th magnitude with a
geometric threshold histogram (the standard TPU fast path: threshold
stats in one sweep, then a compaction against ``tau``).  The threshold
kernel is benchmarked and tested against the exact selection
(``consensus/compressor_frontier``).

The decompressed ("gather-dequant") form is a dense f32 bucket with the
un-selected elements at zero — it feeds the fused update kernels'
existing self-separated weight path unchanged (dense neighbor stacks with
unit scales; the self term never crossed the wire).

Rank-r (``rank:r``)
-------------------
One PowerSGD-style power iteration per step (Vogels et al., 1905.13727):

    P = orth(M @ Q)          # (rows, r)
    Qt = P^T @ M             # (r, 128)   — ship (P, Qt)
    M_hat = P @ Qt           # reconstruction
    Q' = orth(Qt^T)          # (128, r)   — warm start, carried in OptState

The two factors ride the ``ppermute`` as two *dense* payloads —
``4 * (rows*r + r*128)`` bytes per neighbor versus ``4 * rows * 128``
for f32.  The warm-started ``Q`` lives in ``OptState.qwarm`` next to the
wire, checkpointing and resuming like any other optimizer state.

All functions are deterministic: ``lax.top_k`` breaks ties by index, the
Gram-Schmidt orthonormalization is a fixed static-``r`` loop with a
zero-column guard (no ``jnp.linalg.qr`` in the step), and the SR bits of
the compact values draw from the same ``wire_seed`` composition as the
dense int8 wire.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

from repro.kernels.consensus_update.consensus_update import (
    DEFAULT_BLOCK_ROWS,
    LANE,
    sr_quantize_2d,
)


# --------------------------------------------------------------------------
# static shape math (the single source the byte accounting prices from)
# --------------------------------------------------------------------------


def topk_k_rows(rows: int, p: float) -> int:
    """Lane-aligned compact row count for density ``p`` over ``rows*128``.

    ``K = ceil(p * rows * 128)`` elements, rounded up to whole 128-lane
    rows so the compact payload keeps the flat-bucket tile shape (and the
    per-row scale layout of :func:`sr_quantize_2d`); clamped to ``rows``.
    """
    if not (0.0 < p <= 1.0):
        raise ValueError(f"top-k density must be in (0, 1], got {p!r}")
    k = max(1, math.ceil(p * rows * LANE))
    return min(rows, max(1, -(-k // LANE)))


#: Wire bytes of ONE compact lane row: 128 int8 values + 128 int32 flat
#: indices + one f32 row scale.  The single price every byte account and
#: the ``topk:auto`` budget solver use.
TOPK_LANE_ROW_BYTES = LANE * (1 + 4) + 4


def topk_auto_k_rows(rows_list, budget: int):
    """Per-bucket compact row counts meeting a total byte budget per neighbor.

    ``topk:auto:B`` adaptive density: given the dense row counts of every
    bucket, choose ``k_rows[i]`` so that ``sum(k_rows) *
    TOPK_LANE_ROW_BYTES <= budget`` with at least one lane row per bucket
    (a bucket that ships nothing would stall its EF residual forever).
    Rows are spread proportionally to each bucket's size, then a greedy
    top-up hands the integer remainder to the largest uncovered buckets
    (deterministic: ties break toward the lower bucket index) — so unless
    every bucket saturates at full density, the shortfall under ``budget``
    is less than one lane row total.
    """
    rows_list = list(rows_list)
    n = len(rows_list)
    floor_bytes = n * TOPK_LANE_ROW_BYTES
    if budget < floor_bytes:
        raise ValueError(
            f"topk:auto budget {budget} B cannot cover one compact lane row "
            f"per bucket ({n} buckets x {TOPK_LANE_ROW_BYTES} B = "
            f"{floor_bytes} B minimum)")
    afford = budget // TOPK_LANE_ROW_BYTES
    k = [1] * n
    rem = afford - n
    frac = [r - 1 for r in rows_list]
    total_frac = sum(frac)
    if total_frac > 0:
        for i in range(n):
            k[i] += min(frac[i], rem * frac[i] // total_frac)
    while sum(k) < afford:
        cands = [(rows_list[i] - k[i], -i) for i in range(n)
                 if k[i] < rows_list[i]]
        if not cands:
            break                       # every bucket already full density
        uncovered, neg_i = max(cands)
        k[-neg_i] += 1
    return k


def topk_k_rows_for(rows_list, param):
    """Per-bucket ``k_rows`` for a parsed ``topk`` compressor parameter.

    ``param`` is either a float density ``p`` (``topk:p`` — applied to each
    bucket independently) or the tuple ``("auto", budget_bytes)`` from
    ``topk:auto:B`` (the byte-budget solver above).
    """
    if isinstance(param, tuple):
        kind, budget = param
        assert kind == "auto", param
        return topk_auto_k_rows(rows_list, budget)
    return [topk_k_rows(r, param) for r in rows_list]


# --------------------------------------------------------------------------
# Pallas magnitude-threshold kernel (one HBM sweep)
# --------------------------------------------------------------------------


def _threshold_count_kernel(taus_ref, x_ref, counts_ref, *, n_bins: int,
                            rows: int, block_rows: int):
    """Accumulate ``count(|x| >= tau_b)`` per geometric threshold bin.

    Sequential-grid accumulation: block 0 zeroes the (1, n_bins) counts,
    every block adds its tile's per-bin counts.  Rows past ``rows`` (the
    zero-padded tail of the last block) are masked to a negative sentinel
    so they never count against the strictly positive thresholds.
    """
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        counts_ref[...] = jnp.zeros_like(counts_ref)

    ax = jnp.abs(x_ref[...].astype(jnp.float32))
    row0 = i * block_rows
    ridx = row0 + jax.lax.broadcasted_iota(jnp.int32, ax.shape, 0)
    ax = jnp.where(ridx < rows, ax, -1.0)
    taus = taus_ref[...]                                  # (1, n_bins)
    bidx = jax.lax.broadcasted_iota(jnp.int32, (1, n_bins), 1)
    acc = jnp.zeros((1, n_bins), jnp.float32)
    for b in range(n_bins):
        cnt = jnp.sum((ax >= taus[0, b]).astype(jnp.float32))
        acc = acc + jnp.where(bidx == b, cnt, 0.0)
    counts_ref[...] += acc


def topk_threshold_2d(x: jnp.ndarray, k: int, *, n_bins: int = 16,
                      span: float = 1e-4,
                      block_rows: int = DEFAULT_BLOCK_ROWS,
                      interpret: Optional[bool] = None):
    """Bracket the k-th largest magnitude of a flat bucket in ONE sweep.

    Sweeps the ``(rows, 128)`` bucket once, counting ``|x| >= tau_b`` for
    ``n_bins`` geometric thresholds ``tau_b = amax * span^(b/(n_bins-1))``
    (``tau_0 = amax`` down to ``amax * span``), and returns ``(tau,
    counts)`` where ``tau`` is the smallest threshold whose count is
    ``<= k`` — so the true k-th magnitude lies within one geometric bin
    below ``tau`` (tested).  ``counts`` is the ``(n_bins,)`` f32 histogram.

    This is the TPU fast-path statistic for top-k selection (threshold
    then compact); the exact selection of :func:`topk_compress_2d` uses
    ``lax.top_k`` — see the module docstring for why the element-granular
    gather stays in XLA.
    """
    rows, lane = x.shape
    assert lane == LANE, x.shape
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)))
    ratios = jnp.asarray(
        [span ** (b / max(n_bins - 1, 1)) for b in range(n_bins)],
        jnp.float32)
    # degenerate all-zero bucket: keep taus strictly positive so the
    # padded/zero elements never count (tau selects nothing, count 0 <= k)
    taus = (jnp.maximum(amax, 1e-30) * ratios).reshape(1, n_bins)
    block_rows = min(block_rows, rows)
    n_blocks = pl.cdiv(rows, block_rows)
    kernel = functools.partial(_threshold_count_kernel, n_bins=n_bins,
                               rows=rows, block_rows=block_rows)
    counts = pl.pallas_call(
        kernel,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((1, n_bins), lambda i: (0, 0)),
            pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, n_bins), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, n_bins), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(taus, x)[0]
    # counts are nondecreasing in b (taus decreasing); pick the smallest
    # tau still selecting <= k elements — prefix-sum of the <=k mask
    ok = (counts <= jnp.float32(k)).astype(jnp.int32)
    idx = jnp.maximum(jnp.sum(ok) - 1, 0)
    return taus[0, idx], counts


# --------------------------------------------------------------------------
# top-k compress / decompress (exact selection)
# --------------------------------------------------------------------------


def topk_compress_2d(x: jnp.ndarray, k_rows: int, seed, *,
                     block_rows: int = DEFAULT_BLOCK_ROWS,
                     interpret: Optional[bool] = None):
    """Compress one dense bucket to its lane-aligned top-K compact form.

    Returns ``(values, indices, scales)``: int8 ``(k_rows, 128)`` compact
    values (SR-quantized with the shared :func:`sr_quantize_2d` kernel —
    same ``wire_seed`` stream discipline as the dense int8 wire), int32
    ``(k_rows, 128)`` flat dense positions (``row * 128 + lane``, sorted
    ascending — ``lax.top_k`` is deterministic, ties break by index), and
    the ``(k_rows, 1)`` f32 per-compact-row scales.
    """
    rows, lane = x.shape
    assert lane == LANE, x.shape
    assert 1 <= k_rows <= rows, (k_rows, rows)
    kk = k_rows * LANE
    flat = x.reshape(-1).astype(jnp.float32)
    _, idx = jax.lax.top_k(jnp.abs(flat), kk)
    idx = jnp.sort(idx).astype(jnp.int32)
    vals = flat[idx].reshape(k_rows, LANE)
    q, sc = sr_quantize_2d(vals, seed, exchange="int8",
                           block_rows=block_rows, interpret=interpret)
    return q, idx.reshape(k_rows, LANE), sc


def topk_decompress_2d(values: jnp.ndarray, indices: jnp.ndarray,
                       scales: jnp.ndarray, rows: int) -> jnp.ndarray:
    """Gather-dequant form: compact payload -> dense f32 ``(rows, 128)``.

    Un-selected elements are zero; the result feeds the fused kernels as
    a dense neighbor buffer with unit scales (the in-register dequant
    multiply is then the identity).  Indices are unique by construction,
    so a plain scatter-set suffices.
    """
    deq = values.astype(jnp.float32) * scales
    flat = jnp.zeros((rows * LANE,), jnp.float32)
    flat = flat.at[indices.reshape(-1)].set(deq.reshape(-1))
    return flat.reshape(rows, LANE)


# --------------------------------------------------------------------------
# rank-r power-iteration compressor (PowerSGD-style)
# --------------------------------------------------------------------------


def _orthonormalize_cols(a: jnp.ndarray, eps: float = 1e-8) -> jnp.ndarray:
    """Modified Gram-Schmidt over the (static, small) column count.

    A numerically degenerate column collapses to zero instead of NaN —
    it then contributes nothing to the reconstruction, and every agent
    collapses identically (deterministic), so the modes stay in lockstep.
    """
    cols = []
    for i in range(a.shape[1]):
        v = a[:, i].astype(jnp.float32)
        for u in cols:
            v = v - jnp.dot(u, v) * u
        nrm = jnp.sqrt(jnp.sum(v * v))
        cols.append(jnp.where(nrm > eps, v / jnp.maximum(nrm, eps),
                              jnp.zeros_like(v)))
    return jnp.stack(cols, axis=1)


def rank_init_q(r: int, seed: int = 0) -> jnp.ndarray:
    """Deterministic orthonormal ``(128, r)`` warm-start basis.

    Identical across agents, buckets, and execution modes — the power
    iteration re-aligns it to the data from step 0, and a shared init
    keeps stacked/sharded trajectories bit-identical.
    """
    if not isinstance(r, int) or r < 1 or r > LANE:
        raise ValueError(f"rank must be an int in [1, {LANE}], got {r!r}")
    g = jax.random.normal(jax.random.PRNGKey(seed), (LANE, r), jnp.float32)
    return _orthonormalize_cols(g)


def rank_compress_2d(m: jnp.ndarray, q: jnp.ndarray):
    """One warm-started power iteration: ``m (rows, 128)`` -> factors.

    Returns ``(p, qt, q_next)``: the orthonormal left factor ``(rows, r)``,
    the right factor ``(r, 128)`` (``p^T m`` — the two wire payloads), and
    the orthonormalized ``(128, r)`` warm start for the next step.
    Reconstruction is ``p @ qt`` (:func:`rank_decompress_2d`).
    """
    m = m.astype(jnp.float32)
    p = _orthonormalize_cols(m @ q)
    qt = p.T @ m
    q_next = _orthonormalize_cols(qt.T)
    return p, qt, q_next


def rank_decompress_2d(p: jnp.ndarray, qt: jnp.ndarray) -> jnp.ndarray:
    """Reconstruct the dense f32 bucket from the two wire factors."""
    return (p.astype(jnp.float32) @ qt.astype(jnp.float32))
