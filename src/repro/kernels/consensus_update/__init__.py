"""Fused consensus-update kernels (paper eq. 5/6) on flat parameter buffers.

Flat-buffer layout contract (:mod:`repro.core.flatbuf`)
-------------------------------------------------------

The whole parameter pytree is packed into **dtype buckets**: per bucket a
single ``(*lead, rows, 128)`` array in which leaves sit contiguously at
static element ``offset``\\ s with one zero-padded tail row block.  The
fused update is then **one** ``pallas_call`` per dtype bucket per step —
the kernel grid walks ``(block_rows, 128)`` tiles, loads
self/neighbor/gradient/state tiles into VMEM, accumulates in f32 and
writes the updated tiles — instead of one launch (plus per-leaf padding
waste) per pytree leaf.

Kernels: ``cdsgd_update_2d`` (Algorithm 1), ``cdmsgd_update_2d``
(Algorithm 2, Polyak), ``cdmsgd_nesterov_update_2d`` (Algorithm 3 — also
emits the next lookahead point ``x' + mu v'`` in the same sweep), and
``cdadam_update_2d`` (beyond-paper: consensus mixing with local Adam
moments).  All take ``neighbors (S, rows, 128)`` + ``weights (S,)`` where
``S`` = stencil size (degree + self); their scalar operands (weights,
step sizes, the quantize seed) ride in SMEM as ``(1, n)`` arrays.  They
compile with Mosaic on a TPU and run in the Pallas interpreter elsewhere
(:func:`repro.kernels.resolve_interpret`).

Two perf levers ride on every kernel:

* **Quantized exchange** — ``sr_quantize_2d`` turns a bucket into int8 (or
  fp8-e4m3) payloads with one f32 scale per 128-lane row *before* the
  ``ppermute``; passing the matching ``scales`` operand makes the kernels
  dequantize in-register during mixing, so the wire moves ~4x fewer bytes
  and no dequantized neighbor copy ever lands in HBM.
* **In-place updates** — ``input_output_aliases`` donate the gradient
  buffer to the updated params and each optimizer-state buffer to its
  successor, eliminating the extra HBM output copy per model/slot.

``mixing="ppermute_fused"`` contract (sharded trainer)
------------------------------------------------------

Under :func:`repro.launch.steps.build_train_step` with
``mixing="ppermute_fused"``, the entire optimizer update executes inside a
single ``shard_map`` region over the agent mesh axes: pack → (optionally
quantize) → one ``lax.ppermute`` per circulant shift offset *per bucket*
(NOT per leaf) → fused update kernel → unpack.  Total per-step collective
count is ``len(shift_offsets) - 1`` per dtype bucket (self-shift moves no
data) — times two when the exchange is quantized (payload + row scales,
still ~3.9x fewer bytes); total kernel-launch count equals the number of
dtype buckets.  Requires a circulant topology
(``Topology.shift_weights() is not None``); non-circulant graphs must use
``mixing="ppermute"`` (per-leaf) or ``"dense"``.

The stacked simulation reaches the same kernels through
``CommOps.flat`` (see :func:`repro.core.consensus.stacked_flat_comm`): the
dense ``Pi`` becomes an ``(A, A)`` weight matrix and the kernel is vmapped
over agent rows — still a single batched ``pallas_call`` per bucket.
"""
