"""Flat parameter buffers: pack a pytree into dtype-bucketed (rows, 128) tiles.

The consensus optimizers (CDSGD family) are purely memory-bound elementwise
updates over the *whole* parameter vector.  Applying them leaf-by-leaf costs
one kernel launch + one padded HBM sweep + (sharded) one ``ppermute``
collective *per leaf per neighbor* — hundreds of launches and collectives
per step for a transformer.  This module gives every consensus path a flat
view instead:

* leaves are grouped into **dtype buckets** (bf16 params never mix bits with
  f32 gains/biases), preserving first-appearance order;
* within a bucket leaves are packed **contiguously** at static element
  ``offset``\\ s; only the bucket tail is zero-padded up to a whole number of
  128-wide rows — so the packed buffer is a ``(*lead, rows, 128)`` array
  whose layout is described entirely by compile-time metadata
  (:class:`FlatSpec`);
* ``pack`` is a cast + reshape per leaf, **one** concatenate along the
  rows axis and **one** tail pad per bucket (reshape-only when the bucket
  is a single 128-aligned leaf); ``unpack`` is a static slice + reshape per
  leaf — no gathers, no scatter, no host work.  Both move whole rows:
  a leaf that starts on a row boundary and fills whole rows reshapes
  straight to ``(*lead, rows_i, 128)``, and only leaves that straddle a row
  boundary go through a flat vector (:func:`_row_groups`).  On a TPU the
  relayout of a large leaf into one flat vector is slow to compile and to
  run; into 128-lane rows it is a plain tiled copy.

``lead`` counts leading *replica* axes excluded from flattening: the stacked
simulation packs ``(A, ...)`` leaves with ``lead=1`` into ``(A, rows, 128)``
buffers; the sharded trainer packs its local shard (agent axis of size 1)
the same way and squeezes.

:func:`make_flat_spec` memoizes by ``(treedef, shapes, dtypes, lead)`` so
retraced steps reuse the same slot metadata instead of rebuilding it.

The fused update kernels in :mod:`repro.kernels.consensus_update` then walk
one bucket in a single ``pallas_call``, and the sharded circulant exchange
issues one ``lax.ppermute`` per shift offset per bucket — instead of one
per leaf — which is the whole-step communication pattern the paper's
fixed-topology argument (eq. 5/6) assumes.

Exchange precision
------------------
What each ``ppermute`` carries is selectable (``FlatComm(exchange=...)`` in
:mod:`repro.core.consensus`): ``"f32"`` moves the native bucket bytes,
``"bf16"`` halves f32 buckets, and ``"int8"`` / ``"fp8"`` move one byte per
element plus one f32 scale per 128-lane row (stochastic-rounding
quantization; dequantized in-register inside the fused kernels).
:meth:`FlatSpec.exchange_bytes` is the bytes-on-wire estimator used by the
benchmarks, examples and the dryrun to report per-step exchange cost.

Because leaves pack contiguously, a 128-lane row at a leaf boundary can
span two leaves, and a quantized exchange then shares one scale across
them — a small-magnitude leaf adjacent to a large-magnitude one absorbs
rounding noise proportional to the neighbor's row amax in that row.  At
most ``n_leaves - 1`` of the bucket's rows are affected; the documented
int8 trajectory tolerances (tests/test_flatbuf_fused.py,
tests/test_sharded.py) are measured on real mixed-magnitude models and
include this effect.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp

PyTree = Any

LANE = 128

# bytes per element moved over the wire, per exchange precision; quantized
# exchanges additionally move one f32 scale per LANE-wide row (see
# `BucketSpec.exchange_bytes`).  "f32" means *native* bucket precision.
EXCHANGE_DTYPES = ("f32", "bf16", "int8", "fp8")


@dataclasses.dataclass(frozen=True)
class LeafSlot:
    """Placement of one pytree leaf inside its dtype bucket."""

    index: int                      # position in the flattened-tree order
    shape: Tuple[int, ...]          # per-replica shape (lead axes excluded)
    size: int                       # prod(shape)
    offset: int                     # element offset in the flattened bucket


@dataclasses.dataclass(frozen=True)
class BucketSpec:
    dtype: Any                      # canonical jnp dtype of the bucket
    rows: int                       # ceil(sum(slot.size) / LANE)
    slots: Tuple[LeafSlot, ...]

    @property
    def n_padded(self) -> int:
        return self.rows * LANE

    @property
    def n_real(self) -> int:
        return sum(s.size for s in self.slots)

    @property
    def bytes(self) -> int:
        return self.n_padded * jnp.dtype(self.dtype).itemsize

    def exchange_bytes(self, exchange: str = "f32") -> int:
        """Bytes one neighbor transfer of this bucket puts on the wire."""
        if exchange == "f32":               # native bucket precision
            return self.bytes
        if exchange == "bf16":
            return self.n_padded * min(2, jnp.dtype(self.dtype).itemsize)
        if exchange in ("int8", "fp8"):
            # 1 byte/element + one f32 scale per 128-lane row
            return self.n_padded + self.rows * 4
        raise ValueError(f"unknown exchange precision {exchange!r}; "
                         f"expected one of {EXCHANGE_DTYPES}")


@dataclasses.dataclass(frozen=True)
class FlatSpec:
    """Static packing metadata for one pytree structure."""

    treedef: Any
    n_leaves: int
    lead: int                       # leading replica axes excluded from packing
    buckets: Tuple[BucketSpec, ...]

    @property
    def n_buckets(self) -> int:
        return len(self.buckets)

    @property
    def total_bytes(self) -> int:
        return sum(b.bytes for b in self.buckets)

    def exchange_bytes(self, exchange: str = "f32") -> int:
        """Bytes-on-wire for ONE neighbor transfer of the whole model."""
        return sum(b.exchange_bytes(exchange) for b in self.buckets)


# spec cache: keyed on everything make_flat_spec reads — retraced steps hand
# in fresh tracers but identical (treedef, shapes, dtypes, lead) signatures.
_SPEC_CACHE: Dict[Any, FlatSpec] = {}


def make_flat_spec(tree: PyTree, lead: int = 0) -> FlatSpec:
    """Build the bucketed layout for ``tree`` (shapes/dtypes only, no data).

    Memoized: repeated calls with the same structure/shapes/dtypes return
    the identical :class:`FlatSpec` object.
    """
    leaves, treedef = jax.tree.flatten(tree)
    key = (treedef, lead,
           tuple((tuple(x.shape), jnp.dtype(x.dtype).name) for x in leaves))
    cached = _SPEC_CACHE.get(key)
    if cached is not None:
        return cached
    order: List[Any] = []           # bucket dtypes in first-appearance order
    grouped = {}
    for index, leaf in enumerate(leaves):
        dt = jnp.dtype(leaf.dtype)
        shape = tuple(leaf.shape[lead:])
        size = 1
        for d in shape:
            size *= d
        if dt not in grouped:
            grouped[dt] = []
            order.append(dt)
        grouped[dt].append((index, shape, size))
    buckets = []
    for dt in order:
        slots = []
        offset = 0
        for index, shape, size in grouped[dt]:
            slots.append(LeafSlot(index=index, shape=shape, size=size,
                                  offset=offset))
            offset += size
        rows = -(-offset // LANE)
        buckets.append(BucketSpec(dtype=dt, rows=rows, slots=tuple(slots)))
    spec = FlatSpec(treedef=treedef, n_leaves=len(leaves), lead=lead,
                    buckets=tuple(buckets))
    _SPEC_CACHE[key] = spec
    return spec


def _row_groups(bucket: BucketSpec):
    """Split a bucket's slots into row-aligned groups ``(row0, rows, slots)``.

    Each group starts on a row boundary and ends on one (or at the bucket
    tail).  A leaf that starts on a row boundary and fills whole rows is a
    group of its own; leaves that straddle a row boundary share one group.
    The slot offsets stay contiguous — grouping only changes how pack and
    unpack express the same layout.
    """
    groups, cur, start = [], [], 0
    for slot in bucket.slots:
        cur.append(slot)
        end = slot.offset + slot.size
        if end % LANE == 0:
            groups.append((start // LANE, (end - start) // LANE, tuple(cur)))
            cur, start = [], end
    if cur:
        groups.append((start // LANE, bucket.rows - start // LANE, tuple(cur)))
    return groups


def pack(tree: PyTree, spec: FlatSpec) -> List[jnp.ndarray]:
    """Pack ``tree`` into one ``(*lead, rows, 128)`` buffer per dtype bucket.

    Leaves are cast to their bucket dtype (grads/momenta packed against a
    parameter spec inherit the unfused ``g.astype(param.dtype)`` semantics).
    Each bucket is ONE concatenate of row blocks plus ONE tail pad up to the
    row boundary; a single 128-aligned leaf is a pure reshape.
    """
    leaves, treedef = jax.tree.flatten(tree)
    if treedef != spec.treedef:
        raise ValueError(f"tree structure {treedef} != spec structure {spec.treedef}")
    for slot in (sl for b in spec.buckets for sl in b.slots):
        x = leaves[slot.index]
        if tuple(x.shape[spec.lead:]) != slot.shape:
            raise ValueError(
                f"leaf {slot.index}: shape {x.shape} != spec {slot.shape} "
                f"(lead={spec.lead})")
    lead_shape = tuple(leaves[0].shape[:spec.lead])
    out = []
    for bucket in spec.buckets:
        blocks = []
        for _, rows, slots in _row_groups(bucket):
            flat = [leaves[sl.index].astype(bucket.dtype).reshape(
                lead_shape + (sl.size,)) for sl in slots]
            flat = flat[0] if len(flat) == 1 else jnp.concatenate(flat, axis=-1)
            padding = rows * LANE - flat.shape[-1]      # bucket tail only
            if padding:
                flat = jnp.pad(flat, [(0, 0)] * spec.lead + [(0, padding)])
            blocks.append(flat.reshape(lead_shape + (rows, LANE)))
        out.append(blocks[0] if len(blocks) == 1
                   else jnp.concatenate(blocks, axis=-2))
    return out


def unpack(bufs: Sequence[jnp.ndarray], spec: FlatSpec) -> PyTree:
    """Inverse of :func:`pack`: static slice + reshape per leaf."""
    if len(bufs) != spec.n_buckets:
        raise ValueError(f"{len(bufs)} buffers != {spec.n_buckets} buckets")
    leaves: List[Any] = [None] * spec.n_leaves
    for bucket, buf in zip(spec.buckets, bufs):
        lead_shape = tuple(buf.shape[:-2])
        for row0, rows, slots in _row_groups(bucket):
            block = buf[..., row0:row0 + rows, :]
            flat = block.reshape(lead_shape + (rows * LANE,))
            for slot in slots:
                lo = slot.offset - row0 * LANE
                leaves[slot.index] = flat[..., lo:lo + slot.size].reshape(
                    lead_shape + slot.shape)
    return jax.tree.unflatten(spec.treedef, leaves)
