"""jit'd wrappers: whole-model fused consensus updates on flat buffers.

The pytree entry points (``cdsgd_update_tree`` & co.) pack the entire model
into dtype-bucketed ``(rows, 128)`` buffers (:mod:`repro.core.flatbuf`) and
run **one** ``pallas_call`` per dtype bucket — not one per leaf.  For a
transformer that collapses hundreds of kernel launches (each with its own
padding waste) into one whole-model HBM sweep per bucket.

``neighbor_trees`` are the already-communicated neighbor parameter pytrees
(the ppermute outputs in the sharded trainer, or plain stacked slices in
simulation) in the same order as ``weights``.

The ``*_update_flat`` entry points operate on already-packed buffers and
dispatch on ``weights.ndim``:

* ``weights (S,)``   — one agent's stencil: ``neighbors (S, rows, 128)``,
  per-agent operands ``(rows, 128)`` (the sharded path inside shard_map);
* ``weights (A, A)`` — the dense stacked simulation: ``neighbors`` is the
  full agent stack ``(A, rows, 128)`` shared by every agent, per-agent
  operands ``(A, rows, 128)``, and the kernel is vmapped over agent rows of
  ``Pi`` (still a single batched ``pallas_call`` in the jaxpr).

``scales`` (same leading shape as ``neighbors``, trailing ``(rows, 1)``)
marks the neighbor stack as int8/fp8-quantized wire payloads
(:func:`repro.kernels.consensus_update.consensus_update.sr_quantize_2d`);
the kernels dequantize in-register during the mixing accumulation.  In that
form ``neighbors`` excludes the self tile — the native-precision self
buffer rides in ``self_buf`` at ``weights[0]`` (per-agent ``(A, rows, 128)``
in the stacked mode, with ``weights (A, A+1)`` = ``[diag(Pi), off-diag
rows]``), since the local parameters never cross the wire.

``interpret`` defaults to ``None``: :func:`repro.kernels.resolve_interpret`
runs the compiled kernels on a TPU and the Pallas interpreter elsewhere.
"""

from __future__ import annotations

import functools
from typing import Any, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.extend.core import ClosedJaxpr, Jaxpr

from repro.core import flatbuf
from repro.kernels.consensus_update.consensus_update import (
    LANE,
    cdsgd_update_2d,
    cdmsgd_update_2d,
    cdmsgd_nesterov_update_2d,
    cdadam_update_2d,
    cdsgd_update_sparse_2d,
    cdmsgd_update_sparse_2d,
    cdmsgd_nesterov_update_sparse_2d,
    cdadam_update_sparse_2d,
)

PyTree = Any


class SparseNeighbors(NamedTuple):
    """Top-k compact neighbor operands for one dtype bucket.

    Passing this as ``neighbors`` to a ``*_update_flat`` entry point selects
    the sparse operand form: the kernel scatter-accumulates straight from the
    wire fields instead of reading a dense decompressed stack.  The fields
    are the :class:`repro.core.consensus.TopKWire` payloads stacked over the
    stencil — ``(S, k_rows, 128)`` int8 values, int32 flat dense indices,
    and ``(S, k_rows, 1)`` f32 scales.  ``self_buf`` is required (the self
    tile never crosses the wire) and ``scales=None`` (per-compact-row scales
    ride inside this tuple).  In the stacked simulation the same compact
    stack is shared by every agent, exactly like the dense quantized form.
    """

    values: jnp.ndarray
    indices: jnp.ndarray
    scales: jnp.ndarray


def _eqn_sub_jaxprs(params: dict):
    for v in params.values():
        vals = v if isinstance(v, (tuple, list)) else (v,)
        for x in vals:
            if isinstance(x, (Jaxpr, ClosedJaxpr)):
                yield x.jaxpr if isinstance(x, ClosedJaxpr) else x


def alias_groups(jaxpr) -> List[List[Tuple[int, int]]]:
    """``input_output_aliases`` pairs per ``pallas_call`` eqn in a jaxpr.

    Shared accounting helper (tests, benchmarks, and the static checker's
    alias-coverage pass): one inner list per launch in eqn order, each
    entry an ``(input_index, output_index)`` alias pair, read structurally
    from ``eqn.params["input_output_aliases"]``.  Accepts a ``Jaxpr`` or
    ``ClosedJaxpr`` (e.g. ``jax.make_jaxpr(fn)(*args)``) and recurses into
    call/control-flow sub-jaxprs; the kernel body itself is not descended
    into.  Printed jaxpr text is rejected — the old regex parse of it
    silently returned ``[]`` whenever jax's pretty-printer elided or
    reformatted the params.
    """
    if isinstance(jaxpr, str):
        raise TypeError(
            "alias_groups walks jaxpr eqns structurally; pass the jaxpr "
            "object from jax.make_jaxpr(...), not its printed text")
    j = jaxpr.jaxpr if isinstance(jaxpr, ClosedJaxpr) else jaxpr
    out: List[List[Tuple[int, int]]] = []

    def walk(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "pallas_call":
                pairs = eqn.params.get("input_output_aliases", ())
                out.append([(int(a), int(b)) for a, b in pairs])
                continue
            for sub in _eqn_sub_jaxprs(eqn.params):
                walk(sub)

    walk(j)
    return out


# --------------------------------------------------------------------------
# bucket-level entry points (packed buffers in, packed buffers out)
# --------------------------------------------------------------------------


def cdsgd_update_flat(neighbors, weights, grad, alpha, *, scales=None,
                      self_buf=None, interpret: Optional[bool] = None):
    if isinstance(neighbors, SparseNeighbors):
        nb = neighbors
        if weights.ndim == 2:
            return jax.vmap(lambda w, sb, g: cdsgd_update_sparse_2d(
                nb.values, nb.indices, nb.scales, w, g, alpha, self_buf=sb,
                interpret=interpret))(weights, self_buf, grad)
        return cdsgd_update_sparse_2d(nb.values, nb.indices, nb.scales,
                                      weights, grad, alpha,
                                      self_buf=self_buf, interpret=interpret)
    if weights.ndim == 2:
        if scales is not None:
            return jax.vmap(lambda w, sb, g: cdsgd_update_2d(
                neighbors, w, g, alpha, scales=scales, self_buf=sb,
                interpret=interpret))(weights, self_buf, grad)
        return jax.vmap(lambda w, g: cdsgd_update_2d(
            neighbors, w, g, alpha, interpret=interpret))(weights, grad)
    return cdsgd_update_2d(neighbors, weights, grad, alpha, scales=scales,
                           self_buf=self_buf, interpret=interpret)


def cdmsgd_update_flat(neighbors, weights, grad, momentum, alpha, mu, *,
                       scales=None, self_buf=None, mom_neighbors=None,
                       mom_scales=None, interpret: Optional[bool] = None):
    if isinstance(neighbors, SparseNeighbors):
        nb = neighbors
        if weights.ndim == 2:
            return jax.vmap(lambda w, sb, g, v: cdmsgd_update_sparse_2d(
                nb.values, nb.indices, nb.scales, w, g, v, alpha, mu,
                self_buf=sb, interpret=interpret))(
                    weights, self_buf, grad, momentum)
        return cdmsgd_update_sparse_2d(nb.values, nb.indices, nb.scales,
                                       weights, grad, momentum, alpha, mu,
                                       self_buf=self_buf, interpret=interpret)
    if weights.ndim == 2:
        if mom_neighbors is not None:
            # mixed momentum: the per-agent momentum row is the momentum
            # SELF tile; the shared wire stacks carry everyone's payloads
            return jax.vmap(lambda w, sb, g, v: cdmsgd_update_2d(
                neighbors, w, g, v, alpha, mu, scales=scales, self_buf=sb,
                mom_neighbors=mom_neighbors, mom_scales=mom_scales,
                interpret=interpret))(weights, self_buf, grad, momentum)
        if scales is not None:
            return jax.vmap(lambda w, sb, g, v: cdmsgd_update_2d(
                neighbors, w, g, v, alpha, mu, scales=scales, self_buf=sb,
                interpret=interpret))(weights, self_buf, grad, momentum)
        return jax.vmap(lambda w, g, v: cdmsgd_update_2d(
            neighbors, w, g, v, alpha, mu,
            interpret=interpret))(weights, grad, momentum)
    return cdmsgd_update_2d(neighbors, weights, grad, momentum, alpha, mu,
                            scales=scales, self_buf=self_buf,
                            mom_neighbors=mom_neighbors,
                            mom_scales=mom_scales, interpret=interpret)


def cdmsgd_nesterov_update_flat(neighbors, weights, grad, momentum, alpha, mu,
                                *, scales=None, self_buf=None,
                                mom_neighbors=None, mom_scales=None,
                                interpret: Optional[bool] = None):
    if isinstance(neighbors, SparseNeighbors):
        nb = neighbors
        if weights.ndim == 2:
            return jax.vmap(
                lambda w, sb, g, v: cdmsgd_nesterov_update_sparse_2d(
                    nb.values, nb.indices, nb.scales, w, g, v, alpha, mu,
                    self_buf=sb, interpret=interpret))(
                        weights, self_buf, grad, momentum)
        return cdmsgd_nesterov_update_sparse_2d(
            nb.values, nb.indices, nb.scales, weights, grad, momentum,
            alpha, mu, self_buf=self_buf, interpret=interpret)
    if weights.ndim == 2:
        if mom_neighbors is not None:
            return jax.vmap(lambda w, sb, g, v: cdmsgd_nesterov_update_2d(
                neighbors, w, g, v, alpha, mu, scales=scales, self_buf=sb,
                mom_neighbors=mom_neighbors, mom_scales=mom_scales,
                interpret=interpret))(weights, self_buf, grad, momentum)
        if scales is not None:
            return jax.vmap(lambda w, sb, g, v: cdmsgd_nesterov_update_2d(
                neighbors, w, g, v, alpha, mu, scales=scales, self_buf=sb,
                interpret=interpret))(weights, self_buf, grad, momentum)
        return jax.vmap(lambda w, g, v: cdmsgd_nesterov_update_2d(
            neighbors, w, g, v, alpha, mu,
            interpret=interpret))(weights, grad, momentum)
    return cdmsgd_nesterov_update_2d(neighbors, weights, grad, momentum,
                                     alpha, mu, scales=scales,
                                     self_buf=self_buf,
                                     mom_neighbors=mom_neighbors,
                                     mom_scales=mom_scales,
                                     interpret=interpret)


def cdadam_update_flat(neighbors, weights, grad, m, v, alpha, b1, b2, eps,
                       bc1, bc2, *, scales=None, self_buf=None,
                       mom_neighbors=None, mom_scales=None,
                       interpret: Optional[bool] = None):
    if isinstance(neighbors, SparseNeighbors):
        nb = neighbors
        if weights.ndim == 2:
            return jax.vmap(lambda w, sb, g, mi, vi: cdadam_update_sparse_2d(
                nb.values, nb.indices, nb.scales, w, g, mi, vi, alpha, b1,
                b2, eps, bc1, bc2, self_buf=sb, interpret=interpret))(
                    weights, self_buf, grad, m, v)
        return cdadam_update_sparse_2d(nb.values, nb.indices, nb.scales,
                                       weights, grad, m, v, alpha, b1, b2,
                                       eps, bc1, bc2, self_buf=self_buf,
                                       interpret=interpret)
    if weights.ndim == 2:
        if mom_neighbors is not None:
            return jax.vmap(lambda w, sb, g, mi, vi: cdadam_update_2d(
                neighbors, w, g, mi, vi, alpha, b1, b2, eps, bc1, bc2,
                scales=scales, self_buf=sb, mom_neighbors=mom_neighbors,
                mom_scales=mom_scales, interpret=interpret))(
                    weights, self_buf, grad, m, v)
        if scales is not None:
            return jax.vmap(lambda w, sb, g, mi, vi: cdadam_update_2d(
                neighbors, w, g, mi, vi, alpha, b1, b2, eps, bc1, bc2,
                scales=scales, self_buf=sb, interpret=interpret))(
                    weights, self_buf, grad, m, v)
        return jax.vmap(lambda w, g, mi, vi: cdadam_update_2d(
            neighbors, w, g, mi, vi, alpha, b1, b2, eps, bc1, bc2,
            interpret=interpret))(weights, grad, m, v)
    return cdadam_update_2d(neighbors, weights, grad, m, v, alpha, b1, b2,
                            eps, bc1, bc2, scales=scales, self_buf=self_buf,
                            mom_neighbors=mom_neighbors,
                            mom_scales=mom_scales, interpret=interpret)


# --------------------------------------------------------------------------
# pytree entry points (one kernel launch per dtype bucket)
# --------------------------------------------------------------------------


def _pack_all(spec, self_tree, neighbor_trees, *other_trees):
    """Pack self+neighbors into stacked (S, rows, 128) buckets + extras."""
    self_bufs = flatbuf.pack(self_tree, spec)
    nbr_bufs = [flatbuf.pack(t, spec) for t in neighbor_trees]
    stacked = [jnp.stack([sb] + [nb[i] for nb in nbr_bufs])
               for i, sb in enumerate(self_bufs)]
    others = [flatbuf.pack(t, spec) for t in other_trees]
    return stacked, others


@functools.partial(jax.jit, static_argnames=("interpret",))
def cdsgd_update_tree(
    self_tree: PyTree,
    neighbor_trees: Sequence[PyTree],
    weights: jnp.ndarray,          # (S,) — weight 0 applies to self_tree
    grad_tree: PyTree,
    alpha,
    *,
    interpret: Optional[bool] = None,
) -> PyTree:
    spec = flatbuf.make_flat_spec(self_tree)
    stacked, (grads,) = _pack_all(spec, self_tree, neighbor_trees, grad_tree)
    outs = [cdsgd_update_2d(nb, weights, g, alpha, interpret=interpret)
            for nb, g in zip(stacked, grads)]
    return flatbuf.unpack(outs, spec)


@functools.partial(jax.jit, static_argnames=("interpret",))
def cdmsgd_update_tree(
    self_tree: PyTree,
    neighbor_trees: Sequence[PyTree],
    weights: jnp.ndarray,
    grad_tree: PyTree,
    momentum_tree: PyTree,
    alpha,
    mu,
    *,
    interpret: Optional[bool] = None,
):
    spec = flatbuf.make_flat_spec(self_tree)
    stacked, (grads, moms) = _pack_all(
        spec, self_tree, neighbor_trees, grad_tree, momentum_tree)
    pairs = [cdmsgd_update_2d(nb, weights, g, v, alpha, mu, interpret=interpret)
             for nb, g, v in zip(stacked, grads, moms)]
    params = flatbuf.unpack([p for p, _ in pairs], spec)
    mom = flatbuf.unpack([v for _, v in pairs], spec)
    return params, mom


@functools.partial(jax.jit, static_argnames=("interpret",))
def cdmsgd_nesterov_update_tree(
    self_tree: PyTree,
    neighbor_trees: Sequence[PyTree],
    weights: jnp.ndarray,
    grad_tree: PyTree,            # evaluated at the current lookahead point
    momentum_tree: PyTree,
    alpha,
    mu,
    *,
    interpret: Optional[bool] = None,
):
    """Returns ``(params', momentum', lookahead')`` in one sweep per bucket."""
    spec = flatbuf.make_flat_spec(self_tree)
    stacked, (grads, moms) = _pack_all(
        spec, self_tree, neighbor_trees, grad_tree, momentum_tree)
    triples = [cdmsgd_nesterov_update_2d(nb, weights, g, v, alpha, mu,
                                         interpret=interpret)
               for nb, g, v in zip(stacked, grads, moms)]
    params = flatbuf.unpack([t[0] for t in triples], spec)
    mom = flatbuf.unpack([t[1] for t in triples], spec)
    look = flatbuf.unpack([t[2] for t in triples], spec)
    return params, mom, look


@functools.partial(jax.jit, static_argnames=("interpret",))
def cdadam_update_tree(
    self_tree: PyTree,
    neighbor_trees: Sequence[PyTree],
    weights: jnp.ndarray,
    grad_tree: PyTree,
    m_tree: PyTree,
    v_tree: PyTree,
    alpha,
    b1,
    b2,
    eps,
    bc1,
    bc2,
    *,
    interpret: Optional[bool] = None,
):
    """Returns ``(params', m', v')``; moments stay local, params mix."""
    spec = flatbuf.make_flat_spec(self_tree)
    stacked, (grads, ms, vs) = _pack_all(
        spec, self_tree, neighbor_trees, grad_tree, m_tree, v_tree)
    triples = [cdadam_update_2d(nb, weights, g, m, v, alpha, b1, b2, eps,
                                bc1, bc2, interpret=interpret)
               for nb, g, m, v in zip(stacked, grads, ms, vs)]
    params = flatbuf.unpack([t[0] for t in triples], spec)
    new_m = flatbuf.unpack([t[1] for t in triples], spec)
    new_v = flatbuf.unpack([t[2] for t in triples], spec)
    return params, new_m, new_v
