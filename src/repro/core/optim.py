"""Distributed optimizers: CDSGD, CDMSGD (Polyak & Nesterov) + baselines.

Every optimizer operates on an opaque parameter pytree and a ``CommOps``
bundle describing the collective operations available on the agent axis:

* ``comm.mix``  — ``w = Pi x`` over the fixed topology (paper eq. 5),
* ``comm.mean`` — exact global average (parameter-server emulation, used
  by FedAvg / centralized baselines),
* ``comm.lambda2 / lambdan`` — spectral constants for theory utilities.

The same optimizer code runs in both execution modes:

* **stacked simulation** — leaves carry a leading agent axis; ``comm`` is
  built by :func:`stacked_comm_ops` (dense ``Pi`` matmul);
* **sharded production** — inside ``shard_map``; ``comm`` is built from
  :func:`repro.core.consensus.make_sharded_mix_fn` (ppermute collectives).

Update rules (paper Algorithm 1-3):

    CDSGD:            x_{k+1} = Pi x_k - a_k g(x_k)
    CDMSGD (Polyak):  w = Pi x_k ; v_{k+1} = mu v_k - a_k g(x_k)
                      x_{k+1} = w + v_{k+1}
    CDMSGD (Nesterov): same, but g evaluated at x_k + mu v_k
    FedAvg:           E local SGD(+momentum) steps, then x <- mean(x)
    Centralized SGD:  g <- mean(g) every step; x_{k+1} = x_k - a_k g
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import consensus
from repro.core.schedules import Schedule, fixed
from repro.utils.tree import tree_axpy, tree_zeros_like

PyTree = Any
MixFn = Callable[[PyTree], PyTree]


@dataclasses.dataclass(frozen=True)
class CommOps:
    """Collective operations over the agent population."""

    mix: MixFn            # w = Pi x  (fixed topology)
    mean: MixFn           # exact global average
    n_agents: int
    lambda2: float = 0.0
    lambdan: float = 1.0
    # whole-model fused-update support (flat buffers + Pallas kernels);
    # None disables the optimizers' ``fused=True`` fast path.
    flat: Optional[consensus.FlatComm] = None


def identity_comm_ops() -> CommOps:
    """Single-agent degenerate comm (centralized training)."""
    ident = lambda t: t
    return CommOps(mix=ident, mean=ident, n_agents=1, lambda2=0.0, lambdan=1.0)


def stacked_comm_ops(topology, *, interpret: Optional[bool] = None,
                     exchange: str = "f32",
                     program: Optional[consensus.MixingProgram] = None) -> CommOps:
    """CommOps for agent-stacked pytrees (leading axis = agent).

    ``exchange`` sets the fused path's simulated wire precision
    (f32 | bf16 | int8 | fp8 — see :class:`repro.core.consensus.FlatComm`);
    ``program`` selects the mixing strategy of the fused path (time-varying
    ``Pi_t``, multi-round i-CDSGD, error feedback — see
    :class:`repro.core.consensus.MixingProgram`).
    """
    pi = jnp.asarray(topology.pi, dtype=jnp.float32)

    def mix(tree):
        return consensus.mix_pytree_stacked(pi, tree)

    def mean(tree):
        return jax.tree.map(lambda x: jnp.broadcast_to(jnp.mean(x, 0, keepdims=True), x.shape), tree)

    return CommOps(mix=mix, mean=mean, n_agents=topology.n_agents,
                   lambda2=topology.lambda2, lambdan=topology.lambdan,
                   flat=consensus.stacked_flat_comm(topology, interpret=interpret,
                                                    exchange=exchange,
                                                    program=program))


def sharded_comm_ops(topology, axis_name: str) -> CommOps:
    """CommOps for use inside shard_map over ``axis_name``."""
    mix = consensus.make_sharded_mix_fn(topology, axis_name)
    mean = consensus.make_sharded_mean_fn(axis_name)
    return CommOps(mix=mix, mean=mean, n_agents=topology.n_agents,
                   lambda2=topology.lambda2, lambdan=topology.lambdan)


def factored_comm_ops(factored: consensus.FactoredMix, axis_names) -> CommOps:
    mix = factored.make_mix_fn()
    mean = consensus.make_sharded_mean_fn(tuple(axis_names))
    return CommOps(mix=mix, mean=mean, n_agents=factored.n_agents,
                   lambda2=factored.lambda2, lambdan=factored.lambdan)


# --------------------------------------------------------------------------
# Optimizer protocol
# --------------------------------------------------------------------------


class OptState(NamedTuple):
    step: jnp.ndarray      # scalar int32
    inner: Any             # optimizer-specific (momentum, adam moments, ...)
    # in-flight wire buffers of the overlap schedule: one (quantized
    # payload, row scales) pair per flat bucket, quantized from the params
    # at the *previous* step (see repro.core.engine).  () under
    # schedule="sync" — the StepProgram engine owns filling/refreshing it.
    wire: Any = ()
    # error-feedback residuals (MixingProgram(error_feedback=True)): one
    # f32 buffer per flat bucket carrying the compression error of the
    # last quantized wire payload; local state, never crosses the wire.
    # () when error feedback is off — the engine owns filling/refreshing.
    residual: Any = ()
    # warm-start state of the rank-r wire compressor (compressor="rank:r"):
    # one (A, 128, r) / (1, 128, r) orthonormal basis per flat bucket,
    # carried like the wire and refreshed by compress_ef each step.  ()
    # for every other program — the engine owns filling/refreshing.
    qwarm: Any = ()


@dataclasses.dataclass(frozen=True)
class ExchangeResult:
    """Kernel-ready mixing operands produced by the engine's phase pipeline.

    ``DistributedOptimizer.update(..., exchanged=...)`` consumes this
    instead of calling ``comm.flat.gather`` itself: the StepProgram engine
    ran pack / quantize / exchange as separately scheduled phases (possibly
    against one-step-stale wire state) and hands the fused kernels their
    operands.  ``selfs`` is always the *fresh* native-precision packed
    params — the self term never crosses the wire and never goes stale.

    With ``momentum_mixing="mixed"`` the wire carried a second payload
    tree: ``mom_neighbors`` / ``mom_scales`` / ``mom_selfs`` are the
    momentum buffer's exchanged operands (same weights as the params —
    one agent-interaction matrix mixes both), ``None`` otherwise.
    ``mom_selfs`` is the momentum buffer the fused kernels mix the self
    weight against — the freshly packed momentum (single round) or the
    round-``k-1`` partially mixed buffer (multi-round), exactly mirroring
    ``selfs``.

    Sparse operand variant (``MixingProgram.sparse_update`` with the top-k
    compressor): a bucket's ``neighbors`` entry is a
    :class:`repro.kernels.consensus_update.ops.SparseNeighbors` tuple (the
    raw ``TopKWire`` compact fields) and its ``scales`` entry is ``None``
    — the per-compact-row scales ride inside the tuple and the fused
    kernels scatter-accumulate straight from the wire instead of reading
    a dense decompressed stack.
    """

    spec: Any                     # flatbuf.FlatSpec of the param pytree
    neighbors: Sequence           # per-bucket wire payload stacks
    weights: jnp.ndarray          # self-separated weights (self first)
    scales: Sequence              # per-bucket row-scale stacks
    selfs: Sequence               # per-bucket fresh native self buffers
    # the mixed-momentum payload's operands (momentum_mixing="mixed" only)
    mom_neighbors: Optional[Sequence] = None
    mom_scales: Optional[Sequence] = None
    mom_selfs: Optional[Sequence] = None

    @property
    def momentum_mixed(self) -> bool:
        return self.mom_neighbors is not None


class DistributedOptimizer:
    """Base: subclasses implement `init_inner` and `apply`.

    ``fused=True`` (consensus optimizers only) routes the update through the
    flat-buffer Pallas path when the ``CommOps`` carries a
    :class:`repro.core.consensus.FlatComm`: the whole model is packed into
    dtype-bucketed ``(rows, 128)`` buffers and updated with one kernel
    launch per bucket (see :mod:`repro.kernels.consensus_update`).  When the
    comm has no flat support the optimizer falls back to the per-leaf
    reference ``apply`` with identical semantics.  Pallas interpret-vs-
    compiled mode is owned by the ``FlatComm`` (True on CPU, False on TPU).
    """

    #: declared in-place contract of the fused path: how many
    #: ``(input, output)`` ``input_output_aliases`` pairs every fused bucket
    #: launch must carry (params always alias in place; momentum-family
    #: optimizers alias their inner buffers too).  ``None`` = no fused
    #: in-place contract (baselines / reference-path optimizers).  The
    #: static checker's alias-coverage pass audits the traced step against
    #: this number (see :mod:`repro.analysis.staticcheck`).
    fused_alias_pairs = None

    def __init__(self, schedule: Schedule | float, *, fused: bool = False):
        self.schedule: Schedule = fixed(schedule) if isinstance(schedule, (int, float)) else schedule
        self.fused = fused

    # -- public API --------------------------------------------------------
    def init(self, params: PyTree) -> OptState:
        return OptState(step=jnp.zeros((), jnp.int32), inner=self.init_inner(params))

    def grad_params(self, params: PyTree, state: OptState) -> PyTree:
        """Point at which the caller should evaluate the gradient."""
        return params

    def update(self, params: PyTree, grads: PyTree, state: OptState,
               comm: CommOps, *, exchanged: Optional[ExchangeResult] = None):
        """One optimizer step.

        ``exchanged`` carries pre-computed mixing operands from the
        StepProgram engine's pack/quantize/exchange phases (the overlap
        schedule's one-step-stale wire); when None the fused path gathers
        synchronously via ``comm.flat``.  The wire and residual fields of
        the state are passed through untouched — the engine refreshes them.
        """
        alpha = self.schedule(state.step)
        # fused is a perf hint: optimizers without a fused implementation
        # (baselines) and comms without flat support use the reference path.
        has_fused = type(self).apply_fused is not DistributedOptimizer.apply_fused
        if self.fused and has_fused and comm.flat is not None:
            new_params, new_inner = self.apply_fused(
                params, grads, state.inner, alpha, comm, state.step,
                exchanged=exchanged)
        elif exchanged is not None:
            raise ValueError(
                f"{type(self).__name__} cannot consume exchanged operands: "
                "the engine's exchange phase feeds fused optimizers only")
        else:
            new_params, new_inner = self.apply(params, grads, state.inner, alpha, comm, state.step)
        return new_params, OptState(step=state.step + 1, inner=new_inner,
                                    wire=state.wire, residual=state.residual,
                                    qwarm=state.qwarm)

    def state_specs(self, param_specs: PyTree) -> "OptState":
        """PartitionSpec tree mirroring init() (for pjit in_shardings)."""
        from jax.sharding import PartitionSpec
        return OptState(step=PartitionSpec(), inner=self.inner_specs(param_specs))

    def inner_specs(self, param_specs: PyTree) -> Any:
        return ()

    # -- to implement -------------------------------------------------------
    def init_inner(self, params: PyTree) -> Any:
        return ()

    def apply(self, params, grads, inner, alpha, comm: CommOps, step):
        raise NotImplementedError

    def apply_fused(self, params, grads, inner, alpha, comm: CommOps, step,
                    *, exchanged: Optional[ExchangeResult] = None):
        """Flat-buffer fast path; same contract as ``apply``."""
        raise NotImplementedError(f"{type(self).__name__} has no fused path")

    @property
    def uses_consensus(self) -> bool:
        return True

    # -- momentum-consensus mixing (MixingProgram momentum_mixing="mixed") --
    @property
    def has_mixable_momentum(self) -> bool:
        """True when the optimizer carries a momentum-like buffer the wire
        can mix alongside the params (CDMSGD family's ``v``, CDAdam's first
        moment).  Optimizers without one reject ``momentum_mixing``."""
        return False

    def momentum_tree(self, inner) -> Optional[PyTree]:
        """The momentum pytree to put on the wire (param-structured), or
        ``None``.  The engine packs it next to the params when the comm's
        program mixes momentum."""
        return None


# --------------------------------------------------------------------------
# The paper's algorithms
# --------------------------------------------------------------------------


def _flat_setup(fl, params, step, *trees, exchanged=None):
    """Pack params (+ same-structured trees) against one shared FlatSpec.

    ``step`` seeds the stochastic rounding of quantized exchanges (the
    gather decorrelates it per bucket/agent); unquantized exchanges ignore
    it and return ``None`` scales.  When the engine already ran the
    pack/quantize/exchange phases (``exchanged`` given) only the extra
    trees are packed here; the mixing operands come from the phase outputs.
    """
    if exchanged is not None:
        others = [fl.pack(t, exchanged.spec) for t in trees]
        return (exchanged.spec, exchanged.neighbors, exchanged.weights,
                exchanged.scales, exchanged.selfs, others)
    if fl.program is not None and fl.program.momentum_mixing == "mixed":
        # the momentum payload lives on the engine's staged pipeline (the
        # engine packs params + momentum and splits the exchanged operands);
        # a bare gather here would see the params-only bucket list
        raise ValueError(
            "momentum_mixing='mixed' needs the StepProgram engine's staged "
            "exchange (CollaborativeTrainer / build_train_step); the "
            "optimizer cannot gather the momentum payload itself")
    spec = fl.spec(params)
    bufs = fl.pack(params, spec)
    others = [fl.pack(t, spec) for t in trees]
    nbrs, weights, scales, selfs = fl.gather(bufs, jnp.asarray(step, jnp.int32))
    return spec, nbrs, weights, scales, selfs, others


class CDSGD(DistributedOptimizer):
    """Algorithm 1: ``x_{k+1} = Pi x_k - alpha g(x_k)``."""

    fused_alias_pairs = 1   # params in-place

    def apply(self, params, grads, inner, alpha, comm, step):
        mixed = comm.mix(params)
        # final .astype keeps bf16 params bf16 (traced f32 alpha promotes)
        new_params = jax.tree.map(
            lambda w, g: (w - alpha * g.astype(w.dtype)).astype(w.dtype),
            mixed, grads)
        return new_params, inner

    def apply_fused(self, params, grads, inner, alpha, comm, step, *,
                    exchanged=None):
        from repro.kernels.consensus_update import ops as kops
        fl = comm.flat
        spec, nbrs, w, scs, sfs, (g,) = _flat_setup(fl, params, step, grads,
                                                    exchanged=exchanged)
        outs = [kops.cdsgd_update_flat(nb, w, gb, alpha, scales=sc,
                                       self_buf=sf, interpret=fl.interpret)
                for nb, sc, sf, gb in zip(nbrs, scs, sfs, g)]
        return fl.unpack(outs, spec), inner


class CDMSGD(DistributedOptimizer):
    """Algorithm 2 (Polyak momentum):
    ``v' = mu v - alpha g(x); x' = Pi x + v'``.

    With ``momentum_mixing="mixed"`` the momentum buffer rides the wire and
    is mixed with the same ``Pi``: ``v' = mu (Pi v) - alpha g`` (momentum-
    accelerated consensus, 2010.11166) — the consensus and momentum
    dynamics then contract together instead of fighting, which is what
    stabilizes quantized exchanges at large step sizes.
    """

    fused_alias_pairs = 2   # params + momentum v in-place

    def __init__(self, schedule, mu: float = 0.9, **kw):
        super().__init__(schedule, **kw)
        self.mu = mu

    def init_inner(self, params):
        return tree_zeros_like(params)

    def inner_specs(self, param_specs):
        return param_specs

    @property
    def has_mixable_momentum(self):
        return True

    def momentum_tree(self, inner):
        return inner

    def apply(self, params, grads, v, alpha, comm, step):
        mixed = comm.mix(params)
        new_v = jax.tree.map(
            lambda vi, g: (self.mu * vi - alpha * g.astype(vi.dtype)).astype(vi.dtype),
            v, grads)
        new_params = jax.tree.map(lambda w, nv: (w + nv).astype(w.dtype), mixed, new_v)
        return new_params, new_v

    def apply_fused(self, params, grads, v, alpha, comm, step, *,
                    exchanged=None):
        from repro.kernels.consensus_update import ops as kops
        fl = comm.flat
        if exchanged is not None and exchanged.momentum_mixed:
            # mixed-momentum operand form: the momentum self buffer is the
            # engine's mom_selfs (= packed v, or the round-(k-1) partially
            # mixed v under a multi-round program), not a fresh pack of v
            spec = exchanged.spec
            g = fl.pack(grads, spec)
            pairs = [kops.cdmsgd_update_flat(nb, exchanged.weights, gb, vi,
                                             alpha, self.mu, scales=sc,
                                             self_buf=sf, mom_neighbors=mnb,
                                             mom_scales=msc,
                                             interpret=fl.interpret)
                     for nb, sc, sf, gb, vi, mnb, msc in zip(
                         exchanged.neighbors, exchanged.scales,
                         exchanged.selfs, g, exchanged.mom_selfs,
                         exchanged.mom_neighbors, exchanged.mom_scales)]
            new_params = fl.unpack([p for p, _ in pairs], spec)
            new_v = fl.unpack([nv for _, nv in pairs], spec)
            return new_params, new_v
        spec, nbrs, w, scs, sfs, (g, vb) = _flat_setup(fl, params, step, grads,
                                                       v, exchanged=exchanged)
        pairs = [kops.cdmsgd_update_flat(nb, w, gb, vi, alpha, self.mu,
                                         scales=sc, self_buf=sf,
                                         interpret=fl.interpret)
                 for nb, sc, sf, gb, vi in zip(nbrs, scs, sfs, g, vb)]
        new_params = fl.unpack([p for p, _ in pairs], spec)
        new_v = fl.unpack([nv for _, nv in pairs], spec)
        return new_params, new_v


class CDMSGDNesterov(CDMSGD):
    """Algorithm 3: gradient evaluated at the lookahead point x + mu v.

    Unfused, the state is the momentum ``v`` and the lookahead is a
    ``tree_axpy`` recomputed before every backward.  Fused, the state is
    ``(v, lookahead)``: the kernel emits ``x' + mu v'`` in the same HBM
    sweep as the update, so ``grad_params`` is a free state lookup.
    """

    fused_alias_pairs = 2   # params + momentum v in-place (lookahead is new)

    def init_inner(self, params):
        if self.fused:
            # lookahead_0 = x_0 + mu * 0 = x_0 — copied, NOT aliased: the
            # trainer donates params and optimizer state to the jitted
            # step, and donating the same buffer through both arguments is
            # a runtime error on the very first step
            return (tree_zeros_like(params), jax.tree.map(jnp.copy, params))
        return tree_zeros_like(params)

    def inner_specs(self, param_specs):
        if self.fused:
            return (param_specs, param_specs)
        return param_specs

    def grad_params(self, params, state):
        if self.fused:
            return state.inner[1]
        return tree_axpy(self.mu, state.inner, params)

    def momentum_tree(self, inner):
        return inner[0] if self.fused else inner

    def apply(self, params, grads, inner, alpha, comm, step):
        # reference path for fused-shaped state (comm without flat support)
        if self.fused:
            v, _ = inner
            new_params, new_v = super().apply(params, grads, v, alpha, comm, step)
            look = tree_axpy(self.mu, new_v, new_params)
            return new_params, (new_v, look)
        return super().apply(params, grads, inner, alpha, comm, step)

    def apply_fused(self, params, grads, inner, alpha, comm, step, *,
                    exchanged=None):
        from repro.kernels.consensus_update import ops as kops
        fl = comm.flat
        v, _ = inner
        if exchanged is not None and exchanged.momentum_mixed:
            spec = exchanged.spec
            g = fl.pack(grads, spec)
            triples = [kops.cdmsgd_nesterov_update_flat(
                           nb, exchanged.weights, gb, vi, alpha, self.mu,
                           scales=sc, self_buf=sf, mom_neighbors=mnb,
                           mom_scales=msc, interpret=fl.interpret)
                       for nb, sc, sf, gb, vi, mnb, msc in zip(
                           exchanged.neighbors, exchanged.scales,
                           exchanged.selfs, g, exchanged.mom_selfs,
                           exchanged.mom_neighbors, exchanged.mom_scales)]
        else:
            spec, nbrs, w, scs, sfs, (g, vb) = _flat_setup(
                fl, params, step, grads, v, exchanged=exchanged)
            triples = [kops.cdmsgd_nesterov_update_flat(nb, w, gb, vi, alpha,
                                                        self.mu, scales=sc,
                                                        self_buf=sf,
                                                        interpret=fl.interpret)
                       for nb, sc, sf, gb, vi in zip(nbrs, scs, sfs, g, vb)]
        new_params = fl.unpack([t[0] for t in triples], spec)
        new_v = fl.unpack([t[1] for t in triples], spec)
        look = fl.unpack([t[2] for t in triples], spec)
        return new_params, (new_v, look)


class CDAdam(DistributedOptimizer):
    """Beyond-paper extension: consensus mixing of parameters with local
    Adam moments (``x' = Pi x - alpha * adam_dir(g)``).  Moments stay local
    (they are statistics of the *local* data distribution); parameters mix.

    ``momentum_mixing="mixed"`` mixes the FIRST moment over the wire
    (``m' = b1 (Pi m) + (1-b1) g``, the Adam analog of 2010.11166's
    momentum-accelerated consensus); the second moment stays local — it is
    a positive per-coordinate scale, not a direction, and mixing it would
    skew the bias correction.
    """

    fused_alias_pairs = 3   # params + both Adam moments in-place

    def __init__(self, schedule, b1=0.9, b2=0.999, eps=1e-8, **kw):
        super().__init__(schedule, **kw)
        self.b1, self.b2, self.eps = b1, b2, eps

    def init_inner(self, params):
        return (tree_zeros_like(params), tree_zeros_like(params))

    def inner_specs(self, param_specs):
        return (param_specs, param_specs)

    @property
    def has_mixable_momentum(self):
        return True

    def momentum_tree(self, inner):
        return inner[0]

    def apply(self, params, grads, inner, alpha, comm, step):
        m, v = inner
        t = (step + 1).astype(jnp.float32)
        new_m = jax.tree.map(lambda mi, g: self.b1 * mi + (1 - self.b1) * g.astype(mi.dtype), m, grads)
        new_v = jax.tree.map(lambda vi, g: self.b2 * vi + (1 - self.b2) * jnp.square(g.astype(vi.dtype)), v, grads)
        bc1 = 1.0 - self.b1**t
        bc2 = 1.0 - self.b2**t
        mixed = comm.mix(params)
        new_params = jax.tree.map(
            lambda w, mi, vi: w - (alpha * (mi / bc1) / (jnp.sqrt(vi / bc2) + self.eps)).astype(w.dtype),
            mixed, new_m, new_v)
        return new_params, (new_m, new_v)

    def apply_fused(self, params, grads, inner, alpha, comm, step, *,
                    exchanged=None):
        from repro.kernels.consensus_update import ops as kops
        fl = comm.flat
        m, v = inner
        t = (step + 1).astype(jnp.float32)
        bc1 = 1.0 - self.b1**t
        bc2 = 1.0 - self.b2**t
        if exchanged is not None and exchanged.momentum_mixed:
            spec = exchanged.spec
            g = fl.pack(grads, spec)
            vb = fl.pack(v, spec)
            triples = [kops.cdadam_update_flat(
                           nb, exchanged.weights, gb, mi, vi, alpha, self.b1,
                           self.b2, self.eps, bc1, bc2, scales=sc,
                           self_buf=sf, mom_neighbors=mnb, mom_scales=msc,
                           interpret=fl.interpret)
                       for nb, sc, sf, gb, mi, vi, mnb, msc in zip(
                           exchanged.neighbors, exchanged.scales,
                           exchanged.selfs, g, exchanged.mom_selfs, vb,
                           exchanged.mom_neighbors, exchanged.mom_scales)]
        else:
            spec, nbrs, w, scs, sfs, (g, mb, vb) = _flat_setup(
                fl, params, step, grads, m, v, exchanged=exchanged)
            triples = [kops.cdadam_update_flat(nb, w, gb, mi, vi, alpha,
                                               self.b1, self.b2, self.eps,
                                               bc1, bc2, scales=sc,
                                               self_buf=sf,
                                               interpret=fl.interpret)
                       for nb, sc, sf, gb, mi, vi in zip(nbrs, scs, sfs, g,
                                                         mb, vb)]
        new_params = fl.unpack([t_[0] for t_ in triples], spec)
        new_m = fl.unpack([t_[1] for t_ in triples], spec)
        new_v = fl.unpack([t_[2] for t_ in triples], spec)
        return new_params, (new_m, new_v)


# --------------------------------------------------------------------------
# Baselines
# --------------------------------------------------------------------------


class CentralizedSGD(DistributedOptimizer):
    """Data-parallel SGD: grads averaged across agents every step."""

    def apply(self, params, grads, inner, alpha, comm, step):
        g = comm.mean(grads)
        return jax.tree.map(
            lambda x, gi: (x - alpha * gi.astype(x.dtype)).astype(x.dtype),
            params, g), inner

    @property
    def uses_consensus(self):
        return False


class CentralizedMSGD(DistributedOptimizer):
    """Data-parallel Polyak-momentum SGD (paper's 'MSGD')."""

    def __init__(self, schedule, mu: float = 0.9, **kw):
        super().__init__(schedule, **kw)
        self.mu = mu

    def init_inner(self, params):
        return tree_zeros_like(params)

    def inner_specs(self, param_specs):
        return param_specs

    def apply(self, params, grads, v, alpha, comm, step):
        g = comm.mean(grads)
        new_v = jax.tree.map(
            lambda vi, gi: (self.mu * vi - alpha * gi.astype(vi.dtype)).astype(vi.dtype),
            v, g)
        return jax.tree.map(lambda x, nv: (x + nv).astype(x.dtype), params, new_v), new_v

    @property
    def uses_consensus(self):
        return False


class FedAvg(DistributedOptimizer):
    """Federated Averaging [McMahan et al. 2016] with C=1 (all clients).

    Each agent takes local SGD(+momentum) steps; every ``local_steps``
    steps the parameters AND the momentum buffer are replaced by their
    global averages — a brute-force consensus through a central parameter
    server (paper §5.1 discussion).  The averaging collective runs under
    ``lax.cond`` gated on the sync step, so ``local_steps = E > 1`` pays
    the all-reduce once per E steps instead of every step (it used to run
    unconditionally with the result discarded on non-sync steps), and the
    momentum average keeps the local ``v`` buffers from silently diverging
    across agents between syncs — without it each agent's momentum keeps
    pulling toward its own shard after every sync, which is NOT the E-step
    server-side FedAvg recurrence (asserted against the hand-rolled
    reference in tests/test_optim.py).

    ``faults`` (a :class:`repro.core.faults.FaultSchedule`) enables
    **partial participation**: at a sync step whose fault table marks
    agents as straggling, the server averages over the ``k``-of-``N``
    *present* agents only (masked sum renormalized by ``N/k``) instead of
    silently including the absent agents' stale params, and broadcasts the
    result to everyone — the deterministic analog of client sampling.  A
    sync step where nobody is present keeps the local params (no sync
    happened).  The momentum average is masked identically.  Agent-stacked
    execution mode (the FedAvg baseline's home); asserted against a
    hand-rolled k-of-N server reference in tests/test_optim.py.
    """

    def __init__(self, schedule, local_steps: int = 1, mu: float = 0.0,
                 faults=None, **kw):
        super().__init__(schedule, **kw)
        self.local_steps = int(local_steps)
        self.mu = mu
        self.faults = faults
        if faults is not None:
            faults.validate()
            # presence = NOT straggling at the sync step (link drops are a
            # neighbor-exchange concept; the server round-trip only cares
            # whether the client reported in)
            self._present = jnp.asarray(
                (~faults.straggle).astype("float32"))     # (P, A)
            self._fault_period = faults.period

    def init_inner(self, params):
        return tree_zeros_like(params)

    def inner_specs(self, param_specs):
        return param_specs

    def apply(self, params, grads, v, alpha, comm, step):
        new_v = jax.tree.map(
            lambda vi, g: (self.mu * vi - alpha * g.astype(vi.dtype)).astype(vi.dtype),
            v, grads)
        local = jax.tree.map(lambda x, nv: (x + nv).astype(x.dtype), params, new_v)

        def sync(args):
            p, vv = args
            if self.faults is None:
                # mu == 0: v is identically -alpha g, already consumed —
                # skip the second collective
                return comm.mean(p), (comm.mean(vv) if self.mu else vv)
            tp = jnp.mod(jnp.asarray(step, jnp.int32), self._fault_period)
            m = jnp.take(self._present, tp, axis=0)       # (A,) f32
            k = jnp.sum(m)
            scale = m.shape[0] / jnp.maximum(k, 1.0)

            def masked_mean(tree):
                wsum = comm.mean(jax.tree.map(
                    lambda x: x * m.reshape((-1,) + (1,) * (x.ndim - 1)),
                    tree))
                return jax.tree.map(
                    lambda mn, x: jnp.where(k > 0, (mn * scale).astype(x.dtype), x),
                    wsum, tree)

            return masked_mean(p), (masked_mean(vv) if self.mu else vv)

        if self.local_steps <= 1:
            return sync((local, new_v))
        do_avg = (step + 1) % self.local_steps == 0
        return lax.cond(do_avg, sync, lambda args: args, (local, new_v))

    @property
    def uses_consensus(self):
        return False


class GossipSGD(DistributedOptimizer):
    """Gossip SGD baseline [Jin et al. 2016, paper Table 1 row 4].

    Decentralized but *unconstrained* communication: each step every agent
    averages with one uniformly random partner (mixing matrix
    ``W_k = (I + P_k)/2`` for a random permutation ``P_k`` — doubly
    stochastic, changes every step), then takes a local SGD step.  Contrast
    with CDSGD where the communication graph is FIXED — the paper's whole
    point is that random pairwise exchange is infeasible in mesh-constrained
    deployments.  Stacked-simulation execution mode only.
    """

    def __init__(self, schedule, n_agents: int, seed: int = 0, **kw):
        super().__init__(schedule, **kw)
        self.n_agents = n_agents
        self.seed = seed

    def apply(self, params, grads, inner, alpha, comm, step):
        key = jax.random.fold_in(jax.random.PRNGKey(self.seed), step)
        perm = jax.random.permutation(key, self.n_agents)

        def mix_leaf(x):
            return 0.5 * (x + x[perm])

        mixed = jax.tree.map(mix_leaf, params)
        return jax.tree.map(
            lambda w, g: (w - alpha * g.astype(w.dtype)).astype(w.dtype),
            mixed, grads), inner


class TimeVaryingCDSGD(DistributedOptimizer):
    """CDSGD over a time-varying topology (paper future work §6.ii).

    Cycles through a list of agent-interaction matrices ``Pi_k`` (one per
    step, modulo the list length).  Consensus requires only that the
    *union* graph is connected — e.g. alternating horizontal/vertical line
    graphs on a grid — which the tests verify.  Stacked execution mode.
    """

    def __init__(self, schedule, topologies, **kw):
        super().__init__(schedule, **kw)
        import numpy as _np
        self.pis = jnp.asarray(_np.stack([t.pi for t in topologies]), jnp.float32)

    def apply(self, params, grads, inner, alpha, comm, step):
        pi = self.pis[step % self.pis.shape[0]]
        mixed = consensus.mix_pytree_stacked(pi, params)
        return jax.tree.map(
            lambda w, g: (w - alpha * g.astype(w.dtype)).astype(w.dtype),
            mixed, grads), inner


def make_optimizer(name: str, schedule, **kw) -> DistributedOptimizer:
    """Registry used by configs / CLI (`--optimizer cdsgd` etc.)."""
    name = name.lower()
    table = {
        "cdsgd": CDSGD,
        "cdmsgd": CDMSGD,
        "cdmsgd_nesterov": CDMSGDNesterov,
        "cdadam": CDAdam,
        "sgd": CentralizedSGD,
        "msgd": CentralizedMSGD,
        "fedavg": FedAvg,
        "gossip": GossipSGD,
        "cdsgd_tv": TimeVaryingCDSGD,
    }
    if name not in table:
        raise ValueError(f"unknown optimizer {name!r}; available: {sorted(table)}")
    return table[name](schedule, **kw)
