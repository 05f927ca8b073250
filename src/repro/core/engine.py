"""StepProgram: one phase-pipeline for both execution modes.

Every collaborative training step — stacked simulation
(:class:`repro.core.trainer.CollaborativeTrainer`) and sharded production
(:func:`repro.launch.steps.build_train_step`) — is the same five named
phases; this module is their single definition and the front-ends only
supply mode-specific comm ops and (for the sharded mode) the ``shard_map``
wrapper around the update group:

* ``grad``     — one vmapped backward over the leading agent axis,
  including the gradient-accumulation ``scan`` when ``microbatches > 1``
  (:func:`make_grad_phase`);
* ``pack``     — the parameter pytree into dtype-bucketed ``(rows, 128)``
  flat buffers (:mod:`repro.core.flatbuf`);
* ``quantize`` — stochastic-rounding int8/fp8 wire payloads + per-row f32
  scales (``FlatComm.quantize_stage``; f32/bf16 wires cast + unit scales);
* ``exchange`` — neighbor mixing operands: dense-``Pi`` stacks in the
  stacked mode, one circulant ``lax.ppermute`` per shift per bucket in the
  sharded mode (``FlatComm.exchange_stage``);
* ``update``   — the fused Pallas kernel per bucket (or the reference
  per-leaf path for unfused optimizers).

Schedules
---------
``schedule="sync"`` (default) runs quantize -> exchange -> update on the
*current* params inside the optimizer's ``comm.flat.gather`` — today's
semantics, bit-for-bit.

``schedule="overlap"`` pipelines the exchange one step deep: the quantized
buckets + row scales live double-buffered in ``OptState.wire``, so step
``t`` exchanges the payload quantized at step ``t-1`` while the backward of
step ``t`` runs.  The update becomes the one-step-stale mixing

    x^i_{t+1} = pi_ii x^i_t + sum_{j != i} pi_ij q(x^j_{t-1}) - alpha g^i_t

with the self term always fresh and full precision (it never crosses the
wire).  The staleness rides entirely in *which* buffers feed the existing
fused kernels' self-separated ``(self, wire payloads)`` weight form — no
new kernel variants.  Lian et al. (1705.09056) show decentralized SGD
tolerates exactly this stale/pipelined communication at an unchanged
convergence rate; Jiang et al. (1805.12120) generalize the mixing schedule.
The payoff is structural: the ``ppermute``\\ s consume only carried
optimizer state, so the collective is off the grad->update critical path —
:func:`exchange_dependency_report` proves it from the jaxpr and the dryrun
records it.

Mixing strategies
-----------------
What one "exchange" means per step is owned by the comm's
:class:`repro.core.consensus.MixingStrategy` (configured by a
``MixingProgram``): fixed ``Pi``, step-indexed time-varying ``Pi_t``, or
``k`` inner consensus rounds.  The engine only decides *which wire feeds
round 1* — fresh (sync) or carried (overlap; rounds ``2..k`` always stay
on the critical path) — and threads the error-feedback residual state
(``OptState.residual``) through the round-1 quantizer when the program
asks for it.  With ``momentum_mixing="mixed"`` the engine also packs the
optimizer's momentum buffer (``DistributedOptimizer.momentum_tree``) as
a second wire payload next to the params — the strategy exchanges both
with the same weights and the engine splits the operands back into the
:class:`ExchangeResult` payload groups the fused kernels consume.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.extend.core import ClosedJaxpr, Jaxpr, Literal

from repro.core import consensus
from repro.core.optim import (
    CommOps,
    DistributedOptimizer,
    ExchangeResult,
    OptState,
)

PyTree = Any

PHASES = ("grad", "pack", "quantize", "exchange", "update")
SCHEDULES = ("sync", "overlap")


# --------------------------------------------------------------------------
# grad phase (shared by both execution modes)
# --------------------------------------------------------------------------


def make_grad_phase(agent_loss: Callable, microbatches: int = 1) -> Callable:
    """The ``grad`` phase: ``(gp, batch) -> ((losses, metrics), grads)``.

    ``agent_loss(params, batch) -> (loss, metrics)`` is the single-agent
    loss; the phase vmaps its value_and_grad over the leading agent axis.
    ``microbatches > 1`` splits the per-agent batch dim and accumulates
    gradients in f32 over a ``lax.scan`` (losses/metrics keep the leading
    microbatch axis; callers reduce with ``jnp.mean`` either way).
    """
    grad_fn = jax.vmap(jax.value_and_grad(agent_loss, has_aux=True))
    if microbatches == 1:
        return grad_fn

    def grad_phase(gp, batch):
        # gradient accumulation: (A, B, ...) -> scan over (M, A, B/M, ...)
        def split(x):
            a, b = x.shape[:2]
            return jnp.moveaxis(
                x.reshape(a, microbatches, b // microbatches, *x.shape[2:]), 1, 0)

        mb = jax.tree.map(split, batch)
        zero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), gp)

        def mb_step(acc, one):
            (l, met), g = grad_fn(gp, one)
            acc = jax.tree.map(lambda a, gi: a + gi.astype(jnp.float32), acc, g)
            return acc, (l, met)

        gsum, (losses, metrics) = jax.lax.scan(mb_step, zero, mb)
        grads = jax.tree.map(lambda g: g / microbatches, gsum)
        return (losses, metrics), grads

    return grad_phase


# --------------------------------------------------------------------------
# update phase group (pack / quantize / exchange / update)
# --------------------------------------------------------------------------


def _check_fused_flat(optimizer: DistributedOptimizer, comm: CommOps,
                      what: str) -> consensus.FlatComm:
    """``what`` needs the staged flat-buffer path; fail with the reason."""
    fl = comm.flat
    if fl is None or fl.exchange_stage is None or fl.strategy is None:
        raise ValueError(
            f"{what} needs a flat-buffer comm with split "
            "quantize/exchange stages (stacked_comm_ops / "
            "make_local_fused_comm with mixing='ppermute_fused')")
    has_fused = type(optimizer).apply_fused is not DistributedOptimizer.apply_fused
    if not (getattr(optimizer, "fused", False) and has_fused):
        raise ValueError(
            f"{what} needs a fused=True consensus optimizer; "
            f"{type(optimizer).__name__}(fused="
            f"{getattr(optimizer, 'fused', False)}) has no fused update to "
            "feed the staged exchange into")
    return fl


def check_overlap_support(optimizer: DistributedOptimizer,
                          comm: CommOps) -> consensus.FlatComm:
    """Overlap needs the staged flat-buffer path; fail with the reason."""
    return _check_fused_flat(optimizer, comm, "schedule='overlap'")


def check_program_support(optimizer: DistributedOptimizer,
                          comm: CommOps) -> Optional[consensus.FlatComm]:
    """A non-trivial MixingProgram needs the staged flat-buffer path.

    Time-varying / multi-round / error-feedback / momentum mixing all live
    on the flat-buffer strategy layer; a non-fused optimizer's reference
    path would silently mix a fixed dense ``Pi`` instead, so this fails
    loudly at config time.  ``momentum_mixing="mixed"`` additionally needs
    an optimizer that *has* a mixable momentum buffer (CDMSGD family /
    CDAdam's first moment).  Trivial (or absent) programs return
    ``comm.flat`` unchecked — every optimizer supports them.
    """
    fl = comm.flat
    if fl is None or fl.program is None or fl.program.is_trivial:
        return fl
    p = fl.program
    what = (f"mixing strategy {p.strategy!r} (rounds={p.rounds}, "
            f"error_feedback={p.error_feedback}, "
            f"momentum_mixing={p.momentum_mixing})")
    fl = _check_fused_flat(optimizer, comm, what)
    if p.momentum_mixing == "mixed" and not optimizer.has_mixable_momentum:
        raise ValueError(
            f"momentum_mixing='mixed' puts the momentum buffer on the wire, "
            f"but {type(optimizer).__name__} has no mixable momentum state "
            "(use CDMSGD, CDMSGDNesterov, or CDAdam)")
    return fl


def _mixed_momentum(fl: Optional[consensus.FlatComm]) -> bool:
    return (fl is not None and fl.program is not None
            and fl.program.momentum_mixing == "mixed")


def _pack_wire_bufs(fl: consensus.FlatComm, params, momentum=None):
    """Pack the wire payload bucket list: params, then the mixed momentum.

    ``momentum=None`` with a momentum-mixing program packs zeros via
    :func:`repro.core.consensus.widen_with_momentum` (the
    state-initializer convention, ``v_{-1} := v_0 = 0``); a momentum tree
    packs against the SAME spec, so the second half of the list mirrors
    the first bucket-for-bucket.
    """
    spec = fl.spec(params)
    bufs = fl.pack(params, spec)
    mom_bufs = None
    if _mixed_momentum(fl) and momentum is not None:
        mom_bufs = fl.pack(momentum, spec)
    return spec, consensus.widen_with_momentum(fl, bufs, mom_bufs)


def _momentum_payload(optimizer: DistributedOptimizer, state: OptState):
    """The momentum tree a mixed-momentum step puts on the wire.

    Fails loudly if the optimizer claims a mixable momentum but its
    ``momentum_tree`` returns nothing for this state shape — silently
    packing zeros here would degrade the wire to ``v' = -a g`` neighbor
    terms with no error.
    """
    mom = optimizer.momentum_tree(state.inner)
    if mom is None:
        raise ValueError(
            f"momentum_mixing='mixed': {type(optimizer).__name__}."
            "momentum_tree returned None for the current optimizer state — "
            "no momentum payload to put on the wire")
    return mom


def make_local_wire_init(fl: consensus.FlatComm) -> Callable:
    """Per-shard overlap wire initializer (run it inside ``shard_map``).

    Packs the *local* params and quantizes with seed ``-1`` — the same
    ``x_{-1} := x_0`` convention as :func:`repro.core.consensus.
    initial_wire_state`, but with the local flat layout, which differs from
    the global one whenever params also shard over non-agent mesh axes.
    With momentum mixing the wire also carries the momentum payload
    (``v_{-1} := v_0 = 0``).
    """

    def local_init(params):
        _, bufs = _pack_wire_bufs(fl, params)
        # the strategy wraps the seed -1 generation into a depth-S WireRing
        # on the fault path (plain quantize_stage otherwise, bit-for-bit)
        return fl.strategy.initial_wire(bufs)

    return local_init


def make_local_residual_init(fl: consensus.FlatComm) -> Callable:
    """Per-shard error-feedback residual initializer (inside ``shard_map``).

    Zeros, shaped like the *local* packed buckets (one per bucket per wire
    payload) — the analog of :func:`make_local_wire_init` for
    ``OptState.residual``.
    """

    def local_init(params):
        _, bufs = _pack_wire_bufs(fl, params)
        return fl.strategy.residual_init(bufs)

    return local_init


def make_local_qwarm_init(fl: consensus.FlatComm) -> Callable:
    """Per-shard rank-compressor warm-start initializer (inside
    ``shard_map``): the deterministic init basis per local bucket, the
    analog of :func:`make_local_residual_init` for ``OptState.qwarm``
    (``()`` for non-rank programs)."""

    def local_init(params):
        _, bufs = _pack_wire_bufs(fl, params)
        return fl.strategy.qwarm_init(bufs)

    return local_init


def _exchange_result(spec, nbrs, w, scales, selfs, mixed: bool):
    """Split the strategy's flat per-bucket operand lists into the
    :class:`ExchangeResult` payload groups (params / mixed momentum)."""
    if not mixed:
        return ExchangeResult(spec=spec, neighbors=nbrs, weights=w,
                              scales=scales, selfs=selfs)
    b = len(nbrs) // 2
    return ExchangeResult(spec=spec, neighbors=nbrs[:b], weights=w,
                          scales=scales[:b], selfs=selfs[:b],
                          mom_neighbors=nbrs[b:], mom_scales=scales[b:],
                          mom_selfs=selfs[b:])


def make_update_phase(optimizer: DistributedOptimizer, comm: CommOps,
                      schedule: str = "sync") -> Callable:
    """The update phase group: ``(params, grads, state) -> (params', state')``.

    ``sync``: the optimizer gathers synchronously on the current params —
    bit-for-bit today's behavior for the trivial static program; the
    gather internally runs whatever :class:`repro.core.consensus.
    MixingProgram` the comm carries (time-varying ``Pi_t`` selected by the
    step, ``k`` inner consensus rounds), so non-trivial strategies need no
    special casing here.  With ``error_feedback`` the sync path is staged
    explicitly instead, because the EF quantizer must thread
    ``OptState.residual`` through the round-1 compression.

    ``overlap``: exchange the carried one-step-stale wire state (round 1 —
    the only round off the critical path), run rounds ``2..k`` on the
    partially mixed buffers, update against the final round's operands,
    then quantize the *current* params as the next step's round-1 wire
    (EF-compressed when the program asks).  In the sharded mode the
    returned callable is the function the caller wraps in ``shard_map``;
    in the stacked mode it is called directly — the same phase code serves
    both.
    """
    if schedule not in SCHEDULES:
        raise ValueError(f"unknown schedule {schedule!r}; expected one of "
                         f"{SCHEDULES}")
    fl = comm.flat
    program = fl.program if fl is not None else None
    error_feedback = program is not None and program.error_feedback
    if program is not None and program.fault_tolerant and schedule != "overlap":
        raise ValueError(
            "staleness > 1 / fault injection needs schedule='overlap': the "
            "staleness ring generalizes the overlap wire double-buffer — a "
            "sync exchange has no carried wire state to be stale in")
    mixed = _mixed_momentum(fl)
    # a non-trivial program needs the fused staged path under EVERY
    # schedule — without this, a hand-assembled StepProgram with a
    # non-fused optimizer would silently mix the fixed dense Pi instead
    # of the configured strategy (no-op for trivial/absent programs)
    check_program_support(optimizer, comm)

    if schedule == "sync" and not error_feedback and not mixed:
        def update_sync(params, grads, state):
            return optimizer.update(params, grads, state, comm)
        return update_sync

    if schedule == "sync":
        # sync + error feedback and/or momentum mixing: the engine stages
        # the pipeline explicitly, because the EF quantizer must thread
        # ``OptState.residual`` through the round-1 compression and the
        # momentum payload must be packed from the optimizer state (the
        # check above already validated the fused flat path exists).
        strategy = fl.strategy

        def update_sync_staged(params, grads, state):
            spec, bufs = _pack_wire_bufs(
                fl, params,
                _momentum_payload(optimizer, state) if mixed else None)
            if error_feedback:
                wire, new_res, new_qwarm = strategy.compress_ef(
                    bufs, state.step, state.residual, state.qwarm)
            else:
                wire = strategy.quantize_stage(bufs, state.step)
            nbrs, w, scales, selfs = strategy.continue_from_wire(
                bufs, wire, state.step)
            ex = _exchange_result(spec, nbrs, w, scales, selfs, mixed)
            new_params, new_state = optimizer.update(params, grads, state,
                                                     comm, exchanged=ex)
            if error_feedback:
                new_state = new_state._replace(residual=new_res,
                                               qwarm=new_qwarm)
            return new_params, new_state

        return update_sync_staged

    fl = check_overlap_support(optimizer, comm)
    strategy = fl.strategy

    def update_overlap(params, grads, state):
        # pack (fresh selfs): params, plus the momentum payload when mixed
        spec, bufs = _pack_wire_bufs(
            fl, params,
            _momentum_payload(optimizer, state) if mixed else None)
        # round 1 exchanges the stale carried wire; rounds 2..k (if any)
        # re-quantize the partially mixed buffers on the critical path
        nbrs, w, scales, selfs = strategy.continue_from_wire(
            bufs, state.wire, state.step)
        ex = _exchange_result(spec, nbrs, w, scales, selfs, mixed)
        new_params, new_state = optimizer.update(params, grads, state, comm,
                                                 exchanged=ex)
        # quantize (x_t, v_t) as the wire step t+1 exchanges (one step
        # stale there)
        if error_feedback:
            new_wire, new_res, new_qwarm = strategy.compress_ef(
                bufs, state.step, state.residual, state.qwarm)
            return new_params, new_state._replace(wire=new_wire,
                                                  residual=new_res,
                                                  qwarm=new_qwarm)
        # advance_wire = quantize_stage on the fault-free path; with a
        # staleness ring it also pushes the fresh generation and advances
        # the age counters (no extra bytes — the old slots never move)
        new_wire = strategy.advance_wire(bufs, state.wire, state.step)
        return new_params, new_state._replace(wire=new_wire)

    return update_overlap


# --------------------------------------------------------------------------
# the assembled program
# --------------------------------------------------------------------------


@dataclasses.dataclass
class StepProgram:
    """One training step assembled from the named phases.

    Both execution modes build this with :func:`make_grad_phase` +
    :func:`make_update_phase`; the sharded front-end additionally wraps the
    update group in ``shard_map`` (``update_phase`` is whatever callable the
    front-end hands over).  ``extra_metrics(new_params)`` appends
    mode-specific diagnostics (the stacked trainer's consensus error).
    """

    optimizer: DistributedOptimizer
    comm: CommOps
    grad_phase: Callable          # (gp, batch) -> ((losses, metrics), grads)
    update_phase: Callable        # (params, grads, state) -> (params', state')
    schedule: str = "sync"
    extra_metrics: Optional[Callable[[PyTree], Dict[str, jnp.ndarray]]] = None
    # overlap wire initializer override: the sharded front-end supplies a
    # shard_map-local packer (the local flat layout differs from the global
    # one whenever params also shard over non-agent mesh axes); None uses
    # the global agent-stacked path (the stacked trainer).
    init_wire: Optional[Callable[[PyTree], Any]] = None
    # same override for the error-feedback residual buffers
    init_residual: Optional[Callable[[PyTree], Any]] = None
    # same override for the rank compressor's warm-start basis
    init_qwarm: Optional[Callable[[PyTree], Any]] = None

    def init_state(self, params: PyTree) -> OptState:
        state = self.optimizer.init(params)
        if self.schedule == "overlap":
            fl = check_overlap_support(self.optimizer, self.comm)
            if self.init_wire is not None:
                state = state._replace(wire=self.init_wire(params))
            else:
                state = state._replace(
                    wire=consensus.initial_wire_state(fl, params))
        fl = self.comm.flat
        if fl is not None and fl.program is not None \
                and fl.program.error_feedback:
            check_program_support(self.optimizer, self.comm)
            if self.init_residual is not None:
                state = state._replace(residual=self.init_residual(params))
            else:
                state = state._replace(
                    residual=consensus.initial_residual_state(fl, params))
        if fl is not None and fl.program is not None \
                and fl.program.compressed:
            # rank warm-start basis, under BOTH schedules (sync compress_ef
            # consumes it too); independent of the wire init by design
            if self.init_qwarm is not None:
                state = state._replace(qwarm=self.init_qwarm(params))
            else:
                state = state._replace(
                    qwarm=consensus.initial_qwarm_state(fl, params))
        return state

    def step_fn(self, params: PyTree, opt_state: OptState, batch):
        gp = self.optimizer.grad_params(params, opt_state)
        (losses, metrics), grads = self.grad_phase(gp, batch)
        new_params, new_state = self.update_phase(params, grads, opt_state)
        out = {"loss": jnp.mean(losses)}
        if self.extra_metrics is not None:
            out.update(self.extra_metrics(new_params))
        for k, v in metrics.items():
            out[k] = jnp.mean(v)
        return new_params, new_state, out


def wire_bytes_per_neighbor(wire) -> int:
    """Bytes ONE neighbor transfer of a carried wire state moves, per agent,
    counted from the actual buffers — the overlap schedule must put exactly
    the sync schedule's bytes on the wire (``FlatSpec.exchange_bytes``),
    just one step later.  Row scales only cross the wire for quantized
    payloads; the unit scales of f32/bf16 wires are synthesized locally
    after the exchange (shift-invariant), so they cost nothing here.

    A :class:`repro.core.consensus.WireRing` counts ONE ring generation —
    the sender-selected slot is the only thing exchanged each step, so the
    bytes are independent of the ring depth ``S``; the stale slots and the
    age counters are local state and move nothing (asserted by
    ``benchmarks/kernel_microbench.py consensus/stale_ring``).

    Compressed entries (:class:`repro.core.consensus.TopKWire` /
    :class:`repro.core.consensus.RankWire`) count EVERY field — the
    neighbors can reconstruct nothing locally, so values, indices, scales
    and both rank factors all cross the wire.  The accounting-side figure
    is :func:`repro.core.consensus.program_bytes_per_neighbor`; the
    microbench asserts the two agree on the actual carried buffers."""

    def _entry_bytes(entry, drop_axes: int) -> int:
        if isinstance(entry, (consensus.TopKWire, consensus.RankWire)):
            fields = list(entry)
        else:
            payload, scales = entry
            quantized = jnp.dtype(payload.dtype).itemsize == 1
            fields = [payload, scales] if quantized else [payload]
        total = 0
        for x in fields:
            per_agent = 1
            for d in x.shape[drop_axes:]:
                per_agent *= d
            total += per_agent * jnp.dtype(x.dtype).itemsize
        return total

    if isinstance(wire, consensus.WireRing):
        # drop the agent AND ring axes
        return sum(_entry_bytes(e, 2) for e in wire.slots)
    return sum(_entry_bytes(e, 1) for e in wire)


# --------------------------------------------------------------------------
# critical-path proof: which step inputs reach the collective exchange?
# --------------------------------------------------------------------------


def _sub_jaxprs(params: dict):
    for v in params.values():
        vals = v if isinstance(v, (tuple, list)) else (v,)
        for x in vals:
            if isinstance(x, (Jaxpr, ClosedJaxpr)):
                yield x


def _taint_walk(jaxpr, in_taints, hits, prims, path=()):
    """Propagate per-invar label sets through ``jaxpr``; collect the merged
    input labels of every eqn whose primitive name contains one of
    ``prims``.  Conservative: opaque/unmatched sub-jaxprs taint all
    outputs with the union of inputs, and loop-carried sub-jaxprs
    (scan/while) iterate to a fixpoint.  Returns per-outvar label sets.

    ``path`` names the enclosing call chain as a tuple of
    ``(primitive_name, eqn_index, sub_jaxpr_index)`` frames.  Hits are
    keyed ``((path, id(eqn)), primitive_name, labels)``: jax shares
    sub-jaxpr objects between call sites (two ``pjit`` eqns of the same
    jitted fn carry the *same* inner eqn objects), so a bare ``id(eqn)``
    would merge structurally distinct collectives reached through
    different call sites — the path disambiguates them, while fixpoint
    re-walks of one site (same path) still dedupe.
    """
    env = {}

    def read(v):
        if isinstance(v, Literal):
            return frozenset()
        return env.get(v, frozenset())

    for v, t in zip(jaxpr.invars, in_taints):
        env[v] = frozenset(t)
    for ei, eqn in enumerate(jaxpr.eqns):
        ins = [read(v) for v in eqn.invars]
        merged = frozenset().union(*ins) if ins else frozenset()
        if any(p in eqn.primitive.name for p in prims):
            hits.append(((path, id(eqn)), eqn.primitive.name, merged))
        out_ts = None
        subs = list(_sub_jaxprs(eqn.params))
        if subs:
            acc = None
            for si, sub in enumerate(subs):
                j = sub.jaxpr if isinstance(sub, ClosedJaxpr) else sub
                n = len(j.invars)
                if n == len(ins):
                    sub_in = list(ins)
                elif n < len(ins):
                    sub_in = list(ins[len(ins) - n:])
                else:
                    sub_in = [merged] * n
                looping = eqn.primitive.name in ("scan", "while")
                sub_path = path + ((eqn.primitive.name, ei, si),)
                for _ in range(5):
                    sub_out = _taint_walk(j, sub_in, hits, prims, sub_path)
                    if not looping:
                        break
                    # feed carried-output taints back into the carried inputs
                    grown = list(sub_in)
                    nc = eqn.params.get("num_consts")
                    nk = eqn.params.get("num_carry")
                    if nc is not None and nk is not None:   # scan layout
                        for i in range(min(nk, len(sub_out))):
                            if nc + i < len(grown):
                                grown[nc + i] = grown[nc + i] | sub_out[i]
                    else:                                   # while: carry last
                        k = min(len(sub_out), len(grown))
                        for i in range(k):
                            grown[len(grown) - k + i] |= sub_out[i]
                    if grown == sub_in:
                        break
                    sub_in = grown
                if len(sub_out) == len(eqn.outvars):
                    acc = (sub_out if acc is None
                           else [a | b for a, b in zip(acc, sub_out)])
                else:
                    acc = [merged] * len(eqn.outvars)
            out_ts = acc
        if out_ts is None:
            out_ts = [merged] * len(eqn.outvars)
        for v, t in zip(eqn.outvars, out_ts):
            env[v] = t
    return [read(v) for v in jaxpr.outvars]


#: step-input label names, in the order `step_input_labels` emits them
STEP_INPUT_LABELS = ("params", "state", "wire", "residual", "qwarm", "batch")

#: primitive-name substrings of every collective the census audits
COLLECTIVE_PRIMS = ("ppermute", "psum", "all_gather", "all_to_all",
                    "all_reduce", "reduce_scatter")


def step_input_labels(params, opt_state, batch):
    """Per-flat-input label sets for ``step_fn(params, opt_state, batch)``:
    ``params`` / ``state`` / ``wire`` / ``residual`` / ``qwarm`` / ``batch``
    (the taxonomy both the dependency report and the static checker's
    collective census taint through the traced step)."""
    label_tree = (
        jax.tree.map(lambda _: "params", params),
        OptState(step="state",
                 inner=jax.tree.map(lambda _: "state", opt_state.inner),
                 wire=jax.tree.map(lambda _: "wire", opt_state.wire),
                 residual=jax.tree.map(lambda _: "residual",
                                       opt_state.residual),
                 qwarm=jax.tree.map(lambda _: "qwarm", opt_state.qwarm)),
        jax.tree.map(lambda _: "batch", batch),
    )
    return [frozenset([l]) for l in jax.tree.leaves(label_tree)]


def collective_taint_hits(step_fn, params, opt_state, batch, *,
                          prims=("ppermute",), closed=None):
    """Trace ``step_fn`` and return one record per (collective eqn,
    enclosing call path): ``{"prim", "path", "labels"}``.

    The shared engine under both ``exchange_dependency_report`` and the
    static checker's collective census.  Two structurally distinct
    collectives that happen to live in a shared (cloned) sub-jaxpr object
    are counted separately — hits key on the call path, not bare eqn
    identity — while fixpoint re-walks of loop bodies merge into one
    record per site with the union of the taints seen.

    Works on concrete arrays or ShapeDtypeStructs.  ``closed`` lets a
    caller that already traced the step (the static checker shares one
    jaxpr across passes) skip the re-trace.
    """
    labels = step_input_labels(params, opt_state, batch)
    if closed is None:
        closed = jax.make_jaxpr(step_fn)(params, opt_state, batch)
    assert len(closed.jaxpr.invars) == len(labels), \
        (len(closed.jaxpr.invars), len(labels))
    hits: list = []
    _taint_walk(closed.jaxpr, labels, hits, prims=prims)
    merged: dict = {}
    names: dict = {}
    order: list = []
    for key, name, taint in hits:
        if key not in merged:
            order.append(key)
        merged[key] = merged.get(key, frozenset()) | taint
        names[key] = name
    return [{"prim": names[k], "path": k[0], "labels": merged[k]}
            for k in order]


def exchange_dependency_report(step_fn, params, opt_state, batch) -> dict:
    """Which step inputs can reach the collective exchange, from the jaxpr.

    Labels every flat input of ``step_fn(params, opt_state, batch)`` as
    ``params`` / ``state`` / ``wire`` (the overlap double-buffer inside the
    optimizer state) / ``residual`` (error-feedback buffers) / ``batch``
    and taints them through the traced step.  The returned record is the
    dryrun's critical-path proof:

    * ``sync``    — the ``ppermute`` payload is quantized from the current
      params, so ``depends_on_params`` is True: the exchange can only start
      once the previous step's update has produced those params.
    * ``overlap`` — the round-1 payload is the carried wire state: those
      ``ppermute``\\ s taint only carried optimizer state
      (``n_ppermutes_carried_only``), i.e. they need neither the current
      params (previous update) nor the current batch (backward) —
      ``round1_off_critical_path``.  With a multi-round program the inner
      rounds ``2..k`` re-quantize partially mixed *current* buffers, so
      those collectives stay on the critical path
      (``n_ppermutes_fresh``) and the all-hits summary
      ``off_grad_update_critical_path`` is True only for ``k = 1``.

    Collectives are counted per (jaxpr equation, enclosing call path): a
    ``ppermute`` inside the multi-round ``lax.scan`` counts once regardless
    of trip count, while the same eqn object reached through two distinct
    call sites (jax shares cloned sub-jaxprs) counts twice.

    Works on concrete arrays or ShapeDtypeStructs.  Programs whose mixing
    has no ``ppermute`` (stacked dense ``Pi``) report ``n_ppermutes == 0``.
    """
    hits = collective_taint_hits(step_fn, params, opt_state, batch,
                                 prims=("ppermute",))
    taints = [h["labels"] for h in hits]
    union = frozenset().union(*taints) if taints else frozenset()
    carried = [t for t in taints if not (t & frozenset(("params", "batch")))]
    return {
        "n_ppermutes": len(taints),
        "n_ppermutes_carried_only": len(carried),
        "n_ppermutes_fresh": len(taints) - len(carried),
        "depends_on_params": "params" in union,
        "depends_on_batch": "batch" in union,
        "depends_on_wire_state": "wire" in union,
        "off_grad_update_critical_path": bool(taints)
            and "params" not in union and "batch" not in union,
        "round1_off_critical_path": len(carried) > 0,
    }
