"""Sharded production step builders: train_step / prefill_step / serve_step.

``build_train_step`` is a thin front-end over the shared
:class:`repro.core.engine.StepProgram` phase pipeline (grad -> pack ->
quantize -> exchange -> update — the same phases the stacked
``CollaborativeTrainer`` assembles): this module only supplies the
mesh-specific comm ops and wraps the update phase group in ``shard_map``.
The consensus mixing runs either as

* ``mixing="dense"``   — stacked ``Pi`` einsum under pjit (paper-faithful
  semantics, naive collective schedule: XLA lowers it to all-gathers over
  the agent axis),
* ``mixing="ppermute"``— a ``shard_map`` region whose circulant topology
  lowers to `collective-permute`s between ICI neighbours, applied leaf by
  leaf (one collective per leaf per shift), or
* ``mixing="ppermute_fused"`` — the whole optimizer update runs inside one
  ``shard_map`` region on dtype-bucketed flat buffers
  (:mod:`repro.core.flatbuf`): one ``lax.ppermute`` per circulant shift
  offset per bucket for the *entire model*, followed by the fused Pallas
  update kernel (one launch per bucket) in the same region.  This is the
  §Perf fast path and expects a ``fused=True`` optimizer (a non-fused one
  still runs correctly inside the region, with a warning).

``schedule="overlap"`` (fused path only) pipelines the exchange one step
deep: the quantized buckets + row scales double-buffer in the optimizer
state, so the ``ppermute``\\ s consume only carried state and drop off the
grad->update critical path (one-step-stale neighbor mixing, fresh
full-precision self term — see :mod:`repro.core.engine`; the dryrun's
``exchange_schedule`` record proves the dependency structure per config).

The fused path exposes the **exchange-precision knob**
(``exchange="f32"|"bf16"|"int8"|"fp8"``): int8/fp8 quantize each packed
bucket (stochastic rounding, one f32 scale per 128-lane row) before the
circulant ``ppermute`` so every shift moves ~3.9x fewer bytes, and the
fused kernels dequantize in-register.  It also carries the **mixing
strategy** (:class:`repro.core.consensus.MixingProgram`, see
ARCHITECTURE.md §mixing strategies): ``mixing_strategy`` /
``topology_schedule`` select time-varying ``Pi_t`` (one ``lax.switch``
branch of ppermutes per schedule entry), ``consensus_rounds`` the inner
i-CDSGD round count (k x the wire bytes), ``error_feedback`` the
quantization-residual state riding ``OptState.residual`` (sharded like
the wire buffers, initialized inside ``shard_map``), and
``momentum_mixing="mixed"`` the widened two-payload wire (the momentum
buffer mixes with the same ``Pi``; wire/residual state and ppermute
count double — one wire pair and one EF residual per bucket per
payload).  The fused kernels also alias their
gradient/state inputs to their outputs (``input_output_aliases``); jit the
returned ``step_fn`` with ``donate_argnums=TrainStepBundle.donate_argnums``
to let params, momentum, and Adam moments update in place (saving roughly
one model copy of peak HBM per optimizer slot).

`serve_step` decodes one token against the sharded KV cache; `prefill_step`
is the full-sequence forward (compute-equivalent to cache-filling prefill;
it returns last-position logits).
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.configs.base import ArchConfig, InputShape
from repro.core import consensus as consensus_lib
from repro.core import engine, flatbuf
from repro.core.optim import CommOps, DistributedOptimizer, stacked_comm_ops
from repro.core.topology import Topology, make_topology, make_topology_schedule
from repro.launch import sharding as shlib
from repro.nn.param import stack_agent_axis
from repro.nn.transformer import decode_step, forward, loss_fn, model_template

P = PartitionSpec
PyTree = Any


@dataclasses.dataclass
class TrainStepBundle:
    step_fn: Callable                     # (params, opt_state, batch) -> (params, opt_state, metrics)
    param_template: PyTree                # ParamDef tree (agent-stacked)
    param_specs: PyTree                   # PartitionSpec tree
    opt_state_specs: Any
    batch_specs: Dict[str, jax.ShapeDtypeStruct]
    n_agents: int
    topology: Topology
    exchange: str = "f32"                 # neighbor-exchange wire precision
    schedule: str = "sync"                # exchange schedule: sync | overlap
    # the mixing-strategy configuration of the fused path (None only when
    # the comm carries no flat support, e.g. mixing="dense")
    mixing_program: Optional[consensus_lib.MixingProgram] = None
    # params + opt_state update in place every step: pass to jax.jit so the
    # fused kernels' input_output_aliases actually elide the output copies.
    donate_argnums: Tuple[int, ...] = (0, 1)
    # StepProgram state initializer (fills the overlap wire double-buffer);
    # falls back to optimizer.init when absent.
    init_state: Optional[Callable] = None
    # the optimizer the step was assembled around — the static checker
    # (repro.analysis.staticcheck) reads its declared alias contract and
    # the dryrun verify block threads it through without re-deriving the
    # launch configuration.
    optimizer: Optional[DistributedOptimizer] = None

    def param_structs(self, mesh: Mesh) -> PyTree:
        def leaf(pd, spec):
            return jax.ShapeDtypeStruct(pd.shape, pd.dtype, sharding=NamedSharding(mesh, spec))
        return jax.tree.map(leaf, self.param_template, self.param_specs,
                            is_leaf=lambda x: hasattr(x, "axes") and hasattr(x, "init"))

    def opt_state_structs(self, mesh: Mesh, optimizer) -> Any:
        init = self.init_state if self.init_state is not None else optimizer.init
        structs = jax.eval_shape(init, self.param_structs(mesh))
        specs = self.opt_state_specs
        return jax.tree.map(
            lambda s, sp: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=NamedSharding(mesh, sp)),
            structs, specs)


def _agent_factors(mesh: Mesh, agent_axes) -> consensus_lib.FactoredMix:
    """Per-axis circulant factors for a multi-axis agent mesh."""
    factors = []
    for a in agent_axes:
        s = mesh.shape[a]
        t = make_topology("ring" if s > 2 else "fully_connected", s)
        factors.append((a, t))
    return consensus_lib.FactoredMix(tuple(factors))


def make_local_fused_comm(
    topology: Topology, mesh: Mesh, mode: str, *,
    interpret: Optional[bool] = None,
    exchange: str = "f32",
    program: Optional[consensus_lib.MixingProgram] = None,
) -> CommOps:
    """CommOps whose every member runs *inside* a shard_map region.

    Carries a :class:`repro.core.consensus.FlatComm` so ``fused=True``
    optimizers run the flat-buffer ppermute + Pallas-kernel fast path; the
    ``mix``/``mean`` members are the local (non-shard_map-wrapped) circulant
    fns so non-fused optimizers work in the same region.  ``exchange``
    selects the ppermute wire precision (f32 | bf16 | int8 | fp8);
    ``program`` the mixing strategy (time-varying schedules compile one
    ``lax.switch`` branch of ppermutes per entry — single agent axis only).
    """
    rules = shlib.rules_for_mode(mode, mesh)
    agent_axes = rules["agent"]
    axes = agent_axes if isinstance(agent_axes, tuple) else (agent_axes,)
    if len(axes) > 1:
        fm = _agent_factors(mesh, axes)
        flat = consensus_lib.sharded_flat_comm(fm.factors, interpret=interpret,
                                               exchange=exchange,
                                               program=program)
        local_mix = fm.make_mix_fn()
        lam2, lamn, n_agents = fm.lambda2, fm.lambdan, fm.n_agents
    else:
        flat = consensus_lib.sharded_flat_comm([(axes[0], topology)],
                                               interpret=interpret,
                                               exchange=exchange,
                                               program=program)
        local_mix = consensus_lib.make_sharded_mix_fn(topology, axes[0])
        lam2, lamn, n_agents = topology.lambda2, topology.lambdan, topology.n_agents
    local_mean = consensus_lib.make_sharded_mean_fn(axes)
    return CommOps(mix=local_mix, mean=local_mean, n_agents=n_agents,
                   lambda2=lam2, lambdan=lamn, flat=flat)


def make_mix_comm(
    topology: Topology, mesh: Mesh, param_specs: PyTree, mode: str, mixing: str,
) -> CommOps:
    """CommOps over the agent axis for the sharded trainer."""
    rules = shlib.rules_for_mode(mode, mesh)
    agent_axes = rules["agent"]
    if mixing == "dense":
        # no FlatComm here: under pjit the batched (vmapped) fused kernel
        # would force all-gathers of the stacked params — the sharded fused
        # fast path is mixing="ppermute_fused"; dense stays the reference.
        return dataclasses.replace(stacked_comm_ops(topology), flat=None)
    if mixing != "ppermute":
        raise ValueError(f"unknown mixing {mixing!r}")

    if isinstance(agent_axes, tuple) and len(agent_axes) > 1:
        # factored topology: one circulant factor per mesh axis
        fm = _agent_factors(mesh, agent_axes)
        local_mix = fm.make_mix_fn()
        lam2, lamn = fm.lambda2, fm.lambdan
        n_agents = fm.n_agents
    else:
        axis = agent_axes[0] if isinstance(agent_axes, tuple) else agent_axes
        local_mix = consensus_lib.make_sharded_mix_fn(topology, axis)
        lam2, lamn = topology.lambda2, topology.lambdan
        n_agents = topology.n_agents

    # built once per bundle, not once per mean() invocation
    ax = agent_axes if isinstance(agent_axes, tuple) else (agent_axes,)
    local_mean = consensus_lib.make_sharded_mean_fn(ax)

    def mix(tree: PyTree) -> PyTree:
        return jax.shard_map(local_mix, mesh=mesh, in_specs=(param_specs,),
                             out_specs=param_specs, check_vma=False)(tree)

    def mean(tree: PyTree) -> PyTree:
        return jax.shard_map(local_mean, mesh=mesh, in_specs=(param_specs,),
                             out_specs=param_specs, check_vma=False)(tree)

    return CommOps(mix=mix, mean=mean, n_agents=n_agents, lambda2=lam2, lambdan=lamn)


def build_train_step(
    cfg: ArchConfig,
    shape: InputShape,
    mesh: Mesh,
    optimizer: DistributedOptimizer,
    *,
    mode: str = "train",
    topology_name: str = "ring",
    mixing: str = "dense",
    remat: bool = True,
    microbatches: int = 1,
    interpret: Optional[bool] = None,  # Pallas interpret mode; None: by backend
    exchange: str = "f32",        # ppermute wire precision (fused path only)
    schedule: str = "sync",       # exchange schedule: sync | overlap
    mixing_strategy: str = "static",   # static | time_varying | multi_round
    consensus_rounds: int = 1,    # inner i-CDSGD rounds per step (fused path)
    topology_schedule: Optional[str] = None,  # TopologySchedule factory spec
    error_feedback: bool = False,  # EF residuals for quantized exchanges
    momentum_mixing: str = "none",  # "mixed": momentum rides the wire too
    staleness: int = 1,           # bounded-staleness ring depth S (overlap)
    fault_schedule=None,          # FaultSchedule | spec str (repro.core.faults)
    compressor: str = "none",     # none | int8 | fp8 | topk:p|auto:B | rank:r
    sparse_update: Optional[bool] = None,  # sparse fused update (topk default)
) -> TrainStepBundle:
    rules = shlib.rules_for_mode(mode, mesh)
    n_agents = shlib.agent_count(mesh, mode)
    topology = make_topology(topology_name, n_agents)
    sched_obj = None
    if topology_schedule is not None:
        sched_obj = make_topology_schedule(topology_schedule, n_agents)
    if isinstance(fault_schedule, str):
        from repro.core.faults import make_fault_schedule
        fault_schedule = make_fault_schedule(fault_schedule, n_agents)
    program = consensus_lib.make_mixing_program(
        sched_obj if sched_obj is not None else topology,
        strategy=mixing_strategy, rounds=consensus_rounds,
        error_feedback=error_feedback, exchange=exchange,
        momentum_mixing=momentum_mixing,
        staleness=staleness, faults=fault_schedule,
        compressor=compressor, sparse_update=sparse_update)
    exchange = program.exchange   # compressor aliases normalize the precision
    if not program.is_trivial and mixing != "ppermute_fused":
        raise ValueError(
            f"mixing strategy {program.strategy!r} (rounds={program.rounds}, "
            f"error_feedback={program.error_feedback}) lives on the "
            f"flat-buffer path: requires mixing='ppermute_fused', got "
            f"mixing={mixing!r}")

    base_t = model_template(cfg)
    template = stack_agent_axis(base_t, n_agents)
    pspecs = shlib.safe_partition_specs(template, rules, mesh)
    opt_specs = optimizer.state_specs(pspecs)
    batch_specs = shlib.train_batch_specs(cfg, shape, mesh, mode)
    if mixing == "ppermute_fused":
        # the whole update phase group (pack -> quantize -> exchange ->
        # fused kernel) runs inside one shard_map region; comm members are
        # local fns.
        if not getattr(optimizer, "fused", False):
            warnings.warn(
                f"mixing='ppermute_fused' with {type(optimizer).__name__}"
                "(fused=False): the update falls back to the per-leaf "
                "reference path inside the shard_map region — pass "
                "fused=True for the flat-buffer fast path", stacklevel=2)
        comm = make_local_fused_comm(topology, mesh, mode, interpret=interpret,
                                     exchange=exchange, program=program)
        # non-trivial strategies additionally need the fused optimizer —
        # validate here, not deep inside the first traced step
        engine.check_program_support(optimizer, comm)
    else:
        if exchange != "f32":
            warnings.warn(
                f"exchange={exchange!r} only affects mixing='ppermute_fused'; "
                f"mixing={mixing!r} moves native bytes", stacklevel=2)
        comm = make_mix_comm(topology, mesh, pspecs, mode, mixing)
    init_wire = None
    init_residual = None
    init_qwarm = None
    agent_axes_t = rules["agent"] if isinstance(rules["agent"], tuple) \
        else (rules["agent"],)
    other_axes = tuple(a for a in mesh.axis_names if a not in agent_axes_t)
    state_sp = P(rules["agent"], other_axes or None, None)
    if program.compressed and any(mesh.shape[a] > 1 for a in other_axes):
        raise ValueError(
            f"compressor={program.compressor!r} supports agent-only sharding: "
            f"the rank factors / warm-start bases ((r, 128) and (128, r)) and "
            f"the top-k index payload do not shard over the non-agent mesh "
            f"axes {other_axes}; use an agent-only mesh or a dense "
            f"compressor (int8/fp8)")

    def _n_buckets():
        # one wire/residual entry per flat bucket per payload tree — the
        # mixed momentum payload mirrors the param buckets one-for-one
        return program.n_payloads * flatbuf.make_flat_spec(
            jax.tree.map(lambda pd: jax.ShapeDtypeStruct(pd.shape, pd.dtype),
                         template,
                         is_leaf=lambda x: hasattr(x, "axes") and hasattr(x, "init")),
            lead=1).n_buckets

    if program.error_feedback:
        # EF residuals ride the optimizer state like the wire buffers do:
        # one f32 buffer per flat bucket per payload, rows sharded over the
        # non-agent mesh axes (shard-local flat layout), initialized inside
        # shard_map.
        residual_specs = tuple(state_sp for _ in range(_n_buckets()))
        opt_specs = opt_specs._replace(residual=residual_specs)
        local_residual_init = engine.make_local_residual_init(comm.flat)

        def init_residual(params):
            return jax.shard_map(local_residual_init, mesh=mesh, in_specs=(pspecs,),
                                 out_specs=residual_specs, check_vma=False)(params)

    if program.compressed and program.compressor_kind == "rank":
        # the rank compressor's warm-start bases ride the optimizer state
        # like the wire: one (A, 128, r) stack per bucket, agent-sharded,
        # initialized inside shard_map (needed under BOTH schedules — the
        # sync compress_ef consumes them too)
        qwarm_specs = tuple(state_sp for _ in range(_n_buckets()))
        opt_specs = opt_specs._replace(qwarm=qwarm_specs)
        local_qwarm_init = engine.make_local_qwarm_init(comm.flat)

        def init_qwarm(params):
            return jax.shard_map(local_qwarm_init, mesh=mesh, in_specs=(pspecs,),
                                 out_specs=qwarm_specs, check_vma=False)(params)

    if schedule == "overlap":
        if mixing != "ppermute_fused":
            raise ValueError(
                "schedule='overlap' requires mixing='ppermute_fused' (the "
                "one-step-stale wire double-buffer lives on the flat-buffer "
                f"path); got mixing={mixing!r}")
        fl = engine.check_overlap_support(optimizer, comm)
        # The wire double-buffer rides in the optimizer state: one
        # (payload, row-scales) pair per flat bucket, agent axis leading.
        # Buckets pack the *local* shard, so the rows dim shards over every
        # non-agent mesh axis (a model-parallel device pair carries two
        # different row blocks — the wire is never read as one global
        # buffer, only round-tripped shard-to-shard between steps).
        if program.fault_tolerant:
            # Depth-S staleness ring: the ring axis (dim 1) is unsharded —
            # every shard keeps its own S generations locally; rows still
            # shard over the non-agent axes exactly like the flat buffers.
            ring_sp = P(rules["agent"], None, other_axes or None, None)
            wire_specs = consensus_lib.WireRing(
                slots=tuple((ring_sp, ring_sp) for _ in range(_n_buckets())),
                send_age=P(rules["agent"]),
                ages=P(rules["agent"], None))
        elif program.compressed:
            # compressed wire entries are NamedTuples (TopKWire/RankWire);
            # every field carries the leading agent axis and two trailing
            # unsharded dims, so state_sp applies field-wise (agent-only
            # meshes — validated above)
            if program.compressor_kind == "topk":
                wire_specs = tuple(
                    consensus_lib.TopKWire(values=state_sp, indices=state_sp,
                                           scales=state_sp)
                    for _ in range(_n_buckets()))
            else:
                wire_specs = tuple(
                    consensus_lib.RankWire(p=state_sp, qt=state_sp)
                    for _ in range(_n_buckets()))
        else:
            wire_specs = tuple((state_sp, state_sp)
                               for _ in range(_n_buckets()))
        opt_specs = opt_specs._replace(wire=wire_specs)
        local_wire_init = engine.make_local_wire_init(fl)

        def init_wire(params):
            return jax.shard_map(local_wire_init, mesh=mesh, in_specs=(pspecs,),
                                 out_specs=wire_specs, check_vma=False)(params)

    grad_phase = engine.make_grad_phase(
        lambda p, b: loss_fn(cfg, p, b, remat=remat), microbatches)
    update_local = engine.make_update_phase(optimizer, comm, schedule)
    if mixing == "ppermute_fused":
        def update_phase(params, grads, opt_state):
            return jax.shard_map(
                update_local, mesh=mesh, in_specs=(pspecs, pspecs, opt_specs),
                out_specs=(pspecs, opt_specs), check_vma=False,
            )(params, grads, opt_state)
    else:
        update_phase = update_local

    step_program = engine.StepProgram(
        optimizer=optimizer,
        comm=comm,
        grad_phase=grad_phase,
        update_phase=update_phase,
        schedule=schedule,
        init_wire=init_wire,
        init_residual=init_residual,
        init_qwarm=init_qwarm,
    )

    return TrainStepBundle(
        step_fn=step_program.step_fn,
        param_template=template,
        param_specs=pspecs,
        opt_state_specs=opt_specs,
        batch_specs=batch_specs,
        n_agents=n_agents,
        topology=topology,
        exchange=exchange,
        schedule=schedule,
        mixing_program=program if mixing == "ppermute_fused" else None,
        init_state=step_program.init_state,
        optimizer=optimizer,
    )


@dataclasses.dataclass
class ServeStepBundle:
    step_fn: Callable
    param_template: PyTree
    param_specs: PyTree
    input_structs: Tuple                  # (cache, tokens, cur_index) or batch
    kind: str

    def param_structs(self, mesh: Mesh) -> PyTree:
        def leaf(pd, spec):
            return jax.ShapeDtypeStruct(pd.shape, pd.dtype, sharding=NamedSharding(mesh, spec))
        return jax.tree.map(leaf, self.param_template, self.param_specs,
                            is_leaf=lambda x: hasattr(x, "axes") and hasattr(x, "init"))


def build_prefill_step(cfg: ArchConfig, shape: InputShape, mesh: Mesh,
                       *, context_parallel: bool = False) -> ServeStepBundle:
    from repro.nn import attention as attn_lib

    template = model_template(cfg)
    pspecs = shlib.safe_partition_specs(template, shlib.rules_for_mode("serve", mesh), mesh)
    batch_specs = shlib.prefill_batch_specs(cfg, shape, mesh)
    b_axes = shlib.serve_batch_count(shape, mesh)[1]

    def prefill_step(params, batch):
        if context_parallel:
            with attn_lib.context_parallel(b_axes, "model"):
                logits, _ = forward(cfg, params, batch, remat=False)
        else:
            logits, _ = forward(cfg, params, batch, remat=False)
        return logits[:, -1, :]

    return ServeStepBundle(step_fn=prefill_step, param_template=template,
                           param_specs=pspecs, input_structs=(batch_specs,), kind="prefill")


def build_serve_step(cfg: ArchConfig, shape: InputShape, mesh: Mesh) -> ServeStepBundle:
    template = model_template(cfg)
    pspecs = shlib.safe_partition_specs(template, shlib.rules_for_mode("serve", mesh), mesh)
    cache, tokens, cur = shlib.decode_input_specs(cfg, shape, mesh)

    def serve_step(params, cache, tokens, cur_index):
        logits, new_cache = decode_step(cfg, params, cache, tokens, cur_index)
        next_tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        return next_tok, new_cache

    return ServeStepBundle(step_fn=serve_step, param_template=template,
                           param_specs=pspecs, input_structs=(cache, tokens, cur),
                           kind="decode")
