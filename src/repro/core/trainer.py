"""Multi-agent collaborative trainer (stacked simulation execution mode).

Simulates the paper's N-agent fixed-topology network on any backend: every
parameter leaf carries a leading agent axis and the step is assembled from
the shared :class:`repro.core.engine.StepProgram` phases — the same
grad/pack/quantize/exchange/update pipeline the sharded production mode
(:mod:`repro.launch.steps`) wraps in ``shard_map``.  This front-end only
supplies the stacked ``CommOps`` (dense ``Pi``) and the consensus-error
metric; it is the execution mode behind every paper-figure benchmark and
the theory tests, and the oracle the sharded trainers are verified
against.  ``schedule="overlap"`` selects the one-step-stale pipelined
exchange (see :mod:`repro.core.engine`).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import engine, flatbuf
from repro.core.consensus import (
    MixingProgram,
    consensus_error_pytree,
    exchange_bytes_per_step,
    make_mixing_program,
    mean_exchange_bytes_per_step,
)
from repro.core.optim import (
    CommOps,
    DistributedOptimizer,
    FedAvg,
    stacked_comm_ops,
)
from repro.core.topology import Topology, TopologySchedule, make_topology_schedule
from repro.utils.metrics import MetricHistory

PyTree = Any
LossFn = Callable[[PyTree, Dict[str, jnp.ndarray]], Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]]


def broadcast_to_agents(params: PyTree, n_agents: int) -> PyTree:
    """Replicate a single parameter set to all agents (common init)."""
    return jax.tree.map(lambda x: jnp.broadcast_to(x[None], (n_agents,) + x.shape).copy(), params)


def perturb_per_agent(params: PyTree, key, scale: float = 0.01) -> PyTree:
    """Optionally de-synchronize agent initializations."""
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(key, len(leaves))
    out = [x + scale * jax.random.normal(k, x.shape, x.dtype) for x, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, out)


@dataclasses.dataclass
class TrainState:
    params: PyTree            # stacked (A, ...)
    opt_state: Any
    step: int = 0


class CollaborativeTrainer:
    """Drives N collaborating agents through a DistributedOptimizer.

    An optimizer constructed with ``fused=True`` runs the whole-model
    flat-buffer update here: the stacked ``CommOps`` carries a ``FlatComm``
    (dense ``Pi`` on packed buffers), so each step issues exactly one
    ``pallas_call`` per parameter dtype bucket instead of one mix + axpy
    per pytree leaf.  ``interpret`` selects Pallas interpret mode; the
    default ``None`` follows the backend (compiled kernels on a TPU, see
    :func:`repro.kernels.resolve_interpret`).

    ``exchange`` simulates the neighbor-exchange wire precision of the
    fused path (``"f32"`` native, ``"bf16"``, or ``"int8"``/``"fp8"``
    stochastic-rounding quantization — the bandwidth knob of the sharded
    trainer, see :class:`repro.core.consensus.FlatComm`).  ``donate=True``
    (default) donates params and optimizer state to the jitted step, so
    together with the kernels' ``input_output_aliases`` the model updates
    in place instead of allocating a fresh copy per optimizer slot.

    ``schedule="overlap"`` double-buffers the quantized wire payloads in
    the optimizer state (one-step-stale neighbor mixing, fresh self term);
    ``microbatches`` enables the shared gradient-accumulation scan.

    The **mixing strategy** of the fused path is configurable
    (:class:`repro.core.consensus.MixingProgram`): ``mixing_strategy``
    selects ``static`` / ``time_varying`` / ``multi_round``,
    ``consensus_rounds`` sets the inner i-CDSGD round count,
    ``topology_schedule`` supplies the time-varying ``Pi_t`` sequence (a
    :class:`repro.core.topology.TopologySchedule` or a factory spec like
    ``"alternating:ring:torus"`` / ``"gossip:8"``), and
    ``error_feedback=True`` carries quantization residuals in the
    optimizer state, and ``momentum_mixing="mixed"`` puts the momentum
    buffer on the wire next to the params (``v' = mu (Pi v) - a g``,
    2010.11166 — the principled fix for the momentum/quantization
    large-lr instability; 2x the wire bytes, momentum-capable optimizers
    only).  ``staleness=S`` / ``fault_schedule=`` (a
    :class:`repro.core.faults.FaultSchedule` or a spec string like
    ``"stall:1:1:3,drop:0:2"``) engage the bounded-staleness wire ring with
    arrival-masked mixing under ``schedule="overlap"`` — injected
    stragglers/drops cost bounded drift instead of a stalled step.
    ``compressor=`` selects the wire compressor axis (``"int8"`` /
    ``"fp8"`` alias the exchange precisions; ``"topk:p"`` / ``"rank:r"``
    are the biased sparse / low-rank compressors riding the EF rail —
    they require ``error_feedback=True`` and normalize ``exchange``
    themselves; ``"topk:auto:B"`` picks per-bucket densities against a
    byte budget).  With a top-k compressor ``sparse_update`` (default on)
    feeds the compact wire fields straight to the fused sparse kernels —
    ``sparse_update=False`` forces the dense decompress-then-update
    reference path.  Everything validates at construction; non-trivial
    programs require a ``fused=True`` consensus optimizer.
    """

    def __init__(
        self,
        loss_fn: LossFn,
        params: PyTree,                   # single-agent params (will be stacked)
        topology: Topology,
        optimizer: DistributedOptimizer,
        *,
        stack: bool = True,
        donate: bool = True,
        interpret: Optional[bool] = None,
        exchange: str = "f32",
        schedule: str = "sync",
        microbatches: int = 1,
        mixing_strategy: str = "static",
        consensus_rounds: int = 1,
        topology_schedule=None,           # TopologySchedule | factory spec str
        error_feedback: bool = False,
        momentum_mixing: str = "none",
        staleness: int = 1,
        fault_schedule=None,              # FaultSchedule | spec str (faults.py)
        compressor: str = "none",
        sparse_update: Optional[bool] = None,
    ):
        self.loss_fn = loss_fn
        self.topology = topology
        self.optimizer = optimizer
        self.exchange = exchange
        self.schedule = schedule
        if exchange != "f32" and not getattr(optimizer, "fused", False):
            import warnings
            warnings.warn(
                f"exchange={exchange!r} only affects fused optimizers; "
                f"{type(optimizer).__name__}(fused=False) will mix in native "
                "precision", stacklevel=2)
        if isinstance(topology_schedule, str):
            topology_schedule = make_topology_schedule(
                topology_schedule, topology.n_agents)
        if topology_schedule is not None and \
                topology_schedule.n_agents != topology.n_agents:
            raise ValueError(
                f"topology_schedule spans {topology_schedule.n_agents} agents "
                f"but the topology has {topology.n_agents}")
        if isinstance(fault_schedule, str):
            from repro.core.faults import make_fault_schedule
            fault_schedule = make_fault_schedule(fault_schedule,
                                                 topology.n_agents)
        self.program: MixingProgram = make_mixing_program(
            topology_schedule if topology_schedule is not None else topology,
            strategy=mixing_strategy, rounds=consensus_rounds,
            error_feedback=error_feedback, exchange=exchange,
            momentum_mixing=momentum_mixing,
            staleness=staleness, faults=fault_schedule,
            compressor=compressor, sparse_update=sparse_update)
        self.exchange = exchange = self.program.exchange
        self.faults = self.program.faults
        self.comm: CommOps = stacked_comm_ops(topology, interpret=interpret,
                                              exchange=exchange,
                                              program=self.program)
        # non-trivial strategies live on the fused flat-buffer path only —
        # fail here, at config time, not deep inside the first traced step
        engine.check_program_support(optimizer, self.comm)
        stacked = broadcast_to_agents(params, topology.n_agents) if stack else params
        self._program = engine.StepProgram(
            optimizer=optimizer,
            comm=self.comm,
            grad_phase=engine.make_grad_phase(loss_fn, microbatches),
            update_phase=engine.make_update_phase(optimizer, self.comm, schedule),
            schedule=schedule,
            extra_metrics=lambda p: {"consensus_error": consensus_error_pytree(p)},
        )
        self.state = TrainState(params=stacked,
                                opt_state=self._program.init_state(stacked))
        self.history = MetricHistory()
        # recorded for the static checker's alias/donation-coverage pass
        self.donate_argnums = (0, 1) if donate else ()
        self._step_fn = jax.jit(self._program.step_fn,
                                donate_argnums=self.donate_argnums)
        self._eval_fn = jax.jit(self._make_eval())
        # per-step neighbor-exchange cost of the fused flat path (estimate;
        # train_loop reports the cumulative figure alongside steps/sec).
        # k consensus rounds move exactly k x the single-round bytes; a
        # time-varying schedule amortizes its period-mean degree; momentum
        # mixing doubles the payload trees per transfer.  FedAvg pays a
        # whole-model all-reduce once per local_steps (the collective is
        # gated on the sync step), amortized here as bytes/E per step.
        self.wire_bytes_per_step = 0
        if optimizer.uses_consensus:
            self.wire_bytes_per_step = exchange_bytes_per_step(
                flatbuf.make_flat_spec(stacked, lead=1),
                self.program.schedule if not self.program.schedule.is_static
                else topology,
                exchange, rounds=self.program.rounds,
                payloads=self.program.n_payloads,
                program=self.program)["per_step_bytes"]
        elif isinstance(optimizer, FedAvg):
            self.wire_bytes_per_step = mean_exchange_bytes_per_step(
                flatbuf.make_flat_spec(stacked, lead=1), topology.n_agents,
                period=optimizer.local_steps,
                payloads=2 if optimizer.mu else 1)["per_step_bytes"]

    def _make_eval(self):
        loss_fn = self.loss_fn

        def evaluate(params, batch):
            """Every agent evaluated on the same (global) eval batch."""

            def agent_eval(p):
                loss, metrics = loss_fn(p, batch)
                return loss, metrics

            losses, metrics = jax.vmap(agent_eval)(params)
            out = {"loss_mean": jnp.mean(losses), "loss_var": jnp.var(losses)}
            for k, v in metrics.items():
                out[f"{k}_mean"] = jnp.mean(v)
                out[f"{k}_var"] = jnp.var(v)
            return out

        return evaluate

    # ------------------------------------------------------------------
    def step(self, batch: Dict[str, np.ndarray]) -> Dict[str, float]:
        p, o, metrics = self._step_fn(self.state.params, self.state.opt_state, batch)
        self.state = TrainState(params=p, opt_state=o, step=self.state.step + 1)
        out = {k: float(v) for k, v in metrics.items()}
        self.history.log(self.state.step, **out)
        return out

    def evaluate(self, batch: Dict[str, np.ndarray]) -> Dict[str, float]:
        return {k: float(v) for k, v in self._eval_fn(self.state.params, batch).items()}

    def mean_params(self) -> PyTree:
        """The consensus (agent-averaged) model."""
        return jax.tree.map(lambda x: jnp.mean(x, axis=0), self.state.params)

    def agent_params(self, j: int) -> PyTree:
        return jax.tree.map(lambda x: x[j], self.state.params)


def train_loop(
    trainer: CollaborativeTrainer,
    batches,
    n_steps: int,
    *,
    eval_batch: Optional[Dict[str, np.ndarray]] = None,
    eval_every: int = 0,
    log_every: int = 0,
    printer: Optional[Callable[[str], None]] = None,
) -> MetricHistory:
    printer = printer or (lambda s: None)
    wire_per_step = getattr(trainer, "wire_bytes_per_step", 0)
    t0 = time.time()
    for i in range(n_steps):
        m = trainer.step(next(batches))
        if log_every and (i + 1) % log_every == 0:
            dt = time.time() - t0
            sps = (i + 1) / dt if dt > 0 else float("inf")
            wire = ""
            if wire_per_step:
                wire = f" wire={wire_per_step * (i + 1) / 1e6:.1f}MB"
            printer(f"step {i+1}/{n_steps} loss={m['loss']:.4f} "
                    f"cons={m['consensus_error']:.3e} {sps:.2f} steps/s"
                    f"{wire} ({dt:.1f}s)")
        if eval_batch is not None and eval_every and (i + 1) % eval_every == 0:
            em = trainer.evaluate(eval_batch)
            trainer.history.log(trainer.state.step, **{f"eval_{k}": v for k, v in em.items()})
    return trainer.history
