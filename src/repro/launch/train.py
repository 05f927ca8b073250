"""Training launcher: collaborative CDSGD training for any --arch.

Drives the stacked :class:`repro.core.trainer.CollaborativeTrainer`: every
agent's parameters carry a leading agent axis on one device, and the fused
update runs compiled Pallas kernels on a TPU and the Pallas interpreter
elsewhere (:func:`repro.kernels.resolve_interpret`).  ``--preset tiny``
is the reduced config for CPU runs; ``--preset full`` keeps the published
widths.  The sharded step (one agent per chip, neighbours mixed by
``ppermute``) is :func:`repro.launch.steps.build_train_step`, which
``chip_smoke.py --chips 4`` and ``repro.launch.dryrun`` drive.

Examples:
  python -m repro.launch.train --arch gemma3-1b --preset tiny --steps 50
  python -m repro.launch.train --arch rwkv6-1.6b --preset tiny \
      --optimizer cdmsgd --topology ring --agents 8
"""

from __future__ import annotations

import argparse
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--preset", default="tiny", choices=["tiny", "full"])
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--agents", type=int, default=5)
    ap.add_argument("--topology", default="fully_connected")
    ap.add_argument("--optimizer", default="cdsgd")
    ap.add_argument("--fused", action="store_true",
                    help="flat-buffer fused consensus update (one Pallas "
                         "launch per dtype bucket; consensus optimizers only)")
    ap.add_argument("--exchange", default="f32",
                    choices=["f32", "bf16", "int8", "fp8"],
                    help="neighbor-exchange wire precision of the fused "
                         "path: int8/fp8 = stochastic-rounding quantization "
                         "before the exchange, ~4x fewer bytes per neighbor")
    ap.add_argument("--schedule", default="sync", choices=["sync", "overlap"],
                    help="exchange schedule: 'overlap' double-buffers the "
                         "quantized wire payloads in the optimizer state "
                         "(one-step-stale neighbor mixing, exchange off the "
                         "grad->update critical path; implies --fused)")
    ap.add_argument("--mixing-strategy", default="static",
                    choices=["static", "time_varying", "multi_round"],
                    help="mixing strategy of the fused consensus path: "
                         "'time_varying' cycles --topology-schedule's Pi_t, "
                         "'multi_round' runs --consensus-rounds inner "
                         "i-CDSGD rounds per step (implies --fused)")
    ap.add_argument("--consensus-rounds", type=int, default=1,
                    help="inner consensus rounds per gradient step (k-round "
                         "i-CDSGD: x' = Pi^k x - a g; k x the wire bytes)")
    ap.add_argument("--topology-schedule", default=None,
                    help="time-varying Pi_t schedule spec, e.g. "
                         "'alternating:ring:torus' or 'gossip:8' "
                         "(see repro.core.topology.make_topology_schedule)")
    ap.add_argument("--error-feedback", action="store_true",
                    help="carry quantization residuals in the optimizer "
                         "state and compress residual+payload (int8/fp8 "
                         "exchanges only; adds 0 wire bytes)")
    ap.add_argument("--momentum-mixing", default="none",
                    choices=["none", "mixed"],
                    help="'mixed' puts the momentum buffer on the wire and "
                         "mixes it with the same Pi (v' = mu Pi v - a g, "
                         "2010.11166) — stabilizes quantized exchanges at "
                         "large lr; 2x wire bytes; momentum optimizers only "
                         "(implies --fused)")
    ap.add_argument("--staleness", type=int, default=1,
                    help="bounded-staleness ring depth S: each neighbor slot "
                         "may be up to S steps stale before its weight is "
                         "masked out (arrival-renormalized mixing; requires "
                         "--schedule overlap, implies --fused)")
    ap.add_argument("--fault-schedule", default=None,
                    help="deterministic fault-injection spec, e.g. "
                         "'straggler:1:2', 'stall:1:1:3,drop:0:2', "
                         "'random:0.1:16' or 'none' (see "
                         "repro.core.faults.make_fault_schedule; requires "
                         "--schedule overlap, implies --fused)")
    ap.add_argument("--compressor", default="none",
                    help="wire compressor: 'none', 'int8'/'fp8' (alias the "
                         "--exchange precisions), 'topk:p' (top-k sparse, "
                         "density p, e.g. topk:0.01), 'topk:auto:B' "
                         "(adaptive per-bucket density against a byte "
                         "budget B per neighbor, e.g. topk:auto:65536) or "
                         "'rank:r' (rank-r PowerSGD-style factors, e.g. "
                         "rank:4); topk/rank are biased and require "
                         "--error-feedback (implies --fused)")
    ap.add_argument("--sparse-update", default=None,
                    choices=["on", "off"],
                    help="top-k compressor only: 'on' (the default for "
                         "topk) feeds the compact wire fields straight to "
                         "the fused sparse scatter-accumulate kernels "
                         "(O(k_rows) neighbor reads); 'off' forces the "
                         "dense decompress-then-update reference path "
                         "(O(rows))")
    ap.add_argument("--microbatch", type=int, default=1,
                    help="gradient-accumulation microbatches per step")
    ap.add_argument("--lr", type=float, default=0.01)
    ap.add_argument("--momentum", type=float, default=0.9)
    ap.add_argument("--local-steps", type=int, default=1,
                    help="FedAvg E: local steps between (gated) all-reduce "
                         "sync averages; wire accounting reports bytes/E")
    ap.add_argument("--lr-schedule", default="fixed", choices=["fixed", "diminishing"])
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--resume", action="store_true",
                    help="restore params AND the full optimizer state "
                         "(incl. overlap wire buffers / error-feedback "
                         "residuals) from --checkpoint-dir before training")
    ap.add_argument("--log-every", type=int, default=10)
    return ap


def config_for(args: argparse.Namespace):
    """The ``ArchConfig`` of ``--arch`` under ``--preset``."""
    from repro.configs import get_config

    cfg = get_config(args.arch)
    return cfg.reduced() if args.preset == "tiny" else cfg


def build_trainer(args: argparse.Namespace, cfg, *,
                  printer: Callable[[str], None] = print):
    """Everything :func:`main` does before the first step.

    Initializes the parameters of ``cfg`` from ``--seed``, normalizes the
    flags that imply ``--fused``, builds the optimizer, topology and
    :class:`CollaborativeTrainer`, reports the mixing program and its wire
    cost through ``printer``, and returns ``(trainer, batches)`` with
    ``batches`` the seeded per-agent LM batch stream.  Raises
    ``ValueError`` for a flag combination the trainer cannot run.
    """
    from repro.core import make_topology, make_optimizer, schedules
    from repro.core.trainer import CollaborativeTrainer
    from repro.data import make_lm_tokens, lm_agent_batches
    from repro.nn import model_template, init_params, loss_fn, count_params

    template = model_template(cfg)
    params = init_params(template, jax.random.PRNGKey(args.seed))
    printer(f"[train] {cfg.name}: {count_params(template):,} params, "
            f"{args.agents} agents over {args.topology}")

    sched = (args.lr if args.lr_schedule == "fixed"
             else schedules.diminishing(theta=args.lr * 10, eps=1.0, t=10.0))
    kw = {}
    if args.optimizer in ("cdmsgd", "cdmsgd_nesterov", "msgd", "fedavg"):
        kw["mu"] = args.momentum
    if args.optimizer == "fedavg":
        kw["local_steps"] = args.local_steps
    if args.exchange != "f32" and not args.fused:
        # the exchange knob lives on the fused flat-buffer path
        printer(f"[train] --exchange {args.exchange} implies --fused; enabling")
        args.fused = True
    if args.schedule == "overlap" and not args.fused:
        # the overlap wire double-buffer lives on the fused flat-buffer path
        printer("[train] --schedule overlap implies --fused; enabling")
        args.fused = True
    fault_tolerant = (args.staleness > 1
                      or (args.fault_schedule not in (None, "none")))
    if fault_tolerant and args.schedule != "overlap":
        raise ValueError(
            "--staleness > 1 / --fault-schedule need --schedule overlap "
            "(the staleness ring generalizes the overlap wire buffer)")
    nontrivial_mixing = (args.mixing_strategy != "static"
                         or args.consensus_rounds > 1 or args.error_feedback
                         or args.momentum_mixing != "none" or fault_tolerant
                         or args.compressor != "none")
    if nontrivial_mixing and not args.fused:
        # the strategy layer lives on the fused flat-buffer path
        printer("[train] non-static mixing strategy implies --fused; enabling")
        args.fused = True
    if args.fused:
        kw["fused"] = True
    opt = make_optimizer(args.optimizer, sched, **kw)
    topo = make_topology(args.topology, args.agents)

    def lm_loss(p, batch):
        extra = {}
        if cfg.modality in ("audio", "vlm"):
            extra["frontend"] = jnp.ones(
                (batch["inputs"].shape[0], cfg.frontend_tokens, cfg.frontend_dim),
                jnp.float32)
        return loss_fn(cfg, p, {**batch, **extra}, remat=True)

    trainer = CollaborativeTrainer(lm_loss, params, topo, opt,
                                   exchange=args.exchange,
                                   schedule=args.schedule,
                                   microbatches=args.microbatch,
                                   mixing_strategy=args.mixing_strategy,
                                   consensus_rounds=args.consensus_rounds,
                                   topology_schedule=args.topology_schedule,
                                   error_feedback=args.error_feedback,
                                   momentum_mixing=args.momentum_mixing,
                                   staleness=args.staleness,
                                   fault_schedule=args.fault_schedule,
                                   compressor=args.compressor,
                                   sparse_update=(None if args.sparse_update
                                                  is None else
                                                  args.sparse_update == "on"))

    from repro.core.consensus import describe_exchange_cost
    program = trainer.program
    if not program.is_trivial:
        printer(f"[train] mixing program: {program.describe()}")
        if not program.schedule.is_static:
            d = program.schedule.diagnostics(program.rounds)
            printer(f"[train] schedule effective gap "
                    f"{d['effective_gap']:.4f} (per-matrix "
                    f"{['%.4f' % g for g in d['per_matrix_gap']]})")
    if args.optimizer == "fedavg":
        # FedAvg moves no neighbor traffic — its cost is the whole-model
        # all-reduce once per E sync steps (gated; amortized bytes/E)
        printer(f"[train] fedavg all-reduce: {trainer.wire_bytes_per_step:,} "
                f"bytes/agent/step amortized (sync every "
                f"{opt.local_steps} steps"
                + (", params + momentum averaged" if opt.mu else "") + ")")
    else:
        printer("[train] " + describe_exchange_cost(
            trainer.state.params,
            program.schedule if not program.schedule.is_static else topo,
            trainer.exchange, rounds=program.rounds,
            payloads=program.n_payloads, program=program))
    tokens = make_lm_tokens(1 << 15, vocab=cfg.vocab_size, seed=args.seed)
    batches = lm_agent_batches(tokens, args.agents, args.batch, args.seq, seed=args.seed)
    return trainer, batches


def main(argv: Optional[Sequence[str]] = None) -> None:
    ap = build_parser()
    args = ap.parse_args(argv)
    from repro.checkpoint import restore_train_state, save_train_state
    from repro.core.trainer import train_loop
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    try:
        trainer, batches = build_trainer(args, config_for(args))
    except ValueError as e:
        ap.error(str(e))

    if args.resume:
        if not args.checkpoint_dir:
            ap.error("--resume needs --checkpoint-dir")
        from repro.core.trainer import TrainState
        p0, o0 = restore_train_state(args.checkpoint_dir,
                                     trainer.state.params,
                                     trainer.state.opt_state)
        trainer.state = TrainState(params=p0, opt_state=o0,
                                   step=int(o0.step))
        # fast-forward the (deterministic, seed-keyed) batch stream past the
        # steps the checkpointed run already consumed — otherwise the
        # resumed run re-trains on batches 0..step and the trajectory
        # silently diverges from an uninterrupted run
        for _ in range(trainer.state.step):
            next(batches)
        print(f"[train] resumed at step {trainer.state.step} (full opt "
              "state incl. wire/residual buffers; batch stream "
              "fast-forwarded)")

    train_loop(trainer, batches, args.steps, log_every=args.log_every, printer=print)
    final = trainer.history.rows[-1]
    print(f"[train] done: loss={final['loss']:.4f} "
          f"consensus_error={final['consensus_error']:.3e}")
    if args.checkpoint_dir:
        p = save_train_state(args.checkpoint_dir, trainer.state.step,
                             trainer.state.params, trainer.state.opt_state)
        print(f"[train] checkpoint: {p}")


if __name__ == "__main__":
    main()
