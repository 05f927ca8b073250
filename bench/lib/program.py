"""The system under test: the program's collaborative trainer.

The window drives ``repro.core.trainer.CollaborativeTrainer.step`` as
``python -m repro.launch.train`` builds it (``build_trainer``), with the
traffic file's optimizer, topology, wire precision and schedule, at the
configuration's widths and kept depth.  Only this module imports the
program.
"""

from __future__ import annotations

import dataclasses
import functools
import sys

import jax
import jax.numpy as jnp

from . import weights
from .spec import ROOT


def _import_program():
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def arch_config(config: dict):
    """The program's ``ArchConfig`` at the configuration file's depth; every
    size the file states has to match the program's own."""
    _import_program()
    from repro.configs import get_config

    cfg = dataclasses.replace(get_config(config["program_arch"]),
                              n_layers=config["n_layers"])
    for key, want in config["program_sizes"].items():
        got = getattr(cfg, key)
        if got != want:
            raise ValueError(f"{config['name']}: the program's {key} is "
                             f"{got!r}, the configuration file states {want!r}")
    return cfg


def build(cell, seed: int, snapshot=lambda part: None):
    """The trainer of one cell, its parameters replaced by the benchmark's
    weights from ``seed`` (every agent starts from the same weights, as
    the program's own init has them).  ``snapshot(part)`` is called once
    the program has built its trainer, before the benchmark's weights."""
    config, traffic = cell.config, cell.traffic
    _import_program()
    from repro.launch import train as train_cli

    argv = ["--arch", config["program_arch"], "--preset", "full",
            "--agents", str(traffic["agents"]),
            "--topology", traffic["topology"],
            "--optimizer", traffic["optimizer"],
            "--lr", repr(traffic["lr"]), "--momentum", repr(traffic["momentum"]),
            "--exchange", traffic["exchange"], "--schedule", traffic["schedule"],
            "--batch", str(traffic["batch_per_agent"]),
            "--seq", str(traffic["seq"]),
            "--seed", str(seed % 2 ** 31)]
    if traffic["fused"]:
        argv.append("--fused")
    args = train_cli.build_parser().parse_args(argv)
    trainer, _ = train_cli.build_trainer(args, arch_config(config),
                                         printer=lambda s: None)
    snapshot("program_built")
    set_weights(trainer, cell, seed)
    return trainer


def set_weights(trainer, cell, seed: int) -> None:
    """Start ``trainer`` from the benchmark's weights of ``seed``, step 0
    and zero optimizer state (CDMSGD's momentum starts at zero)."""
    old = trainer.state.params
    agents = jax.tree.leaves(old)[0].shape[0]
    template = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                            old)
    # free the old parameters first: the set-up never holds two copies
    trainer.state = dataclasses.replace(trainer.state, params=None)
    jax.tree.map(lambda x: x.delete(), old)
    del old
    stacked = weights.make(cell.reference.param_shapes(cell.config), seed,
                           cell.config["param_dtype"], agents=agents)
    params = weights.into_tree(template, stacked)
    opt_state = trainer.state.opt_state
    if trainer.state.step:
        opt_state = jax.tree.map(jnp.zeros_like, opt_state)
    trainer.state = dataclasses.replace(trainer.state, params=params,
                                        opt_state=opt_state, step=0)


def _by_agent(norms) -> dict:
    """{path: (agents,) norms} -> {path: [norm of agent 0, 1, ...]}."""
    return {k: [float(n) for n in v] for k, v in jax.device_get(norms).items()}


@jax.jit
def _stacked_norms(tree: dict) -> dict:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)),
                                axis=tuple(range(1, v.ndim))))
            for k, v in tree.items()}


@functools.partial(jax.jit, static_argnames=("layout", "dtype"))
def _stacked_change_norms(tree: dict, key, layout, dtype) -> dict:
    """Per agent, the norm of each leaf's change from the seed's weights,
    which are made inside this program, one leaf at a time."""
    x0 = weights.make_from_key(key, layout, dtype)
    info = jnp.finfo(dtype)

    def stored(x):
        # the weights as stored in ``dtype``: XLA may otherwise keep them in
        # float32 through a convert pair and skip the rounding
        return jax.lax.reduce_precision(x.astype(jnp.float32), info.nexp,
                                        info.nmant)

    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)
                                           - stored(x0[k])[None]),
                                axis=tuple(range(1, v.ndim))))
            for k, v in tree.items()}


def first_steps(trainer, cell, batches, seed: int,
                snapshot=lambda part: None):
    """Drive ``trainer`` through its first steps with ``batches`` through
    the window's own call, and read what the reference reads.

    The first gradient, as the optimizer got it, is worked out from the
    momentum after one step (``v_1 = -lr g_1``); the change of the
    parameters is read as step ``len(batches) + 1`` would receive them.
    ``snapshot(part)`` is called after the first step, before any program
    of the benchmark's own has run on the step's output.
    """
    lr = cell.traffic["lr"]
    losses, grad_norms = [], None
    for t, batch in enumerate(batches):
        losses.append(trainer.step(batch)["loss"])
        if t == 0:
            snapshot("first_step")
            v1 = _by_agent(_stacked_norms(
                weights.tree_paths(trainer.state.opt_state.inner)))
            grad_norms = {k: [n / lr for n in v] for k, v in v1.items()}
    change = _by_agent(_stacked_change_norms(
        weights.tree_paths(trainer.state.params), weights.seed_key(seed),
        weights.layout(cell.reference.param_shapes(cell.config)),
        jnp.dtype(cell.config["param_dtype"]).name))
    return {"losses": losses, "grad_norms": grad_norms, "change_norms": change}
