"""The plain reference of a cell's first steps, and the numbers compared.

The reference follows the program's first ``steps`` steps from the same
seed, weights and batches: each agent's loss and float32 gradient through
the configuration's plain reference, then the CDMSGD update as the
algorithm states it,

    v' = mu v - lr g,   x' = sum_b Pi[a, b] x_b + v'

computed in float32 and stored in the configuration's parameter type, as
the program stores its parameters and momentum.  It reads three things,
the same three that the program's run reads from its own state:

    losses        each step's loss, the mean over the agents
    grad_norms    per leaf and agent, the norm of the first gradient
    change_norms  per leaf and agent, the norm of x after the steps - x_0
"""

from __future__ import annotations

import functools
import statistics
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import weights


@functools.partial(jax.jit, donate_argnums=(2,))
def _momentum(v, lr, mu, g):
    """v' = mu v - lr g in float32 (the gradient's buffer is reused)."""
    return {k: mu * v[k].astype(jnp.float32) - lr * g[k] for k in g}


@functools.partial(jax.jit, static_argnames=("dtype",))
def _mix(xs, weights_row, v32, dtype):
    """x' = sum_b Pi[a, b] x_b + v' and v', stored in ``dtype``."""
    x = {k: (sum(w * x_b[k].astype(jnp.float32)
                 for w, x_b in zip(weights_row, xs)) + v32[k]).astype(dtype)
         for k in v32}
    return x, {k: v.astype(dtype) for k, v in v32.items()}


@jax.jit
def leaf_norms(tree: Dict[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


@jax.jit
def diff_norms(x: Dict[str, jnp.ndarray], x0: Dict[str, jnp.ndarray]):
    return {k: jnp.sqrt(jnp.sum(jnp.square(x[k].astype(jnp.float32)
                                           - x0[k].astype(jnp.float32))))
            for k in x}


def per_agent(norm_dicts: List[Dict[str, jnp.ndarray]]) -> Dict[str, list]:
    """[{path: norm} per agent] -> {path: [norm of agent 0, 1, ...]}."""
    host = jax.device_get(norm_dicts)
    return {k: [float(h[k]) for h in host] for k in host[0]}


def run(ref, cfg: dict, batches: List[dict], seed: int, pi: np.ndarray,
        lr: float, mu: float, cast: Callable, fault: Optional[str] = None):
    """Readings of the reference over ``len(batches)`` steps.

    ``fault`` plants one of the faults a run can have, for reading what it
    does to the compared numbers: ``"half_batch"`` (the loss of each row
    over its first half only), ``"no_exchange"`` (Pi replaced by the
    identity), ``"altered"`` (the embedding's update doubled where it is
    produced).
    """
    dtype = jnp.dtype(cfg["param_dtype"])
    shapes = ref.param_shapes(cfg)
    x0 = weights.make(shapes, seed, dtype)
    agents = pi.shape[0]
    if fault == "no_exchange":
        pi = np.eye(agents)

    def agent_loss(p32, inputs, targets):
        if fault == "half_batch":
            half = inputs.shape[-1] // 2
            inputs, targets = inputs[..., :half], targets[..., :half]
        return ref.loss(p32, inputs, targets, cfg, cast)

    grad_fn = jax.jit(lambda p, i, t: jax.value_and_grad(agent_loss)(
        {k: v.astype(jnp.float32) for k, v in p.items()}, i, t))
    xs = [x0] * agents
    vs = [{k: jnp.zeros_like(v) for k, v in x0.items()}] * agents
    del x0
    lr, mu = jnp.float32(lr), jnp.float32(mu)
    losses, grad_norms = [], None
    for t, batch in enumerate(batches):
        ls, v32 = [], []
        for a in range(agents):
            loss, g = grad_fn(xs[a], batch["inputs"][a], batch["targets"][a])
            ls.append(float(loss))
            if t == 0:
                grad_norms = (grad_norms or []) + [leaf_norms(g)]
            v32.append(_momentum(vs[a], lr, mu, g))
            del g
        losses.append(float(np.mean(ls)))
        if fault == "altered":
            v32 = [{**v, "embed/table": 2 * v["embed/table"]} for v in v32]
        new = [_mix(xs, [jnp.float32(w) for w in pi[a]], v32[a], dtype.name)
               for a in range(agents)]
        del v32
        xs, vs = [x for x, _ in new], [v for _, v in new]
        del new
    grad_norms = per_agent(grad_norms)
    del vs
    x0 = weights.make(shapes, seed, dtype)
    change = per_agent([diff_norms(x, x0) for x in xs])
    return {"losses": losses, "grad_norms": grad_norms, "change_norms": change}


# --------------------------------------------------------------------------
# the numbers compared
# --------------------------------------------------------------------------


def loss_gap(got: List[float], want: List[float]) -> float:
    """Largest |loss - reference loss| / |reference loss| over the steps."""
    if len(got) != len(want):
        raise ValueError(f"{len(got)} losses against {len(want)}")
    return max(abs(g - w) / abs(w) if np.isfinite(g) else float("inf")
               for g, w in zip(got, want))


def worst_norm_gap(got: Dict[str, list], want: Dict[str, list],
                   leaves=None):
    """Worst leaf by ``| |got| - |want| | / max(|want|, median |want|)``
    over ``leaves`` (every leaf when None) and agents.

    Returns ``(gap, "path agent a")``.
    """
    leaves = sorted(want) if leaves is None else sorted(leaves)
    if set(got) != set(want):
        raise ValueError(f"leaves differ: {sorted(set(got) ^ set(want))}")
    median = statistics.median(n for k in leaves for n in want[k])
    worst = (0.0, "")
    for k in leaves:
        for a, (g, w) in enumerate(zip(got[k], want[k])):
            gap = abs(g - w) / max(w, median) if np.isfinite(g) \
                else float("inf")
            if gap >= worst[0]:
                worst = (gap, f"{k} agent {a}")
    return worst


def moving_leaves(grad_norms: Dict[str, list], share: float = 1e-3):
    """Leaves whose reference gradient is at least ``share`` of the median
    leaf's on every agent; the others move by round-off alone."""
    median = statistics.median(n for v in grad_norms.values() for n in v)
    return [k for k, v in grad_norms.items() if min(v) >= share * median]


def compare(got: dict, want: dict) -> Dict[str, tuple]:
    """``{number: (value, where)}`` of the program's readings against the
    reference's."""
    return {
        "loss_gap": (loss_gap(got["losses"], want["losses"]), "steps"),
        "grad_norm_gap": worst_norm_gap(got["grad_norms"], want["grad_norms"]),
        "change_norm_gap": worst_norm_gap(
            got["change_norms"], want["change_norms"],
            moving_leaves(want["grad_norms"])),
    }
