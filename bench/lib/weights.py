"""Weights from the seed, made on the device in one jitted call.

The configuration's reference module names every parameter by its path in
the program's parameter tree, with its shape and an init rule.  The same
function makes the program's stacked copy (every agent starts from the
same weights) and, after the window, the reference's own copy: the
reference takes nothing that the program made.
"""

from __future__ import annotations

import functools
import zlib
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Rule = Tuple  # ("normal", std) | ("uniform", lo, hi) | ("zeros",) | ...


def seed_key(seed: int) -> jax.Array:
    """A threefry key from all 64 low bits of ``seed`` (``PRNGKey`` would
    keep only 32 of them)."""
    s = int(seed) & (2 ** 64 - 1)
    return jax.random.wrap_key_data(
        jnp.asarray([s >> 32, s & 0xFFFFFFFF], jnp.uint32))


def _leaf(key, shape, rule: Rule) -> jnp.ndarray:
    kind = rule[0]
    if kind == "zeros":
        return jnp.zeros(shape, jnp.float32)
    if kind == "ones":
        return jnp.ones(shape, jnp.float32)
    if kind == "normal":
        return rule[1] * jax.random.normal(key, shape, jnp.float32)
    if kind == "uniform":
        return jax.random.uniform(key, shape, jnp.float32, rule[1], rule[2])
    if kind == "log_arange":
        # S4D-real init of a diagonal state matrix: log(1 .. n) on the last
        # axis, the same in every row
        n = shape[-1]
        return jnp.broadcast_to(jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32)),
                                shape)
    if kind == "inv_softplus_loguniform":
        # a step size dt drawn log-uniformly in [lo, hi], stored as the bias
        # that softplus maps onto it
        u = jax.random.uniform(key, shape, jnp.float32)
        dt = jnp.exp(np.log(rule[1]) + u * (np.log(rule[2]) - np.log(rule[1])))
        return dt + jnp.log(-jnp.expm1(-dt))
    raise ValueError(f"unknown init rule {rule!r}")


def _path_key(key, path: str):
    return jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


@functools.partial(jax.jit, static_argnames=("layout", "dtype", "agents"))
def make_from_key(key, layout, dtype, agents=None):
    """:func:`make` from a key and a :func:`layout` (also inside a jitted
    function)."""
    out = {}
    for path, shape, rule in layout:
        x = _leaf(_path_key(key, path), shape, rule).astype(dtype)
        if agents:
            x = jnp.broadcast_to(x[None], (agents,) + shape)
        out[path] = x
    return out


def layout(shapes: Dict[str, Tuple[tuple, Rule]]) -> tuple:
    """The hashable form of ``shapes`` that the jitted makers take."""
    return tuple((p, tuple(s), tuple(r)) for p, (s, r) in sorted(shapes.items()))


def make(shapes: Dict[str, Tuple[tuple, Rule]], seed: int, dtype,
         agents: Optional[int] = None) -> Dict[str, jnp.ndarray]:
    """``{path: array}`` in ``dtype``; with ``agents``, each array gets a
    leading agent axis holding the same weights for every agent."""
    return make_from_key(seed_key(seed), layout(shapes),
                         jnp.dtype(dtype).name, agents)


def tree_paths(tree) -> Dict[str, jnp.ndarray]:
    """``{"a/b/c": leaf}`` of a nested dict pytree."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {"/".join(str(k.key) for k in path): leaf for path, leaf in flat}


def into_tree(tree, by_path: Dict[str, jnp.ndarray]):
    """``tree`` with each leaf replaced by ``by_path[its path]``; the paths,
    shapes and dtypes have to match exactly."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    have = {"/".join(str(k.key) for k in p) for p, _ in flat}
    if have != set(by_path):
        raise ValueError(f"parameter paths differ: program only "
                         f"{sorted(have - set(by_path))}, reference only "
                         f"{sorted(set(by_path) - have)}")
    leaves = []
    for path, old in flat:
        new = by_path["/".join(str(k.key) for k in path)]
        if new.shape != old.shape or new.dtype != old.dtype:
            raise ValueError(f"{path}: program {old.shape} {old.dtype}, "
                             f"reference {new.shape} {new.dtype}")
        leaves.append(new)
    return jax.tree.unflatten(treedef, leaves)
