"""Shared utilities: pytree arithmetic, PRNG helpers, metrics logging."""

from repro.utils.tree import (
    tree_add,
    tree_axpy,
    tree_dot,
    tree_l2_norm,
    tree_scale,
    tree_sub,
    tree_weighted_sum,
    tree_zeros_like,
    tree_cast,
    tree_size,
    tree_bytes,
)
from repro.utils.prng import split_key, fold_in_name
from repro.utils.metrics import MetricHistory

__all__ = [
    "tree_add",
    "tree_axpy",
    "tree_dot",
    "tree_l2_norm",
    "tree_scale",
    "tree_sub",
    "tree_weighted_sum",
    "tree_zeros_like",
    "tree_cast",
    "tree_size",
    "tree_bytes",
    "split_key",
    "fold_in_name",
    "MetricHistory",
]
