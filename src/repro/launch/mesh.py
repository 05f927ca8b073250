"""Production mesh construction.

Defined as FUNCTIONS (not module-level constants) so importing this module
never touches jax device state — device count is locked at first jax init,
and only `dryrun.py` forces the 512-placeholder-device environment.
"""

from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips).

    Axes: ``data`` (CDSGD agent axis in the paper-faithful mapping),
    ``model`` (tensor/expert parallel), and ``pod`` (multi-pod; agents in
    the hierarchical mapping — see DESIGN.md §5).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_debug_mesh(n_data: int = 4, n_model: int = 2, *, multi_pod: bool = False):
    """Small host-device mesh for tests (requires the XLA host-device flag)."""
    if multi_pod:
        return _make_mesh((2, n_data, n_model), ("pod", "data", "model"))
    return _make_mesh((n_data, n_model), ("data", "model"))
