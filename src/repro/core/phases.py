"""The named phases of a collaborative step, as profiler scopes.

Every op the step traces inside a phase carries ``phase.<name>`` in its
``op_name`` metadata (``jax.named_scope``), so a device trace can give each
op its phase: the innermost ``phase.*`` component of the path wins (a pack
inside the update group is ``pack``), and a fusion takes its root's.  The
scopes change op metadata only, never the computation.
"""

from __future__ import annotations

import functools

import jax

PHASES = ("grad", "pack", "quantize", "exchange", "update", "unpack",
          "metrics")
PREFIX = "phase."


def scope(name: str):
    """The profiler scope of one phase: ``with scope("pack"): ...``."""
    if name not in PHASES:
        raise ValueError(f"unknown phase {name!r}; expected one of {PHASES}")
    return jax.named_scope(PREFIX + name)


def scoped(name: str):
    """Decorator: trace the function inside :func:`scope` ``(name)``."""
    scope(name)     # an unknown phase fails here, where the module loads

    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with scope(name):
                return fn(*args, **kwargs)
        return inner

    return wrap
