"""Device time per step of the fused consensus update, over the traced
window's steps.  Moves ``tokens_per_s``.

The trace names a device op by its HLO text, which holds no kernel name
(Pallas keeps that inside the custom call's encoded body), and the program
has other Pallas kernels.  So the update is told by what it writes: a
``tpu_custom_call`` whose results are ``(agents, rows, 128)`` buffers of
the parameters' type, ``rows`` one agent's parameters in 128-wide rows, as
the program packs them (its new parameters and momentum).
"""

import math

import jax.numpy as jnp

from lib import trace

TARGET = 'custom_call_target="tpu_custom_call"'
HLO_TYPES = {"bfloat16": "bf16", "float16": "f16", "float32": "f32"}
LANE = 128


def packed_shape(cell) -> str:
    """The HLO type of the agents' packed parameters, e.g.
    ``bf16[2,3810208,128]``."""
    n = sum(math.prod(s[0]) for s in
            cell.reference.param_shapes(cell.config).values())
    dtype = HLO_TYPES[jnp.dtype(cell.config["param_dtype"]).name]
    return f"{dtype}[{cell.traffic['agents']},{-(-n // LANE)},{LANE}]"


def is_update(hlo: str, shape: str) -> bool:
    results, call, _ = hlo.partition(" custom-call(")
    return bool(call) and TARGET in hlo and shape in results


def kernel_ns(ctx) -> float:
    lo, hi = ctx.trace.window
    shape = packed_shape(ctx.cell)
    return sum(trace.busy_ns([op for op in ctx.trace.ops[p]
                              if is_update(op[0], shape)], lo, hi)
               for p in ctx.planes) / len(ctx.planes)


def read(ctx):
    ns = kernel_ns(ctx)
    return ns / 1e6 / ctx.window["steps"] if ns else None
