"""What the fused CDMSGD consensus update needs, per step.

Counted from the parameters' shapes and type as the algorithm needs them,
not from what a kernel happens to read: per agent and parameter it reads
its own value, each neighbour's (the other non-zeros of its row of Pi),
its momentum and its gradient, and writes its new value and momentum,

    v' = mu v - lr g,   x' = sum_b Pi[a, b] x_b + v'

so a change to how the kernel reads its neighbours does the same work.
"""

from __future__ import annotations

import math

import numpy as np


def cost(shapes: dict, itemsize: int, pi: np.ndarray):
    """``(flops, bytes)`` of one update of every agent.

    ``shapes`` maps each parameter to ``(shape, ...)`` for one agent; every
    parameter is stored in ``itemsize`` bytes (parameters, momentum and
    gradient alike, as the configuration states).
    """
    n = sum(math.prod(s[0]) for s in shapes.values())
    flops = bytes_ = 0
    for row in np.asarray(pi):
        reads = int(np.count_nonzero(row))      # self and each neighbour
        bytes_ += (reads + 2 + 2) * n * itemsize
        flops += (2 * reads + 4) * n            # mix; mu v - lr g; + v'
    return flops, bytes_
