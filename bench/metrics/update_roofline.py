"""The fused consensus update's share of its roofline: the least time the
CDMSGD update needs on the chip (its bytes over the HBM bandwidth, or its
FLOPs over the peak, whichever is longer; see
``bench/kernels/consensus_update.py``) over its measured device time per
step.  Moves ``tokens_per_s``."""

import pathlib

import jax.numpy as jnp

from lib import spec, traffic

HERE = pathlib.Path(__file__).resolve().parent


def read(ctx):
    kernel = spec.load_module(HERE / "update_kernel_ms.py")
    ns = kernel.kernel_ns(ctx)
    if not ns:
        return None
    cell = ctx.cell
    cost = spec.load_module(HERE.parent / "kernels" / "consensus_update.py")
    pi = traffic.TOPOLOGIES[cell.traffic["topology"]](cell.traffic["agents"])
    flops, bytes_ = cost.cost(cell.reference.param_shapes(cell.config),
                              jnp.dtype(cell.config["param_dtype"]).itemsize,
                              pi)
    least_s = max(bytes_ / ctx.peaks["hbm_bytes_per_s"],
                  flops / ctx.peaks["bf16_flops_per_s"])
    return 100.0 * least_s * ctx.window["steps"] / (ns / 1e9)
