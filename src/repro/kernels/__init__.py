"""Pallas TPU kernels for the compute hot-spots.

* ``consensus_update`` — fused Pi-mixing + (momentum) SGD update, the
  paper's per-step parameter sweep (eq. 5) in one HBM pass.
* ``flash_attention`` — blockwise online-softmax attention for prefill
  (causal / sliding-window / GQA).
* ``rwkv_scan`` — chunked WKV6 recurrence with VMEM-resident state.

Each subpackage ships ``<name>.py`` (pl.pallas_call + BlockSpec),
``ops.py`` (jit'd wrapper in model layout) and ``ref.py`` (pure-jnp
oracle); tests sweep shapes/dtypes in ``interpret=True`` on CPU, and
``tests/test_tpu_compile.py`` compiles the wire-path kernels for a
described TPU v5e.
"""

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """The one place that decides compiled vs interpreted Pallas kernels.

    ``None`` (every entry point's default) follows the backend the arrays
    run on: compiled Mosaic kernels on a TPU, the Pallas interpreter on any
    other backend (the CPU tests).  An explicit bool wins, so a test that
    means interpret mode says ``interpret=True``.
    """
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret
