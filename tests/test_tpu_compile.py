"""Ahead-of-time compiles of the wire-path Pallas kernels for a TPU v5e.

The TPU compiler ships with jaxlib, so a chip that is described (not
attached) can compile a kernel here: a kernel the chip's compiler refuses —
a block shape Mosaic rejects, a primitive with no Mosaic lowering — fails
in this file at no chip time.  Each test compiles one kernel with
``interpret=False`` on the bf16 flat bucket of rwkv6-1.6b at its published
widths (two layers) and asserts the compiled module holds a Mosaic kernel
(``tpu_custom_call``); one more pins how many bytes the step's
consensus-error metric reads.  The topology is described inside a module
fixture: only the worker that runs these tests loads the TPU library.
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core import flatbuf
from repro.core.consensus import consensus_error_pytree
from repro.kernels.consensus_update import consensus_update as cu
from repro.kernels.consensus_update.ops import cdmsgd_update_flat
from repro.kernels.consensus_update.topk import topk_threshold_2d
from repro.nn import init_params, model_template

A = 2          # agents stacked on one chip by chip_smoke.py
S = 3          # ring stencil: self + two neighbours


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def shapes():
    """Parameter shapes of a 2-layer rwkv6-1.6b."""
    cfg = dataclasses.replace(get_config("rwkv6-1.6b"), n_layers=2)
    return jax.eval_shape(
        lambda: init_params(model_template(cfg), jax.random.PRNGKey(0)))


@pytest.fixture(scope="module")
def rows(shapes):
    """Rows of the one (bf16) flat bucket of a 2-layer rwkv6-1.6b."""
    (bucket,) = flatbuf.make_flat_spec(shapes).buckets
    assert bucket.dtype == jnp.bfloat16
    return bucket.rows


def _compile_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _structs(sharding, *specs):
    return [jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
            for shape, dt in specs]


def test_cdmsgd_update_bf16_compiles(one_chip, rows):
    """The sharded form: one agent's ring stencil, bf16 bucket."""
    b = (rows, 128)
    args = _structs(one_chip, ((S,) + b, jnp.bfloat16), ((S,), jnp.float32),
                    (b, jnp.bfloat16), (b, jnp.bfloat16))
    text = _compile_text(lambda n, w, g, m: cu.cdmsgd_update_2d(
        n, w, g, m, 0.01, 0.9, interpret=False), *args)
    assert "tpu_custom_call" in text


def test_cdmsgd_update_stacked_agents_compiles(one_chip, rows):
    """The stacked one-chip form the trainer runs: dense ``(A, A)`` Pi,
    the kernel vmapped over agent rows."""
    b = (A, rows, 128)
    args = _structs(one_chip, (b, jnp.bfloat16), ((A, A), jnp.float32),
                    (b, jnp.bfloat16), (b, jnp.bfloat16))
    text = _compile_text(lambda n, w, g, m: cdmsgd_update_flat(
        n, w, g, m, 0.01, 0.9, interpret=False), *args)
    assert "tpu_custom_call" in text


def test_cdmsgd_update_int8_wire_compiles(one_chip, rows):
    """int8 wire payloads + row scales, the native self tile apart."""
    b = (rows, 128)
    args = _structs(one_chip, ((S - 1,) + b, jnp.int8),
                    ((S - 1, rows, 1), jnp.float32), (b, jnp.bfloat16),
                    ((S,), jnp.float32), (b, jnp.bfloat16), (b, jnp.bfloat16))
    text = _compile_text(lambda n, sc, sb, w, g, m: cu.cdmsgd_update_2d(
        n, w, g, m, 0.01, 0.9, scales=sc, self_buf=sb, interpret=False),
        *args)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("stacked", [False, True],
                         ids=["one_agent", "stacked_agents"])
def test_sr_quantize_int8_compiles(one_chip, rows, stacked):
    lead = (A,) if stacked else ()
    args = _structs(one_chip, (lead + (rows, 128), jnp.float32),
                    (lead, jnp.int32))
    quant = lambda x, s: cu.sr_quantize_2d(x, s, exchange="int8",
                                           interpret=False)
    text = _compile_text(jax.vmap(quant) if stacked else quant, *args)
    assert "tpu_custom_call" in text


def test_topk_threshold_compiles(one_chip, rows):
    (x,) = _structs(one_chip, ((rows, 128), jnp.float32))
    text = _compile_text(
        lambda a: topk_threshold_2d(a, rows, interpret=False), x)
    assert "tpu_custom_call" in text


def _bytes_accessed(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().cost_analysis()[
        "bytes accessed"]


@pytest.mark.parametrize("where", ["alone", "after_unpack"])
def test_consensus_error_reads_the_parameters_once(one_chip, shapes, where):
    """The step's consensus-error metric on the agent-stacked tree: one
    fused read of each leaf, alone and where the step computes it, on the
    leaves ``flatbuf.unpack`` makes (there it counts as the bytes it adds
    to unpack's).  A reduction over the agent axis followed by a flattened
    sum of squares read the tree about 5.5 times alone and 5 after unpack;
    agent slices fused into unpack's ops read it 11 times."""
    stacked = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        (A,) + s.shape, s.dtype, sharding=one_chip), shapes)
    tree_bytes = sum(s.size * s.dtype.itemsize
                     for s in jax.tree.leaves(stacked))
    if where == "alone":
        added = _bytes_accessed(consensus_error_pytree, stacked)
    else:
        spec = flatbuf.make_flat_spec(stacked, lead=1)
        bufs = _structs(one_chip, *(((A, b.rows, 128), b.dtype)
                                    for b in spec.buckets))

        def unpacked(bs):
            return flatbuf.unpack(bs, spec)

        def with_metric(bs):
            params = unpacked(bs)
            return params, consensus_error_pytree(params)

        added = (_bytes_accessed(with_metric, bufs)
                 - _bytes_accessed(unpacked, bufs))
    assert added < 2 * tree_bytes


def test_sparse_update_refuses_compiled_mode():
    """Mosaic has no scatter-add lowering: the sparse top-k update raises in
    compiled mode instead of falling back to another path."""
    k_rows, rows = 2, 16
    vals = jnp.zeros((S - 1, k_rows, 128), jnp.int8)
    idx = jnp.zeros((S - 1, k_rows, 128), jnp.int32)
    scs = jnp.ones((S - 1, k_rows, 1), jnp.float32)
    buf = jnp.zeros((rows, 128), jnp.float32)
    with pytest.raises(NotImplementedError, match="scatter-add"):
        cu.cdmsgd_update_sparse_2d(vals, idx, scs, jnp.ones((S,)), buf, buf,
                                   0.01, 0.9, self_buf=buf, interpret=False)
