"""The FLOP and byte counts behind step_mfu and update_roofline, against
hand counts."""

import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lib import spec, trace, traffic

RWKV = spec.load_module(spec.ROOT / "bench/configs/rwkv6-1.6b.py")
HYMBA = spec.load_module(spec.ROOT / "bench/configs/hymba-1.5b.py")
COST = spec.load_module(spec.ROOT / "bench/kernels/consensus_update.py")

SMALL_RWKV = {"n_layers": 1, "d_model": 4, "d_ff": 8, "decay_lora": 2,
              "n_heads": 1, "head_size": 4, "vocab_size": 16}
SMALL_HYMBA = {"n_layers": 1, "d_model": 4, "n_heads": 2, "n_kv_heads": 1,
               "head_dim": 2, "d_ff": 8, "ssm_inner": 4, "ssm_state": 2,
               "window": 2, "vocab_size": 16}


def test_rwkv6_flops_by_hand():
    macs = (5 * 4 * 4          # r, k, v, g, o projections
            + 4 * 2 + 2 * 4    # decay LoRA
            + 4 * 8 + 8 * 4    # channel mix key and value
            + 4 * 4            # channel mix receptance
            + 1 * 4 * 4 * 2    # WKV: read-out r.S and update k v^T, per head
            + 4 * 16)          # LM head; the embedding is a gather
    assert RWKV.flops_per_token(SMALL_RWKV, seq=64) == 2 * macs


def test_hymba_flops_by_hand():
    keys = (1 + 2 + 2 + 2) / 4          # positions 0..3, window 2
    macs = (4 * 2 * 2 + 2 * 4 * 1 * 2 + 2 * 2 * 4    # q; k, v; o
            + 2 * 2 * 2 * keys                      # QK^T and AV
            + 4 * 8 + 4 * 4 + 2 * 4 * 2 + 4 * 4     # in, dt, B and C, out
            + 2 * 4 * 2                             # SSM update and read-out
            + 3 * 4 * 8                             # gated MLP
            + 4 * 16)                               # LM head
    assert HYMBA.flops_per_token(SMALL_HYMBA, seq=4) == pytest.approx(2 * macs)


@pytest.mark.parametrize("ref,cfg", [(RWKV, SMALL_RWKV), (HYMBA, SMALL_HYMBA)])
def test_the_embedding_is_not_counted(ref, cfg):
    # one more vocabulary row adds the LM head's d MACs, not the gather's
    bigger = {**cfg, "vocab_size": cfg["vocab_size"] + 1}
    grow = ref.flops_per_token(bigger, 4) - ref.flops_per_token(cfg, 4)
    assert grow == pytest.approx(2 * cfg["d_model"])


def test_update_bytes_against_a_flat_spec():
    sys.path.insert(0, str(spec.ROOT / "src"))
    from repro.core import flatbuf

    cfg = {**SMALL_RWKV, "d_model": 64, "d_ff": 96, "n_heads": 1,
           "head_size": 64, "n_layers": 2}
    shapes = RWKV.param_shapes(cfg)
    tree = {k: jax.ShapeDtypeStruct(s, jnp.bfloat16)
            for k, (s, _) in shapes.items()}
    fs = flatbuf.make_flat_spec(tree)
    real = sum(b.n_real for b in fs.buckets)
    pi = traffic.ring_pi(2)
    flops, bytes_ = COST.cost(shapes, 2, pi)
    # per agent: read self, one neighbour, momentum, grad; write x and v
    assert bytes_ == 2 * 6 * real * 2
    assert flops == 2 * (2 * 2 + 4) * real
    # a ring of 4: two neighbours each
    assert COST.cost(shapes, 2, traffic.ring_pi(4))[1] == 4 * 7 * real * 2


def test_ring_weights():
    np.testing.assert_allclose(traffic.ring_pi(2), [[0.5, 0.5], [0.5, 0.5]])
    p4 = traffic.ring_pi(4)
    np.testing.assert_allclose(p4.sum(0), 1)
    np.testing.assert_allclose(np.diag(p4), 1 / 3)


def _ctx(kernel_s, steps, tokens_per_s):
    cfg = {**SMALL_RWKV, "param_dtype": "bfloat16"}
    cell = types.SimpleNamespace(
        chips=1, config=cfg, reference=RWKV,
        traffic={"topology": "ring", "agents": 2, "seq": 64})
    packed = spec.metric_reader("update_kernel_ms").packed_shape(cell)
    ops = [(f'%c.1 = ({packed}, {packed}) custom-call(), custom_call_target='
            '"tpu_custom_call"', 0, kernel_s * 1e9)]
    t = trace.Trace(ops={"/device:TPU:0": ops}, host=[], window=(0, 10e9))
    peaks = spec.peaks("TPU v5 lite")
    return types.SimpleNamespace(trace=t, planes=["/device:TPU:0"],
                                 window={"steps": steps,
                                         "tokens_per_s": tokens_per_s},
                                 cell=cell, peaks=peaks, root=spec.ROOT)


def test_shares_reach_100_percent_exactly_at_the_peak():
    peaks = spec.peaks("TPU v5 lite")
    cfg = {**SMALL_RWKV, "param_dtype": "bfloat16"}
    _, bytes_ = COST.cost(RWKV.param_shapes(cfg), 2, traffic.ring_pi(2))
    least = bytes_ / peaks["hbm_bytes_per_s"]
    roof = spec.metric_reader("update_roofline")
    assert roof.read(_ctx(3 * least, 3, 1.0)) == pytest.approx(100.0)
    assert roof.read(_ctx(6 * least, 3, 1.0)) == pytest.approx(50.0)
    at_peak = peaks["bf16_flops_per_s"] / (3 * RWKV.flops_per_token(cfg, 64))
    mfu = spec.metric_reader("step_mfu")
    assert mfu.read(_ctx(1.0, 3, at_peak)) == pytest.approx(100.0)


def test_an_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        spec.peaks("TPU v99")
