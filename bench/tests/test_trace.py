"""The trace reductions, on a small synthetic trace."""

import types

import pytest

from lib import spec, trace

MS = 1_000_000  # ns

RWKV = spec.load_module(spec.ROOT / "bench/configs/rwkv6-1.6b.py")
SMALL = {"n_layers": 1, "d_model": 4, "d_ff": 8, "decay_lora": 2,
         "n_heads": 1, "head_size": 4, "vocab_size": 16,
         "param_dtype": "bfloat16"}
UPDATE = spec.metric_reader("update_kernel_ms")


def custom_call(name, results):
    return (f"%{name} = ({results}) custom-call(f32[2,1,2]{{2,1,0}} %w), "
            'custom_call_target="tpu_custom_call", '
            "output_to_operand_aliasing={{0}: (4, {})}")


def synthetic():
    # window 0..100 ms; ops overlap (a while loop and its body), one custom
    # call (the update kernel); the host feeds, calls and syncs
    ops = [("while.1", 0, 40 * MS), ("fusion.2", 10 * MS, 20 * MS),
           (custom_call("custom-call.3", "bf16[2,3,128]{2,1,0:T(8,128)(2,1)}, "
                        "bf16[2,3,128]{2,1,0:T(8,128)(2,1)}"),
            45 * MS, 55 * MS),
           ("fusion.2", 70 * MS, 90 * MS), ("fusion.4", 95 * MS, 120 * MS)]
    host = [(trace.WINDOW_SPAN, 0, 100 * MS),
            ("bench:call", 38 * MS, 68 * MS), ("bench:sync", 68 * MS, 70 * MS),
            ("bench:feed", 90 * MS, 91 * MS)]
    return trace.Trace(ops={"/device:TPU:0": ops}, host=host,
                       window=(0, 100 * MS))


def test_busy_is_the_union_clipped_to_the_window():
    t = synthetic()
    # 0-40, 45-55, 70-90, 95-100 (fusion.4 is cut at the window's end)
    assert trace.busy_ns(t.ops["/device:TPU:0"], *t.window) == 75 * MS


def test_idle_gaps_and_what_the_host_did_in_them():
    t = synthetic()
    gaps = trace.idle_gaps(t.ops["/device:TPU:0"], *t.window)
    assert gaps == [(40 * MS, 45 * MS), (55 * MS, 70 * MS), (90 * MS, 95 * MS)]
    labelled = trace.longest_gaps(t, "/device:TPU:0")
    assert labelled == [("call", 0.015), ("call", 0.005), ("none", 0.005)]


def test_kernel_time_matches_the_op_text():
    # SMALL has 368 parameters an agent: 3 rows of 128, two agents
    cell = types.SimpleNamespace(config=SMALL, reference=RWKV,
                                 traffic={"agents": 2})
    shape = UPDATE.packed_shape(cell)
    assert shape == "bf16[2,3,128]"
    t = synthetic()
    assert [UPDATE.is_update(name, shape) for name, _, _ in
            t.ops["/device:TPU:0"]] == [False, False, True, False, False]
    assert UPDATE.kernel_ns(ctx(t)) == 10 * MS


def test_a_second_custom_call_is_left_out():
    # another Pallas kernel, and a fusion that writes the packed shape
    t = synthetic()
    t.ops["/device:TPU:0"] += [
        (custom_call("custom-call.5", "bf16[2,4,2048,64]{3,2,1,0}"),
         20 * MS, 30 * MS),
        ("%fusion.6 = bf16[2,3,128]{2,1,0} fusion(bf16[2,384] %p), "
         "kind=kLoop", 60 * MS, 65 * MS)]
    assert UPDATE.kernel_ns(ctx(t)) == 10 * MS
    assert UPDATE.read(ctx(t, steps=2)) == pytest.approx(5.0)


def test_top_ops_count_each_op_s_own_time_inside_the_window():
    t = synthetic()
    top = trace.top_ops(t.ops["/device:TPU:0"], *t.window, k=3)
    # while.1 holds fusion.2's first run (10 ms) inside its 40 ms
    assert sorted(top) == [("%custom-call.3 custom-call tpu_custom_call",
                            10 * MS), ("fusion.2", 30 * MS),
                           ("while.1", 30 * MS)]


def test_short_names_from_hlo_text():
    hlo = ('%vmap__.1 = (bf16[2,3,128]{2,1,0:T(8,128)(2,1)}) custom-call('
           'f32[2,1,2]{2,1,0:T(1,128)S(1)} %b), custom_call_target='
           '"tpu_custom_call", output_to_operand_aliasing={}')
    assert trace.short_name(hlo) == "%vmap__.1 custom-call tpu_custom_call"
    assert trace.short_name("%while.3 = (s32[]{:T(128)}, f32[4]) while("
                            "(s32[]) %t), body=%b") == "%while.3 while"
    assert trace.short_name("%fusion.7 = bf16[2,8]{1,0:T(2,128)(2,1)} "
                            "fusion(bf16[2,8] %a), kind=kOutput"
                            ) == "%fusion.7 fusion"


def ctx(t, steps=2):
    cell = types.SimpleNamespace(chips=1, config=SMALL, reference=RWKV,
                                 traffic={"agents": 2})
    return types.SimpleNamespace(trace=t, planes=sorted(t.ops),
                                 window={"steps": steps}, cell=cell,
                                 peaks={}, root=spec.ROOT)


def test_idle_share_and_kernel_readers():
    t = synthetic()
    idle = spec.metric_reader("device_idle_share").read(ctx(t))
    assert idle == pytest.approx(25.0)
    kernel = spec.metric_reader("update_kernel_ms").read(ctx(t, steps=2))
    assert kernel == pytest.approx(5.0)


def test_a_reader_that_finds_nothing_returns_nothing():
    t = synthetic()
    t.ops = {"/device:TPU:0": [op for op in t.ops["/device:TPU:0"]
                               if "custom" not in op[0]]}
    assert spec.metric_reader("update_kernel_ms").read(ctx(t)) is None
    assert spec.metric_reader("update_roofline").read(ctx(t)) is None
