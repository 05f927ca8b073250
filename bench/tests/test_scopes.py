"""Phases of device ops and host spans of idle time, on a small synthetic
trace."""

import types

import pytest

from lib import scopes, spec, trace

MS = 1_000_000  # ns
DEVICE = "/device:TPU:0"
METRICS = ("grad_ms", "flatbuf_ms", "step_metrics_ms", "idle_dispatch_ms",
           "idle_fetch_ms")


def synthetic():
    # two steps in a 0..100 ms window; each step: a layer loop (grad) whose
    # body holds a grad fusion and an op with no scope (the compiler's),
    # then pack, the update kernel, unpack, the metrics and a copy with no
    # scope; the host dispatches each step and fetches its metrics
    ops, spans = [], []
    for t0 in (0, 50 * MS):
        ops += [("grad", t0 + 2 * MS, t0 + 30 * MS),            # the loop
                ("grad", t0 + 4 * MS, t0 + 14 * MS),            # its body
                ("unscoped", t0 + 20 * MS, t0 + 22 * MS),       # its body
                ("pack", t0 + 30 * MS, t0 + 33 * MS),
                ("update", t0 + 33 * MS, t0 + 40 * MS),
                ("unpack", t0 + 40 * MS, t0 + 42 * MS),
                ("metrics", t0 + 42 * MS, t0 + 44 * MS),
                ("unscoped", t0 + 44 * MS, t0 + 45 * MS)]
        spans += [("repro:dispatch", t0, t0 + 3 * MS),
                  ("repro:fetch", t0 + 3 * MS, t0 + 46 * MS)]
    return scopes.Scoped(ops={DEVICE: ops}, spans=spans)


def test_the_innermost_phase_scope_wins():
    assert scopes.phase_of("jit(step_fn)/phase.update/phase.pack/"
                           "reshape") == "pack"
    assert scopes.phase_of("jit(step_fn)/phase.grad/while/body/"
                           "closed_call/dot_general") == "grad"


def test_an_op_with_no_phase_scope_is_unscoped():
    assert scopes.phase_of("jit(step_fn)/jit(main)/add") == scopes.UNSCOPED
    assert scopes.phase_of("") == scopes.UNSCOPED


def test_an_op_with_no_phase_inside_a_scoped_loop_stays_unscoped():
    # the loop's phase is not lent to the ops of its body that have none
    got = trace.self_times(synthetic().ops[DEVICE], 0, 50 * MS)
    assert got["unscoped"] == 3 * MS        # the body's 2 ms, the copy's 1
    assert got["grad"] == 26 * MS           # the loop's own 16, its body's 10


def test_phases_and_unscoped_add_up_to_the_busy_time():
    t = synthetic()
    ops = t.ops[DEVICE]
    got = trace.self_times(ops, 0, 100 * MS)
    # per step: the loop's own 16 ms and its body's 10 are grad; the body's
    # op with no scope (2 ms) and the copy (1 ms) are unscoped
    assert got == {"grad": 52 * MS, "unscoped": 6 * MS, "pack": 6 * MS,
                   "update": 14 * MS, "unpack": 4 * MS, "metrics": 4 * MS}
    assert sum(got.values()) == trace.busy_ns(ops, 0, 100 * MS)


def test_idle_time_splits_by_the_host_span_over_it():
    t = synthetic()
    got = scopes.idle_ns(t.ops[DEVICE], t.spans, 0, 100 * MS)
    # per step: 0-2 ms in dispatch, 45-46 in fetch, 46-50 in neither
    assert got == {"dispatch": 4 * MS, "fetch": 2 * MS, "outside": 8 * MS}
    assert sum(got.values()) == sum(
        e - s for s, e in trace.idle_gaps(t.ops[DEVICE], 0, 100 * MS))


def test_values_are_per_step():
    got = scopes.per_step(synthetic(), (0, 100 * MS), steps=2)
    assert got["phases"]["grad"] == pytest.approx(26.0)
    assert got["phases"]["unscoped"] == pytest.approx(3.0)
    assert got["idle"] == pytest.approx({"dispatch": 2.0, "fetch": 1.0,
                                         "outside": 4.0})


def test_the_readers_on_the_synthetic_trace(monkeypatch):
    monkeypatch.setattr(scopes, "load", lambda *_: synthetic())
    ctx = types.SimpleNamespace(trace=types.SimpleNamespace(
        window=(0, 100 * MS)), planes=[DEVICE], window={"steps": 2},
        root=spec.ROOT)
    got = {m: spec.metric_reader(m).read(ctx) for m in METRICS}
    assert got == pytest.approx({"grad_ms": 26.0, "flatbuf_ms": 5.0,
                                 "step_metrics_ms": 2.0,
                                 "idle_dispatch_ms": 2.0,
                                 "idle_fetch_ms": 1.0})


def test_a_program_without_scopes_or_spans_reads_nothing(monkeypatch):
    t = synthetic()
    bare = scopes.Scoped(ops={DEVICE: [(scopes.UNSCOPED, s, e) for _, s, e
                                       in t.ops[DEVICE]]}, spans=[])
    monkeypatch.setattr(scopes, "load", lambda *_: bare)
    ctx = types.SimpleNamespace(trace=types.SimpleNamespace(
        window=(0, 100 * MS)), planes=[DEVICE], window={"steps": 2},
        root=spec.ROOT)
    assert {m: spec.metric_reader(m).read(ctx) for m in METRICS} == \
        dict.fromkeys(METRICS)


XSPACE = """
planes {
  name: "/device:TPU:0"
  lines { name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 5000000000 }
    events { metadata_id: 2 offset_ps: 6000000000 duration_ps: 1000000000
      stats { metadata_id: 1 str_value: "jit(step_fn)/phase.grad/while" } }
    events { metadata_id: 3 offset_ps: 7000000000 duration_ps: 1000000000
      stats { metadata_id: 1 ref_value: 9 } }
    events { metadata_id: 4 offset_ps: 8000000000 duration_ps: 1000000000 }
  }
  event_metadata { key: 1 value { id: 1
    name: "%fusion.1 = bf16[2,3]{1,0} fusion(bf16[2,3]{1,0} %p), kind=kLoop"
    stats { metadata_id: 1
            str_value: "jit(step_fn)/phase.update/phase.pack/reshape" } } }
  event_metadata { key: 2 value { id: 2 name: "%while.2 = () while(() %t)" } }
  event_metadata { key: 3 value { id: 3 name: "%copy.3 = bf16[2] copy(%p)" } }
  event_metadata { key: 4 value { id: 4 name: "%copy.4 = bf16[2] copy(%p)"
    stats { metadata_id: 2 str_value: "copy.4" } } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
  stat_metadata { key: 2 value { id: 2 name: "hlo_op" } }
  stat_metadata { key: 9 value { id: 9
    name: "jit(step_fn)/phase.metrics/reduce_sum" } }
}
planes {
  name: "/host:CPU"
  lines { name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 9000000000 } }
  event_metadata { key: 1 value { id: 1 name: "repro:dispatch" } }
  event_metadata { key: 2 value { id: 2 name: "bench:call" } }
}
"""


def test_the_phase_is_read_where_the_chip_s_trace_carries_it(tmp_path):
    from google.protobuf import text_format

    from lib import xspace

    # the op-name path is the ``tf_op`` stat, of the event's metadata (the
    # fusion) or of the event itself (the loop), its string interned or not
    # (the metrics op); an op with none is unscoped
    space = text_format.Parse(XSPACE, xspace.message_class("XSpace")())
    path = tmp_path / "plugins" / "profile" / "run" / "host.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(space.SerializeToString())
    got = scopes.load(str(tmp_path), [DEVICE])
    assert got.ops == {DEVICE: [("pack", 0, 5 * MS), ("grad", 6 * MS, 7 * MS),
                                ("metrics", 7 * MS, 8 * MS),
                                (scopes.UNSCOPED, 8 * MS, 9 * MS)]}
    assert got.spans == [("repro:dispatch", 0, 2 * MS)]
