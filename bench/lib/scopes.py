"""The program's own tracing, read from the profiler trace: each device op's
phase and each idle interval's host span.

The program names its phases with ``jax.named_scope`` (``phase.grad``,
``phase.pack``, ...), which reaches each device op of the trace as its
op-name path (``jit(step_fn)/phase.update/phase.pack/reshape``, the
``tf_op`` stat of its trace event or of the event's metadata).  An op
takes the innermost ``phase.*`` of its path, or ``unscoped``; a fusion
carries its root's path.  Where ops nest (a loop and the ops of its
body), each instant of busy time belongs to the innermost op running
then, so the phases and ``unscoped`` add up to the busy time.

The host side is the program's two spans in ``CollaborativeTrainer.step``:
``repro:dispatch`` (launching the jitted step) and ``repro:fetch``
(waiting for its metrics).  The idle time of the device splits by the span
the host was in: dispatch, fetch, or outside both.

A program without these scopes or spans (an older one) reads as nothing:
:func:`per_step` gives None for what it lacks, and so do the metrics built
on it.
"""

from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

from lib import trace, xspace

PHASE_PREFIX = "phase."
UNSCOPED = "unscoped"
SPAN_PREFIX = "repro:"
DISPATCH, FETCH = "repro:dispatch", "repro:fetch"
OUTSIDE = "outside"
TRACE_DIR = ".bench_trace"      # under the checkout's root, as run.py writes it
# the event stat that holds an op's op-name path on the chip's trace
OP_PATH_STAT = "tf_op"


@dataclasses.dataclass
class Scoped:
    ops: Dict[str, List[trace.Interval]]    # device -> (phase, start, end)
    spans: List[trace.Interval]             # the program's host spans


def phase_of(path: str) -> str:
    """The innermost ``phase.*`` component of an op-name path, without its
    prefix, or ``unscoped``."""
    inner = [c for c in path.split("/") if c.startswith(PHASE_PREFIX)]
    return inner[-1][len(PHASE_PREFIX):] if inner else UNSCOPED


def from_space(space, planes: Sequence[str]) -> Scoped:
    """The phases of the device ops on ``planes`` and the program's host
    spans, from a profiler ``XSpace`` (:mod:`lib.xspace`)."""
    ops: Dict[str, List[trace.Interval]] = {p: [] for p in planes}
    spans: List[trace.Interval] = []
    for plane in space.planes:
        for line in plane.lines:
            if plane.name in ops and line.name == trace.DEVICE_OP_LINE:
                ops[plane.name] += [
                    (phase_of(stats.get(OP_PATH_STAT, "")), s, e)
                    for _, s, e, stats in xspace.events(plane, line)]
            elif plane.name.startswith("/host:"):
                spans += [(n, s, e) for n, s, e, _ in xspace.events(
                    plane, line, keep=lambda n: n.startswith(SPAN_PREFIX))]
    return Scoped(ops=ops, spans=spans)


_LOADED: Dict[Tuple[str, Tuple[str, ...]], Scoped] = {}


def load(trace_dir: str, planes: Sequence[str]) -> Scoped:
    """:func:`from_space` of the ``.xplane.pb`` under ``trace_dir``, read
    once for all the metrics of a run."""
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace file under {trace_dir}, "
                           f"found {files}")
    key = (files[0], tuple(planes))
    if key not in _LOADED:
        _LOADED[key] = from_space(xspace.read(files[0]), planes)
    return _LOADED[key]


def idle_ns(ops: List[trace.Interval], spans: List[trace.Interval],
            lo: float, hi: float) -> Dict[str, float]:
    """Idle time in [lo, hi] by the host span it fell in: ``dispatch``,
    ``fetch`` or ``outside`` both."""
    gaps = trace.idle_gaps(ops, lo, hi)
    out = {}
    for name in (DISPATCH, FETCH):
        inside = trace.merged([(s, e) for n, s, e in spans if n == name])
        out[name[len(SPAN_PREFIX):]] = sum(
            max(0.0, min(ge, se) - max(gs, ss))
            for gs, ge in gaps for ss, se in inside)
    out[OUTSIDE] = sum(e - s for s, e in gaps) - sum(out.values())
    return out


def per_step(scoped: Scoped, window: Tuple[float, float], steps: int
             ) -> Dict[str, Optional[Dict[str, float]]]:
    """Milliseconds per step in ``window``, averaged over the devices:
    ``{"phases": {phase: ms}, "idle": {dispatch|fetch|outside: ms}}``;
    ``phases`` is None where no op carries a phase scope, ``idle`` None
    where the host wrote no program span."""
    lo, hi = window
    per = 1e6 * steps * len(scoped.ops)
    phases: Dict[str, float] = {}
    idle: Dict[str, float] = {}
    for ops in scoped.ops.values():
        for k, ns in trace.self_times(ops, lo, hi).items():
            phases[k] = phases.get(k, 0.0) + ns / per
        for k, ns in idle_ns(ops, scoped.spans, lo, hi).items():
            idle[k] = idle.get(k, 0.0) + ns / per
    return {"phases": phases if set(phases) - {UNSCOPED} else None,
            "idle": idle if scoped.spans else None}


def read(ctx) -> Dict[str, Optional[Dict[str, float]]]:
    """:func:`per_step` of a traced run, for a metric reader's ``ctx``."""
    scoped = load(str(ctx.root / TRACE_DIR), ctx.planes)
    return per_step(scoped, ctx.trace.window, ctx.window["steps"])
