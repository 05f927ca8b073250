"""Reduction of a profiler trace to device busy time, kernel time and the
idle gaps, each gap named by what the host was doing in it.

The reduction works on plain tuples, so that it can be checked on a small
synthetic trace:

    device ops   {device: [(name, start_ns, end_ns), ...]}
    host spans   [(name, start_ns, end_ns), ...]   the benchmark's own
                 ``TraceAnnotation`` spans around its calls into the program
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

Interval = Tuple[str, float, float]

DEVICE_OP_LINE = "XLA Ops"
HOST_PREFIX = "bench:"      # the benchmark's host spans
WINDOW_SPAN = "bench:window"


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Interval]]
    host: List[Interval]
    window: Tuple[float, float]          # ns, from the window's host span


def merged(intervals: Sequence[Tuple[float, float]]) -> List[List[float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def busy_ns(ops: List[Interval], lo: float, hi: float) -> float:
    """Length of the union of the op intervals inside [lo, hi]."""
    return sum(e - s for s, e in merged(clip([(s, e) for _, s, e in ops],
                                            lo, hi)))


def idle_gaps(ops: List[Interval], lo: float, hi: float):
    """The intervals of [lo, hi] in which no op ran."""
    gaps, t = [], lo
    for s, e in merged(clip([(s, e) for _, s, e in ops], lo, hi)):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def host_activity(host: List[Interval], t: float) -> str:
    """The innermost benchmark span open at ``t`` (its name without the
    prefix), or ``"none"``."""
    best = None
    for name, s, e in host:
        if s <= t <= e and name != WINDOW_SPAN and (
                best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0][len(HOST_PREFIX):] if best else "none"


def short_name(hlo: str) -> str:
    """``%fusion.709 fusion`` from the op's HLO text, as the profiler names
    device ops (``%name = shape opcode(operands), ...``); a custom call
    keeps its target."""
    m = re.match(r"(%?[\w.\-]+) = .*?\s([a-z][a-z\-]*)\(", hlo)
    if not m:
        return hlo[:80]
    name = f"{m.group(1)} {m.group(2)}"
    target = re.search(r'custom_call_target="([^"]+)"', hlo)
    return f"{name} {target.group(1)}" if target else name


def self_times(ops: List[Interval], lo: float, hi: float) -> Dict[str, float]:
    """Each op's own time inside [lo, hi]: its interval less the ops nested
    in it (a loop's body ops are listed inside the loop op)."""
    total: Dict[str, float] = {}
    stack: List[list] = []          # [name, end, own time so far, start]

    def close(entry):
        total[entry[0]] = total.get(entry[0], 0.0) + entry[2]

    for name, s, e in sorted(((n, max(s, lo), min(e, hi)) for n, s, e in ops
                              if e > lo and s < hi),
                             key=lambda op: (op[1], -op[2])):
        while stack and stack[-1][1] <= s:
            close(stack.pop())
        if stack:                   # nested: not the parent's own time
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([name, e, e - s, s])
    while stack:
        close(stack.pop())
    return total


def top_ops(ops: List[Interval], lo: float, hi: float, k: int = 10):
    """The ``k`` ops with the most own time in [lo, hi], by short name."""
    total: Dict[str, float] = {}
    for name, ns in self_times(ops, lo, hi).items():
        key = short_name(name)
        total[key] = total.get(key, 0.0) + ns
    return sorted(total.items(), key=lambda kv: -kv[1])[:k]


def longest_gaps(trace: "Trace", device: str, k: int = 10):
    lo, hi = trace.window
    gaps = idle_gaps(trace.ops[device], lo, hi)
    gaps.sort(key=lambda g: -(g[1] - g[0]))
    return [(host_activity(trace.host, (s + e) / 2), (e - s) / 1e9)
            for s, e in gaps[:k]]


# --------------------------------------------------------------------------
# reading the profiler's file
# --------------------------------------------------------------------------


def load(trace_dir: str, devices: Sequence[int]) -> Trace:
    """Read the ``.xplane.pb`` the profiler wrote under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace file under {trace_dir}, "
                           f"found {files}")
    data = ProfileData.from_file(files[0])
    wanted = {f"/device:TPU:{d}" for d in devices}
    ops: Dict[str, List[Interval]] = {}
    host: List[Interval] = []
    for plane in data.planes:
        if plane.name in wanted:
            for line in plane.lines:
                if line.name != DEVICE_OP_LINE:
                    continue
                evs = ops.setdefault(plane.name, [])
                for ev in line.events:
                    evs.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        host.append((ev.name, ev.start_ns,
                                     ev.start_ns + ev.duration_ns))
    windows = [(s, e) for n, s, e in host if n == WINDOW_SPAN]
    if len(windows) != 1:
        raise RuntimeError(f"expected one {WINDOW_SPAN} span, found "
                           f"{len(windows)}")
    missing = wanted - set(ops)
    if missing:
        raise RuntimeError(f"no {DEVICE_OP_LINE!r} line for {sorted(missing)}")
    return Trace(ops=ops, host=host, window=windows[0])
