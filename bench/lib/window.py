"""The measured window and its accounting.

The window starts when the last warm-up step has completed and ends when
the last counted step has; every step in it counts.  A step is the feed
(the next batch), the call into the program, and ``block_until_ready`` on
the new parameters, each timed by the host clock.  The rate is all the
counted steps' tokens over the window's elapsed time.
"""

from __future__ import annotations

import contextlib
import gc
import statistics
import threading
import time
from typing import Callable, Dict, List

import jax

PHASES = ("feed", "call", "sync")
SLOW = 1.5          # a step longer than SLOW x the median is listed


class Counters:
    """Compilations (JAX's monitoring events) and garbage collections (by
    generation) since the last :meth:`reset`."""

    COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                      "/jax/core/compile/jaxpr_trace_duration")

    def __init__(self):
        self.events: Dict[str, int] = {}
        self.gc = [0, 0, 0]
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        jax.monitoring.register_event_listener(
            lambda name, **kw: self._on_event(name, 0.0))
        gc.callbacks.append(self._on_gc)

    def _on_event(self, name, _secs, **_kw):
        if name in self.COMPILE_EVENTS or name.endswith("cache_hits"):
            self.events[name] = self.events.get(name, 0) + 1

    def _on_gc(self, phase, info):
        if phase == "start":
            self.gc[info["generation"]] += 1

    def reset(self):
        self.events, self.gc = {}, [0, 0, 0]

    @property
    def compiles(self) -> int:
        return sum(self.events.values())

    def close(self):
        gc.callbacks.remove(self._on_gc)


class StallWatch:
    """A thread that wakes every ``TICK`` seconds and records each wake-up
    that came more than ``LATE`` seconds late: the host process (or the
    interpreter lock) was held up for that long.  A slow step with no such
    record was waiting on the device or the runtime."""

    TICK, LATE = 0.01, 0.05

    def __init__(self):
        self.stalls: List[List[float]] = []
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._watch, daemon=True)

    def _watch(self):
        start = prev = time.perf_counter()
        while not self._stop.wait(self.TICK):
            now = time.perf_counter()
            if now - prev - self.TICK > self.LATE:
                self.stalls.append([prev - start, 1e3 * (now - prev)])
            prev = now

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join(timeout=5.0)


def run(feed: Callable, call: Callable, sync: Callable, seconds: float,
        max_steps: int = 0, traced: bool = False) -> List[List[float]]:
    """Steps until ``seconds`` have passed (or ``max_steps`` are done); per
    step the seconds of ``feed``, ``call`` and ``sync``.  With ``traced``
    every phase and the window get a host span in the profiler's trace."""
    span = jax.profiler.TraceAnnotation if traced else (
        lambda name: contextlib.nullcontext())
    steps = []
    with span("bench:window"):
        start = prev = time.perf_counter()
        while prev - start < seconds and not (
                max_steps and len(steps) >= max_steps):
            with span("bench:feed"):
                batch = feed()
            t1 = time.perf_counter()
            with span("bench:call"):
                call(batch)
            t2 = time.perf_counter()
            with span("bench:sync"):
                sync()
            t3 = time.perf_counter()
            steps.append([t1 - prev, t2 - t1, t3 - t2])
            prev = t3
    return steps


def account(steps: List[List[float]], tokens_per_step: int) -> dict:
    """The window's arithmetic: every counted token over the window's whole
    elapsed time, and where that time went."""
    times = [sum(s) for s in steps]
    window_s = sum(times)
    median = statistics.median(times)
    return {
        "steps": len(times),
        "window_s": window_s,
        "sum_step_s": window_s,
        "tokens": len(times) * tokens_per_step,
        "tokens_per_s": len(times) * tokens_per_step / window_s,
        "step_p90_ms": 1e3 * (statistics.quantiles(
            times, n=10, method="inclusive")[8] if len(times) > 1
            else times[0]),
        "step_median_ms": 1e3 * median,
        "step_longest_ms": 1e3 * max(times),
        "slow_steps": [[i, 1e3 * t] for i, t in enumerate(times)
                       if t > SLOW * median],
        "longest_steps": sorted(([i, 1e3 * t] for i, t in enumerate(times)),
                                key=lambda it: -it[1])[:5],
        "phases_s": {p: {"sum": sum(s[i] for s in steps),
                         "median": statistics.median(s[i] for s in steps),
                         "max": max(s[i] for s in steps)}
                     for i, p in enumerate(PHASES)},
    }
