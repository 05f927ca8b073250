"""The window's arithmetic: whole steps over their own elapsed time."""

import itertools

import pytest

from lib import window


def test_rate_is_every_token_over_the_whole_window():
    steps = [[0.01, 0.2, 0.04]] * 9 + [[0.01, 1.2, 0.04]]   # one slow step
    acc = window.account(steps, tokens_per_step=4096)
    assert acc["steps"] == 10
    assert acc["window_s"] == pytest.approx(9 * 0.25 + 1.25)
    assert acc["tokens_per_s"] == pytest.approx(10 * 4096 / 3.5)
    assert acc["step_median_ms"] == pytest.approx(250.0)
    assert acc["step_longest_ms"] == pytest.approx(1250.0)
    assert acc["slow_steps"] == [[9, pytest.approx(1250.0)]]
    assert acc["phases_s"]["call"]["sum"] == pytest.approx(9 * 0.2 + 1.2)


def test_p90_is_the_inclusive_90th_percentile():
    steps = [[0.0, t / 1000, 0.0] for t in range(1, 101)]
    acc = window.account(steps, tokens_per_step=1)
    assert acc["step_p90_ms"] == pytest.approx(90.1)


def test_run_counts_whole_steps_until_the_time_is_up():
    clock = itertools.count()
    calls = []
    steps = window.run(feed=lambda: next(clock), call=calls.append,
                       sync=lambda: None, seconds=0.05)
    assert len(steps) == len(calls) >= 1
    assert sum(map(sum, steps)) >= 0.05
    capped = window.run(feed=lambda: 0, call=lambda b: None,
                        sync=lambda: None, seconds=60.0, max_steps=3)
    assert len(capped) == 3


def test_counters_see_a_compile_and_a_collection():
    import gc

    import jax
    import jax.numpy as jnp

    c = window.Counters()
    try:
        c.reset()
        jax.jit(lambda x: x * 3 + 1)(jnp.ones(7)).block_until_ready()
        gc.collect()
        assert c.compiles >= 1
        assert c.gc[2] >= 1
    finally:
        c.close()
