"""The training entry points: ``build_trainer``, the compile-cache rule,
the interpret-mode decision and ``chip_smoke.py``'s host-side logic."""

import os
import sys

import jax
import numpy as np
import pytest

from repro.kernels import resolve_interpret
from repro.launch import compile_cache
from repro.launch import train as train_cli

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402

TINY = ["--arch", "rwkv6-1.6b", "--preset", "tiny", "--agents", "2",
        "--topology", "ring", "--optimizer", "cdmsgd", "--batch", "1",
        "--seq", "16"]


def test_build_trainer_runs_the_cli_path():
    args = train_cli.build_parser().parse_args(TINY + ["--fused"])
    lines = []
    trainer, batches = train_cli.build_trainer(
        args, train_cli.config_for(args), printer=lines.append)
    assert trainer.optimizer.fused
    assert any("2 agents over ring" in line for line in lines)
    for _ in range(2):
        m = trainer.step(next(batches))
        assert np.isfinite(m["loss"]) and np.isfinite(m["consensus_error"])
    assert trainer.state.step == 2


def test_build_trainer_implies_fused_for_a_quantized_exchange():
    args = train_cli.build_parser().parse_args(TINY + ["--exchange", "int8"])
    trainer, _ = train_cli.build_trainer(args, train_cli.config_for(args),
                                         printer=lambda s: None)
    assert args.fused and trainer.optimizer.fused


def test_flag_conflict_is_a_value_error_and_a_usage_error():
    argv = TINY + ["--staleness", "2"]
    args = train_cli.build_parser().parse_args(argv)
    with pytest.raises(ValueError, match="--schedule overlap"):
        train_cli.build_trainer(args, train_cli.config_for(args),
                                printer=lambda s: None)
    with pytest.raises(SystemExit) as e:
        train_cli.main(argv)
    assert e.value.code == 2


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_uses_the_env_dir_when_set(monkeypatch,
                                                 restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/cache")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == "/some/cache"
    assert jax.config.jax_compilation_cache_dir == before   # nothing set


def test_compile_cache_defaults_to_one_fixed_dir_in_the_checkout(
        monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert compile_cache.enable_compile_cache() == path     # stable
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_resolve_interpret_follows_the_backend():
    assert resolve_interpret(None) == (jax.default_backend() != "tpu")
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False


@pytest.mark.parametrize("peaks,published,budget,want", [
    ({n: 10 + n for n in range(1, 25)}, 24, 16, 6),        # linear
    ({n: 10 + n for n in range(1, 25)}, 24, 100, 24),      # all fit
    ({2: 10, 3: 11, 4: 12, 5: 20, 6: 21}, 6, 15, 4),       # jump past guess
    ({2: 10, 3: 20, 4: 21}, 4, 15, 2),                     # 3 does not fit
    ({2: 10, 3: 10.5, 4: 11, 5: 11.5, 6: 30}, 6, 12, 5),   # guess too deep
])
def test_deepest_depth_rests_on_compiles(peaks, published, budget, want):
    probed = []

    def peak_at(n):
        probed.append(n)
        return peaks[n]

    n, seen = chip_smoke.deepest_depth(published, budget, peak_at)
    assert n == want
    assert seen[n] <= budget
    assert n == published or seen[n + 1] > budget
    assert len(probed) == len(set(probed))                 # no recompiles


def test_deepest_depth_refuses_when_two_layers_do_not_fit():
    with pytest.raises(RuntimeError, match="2 layers"):
        chip_smoke.deepest_depth(24, 5, lambda n: 10)


def test_chip_smoke_refuses_a_host_without_a_tpu(capsys):
    if jax.devices()[0].platform == "tpu":
        pytest.skip("a TPU is attached")
    with pytest.raises(SystemExit) as e:
        chip_smoke.main([])
    assert "no TPU found" in str(e.value.code)
    assert '"ok"' not in capsys.readouterr().out
