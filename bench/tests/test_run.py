"""Whole runs on the CPU at a small size, the chip check skipped: the sound
program is correct; with the timed path broken underneath, or the control
in its place, it is not.

Limits here are the small size's own (``tiny.LIMITS``), set from what
these seeds read: the program about 2e-4 / 5e-3 / 0.11, the control
0.04 / 0.46 / 0.65, the faults at least 0.35 on one number.
"""

import contextlib
import io
import json
import sys

import jax
import jax.numpy as jnp
import pytest

import control
import run
import tiny
from lib import spec

sys.path.insert(0, str(spec.ROOT / "src"))
from repro.core import engine  # noqa: E402

CELL = "rwkv6-tiny-s64"
SEED = 2 ** 33 + 17
ORIGINAL = engine.StepProgram.step_fn


def unchanged(self, params, opt_state, batch):
    _, _, metrics = ORIGINAL(self, params, opt_state, batch)
    return params, opt_state, metrics


def half_batch(self, params, opt_state, batch):
    half = batch["inputs"].shape[-1] // 2
    return ORIGINAL(self, params, opt_state,
                    {k: v[..., :half] for k, v in batch.items()})


def no_exchange(self, params, opt_state, batch):
    _, new_state, metrics = ORIGINAL(self, params, opt_state, batch)
    step = jax.tree.map(lambda x, v: (x.astype(jnp.float32)
                                      + v.astype(jnp.float32)).astype(x.dtype),
                        params, new_state.inner)
    return step, new_state, metrics


def altered(self, params, opt_state, batch):
    new, new_state, metrics = ORIGINAL(self, params, opt_state, batch)
    old, upd = params["embed"]["table"], new["embed"]["table"]
    new = {**new, "embed": {"table": (2 * upd.astype(jnp.float32)
                                      - old.astype(jnp.float32)).astype(
                                          old.dtype)}}
    return new, new_state, metrics


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("checkout"))


def result(root, workload=CELL, seed=SEED):
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        code = run.main(["--workload", workload, "--seed", str(seed),
                         "--seconds", "0.5", "--trace", "0"],
                        require_chip=False, root=root)
    assert code == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.mark.parametrize("workload", ["rwkv6-tiny-s64", "hymba-tiny-s64"])
def test_the_sound_program_is_correct(root, workload):
    r = result(root, workload)
    assert r["correct"], r["checks"]
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) == {"tokens_per_s", "step_p90_ms", "peak_hbm_gb",
                                 "setup_s"}
    assert r["attempted"] >= 1 and r["failed"] == 0


@pytest.mark.parametrize("fault", [unchanged, half_batch, no_exchange,
                                   altered], ids=lambda f: f.__name__)
def test_a_broken_step_is_not_correct(root, monkeypatch, fault):
    monkeypatch.setattr(engine.StepProgram, "step_fn", fault)
    r = result(root)
    assert not r["correct"], r["checks"]


def test_the_control_and_the_planted_faults_fail_a_limit(root):
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        control.main(["--workload", CELL, "--seeds", str(SEED), "--control",
                      "--faults", "half_batch,no_exchange,altered"], root=root)
    readings = [json.loads(line) for line in out.getvalue().splitlines()]
    limits = spec.load_cell(CELL, root).cell["limits"]
    upper = [r for r in readings if r["reading"] != "reference"]
    assert {r["reading"] for r in upper} == {"control", "half_batch",
                                             "no_exchange", "altered"}
    for r in upper:
        assert any(r[k] > limits[k] for k in limits), r


def test_no_accelerator_means_no_result(root, capsys):
    with pytest.raises(SystemExit) as exit_:
        run.main(["--workload", CELL, "--seed", "1", "--seconds", "1"],
                 root=root)
    assert exit_.value.code != 0
    assert capsys.readouterr().out == ""


def test_a_number_without_a_limit_is_not_compared(tmp_path):
    limits = {k: v for k, v in tiny.LIMITS.items() if k != "loss_gap"}
    root = tiny.make_root(tmp_path, limits=limits)
    cell = root / "bench" / "workloads" / f"{CELL}.json"
    spec_ = json.loads(cell.read_text())
    spec_["not_compared"] = {"loss_gap": "no upper reading"}
    cell.write_text(json.dumps(spec_))
    r = result(root)
    assert set(r["checks"]) == set(limits)
    assert r["correct"], r["checks"]
