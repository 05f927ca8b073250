"""Phase scopes and host spans: what a profiler trace of a step can name.

* Every op the compiled step traced carries exactly one innermost
  ``phase.*`` scope, and the scopes change nothing but op metadata.
* The sharded step's neighbor exchange (its ``collective-permute`` ops)
  sits under ``phase.exchange``.
* ``CollaborativeTrainer.step`` writes one ``repro:dispatch`` and one
  ``repro:fetch`` host span per step into the profiler's trace.
"""

import contextlib
import functools
import glob
import os
import re
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine, phases
from repro.core.optim import CDMSGD
from repro.core.topology import make_topology
from repro.core.trainer import CollaborativeTrainer
from repro.nn.paper_models import (
    classifier_loss,
    mlp_classifier_apply,
    mlp_classifier_template,
)
from repro.nn.param import init_params
from test_sharded import run_sub

LOSS = functools.partial(classifier_loss, mlp_classifier_apply)
N_AGENTS = 4

# the stacked mode's exchange moves no data (every agent already holds the
# stack; the weights are constants), so no compiled op carries its scope
# there: the sharded test below is where phase.exchange shows
CONFIGS = {
    "sync-f32": ({}, {"grad", "pack", "update", "unpack", "metrics"}),
    "overlap-int8": ({"schedule": "overlap", "exchange": "int8"},
                     {"grad", "pack", "quantize", "update", "unpack",
                      "metrics"}),
    "sync-int8-ef": ({"exchange": "int8", "error_feedback": True},
                     {"grad", "pack", "quantize", "update", "unpack",
                      "metrics"}),
}


def _trainer(**kw):
    params = init_params(mlp_classifier_template(8, 4, width=16, depth=2),
                         jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batch = {"x": jnp.asarray(rng.standard_normal((N_AGENTS, 8, 8)),
                              jnp.float32),
             "y": jnp.asarray(rng.integers(0, 4, (N_AGENTS, 8)), jnp.int32)}
    tr = CollaborativeTrainer(LOSS, params, make_topology("ring", N_AGENTS),
                              CDMSGD(0.05, mu=0.9, fused=True), **kw)
    return tr, batch


def _compiled(tr, batch):
    return tr._step_fn.lower(tr.state.params, tr.state.opt_state,
                             batch).compile()


def innermost_phases(hlo_text: str):
    """The innermost ``phase.*`` scope of every instruction whose op_name
    is a traced op path (``jit(...)/...``); None where it has none."""
    out = []
    for m in re.finditer(r'op_name="([^"]*)"', hlo_text):
        path = m.group(1)
        if not path.startswith("jit("):
            continue        # parameters and reducer arguments name no path
        scopes = [c[len(phases.PREFIX):] for c in path.split("/")
                  if c.startswith(phases.PREFIX)]
        out.append(scopes[-1] if scopes else None)
    return out


def without_metadata(hlo_text: str) -> str:
    """The compiled module less its metadata: op metadata and the source
    location tables printed before the computations."""
    tables = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")
    lines = [ln for ln in hlo_text.splitlines()
             if ln and not ln[0].isdigit() and ln not in tables]
    return re.sub(r", metadata=\{[^}]*\}", "", "\n".join(lines))


@pytest.fixture
def no_persistent_cache():
    """The persistent compilation cache keys a program without its debug
    info, name scopes included: with it on, a build could load an
    executable compiled without the scopes (or with them, for the build
    that leaves them out)."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_phase_names_are_defined_once():
    assert engine.PHASES is phases.PHASES
    assert set(phases.PHASES) >= {"grad", "pack", "quantize", "exchange",
                                  "update", "unpack", "metrics"}
    with pytest.raises(ValueError, match="unknown phase"):
        phases.scope("pakc")


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_every_compiled_op_carries_one_phase(name, no_persistent_cache):
    kw, expected = CONFIGS[name]
    tr, batch = _trainer(**kw)
    found = innermost_phases(_compiled(tr, batch).as_text())
    assert found, "the compiled step names no traced op"
    assert None not in found, "an op outside every phase scope"
    assert set(found) == expected


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_scopes_change_only_op_metadata(name, monkeypatch,
                                        no_persistent_cache):
    kw, _ = CONFIGS[name]
    tr, batch = _trainer(**kw)
    scoped = _compiled(tr, batch)
    monkeypatch.setattr(jax, "named_scope",
                        lambda _name: contextlib.nullcontext())
    tr, batch = _trainer(**kw)
    plain = _compiled(tr, batch)
    assert "phase.grad" in scoped.as_text()
    assert "phase.grad" not in plain.as_text()
    assert without_metadata(scoped.as_text()) == \
        without_metadata(plain.as_text())
    assert str(scoped.memory_analysis()) == str(plain.memory_analysis())


def test_one_dispatch_and_one_fetch_span_per_step(tmp_path):
    from jax.profiler import ProfileData

    tr, batch = _trainer()
    tr.step(batch)                      # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        tr.step(batch)
    files = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    assert len(files) == 1
    names = [ev.name for plane in ProfileData.from_file(files[0]).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events
             if ev.name.startswith("repro:")]
    assert sorted(names) == ["repro:dispatch", "repro:fetch"]


@pytest.mark.slow
def test_sharded_collective_permutes_sit_under_exchange():
    res = run_sub(textwrap.dedent("""
        import dataclasses, json, re
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.configs.base import InputShape
        from repro.core.optim import make_optimizer
        from repro.launch.mesh import make_debug_mesh
        from repro.launch import steps as steps_lib
        from repro.nn.param import init_params

        cfg = dataclasses.replace(get_config("granite-3-8b").reduced(),
                                  param_dtype="float32")
        shape = InputShape("tiny_train", 16, 8, "train")
        mesh = make_debug_mesh(4, 2)
        out = {}
        for exchange, schedule in (("f32", "sync"), ("int8", "overlap")):
            opt = make_optimizer("cdmsgd", 0.05, mu=0.9, fused=True)
            b = steps_lib.build_train_step(
                cfg, shape, mesh, opt, mode="train", topology_name="ring",
                mixing="ppermute_fused", exchange=exchange,
                schedule=schedule)
            params = init_params(b.param_template, jax.random.PRNGKey(0))
            batch = {k: jnp.ones((4, 2, 16), jnp.int32)
                     for k in ("inputs", "targets")}
            with mesh:
                opt_state = b.init_state(params)
                text = jax.jit(b.step_fn).lower(
                    params, opt_state, batch).compile().as_text()
            ops = []
            for line in text.splitlines():
                if " collective-permute" not in line:
                    continue
                m = re.search(r'op_name="([^"]*)"', line)
                path = m.group(1) if m else ""
                scopes = [c for c in path.split("/")
                          if c.startswith("phase.")]
                ops.append(scopes[-1] if scopes else None)
            out[exchange + "-" + schedule] = ops
        print("RESULT " + json.dumps(out))
    """))
    for name, ops in res.items():
        assert ops, f"{name}: no collective-permute in the compiled step"
        assert set(ops) == {"phase.exchange"}, (name, ops)
