"""Sharded execution tests: run in a SUBPROCESS with 8 host devices so the
main test process keeps its single-device view (the dryrun contract).

Verifies on a 4x2 ("data","model") debug mesh that:
* the sharded CDSGD train_step lowers, compiles AND runs, with per-agent
  distinct parameters sharded over the data axis,
* ppermute mixing == dense-Pi mixing numerically (same topology),
* the decode serve_step lowers and runs with a sharded KV cache,
* the production mesh builders construct (16,16) and (2,16,16) meshes.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(code: str, timeout=560) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=timeout, env=env)
    assert out.returncode == 0, f"subprocess failed:\n{out.stderr[-4000:]}"
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


@pytest.mark.slow
def test_sharded_train_step_runs_and_mixings_agree():
    res = run_sub(textwrap.dedent("""
        import dataclasses
        import json
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config, INPUT_SHAPES
        from repro.configs.base import InputShape
        from repro.core.optim import make_optimizer
        from repro.launch.mesh import make_debug_mesh
        from repro.launch import steps as steps_lib
        from repro.nn.param import init_params

        # f32: differently-compiled bf16 programs pick different XLA-CPU dot
        # strategies (%-level numeric drift) which would mask real bugs here
        cfg = dataclasses.replace(get_config("granite-3-8b").reduced(),
                                  param_dtype="float32")
        shape = InputShape("tiny_train", 16, 8, "train")   # 8 batch over 4 agents
        mesh = make_debug_mesh(4, 2)

        outs = {}
        for mixing in ("dense", "ppermute"):
            opt = make_optimizer("cdsgd", 0.05)
            b = steps_lib.build_train_step(cfg, shape, mesh, opt, mode="train",
                                           topology_name="ring", mixing=mixing)
            params = init_params(b.param_template, jax.random.PRNGKey(0))
            # de-synchronize agents so mixing has something to do
            params = jax.tree.map(
                lambda x: x + 0.01 * jax.random.normal(jax.random.PRNGKey(1), x.shape, x.dtype), params)
            opt_state = opt.init(params)
            rng = np.random.default_rng(0)
            batch = {
                "inputs": jnp.asarray(rng.integers(1, cfg.vocab_size, (4, 2, 16)), jnp.int32),
                "targets": jnp.asarray(rng.integers(1, cfg.vocab_size, (4, 2, 16)), jnp.int32),
            }
            with mesh:
                step = jax.jit(b.step_fn)
                new_params, new_state, metrics = step(params, opt_state, batch)
            outs[mixing] = (new_params, float(metrics["loss"]))

        pd, ld = outs["dense"]; pp, lp = outs["ppermute"]
        diffs = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(
            a.astype(jnp.float32) - b.astype(jnp.float32)))), pd, pp)
        max_diff = max(jax.tree.leaves(diffs))
        print("RESULT " + json.dumps({
            "loss_dense": ld, "loss_ppermute": lp, "max_param_diff": max_diff,
            "finite": bool(all(jnp.all(jnp.isfinite(x)) for x in jax.tree.leaves(pd))),
        }))
    """))
    assert res["finite"]
    assert abs(res["loss_dense"] - res["loss_ppermute"]) < 1e-4
    assert res["max_param_diff"] < 1e-3, "ppermute mixing must equal dense Pi"


@pytest.mark.slow
def test_sharded_fused_train_step_matches_dense():
    """mixing="ppermute_fused" + fused optimizer: the whole-model flat-buffer
    update inside one shard_map region must match dense-Pi mixing, with
    exactly one pallas_call per dtype bucket and one ppermute per non-zero
    circulant shift in the step jaxpr."""
    res = run_sub(textwrap.dedent("""
        import dataclasses
        import json
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

        outs = {}
        for mixing, fused in (("dense", False), ("ppermute_fused", True)):
            opt = make_optimizer("cdmsgd", 0.05, mu=0.9, fused=fused)
            b = steps_lib.build_train_step(cfg, shape, mesh, opt, mode="train",
                                           topology_name="ring", mixing=mixing)
            params = init_params(b.param_template, jax.random.PRNGKey(0))
            params = jax.tree.map(
                lambda x: x + 0.01 * jax.random.normal(jax.random.PRNGKey(1), x.shape, x.dtype), params)
            opt_state = opt.init(params)
            rng = np.random.default_rng(0)
            batch = {
                "inputs": jnp.asarray(rng.integers(1, cfg.vocab_size, (4, 2, 16)), jnp.int32),
                "targets": jnp.asarray(rng.integers(1, cfg.vocab_size, (4, 2, 16)), jnp.int32),
            }
            with mesh:
                if mixing == "ppermute_fused":
                    # structured census via the static checker (PR 10) in
                    # place of counting substrings of the printed jaxpr
                    from repro.analysis import staticcheck
                    from repro.kernels.consensus_update import ops as kops
                    jaxpr = jax.make_jaxpr(b.step_fn)(params, opt_state, batch)
                    rep = staticcheck.check_bundle(
                        b, mesh, batch, passes=("census",))
                    counts = {"pallas": len(kops.alias_groups(jaxpr)),
                              "ppermute": rep.rule("census.ppermute_count").evidence["actual"],
                              "census_ok": rep.rule("census.ppermute_count").ok,
                              "critical_path_ok": rep.rule("census.critical_path").ok}
                step = jax.jit(b.step_fn)
                new_params, new_state, metrics = step(params, opt_state, batch)
            outs[mixing] = (new_params, float(metrics["loss"]))

        pd, ld = outs["dense"]; pp, lp = outs["ppermute_fused"]
        diffs = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(
            a.astype(jnp.float32) - b.astype(jnp.float32)))), pd, pp)
        print("RESULT " + json.dumps({
            "loss_dense": ld, "loss_fused": lp,
            "max_param_diff": max(jax.tree.leaves(diffs)),
            "n_buckets": 1, "pallas_calls": counts["pallas"],
            "ppermutes": counts["ppermute"],
            "census_ok": counts["census_ok"],
            "critical_path_ok": counts["critical_path_ok"],
        }))
    """))
    assert abs(res["loss_dense"] - res["loss_fused"]) < 1e-4
    assert res["max_param_diff"] < 1e-3, "fused update must equal dense Pi"
    assert res["pallas_calls"] == res["n_buckets"], "one kernel launch per bucket"
    assert res["ppermutes"] == 2, "ring = one ppermute per non-zero shift"
    assert res["census_ok"], "checker's closed-form count must match the trace"
    assert res["critical_path_ok"], "sync schedule: every ppermute may read params"


@pytest.mark.slow
def test_sharded_quantized_fused_tracks_dense_over_20_steps():
    """exchange="int8": the quantized ppermute_fused trajectory must track
    the unquantized dense-Pi trajectory over 20 optimizer steps, with TWO
    ppermutes per non-zero shift (int8 payload + row scales) and the
    params/opt_state donated to the jitted step.

    Documented tolerance: per step each mixed parameter absorbs unbiased
    rounding noise <= row_amax/127 per neighbor term (the native-precision
    self term pays none), so a contractive small-lr trajectory stays within
    a few row-quantization steps of exact mixing: empirically 3.8e-2 max
    |param diff| after 20 CDSGD steps at lr 5e-3 on this reduced
    transformer; asserted at 1e-1.  (Momentum at large lr amplifies any
    per-step perturbation chaotically — bf16 or int8 alike — so
    trajectory-level comparisons are only meaningful in this regime; see
    the loss-level tracking in benchmarks/README.md.)"""
    res = run_sub(textwrap.dedent("""
        import dataclasses
        import json
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

        outs = {}
        for mixing, fused, exch in (("dense", False, "f32"),
                                    ("ppermute_fused", True, "int8")):
            opt = make_optimizer("cdsgd", 0.005, fused=fused)
            b = steps_lib.build_train_step(cfg, shape, mesh, opt, mode="train",
                                           topology_name="ring", mixing=mixing,
                                           exchange=exch)
            params = init_params(b.param_template, jax.random.PRNGKey(0))
            params = jax.tree.map(
                lambda x: x + 0.01 * jax.random.normal(jax.random.PRNGKey(1), x.shape, x.dtype), params)
            opt_state = opt.init(params)
            rng = np.random.default_rng(0)
            batch = {
                "inputs": jnp.asarray(rng.integers(1, cfg.vocab_size, (4, 2, 16)), jnp.int32),
                "targets": jnp.asarray(rng.integers(1, cfg.vocab_size, (4, 2, 16)), jnp.int32),
            }
            with mesh:
                if mixing == "ppermute_fused":
                    # structured census: the checker's closed form predicts
                    # 2 fields (int8 payload + row scales) per non-zero shift
                    from repro.analysis import staticcheck
                    rep = staticcheck.check_bundle(
                        b, mesh, batch, passes=("census",))
                    counts = {"ppermute": rep.rule("census.ppermute_count").evidence["actual"],
                              "census_ok": rep.rule("census.ppermute_count").ok}
                step = jax.jit(b.step_fn, donate_argnums=b.donate_argnums)
                for _ in range(20):
                    params, opt_state, metrics = step(params, opt_state, batch)
            outs[mixing] = (params, float(metrics["loss"]))

        pd, ld = outs["dense"]; pq, lq = outs["ppermute_fused"]
        scale = max(float(jnp.max(jnp.abs(x))) for x in jax.tree.leaves(pd))
        diffs = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(
            a.astype(jnp.float32) - b.astype(jnp.float32)))), pd, pq)
        print("RESULT " + json.dumps({
            "loss_dense": ld, "loss_int8": lq,
            "max_param_diff": max(jax.tree.leaves(diffs)),
            "param_scale": scale,
            "ppermutes": counts["ppermute"],
            "census_ok": counts["census_ok"],
            "finite": bool(all(jnp.all(jnp.isfinite(x)) for x in jax.tree.leaves(pq))),
        }))
    """))
    assert res["finite"]
    # int8 payload + (rows, 1) scales each ppermute per non-zero ring shift
    assert res["ppermutes"] == 4
    assert res["census_ok"], "checker's closed-form count must match the trace"
    assert abs(res["loss_dense"] - res["loss_int8"]) < 5e-2
    assert res["max_param_diff"] < 1e-1, "int8 must track the exact mix"


@pytest.mark.slow
def test_sharded_overlap_schedule_critical_path_and_warning():
    """schedule="overlap" on the sharded path: the jaxpr taint analysis must
    show the ppermutes consuming ONLY the carried wire state (off the
    grad->update critical path — what the dryrun records per config), while
    schedule="sync" ppermutes depend on the current params; plus the
    satellite warning when mixing='ppermute_fused' is paired with a
    fused=False optimizer."""
    res = run_sub(textwrap.dedent("""
        import dataclasses, json, warnings
        import jax, jax.numpy as jnp
        from repro.configs import get_config
        from repro.configs.base import InputShape
        from repro.core import engine
        from repro.core.optim import make_optimizer
        from repro.launch.mesh import make_debug_mesh
        from repro.launch import steps as steps_lib
        from repro.nn.param import init_params

        cfg = dataclasses.replace(get_config("granite-3-8b").reduced(),
                                  param_dtype="float32")
        shape = InputShape("tiny_train", 16, 8, "train")
        mesh = make_debug_mesh(4, 2)
        batch = {"inputs": jnp.ones((4, 2, 16), jnp.int32),
                 "targets": jnp.ones((4, 2, 16), jnp.int32)}

        reports = {}
        for schedule, exch in (("sync", "int8"), ("overlap", "int8"),
                               ("overlap", "f32")):
            opt = make_optimizer("cdsgd", 0.005, fused=True)
            b = steps_lib.build_train_step(
                cfg, shape, mesh, opt, mode="train", topology_name="ring",
                mixing="ppermute_fused", exchange=exch, schedule=schedule)
            params = init_params(b.param_template, jax.random.PRNGKey(0))
            with mesh:
                state = b.init_state(params)
                reports[schedule + "_" + exch] = engine.exchange_dependency_report(
                    b.step_fn, params, state, batch)

        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            steps_lib.build_train_step(
                cfg, shape, mesh, make_optimizer("cdsgd", 0.005),
                mode="train", topology_name="ring", mixing="ppermute_fused")
        warned = any("fused=False" in str(w.message) for w in caught)
        print("RESULT " + json.dumps({**reports, "warned_unfused": warned}))
    """))
    # sync: the exchange payload is quantized from the current params, so
    # the collective waits on the previous update; overlap: only on the
    # carried wire buffers.
    assert res["sync_int8"]["n_ppermutes"] == 4
    assert res["sync_int8"]["depends_on_params"]
    assert not res["sync_int8"]["off_grad_update_critical_path"]
    for key in ("overlap_int8", "overlap_f32"):
        assert not res[key]["depends_on_params"]
        assert not res[key]["depends_on_batch"]
        assert res[key]["depends_on_wire_state"]
        assert res[key]["off_grad_update_critical_path"]
    assert res["overlap_int8"]["n_ppermutes"] == 4
    # f32 wire: unit scales are synthesized after the exchange, so only the
    # payload pays a collective — one ppermute per non-zero ring shift
    assert res["overlap_f32"]["n_ppermutes"] == 2
    assert res["warned_unfused"]


@pytest.mark.slow
def test_sharded_overlap_matches_stacked_over_20_steps():
    """schedule="overlap" stacked-vs-sharded 20-step parity on the reduced
    transformer (small-lr CDSGD per the PR 2 quantization caveat).

    Documented tolerance: stacked and sharded compile DIFFERENT backward
    programs (single-device vmap vs pjit), whose gradients agree only to
    ~1.5e-4 relative per step — so even the sync schedule's stacked-vs-
    sharded trajectories drift ~8e-3 apart over 20 lr-5e-3 steps (measured;
    the pre-existing sync parity tests never crossed execution modes, they
    compared two sharded programs).  The test therefore measures the sync
    cross-mode drift as its own baseline in the same subprocess and asserts
    the deterministic f32-wire overlap drift stays within 3x of it
    (measured 1.31e-2 vs 8.4e-3 — staleness recycles the drift one extra
    step but adds no divergence of its own), capped absolutely at 5e-2;
    the int8 wire additionally randomizes the SR streams (the sharded mode
    quantizes model-shard-local buckets, the stacked mode global ones) and
    is asserted at the documented 1e-1 sync-int8 envelope."""
    res = run_sub(textwrap.dedent("""
        import dataclasses, json
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.configs.base import InputShape
        from repro.core.optim import make_optimizer
        from repro.core.trainer import CollaborativeTrainer
        from repro.launch.mesh import make_debug_mesh
        from repro.launch import steps as steps_lib
        from repro.nn.param import init_params
        from repro.nn.transformer import loss_fn

        cfg = dataclasses.replace(get_config("granite-3-8b").reduced(),
                                  param_dtype="float32")
        shape = InputShape("tiny_train", 16, 8, "train")
        mesh = make_debug_mesh(4, 2)
        rng = np.random.default_rng(0)
        batch = {
            "inputs": jnp.asarray(rng.integers(1, cfg.vocab_size, (4, 2, 16)), jnp.int32),
            "targets": jnp.asarray(rng.integers(1, cfg.vocab_size, (4, 2, 16)), jnp.int32),
        }
        out = {}
        for schedule, exch in (("sync", "f32"), ("overlap", "f32"),
                               ("overlap", "int8")):
            opt = make_optimizer("cdsgd", 0.005, fused=True)
            b = steps_lib.build_train_step(
                cfg, shape, mesh, opt, mode="train", topology_name="ring",
                mixing="ppermute_fused", exchange=exch, schedule=schedule)
            params0 = init_params(b.param_template, jax.random.PRNGKey(0))
            params0 = jax.tree.map(
                lambda x: x + 0.01 * jax.random.normal(jax.random.PRNGKey(1), x.shape, x.dtype), params0)

            params = params0
            with mesh:
                opt_state = b.init_state(params)
                step = jax.jit(b.step_fn, donate_argnums=b.donate_argnums)
                for _ in range(20):
                    params, opt_state, metrics = step(params, opt_state, batch)

            tr = CollaborativeTrainer(
                lambda p, bb: loss_fn(cfg, p, bb), params0, b.topology,
                make_optimizer("cdsgd", 0.005, fused=True),
                stack=False, schedule=schedule, exchange=exch)
            for _ in range(20):
                m = tr.step(batch)

            diffs = jax.tree.map(lambda a, c: float(jnp.max(jnp.abs(
                a.astype(jnp.float32) - c.astype(jnp.float32)))),
                params, tr.state.params)
            out[schedule + "_" + exch] = {
                "max_param_diff": max(jax.tree.leaves(diffs)),
                "loss_sharded": float(metrics["loss"]),
                "loss_stacked": float(m["loss"]),
                "finite": bool(all(jnp.all(jnp.isfinite(x))
                                   for x in jax.tree.leaves(params))),
            }
        print("RESULT " + json.dumps(out))
    """), timeout=840)
    for key in ("sync_f32", "overlap_f32", "overlap_int8"):
        assert res[key]["finite"]
        assert abs(res[key]["loss_sharded"] - res[key]["loss_stacked"]) < 5e-2
    base = res["sync_f32"]["max_param_diff"]          # cross-mode fp envelope
    assert res["overlap_f32"]["max_param_diff"] < max(3 * base, 1e-3), \
        "deterministic overlap wire must track the stacked oracle as " \
        "closely as the sync schedule does"
    assert res["overlap_f32"]["max_param_diff"] < 5e-2
    assert res["overlap_int8"]["max_param_diff"] < 1e-1, \
        "int8 overlap must stay inside the documented SR envelope"


@pytest.mark.slow
def test_sharded_microbatch_accumulation_parity():
    """microbatches=2 == microbatches=1 on identical data through the
    shared grad phase (satellite: this path was untested).

    Documented tolerance: single-device the accumulated gradients agree to
    ~3e-7 relative, but under pjit the scanned half-batch backward compiles
    to a differently-partitioned program and every leaf's gradient agrees
    only to ~1.5e-4 RELATIVE (uniform across leaves — dot-strategy
    reassociation, not accumulation error; the forward loss still matches
    to 1e-6).  One lr-5e-3 update turns the largest gradient (embedding
    table, |g| ~ 46) into a 3.6e-5 param diff; asserted at 2e-4.  The test
    stops after one step because the transformer's curvature amplifies this
    fp-level seed ~10x per extra step (measured, lr-independent in relative
    terms)."""
    res = run_sub(textwrap.dedent("""
        import dataclasses, json
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
        rng = np.random.default_rng(0)
        batch = {
            "inputs": jnp.asarray(rng.integers(1, cfg.vocab_size, (4, 2, 16)), jnp.int32),
            "targets": jnp.asarray(rng.integers(1, cfg.vocab_size, (4, 2, 16)), jnp.int32),
        }
        outs = {}
        for mb in (1, 2):
            opt = make_optimizer("cdsgd", 0.005)
            b = steps_lib.build_train_step(cfg, shape, mesh, opt, mode="train",
                                           topology_name="ring", mixing="dense",
                                           microbatches=mb)
            params = init_params(b.param_template, jax.random.PRNGKey(0))
            opt_state = opt.init(params)
            with mesh:
                step = jax.jit(b.step_fn)
                params, opt_state, metrics = step(params, opt_state, batch)
            outs[mb] = (params, float(metrics["loss"]))

        p1, l1 = outs[1]; p2, l2 = outs[2]
        diffs = jax.tree.map(lambda a, b_: float(jnp.max(jnp.abs(a - b_))), p1, p2)
        print("RESULT " + json.dumps({
            "loss_mb1": l1, "loss_mb2": l2,
            "max_param_diff": max(jax.tree.leaves(diffs)),
        }))
    """))
    assert abs(res["loss_mb1"] - res["loss_mb2"]) < 1e-5
    assert res["max_param_diff"] < 2e-4, \
        "gradient accumulation must equal the single-shot gradient"


@pytest.mark.slow
def test_sharded_serve_step_runs():
    res = run_sub(textwrap.dedent("""
        import json
        import jax, jax.numpy as jnp
        from repro.configs import get_config
        from repro.configs.base import InputShape
        from repro.launch.mesh import make_debug_mesh
        from repro.launch import steps as steps_lib
        from repro.nn.param import init_params
        from repro.nn.transformer import init_cache

        cfg = get_config("granite-3-8b").reduced()
        shape = InputShape("tiny_decode", 32, 8, "decode")
        mesh = make_debug_mesh(4, 2)
        b = steps_lib.build_serve_step(cfg, shape, mesh)
        params = init_params(b.param_template, jax.random.PRNGKey(0))
        cache = init_cache(cfg, 8, 32)
        tok = jnp.ones((8, 1), jnp.int32)
        with mesh:
            step = jax.jit(b.step_fn)
            nxt, cache = step(params, cache, tok, jnp.int32(0))
            nxt2, cache = step(params, cache, nxt, jnp.int32(1))
        print("RESULT " + json.dumps({
            "shape": list(nxt2.shape),
            "finite": bool(jnp.all(nxt2 >= 0)),
        }))
    """))
    assert res["shape"] == [8, 1]
    assert res["finite"]


@pytest.mark.slow
def test_production_meshes_construct():
    res = run_sub(textwrap.dedent("""
        import os, json
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
        import jax
        from repro.launch.mesh import make_production_mesh
        m1 = make_production_mesh()
        m2 = make_production_mesh(multi_pod=True)
        print("RESULT " + json.dumps({
            "single": dict(m1.shape), "multi": dict(m2.shape),
            "devices": jax.device_count(),
        }))
    """))
    assert res["single"] == {"data": 16, "model": 16}
    assert res["multi"] == {"pod": 2, "data": 16, "model": 16}
    assert res["devices"] == 512


@pytest.mark.slow
def test_dryrun_cli_single_pair(tmp_path):
    """The dryrun CLI end-to-end on the full production mesh (real 512-dev)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "gemma3-1b",
         "--shape", "decode_32k", "--out", str(tmp_path)],
        capture_output=True, text=True, timeout=560, env=env, cwd=REPO)
    assert out.returncode == 0, out.stderr[-2000:]
    files = list(tmp_path.glob("*.json"))
    assert len(files) == 1
    rec = json.loads(files[0].read_text())
    assert rec["status"] == "ok"
    assert rec["roofline"]["dominant"] in ("compute", "memory", "collective")


@pytest.mark.slow
def test_sharded_mixing_strategies():
    """The MixingProgram strategy layer on the sharded path:

    * multi-round k=2 sync doubles the collectives, all on the critical
      path; k=2 overlap splits them — round 1 consumes only carried wire
      state (``n_ppermutes_carried_only``), round 2 re-quantizes current
      buffers (``n_ppermutes_fresh``) — the ISSUE-4 acceptance criterion
      that overlap's round-1 ppermutes stay off the grad->update critical
      path for every strategy;
    * time-varying f32 (lax.switch over per-entry circulant shift sets)
      matches the stacked dense-Pi_t oracle over 2 steps within the
      documented cross-mode fp envelope;
    * error-feedback overlap keeps ALL collectives off the critical path
      and populates the sharded residual state.
    """
    res = run_sub(textwrap.dedent("""
        import dataclasses, json
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.configs.base import InputShape
        from repro.core import engine
        from repro.core.optim import make_optimizer
        from repro.core.trainer import CollaborativeTrainer
        from repro.launch.mesh import make_debug_mesh
        from repro.launch import steps as steps_lib
        from repro.nn.param import init_params
        from repro.nn.transformer import loss_fn

        cfg = dataclasses.replace(get_config("granite-3-8b").reduced(),
                                  param_dtype="float32")
        shape = InputShape("tiny_train", 16, 8, "train")
        mesh = make_debug_mesh(4, 2)
        rng = np.random.default_rng(0)
        batch = {
            "inputs": jnp.asarray(rng.integers(1, cfg.vocab_size, (4, 2, 16)), jnp.int32),
            "targets": jnp.asarray(rng.integers(1, cfg.vocab_size, (4, 2, 16)), jnp.int32),
        }
        out = {}

        def build(**kw):
            opt = make_optimizer("cdsgd", 0.005, fused=True)
            return steps_lib.build_train_step(
                cfg, shape, mesh, opt, mode="train", topology_name="ring",
                mixing="ppermute_fused", **kw)

        # multi-round reports: sync (2x fresh) vs overlap (round 1 carried)
        for schedule in ("sync", "overlap"):
            b = build(exchange="int8", consensus_rounds=2, schedule=schedule)
            params = init_params(b.param_template, jax.random.PRNGKey(0))
            with mesh:
                state = b.init_state(params)
                out["mr2_" + schedule] = engine.exchange_dependency_report(
                    b.step_fn, params, state, batch)
                if schedule == "overlap":
                    p1, s1, m = jax.jit(b.step_fn)(params, state, batch)
                    out["mr2_overlap_run"] = {
                        "loss": float(m["loss"]),
                        "finite": bool(all(jnp.all(jnp.isfinite(x))
                                           for x in jax.tree.leaves(p1)))}

        # time-varying f32 vs the stacked dense-Pi_t oracle, 2 steps
        b = build(exchange="f32", mixing_strategy="time_varying",
                  topology_schedule="alternating:ring:fully_connected")
        params0 = init_params(b.param_template, jax.random.PRNGKey(0))
        params0 = jax.tree.map(
            lambda x: x + 0.01 * jax.random.normal(jax.random.PRNGKey(1), x.shape, x.dtype), params0)
        params = params0
        with mesh:
            state = b.init_state(params)
            step = jax.jit(b.step_fn)
            for _ in range(2):
                params, state, m = step(params, state, batch)
        tr = CollaborativeTrainer(
            lambda p, bb: loss_fn(cfg, p, bb), params0, b.topology,
            make_optimizer("cdsgd", 0.005, fused=True), stack=False,
            mixing_strategy="time_varying",
            topology_schedule="alternating:ring:fully_connected")
        for _ in range(2):
            ms = tr.step(batch)
        diffs = jax.tree.map(lambda a, c: float(jnp.max(jnp.abs(
            a.astype(jnp.float32) - c.astype(jnp.float32)))),
            params, tr.state.params)
        out["tv"] = {"max_param_diff": max(jax.tree.leaves(diffs)),
                     "loss_sharded": float(m["loss"]),
                     "loss_stacked": float(ms["loss"])}

        # time-varying + overlap: the lax.switch branches consume only the
        # carried wire (trace-only; no execution needed for the proof)
        b = build(exchange="int8", mixing_strategy="time_varying",
                  topology_schedule="alternating:ring:fully_connected",
                  schedule="overlap")
        params = init_params(b.param_template, jax.random.PRNGKey(0))
        with mesh:
            state = b.init_state(params)
            out["tv_overlap"] = engine.exchange_dependency_report(
                b.step_fn, params, state, batch)

        # error-feedback overlap: carried-only collectives + residual state
        b = build(exchange="int8", error_feedback=True, schedule="overlap")
        params = init_params(b.param_template, jax.random.PRNGKey(0))
        with mesh:
            state = b.init_state(params)
            out["ef_overlap"] = engine.exchange_dependency_report(
                b.step_fn, params, state, batch)
            p1, s1, m = jax.jit(b.step_fn)(params, state, batch)
        out["ef_overlap_run"] = {
            "loss": float(m["loss"]),
            "res_max": float(max(jnp.max(jnp.abs(r)) for r in s1.residual)),
            "n_res_bufs": len(s1.residual)}
        print("RESULT " + json.dumps(out))
    """), timeout=840)
    # sync k=2: both rounds' collectives wait on the current params
    assert res["mr2_sync"]["n_ppermutes"] == 8
    assert res["mr2_sync"]["n_ppermutes_fresh"] == 8
    assert not res["mr2_sync"]["round1_off_critical_path"]
    # overlap k=2: round 1 (4 ppermutes: 2 shifts x payload+scales) carried,
    # round 2 fresh — overlap composes with multi-round as designed
    assert res["mr2_overlap"]["n_ppermutes"] == 8
    assert res["mr2_overlap"]["n_ppermutes_carried_only"] == 4
    assert res["mr2_overlap"]["n_ppermutes_fresh"] == 4
    assert res["mr2_overlap"]["round1_off_critical_path"]
    assert not res["mr2_overlap"]["off_grad_update_critical_path"]
    assert res["mr2_overlap_run"]["finite"]
    # time-varying: the lax.switch exchange equals dense Pi_t mixing within
    # the documented cross-mode fp envelope (~2e-4/step, 2 steps)
    assert res["tv"]["max_param_diff"] < 2e-3
    assert abs(res["tv"]["loss_sharded"] - res["tv"]["loss_stacked"]) < 1e-3
    # time-varying + overlap: every switch branch's ppermutes consume only
    # carried state (ring branch 2 shifts + fully-connected branch 3, each
    # permuting int8 payload + row scales = 10 collectives, all carried)
    assert res["tv_overlap"]["n_ppermutes"] == 10
    assert res["tv_overlap"]["off_grad_update_critical_path"]
    assert res["tv_overlap"]["round1_off_critical_path"]
    # EF overlap: all collectives carried; residual state is live & sharded
    assert res["ef_overlap"]["off_grad_update_critical_path"]
    assert res["ef_overlap"]["n_ppermutes"] == 4
    assert res["ef_overlap_run"]["res_max"] > 0.0
    assert res["ef_overlap_run"]["n_res_bufs"] >= 1


@pytest.mark.slow
def test_sharded_momentum_mixing_acceptance():
    """ISSUE-5 acceptance, sharded half: the momentum-mixed int8 CDMSGD
    wire through the REAL shard_map machinery (make_local_fused_comm ->
    engine phases -> ppermutes), on the paper MLP testbed at the PR 2
    caveat lr (0.01, mu 0.9), both schedules:

    * drift(mixed-int8 vs mixed-f32, same schedule) is bounded and
      strictly below drift(plain-int8 vs plain-f32) — the same criterion
      and (mesh 4x1: no model sharding, so the shard-local SR streams
      equal the stacked oracle's) the same measured envelope as the
      stacked test in tests/test_mixing.py;
    * the wire widens structurally: int8 mixed moves BOTH payload trees
      -> 8 ppermutes per step (2 ring shifts x (payload + row scales) x
      2 payload trees) vs 4 for plain, all of them consuming ONLY
      carried wire state under schedule='overlap' (the jaxpr taint
      proof), and OptState.wire holds one pair per bucket per payload.
    """
    res = run_sub(textwrap.dedent("""
        import functools, json
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.core import consensus as C
        from repro.core import engine
        from repro.core.optim import CDMSGD
        from repro.core.topology import make_topology
        from repro.launch.mesh import make_debug_mesh
        from repro.launch import steps as steps_lib
        from repro.nn.paper_models import (classifier_loss,
                                           mlp_classifier_apply,
                                           mlp_classifier_template)
        from repro.nn.param import init_params

        LOSS = functools.partial(classifier_loss, mlp_classifier_apply)
        A = 4
        mesh = make_debug_mesh(A, 1)
        topo = make_topology("ring", A)
        base = init_params(mlp_classifier_template(8, 4, width=16, depth=2),
                           jax.random.PRNGKey(0))
        params0 = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (A,) + x.shape).copy(), base)
        rng = np.random.default_rng(0)
        batch = {"x": jnp.asarray(rng.standard_normal((A, 8, 8)), jnp.float32),
                 "y": jnp.asarray(rng.integers(0, 4, (A, 8)), jnp.int32)}
        pspecs = jax.tree.map(
            lambda x: P(*(("data",) + (None,) * (x.ndim - 1))), params0)
        state_sp = P("data", None, None)

        def build(mm, exch, schedule):
            opt = CDMSGD(0.01, mu=0.9, fused=True)
            program = C.make_mixing_program(topo, exchange=exch,
                                            momentum_mixing=mm)
            comm = steps_lib.make_local_fused_comm(
                topo, mesh, "train", interpret=True, exchange=exch,
                program=program)
            engine.check_program_support(opt, comm)
            opt_specs = opt.state_specs(pspecs)
            n_entries = program.n_payloads  # MLP packs into one f32 bucket
            init_wire = None
            if schedule == "overlap":
                wire_specs = tuple((state_sp, state_sp)
                                   for _ in range(n_entries))
                opt_specs = opt_specs._replace(wire=wire_specs)
                local_wire_init = engine.make_local_wire_init(comm.flat)
                init_wire = lambda p: jax.shard_map(
                    local_wire_init, mesh=mesh, in_specs=(pspecs,),
                    out_specs=wire_specs, check_vma=False)(p)
            update_local = engine.make_update_phase(opt, comm, schedule)
            update_phase = lambda p, g, s: jax.shard_map(
                update_local, mesh=mesh, in_specs=(pspecs, pspecs, opt_specs),
                out_specs=(pspecs, opt_specs), check_vma=False)(p, g, s)
            return engine.StepProgram(
                optimizer=opt, comm=comm,
                grad_phase=engine.make_grad_phase(LOSS),
                update_phase=update_phase, schedule=schedule,
                init_wire=init_wire)

        def run(mm, exch, schedule):
            prog = build(mm, exch, schedule)
            with mesh:
                state = prog.init_state(params0)
                step = jax.jit(prog.step_fn)
                p = params0
                for _ in range(20):
                    p, state, m = step(p, state, batch)
            return p, state, float(m["loss"])

        def md(a, b):
            return max(jax.tree.leaves(jax.tree.map(
                lambda x, y: float(jnp.max(jnp.abs(x - y))), a, b)))

        out = {}
        for schedule in ("sync", "overlap"):
            rp, _, _ = run("none", "f32", schedule)
            rm, _, lm = run("mixed", "f32", schedule)
            pp, _, _ = run("none", "int8", schedule)
            pm, sm, lq = run("mixed", "int8", schedule)
            out[schedule] = {
                "drift_plain": md(rp, pp), "drift_mixed": md(rm, pm),
                "loss_gap_mixed": abs(lq - lm),
                "n_wire_entries": len(sm.wire),
                "finite": bool(all(jnp.all(jnp.isfinite(x))
                                   for x in jax.tree.leaves(pm))),
            }

        # structural: ppermute counts + the overlap taint proof
        for schedule in ("sync", "overlap"):
            for mm, key in (("none", "plain"), ("mixed", "mixed")):
                prog = build(mm, "int8", schedule)
                with mesh:
                    state = prog.init_state(params0)
                    rep = engine.exchange_dependency_report(
                        prog.step_fn, params0, state, batch)
                out[f"rep_{schedule}_{key}"] = rep
        print("RESULT " + json.dumps(out))
    """), timeout=840)
    for schedule in ("sync", "overlap"):
        r = res[schedule]
        assert r["finite"]
        # same criterion + envelope as the stacked acceptance test
        assert r["drift_mixed"] < 5e-2, r
        assert r["drift_mixed"] < r["drift_plain"], r
        assert r["loss_gap_mixed"] < 5e-2, r
    assert res["overlap"]["n_wire_entries"] == 2    # one pair per payload
    # widened wire: 2 ring shifts x (payload + scales) x 2 payload trees
    assert res["rep_sync_plain"]["n_ppermutes"] == 4
    assert res["rep_sync_mixed"]["n_ppermutes"] == 8
    assert res["rep_sync_mixed"]["depends_on_params"]
    assert res["rep_overlap_mixed"]["n_ppermutes"] == 8
    assert res["rep_overlap_mixed"]["n_ppermutes_carried_only"] == 8
    assert res["rep_overlap_mixed"]["off_grad_update_critical_path"]


@pytest.mark.slow
def test_sharded_build_train_step_momentum_mixing():
    """build_train_step threads momentum_mixing end-to-end on the real
    transformer path: the opt-state specs carry one wire pair AND one EF
    residual per bucket per payload, init_state fills them inside
    shard_map, one jitted step runs finite, and the dryrun-style record
    doubles the wire bytes (payloads=2)."""
    res = run_sub(textwrap.dedent("""
        import dataclasses, json
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.configs.base import InputShape
        from repro.core import consensus as consensus_lib
        from repro.core import engine, flatbuf
        from repro.core.optim import make_optimizer
        from repro.launch.mesh import make_debug_mesh
        from repro.launch import steps as steps_lib
        from repro.nn.param import init_params

        cfg = dataclasses.replace(get_config("granite-3-8b").reduced(),
                                  param_dtype="float32")
        shape = InputShape("tiny_train", 16, 8, "train")
        mesh = make_debug_mesh(4, 2)
        opt = make_optimizer("cdmsgd", 0.01, mu=0.9, fused=True)
        b = steps_lib.build_train_step(
            cfg, shape, mesh, opt, mode="train", topology_name="ring",
            mixing="ppermute_fused", exchange="int8", schedule="overlap",
            error_feedback=True, momentum_mixing="mixed")
        params = init_params(b.param_template, jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        batch = {
            "inputs": jnp.asarray(rng.integers(1, cfg.vocab_size, (4, 2, 16)), jnp.int32),
            "targets": jnp.asarray(rng.integers(1, cfg.vocab_size, (4, 2, 16)), jnp.int32),
        }
        n_buckets = flatbuf.make_flat_spec(params, lead=1).n_buckets
        with mesh:
            state = b.init_state(params)
            rep = engine.exchange_dependency_report(
                b.step_fn, params, state, batch)
            p1, s1, m = jax.jit(b.step_fn)(params, state, batch)
        wire = consensus_lib.exchange_bytes_per_step(
            flatbuf.make_flat_spec(params, lead=1), b.topology, "int8",
            b.mixing_program.rounds, b.mixing_program.n_payloads)
        base = consensus_lib.exchange_bytes_per_step(
            flatbuf.make_flat_spec(params, lead=1), b.topology, "int8")
        print("RESULT " + json.dumps({
            "n_buckets": n_buckets,
            "n_wire": len(state.wire), "n_residual": len(state.residual),
            "report": rep,
            "loss": float(m["loss"]),
            "finite": bool(all(jnp.all(jnp.isfinite(x))
                               for x in jax.tree.leaves(p1))),
            "residual_live": float(max(jnp.max(jnp.abs(r))
                                       for r in s1.residual)),
            "wire_bytes": wire["per_step_bytes"],
            "wire_bytes_base": base["per_step_bytes"],
        }))
    """), timeout=840)
    assert res["finite"]
    assert res["n_wire"] == 2 * res["n_buckets"]
    assert res["n_residual"] == 2 * res["n_buckets"]
    # overlap + momentum mixing: every collective consumes carried state
    assert res["report"]["n_ppermutes"] == 8 * res["n_buckets"]
    assert res["report"]["off_grad_update_critical_path"]
    assert res["residual_live"] > 0.0
    assert res["wire_bytes"] == 2 * res["wire_bytes_base"]


# host-side mirror of the subprocess fault table: at t = 0 mod 4 every
# sender has just published (stall window is steps 1..3)
FAULT_SEND_AGE_T0 = [0, 0, 0, 0]


@pytest.mark.slow
def test_sharded_bounded_staleness_acceptance():
    """ISSUE-6 acceptance, sharded half: the depth-S staleness ring +
    fault-injection layer through the REAL shard_map machinery
    (make_local_fused_comm -> engine phases -> ppermutes) on the paper
    MLP testbed, subprocess mesh, injected straggler schedule (one
    neighbor up to s_j = S steps stale for a 3-step window) plus one
    permanently dropped link:

    * training completes EVERY step at S in {1, 2, 4}, params finite,
      and the drift vs the fault-free overlap run is bounded — the same
      envelope as the stacked test in tests/test_faults.py;
    * S=1 with no faults (and the ENGAGED ring with no faults) is
      bit-for-bit today's overlap schedule;
    * exchange_dependency_report certifies every ppermute consumes ONLY
      carried wire state at EVERY tested S — the collective count stays
      the plain overlap schedule's 4 (2 ring shifts x (payload + row
      scales)): the ring deepens local state, never the wire.
    """
    res = run_sub(textwrap.dedent("""
        import functools, json
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.core import consensus as C
        from repro.core import engine
        from repro.core.faults import make_fault_schedule
        from repro.core.optim import CDSGD
        from repro.core.topology import make_topology
        from repro.launch.mesh import make_debug_mesh
        from repro.launch import steps as steps_lib
        from repro.nn.paper_models import (classifier_loss,
                                           mlp_classifier_apply,
                                           mlp_classifier_template)
        from repro.nn.param import init_params

        LOSS = functools.partial(classifier_loss, mlp_classifier_apply)
        A = 4
        mesh = make_debug_mesh(A, 1)
        topo = make_topology("ring", A)
        base = init_params(mlp_classifier_template(8, 4, width=16, depth=2),
                           jax.random.PRNGKey(0))
        params0 = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (A,) + x.shape).copy(), base)
        rng = np.random.default_rng(0)
        batch = {"x": jnp.asarray(rng.standard_normal((A, 8, 8)), jnp.float32),
                 "y": jnp.asarray(rng.integers(0, 4, (A, 8)), jnp.int32)}
        pspecs = jax.tree.map(
            lambda x: P(*(("data",) + (None,) * (x.ndim - 1))), params0)
        state_sp = P("data", None, None)
        FAULT = make_fault_schedule("stall:1:1:3,drop:0:2", A)

        def build(S, fault):
            opt = CDSGD(0.05, fused=True)
            program = C.make_mixing_program(topo, exchange="int8",
                                            staleness=S, faults=fault)
            comm = steps_lib.make_local_fused_comm(
                topo, mesh, "train", interpret=True, exchange="int8",
                program=program)
            engine.check_program_support(opt, comm)
            opt_specs = opt.state_specs(pspecs)
            n_entries = program.n_payloads
            if program.fault_tolerant:
                ring_sp = P("data", None, None, None)
                wire_specs = C.WireRing(
                    slots=tuple((ring_sp, ring_sp)
                                for _ in range(n_entries)),
                    send_age=P("data"), ages=P("data", None))
            else:
                wire_specs = tuple((state_sp, state_sp)
                                   for _ in range(n_entries))
            opt_specs = opt_specs._replace(wire=wire_specs)
            local_wire_init = engine.make_local_wire_init(comm.flat)
            init_wire = lambda p: jax.shard_map(
                local_wire_init, mesh=mesh, in_specs=(pspecs,),
                out_specs=wire_specs, check_vma=False)(p)
            update_local = engine.make_update_phase(opt, comm, "overlap")
            update_phase = lambda p, g, s: jax.shard_map(
                update_local, mesh=mesh, in_specs=(pspecs, pspecs, opt_specs),
                out_specs=(pspecs, opt_specs), check_vma=False)(p, g, s)
            return engine.StepProgram(
                optimizer=opt, comm=comm,
                grad_phase=engine.make_grad_phase(LOSS),
                update_phase=update_phase, schedule="overlap",
                init_wire=init_wire)

        def run(S, fault, steps=16):
            prog = build(S, fault)
            with mesh:
                state = prog.init_state(params0)
                step = jax.jit(prog.step_fn)
                p = params0
                losses = []
                for _ in range(steps):
                    p, state, m = step(p, state, batch)
                    losses.append(float(m["loss"]))
            return p, state, losses

        def md(a, b):
            return max(jax.tree.leaves(jax.tree.map(
                lambda x, y: float(jnp.max(jnp.abs(x - y))), a, b)))

        p_ref, _, _ = run(1, None)
        out = {"ring_noop_drift": md(p_ref, run(2, None)[0])}
        for S in (1, 2, 4):
            pf, sf, losses = run(S, FAULT)
            prog = build(S, FAULT)
            with mesh:
                st = prog.init_state(params0)
                rep = engine.exchange_dependency_report(
                    prog.step_fn, params0, st, batch)
            out[f"S{S}"] = {
                "drift": md(p_ref, pf),
                "all_finite": bool(all(np.isfinite(l) for l in losses)
                                   and all(jnp.all(jnp.isfinite(x))
                                           for x in jax.tree.leaves(pf))),
                "n_steps": len(losses),
                "send_age": np.asarray(sf.wire.send_age).tolist(),
                "report": rep,
            }
        print("RESULT " + json.dumps(out))
    """), timeout=840)
    # engaged ring + no faults == plain overlap, bit for bit
    assert res["ring_noop_drift"] == 0.0
    for S in (1, 2, 4):
        r = res[f"S{S}"]
        assert r["n_steps"] == 16 and r["all_finite"], r
        # bounded drift vs the fault-free run (stacked envelope, see
        # tests/test_faults.py::FAULT_DRIFT_BOUND)
        assert 0 < r["drift"] < 5e-2, r
        # every collective consumes ONLY carried wire state at every S,
        # and the count stays the plain overlap schedule's 4 — bytes on
        # the wire are independent of the ring depth
        assert r["report"]["n_ppermutes"] == 4, r
        assert r["report"]["n_ppermutes_carried_only"] == 4, r
        assert r["report"]["off_grad_update_critical_path"], r
        assert not r["report"]["depends_on_params"], r
        # the runtime send_age counters match the host fault table at the
        # consumption step the wire is positioned for (16 % period 4 = 0)
        assert r["send_age"] == FAULT_SEND_AGE_T0, r

