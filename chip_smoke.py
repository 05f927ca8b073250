"""On-chip smoke test of the collaborative CDMSGD train step.

    python chip_smoke.py              # one TPU chip
    python chip_smoke.py --chips 4    # four TPU chips of one host

Model: rwkv6-1.6b at its published widths (d_model 2048, 32 WKV heads of
64, d_ff 7168, vocab 65536, bf16 params) with random weights from a seed.
Only the depth is cut: to the deepest whole-layer count (at least 2) whose
compiled train step fits in 85% of one chip's HBM by
``compiled.memory_analysis()``.  Sequence length 2048, batch 1 per agent,
fused CDMSGD (momentum 0.9, lr 0.01) over a ring.

One chip: two agents stacked on the chip, driven through the same
``build_trainer`` as ``python -m repro.launch.train``.  The script compiles
the step, checks that it holds the compiled Pallas update
(``tpu_custom_call``), runs five steps (loss and consensus error finite),
and checks one step of the fused flat-buffer update against the per-leaf
unfused CDMSGD path from the same state and batch.

``--chips 4``: one agent per chip on a (data=4, model=1) mesh through
``repro.launch.steps.build_train_step`` with ``mixing="ppermute_fused"``,
checked against ``mixing="dense"`` from the same seed and batches.  Only
this phase runs.

Exits non-zero when JAX finds no TPU, and when any check fails.  The last
line of standard output is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

ARCH = "rwkv6-1.6b"
SEQ = 2048
SEED = 0
HBM_SHARE = 0.85            # of the device's bytes_limit
STEPS = 5                   # one-chip smoke steps
SHARDED_STEPS = 3           # four-chip steps, per mixing mode
STACKED_AGENTS = 2          # agents stacked on one chip (a ring of 2 = K2)
# bf16 unit roundoff: bf16 keeps 8 significant bits
BF16_U = 2.0 ** -8


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def require_tpu(n_chips: int):
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU found: jax.devices()[0].platform is "
            f"{devices[0].platform!r}")
    if len(devices) != n_chips:
        raise SystemExit(f"chip_smoke: --chips {n_chips} needs exactly "
                         f"{n_chips} TPU devices, JAX sees {len(devices)}")
    return devices


def step_peak_bytes(compiled) -> int:
    """Peak device bytes of one compiled program, per device."""
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes
            + m.generated_code_size_in_bytes)


def hbm_budget(device) -> int:
    limit = (device.memory_stats() or {}).get("bytes_limit")
    if not limit:
        raise RuntimeError(f"{device} reports no bytes_limit")
    return int(HBM_SHARE * limit)


def gb(n: float) -> str:
    return f"{n / 1e9:.3f} GB"


def deepest_depth(published: int, budget: int, peak_at):
    """Deepest layer count in ``[2, published]`` whose step fits ``budget``.

    ``peak_at(n)`` compiles the step at ``n`` layers and returns its peak
    bytes.  The 2- and 3-layer compiles give a linear first guess; the
    search then walks down until the guess fits and up until the next
    depth does not, so the answer rests on compiles, not on the guess.
    """
    peaks = {}

    def fits(n):
        if n not in peaks:
            peaks[n] = peak_at(n)
        return peaks[n] <= budget

    if not fits(2):
        raise RuntimeError(f"2 layers need {gb(peaks[2])} > budget "
                           f"{gb(budget)}")
    if published == 2 or not fits(3):
        return 2, peaks
    slope = max(peaks[3] - peaks[2], 1)
    n = min(published, 3 + int((budget - peaks[3]) // slope))
    while n > 3 and not fits(n):
        n -= 1
    while n < published and fits(n + 1):
        n += 1
    return n, peaks


def print_cut(published, n, agents, where: str) -> None:
    log(f"cut: {ARCH} published depth {published.n_layers} layers -> kept "
        f"{n} (the deepest whole-layer count >= 2 whose compiled step fits "
        f"{HBM_SHARE:.0%} of one chip's HBM); widths as published "
        f"(d_model {published.d_model}, {published.n_heads} WKV heads of "
        f"{published.head_dim_}, d_ff {published.d_ff}, vocab "
        f"{published.vocab_size}, {published.param_dtype}); {agents} agents "
        f"{where}; seq {SEQ}, batch 1 per agent")


def assert_finite(name: str, value: float) -> None:
    if not np.isfinite(value):
        raise AssertionError(f"{name} is not finite: {value}")


def assert_in_compiled(text: str, needles, what: str) -> None:
    missing = [n for n in needles if n not in text]
    if missing:
        raise AssertionError(f"{missing} missing from the compiled {what}")
    log(f"{' and '.join(needles)} present in the compiled {what}")


def max_rel_diff(a, b) -> float:
    """max |a - b| over max |b| of one leaf, in f32."""
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    scale = float(np.max(np.abs(b)))
    return float(np.max(np.abs(a - b))) / scale if scale else \
        float(np.max(np.abs(a)))


# --------------------------------------------------------------------------
# one chip: CollaborativeTrainer, agents stacked on the chip
# --------------------------------------------------------------------------


def trainer_args(fused: bool):
    from repro.launch import train as train_cli

    argv = ["--arch", ARCH, "--preset", "full",
            "--agents", str(STACKED_AGENTS), "--topology", "ring",
            "--optimizer", "cdmsgd", "--batch", "1", "--seq", str(SEQ),
            "--seed", str(SEED)]
    return train_cli.build_parser().parse_args(
        argv + (["--fused"] if fused else []))


def abstract_step(args, cfg, device):
    """Lower the trainer's step for ``cfg`` without allocating its state."""
    from repro.launch import train as train_cli

    built = {}

    def build():
        trainer, batches = train_cli.build_trainer(args, cfg,
                                                   printer=lambda s: None)
        built["trainer"], built["batch"] = trainer, next(batches)
        return trainer.state.params, trainer.state.opt_state

    state = jax.eval_shape(build)
    sharding = jax.sharding.SingleDeviceSharding(device)
    put = lambda t: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding), t)
    batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=sharding)
             for k, v in built["batch"].items()}
    return built["trainer"]._step_fn.lower(*put(state), batch)


def one_chip(device) -> None:
    from repro.configs import get_config
    from repro.launch import train as train_cli

    published = get_config(ARCH)
    at_depth = lambda n: dataclasses.replace(published, n_layers=n)
    budget = hbm_budget(device)
    fused_args = trainer_args(fused=True)

    def peak_at(n):
        t0 = time.perf_counter()
        peak = step_peak_bytes(
            abstract_step(fused_args, at_depth(n), device).compile())
        log(f"depth probe: {n} layers -> compiled step peak {gb(peak)} "
            f"(budget {gb(budget)}), {time.perf_counter() - t0:.1f} s")
        return peak

    n, _ = deepest_depth(published.n_layers, budget, peak_at)
    print_cut(published, n, STACKED_AGENTS,
              "stacked on one chip (a ring of 2 is the complete graph; 4 "
              "stacked agents hold 5-6 copies of 2.1 GB of embedding and "
              "head each, more than 16 GB)")

    trainer, batches = train_cli.build_trainer(fused_args, at_depth(n),
                                               printer=log)
    batch = next(batches)
    t0 = time.perf_counter()
    compiled = trainer._step_fn.lower(trainer.state.params,
                                      trainer.state.opt_state,
                                      batch).compile()
    log(f"compile: {time.perf_counter() - t0:.1f} s; compiled step peak "
        f"{gb(step_peak_bytes(compiled))}")
    # the fused update is a compiled Mosaic kernel, not interpreted Python
    assert_in_compiled(compiled.as_text(), ("tpu_custom_call",), "step")
    for i in range(STEPS):
        t0 = time.perf_counter()
        m = trainer.step(batch)
        jax.block_until_ready(trainer.state.params)
        dt = time.perf_counter() - t0
        assert_finite("loss", m["loss"])
        assert_finite("consensus_error", m["consensus_error"])
        log(f"step {i + 1}/{STEPS}: loss {m['loss']:.6f} consensus_error "
            f"{m['consensus_error']:.6e} step {dt:.3f} s")
        batch = next(batches)
    log(f"peak_bytes_in_use "
        f"{(device.memory_stats() or {}).get('peak_bytes_in_use')}")
    del trainer, compiled, batches
    fused_vs_unfused(at_depth(2))


def fused_vs_unfused(cfg) -> None:
    """One step from the same state and batch: fused kernel vs per-leaf.

    The state is one fused step in (momentum non-zero) with each agent's
    parameters perturbed by independent N(0, 0.02^2) noise — about the
    scale of the initial weights — so the mix moves every element by
    O(|x|).  Tolerance per leaf and agent: the unfused path
    rounds the mix, the new momentum and their sum to bf16 (three roundings
    of unit roundoff 2^-8) where the fused kernel accumulates in f32 and
    rounds once, so the two may differ by about 3 * 2^-8 of the leaf's
    magnitude; 4 * 2^-8 leaves one rounding of slack.  A kernel that drops
    the mix or the gradient misses by far more.
    """
    from repro.core.trainer import TrainState, perturb_per_agent
    from repro.launch import train as train_cli

    tol = 4 * BF16_U
    trainer, batches = train_cli.build_trainer(trainer_args(fused=True), cfg,
                                               printer=lambda s: None)
    b1, b2 = next(batches), next(batches)
    trainer.step(b1)
    params = perturb_per_agent(trainer.state.params,
                               jax.random.PRNGKey(SEED + 1), scale=0.02)
    state = jax.device_get((params, trainer.state.opt_state))
    trainer.state = TrainState(params=params,
                               opt_state=trainer.state.opt_state, step=1)
    m_f = trainer.step(b2)
    fused = jax.device_get((trainer.state.params,
                            trainer.state.opt_state.inner))
    del trainer
    trainer, _ = train_cli.build_trainer(trainer_args(fused=False), cfg,
                                         printer=lambda s: None)
    if (jax.tree.structure(state[1])
            != jax.tree.structure(trainer.state.opt_state)):
        raise AssertionError("fused and unfused optimizer states differ in "
                             "structure; cannot start both from one state")
    trainer.state = TrainState(params=jax.device_put(state[0]),
                               opt_state=jax.device_put(state[1]), step=1)
    m_u = trainer.step(b2)
    unfused = jax.device_get((trainer.state.params,
                              trainer.state.opt_state.inner))
    del trainer
    worst = {}
    for what, f_tree, u_tree in (("params", fused[0], unfused[0]),
                                 ("momentum", fused[1], unfused[1])):
        for (path, f), u in zip(jax.tree_util.tree_leaves_with_path(f_tree),
                                jax.tree.leaves(u_tree)):
            for a in range(STACKED_AGENTS):
                d = max_rel_diff(f[a], u[a])
                key = (what, a)
                if d > worst.get(key, (-1.0, ""))[0]:
                    worst[key] = (d, jax.tree_util.keystr(path))
    for (what, a), (d, path) in sorted(worst.items()):
        log(f"fused vs unfused, agent {a} {what}: max |diff| / max |x| = "
            f"{d:.3e} at {path} (tolerance {tol:.3e})")
    bad = {k: v for k, v in worst.items() if not v[0] <= tol}
    if bad:
        raise AssertionError(f"fused update disagrees with the per-leaf "
                             f"path beyond {tol:.3e}: {bad}")
    if m_f["loss"] != m_u["loss"]:
        log(f"note: step losses differ: fused {m_f['loss']} unfused "
            f"{m_u['loss']}")
    log("fused vs unfused: within tolerance")


# --------------------------------------------------------------------------
# four chips: build_train_step, one agent per chip
# --------------------------------------------------------------------------


def sharded_bundle(cfg, mesh, mixing: str):
    from repro.configs.base import InputShape
    from repro.core.optim import make_optimizer
    from repro.launch.steps import build_train_step

    n_agents = mesh.shape["data"]
    shape = InputShape("chip_smoke", SEQ, n_agents, "train")   # 1 per agent
    opt = make_optimizer("cdmsgd", 0.01, mu=0.9,
                         fused=mixing == "ppermute_fused")
    bundle = build_train_step(cfg, shape, mesh, opt, mode="train",
                              topology_name="ring", mixing=mixing)
    return bundle, opt


def sharded_lowered(bundle, opt, mesh):
    step = jax.jit(bundle.step_fn, donate_argnums=bundle.donate_argnums)
    return step.lower(bundle.param_structs(mesh),
                      bundle.opt_state_structs(mesh, opt),
                      bundle.batch_specs)


def four_chips(devices) -> None:
    from repro.configs import get_config
    from repro.data import make_lm_tokens, lm_agent_batches
    from repro.launch.mesh import make_debug_mesh
    from repro.nn import init_params

    mesh = make_debug_mesh(len(devices), 1)
    published = get_config(ARCH)
    at_depth = lambda n: dataclasses.replace(published, n_layers=n)
    budget = hbm_budget(devices[0])

    def peak_at(n):
        t0 = time.perf_counter()
        bundle, opt = sharded_bundle(at_depth(n), mesh, "ppermute_fused")
        peak = step_peak_bytes(sharded_lowered(bundle, opt, mesh).compile())
        log(f"depth probe: {n} layers, mixing=ppermute_fused -> compiled "
            f"step peak {gb(peak)} per chip (budget {gb(budget)}), "
            f"{time.perf_counter() - t0:.1f} s")
        return peak

    # the rule runs on the path under test; the dense reference holds less
    # (no packed buffers) and its own compile below must fit as well
    n, _ = deepest_depth(published.n_layers, budget, peak_at)
    print_cut(published, n, len(devices),
              "one per chip on a (data=4, model=1) mesh, ring mixing")
    cfg = at_depth(n)
    tokens = make_lm_tokens(1 << 15, vocab=cfg.vocab_size, seed=SEED)
    batches = lm_agent_batches(tokens, len(devices), 1, SEQ, seed=SEED)
    host_batches = [next(batches) for _ in range(SHARDED_STEPS)]
    results = {}
    for mixing in ("ppermute_fused", "dense"):
        bundle, opt = sharded_bundle(cfg, mesh, mixing)
        t0 = time.perf_counter()
        compiled = sharded_lowered(bundle, opt, mesh).compile()
        peak = step_peak_bytes(compiled)
        log(f"mixing={mixing}: compile {time.perf_counter() - t0:.1f} s, "
            f"step peak {gb(peak)} per chip")
        if peak > budget:
            raise RuntimeError(f"mixing={mixing} needs {gb(peak)} per chip "
                               f"at {n} layers, over the budget {gb(budget)}")
        if mixing == "ppermute_fused":
            assert_in_compiled(compiled.as_text(),
                               ("collective-permute", "tpu_custom_call"),
                               "ppermute_fused step")
        pshard = jax.tree.map(lambda s: s.sharding, bundle.param_structs(mesh))
        oshard = jax.tree.map(lambda s: s.sharding,
                              bundle.opt_state_structs(mesh, opt))
        params = jax.jit(lambda k: init_params(bundle.param_template, k),
                         out_shardings=pshard)(jax.random.PRNGKey(SEED))
        state = jax.jit(bundle.init_state or opt.init,
                        out_shardings=oshard)(params)
        check_one_agent_per_device(params, len(devices), "initial params")
        losses = []
        for i, hb in enumerate(host_batches):
            batch = {k: jax.device_put(v, bundle.batch_specs[k].sharding)
                     for k, v in hb.items()}
            t0 = time.perf_counter()
            params, state, metrics = compiled(params, state, batch)
            jax.block_until_ready(params)
            dt = time.perf_counter() - t0
            loss = float(metrics["loss"])
            assert_finite("loss", loss)
            losses.append(loss)
            if i == 0:
                first = jax.device_get(params)
            log(f"mixing={mixing} step {i + 1}/{SHARDED_STEPS}: loss "
                f"{loss:.6f} step {dt:.3f} s")
        check_one_agent_per_device(params, len(devices), "params after "
                                   f"{SHARDED_STEPS} steps")
        results[mixing] = (losses, first)
        del params, state, compiled
    compare_sharded(results["ppermute_fused"], results["dense"])


def check_one_agent_per_device(params, n_devices: int, what: str) -> None:
    leaf = jax.tree.leaves(params)[0]
    shards = leaf.addressable_shards
    devices = {s.device for s in shards}
    if len(devices) != n_devices or any(s.data.shape[0] != 1 for s in shards):
        raise AssertionError(
            f"{what}: expected one agent on each of {n_devices} devices, got "
            f"shards {[(str(s.device), s.data.shape) for s in shards]}")
    log(f"{what}: agent dim 1 on each of {n_devices} devices "
        f"({leaf.shape} -> shards of {shards[0].data.shape})")


def compare_sharded(fused, dense) -> None:
    """ppermute_fused vs dense from the same seed and batches.

    The parameters after the first step come from one state and one batch:
    per leaf and agent they may differ by about 3 * 2^-8 of the leaf's
    magnitude (the dense path rounds the mix, the momentum and their sum
    to bf16, the fused kernel rounds once), so the tolerance is 4 * 2^-8.
    The agents start from distinct random weights, so a wrong mix misses
    by O(1).  Later steps start from states that already differ by that
    rounding; their losses must stay within the same relative tolerance.
    """
    tol = 4 * BF16_U
    (lf, pf), (ld, pd) = fused, dense
    for i, (a, b) in enumerate(zip(lf, ld)):
        log(f"step {i + 1} loss: ppermute_fused {a:.6f} dense {b:.6f}")
        if not abs(a - b) <= tol * abs(b):
            raise AssertionError(f"step {i + 1} losses differ beyond "
                                 f"{tol:.3e} relative: {a} vs {b}")
    worst = (0.0, "")
    for (path, f), d in zip(jax.tree_util.tree_leaves_with_path(pf),
                            jax.tree.leaves(pd)):
        for a in range(f.shape[0]):
            r = max_rel_diff(f[a], d[a])
            if r > worst[0]:
                worst = (r, f"{jax.tree_util.keystr(path)} agent {a}")
    log(f"ppermute_fused vs dense params after step 1: max |diff| / max |x| "
        f"= {worst[0]:.3e} at {worst[1]} (tolerance {tol:.3e})")
    if not worst[0] <= tol:
        raise AssertionError("ppermute_fused disagrees with dense mixing")
    log("ppermute_fused matches dense within tolerance")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)
    devices = require_tpu(args.chips)
    from repro.launch.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    log(f"devices: {len(devices)} x {devices[0].device_kind}")
    if args.chips == 1:
        one_chip(devices[0])
    else:
        four_chips(devices)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
