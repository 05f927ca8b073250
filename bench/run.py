"""Run one cell of the benchmark on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up builds the program's trainer for the cell (``BENCHMARK.json`` names
its configuration and traffic), starts it from weights made from the seed,
and drives it through its first steps on the cell's own batches: they
compile the step, and they are what the plain reference checks.  A few more
steps warm it up; then the window measures for ``--seconds``.  With
``--trace 0`` the result holds the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of a short
window.  After the window the program's state is freed and the reference
follows the same first steps; ``correct`` says whether the program's
readings lie within the cell's limits of it.

Earlier lines of standard output give the set-up's split and the window's
accounting; the last line is the result.  Exits non-zero, with no result,
where JAX finds no accelerator or fewer chips than the cell needs.
"""

import time

START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from lib import spec  # noqa: E402

CACHE_DIR = ".jax_cache"          # under the checkout's root
TRACE_DIR = ".bench_trace"


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def enable_cache(root: pathlib.Path) -> None:
    import jax

    jax.config.update("jax_compilation_cache_dir", str(root / CACHE_DIR))
    # every program, however quick to compile, is read back by the next run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def chips(n: int):
    """The first ``n`` accelerator devices; exits when there are fewer."""
    import jax

    devices = jax.devices()
    if devices[0].platform == "cpu" or len(devices) < n:
        log(f"needs {n} accelerator chip(s); JAX sees {len(devices)} "
            f"{devices[0].platform} device(s)")
        raise SystemExit(3)
    return devices[:n]


def memory_peaks(devices) -> dict:
    """The fullest chip's peaks so far: the buffers in use, and the bytes
    the TPU runtime reserved for its programs' temporaries."""
    stats = [d.memory_stats() or {} for d in devices]
    return {k: max(st.get(k, 0) for st in stats)
            for k in ("peak_bytes_in_use", "peak_bytes_reserved")}


def traced_metrics(cell, root, trace_dir, devices, acc, peaks):
    """The per-layer metrics and the device's busy time from the trace."""
    from lib import trace as tr

    t = tr.load(str(trace_dir), [d.id for d in devices])
    lo, hi = t.window
    planes = sorted(t.ops)
    busy = sum(tr.busy_ns(t.ops[p], lo, hi) for p in planes) / len(planes)
    ctx = types.SimpleNamespace(trace=t, planes=planes, window=acc,
                                cell=cell, peaks=peaks, root=root)
    metrics = {}
    for m in cell.per_layer:
        value = spec.metric_reader(m["name"], root).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    breakdown = {
        "device_ops": [[n, ns / 1e9] for n, ns in
                       tr.top_ops(t.ops[planes[0]], lo, hi)],
        "idle_gaps": [list(g) for g in tr.longest_gaps(t, planes[0])],
    }
    return metrics, {"busy_s": busy / 1e9, "window_s": (hi - lo) / 1e9}, \
        breakdown


def main(argv=None, *, require_chip: bool = True,
         root: pathlib.Path = spec.ROOT) -> int:
    args = parse(argv)
    cell = spec.load_cell(args.workload, root)
    import jax

    from lib import nn, program, reference, traffic, window

    enable_cache(root)
    devices = chips(cell.chips) if require_chip else jax.devices()[:1]
    split = {"jax_init_s": time.perf_counter() - START}
    peaks = spec.peaks(devices[0].device_kind, root) if require_chip else {}
    counters = window.Counters()

    # the peaks after each part of the set-up: which program set the peak
    # that peak_hbm_gb reads after the window
    memory = {}
    snapshot = lambda part: memory.update({part: memory_peaks(devices)})  # noqa: E731

    t = time.perf_counter()
    trainer = program.build(cell, args.seed, snapshot)
    split["build_s"] = time.perf_counter() - t
    snapshot("weights")

    t = time.perf_counter()
    feed = traffic.agent_batches(cell.traffic, cell.config["vocab_size"],
                                 args.seed)
    first = [next(feed) for _ in range(cell.traffic["check_steps"])]
    got = program.first_steps(trainer, cell, first, args.seed, snapshot)
    split["first_steps_s"] = time.perf_counter() - t
    snapshot("change_norms")

    t = time.perf_counter()
    for _ in range(cell.traffic["warmup_steps"]):
        trainer.step(next(feed))
    jax.block_until_ready(trainer.state.params)
    split["warmup_s"] = time.perf_counter() - t
    snapshot("warmup")
    split["memory_peaks"] = memory
    split["compiles_in_setup"] = dict(counters.events)
    gc.collect()
    gc.freeze()
    counters.reset()
    setup_s = time.perf_counter() - START
    print("setup " + json.dumps({"setup_s": setup_s, **split}), flush=True)

    losses = []
    sync = lambda: jax.block_until_ready(trainer.state.params)  # noqa: E731
    call = lambda b: losses.append(trainer.step(b)["loss"])  # noqa: E731
    trace_dir = root / TRACE_DIR
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        with jax.profiler.trace(str(trace_dir)), window.StallWatch() as watch:
            steps = window.run(lambda: next(feed), call, sync, args.seconds,
                               max_steps=cell.cell["trace_steps"],
                               traced=True)
    else:
        with window.StallWatch() as watch:
            steps = window.run(lambda: next(feed), call, sync, args.seconds)
    acc = window.account(steps, traffic.tokens_per_step(cell.traffic))
    acc["compiles"], acc["gc_collections"] = dict(counters.events), counters.gc
    acc["host_stalls"] = watch.stalls
    counters.close()
    # the TPU runtime holds a program's temporaries as reserved bytes, apart
    # from the buffers in use: the chip's peak is the sum of the two peaks
    stats = [d.memory_stats() or {} for d in devices]
    peak = max(s.get("peak_bytes_in_use", 0) + s.get("peak_bytes_reserved", 0)
               for s in stats)
    acc["memory_stats"] = stats
    print("accounting " + json.dumps(acc), flush=True)
    if acc["compiles"]:
        log(f"compiled inside the window: {acc['compiles']}")
        return 4

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    breakdown = None
    if args.trace:
        t = time.perf_counter()
        metrics, busy, breakdown = traced_metrics(cell, root, trace_dir,
                                                  devices, acc, peaks)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device.update(busy)
        log(f"trace read in {time.perf_counter() - t:.1f} s")
    else:
        values = {"tokens_per_s": acc["tokens_per_s"],
                  "step_p90_ms": acc["step_p90_ms"],
                  "peak_hbm_gb": peak / 1e9, "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}

    del trainer, call, sync
    gc.collect()
    t = time.perf_counter()
    want = reference.run(cell.reference, cell.config, first, args.seed,
                         traffic.TOPOLOGIES[cell.traffic["topology"]](
                             cell.traffic["agents"]),
                         cell.traffic["lr"], cell.traffic["momentum"],
                         nn.exact)
    log(f"reference followed {len(first)} steps in "
        f"{time.perf_counter() - t:.1f} s")
    compared = reference.compare(got, want)
    limits = cell.cell["limits"]
    for k in set(compared) - set(limits):
        log(f"not compared: {k} {compared.pop(k)[0]!r} "
            f"({cell.cell['not_compared'][k]})")
    checks = {k: {"value": v, "limit": limits[k]}
              for k, (v, _) in compared.items()}
    failed = sum(1 for x in losses if not math.isfinite(x))
    correct = failed == 0 and all(c["value"] <= c["limit"]
                                  for c in checks.values())
    result = {"correct": correct, "attempted": len(losses), "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    for k, (v, where) in compared.items():
        log(f"check {k} {v!r} limit {limits[k]!r} (worst at {where})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
