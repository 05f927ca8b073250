"""A checkout root with small cells of both configurations, for the CPU.

The small configurations are the program's ``-reduced`` presets (2 layers,
d_model 256, vocabulary 512), each with its configuration's own reference
file; the traffic is the s2048 mix at 64 tokens a row.
"""

import json
import pathlib
import shutil

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent

CONFIGS = {
    "rwkv6-tiny": ("rwkv6-1.6b", {
        "program_arch": "rwkv6-1.6b-reduced", "n_layers": 2, "d_model": 256,
        "n_heads": 4, "head_size": 64, "d_ff": 512, "decay_lora": 64,
        "vocab_size": 512, "param_dtype": "bfloat16",
        "program_sizes": {"d_model": 256, "d_ff": 512, "vocab_size": 512}}),
    "hymba-tiny": ("hymba-1.5b", {
        "program_arch": "hymba-1.5b-reduced", "n_layers": 2, "d_model": 256,
        "n_heads": 4, "n_kv_heads": 4, "head_dim": 64, "d_ff": 512,
        "window": 8, "ssm_state": 16, "ssm_inner": 256, "rope_theta": 1e4,
        "vocab_size": 512, "param_dtype": "bfloat16",
        "program_sizes": {"d_model": 256, "n_kv_heads": 4, "window": 8}}),
}

# set from the program's and the control's readings at this size (see
# bench/tests/test_run.py): the program reads under a tenth of each
LIMITS = {"loss_gap": 1.2e-3, "grad_norm_gap": 0.015, "change_norm_gap": 0.25}


def make_root(tmp: pathlib.Path, limits=None) -> pathlib.Path:
    """``tmp`` laid out as a checkout holding only the small cells."""
    bench = tmp / "bench"
    for d in ("configs", "traffic", "workloads"):
        (bench / d).mkdir(parents=True, exist_ok=True)
    for d in ("lib", "metrics", "kernels"):
        shutil.copytree(BENCH / d, bench / d, dirs_exist_ok=True)
    shutil.copy(BENCH / "peaks.json", bench / "peaks.json")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"], spec["workloads"] = [], []
    for name, (source, cfg) in CONFIGS.items():
        shutil.copy(BENCH / "configs" / f"{source}.py",
                    bench / "configs" / f"{name}.py")
        (bench / "configs" / f"{name}.json").write_text(
            json.dumps({"name": name, **cfg}))
        spec["configs"].append({"name": name, "source": "test",
                                "file": f"bench/configs/{name}.json",
                                "reduced": [], "why": "test"})
        cell = f"{name}-s64"
        spec["workloads"].append({"name": cell, "config": name,
                                  "traffic": "ring2-s64", "chips": 1,
                                  "why": "test"})
        (bench / "workloads" / f"{cell}.json").write_text(json.dumps(
            {"limits": limits or LIMITS, "trace_steps": 3}))
    traffic = json.loads((BENCH / "traffic" / "ring2-s2048.json").read_text())
    traffic.update(seq=64, stream_tokens=4096)
    (bench / "traffic" / "ring2-s64.json").write_text(json.dumps(traffic))
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp
