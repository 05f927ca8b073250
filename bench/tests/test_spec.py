"""A cell, configuration or metric is found by its name: adding files is
enough."""

import json
import shutil

import pytest

from lib import spec

from tiny import BENCH, ROOT


def test_every_cell_of_the_benchmark_loads():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        numbers = {"loss_gap", "grad_norm_gap", "change_norm_gap"}
        # a number with no limit is left out by a stated reason
        assert set(cell.cell["limits"]) | set(
            cell.cell.get("not_compared", {})) == numbers
        assert cell.config["reduced"] == [
            c for c in bench["configs"] if c["name"] == w["config"]][0][
                "reduced"]
        for m in cell.per_layer:
            assert hasattr(spec.metric_reader(m["name"]), "read")


def test_a_cell_added_as_files_is_found(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "hymba-ring2-s256",
                               "config": "hymba-1.5b", "traffic": "ring2-s256",
                               "chips": 1, "why": "a new cell"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(FileNotFoundError):   # no cell file yet
        spec.load_cell("hymba-ring2-s256", tmp_path)
    shutil.copy(tmp_path / "bench/workloads/hymba-ring2-s2048.json",
                tmp_path / "bench/workloads/hymba-ring2-s256.json")
    cell = spec.load_cell("hymba-ring2-s256", tmp_path)
    assert cell.traffic["seq"] == 256
    assert cell.config["name"] == "hymba-1.5b"
    assert cell.reference.flops_per_token(cell.config, 256) > 0
