"""Find a cell's files by name.

``BENCHMARK.json`` at the root of the checkout names the cells.  Everything
that belongs to one configuration, one traffic mix, one cell or one
per-layer metric sits in a file of its own, found here by its name:

    <file named by the configuration's "file">   sizes, cut, init rules
    <same path, .py>                              its plain f32 reference
    bench/traffic/<traffic>.json                  the traffic mix
    bench/workloads/<cell>.json                   the cell's limits
    bench/metrics/<metric>.py                     one per-layer reader

A later cell, configuration or metric is added by adding files only.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
from types import ModuleType
from typing import List

ROOT = pathlib.Path(__file__).resolve().parents[2]


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict            # the configuration file's contents
    reference: ModuleType   # the configuration's plain reference
    traffic: dict           # the traffic file's contents
    cell: dict              # the cell's own file: its limits, trace length
    end_to_end: List[dict]  # BENCHMARK.json's metrics: every cell reports each
    per_layer: List[dict]


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path) -> ModuleType:
    """Import a file whose name need not be a Python identifier."""
    name = "bench_" + "".join(c if c.isalnum() else "_" for c in
                              str(path.relative_to(path.parents[1])))
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(by_name)}")
    w = by_name[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_path = root / configs[w["config"]]["file"]
    config = load_json(cfg_path)
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=config,
        reference=load_module(cfg_path.with_suffix(".py")),
        traffic=load_json(root / "bench" / "traffic" / f"{w['traffic']}.json"),
        cell=load_json(root / "bench" / "workloads" / f"{name}.json"),
        end_to_end=bench["end_to_end"],
        per_layer=bench["per_layer"],
    )


def metric_reader(name: str, root: pathlib.Path = ROOT) -> ModuleType:
    return load_module(root / "bench" / "metrics" / f"{name}.py")


def peaks(device_kind: str, root: pathlib.Path = ROOT) -> dict:
    """The published peaks of one chip; a kind not in the table is an error."""
    table = load_json(root / "bench" / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"bench/peaks.json ({sorted(table)})")
    return table[device_kind]
