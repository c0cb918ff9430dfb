"""Resolve a cell of ``BENCHMARK.json`` to its files, by name.

A cell names a configuration and a traffic mix. The configuration's
file is the one ``BENCHMARK.json`` lists for it; the mix is
``traffic/<mix>.json`` beside this file; each per-layer metric is read
by ``metrics/<metric>.py``, a module with ``read(run) -> float | None``.
Adding a cell, configuration, mix or metric adds files and entries
only: nothing here names one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict            # the configuration file's contents
    traffic: dict           # the traffic file's contents
    end_to_end: list[dict]  # BENCHMARK.json metric entries for this cell
    per_layer: list[dict]


def bench_dir(root: str) -> str:
    """This directory, as it lies in the checkout at ``root``."""
    return os.path.join(root, os.path.relpath(HERE, ROOT))


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(bench: dict, cell_name: str, root: str = ROOT) -> Cell:
    cells = {w["name"]: w for w in bench["workloads"]}
    if cell_name not in cells:
        raise KeyError(f"no cell {cell_name!r}; have {sorted(cells)}")
    cell = cells[cell_name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _load_json(os.path.join(root, configs[cell["config"]]["file"]))
    traffic = _load_json(os.path.join(bench_dir(root), "traffic",
                                      cell["traffic"] + ".json"))
    e2e = [m for m in bench["end_to_end"] if _applies(m, cell_name)]
    per_layer = [m for m in bench["per_layer"]
                 if cell_name in m["workloads"]]
    return Cell(cell_name, int(cell["chips"]), config, traffic, e2e,
                per_layer)


def metric_reader(name: str, root: str = ROOT):
    """``read`` of ``metrics/<name>.py``."""
    path = os.path.join(bench_dir(root), "metrics", name + ".py")
    module = "chip_metric_" + name.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(module, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
