"""Finding a cell's files by the names in BENCHMARK.json.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a data file of its own; a later PR adds a cell by
adding files and entries and edits nothing that is here.
"""

from __future__ import annotations

import dataclasses
import glob
import importlib
import json
import os

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class BenchmarkFileError(ValueError):
    """A data file is missing a key or names something that is not there."""


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _need(d: dict, keys, where: str) -> None:
    missing = [k for k in keys if k not in d]
    if missing:
        raise BenchmarkFileError(f"{where}: missing {missing}")


CONFIG_KEYS = (
    "source", "factory", "params_class", "params", "factory_kwargs",
    "reference", "twin", "guarantees", "assumed", "reduced",
)
TRAFFIC_KEYS = ("replicas", "chunk_ms", "rows", "on_done", "why")
LAYER_METRIC_KEYS = ("name", "unit", "layer", "moves", "source", "reducer")


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: tuple  # metric entries of BENCHMARK.json this cell reports
    layer_metrics: tuple  # layer-metric files that apply to this cell


def load_benchmark(root: str = ROOT) -> dict:
    return _load(os.path.join(root, "BENCHMARK.json"))


def load_layer_metrics(bench_dir: str = BENCH_DIR) -> list:
    """Every file in layer_metrics/, in name order."""
    out = []
    for path in sorted(glob.glob(os.path.join(bench_dir, "layer_metrics", "*.json"))):
        m = _load(path)
        _need(m, LAYER_METRIC_KEYS, path)
        out.append(m)
    return out


def load_cell(workload: str, root: str = ROOT) -> Cell:
    bench = load_benchmark(root)
    bench_dir = os.path.join(root, bench["paths"][0])
    entry = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if entry is None:
        names = [w["name"] for w in bench["workloads"]]
        raise BenchmarkFileError(f"no workload {workload!r} in BENCHMARK.json: {names}")
    cfg_entry = next(c for c in bench["configs"] if c["name"] == entry["config"])
    config = _load(os.path.join(root, cfg_entry["file"]))
    _need(config, CONFIG_KEYS, cfg_entry["file"])
    traffic_path = os.path.join(bench_dir, "traffic", entry["traffic"] + ".json")
    traffic = _load(traffic_path)
    _need(traffic, TRAFFIC_KEYS, traffic_path)

    def applies(m):
        return workload in m.get("workloads", [workload])

    return Cell(
        name=workload,
        chips=int(entry["chips"]),
        config_name=entry["config"],
        config=config,
        traffic_name=entry["traffic"],
        traffic=traffic,
        end_to_end=tuple(m for m in bench["end_to_end"] if applies(m)),
        layer_metrics=tuple(m for m in load_layer_metrics(bench_dir) if applies(m)),
    )


def resolve(dotted: str):
    """'package.module.attr' -> the attribute."""
    module, _, attr = dotted.rpartition(".")
    return getattr(importlib.import_module(module), attr)


def build_params(config: dict, params_class: str, overrides: dict | None = None):
    """The configuration's parameter object, with a twin's or a
    rehearsal's overrides on top."""
    return resolve(params_class)(**{**config["params"], **(overrides or {})})
