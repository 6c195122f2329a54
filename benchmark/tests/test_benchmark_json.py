"""BENCHMARK.json against the contract's limits on names and against
the benchmark's own data files."""

import json
import os
import re

import pytest

import cells

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return cells.load_benchmark()


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "benchmark/run.py"] and bench["paths"] == ["benchmark"]
    assert isinstance(bench["run_seconds"], int) and 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(cells.ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_names_units_and_lines(bench):
    metrics = bench["end_to_end"] + bench["per_layer"]
    for group in (bench["configs"], bench["workloads"], metrics):
        names = [e["name"] for e in group]
        assert len(set(names)) == len(names)
        assert all(NAME.match(n) for n in names), names
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert _line(m["layer"])
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert _line(c["source"]) and _line(c["why"]) and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        assert c["file"].startswith("benchmark/") and os.path.exists(os.path.join(cells.ROOT, c["file"]))
    files = [c["file"] for c in bench["configs"]]
    assert len(set(files)) == len(files)
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(pairs)) == len(pairs)
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert _line(w["why"]) and w["chips"] in (1, 4) and NAME.match(w["traffic"])
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(1, len(bench["workloads"]) // 2)
    assert "setup_s" in [m["name"] for m in bench["end_to_end"]]


def test_files_under_paths_are_named_from_name_characters():
    for folder, _, files in os.walk(cells.BENCH_DIR):
        if "__pycache__" in folder or ".pytest_cache" in folder:
            continue
        for f in files:
            rel = os.path.relpath(os.path.join(folder, f), cells.ROOT)
            assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel


def test_every_moves_is_reported_where_the_layer_metric_is(bench):
    cell_names = [w["name"] for w in bench["workloads"]]
    reported = {
        m["name"]: set(m.get("workloads", cell_names)) for m in bench["end_to_end"]
    }
    for m in bench["per_layer"]:
        assert m["moves"] in reported, m
        assert set(m.get("workloads", cell_names)) <= reported[m["moves"]], m


def test_layer_metric_files_are_what_benchmark_json_declares(bench):
    files = {m["name"]: m for m in cells.load_layer_metrics()}
    declared = {m["name"]: m for m in bench["per_layer"]}
    assert set(files) == set(declared)
    for name, m in declared.items():
        for key in ("unit", "better", "source", "layer", "moves"):
            assert files[name][key] == m[key], (name, key)
        assert files[name].get("workloads") == m.get("workloads")


def test_every_cell_loads_and_reports_what_the_contract_asks(bench):
    for w in bench["workloads"]:
        cell = cells.load_cell(w["name"])
        e2e = [m["name"] for m in cell.end_to_end]
        assert "setup_s" in e2e and len(e2e) >= 2 and cell.layer_metrics
        assert cell.config["twin"]["tolerance"] and cell.config["twin"]["controls"]
        assert cell.config["timed_rows"]["limits"] and cell.config["timed_rows"]["controls"]
        assert len(json.dumps(cell.config["source"])) - 2 <= 200
        assert sorted(cell.config["reduced"]) == sorted(
            next(c for c in bench["configs"] if c["name"] == w["config"])["reduced"]
        )
