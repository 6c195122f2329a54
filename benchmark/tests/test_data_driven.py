"""A cell, a traffic mix, a configuration and a layer metric are added
as files and entries; nothing that is there is edited."""

import hashlib
import json
import os
import shutil

import pytest

import cells


def _hashes(root):
    out = {}
    for folder, _, files in os.walk(os.path.join(root, "benchmark")):
        for f in files:
            path = os.path.join(folder, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, root)] = hashlib.sha256(fh.read()).hexdigest()
    return out


@pytest.fixture
def copy(tmp_path):
    root = str(tmp_path)
    shutil.copytree(cells.BENCH_DIR, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", ".pytest_cache", "tests", "reference"))
    shutil.copy(os.path.join(cells.ROOT, "BENCHMARK.json"), root)
    return root


def test_new_files_are_picked_up_with_no_edit(copy):
    before = _hashes(copy)
    bench_dir = os.path.join(copy, "benchmark")
    # a traffic mix, a configuration and a layer metric: new files only
    with open(os.path.join(bench_dir, "traffic", "sweep-r2.json"), "w") as f:
        json.dump({"replicas": 2, "chunk_ms": 10, "rows": "seeds", "on_done": "next", "why": "test"}, f)
    config = json.load(open(os.path.join(bench_dir, "configs", "gsf-2048.json")))
    config["params"]["node_count"] = 1024
    with open(os.path.join(bench_dir, "configs", "gsf-1024.json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(bench_dir, "layer_metrics", "gather_share.json"), "w") as f:
        json.dump({"name": "gather_share", "unit": "%", "better": "lower", "layer": "protocol tick",
                   "moves": "sim_ms_per_s", "source": "device_trace", "reducer": "device_trace",
                   "per": "share", "regex": "gather", "workloads": ["gsf-1024.sweep-r2"]}, f)
    # entries added to BENCHMARK.json; the entries that were there are untouched
    bench = json.load(open(os.path.join(copy, "BENCHMARK.json")))
    old = json.dumps({k: bench[k] for k in ("configs", "workloads", "end_to_end", "per_layer")})
    bench["configs"].append({"name": "gsf-1024", "source": "test", "file": "benchmark/configs/gsf-1024.json",
                             "reduced": ["replicas"], "why": "test"})
    bench["workloads"].append({"name": "gsf-1024.sweep-r2", "config": "gsf-1024", "traffic": "sweep-r2",
                               "chips": 1, "why": "test"})
    json.dump(bench, open(os.path.join(copy, "BENCHMARK.json"), "w"))
    kept = {k: bench[k][: len(json.loads(old)[k])] for k in ("configs", "workloads")}
    assert kept == {k: json.loads(old)[k] for k in kept}

    cell = cells.load_cell("gsf-1024.sweep-r2", root=copy)
    assert cell.config["params"]["node_count"] == 1024 and cell.traffic["replicas"] == 2
    names = [m["name"] for m in cell.layer_metrics]
    assert "gather_share" in names and "device_ms_per_tick" in names
    # chunk_p95_ms lists the cell whose window holds enough chunks for a percentile
    assert [m["name"] for m in cell.end_to_end] == ["sim_ms_per_s", "setup_s"]
    assert "chunk_p95_ms" in [m["name"] for m in cells.load_cell("handel-4096.single-r1", root=copy).end_to_end]
    # the new metric applies to its own cell only
    other = cells.load_cell("gsf-2048.single-r1", root=copy)
    assert "gather_share" not in [m["name"] for m in other.layer_metrics]
    # a metric that lists no cells is due in the new cell too, with no edit to its file
    assert {"flatten_gather_share", "sort_scatter_share", "bitops_kernel_ms_per_tick"} <= set(names)
    after = _hashes(copy)
    assert {k: after[k] for k in before} == before  # no file that was there has changed


def test_a_name_that_is_not_there_is_an_error(copy):
    with pytest.raises(cells.BenchmarkFileError):
        cells.load_cell("no-such.cell", root=copy)
