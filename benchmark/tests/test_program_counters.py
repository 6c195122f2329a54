"""The program's counters against the data files that read them.

A `counter_delta` metric is a data file that names a key of the
program's `run_cache_info()`; nothing but this test holds the two
together.  Where a program lacks the counter (a parent, under the file a
later PR adds) the reducer finds nothing to read, the metric is left out
of that run's line and the run goes on (ISSUE 29; before it the traced
run died with `KeyError`, which kept PR 27's five metrics out).
"""

import dataclasses
import io
import json
from contextlib import redirect_stdout

import cells

ENTRY_KEYS = ("name", "unit", "layer", "moves", "source")


def _counter_metrics():
    return [m for m in cells.load_layer_metrics() if m["reducer"] == "counter_delta"]


def test_every_counter_metric_names_a_counter_the_program_has():
    from wittgenstein_tpu.parallel.replica_shard import run_cache_info

    info = run_cache_info()
    metrics = _counter_metrics()
    assert {m["name"] for m in metrics} >= {
        "compile_s", "compiles_in_window", "lower_s", "backend_compile_s", "first_enqueue_s",
        "lookup_s_in_window", "enqueue_s_in_window"}
    for m in metrics:
        assert m["counter"] in info, (m["name"], sorted(info))
        assert isinstance(info[m["counter"]], (int, float))
        assert m["over"] in ("setup", "window") and m["source"] == "program_counter"


def test_the_split_of_compile_seconds_is_there_for_the_metrics_that_read_it():
    """What the five data files of ISSUE 29 name, and the identity
    `compile_s` rests on."""
    from wittgenstein_tpu.parallel.replica_shard import run_cache_info

    info = run_cache_info()
    for key in ("lower_seconds_total", "backend_compile_seconds_total",
                "lookup_seconds_total", "execute_seconds_total", "calls"):
        assert key in info
    assert abs(info["compile_seconds_total"] - info["lower_seconds_total"]
               - info["backend_compile_seconds_total"]) <= 1e-9 * max(1.0, info["compile_seconds_total"])


def test_every_layer_metric_file_equals_its_entry():
    entries = {m["name"]: m for m in cells.load_benchmark()["per_layer"]}
    files = {m["name"]: m for m in cells.load_layer_metrics()}
    assert set(files) == set(entries)
    for name, m in files.items():
        assert {k: m[k] for k in ENTRY_KEYS} == {k: entries[name][k] for k in ENTRY_KEYS}, name
        assert m.get("workloads") == entries[name].get("workloads"), name


def test_a_traced_rehearsal_prints_every_per_layer_metric_a_cpu_can_read(monkeypatch):
    import run

    # one more file, naming a counter that no program has: the case that shut PR 27 out
    cell = cells.load_cell("handel-4096.single-r1")
    absent = {"name": "absent_counter_s", "unit": "s", "better": "lower", "layer": "run cache",
              "moves": "setup_s", "source": "program_counter", "reducer": "counter_delta",
              "counter": "no_such_seconds_total", "over": "window"}
    cell = dataclasses.replace(cell, layer_metrics=cell.layer_metrics + (absent,))
    monkeypatch.setattr(cells, "load_cell", lambda workload: cell)
    out = io.StringIO()
    with redirect_stdout(out):
        code = run.main(["--workload", "handel-4096.single-r1", "--seed", "1", "--seconds", "5",
                         "--trace", "1", "--rehearse"])
    assert code == 4
    result = json.loads(out.getvalue().splitlines()[-1])  # the run still prints its line
    # a CPU has no memory_stats(): the one metric of the files that a rehearsal leaves out
    due = {m["name"] for m in cells.load_layer_metrics() if m["reducer"] != "memory_stat_gb"}
    assert set(result["metrics"]) == due and len(due) == len(cells.load_layer_metrics()) - 1
    assert result["metrics"]["compiles_in_window"]["value"] == 0
    value = {name: m["value"] for name, m in result["metrics"].items()}
    assert value["compile_s"] > 0 and value["lower_s"] > 0 and value["backend_compile_s"] > 0
    assert abs(value["lower_s"] + value["backend_compile_s"] - value["compile_s"]) < 1e-9
    assert min(value["first_enqueue_s"], value["lookup_s_in_window"], value["enqueue_s_in_window"]) > 0


def test_a_counter_that_either_snapshot_lacks_reads_as_nothing():
    import run

    m = {"name": "x", "unit": "s", "reducer": "counter_delta", "counter": "c", "over": "window"}
    cell = dataclasses.replace(cells.load_cell("gsf-2048.single-r1"), layer_metrics=(m,))
    for a, b, value in (({"c": 1.5}, {"c": 4.0}, 2.5), ({}, {"c": 4.0}, None), ({"c": 1.5}, {}, None),
                        ({"c": 0}, {"c": 0}, 0)):
        got = run.reduce_layer_metrics(cell, {"counters": {"window": [a, b]}})
        assert got == ({} if value is None else {"x": {"value": value, "unit": "s"}})
