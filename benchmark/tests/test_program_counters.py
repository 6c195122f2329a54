"""The program's counters against the data files that read them.

A `counter_delta` metric is a data file that names a key of the
program's `run_cache_info()`; nothing but this test holds the two
together.  The reducer indexes the key directly, so a file that names a
counter the program under test lacks makes every traced run raise: a
counter is added to the benchmark only once both sides of a comparison
have it (PERF.md section 7).
"""

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
    assert {m["name"] for m in metrics} >= {"compile_s", "compiles_in_window"}
    for m in metrics:
        assert m["counter"] in info, (m["name"], sorted(info))
        assert isinstance(info[m["counter"]], (int, float))
        assert m["over"] in ("setup", "window") and m["source"] == "program_counter"


def test_the_split_of_compile_seconds_is_there_for_the_metrics_that_will_read_it():
    """What a later `benchmark` PR's data files can name (PERF.md section
    7), and the identity `compile_s` rests on."""
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


def test_a_traced_rehearsal_prints_every_per_layer_metric_a_cpu_can_read():
    import run

    out = io.StringIO()
    with redirect_stdout(out):
        code = run.main(["--workload", "handel-4096.single-r1", "--seed", "1", "--seconds", "5",
                         "--trace", "1", "--rehearse"])
    assert code == 4
    result = json.loads(out.getvalue().splitlines()[-1])
    # a CPU has no memory_stats(): the one metric a rehearsal leaves out
    due = {m["name"] for m in cells.load_layer_metrics() if m["reducer"] != "memory_stat_gb"}
    assert set(result["metrics"]) == due and len(due) == len(cells.load_layer_metrics()) - 1
    assert result["metrics"]["compiles_in_window"]["value"] == 0
    assert result["metrics"]["compile_s"]["value"] > 0
