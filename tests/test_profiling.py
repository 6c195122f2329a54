"""Cost-attribution profiling layer tests (ISSUE 7).

Covers the profiling package (XLA cost/memory normalization, the HBM
replica model, probe-verdict cache + export, the feasibility-budget
arithmetic and staleness gate), the run cache's counter/metrics surface,
the Supervisor's chunk-time histogram, and the host trace/Prom export
helpers (SpanTracer, PromText) the layer emits through.
"""

from __future__ import annotations

import json
import math
import pathlib

import numpy as np
import pytest

REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]


# ---------------------------------------------------------------------------
# telemetry.trace: SpanTracer
# ---------------------------------------------------------------------------

def test_span_tracer_chrome_schema(tmp_path):
    from wittgenstein_tpu.telemetry.trace import SpanTracer, validate_chrome_trace

    tr = SpanTracer(process_name="test-proc")
    with tr.span("outer", kind="a"):
        with tr.span("inner"):
            pass
    tr.instant("mark", chunk=3)
    tr.add_span("manual", tr.now_us(), 12.5, chunk=1)

    doc = tr.to_json()
    validate_chrome_trace(doc)
    evs = doc["traceEvents"]
    # metadata event first, then inner closes before outer
    assert evs[0]["ph"] == "M"
    names = [e["name"] for e in evs[1:]]
    assert names == ["inner", "outer", "mark", "manual"]
    outer = next(e for e in evs if e["name"] == "outer")
    inner = next(e for e in evs if e["name"] == "inner")
    # nesting: inner lies within outer's [ts, ts+dur] window
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 0.2
    assert outer["args"] == {"kind": "a"}

    p = tr.write(str(tmp_path / "trace.json"))
    validate_chrome_trace(json.loads(pathlib.Path(p).read_text()))


def test_span_tracer_now_us_monotonic():
    from wittgenstein_tpu.telemetry.trace import SpanTracer

    tr = SpanTracer()
    a = tr.now_us()
    b = tr.now_us()
    assert 0 <= a <= b


def test_maybe_span_no_tracer():
    from wittgenstein_tpu.telemetry.trace import maybe_span

    with maybe_span(None, "anything"):
        pass  # must be a clean no-op


def test_validate_chrome_trace_rejects_malformed():
    from wittgenstein_tpu.telemetry.trace import validate_chrome_trace

    with pytest.raises(ValueError):
        validate_chrome_trace({"events": []})
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": [{"ph": "X", "name": "x"}]})


# ---------------------------------------------------------------------------
# telemetry.export: PromText
# ---------------------------------------------------------------------------

def test_promtext_families_and_escaping():
    from wittgenstein_tpu.telemetry.export import PromText

    p = PromText("witt")
    p.add("thing_total", 1, "a counter", "counter", {"mtype": "x"})
    p.add("thing_total", 2, "a counter", "counter", {"mtype": 'y"\\z'})
    p.add("gauge_v", 3.5, 'help with "quotes"\nand newline')
    text = p.render()

    # one HELP/TYPE header per family even with two samples
    assert text.count("# TYPE witt_thing_total counter") == 1
    assert 'witt_thing_total{mtype="x"} 1' in text
    # label escaping: backslash then quote
    assert 'mtype="y\\"\\\\z"' in text
    # HELP escaping: newline must not split the line
    assert "# HELP witt_gauge_v" in text
    assert '\\nand newline' in text
    assert text.endswith("\n")


def test_promtext_no_prefix():
    from wittgenstein_tpu.telemetry.export import PromText

    text = PromText("").add("bare", 1).render()
    assert "bare 1" in text
    assert "witt" not in text


# ---------------------------------------------------------------------------
# profiling.xla_cost
# ---------------------------------------------------------------------------

def test_cost_and_memory_analysis_on_tiny_fn():
    import jax
    import jax.numpy as jnp

    from wittgenstein_tpu.profiling.xla_cost import (
        compiled_cost_summary,
        cost_analysis_dict,
        memory_analysis_dict,
    )

    x = jnp.arange(1024, dtype=jnp.float32)
    compiled = jax.jit(lambda v: (v * 2.0).sum()).lower(x).compile()

    cost = cost_analysis_dict(compiled)
    assert cost is not None
    assert cost["flops"] >= 1024  # at least one flop per element
    assert cost["bytes_accessed"] >= 4 * 1024

    mem = memory_analysis_dict(compiled)
    assert mem is not None
    assert mem["argument_size_in_bytes"] >= 4 * 1024
    assert mem["live_bytes"] >= mem["output_size_in_bytes"]

    summary = compiled_cost_summary(compiled, compile_seconds=0.5)
    assert summary["compile_seconds"] == 0.5
    assert summary["cost"]["flops"] == cost["flops"]


def test_format_bytes():
    from wittgenstein_tpu.profiling.xla_cost import format_bytes

    assert format_bytes(512) == "512 B"
    assert format_bytes(2048) == "2.0 KiB"
    assert "MiB" in format_bytes(3 * 1024 * 1024)


# ---------------------------------------------------------------------------
# profiling.hbm
# ---------------------------------------------------------------------------

def test_state_bytes_and_replicas_per_chip():
    from wittgenstein_tpu.profiling.hbm import (
        replicas_per_chip,
        state_bytes_per_replica,
    )

    state = {
        "a": np.zeros((100,), np.int32),  # 400 B
        "b": np.zeros((10, 10), np.float32),  # 400 B
        "c": np.zeros((), np.bool_),  # 1 B
    }
    rep = state_bytes_per_replica(state)
    assert rep["total_bytes"] == 801
    assert rep["n_leaves"] == 3
    assert rep["top"][0][1] == 400  # largest leaves first

    model = replicas_per_chip(state, hbm_gib=1.0, overhead=2.0, reserved_gib=0.5)
    expect = math.floor(0.5 * 1024**3 / (801 * 2.0))
    assert model["replicas"] == expect
    assert model["bytes_per_replica"] == 801


def test_hbm_report_cross_check():
    from wittgenstein_tpu.profiling.hbm import hbm_report

    state = {"a": np.zeros((1000,), np.float32)}  # 4000 B modeled
    rep = hbm_report(
        state,
        memory={
            "argument_size_in_bytes": 4000,
            "output_size_in_bytes": 4000,
            "temp_size_in_bytes": 100,
            "live_bytes": 8100,
        },
    )
    assert rep["model"]["bytes_per_replica"] == 4000
    assert rep["measured"]["live_bytes_1_replica"] == 8100
    # modeled = bytes_per_replica * the 2x overhead factor
    assert rep["measured"]["modeled_bytes"] == 8000
    assert rep["measured"]["model_over_measured"] == pytest.approx(
        8000 / 8100, abs=0.01
    )


# ---------------------------------------------------------------------------
# profiling.probe
# ---------------------------------------------------------------------------

def _verdict(platform="cpu", reason=None):
    return {
        "platform": platform,
        "fallback_reason": reason,
        "attempts": [{"platform": "tpu", "rc": 1}, {"platform": "cpu", "rc": 0}],
    }


def test_probe_cache_roundtrip(tmp_path):
    from wittgenstein_tpu.profiling.probe import (
        read_probe_cache,
        write_probe_cache,
    )

    path = str(tmp_path / "probe.json")
    assert read_probe_cache(path) is None
    write_probe_cache(_verdict(), path)
    cached = read_probe_cache(path)
    assert cached is not None and cached["platform"] == "cpu"
    assert "ts" in cached

    # stale entries are rejected
    doc = json.loads(pathlib.Path(path).read_text())
    doc["ts"] = doc["ts"] - 10 * 3600
    pathlib.Path(path).write_text(json.dumps(doc))
    assert read_probe_cache(path) is None


def test_probe_verdict_fields():
    from wittgenstein_tpu.profiling.probe import probe_verdict_fields

    f = probe_verdict_fields(_verdict(reason="tpu probe failed (rc=1)"))
    assert f["platform"] == "cpu"
    assert f["attempts"] == 2
    assert f["last_rc"] == 0
    assert f["from_cache"] is False

    f2 = probe_verdict_fields(_verdict(reason="cached probe verdict (cpu)"))
    assert f2["from_cache"] is True


def test_add_probe_metrics(tmp_path):
    from wittgenstein_tpu.profiling.probe import (
        add_probe_metrics,
        write_probe_cache,
    )
    from wittgenstein_tpu.telemetry.export import PromText

    path = str(tmp_path / "probe.json")
    p = PromText("witt")
    add_probe_metrics(p, path)
    assert "witt_probe_cache_present 0" in p.render()

    write_probe_cache(_verdict(), path)
    p = PromText("witt")
    add_probe_metrics(p, path)
    text = p.render()
    assert "witt_probe_cache_present 1" in text
    assert 'witt_probe_platform_verdict{platform="cpu"} 1' in text
    assert "witt_probe_cache_age_seconds" in text


# ---------------------------------------------------------------------------
# profiling.budget
# ---------------------------------------------------------------------------

def test_required_tick_us_arithmetic():
    from wittgenstein_tpu.profiling.budget import required_tick_us

    # 1000 replicas, 1000 ticks/sim, 21 sims/s -> 47.6 µs/tick
    v = required_tick_us(1000, 1000, 21.0)
    assert v == pytest.approx(1000 / (21.0 * 1000) * 1e6)
    with pytest.raises(ValueError):
        required_tick_us(0, 1000)
    with pytest.raises(ValueError):
        required_tick_us(10, -1)


def test_budget_from_parts_and_headroom():
    from wittgenstein_tpu.profiling.budget import budget_from_parts

    hbm = {"model": {"replicas": 144, "bytes_per_replica": 111 << 20}}
    doc = budget_from_parts(
        ticks_per_sim=500.0,
        hbm=hbm,
        measured={"tick_us": 1000.0},
        config={"node_count": 4096},
    )
    assert doc["schema"] == "witt-budget/v1"
    assert doc["replicas_per_chip"] == 144
    expect = 144 / (21.0 * 500.0) * 1e6
    assert doc["required_tick_us"] == pytest.approx(expect, abs=0.01)
    assert doc["headroom_factor"] == pytest.approx(expect / 1000.0, abs=0.001)
    assert "derivation" in doc


def test_load_budget_and_schema_gate(tmp_path):
    from wittgenstein_tpu.profiling.budget import load_budget

    p = tmp_path / "BUDGET.json"
    assert load_budget(path=str(p)) is None
    p.write_text(json.dumps({"schema": "other/v9"}))
    assert load_budget(path=str(p)) is None
    p.write_text(json.dumps({"schema": "witt-budget/v1", "required_tick_us": 5}))
    assert load_budget(path=str(p))["required_tick_us"] == 5


def test_budget_staleness_dates_only():
    from wittgenstein_tpu.profiling.budget import budget_staleness

    floor = {"recorded": "2026-08-05", "node_count": 256}
    assert budget_staleness({"recorded": "2026-08-05"}, floor) is None
    assert budget_staleness({"recorded": "2026-09-01"}, floor) is None
    why = budget_staleness({"recorded": "2026-08-01"}, floor)
    assert why and "predates" in why
    assert budget_staleness({}, floor)  # missing timestamp is stale


def test_committed_budget_artifact_is_fresh():
    """The repo-root BUDGET.json must parse, carry the derivation, and
    not predate BENCH_FLOOR.json (the CI gate, run as a test)."""
    from wittgenstein_tpu.profiling.budget import (
        budget_staleness,
        load_budget,
        required_tick_us,
    )

    budget = load_budget(root=str(REPO_ROOT))
    assert budget is not None, "BUDGET.json missing at repo root"
    assert budget["required_tick_us"] == pytest.approx(
        required_tick_us(
            budget["replicas_per_chip"], budget["ticks_per_sim"]
        ),
        rel=0.01,
    )
    floor_path = REPO_ROOT / "BENCH_FLOOR.json"
    if floor_path.exists():
        floor = json.loads(floor_path.read_text())
        assert budget_staleness(budget, floor) is None


# ---------------------------------------------------------------------------
# run cache counters + per-program accounting
# ---------------------------------------------------------------------------

def test_run_cache_counters_and_metrics():
    from wittgenstein_tpu.engine import replicate_state
    from wittgenstein_tpu.parallel.replica_shard import (
        clear_run_cache,
        run_cache_info,
        run_cache_metrics,
        sharded_run_stats,
    )
    from wittgenstein_tpu.protocols.pingpong_batched import make_pingpong

    clear_run_cache()
    base = run_cache_info()
    assert base["size"] == 0

    net, state = make_pingpong(16)
    states = replicate_state(state, 2)
    sharded_run_stats(net, states, 5)
    after_first = run_cache_info()
    assert after_first["misses"] == base["misses"] + 1
    assert after_first["size"] == 1

    sharded_run_stats(net, states, 5)
    after_second = run_cache_info()
    assert after_second["hits"] == after_first["hits"] + 1
    assert after_second["misses"] == after_first["misses"]

    m = run_cache_metrics()
    assert m["size"] == 1
    entry = m["entries"][0]
    assert entry["sim_ms"] == 5
    assert entry["programs"], "AOT compile should have recorded a program"
    prog = entry["programs"][0]
    assert prog["replicas"] == 2
    assert prog["compile_seconds"] > 0
    # cost/memory may be None on exotic backends but the keys exist
    assert "cost" in prog and "memory" in prog

    # counters survive a cache clear (monotonic, Prometheus-safe)
    clear_run_cache()
    cleared = run_cache_info()
    assert cleared["size"] == 0
    assert cleared["hits"] == after_second["hits"]
    assert cleared["misses"] == after_second["misses"]


# ---------------------------------------------------------------------------
# Supervisor: chunk-time histogram
# ---------------------------------------------------------------------------

def test_chunk_time_histogram():
    from wittgenstein_tpu.runtime.supervisor import (
        CHUNK_HIST_BUCKETS_S,
        chunk_time_histogram,
    )

    h = chunk_time_histogram([0.05, 0.3, 1.5, 100.0, 200.0])
    assert h["count"] == 5
    assert h["sum_s"] == pytest.approx(301.85)
    assert h["max_s"] == 200.0
    # cumulative counts: le=0.1 sees 1, le=2.0 sees 3, +Inf sees all
    assert h["buckets"]["0.1"] == 1
    assert h["buckets"]["2.0"] == 3
    assert h["buckets"]["+Inf"] == 5
    # every declared bucket is present, in Prometheus cumulative form
    for b in CHUNK_HIST_BUCKETS_S:
        assert str(b) in h["buckets"]

    empty = chunk_time_histogram([])
    assert empty["count"] == 0
    assert empty["buckets"]["+Inf"] == 0


def test_supervisor_provenance_histogram_and_spans(tmp_path):
    """A supervised run reports the chunk-time histogram + watchdog
    counter in provenance and emits per-chunk spans into a tracer."""
    import jax.numpy as jnp

    from wittgenstein_tpu.runtime.supervisor import Supervisor
    from wittgenstein_tpu.telemetry.trace import SpanTracer, validate_chrome_trace

    state = {"x": jnp.arange(4, dtype=jnp.int32)}
    tracer = SpanTracer()
    rep = Supervisor(
        lambda s: {"x": s["x"] + 1},
        state,
        n_chunks=3,
        checkpoint_dir=str(tmp_path / "ckpt"),
        tracer=tracer,
    ).run()
    assert rep.ok
    hist = rep.provenance["chunk_time_hist"]
    assert hist["count"] == 3
    assert hist["buckets"]["+Inf"] == 3
    assert rep.provenance["watchdog_timeouts"] == 0
    chunk_spans = [e for e in tracer.events if e.get("name") == "chunk"]
    assert len(chunk_spans) == 3
    assert [e["args"]["chunk"] for e in chunk_spans] == [0, 1, 2]
    assert all(e["args"]["degraded"] is False for e in chunk_spans)
    validate_chrome_trace(tracer.to_json())


# ---------------------------------------------------------------------------
# server /metrics: cost families render without a protocol
# ---------------------------------------------------------------------------

def test_server_metrics_includes_cost_families():
    from wittgenstein_tpu.server.server import Server

    text = Server().metrics_text()
    assert "witt_server_up 1" in text
    # run-cache families render even before any protocol is initialized
    assert "witt_run_cache_size" in text
    assert "witt_run_cache_hits_total" in text
    assert "witt_run_cache_compile_seconds_total" in text
