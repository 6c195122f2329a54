"""Telemetry: device-side counters, progress time-series, host exports.

The observability spine of the TPU rebuild (the StatsHelper/wserver
capability of the reference, SURVEY §L5, captured *inside* jit):

  state.py   TelemetryConfig + TelemetryState — the in-graph counter
             side-car threaded through the engine's send/deliver/jump
             sites, plus the on-device progress-snapshot ring.  Static
             enable: a disabled engine compiles the exact
             pre-telemetry program.
  export.py  host layer — counter summaries, Prometheus text
             exposition, JSONL run records, snapshot-ring decoding
             (progress curves / done-at CDFs in one transfer).
  trace.py   SpanTracer — Chrome trace-event JSON for host phases
             (probe/compile/chunks); complements tools/profiling.py's
             device-level jax.profiler traces.

Enable on any engine:

    from wittgenstein_tpu.telemetry import TelemetryConfig
    net = BatchedNetwork(proto, latency, n,
                         telemetry=TelemetryConfig(snapshots=128,
                                                   snapshot_every_ms=10))
    out = net.run_ms(state, 1000)
    summary = counters(net, out)          # dict for BENCH/JSONL records
    text = prometheus_from_counters(summary)   # /metrics payload
    series = progress_series(out)              # time/done/pending curve

See docs/telemetry.md for the counter catalog and overhead notes.
"""

from .export import (
    PromText,
    RunRecordWriter,
    counters,
    done_counts_at,
    pending_count,
    progress_series,
    prometheus_from_counters,
    read_run_records,
)
from .state import TelemetryConfig, TelemetryState, init_telemetry
from .trace import SpanTracer, maybe_span, validate_chrome_trace

__all__ = [
    "PromText",
    "RunRecordWriter",
    "SpanTracer",
    "TelemetryConfig",
    "TelemetryState",
    "counters",
    "done_counts_at",
    "init_telemetry",
    "maybe_span",
    "pending_count",
    "progress_series",
    "prometheus_from_counters",
    "read_run_records",
    "validate_chrome_trace",
]
