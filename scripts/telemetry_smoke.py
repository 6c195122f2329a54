"""Telemetry smoke: one short instrumented sim through every export tier.

Runs an instrumented PingPong simulation (in-graph counters + snapshot
ring), then exercises the whole export surface — counter summary, store
invariant, Prometheus text, progress series, Chrome trace, JSONL run
record — and FAILS LOUDLY on any inconsistency.  CI runs this as the
tier-1 telemetry step and uploads the output directory as a build
artifact, so every green build carries a machine-readable run record.

Usage: python scripts/telemetry_smoke.py [out_dir]   (default ./telemetry_smoke)
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax  # noqa: E402

from wittgenstein_tpu.protocols.pingpong_batched import make_pingpong  # noqa: E402
from wittgenstein_tpu.telemetry import (  # noqa: E402
    RunRecordWriter,
    SpanTracer,
    TelemetryConfig,
    counters,
    done_counts_at,
    progress_series,
    prometheus_from_counters,
    read_run_records,
    validate_chrome_trace,
)

SIM_MS = 400
NODES = 200


def main() -> int:
    out_dir = sys.argv[1] if len(sys.argv) > 1 else os.path.join(ROOT, "telemetry_smoke")
    os.makedirs(out_dir, exist_ok=True)
    tracer = SpanTracer("telemetry-smoke")

    with tracer.span("build", nodes=NODES):
        cfg = TelemetryConfig(snapshots=64, snapshot_every_ms=10)
        net, state = make_pingpong(NODES, telemetry=cfg)
    with tracer.span("run", sim_ms=SIM_MS):
        out = net.run_ms(state, SIM_MS)
        jax.block_until_ready(out)

    # counter summary + the store invariant
    c = counters(net, out)
    s = c["store"]
    lhs = sum(s["sent"])
    rhs = sum(s["delivered"]) + sum(s["discarded"]) + sum(s["dropped"]) + s["pending"]
    assert lhs == rhs, f"store invariant broken: sent={lhs} != {rhs}"
    assert c["node"]["msg_received"] > 0, "no traffic delivered?"
    assert c["loop"]["ticks"] > 0

    # progress series decodes and is monotone in time and delivered
    series = progress_series(out)
    assert len(series) > 2, f"snapshot ring empty: {series}"
    times = [r["time"] for r in series]
    assert times == sorted(times)
    deliv = [r["delivered"] for r in series]
    assert deliv == sorted(deliv), "cumulative delivered must be monotone"
    assert done_counts_at(series, [SIM_MS])[0] >= 0

    # Prometheus text
    prom = prometheus_from_counters(c)
    assert "witt_messages_sent_total" in prom
    with open(os.path.join(out_dir, "metrics.prom"), "w") as f:
        f.write(prom)

    # Chrome trace
    trace_path = tracer.write(os.path.join(out_dir, "trace.json"))
    validate_chrome_trace(json.load(open(trace_path)))

    # JSONL run record round-trip
    rec_path = os.path.join(out_dir, "run_records.jsonl")
    written = RunRecordWriter(rec_path).write(
        {"kind": "telemetry_smoke", "counters": c, "progress": series},
        sim_ms=SIM_MS,
        nodes=NODES,
    )
    back = read_run_records(rec_path)[-1]
    assert back == json.loads(json.dumps(written)), "run record round-trip"

    print(
        json.dumps(
            {
                "ok": True,
                "out_dir": out_dir,
                "ticks": c["loop"]["ticks"],
                "jumps": c["loop"]["jumps"],
                "sent": lhs,
                "snapshots": len(series),
            }
        )
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
