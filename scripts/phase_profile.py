"""Per-phase tick cost of batched Handel (the per-phase profile table).

Times each tick phase in isolation by scanning it K times, after
advancing the simulation far enough that channels/candidates carry
realistic occupancy.  Runs on the CPU backend by DEFAULT — the numbers
are an op-count proxy for ranking phases, not device times.  Set
WITT_PROFILE_DEVICE=1 to profile on the session's device platform.  The
backend actually used is printed in the table header.

The timing loop is the telemetry span-tracer harness
(wittgenstein_tpu.telemetry.phases — the same one behind bench.py's
--phase-profile); WITT_PROFILE_TRACE=FILE keeps the Chrome trace-event
JSON of the measurement phases.

Usage: python scripts/phase_profile.py [nodes] [replicas]
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

_on_device = os.environ.get("WITT_PROFILE_DEVICE") == "1"
if not _on_device:
    os.environ["JAX_PLATFORMS"] = "cpu"
import jax  # noqa: E402

import bench as benchmod  # noqa: E402
from wittgenstein_tpu.engine import replicate_state  # noqa: E402
from wittgenstein_tpu.protocols.handel_batched import make_handel  # noqa: E402
from wittgenstein_tpu.telemetry import SpanTracer, scan_phase_seconds  # noqa: E402


def main() -> None:
    nodes = int(sys.argv[1]) if len(sys.argv) > 1 else 256
    replicas = int(sys.argv[2]) if len(sys.argv) > 2 else 4
    scans = int(os.environ.get("WITT_PROFILE_SCANS", "50"))

    net, state = make_handel(benchmod._params(nodes))
    states = replicate_state(state, replicas)
    # realistic occupancy: run 120 simulated ms first
    states = net.run_ms_batched(states, 120)
    jax.block_until_ready(states)

    proto = net.protocol
    tracer = SpanTracer(f"phase-profile handel{nodes}x{replicas}")
    # handel-internal phases (this script's table) on the SHARED timing
    # loop — bench --phase-profile times the engine-generic set instead
    def _iso(fn):
        # internal phases consume/produce the int32 compute view; apply
        # the same NARROW_LEAVES widen/narrow boundary the tick wrapper
        # does so the scanned carry keeps the narrow storage dtypes
        def run(s):
            out = fn(net, s._replace(proto=proto.widen_proto(s.proto)))
            return out._replace(proto=proto.narrow_proto(out.proto))

        return run

    phases = {
        "full step": lambda s: net.step(s),
        "channel_deliver": _iso(proto._channel_deliver),
        "commit": _iso(proto._commit),
        "dissemination": _iso(proto._dissemination),
        "select": _iso(proto._select),
    }
    t = scan_phase_seconds(states, phases, scans, tracer)
    full = t["full step"]["mean_s"]
    print(f"\nHandel {nodes}x{replicas}, scan x{scans}, backend={jax.default_backend()}")
    print(f"{'phase':<18} {'ms/iter':>8} {'±std':>6} {'share':>6}")
    for name in phases:
        s = t[name]
        print(
            f"{name:<18} {s['mean_s']*1e3:>8.1f} {s['std_s']*1e3:>6.2f}"
            f" {s['mean_s']/full*100:>5.0f}%"
        )
    trace_path = os.environ.get("WITT_PROFILE_TRACE")
    if trace_path:
        print(f"trace -> {tracer.write(trace_path)}")


if __name__ == "__main__":
    main()
