#!/usr/bin/env python3
"""Device time by `witt.*` scope, for one benchmark configuration's program.

    python3 scripts/scope_profile.py --config benchmark/configs/handel-4096.json --replicas 8 --chunks 2
    python3 scripts/scope_profile.py --config benchmark/configs/casper-1024.json --replicas 1 --chunks 1 --chunk-ms 8000
    python3 scripts/scope_profile.py --config benchmark/configs/dfinity-4096.json --replicas 1 --chunks 1 --chunk-ms 6000
    python3 scripts/scope_profile.py --config benchmark/configs/dfinity-4096-part20.json --replicas 1 --chunks 1 --chunk-ms 6000   # the same executable, the line set: the witt.reach.* rows

Builds the program by the configuration's own factory and parameters,
compiles and warms it through `sharded_run_stats` (one chunk of
`--chunk-ms`, 10 unless given: a jump-loop protocol's chunk is its slot,
so Casper's warm-up is the empty slot 0, its untraced chunk slot 1 and
its traced chunk slot 2, the first with every committee's wave;
Dfinity's chunk is its beacon's 6000-ms cycle, the warm-up the row's
first block, the untraced and the traced chunk two blocks each, with the
role scopes `witt.chain.propose`, `.notarize`, `.beacon` and the
fan-out's `witt.store.fanout` around `witt.store.insert`),
runs `--chunks` chunks untraced and `--chunks` under a profiler trace,
reads the trace with the benchmark's reader (`benchmark/xplane.py`
`read_trace`: leaf ops, self time) and joins every op event's leading
instruction name with `run_cache_op_scopes(net, chunk_ms)`, the table from
instruction to scope that this one compiled program itself carries.

Printed, as one JSON line: per innermost scope and per scope chain
the ms per simulated tick, the share of the ops' summed self time and
the three heaviest instructions; `unscoped` the same way, split by what
feeds it (`fed_by`: the scopes of an unscoped instruction's nearest
scoped producers; XLA:TPU's scatters and their sorts carry no op_name);
the coverage (share under an op with a `witt.*` scope); the
`witt.host.*` spans' totals beside the `bench.*` ones; the run cache's counters across
set-up and across the traced chunks; traced against untraced chunk time.

Scopes are metadata, which JAX leaves out of the persistent compilation
cache's key: a cache hit serves the names the program was compiled
with.  Read scopes after a cold compile (a fresh
JAX_COMPILATION_CACHE_DIR); the document says which it was.

A TPU or no result: without one the script exits 3 and prints nothing.
`--nodes N` is the rehearsal, the one use off the chip: N nodes instead
of the configuration's, host op events standing in for the device's.
The document then says `"rehearsal": true` and the exit code is 4, as
`benchmark/run.py --rehearse` ends: the join is real, the times are the
host's and mean nothing.
`--host-tracer-level N` (1 unless given, as the benchmark traces) raises
the profiler's host tracer level: at 2 or 3 the runtime's own events
(argument checks, buffer allocation, the executable's launch) are on the
host planes, and the document gains `inside_enqueue`: what lies inside
the `witt.host.enqueue` spans, by event name.
`--out DIR` also writes the document (indented) and, gzipped, every op
event's text with its self time beside the whole instruction table, for
a second look without a second run.
"""

from __future__ import annotations

import argparse
import collections
import contextlib
import gzip
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "benchmark")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

CHUNK_MS = 10  # the aggregation cells' chunk: one dissemination period
HOST_PREFIX = "witt.host."
HEAVIEST = 3


def span_totals(spans) -> dict:
    """{span: {"count", "seconds"}} of (name, duration ns) pairs."""
    out: dict = {}
    for name, ns in spans:
        row = out.setdefault(name, {"count": 0, "seconds": 0.0})
        row["count"] += 1
        row["seconds"] += ns / 1e9
    return out


def host_span_totals(path: str) -> dict:
    """`span_totals` of the program's `witt.host.*` TraceAnnotations on
    the host planes (`xplane.read_trace` keeps the benchmark's `bench.*`
    spans only)."""
    from jax.profiler import ProfileData

    return span_totals(
        (e.name, e.duration_ns)
        for plane in ProfileData.from_file(path).planes if plane.name.startswith("/host:")
        for line in plane.lines for e in line.events if e.name.startswith(HOST_PREFIX)
    )


def events_inside(path: str, span: str, heaviest: int = 25) -> dict:
    """What fills a host span: the host planes' events that lie inside an
    event named `span` on the same plane, by name, the `heaviest` by
    seconds ({"count", "seconds"}; an event that encloses another holds
    its time too).  Told apart from the span's own time by `span_totals`."""
    from jax.profiler import ProfileData

    inside = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        events = [
            (e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
            for line in plane.lines for e in line.events
        ]
        spans = sorted((s, t) for name, s, t in events if name == span)
        for name, start, end in events:
            if name != span and any(s <= start and end <= t for s, t in spans):
                inside.append((name, end - start))
    totals = span_totals(inside)
    top = sorted(totals.items(), key=lambda kv: -kv[1]["seconds"])[:heaviest]
    return dict(top)


def profile_rows(times: dict, ticks: int) -> dict:
    """`scope_self_times` as printable rows: ms per tick, share of the
    summed self time, the heaviest instructions of each row."""
    total = times["total_ns"] or 1

    def row(ns, chain=None):
        out = {"ms_per_tick": ns / 1e6 / ticks, "share_pct": 100.0 * ns / total}
        if chain is not None:
            top = collections.Counter(times["instructions"].get(chain, {}))
            out["heaviest"] = [[n, v / 1e6 / ticks] for n, v in top.most_common(HEAVIEST)]
        return out

    by_ns = lambda d: sorted(d.items(), key=lambda kv: -kv[1])  # noqa: E731
    return {
        "scopes": {k: row(v) for k, v in by_ns(times["scopes"])},
        "chains": {k: row(v, k) for k, v in by_ns(times["chains"])},
        "unscoped": row(times["unscoped_ns"]),
        "unscoped_fed_by": {
            k: row(v, "fed_by:" + k) for k, v in by_ns(times["unscoped_fed_by"])
        },
        "coverage_pct": 100.0 * (total - times["unscoped_ns"]) / total,
        "self_ms_per_tick": times["total_ns"] / 1e6 / ticks,
    }


def counter_delta(a: dict, b: dict) -> dict:
    return {k: b[k] - a[k] for k in b if b[k] != a[k]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True, help="benchmark/configs/<name>.json")
    ap.add_argument("--replicas", type=int, required=True)
    ap.add_argument("--chunks", type=int, default=2)
    ap.add_argument("--chunk-ms", type=int, default=CHUNK_MS)
    ap.add_argument("--nodes", type=int,
                    help="rehearsal, with or without a TPU: node count instead of the configuration's; exit 4")
    ap.add_argument("--host-tracer-level", type=int, default=1,
                    help="the profiler's host tracer level (2, 3: the runtime's own events, and `inside_enqueue`)")
    ap.add_argument("--out", help="directory for the document and the rows")
    args = ap.parse_args(argv)

    import tempfile

    import jax

    import cells
    import xplane
    from wittgenstein_tpu.engine import replicate_state
    from wittgenstein_tpu.parallel.replica_shard import (
        run_cache_info,
        run_cache_op_scopes,
        sharded_run_stats,
    )
    from wittgenstein_tpu.profiling.xla_cost import scope_self_times
    from wittgenstein_tpu.utils.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    cache_events = collections.Counter()
    jax.monitoring.register_event_listener(lambda event, **kw: cache_events.update([event]))
    device = jax.devices()[0]
    on_tpu = device.platform == "tpu"
    if not on_tpu:
        if not args.nodes:
            print(f"scope_profile: no TPU (found {device.platform}); "
                  "a rehearsal takes --nodes N", file=sys.stderr)
            return 3
        # what the chip would choose, interpreted (as the benchmark's rehearsal)
        os.environ.setdefault("WITT_BITOPS", "pallas")

    with open(args.config) as f:
        config = json.load(f)
    name = os.path.splitext(os.path.basename(args.config))[0]
    overrides = {"node_count": args.nodes} if args.nodes else None
    params = cells.build_params(config, config["params_class"], overrides)
    net, state = cells.resolve(config["factory"])(params, **config["factory_kwargs"])

    def rows():
        return jax.block_until_ready(replicate_state(state, args.replicas, seeds=range(args.replicas)))

    def chunk(states, annotate=False):
        """One chunk as the benchmark drives it; (out, wall s)."""
        span = jax.profiler.TraceAnnotation if annotate else (lambda name: contextlib.nullcontext())
        t0 = time.perf_counter()
        with span("bench.dispatch"):
            out, stats = sharded_run_stats(net, states, args.chunk_ms)
        with span("bench.block"):
            jax.block_until_ready((out, stats))
        return out, time.perf_counter() - t0

    # set-up: compile (or load) and the first execution, as the benchmark's
    c0 = run_cache_info()
    states, first_call_s = chunk(rows())
    c1 = run_cache_info()
    setup = counter_delta(c0, c1)
    hits = cache_events["/jax/compilation_cache/cache_hits"]
    misses = cache_events["/jax/compilation_cache/cache_misses"]

    untraced = []
    for _ in range(args.chunks):
        states, wall = chunk(states)
        untraced.append(wall)

    trace_dir = tempfile.mkdtemp(prefix="scope-profile-")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = args.host_tracer_level
    traced = []
    c2 = run_cache_info()
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        for _ in range(args.chunks):
            states, wall = chunk(states, annotate=True)
            traced.append(wall)
    finally:
        jax.profiler.stop_trace()
    c3 = run_cache_info()

    path = xplane.find_xplane(trace_dir)
    tr = xplane.read_trace(path, allow_host_ops=not on_tpu)
    (op_scopes,) = run_cache_op_scopes(net, args.chunk_ms).values()  # one R, one placement: one program
    events = [(o.name, o.self_ns) for plane in tr.ops.values() for o in plane]
    times = scope_self_times(events, op_scopes)
    ticks = args.chunks * args.chunk_ms
    bench_spans = span_totals(("bench." + span, end - start) for span, start, end in tr.spans)

    doc = {
        "config": name, "nodes": params.node_count, "replicas": args.replicas,
        "chunks": args.chunks, "chunk_ms": args.chunk_ms, "ticks_traced": ticks,
        "device": {"platform": device.platform, "kind": device.device_kind},
        "rehearsal": bool(args.nodes),
        "compile_cache_dir": cache_dir,
        "compile_was": "cache-hit" if hits and not misses else "cold",
        "setup": {**setup, "first_call_s": first_call_s,
                  "first_call_less_compile_s": first_call_s - setup.get("compile_seconds_total", 0.0)},
        "chunk_wall_s": {"untraced": untraced, "traced": traced},
        "traced_chunks_counters": counter_delta(c2, c3),
        "busy_s": xplane.busy_s(tr), "window_s": tr.window_s,
        "instructions_in_program": len(op_scopes),
        "op_events": len(events),
        **profile_rows(times, ticks),
        "host_spans": {**bench_spans, **host_span_totals(path)},
    }
    if args.host_tracer_level > 1:
        doc["inside_enqueue"] = events_inside(path, HOST_PREFIX + "enqueue")
    print(json.dumps(doc), flush=True)
    if args.out:
        os.makedirs(args.out, exist_ok=True)
        stem = os.path.join(args.out, f"{name}-r{args.replicas}")
        with open(stem + ".json", "w") as f:
            json.dump(doc, f, indent=1)
        text = collections.Counter()
        for event_name, self_ns in events:
            text[event_name] += self_ns
        with gzip.open(stem + ".rows.json.gz", "wt") as f:
            json.dump({"events": text.most_common(), "op_scopes": op_scopes}, f)
    return 4 if args.nodes else 0


if __name__ == "__main__":
    sys.exit(main())
