"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, no children.  The cell's configuration, traffic mix and
per-layer metrics are data files found by the names in BENCHMARK.json
(benchmark/README.md).  The run fails, with no result line, unless JAX
reports a TPU with the chips the cell asks for.  `--rehearse` drives the
same control flow on the CPU at tiny sizes and can only end with
`correct: false` (exit 4).

Set-up (timed as `setup_s`, from process start): imports, the node
population and the rows' states from `--seed`, the chunk program compiled
or loaded through `sharded_run_stats`, one warm-up chunk, its fingerprint.
Window: from fresh t=0 states, `sharded_run_stats` on its own output,
`block_until_ready` after every chunk, until the first chunk boundary at
or after `--seconds`.  After it: peak memory, the checks that decide
`correct` (the invariants and the rows the window left against the
reference at their own width, the warm-up's fingerprint, the twin), and
with `--trace 1` the reduction of the trace of the window's first
chunks.  Earlier lines carry what is worth reading; the last line of
stdout is the result.
"""

from __future__ import annotations

import time

_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import contextlib  # noqa: E402
import hashlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
for _p in (ROOT, BENCH_DIR):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import cells  # noqa: E402
import metrics  # noqa: E402
import timed_rows  # noqa: E402
import twin  # noqa: E402

TRACED_CHUNKS = 2
TICK_MS = 1  # the engine's tick is one simulated millisecond
# a rehearsal cuts the measured rows; the twin is already small enough for a CPU
REHEARSAL = {"params": {"node_count": 64}, "replicas": 2}


def rehearsal_params(config: dict) -> dict:
    """The overrides a rehearsal puts on the configuration's parameters:
    its own `rehearsal.params` where it states them (one with nodes down
    needs its `nodes_down` and threshold cut with the node count)."""
    return config.get("rehearsal", {}).get("params", REHEARSAL["params"])


_CACHE_EVENTS = collections.Counter()


def note(what: str, **fields) -> None:
    print(json.dumps({"note": what, **fields}), flush=True)


def fingerprint(tree):
    """A position-weighted 32-bit checksum of every leaf, computed on the
    device in one small program: one uint32 per leaf.  It is dispatched
    behind a chunk and read after the window, so that the determinism
    check neither reads state back inside the window nor holds a copy of
    it (0.86 GB at 4096 x 8) for the window's length."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    sums = []
    for leaf in jax.tree_util.tree_leaves(tree):
        x = leaf.astype(jnp.uint8) if leaf.dtype == jnp.bool_ else leaf
        bits = lax.bitcast_convert_type(x, jnp.dtype(f"uint{8 * x.dtype.itemsize}"))
        u = bits.astype(jnp.uint32).reshape(-1)
        weight = lax.iota(jnp.uint32, u.size) * jnp.uint32(2654435761) + jnp.uint32(1)
        sums.append(jnp.sum(u * weight, dtype=jnp.uint32))
    return jnp.stack(sums)


def fingerprint_hex(sums) -> str:
    import numpy as np

    return hashlib.sha256(np.asarray(sums).tobytes()).hexdigest()[:32]


class Spans:
    """The benchmark's own spans around the calls into the program: on
    the host clock always, and as TraceAnnotations so that a traced run
    has them on the device events' clock."""

    def __init__(self):
        self.records = []  # (name, start s, end s)

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax

        with jax.profiler.TraceAnnotation("bench." + name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.records.append((name, t0, time.perf_counter()))

    def mean_ms(self, name: str):
        took = [t1 - t0 for n, t0, t1 in self.records if n == name]
        return 1e3 * sum(took) / len(took) if took else None


def device_or_exit(cell, rehearse: bool) -> dict:
    import jax

    devices = jax.devices()
    d = devices[0]
    if not rehearse and d.platform != "tpu":
        sys.exit(f"benchmark: no TPU (jax.devices()[0] is {d.platform}); no result")
    if not rehearse and len(devices) < cell.chips:
        sys.exit(f"benchmark: {cell.name} needs {cell.chips} chips, JAX has {len(devices)}")
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        peaks = json.load(f)
    if not rehearse and d.device_kind not in peaks:
        sys.exit(f"benchmark: {d.device_kind!r} is not in benchmark/peaks.json; no result")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devices)}


def build(cell, seed: int, rehearse: bool):
    """The network and a maker of fresh t=0 rows."""
    from wittgenstein_tpu.engine import replicate_state
    from wittgenstein_tpu.ops.bitops import bitops_backend

    config = cell.config
    params = cells.build_params(
        config, config["params_class"], rehearsal_params(config) if rehearse else None
    )
    net, state = cells.resolve(config["factory"])(params, **config["factory_kwargs"])
    replicas = cell.traffic["replicas"]
    if rehearse:
        replicas = min(replicas, REHEARSAL["replicas"])

    expect = config.get("expect", {})
    seen = {
        "bitops_backend": bitops_backend(),
        "protocol_attrs": {
            k: getattr(net.protocol, k) for k in expect.get("protocol_attrs", {})
        },
    }
    note("program", factory=config["factory"], nodes=params.node_count, replicas=replicas,
         chosen=seen, expected=expect)
    if not rehearse and seen != {"protocol_attrs": {}, **expect}:
        raise RuntimeError(f"the program is not the default one on a TPU: {seen} != {expect}")

    def fresh(start: int):
        return replicate_state(
            state, replicas, seeds=twin.row_seeds(seed, replicas, start)
        )

    return net, fresh, replicas


def run_window(net, fresh, replicas, chunk_ms, seconds, spans, trace_dir, fingerprint_fn):
    """The measured window.  Returns what the checks and the metrics
    need; reads nothing back but the scalar the on_done rule needs."""
    import jax
    from wittgenstein_tpu.parallel.replica_shard import sharded_run_stats

    states = jax.block_until_ready(fresh(0))
    chunks = []  # per chunk: wall_s, stats (device scalars), first (of its rows)
    first, last, row_start, error = None, None, 0, None
    new_rows = True
    tracing = trace_dir is not None
    if tracing:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        t_window = time.perf_counter()
        while True:
            t0 = time.perf_counter()
            try:
                with spans("dispatch"):
                    out, stats = sharded_run_stats(net, states, chunk_ms)
                with spans("block"):
                    jax.block_until_ready((out, stats))
                with spans("readback"):
                    all_done = bool(stats["all_done"])
            except Exception:  # noqa: BLE001 — a failed chunk is counted, not hidden
                error = traceback.format_exc()
                break
            chunks.append(
                {"wall_s": time.perf_counter() - t0, "stats": stats, "first": new_rows}
            )
            if first is None:
                with spans("fingerprint"):  # dispatched, not waited for
                    first = {"fingerprint": fingerprint_fn(out), "time": out.time, "dropped": out.dropped}
            states, last, new_rows = out, out, False
            if tracing and len(chunks) == TRACED_CHUNKS:
                jax.profiler.stop_trace()
                tracing = False
            if all_done:
                with spans("next-rows"):
                    row_start += replicas
                    states, new_rows = jax.block_until_ready(fresh(row_start)), True
            elapsed = time.perf_counter() - t_window
            if metrics.window_is_over(elapsed, seconds):
                break
    finally:
        if tracing:
            jax.profiler.stop_trace()
    return {
        "chunks": chunks,
        "elapsed_s": time.perf_counter() - t_window if error else elapsed,
        "first": first,
        "last": last,  # what the last chunk left: the rows every check reads
        "error": error,
    }


def row_stats(states) -> dict:
    """The averages `sharded_run_stats` returns with a chunk, of rows that
    no chunk has touched: what the first chunk's are held against."""
    import numpy as np

    live = ~np.asarray(states.down)
    n_live = max(1, int(live.sum()))
    return {
        "msg_rcv_avg": float(np.where(live, np.asarray(states.msg_received), 0).sum() / n_live),
        "done_avg": float(np.where(live, np.asarray(states.done_at), 0).sum() / n_live),
    }


def check_invariants(window, chunk_ms: int, compiles_in_window: int, at_start: dict) -> dict:
    """The full-width invariants on the measured states.  Every number is
    printed beside its limit; `broken` lists the chunks that broke one.
    `at_start` is `row_stats` of the t=0 rows, so that a count a protocol
    starts with is not taken for traffic."""
    import numpy as np

    broken, rows = set(), []
    t = prev = None
    for k, chunk in enumerate(window["chunks"]):
        s = {name: float(np.asarray(v)) for name, v in chunk["stats"].items()}
        if chunk["first"]:
            t, prev = 0, at_start
        t += chunk_ms
        ok = (
            s["msg_rcv_avg"] >= prev["msg_rcv_avg"]  # a received count never falls
            and s["done_avg"] >= prev["done_avg"]  # done nodes only increase
            and s["done_max"] <= t  # nobody finished in the future
        )
        rows.append({"chunk": k, "sim_time_ms": t, "msg_rcv_avg": s["msg_rcv_avg"],
                     "msg_rcv_avg_at_least": prev["msg_rcv_avg"], "done_avg": s["done_avg"],
                     "done_avg_at_least": prev["done_avg"], "done_max": s["done_max"],
                     "done_max_at_most": t, "ok": ok})
        if not ok:
            broken.add(k)
        prev = s
    last = window["last"]
    times = np.asarray(last.time).reshape(-1)
    dropped = max(int(np.asarray(last.dropped).max()),
                  int(np.asarray(window["first"]["dropped"]).max()))
    first_time = np.asarray(window["first"]["time"]).reshape(-1)
    grown = sum(r["msg_rcv_avg"] - r["msg_rcv_avg_at_least"] for r in rows)
    whole = {
        "msg_rcv_avg_at_start": at_start["msg_rcv_avg"],
        "msg_rcv_avg_grown_in_window": grown, "msg_rcv_avg_grown_must_exceed": 0,
        "last_chunk_time_ms": sorted(set(times.tolist())), "last_chunk_time_must_be": t,
        "first_chunk_time_ms": sorted(set(first_time.tolist())), "first_chunk_time_must_be": chunk_ms,
        "dropped_max": dropped, "dropped_limit": 0,
        "compiles_in_window": compiles_in_window, "compiles_limit": 0,
    }
    whole["ok"] = bool(
        (times == t).all() and (first_time == chunk_ms).all()
        and dropped == 0 and compiles_in_window == 0 and grown > 0
    )
    return {"chunks": rows, "whole": whole, "broken": sorted(broken),
            "ok": whole["ok"] and not broken}


def reduce_layer_metrics(cell, context: dict) -> dict:
    """Each per-layer metric by its file's reducer.  A reducer that finds
    nothing to read returns None and the metric is left out."""
    from xplane import reduce_device_trace

    def span_mean_ms(m):
        return context["spans"].mean_ms(m["span"])

    def counter_delta(m):
        a, b = context["counters"][m["over"]]
        if m["counter"] not in a or m["counter"] not in b:
            return None  # a program without that counter: a parent, under a later PR's file
        return b[m["counter"]] - a[m["counter"]]

    def memory_stat_gb(m):
        value = (context["memory_stats"] or {}).get(m["key"])
        return None if value is None else value / 1e9

    def device_trace(m):
        return reduce_device_trace(m, context["trace"], context["ticks_traced"])

    reducers = {f.__name__: f for f in (span_mean_ms, counter_delta, memory_stat_gb, device_trace)}
    out = {}
    for m in cell.layer_metrics:
        value = reducers[m["reducer"]](m)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def set_up(cell, seed: int, rehearse: bool) -> dict:
    """Everything before the window: the network and its rows, the chunk
    program compiled or loaded by its first call, which is also the
    warm-up chunk, and that chunk's fingerprint."""
    import jax
    from wittgenstein_tpu.parallel.replica_shard import run_cache_info, sharded_run_stats

    chunk_ms = int(cell.traffic["chunk_ms"])
    t0 = time.perf_counter()
    net, fresh, replicas = build(cell, seed, rehearse)
    build_s = time.perf_counter() - t0
    counters = [run_cache_info()]
    events = collections.Counter(_CACHE_EVENTS)
    t0 = time.perf_counter()
    rows = fresh(0)
    timed_rows.named_leaves(cell.config, rows)  # a leaf that is not there: an error now
    at_start = row_stats(rows)
    warm, warm_stats = sharded_run_stats(net, rows, chunk_ms)
    del rows
    jax.block_until_ready((warm, warm_stats))
    bool(warm_stats["all_done"])
    first_call_s = time.perf_counter() - t0
    counters.append(run_cache_info())
    events = _CACHE_EVENTS - events  # the persistent cache's part in that one call
    t0 = time.perf_counter()
    fingerprint_fn = jax.jit(fingerprint)
    warm_fingerprint = fingerprint_hex(fingerprint_fn(warm))
    state_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(warm))
    fingerprint_s = time.perf_counter() - t0
    compile_s = counters[1]["compile_seconds_total"] - counters[0]["compile_seconds_total"]
    hits = events["/jax/compilation_cache/cache_hits"]
    misses = events["/jax/compilation_cache/cache_misses"]
    note("setup", build_s=build_s, compile_s=compile_s,
         compile_was="cache-hit" if hits and not misses else "cold",
         persistent_cache_hits=hits, persistent_cache_misses=misses,
         warmup_chunk_s=first_call_s - compile_s, fingerprint_s=fingerprint_s,
         state_bytes=state_bytes, warmup_fingerprint=warm_fingerprint)
    return {"net": net, "fresh": fresh, "replicas": replicas, "chunk_ms": chunk_ms,
            "fingerprint_fn": fingerprint_fn, "warm_fingerprint": warm_fingerprint,
            "counters": counters, "at_start": at_start}


def judge(cell, seed: int, window: dict, setup: dict, compiles_in_window: int, rehearse: bool):
    """`correct` and `failed`: the full-width invariants, the rows the
    window left against the reference at their own width, the
    determinism of the first window chunk against the warm-up chunk, and
    the twin against the reference.  All outside the window."""
    inv = check_invariants(window, setup["chunk_ms"], compiles_in_window, setup["at_start"])
    note("invariants-chunks", **{k: [row[k] for row in inv["chunks"]] for k in inv["chunks"][0]})
    note("invariants", **inv["whole"], broken_chunks=inv["broken"])
    first = fingerprint_hex(window["first"]["fingerprint"])
    deterministic = first == setup["warm_fingerprint"]
    note("determinism", first_window_chunk_fingerprint=first,
         must_equal=setup["warm_fingerprint"], ok=deterministic)
    counts = timed_rows.program_counts(window["last"], cell.config)
    window["last"] = None  # free the rows before the reference and the twin
    rows = timed_rows.check(cell.config, twin.row_seeds(seed, 1)[0], counts,
                            rehearsal_params(cell.config) if rehearse else None)
    note("timed-rows", **rows, replicas=setup["replicas"])
    t0 = time.perf_counter()
    fidelity = twin.check(cell.config, seed, batch=setup["replicas"])
    tw = cell.config["twin"]
    note("twin", **fidelity, nodes=tw["params"]["node_count"],
         horizon_ms=tw["horizon_ms"], seconds=time.perf_counter() - t0)
    failed = len(inv["broken"]) + (1 if window["error"] else 0)
    if not (inv["whole"]["ok"] and rows["ok"]) and not failed:
        failed = 1
    correct = bool(inv["ok"] and rows["ok"] and deterministic and fidelity["ok"]
                   and not window["error"])
    return correct, failed


def traced_metrics(cell, trace_dir, rehearse: bool, context: dict, device: dict):
    """The per-layer metrics and the breakdown of a traced run; adds
    `busy_s` and `window_s` to `device`.  The trace goes with the run,
    unless BENCH_KEEP_TRACE is set: then its path is on a note, for
    `python3 benchmark/xplane.py <path>` to describe by hand."""
    import shutil

    import xplane

    try:
        tr = xplane.read_trace(xplane.find_xplane(trace_dir), allow_host_ops=rehearse)
    finally:
        if os.environ.get("BENCH_KEEP_TRACE"):
            note("trace-kept", path=trace_dir)
        else:
            shutil.rmtree(trace_dir, ignore_errors=True)
    result = reduce_layer_metrics(cell, {**context, "trace": tr})
    device["busy_s"] = xplane.busy_s(tr)
    device["window_s"] = tr.window_s
    note("trace", ticks_traced=context["ticks_traced"], device_planes=sorted(tr.ops),
         op_events=sum(len(v) for v in tr.ops.values()), spans=len(tr.spans),
         busy_s=device["busy_s"], window_s=device["window_s"],
         module_busy_s=xplane.module_busy_s(tr),
         matched={m["name"]: xplane.top_matching(tr, m["regex"])
                  for m in cell.layer_metrics if m.get("regex")})
    return result, {"device_ops": xplane.top_ops(tr, 10), "idle_gaps": xplane.idle_gaps(tr, 5)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    cell = cells.load_cell(args.workload)
    if args.rehearse:
        # what the chip would choose, interpreted (as chip_smoke.py's rehearsal)
        os.environ.setdefault("WITT_BITOPS", "pallas")
    device = device_or_exit(cell, args.rehearse)

    import jax
    from wittgenstein_tpu.parallel.replica_shard import run_cache_info
    from wittgenstein_tpu.utils.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    jax.monitoring.register_event_listener(lambda event, **kw: _CACHE_EVENTS.update([event]))
    note("device", **device, bytes_limit=(jax.devices()[0].memory_stats() or {}).get("bytes_limit"),
         jax=jax.__version__, compile_cache_dir=cache_dir, rehearse=args.rehearse,
         workload=cell.name, config=cell.config_name, traffic=cell.traffic_name, seed=args.seed)

    setup = set_up(cell, args.seed, args.rehearse)
    replicas, chunk_ms = setup["replicas"], setup["chunk_ms"]
    spans = Spans()
    trace_dir = None
    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    counters = {"setup": setup["counters"], "window": [run_cache_info()]}
    setup_s = time.perf_counter() - _PROCESS_START
    window = run_window(setup["net"], setup["fresh"], replicas, chunk_ms, args.seconds, spans,
                        trace_dir, setup["fingerprint_fn"])
    counters["window"].append(run_cache_info())
    memory_stats = [d.memory_stats() or {} for d in jax.devices()[: cell.chips]]
    fullest = max(memory_stats, key=lambda m: m.get("peak_bytes_in_use", 0))

    chunks = window["chunks"]
    attempted = len(chunks) + (1 if window["error"] else 0)
    if window["error"]:
        note("chunk-raised", chunk=len(chunks), traceback=window["error"])
    walls = [c["wall_s"] for c in chunks]
    note("window", chunks=len(chunks), samples=len(walls), elapsed_s=window["elapsed_s"],
         chunk_wall_s=walls, replicas=replicas, chunk_ms=chunk_ms,
         peak_bytes_in_use=fullest.get("peak_bytes_in_use"), bytes_limit=fullest.get("bytes_limit"))

    correct, failed = False, attempted
    result_metrics, breakdown = {}, None
    if chunks:
        compiles = counters["window"][1]["compiles"] - counters["window"][0]["compiles"]
        correct, failed = judge(cell, args.seed, window, setup, compiles, args.rehearse)
        if args.trace:
            context = {"spans": spans, "counters": counters, "memory_stats": fullest,
                       "ticks_traced": min(TRACED_CHUNKS, len(chunks)) * chunk_ms // TICK_MS}
            result_metrics, breakdown = traced_metrics(
                cell, trace_dir, args.rehearse, context, device)
        else:
            values = {
                "sim_ms_per_s": metrics.sim_ms_per_s(replicas, chunk_ms, len(chunks), window["elapsed_s"]),
                "chunk_p95_ms": 1e3 * metrics.nearest_rank(walls, 0.95),
                "setup_s": setup_s,
            }
            result_metrics = {
                m["name"]: {"value": values[m["name"]], "unit": m["unit"]} for m in cell.end_to_end
            }

    device["memory_peak_bytes"] = fullest.get("peak_bytes_in_use")
    result = {
        "correct": correct and not args.rehearse,
        "attempted": attempted,
        "failed": failed,
        "metrics": result_metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    if args.rehearse:
        note("rehearsal", passed=correct)
    print(json.dumps(result), flush=True)
    return 4 if args.rehearse else 0


if __name__ == "__main__":
    sys.exit(main())
