"""Benchmark: batched Handel aggregation throughput vs the oracle DES.

Prints ONE JSON line with at least {"metric", "value", "unit",
"vs_baseline"}, plus the device it ran on so a CPU number can never
masquerade as a TPU number:

  "platform":      the backend that actually ran ("tpu" / "cpu"),
  "device_kind":   e.g. "TPU v5 lite",
  "config":        node_count / n_replicas / sim_ms actually run,
  "compile_s", "run_s": wall-clock split.

Without a TPU it exits non-zero; WITT_BENCH_PLATFORM=cpu asks for the
CPU deliberately (256 nodes, metric named ..._cpu).  Everything runs in
this one process — a chip belongs to one process at a time.

Flagship config per BASELINE.json: Handel BLS aggregation, 4096 nodes
(0% Byzantine for the headline number), NetworkLatencyByDistanceWJitter.
One "sim" = 1000 simulated ms of the full protocol — all nodes reach the
99% threshold well within that horizon.  The baseline is the single-thread
oracle DES (this repo's exact-semantics port of the reference's Java event
loop) running the identical configuration once; vs_baseline is the
speedup: batched sims/sec divided by oracle sims/sec.

Execution is CHUNKED (one fixed CHUNK_MS program per config, AOT-compiled
once, host sync between chunks).  Budget enforcement is a rolling check
BETWEEN chunks (a partial pass returns a "too_slow" record instead of a
result).  The ladder climbs replicas cheap-first so a number exists
early; every measured rung is recorded in the output under "rungs" (the
replica-scaling curve).

Env knobs:
  WITT_BENCH_PLATFORM=cpu      measure the CPU on purpose
  WITT_BENCH_REPLICAS=N        pin the replica ladder to one value
  WITT_BENCH_BUDGET_S=N        total measurement budget (default 1500)
  WITT_BENCH_CHUNK_MS=N        the per-device-call chunk (default 20;
                               one XLA program per config — no adaptive
                               second compile)
  WITT_BENCH_PROFILE=DIR       capture a jax.profiler trace of the timed run
  WITT_BENCH_TRACE=FILE        write a Chrome trace-event JSON of the host
                               phases (compile / timed pass, or the
                               --phase-profile measurements) via the
                               telemetry span tracer
  WITT_BENCH_RUNRECORD=FILE    append the final BENCH record to a JSONL
                               run-record file (telemetry.RunRecordWriter)
"""

from __future__ import annotations

import json
import os
import sys
import time

SIM_MS = 1000
# 20-tick chunks, host sync between chunks: the budget check and the
# heartbeat run between device calls, never inside one
CHUNK_MS = int(os.environ.get("WITT_BENCH_CHUNK_MS", "20"))
if CHUNK_MS <= 0 or SIM_MS % CHUNK_MS != 0:
    raise SystemExit(
        f"WITT_BENCH_CHUNK_MS={CHUNK_MS} must be a positive divisor of {SIM_MS}"
    )
def _params(node_ct: int):
    # ONE definition of the flagship config, shared with the ablation
    # matrix and budget_report (profiling.ablation.flagship_params)
    from wittgenstein_tpu.profiling import flagship_params

    return flagship_params(node_ct)


def bench_oracle(node_ct: int) -> float:
    from wittgenstein_tpu.protocols.handel import Handel

    p = Handel(_params(node_ct))
    p.init()
    t0 = time.perf_counter()
    p.network().run_ms(SIM_MS)
    dt = time.perf_counter() - t0
    assert all(n.done_at > 0 for n in p.network().live_nodes()), "oracle not done"
    return 1.0 / dt


def _setup_cache() -> None:
    from wittgenstein_tpu.utils.compile_cache import use_compile_cache

    use_compile_cache()


def chunked_pass(
    compiled,
    states,
    n_chunks,
    budget_s,
    heartbeat=None,
    checkpoint_dir=None,
    run_key=None,
    run_meta=None,
    chunk_ms=None,
    checkpoint_every=1,
    tracer=None,
    on_report=None,
    ctx=None,
    recorder=None,
):
    """One budgeted chunked pass over an AOT executable (bench ladder +
    scripts/tpu_campaign.py both use it).  A thin wrapper over
    runtime.Supervisor: the sync-smallest-leaf readback after every chunk
    and the between-chunks budget abort live there, and passing
    `checkpoint_dir` makes the pass RESUMABLE — a re-invocation with the
    same dir + run_key picks up at the last completed chunk.  Aborts
    BETWEEN chunks when the rolling elapsed time exceeds budget_s;
    `heartbeat(i, chunk_s)` is called after every chunk.  Returns
    (out, times, ok) — `times` covers this invocation's chunks only.
    `tracer` (a telemetry SpanTracer) records per-chunk spans and
    retry/degrade instants; `on_report(RunReport)` hands the caller the
    full report — provenance carries the per-chunk wall-time histogram
    and retry counters (ISSUE-7d).

    `compiled` may be jitted with donate_argnums — the supervisor only
    ever feeds each chunk's OUTPUT to the next chunk, so donation is
    safe here and saves a full state copy per chunk.  Callers that reuse
    `states` after the pass must hand in a disposable copy (see
    _fresh_states)."""
    from wittgenstein_tpu.runtime import RetryPolicy, Supervisor

    sup = Supervisor(
        compiled,
        states,
        n_chunks=n_chunks,
        chunk_ms=chunk_ms or CHUNK_MS,
        checkpoint_dir=checkpoint_dir,
        checkpoint_every=checkpoint_every,
        retry=RetryPolicy(max_attempts=1),  # bench fails fast; the
        # ladder's parent decides whether a rung is worth retrying
        run_key=run_key,
        run_meta=run_meta,
        heartbeat=heartbeat,
        budget_s=budget_s,
        consume_template=True,
        tracer=tracer,
        # obs spine: the bench-entry TraceContext rides the supervisor's
        # flight-recorder events and checkpoint manifests too
        ctx=ctx,
        recorder=recorder,
    )
    rep = sup.run()
    if on_report is not None:
        on_report(rep)
    return rep.state, [round(t, 2) for t in rep.chunk_seconds], rep.ok


def bench_batched(node_ct: int, n_replicas: int, budget_s: float = 1e9) -> dict:
    """One measured config, SELF-BUDGETING.  ONE XLA program per config
    (chunk CHUNK_MS, AOT-compiled once and reused for every chunk): an
    early-window probe would underestimate per-tick cost (the empty-ms
    jump makes the first simulated ms nearly free).  The budget is
    enforced with rolling checks BETWEEN chunks — a partial pass returns
    {"too_slow", "per_tick_ms", "projected_s", "chunks_done"} so the
    ladder can stop with data in hand."""
    import jax

    from wittgenstein_tpu.engine import replicate_state
    from wittgenstein_tpu.protocols.handel_batched import make_handel

    _setup_cache()

    # production config: fused delivery+tick (bit-identical to the
    # per-phase path — tests/test_step_fusion.py — and measured ~3%
    # cheaper on the real chunked workload; the profiling paths keep the
    # unfused engine for per-phase attribution).  score_cache stays at
    # its backend-auto default (on-TPU only — see make_handel).
    net, state = make_handel(_params(node_ct), fuse_step=True)
    states = replicate_state(state, n_replicas)

    chunk_ms = CHUNK_MS
    n_chunks = max(1, SIM_MS // chunk_ms)
    # stop_when_done: once every replica's aggregation completed, later
    # chunks exit their lockstep loop immediately — the DES-quiescence
    # analog; the deliverable (time-to-aggregation CDF) is decided by then.
    # donate_argnums: each chunk consumes its input buffers in place —
    # the 20-tick readback-synced chunks stop round-tripping a full state
    # copy per chunk (chunked_pass only ever feeds outputs forward)
    run = jax.jit(
        lambda s: net.run_ms_batched(s, chunk_ms, True), donate_argnums=(0,)
    )
    t0 = time.perf_counter()
    compiled = run.lower(states).compile()
    compile_s = time.perf_counter() - t0

    def _fresh_states():
        # donation consumes the pass's input: hand each pass its own copy
        # (one copy per PASS instead of the one per CHUNK donation saves)
        import jax.numpy as jnp

        return jax.tree_util.tree_map(jnp.copy, states)

    def run_chunked(st, budget, **kw):
        return chunked_pass(compiled, st, n_chunks, budget, **kw)

    def _partial(times):
        per_tick_s = sum(times) / (len(times) * chunk_ms)
        return {
            "too_slow": True,
            "per_tick_ms": round(per_tick_s * 1e3, 2),
            "projected_s": round(per_tick_s * SIM_MS, 1),
            "compile_s": round(compile_s, 1),
            "chunks_done": len(times),
        }

    pass_budget = max(30.0, (budget_s - compile_s) / 2)  # warm + timed
    t0 = time.perf_counter()
    out, warm_times, ok = run_chunked(_fresh_states(), pass_budget)
    if not ok:
        return _partial(warm_times)
    assert int(out.done_at.min()) > 0, "sim did not converge"
    assert int(out.dropped.max()) == 0, "message ring overflow"

    import contextlib

    from wittgenstein_tpu.obs import mint_context
    from wittgenstein_tpu.telemetry import SpanTracer, counters
    from wittgenstein_tpu.tools.profiling import trace

    # bench entry is a run_id mint point (the serve path's counterpart
    # is job admission): the ctx correlates the span trace, the timed
    # pass's flight-recorder events, and the emitted record
    ctx = mint_context("bench")
    # host-phase span trace (compile is already gone by the timed pass;
    # chunks are spanned from the heartbeat timings chunked_pass reports)
    tracer = SpanTracer(f"bench handel{node_ct}x{n_replicas}", ctx=ctx)
    tracer.add_span("compile", 0.0, compile_s * 1e6, nodes=node_ct)

    profile_dir = os.environ.get("WITT_BENCH_PROFILE")
    reports = []
    with trace(profile_dir) if profile_dir else contextlib.nullcontext():
        t0 = time.perf_counter()
        with tracer.span("timed_pass", replicas=n_replicas):
            out, chunk_times, ok = run_chunked(
                _fresh_states(), pass_budget,
                tracer=tracer, on_report=reports.append, ctx=ctx,
            )
        run_s = time.perf_counter() - t0
    if not ok:
        return _partial(chunk_times)
    trace_path = os.environ.get("WITT_BENCH_TRACE")
    if trace_path:
        tracer.write(trace_path)
    return {
        "run_id": ctx.run_id,
        "sims_per_sec": n_replicas / run_s,
        "compile_s": round(compile_s, 1),
        "run_s": round(run_s, 3),
        "chunk_ms": chunk_ms,
        # supervisor provenance of the timed pass: per-chunk wall-time
        # histogram + retry/degrade counters (ISSUE-7d)
        "supervisor": reports[-1].provenance if reports else None,
        # telemetry counter summary of the measured final state (node +
        # store tiers; the in-graph tier stays off — the headline must
        # measure the uninstrumented program)
        "counters": counters(net, out),
    }


def phase_profile(
    node_ct: int = 256,
    n_replicas: int = 2,
    scans: int = 25,
    trace_path: "str | None" = None,
    ablate: bool = True,
    repeats: int = 3,
    ablation_levers: "list | None" = None,
) -> dict:
    """Per-phase tick cost + wheel occupancy high-water marks + the
    config-ablation lever report, reported into the BENCH json so
    future rounds can see where ticks go.

    Three probes:
      * handel (the bench rung): each tick phase — delivery, emission
        apply, protocol tick, beat — scanned `scans` times in isolation
        (phases overlap by construction: delivery is part of the full
        step, so shares are an op-cost ranking, not a partition);
      * pingpong at 1x and 8x ring capacity: the same delivery phase —
        with the time wheel its cost tracks the VIEW (window*B + V), not
        the total capacity C, and the two numbers should be ~equal;
      * the ablation matrix (profiling.ablation, `ablate=True`): full
        steps of channel_depth_8 / boundary_view_off / pre_r5 / wheel /
        telemetry_on / faults_on / annotations_off vs base, ranked by
        per-tick delta — the r4→r5 regression attributed to named
        levers, and the named-scope annotation overhead bound.
    Occupancy high-water (wheel row fill / overflow lane census) comes
    from the engine's instrumented run (run_ms_occupancy).

    The timing loop is the telemetry span-tracer harness
    (telemetry.phases — shared with scripts/phase_profile.py),
    warmup-discarded with per-phase mean+stddev; pass trace_path to
    keep the Chrome-trace JSON of the measurement."""
    import jax

    from wittgenstein_tpu.engine import replicate_state
    from wittgenstein_tpu.protocols.handel_batched import make_handel
    from wittgenstein_tpu.protocols.pingpong_batched import make_pingpong
    from wittgenstein_tpu.telemetry import (
        SpanTracer,
        engine_phase_fns,
        scan_phase_seconds,
    )

    _setup_cache()
    tracer = SpanTracer("phase-profile")

    net, state = make_handel(_params(node_ct))
    states = replicate_state(state, n_replicas)
    states = net.run_ms_batched(states, 120)  # realistic channel occupancy
    jax.block_until_ready(states)
    stats = scan_phase_seconds(states, engine_phase_fns(net), scans, tracer)
    t = {k: v["mean_s"] for k, v in stats.items()}
    r3 = lambda x: round(x * 1e3, 3)
    phases = {
        "full_step_ms": r3(t["full_step"]),
        "delivery_ms": r3(t["delivery"]),
        "emission_apply_ms": r3(max(0.0, t["deliver_apply"] - t["delivery"])),
        "protocol_tick_ms": r3(t["protocol_tick"]),
        "beat_ms": r3(t["beat"]),
        "stddev_ms": {k: r3(v["std_s"]) for k, v in stats.items()},
    }
    _, occ = net.run_ms_occupancy(state, 300)
    occupancy = {k: int(v) for k, v in occ.items()}

    # delivery-vs-capacity scaling witness (pingpong uses the wheel)
    scaling = []
    for mult in (1, 8):
        pnet, pstate = make_pingpong(1000, capacity=(2 * 1000 + 64) * mult)
        pstate = pnet.run_ms(pstate, 150)  # mid-flight in-flight load
        pstates = replicate_state(pstate, n_replicas)
        dt = scan_phase_seconds(
            pstates, {"delivery": pnet._phase_deliver}, scans, tracer
        )["delivery"]["mean_s"]
        pn, pocc = pnet.run_ms_occupancy(pstate, 150)
        scaling.append(
            {
                "capacity": pnet.capacity,
                "view_rows": pnet._window() * pnet.wheel_slots
                + pnet.overflow_capacity,
                "delivery_ms": r3(dt),
                "wheel_fill_hwm": int(pocc["wheel_fill_hwm"]),
                "overflow_hwm": int(pocc["overflow_hwm"]),
            }
        )
    # jump lever (ISSUE 18): pingpong declares TICK_INTERVAL=None, so
    # the batched consensus-jump gate applies.  Two readings: a `jump`
    # phase row — one next-arrival jump step beside one plain step in
    # the same scan harness (op-cost ranking, like every phase row) —
    # and a paired INTERLEAVED off/on wall of the identical batched
    # chunk (the PR-11 noise discipline), with the armed run's
    # skipped-ms census.  Pingpong at n=1000 post-warmup is the
    # neutral-traffic case: the frac reports how much dead time even a
    # dense schedule carries, and the wall pair prices the gate itself.
    import jax.numpy as jnp

    from wittgenstein_tpu.telemetry import counters as _tele_counters
    from wittgenstein_tpu.telemetry.state import TelemetryConfig

    jnet, jstate = make_pingpong(1000)
    jnet, jstate = jnet.with_telemetry(jstate, TelemetryConfig())
    jstate = jnet.run_ms(jstate, 150)
    jstates = replicate_state(jstate, n_replicas)
    jstats = scan_phase_seconds(
        jstates,
        {
            "step": jnet.step,
            "jump": lambda s: jnet._step_jump(s, s.time + jnp.int32(1 << 20)),
        },
        scans,
        tracer,
    )
    off_run = jax.jit(lambda s: jnet.run_ms_batched(s, 200))
    on_net = jnet.with_batched_jumps(True)
    on_run = jax.jit(lambda s: on_net.run_ms_batched(s, 200))
    jax.block_until_ready(off_run(jstates))  # compile + warm both
    out_on = jax.block_until_ready(on_run(jstates))
    offs, ons = [], []
    for r in range(max(1, repeats)):
        with tracer.span("jump-ab-off", repeat=r):
            t0 = time.perf_counter()
            jax.block_until_ready(off_run(jstates))
            offs.append(time.perf_counter() - t0)
        with tracer.span("jump-ab-on", repeat=r):
            t0 = time.perf_counter()
            out_on = jax.block_until_ready(on_run(jstates))
            ons.append(time.perf_counter() - t0)
    jump = {
        "step_ms": r3(jstats["step"]["mean_s"]),
        "jump_ms": r3(jstats["jump"]["mean_s"]),
        "paired_wall_s": {
            "off": [round(x, 3) for x in offs],
            "on": [round(x, 3) for x in ons],
        },
        "speedup": round(min(offs) / max(min(ons), 1e-9), 3),
        "jumped_ms_frac": _tele_counters(on_net, out_on)["loop"][
            "jumped_ms_frac"
        ],
    }
    ablation = None
    if ablate:
        from wittgenstein_tpu.profiling import ablation_matrix, lever_report

        matrix = ablation_matrix(
            node_ct,
            n_replicas,
            scans=scans,
            repeats=repeats,
            levers=ablation_levers,
            tracer=tracer,
        )
        ablation = {"matrix": matrix, "report": lever_report(matrix)}
    if trace_path:
        tracer.write(trace_path)
    return {
        "config": {"node_count": node_ct, "n_replicas": n_replicas, "scans": scans},
        "backend": jax.default_backend(),
        "handel_phases": phases,
        "handel_occupancy": occupancy,
        "pingpong_delivery_vs_capacity": scaling,
        "jump": jump,
        "ablation": ablation,
    }


def overhead_check(
    node_ct: int = 256, n_replicas: int = 4, repeats: int = 3
) -> dict:
    """Supervisor overhead on the CPU ladder rung: the same compiled
    chunk schedule run (a) as a bare python loop with the readback sync
    and (b) through chunked_pass/Supervisor.  min-of-repeats on both
    sides; the supervised loop must stay within 2% of raw (the ISSUE-6
    acceptance bound — the floor check guards it continuously)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from wittgenstein_tpu.engine import replicate_state
    from wittgenstein_tpu.protocols.handel_batched import make_handel

    _setup_cache()
    # same production config as bench_batched (fused) — the overhead
    # bound compares supervision, not engine variants
    net, state = make_handel(_params(node_ct), fuse_step=True)
    states = replicate_state(state, n_replicas)
    chunk_ms = CHUNK_MS
    n_chunks = max(1, SIM_MS // chunk_ms)
    run = jax.jit(
        lambda s: net.run_ms_batched(s, chunk_ms, True), donate_argnums=(0,)
    )
    compiled = run.lower(states).compile()

    def fresh():
        return jax.tree_util.tree_map(jnp.copy, states)

    def raw_pass() -> float:
        st = fresh()
        t0 = time.perf_counter()
        for _ in range(n_chunks):
            st = compiled(st)
            leaves = jax.tree_util.tree_leaves(st)
            np.asarray(min(leaves, key=lambda a: getattr(a, "size", 1 << 62)))
        return time.perf_counter() - t0

    def supervised_pass() -> float:
        st = fresh()
        t0 = time.perf_counter()
        _, _, ok = chunked_pass(compiled, st, n_chunks, 1e9)
        assert ok
        return time.perf_counter() - t0

    raw_pass(), supervised_pass()  # warm both paths
    raw = min(raw_pass() for _ in range(repeats))
    sup = min(supervised_pass() for _ in range(repeats))
    pct = (sup - raw) / raw * 100.0
    return {
        "config": {
            "node_count": node_ct,
            "n_replicas": n_replicas,
            "chunk_ms": chunk_ms,
            "repeats": repeats,
        },
        "raw_s": round(raw, 3),
        "supervised_s": round(sup, 3),
        "overhead_pct": round(pct, 2),
        "ok": pct < 2.0,
    }


# which invariants the measured config preserves: the
# headline runs stop_when_done=True, whose early exit skips post-done
# ticks — done_at (the deliverable: time-to-aggregation) is bit-preserved
# (pinned by test_beat_gated_run_bit_identical_to_ungated +
# test_stop_when_done tests), but traffic counters exclude post-done
# dissemination the oracle would still count
# ROADMAP item-1 north star: 21 sims/s/chip at the flagship node count.
# One sim = ticks_per_sim EXECUTED ticks (SIM_MS when nothing quiesces;
# less with the stop_when_done early exit — BUDGET.json records the
# measured value), so at R replicas/batch the whole batch must average
# R / (21 * ticks_per_sim) seconds per tick — the chip-independent
# per-tick budget every rung is judged against.
NORTH_STAR_SIMS_PER_SEC = 21.0


def _budget_ticks_per_sim() -> float:
    """Measured ticks/sim from BUDGET.json (scripts/budget_report.py);
    SIM_MS — the no-quiescence worst case — when no budget exists."""
    from wittgenstein_tpu.profiling import load_budget

    budget = load_budget(
        root=os.path.dirname(os.path.abspath(__file__))
    )
    if budget and float(budget.get("ticks_per_sim") or 0) > 0:
        return float(budget["ticks_per_sim"])
    return float(SIM_MS)


def target_tick_us(n_replicas: int) -> float:
    """Per-tick wall budget (µs) for the north-star throughput at this
    replica count — DERIVED from BUDGET.json's measured ticks/sim (the
    profiling.budget arithmetic), not hand-set."""
    from wittgenstein_tpu.profiling import required_tick_us

    return required_tick_us(
        n_replicas, _budget_ticks_per_sim(), NORTH_STAR_SIMS_PER_SEC
    )


def _floor_path() -> str:
    return os.environ.get(
        "WITT_BENCH_FLOOR",
        os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "BENCH_FLOOR.json"
        ),
    )


def check_cpu_floor(results) -> "dict | None":
    """CPU-throughput floor: compare the 256x4 rung against the recorded
    floor (BENCH_FLOOR.json); >10% below is a LOUD failure — it guards
    both engine regressions and this file's own supervisor overhead.
    Returns a verdict dict, or None when no comparison applies (no floor
    recorded, different core count, rung not measured)."""
    path = _floor_path()
    if not os.path.exists(path):
        return None
    try:
        with open(path) as f:
            floor_rec = json.load(f)
    except (OSError, ValueError):
        return None
    rung = next(
        (
            r
            for n, rr, r in results
            if n == floor_rec.get("node_count", 256)
            and rr == floor_rec.get("n_replicas", 4)
            and "sims_per_sec" in r
        ),
        None,
    )
    if rung is None:
        return None
    if floor_rec.get("host_cpus") != os.cpu_count():
        # CPU numbers are only comparable at equal core counts (the r5
        # multi-core vs r6 1-core lesson baked into _headline.config)
        return {
            "floor": floor_rec.get("floor"),
            "verdict": "skipped",
            "reason": (
                f"floor recorded on {floor_rec.get('host_cpus')} cpus, "
                f"this host has {os.cpu_count()}"
            ),
        }
    floor = float(floor_rec["floor"])
    val = float(rung["sims_per_sec"])
    out = {
        "floor": floor,
        "measured": round(val, 3),
        "ratio": round(val / floor, 3),
        "recorded": floor_rec.get("recorded"),
    }
    out["verdict"] = "fail" if val < 0.9 * floor else "ok"
    return out


PARITY_STOP_WHEN_DONE = {
    "done_at": True,
    "traffic_counters": False,
    "note": (
        "stop_when_done=True: aggregation-completion times are exact "
        "(DES-quiescence analog, pinned by test); msg/displacement "
        "counters exclude post-done traffic"
    ),
}


def _headline(
    node_ct,
    n_replicas,
    result,
    platform,
    device_kind,
    probe,
    bench_error,
    rungs,
    oracle,
    provenance="measured live by this bench run",
) -> dict:
    return {
        # "chip" names a TPU measurement only
        "metric": f"handel{node_ct}_sims_per_sec_"
        + ("chip" if platform == "tpu" else platform),
        "value": round(result["sims_per_sec"], 3),
        "unit": "sims/sec",
        "vs_baseline": round(result["sims_per_sec"] / oracle, 3),
        "platform": platform,
        "device_kind": device_kind,
        "provenance": provenance,
        "config": {
            "node_count": node_ct,
            "n_replicas": n_replicas,
            "sim_ms": SIM_MS,
            "chunk_ms": result.get("chunk_ms", CHUNK_MS),
            # CPU numbers are only comparable at equal core counts: the
            # r6 container exposes ONE core (r5's 1.174 handel256 value
            # was multi-core; the r5-engine code measures 0.554 sims/sec
            # on this 1-core host — r6 measures above that)
            "host_cpus": os.cpu_count(),
        },
        "compile_s": result.get("compile_s"),
        "run_s": result.get("run_s"),
        # chip-independent per-tick budget (ROADMAP item 1) vs measured;
        # the target derives from BUDGET.json's measured ticks/sim
        # (profiling.budget) — falls back to SIM_MS when absent
        "target_tick_us": round(target_tick_us(n_replicas), 1),
        "budget_ticks_per_sim": round(_budget_ticks_per_sim(), 1),
        "measured_tick_us": (
            round(result["run_s"] / SIM_MS * 1e6, 1)
            if result.get("run_s")
            else None
        ),
        "oracle_sims_per_sec": round(oracle, 4),
        # jump efficacy of the measured run (None when the headline ran
        # uninstrumented — the in-graph telemetry tier stays off for the
        # headline number; the sweep/A-B records carry measured fracs)
        "jumped_ms_frac": (
            (result.get("counters") or {}).get("loop") or {}
        ).get("jumped_ms_frac"),
        "parity": PARITY_STOP_WHEN_DONE,
        "rungs": rungs,
        "workload": (
            "handel-full: windowed scoring, Byzantine attack machinery,"
            " fastPath, per-node pairing.  r4: send-time xor_shuffle,"
            " due-pair delivery, beat-gated dissemination, 20-tick"
            " readback-synced chunks, DES-quiescence early exit"
            " (stop_when_done).  r5: CHANNEL_DEPTH=32 (displacement"
            " 25%->10%), boundary-view selection (reference conditional-"
            "task timing; CDF parity ~1% at P10/P50), absolute-arrival"
            " channel keys (no per-tick countdown traffic), PRP reception"
            " ranks.  r6: time-wheel message store (O(B+V) delivery vs"
            " O(C) ring scan), donated state buffers on the chunked runs,"
            " CPU replica ladder.  Not comparable to the r1/r2 lite engine"
        ),
        "probe": probe,
        "bench_error": bench_error,
    }


def _emit(rec: dict) -> None:
    """Print the BENCH record and (optionally) append it to the durable
    JSONL run-record file.  Every record carries the run-cache counter
    snapshot (hit/miss/eviction/compile) so compile-amortization claims
    — the serve scheduler's "fixed number of compiles" in particular —
    are auditable from the bench archive alone."""
    from wittgenstein_tpu.parallel.replica_shard import run_cache_info

    rec.setdefault("run_cache", run_cache_info())
    print(json.dumps(rec))
    path = os.environ.get("WITT_BENCH_RUNRECORD")
    if path:
        from wittgenstein_tpu.telemetry import RunRecordWriter

        RunRecordWriter(path).write(rec, kind="bench")


def main() -> None:
    import jax

    forced = os.environ.get("WITT_BENCH_PLATFORM")
    if forced:
        jax.config.update("jax_platforms", forced)
    devs = jax.devices()
    platform = devs[0].platform
    device_kind = getattr(devs[0], "device_kind", "?")
    if platform != "tpu" and forced != "cpu":
        raise SystemExit(
            f"bench.py: no TPU (jax.devices()[0].platform={platform!r}); set "
            "WITT_BENCH_PLATFORM=cpu to measure the CPU on purpose"
        )
    probe = {"platform": platform, "forced": forced}

    results, errors = [], []  # results: (nodes, replicas, rung dict)
    # CHEAP-FIRST replica ladder at the flagship node count (256 nodes
    # when the CPU was asked for): the first rung lands a number early,
    # then replicas climb while the budget lasts
    node_ct, replica_ladder = (
        (4096, (4, 8, 16, 32, 64)) if platform == "tpu" else (256, (4, 8, 16))
    )
    if os.environ.get("WITT_BENCH_REPLICAS"):
        replica_ladder = (int(os.environ["WITT_BENCH_REPLICAS"]),)
    budget = float(os.environ.get("WITT_BENCH_BUDGET_S", "1500"))
    t_start = time.time()
    remaining = lambda: budget - (time.time() - t_start)

    for r in replica_ladder:
        if remaining() < 60:
            errors.append(f"budget exhausted before {node_ct}x{r}")
            break
        try:
            rec = bench_batched(node_ct, r, remaining())
        except Exception as e:  # noqa: BLE001 — a failed rung ends the ladder
            errors.append(f"{node_ct}x{r}: {type(e).__name__}: {str(e)[:300]}")
            break
        if rec.get("too_slow"):
            errors.append(
                f"{node_ct}x{r}: projected {rec['projected_s']}s exceeds "
                f"remaining budget (per_tick_ms={rec['per_tick_ms']})"
            )
            break
        results.append((node_ct, r, rec))
        if (
            len(results) >= 2
            and results[-1][2]["sims_per_sec"]
            < 1.15 * results[-2][2]["sims_per_sec"]
        ):
            break  # replica scaling saturated

    bench_error = "; ".join(errors) if errors else None
    if not results:
        raise SystemExit(f"bench.py: no rung was measured: {bench_error}")

    node_ct, n_replicas, result = max(results, key=lambda x: x[2]["sims_per_sec"])
    oracle = bench_oracle(node_ct)
    rec = _headline(
        node_ct,
        n_replicas,
        result,
        platform,
        device_kind,
        probe,
        bench_error,
        [dict(rec, nodes=n, replicas=r) for n, r, rec in results],
        oracle,
    )
    # per-phase tick profile + wheel occupancy high-water: cheap on CPU;
    # on the TPU only when explicitly requested (extra 4096-node compiles)
    if platform != "tpu" or os.environ.get("WITT_BENCH_PHASE_PROFILE") == "1":
        try:
            # ablation matrix off here: 8 fresh configs are minutes of
            # compile on the 1-core box — --phase-profile runs it
            rec["phase_profile"] = phase_profile(ablate=False)
        except Exception as e:
            rec["phase_profile"] = {
                "error": f"{type(e).__name__}: {str(e)[:300]}"
            }
    if platform != "tpu":
        verdict = check_cpu_floor(results)
        if verdict is not None:
            rec["cpu_floor"] = verdict
    _emit(rec)
    if rec.get("cpu_floor", {}).get("verdict") == "fail":
        v = rec["cpu_floor"]
        print(
            f"BENCH FLOOR VIOLATION: 256x4 measured {v['measured']} "
            f"sims/sec is >10% below the recorded CPU floor {v['floor']} "
            f"({_floor_path()}) — engine or supervisor regression",
            file=sys.stderr,
        )
        sys.exit(1)


if __name__ == "__main__":
    if len(sys.argv) >= 2 and sys.argv[1] == "--overhead":
        # supervisor-overhead audit on the CPU 256x4 rung: one JSON
        # line, rc=1 when the supervised loop costs >2% over raw
        import jax

        jax.config.update("jax_platforms", "cpu")
        rec = overhead_check(
            int(sys.argv[2]) if len(sys.argv) > 2 else 256,
            int(sys.argv[3]) if len(sys.argv) > 3 else 4,
        )
        print(json.dumps(rec))
        sys.exit(0 if rec["ok"] else 1)
    elif len(sys.argv) >= 2 and sys.argv[1] == "--phase-profile":
        # standalone microbenchmark mode: per-phase wall time + wheel
        # occupancy high-water + the ranked ablation lever report, one
        # JSON line on stdout, the human lever table on stderr (CPU by
        # default — pass WITT_BENCH_PLATFORM=tpu to profile the chip
        # deliberately).  Args: [node_ct] [replicas] [scans].
        # WITT_BENCH_ABLATION=smoke restricts the matrix to the r4→r5
        # attribution levers (the CI tier); =off skips it.
        import jax

        if os.environ.get("WITT_BENCH_PLATFORM", "cpu") != "tpu":
            jax.config.update("jax_platforms", "cpu")
        node_ct = int(sys.argv[2]) if len(sys.argv) > 2 else 256
        n_replicas = int(sys.argv[3]) if len(sys.argv) > 3 else 2
        scans = int(sys.argv[4]) if len(sys.argv) > 4 else 25
        ablate_mode = os.environ.get("WITT_BENCH_ABLATION", "full")
        levers = None
        if ablate_mode == "smoke":
            from wittgenstein_tpu.profiling import smoke_ablation_configs

            levers = smoke_ablation_configs()
        rec = phase_profile(
            node_ct,
            n_replicas,
            scans,
            trace_path=os.environ.get("WITT_BENCH_TRACE"),
            ablate=ablate_mode != "off",
            ablation_levers=levers,
        )
        print(json.dumps(rec))
        if rec.get("ablation"):
            from wittgenstein_tpu.profiling.ablation import format_lever_report

            print(format_lever_report(rec["ablation"]["report"]), file=sys.stderr)
    else:
        main()
