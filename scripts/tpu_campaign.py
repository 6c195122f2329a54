"""Patient TPU measurement campaign for the flagship bench.

Design: a SUPERVISOR process (no jax, so it never holds the chip) polls
health in a killable subprocess; when the chip answers it spawns the
measuring child (`--run`) — one process on the chip at a time.  The child
works in SMALL steps — one chunk at a time, host sync between chunks —
and appends every measurement to tpu_campaign.jsonl as it happens.  The
supervisor watches that file's mtime: a long silence means the child
hangs inside a device call, and it is killed.  Completed rungs are
skipped on re-entry, so a restarted campaign resumes where it stopped.

Run detached:  nohup python scripts/tpu_campaign.py > campaign.log 2>&1 &
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.environ.get(
    "WITT_CAMPAIGN_OUT", os.path.join(ROOT, "tpu_campaign.jsonl")
)
# dry-run the CHILD logic on the CPU backend so a recovered chip never
# meets untested campaign code.  Requires an explicit WITT_CAMPAIGN_OUT:
# CPU rungs in the real jsonl would poison done_rungs() resume keys and
# campaign_best with CPU numbers.
ALLOW_CPU = os.environ.get("WITT_CAMPAIGN_ALLOW_CPU") == "1"
if ALLOW_CPU and not os.environ.get("WITT_CAMPAIGN_OUT"):
    raise SystemExit("WITT_CAMPAIGN_ALLOW_CPU=1 requires WITT_CAMPAIGN_OUT")
PROBE_TIMEOUT_S = 150

sys.path.insert(0, ROOT)
SAFE_CALL_S = 60.0  # refuse a rung whose projected chunk runs longer
POLL_INTERVAL_S = 300
SILENCE_KILL_S = 900  # no jsonl progress for this long => child hangs
COMPILE_LIMIT_S = 780  # child self-aborts a compile running past this
CHUNK_LIMIT_S = 180  # ... and a device chunk past this
NODES = int(os.environ.get("WITT_CAMPAIGN_NODES", "4096"))
REPLICA_LADDER = (4, 8, 16, 32, 64)
SIM_MS = 1000
# one program per rung; 20-tick chunks keep every device call short
CHUNK_MS = int(os.environ.get("WITT_CAMPAIGN_CHUNK_MS", "20"))
if CHUNK_MS <= 0 or SIM_MS % CHUNK_MS != 0:
    raise SystemExit(
        f"WITT_CAMPAIGN_CHUNK_MS={CHUNK_MS} must be a positive divisor of {SIM_MS}"
    )
RUNG_BUDGET_S = 900  # full-pass cost cap per rung (checked between chunks)
# rung passes checkpoint through engine.checkpoint every N chunks (at
# CHUNK_MS=20 that's one state write per 100 simulated ms): an aborted
# or killed pass RESUMES at its last checkpoint on the next
# campaign entry instead of restarting the rung from scratch
CKPT_ROOT = os.environ.get(
    "WITT_CAMPAIGN_CKPT", os.path.join(ROOT, ".campaign_ckpt")
)
CHECKPOINT_EVERY = int(os.environ.get("WITT_CAMPAIGN_CKPT_EVERY", "5"))


def log(rec: dict) -> None:
    rec = dict(rec, ts=round(time.time(), 1))
    parent = os.path.dirname(os.path.abspath(OUT))
    if parent and not os.path.isdir(parent):
        os.makedirs(parent, exist_ok=True)
    with open(OUT, "a") as f:
        f.write(json.dumps(rec) + "\n")
    print(json.dumps(rec), flush=True)


# mission control: the campaign feeds each rung's sims/s into an
# in-process timeseries and evaluates the BENCH_FLOOR.json floor SLO
# (obs/slo.py) — a breach lands in the ledger as an slo_alert event
# (witt_watch --campaign surfaces it) and as a typed flight-recorder
# event.  Lazily armed on the first rung; [engine] boxed for the
# child's single thread.
_campaign_slo = [None]


def _observe_rung(rec: dict) -> None:
    """Best-effort by contract: monitoring never kills a campaign."""
    try:
        from wittgenstein_tpu.obs import (
            SLOEngine,
            TimeSeriesStore,
            default_serve_specs,
            get_recorder,
        )

        if _campaign_slo[0] is None:
            specs = [
                s for s in default_serve_specs()
                if s.name == "sims-per-sec-floor"
            ]
            if not specs:
                return  # no committed BENCH_FLOOR.json: nothing to arm
            _campaign_slo[0] = SLOEngine(
                TimeSeriesStore(), specs, recorder=get_recorder()
            )
        engine = _campaign_slo[0]
        engine.store.observe(
            "campaign.sims_per_sec", float(rec["sims_per_sec"]),
            ctx={"nodes": rec.get("nodes"),
                 "replicas": rec.get("replicas")},
        )
        before = engine.alert_counts()["total"]
        rows = engine.evaluate()
        if engine.alert_counts()["total"] > before:
            for row in rows:
                if row["state"] == "firing":
                    log({
                        "event": "slo_alert", "slo": row["slo"],
                        "severity": row["severity"],
                        "measured": row["measured_fast"],
                        "objective": row["objective"],
                        "burn_slow": row["burn_slow"],
                    })
    except Exception as e:  # noqa: BLE001 — monitoring is best-effort
        log({"event": "slo_eval_error", "error": f"{type(e).__name__}: {e}"})


def _events() -> list:
    evs = []
    if os.path.exists(OUT):
        for line in open(OUT):
            try:
                evs.append(json.loads(line))
            except ValueError:
                continue
    return evs


def done_rungs() -> set:
    return {
        (r["nodes"], r["replicas"]) for r in _events() if r.get("event") == "rung"
    }


def done_mesh_rungs() -> set:
    """Resume keys for the 2D-mesh ladder: one per completed
    (nodes, replicas, p_replica, p_node) rung in the jsonl."""
    return {
        (r["nodes"], r["replicas"], r["p_replica"], r["p_node"])
        for r in _events()
        if r.get("event") == "mesh_rung"
    }


_phase_deadline = [None]  # child phase watchdog (compile / chunk limits)


def _phase_watchdog() -> None:
    while True:
        time.sleep(10)
        d = _phase_deadline[0]
        if d is not None and time.time() > d:
            log({"event": "phase_overrun_abort",
                 "over_s": round(time.time() - d, 1)})
            os._exit(3)


def campaign() -> None:
    """Child mode: runs jax against the chip, one safe step at a time."""
    import threading

    import jax
    import jax.numpy as jnp

    threading.Thread(target=_phase_watchdog, daemon=True).start()

    import bench as benchmod
    from wittgenstein_tpu.utils.compile_cache import use_compile_cache

    use_compile_cache()
    from wittgenstein_tpu.engine import replicate_state
    from wittgenstein_tpu.protocols.handel_batched import make_handel

    if ALLOW_CPU:
        jax.config.update("jax_platforms", "cpu")
    dev = jax.devices()[0]
    log({"event": "campaign_start", "device": str(dev), "kind": dev.device_kind})
    if dev.platform != "tpu" and not ALLOW_CPU:
        log({"event": "abort", "reason": f"platform {dev.platform} != tpu"})
        return

    # same production config as bench.bench_batched: fused delivery+tick,
    # score cache at its backend-auto default (ON here on TPU)
    net, state0 = make_handel(benchmod._params(NODES), fuse_step=True)
    skip = done_rungs()

    results = []
    for r in REPLICA_LADDER:
        if (NODES, r) in skip:
            log({"event": "rung_cached", "nodes": NODES, "replicas": r})
            continue
        states = replicate_state(state0, r)
        # ONE chunk size for the whole rung — a second chunk size would be a
        # second XLA program and a second minutes-long compile.
        n_chunks = SIM_MS // CHUNK_MS
        # donated chunks (see bench.bench_batched): each chunk consumes its
        # input buffers, so the 20-tick readback-synced loop stops paying a
        # full state copy per chunk; each PASS gets its own fresh copy below
        run = jax.jit(
            lambda s: net.run_ms_batched(s, CHUNK_MS, True), donate_argnums=(0,)
        )

        # the compile is one long blocking call: log its START so the
        # supervisor's mtime watchdog doesn't count tracing+compile as
        # silence, and self-abort via the phase watchdog if it truly runs
        # away
        log({"event": "compiling", "nodes": NODES, "replicas": r,
             "limit_s": COMPILE_LIMIT_S})
        _phase_deadline[0] = time.time() + COMPILE_LIMIT_S
        t0 = time.perf_counter()
        compiled = run.lower(states).compile()
        compile_s = time.perf_counter() - t0
        _phase_deadline[0] = None
        log({"event": "compiled", "nodes": NODES, "replicas": r,
             "chunk_ms": CHUNK_MS, "compile_s": round(compile_s, 1)})

        def heartbeat(i, chunk_s, r=r):
            # every chunk: with the readback sync in chunked_pass the
            # times are honest, and per-chunk writes give the supervisor
            # the tightest possible hang detection
            ev = "chunk_over_safe" if chunk_s > SAFE_CALL_S else "hb"
            log({"event": ev, "replicas": r, "chunk": i, "chunk_s": chunk_s})
            _phase_deadline[0] = time.time() + CHUNK_LIMIT_S

        from wittgenstein_tpu.engine.checkpoint import (
            CheckpointManager,
            read_manifest,
        )
        from wittgenstein_tpu.runtime import stable_run_key

        run_key = stable_run_key(net, states, n_chunks, CHUNK_MS)
        ck_base = os.path.join(CKPT_ROOT, f"{NODES}x{r}")

        def full_pass(st, budget_s, tag, r=r):
            """The shared never-kill-mid-call loop (bench.chunked_pass,
            now runtime.Supervisor underneath); early chunks are cheap —
            empty-ms jumps — so per-chunk times are logged, not assumed.
            Checkpoints under ck_base/tag: an aborted/killed pass resumes
            at its last completed chunk on the next campaign entry.
            Returns (out, this_run_times, ok, total_pass_s, resumed)."""
            ckdir = os.path.join(ck_base, tag)
            mgr = CheckpointManager(ckdir)
            pre_step = mgr.latest_step()
            if pre_step:
                log({"event": "rung_resume", "nodes": NODES, "replicas": r,
                     "pass": tag, "from_chunk": pre_step})
            _phase_deadline[0] = time.time() + CHUNK_LIMIT_S
            try:
                out, times, ok = benchmod.chunked_pass(
                    compiled, st, n_chunks, budget_s,
                    heartbeat=heartbeat,
                    checkpoint_dir=ckdir, run_key=run_key,
                    chunk_ms=CHUNK_MS, checkpoint_every=CHECKPOINT_EVERY,
                )
            finally:
                _phase_deadline[0] = None
            # total pass cost across ALL invocations (the checkpoint
            # meta accumulates chunk_seconds) — a resumed timed pass must
            # not report sims_per_sec from its remaining chunks only
            total_s = sum(times)
            step = mgr.latest_step()
            if step:
                man = read_manifest(mgr.path_for(step)) or {}
                saved = man.get("meta", {}).get("chunk_seconds")
                if saved:
                    total_s = sum(saved)
            return out, times, ok, total_s, bool(pre_step)

        def fresh_states():
            return jax.tree_util.tree_map(jnp.copy, states)

        t0 = time.perf_counter()
        out, warm_times, ok, _, warm_resumed = full_pass(
            fresh_states(), RUNG_BUDGET_S, "warm"
        )
        warm_s = time.perf_counter() - t0
        if not ok:
            log({"event": "rung_aborted", "nodes": NODES, "replicas": r,
                 "chunk_times": warm_times, "resumable": True,
                 "reason": f"pass exceeded {RUNG_BUDGET_S}s budget"})
            break
        ok_done = bool(out.done_at.min() > 0)
        t0 = time.perf_counter()
        out, chunk_times, ok, timed_total_s, timed_resumed = full_pass(
            fresh_states(), RUNG_BUDGET_S, "timed"
        )
        run_s = time.perf_counter() - t0
        if not ok:
            # a partial timed pass must NOT be logged as a completed rung:
            # done_rungs() would skip it forever and sims_per_sec would be
            # inflated by the missing chunks — but its checkpoint survives,
            # so the next campaign entry finishes it instead of restarting
            log({"event": "rung_aborted", "nodes": NODES, "replicas": r,
                 "chunk_times": chunk_times, "resumable": True,
                 "reason": "timed pass exceeded budget (worker degraded?)"})
            break
        if timed_resumed:
            # wall time this invocation misses the pre-kill chunks; the
            # checkpoint-accumulated per-chunk total is the honest cost
            run_s = timed_total_s
        from wittgenstein_tpu.telemetry import counters

        rec = {
            "event": "rung", "nodes": NODES, "replicas": r,
            "chunk_ms": CHUNK_MS, "warm_s": round(warm_s, 1),
            "run_s": round(run_s, 2),
            "sims_per_sec": round(r / run_s, 4),
            "per_tick_ms": round(run_s / SIM_MS * 1e3, 2),
            "all_done": ok_done,
            "resumed": bool(warm_resumed or timed_resumed),
            "chunk_times": chunk_times,
            "displaced": int(out.proto["displaced"].sum()),
            # telemetry counter summary of the measured final state (the
            # MULTICHIP-record payload; in-graph tier off — the rung
            # must measure the uninstrumented program)
            "counters": counters(net, out),
        }
        log(rec)
        _observe_rung(rec)
        results.append(rec)
        # the rung is durably logged: drop its checkpoints so a later
        # campaign with a cleaned jsonl can never resume a finished pass
        # into an instant (and wrongly cheap) "measurement"
        import shutil

        shutil.rmtree(ck_base, ignore_errors=True)
        # stop climbing when doubling replicas stopped paying (<1.25x)
        if len(results) >= 2 and results[-1]["sims_per_sec"] < 1.25 * results[-2]["sims_per_sec"]:
            log({"event": "saturated", "at_replicas": r})
            break
        # refuse a rung whose projected worst chunk (linear replica
        # scaling, conservative) would pass SAFE_CALL_S: its FIRST chunk
        # runs before any budget check does
        i_next = REPLICA_LADDER.index(r) + 1
        if i_next < len(REPLICA_LADDER):
            proj = max(chunk_times) * REPLICA_LADDER[i_next] / r
            if proj > SAFE_CALL_S:
                log({"event": "stop_climbing",
                     "next_replicas": REPLICA_LADDER[i_next],
                     "projected_chunk_s": round(proj, 1)})
                break

    if results:
        best = max(results, key=lambda x: x["sims_per_sec"])
        log({**best, "event": "campaign_best"})
    log({"event": "campaign_end"})


MESH_SCHEMA = "witt-bench-mesh/v1"
MESH_NODES = int(os.environ.get("WITT_MESH_NODES", "64"))
MESH_REPLICAS = int(os.environ.get("WITT_MESH_REPLICAS", "8"))
MESH_SIM_MS = int(os.environ.get("WITT_MESH_SIM_MS", "300"))


def _mesh_ladder_rungs(n_devices: int) -> list:
    """The P_replica × P_node sweep: every (p_r, p_n) factorization of
    the visible device count whose node axis divides the node count and
    whose replica axis divides the replica rows.  Includes the (D, 1)
    pure-replica rung — the 1D baseline every 2D rung is judged
    against."""
    rungs = []
    for p_node in range(1, n_devices + 1):
        if n_devices % p_node != 0:
            continue
        p_replica = n_devices // p_node
        if MESH_NODES % p_node != 0 or MESH_REPLICAS % p_replica != 0:
            continue
        rungs.append((p_replica, p_node))
    return rungs


def mesh_ladder(out_json: "str | None" = None) -> None:
    """Child mode: the resumable 2D-mesh rung ladder.  Each rung places
    the SAME replicated state on a (p_replica, p_node) mesh2d layout,
    runs the cached partitioned program, and records wall time +
    bit-identity against the unsharded singleton + the 1/P channel-
    ownership audit.  Completed rungs (mesh_rung events in the jsonl)
    are skipped on re-entry, so a killed ladder resumes where it
    stopped.  Every completed entry lands in BENCH_MESH.json
    (witt-bench-mesh/v1), which bench_trend.py ingests."""
    import threading

    import numpy as np

    threading.Thread(target=_phase_watchdog, daemon=True).start()

    import jax

    if ALLOW_CPU:
        jax.config.update("jax_platforms", "cpu")

    sys.path.insert(0, ROOT)
    import bench as benchmod
    from wittgenstein_tpu.engine import replicate_state
    from wittgenstein_tpu.parallel import (
        assert_channel_ownership,
        make_mesh2d_layout,
        sharded_run_stats,
    )
    from wittgenstein_tpu.protocols.handel_batched import make_handel

    dev = jax.devices()[0]
    n_devices = jax.device_count()
    log({"event": "mesh_ladder_start", "device": str(dev),
         "n_devices": n_devices, "nodes": MESH_NODES,
         "replicas": MESH_REPLICAS, "sim_ms": MESH_SIM_MS})
    if dev.platform != "tpu" and not ALLOW_CPU:
        log({"event": "abort", "reason": f"platform {dev.platform} != tpu"})
        return

    net, state0 = make_handel(benchmod._params(MESH_NODES))
    states = replicate_state(state0, MESH_REPLICAS)
    skip = done_mesh_rungs()
    rungs = _mesh_ladder_rungs(n_devices)
    if not rungs:
        log({"event": "abort",
             "reason": f"no (p_replica, p_node) factorization of "
                       f"{n_devices} devices fits nodes={MESH_NODES} "
                       f"replicas={MESH_REPLICAS}"})
        return

    # the unsharded singleton: the bit-identity reference every rung is
    # compared against (same bar as flat-vs-wheel / fused-vs-unfused)
    _phase_deadline[0] = time.time() + COMPILE_LIMIT_S
    ref_out, _ = sharded_run_stats(net, states, MESH_SIM_MS)
    jax.block_until_ready(ref_out)
    _phase_deadline[0] = None
    ref_leaves = [np.asarray(x) for x in jax.tree_util.tree_leaves(ref_out)]

    for p_replica, p_node in rungs:
        key = (MESH_NODES, MESH_REPLICAS, p_replica, p_node)
        if key in skip:
            log({"event": "mesh_rung_cached", "nodes": MESH_NODES,
                 "replicas": MESH_REPLICAS, "p_replica": p_replica,
                 "p_node": p_node})
            continue
        layout = make_mesh2d_layout(p_replica, p_node)
        log({"event": "mesh_compiling", "p_replica": p_replica,
             "p_node": p_node, "limit_s": COMPILE_LIMIT_S})
        _phase_deadline[0] = time.time() + COMPILE_LIMIT_S
        placed = layout.place(net, states)
        owned = assert_channel_ownership(net, placed, n_devices)
        t0 = time.perf_counter()
        out, _stats = sharded_run_stats(net, states, MESH_SIM_MS,
                                        layout=layout)
        jax.block_until_ready(out)
        warm_s = time.perf_counter() - t0
        _phase_deadline[0] = time.time() + CHUNK_LIMIT_S
        t0 = time.perf_counter()
        out, _stats = sharded_run_stats(net, states, MESH_SIM_MS,
                                        layout=layout)
        jax.block_until_ready(out)
        run_s = time.perf_counter() - t0
        _phase_deadline[0] = None
        bit_identical = all(
            (np.asarray(a) == b).all()
            for a, b in zip(jax.tree_util.tree_leaves(out), ref_leaves)
        )
        per_dev_b = max(b for b, _t in owned.values())
        rec = {
            "event": "mesh_rung", "nodes": MESH_NODES,
            "replicas": MESH_REPLICAS, "p_replica": p_replica,
            "p_node": p_node, "sim_ms": MESH_SIM_MS,
            "warm_s": round(warm_s, 3), "run_s": round(run_s, 3),
            "sims_per_sec": round(MESH_REPLICAS / run_s, 4),
            "bit_identical": bool(bit_identical),
            "ownership_ok": True,
            "channels": len(owned),
            "channel_bytes_per_device": int(per_dev_b),
        }
        log(rec)

    _write_mesh_record(out_json)
    log({"event": "mesh_ladder_end"})


def _write_mesh_record(out_json: "str | None" = None) -> None:
    """Assemble BENCH_MESH.json from every mesh_rung event matching the
    current ladder geometry — resumed ladders re-emit the full record."""
    import jax

    rungs = [
        {k: v for k, v in r.items() if k not in ("event", "ts")}
        for r in _events()
        if r.get("event") == "mesh_rung"
        and r.get("nodes") == MESH_NODES
        and r.get("replicas") == MESH_REPLICAS
        and r.get("sim_ms") == MESH_SIM_MS
    ]
    # last write wins per (p_replica, p_node): a re-run rung supersedes
    by_shape = {(r["p_replica"], r["p_node"]): r for r in rungs}
    rungs = [by_shape[k] for k in sorted(by_shape)]
    ok = bool(rungs) and all(
        r.get("bit_identical") and r.get("ownership_ok") for r in rungs
    )
    record = {
        "schema": MESH_SCHEMA,
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "nodes": MESH_NODES,
        "replicas": MESH_REPLICAS,
        "sim_ms": MESH_SIM_MS,
        "rungs": rungs,
        "ok": ok,
        "best": (
            max(rungs, key=lambda r: r["sims_per_sec"]) if rungs else None
        ),
    }
    path = out_json or os.environ.get(
        "WITT_MESH_OUT", os.path.join(ROOT, "BENCH_MESH.json")
    )
    parent = os.path.dirname(os.path.abspath(path))
    if parent and not os.path.isdir(parent):
        os.makedirs(parent, exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(record, f, indent=2, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)
    log({"event": "mesh_record", "path": path, "ok": ok,
         "rungs": len(rungs)})


def _mtime() -> float:
    try:
        return os.path.getmtime(OUT)
    except OSError:
        return 0.0


def probe_worker_healthy(timeout_s: int) -> bool:
    """One killable-subprocess TPU health probe.  The supervisor holds no
    chip itself and runs the probe only while no child is alive."""
    try:
        hp = subprocess.run(
            [
                sys.executable,
                "-c",
                "import jax, numpy; d = jax.devices()[0];"
                " print(d.platform, int(numpy.asarray(jax.numpy.arange(4).sum())))",
            ],
            timeout=timeout_s,
            capture_output=True,
            text=True,
        )
        last = hp.stdout.strip().splitlines()[-1] if hp.stdout.strip() else ""
        return hp.returncode == 0 and last == "tpu 6"
    except subprocess.TimeoutExpired:
        return False


def supervise() -> None:
    if ALLOW_CPU:
        # the dry-run flag is child-only: a supervisor would hand a live
        # TPU to a CPU-pinned child and record CPU rungs as real
        raise SystemExit("WITT_CAMPAIGN_ALLOW_CPU is only valid with --run")
    deadline = time.time() + float(os.environ.get("WITT_CAMPAIGN_HOURS", "10")) * 3600
    child_err = open(os.path.join(ROOT, "campaign_child.log"), "ab")
    while time.time() < deadline:
        if not probe_worker_healthy(PROBE_TIMEOUT_S):
            log({"event": "tpu_down", "next_poll_s": POLL_INTERVAL_S})
            time.sleep(POLL_INTERVAL_S)
            continue
        log({"event": "tpu_healthy"})
        child_started = time.time()
        child = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--run"],
            cwd=ROOT,
            stdout=subprocess.DEVNULL,
            stderr=child_err,
        )
        finished = False
        while True:
            try:
                child.wait(timeout=30)
                finished = True
                break
            except subprocess.TimeoutExpired:
                pass
            if time.time() - max(_mtime(), child_started) > SILENCE_KILL_S:
                log({"event": "child_hung",
                     "silence_s": round(time.time() - _mtime(), 0)})
                child.send_signal(signal.SIGKILL)
                child.wait()
                break
            if time.time() > deadline:
                log({"event": "deadline_mid_child"})
                child.send_signal(signal.SIGKILL)
                child.wait()
                return
        # only a campaign_end logged by THIS child counts — the jsonl is
        # persistent across campaigns (done_rungs resume), so a stale end
        # event from a prior run must not mask an early abort
        reached_end = any(
            e.get("event") == "campaign_end"
            and e.get("ts", 0) >= child_started
            for e in _events()
        )
        if finished and child.returncode == 0 and reached_end:
            log({"event": "child_exit", "rc": child.returncode})
            return
        # rc=0 without campaign_end = the child aborted early — retry
        log({"event": "child_retry", "rc": child.returncode})
        time.sleep(POLL_INTERVAL_S)
    log({"event": "gave_up", "reason": "deadline reached with no healthy TPU"})


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--run":
        campaign()
    elif len(sys.argv) > 1 and sys.argv[1] == "--mesh-ladder":
        mesh_ladder(sys.argv[2] if len(sys.argv) > 2 else None)
    else:
        supervise()
