"""Serving load benchmark: concurrent tenants + the wave-packing fleet.

Phase 1 (smoke): boots the HTTP server in-process (`server.ws.serve(0)`)
and fires N concurrent clients at it — a seed sweep, crash/recover fault
plans, message-level fault plans (drop / inflate / silence), and a long
chunked (preemptible) job that a late high-priority client overtakes.
Every client asserts its OWN result: the returned state digest must be
bitwise-identical to a singleton run of the same spec, so multi-tenancy
is provably free of cross-tenant interference.

The smoke then asserts the serving economics:

  * fixed compiles — the whole workload (>= 8 clients, >= 3 scenario
    families on one compatibility key, plus the chunked family) costs
    at most 2 run-cache compiles (direct program + chunk program),
    proven from the run cache's monotonic counters;
  * batching actually happened — batch occupancy > 0 and fewer batches
    than jobs;
  * the SLO surface is live — queue depth, occupancy, latency/TTFR
    quantiles, and the compile-cache hit ratio are all present in
    /metrics.

Phase 2 (fleet benchmark, ISSUE 13): runs one two-family workload twice
through in-process schedulers — single-lane, then ``--device-groups``
wave-packed lanes — asserts the two runs are bitwise identical per job,
and measures aggregate sims/s, queue-wait and end-to-end latency
quantiles (p50/p95/p99), and the observed wave width.  The measurements
land in ``BENCH_SERVE.json`` (schema witt-bench-serve/v1), which
``scripts/bench_trend.py`` ingests next to the engine bench rounds.
``--min-speedup`` arms the wave-vs-serial throughput gate; it defaults
to 1.5 when the host has >= 4 CPUs (CI) and 0 (measure-only) on
smaller boxes, where lanes cannot physically overlap.

Writes an SLO report (JSONL + human-readable) to the output directory
and exits nonzero on ANY failed job or violated assertion.  CI runs
this as the tier-1 serving smoke step and uploads the report.

Usage: python scripts/serve_loadgen.py [out_dir] [--clients N]
           [--device-groups G] [--min-speedup X] [--bench-out PATH]
       (defaults: ./serve_loadgen, 8 clients + 1 preemptor, 2 groups)
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
import urllib.error
import urllib.request

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if os.environ.get("JAX_PLATFORMS") == "cpu" and \
        "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    # the fleet phase needs >= 2 visible devices for its lane groups;
    # mirror the tests' conftest virtual-device split (must be set
    # before jax initializes its backends)
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()
import jax  # noqa: E402

from wittgenstein_tpu.parallel.replica_shard import run_cache_info  # noqa: E402
from wittgenstein_tpu.runtime.locks import (  # noqa: E402
    arm_lock_trace, lock_trace_status, reset_lock_trace,
)
from wittgenstein_tpu.serve import BatchScheduler, quantile  # noqa: E402
from wittgenstein_tpu.server.ws import (  # noqa: E402
    WServer, serve, shutdown_server,
)

SIM_MS = 100
BASE = {"protocol": "PingPong", "params": {"node_ct": 64}, "simMs": SIM_MS}


def scenarios(n_clients: int):
    """>= 3 scenario families, all per-replica data on ONE compat key:
    seed sweep, node-level fault plans, message-level fault plans."""
    fams = [
        lambda i: {**BASE, "seed": i},  # seeds
        lambda i: {**BASE, "seed": i, "faults": [  # node faults
            {"op": "crash", "nodes": [1 + i % 5, 7], "at": 10 + i,
             "recover": 80},
        ]},
        lambda i: {**BASE, "seed": i, "faults": [  # message faults
            {"op": "drop", "per_mille": 100 * (1 + i % 3)},
            {"op": "inflate", "multiplier_pm": 1500, "add_ms": 2},
        ]},
    ]
    return [
        {"family": f"scenario-{i % len(fams)}", "spec": fams[i % len(fams)](i)}
        for i in range(n_clients)
    ]


class Client(threading.Thread):
    """One tenant: submit, long-poll the result, record latencies."""

    def __init__(self, base_url: str, name: str, spec: dict):
        super().__init__(name=name, daemon=True)
        self.base_url = base_url
        self.spec = spec
        self.record = {"client": name, "spec": spec, "ok": False}

    def _call(self, method, path, payload=None):
        data = json.dumps(payload).encode() if payload is not None else None
        req = urllib.request.Request(
            self.base_url + path, data=data, method=method,
            headers={"Content-Type": "application/json"},
        )
        try:
            with urllib.request.urlopen(req, timeout=600) as r:
                return r.status, json.loads(r.read().decode())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read().decode())

    def run(self):
        t0 = time.monotonic()
        try:
            status, out = self._call("POST", "/w/jobs", self.spec)
            self.record["submitStatus"] = status
            if status != 202:
                self.record["error"] = f"submit -> {status}: {out}"
                return
            jid = out["id"]
            status, res = self._call("GET", f"/w/jobs/{jid}/result?waitS=590")
            self.record["resultStatus"] = status
            self.record["latencyS"] = time.monotonic() - t0
            if status != 200 or res.get("state") != "done":
                self.record["error"] = f"result -> {status}: {res}"
                return
            self.record["jobId"] = jid
            self.record["digest"] = res["result"]["digest"]
            self.record["ok"] = True
        except Exception as e:  # noqa: BLE001 — recorded, run fails
            self.record["error"] = f"{type(e).__name__}: {e}"


FLEET_SIM_MS = 200
FLEET_CAPACITY = 4


def _fleet_specs(per_family: int):
    """Two real compatibility families (different protocols — nothing
    can merge them), enough jobs each for several batches per family."""
    specs = []
    for seed in range(per_family):
        specs.append({
            "protocol": "PingPong", "params": {"node_ct": 64},
            "simMs": FLEET_SIM_MS, "seed": seed,
        })
        specs.append({
            "protocol": "P2PFlood",
            "params": {"node_count": 64, "msg_count": 2,
                       "msg_to_receive": 2, "peers_count": 3},
            "simMs": FLEET_SIM_MS, "seed": seed,
        })
    return specs


def _fleet_run(specs, device_groups: int) -> dict:
    """One timed pass: fresh scheduler, per-family warmup dispatch
    (absorbs the compiles — the benchmark measures execution overlap,
    not XLA), then all jobs at once through the lane workers."""
    from wittgenstein_tpu.serve import JobState

    sched = BatchScheduler(
        auto_start=False, max_batch_replicas=FLEET_CAPACITY,
        device_groups=device_groups,
    )
    warm = {}
    for s in specs:
        warm.setdefault(s["protocol"], {**s, "seed": 10_000})
    # warm one family per lane, one at a time: the warmup dispatch both
    # absorbs the family's compile AND sticky-binds it to the lane that
    # will serve it (draining everything on lane 0 would bind every
    # family there and serialize the whole wave)
    for i, s in enumerate(warm.values()):
        sched.submit(s)
        lane = i % sched.device_groups
        while sched.drain_once(lane):
            pass
    jobs = [sched.submit(s) for s in specs]
    t0 = time.monotonic()
    sched.start()
    for j in jobs:
        if not j.done_event.wait(600):
            raise TimeoutError(f"fleet job {j.id} did not finish")
    wall_s = time.monotonic() - t0
    sched.stop()
    failed = [j for j in jobs if j.state is not JobState.DONE]
    if failed:
        raise RuntimeError(
            f"fleet jobs failed: {[(j.id, j.error) for j in failed]}"
        )
    queue_wait = sorted(j.started_at - j.submitted_at for j in jobs)
    latency = sorted(j.finished_at - j.submitted_at for j in jobs)
    m = sched.metrics
    # mission control: evaluate the SLO engine once at the end of the
    # run (pull model) so the record carries the alert counts — a
    # fault-free benchmark must show zero
    sched.slo.evaluate()
    return {
        "alerts": sched.slo.alert_counts(),
        "deviceGroups": device_groups,
        "jobs": len(jobs),
        "wallS": round(wall_s, 4),
        "simsPerSec": round(len(jobs) / wall_s, 4),
        "queueWaitS": {
            "p50": round(quantile(queue_wait, 0.50), 4),
            "p95": round(quantile(queue_wait, 0.95), 4),
            "p99": round(quantile(queue_wait, 0.99), 4),
        },
        "latencyS": {
            "p50": round(quantile(latency, 0.50), 4),
            "p95": round(quantile(latency, 0.95), 4),
            "p99": round(quantile(latency, 0.99), 4),
        },
        "waveWidthMax": m.wave_width_max,
        "laneDispatches": dict(m._lane_dispatches),
        "resilience": {
            "quarantined": m.jobs_quarantined,
            "laneFailures": m.lane_failures_total,
            "laneRestarts": m.lane_restarts_total,
            "salvageRuns": m.salvage_runs_total,
            "salvageSeconds": round(m.salvage_seconds_total, 4),
        },
        "occupancyAvg": round(
            m.replicas_packed_total / m.replicas_capacity_total, 4
        ) if m.replicas_capacity_total else 0.0,
        "digests": {
            f"{s['protocol']}/{s['seed']}": j.result["digest"]
            for s, j in zip(specs, jobs)
        },
    }


def fleet_bench(device_groups: int, per_family: int,
                min_speedup: float) -> dict:
    """Serial-vs-wave comparison on one workload.  Returns the
    witt-bench-serve record; appends to its own failure list."""
    failures = []
    specs = _fleet_specs(per_family)
    # phase 0: a short ARMED probe — a slice of the workload runs under
    # the lock trace so the record carries a lock-wait profile and a
    # runtime lock-order audit.  Armed and disarmed (state reset) around
    # the probe only: the timed serial/wave phases below stay untraced.
    arm_lock_trace(True)
    reset_lock_trace()
    try:
        _fleet_run(specs[: max(2, len(specs) // 4)], 1)
        lt = lock_trace_status()
    finally:
        arm_lock_trace(False)
        reset_lock_trace()
    lock_trace = {
        "armedProbe": True,
        "lockWaitP99S": lt["waitP99S"],
        "maxWaitS": lt["maxWaitS"],
        "violationCount": lt["violationCount"],
    }
    if lt["violationCount"]:
        failures.append(
            f"lock-order violations under the armed fleet probe: "
            f"{lt['violations'][:3]}"
        )
    serial = _fleet_run(specs, 1)
    wave = _fleet_run(specs, device_groups)
    # correctness first: wave packing must not change a single byte
    identical = serial["digests"] == wave["digests"]
    if not identical:
        diff = [k for k in serial["digests"]
                if serial["digests"][k] != wave["digests"][k]]
        failures.append(
            f"wave-packed results differ from single-lane on {diff} — "
            "lane placement leaked into the simulation"
        )
    if wave["waveWidthMax"] < min(2, device_groups):
        failures.append(
            f"wave width never exceeded {wave['waveWidthMax']} with "
            f"{device_groups} lanes — families are still serializing"
        )
    speedup = (
        serial["wallS"] / wave["wallS"] if wave["wallS"] else 0.0
    )
    if min_speedup and speedup < min_speedup:
        failures.append(
            f"wave speedup {speedup:.2f}x < required {min_speedup}x "
            f"(serial {serial['wallS']}s vs wave {wave['wallS']}s)"
        )
    for run in (serial, wave):
        run.pop("digests")  # bulky; identity already asserted
    # a clean benchmark run pays ZERO resilience tax; any quarantine,
    # lane restart, or salvage re-run here is itself a regression, and
    # salvageSeconds/wallS is the overhead fraction trend CI watches
    resilience = {
        k: serial["resilience"][k] + wave["resilience"][k]
        for k in serial["resilience"]
    }
    resilience["salvageSeconds"] = round(resilience["salvageSeconds"], 4)
    total_wall = serial["wallS"] + wave["wallS"]
    resilience["salvageOverheadFrac"] = round(
        resilience["salvageSeconds"] / total_wall, 4
    ) if total_wall else 0.0
    if resilience["quarantined"] or resilience["laneRestarts"]:
        failures.append(
            f"resilience machinery fired during a fault-free benchmark "
            f"(quarantined={resilience['quarantined']}, "
            f"laneRestarts={resilience['laneRestarts']})"
        )
    # ... and zero SLO alerts: any alert during a fault-free benchmark
    # is either a real service regression or alert noise, and both must
    # fail the run (bench_trend --check re-asserts this on the
    # committed record)
    by_slo: dict = {}
    for run in (serial, wave):
        for slo, n in run["alerts"]["by_slo"].items():
            by_slo[slo] = by_slo.get(slo, 0) + n
        run.pop("alerts")
    alerts = {"total": sum(by_slo.values()),
              "by_slo": dict(sorted(by_slo.items()))}
    if alerts["total"]:
        failures.append(
            f"SLO alerts fired during a fault-free benchmark: "
            f"{alerts['by_slo']}"
        )
    return {
        "alerts": alerts,
        "schema": "witt-bench-serve/v1",
        "ok": not failures,
        "config": {
            "deviceGroups": device_groups,
            "jobsPerFamily": per_family,
            "families": 2,
            "simMs": FLEET_SIM_MS,
            "maxBatchReplicas": FLEET_CAPACITY,
            "cpus": os.cpu_count(),
        },
        "serial": serial,
        "wave": wave,
        "resilience": resilience,
        "lockTrace": lock_trace,
        "speedup": round(speedup, 4),
        "minSpeedup": min_speedup,
        "speedupGateArmed": bool(min_speedup),
        "bitwiseIdentical": identical,
        "failures": failures,
    }


def parse_metrics(text: str) -> dict:
    out = {}
    for line in text.splitlines():
        if line.startswith("#") or not line.strip():
            continue
        name, _, value = line.rpartition(" ")
        try:
            out[name] = float(value)
        except ValueError:
            pass
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("out_dir", nargs="?",
                    default=os.path.join(ROOT, "serve_loadgen"))
    ap.add_argument("--clients", type=int, default=8,
                    help="concurrent batch clients (>= 8 for the "
                    "acceptance run; the chunked preemptor is extra)")
    ap.add_argument("--device-groups", type=int, default=2,
                    help="lanes for the fleet benchmark phase "
                    "(0 skips the phase)")
    ap.add_argument("--jobs-per-family", type=int, default=6,
                    help="fleet phase jobs per family (two families)")
    ap.add_argument("--min-speedup", type=float, default=None,
                    help="required wave-vs-serial speedup; default 1.5 "
                    "with >= 4 CPUs, else 0 (measure only)")
    ap.add_argument("--bench-out", default=os.path.join(
                    ROOT, "BENCH_SERVE.json"),
                    help="where the witt-bench-serve record lands "
                    "(bench_trend.py reads it from the repo root)")
    args = ap.parse_args()
    if args.min_speedup is None:
        # lanes cannot physically overlap on a 1-2 core box: measure
        # there, gate where the hardware can express the claim (CI)
        args.min_speedup = 1.5 if (os.cpu_count() or 1) >= 4 else 0.0
    os.makedirs(args.out_dir, exist_ok=True)

    ws = WServer(scheduler=BatchScheduler(max_batch_replicas=8))
    httpd = serve(0, ws=ws)
    base_url = f"http://127.0.0.1:{httpd.server_address[1]}"
    failures = []
    cache0 = dict(run_cache_info())

    # the chunked, preemptible tenant goes first so the direct clients
    # (higher priority) demonstrably overtake it between slices
    chunked_spec = {**BASE, "seed": 97, "simMs": 400, "chunkMs": 100,
                    "priority": 0}
    clients = [Client(base_url, "chunked-00", chunked_spec)]
    for i, sc in enumerate(scenarios(args.clients)):
        sc["spec"]["priority"] = 5
        clients.append(Client(base_url, f"{sc['family']}-{i:02d}", sc["spec"]))

    t_start = time.monotonic()
    for c in clients:
        c.start()
        time.sleep(0.01)  # arrival jitter: exercise admission ordering
    for c in clients:
        c.join(600)
    wall_s = time.monotonic() - t_start

    for c in clients:
        if not c.record["ok"]:
            failures.append(f"{c.name}: {c.record.get('error')}")

    # per-job correctness: batched result == singleton run, bitwise
    if not failures:
        for c in clients:
            ref = ws.jobs.run_singleton(c.spec)
            if c.record["digest"] != ref["digest"]:
                failures.append(
                    f"{c.name}: digest {c.record['digest']} != singleton "
                    f"{ref['digest']} — cross-tenant interference"
                )
        distinct = {c.record.get("digest") for c in clients}
        if len(distinct) != len(clients):
            failures.append(
                f"only {len(distinct)} distinct digests for {len(clients)} "
                "distinct scenarios — results are not scenario-faithful"
            )

    # serving economics: <= 2 compiles for the whole workload
    cache1 = dict(run_cache_info())
    new_misses = cache1["misses"] - cache0["misses"]
    new_compiles = cache1["compiles"] - cache0["compiles"]
    if new_compiles > 2 or new_misses > 2:
        failures.append(
            f"workload cost {new_compiles} compiles / {new_misses} "
            "run-cache misses (budget: 2 — direct + chunk program)"
        )

    m = ws.jobs.metrics
    if m.batches_total == 0 or m.last_occupancy <= 0:
        failures.append(
            f"no batching observed (batches={m.batches_total}, "
            f"occupancy={m.last_occupancy})"
        )
    if m.batches_total >= m.jobs_completed and args.clients >= 8:
        failures.append(
            f"{m.batches_total} batches for {m.jobs_completed} jobs — "
            "jobs are not sharing dispatches"
        )
    if m.preemptions_total < 1 or m.resumes_total < 1:
        failures.append(
            f"the chunked tenant was never preempted/resumed "
            f"(preemptions={m.preemptions_total}, resumes={m.resumes_total})"
        )

    # SLO exposition: the families CI alarms on must be present and sane
    with urllib.request.urlopen(base_url + "/metrics", timeout=60) as r:
        metrics_text = r.read().decode()
    gauges = parse_metrics(metrics_text)
    for family in (
        "witt_serve_queue_depth",
        "witt_serve_batch_occupancy",
        'witt_serve_job_latency_seconds{quantile="0.5"}',
        'witt_serve_job_latency_seconds{quantile="0.99"}',
        'witt_serve_time_to_first_result_seconds{quantile="0.5"}',
        "witt_serve_compile_cache_hit_ratio",
        "witt_run_cache_misses_total",
        'witt_obs_slo_firing{slo="error-kind-rate"}',
        'witt_obs_slo_firing{slo="queue-wait-p95"}',
    ):
        if family not in gauges:
            failures.append(f"/metrics is missing {family}")
    # mission control: this phase injects no faults, so it must end
    # with ZERO SLO alerts — an alert here is either a real service
    # regression or alert noise, both failures
    ws.jobs.slo.evaluate()
    alerts = ws.jobs.slo.alert_counts()
    if alerts["total"]:
        failures.append(
            f"SLO alerts fired during fault-free loadgen: "
            f"{alerts['by_slo']}"
        )
    shutdown_server(httpd)
    ws.jobs.stop()

    lat = sorted(
        c.record["latencyS"] for c in clients if "latencyS" in c.record
    )
    slo = {
        "kind": "serve_loadgen",
        "ok": not failures,
        "clients": len(clients),
        "scenarioFamilies": 3 + 1,  # 3 direct families + chunked
        "wallS": round(wall_s, 3),
        "jobsCompleted": m.jobs_completed,
        "jobsFailed": m.jobs_failed,
        "batches": m.batches_total,
        "occupancy": round(m.last_occupancy, 4),
        "preemptions": m.preemptions_total,
        "resumes": m.resumes_total,
        "latencyS": {
            "p50": quantile(lat, 0.5),
            "p99": quantile(lat, 0.99),
        },
        "runCacheDelta": {"misses": new_misses, "compiles": new_compiles},
        "alerts": alerts,
        "failures": failures,
    }
    with open(os.path.join(args.out_dir, "slo_report.jsonl"), "a") as f:
        f.write(json.dumps(slo, sort_keys=True) + "\n")
    with open(os.path.join(args.out_dir, "clients.jsonl"), "w") as f:
        for c in clients:
            f.write(json.dumps(c.record, sort_keys=True, default=str) + "\n")

    # -- phase 2: wave-packing fleet benchmark ------------------------
    n_dev = len(jax.devices())
    if 1 <= n_dev < args.device_groups:
        print(f"serve_loadgen: clamping --device-groups "
              f"{args.device_groups} -> {n_dev} (visible devices)",
              file=sys.stderr)
        args.device_groups = n_dev
        args.min_speedup = 0.0  # one lane cannot beat itself
    if args.device_groups >= 1:
        try:
            bench = fleet_bench(
                args.device_groups, args.jobs_per_family, args.min_speedup
            )
        except Exception as e:  # noqa: BLE001 — recorded, run fails
            bench = {
                "schema": "witt-bench-serve/v1", "ok": False,
                "failures": [f"fleet bench crashed: "
                             f"{type(e).__name__}: {e}"],
            }
        bench["smoke"] = {k: slo[k] for k in (
            "ok", "clients", "batches", "occupancy", "latencyS",
            "runCacheDelta",
        )}
        with open(args.bench_out, "w") as f:
            json.dump(bench, f, indent=2, sort_keys=True)
            f.write("\n")
        print(json.dumps(bench, indent=2, sort_keys=True))
        failures.extend(bench.get("failures", []))
        slo["fleet"] = {k: bench.get(k) for k in (
            "ok", "speedup", "minSpeedup", "bitwiseIdentical",
            "resilience",
        )}
        slo["ok"] = not failures

    print(json.dumps(slo, indent=2, sort_keys=True))
    if failures:
        print("serve_loadgen: FAILED", file=sys.stderr)
        for msg in failures:
            print(f"  - {msg}", file=sys.stderr)
        return 1
    print(
        f"serve_loadgen: OK — {len(clients)} tenants, "
        f"{m.batches_total} batches, {new_compiles} compiles, "
        f"p99 {slo['latencyS']['p99']:.2f}s"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
