"""Paired A/B of done-row harvesting (serve/scheduler.py, ISSUE 18).

One mixed-horizon workload — short jobs that finalize at an early chunk
boundary packed with one long tail job — run as repeated WAVES through
a warm harvest-off and a warm harvest-on `BatchScheduler`, INTERLEAVED
per repeat (the PR-11 noise discipline).  With harvesting on, the tail
job's surviving row compacts into the 1-row capacity bucket after the
short jobs finalize, so every remaining chunk steps 1 row instead of
`capacity`; off, the full-width batch re-runs its finished rows to the
end of the horizon.

Both schedulers are built ONCE and warmed with one throwaway wave each
before timing starts: the steady state being measured is the PR-13
zero-compile warm start (same family ⇒ run-cache hit), not the
first-wave compile.  A cold-scheduler pairing would time one XLA
compile against two and report the compile count, not the lever.

Digests gate, timing is recorded: the warm wave's jobs must equal the
fault-free `run_singleton` under BOTH schedulers (per-wave identity is
tests/test_harvest.py's job), and the aggregate sims/s pair + speedup
land in the JSON record.  BENCH_SERVE.json's `harvest` block is the
documentation channel for the accepted numbers
(scripts/bench_trend.py refuses a committed block whose record is not
ok).

Usage: python scripts/harvest_ab.py [out.json]
"""

from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

os.environ.setdefault("JAX_PLATFORMS", "cpu")
from wittgenstein_tpu.utils.compile_cache import use_compile_cache  # noqa: E402

use_compile_cache()

from wittgenstein_tpu.serve import BatchScheduler, JobState  # noqa: E402

BASE = {"protocol": "PingPong", "params": {"node_ct": 128}}
SHORT_MS, LONG_MS, N_SHORT = 100, 600, 3
REPEATS = 3


def specs(seed0: int):
    out = [
        {**BASE, "seed": seed0 + i, "simMs": SHORT_MS} for i in range(N_SHORT)
    ]
    out.append({**BASE, "seed": seed0 + N_SHORT, "simMs": LONG_MS})
    return out


def make_sched(harvest: bool) -> BatchScheduler:
    return BatchScheduler(
        auto_start=False,
        max_batch_replicas=N_SHORT + 1,
        horizon_quantum_ms=50,
        harvest=harvest,
    )


def wave(sched: BatchScheduler, seed0: int, check: bool = False) -> dict:
    ss = specs(seed0)
    t0 = time.perf_counter()
    jobs = [sched.submit(s) for s in ss]
    while sched.drain_once():
        pass
    wall = time.perf_counter() - t0
    assert all(j.state is JobState.DONE for j in jobs), [j.error for j in jobs]
    if check:
        for j, s in zip(jobs, ss):
            assert j.result["digest"] == sched.run_singleton(s)["digest"], s
    total_ms = sum(s["simMs"] for s in ss)
    return {
        "wall_s": round(wall, 3),
        "sims_per_sec": round(total_ms / 1000.0 / wall, 4),
    }


def main() -> int:
    out_path = sys.argv[1] if len(sys.argv) > 1 else None
    scheds = {"off": make_sched(False), "on": make_sched(True)}
    # warm wave per side: compiles land here (and the digest-vs-
    # singleton identity gate runs once per side)
    for k, sched in scheds.items():
        wave(sched, 9000 if k == "off" else 9100, check=True)
    runs = {"off": [], "on": []}
    for r in range(REPEATS):
        runs["off"].append(wave(scheds["off"], 1000 + 100 * r))
        runs["on"].append(wave(scheds["on"], 5000 + 100 * r))
    harvests = scheds["on"].metrics.summary()["harvests_total"]
    assert harvests >= REPEATS, f"harvest never fired ({harvests})"
    best = {k: max(v, key=lambda x: x["sims_per_sec"]) for k, v in runs.items()}
    rec = {
        "schema": "witt-harvest-ab/v1",
        "ok": True,
        "scenario": {
            **BASE,
            "jobs": f"{N_SHORT}x{SHORT_MS}ms + 1x{LONG_MS}ms",
            "capacity": N_SHORT + 1,
            "horizon_quantum_ms": 50,
        },
        "paired": runs,
        "harvests_total": harvests,
        "sims_per_sec": {k: best[k]["sims_per_sec"] for k in best},
        "speedup": round(
            best["on"]["sims_per_sec"] / best["off"]["sims_per_sec"], 3
        ),
        "host_cpus": os.cpu_count(),
    }
    if out_path:
        with open(out_path, "w") as f:
            json.dump(rec, f, indent=2, sort_keys=True)
    print(json.dumps(rec))
    return 0


if __name__ == "__main__":
    sys.exit(main())
