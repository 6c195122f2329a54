"""Adversary-search smoke: the search subsystem's end-to-end CI gate.

Runs a short seeded campaign (3 ES generations, population 6) against
the registry's p2pflood build and FAILS LOUDLY unless the subsystem's
three load-bearing claims hold on this box, today:

  1. DISCOVERY — the champion's done_at objective STRICTLY beats every
     plan of the static 5-plan sweep (control, crash window, partition,
     drop, inflation): three generations of black-box search must find
     a schedule worse than anything the hand-written battery contains.
  2. REPLAY — the champion pins to a witt-regression/v1 file and
     `verify_regression` replays it BITWISE from that file alone
     (rebuild from the registry, lower, re-run, exact score equality,
     baseline dominance re-asserted).
  3. ONE COMPILE — after generation 1's warm-up, further generations
     tick ZERO new XLA compiles on the run-cache counters: a whole
     campaign rides one compiled program.

Writes the witt-bench-search/v1 throughput record (evals/sec through
the cached path, generation count, champion-objective trajectory, and
the documented evals/sec floor + note that bench_trend.py --check
gates on) to <out_dir>/BENCH_SEARCH.json, the frontier report to
<out_dir>/report.json, and the pinned champion to
<out_dir>/champion.json.  CI uploads the directory as an artifact.

Usage: python scripts/adversary_smoke.py [out_dir]  (default ./adversary_smoke)
"""

from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax  # noqa: E402

from wittgenstein_tpu.parallel.replica_shard import run_cache_info  # noqa: E402
from wittgenstein_tpu.scenarios.regressions import verify_regression  # noqa: E402
from wittgenstein_tpu.search import (  # noqa: E402
    SearchConfig,
    SearchDriver,
    baseline_scores,
)

SIM_MS = 1000
GENERATIONS = 3
POPULATION = 6
SEED = 0

#: accepted evals/sec level + why (the documentation channel the
#: bench_trend gate reads; re-record with a new note to accept a drop)
EVALS_PER_SEC_FLOOR = 0.05
FLOOR_NOTE = (
    "single-core CPU CI box, p2pflood n=64 sim_ms=1000 pop=6: ~3 s/"
    "generation through the cached path after a ~5 s warm-up compile; "
    "floor set ~10x under the measured level to absorb box noise"
)


def main() -> int:
    out_dir = (
        sys.argv[1] if len(sys.argv) > 1 else os.path.join(ROOT, "adversary_smoke")
    )
    os.makedirs(out_dir, exist_ok=True)
    failures = []

    cfg = SearchConfig(
        protocol="p2pflood",
        objective="done_at",
        sim_ms=SIM_MS,
        generations=GENERATIONS,
        population=POPULATION,
        seed=SEED,
        optimizer="es",
        label="adversary-smoke",
    )
    driver = SearchDriver(cfg)

    # static bar first (plain sweep path — does not touch the run cache)
    static = baseline_scores(driver.net, driver.state, SIM_MS, cfg.objective)
    bar = max(static.values())

    t0 = time.perf_counter()
    driver.run_generation()
    compiles_after_g1 = run_cache_info()["compiles"]
    while driver.generation < GENERATIONS:
        driver.run_generation()
    wall_s = time.perf_counter() - t0
    compile_delta = run_cache_info()["compiles"] - compiles_after_g1

    report = driver.report()
    with open(os.path.join(out_dir, "report.json"), "w") as f:
        json.dump(report, f, indent=2, sort_keys=True, default=float)

    champ = driver.champion
    # 1. discovery: strictly beat the whole static battery
    if not champ or not champ["score"] > bar:
        failures.append(
            f"champion {champ['score'] if champ else None} does not "
            f"strictly beat the static battery's best {bar} "
            f"(static scores: {static})"
        )

    # 3. one compile per campaign after warm-up
    if compile_delta != 0:
        failures.append(
            f"{compile_delta} extra XLA compile(s) after generation 1 — "
            "the generation loop fell off the cached program"
        )

    # 2. pin + bitwise replay from the file alone
    pin_path = os.path.join(out_dir, "champion.json")
    if champ:
        driver.pin_champion(pin_path)
        try:
            verify_regression(pin_path)
        except AssertionError as e:
            failures.append(f"pinned champion failed bitwise replay: {e}")

    evals = sum(h["evals"] * h["replicas_per_plan"] for h in driver.history)
    eval_s = sum(h["eval_s"] for h in driver.history)
    bench = {
        "schema": "witt-bench-search/v1",
        "ok": not failures,
        "failures": failures,
        "protocol": cfg.protocol,
        "objective": cfg.objective,
        "sim_ms": SIM_MS,
        "optimizer": cfg.optimizer,
        "population": POPULATION,
        "generations": driver.generation,
        "evals": evals,
        "eval_seconds": round(eval_s, 3),
        "wall_seconds": round(wall_s, 3),
        "evals_per_sec": round(evals / eval_s, 4) if eval_s else None,
        "champion_trajectory": [
            h["champion_score"] for h in driver.history
        ],
        "champion_score": champ["score"] if champ else None,
        "static_best": bar,
        "compile_delta_after_g1": compile_delta,
        "evals_per_sec_floor": EVALS_PER_SEC_FLOOR,
        "floor_note": FLOOR_NOTE,
        "backend": jax.default_backend(),
    }
    with open(os.path.join(out_dir, "BENCH_SEARCH.json"), "w") as f:
        json.dump(bench, f, indent=2, sort_keys=True)

    print(
        json.dumps(
            {
                "ok": not failures,
                "out_dir": out_dir,
                "champion_score": champ["score"] if champ else None,
                "static_best": bar,
                "compile_delta_after_g1": compile_delta,
                "evals_per_sec": bench["evals_per_sec"],
                "failures": failures,
            }
        )
    )
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
