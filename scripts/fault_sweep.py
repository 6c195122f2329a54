"""Fault sweep: heterogeneous fault plans across replicas, one compile.

Default mode builds a toy P2PFlood simulation and runs FIVE fault
scenarios — a fault-free control, a 20% crash at t=200ms, a two-way
partition window, probabilistic message drop, and latency inflation —
as replica rows of ONE `run_ms_batched` invocation (the schedules are
FaultState data, not traced branches, so the whole sweep is a single
jit).  Emits an availability-vs-latency report plus a JSONL run record,
and FAILS LOUDLY if the sweep misbehaves: the control row must be
bit-identical to a fault-free singleton run (fault-off neutrality at
full scale), the crash row must lose availability, and the drop/
inflation counters must show their lanes fired.  CI runs this as the
tier-1 fault step and uploads the output directory as a build artifact.

--search mode turns the same machinery into a RESUMABLE adversary
search (wittgenstein_tpu.search): an optimizer population lowers to
heterogeneous FaultPlans, each generation is one cached batched sweep,
generation state checkpoints under <out_dir>/checkpoints, and the run
emits a frontier report (report.json) — interrupt it and re-invoke with
the same arguments to resume.  --pin writes the champion as a
replayable scenarios/regressions pin.

Usage: python scripts/fault_sweep.py [out_dir]            (static sweep)
       python scripts/fault_sweep.py [out_dir] --search
           [--protocol p2pflood] [--objective done_at]
           [--optimizer es|random|sha] [--generations N]
           [--population N] [--sim-ms MS] [--seed N] [--pin PATH]
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax  # noqa: E402

import numpy as np  # noqa: E402

from wittgenstein_tpu.protocols.p2pflood import P2PFloodParameters  # noqa: E402
from wittgenstein_tpu.protocols.p2pflood_batched import make_p2pflood  # noqa: E402
from wittgenstein_tpu.scenarios.sweep import run_fault_sweep  # noqa: E402
from wittgenstein_tpu.telemetry import RunRecordWriter  # noqa: E402

SIM_MS = 1500
SEED0 = 0


from wittgenstein_tpu.search.driver import static_baseline_plans  # noqa: E402

# the canonical static 5-plan battery now lives next to the search
# driver (its champions must strictly beat it); keep the historical
# script-level name for callers and docs
build_plans = static_baseline_plans


def run_search(argv, out_dir: str) -> int:
    """--search mode: resumable optimizer campaign (module docstring)."""
    import argparse

    from wittgenstein_tpu.search import SearchConfig, SearchDriver

    p = argparse.ArgumentParser(prog="fault_sweep.py --search")
    p.add_argument("--protocol", default="p2pflood")
    p.add_argument("--objective", default="done_at")
    p.add_argument("--optimizer", default="es",
                   choices=("es", "random", "sha"))
    p.add_argument("--generations", type=int, default=3)
    p.add_argument("--population", type=int, default=8)
    p.add_argument("--sim-ms", type=int, default=SIM_MS)
    p.add_argument("--seed", type=int, default=SEED0)
    p.add_argument("--pin", default=None,
                   help="also pin the champion to this regression path")
    args = p.parse_args(argv)

    cfg = SearchConfig(
        protocol=args.protocol,
        objective=args.objective,
        sim_ms=args.sim_ms,
        generations=args.generations,
        population=args.population,
        seed=args.seed,
        optimizer=args.optimizer,
        checkpoint_dir=os.path.join(out_dir, "checkpoints"),
        label=f"{args.protocol}-{args.optimizer}-s{args.seed}",
    )
    driver = SearchDriver(cfg)
    if driver.generation:
        print(f"resuming at generation {driver.generation}")
    report = driver.run()
    with open(os.path.join(out_dir, "report.json"), "w") as f:
        json.dump(report, f, indent=2, sort_keys=True, default=float)
    if args.pin:
        driver.pin_champion(args.pin)
    champ = report["champion"]
    print(
        json.dumps(
            {
                "ok": True,
                "out_dir": out_dir,
                "generations": driver.generation,
                "champion_score": champ["score"] if champ else None,
                "frontier_size": len(report["frontier"]),
                "pinned": args.pin,
            }
        )
    )
    return 0


def main() -> int:
    argv = sys.argv[1:]
    out_dir = (
        argv.pop(0)
        if argv and not argv[0].startswith("-")
        else os.path.join(ROOT, "fault_sweep")
    )
    os.makedirs(out_dir, exist_ok=True)
    if "--search" in argv:
        argv.remove("--search")
        return run_search(argv, out_dir)

    net, state = make_p2pflood(P2PFloodParameters(), capacity=2048, seed=SEED0)
    plans = build_plans(net, state)
    out, records = run_fault_sweep(
        net, state, plans, sim_ms=SIM_MS, seed0=SEED0, done_cdf_every=100
    )

    # fault-off neutrality at full scale: the control replica (row 0,
    # same seed) must be bitwise-identical to a fault-free singleton run
    single = net.run_ms(state, SIM_MS)
    for field in state._fields:
        if field == "faults":
            continue
        for a, b in zip(
            jax.tree_util.tree_leaves(getattr(single, field)),
            jax.tree_util.tree_leaves(getattr(out, field)),
        ):
            assert np.array_equal(np.asarray(a), np.asarray(b)[0]), (
                f"control row diverged from fault-free run on {field}"
            )

    by_label = {r["plan"]["label"]: r for r in records}
    ctrl = by_label["control"]
    assert ctrl["availability"] == 1.0, f"control did not finish: {ctrl}"
    assert sum(ctrl["dropped_by_fault"]) == 0 and sum(ctrl["delayed_by_fault"]) == 0
    crash = by_label["crash20@200"]
    assert crash["availability"] < ctrl["availability"], (
        f"crash plan lost no availability: {crash}"
    )
    assert sum(by_label["drop30%"]["dropped_by_fault"]) > 0
    assert sum(by_label["slow3x"]["delayed_by_fault"]) > 0

    # availability-vs-latency report
    lines = [
        f"fault sweep: p2pflood n={net.n_nodes}, sim_ms={SIM_MS}, "
        f"{len(plans)} plans x 1 replica, ONE run_ms_batched compile",
        "",
        f"{'plan':<16} {'avail':>6} {'done p50':>9} {'done p90':>9} "
        f"{'dropped':>8} {'delayed':>8}",
    ]
    for r in records:
        q = r["done_at_ms"] or {"p50": -1, "p90": -1}
        lines.append(
            f"{r['plan']['label']:<16} {r['availability']:>6.2f} "
            f"{q['p50']:>9} {q['p90']:>9} "
            f"{sum(r['dropped_by_fault']):>8} {sum(r['delayed_by_fault']):>8}"
        )
    report = "\n".join(lines) + "\n"
    with open(os.path.join(out_dir, "report.txt"), "w") as f:
        f.write(report)
    print(report)

    rec_path = os.path.join(out_dir, "run_records.jsonl")
    RunRecordWriter(rec_path).write(
        {"kind": "fault_sweep", "records": records},
        sim_ms=SIM_MS,
        nodes=net.n_nodes,
        plans=len(plans),
    )

    print(
        json.dumps(
            {
                "ok": True,
                "out_dir": out_dir,
                "plans": len(plans),
                "availability": {
                    r["plan"]["label"]: r["availability"] for r in records
                },
            }
        )
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
