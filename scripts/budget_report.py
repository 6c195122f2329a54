"""Generate BUDGET.json — the machine-readable feasibility budget.

The chip-independent feasibility arithmetic, materialized from
measurement instead of hand-waving (profiling.budget):

  1. ticks/sim: a telemetry-armed flagship Handel sim runs SIM_MS
     simulated ms with the quiescence early-exit (stop_when_done); the
     in-graph `ticks` counter says how many engine ticks actually
     executed — the empty-ms jump and the early exit make this < SIM_MS.
  2. replicas/chip: the pytree-leaf HBM model (profiling.hbm) on the
     actual init_state() at D=32, cross-checked against the compiled
     run_ms program's memory_analysis().
  3. required tick_µs = R / (21 sims/s * ticks_per_sim) * 1e6.

Runs on the CPU backend ALWAYS (the numbers are state-layout and
tick-count facts, not wall-clock).  XLA cost/memory analysis comes from the CPU compile —
docs/profiling.md records why that is acceptable for bytes and a lower
bound for FLOPs.

Usage:
  python scripts/budget_report.py                 # 4096 -> BUDGET.json
  python scripts/budget_report.py --smoke OUTDIR  # 256-node CI tier
  python scripts/budget_report.py --check         # staleness vs floor
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

jax.config.update("jax_platforms", "cpu")  # CPU always, whatever the env says

SIM_MS = 1000
FLAGSHIP_NODES = 4096
SMOKE_NODES = 256


def measure(node_ct: int) -> dict:
    """Build the flagship config at `node_ct` and measure all three
    budget inputs.  One full run (telemetry-armed, quiescence exit) for
    ticks/sim; one AOT compile of the bare program for cost/memory."""
    import dataclasses

    from wittgenstein_tpu.engine.capacity import load_capacity, lookup
    from wittgenstein_tpu.profiling import budget_from_parts, hbm_report
    from wittgenstein_tpu.profiling.xla_cost import (
        compiled_cost_summary,
        format_bytes,
    )
    from wittgenstein_tpu.protocols.handel_batched import make_handel
    from wittgenstein_tpu.scenarios.handel_scenarios import flagship_params
    from wittgenstein_tpu.telemetry import TelemetryConfig, counters

    # The budget is the TPU feasibility statement, so it prices the
    # program the benchmark's Handel cells build (fuse_step=True) even
    # though it always runs on CPU.
    params = flagship_params(node_ct)
    # telemetry-sized capacity: the autotuned cand_slots for this node
    # count (scripts/density_autotune.py -> CAPACITY.json) — bit-identical
    # by the re-sort argument (docs/density.md), absent table = default K
    cap = lookup(load_capacity(ROOT), "handel", node_ct)
    if cap is not None and "cand_slots" in cap.sized:
        params = dataclasses.replace(params, cand_slots=cap.sized["cand_slots"])
    net, state = make_handel(params, fuse_step=True)

    # (2) the compiled bare program: compile cost + XLA cost/memory.
    # stop_when_done=True: the budget prices a run that exits at
    # quiescence.
    t0 = time.perf_counter()
    compiled = (
        jax.jit(lambda s: net.run_ms(s, SIM_MS, True)).lower(state).compile()
    )
    cost = compiled_cost_summary(compiled, time.perf_counter() - t0)

    # (1) executed ticks under quiescence: telemetry-armed copy (bit-
    # neutral to sim state — simlint SL403 — so ticks match the bare
    # program exactly)
    tnet, tstate = net.with_telemetry(state, TelemetryConfig())
    out = tnet.run_ms(tstate, SIM_MS, True)
    jax.block_until_ready(out)
    summary = counters(tnet, out)
    loop = summary["loop"]
    ticks = int(loop["ticks"])
    if ticks <= 0:
        raise SystemExit(f"measured ticks={ticks} — telemetry loop census broken?")

    # (3) HBM model on the bare state, cross-checked vs memory_analysis
    hbm = hbm_report(state, memory=cost.get("memory"))

    doc = budget_from_parts(
        ticks_per_sim=ticks,
        hbm=hbm,
        measured={
            "compile_s": cost.get("compile_seconds"),
            "xla_cost": cost.get("cost"),
            "xla_memory": cost.get("memory"),
            "backend": jax.default_backend(),
        },
        config={
            "node_count": node_ct,
            "sim_ms": SIM_MS,
            "stop_when_done": True,
            "channel_depth": net.protocol.CHANNEL_DEPTH,
            "cand_slots": net.protocol.CAND_SLOTS,
            "capacity_table": cap is not None,
            "loop": {k: int(v) for k, v in loop.items()},
        },
    )
    doc["recorded"] = time.strftime("%Y-%m-%d")
    print(
        f"ticks/sim={ticks} (of {SIM_MS} simulated ms;"
        f" jumps={loop['jumps']}, jumped_ms={loop['jumped_ms']}),"
        f" replica={format_bytes(hbm['model']['bytes_per_replica'])},"
        f" R={doc['replicas_per_chip']},"
        f" required_tick_us={doc['required_tick_us']}",
        file=sys.stderr,
    )
    return doc


def check() -> int:
    """CI gate: BUDGET.json must exist, parse, not be stale vs
    BENCH_FLOOR.json, and its required_tick_us must still equal the
    arithmetic freshly derived from its own recorded inputs (a
    hand-edited or half-regenerated artifact fails loudly)."""
    from wittgenstein_tpu.profiling import (
        budget_staleness,
        load_budget,
        required_tick_us,
    )

    budget = load_budget(root=ROOT)
    if budget is None:
        print("BUDGET.json missing or unreadable at repo root", file=sys.stderr)
        return 1
    try:
        fresh = required_tick_us(
            int(budget["replicas_per_chip"]),
            float(budget["ticks_per_sim"]),
            float(budget["north_star_sims_per_sec_per_chip"]),
        )
    except (KeyError, TypeError, ValueError) as e:
        print(f"BUDGET.json inputs unusable for re-derivation: {e}",
              file=sys.stderr)
        return 1
    recorded = float(budget.get("required_tick_us", 0.0))
    if abs(fresh - recorded) > 0.01:
        print(
            f"BUDGET.json required_tick_us DRIFTED: recorded {recorded}"
            f" but R/(sims_per_sec*ticks_per_sim)*1e6 ="
            f" {round(fresh, 2)} from its own inputs"
            f" (R={budget['replicas_per_chip']},"
            f" ticks={budget['ticks_per_sim']}) — regenerate"
            " scripts/budget_report.py",
            file=sys.stderr,
        )
        return 1
    floor_path = os.path.join(ROOT, "BENCH_FLOOR.json")
    if not os.path.exists(floor_path):
        print("no BENCH_FLOOR.json — nothing to be stale against")
        return 0
    with open(floor_path) as f:
        floor = json.load(f)
    why = budget_staleness(budget, floor)
    if why:
        print(f"BUDGET.json is STALE: {why}", file=sys.stderr)
        return 1
    print(
        f"BUDGET.json fresh (recorded {budget['recorded']}):"
        f" required_tick_us={budget['required_tick_us']}"
        f" at R={budget['replicas_per_chip']},"
        f" ticks/sim={budget['ticks_per_sim']}"
    )
    return 0


def main() -> None:
    if "--check" in sys.argv:
        raise SystemExit(check())
    smoke = "--smoke" in sys.argv
    if smoke:
        i = sys.argv.index("--smoke")
        outdir = sys.argv[i + 1] if len(sys.argv) > i + 1 else "budget_smoke"
        doc = measure(SMOKE_NODES)
        doc["note"] = (
            f"SMOKE tier ({SMOKE_NODES} nodes): CI exercises the"
            " measurement path; the committed BUDGET.json is the"
            f" {FLAGSHIP_NODES}-node artifact"
        )
        os.makedirs(outdir, exist_ok=True)
        path = os.path.join(outdir, "budget_smoke.json")
    else:
        node_ct = int(sys.argv[1]) if len(sys.argv) > 1 else FLAGSHIP_NODES
        doc = measure(node_ct)
        path = os.path.join(ROOT, "BUDGET.json")
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")
    print(f"wrote {path}")


if __name__ == "__main__":
    main()
