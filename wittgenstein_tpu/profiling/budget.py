"""The chip-independent feasibility budget.

North star: 21 sims/s/chip of flagship Handel at 4096 nodes.  The
budget that implies is pure arithmetic once two quantities are measured
instead of assumed:

  ticks_per_sim   how many engine ticks one sim actually executes —
                  SIM_MS with the naive fixed-horizon loop, LESS when
                  the quiescence exit (stop_when_done / the empty-ms
                  jump) cuts the tail after the last node finishes;
  replicas        the HBM-bounded replicas/chip at the flagship state
                  layout (profiling.hbm model, D=32).

Then, with R replicas advancing in lockstep:

  required_tick_us = R / (21 * ticks_per_sim) * 1e6

i.e. each batched tick may take at most that many microseconds of
wall-clock for the chip to emit 21 finished sims per second.
scripts/budget_report.py materializes this as BUDGET.json.
"""

from __future__ import annotations

import json
import os
from typing import Optional

NORTH_STAR_SIMS_PER_SEC = 21.0
BUDGET_PATH = "BUDGET.json"
BUDGET_SCHEMA = "witt-budget/v1"


def required_tick_us(
    replicas: int,
    ticks_per_sim: float,
    sims_per_sec: float = NORTH_STAR_SIMS_PER_SEC,
) -> float:
    """Max per-tick wall-clock (µs) for `replicas` lockstep replicas to
    yield `sims_per_sec` finished sims per second when one sim runs
    `ticks_per_sim` ticks."""
    if replicas <= 0 or ticks_per_sim <= 0 or sims_per_sec <= 0:
        raise ValueError(
            f"replicas={replicas}, ticks_per_sim={ticks_per_sim},"
            f" sims_per_sec={sims_per_sec} must all be positive"
        )
    return replicas / (sims_per_sec * ticks_per_sim) * 1e6


def budget_from_parts(
    ticks_per_sim: float,
    hbm: dict,
    measured: Optional[dict] = None,
    sims_per_sec: float = NORTH_STAR_SIMS_PER_SEC,
    config: Optional[dict] = None,
) -> dict:
    """Assemble the BUDGET.json document.  `hbm` is
    profiling.hbm.hbm_report() output (its model.replicas bounds R);
    `measured` optionally carries the current measured tick cost so the
    gap to the budget is stated in the artifact itself."""
    replicas = int(hbm["model"]["replicas"])
    tick_us = required_tick_us(replicas, ticks_per_sim, sims_per_sec)
    doc = {
        "schema": BUDGET_SCHEMA,
        "north_star_sims_per_sec_per_chip": sims_per_sec,
        "config": config or {},
        "ticks_per_sim": round(float(ticks_per_sim), 1),
        "hbm": hbm,
        "replicas_per_chip": replicas,
        "required_tick_us": round(tick_us, 2),
        "derivation": (
            f"required_tick_us = replicas / (sims_per_sec * ticks_per_sim)"
            f" * 1e6 = {replicas} / ({sims_per_sec} * {ticks_per_sim:.0f})"
            f" * 1e6"
        ),
    }
    if measured:
        doc["measured"] = measured
        mt = measured.get("tick_us")
        if mt:
            doc["headroom_factor"] = round(tick_us / mt, 3)
    return doc


def load_budget(path: Optional[str] = None, root: Optional[str] = None) -> Optional[dict]:
    """Read BUDGET.json (repo root by default); None when absent or
    unparseable — callers fall back to the fixed-horizon assumption."""
    if path is None:
        root = root or os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        path = os.path.join(root, BUDGET_PATH)
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return None
    if doc.get("schema") != BUDGET_SCHEMA:
        return None
    return doc


def budget_staleness(budget: dict, floor: dict) -> Optional[str]:
    """Why `budget` is stale relative to a BENCH_FLOOR.json doc, or None
    when fresh.  Stale = the floor was recorded after the budget (a
    perf-moving PR re-recorded the floor without regenerating the
    budget), or the budget has no timestamp at all.  The two documents
    deliberately have different geometries — the floor guards the 256x4
    CPU rung, the budget states the 4096 chip target — so only the
    recorded dates are compared (ISO dates order lexicographically)."""
    b_rec = budget.get("recorded")
    f_rec = floor.get("recorded")
    if not b_rec:
        return "budget has no 'recorded' timestamp"
    if f_rec and str(b_rec) < str(f_rec):
        return (
            f"budget recorded {b_rec} predates BENCH_FLOOR.json"
            f" recorded {f_rec} — regenerate scripts/budget_report.py"
        )
    return None
