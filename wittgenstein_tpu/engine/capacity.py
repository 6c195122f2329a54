"""Telemetry-sized message-store capacities (the density war's second
front, next to engine.density's narrow dtypes).

The engine's wheel/overflow defaults (core.BatchedNetwork.__init__) are
sized for "never drop", which at flagship scale means paying for slots
no run ever fills.  This module is the contract between the measured
occupancy high-water marks and the knobs the constructors accept:

  scripts/density_autotune.py   probes each registered protocol config
                                with run_ms() (the census's wheel and
                                lane peaks) plus the Handel candidate-
                                slot occupancy probe, and writes the
                                results into CAPACITY.json at the repo
                                root.
  engine/capacity.py (here)     loads/validates that table and turns an
                                entry into constructor overrides
                                (sized_overrides()).
  state.dropped                 remains the RUNTIME guard: a sized run
                                that ever hits its ceiling shows up as a
                                nonzero dropped counter, and the
                                capacity regression test fails.

Sizing rule: sized = max(floor, ceil(hwm * margin)) rounded up to a
multiple of 8 (friendly to the bitset word layout and vector lanes).
The margin (default 1.5x) covers seed-to-seed occupancy variance; the
probe records which seeds/horizon produced the HWM so a stale table is
auditable.  Handel's cand_slots uses hwm + 1 instead — the top-K buffer
is re-sorted every tick, so any K' strictly above the post-tick
occupancy HWM is bit-identical to the engine default (see
docs/density.md); one spare slot is the guard band.
"""

from __future__ import annotations

import json
import math
import os
from dataclasses import dataclass, field
from typing import Dict, Optional

CAPACITY_SCHEMA = "witt-capacity/v1"
CAPACITY_BASENAME = "CAPACITY.json"

# seed-to-seed occupancy variance guard for wheel/overflow sizing
DEFAULT_MARGIN = 1.5
# never size below these, however empty the probe ran: the engine
# rejects degenerate stores and tiny pads cost nothing
MIN_WHEEL_SLOTS = 8
MIN_OVERFLOW = 16


def capacity_path(root: Optional[str] = None) -> str:
    """Repo-root CAPACITY.json (root defaults to the package parent)."""
    if root is None:
        root = os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        )
    return os.path.join(root, CAPACITY_BASENAME)


def size_from_hwm(
    hwm: int, margin: float = DEFAULT_MARGIN, floor: int = MIN_OVERFLOW
) -> int:
    """hwm -> capacity: margin, floor, then round up to a multiple of 8."""
    sized = max(int(floor), int(math.ceil(int(hwm) * float(margin))))
    return -(-sized // 8) * 8


@dataclass(frozen=True)
class CapacityEntry:
    """One probed (protocol, n_nodes) config from CAPACITY.json."""

    protocol: str
    n_nodes: int
    hwms: Dict[str, int]
    sized: Dict[str, int]
    margin: float = DEFAULT_MARGIN
    probe: Dict = field(default_factory=dict)
    dropped: int = 0

    @property
    def key(self) -> str:
        return f"{self.protocol}@{self.n_nodes}"

    def to_json(self) -> dict:
        return {
            "protocol": self.protocol,
            "n_nodes": self.n_nodes,
            "hwms": dict(self.hwms),
            "sized": dict(self.sized),
            "margin": self.margin,
            "probe": dict(self.probe),
            "dropped": self.dropped,
        }


def _entry_problems(key: str, e: dict) -> list:
    """Schema/consistency findings for one table entry (strings)."""
    out = []
    for f in ("protocol", "n_nodes", "hwms", "sized"):
        if f not in e:
            out.append(f"{key}: missing field {f!r}")
    if out:
        return out
    if key != f"{e['protocol']}@{e['n_nodes']}":
        out.append(f"{key}: key does not match protocol@n_nodes fields")
    if int(e.get("dropped", 0)) != 0:
        out.append(
            f"{key}: probe recorded dropped={e['dropped']} — sized run"
            " lost messages; re-probe with larger capacity"
        )
    margin = float(e.get("margin", DEFAULT_MARGIN))
    hwms, sized = e["hwms"], e["sized"]
    # every sized wheel/overflow knob must still satisfy the margin rule
    # against its recorded HWM (a hand-edited number fails loudly)
    for knob, hwm_key, floor in (
        ("wheel_slots", "wheel_fill_hwm", MIN_WHEEL_SLOTS),
        ("overflow_capacity", "overflow_hwm", MIN_OVERFLOW),
    ):
        if knob in sized:
            if hwm_key not in hwms:
                out.append(f"{key}: sized {knob} without recorded {hwm_key}")
            elif int(sized[knob]) < size_from_hwm(
                int(hwms[hwm_key]), margin, floor
            ):
                out.append(
                    f"{key}: sized {knob}={sized[knob]} below the margin"
                    f" rule for {hwm_key}={hwms[hwm_key]} (margin {margin})"
                )
    if "cand_slots" in sized:
        if "cand_occ_hwm" not in hwms:
            out.append(f"{key}: sized cand_slots without cand_occ_hwm")
        elif int(sized["cand_slots"]) < int(hwms["cand_occ_hwm"]) + 1:
            out.append(
                f"{key}: cand_slots={sized['cand_slots']} leaves no guard"
                f" slot over cand_occ_hwm={hwms['cand_occ_hwm']}"
                " (bit-identity needs occupancy < K)"
            )
    return out


def validate_table(doc: dict) -> list:
    """All schema problems in a loaded CAPACITY.json doc ([] = valid)."""
    if not isinstance(doc, dict):
        return ["capacity table is not a JSON object"]
    if doc.get("schema") != CAPACITY_SCHEMA:
        return [
            f"schema is {doc.get('schema')!r}, expected {CAPACITY_SCHEMA!r}"
        ]
    entries = doc.get("entries")
    if not isinstance(entries, dict):
        return ["entries missing or not an object"]
    problems = []
    for key, e in entries.items():
        problems.extend(_entry_problems(key, e))
    return problems


def load_capacity(root: Optional[str] = None) -> Optional[dict]:
    """Parsed CAPACITY.json, or None when absent/unreadable/invalid.
    Callers treat None as "no table": constructors keep their defaults,
    so a deleted table degrades to the safe over-provisioned sizing."""
    path = capacity_path(root)
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return None
    return doc if not validate_table(doc) else None


def lookup(
    table: Optional[dict], protocol: str, n_nodes: int
) -> Optional[CapacityEntry]:
    """The CapacityEntry for protocol@n_nodes, or None."""
    if not table:
        return None
    e = table.get("entries", {}).get(f"{protocol}@{int(n_nodes)}")
    if e is None:
        return None
    return CapacityEntry(
        protocol=e["protocol"],
        n_nodes=int(e["n_nodes"]),
        hwms={k: int(v) for k, v in e["hwms"].items()},
        sized={k: int(v) for k, v in e["sized"].items()},
        margin=float(e.get("margin", DEFAULT_MARGIN)),
        probe=dict(e.get("probe", {})),
        dropped=int(e.get("dropped", 0)),
    )


ENGINE_KNOBS = ("wheel_slots", "overflow_capacity")
PROTOCOL_KNOBS = ("cand_slots",)


def sized_overrides(
    entry: Optional[CapacityEntry],
) -> Dict[str, Dict[str, int]]:
    """Split an entry's sized knobs into the two constructor surfaces:
    {"engine": {wheel_slots/overflow_capacity...},
     "protocol": {cand_slots...}}.  Empty dicts when entry is None —
    callers can always ** the result."""
    out: Dict[str, Dict[str, int]] = {"engine": {}, "protocol": {}}
    if entry is None:
        return out
    for k, v in entry.sized.items():
        if k in ENGINE_KNOBS:
            out["engine"][k] = int(v)
        elif k in PROTOCOL_KNOBS:
            out["protocol"][k] = int(v)
    return out
