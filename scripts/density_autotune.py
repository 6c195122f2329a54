"""Per-protocol capacity autotuner — writes CAPACITY.json.

The density war's sizing probe (engine.capacity is the contract it
feeds).  Two instruments:

  1. Generic message store: every registered generic-engine protocol is
     run through net.run_ms() and the wheel/overflow high-water marks
     are read from the work census it counts on every run
     (`census.wheel_fill_peak`, `.lane_live_peak`: the store's fill after
     each executed step's inserts, the only moment occupancy can peak,
     and the fill the state began with).  Sized knobs follow
     engine.capacity.size_from_hwm (margin + floor + x8 rounding).
     Flat-mode protocols (wheel_rows=0: the Handel family) get only an
     overflow_capacity sizing — their overflow lane IS the store.
  2. Handel candidate slots: the flagship config's post-tick candidate
     occupancy HWM over (node, level).  The K-slot buffer is re-sorted
     every tick, so any K' strictly above that HWM is bit-identical to
     the engine default (docs/density.md derives this); sized
     cand_slots = hwm + 1 (one guard slot).

Runs on the CPU backend ALWAYS — occupancy is a simulation fact, not a
wall-clock one.

Usage:
  python scripts/density_autotune.py            # full probe -> CAPACITY.json
  python scripts/density_autotune.py --smoke D  # short-horizon subset -> D/
  python scripts/density_autotune.py --check    # CI gate: no probing
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

PROBE_MS = 400
# the flagship cand-occupancy probe covers the budget's full horizon so
# the HWM sees the whole active phase, not a truncated prefix
FLAGSHIP_MS = 1000
SMOKE_MS = 60
FLAGSHIP_NODES = 4096
SMOKE_FLAGSHIP_NODES = 256
# protocols worth probing in --smoke (one wheel-mode, one flat-mode)
SMOKE_NAMES = ("pingpong", "p2pflood")


def probe_store(entry, probe_ms: int):
    """run_ms over one registry entry, its census's peaks -> CapacityEntry."""
    import jax.numpy as jnp

    from wittgenstein_tpu.engine.capacity import (
        MIN_OVERFLOW,
        MIN_WHEEL_SLOTS,
        CapacityEntry,
        DEFAULT_MARGIN,
        size_from_hwm,
    )

    net, state = entry.factory()
    out = jax.block_until_ready(net.run_ms(state, probe_ms))
    fill = int(out.census.wheel_fill_peak)
    ovf = int(out.census.lane_live_peak)
    dropped = int(jnp.max(out.dropped))
    sized = {"overflow_capacity": size_from_hwm(ovf, floor=MIN_OVERFLOW)}
    if not net.flat:
        sized["wheel_slots"] = size_from_hwm(fill, floor=MIN_WHEEL_SLOTS)
    return CapacityEntry(
        protocol=entry.name,
        n_nodes=int(net.n_nodes),
        hwms={"wheel_fill_hwm": fill, "overflow_hwm": ovf},
        sized=sized,
        margin=DEFAULT_MARGIN,
        probe={
            "sim_ms": probe_ms,
            "mode": "flat" if net.flat else "wheel",
            "defaults": {
                "wheel_slots": int(net.wheel_slots),
                "overflow_capacity": int(net.overflow_capacity),
            },
            "source": "registry factory",
        },
        dropped=dropped,
    )


def probe_handel_cand(node_ct: int, probe_ms: int):
    """Flagship Handel candidate-occupancy HWM -> CapacityEntry with the
    sized cand_slots knob (hwm + 1 guard slot)."""
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from wittgenstein_tpu.engine.capacity import CapacityEntry
    from wittgenstein_tpu.protocols.handel_batched import make_handel
    from wittgenstein_tpu.scenarios.handel_scenarios import flagship_params

    net, state = make_handel(flagship_params(node_ct))
    proto = net.protocol
    n, L, K = proto.n_nodes, proto.n_levels, proto.CAND_SLOTS
    # empty slots hold the dtype's own sentinel (engine.density maps
    # INT32_MAX to the narrow max), so read it off the live leaf
    sent = int(np.iinfo(np.dtype(state.proto["cand_rank"].dtype)).max)

    @jax.jit
    def run(state):
        def body(_, carry):
            s, hwm = carry
            s = net.step(s)
            occ = jnp.sum(
                s.proto["cand_rank"].reshape(n, L - 1, K) != sent, axis=-1
            )
            return s, jnp.maximum(hwm, jnp.max(occ))

        return lax.fori_loop(0, probe_ms, body, (state, jnp.int32(0)))

    out, hwm = run(state)
    jax.block_until_ready(out)
    hwm = int(hwm)
    return CapacityEntry(
        protocol="handel",
        n_nodes=node_ct,
        hwms={"cand_occ_hwm": hwm},
        sized={"cand_slots": hwm + 1},
        margin=1.0,  # cand_slots uses the +1 guard-slot rule, not margin
        probe={
            "sim_ms": probe_ms,
            "mode": "cand_slots",
            "defaults": {"cand_slots": K},
            "source": "flagship_params",
        },
        dropped=int(jnp.max(out.dropped)),
    )


def check() -> int:
    """CI gate: CAPACITY.json must exist, validate (schema + margin +
    guard-slot rules), and agree with BUDGET.json's recorded cand_slots.
    Deliberately probe-free — staleness is caught by the bit-identity
    and dropped==0 regression tests, not by re-measuring in CI."""
    from wittgenstein_tpu.engine.capacity import (
        capacity_path,
        validate_table,
    )

    path = capacity_path(ROOT)
    try:
        with open(path) as f:
            doc = json.load(f)
    except OSError:
        print(f"{path} missing — run scripts/density_autotune.py",
              file=sys.stderr)
        return 1
    except ValueError as e:
        print(f"{path} unparseable: {e}", file=sys.stderr)
        return 1
    problems = validate_table(doc)
    for p in problems:
        print(f"CAPACITY.json: {p}", file=sys.stderr)
    if problems:
        return 1
    # cross-check the flagship knob actually priced into BUDGET.json
    budget_path = os.path.join(ROOT, "BUDGET.json")
    if os.path.exists(budget_path):
        with open(budget_path) as f:
            budget = json.load(f)
        cfg = budget.get("config", {})
        node_ct = cfg.get("node_count")
        recorded = cfg.get("cand_slots")
        e = doc["entries"].get(f"handel@{node_ct}")
        if recorded is not None and e is not None:
            sized = e["sized"].get("cand_slots")
            if sized != recorded:
                print(
                    f"BUDGET.json prices cand_slots={recorded} but"
                    f" CAPACITY.json sizes handel@{node_ct} at {sized} —"
                    " regenerate scripts/budget_report.py",
                    file=sys.stderr,
                )
                return 1
    print(f"CAPACITY.json valid: {len(doc['entries'])} entries")
    return 0


def main() -> None:
    if "--check" in sys.argv:
        raise SystemExit(check())
    smoke = "--smoke" in sys.argv
    from wittgenstein_tpu.core.registries import registry_batched_protocols
    from wittgenstein_tpu.engine.capacity import (
        CAPACITY_SCHEMA,
        capacity_path,
    )

    probe_ms = SMOKE_MS if smoke else PROBE_MS
    flag_ms = SMOKE_MS if smoke else FLAGSHIP_MS
    flag_n = SMOKE_FLAGSHIP_NODES if smoke else FLAGSHIP_NODES
    entries = {}
    for entry in registry_batched_protocols.entries():
        if not entry.contract_checks:
            continue  # not a generic-engine kernel; no store to size
        if smoke and entry.name not in SMOKE_NAMES:
            continue
        t0 = time.perf_counter()
        cap = probe_store(entry, probe_ms)
        entries[cap.key] = cap.to_json()
        print(
            f"{cap.key}: {cap.probe['mode']} hwms={cap.hwms}"
            f" sized={cap.sized} dropped={cap.dropped}"
            f" ({time.perf_counter() - t0:.1f}s)",
            file=sys.stderr,
        )
    t0 = time.perf_counter()
    cap = probe_handel_cand(flag_n, flag_ms)
    entries[cap.key] = cap.to_json()
    print(
        f"{cap.key}: cand_occ_hwm={cap.hwms['cand_occ_hwm']}"
        f" -> cand_slots={cap.sized['cand_slots']}"
        f" (default {cap.probe['defaults']['cand_slots']},"
        f" {time.perf_counter() - t0:.1f}s)",
        file=sys.stderr,
    )
    doc = {
        "schema": CAPACITY_SCHEMA,
        "generated_by": "scripts/density_autotune.py",
        "recorded": time.strftime("%Y-%m-%d"),
        "backend": jax.default_backend(),
        "entries": dict(sorted(entries.items())),
    }
    if smoke:
        i = sys.argv.index("--smoke")
        outdir = sys.argv[i + 1] if len(sys.argv) > i + 1 else "capacity_smoke"
        os.makedirs(outdir, exist_ok=True)
        path = os.path.join(outdir, "capacity_smoke.json")
        doc["note"] = (
            "SMOKE tier: short horizon, subset of protocols; the"
            " committed CAPACITY.json is the full-probe artifact"
        )
    else:
        path = capacity_path(ROOT)
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")
    print(f"wrote {path}")


if __name__ == "__main__":
    main()
