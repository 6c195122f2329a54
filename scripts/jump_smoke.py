"""Jump smoke: crash-heavy fault sweep with batched consensus jumps armed.

The dead-time lever's CI gate (ISSUE 18).  Builds a telemetry- and
fault-armed P2PFlood population, stacks a crash-heavy plan sweep
(control rows plus 20%/40% crashes — the rows go quiet early, so the
consensus jump has real dead time to skip), and asserts:

  1. ZERO digest drift: the jump-armed `run_ms_batched` equals the
     ungated lockstep loop leaf-for-leaf (one blake2b digest over every
     leaf's path/dtype/shape/bytes, compared across the two paths);
  2. efficacy: the armed run's `jumped_ms_frac` > 0 (the census must
     show milliseconds actually skipped, not just a passing gate);
  3. the paired INTERLEAVED off/on walls (the PR-11 noise discipline:
     alternate off/on per repeat so drift lands on both sides) — the
     timing is recorded, never asserted; BENCH_FLOOR.json's `jump`
     block is the documentation channel for the accepted numbers.

Writes `out_dir/jump_smoke.json` (the BENCH artifact CI uploads) and
exits nonzero on any violated assertion.

Usage: python scripts/jump_smoke.py [out_dir]   (default ./jump_smoke)
"""

from __future__ import annotations

import hashlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax  # noqa: E402

import numpy as np  # noqa: E402

from wittgenstein_tpu.engine.core import replicate_state  # noqa: E402
from wittgenstein_tpu.faults import FaultConfig, FaultPlan  # noqa: E402
from wittgenstein_tpu.faults.plan import lower_plans  # noqa: E402
from wittgenstein_tpu.protocols.p2pflood import P2PFloodParameters  # noqa: E402
from wittgenstein_tpu.protocols.p2pflood_batched import make_p2pflood  # noqa: E402
from wittgenstein_tpu.telemetry import counters  # noqa: E402
from wittgenstein_tpu.telemetry.state import TelemetryConfig  # noqa: E402

SIM_MS = 800
SEED0 = 0
REPLICAS_PER_PLAN = 2
AB_REPEATS = 3


def state_digest(state) -> str:
    """blake2b over every leaf's flatten-order index, dtype, shape and
    bytes — any single-bit drift between the two paths changes it."""
    h = hashlib.blake2b(digest_size=16)
    for i, leaf in enumerate(jax.tree_util.tree_leaves(state)):
        a = np.asarray(leaf)
        h.update(f"{i}|{a.dtype}|{a.shape}|".encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def build_sweep():
    """Telemetry- and fault-armed p2pflood, stacked over a crash-heavy
    plan sweep (the sparse-traffic scenario the jump lever targets)."""
    net, state = make_p2pflood(P2PFloodParameters(), capacity=2048, seed=SEED0)
    net, state = net.with_telemetry(state, TelemetryConfig())
    net, state = net.with_faults(state, FaultConfig())
    live = np.flatnonzero(~np.asarray(state.down))
    plans = [
        None,  # fault-free control rows
        FaultPlan("crash20@100").crash(live[: len(live) // 5], at=100),
        FaultPlan("crash40@50").crash(live[: (2 * len(live)) // 5], at=50),
    ]
    n_rep = len(plans) * REPLICAS_PER_PLAN
    fs = lower_plans(
        [p for p in plans for _ in range(REPLICAS_PER_PLAN)],
        net.n_nodes,
        net.protocol.n_msg_types(),
    )
    batched = replicate_state(
        state, n_rep, seeds=np.arange(SEED0, SEED0 + n_rep, dtype=np.int64)
    )._replace(faults=fs)
    return net, batched, [p.describe()["label"] if p else "control" for p in plans]


def main() -> int:
    out_dir = sys.argv[1] if len(sys.argv) > 1 else os.path.join(ROOT, "jump_smoke")
    os.makedirs(out_dir, exist_ok=True)

    net, batched, labels = build_sweep()
    jnet = net.with_batched_jumps(True)

    off_run = jax.jit(lambda s: net.run_ms_batched(s, SIM_MS))
    on_run = jax.jit(lambda s: jnet.run_ms_batched(s, SIM_MS))
    base = jax.block_until_ready(off_run(batched))
    armed = jax.block_until_ready(on_run(batched))

    # 1. zero digest drift, leaf for leaf (the digest is the headline,
    # the per-leaf compare is the diagnosable version of the same claim)
    for i, (a, b) in enumerate(
        zip(jax.tree_util.tree_leaves(base), jax.tree_util.tree_leaves(armed))
    ):
        assert np.array_equal(np.asarray(a), np.asarray(b)), (
            f"jump-armed run diverged from the ungated loop at leaf {i}"
        )
    d_off, d_on = state_digest(base), state_digest(armed)
    assert d_off == d_on, f"digest drift: {d_off} != {d_on}"

    # 2. efficacy: the census must show real skipped milliseconds
    cnt = counters(jnet, armed)
    frac = cnt["loop"]["jumped_ms_frac"]
    assert frac > 0, f"jumps armed but jumped_ms_frac={frac} (nothing skipped)"

    # 3. paired interleaved off/on walls (recorded, not asserted)
    offs, ons = [], []
    for _ in range(AB_REPEATS):
        t0 = time.perf_counter()
        jax.block_until_ready(off_run(batched))
        offs.append(round(time.perf_counter() - t0, 3))
        t0 = time.perf_counter()
        jax.block_until_ready(on_run(batched))
        ons.append(round(time.perf_counter() - t0, 3))

    rec = {
        "schema": "witt-jump-smoke/v1",
        "ok": True,
        "scenario": {
            "protocol": "p2pflood",
            "nodes": net.n_nodes,
            "sim_ms": SIM_MS,
            "plans": labels,
            "replicas_per_plan": REPLICAS_PER_PLAN,
            "rows": int(np.asarray(batched.time).size),
        },
        "digest": d_on,
        "jumped_ms_frac": frac,
        "loop": cnt["loop"],
        "paired_wall_s": {"off": offs, "on": ons},
        "speedup": round(min(offs) / max(min(ons), 1e-9), 3),
        "host_cpus": os.cpu_count(),
    }
    with open(os.path.join(out_dir, "jump_smoke.json"), "w") as f:
        json.dump(rec, f, indent=2, sort_keys=True)
    print(json.dumps(rec))
    return 0


if __name__ == "__main__":
    sys.exit(main())
