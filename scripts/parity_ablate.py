"""Ablate candidate sources of the batched-vs-oracle CDF residual.

Knobs (combinable):
  --depth D      channel depth (default 8): displacement-loss hypothesis
  --replicas R   batched replicas
Prints quantiles + displaced counts (and the fast path's commit rounds and
landing high-water mark) vs the SAME oracle population used by
scripts/parity_residual.py (oracle side re-run here for self-containment;
cache it with --oracle-json to iterate on batched-only changes).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tests"))

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

QS = (10, 50, 90)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=64)
    ap.add_argument("--depth", type=int, default=8)
    ap.add_argument("--replicas", type=int, default=64)
    ap.add_argument("--oracle-runs", type=int, default=64)
    ap.add_argument("--run-ms", type=int, default=2500)
    ap.add_argument("--oracle-json", default=None,
                    help="cache file for the oracle population")
    args = ap.parse_args()

    from test_handel_batched import make_params, oracle_done_at

    from wittgenstein_tpu.engine import replicate_state
    from wittgenstein_tpu.protocols import handel_batched as hb

    thr = args.nodes - 1
    p = make_params(node_count=args.nodes, threshold=thr)

    if args.oracle_json and os.path.exists(args.oracle_json):
        oq = np.asarray(json.load(open(args.oracle_json))["oq"])
    else:
        o = np.concatenate(
            [oracle_done_at(p, [s], args.run_ms) for s in range(args.oracle_runs)]
        )
        oq = np.percentile(o, QS)
        if args.oracle_json:
            json.dump({"oq": oq.tolist()}, open(args.oracle_json, "w"))

    hb.BatchedHandel.CHANNEL_DEPTH = args.depth
    net, state = hb.make_handel(p)
    states = replicate_state(state, args.replicas)
    t0 = time.time()
    out = net.run_ms_batched(states, args.run_ms)
    dt = time.time() - t0
    done = np.asarray(out.done_at)[~np.asarray(out.down)]
    assert (done > 0).all()
    bq = np.percentile(done, QS)
    displaced = int(np.asarray(out.proto["displaced"]).sum())
    rcv = int(np.asarray(out.msg_received).sum())
    # the fast path's commit over landing rows: rounds run (summed over
    # ticks and replicas) and the most rows that landed in one tick
    rounds = int(np.asarray(out.proto["commit_rounds"]).sum())
    landing_peak = int(np.asarray(out.proto["landing_peak"]).max())
    print(json.dumps({
        "depth": args.depth,
        "replicas": args.replicas,
        "oracle_q": [round(float(x), 1) for x in oq],
        "batched_q": [round(float(x), 1) for x in bq],
        "rel_gap": [round(float(b - o) / float(o), 4) for b, o in zip(bq, oq)],
        "displaced_total": displaced,
        "displaced_per_replica": round(displaced / args.replicas, 1),
        "received_total": rcv,
        "displaced_over_received": round(displaced / max(rcv, 1), 4),
        "commit_rounds_per_replica": round(rounds / args.replicas, 1),
        "landing_peak": landing_peak,
        "batched_s": round(dt, 1),
    }))


if __name__ == "__main__":
    main()
