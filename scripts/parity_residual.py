"""Decompose the Handel CDF parity residual.

Measures P10/P50/P90 of time-to-threshold (done_at) for the oracle DES
and the batched engine with ENOUGH samples that quantile sampling noise
is <1%, then reports the remaining relative gap per quantile with a
cluster-bootstrap confidence band (done_at is correlated within a run,
so resampling is over RUNS, not nodes).

Usage:
  python scripts/parity_residual.py [--nodes 64] [--oracle-runs 64]
      [--replicas 128] [--run-ms 2500] [--json OUT]
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

jax.config.update("jax_platforms", "cpu")  # parity is a CPU-side measurement

import numpy as np  # noqa: E402

QS = (10, 50, 90)


def cluster_quantiles(done_by_run, n_boot=2000, seed=0):
    """Quantiles over the pooled population + bootstrap SE resampling
    whole runs (the within-run correlation makes per-node bootstrap
    overconfident by ~sqrt(nodes))."""
    rng = np.random.default_rng(seed)
    pooled = np.concatenate(done_by_run)
    q = np.percentile(pooled, QS)
    runs = len(done_by_run)
    boots = np.empty((n_boot, len(QS)))
    for b in range(n_boot):
        pick = rng.integers(0, runs, runs)
        boots[b] = np.percentile(np.concatenate([done_by_run[i] for i in pick]), QS)
    return q, boots.std(axis=0)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=64)
    ap.add_argument("--threshold", type=int, default=None)
    ap.add_argument("--oracle-runs", type=int, default=64)
    ap.add_argument("--replicas", type=int, default=128)
    ap.add_argument("--run-ms", type=int, default=2500)
    ap.add_argument("--json", default=None)
    args = ap.parse_args()

    from test_handel_batched import batched_done_at, make_params, oracle_done_at

    thr = args.threshold if args.threshold is not None else args.nodes - 1
    p = make_params(node_count=args.nodes, threshold=thr)

    t0 = time.time()
    o_runs = []
    for seed in range(args.oracle_runs):
        o_runs.append(oracle_done_at(p, [seed], args.run_ms))
    o_t = time.time() - t0
    oq, ose = cluster_quantiles(o_runs)

    t0 = time.time()
    b = batched_done_at(p, args.replicas, args.run_ms)
    b_t = time.time() - t0
    b_runs = list(b.reshape(args.replicas, -1))
    bq, bse = cluster_quantiles(b_runs)

    rel = (bq - oq) / oq
    noise = np.sqrt(ose**2 + bse**2) / oq  # 1-sigma noise on rel
    rec = {
        "nodes": args.nodes,
        "threshold": thr,
        "oracle_runs": args.oracle_runs,
        "replicas": args.replicas,
        "quantiles": list(QS),
        "oracle_q_ms": [round(float(x), 1) for x in oq],
        "oracle_se_rel": [round(float(x), 4) for x in ose / oq],
        "batched_q_ms": [round(float(x), 1) for x in bq],
        "batched_se_rel": [round(float(x), 4) for x in bse / bq],
        "rel_gap": [round(float(x), 4) for x in rel],
        "rel_noise_1sigma": [round(float(x), 4) for x in noise],
        "oracle_s": round(o_t, 1),
        "batched_s": round(b_t, 1),
    }
    print(json.dumps(rec, indent=2))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(rec, f, indent=2)


if __name__ == "__main__":
    main()
