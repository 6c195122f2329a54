"""Fidelity against the plain reference, on a twin of the configuration.

A whole simulation at the measured width does not finish inside a run
today (PERF.md section 4), and the reference's answer is a distribution
of completion times, so it can only be held against completed sims.
The twin is the same configuration at a node count that completes:
same factory, same parameters, same entry point (`sharded_run_stats`)
at the cell's own replica count, on the same chip, after the window.  The reference is the discrete-event
simulator under benchmark/reference, which imports nothing of the
program; it runs on the host for as many seeds as the twin has rows.

The numbers compared, each |program - reference| / reference against a
limit written in the configuration file with the readings it was set
from: P10/P50/P90 of `done_at` over all live nodes of all rows
(`tolerance`), and the mean number of messages a live node has sent
at the horizon (`traffic_tolerance`).  The completion times barely move
with the dissemination period or the level timeout when no node is down;
the traffic does, so between them they hold both what the protocol
computes and what its send path sends.
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

from cells import BENCH_DIR, build_params, resolve

QUANTILES = (10, 50, 90)


def row_seeds(seed: int, rows: int, start: int = 0) -> list:
    """Row seeds `seed+start ... seed+start+rows-1`, folded into what an
    int32 leaf holds (the driver's seeds pass 2**31)."""
    return [(seed + start + i) % (2**31 - 1) for i in range(rows)]


def reference_run(config: dict, overrides: dict, seeds, horizon_ms: int):
    """(`done_at` of every live node, mean messages sent by a live
    node) over one reference run per seed."""
    ref_dir = os.path.join(BENCH_DIR, "reference")
    if ref_dir not in sys.path:
        sys.path.insert(0, ref_dir)
    ref = config["reference"]
    done, sent = [], []
    for seed in seeds:
        proto = resolve(ref["protocol"])(build_params(config, ref["params_class"], overrides))
        proto.network().rd.set_seed(seed)
        proto.init()
        proto.network().run_ms(horizon_ms)
        live = proto.network().live_nodes()
        done += [node.done_at for node in live]
        sent += [node.msg_sent for node in live]
    return np.asarray(done), float(np.mean(sent))


def program_run(config: dict, overrides: dict, seeds, horizon_ms: int, batch: int, step=None):
    """The same two readings from every row of the twin, run through the
    measured entry point `batch` rows at a time: the measured cell's own
    replica count, so that the twin's program has the cell's batch axis.
    `step` replaces that entry point in the tests that break it."""
    import jax
    from wittgenstein_tpu.engine import replicate_state
    from wittgenstein_tpu.parallel.replica_shard import sharded_run_stats

    step = step or sharded_run_stats
    params = build_params(config, config["params_class"], overrides)
    net, state = resolve(config["factory"])(params, **config["factory_kwargs"])
    done, sent = [], []
    for k in range(0, len(seeds), batch):
        states = replicate_state(state, batch, seeds=seeds[k : k + batch])
        out, _stats = step(net, states, horizon_ms)
        jax.block_until_ready(out)
        live = ~np.asarray(out.down)
        done.append(np.asarray(out.done_at)[live])
        sent.append(np.asarray(out.msg_sent)[live])
    return np.concatenate(done), float(np.concatenate(sent).mean())


def compare(program, reference, tolerance, traffic_tolerance) -> dict:
    """The comparison that decides the twin's part of `correct`, with
    each number beside its limit.  `program` and `reference` are the
    pairs the two runs return."""
    (program, program_sent), (reference, reference_sent) = program, reference
    traffic_gap = abs(program_sent - reference_sent) / reference_sent
    result = {
        "program_all_done": bool((program > 0).all()),
        "reference_all_done": bool((reference > 0).all()),
        "quantiles": list(QUANTILES),
        "limit": list(tolerance),
    }
    pq = np.percentile(program, QUANTILES)
    rq = np.percentile(reference, QUANTILES)
    rel = np.abs(pq - rq) / rq
    result.update(
        program_q=pq.tolist(),
        reference_q=rq.tolist(),
        rel_gap=rel.tolist(),
        program_msg_sent=program_sent,
        reference_msg_sent=reference_sent,
        msg_sent_rel_gap=traffic_gap,
        msg_sent_limit=traffic_tolerance,
        ok=bool(
            result["program_all_done"]
            and result["reference_all_done"]
            and (rel <= np.asarray(tolerance)).all()
            and traffic_gap <= traffic_tolerance
        ),
    )
    return result


def check(config: dict, seed: int, overrides: dict | None = None, step=None,
          batch: int | None = None) -> dict:
    """Run the twin and its reference for `seed` and compare them.
    `overrides` go to the PROGRAM's side only: the controls use them to
    put a wrong configuration in the program's place.  The twin has
    `twin.replicas` rows or the cell's `batch`, whichever is more, in
    whole batches: the limits were read at `twin.replicas` rows, and
    more rows only steady the quantiles."""
    twin = config["twin"]
    batch = batch or twin["replicas"]
    seeds = row_seeds(seed, batch * -(-twin["replicas"] // batch))
    base = twin["params"]
    t0 = time.perf_counter()
    reference = reference_run(config, base, seeds, twin["horizon_ms"])
    t1 = time.perf_counter()
    program = program_run(
        config, {**base, **(overrides or {})}, seeds, twin["horizon_ms"], batch, step=step
    )
    result = compare(program, reference, twin["tolerance"], twin["traffic_tolerance"])
    result.update(rows=len(seeds), batch=batch, reference_s=t1 - t0,
                  program_s=time.perf_counter() - t1)
    return result
