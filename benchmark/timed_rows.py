"""The timed rows themselves against the plain reference.

The twin (twin.py) holds what the protocol computes by the time it
completes, at a node count that completes.  This holds the rows the
window timed, at the width and the batch it timed them at: the state
the last chunk of the window left, read once the window has closed.

Two numbers for every row, each printed beside its limit:

- the mean number of messages a live node has sent by the rows' time,
  |program - reference| / reference, the reference being the
  discrete-event simulator under benchmark/reference at the
  configuration's OWN node count, one run from the first row's seed to
  the same simulated time.  At thousands of nodes that mean moves by
  less than 1% from seed to seed, so one reference run stands for every
  row (`timed_rows.calibration` in the configuration file);
- conservation: every message a live node has sent is either counted
  for its receiver or counted in a state leaf that the configuration
  names (`timed_rows.conservation`), exactly: sent total == received
  total + the named leaves' sums, limit 0.  The aggregation protocols
  count a message for its receiver when it is sent, so with no node
  down no leaf is named and a row's received total equals its sent
  total.  A configuration with nodes down names the program's count of
  sends whose receiver could not take them; a protocol on the message
  store, which counts at delivery, names the store's occupancy.  A
  scatter that loses an update, or a row whose send path ran on part of
  its nodes, breaks it, by as little as 1.

What it cannot see: what the messages carry.  By the time an R=8 window
ends (30 simulated ms) a tenth of the nodes have received anything, so
a fault in the content of the planes shows in the twin, not here.
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

from cells import BENCH_DIR, BenchmarkFileError, build_params, resolve


def reference_sent(config: dict, seed: int, stops, overrides: dict | None = None) -> list:
    """Mean messages sent by a live node at each simulated time of
    `stops` (ascending), over one reference run from `seed` at the
    configuration's own node count.  `overrides` make the controls."""
    ref_dir = os.path.join(BENCH_DIR, "reference")
    if ref_dir not in sys.path:
        sys.path.insert(0, ref_dir)
    ref = config["reference"]
    proto = resolve(ref["protocol"])(build_params(config, ref["params_class"], overrides))
    proto.network().rd.set_seed(seed)
    proto.init()
    out, now = [], 0
    for t in stops:
        proto.network().run_ms(t - now)
        now = t
        out.append(float(np.mean([node.msg_sent for node in proto.network().live_nodes()])))
    return out


def named_leaves(config: dict, state) -> list:
    """(path, leaf, per node?) for every state leaf that
    `timed_rows.conservation.received_plus` names: `per_node` paths are
    leaves of `down`'s own shape, masked by `~down` as `msg_sent` is;
    `whole` paths are summed whole, row by row.  A path is dotted, through
    fields of the state and keys of `state.proto`.  A path that reaches
    nothing, or a leaf that cannot be counted, is an error here, before
    the window, never a sum of 0."""
    plus = config.get("timed_rows", {}).get("conservation", {}).get("received_plus", {})
    if set(plus) - {"per_node", "whole"}:
        raise BenchmarkFileError(f"timed_rows.conservation.received_plus: {sorted(plus)} "
                                 "are not all of per_node, whole")
    out = []
    for kind in ("per_node", "whole"):
        for path in plus.get(kind, []):
            where = f"timed_rows.conservation.received_plus.{kind} {path!r}"
            leaf = state
            for part in path.split("."):
                if isinstance(leaf, dict) and part in leaf:
                    leaf = leaf[part]
                elif part in getattr(leaf, "_fields", ()):
                    leaf = getattr(leaf, part)
                else:
                    raise BenchmarkFileError(f"{where}: the state has no {part!r} there")
            dtype, shape = getattr(leaf, "dtype", None), getattr(leaf, "shape", None)
            if dtype is None or not (dtype == bool or np.issubdtype(dtype, np.integer)):
                raise BenchmarkFileError(f"{where}: not a leaf of whole numbers or booleans")
            rows = state.down.shape[:-1]
            fits = shape == state.down.shape if kind == "per_node" else shape[: len(rows)] == rows
            if not fits:
                raise BenchmarkFileError(f"{where}: shape {shape} against down's {state.down.shape}")
            out.append((path, leaf, kind == "per_node"))
    return out


def program_counts(state, config: dict | None = None) -> dict:
    """Per row of a batched state: its time, the mean messages sent by a
    live node, the sent and received totals, and the sum of every leaf
    the configuration names beside the received total (a boolean leaf
    counts its true entries)."""
    live = ~np.asarray(state.down)
    sent = np.where(live, np.asarray(state.msg_sent), 0).astype(np.int64)
    received = np.where(live, np.asarray(state.msg_received), 0).astype(np.int64)
    rows = live.shape[:-1]
    plus = {}
    for path, leaf, per_node in named_leaves(config or {}, state):
        leaf = np.asarray(leaf).astype(np.int64)
        leaf = np.where(live, leaf, 0) if per_node else leaf.reshape(*rows, -1)
        plus[path] = leaf.sum(-1).tolist()
    return {
        "time_ms": np.asarray(state.time).reshape(-1).tolist(),
        "sent_mean": (sent.sum(-1) / np.maximum(1, live.sum(-1))).tolist(),
        "sent_total": sent.sum(-1).tolist(),
        "received_total": received.sum(-1).tolist(),
        "received_plus": plus,
    }


def compare(counts: dict, reference_mean: float, limit: float) -> dict:
    """The comparison that decides this part of `correct`."""
    gaps = [abs(s - reference_mean) / reference_mean for s in counts["sent_mean"]]
    plus = counts["received_plus"]
    lost = [abs(s - r - sum(leaf[row] for leaf in plus.values()))
            for row, (s, r) in enumerate(zip(counts["sent_total"], counts["received_total"]))]
    named = {"sent_total": counts["sent_total"], "received_total": counts["received_total"],
             "received_plus": plus} if plus else {}
    return {
        "program_sent_mean": counts["sent_mean"],
        "reference_sent_mean": reference_mean,
        "sent_rel_gap": gaps,
        "sent_rel_gap_worst": max(gaps),
        "sent_rel_gap_limit": limit,
        **named,  # what was added to what, where the configuration names leaves
        "sent_minus_received": lost,
        "sent_minus_received_limit": 0,
        "ok": bool(max(gaps) <= limit and max(lost) == 0),
    }


def limit_at(config: dict, t_ms: int) -> float:
    """The limit for the rows' time: `timed_rows.limits` is a list of
    [up to simulated ms, limit], ascending; past the last the last holds."""
    limits = config["timed_rows"]["limits"]
    return next((lim for upto, lim in limits if t_ms <= upto), limits[-1][1])


def check(config: dict, seed: int, counts: dict, overrides: dict | None = None) -> dict:
    """Hold `counts` (`program_counts` of the window's last output)
    against one reference run from `seed` to the rows' simulated time.
    `overrides` go to the REFERENCE's parameters: a rehearsal's node
    count, or a control's wrong parameter."""
    times = sorted(set(counts["time_ms"]))
    t_ms = int(times[0])
    if len(times) != 1 or t_ms <= 0:
        return {"ok": False, "rows_time_ms": times, "why": "the rows are not at one time after 0"}
    t0 = time.perf_counter()
    (reference_mean,) = reference_sent(config, seed, [t_ms], overrides)
    result = compare(counts, reference_mean, limit_at(config, t_ms))
    result.update(rows_time_ms=t_ms, reference_seed=seed, reference_s=time.perf_counter() - t0)
    return result
