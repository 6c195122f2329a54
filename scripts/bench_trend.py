"""Bench trajectory: the committed BENCH_r*.json rounds as one
machine-readable perf trend, with a CI regression gate.

Every round's BENCH_rNN.json holds the bench harness's stdout tail —
sometimes a clean ``parsed`` record, sometimes a truncated JSON record
buried after XLA warning spew.  This script recovers what is
recoverable from each round (sims/s, vs_baseline, config, compile/run
seconds), derives µs/tick where the inputs exist (needs a
ticks-per-sim census for the round's node count — BUDGET.json carries
one for its committed config), attaches the BUDGET.json HBM model
(MiB/replica) as the capacity reference, folds in the serving-fleet
benchmark (BENCH_SERVE.json — sims/s, queue-latency quantiles, wave
width/speedup, written by scripts/serve_loadgen.py), and emits the
whole trajectory as JSON.

``--check`` is the perf-trend gate (tier1.yml): it FAILS when the
newest round comparable to BENCH_FLOOR.json (same node_count +
n_replicas, a value actually recovered) falls below the floor.  The
floor file is the documentation channel for accepted regressions — its
note records why the current level is the accepted one and its
re-record policy (±6% run-to-run spread on the 1-core box; engine
rewrites re-anchor it).  A >10% drop between consecutive rounds is
reported in the trajectory (``regressions``) but only fails the gate
when the newer round ALSO breaks the floor: a drop the floor file
absorbs is a documented regression, a drop below the floor is not.
The gate also refuses a committed BENCH_SERVE.json that failed or
whose ``alerts`` block shows ANY SLO alert (the serve benchmark is
fault-free by construction — an alert there is a regression or noise).

Usage:
  python scripts/bench_trend.py [-o trend.json]
  python scripts/bench_trend.py --check [-o trend.json]
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: consecutive-round drop worth flagging in the trajectory
REGRESSION_FRAC = 0.10


def _extract_record(tail: str):
    """Best-effort recovery of the LAST bench JSON record in a stdout
    tail.  Tries json.loads at every '{"metric"' occurrence (records
    may be truncated mid-object — raw_decode fails there, so fall back
    to field-level regex on the remainder)."""
    best = None
    for m in re.finditer(r'\{"metric"', tail):
        chunk = tail[m.start():]
        try:
            best = json.JSONDecoder().raw_decode(chunk)[0]
            continue
        except json.JSONDecodeError:
            pass
        # truncated record: scrape the scalar fields individually
        rec = {}
        for key, rx, conv in (
            ("metric", r'"metric":\s*"([^"]+)"', str),
            ("value", r'"value":\s*([0-9.eE+-]+)', float),
            ("vs_baseline", r'"vs_baseline":\s*([0-9.eE+-]+)', float),
            ("compile_s", r'"compile_s":\s*([0-9.eE+-]+)', float),
            ("run_s", r'"run_s":\s*([0-9.eE+-]+)', float),
            ("node_count", r'"node_count":\s*([0-9]+)', int),
            ("n_replicas", r'"n_replicas":\s*([0-9]+)', int),
            ("sim_ms", r'"sim_ms":\s*([0-9]+)', int),
            ("chunk_ms", r'"chunk_ms":\s*([0-9]+)', int),
            ("jumped_ms_frac", r'"jumped_ms_frac":\s*([0-9.eE+-]+)', float),
        ):
            got = re.search(rx, chunk)
            if got:
                rec[key] = conv(got.group(1))
        if "value" in rec:
            best = rec
    return best


def _load_budget(root: str):
    try:
        with open(os.path.join(root, "BUDGET.json")) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError):
        return None


def _load_serve(root: str):
    """The serving-fleet benchmark record (BENCH_SERVE.json, written by
    scripts/serve_loadgen.py): aggregate sims/s, queue-latency
    quantiles, wave width, wave-vs-serial speedup.  Optional — absent
    until the serve loadgen has run."""
    try:
        with open(os.path.join(root, "BENCH_SERVE.json")) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError):
        return None


def _load_mesh(root: str):
    """The 2D-mesh rung-ladder record (BENCH_MESH.json,
    witt-bench-mesh/v1; its writer, the mesh ladder, is gone):
    per-(P_replica, P_node) wall time, sims/s,
    bit-identity vs the unsharded singleton and the 1/P channel-
    ownership verdict.  Optional — absent until the ladder has run."""
    try:
        with open(os.path.join(root, "BENCH_MESH.json")) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError):
        return None


def _load_search(root: str):
    """The adversary-search benchmark record (BENCH_SEARCH.json,
    witt-bench-search/v1, written by scripts/adversary_smoke.py):
    evals/sec through the cached sweep path, generation count, the
    champion-objective trajectory, and its own documented evals/sec
    floor + note (the accepted-regression channel, like
    BENCH_FLOOR.json).  Optional — absent until the smoke has run."""
    try:
        with open(os.path.join(root, "BENCH_SEARCH.json")) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError):
        return None


def _round_row(path: str, budget) -> dict:
    with open(path) as f:
        doc = json.load(f)
    n = doc.get("n")
    if n is None:
        m = re.search(r"BENCH_r(\d+)\.json$", path)
        n = int(m.group(1)) if m else None
    rec = doc.get("parsed") or {}
    scraped = _extract_record(doc.get("tail", "") or "")
    if scraped:
        # the tail record is the fuller source (parsed is its prefix)
        rec = {**rec, **scraped}
    cfg = rec.get("config") or {}
    node_count = cfg.get("node_count", rec.get("node_count"))
    n_replicas = cfg.get("n_replicas", rec.get("n_replicas"))
    row = {
        "round": n,
        "file": os.path.basename(path),
        "metric": rec.get("metric"),
        "sims_per_sec": rec.get("value"),
        "vs_baseline": rec.get("vs_baseline"),
        "node_count": node_count,
        "n_replicas": n_replicas,
        "sim_ms": cfg.get("sim_ms", rec.get("sim_ms")),
        "chunk_ms": cfg.get("chunk_ms", rec.get("chunk_ms")),
        "compile_s": rec.get("compile_s"),
        "run_s": rec.get("run_s"),
        # jump efficacy (ISSUE 18): share of billed simulated ms the
        # consensus-jump lever skipped; None when the round predates the
        # lever or ran uninstrumented
        "jumped_ms_frac": rec.get("jumped_ms_frac"),
        "rc": doc.get("rc"),
        # derivables, filled below when the inputs exist
        "us_per_tick": None,
        "mib_per_replica": None,
    }
    # µs/tick: R replicas in lockstep at S sims/s with T ticks/sim ->
    # tick_us = R / (S*T) * 1e6.  T comes from BUDGET.json's census and
    # is only valid for the budget's own node count.
    if budget:
        b_nodes = ((budget.get("config") or {}).get("node_count"))
        ticks_per_sim = budget.get("ticks_per_sim")
        if (
            row["sims_per_sec"]
            and ticks_per_sim
            and node_count is not None
            and b_nodes == node_count
        ):
            row["us_per_tick"] = round(
                (n_replicas or 1)
                / (row["sims_per_sec"] * ticks_per_sim)
                * 1e6,
                2,
            )
        hbm = ((budget.get("hbm") or {}).get("model") or {})
        if hbm.get("mib_per_replica") and b_nodes == node_count:
            row["mib_per_replica"] = hbm["mib_per_replica"]
    return row


def build_trend(root: str = ROOT) -> dict:
    budget = _load_budget(root)
    rows = [
        _round_row(p, budget)
        for p in sorted(glob.glob(os.path.join(root, "BENCH_r*.json")))
    ]
    rows.sort(key=lambda r: (r["round"] is None, r["round"]))
    floor = None
    try:
        with open(os.path.join(root, "BENCH_FLOOR.json")) as f:
            floor = json.load(f)
    except (OSError, json.JSONDecodeError):
        pass

    def comparable(r):
        return (
            floor is not None
            and r["sims_per_sec"] is not None
            and r["node_count"] == floor.get("node_count")
            and r["n_replicas"] == floor.get("n_replicas")
        )

    comp = [r for r in rows if comparable(r)]
    regressions = []
    for prev, cur in zip(comp, comp[1:]):
        drop = 1.0 - cur["sims_per_sec"] / prev["sims_per_sec"]
        if drop > REGRESSION_FRAC:
            regressions.append(
                {
                    "from_round": prev["round"],
                    "to_round": cur["round"],
                    "drop_frac": round(drop, 4),
                    # absorbed by the committed floor -> documented
                    "documented": bool(
                        floor and cur["sims_per_sec"] >= floor["floor"]
                    ),
                }
            )
    trend = {
        "schema": "witt-bench-trend/v1",
        "rounds": rows,
        "floor": floor,
        "comparable_rounds": [r["round"] for r in comp],
        "latest_comparable": comp[-1] if comp else None,
        "regressions": regressions,
        "budget": _load_budget(root),
        "serve": _load_serve(root),
        "mesh": _load_mesh(root),
        "search": _load_search(root),
    }
    return trend


def check(trend: dict) -> list:
    """Gate violations (empty = pass).  See module docstring for what
    counts as documented."""
    problems = []
    floor = trend.get("floor")
    if not floor:
        return ["BENCH_FLOOR.json missing or unreadable — nothing to gate on"]
    latest = trend.get("latest_comparable")
    if latest is None:
        problems.append(
            "no BENCH round comparable to the floor config "
            f"({floor.get('node_count')}x{floor.get('n_replicas')}) — "
            "the gate cannot see the current perf level"
        )
        return problems
    if latest["sims_per_sec"] < floor["floor"]:
        problems.append(
            f"round {latest['round']} ({latest['sims_per_sec']:.3f} sims/s) "
            f"is below the committed floor {floor['floor']} — an "
            "UNDOCUMENTED regression.  Either fix the perf or re-record "
            "BENCH_FLOOR.json with a note explaining the accepted level "
            "(the floor file is the documentation channel)."
        )
    for reg in trend.get("regressions", []):
        if not reg["documented"]:
            problems.append(
                f"rounds r{reg['from_round']}->r{reg['to_round']} dropped "
                f"{reg['drop_frac']:.1%} (> {REGRESSION_FRAC:.0%}) and the "
                "newer round is below the floor — undocumented regression"
            )
    # jump-efficacy gate (ISSUE 18): the floor file's optional "jump"
    # block is the documentation channel for the dead-time lever's
    # paired interleaved A/B.  Once a block is committed, a newer round
    # whose measured jumped_ms_frac falls below the documented floor is
    # an UNDOCUMENTED efficacy regression (the jump stopped skipping
    # the dead time it was priced on); so is a committed block whose
    # A/B contradicts the shipped default (ok: false — e.g. the lever
    # armed by default while the paired walls record a loss).
    # Re-recording the block with a note is the accepted-regression
    # channel, same as the throughput floor.
    jump = floor.get("jump")
    if jump:
        if not jump.get("ok", True):
            problems.append(
                "BENCH_FLOOR.json's jump block records an A/B that "
                "contradicts the shipped default (note: "
                f"{jump.get('note', 'none')!r}) — re-measure, flip the "
                "default, or remove the block"
            )
        frac_floor = jump.get("jumped_ms_frac_floor")
        measured = latest.get("jumped_ms_frac")
        if (
            frac_floor is not None
            and measured is not None
            and measured < frac_floor
        ):
            problems.append(
                f"round {latest['round']} jumped_ms_frac {measured} is "
                f"below the documented efficacy floor {frac_floor} — "
                "an UNDOCUMENTED jump-efficacy regression.  Either "
                "restore the lever or re-record the jump block in "
                "BENCH_FLOOR.json with a note explaining the accepted "
                "level."
            )
    # the serve record gates itself (loadgen exits nonzero); here we
    # only refuse a committed record that says it failed
    serve = trend.get("serve")
    if serve is not None and not serve.get("ok", True):
        problems.append(
            "BENCH_SERVE.json records a failed serve benchmark: "
            + "; ".join(serve.get("failures", ["unknown"]))[:300]
        )
    # mission control: the serve benchmark runs fault-free, so ANY SLO
    # alert in its committed record means either a service regression
    # or alert noise — both gate failures, even if the record claims ok
    if serve is not None:
        alerts = serve.get("alerts") or {}
        if alerts.get("total"):
            problems.append(
                "BENCH_SERVE.json records SLO alerts during a fault-free "
                f"benchmark: {alerts.get('by_slo')}"
            )
    # concurrency contract: the serve record's armed lock-trace probe
    # must have seen ZERO lock-order violations — a committed record
    # carrying one documents a deadlock-order bug and must not pass CI
    if serve is not None:
        lt = serve.get("lockTrace") or {}
        if lt.get("violationCount"):
            problems.append(
                "BENCH_SERVE.json's lock-trace probe recorded "
                f"{lt['violationCount']} lock-order violation(s) — the "
                "fleet inverted LOCK_HIERARCHY at runtime; fix the "
                "acquisition order (see docs/serving.md, Lock hierarchy)"
            )
    # done-row harvesting (ISSUE 18): the serve record's optional
    # "harvest" block carries the paired A/B of the compaction lever —
    # a committed block whose A/B contradicts the shipped default
    # (ok: false) is refused like any other failed benchmark
    if serve is not None:
        harvest = serve.get("harvest")
        if harvest is not None and not harvest.get("ok", True):
            problems.append(
                "BENCH_SERVE.json's harvest block records an A/B that "
                "contradicts the shipped default (note: "
                f"{harvest.get('note', 'none')!r}) — re-measure, flip "
                "the default, or remove the block"
            )
    # same discipline for the 2D-mesh ladder: a committed record whose
    # rungs broke bit-identity or channel ownership must not pass CI
    mesh = trend.get("mesh")
    if mesh is not None:
        if mesh.get("schema") != "witt-bench-mesh/v1":
            problems.append(
                f"BENCH_MESH.json has unknown schema "
                f"{mesh.get('schema')!r} (expected witt-bench-mesh/v1)"
            )
        elif not mesh.get("ok", False):
            bad = [
                f"({r.get('p_replica')},{r.get('p_node')})"
                for r in mesh.get("rungs", [])
                if not (r.get("bit_identical") and r.get("ownership_ok"))
            ]
            problems.append(
                "BENCH_MESH.json records a failed 2D-mesh ladder"
                + (f" — rungs {', '.join(bad)}" if bad else " (no rungs)")
            )
    # adversary-search throughput (ISSUE 20): the committed record
    # carries its own evals/sec floor + note (same documentation
    # discipline as BENCH_FLOOR.json) — an evals/sec below it is an
    # UNDOCUMENTED search-throughput regression; a champion trajectory
    # that ever decreases means the strict-improvement champion update
    # broke (it is best-so-far by construction)
    search = trend.get("search")
    if search is not None:
        if search.get("schema") != "witt-bench-search/v1":
            problems.append(
                f"BENCH_SEARCH.json has unknown schema "
                f"{search.get('schema')!r} (expected witt-bench-search/v1)"
            )
        else:
            if not search.get("ok", False):
                problems.append(
                    "BENCH_SEARCH.json records a failed adversary smoke: "
                    + "; ".join(search.get("failures", ["unknown"]))[:300]
                )
            eps = search.get("evals_per_sec")
            eps_floor = search.get("evals_per_sec_floor")
            if eps is not None and eps_floor is not None and eps < eps_floor:
                problems.append(
                    f"BENCH_SEARCH.json evals/sec {eps} is below its "
                    f"documented floor {eps_floor} — an UNDOCUMENTED "
                    "search-throughput regression.  Either fix the perf "
                    "or re-record the floor with a note explaining the "
                    "accepted level."
                )
            traj = search.get("champion_trajectory") or []
            if any(b < a for a, b in zip(traj, traj[1:])):
                problems.append(
                    "BENCH_SEARCH.json champion_trajectory decreases "
                    f"({traj}) — the best-so-far champion update is "
                    "broken"
                )
    return problems


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--check", action="store_true",
                    help="exit 1 on an undocumented >10%% regression")
    ap.add_argument("-o", "--out", help="write the trend JSON here")
    ap.add_argument("--root", default=ROOT,
                    help="repo root holding BENCH_r*.json (tests)")
    args = ap.parse_args(argv)
    trend = build_trend(args.root)
    if args.out:
        d = os.path.dirname(os.path.abspath(args.out))
        os.makedirs(d, exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(trend, f, indent=2, sort_keys=True)
    else:
        json.dump(trend, sys.stdout, indent=2, sort_keys=True)
        sys.stdout.write("\n")
    n_rows = len(trend["rounds"])
    latest = trend.get("latest_comparable")
    print(
        f"bench_trend: {n_rows} round(s), latest comparable "
        f"{('r%s @ %.3f sims/s' % (latest['round'], latest['sims_per_sec'])) if latest else 'none'}",
        file=sys.stderr,
    )
    if args.check:
        problems = check(trend)
        for p in problems:
            print(f"bench_trend FAIL: {p}", file=sys.stderr)
        if problems:
            return 1
        print("bench_trend: gate PASS", file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
