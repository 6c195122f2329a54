"""Mission-control watch: one refreshing terminal over a live fleet or
a running TPU campaign.

Fleet mode (``--url``) polls the serving process the operator already
has: ``GET /w/health`` (queue pressure, lanes, drain, quarantine) plus
the new ``GET /w/slo`` (burn-rate SLO states, active alerts, alert
counters) and renders them side by side — the first place a paging
alert becomes visible without grepping a flight-recorder dump.

Campaign mode (``--campaign PATH``) tails a campaign ledger of rung
events (file or the directory holding it; nothing in the repo writes
one today, ROADMAP C1) and shows rung progress, the ETA of
the in-flight rung projected from its own chunk times, and the
tick-vs-budget margin (RUNG_BUDGET_S minus the pass cost so far) — the
number that predicts a ``rung_aborted`` before it happens.

Loadgen mode (``--loadgen``) is the CI self-test: boot an in-process
fleet (WServer + BatchScheduler), push a small fault-free workload
through real HTTP loopback, then take the fleet snapshot.  A fault-free
workload must show ZERO alerts; any firing SLO fails the step — the
"quiet when healthy" half of the chaos proof (chaos_smoke.py is the
"loud when broken" half).

``--once --format json`` prints a single machine-readable snapshot and
exits 0 (healthy), 1 (alerts firing / degraded / failures), or 2
(unreachable / no ledger) — the CI contract.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import urllib.error
import urllib.request

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CAMPAIGN_LEDGER = "tpu_campaign.jsonl"
RUNG_BUDGET_S = 900.0  # a rung's wall-clock budget in the ledger's writer
SILENCE_STALL_S = 900.0  # silence after which that writer killed a rung


# -- fleet mode --------------------------------------------------------------
def _get_json(url: str, timeout: float):
    """(status, payload) — HTTP errors with JSON bodies are data, not
    exceptions (health answers 200 while degraded; ready answers 503)."""
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.status, json.loads(r.read().decode())
    except urllib.error.HTTPError as e:
        try:
            return e.code, json.loads(e.read().decode())
        except ValueError:
            return e.code, None


def fleet_snapshot(base_url: str, timeout: float = 10.0) -> dict:
    """One joined /w/health + /w/slo view.  Raises OSError when the
    fleet is unreachable (exit code 2)."""
    _, health = _get_json(base_url + "/w/health", timeout)
    status, slo = _get_json(base_url + "/w/slo", timeout)
    if status == 404:
        slo = None  # older server without the SLO surface
    alerts = (slo or {}).get("alerts", {})
    firing = [
        row for row in (slo or {}).get("slos", [])
        if row.get("state") == "firing"
    ]
    degraded = bool((health or {}).get("degraded"))
    return {
        "mode": "fleet",
        "url": base_url,
        "ts": round(time.time(), 3),
        "ok": not degraded and not firing and not alerts.get("total"),
        "degraded": degraded,
        "health": health,
        "slo": slo,
        "firing": firing,
        "alertTotal": int(alerts.get("total") or 0),
    }


def render_fleet(snap: dict) -> str:
    h = snap.get("health") or {}
    lines = [
        f"fleet {snap['url']}  "
        f"{'OK' if snap['ok'] else 'ATTENTION'}"
        f"{'  DEGRADED' if snap['degraded'] else ''}",
        f"  queue depth {h.get('queueDepth', '?')}  "
        f"draining={h.get('draining', False)}  "
        f"jobs done/failed "
        f"{h.get('jobsCompleted', '?')}/{h.get('jobsFailed', '?')}  "
        f"quarantined {h.get('jobsQuarantined', 0)}",
    ]
    lanes = h.get("lanes") or []
    if lanes:
        row = "  ".join(
            f"lane{l.get('lane', i)}:"
            f"{'up' if l.get('alive') else 'DOWN'}"
            f"(r{l.get('restarts', 0)})"
            for i, l in enumerate(lanes)
        )
        lines.append(f"  {row}")
    lt = h.get("lockTrace") or {}
    if lt.get("armed"):
        viol = int(lt.get("violationCount") or 0)
        lines.append(
            f"  lock trace: armed  waitMax={_fmt(lt.get('maxWaitS'))}s "
            f"waitP99={_fmt(lt.get('waitP99S'))}s  "
            + (f"LOCK-ORDER VIOLATIONS {viol} !!" if viol
               else "violations 0")
        )
    slo = snap.get("slo")
    if slo is None:
        lines.append("  /w/slo: not available on this server")
        return "\n".join(lines)
    lines.append(
        f"  alerts total {snap['alertTotal']} "
        f"(by severity {json.dumps(slo.get('alerts', {}).get('bySeverity', {}))})"
    )
    for row in slo.get("slos", []):
        mark = {"firing": "!!", "ok": "ok", "no_data": "--"}.get(
            row.get("state"), "??"
        )
        burn = row.get("burn_fast")
        lines.append(
            f"  [{mark}] {row.get('slo'):<22} "
            f"measured={_fmt(row.get('measured_fast'))} "
            f"objective={_fmt(row.get('objective'))} "
            f"burn={_fmt(burn)}"
            + (f"  severity={row['severity']}" if row.get("severity") else "")
        )
    for a in slo.get("activeAlerts", []):
        lines.append(
            f"  FIRING {a.get('slo')} severity={a.get('severity')}"
            + (f" run_id={a['run_id']}" if a.get("run_id") else "")
        )
    return "\n".join(lines)


def _fmt(v) -> str:
    if v is None:
        return "-"
    if isinstance(v, float):
        return f"{v:.4g}"
    return str(v)


# -- campaign mode -----------------------------------------------------------
def _ledger_path(path: str) -> str:
    return os.path.join(path, CAMPAIGN_LEDGER) if os.path.isdir(path) else path


def _read_events(path: str) -> list:
    evs = []
    try:
        with open(path) as f:
            for line in f:
                try:
                    evs.append(json.loads(line))
                except ValueError:
                    continue  # torn tail line mid-write
    except OSError:
        pass
    return evs


def campaign_snapshot(path: str, budget_s: float = RUNG_BUDGET_S) -> dict:
    """Digest a campaign ledger into rung progress + in-flight ETA.

    The in-flight rung is reconstructed from its own events: ``compiled``
    carries chunk_ms, per-chunk ``hb``/``chunk_over_safe`` heartbeats
    carry chunk index + seconds, and 1000 sim-ms per rung fixes the
    chunk count.  ETA projects the median observed
    chunk over the chunks remaining; margin is the budget minus the
    pass cost so far — negative margin means the next budget check
    aborts the pass."""
    ledger = _ledger_path(path)
    evs = _read_events(ledger)
    if not evs:
        return {"mode": "campaign", "ledger": ledger, "ok": False,
                "state": "missing", "events": 0}
    rungs = [e for e in evs if e.get("event") == "rung"]
    mesh_rungs = [e for e in evs if e.get("event") == "mesh_rung"]
    aborted = [e for e in evs if e.get("event") == "rung_aborted"]
    best = next(
        (e for e in reversed(evs) if e.get("event") == "campaign_best"), None
    )
    ended = any(
        e.get("event") in ("campaign_end", "mesh_ladder_end") for e in evs
    )

    # the in-flight rung: everything after the last terminal rung event
    terminal = {"rung", "rung_cached", "rung_aborted", "campaign_end",
                "saturated", "stop_climbing", "mesh_rung",
                "mesh_ladder_end"}
    tail_start = 0
    for i, e in enumerate(evs):
        if e.get("event") in terminal:
            tail_start = i + 1
    tail = evs[tail_start:]
    current = None
    compiled = next(
        (e for e in reversed(tail) if e.get("event") == "compiled"), None
    )
    hbs = [e for e in tail
           if e.get("event") in ("hb", "chunk_over_safe")]
    compiling = next(
        (e for e in reversed(tail) if e.get("event") == "compiling"), None
    )
    if compiled is not None or hbs:
        chunk_ms = (compiled or {}).get("chunk_ms") or 20
        sim_ms = 1000  # one program per rung
        n_chunks = max(1, sim_ms // int(chunk_ms))
        chunk_s = sorted(
            float(e["chunk_s"]) for e in hbs if "chunk_s" in e
        )
        done = max((int(e.get("chunk", -1)) for e in hbs), default=-1) + 1
        median = chunk_s[len(chunk_s) // 2] if chunk_s else None
        spent = sum(chunk_s)
        current = {
            "replicas": (compiled or hbs[-1] if hbs else {}).get("replicas"),
            "chunks_done": done,
            "chunks_total": n_chunks,
            "median_chunk_s": round(median, 3) if median else None,
            "eta_s": (
                round((n_chunks - done) * median, 1) if median else None
            ),
            "spent_s": round(spent, 1),
            "budget_s": budget_s,
            "budget_margin_s": round(budget_s - spent, 1),
        }
    elif compiling is not None:
        current = {
            "replicas": compiling.get("replicas"),
            "phase": "compiling",
            "limit_s": compiling.get("limit_s"),
        }

    try:
        silence_s = time.time() - os.path.getmtime(ledger)
    except OSError:
        silence_s = None
    state = "ended" if ended else (
        "stalled" if silence_s is not None and silence_s > SILENCE_STALL_S
        else "running"
    )
    return {
        "mode": "campaign",
        "ledger": ledger,
        "ts": round(time.time(), 3),
        "ok": True,
        "state": state,
        "events": len(evs),
        "silence_s": round(silence_s, 1) if silence_s is not None else None,
        "rungs": [
            {k: r.get(k) for k in ("nodes", "replicas", "sims_per_sec",
                                   "run_s", "all_done", "resumed")}
            for r in rungs
        ],
        "mesh_rungs": [
            {k: r.get(k) for k in ("p_replica", "p_node", "sims_per_sec",
                                   "bit_identical")}
            for r in mesh_rungs
        ],
        "aborted": len(aborted),
        "best": (
            {k: best.get(k) for k in ("nodes", "replicas", "sims_per_sec")}
            if best else None
        ),
        "current": current,
    }


def render_campaign(snap: dict) -> str:
    lines = [
        f"campaign {snap['ledger']}  state={snap['state']}  "
        f"events={snap['events']}"
        + (f"  silent {snap['silence_s']}s" if snap.get("silence_s") else ""),
    ]
    if snap["state"] == "missing":
        lines.append("  (no ledger yet)")
        return "\n".join(lines)
    for r in snap["rungs"]:
        lines.append(
            f"  rung {r['nodes']}x{r['replicas']:<3} "
            f"{_fmt(r['sims_per_sec'])} sims/s in {_fmt(r['run_s'])}s"
            f"{'  (resumed)' if r.get('resumed') else ''}"
            f"{'' if r.get('all_done') else '  INCOMPLETE'}"
        )
    for r in snap["mesh_rungs"]:
        lines.append(
            f"  mesh {r['p_replica']}x{r['p_node']} "
            f"{_fmt(r['sims_per_sec'])} sims/s"
            f"{'' if r.get('bit_identical') else '  NOT BIT-IDENTICAL'}"
        )
    cur = snap.get("current")
    if cur:
        if cur.get("phase") == "compiling":
            lines.append(
                f"  compiling replicas={cur.get('replicas')} "
                f"(limit {cur.get('limit_s')}s)"
            )
        else:
            margin = cur.get("budget_margin_s")
            warn = "  BUDGET AT RISK" if (
                margin is not None and cur.get("eta_s") is not None
                and margin < cur["eta_s"]
            ) else ""
            lines.append(
                f"  in flight: replicas={cur.get('replicas')} "
                f"chunk {cur['chunks_done']}/{cur['chunks_total']}  "
                f"eta {_fmt(cur.get('eta_s'))}s  "
                f"budget margin {_fmt(margin)}s{warn}"
            )
    if snap.get("aborted"):
        lines.append(f"  aborted passes: {snap['aborted']} (resumable)")
    if snap.get("best"):
        b = snap["best"]
        lines.append(
            f"  best {b['nodes']}x{b['replicas']} = "
            f"{_fmt(b['sims_per_sec'])} sims/s"
        )
    return "\n".join(lines)


# -- loadgen self-test mode --------------------------------------------------
def _boot_loadgen(jobs_per_family: int = 3):
    """In-process mini fleet + a fault-free workload over real HTTP
    loopback.  Returns (httpd, ws, base_url); the workload is complete
    when this returns."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    if os.environ.get("JAX_PLATFORMS") == "cpu":
        jax.config.update("jax_platforms", "cpu")
    from wittgenstein_tpu.server.ws import WServer, serve

    ws = WServer()
    httpd = serve(0, ws=ws)
    base = f"http://127.0.0.1:{httpd.server_address[1]}"
    ids = []
    for seed in range(jobs_per_family):
        for spec in (
            {"protocol": "PingPong", "params": {"node_ct": 32},
             "simMs": 60, "seed": seed},
        ):
            req = urllib.request.Request(
                base + "/w/jobs", data=json.dumps(spec).encode(),
                method="POST",
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req, timeout=60) as r:
                ids.append(json.loads(r.read().decode())["id"])
    for jid in ids:
        status, res = _get_json(
            base + f"/w/jobs/{jid}/result?waitS=120", timeout=180
        )
        if status != 200 or res.get("state") != "done":
            raise RuntimeError(
                f"loadgen job {jid} -> {status}: {res}"
            )
    return httpd, ws, base


# -- CLI ---------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    mode = ap.add_mutually_exclusive_group(required=True)
    mode.add_argument("--url", help="fleet base url, e.g. "
                      "http://127.0.0.1:8080")
    mode.add_argument("--campaign", metavar="PATH",
                      help="campaign ledger jsonl (or its directory)")
    mode.add_argument("--loadgen", action="store_true",
                      help="boot an in-process fleet, run a fault-free "
                      "workload, snapshot it (CI self-test)")
    ap.add_argument("--once", action="store_true",
                    help="one snapshot, then exit with the health code")
    ap.add_argument("--format", choices=("text", "json"), default="text")
    ap.add_argument("--interval", type=float, default=2.0,
                    help="refresh period in watch mode (seconds)")
    ap.add_argument("--timeout", type=float, default=10.0,
                    help="per-request HTTP timeout (fleet mode)")
    ap.add_argument("--out", help="also write the final JSON snapshot "
                    "to this path (the CI artifact)")
    args = ap.parse_args(argv)

    httpd = ws = None
    if args.loadgen:
        try:
            httpd, ws, args.url = _boot_loadgen()
        except Exception as e:  # noqa: BLE001 — CI wants the code, not a trace
            print(f"witt_watch: loadgen boot failed: "
                  f"{type(e).__name__}: {e}", file=sys.stderr)
            return 2
        args.once = True  # the self-test is single-shot by nature

    def take() -> dict:
        if args.campaign:
            return campaign_snapshot(args.campaign)
        return fleet_snapshot(args.url, args.timeout)

    def code(snap: dict) -> int:
        if snap["mode"] == "campaign":
            if snap["state"] == "missing":
                return 2
            return 0 if snap["state"] != "stalled" else 1
        return 0 if snap["ok"] else 1

    try:
        while True:
            try:
                snap = take()
            except OSError as e:
                if args.once:
                    print(f"witt_watch: unreachable: {e}", file=sys.stderr)
                    return 2
                snap = {"mode": "fleet", "url": args.url, "ok": False,
                        "error": str(e)}
            if args.format == "json":
                text = json.dumps(snap, indent=2, sort_keys=True)
            elif snap.get("error"):
                text = f"fleet {args.url}  UNREACHABLE: {snap['error']}"
            elif snap["mode"] == "campaign":
                text = render_campaign(snap)
            else:
                text = render_fleet(snap)
            if args.once:
                print(text)
                if args.out:
                    with open(args.out, "w") as f:
                        json.dump(snap, f, indent=2, sort_keys=True)
                        f.write("\n")
                return code(snap)
            # ANSI clear + home: a refreshing pane, not a scrolling log
            sys.stdout.write("\x1b[2J\x1b[H" + text + "\n")
            sys.stdout.flush()
            time.sleep(args.interval)
    except KeyboardInterrupt:
        return 0
    finally:
        if httpd is not None:
            from wittgenstein_tpu.server.ws import shutdown_server

            shutdown_server(httpd)
        if ws is not None:
            ws.jobs.stop()


if __name__ == "__main__":
    sys.exit(main())
