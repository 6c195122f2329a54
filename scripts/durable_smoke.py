"""Durable-run smoke: SIGKILL a supervised run mid-flight, resume, compare.

Three subprocess invocations of this script's --child mode, all running
the SAME supervised chunked P2PFlood sim (telemetry armed, fault plan
armed, run_ms_batched over 2 replicas):

  1. reference: runs all chunks uninterrupted, checkpointing each chunk;
  2. victim: same run in a fresh checkpoint dir, SIGKILLed from INSIDE
     the heartbeat callback after chunk 3 — a real `kill -9`, not a
     simulated preemption, so nothing gets to flush or clean up;
  3. resume: the victim's command line again; the supervisor restores
     the newest intact checkpoint and replays the remaining schedule.

The parent then asserts the resume actually resumed (resumed_from_step
> 0, fewer chunks executed than the reference) and that the final
checkpoints are BIT-IDENTICAL leaf-for-leaf — telemetry counters,
snapshot ring, and fault side-car included.  The final manifest +
summary land in out_dir as the CI artifact.  See docs/durability.md.

The victim and resume children also arm a tail-safe FlightRecorder
(wittgenstein_tpu.obs) on a JSONL file beside the checkpoints, while
the reference runs unarmed — so the leaf-for-leaf compare doubles as
the recorder-neutrality proof under a real SIGKILL.  The parent then
replays the black box and asserts the whole story survived the kill
under ONE run_id: admission and packing (recorded by the victim at
entry), every chunk with tick HWMs, the checkpoint writes, the kill
event itself (flushed+fsynced before os.kill), the resume (run_id
adopted from the checkpoint manifest), and run-complete — with
chunk-end coverage over the full schedule across both processes.
timeline.txt and a validated Chrome trace.json are rendered into
out_dir via scripts/obs_query.py.

Usage: python scripts/durable_smoke.py [out_dir]   (default ./durable_smoke)
"""

from __future__ import annotations

import json
import os
import shutil
import signal
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

TOTAL_MS = 400
CHUNK_MS = 50
KILL_AFTER = 3  # chunks completed before the SIGKILL lands
REPLICAS = 2
SEED = 7


# -- child: one supervised run (possibly suicidal) ------------------------


def child(ckpt_dir: str, kill_after: int, flight: bool) -> int:
    import glob

    import jax

    import numpy as np

    from wittgenstein_tpu.engine import replicate_state
    from wittgenstein_tpu.faults import FaultPlan
    from wittgenstein_tpu.obs import LIVE_BASENAME, FlightRecorder, mint_context
    from wittgenstein_tpu.protocols.p2pflood import P2PFloodParameters
    from wittgenstein_tpu.protocols.p2pflood_batched import make_p2pflood
    from wittgenstein_tpu.runtime import Supervisor
    from wittgenstein_tpu.telemetry.state import TelemetryConfig

    net, state = make_p2pflood(
        P2PFloodParameters(node_count=40, dead_node_count=4),
        capacity=2048,
        seed=SEED,
    )
    live = np.flatnonzero(~np.asarray(state.down))
    net, state = net.with_faults(
        state, plan=FaultPlan("crash5@100").crash(live[:5], at=100)
    )
    net, state = net.with_telemetry(
        state, TelemetryConfig(snapshots=4, snapshot_every_ms=100)
    )

    # armed: every event append+flush+fsync'd to a JSONL beside the
    # checkpoints, so the black box survives the SIGKILL below.
    # unarmed (reference): in-memory ring only — the bitwise compare
    # against the armed runs is the recorder-neutrality proof.
    rec = FlightRecorder(
        path=os.path.join(ckpt_dir, LIVE_BASENAME) if flight else None
    )
    ctx = None
    if not glob.glob(os.path.join(ckpt_dir, "ckpt_*.npz")):
        # fresh run: this script IS the admission point — mint the run
        # context here and record the serve-shaped prologue.  A resume
        # child skips this; the supervisor adopts the run_id from the
        # checkpoint manifest instead.
        ctx = mint_context("smoke")
        rec.record(
            "admission", ctx, protocol="p2pflood",
            sim_ms=TOTAL_MS, chunk_ms=CHUNK_MS,
        )
        rec.record(
            "pack", ctx, mode="chunked", live_rows=REPLICAS,
            padding_rows=0, capacity=REPLICAS,
        )

    def heartbeat(i: int, dt: float) -> None:
        if kill_after >= 0 and i + 1 >= kill_after:
            # flushed+fsynced by record() — the last durable word
            rec.record("kill", ctx, after_chunk=i, signal="SIGKILL")
            # the hard way: no atexit, no finally, no flushed buffers —
            # exactly what a preempted TPU worker looks like from disk
            os.kill(os.getpid(), signal.SIGKILL)

    sup = Supervisor.from_network(
        net,
        replicate_state(state, REPLICAS),
        total_ms=TOTAL_MS,
        chunk_ms=CHUNK_MS,
        checkpoint_dir=ckpt_dir,
        checkpoint_every=1,
        heartbeat=heartbeat,
        ctx=ctx,
        recorder=rec,
    )
    report = sup.run()
    final = report.state
    print(
        json.dumps(
            {
                "ok": report.ok,
                "resumed_from_step": report.provenance["resumed_from_step"],
                "chunks_executed": len(report.chunk_seconds),
                "run_id": report.provenance.get("run_id"),
                "delivered": int(np.asarray(final.tele.delivered).sum()),
                "dropped_by_fault": int(
                    np.asarray(final.faults.dropped_by_fault).sum()
                ),
            }
        )
    )
    return 0


# -- parent: orchestrate, kill, diff --------------------------------------


def run_child(ckpt_dir: str, kill_after: int = -1, flight: bool = False):
    """-> (returncode, parsed stdout json or None)."""
    proc = subprocess.run(
        [
            sys.executable,
            os.path.abspath(__file__),
            "--child",
            ckpt_dir,
            "--kill-after",
            str(kill_after),
            "--flight",
            "1" if flight else "0",
        ],
        capture_output=True,
        text=True,
        cwd=ROOT,
        timeout=600,
    )
    out = None
    for line in proc.stdout.splitlines():
        if line.startswith("{"):
            out = json.loads(line)
    return proc.returncode, out, proc.stderr


def final_leaves(ckpt_dir: str):
    """Raw arrays of the final checkpoint, keyed by leaf path."""
    import numpy as np

    from wittgenstein_tpu.engine import checkpoint as ck

    path = os.path.join(ckpt_dir, f"ckpt_{TOTAL_MS // CHUNK_MS:08d}.npz")
    assert os.path.exists(path), f"no final checkpoint at {path}"
    with np.load(path, allow_pickle=False) as data:
        skip = {ck.LAYOUT_KEY, ck.MANIFEST_KEY}
        return path, {k: data[k] for k in data.files if k not in skip}


def main() -> int:
    out_dir = (
        sys.argv[1] if len(sys.argv) > 1 else os.path.join(ROOT, "durable_smoke")
    )
    os.makedirs(out_dir, exist_ok=True)
    ref_dir = os.path.join(out_dir, "ref_ckpts")
    run_dir = os.path.join(out_dir, "run_ckpts")
    for d in (ref_dir, run_dir):
        shutil.rmtree(d, ignore_errors=True)

    # 1. uninterrupted reference
    rc, ref, err = run_child(ref_dir)
    assert rc == 0, f"reference run failed (rc={rc}):\n{err}"
    assert ref["ok"] and ref["resumed_from_step"] is None, ref
    assert ref["delivered"] > 0, "telemetry lane silent — smoke is vacuous"
    assert ref["dropped_by_fault"] > 0, "fault lane silent — smoke is vacuous"

    # 2. the same run, SIGKILLed from inside the heartbeat — flight
    #    recorder armed (the reference stays unarmed, so the bitwise
    #    compare below also proves the recorder changes nothing)
    rc, _, err = run_child(run_dir, kill_after=KILL_AFTER, flight=True)
    assert rc == -signal.SIGKILL, (
        f"victim should die by SIGKILL, got rc={rc}:\n{err}"
    )

    # 3. resume: same command line, supervisor picks up the checkpoint
    rc, res, err = run_child(run_dir, flight=True)
    assert rc == 0, f"resume run failed (rc={rc}):\n{err}"
    assert res["ok"], res
    assert res["resumed_from_step"] and res["resumed_from_step"] > 0, (
        f"resume did not restore a checkpoint: {res}"
    )
    assert res["chunks_executed"] < ref["chunks_executed"], (
        "resume re-executed the whole schedule — checkpoint was ignored"
    )

    # 4. bit-identity, side-cars included
    ref_path, ref_leaves = final_leaves(ref_dir)
    _, res_leaves = final_leaves(run_dir)
    assert ref_leaves.keys() == res_leaves.keys(), (
        sorted(ref_leaves.keys() ^ res_leaves.keys())
    )
    diverged = [
        k
        for k in sorted(ref_leaves)
        if ref_leaves[k].shape != res_leaves[k].shape
        or ref_leaves[k].dtype != res_leaves[k].dtype
        or ref_leaves[k].tobytes() != res_leaves[k].tobytes()
    ]
    assert not diverged, f"kill-and-resume diverged on leaves: {diverged}"
    assert res["delivered"] == ref["delivered"]
    assert res["dropped_by_fault"] == ref["dropped_by_fault"]

    # 5. replay the black box: one JSONL accumulated by victim+resume
    #    (append mode, same file) must tell the whole story under one
    #    run_id, kill included
    import importlib.util

    from wittgenstein_tpu.obs import LIVE_BASENAME, read_events

    spec = importlib.util.spec_from_file_location(
        "obs_query", os.path.join(ROOT, "scripts", "obs_query.py")
    )
    obs_query = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(obs_query)

    flight_src = os.path.join(run_dir, LIVE_BASENAME)
    assert os.path.exists(flight_src), "armed run left no flight recorder"
    flight_dst = os.path.join(out_dir, LIVE_BASENAME)
    shutil.copy2(flight_src, flight_dst)
    events = read_events([flight_dst])
    rids = {e["run_id"] for e in events if e.get("run_id")}
    assert len(rids) == 1, (
        f"kill+resume should share ONE run_id, saw {sorted(rids)}"
    )
    run_id = rids.pop()
    assert run_id == res["run_id"], (run_id, res["run_id"])
    kinds = {e["kind"] for e in events}
    need = {
        "admission", "pack", "chunk-start", "chunk-end", "checkpoint",
        "kill", "resume", "run-complete",
    }
    assert need <= kinds, f"timeline missing kinds: {sorted(need - kinds)}"
    ends = {
        e.get("chunk_seq") for e in events if e["kind"] == "chunk-end"
    }
    assert ends == set(range(TOTAL_MS // CHUNK_MS)), (
        f"chunk-end coverage across kill+resume broken: {sorted(ends)}"
    )
    hwm_ends = [
        e for e in events if e["kind"] == "chunk-end" and "ticks" in e
    ]
    assert hwm_ends, "chunk-end events carry no tick HWMs"
    with open(os.path.join(out_dir, "timeline.txt"), "w") as f:
        f.write(obs_query.render_timeline(events))
    from wittgenstein_tpu.telemetry.trace import validate_chrome_trace

    trace_doc = obs_query.to_chrome_trace(events)
    validate_chrome_trace(trace_doc)
    with open(os.path.join(out_dir, "trace.json"), "w") as f:
        json.dump(trace_doc, f)

    # artifact: the final manifest + a summary the CI job uploads
    from wittgenstein_tpu.engine.checkpoint import read_manifest

    manifest = read_manifest(ref_path)
    with open(os.path.join(out_dir, "manifest.json"), "w") as f:
        json.dump(manifest, f, indent=2, sort_keys=True)
    summary = {
        "ok": True,
        "total_ms": TOTAL_MS,
        "chunk_ms": CHUNK_MS,
        "killed_after_chunks": KILL_AFTER,
        "resumed_from_step": res["resumed_from_step"],
        "leaves_compared": len(ref_leaves),
        "delivered": ref["delivered"],
        "dropped_by_fault": ref["dropped_by_fault"],
        "run_id": run_id,
        "flight_events": len(events),
    }
    with open(os.path.join(out_dir, "summary.json"), "w") as f:
        json.dump(summary, f, indent=2, sort_keys=True)
    for d in (ref_dir, run_dir):  # the checkpoints are big; keep the proof
        shutil.rmtree(d, ignore_errors=True)
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--child":
        ckpt_dir = sys.argv[2]
        kill_after = int(sys.argv[sys.argv.index("--kill-after") + 1])
        flight = False
        if "--flight" in sys.argv:
            flight = sys.argv[sys.argv.index("--flight") + 1] == "1"
        sys.exit(child(ckpt_dir, kill_after, flight))
    sys.exit(main())
