"""Obs spine tests: trace context, flight recorder, supervisor event
flow (one run_id across kill+resume), failure dumps, armed-vs-unarmed
bit-identity across protocols, per-tenant attribution (unit + through
the serve scheduler and /metrics), and the obs_query / bench_trend
tooling.

The non-negotiable invariant pinned throughout: everything in
wittgenstein_tpu/obs is host-side and read-only — arming a recorder or
computing attribution changes ZERO bytes of sim state.
"""

import importlib.util
import json
import os
import threading

import jax
import numpy as np
import pytest

from wittgenstein_tpu.engine import replicate_state
from wittgenstein_tpu.obs import (
    DUMP_BASENAME,
    ENV_DIR,
    FlightRecorder,
    TraceContext,
    batch_attribution,
    failure_dump_paths,
    get_recorder,
    mint_context,
    new_run_id,
    read_events,
    replica_rows,
    reset_default_recorder,
)
from wittgenstein_tpu.runtime import Supervisor

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "scripts", f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# context


class TestTraceContext:
    def test_run_id_format_and_uniqueness(self):
        rid = new_run_id("serve")
        head, t, r = rid.split("-")
        assert head == "serve" and len(t) == 8 and len(r) == 8
        int(t, 16), int(r, 16)
        assert len({new_run_id("x") for _ in range(64)}) == 64

    def test_ids_drop_none(self):
        ctx = mint_context("run", job_id="j1")
        assert set(ctx.ids()) == {"run_id", "job_id"}
        assert ctx.ids()["job_id"] == "j1"

    def test_child_overrides_preserve_rest(self):
        ctx = TraceContext(run_id="r", job_id="j", tenant_id="t")
        kid = ctx.child(chunk_seq=4)
        assert kid.run_id == "r" and kid.tenant_id == "t"
        assert kid.chunk_seq == 4 and ctx.chunk_seq is None

    def test_frozen(self):
        with pytest.raises(Exception):
            TraceContext(run_id="r").run_id = "other"


# ---------------------------------------------------------------------------
# recorder


class TestFlightRecorder:
    def test_ring_bound(self):
        rec = FlightRecorder(capacity=4)
        for i in range(10):
            rec.record("tick", n=i)
        assert len(rec) == 4
        assert [e["n"] for e in rec.events()] == [6, 7, 8, 9]

    def test_reserved_keys_not_clobbered(self):
        rec = FlightRecorder()
        # a field named `kind` is a TypeError at the call boundary
        # (producers use error_kind); ts/seq are guarded in the body
        with pytest.raises(TypeError):
            rec.record("retry", kind="transient")
        ev = rec.record("retry", ts=-1, seq=99, extra=1)
        assert ev["kind"] == "retry" and ev["extra"] == 1
        assert ev["ts"] > 0 and ev["seq"] == 0

    def test_armed_path_appends_per_event(self, tmp_path):
        path = str(tmp_path / "sub" / "flight_recorder.jsonl")
        rec = FlightRecorder(path=path)
        ctx = TraceContext(run_id="r1")
        rec.record("a", ctx)
        rec.record("b", ctx, step=2)
        with open(path) as f:
            lines = f.read().splitlines()
        assert len(lines) == 2  # one durable line per event, no buffering
        evs = read_events([path])
        assert [e["kind"] for e in evs] == ["a", "b"]
        assert all(e["run_id"] == "r1" for e in evs)

    def test_read_events_skips_torn_tail(self, tmp_path):
        path = str(tmp_path / "torn.jsonl")
        with open(path, "w") as f:
            f.write(json.dumps({"ts": 1.0, "seq": 0, "kind": "ok"}) + "\n")
            f.write('{"ts": 2.0, "seq": 1, "kind": "to')  # SIGKILL mid-write
        evs = read_events([path])
        assert [e["kind"] for e in evs] == ["ok"]

    def test_read_events_merges_and_orders(self, tmp_path):
        a, b = str(tmp_path / "a.jsonl"), str(tmp_path / "b.jsonl")
        with open(a, "w") as f:
            f.write(json.dumps({"ts": 3.0, "seq": 0, "kind": "late"}) + "\n")
        with open(b, "w") as f:
            f.write(json.dumps({"ts": 1.0, "seq": 0, "kind": "early"}) + "\n")
        assert [e["kind"] for e in read_events([a, b])] == ["early", "late"]

    def test_dump_atomic(self, tmp_path):
        rec = FlightRecorder()
        rec.record("x", n=1)
        path = str(tmp_path / "dump" / "flight_recorder_dump.jsonl")
        assert rec.dump(path) == path
        assert [e["kind"] for e in read_events([path])] == ["x"]
        assert not [
            p for p in os.listdir(os.path.dirname(path)) if ".tmp." in p
        ]

    def test_thread_safety_no_lost_events(self):
        rec = FlightRecorder(capacity=10_000)
        threads = [
            threading.Thread(
                target=lambda: [rec.record("t") for _ in range(100)]
            )
            for _ in range(8)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        evs = rec.events()
        assert len(evs) == 800
        assert len({e["seq"] for e in evs}) == 800

    def test_default_recorder_armed_by_env(self, tmp_path, monkeypatch):
        monkeypatch.setenv(ENV_DIR, str(tmp_path))
        reset_default_recorder()
        try:
            rec = get_recorder()
            assert rec.path and rec.path.startswith(str(tmp_path))
            assert get_recorder() is rec  # process singleton
            dumps = failure_dump_paths("/ckpts")
            assert os.path.join("/ckpts", DUMP_BASENAME) in dumps
            assert any(p.startswith(str(tmp_path)) for p in dumps)
        finally:
            reset_default_recorder()

    def test_default_recorder_unarmed_without_env(self, monkeypatch):
        monkeypatch.delenv(ENV_DIR, raising=False)
        reset_default_recorder()
        try:
            assert get_recorder().path is None
            assert failure_dump_paths(None) == []
        finally:
            reset_default_recorder()


# ---------------------------------------------------------------------------
# supervisor event flow (toy pytree — no device work)


def toy_state():
    import jax.numpy as jnp

    return {"x": jnp.arange(4, dtype=jnp.int32), "step": jnp.int32(0)}


def toy_chunk(s):
    return {"x": s["x"] * 2 + 1, "step": s["step"] + 1}


class TestSupervisorEvents:
    def test_full_run_event_flow(self):
        rec = FlightRecorder()
        ctx = mint_context("test", job_id="jX", tenant_id="acme")
        rep = Supervisor(
            toy_chunk, toy_state(), n_chunks=3, ctx=ctx, recorder=rec
        ).run()
        assert rep.ok
        evs = rec.events()
        kinds = [e["kind"] for e in evs]
        assert kinds.count("chunk-start") == 3
        assert kinds.count("chunk-end") == 3
        assert kinds[-1] == "run-complete"
        assert all(e["run_id"] == ctx.run_id for e in evs)
        starts = [e for e in evs if e["kind"] == "chunk-start"]
        assert [e["chunk_seq"] for e in starts] == [0, 1, 2]
        assert all(e["tenant_id"] == "acme" for e in starts)
        # provenance carries the same ids — the ledger join key
        assert rep.provenance["run_id"] == ctx.run_id
        assert rep.provenance["job_id"] == "jX"

    def test_supervisor_mints_ctx_when_entry_point(self):
        rec = FlightRecorder()
        sup = Supervisor(toy_chunk, toy_state(), n_chunks=1, recorder=rec)
        rep = sup.run()
        assert sup.ctx is not None and sup.ctx.run_id.startswith("run-")
        assert rep.provenance["run_id"] == sup.ctx.run_id

    def test_resume_adopts_run_id_from_manifest(self, tmp_path):
        """The kill+resume identity contract, in-suite: a second process
        (fresh supervisor, no ctx) picks up the stored run_id, so the
        whole timeline shares one run."""
        rec1 = FlightRecorder()
        first = Supervisor(
            toy_chunk, toy_state(), n_chunks=4,
            checkpoint_dir=str(tmp_path), checkpoint_every=1,
            max_chunks_this_run=2, recorder=rec1,
        )
        rep1 = first.run()
        assert not rep1.ok  # controlled partial stop
        run_id = rep1.provenance["run_id"]
        assert {"checkpoint", "partial-stop"} <= {
            e["kind"] for e in rec1.events()
        }

        rec2 = FlightRecorder()
        second = Supervisor(
            toy_chunk, toy_state(), n_chunks=4,
            checkpoint_dir=str(tmp_path), checkpoint_every=1, recorder=rec2,
        )
        rep2 = second.run()
        assert rep2.ok
        assert rep2.provenance["run_id"] == run_id
        evs = rec2.events()
        resume = [e for e in evs if e["kind"] == "resume"]
        assert resume and resume[0]["run_id"] == run_id
        assert all(e["run_id"] == run_id for e in evs)
        # resumed continuation only runs the remaining chunks
        ends = [e["chunk_seq"] for e in evs if e["kind"] == "chunk-end"]
        assert ends == [2, 3]

    def test_failure_dumps_black_box(self, tmp_path):
        rec = FlightRecorder()

        def broken(s):
            raise ValueError("semantic bug")

        with pytest.raises(ValueError):
            Supervisor(
                broken, toy_state(), n_chunks=2,
                checkpoint_dir=str(tmp_path), recorder=rec,
            ).run()
        dump = os.path.join(str(tmp_path), DUMP_BASENAME)
        assert os.path.exists(dump)
        evs = read_events([dump])
        fail = [e for e in evs if e["kind"] == "failure"]
        assert fail, "no failure event in the dump"
        assert fail[0]["error"] == "ValueError"
        assert fail[0]["error_kind"] == "fatal"
        assert "semantic bug" in fail[0]["message"]

    def test_retry_events_recorded(self):
        rec = FlightRecorder()
        calls = {"n": 0}

        def flaky(s):
            calls["n"] += 1
            if calls["n"] == 2:
                raise RuntimeError("UNAVAILABLE: connection reset")
            return toy_chunk(s)

        from wittgenstein_tpu.runtime import RetryPolicy

        rep = Supervisor(
            flaky, toy_state(), n_chunks=2,
            retry=RetryPolicy(max_attempts=3, backoff_base_s=0.0),
            sleep=lambda s: None, recorder=rec,
        ).run()
        assert rep.ok
        retry = [e for e in rec.events() if e["kind"] == "retry"]
        assert retry and retry[0]["error_kind"] == "transient"
        assert retry[0]["error"] == "RuntimeError"


# ---------------------------------------------------------------------------
# armed-vs-unarmed bit-identity (>= 3 protocols)


def _final_bytes(state) -> dict:
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(state)[0]:
        a = np.asarray(leaf)
        out[jax.tree_util.keystr(path)] = (a.shape, str(a.dtype), a.tobytes())
    return out


def _build(protocol: str):
    from wittgenstein_tpu.serve.jobs import SERVE_PROTOCOLS
    from wittgenstein_tpu.telemetry import TelemetryConfig

    params = {
        "PingPong": {"node_ct": 32},
        "P2PFlood": {"node_count": 40},
        "Handel": {
            "node_count": 16, "threshold": 12, "pairing_time": 3,
            "level_wait_time": 20, "extra_cycle": 5,
            "dissemination_period_ms": 10, "fast_path": 10, "nodes_down": 0,
        },
    }[protocol]
    tele = TelemetryConfig(snapshots=2, snapshot_every_ms=20)
    return SERVE_PROTOCOLS[protocol].build(params, tele)


@pytest.mark.parametrize("protocol", ["PingPong", "P2PFlood", "Handel"])
def test_recorder_is_bitwise_neutral(protocol, tmp_path):
    """Same supervised chunked run twice — recorder armed to disk with a
    full trace context vs completely default — must produce final states
    that are bit-identical leaf-for-leaf.  The obs spine is read-only."""
    net, state = _build(protocol)
    states = replicate_state(state, 2)

    def run(armed: bool):
        kw = {}
        if armed:
            kw["recorder"] = FlightRecorder(
                path=str(tmp_path / f"{protocol}.jsonl")
            )
            kw["ctx"] = mint_context("parity", tenant_id="t0")
        rep = Supervisor.from_network(
            net, states, total_ms=40, chunk_ms=20, **kw
        ).run()
        assert rep.ok
        return rep.state

    armed = _final_bytes(run(True))
    unarmed = _final_bytes(run(False))
    assert armed.keys() == unarmed.keys()
    for key in armed:
        assert armed[key] == unarmed[key], f"{protocol}: {key} diverged"


# ---------------------------------------------------------------------------
# attribution


class TestAttributionUnit:
    @pytest.fixture(scope="class")
    def batched_final(self):
        net, state = _build("P2PFlood")
        rep = Supervisor.from_network(
            net, replicate_state(state, 3), total_ms=40, chunk_ms=40
        ).run()
        assert rep.ok
        return net, rep.state

    def test_replica_rows_shapes(self, batched_final):
        net, final = batched_final
        rows = replica_rows(net, final)
        assert rows["replicas"] == 3
        for key in ("ticks", "delivered", "dropped", "done_nodes"):
            assert rows[key] is not None and len(rows[key]) == 3

    def test_tenant_sums_reconcile_exactly(self, batched_final):
        net, final = batched_final
        members = [
            {"job_id": "a", "run_id": "ra", "tenant": "acme"},
            {"job_id": "b", "run_id": "rb", "tenant": "beta"},
        ]
        at = batch_attribution(net, final, members, capacity=3)
        batch, jobs, tenants = at["batch"], at["jobs"], at["tenants"]
        assert batch["live_rows"] == 2 and batch["padding_rows"] == 1
        # live + padding ticks account for every executed row-tick
        assert batch["ticks_live"] + batch["ticks_padding"] == (
            batch["ticks_total"]
        )
        # per-tenant ints sum EXACTLY to the live total; shares to 1.0
        assert sum(t["ticks"] for t in tenants.values()) == (
            batch["ticks_live"]
        )
        assert sum(
            t["device_time_share"] for t in tenants.values()
        ) == pytest.approx(1.0)
        assert jobs["a"]["replica"] == 0 and jobs["b"]["replica"] == 1
        assert jobs["a"]["run_id"] == "ra"
        assert tenants["acme"]["jobs"] == 1

    def test_unbatched_state_single_row(self):
        net, state = _build("PingPong")
        rep = Supervisor.from_network(
            net, state, total_ms=20, chunk_ms=20, batched=False
        ).run()
        rows = replica_rows(net, rep.state)
        assert rows["replicas"] == 1


class TestServeAttribution:
    BASE = {"protocol": "PingPong", "params": {"node_ct": 32}, "simMs": 60}

    def test_two_tenant_batch_attribution_and_metrics(self):
        from wittgenstein_tpu.serve import BatchScheduler, JobState
        from wittgenstein_tpu.telemetry.export import PromText

        rec = FlightRecorder()
        sched = BatchScheduler(auto_start=False, recorder=rec)
        a = sched.submit({**self.BASE, "seed": 0, "tenant": "acme"})
        b = sched.submit({**self.BASE, "seed": 1, "tenant": "beta"})
        while sched.drain_once():
            pass
        assert a.state is JobState.DONE, a.error
        assert b.state is JobState.DONE, b.error

        # admission + pack events tie job run_ids to the batch run
        kinds = [e["kind"] for e in rec.events()]
        assert kinds.count("admission") == 2
        pack = [e for e in rec.events() if e["kind"] == "pack"][0]
        assert [m["job_id"] for m in pack["members"]] == [a.id, b.id]
        assert {m["tenant"] for m in pack["members"]} == {"acme", "beta"}
        assert pack["run_id"].startswith("batch-")

        # each job's attribution reconciles against the batch totals
        at = a.result["attribution"]
        assert at["job"]["tenant"] == "acme"
        batch = at["batch"]
        assert batch["live_rows"] == 2
        tenant_ticks = (
            a.attribution["tenant"]["ticks"]
            + b.attribution["tenant"]["ticks"]
        )
        assert tenant_ticks == batch["ticks_live"]
        shares = (
            a.attribution["tenant"]["device_time_share"]
            + b.attribution["tenant"]["device_time_share"]
        )
        assert shares == pytest.approx(1.0)

        # metrics: per-tenant families + run_id-labelled latency samples
        summary = sched.metrics.summary()
        assert summary["tenants"]["acme"]["jobs"] == 1
        assert summary["tenants"]["beta"]["ticks"] == (
            b.attribution["tenant"]["ticks"]
        )
        p = PromText()
        sched.metrics.add_prometheus(p, sched.queue)
        text = p.render()
        assert 'witt_serve_tenant_ticks_total{tenant="acme"}' in text
        assert 'witt_serve_tenant_device_time_share{tenant="beta"}' in text
        assert f'run_id="{a.run_id}"' in text

    def test_job_payload_exposes_run_id_and_tenant(self):
        from wittgenstein_tpu.serve import BatchScheduler, JobState

        sched = BatchScheduler(auto_start=False)
        job = sched.submit({**self.BASE, "seed": 0, "tenant": "acme"})
        assert job.run_id.startswith("job-")
        doc = job.to_dict()
        assert doc["runId"] == job.run_id
        assert doc["tenant"] == "acme"
        while sched.drain_once():
            pass
        assert job.state is JobState.DONE
        assert job.to_dict()["attribution"]["job"]["tenant"] == "acme"

    def test_tenant_defaults_and_validation(self):
        from wittgenstein_tpu.serve.jobs import JobSpec

        assert JobSpec.from_dict(self.BASE).tenant == "default"
        assert (
            JobSpec.from_dict({**self.BASE, "tenantId": "t2"}).tenant == "t2"
        )
        with pytest.raises(ValueError):
            JobSpec.from_dict({**self.BASE, "tenant": ""})

    def test_tenant_never_splits_compat(self):
        from wittgenstein_tpu.serve import BatchScheduler

        sched = BatchScheduler(auto_start=False)
        a = sched.submit({**self.BASE, "seed": 0, "tenant": "acme"})
        b = sched.submit({**self.BASE, "seed": 1, "tenant": "beta"})
        assert a.compat == b.compat  # tenancy is attribution, not tracing


# ---------------------------------------------------------------------------
# obs_query + bench_trend tooling


class TestObsQuery:
    EVENTS = [
        {"ts": 10.0, "seq": 0, "kind": "admission", "run_id": "r1",
         "protocol": "PingPong"},
        {"ts": 10.5, "seq": 1, "kind": "chunk-start", "run_id": "r1",
         "chunk_seq": 0},
        {"ts": 11.0, "seq": 2, "kind": "chunk-end", "run_id": "r1",
         "chunk_seq": 0, "ticks": 9},
        {"ts": 11.2, "seq": 3, "kind": "chunk-start", "run_id": "r1",
         "chunk_seq": 1},
        {"ts": 11.3, "seq": 4, "kind": "kill", "run_id": "r1"},
    ]

    @pytest.fixture(scope="class")
    def obs_query(self):
        return _load_script("obs_query")

    def test_timeline_renders_every_event(self, obs_query):
        text = obs_query.render_timeline(self.EVENTS)
        assert "admission" in text and "kill" in text
        assert "chunk-end[0]" in text and "r1" in text
        assert len(text.splitlines()) == len(self.EVENTS)

    def test_chrome_trace_spans_and_orphans(self, obs_query):
        from wittgenstein_tpu.telemetry.trace import validate_chrome_trace

        doc = obs_query.to_chrome_trace(self.EVENTS)
        validate_chrome_trace(doc)
        spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert len(spans) == 1 and spans[0]["name"] == "chunk 0"
        assert spans[0]["dur"] == pytest.approx(0.5e6)
        # the start with no end (the kill) stays visible as an instant
        orphans = [
            e for e in doc["traceEvents"] if e["name"] == "chunk 1 (no end)"
        ]
        assert len(orphans) == 1

    def test_run_ids_summary(self, obs_query):
        runs = obs_query.run_ids(self.EVENTS)
        assert runs["r1"]["events"] == 5
        assert runs["r1"]["kinds"]["chunk-start"] == 2

    def test_collect_gathers_dumps(self, obs_query, tmp_path):
        src = tmp_path / "ckpts"
        src.mkdir()
        rec = FlightRecorder()
        rec.record("admission", TraceContext(run_id="rX"))
        rec.dump(str(src / DUMP_BASENAME))
        out = tmp_path / "out"
        report = obs_query.collect(str(out), [str(src)])
        assert report["events"] == 1 and "rX" in report["runs"]
        assert (out / "timeline.txt").exists()
        assert (out / "collect_report.json").exists()


class TestBenchTrend:
    @pytest.fixture(scope="class")
    def bench_trend(self):
        return _load_script("bench_trend")

    def _write_round(self, root, n, value, with_config=True, truncate=False):
        rec = {
            "metric": "handel256_sims_per_sec_chip", "value": value,
            "vs_baseline": value / 0.5,
        }
        if with_config:
            rec["config"] = {
                "node_count": 256, "n_replicas": 4,
                "sim_ms": 1000, "chunk_ms": 20,
            }
        tail = "XLA warning noise\n" + json.dumps(rec)
        if truncate:
            tail = tail[:-20]  # SIGKILL'd tee: record cut mid-object
        with open(os.path.join(root, f"BENCH_r{n:02d}.json"), "w") as f:
            json.dump({"n": n, "cmd": "bench", "rc": 0, "tail": tail}, f)

    def _write_floor(self, root, floor=0.5):
        with open(os.path.join(root, "BENCH_FLOOR.json"), "w") as f:
            json.dump(
                {
                    "metric": "handel256x4_cpu_sims_per_sec",
                    "node_count": 256, "n_replicas": 4, "floor": floor,
                    "note": "test floor",
                },
                f,
            )

    def test_parses_clean_and_truncated_rounds(self, bench_trend, tmp_path):
        root = str(tmp_path)
        self._write_round(root, 1, 1.0)
        self._write_round(root, 2, 1.2, truncate=True)
        self._write_floor(root)
        trend = bench_trend.build_trend(root)
        by_round = {r["round"]: r for r in trend["rounds"]}
        assert by_round[1]["sims_per_sec"] == 1.0
        assert by_round[2]["sims_per_sec"] == 1.2  # regex-recovered
        assert by_round[2]["node_count"] == 256
        assert trend["comparable_rounds"] == [1, 2]
        assert bench_trend.check(trend) == []

    def test_check_fails_below_floor(self, bench_trend, tmp_path):
        root = str(tmp_path)
        self._write_round(root, 1, 1.0)
        self._write_round(root, 2, 0.3)  # below floor 0.5 AND a >10% drop
        self._write_floor(root, floor=0.5)
        trend = bench_trend.build_trend(root)
        problems = bench_trend.check(trend)
        assert problems and "UNDOCUMENTED" in problems[0]
        assert trend["regressions"][0]["documented"] is False

    def test_documented_drop_passes(self, bench_trend, tmp_path):
        root = str(tmp_path)
        self._write_round(root, 1, 1.5)
        self._write_round(root, 2, 1.2)  # 20% drop, still above floor
        self._write_floor(root, floor=0.5)
        trend = bench_trend.build_trend(root)
        assert trend["regressions"][0]["documented"] is True
        assert bench_trend.check(trend) == []

    def test_repo_artifacts_pass_the_gate(self, bench_trend, tmp_path):
        """The committed floor (BENCH_FLOOR.json) and side-car artifacts
        must satisfy the gate the CI step enforces.  The repo keeps no
        BENCH_r*.json round files any more (none was measured on the
        chip), so one round at the floor's value stands in a fixture
        directory."""
        import shutil

        root = str(tmp_path)
        for name in os.listdir(ROOT):
            if name.startswith("BENCH_") and name.endswith(".json"):
                shutil.copy(os.path.join(ROOT, name), root)
        with open(os.path.join(root, "BENCH_FLOOR.json")) as f:
            floor = json.load(f)["floor"]
        self._write_round(root, 1, floor)
        trend = bench_trend.build_trend(root)
        assert trend["rounds"], "no BENCH rounds found"
        assert bench_trend.check(trend) == []
