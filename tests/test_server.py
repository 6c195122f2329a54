"""Serve-layer tests: the WServerTest-style every-protocol API sweep
(reference ws/WServerTest.java:65-122) plus endpoint flows over real HTTP
(stdlib client against the stdlib server on an ephemeral port)."""

import json
import urllib.request

import pytest

from wittgenstein_tpu.server import WServer, serve, shutdown_server


@pytest.fixture(scope="module")
def base_url():
    httpd = serve(0)
    port = httpd.server_address[1]
    yield f"http://127.0.0.1:{port}"
    shutdown_server(httpd)


def get(base, path):
    with urllib.request.urlopen(base + path, timeout=60) as r:
        return r.status, json.loads(r.read().decode())


def post(base, path, payload=None, method="POST"):
    data = (
        payload.encode()
        if isinstance(payload, str)
        else json.dumps(payload).encode()
        if payload is not None
        else b""
    )
    req = urllib.request.Request(
        base + path, data=data, method=method,
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=300) as r:
            return r.status, json.loads(r.read().decode())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read().decode())


class TestWServer:
    def test_protocol_list(self, base_url):
        status, ps = get(base_url, "/w/protocols")
        assert status == 200
        assert "PingPong" in ps
        # every reference protocol family, and Dfinity under its main()'s
        # partition as a parameter (protocols/dfinity_part.py, PR 46)
        assert len(ps) == 17 and "PartitionedDfinity" in ps

    def test_all_protocols_api_sweep(self, base_url):
        """WServerTest.testBasicAllProtocols (:65-122): for EVERY registered
        protocol, fetch default params, re-post them to init, and check the
        nodes and messages endpoints respond."""
        _, ps = get(base_url, "/w/protocols")
        for p in ps:
            status, params = get(base_url, f"/w/protocols/{p}")
            assert status == 200, p
            assert params["type"].endswith("Parameters"), p

            status, _ = post(base_url, f"/w/network/init/{p}", params)
            assert status == 200, p

            status, nodes = get(base_url, "/w/network/nodes")
            assert status == 200, p
            assert len(nodes) > 0, p

            status, out = get(base_url, "/w/network/messages")
            assert status == 200, p
            assert isinstance(out["messages"], list), p
            assert "occupancy" in out and "dropped" in out, p

    def test_run_and_inspect_flow(self, base_url):
        _, params = get(base_url, "/w/protocols/PingPong")
        params["node_ct"] = 100
        assert post(base_url, "/w/network/init/PingPong", params)[0] == 200

        status, out = post(base_url, "/w/network/runMs/200")
        assert status == 200 and out["time"] == 200
        assert get(base_url, "/w/network/time")[1] == 200

        _, n0 = get(base_url, "/w/network/nodes/0")
        assert n0["nodeId"] == 0
        assert n0["msgReceived"] > 0  # pongs arrived at the witness

        # stop/start (note the reference's own path asymmetry)
        assert post(base_url, "/w/network/nodes/5/stop")[0] == 200
        assert get(base_url, "/w/network/nodes/5")[1]["down"] is True
        assert post(base_url, "/w/nodes/5/start")[0] == 200
        assert get(base_url, "/w/network/nodes/5")[1]["down"] is False

    def test_message_injection(self, base_url):
        _, params = get(base_url, "/w/protocols/PingPong")
        params["node_ct"] = 50
        post(base_url, "/w/network/init/PingPong", params)
        status, _ = post(
            base_url,
            "/w/network/send",
            {
                "from": 3,
                "to": [1, 2],
                "sendTime": 1,
                "delayBetweenSend": 0,
                "message": {"type": "Ping"},
            },
        )
        assert status == 200
        _, out = get(base_url, "/w/network/messages")
        msgs = out["messages"]
        # one envelope may fan out to several EnvelopeInfos, so the
        # census bounds are envelope-count <= info-count
        assert 1 <= out["occupancy"]["pending_msgs"] <= len(msgs)
        assert any(m["msg"] == "Ping" and m["from"] == 3 for m in msgs)
        # deliver them: receivers answer with pongs
        post(base_url, "/w/network/runMs/1000")
        _, n3 = get(base_url, "/w/network/nodes/3")
        assert n3["msgReceived"] >= 2

    def test_external_sink_and_mock(self, base_url):
        _, params = get(base_url, "/w/protocols/PingPong")
        params["node_ct"] = 20
        post(base_url, "/w/network/init/PingPong", params)
        # the demo sink accepts an EnvelopeInfo and returns no sends
        status, out = post(base_url, "/w/external_sink", {"x": 1}, method="PUT")
        assert status == 200 and out == []
        # attach the local mock External to a node: sim keeps working
        assert post(base_url, "/w/network/nodes/2/external", "mock")[0] == 200
        _, n2 = get(base_url, "/w/network/nodes/2")
        assert n2["external"] == "ExternalMockImplementation"
        assert post(base_url, "/w/network/runMs/300")[0] == 200

    def test_sweep_endpoint(self, base_url):
        status, out = post(
            base_url,
            "/w/sweep",
            {
                "protocol": "Handel",
                "params": {},
                "runs": 2,
                "maxTime": 10_000,
                "stats": ["doneAt", "msgReceived"],
                "untilDone": True,
            },
        )
        assert status == 200
        assert out["runs"] == 2
        assert len(out["stats"]) == 2
        assert out["stats"][0]["max"] > 0

    def test_errors(self, base_url):
        assert post(base_url, "/w/network/init/NoSuchProtocol")[0] == 400
        assert get(base_url, "/w/protocols")[0] == 200
        status, _ = post(base_url, "/w/unknown/route")
        assert status == 404

    def test_external_rest_loopback(self, base_url):
        """ExternalRest round trip against our own /w/external_sink: a node
        delegated to the demo endpoint keeps the simulation running
        (reference flow: Network delivery -> ExternalRest PUT ->
        List[SendMessage], ExternalRest.java:36-59 + ExternalWS.java:22-40)."""
        _, params = get(base_url, "/w/protocols/PingPong")
        params["node_ct"] = 20
        post(base_url, "/w/network/init/PingPong", params)
        status, _ = post(
            base_url,
            "/w/network/nodes/3/external",
            f"{base_url}/w/external_sink",
        )
        assert status == 200
        _, n3 = get(base_url, "/w/network/nodes/3")
        assert "ExternalRest" in n3["external"]
        # run: node 3's deliveries round-trip over HTTP and return no sends
        assert post(base_url, "/w/network/runMs/400")[0] == 200
        _, n0 = get(base_url, "/w/network/nodes/0")
        assert n0["msgReceived"] > 0


def parse_prometheus(text):
    """Minimal text-format parser: {metric_name: [(labels_dict, value)]}.
    Raises on malformed sample lines — the test doubles as a format
    check."""
    import re as _re

    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = _re.match(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})? (\S+)$", line)
        assert m, f"malformed sample line: {line!r}"
        labels = {}
        if m.group(2):
            for part in m.group(2)[1:-1].split(","):
                if part:
                    k, v = part.split("=", 1)
                    labels[k] = v.strip('"')
        out.setdefault(m.group(1), []).append((labels, float(m.group(3))))
    return out


class TestTelemetryEndpoints:
    def test_metrics_before_init(self, base_url):
        """/metrics answers even on a fresh server (scrapers attach
        before the first init)."""
        import urllib.request as _rq

        with _rq.urlopen(base_url + "/metrics", timeout=60) as r:
            assert r.status == 200
            assert r.headers["Content-Type"].startswith("text/plain")
            text = r.read().decode()
        metrics = parse_prometheus(text)
        assert metrics["witt_server_up"][0][1] == 1

    def test_metrics_live_sim(self, base_url):
        """GET /metrics returns Prometheus text with engine counters for
        a live simulation (the PR's acceptance criterion)."""
        import urllib.request as _rq

        _, params = get(base_url, "/w/protocols/PingPong")
        params["node_ct"] = 60
        assert post(base_url, "/w/network/init/PingPong", params)[0] == 200
        assert post(base_url, "/w/network/runMs/150")[0] == 200

        with _rq.urlopen(base_url + "/metrics", timeout=60) as r:
            assert r.status == 200
            text = r.read().decode()
        metrics = parse_prometheus(text)
        for name in (
            "witt_sim_time_ms",
            "witt_nodes",
            "witt_live_nodes",
            "witt_node_msg_sent_total",
            "witt_node_msg_received_total",
            "witt_messages_dropped_total",
            "witt_store_pending",
        ):
            assert name in metrics, f"{name} missing from /metrics"
        assert metrics["witt_sim_time_ms"][0][1] == 150
        assert metrics["witt_nodes"][0][1] == 60
        assert metrics["witt_node_msg_received_total"][0][1] > 0

    def test_status_endpoint(self, base_url):
        _, params = get(base_url, "/w/protocols/PingPong")
        params["node_ct"] = 40
        post(base_url, "/w/network/init/PingPong", params)
        status, out = post(base_url, "/w/network/runMs/100")
        assert status == 200
        assert "occupancy" in out and "dropped" in out  # runMs status payload
        status, st = get(base_url, "/w/network/status")
        assert status == 200
        assert st["nodeCount"] == 40 and st["time"] == 100
        assert st["msgSent"] >= st["msgReceived"] > 0
        assert st["occupancy"]["pending_msgs"] >= 0
        assert st["dropped"] == 0

    def test_status_dropped_counts_down_sends(self, base_url):
        """Sends to a stopped node are filtered at send time and show up
        in the dropped counter (oracle twin of SimState.dropped)."""
        _, params = get(base_url, "/w/protocols/PingPong")
        params["node_ct"] = 30
        post(base_url, "/w/network/init/PingPong", params)
        post(base_url, "/w/network/nodes/7/stop")
        post(
            base_url,
            "/w/network/send",
            {
                "from": 3,
                "to": [7],
                "sendTime": 1,
                "delayBetweenSend": 0,
                "message": {"type": "Ping"},
            },
        )
        _, st = get(base_url, "/w/network/status")
        assert st["dropped"] >= 1


class TestStaticUI:
    def test_index_served(self, base_url):
        """The browser UI (reference wserver static/index.html analog) is
        served at / and /index.html with the protocol/param/run controls."""
        for path in ("/", "/index.html"):
            with urllib.request.urlopen(base_url + path, timeout=60) as r:
                assert r.status == 200
                assert r.headers["Content-Type"].startswith("text/html")
                page = r.read().decode()
            assert "protocolsList" in page  # protocol list pane
            assert "protocolParameters" in page  # editable params pane
            assert "/network/init/" in page  # init wiring
            assert "runMs" in page and "nodeStatus" in page


class TestDurableRunEndpoints:
    """ISSUE 6 durability surfaces: busy/degraded 503 + Retry-After,
    the interrupt endpoint, and interrupted-runMs resume."""

    def _fresh(self, node_ct=30):
        ws = WServer()
        params = json.loads(
            ws.server.get_protocol_parameters("PingPong").to_json()
        )
        params["node_ct"] = node_ct
        ws.dispatch("POST", "/w/network/init/PingPong", json.dumps(params))
        return ws

    def test_interrupt_endpoint_idle(self, base_url):
        status, out = post(base_url, "/w/network/interrupt")
        assert status == 200
        assert out == {"ok": True, "running": False}

    def test_busy_503_with_retry_after(self):
        ws = self._fresh()
        assert ws.run_lock.acquire(blocking=False)  # a run "in flight"
        try:
            status, resp = ws.dispatch("POST", "/w/network/runMs/100", "")
            assert status == 503
            assert resp.payload["busy"] is True
            assert int(resp.headers["Retry-After"]) >= 1
        finally:
            ws.run_lock.release()
        # lock released: the same request now runs
        status, out = ws.dispatch("POST", "/w/network/runMs/100", "")
        assert status == 200 and out["ok"] is True

    def test_degraded_503_until_reinit(self):
        ws = self._fresh()
        ws.degraded = True
        ws.degraded_reason = "RuntimeError: slice blew up"
        status, resp = ws.dispatch("POST", "/w/network/runMs/50", "")
        assert status == 503
        assert resp.payload["degraded"] is True
        assert "slice blew up" in resp.payload["error"]
        assert resp.headers["Retry-After"] == "30"
        status, st = ws.dispatch("GET", "/w/network/status", "")
        assert st["degraded"] is True and "slice blew up" in st["degradedReason"]
        # re-init clears the latch (a fresh sim is a fresh backend)
        params = json.loads(
            ws.server.get_protocol_parameters("PingPong").to_json()
        )
        ws.dispatch("POST", "/w/network/init/PingPong", json.dumps(params))
        status, out = ws.dispatch("POST", "/w/network/runMs/50", "")
        assert status == 200 and out["ok"] is True

    def test_slice_failure_latches_degraded(self):
        ws = self._fresh()
        def boom(ms):
            raise OSError("backend fell over")
        ws.server.run_ms = boom
        status, resp = ws.dispatch("POST", "/w/network/runMs/100", "")
        assert status == 500
        assert ws.degraded is True and "backend fell over" in ws.degraded_reason
        status, _ = ws.dispatch("POST", "/w/network/runMs/100", "")
        assert status == 503  # honest 503 from now on, not a race

    def test_uninitialized_runms_is_409_not_degraded(self):
        ws = WServer()  # no init
        status, _ = ws.dispatch("POST", "/w/network/runMs/10", "")
        assert status == 409
        assert ws.degraded is False  # operator error, not a backend fault

    def test_interrupted_runms_resumes(self):
        """Interrupt lands on a slice boundary; a repeat runMs with the
        remaining ms resumes to the exact total sim time."""
        ws = self._fresh()
        orig = ws.server.run_ms

        def run_then_interrupt(ms):
            orig(ms)
            ws._interrupt.set()  # as if POST /w/network/interrupt raced in

        ws.server.run_ms = run_then_interrupt
        status, out = ws.dispatch("POST", "/w/network/runMs/200", "")
        assert status == 200
        assert out["interrupted"] is True and out["ok"] is False
        assert out["ranMs"] == ws.RUN_SLICE_MS  # stopped after one slice
        assert out["requestedMs"] == 200
        assert out["time"] == ws.RUN_SLICE_MS

        ws.server.run_ms = orig
        remaining = 200 - out["ranMs"]
        status, out2 = ws.dispatch("POST", f"/w/network/runMs/{remaining}", "")
        assert status == 200 and out2["ok"] is True
        assert out2["interrupted"] is False
        assert out2["time"] == 200  # state was consistent at the boundary


class TestRunMsGateway:
    """ISSUE 13 satellite: runMs is a submitted job over the serve/
    queue — one dispatch discipline for the whole fleet — with the
    legacy busy/degraded/queue-full 503 semantics preserved."""

    def _fresh(self, node_ct=30, **sched_kw):
        from wittgenstein_tpu.serve import BatchScheduler

        ws = WServer(scheduler=BatchScheduler(**sched_kw)) if sched_kw \
            else WServer()
        params = json.loads(
            ws.server.get_protocol_parameters("PingPong").to_json()
        )
        params["node_ct"] = node_ct
        ws.dispatch("POST", "/w/network/init/PingPong", json.dumps(params))
        return ws

    def test_runms_routed_through_job_queue(self):
        ws = self._fresh()
        submitted0 = ws.jobs.metrics.jobs_submitted
        completed0 = ws.jobs.metrics.jobs_completed
        status, out = ws.dispatch("POST", "/w/network/runMs/120", "")
        assert status == 200
        assert out["ok"] is True and out["ranMs"] == 120
        assert "occupancy" in out and "dropped" in out
        assert ws.jobs.metrics.jobs_submitted == submitted0 + 1
        assert ws.jobs.metrics.jobs_completed == completed0 + 1

    def test_runms_queue_full_503_with_retry_after(self):
        from wittgenstein_tpu.serve import BatchScheduler, JobQueue

        ws = WServer(scheduler=BatchScheduler(
            queue=JobQueue(max_depth=1), auto_start=False,
        ))
        # fill the queue; no worker drains it (auto_start=False)
        ws.jobs.queue.submit(
            __import__("wittgenstein_tpu.serve.jobs", fromlist=["Job"]).Job(
                spec=None, compat="filler", kind="legacy",
                thunk=lambda: None,
            ),
            retry_after_s=1,
        )
        status, resp = ws.dispatch("POST", "/w/network/runMs/50", "")
        assert status == 503
        assert resp.payload["busy"] is True
        assert int(resp.headers["Retry-After"]) >= 1
        assert not ws.run_lock.locked()  # released on the rejection path

    def test_runms_errors_keep_status_mapping(self):
        # uninitialized -> 409 even through the queue (RuntimeError is
        # re-raised from the job record into the handler)
        ws = WServer()
        status, _ = ws.dispatch("POST", "/w/network/runMs/10", "")
        assert status == 409
        assert ws.degraded is False


class TestOpsEndpoints:
    """ISSUE 14: the operational surface — /w/health, /w/ready, and the
    graceful-drain admin endpoints, plus the quarantine status mapping
    on the jobs surface."""

    BASE = {"protocol": "PingPong", "params": {"node_ct": 32}, "simMs": 60}

    def _ws(self, **kw):
        from wittgenstein_tpu.serve import BatchScheduler

        kw.setdefault("auto_start", False)
        return WServer(scheduler=BatchScheduler(**kw))

    def test_health_always_200_with_fleet_snapshot(self):
        ws = self._ws()
        status, h = ws.dispatch("GET", "/w/health", "")
        assert status == 200
        for key in ("queueDepth", "lanes", "lanesAlive", "draining",
                    "quarantinedTotal", "laneRestartsTotal", "runCache",
                    "compileStore", "errorKinds", "degraded"):
            assert key in h, key
        # health stays 200 while draining — liveness, not readiness
        ws.jobs.drain()
        status, h = ws.dispatch("GET", "/w/health", "")
        assert status == 200
        assert h["draining"] is True

    def test_ready_flips_503_while_draining(self):
        ws = self._ws()
        status, r = ws.dispatch("GET", "/w/ready", "")
        assert status == 200 and r["ready"] is True
        ws.dispatch("POST", "/w/admin/drain", "")
        status, r = ws.dispatch("GET", "/w/ready", "")
        assert status == 503
        assert r.payload["reason"] == "draining"
        assert int(r.headers["Retry-After"]) >= 1
        ws.dispatch("POST", "/w/admin/undrain", "")
        status, r = ws.dispatch("GET", "/w/ready", "")
        assert status == 200

    def test_ready_503_when_degraded(self):
        ws = self._ws()
        ws.degraded = True
        ws.degraded_reason = "test: slice blew up"
        status, r = ws.dispatch("GET", "/w/ready", "")
        assert status == 503
        assert r.payload["reason"] == "degraded"

    def test_drain_rejects_submissions_with_503(self):
        ws = self._ws()
        status, d = ws.dispatch("POST", "/w/admin/drain", "")
        assert status == 200 and d["draining"] is True
        status, r = ws.dispatch("POST", "/w/jobs", json.dumps(self.BASE))
        assert status == 503
        assert r.payload["draining"] is True
        assert int(r.headers["Retry-After"]) >= 1
        status, r = ws.dispatch(
            "POST", "/w/sweep",
            json.dumps({"protocol": "PingPong", "runs": 1}),
        )
        assert status == 503
        status, d = ws.dispatch("GET", "/w/admin/drain", "")
        assert status == 200 and d["quiescent"] is True
        ws.dispatch("POST", "/w/admin/undrain", "")
        status, r = ws.dispatch("POST", "/w/jobs", json.dumps(self.BASE))
        assert status == 202

    def test_quarantined_job_result_is_422_with_kind(self):
        ws = self._ws(max_batch_replicas=4)
        sched = ws.jobs
        specs = [dict(self.BASE, seed=i) for i in range(3)]
        ids = []
        for s in specs:
            status, r = ws.dispatch("POST", "/w/jobs", json.dumps(s))
            assert status == 202
            ids.append(r.payload["id"])
        poison = ids[1]

        def injector(fam, jobs):
            if any(j.id == poison for j in jobs):
                raise RuntimeError("chaos: poison row")

        sched.chaos_injector = injector
        while sched.drain_once():
            pass
        status, r = ws.dispatch("GET", f"/w/jobs/{poison}/result", "")
        assert status == 422
        assert r.payload["state"] == "quarantined"
        assert r.payload["errorKind"] == "poison_row"
        assert r.payload["quarantined"] is True
        for jid in ids:
            if jid == poison:
                continue
            status, r = ws.dispatch("GET", f"/w/jobs/{jid}/result", "")
            assert status == 200, (jid, r)
        # the status payload carries the taxonomy kind too
        status, r = ws.dispatch("GET", f"/w/jobs/{poison}", "")
        assert status == 200
        assert r["errorKind"] == "poison_row"

    def test_health_over_real_http(self, base_url):
        status, h = get(base_url, "/w/health")
        assert status == 200
        assert h["lanesAlive"] >= 0
        status, r = get(base_url, "/w/ready")
        assert status == 200
