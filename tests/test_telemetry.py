"""Telemetry subsystem tests.

The two contracts that make in-graph telemetry trustworthy:

  1. PARITY — enabling the counter side-car changes NOTHING in sim
     state: every non-tele SimState field of an instrumented run is
     bit-identical to the uninstrumented run (wheel and flat modes).
  2. RECONCILIATION — the store counters balance:
     sent == delivered + discarded + dropped + pending.

Plus the export layer: Prometheus text parses and carries the expected
families, JSONL run records round-trip, Chrome-trace JSON is valid
trace-event format, and the device-side snapshot ring reproduces the
done-at CDF computed host-side from the final state (run_ms_batched,
p2pflood fast; the Handel sweep equivalent lives in the slow tier)."""

import json

import jax
import numpy as np
import pytest

from wittgenstein_tpu.engine import replicate_state
from wittgenstein_tpu.protocols.p2pflood import P2PFloodParameters
from wittgenstein_tpu.protocols.p2pflood_batched import make_p2pflood
from wittgenstein_tpu.protocols.pingpong_batched import make_pingpong
from wittgenstein_tpu.telemetry import (
    PromText,
    RunRecordWriter,
    SpanTracer,
    TelemetryConfig,
    counters,
    done_counts_at,
    pending_count,
    progress_series,
    prometheus_from_counters,
    read_run_records,
    validate_chrome_trace,
)

CFG = TelemetryConfig(snapshots=64, snapshot_every_ms=10)


@pytest.fixture(scope="module")
def p2pflood_tele():
    """ONE instrumented p2pflood run shared by the CDF/reconciliation/
    stats-getter tests (the compile is the expensive part — keep the
    fast tier's added wall time small)."""
    cfg = TelemetryConfig(snapshots=128, snapshot_every_ms=10)
    net, st = make_p2pflood(P2PFloodParameters(), capacity=2048, telemetry=cfg)
    out = net.run_ms_batched(replicate_state(st, 2), 1200)
    return cfg, net, out


@pytest.fixture(scope="module")
def pingpong_tele():
    """One instrumented pingpong run shared by the export tests."""
    net, st = make_pingpong(64, telemetry=CFG)
    return net, net.run_ms(st, 300)


def assert_sim_parity(out_plain, out_tele):
    """Every non-tele field bit-identical (proto compared leaf-wise)."""
    for f in out_plain._fields:
        if f in ("tele", "proto"):
            continue
        a = np.asarray(getattr(out_plain, f))
        b = np.asarray(getattr(out_tele, f))
        assert np.array_equal(a, b), f"field {f} diverged under telemetry"
    pa = jax.tree_util.tree_leaves(out_plain.proto)
    pb = jax.tree_util.tree_leaves(out_tele.proto)
    assert len(pa) == len(pb)
    for i, (a, b) in enumerate(zip(pa, pb)):
        assert np.array_equal(np.asarray(a), np.asarray(b)), f"proto leaf {i}"


def assert_reconciles(net, out):
    """sent == delivered + discarded + dropped + pending, per replica."""
    tele = out.tele
    sent = np.asarray(tele.sent).sum(axis=-1)
    delivered = np.asarray(tele.delivered).sum(axis=-1)
    discarded = np.asarray(tele.discarded).sum(axis=-1)
    dropped = np.asarray(tele.dropped).sum(axis=-1)
    pend = (
        np.asarray(out.msg_valid).sum(axis=(-2, -1))
        + np.asarray(out.ovf_valid).sum(axis=-1)
    )
    np.testing.assert_array_equal(sent, delivered + discarded + dropped + pend)
    # the per-mtype dropped rows are exactly the scalar the store counts
    np.testing.assert_array_equal(dropped, np.asarray(out.dropped))


class TestParityAndReconciliation:
    @pytest.mark.parametrize("wheel_rows", [None, 0], ids=["wheel", "flat"])
    def test_pingpong_parity_and_invariant(self, wheel_rows):
        net0, st0 = make_pingpong(200, wheel_rows=wheel_rows)
        out0 = net0.run_ms(st0, 600)
        net1, st1 = make_pingpong(200, wheel_rows=wheel_rows, telemetry=CFG)
        out1 = net1.run_ms(st1, 600)
        assert_sim_parity(out0, out1)
        assert_reconciles(net1, out1)
        # pingpong: every ping accepted and answered, nothing in flight
        c = counters(net1, out1)
        assert sum(c["store"]["sent"]) == 400
        assert c["store"]["pending"] == 0 == pending_count(out1)
        # TICK_INTERVAL None protocol: the engine skipped empty ms and
        # said so
        assert c["loop"]["jumps"] > 0
        assert c["loop"]["ticks"] + c["loop"]["jumped_ms"] <= 600

    def test_p2pflood_batched_cdf_matches_host_side(self, p2pflood_tele):
        """run_ms_batched + snapshot ring: the device-side progress
        series reproduces the done-at CDF computed host-side from the
        final done_at column (the PR's acceptance criterion, fast-tier
        protocol; the Handel sweep twin is in the slow tier).

        The fixture's ring is sized to the horizon (sim_ms / every <=
        snapshots) so no window is lost to wrap — wrap keeps only the
        most recent S windows, fine for live monitoring, not a CDF."""
        sim_ms = 1200
        cfg, net, out = p2pflood_tele
        assert_reconciles(net, out)

        series = progress_series(out)  # one per replica
        assert len(series) == 2
        ends = [t + cfg.snapshot_every_ms - 1
                for t in range(0, sim_ms, cfg.snapshot_every_ms)]
        for r in range(2):
            done = np.asarray(out.done_at)[r]
            host_cdf = [int(((done > 0) & (done <= t)).sum()) for t in ends]
            dev_cdf = done_counts_at(series[r], ends)
            assert dev_cdf == host_cdf, f"replica {r} CDF diverged"
        # and the curve actually moved (the test is not vacuous)
        assert series[0][-1]["done"] > series[0][0]["done"]

    def test_batched_parity_under_vmap(self):
        """Telemetry is replica-local under vmap: batched instrumented
        run is bit-identical in sim state to the batched plain run."""
        net0, st0 = make_pingpong(128)
        out0 = net0.run_ms_batched(replicate_state(st0, 3), 400)
        net1, st1 = make_pingpong(128, telemetry=CFG)
        out1 = net1.run_ms_batched(replicate_state(st1, 3), 400)
        assert_sim_parity(out0, out1)
        assert_reconciles(net1, out1)
        # replicas draw different latencies -> distinct tick censuses are
        # plausible, but every replica must have executed ticks
        assert np.asarray(out1.tele.ticks).min() > 0


class TestStatsGetters:
    def test_batched_statsgetter_shapes(self, p2pflood_tele):
        from wittgenstein_tpu.core import stats as SH

        _, net, out = p2pflood_tele
        g = SH.DoneAtBatchedStatGetter()
        assert g.fields() == ["min", "max", "avg"]
        stat = g.get(out)
        done = np.asarray(out.done_at)[~np.asarray(out.down)]
        assert stat.get("min") == int(done.min())
        assert stat.get("max") == int(done.max())
        assert stat.get("avg") == int(done.sum()) // done.size
        c = SH.TelemetryCounterStatGetter("sent")
        assert c.fields() == ["count"]
        assert c.get(out).get("count") == int(np.asarray(out.tele.sent).sum())

    def test_telemetry_getter_requires_side_car(self):
        from wittgenstein_tpu.core import stats as SH

        net, st = make_pingpong(32)  # no telemetry
        with pytest.raises(ValueError, match="side-car"):
            SH.TelemetryCounterStatGetter("sent").get(st)


class TestExports:
    def test_prometheus_renders_and_parses(self, pingpong_tele):
        net, out = pingpong_tele
        text = prometheus_from_counters(counters(net, out))
        from test_server import parse_prometheus

        metrics = parse_prometheus(text)
        for name in (
            "witt_sim_time_ms",
            "witt_node_msg_sent_total",
            "witt_store_pending",
            "witt_store_sent_by_type_total",
            "witt_messages_sent_total",
            "witt_wheel_fill_hwm",
            "witt_ticks_total",
        ):
            assert name in metrics, f"{name} missing"
        by_type = dict(
            (labels["mtype"], v)
            for labels, v in metrics["witt_store_sent_by_type_total"]
        )
        assert set(by_type) == {"PING", "PONG"}
        assert by_type["PING"] == 64 and by_type["PONG"] == 64

    def test_promtext_escaping(self):
        text = PromText("x").add(
            "m", 1, 'he said "hi"\nback\\slash', labels={"k": 'v"\n\\'}
        ).render()
        assert '\\"hi\\"' in text and "\\n" in text and "\\\\" in text
        # one sample line, parseable
        from test_server import parse_prometheus

        assert parse_prometheus(text)["x_m"][0][0]["k"] == 'v\\"\\n\\\\'

    def test_run_record_roundtrip(self, tmp_path):
        path = str(tmp_path / "runs.jsonl")
        w = RunRecordWriter(path)
        rec1 = w.write({"a": np.int32(3), "arr": np.arange(3)}, tag="one")
        rec2 = w.write({"b": 2.5}, tag="two")
        back = read_run_records(path)
        assert back == [rec1, rec2]
        assert back[0]["a"] == 3 and back[0]["arr"] == [0, 1, 2]
        assert all(r["schema"] == "witt-run-record/v1" for r in back)
        # torn tail line is skipped, not fatal
        with open(path, "a") as f:
            f.write('{"unterminated": ')
        assert read_run_records(path) == back

    def test_chrome_trace_valid(self, tmp_path):
        tr = SpanTracer("test-proc")
        with tr.span("outer", stage=1):
            with tr.span("inner"):
                pass
        tr.instant("marker", note="x")
        path = tr.write(str(tmp_path / "trace.json"))
        doc = json.load(open(path))
        validate_chrome_trace(doc)
        names = [e["name"] for e in doc["traceEvents"]]
        assert {"outer", "inner", "marker"} <= set(names)
        spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        outer = next(e for e in spans if e["name"] == "outer")
        inner = next(e for e in spans if e["name"] == "inner")
        # containment: inner lies inside outer
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6
        with pytest.raises(ValueError):
            validate_chrome_trace({"nope": []})
        with pytest.raises(ValueError):
            validate_chrome_trace({"traceEvents": [{"ph": "X", "name": "n"}]})

    def test_progress_series_decoding(self, pingpong_tele):
        net, out = pingpong_tele
        series = progress_series(out)
        times = [r["time"] for r in series]
        assert times == sorted(times) and len(set(times)) == len(times)
        for key in ("done", "pending", "sent", "delivered"):
            assert all(key in r for r in series)
        # cumulative counters are monotone
        for key in ("sent", "delivered"):
            vals = [r[key] for r in series]
            assert vals == sorted(vals)
        # forward fill: before the first snapshot the count is 0
        assert done_counts_at(series, [-1]) == [0]


@pytest.mark.slow
class TestHandelTelemetry:
    def _cfgs(self):
        from wittgenstein_tpu.scenarios.handel_scenarios import flagship_params

        return flagship_params(64)

    @pytest.mark.parametrize("wheel_rows", [0, 64], ids=["flat", "wheel"])
    def test_handel_parity(self, wheel_rows):
        """Instrumented Handel (channel messaging bypasses the generic
        store) is bit-identical in sim state, in both store modes."""
        from wittgenstein_tpu.protocols.handel_batched import make_handel

        p = self._cfgs()
        net0, st0 = make_handel(p, wheel_rows=wheel_rows)
        out0 = net0.run_ms(st0, 1000)
        net1, st1 = make_handel(p, wheel_rows=wheel_rows, telemetry=CFG)
        out1 = net1.run_ms(st1, 1000)
        assert_sim_parity(out0, out1)
        assert_reconciles(net1, out1)
        # channel traffic is still visible through the latency-kernel tier
        assert int(np.asarray(out1.tele.lat_sent).sum()) > 0
        assert int(np.asarray(out1.tele.ticks).sum()) > 0

    def test_handel_sweep_progress_matches_host_cdf(self):
        """The PR's acceptance criterion on Handel: the device-side
        progress series from run_ms_batched (via the sweep driver)
        reproduces the done-at CDF the sweep computes host-side from the
        final state."""
        from wittgenstein_tpu.scenarios.handel_scenarios import flagship_params
        from wittgenstein_tpu.scenarios.sweep import SweepConfig, run_sweep

        cfg = TelemetryConfig(snapshots=256, snapshot_every_ms=10)
        tele_out = []
        stats = run_sweep(
            [SweepConfig("base", 0, flagship_params(64))],
            replicas=2,
            sim_ms=1500,
            telemetry=cfg,
            telemetry_out=tele_out,
        )
        assert len(tele_out) == 1
        rec = tele_out[0]
        # StatsGetter-shaped reductions agree with BasicStats
        assert rec["doneAt"]["max"] == stats[0].done_at_max
        assert rec["doneAt"]["min"] == stats[0].done_at_min
        series = rec["progress"]
        assert len(series) == 2
        host = rec["doneAtCdfHost"]
        for r in range(2):
            assert series[r][-1]["done"] == 64  # all nodes aggregated
            dev = done_counts_at(series[r], host["times"])
            assert dev == host["counts"][r], f"replica {r} CDF diverged"