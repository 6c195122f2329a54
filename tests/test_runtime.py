"""Durable-run supervisor tests (wittgenstein_tpu.runtime).

The load-bearing claim, pinned here: a supervised run killed mid-way
(simulated preemption: `max_chunks_this_run` stops the process's loop
exactly the way SIGKILL stops the process, from the checkpoint's point
of view) and then resumed is BIT-IDENTICAL to an uninterrupted run —
including the telemetry counter side-car and the fault-lane schedule
state.  scripts/durable_smoke.py proves the same claim with a real
SIGKILL across processes; these tests keep the in-suite version fast.

Around that claim, the control surfaces: watchdog deadlines and
exhausted retries raise their structured types, transient failures
replay deterministically from the host anchor, degradation stamps
provenance, and a checkpoint from a different run refuses to resume.
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from wittgenstein_tpu.engine import replicate_state
from wittgenstein_tpu.runtime import (
    DegradePolicy,
    DeviceLostError,
    FatalRunError,
    ResumeMismatchError,
    RetriesExhaustedError,
    RetryPolicy,
    Supervisor,
    WatchdogPolicy,
    WatchdogTimeoutError,
    WatchdogWorker,
    classify,
    run_with_deadline,
    stable_run_key,
)


def toy_state():
    return {"x": jnp.arange(4, dtype=jnp.int32), "step": jnp.int32(0)}


def toy_chunk(s):
    return {"x": s["x"] * 2 + 1, "step": s["step"] + 1}


def toy_after(n):
    s = toy_state()
    for _ in range(n):
        s = toy_chunk(s)
    return s


def assert_trees_equal(a, b):
    la = jax.tree_util.tree_flatten_with_path(a)[0]
    lb = jax.tree_util.tree_flatten_with_path(b)[0]
    assert len(la) == len(lb)
    for (pa, va), (_, vb) in zip(la, lb):
        na, nb = np.asarray(va), np.asarray(vb)
        assert na.shape == nb.shape and na.dtype == nb.dtype, pa
        assert na.tobytes() == nb.tobytes(), pa


class TestClassify:
    def test_typed_errors(self):
        assert classify(DeviceLostError("gone")) == "device_lost"
        assert classify(FatalRunError("no")) == "fatal"
        assert classify(WatchdogTimeoutError("chunk", 1.0)) == "fatal"

    def test_backend_message_markers(self):
        assert classify(RuntimeError("DEADLINE_EXCEEDED: rpc")) == "transient"
        assert classify(RuntimeError("server UNAVAILABLE")) == "transient"
        assert classify(RuntimeError("tpu is dead")) == "device_lost"
        assert classify(OSError("Connection reset by peer")) == "transient"

    def test_default_is_fatal(self):
        assert classify(ValueError("shape mismatch")) == "fatal"
        assert classify(KeyboardInterrupt()) == "fatal"


class TestRetryPolicy:
    def test_deterministic_and_bounded(self):
        p = RetryPolicy(backoff_base_s=0.5, backoff_factor=2.0,
                        backoff_max_s=4.0, jitter_frac=0.25, seed=7)
        a = [p.delay_s(k) for k in range(6)]
        b = [p.delay_s(k) for k in range(6)]
        assert a == b  # same (seed, attempt) -> same jitter, replayable
        for k, d in enumerate(a):
            base = min(4.0, 0.5 * 2.0**k)
            assert base * 0.75 <= d <= base * 1.25

    def test_seed_varies_jitter(self):
        d0 = RetryPolicy(seed=0).delay_s(1)
        d1 = RetryPolicy(seed=1).delay_s(1)
        assert d0 != d1


class TestWatchdog:
    def test_fast_call_passes_value(self):
        assert run_with_deadline(lambda: 41 + 1, 5.0, "chunk") == 42

    def test_deadline_miss_raises_typed(self):
        ev = threading.Event()
        with pytest.raises(WatchdogTimeoutError) as ei:
            run_with_deadline(lambda: ev.wait(30), 0.05, "compile+chunk")
        ev.set()  # unblock the leaked worker
        assert ei.value.phase == "compile+chunk"
        assert ei.value.deadline_s == 0.05

    def test_worker_exception_propagates(self):
        def boom():
            raise ValueError("inner")

        with pytest.raises(ValueError, match="inner"):
            run_with_deadline(boom, 5.0, "chunk")


class TestWatchdogWorker:
    """The persistent-worker watchdog (the thread-leak fix): one thread
    serves every guarded call of a run and is joined on close()."""

    def test_one_thread_reused_across_calls(self):
        w = WatchdogWorker()
        names = set()
        for _ in range(5):
            assert w.call(threading.current_thread, 5.0, "chunk").ident
            names.add(w.call(lambda: threading.get_ident(), 5.0, "chunk"))
        assert len(names) == 1, "worker thread churned between calls"
        assert w.close()

    def test_close_joins_thread(self):
        before = threading.active_count()
        w = WatchdogWorker()
        assert w.call(lambda: 1, 5.0, "chunk") == 1
        assert w.close()
        assert threading.active_count() == before

    def test_hung_worker_abandoned_never_reused(self):
        ev = threading.Event()
        w = WatchdogWorker()
        with pytest.raises(WatchdogTimeoutError):
            w.call(lambda: ev.wait(30), 0.05, "chunk")
        assert w.hung
        with pytest.raises(RuntimeError, match="hung"):
            w.call(lambda: 2, 5.0, "chunk")
        assert w.close() is False  # abandoned, not joined
        # once the stuck call returns, the pre-queued sentinel lets the
        # abandoned thread exit — the leak lasts only as long as the hang
        th = w._thread
        ev.set()
        if th is not None:
            th.join(5.0)
            assert not th.is_alive()

    def test_thread_count_stable_across_10_chunk_supervised_run(self):
        """The satellite regression: a watchdog-armed 10-chunk run holds
        at most ONE extra thread while running and zero afterwards (the
        old per-chunk spawn churned a thread per chunk and left the last
        one unjoined)."""
        # threads that earlier tests of this worker left winding down must
        # not be counted as the baseline: wait until the count stands still
        baseline, since = threading.active_count(), time.monotonic()
        while time.monotonic() - since < 0.5:
            time.sleep(0.02)
            if threading.active_count() != baseline:
                baseline, since = threading.active_count(), time.monotonic()
        during = []

        rep = Supervisor(
            toy_chunk, toy_state(), n_chunks=10,
            watchdog=WatchdogPolicy(
                chunk_deadline_s=30.0, compile_deadline_s=30.0
            ),
            heartbeat=lambda i, dt: during.append(threading.active_count()),
        ).run()
        assert rep.ok and rep.chunks_done == 10
        assert max(during) <= baseline + 1, (
            f"watchdog churned threads: baseline={baseline}, "
            f"during={during}"
        )
        deadline = time.monotonic() + 5.0
        while (
            threading.active_count() > baseline
            and time.monotonic() < deadline
        ):
            time.sleep(0.01)
        assert threading.active_count() == baseline, (
            "watchdog worker outlived its run"
        )


class TestSupervisorLoop:
    def test_runs_all_chunks(self):
        rep = Supervisor(toy_chunk, toy_state(), n_chunks=5).run()
        assert rep.ok and rep.chunks_done == 5
        assert len(rep.chunk_seconds) == 5
        assert rep.provenance["platform"] == "cpu"
        assert rep.provenance["retries"] == 0
        assert_trees_equal(rep.state, toy_after(5))

    def test_transient_retry_replays_from_anchor(self):
        calls = {"n": 0}

        def flaky(s):
            calls["n"] += 1
            if calls["n"] == 3:  # fail mid-run, once
                raise RuntimeError("UNAVAILABLE: connection reset")
            return toy_chunk(s)

        rep = Supervisor(
            flaky, toy_state(), n_chunks=4,
            retry=RetryPolicy(max_attempts=3, backoff_base_s=0.0),
            sleep=lambda s: None,
        ).run()
        assert rep.ok
        assert rep.provenance["retries"] == 1
        # the retried timeline produced the exact bytes of a clean run
        assert_trees_equal(rep.state, toy_after(4))

    def test_retries_exhausted_is_typed(self):
        def dead(s):
            raise RuntimeError("UNAVAILABLE: still down")

        with pytest.raises(RetriesExhaustedError) as ei:
            Supervisor(
                dead, toy_state(), n_chunks=2,
                retry=RetryPolicy(max_attempts=3, backoff_base_s=0.0),
                sleep=lambda s: None,
            ).run()
        assert ei.value.attempts == 3
        assert "UNAVAILABLE" in str(ei.value.last)

    def test_fatal_error_raises_raw(self):
        def broken(s):
            raise ValueError("semantic bug")

        with pytest.raises(ValueError, match="semantic bug"):
            Supervisor(broken, toy_state(), n_chunks=2).run()

    def test_watchdog_timeout_raises_in_loop(self):
        ev = threading.Event()

        def hang(s):
            ev.wait(30)
            return s

        with pytest.raises(WatchdogTimeoutError) as ei:
            Supervisor(
                hang, toy_state(), n_chunks=2,
                watchdog=WatchdogPolicy(
                    chunk_deadline_s=0.05, compile_deadline_s=0.05
                ),
            ).run()
        ev.set()
        assert ei.value.phase == "compile+chunk"  # first call of the process

    def test_degrade_stamps_provenance(self):
        calls = {"n": 0}

        def lossy(s):
            calls["n"] += 1
            if calls["n"] == 1:
                raise DeviceLostError("tpu is dead")
            return toy_chunk(s)

        rep = Supervisor(
            lossy, toy_state(), n_chunks=3,
            retry=RetryPolicy(max_attempts=3, backoff_base_s=0.0),
            degrade=DegradePolicy(cpu_fallback=True),
            sleep=lambda s: None,
        ).run()
        assert rep.ok
        assert rep.provenance["degraded"] is True
        assert rep.provenance["degraded_at_chunk"] == 0
        assert_trees_equal(rep.state, toy_after(3))

    def test_heartbeat_sees_every_chunk(self):
        beats = []
        Supervisor(
            toy_chunk, toy_state(), n_chunks=3,
            heartbeat=lambda i, dt: beats.append(i),
        ).run()
        assert beats == [0, 1, 2]


class TestCheckpointResume:
    def test_partial_stop_then_resume_is_bitwise(self, tmp_path):
        ckdir = str(tmp_path / "ck")
        kw = dict(n_chunks=5, checkpoint_dir=ckdir, run_key="toy:5")
        rep1 = Supervisor(
            toy_chunk, toy_state(), max_chunks_this_run=2, **kw
        ).run()
        assert not rep1.ok and rep1.chunks_done == 2

        rep2 = Supervisor(toy_chunk, toy_state(), **kw).run()
        assert rep2.ok and rep2.chunks_done == 5
        assert rep2.provenance["resumed_from_step"] == 2
        assert_trees_equal(rep2.state, toy_after(5))

    def test_off_cadence_partial_stop_still_checkpoints(self, tmp_path):
        ckdir = str(tmp_path / "ck")
        kw = dict(n_chunks=6, checkpoint_dir=ckdir, checkpoint_every=4)
        rep1 = Supervisor(
            toy_chunk, toy_state(), max_chunks_this_run=3, **kw
        ).run()
        assert not rep1.ok and rep1.chunks_done == 3  # 3 is off-cadence

        rep2 = Supervisor(toy_chunk, toy_state(), **kw).run()
        assert rep2.ok
        assert rep2.provenance["resumed_from_step"] == 3
        assert_trees_equal(rep2.state, toy_after(6))

    def test_run_key_mismatch_refuses_resume(self, tmp_path):
        ckdir = str(tmp_path / "ck")
        Supervisor(
            toy_chunk, toy_state(), n_chunks=4, checkpoint_dir=ckdir,
            run_key="run-A", max_chunks_this_run=1,
        ).run()
        with pytest.raises(ResumeMismatchError, match="run-A"):
            Supervisor(
                toy_chunk, toy_state(), n_chunks=4, checkpoint_dir=ckdir,
                run_key="run-B",
            ).run()

    def test_chunk_geometry_mismatch_refuses_resume(self, tmp_path):
        ckdir = str(tmp_path / "ck")
        Supervisor(
            toy_chunk, toy_state(), n_chunks=4, chunk_ms=50,
            checkpoint_dir=ckdir, max_chunks_this_run=1,
        ).run()
        with pytest.raises(ResumeMismatchError, match="chunk_ms"):
            Supervisor(
                toy_chunk, toy_state(), n_chunks=4, chunk_ms=100,
                checkpoint_dir=ckdir,
            ).run()

    def test_meta_carries_cumulative_chunk_seconds(self, tmp_path):
        from wittgenstein_tpu.engine.checkpoint import (
            CheckpointManager,
            read_manifest,
        )

        ckdir = str(tmp_path / "ck")
        kw = dict(n_chunks=4, checkpoint_dir=ckdir)
        Supervisor(toy_chunk, toy_state(), max_chunks_this_run=2, **kw).run()
        Supervisor(toy_chunk, toy_state(), **kw).run()
        mgr = CheckpointManager(ckdir)
        meta = read_manifest(mgr.path_for(mgr.latest_step()))["meta"]
        assert meta["chunks_done"] == 4
        assert len(meta["chunk_seconds"]) == 4  # prior run's times kept


class TestStableRunKey:
    def test_stable_across_copies_and_shape_sensitive(self):
        class FakeNet:
            protocol = object()

        s1 = toy_state()
        s2 = toy_state()
        k1 = stable_run_key(FakeNet(), s1, 8, 50)
        assert k1 == stable_run_key(FakeNet(), s2, 8, 50)
        assert k1 != stable_run_key(FakeNet(), s1, 4, 50)
        wider = {"x": jnp.arange(8, dtype=jnp.int32), "step": jnp.int32(0)}
        assert k1 != stable_run_key(FakeNet(), wider, 8, 50)

    def test_never_materializes_leaves(self):
        class FakeNet:
            protocol = object()

        class ShapeOnly:
            shape = (4,)
            dtype = "int32"

            def __array__(self):  # pragma: no cover - the assertion
                raise AssertionError("run key must not read leaf values")

        key = stable_run_key(FakeNet(), {"x": ShapeOnly()}, 2, 10)
        assert "2x10ms" in key


@pytest.fixture(scope="module")
def armed_pingpong():
    """A fixed-latency pingpong with BOTH side-cars armed: a crash plan
    in the fault lane and the telemetry counter/snapshot lane — the
    instrumented configuration the bit-identity acceptance pins."""
    from wittgenstein_tpu.faults import FaultPlan
    from wittgenstein_tpu.protocols.pingpong_batched import make_pingpong
    from wittgenstein_tpu.telemetry.state import TelemetryConfig

    net, state = make_pingpong(
        32, network_latency_name="NetworkFixedLatency(100)"
    )
    fnet, fstate = net.with_faults(
        state, plan=FaultPlan("crash5").crash([5], at=50, recover=150)
    )
    tnet, tstate = fnet.with_telemetry(
        fstate, TelemetryConfig(snapshots=4, snapshot_every_ms=100)
    )
    return tnet, tstate


class TestKillAndResumeBitIdentity:
    """The acceptance claim: interrupt + resume == uninterrupted, to the
    bit, on a run_ms_batched pass with telemetry ON and a fault plan
    armed."""

    TOTAL_MS, CHUNK_MS, REPLICAS = 400, 50, 2

    def _supervised(self, net, state, **kw):
        return Supervisor.from_network(
            net,
            replicate_state(state, self.REPLICAS),
            total_ms=self.TOTAL_MS,
            chunk_ms=self.CHUNK_MS,
            **kw,
        ).run()

    def test_interrupt_resume_bitwise_with_sidecars(
        self, armed_pingpong, tmp_path
    ):
        net, state = armed_pingpong
        ref = self._supervised(net, state)  # uninterrupted reference
        assert ref.ok and ref.chunks_done == 8

        ckdir = str(tmp_path / "ck")
        rep1 = self._supervised(
            net, state, checkpoint_dir=ckdir, max_chunks_this_run=3
        )
        assert not rep1.ok and rep1.chunks_done == 3  # "killed" mid-run

        rep2 = self._supervised(net, state, checkpoint_dir=ckdir)
        assert rep2.ok
        assert rep2.provenance["resumed_from_step"] == 3
        # bitwise equality over EVERY leaf: sim state, telemetry
        # counters + snapshot ring, fault schedule + fault counters
        assert_trees_equal(rep2.state, ref.state)
        tele = rep2.state.tele
        assert int(np.asarray(tele.delivered).sum()) > 0  # side-car live
        assert int(np.asarray(rep2.state.faults.dropped_by_fault).sum()) > 0

    def test_supervised_equals_manual_chunk_loop(self, armed_pingpong):
        """The supervisor adds nothing to the bytes: its pass equals a
        bare chunk loop with the same schedule.  (For TICK_INTERVAL=None
        protocols like pingpong the SCHEDULE itself is part of identity
        — each run_ms call clips the idle-time jump at its horizon, so
        send_ctr advances per call; that's why run_key pins chunk
        geometry and resume replays the exact remaining schedule.)"""
        net, state = armed_pingpong
        s = replicate_state(state, self.REPLICAS)
        for _ in range(self.TOTAL_MS // self.CHUNK_MS):
            s = net.run_ms_batched(s, self.CHUNK_MS)
        rep = self._supervised(net, state)
        assert_trees_equal(rep.state, s)

    def test_tick_driven_chunked_equals_straight(self):
        """For a tick-driven protocol (TICK_INTERVAL=1: every ms
        executes regardless of chunking) the supervised chunked pass is
        bitwise the STRAIGHT run — the strongest form of the claim."""
        from wittgenstein_tpu.protocols.handel import HandelParameters
        from wittgenstein_tpu.protocols.handel_batched import make_handel

        p = HandelParameters(
            node_count=32, threshold=28, pairing_time=3,
            level_wait_time=20, extra_cycle=5, dissemination_period_ms=10,
            fast_path=5, nodes_down=0,
        )
        net, state = make_handel(p)
        batched = replicate_state(state, 2)
        straight = net.run_ms_batched(batched, 200)
        rep = Supervisor.from_network(
            net, replicate_state(state, 2), total_ms=200, chunk_ms=50
        ).run()
        assert rep.ok
        assert_trees_equal(rep.state, straight)


class TestResumableFaultSweep:
    def test_interrupted_sweep_resumes_bitwise(self, tmp_path):
        from wittgenstein_tpu.faults import FaultPlan
        from wittgenstein_tpu.protocols.pingpong_batched import make_pingpong
        from wittgenstein_tpu.runtime import RunIncompleteError
        from wittgenstein_tpu.scenarios.sweep import run_fault_sweep

        net, state = make_pingpong(
            32, network_latency_name="NetworkFixedLatency(100)"
        )
        plans = [None, FaultPlan("crash5").crash([5], at=50, recover=150)]
        # reference: the SAME chunked sweep, uninterrupted (chunk
        # schedule is part of run identity for jump protocols)
        ref_out, ref_records = run_fault_sweep(
            net, state, plans, sim_ms=400,
            checkpoint_dir=str(tmp_path / "ref_ck"), chunk_ms=100,
        )

        ckdir = str(tmp_path / "sweep_ck")
        with pytest.raises(RunIncompleteError) as ei:
            run_fault_sweep(
                net, state, plans, sim_ms=400,
                checkpoint_dir=ckdir, chunk_ms=100,
                supervisor_kw={"max_chunks_this_run": 2},
            )
        assert ei.value.report.chunks_done == 2

        out, records = run_fault_sweep(
            net, state, plans, sim_ms=400,
            checkpoint_dir=ckdir, chunk_ms=100,
        )
        assert_trees_equal(out._replace(faults=()), ref_out._replace(faults=()))
        assert records == ref_records


class TestSupervisorValidation:
    def test_bad_geometry_rejected(self):
        with pytest.raises(ValueError, match="n_chunks"):
            Supervisor(toy_chunk, toy_state(), n_chunks=0)
        with pytest.raises(ValueError, match="checkpoint_every"):
            Supervisor(toy_chunk, toy_state(), n_chunks=1, checkpoint_every=0)

    def test_from_network_requires_divisible_total(self):
        class FakeNet:
            protocol = object()
            run_ms_batched = staticmethod(lambda s, ms, swd: s)

        with pytest.raises(ValueError, match="multiple"):
            Supervisor.from_network(
                FakeNet(), toy_state(), total_ms=250, chunk_ms=100
            )

    def test_budget_partial_stop(self):
        def slow(s):
            time.sleep(0.05)
            return toy_chunk(s)

        rep = Supervisor(
            slow, toy_state(), n_chunks=50, budget_s=0.12,
        ).run()
        assert not rep.ok
        assert 0 < rep.chunks_done < 50


class TestErrorTaxonomyExtensions:
    """ISSUE 14: poison_row / lane_failed as first-class taxonomy
    kinds, counted process-wide for the /w/health errorKinds surface."""

    def test_new_kinds_classify_first(self):
        from wittgenstein_tpu.runtime import LaneFailedError, PoisonRowError

        perr = PoisonRowError("job-1", ValueError("bad row"))
        assert classify(perr) == "poison_row"
        assert "job-1" in str(perr)
        lerr = LaneFailedError(2, "injected kill")
        assert classify(lerr) == "lane_failed"
        assert lerr.lane == 2

    def test_retryable_kinds_gate(self):
        from wittgenstein_tpu.runtime import (
            RETRYABLE_KINDS,
            LaneFailedError,
            PoisonRowError,
        )

        assert "transient" in RETRYABLE_KINDS
        assert "device_lost" in RETRYABLE_KINDS
        # poison rows and fatal errors must never be silently retried
        assert classify(PoisonRowError("j", ValueError("x"))) not in (
            RETRYABLE_KINDS
        )
        assert classify(FatalRunError("no")) not in RETRYABLE_KINDS
        # a lane death is transient from the JOB's point of view (the
        # fleet restarts the lane and the work re-runs elsewhere)
        assert classify(LaneFailedError(0)) in RETRYABLE_KINDS

    def test_taxonomy_counters_count_per_classify(self):
        from wittgenstein_tpu.runtime import (
            PoisonRowError,
            reset_taxonomy_counters,
            taxonomy_counters,
        )

        reset_taxonomy_counters()
        classify(PoisonRowError("j", ValueError("x")))
        classify(DeviceLostError("gone"))
        classify(RuntimeError("server UNAVAILABLE"))
        counts = taxonomy_counters()
        assert counts["poison_row"] == 1
        assert counts["device_lost"] == 1
        assert counts["transient"] == 1
        reset_taxonomy_counters()
        assert taxonomy_counters() == {}

    def test_supervisor_raises_poison_without_retry(self, tmp_path):
        from wittgenstein_tpu.runtime import PoisonRowError

        calls = {"n": 0}

        def chunk(s):
            calls["n"] += 1
            raise PoisonRowError("job-x", RuntimeError("poison"))

        sup = Supervisor(
            chunk, toy_state(), n_chunks=3,
            checkpoint_dir=str(tmp_path / "ck"),
            retry=RetryPolicy(
                max_attempts=3, backoff_base_s=0.0, jitter_frac=0.0,
            ),
        )
        with pytest.raises(PoisonRowError):
            sup.run()
        assert calls["n"] == 1, "poison row must not be retried"


class TestSupervisorShouldStop:
    """ISSUE 14: cooperative preemption hook — a drain stops the run at
    the next chunk boundary as a controlled partial stop, and the
    resumed run is bit-identical to an uninterrupted one."""

    def test_stop_requested_parks_then_resume_completes(self, tmp_path):
        stop = threading.Event()
        ckdir = str(tmp_path / "ck")

        def chunk_then_stop(s):
            out = toy_chunk(s)
            stop.set()  # drain arrives while the chunk is in flight
            return out

        sup = Supervisor(
            chunk_then_stop, toy_state(), n_chunks=4,
            checkpoint_dir=ckdir, checkpoint_every=1,
            should_stop=stop.is_set,
        )
        report = sup.run()
        assert report.ok is False  # controlled partial stop, not an error
        assert report.chunks_done == 1  # stopped at the NEXT boundary
        stop.clear()
        sup2 = Supervisor(
            toy_chunk, toy_state(), n_chunks=4, checkpoint_dir=ckdir,
            checkpoint_every=1, should_stop=stop.is_set,
        )
        report2 = sup2.run()
        assert report2.ok is True
        assert_trees_equal(report2.state, toy_after(4))

    def test_no_stop_runs_to_completion(self, tmp_path):
        sup = Supervisor(
            toy_chunk, toy_state(), n_chunks=3,
            checkpoint_dir=str(tmp_path / "ck"),
            should_stop=lambda: False,
        )
        report = sup.run()
        assert report.ok is True
        assert_trees_equal(report.state, toy_after(3))
