"""Supervised chunked-run executor: the durable loop around run_ms.

The engine is deterministic in (state, tick count), so a chunked run is
bit-identical to a straight one — which makes durability a pure
host-side concern.  The Supervisor wraps any chunk function
(state -> state, typically a jitted ``run_ms_batched`` slice) in a loop

    resume -> [guard -> chunk -> sync -> checkpoint]* -> report

with:

- **checkpoint/resume** through engine.checkpoint.CheckpointManager:
  periodic numbered checkpoints + LATEST pointer, run_key-stamped so a
  checkpoint from a different run refuses to resume
  (ResumeMismatchError); kill-and-resume is bit-identical to an
  uninterrupted run — including telemetry counters and fault side-cars
  — because resume replays the exact remaining chunk schedule;
- **watchdog**: each chunk executes on ONE persistent WatchdogWorker
  thread with a deadline (the first chunk of a cold process gets the
  compile allowance on top); the worker is reused across chunks and
  joined when the run finishes, so thread count is stable across a
  supervised run.  A miss raises WatchdogTimeoutError rather than
  waiting forever.  Caveat: Python cannot cancel a hung device call — a
  worker whose call truly hangs is abandoned (and replaced); actually
  killing the process is the job of a process-level supervisor;
- **retry with backoff**: transient failures (classify()) replay
  deterministically from the last host ANCHOR — a numpy snapshot taken
  at checkpoint cadence — so retried chunks produce the exact bytes a
  clean run would have, even with donated device buffers (the donated
  input that the failed call consumed is never needed again);
- **graceful degradation**: on device loss with
  DegradePolicy(cpu_fallback=True) the anchor is re-placed on CPU and
  the run continues there, with {degraded, degraded_at_chunk} stamped
  into provenance — a CPU tail can never masquerade as a TPU number;
- **budget/cap partial stops**: budget_s / max_chunks_this_run exceeded
  between chunks -> checkpoint now, return RunReport(ok=False) — the
  next invocation resumes where this one stopped;
- **observability spine** (obs.*): a TraceContext (run_id / job_id /
  tenant_id) rides provenance, checkpoint-manifest meta, tracer spans,
  and the FlightRecorder event stream.  The run_id SURVIVES kill +
  resume: _save stamps it into the manifest and _resume adopts the
  stored id, so the victim process and the resume process emit one
  joinable run.  On any typed runtime failure the recorder ring is
  dumped atomically beside the checkpoints (and under $WITT_OBS_DIR) —
  the per-run black box scripts/obs_query.py replays.  All host-side:
  sim state stays bit-identical with the recorder armed.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, List, Optional

import numpy as np

from ..engine.checkpoint import CheckpointManager
from ..obs import FlightRecorder, TraceContext, failure_dump_paths, get_recorder, mint_context
from .errors import (
    RETRYABLE_KINDS,
    DurableRunError,
    FatalRunError,
    ResumeMismatchError,
    RetriesExhaustedError,
    WatchdogTimeoutError,
    classify,
)
from .policy import DegradePolicy, RetryPolicy, WatchdogPolicy, WatchdogWorker


def _sync(state: Any) -> None:
    """Ground-truth chunk completion: host readback of the SMALLEST
    output leaf (one program's outputs materialize together), so the
    chunk's wall time ends when its bytes are on the host."""
    import jax

    leaves = jax.tree_util.tree_leaves(state)
    if leaves:
        np.asarray(min(leaves, key=lambda a: getattr(a, "size", 1 << 62)))


def run_with_deadline(fn: Callable[[], Any], deadline_s: float, phase: str):
    """One-shot deadline guard (compat shim over policy.WatchdogWorker).
    Raises WatchdogTimeoutError(phase) on a miss.  Unlike the original
    per-call daemon thread, a COMPLETED call's worker is joined before
    returning; only a call that truly hangs (an uncancellable device
    call) still abandons its thread — callers that need the hang
    actually killed must supervise at process level.  Loop callers
    (Supervisor) hold one WatchdogWorker across calls instead."""
    worker = WatchdogWorker(name=f"witt-{phase}")
    try:
        return worker.call(fn, deadline_s, phase)
    finally:
        worker.close()


# per-chunk wall-time histogram buckets (seconds): the interesting
# decades between a CPU smoke chunk and a two-minute device call
CHUNK_HIST_BUCKETS_S = (0.1, 0.5, 1.0, 2.0, 5.0, 10.0, 30.0, 60.0, 120.0)


def chunk_time_histogram(times: List[float]) -> dict:
    """Prometheus-style cumulative histogram of chunk wall-times:
    {"buckets": {"0.1": n, ..., "+Inf": n}, "count", "sum_s", "max_s"}.
    Shared by Supervisor provenance and the server's exports so one
    bucket layout exists."""
    buckets = {}
    for le in CHUNK_HIST_BUCKETS_S:
        buckets[str(le)] = sum(1 for t in times if t <= le)
    buckets["+Inf"] = len(times)
    return {
        "buckets": buckets,
        "count": len(times),
        "sum_s": round(sum(times), 4),
        "max_s": round(max(times), 4) if times else 0.0,
    }


def stable_run_key(net: Any, template: Any, n_chunks: int, chunk_ms: int) -> str:
    """A run identity that survives process restarts (unlike
    core.cache_key, which hashes object ids): protocol type + chunk
    geometry + the template's leaf signature (paths/shapes/dtypes)."""
    import hashlib

    import jax

    proto = getattr(net, "protocol", net)
    parts = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(template)[0]:
        shape = getattr(leaf, "shape", ())
        dtype = getattr(leaf, "dtype", type(leaf).__name__)
        parts.append(f"{path}:{shape}:{dtype}")
    digest = hashlib.blake2b(
        "|".join(parts).encode(), digest_size=8
    ).hexdigest()
    return f"{type(proto).__name__}:{n_chunks}x{chunk_ms}ms:{digest}"


@dataclass
class RunReport:
    """What a supervised run produced.  ok=False is a CONTROLLED partial
    stop (budget / chunk cap) with a checkpoint on disk; failures raise
    instead."""

    state: Any
    ok: bool
    chunk_seconds: List[float] = field(default_factory=list)
    provenance: dict = field(default_factory=dict)

    @property
    def chunks_done(self) -> int:
        return int(self.provenance.get("chunks_done", 0))


class Supervisor:
    """See module docstring.  `chunk_fn(state) -> state` advances one
    chunk; it may be jitted with donated inputs (retries replay from the
    host anchor, never from a consumed buffer)."""

    def __init__(
        self,
        chunk_fn: Callable[[Any], Any],
        template: Any,
        *,
        n_chunks: int,
        chunk_ms: int = 0,
        checkpoint_dir: Optional[str] = None,
        checkpoint_every: int = 1,
        keep: int = 3,
        retry: Optional[RetryPolicy] = None,
        watchdog: Optional[WatchdogPolicy] = None,
        degrade: Optional[DegradePolicy] = None,
        cpu_chunk_fn: Optional[Callable[[Any], Any]] = None,
        run_key: Optional[str] = None,
        run_meta: Optional[dict] = None,
        heartbeat: Optional[Callable[[int, float], None]] = None,
        budget_s: float = float("inf"),
        max_chunks_this_run: Optional[int] = None,
        should_stop: Optional[Callable[[], bool]] = None,
        sleep: Callable[[float], None] = time.sleep,
        consume_template: bool = False,
        tracer: Any = None,
        ctx: Optional[TraceContext] = None,
        recorder: Optional[FlightRecorder] = None,
        placement: Optional[Callable[[Any], Any]] = None,
        timeseries: Any = None,
        sentinel: Any = None,
        row_watch: Optional[Callable[[Any, int], None]] = None,
    ):
        if n_chunks < 1:
            raise ValueError(f"n_chunks must be >= 1, got {n_chunks}")
        if checkpoint_every < 1:
            raise ValueError(
                f"checkpoint_every must be >= 1, got {checkpoint_every}"
            )
        self.chunk_fn = chunk_fn
        self.template = template
        self.n_chunks = n_chunks
        self.chunk_ms = chunk_ms
        self.manager = (
            CheckpointManager(checkpoint_dir, keep=keep)
            if checkpoint_dir
            else None
        )
        self.checkpoint_every = checkpoint_every
        self.retry = retry or RetryPolicy()
        self.watchdog = watchdog
        self.degrade = degrade
        self.cpu_chunk_fn = cpu_chunk_fn
        self.run_key = run_key
        self.run_meta = dict(run_meta or {})
        self.heartbeat = heartbeat
        self.budget_s = budget_s
        self.max_chunks_this_run = max_chunks_this_run
        # cooperative preemption (serve drain): checked between chunks;
        # True -> checkpoint now and return a controlled partial stop,
        # exactly like a budget/cap stop — resume replays bit-identical
        self.should_stop = should_stop
        self.sleep = sleep
        self.consume_template = consume_template
        # optional telemetry.trace.SpanTracer: chunk spans + instants
        # for retry/degrade/watchdog events land in the Chrome trace
        self.tracer = tracer
        # trace context: minted lazily at run() if the caller didn't
        # pass one AND no checkpoint supplies one (_resume adopts the
        # stored run_id so kill+resume stays one run)
        self.ctx = ctx
        self.recorder = get_recorder() if recorder is None else recorder
        # optional device placement for resumed/anchored host states
        # (a serve lane's device group): applied instead of the default
        # jnp.asarray materialization, never in degraded mode (CPU
        # fallback overrides any group placement)
        self.placement = placement
        # mission control (optional): an obs.TimeSeriesStore fed at the
        # per-chunk sync boundary (history the SLO engine queries) and
        # an obs.InvariantSentinel checked there too.  Both read
        # already-synced host state only — arming them is bitwise-
        # neutral, and neither may ever fail the run (_observe_chunk
        # swallows; sentinel.check never raises by contract)
        self.timeseries = timeseries
        self.sentinel = sentinel
        # done-row watcher (serve's harvesting census): called at the
        # same per-chunk sync with (synced_state, chunk_index).  The
        # per-chunk sync is the ONLY place done_at/all_done are already
        # host-materialized, so mid-batch row observations are free
        # here and nowhere else.  Same contract as the sentinel: reads
        # only, never fails the run (_observe_chunk swallows)
        self.row_watch = row_watch
        self._wd_worker: Optional[WatchdogWorker] = None
        self._first_call_done = False
        self._degraded = False

    # -- state placement ------------------------------------------------

    def _snapshot(self, state: Any):
        """Host anchor: a private numpy copy of every leaf (immune to
        donation consuming the device buffers)."""
        import jax

        return jax.tree_util.tree_map(
            lambda a: np.array(np.asarray(a), copy=True), state
        )

    def _place(self, host_state: Any) -> Any:
        import jax
        import jax.numpy as jnp

        if self._degraded:
            cpu = jax.devices("cpu")[0]
            return jax.tree_util.tree_map(
                lambda a: jax.device_put(a, cpu), host_state
            )
        if self.placement is not None:
            return self.placement(host_state)
        return jax.tree_util.tree_map(jnp.asarray, host_state)

    # -- chunk execution ------------------------------------------------

    def _active_chunk_fn(self) -> Callable[[Any], Any]:
        if self._degraded and self.cpu_chunk_fn is not None:
            return self.cpu_chunk_fn
        return self.chunk_fn

    def _run_chunk(self, state: Any) -> Any:
        fn = self._active_chunk_fn()

        def call():
            out = fn(state)
            _sync(out)
            return out

        if self.watchdog is None:
            out = call()
            self._first_call_done = True
            return out
        deadline = self.watchdog.chunk_deadline_s
        phase = "chunk"
        if not self._first_call_done:
            deadline += self.watchdog.compile_deadline_s
            phase = "compile+chunk"
        # one persistent worker across chunks (closed at run() end); a
        # hung worker is discarded and replaced — see WatchdogWorker
        if self._wd_worker is None or self._wd_worker.hung:
            self._wd_worker = WatchdogWorker()
        out = self._wd_worker.call(call, deadline, phase)
        self._first_call_done = True
        return out

    def _close_watchdog(self) -> None:
        if self._wd_worker is not None:
            self._wd_worker.close()
            self._wd_worker = None

    # -- observability ---------------------------------------------------

    def _record(self, kind: str, chunk: Optional[int] = None, **fields) -> None:
        if self.recorder is None:
            return
        ctx = self.ctx
        if ctx is not None and chunk is not None:
            ctx = ctx.child(chunk_seq=chunk)
        elif chunk is not None:
            fields.setdefault("chunk_seq", chunk)
        self.recorder.record(kind, ctx=ctx, **fields)

    @staticmethod
    def _tick_hwms(state: Any) -> dict:
        """Host-side read of the telemetry loop counters / high-water
        marks for the chunk-end event.  Read-only numpy views of an
        already-synced state — never feeds back into the sim."""
        tele = getattr(state, "tele", None)
        if tele is None or not hasattr(tele, "ticks"):
            return {}
        try:
            return {
                "ticks": int(np.asarray(tele.ticks).sum()),
                "jumps": int(np.asarray(tele.jumps).sum()),
                "jumped_ms": int(np.asarray(tele.jumped_ms).sum()),
                "wheel_fill_hwm": int(np.asarray(tele.wheel_fill_hwm).max()),
                "ovf_hwm": int(np.asarray(tele.ovf_hwm).max()),
            }
        except (TypeError, ValueError, AttributeError):
            return {}

    def _observe_chunk(self, state: Any, chunk: int, dt: float,
                       hwms: dict) -> None:
        """Mission-control hook at the per-chunk sync boundary: feed
        the timeseries history and run the invariant sentinel.  The
        state here is the same synced, host-readable one _tick_hwms
        just read.  Monitoring must never fail the run it watches, so
        everything is swallowed."""
        ctx = (
            self.ctx.child(chunk_seq=chunk) if self.ctx is not None else None
        )
        if self.timeseries is not None:
            try:
                self.timeseries.observe(
                    "supervisor.chunk_seconds", dt, ctx=ctx
                )
                for key in ("wheel_fill_hwm", "ovf_hwm"):
                    if key in hwms:
                        self.timeseries.observe(
                            f"supervisor.{key}", float(hwms[key]), ctx=ctx
                        )
            except Exception:  # noqa: BLE001 — monitoring is best-effort
                pass
        if self.row_watch is not None:
            try:
                self.row_watch(state, chunk)
            except Exception:  # noqa: BLE001 — monitoring is best-effort
                pass
        if self.sentinel is not None:
            self.sentinel.check(
                state, ctx=ctx, chunk=chunk,
                members=self.run_meta.get("members"),
                capacity=self.run_meta.get("capacity"),
            )

    # -- resume ---------------------------------------------------------

    @property
    def _needs_anchor(self) -> bool:
        """Host anchors exist to replay retries and seed checkpoints;
        without either, skip them entirely — a bare supervised pass then
        costs only the chunk loop and its sync."""
        return self.manager is not None or self.retry.max_attempts > 1

    def _resume(self):
        """-> (device_state, start_chunk, resumed_from_step, prior_times)."""
        if self.manager is None:
            if self.consume_template:
                # hand the template straight to chunk_fn (a donating
                # chunk_fn consumes it — the caller passed a disposable
                # copy); anchoring, if
                # needed, copies it first
                return self.template, 0, None, []
            return self._place(self._snapshot(self.template)), 0, None, []
        got = self.manager.restore_latest(self.template)
        if got is None:
            if self.consume_template:
                return self.template, 0, None, []
            return self._place(self._snapshot(self.template)), 0, None, []
        state, step, manifest = got
        meta = (manifest or {}).get("meta", {})
        saved_key = meta.get("run_key")
        if (
            self.run_key is not None
            and saved_key is not None
            and saved_key != self.run_key
        ):
            raise ResumeMismatchError(
                f"checkpoint step {step} in {self.manager.directory} "
                f"belongs to run {saved_key!r}, not {self.run_key!r} — "
                "point the supervisor at a fresh checkpoint_dir"
            )
        saved_chunk_ms = meta.get("chunk_ms")
        if (
            self.chunk_ms
            and saved_chunk_ms
            and int(saved_chunk_ms) != int(self.chunk_ms)
        ):
            raise ResumeMismatchError(
                f"checkpoint step {step} was written with "
                f"chunk_ms={saved_chunk_ms}, this run uses "
                f"chunk_ms={self.chunk_ms} — resume would change the "
                "chunk schedule and break bit-identity"
            )
        if step > self.n_chunks:
            raise ResumeMismatchError(
                f"checkpoint step {step} exceeds this run's "
                f"n_chunks={self.n_chunks}"
            )
        # adopt the checkpointed run identity: the ledger's run_id
        # belongs to the RUN, not the process, so a resume after SIGKILL
        # keeps emitting under the id the victim minted — obs_query then
        # reconstructs one timeline across both processes
        saved_run_id = meta.get("run_id")
        if saved_run_id:
            if self.ctx is None:
                self.ctx = TraceContext(
                    run_id=saved_run_id,
                    job_id=meta.get("job_id"),
                    tenant_id=meta.get("tenant_id"),
                )
            elif self.ctx.run_id != saved_run_id:
                self.ctx = self.ctx.child(run_id=saved_run_id)
        prior = list(meta.get("chunk_seconds", []))
        if self.timeseries is not None:
            try:
                # metric history survives kill+resume the same way the
                # run_id does: the manifest is the authority on the past
                self.timeseries.restore(meta.get("timeseries"))
            except Exception:  # noqa: BLE001 — monitoring is best-effort
                pass
        return self._place(self._snapshot(state)), step, step, prior

    def _save(self, state: Any, step: int, times_all: List[float]) -> None:
        meta = {
            **self.run_meta,
            "run_key": self.run_key,
            "chunk_ms": self.chunk_ms,
            "n_chunks": self.n_chunks,
            "chunks_done": step,
            "chunk_seconds": [round(t, 4) for t in times_all],
            "degraded": self._degraded,
        }
        if self.timeseries is not None:
            try:
                meta["timeseries"] = self.timeseries.snapshot()
            except Exception:  # noqa: BLE001 — monitoring is best-effort
                pass
        if self.ctx is not None:
            # trace ids into the manifest meta (checkpoint.save_state
            # surfaces them as manifest["trace"]) — the join key a
            # resume adopts and obs_query correlates on
            meta.setdefault("run_id", self.ctx.run_id)
            if self.ctx.job_id is not None:
                meta.setdefault("job_id", self.ctx.job_id)
            if self.ctx.tenant_id is not None:
                meta.setdefault("tenant_id", self.ctx.tenant_id)
        self.manager.save(state, step, meta=meta)
        self._record("checkpoint", step=step, dir=self.manager.directory)

    # -- the loop -------------------------------------------------------

    def run(self) -> RunReport:
        state, start_chunk, resumed_from, prior_times = self._resume()
        if self.ctx is None:
            # no caller-minted context and no checkpoint to adopt from:
            # this supervisor IS the run's entry point
            self.ctx = mint_context("run")
        if resumed_from is not None:
            self._record(
                "resume", step=resumed_from, run_key=self.run_key
            )
        anchor = self._snapshot(state) if self._needs_anchor else None
        anchor_chunk = start_chunk
        times: List[float] = []  # this run's completed chunks, in order
        i = start_chunk
        fail_streak = 0
        retries_total = 0
        watchdog_timeouts = 0
        checkpoints = 0
        degraded_at = None
        t_start = time.perf_counter()

        def provenance(done: int) -> dict:
            import jax

            return {
                "platform": jax.default_backend(),
                "degraded": self._degraded,
                "degraded_at_chunk": degraded_at,
                "resumed_from_step": resumed_from,
                "retries": retries_total,
                "watchdog_timeouts": watchdog_timeouts,
                "checkpoints": checkpoints,
                "run_key": self.run_key,
                "chunk_ms": self.chunk_ms,
                "n_chunks": self.n_chunks,
                "chunks_done": done,
                "chunk_time_hist": chunk_time_histogram(times),
                **(self.ctx.ids() if self.ctx is not None else {}),
            }

        try:
            while i < self.n_chunks:
                over_budget = time.perf_counter() - t_start > self.budget_s
                over_cap = (
                    self.max_chunks_this_run is not None
                    and len(times) >= self.max_chunks_this_run
                )
                stop_requested = (
                    self.should_stop is not None and self.should_stop()
                )
                if over_budget or over_cap or stop_requested:
                    # controlled partial stop: checkpoint NOW (even
                    # off-cadence — resumability beats cadence) and report
                    if self.manager is not None and i > anchor_chunk:
                        self._save(state, i, prior_times + times)
                        checkpoints += 1
                    self._record(
                        "partial-stop", chunk=i,
                        reason=(
                            "budget" if over_budget
                            else "chunk-cap" if over_cap
                            else "stop-requested"
                        ),
                        chunks_done=i,
                    )
                    return RunReport(
                        state, False, times, provenance(i)
                    )
                try:
                    self._record("chunk-start", chunk=i)
                    t1 = time.perf_counter()
                    state = self._run_chunk(state)
                    dt = time.perf_counter() - t1
                    hwms = self._tick_hwms(state)
                    self._record(
                        "chunk-end", chunk=i, seconds=round(dt, 4),
                        degraded=self._degraded or None,
                        **hwms,
                    )
                    self._observe_chunk(state, i, dt, hwms)
                    if self.tracer is not None:
                        self.tracer.add_span(
                            "chunk", self.tracer.now_us() - dt * 1e6, dt * 1e6,
                            chunk=i, degraded=self._degraded,
                        )
                except BaseException as e:  # noqa: BLE001 — classified below
                    kind = classify(e)
                    if isinstance(e, WatchdogTimeoutError):
                        watchdog_timeouts += 1
                        self._record(
                            "watchdog", chunk=i, phase=e.phase,
                            deadline_s=e.deadline_s,
                        )
                    if self.tracer is not None:
                        self.tracer.instant(
                            "chunk-failed", chunk=i, kind=kind,
                            error=type(e).__name__,
                        )
                    if kind not in RETRYABLE_KINDS:
                        # fatal, poison_row, lane_failed, any future
                        # non-environmental kind: replaying reproduces it
                        raise
                    fail_streak += 1
                    retries_total += 1
                    if fail_streak >= self.retry.max_attempts:
                        raise RetriesExhaustedError(fail_streak, e) from e
                    if (
                        kind == "device_lost"
                        and self.degrade is not None
                        and self.degrade.cpu_fallback
                        and not self._degraded
                    ):
                        self._degraded = True
                        degraded_at = i
                        self._first_call_done = False  # CPU gets a compile
                        self._record("degraded", chunk=i, to="cpu")
                        if self.tracer is not None:
                            self.tracer.instant("degraded-to-cpu", chunk=i)
                    delay = self.retry.delay_s(fail_streak - 1)
                    self._record(
                        "retry", chunk=i, error_kind=kind,
                        error=type(e).__name__, fail_streak=fail_streak,
                        delay_s=round(delay, 4), replay_from=anchor_chunk,
                    )
                    self.sleep(delay)
                    # replay deterministically from the last anchor: the
                    # chunks between anchor_chunk and i re-run and produce
                    # the exact bytes the failed timeline would have
                    state = self._place(anchor)
                    times = times[: anchor_chunk - start_chunk]
                    i = anchor_chunk
                    continue
                fail_streak = 0
                times.append(dt)
                if self.heartbeat is not None:
                    self.heartbeat(i, dt)
                i += 1
                at_cadence = (i - start_chunk) % self.checkpoint_every == 0
                if at_cadence or i == self.n_chunks:
                    if self.manager is not None:
                        self._save(state, i, prior_times + times)
                        checkpoints += 1
                    if self._needs_anchor:
                        anchor = self._snapshot(state)
                        anchor_chunk = i
        except BaseException as e:  # noqa: BLE001 — black-box dump, re-raised
            self._dump_on_failure(e, chunk=i)
            raise
        finally:
            self._close_watchdog()
        self._record("run-complete", chunks_done=self.n_chunks)
        return RunReport(state, True, times, provenance(self.n_chunks))

    def _dump_on_failure(self, exc: BaseException, chunk: int) -> None:
        """The black-box contract: any failure that escapes the retry
        loop dumps the flight-recorder ring atomically beside the
        checkpoints (and under $WITT_OBS_DIR if set) before the
        exception propagates."""
        if self.recorder is None:
            return
        kind = classify(exc)
        self._record(
            "failure", chunk=chunk, error_kind=kind,
            error=type(exc).__name__, message=str(exc)[:500],
            typed=isinstance(exc, DurableRunError),
        )
        ckpt_dir = self.manager.directory if self.manager is not None else None
        for path in failure_dump_paths(ckpt_dir):
            try:
                self.recorder.dump(path)
            except OSError:
                pass  # forensics must never mask the real failure

    # -- convenience ----------------------------------------------------

    @classmethod
    def from_network(
        cls,
        net: Any,
        state: Any,
        *,
        total_ms: int,
        chunk_ms: int,
        batched: bool = True,
        stop_when_done: bool = False,
        donate: bool = False,
        run_key: Optional[str] = None,
        **kw,
    ) -> "Supervisor":
        """Build a supervisor whose chunk_fn is a jitted chunk_ms slice
        of net.run_ms / net.run_ms_batched.

        Donation is SEMANTICALLY safe under the supervisor (retries
        replay from host anchors, never from a consumed buffer) but
        defaults OFF: jit(donate_argnums) chunk loops corrupt the heap
        ("corrupted double-linked list" aborts) on jaxlib 0.4.37 when
        the persistent compilation cache is enabled together with
        --xla_force_host_platform_device_count — exactly the tier-1 test
        configuration.  An AOT `lower().compile()` donated chunk fn
        does not exhibit this; callers that need donated buffers (TPU
        memory pressure) should compile that way and pass chunk_fn
        directly, or opt in here deliberately.

        stop_when_done note: the early exit changes which ticks execute
        per chunk boundary, so bit-identity of a chunked vs straight run
        is only guaranteed for the default stop_when_done=False (the
        done_at deliverable is preserved either way — see run_ms)."""
        import jax

        if total_ms % chunk_ms != 0:
            raise ValueError(
                f"total_ms={total_ms} must be a multiple of chunk_ms={chunk_ms}"
            )
        n_chunks = total_ms // chunk_ms
        runner = net.run_ms_batched if batched else net.run_ms
        chunk_fn = jax.jit(
            lambda s: runner(s, chunk_ms, stop_when_done),
            donate_argnums=(0,) if donate else (),
        )
        # the same jitted fn re-traces for CPU-placed inputs, so the
        # degraded path reuses it (jit specializes on input placement)
        if run_key is None:
            run_key = stable_run_key(net, state, n_chunks, chunk_ms)
        # durable compiles: with a compile store installed the chunk fn
        # dispatches through store-backed AOT programs keyed on the
        # engine's stable identity — a restarted process resumes a
        # checkpointed run without re-paying the chunk compile.  Donated
        # buffers keep the plain jit path: serialized executables do not
        # carry donation, and donation is opt-in anyway (the jaxlib
        # 0.4.37 landmine below).  Geometry (incl. placement) is part of
        # the store key, so the degraded CPU re-placement still works.
        if not donate:
            from .compile_store import durable_jit, get_compile_store

            if get_compile_store() is not None:
                stable = getattr(net, "stable_cache_key", None)
                base = (
                    repr(stable()) if callable(stable) else run_key
                )
                import hashlib as _hashlib

                chunk_fn = durable_jit(
                    chunk_fn,
                    "chunk/"
                    + _hashlib.blake2b(
                        f"{base}|{chunk_ms}|{int(stop_when_done)}|"
                        f"{int(batched)}".encode(),
                        digest_size=12,
                    ).hexdigest(),
                )
        return cls(
            chunk_fn,
            state,
            n_chunks=n_chunks,
            chunk_ms=chunk_ms,
            run_key=run_key,
            **kw,
        )
