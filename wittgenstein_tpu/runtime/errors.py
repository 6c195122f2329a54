"""Structured error taxonomy for the durable-run supervisor.

The split that matters operationally is TRANSIENT vs FATAL:

- **Transient** failures (device lost, preemption, connection resets) are
  the supervisor's to handle — bounded retry with exponential backoff,
  replaying deterministically from the last host anchor so the retried
  run is bit-identical to one that never failed.
- **Fatal** failures (watchdog deadline, shape/layout mismatch on
  resume, retries exhausted) stop the run with a typed exception the
  caller can route — never a bare RuntimeError three frames into jax.

`classify` maps arbitrary exceptions (including jax/XLA runtime errors,
which arrive as generic Exception subclasses with backend-specific
messages) onto the taxonomy using message markers from the jax/XLA
status-code vocabulary.
"""

from __future__ import annotations

import threading
from collections import Counter


class DurableRunError(Exception):
    """Base for every structured supervisor failure."""


class TransientRunError(DurableRunError):
    """Worth retrying: the failure is environmental, not semantic."""


class FatalRunError(DurableRunError):
    """Retrying cannot help; the run stops with this as the reason."""


class DeviceLostError(TransientRunError):
    """The accelerator went away mid-run (connection reset, worker
    crash, preemption of the device)."""


class PreemptedError(TransientRunError):
    """The host/process was asked to stop (scheduler preemption); state
    up to the last checkpoint survives."""


class WatchdogTimeoutError(FatalRunError):
    """A compile or chunk exceeded its deadline.  Fatal IN-PROCESS: a
    hung device call cannot be cancelled from Python, so the in-process
    supervisor stops issuing work and reports; a process-level supervisor
    owns the actual kill."""

    def __init__(self, phase: str, deadline_s: float):
        self.phase = phase
        self.deadline_s = deadline_s
        super().__init__(
            f"{phase} exceeded its {deadline_s:.0f}s watchdog deadline"
        )


class RetriesExhaustedError(FatalRunError):
    """The retry policy's attempt budget ran out on transient failures."""

    def __init__(self, attempts: int, last: BaseException):
        self.attempts = attempts
        self.last = last
        super().__init__(
            f"gave up after {attempts} attempts; last failure: "
            f"{type(last).__name__}: {last}"
        )


class ResumeMismatchError(FatalRunError):
    """A checkpoint exists but belongs to a different run (run_key or
    chunk geometry mismatch) — resuming would silently mix runs."""


class PoisonRowError(FatalRunError):
    """One row of a packed batch is semantically poisonous: the batch
    failed WITH it and succeeded WITHOUT it (scheduler salvage
    bisection), or its row could not even be built.  Quarantining the
    carrying job is the only fix — retrying the batch replays the same
    poison.  Carries the job id and the original failure so the job's
    terminal status stays honest."""

    def __init__(self, job_id: str, cause: BaseException):
        self.job_id = job_id
        self.cause = cause
        super().__init__(
            f"job {job_id} poisons its batch: "
            f"{type(cause).__name__}: {cause}"
        )


class LaneFailedError(TransientRunError):
    """A dispatch lane's worker thread died (escaped exception or an
    injected chaos kill).  Transient at fleet level: the scheduler
    restarts the lane and re-binds its sticky families to a healthy
    one; no job is lost (undispatched work stays queued, parked batches
    keep their checkpoints)."""

    def __init__(self, lane: int, reason: str = "lane worker died"):
        self.lane = lane
        super().__init__(f"lane {lane}: {reason}")


class RunIncompleteError(DurableRunError):
    """A controlled partial stop (budget exhausted / chunk cap reached).
    Carries the partial RunReport so callers can checkpoint-and-requeue."""

    def __init__(self, message: str, report=None):
        self.report = report
        super().__init__(message)


# lowercase substrings that mark an environmental (retryable) failure in
# backend exception text, from the jax/XLA status-code vocabulary
_TRANSIENT_MARKERS = (
    "deadline_exceeded",
    "deadline exceeded",
    "unavailable",
    "resource_exhausted",
    "resource exhausted",
    "preempt",
    "worker crashed",
    "worker process crashed",
    "connection reset",
    "connection refused",
    "broken pipe",
    "socket closed",
    "transport closed",
    "heartbeat",
)

_DEVICE_LOST_MARKERS = (
    "device lost",
    "worker crashed",
    "worker process crashed",
    "tpu is dead",
    "failed to connect",
    "transport closed",
)


# process-wide taxonomy counters: every classify() call increments its
# kind, so /w/health and the chaos harness can report how failures
# distributed without re-walking the flight recorder
_TAXONOMY_LOCK = threading.Lock()
_TAXONOMY_COUNTS: Counter = Counter()


def taxonomy_counters() -> dict:
    """Snapshot of {kind: count} over every classify() call since
    process start (or the last reset)."""
    with _TAXONOMY_LOCK:
        return dict(_TAXONOMY_COUNTS)


def reset_taxonomy_counters() -> None:
    with _TAXONOMY_LOCK:
        _TAXONOMY_COUNTS.clear()


def _classify(exc: BaseException) -> str:
    if isinstance(exc, PoisonRowError):
        return "poison_row"
    if isinstance(exc, LaneFailedError):
        return "lane_failed"
    if isinstance(exc, DeviceLostError):
        return "device_lost"
    if isinstance(exc, TransientRunError):
        return "transient"
    if isinstance(exc, FatalRunError):
        return "fatal"
    if isinstance(exc, (KeyboardInterrupt, SystemExit)):
        return "fatal"
    text = str(exc).lower()
    if any(m in text for m in _DEVICE_LOST_MARKERS):
        return "device_lost"
    if any(m in text for m in _TRANSIENT_MARKERS):
        return "transient"
    return "fatal"


#: kinds the supervisor may retry; everything else ('fatal',
#: 'poison_row', future additions) must propagate — replaying a
#: semantic failure reproduces it.  lane_failed IS retryable: a lane
#: death says nothing about the work it carried (the fleet restarts
#: the lane and the jobs re-run elsewhere, bitwise-identical).
RETRYABLE_KINDS = frozenset({"transient", "device_lost", "lane_failed"})


def classify(exc: BaseException) -> str:
    """Map an exception to a taxonomy kind: 'transient' | 'device_lost'
    | 'fatal' | 'poison_row' | 'lane_failed'.

    device_lost is a sub-case of transient that additionally makes the
    current backend suspect — the degradation policy keys off it.
    poison_row / lane_failed are fleet-level kinds (serve scheduler);
    only RETRYABLE_KINDS are safe to replay.
    """
    kind = _classify(exc)
    with _TAXONOMY_LOCK:
        _TAXONOMY_COUNTS[kind] += 1
    return kind
