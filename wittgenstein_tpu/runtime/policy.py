"""Supervisor policies: retry/backoff, watchdog deadlines, degradation —
plus the WatchdogWorker that executes guarded calls.

The policies are frozen dataclasses so they hash/compare cleanly and can
be stamped into run provenance.  Backoff jitter is DETERMINISTIC (hashed
from seed + attempt) — a resumed supervisor replays the same delays,
keeping kill-and-resume runs reproducible end to end, and tests can pin
exact delay sequences without mocking random.
"""

from __future__ import annotations

import hashlib
import queue
import threading
from dataclasses import dataclass
from typing import Any, Callable

from .errors import WatchdogTimeoutError


@dataclass(frozen=True)
class RetryPolicy:
    """Bounded retry with exponential backoff + deterministic jitter.

    attempt n (0-based retry count) sleeps
      min(backoff_max_s, backoff_base_s * backoff_factor**n) * (1 ± jitter)
    where jitter is a hash of (seed, n) in [-jitter_frac, +jitter_frac].
    max_attempts counts EXECUTIONS, not retries: 3 means one initial try
    plus two retries.
    """

    max_attempts: int = 3
    backoff_base_s: float = 0.5
    backoff_factor: float = 2.0
    backoff_max_s: float = 30.0
    jitter_frac: float = 0.25
    seed: int = 0

    def delay_s(self, attempt: int) -> float:
        """Backoff delay before retry number `attempt` (0-based)."""
        base = min(
            self.backoff_max_s,
            self.backoff_base_s * (self.backoff_factor ** attempt),
        )
        if self.jitter_frac <= 0:
            return base
        h = hashlib.blake2b(
            f"{self.seed}:{attempt}".encode(), digest_size=8
        ).digest()
        unit = int.from_bytes(h, "big") / float(1 << 64)  # [0, 1)
        return base * (1.0 + self.jitter_frac * (2.0 * unit - 1.0))


@dataclass(frozen=True)
class WatchdogPolicy:
    """Per-phase deadlines.  A chunk that misses its deadline is treated
    as a hung device and raises WatchdogTimeoutError; the first chunk of
    a cold process gets compile_deadline_s ON TOP of chunk_deadline_s
    (jit compiles lazily inside the first call)."""

    chunk_deadline_s: float = 180.0
    compile_deadline_s: float = 780.0


class WatchdogWorker:
    """Persistent deadline-guarded executor: ONE worker thread reused
    across every guarded call of a run, joined on completion.

    This fixes the documented watchdog thread leak: the old
    run_with_deadline spawned a fresh daemon thread per chunk, so a
    watchdog-armed N-chunk run churned N threads and a completed run
    still had its last worker unaccounted for.  Here the same thread
    serves every chunk and ``close()`` joins it when the run finishes —
    thread count is stable across an arbitrarily long supervised run
    (pinned by a tier-1 regression test).

    The one unfixable case remains unfixable: Python cannot cancel a
    call that truly hangs inside the device runtime.  A
    deadline miss marks the worker ``hung``; it is abandoned (daemonic,
    never reused — a late result cannot be mistaken for a fresh one
    because the whole worker, result queue included, is discarded) and
    the caller creates a replacement.  Actually killing the hang stays a
    process-level supervisor's job.
    """

    # single-writer by construction: only the owning caller thread ever
    # touches the worker handle or the hung latch (the worker thread
    # itself writes neither), so neither needs a lock (SL1305)
    UNGUARDED_OK = ("_thread", "hung")

    def __init__(self, name: str = "witt-watchdog"):
        self._name = name
        self._requests: "queue.Queue" = queue.Queue()
        self._results: "queue.Queue" = queue.Queue()
        self._thread: threading.Thread | None = None
        self.hung = False

    def _ensure_thread(self) -> None:
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._loop, daemon=True, name=self._name
            )
            self._thread.start()

    def _loop(self) -> None:
        while True:
            fn = self._requests.get()
            if fn is None:
                return
            try:
                self._results.put(("ok", fn()))
            except BaseException as e:  # noqa: BLE001 — forwarded to caller
                self._results.put(("err", e))

    def call(self, fn: Callable[[], Any], deadline_s: float, phase: str):
        """Run fn() on the worker with a deadline; raise
        WatchdogTimeoutError(phase) on a miss (and mark the worker hung
        — callers must discard it and build a fresh one)."""
        if self.hung:
            raise RuntimeError(
                f"WatchdogWorker {self._name!r} is hung; build a new one"
            )
        self._ensure_thread()
        self._requests.put(fn)
        try:
            status, payload = self._results.get(timeout=deadline_s)
        except queue.Empty:
            self.hung = True
            # pre-queue the shutdown sentinel: if the stuck call ever
            # returns, the abandoned worker exits instead of parking on
            # the request queue forever — the leak lasts exactly as long
            # as the hang itself
            self._requests.put(None)
            raise WatchdogTimeoutError(phase, deadline_s) from None
        if status == "err":
            raise payload
        return payload

    def close(self, timeout_s: float = 5.0) -> bool:
        """Join the worker thread (call on run completion).  Returns
        True when the thread is gone; a hung worker is abandoned
        immediately (returns False) rather than blocking the caller."""
        th = self._thread
        self._thread = None
        if th is None or not th.is_alive():
            return True
        if self.hung:
            return False
        self._requests.put(None)
        th.join(timeout_s)
        return not th.is_alive()


@dataclass(frozen=True)
class SalvagePolicy:
    """How the serve scheduler responds to a failed packed batch.

    With ``enabled`` the scheduler bisects the live rows: a failing
    subset splits in half, a passing subset's results are KEPT (padding
    to the fixed capacity means every subset re-run is the same
    compiled program, and replica rows are lane-independent under vmap
    — a surviving row's bytes equal its singleton run's).  Rows that
    fail alone are quarantined as PoisonRowError; with one poison among
    k rows identification costs ~log2(k) re-runs.  ``max_probe_runs``
    bounds the salvage work per batch — past it, still-unresolved rows
    fail with the original batch error (honest FAILED, not a guessed
    quarantine).  Disabled, a batch failure fails every live row (the
    pre-resilience blast-radius behavior)."""

    enabled: bool = True
    max_probe_runs: int = 16


@dataclass(frozen=True)
class DegradePolicy:
    """What to do when the device is lost: with cpu_fallback, the
    supervisor re-places the last anchor on CPU and continues there,
    stamping {degraded, degraded_at_chunk} into provenance so a CPU
    number can never masquerade as a TPU number."""

    cpu_fallback: bool = False
