"""The lock registry, hierarchy, and runtime lock tracing (ISSUE 19).

The serving fleet's host-side concurrency surface — scheduler lanes,
the run cache, the compile store, the flight recorder, the HTTP server
— is certified by simlint pass 10 (analysis/concurrency_check.py)
against the declarations in this module:

* ``LOCK_HIERARCHY`` — every named lock in the host tree, in a TOTAL
  acquisition order (rank = position).  A thread holding a lock may
  only acquire locks of STRICTLY HIGHER rank; any two code paths that
  respect the order cannot deadlock on these locks.  SL1301 flags a
  lock construction missing from the registry, SL1302 flags an
  acquisition chain (across function boundaries) that inverts the
  order, SL1306 flags a stale registry row.
* ``no_blocking`` — dispatch-class locks (the scheduler's dispatch
  lock, the run-cache entry lock) under which NO blocking work may run:
  no XLA compiles, no ``block_until_ready``, no file I/O, no HTTP, no
  timeout-less ``queue.get`` (SL1303).  This is the PR-11 race's dual:
  that fix moved compiles OUTSIDE ``_dispatch_lock``; the rule keeps
  them out.
* ``TracedLock`` — the dynamic side.  Zero-cost-when-off (one module
  flag read per acquire); armed via ``WITT_LOCK_TRACE=1`` or
  ``arm_lock_trace()`` it records wait times and the runtime
  acquisition-order graph, and surfaces rank inversions / graph cycles
  as typed ``lock-order-violation`` flight-recorder events plus
  ``witt_runtime_lock_wait_seconds`` metrics (``lock_trace_status()``).
* ``yield_point`` — named interleaving hooks compiled into the
  scheduler / run-cache / compile-store hot paths.  No-ops unless a
  test installs a controller via ``set_interleave`` (tests/interleave.py
  drives them to force specific thread schedules — e.g. the PR-11
  duplicate-compile reproduction).  SL1307 keeps the ``YIELD_POINTS``
  catalog and the call sites in sync.

This module imports only the stdlib (the checker loads it standalone,
outside the package) and is itself exempt from pass 10.
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

__all__ = [
    "LOCK_HIERARCHY",
    "LOCK_RANKS",
    "LockSpec",
    "TracedLock",
    "YIELD_POINTS",
    "arm_lock_trace",
    "lock_trace_status",
    "make_lock",
    "reset_lock_trace",
    "set_interleave",
    "yield_point",
]


@dataclass(frozen=True)
class LockSpec:
    """One registry row.  ``sites`` anchors the declaration to the
    actual construction(s) — ``"relpath::Class.attr"`` for instance
    locks, ``"relpath::GLOBAL.name"`` for module-level locks — so the
    static pass can prove the registry matches the tree (SL1301 for an
    undeclared construction, SL1306 for a stale row)."""

    name: str
    sites: Tuple[str, ...]
    no_blocking: bool = False
    doc: str = ""


# The total acquisition order, outermost (rank 0) to innermost.  A
# thread may acquire rank j while holding rank i only when j > i.
# Every verified nesting edge in the tree ascends this table; see
# docs/serving.md ("Lock hierarchy") for the edge inventory and the
# reasoning behind each placement.
LOCK_HIERARCHY: Tuple[LockSpec, ...] = (
    LockSpec(
        "server.run", ("server/ws.py::WServer.run_lock",),
        doc="legacy runMs busy latch; held across whole sliced runs",
    ),
    LockSpec(
        "server.http", ("server/ws.py::WServer.lock",),
        doc="shared simulation lock for locked HTTP routes",
    ),
    LockSpec(
        "serve.worker", ("serve/scheduler.py::BatchScheduler._worker_lock",),
        doc="lane thread spawn/restart bookkeeping",
    ),
    LockSpec(
        "serve.dispatch", ("serve/scheduler.py::BatchScheduler._dispatch_lock",),
        no_blocking=True,
        doc="batch claim + lane binding; compiles stay OUTSIDE (PR 11)",
    ),
    LockSpec(
        "serve.family", ("serve/scheduler.py::BatchScheduler._fam_lock",),
        doc="per-family admission bookkeeping",
    ),
    LockSpec(
        "serve.queue", ("serve/jobs.py::JobQueue._lock",),
        doc="job queue state (+ its _work Condition alias)",
    ),
    LockSpec(
        "serve.metrics", ("serve/metrics.py::ServeMetrics._lock",),
        doc="serve counters/quantile rings",
    ),
    LockSpec(
        "obs.sentinel", ("obs/monitor.py::InvariantSentinel._lock",),
        doc="invariant sentinel fired-set latch",
    ),
    LockSpec(
        "obs.slo", ("obs/slo.py::SLOEngine._lock",),
        doc="SLO burn-rate engine state",
    ),
    LockSpec(
        "runcache.entry", ("parallel/replica_shard.py::GLOBAL._CACHE_LOCK",),
        no_blocking=True,
        doc="run-cache entry map + counters; never held across a compile",
    ),
    LockSpec(
        "runcache.compile", ("parallel/replica_shard.py::_CachedRun._compile_lock",),
        doc="per-entry compile serialization (the PR-11 guard)",
    ),
    LockSpec(
        "runcache.census", ("parallel/replica_shard.py::GLOBAL._CENSUS_LOCK",),
        doc="the queue of chunk census vectors on their way to the host "
            "(a leaf: folds and waits happen outside it)",
    ),
    LockSpec(
        "runcache.counters", ("parallel/replica_shard.py::GLOBAL._COUNTER_LOCK",),
        doc="the adds to the run cache's monotonic counters (a leaf: "
            "taken on leaving a span, under the compile lock or none)",
    ),
    LockSpec(
        "store.jit", ("runtime/compile_store.py::DurableJit._lock",),
        doc="DurableJit per-geometry program map",
    ),
    LockSpec(
        "store.entry", ("runtime/compile_store.py::CompileStore._lock",),
        doc="compile-store payload+manifest writes",
    ),
    LockSpec(
        "store.default", ("runtime/compile_store.py::GLOBAL._DEFAULT_LOCK",),
        doc="process-default store singleton latch",
    ),
    LockSpec(
        "store.counters", ("runtime/compile_store.py::GLOBAL._COUNTER_LOCK",),
        doc="store hit/miss counters",
    ),
    LockSpec(
        "runtime.taxonomy", ("runtime/errors.py::GLOBAL._TAXONOMY_LOCK",),
        doc="error taxonomy counters",
    ),
    LockSpec(
        "obs.timeseries", ("obs/timeseries.py::TimeSeriesStore._lock",),
        doc="in-process time-series ring",
    ),
    LockSpec(
        "telemetry.trace", ("telemetry/trace.py::SpanTracer._lock",),
        doc="span tracer event list",
    ),
    LockSpec(
        "obs.recorder_default", ("obs/recorder.py::GLOBAL._default_lock",),
        doc="process-default recorder singleton latch",
    ),
    LockSpec(
        "obs.recorder", ("obs/recorder.py::FlightRecorder._lock",),
        doc="flight-recorder ring; holds its own fsync I/O by design "
        "(tail-safety beats latency), so it is the INNERMOST rank",
    ),
)

LOCK_RANKS: Dict[str, int] = {
    spec.name: rank for rank, spec in enumerate(LOCK_HIERARCHY)
}
_SPECS: Dict[str, LockSpec] = {spec.name: spec for spec in LOCK_HIERARCHY}


def _env_armed() -> bool:
    return os.environ.get("WITT_LOCK_TRACE", "") not in ("", "0", "off")


# -- trace state --------------------------------------------------------------
_armed: bool = _env_armed()
_tls = threading.local()
#: guards every module-level structure below.  Internal to the tracer
#: (not a registry lock): it is only ever the innermost acquisition and
#: never held across a callback, so it cannot participate in a cycle.
_state_lock = threading.Lock()
_edges: Dict[Tuple[str, str], int] = {}
_violations: List[dict] = []
_violation_pairs: set = set()
_wait_stats: Dict[str, List[float]] = {}  # name -> [count, total_s, max_s]
_wait_samples: deque = deque(maxlen=4096)


def arm_lock_trace(on: bool = True) -> None:
    """Flip tracing at runtime (tests).  The env var ``WITT_LOCK_TRACE``
    sets the process default at import time."""
    global _armed
    _armed = bool(on)


def reset_lock_trace() -> None:
    """Clear the recorded graph, violations, and wait metrics (the armed
    flag is untouched).  Call between test phases."""
    with _state_lock:
        _edges.clear()
        _violations.clear()
        _violation_pairs.clear()
        _wait_stats.clear()
        _wait_samples.clear()


def _held_stack() -> list:
    stack = getattr(_tls, "held", None)
    if stack is None:
        stack = _tls.held = []
    return stack


def _has_path(src: str, dst: str) -> bool:
    """DFS over the observed edge graph: is dst reachable from src?"""
    seen = set()
    frontier = [src]
    while frontier:
        node = frontier.pop()
        if node == dst:
            return True
        if node in seen:
            continue
        seen.add(node)
        frontier.extend(b for (a, b) in _edges if a == node)
    return False


class TracedLock:
    """A named, hierarchy-ranked ``threading.Lock`` wrapper.

    Unarmed, ``acquire``/``release`` delegate with a single module-flag
    read — measured indistinguishable from a bare lock.  Armed, each
    acquisition is timed, pushed on a thread-local held stack, and
    checked against every held lock: a rank inversion (or a cycle the
    new edge closes in the cross-thread acquisition graph) is recorded
    once per (held, acquiring) pair and emitted as a
    ``lock-order-violation`` flight-recorder event.
    """

    __slots__ = ("name", "rank", "_lock")

    def __init__(self, name: str):
        if name not in LOCK_RANKS:
            raise ValueError(
                f"lock {name!r} is not in LOCK_HIERARCHY; register it "
                "in runtime/locks.py before constructing it"
            )
        self.name = name
        self.rank = LOCK_RANKS[name]
        self._lock = threading.Lock()

    # threading.Lock signature, Condition-compatible
    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        if not _armed or getattr(_tls, "tracing", False):
            return self._lock.acquire(blocking, timeout)
        _tls.tracing = True
        try:
            held = _held_stack()
            if held:
                self._audit(held)
            t0 = time.perf_counter()
        finally:
            _tls.tracing = False
        ok = self._lock.acquire(blocking, timeout)
        if not _armed:
            return ok
        _tls.tracing = True
        try:
            if ok:
                waited = time.perf_counter() - t0
                _held_stack().append(self)
                with _state_lock:
                    st = _wait_stats.setdefault(self.name, [0, 0.0, 0.0])
                    st[0] += 1
                    st[1] += waited
                    st[2] = max(st[2], waited)
                    _wait_samples.append(waited)
        finally:
            _tls.tracing = False
        return ok

    def release(self) -> None:
        self._lock.release()
        held = getattr(_tls, "held", None)
        if held:
            for i in range(len(held) - 1, -1, -1):
                if held[i] is self:
                    del held[i]
                    break

    def locked(self) -> bool:
        return self._lock.locked()

    def __enter__(self) -> "TracedLock":
        self.acquire()
        return self

    def __exit__(self, *exc) -> None:
        self.release()

    def __repr__(self) -> str:
        return f"TracedLock({self.name!r}, rank={self.rank})"

    def _audit(self, held: list) -> None:
        """Record edges held->self; a rank inversion or a closed cycle
        is a violation (deduped per pair).  Called with tracing=True so
        the recorder emission below cannot recurse."""
        fresh: List[dict] = []
        with _state_lock:
            for h in held:
                pair = (h.name, self.name)
                _edges[pair] = _edges.get(pair, 0) + 1
                bad = None
                if self.rank <= h.rank:
                    bad = (
                        "rank inversion" if self.rank < h.rank
                        else "re-acquisition of a held non-reentrant lock"
                    )
                elif _has_path(self.name, h.name):
                    bad = "acquisition-graph cycle"
                if bad and pair not in _violation_pairs:
                    _violation_pairs.add(pair)
                    v = {
                        "held": h.name,
                        "heldRank": h.rank,
                        "acquiring": self.name,
                        "acquiringRank": self.rank,
                        "kind": bad,
                        "thread": threading.current_thread().name,
                    }
                    _violations.append(v)
                    fresh.append(v)
        for v in fresh:
            _emit_violation(v)


def _emit_violation(v: dict) -> None:
    """Typed flight-recorder event; best-effort (the tracer must never
    take the fleet down).  Absolute import: this module is also loaded
    standalone by the static checker, where the package may be absent —
    there no violations are ever emitted."""
    try:
        from wittgenstein_tpu.obs.recorder import get_recorder

        get_recorder().record(
            "lock-order-violation",
            held=v["held"],
            acquiring=v["acquiring"],
            held_rank=v["heldRank"],
            acquiring_rank=v["acquiringRank"],
            cycle_kind=v["kind"],
            thread=v["thread"],
        )
    except Exception:
        pass


def make_lock(name: str) -> TracedLock:
    """Construct the registered lock ``name``.  The static pass accepts
    only registered names here (SL1301)."""
    return TracedLock(name)


def lock_trace_status() -> dict:
    """The ``witt_runtime_lock_wait_seconds`` surface: armed flag,
    violation count (+ the deduped violation rows), max/p99 observed
    wait, per-lock acquisition counts.  Cheap enough for /w/health."""
    with _state_lock:
        samples = sorted(_wait_samples)
        per_lock = {
            name: {
                "acquisitions": int(st[0]),
                "waitSecondsTotal": round(st[1], 6),
                "maxWaitS": round(st[2], 6),
            }
            for name, st in sorted(_wait_stats.items())
        }
        violations = [dict(v) for v in _violations]
    p99 = samples[min(len(samples) - 1, int(0.99 * len(samples)))] if samples else 0.0
    return {
        "armed": _armed,
        "violationCount": len(violations),
        "violations": violations,
        "maxWaitS": round(max((s[2] for s in _wait_stats.values()), default=0.0), 6),
        "waitP99S": round(p99, 6),
        "perLock": per_lock,
    }


# -- deterministic interleaving hooks ----------------------------------------
#: every named yield point compiled into a hot path.  SL1307 asserts
#: this catalog and the yield_point() call sites stay in sync.
YIELD_POINTS: Tuple[str, ...] = (
    "runcache.lookup-miss",   # after an unlocked run-cache program miss
    "runcache.compile",       # inside the compile lock, recheck missed
    "store.get",              # compile-store payload read
    "store.put",              # compile-store payload publish
    "serve.claim",            # lane about to claim a batch
    "serve.dispatch",         # batch about to execute on its lane
    "serve.harvest",          # done-row harvest decision point
    "serve.lane-failure",     # lane failover about to rebind
)

_interleave: Optional[Callable[[str], None]] = None


def set_interleave(controller: Optional[Callable[[str], None]]) -> None:
    """Install (or clear, with None) the interleaving controller.  The
    controller is called with the yield-point name from the thread that
    reached it and may block to impose a schedule (tests/interleave.py)."""
    global _interleave
    _interleave = controller


def yield_point(name: str) -> None:
    """A named scheduling hook: no-op (one global read) unless a
    controller is installed."""
    c = _interleave
    if c is not None:
        c(name)
