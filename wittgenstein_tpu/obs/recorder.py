"""Flight recorder: a bounded host-side ring of structured run events.

The recorder answers the question a postmortem otherwise answers by
hand: *what happened to this run, in order?*  Producers
(serve scheduler, supervisor, smokes, bench) record small dict events —
admission / 429s, batch packing decisions, chunk start/end with tick
high-water marks, retries with the classified error, watchdog fires,
degradations, checkpoint writes, kills, resumes — each stamped with a
wall-clock ``ts``, a monotone ``seq``, and the TraceContext ids.

Two persistence modes, both host-side only (sim state stays
bit-identical with the recorder armed — same neutrality standard as
telemetry):

- **ring only** (default): a ``deque(maxlen=capacity)`` holding the
  last N events; ``dump(path)`` writes them atomically (pid-tmp +
  ``os.replace``, same convention as engine/checkpoint.py).  The
  supervisor dumps the ring beside the checkpoints on any typed
  runtime/errors.py failure.
- **armed path**: when constructed with ``path=``, every event is ALSO
  appended + flushed to that JSONL file at record time, so the tail
  survives SIGKILL (same tail-safe convention as RunRecordWriter).
  durable_smoke relies on this to reconstruct the kill itself.

Event volume is one-per-chunk scale (not per-tick), so the append+flush
cost is noise next to the device sync that precedes every chunk event.

``scripts/obs_query.py`` replays dumps into a per-run timeline and a
merged Chrome trace.
"""

from __future__ import annotations

import collections
import itertools
import json
import os
import threading
import time
from typing import Iterable, List, Optional

from .context import TraceContext

# File names the CI forensics collector (scripts/obs_query.py collect)
# looks for: the armed live file and the atomic failure dump.
LIVE_BASENAME = "flight_recorder.jsonl"
DUMP_BASENAME = "flight_recorder_dump.jsonl"

# When set, the process-default recorder (get_recorder) persists there
# and supervisor failure dumps land there too; tier1.yml exports it so
# a failing test leaves forensics for the artifact step.
ENV_DIR = "WITT_OBS_DIR"

DEFAULT_CAPACITY = 4096

# The event vocabulary, for dashboards and assertions (record() does
# NOT enforce membership — producers may add kinds, this tuple is the
# documented catalog).  Grouped by producer:
#   admission/dispatch (serve.scheduler): admission, admission-rejected,
#     pack, batch-failed
#   durable execution (runtime.supervisor): chunk, retry, watchdog,
#     degrade, checkpoint, resume, kill, run-start, run-end
#   fleet resilience (serve.scheduler, this PR's additions):
#     lane-failed      a lane worker thread died (error_kind, streak)
#     lane-restart     its supervised replacement thread started
#     lane-abandoned   restart limit reached; lane left down
#     family-rebound   sticky family→lane binding moved off a dead lane
#     binding-expired  idle sticky binding reaped (binding_ttl_s)
#     salvage-start    a failed packed batch enters bisection
#     salvage-run      one bisection probe (rows, ok, error)
#     quarantine       a poison row gets its terminal disposition
#     salvage-done     bisection verdict (salvaged/quarantined/failed)
#     drain-start      graceful drain engaged (admission now refuses)
#     drain-end        undrain — admission + claiming resume
#   mission control (obs.slo / obs.monitor):
#     slo-alert            a burn-rate SLO started firing (slo, severity,
#                          burn_fast/burn_slow, measured, victim ids)
#     slo-resolved         that SLO returned to ok
#     invariant-violation  the runtime sentinel caught a broken
#                          invariant (slo names it; replica/mtype named)
KNOWN_KINDS = (
    "admission",
    "admission-rejected",
    "pack",
    "batch-failed",
    "chunk",
    "retry",
    "watchdog",
    "degrade",
    "checkpoint",
    "resume",
    "kill",
    "run-start",
    "run-end",
    "lane-failed",
    "lane-restart",
    "lane-abandoned",
    "family-rebound",
    "binding-expired",
    "salvage-start",
    "salvage-run",
    "quarantine",
    "salvage-done",
    "drain-start",
    "drain-end",
    "slo-alert",
    "slo-resolved",
    "invariant-violation",
    "lock-order-violation",
    # adversary search campaigns (search/driver.py)
    "search-generation",
    "search-resume",
    "search-complete",
    "search-pinned",
)


class FlightRecorder:
    """Thread-safe bounded event ring with optional tail-safe JSONL."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY, path: Optional[str] = None):
        if capacity <= 0:
            raise ValueError(f"capacity must be positive, got {capacity}")
        self.capacity = int(capacity)
        self.path = path
        self._ring: collections.deque = collections.deque(maxlen=self.capacity)
        self._seq = itertools.count()
        # deferred import: runtime/__init__ -> supervisor -> obs ->
        # recorder would cycle if this sat at module level; recorders
        # are only ever constructed after imports settle
        from ..runtime.locks import make_lock

        self._lock = make_lock("obs.recorder")
        if path:
            parent = os.path.dirname(path)
            if parent:
                os.makedirs(parent, exist_ok=True)

    def record(self, kind: str, ctx: Optional[TraceContext] = None, **fields) -> dict:
        """Append one event.  ``ctx`` ids land as top-level fields so a
        grep for a run_id finds every event of the run.  Returns the
        event dict (callers may log or assert on it)."""
        ev = {"ts": round(time.time(), 6), "kind": str(kind)}
        if ctx is not None:
            ev.update(ctx.ids())
        for key, val in fields.items():
            # reserved envelope keys cannot be clobbered by payloads
            if val is not None and key not in ("ts", "kind", "seq"):
                ev[key] = val
        with self._lock:
            ev["seq"] = next(self._seq)
            self._ring.append(ev)
            if self.path:
                # append+flush per event: the tail survives SIGKILL.
                with open(self.path, "a", encoding="utf-8") as fh:
                    fh.write(json.dumps(ev, sort_keys=True) + "\n")
                    fh.flush()
                    os.fsync(fh.fileno())
        return ev

    def events(self, run_id: Optional[str] = None) -> List[dict]:
        """Snapshot of the ring (oldest first), optionally one run only."""
        with self._lock:
            evs = list(self._ring)
        if run_id is not None:
            evs = [e for e in evs if e.get("run_id") == run_id]
        return evs

    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)

    def dump(self, path: str) -> str:
        """Write the ring to ``path`` as JSONL, atomically (pid-tmp +
        os.replace) so a dump raced by a crash is intact-or-absent.
        Returns the path."""
        evs = self.events()
        parent = os.path.dirname(path)
        if parent:
            os.makedirs(parent, exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w", encoding="utf-8") as fh:
            for ev in evs:
                fh.write(json.dumps(ev, sort_keys=True) + "\n")
            fh.flush()
            os.fsync(fh.fileno())
        os.replace(tmp, path)
        return path


def read_events(paths) -> List[dict]:
    """Load flight-recorder JSONL file(s), skipping torn tail lines
    (the armed file may end mid-write after SIGKILL — same tolerance as
    telemetry.read_run_records).  Events are merged and ordered by
    (ts, seq) so multi-process runs (victim + resume) interleave
    correctly."""
    if isinstance(paths, (str, os.PathLike)):
        paths = [paths]
    out: List[dict] = []
    for path in paths:
        try:
            with open(path, "r", encoding="utf-8") as fh:
                for line in fh:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        ev = json.loads(line)
                    except json.JSONDecodeError:
                        continue  # torn tail line
                    if isinstance(ev, dict):
                        out.append(ev)
        except OSError:
            continue
    out.sort(key=lambda e: (e.get("ts", 0.0), e.get("seq", 0)))
    return out


# ---------------------------------------------------------------------------
# Process-default recorder.
#
# Components that are not handed an explicit recorder (Supervisor,
# BatchScheduler) fall back to one shared per-process ring so forensics
# exist even for callers that never opted in.  With WITT_OBS_DIR set
# the default recorder is armed (tail-safe JSONL under that dir) —
# tier1.yml uses this so any test failure leaves a dump to upload.
# ---------------------------------------------------------------------------

_default_recorder: Optional[FlightRecorder] = None
_default_lock = threading.Lock()


def get_recorder() -> FlightRecorder:
    """The lazily-created process-default recorder (see module note)."""
    global _default_recorder
    with _default_lock:
        if _default_recorder is None:
            obs_dir = os.environ.get(ENV_DIR)
            path = os.path.join(obs_dir, LIVE_BASENAME) if obs_dir else None
            _default_recorder = FlightRecorder(path=path)
        return _default_recorder


def reset_default_recorder() -> None:
    """Drop the process-default recorder (tests; env-var changes)."""
    global _default_recorder
    with _default_lock:
        _default_recorder = None


def failure_dump_paths(checkpoint_dir: Optional[str] = None) -> List[str]:
    """Where a failure dump should land: beside the checkpoints (the
    durable place a resume will look) and under WITT_OBS_DIR (the place
    CI collects from).  Either or both may be absent."""
    paths = []
    if checkpoint_dir:
        paths.append(os.path.join(checkpoint_dir, DUMP_BASENAME))
    obs_dir = os.environ.get(ENV_DIR)
    if obs_dir:
        paths.append(os.path.join(obs_dir, DUMP_BASENAME))
    return paths
