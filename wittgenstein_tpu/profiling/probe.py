"""TPU probe-verdict cache + export surface.

Nothing in the repo probes or writes a verdict today (the benchmark and
chip_smoke.py run in one process and fail without a TPU); what lives
here is the verdict's cache file and export surface, a debt ROADMAP C1
names:

  * the TTL'd /tmp cache, so a process tree probes once;
  * probe_verdict_fields() — the flat run-record view of a verdict
    (attempts, last rc, fallback_reason, cache age) so every BENCH /
    rung JSONL line says WHY it ran where it ran;
  * add_probe_metrics() — the Prometheus families for GET /metrics, so
    a CPU fallback shows up on a dashboard instead of only in raw JSON
    tails.
"""

from __future__ import annotations

import json
import os
import time
from typing import Optional

# cached verdicts older than this are stale
PROBE_CACHE_TTL_S = 3600


def probe_cache_path() -> str:
    """Per-process-tree probe-verdict cache in /tmp: keyed by uid +
    session id so one process tree shares one verdict."""
    import tempfile

    try:
        scope = os.getsid(0)
    except (AttributeError, OSError):  # non-POSIX / detached
        scope = os.getppid()
    return os.path.join(
        tempfile.gettempdir(), f"witt_bench_probe_{os.getuid()}_{scope}.json"
    )


def read_probe_cache(path: Optional[str] = None) -> Optional[dict]:
    """The cached verdict dict (incl. its write timestamp "ts"), or None
    if absent/stale/invalid."""
    path = path or probe_cache_path()
    try:
        with open(path) as f:
            cached = json.load(f)
        if time.time() - float(cached.get("ts", 0)) > PROBE_CACHE_TTL_S:
            return None
        if not cached.get("platform"):
            return None
        return cached
    except (OSError, ValueError):
        return None


def write_probe_cache(verdict: dict, path: Optional[str] = None) -> None:
    path = path or probe_cache_path()
    tmp = f"{path}.{os.getpid()}.tmp"
    try:
        with open(tmp, "w") as f:
            json.dump({**verdict, "ts": time.time()}, f)
        os.replace(tmp, path)  # atomic: concurrent rungs see old or new
    except OSError:
        pass  # cache is an optimization, never a failure


def probe_cache_age_s(path: Optional[str] = None) -> Optional[float]:
    """Seconds since the cached verdict was written, or None when there
    is no live cache entry."""
    cached = read_probe_cache(path)
    if cached is None:
        return None
    return max(0.0, time.time() - float(cached.get("ts", 0)))


def probe_verdict_fields(probe: dict) -> dict:
    """Flatten a _probe_backend() verdict into the run-record fields the
    ISSUE asks for: platform, attempt count, last rc, fallback reason,
    whether/when the verdict came from the /tmp cache."""
    attempts = probe.get("attempts") or []
    last = attempts[-1] if attempts else {}
    reason = probe.get("fallback_reason")
    return {
        "platform": probe.get("platform"),
        "attempts": len(attempts),
        "last_rc": last.get("rc"),
        "fallback_reason": reason,
        "from_cache": bool(reason and "cached probe verdict" in str(reason)),
        "cache_age_s": (
            round(probe_cache_age_s(), 1)
            if probe_cache_age_s() is not None
            else None
        ),
    }


def add_probe_metrics(prom, path: Optional[str] = None) -> None:
    """Append witt_probe_* families to a telemetry.export.PromText.

    Families: probe_cache_present (0/1), probe_cache_age_seconds, and a
    labelled probe_platform_verdict (one sample, platform label) — all
    read from the /tmp cache, because the serving process never probes
    itself."""
    cached = read_probe_cache(path)
    prom.add(
        "probe_cache_present",
        1 if cached is not None else 0,
        help="1 when a live TTL'd TPU probe verdict exists in /tmp",
        mtype="gauge",
    )
    if cached is None:
        return
    age = max(0.0, time.time() - float(cached.get("ts", 0)))
    prom.add(
        "probe_cache_age_seconds",
        round(age, 1),
        help="seconds since the probe verdict was cached",
        mtype="gauge",
    )
    prom.add(
        "probe_platform_verdict",
        1,
        help="cached probe verdict; the platform label says where runs go",
        mtype="gauge",
        labels={"platform": str(cached.get("platform"))},
    )
