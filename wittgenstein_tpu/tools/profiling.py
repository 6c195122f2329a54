"""Profiling helpers (the SURVEY §5 'tracing/profiling' upgrade — the
reference's observability is counters + stdout; here device-level traces
come from jax.profiler).

Usage:

    from wittgenstein_tpu.tools.profiling import host_span, trace
    with trace("/tmp/witt-trace"):
        with host_span("run"):
            out = net.run_ms_batched(states, 1000)
            jax.block_until_ready(out)

The trace directory opens in TensorBoard's profile plugin / Perfetto;
`benchmark/xplane.py` reads it with nothing but JAX.
"""

from __future__ import annotations

import contextlib
import time
from typing import ContextManager, Iterator, MutableMapping, Optional


@contextlib.contextmanager
def trace(log_dir: str) -> Iterator[None]:
    """jax.profiler trace over the with-block (always stopped, even on
    failure — a leaked active profiler poisons every later start_trace)."""
    import jax

    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


_NO_LOCK = contextlib.nullcontext()


class host_span:
    """The program's one span: a name, a start and an end.

    Opens `jax.profiler.TraceAnnotation("witt.host." + name)`, so that
    under a profiler trace the span lies on the `/host:CPU` plane, on
    the device events' clock; with no trace running the annotation is a
    no-op.  On exit the elapsed `perf_counter` seconds are in `.seconds`
    and, when `counters` is given, added to `counters[key]` (a monotonic
    total such as the run cache's `_COUNTERS`), under `lock` where the
    total is shared between threads.  Nothing is kept per call and no
    list grows."""

    __slots__ = ("seconds", "_annotation", "_counters", "_key", "_lock", "_t0")

    def __init__(
        self,
        name: str,
        counters: Optional[MutableMapping[str, float]] = None,
        key: Optional[str] = None,
        lock: Optional[ContextManager] = None,
    ):
        import jax

        self._annotation = jax.profiler.TraceAnnotation("witt.host." + name)
        self._counters = counters
        self._key = key
        self._lock = _NO_LOCK if lock is None else lock
        self.seconds: Optional[float] = None

    def __enter__(self) -> "host_span":
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0
        self._annotation.__exit__(*exc)
        if self._counters is not None:
            with self._lock:
                self._counters[self._key] += self.seconds
