"""The arithmetic of the end-to-end metrics, apart from anything that
touches JAX, so that it can be checked on a recorded list of times."""

from __future__ import annotations

import math
import statistics


def nearest_rank(values, q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least q of the
    sample at or below it.  With fewer than 1/(1-q) samples it is the
    largest."""
    if not values:
        raise ValueError("no samples")
    ordered = sorted(values)
    return ordered[max(1, math.ceil(q * len(ordered))) - 1]


def sim_ms_per_s(replicas: int, chunk_ms: int, chunks: int, elapsed_s: float) -> float:
    """Replica-milliseconds simulated per second of wall time.  `chunks`
    counts every chunk completed in the window, the one that overshot
    `--seconds` too, and `elapsed_s` runs to the completion of that one:
    all the work over all the time."""
    return replicas * chunk_ms * chunks / elapsed_s


def window_is_over(elapsed_s: float, seconds: float) -> bool:
    """The window stops at the first chunk boundary at or after
    `--seconds`."""
    return elapsed_s >= seconds


def iqr_share(values) -> float:
    """The spread the contract's bounds are set from: the distance between
    the first and third quartile over the median."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
