"""Test configuration: force the CPU backend with 8 virtual devices so the
sharding/multi-chip paths are exercised without TPU hardware.  The CPU is
forced because the tests pin CPU results and must not take (or wait for)
a chip that another process holds."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

# persistent compilation cache: the batched-protocol test graphs are large
# (per-level unrolled loop bodies) and identical across runs; JAX's
# default threshold caches every compile of 1 s or more
from wittgenstein_tpu.utils.compile_cache import use_compile_cache  # noqa: E402

use_compile_cache()

# -- opt-in strict JAX runtime guards (docs/static_analysis.md) -------------
# WITT_STRICT_JAX=1 arms the runtime complements of simlint's static
# checks: reject implicit host<->device transfers (a silent sync inside a
# jit path is exactly the bug SL103 hunts textually) and check for leaked
# tracers on every trace.  Not on by default: the guards also flag the
# benign numpy->device uploads of host-side construction and slow every
# trace, so this is a diagnostic mode for kernel development, not a gate.
if os.environ.get("WITT_STRICT_JAX") == "1":
    jax.config.update("jax_transfer_guard", "disallow")
    jax.config.update("jax_check_tracer_leaks", True)

import pytest  # noqa: E402

# -- fast-tier time budget ----------------------------------
# The default run (-m "not slow") must stay inside an iteration-speed
# budget; r4's fast tier silently grew to 43 minutes.  The gate sums the
# durations pytest already measures and FAILS the session when the sum
# exceeds WITT_FAST_BUDGET_S, so a budget regression cannot land quietly.
# The sum is wall-clock of test phases (immune to collection idle time but
# not machine load); the default leaves ~2x headroom over the measured
# unloaded sum so load spikes don't flap the gate.  0 disables.
# r6 recalibration: the r5 budget (900 s, ~2x headroom over a 793 s
# multi-core measurement) is unreachable on the r6 container, which
# exposes ONE CPU core — the unchanged r5 suite alone measures ~1500 s
# there.  1800 keeps the gate armed against silent growth while being
# attainable on a single core; CI sets WITT_FAST_BUDGET_S=0 and relies
# on its own job timeout.
# r12 recalibration: the suite grew ~420 → 646 tests across the serving,
# density and observability PRs and the warm single-core sum now measures
# ~1980 s — over the r6 budget even before this PR (which adds 17 s).
# 2400 restores the same ~1.2x single-core headroom r6 chose; the gate
# stays armed against the next silent 43-minute drift.
try:
    FAST_BUDGET_S = float(os.environ.get("WITT_FAST_BUDGET_S", "2400"))
except ValueError:
    raise SystemExit(
        f"WITT_FAST_BUDGET_S={os.environ['WITT_FAST_BUDGET_S']!r} must be "
        "a number of seconds (0 disables the fast-tier budget gate)"
    )
_phase_seconds = [0.0]


def pytest_runtest_logreport(report):
    _phase_seconds[0] += report.duration


@pytest.fixture(autouse=True, scope="session")
def _fast_budget_gate(request):
    """Fails the session (teardown error on the last test) when the fast
    tier overran the budget — pytest_sessionfinish fires after the exit
    code is decided, so a fixture finalizer is the enforcement point.
    The gate arms exactly when the slow tier is deselected, detected from
    the FINAL selection (session.items — a collection hook would see
    items before pytest's own markexpr deselection and disarm on every
    run)."""
    yield
    slow_selected = any(
        i.get_closest_marker("slow") for i in request.session.items
    )
    if slow_selected or FAST_BUDGET_S <= 0:
        return
    spent = _phase_seconds[0]
    if spent > FAST_BUDGET_S:
        pytest.fail(
            f"FAST-TIER BUDGET EXCEEDED: {spent:.0f}s > {FAST_BUDGET_S:.0f}s "
            "(WITT_FAST_BUDGET_S). Move the offenders (pytest "
            "--durations=10) to the slow tier.",
            pytrace=False,
        )


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_per_module():
    """Drop in-process jit executables between modules: a full-suite run
    otherwise accumulates hundreds of compiled batched-simulation programs
    (each BatchedNetwork's jit cache holds strong refs) and runs several
    times slower than the per-module sum.  The persistent on-disk cache
    keeps recompiles cheap."""
    yield
    jax.clear_caches()


@pytest.fixture(scope="module")
def no_compile_cache():
    """The persistent compilation cache off for a module that asks for it.
    tests/test_tpu_compile.py: a compile for a described chip is written to
    the cache but cannot be read back without one (the next run warns and
    compiles again).  Every test that runs a batched Dfinity program
    (tests/test_dfinity_batched.py, tests/test_dfinity_partition.py, the
    Dfinity cases of tests/test_benchmark_conservation.py and
    test_benchmark_completion.py): XLA:CPU's executable serialisation
    crashes on them now and then, once where the cache WRITES an entry
    (`executable.serialize()`, a cold cache) and three times where it
    reads one (`deserialize_executable`), each time in a worker that ran
    tests/test_dfinity_batched.py, whose cases each pass alone (sandbox,
    PR 45: the first session took the reads for two workers racing on
    one entry; the cold run's write has no second party)."""
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()
