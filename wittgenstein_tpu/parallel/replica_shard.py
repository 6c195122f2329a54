"""Replica-axis data parallelism: shard stacked simulation states over a
device mesh and reduce statistics across devices inside one jit.

This is the TPU-native replacement for RunMultipleTimes' sequential
reseeded loop (RunMultipleTimes.java:48-63): R replicas run in lockstep,
sharded R/D per device; the statistics reduction (min/max/mean over the
(replica, node) axes) compiles to on-device partial reductions plus the
cross-device collective XLA chooses for the sharding — no host gather of
per-replica state ever happens.

Cost accounting (ISSUE-7): every program this cache compiles goes
through the explicit AOT path (lower → compile → call), so the compiled
object is in hand to capture `cost_analysis()` / `memory_analysis()`
and the compile wall-clock.  The cache therefore knows, per (protocol,
config, horizon, input geometry): FLOPs, bytes accessed, live/temp HBM,
and compile seconds — run_cache_metrics() exports all of it, and the
hit/miss/eviction/compile-seconds counters feed the server's
witt_run_cache_* Prometheus families.

Warm starts (ISSUE-13): when a durable compile store is installed
(runtime.compile_store — set_compile_store / $WITT_COMPILE_STORE), the
per-geometry compile first consults the store under the engine's
*stable* cache key (net.stable_cache_key(), id()-free) and publishes
fresh compiles back to it.  A store hit bypasses lower().compile()
entirely, so the monotonic "compiles" counter genuinely stays 0 on a
warm restart — the counter-asserted zero-compile contract; store hits
tick "store_hits" instead.
"""

from __future__ import annotations

import gc
import hashlib
import threading
import time
from collections import OrderedDict, deque
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..engine.core import CENSUS_VECTOR, CENSUS_VECTOR_PEAKS, chunk_census
from ..profiling.xla_cost import compiled_cost_summary, hlo_op_scopes
from ..runtime.locks import make_lock, yield_point
from ..tools.profiling import host_span


def shard_replicas(states, mesh: Mesh, axis: str = "replicas"):
    """Place a stacked state pytree with leading replica axis onto the
    mesh, sharded along `axis` (replicated on any other mesh axes)."""
    sharding = NamedSharding(mesh, P(axis))
    return jax.tree_util.tree_map(lambda a: jax.device_put(a, sharding), states)


def _replica_sharding(states):
    """The one NamedSharding every leaf carries when a stacked state is
    sharded along its leading (replica) axis over more than one device
    and along nothing else; None for any other placement."""
    first = None
    for leaf in jax.tree_util.tree_leaves(states):
        sh = getattr(leaf, "sharding", None)
        if (
            not isinstance(sh, NamedSharding)
            or sh.mesh.size == 1
            or len(sh.spec) != 1
            or sh.spec[0] is None
            or (first is not None and sh != first)
        ):
            return None
        first = first or sh
    return first


# compiled-program cache, keyed EXPLICITLY on (net.cache_key(), sim_ms) —
# protocol name + static engine knobs (see BatchedNetwork.cache_key) —
# instead of hashing the network object through lru_cache.  Bounded FIFO
# with a clear hook: long sweep campaigns that churn through many configs
# can flush it (clear_run_cache) rather than pinning 64 full jit programs
# (and the engines/latency tables their closures hold) for process life.
_RUN_CACHE: "OrderedDict[tuple, _CachedRun]" = OrderedDict()
_RUN_CACHE_MAX = 64
# entry creation is check-then-act; concurrent callers (serve batch
# workers, sweep threads) must not each install their own _CachedRun
# for one key — that duplicates the compile despite the per-entry lock
_CACHE_LOCK = make_lock("runcache.entry")

# the PR-11 guard: recheck the program table AFTER taking the compile
# lock.  Module-level so the regression test can deliberately revert it
# and prove the interleaving harness reproduces the duplicate compile
_RECHECK_UNDER_LOCK = True

# every add to _COUNTERS outside _CACHE_LOCK: serve lanes compile and
# dispatch different entries at once, and `d[k] += x` is not atomic
_COUNTER_LOCK = make_lock("runcache.counters")

# monotonic across clear_run_cache() — Prometheus counters must never
# step backwards just because a campaign flushed the program cache
_COUNTERS = {
    "hits": 0,
    "misses": 0,
    "evictions": 0,
    "compiles": 0,
    # where set-up and dispatch happen, each region under a
    # `witt.host.<span>` host_span that feeds one of these (PERF.md §3).
    # `compile_seconds_total` is the sum of the first two and is not
    # stored (`_counters`), so a compile that raises cannot part them.
    # Python tracing and lowering to StableHLO
    "lower_seconds_total": 0.0,
    # XLA's compile, or the persistent compilation cache's load
    "backend_compile_seconds_total": 0.0,
    # cache key + LRU, layout placement, input signature, program table
    "lookup_seconds_total": 0.0,
    # compiled(states), call to return: the enqueue, not the device time
    "execute_seconds_total": 0.0,
    "calls": 0,
    # durable compile-store integration: programs adopted from /
    # published to the cross-process store (runtime.compile_store)
    "store_hits": 0,
    "store_puts": 0,
    # the work census (engine.core.Census), counted on the device inside
    # the chunk program and folded here a call later (`_harvest`): what
    # the chunks did, as counts (PERF.md section 3, docs/observability.md)
    **{
        f"census_{name}_total": 0
        for name in CENSUS_VECTOR
        if name not in CENSUS_VECTOR_PEAKS
    },
    # its peaks: gauges since process start, each beside the static limit
    # of the program that set it, so headroom is one subtraction
    **{f"census_{name}": 0 for name in CENSUS_VECTOR_PEAKS},
    **{f"census_{name}_limit": 0 for name in CENSUS_VECTOR_PEAKS},
    # the host's part of the census: the copy's start and the folds
    "census_seconds_total": 0.0,
}

# chunk census vectors whose copy to the host is under way, oldest first,
# each with its program's limits: folded by a later call once ready
_PENDING_CENSUS: "deque[tuple]" = deque()
_CENSUS_LOCK = make_lock("runcache.census")

# the collector's pauses, for the stalled chunks PERF.md section 7 puts
# down to a cold run's garbage.  Written by the hook alone (collections
# do not nest), and without `_COUNTER_LOCK`: a collection can start
# inside it
_GC = {"gc_pause_seconds_total": 0.0, "gc_collections_total": 0}
_GC_STARTED = [None]


def _gc_hook(phase: str, info: dict) -> None:
    if phase == "start":
        _GC_STARTED[0] = time.perf_counter()
    elif _GC_STARTED[0] is not None:
        _GC["gc_pause_seconds_total"] += time.perf_counter() - _GC_STARTED[0]
        _GC["gc_collections_total"] += 1
        _GC_STARTED[0] = None


def _count(key: str, amount=1) -> None:
    with _COUNTER_LOCK:
        _COUNTERS[key] += amount


def _span(name: str, key: "str | None" = None) -> host_span:
    """`witt.host.<name>` around a region of set-up or dispatch, its
    seconds added to `_COUNTERS[key]` (PERF.md §3 names each span's
    reader)."""
    return host_span(name, _COUNTERS if key else None, key, lock=_COUNTER_LOCK)


def _fold_census(vector, limits: dict) -> None:
    """One chunk's census into `_COUNTERS`: sums added, of a peak the
    larger kept, with the limit of the program that reached it (or of
    the first program that has the mechanism, while the peak is 0)."""
    values = dict(zip(CENSUS_VECTOR, np.asarray(vector).tolist()))
    with _COUNTER_LOCK:
        for name, value in values.items():
            key = f"census_{name}"
            if name not in CENSUS_VECTOR_PEAKS:
                _COUNTERS[key + "_total"] += value
            elif value > _COUNTERS[key] or not _COUNTERS[key + "_limit"]:
                _COUNTERS[key] = max(value, _COUNTERS[key])
                _COUNTERS[key + "_limit"] = limits[name]


def _harvest(vector=None, limits=None, wait: bool = False) -> None:
    """The census's host side.  With a chunk's `vector`: start its copy
    to the host and queue it.  Then fold the queued vectors that have
    arrived, oldest first, each exactly once (whoever takes one off the
    queue folds it); `wait` takes them all and blocks on them outside the
    queue's lock (`run_cache_info`, outside any window).  The chunk just
    enqueued is still running and is never waited for here."""
    with _span("census", "census_seconds_total"):
        if vector is not None:
            vector.copy_to_host_async()
        arrived = []
        with _CENSUS_LOCK:
            if vector is not None:
                _PENDING_CENSUS.append((vector, limits))
            while _PENDING_CENSUS and (wait or _PENDING_CENSUS[0][0].is_ready()):
                arrived.append(_PENDING_CENSUS.popleft())
        for item in arrived:
            _fold_census(*item)


class _CachedRun:
    """The cached entry for one (net.cache_key(), sim_ms, layout
    geometry): a callable with jit semantics whose compiles are
    explicit.  Per input geometry (leaf shapes/dtypes/shardings) it
    lowers and compiles ONCE, records the compile wall-clock and the
    normalized cost/memory analyses, then dispatches to the compiled
    executable.

    Sharding is a CONSTRUCTOR-TIME layout decision: when a
    mesh2d.MeshLayout is given, every call places the incoming states
    onto that layout before dispatch, and the layout's geometry is part
    of both the in-process cache key and the durable-store key — a
    (2,4) and a (4,2) program over the same devices never collide."""

    def __init__(self, net, sim_ms: int, key: tuple, layout=None):
        self.key = key
        self.net = net
        self.layout = layout
        self.protocol = type(net.protocol).__name__
        self.sim_ms = int(sim_ms)
        # restart-stable identity for the durable compile store; engines
        # predating stable_cache_key simply never use the store.  The
        # layout geometry rides inside the digest so the store cannot
        # serve a program compiled for a different mesh shape.
        stable = getattr(net, "stable_cache_key", None)
        geometry = layout.geometry() if layout is not None else None
        self.stable_key = (
            "run/"
            + hashlib.blake2b(
                # the program's outputs are part of its identity: one
                # stored before the census vector is never adopted
                repr((stable(), self.sim_ms, geometry, "census-4")).encode(),
                digest_size=12,
            ).hexdigest()
            if callable(stable)
            else None
        )

        self.census_limits = net.census_limits()
        if _gc_hook not in gc.callbacks:  # once a process
            gc.callbacks.append(_gc_hook)
        self._programs: "OrderedDict[tuple, object]" = OrderedDict()
        self._summaries: "OrderedDict[tuple, dict]" = OrderedDict()
        # XLA compiles release the GIL, so two threads calling with the
        # same input geometry can BOTH observe "not compiled yet" and
        # duplicate a multi-second compile (observed from concurrent
        # serve batches).  Double-checked locking keeps the per-geometry
        # compile a true singleton.
        self._compile_lock = make_lock("runcache.compile")

    def _jit_for(self, states):
        """The jitted run + statistics program for these states.  GSPMD
        cannot partition a Mosaic kernel ("wrap the call in a
        shard_map"), so states sharded along the replica axis only (what
        shard_replicas produces) run the per-device program under
        shard_map: replicas are independent, so it is the same
        computation, R/D rows per device and no collective.  Any other
        placement is left to the partitioner."""
        net, sim_ms = self.net, self.sim_ms
        run = lambda s: net.run_ms_batched(s, sim_ms)
        sharding = _replica_sharding(states)
        if sharding is not None:
            run = jax.shard_map(
                run,
                mesh=sharding.mesh,
                in_specs=sharding.spec,
                out_specs=sharding.spec,
                check_vma=False,
            )

        @jax.jit
        def fn(s):
            out = run(s)
            live = ~out.down
            done = jnp.where(live, out.done_at, 0)
            n_live = jnp.maximum(1, jnp.sum(live.astype(jnp.int32)))
            stats = {
                "done_min": jnp.min(
                    jnp.where(live, out.done_at, jnp.int32(2**31 - 1))
                ),
                "done_max": jnp.max(done),
                "done_avg": jnp.sum(done) / n_live,
                "msg_rcv_avg": jnp.sum(jnp.where(live, out.msg_received, 0))
                / n_live,
                "all_done": jnp.all(jnp.where(live, out.done_at > 0, True)),
            }
            # beside `stats`, not in it: its keys are the callers'
            return out, stats, chunk_census(s, out)

        return fn

    @staticmethod
    def _signature(states) -> tuple:
        sig = []
        for leaf in jax.tree_util.tree_leaves(states):
            sharding = getattr(leaf, "sharding", None)
            try:
                hash(sharding)
            except TypeError:  # unhashable placement — fall back to repr
                sharding = repr(sharding)
            sig.append(
                (tuple(leaf.shape), str(getattr(leaf, "dtype", "?")), sharding)
            )
        return tuple(sig)

    def _store_key(self, states) -> "str | None":
        if self.stable_key is None:
            return None
        from ..runtime.compile_store import (
            geometry_signature,
            mesh_geometry_signature,
        )

        return (
            f"{self.stable_key}"
            f"/mesh-{mesh_geometry_signature(states)}"
            f"/geom-{geometry_signature(states)}"
        )

    def __call__(self, states):
        with _span("lookup", "lookup_seconds_total"):
            if self.layout is not None:
                states = self.layout.place(self.net, states)
            sig = self._signature(states)
            compiled = self._programs.get(sig)
        if compiled is None:
            # the PR-11 race window: between this unlocked miss and the
            # locked recheck another thread can finish the same compile.
            # The interleaving harness parks threads here to force that
            # schedule deterministically (tests/interleave.py)
            yield_point("runcache.lookup-miss")
            with self._compile_lock:
                if _RECHECK_UNDER_LOCK:
                    compiled = self._programs.get(sig)
                if compiled is None:
                    yield_point("runcache.compile")
                    from ..runtime.compile_store import (
                        get_compile_store,
                        mesh_geometry_signature,
                    )

                    store = get_compile_store()
                    skey = (
                        self._store_key(states)
                        if store is not None
                        else None
                    )
                    mesh_sig = (
                        mesh_geometry_signature(states)
                        if skey is not None
                        else None
                    )
                    if skey is not None:
                        with _span("store_get"):
                            compiled = store.get(skey, mesh_geometry=mesh_sig)
                    if compiled is not None:
                        # adopted from the durable store: no lowering
                        # happened, so "compiles" must NOT tick (the
                        # zero-compile warm-start contract) and there is
                        # no fresh cost analysis to book
                        _count("store_hits")
                        self._summaries[sig] = {
                            "replicas": next(
                                (s[0][0] for s in sig if s[0]), None
                            ),
                            "loaded_from_store": True,
                        }
                    else:
                        with _span("lower", "lower_seconds_total") as lower:
                            lowered = self._jit_for(states).lower(states)
                        with _span(
                            "compile", "backend_compile_seconds_total"
                        ) as backend:
                            compiled = lowered.compile()
                        dt = lower.seconds + backend.seconds
                        _count("compiles")
                        self._summaries[sig] = {
                            "replicas": next(
                                (s[0][0] for s in sig if s[0]), None
                            ),
                            **compiled_cost_summary(compiled, dt),
                        }
                        if skey is not None:
                            with _span("store_put"):
                                put = store.put(
                                    skey, compiled, mesh_geometry=mesh_sig
                                )
                            if put:
                                _count("store_puts")
                    self._programs[sig] = compiled
        with _span("enqueue", "execute_seconds_total"):
            out, stats, census = compiled(states)
        _count("calls")
        _harvest(census, self.census_limits)
        return out, stats

    def summaries(self) -> list:
        return list(self._summaries.values())

    def op_scopes(self, sig: tuple) -> dict:
        """The program's half of the join between a device trace and
        the `witt.*` scopes: {instruction name: {"scope", "op_name",
        "source", "fed_by"}} (profiling.xla_cost.hlo_op_scopes) of the
        program compiled for input signature `sig`, parsed from the
        executable's own text ON DEMAND (set-up pays nothing).  A
        program loaded from the persistent compilation cache carries
        the names it was compiled with: scopes added since show only
        after a cold compile (docs/profiling.md)."""
        return hlo_op_scopes(self._programs[sig].as_text())


def clear_run_cache() -> None:
    """Drop every cached compiled run program (the lru_cache.cache_clear
    analog for long campaigns).  The cost counters survive — they are
    Prometheus counters, monotonic by contract."""
    _RUN_CACHE.clear()


def _counters() -> dict:
    """`_COUNTERS` as exported: with `compile_seconds_total`, what
    `.lower(states).compile()` took, as the sum of its two parts."""
    _harvest(wait=True)
    out = dict(_COUNTERS)
    out["compile_seconds_total"] = (
        out["lower_seconds_total"] + out["backend_compile_seconds_total"]
    )
    return {**out, **_GC}


def run_cache_info() -> dict:
    return {"size": len(_RUN_CACHE), "maxsize": _RUN_CACHE_MAX, **_counters()}


def run_cache_metrics() -> dict:
    """The export view (server /metrics + run records): counters plus
    per-entry compiled-program cost/memory summaries."""
    return {
        **_counters(),
        "size": len(_RUN_CACHE),
        "maxsize": _RUN_CACHE_MAX,
        "entries": [
            {
                "protocol": entry.protocol,
                "sim_ms": entry.sim_ms,
                "programs": entry.summaries(),
            }
            for entry in _RUN_CACHE.values()
        ],
    }


def _entry_key(net, sim_ms: int, layout) -> tuple:
    return (
        net.cache_key(),
        int(sim_ms),
        layout.geometry() if layout is not None else None,
    )


def run_cache_op_scopes(net, sim_ms: int, layout=None) -> dict:
    """{input signature: `_CachedRun.op_scopes`} of the programs cached
    for this network, horizon and layout (one per replica count and
    placement it was called with); {} where none is.  Per program and
    never merged: instruction names such as `fusion.2655` recur from
    one program to the next, so a trace is joined with the table of
    the program that ran."""
    with _CACHE_LOCK:
        entry = _RUN_CACHE.get(_entry_key(net, sim_ms, layout))
    if entry is None:
        return {}
    return {sig: entry.op_scopes(sig) for sig in list(entry._programs)}


def _run_and_reduce(net, sim_ms: int, layout=None):
    """One cached entry per (net.cache_key(), sim_ms, layout geometry):
    repeated calls with an equivalent network AND layout hit the cache
    instead of re-tracing the full simulation.  The layout geometry is
    part of the key — the same network on a (2,4) vs (4,2) mesh is two
    distinct programs."""
    with _span("lookup", "lookup_seconds_total"):
        key = _entry_key(net, sim_ms, layout)
        with _CACHE_LOCK:
            fn = _RUN_CACHE.get(key)
            if fn is not None:
                _COUNTERS["hits"] += 1
                _RUN_CACHE.move_to_end(key)
                return fn

            _COUNTERS["misses"] += 1
            fn = _CachedRun(net, sim_ms, key, layout=layout)
            _RUN_CACHE[key] = fn
            while len(_RUN_CACHE) > _RUN_CACHE_MAX:
                _RUN_CACHE.popitem(last=False)
                _COUNTERS["evictions"] += 1
            return fn


def sharded_run_stats(net, states, sim_ms: int, layout=None
                      ) -> Tuple[jax.Array, dict]:
    """Run the batched simulation and reduce done/traffic statistics
    across every device in the same program.  Without a layout the
    states run on whatever sharding they carry (the legacy contract);
    with a mesh2d.MeshLayout the cached program places them onto that
    layout first and is keyed on its geometry.  Returns (final_states,
    stats dict of scalars)."""
    return _run_and_reduce(net, sim_ms, layout=layout)(states)
