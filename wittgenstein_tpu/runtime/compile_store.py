"""Durable compiled-executable store: zero-compile warm starts.

The run cache (parallel.replica_shard) and the Supervisor chunk fn make
compiles a per-process cost: every restart re-pays multi-second XLA
compiles for programs whose static inputs have not changed.  The
checkpoint manager already made the *state* restart-proof; this module
does the same for the *programs*.  A compiled executable is
AOT-serialized (jax.experimental.serialize_executable — the
`lower().compile()` object round-trips bitwise, proven by the warm-start
smoke) and written under a content-addressed entry:

    <store>/<blake2b(program key)>.bin        pickled (bytes, in_tree,
                                              out_tree) serialize payload
    <store>/<blake2b(program key)>.json       manifest

The manifest mirrors engine/checkpoint.py's discipline: a format stamp,
every key component spelled out (so staleness is *diagnosable*, not just
a cache miss), a payload checksum, and atomic pid-tmp + os.replace
writes so a torn entry can never be observed.  ``get`` validates
backend, jaxlib/jax versions, ENGINE_LAYOUT and the payload checksum
before deserializing; ANY mismatch or decode failure falls back to a
fresh compile — a corrupt store can cost time, never correctness.

Keying: the caller supplies a *stable* program key (restart-stable, the
`stable_run_key` family of digests — NEVER `net.cache_key()`, whose
``id(protocol)`` components die with the process) plus the input
geometry signature.  The entry filename hashes only the program key +
geometry; the environment components (backend, versions, layout) live in
the manifest, so an entry written by an older jaxlib is *detected* as
stale (counted, logged) rather than silently shadowed by a new key.

The store is deliberately NOT the JAX persistent compilation cache: that
cache still pays lowering + cache lookup inside ``lower().compile()``,
so the run cache's "compiles" counter ticks and the cost-attribution
path books a compile.  A store hit bypasses lowering entirely — the
counter-asserted contract is *zero* fresh compiles on a warm restart.
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import threading
from typing import Any, Callable, Dict, Optional

from .locks import make_lock, yield_point

STORE_FORMAT = "witt-compile-store/v1"

#: monotonic per-process counters (Prometheus discipline: survive
#: clear/close, never step backwards)
_COUNTERS = {
    "hits": 0,
    "misses": 0,
    "stores": 0,
    "stale": 0,
    "corrupt": 0,
    "errors": 0,
}
_COUNTER_LOCK = threading.Lock()


def _count(key: str) -> None:
    with _COUNTER_LOCK:
        _COUNTERS[key] += 1


def compile_store_counters() -> dict:
    with _COUNTER_LOCK:
        return dict(_COUNTERS)


def _environment() -> Dict[str, str]:
    """The compile-validity environment: everything that can change the
    meaning of a serialized executable without changing the program key.
    ENGINE_LAYOUT rides along so an engine-generation bump (which changes
    every state layout) bulk-invalidates the store exactly like it
    invalidates checkpoints."""
    import jax
    import jaxlib

    from ..engine.checkpoint import ENGINE_LAYOUT

    return {
        "backend": jax.default_backend(),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "engine_layout": ENGINE_LAYOUT,
        "device_count": str(jax.device_count()),
    }


class CompileStore:
    """One directory of durable executables.  Thread-safe; every public
    method is best-effort — storage failures count and return, they
    never raise into a dispatch path."""

    def __init__(self, directory: str):
        self.directory = str(directory)
        os.makedirs(self.directory, exist_ok=True)
        self._lock = make_lock("store.entry")

    # -- keying ---------------------------------------------------------

    @staticmethod
    def entry_name(stable_key: str) -> str:
        return hashlib.blake2b(
            stable_key.encode(), digest_size=16
        ).hexdigest()

    def _paths(self, stable_key: str):
        name = self.entry_name(stable_key)
        return (
            os.path.join(self.directory, name + ".json"),
            os.path.join(self.directory, name + ".bin"),
        )

    # -- write ----------------------------------------------------------

    def put(self, stable_key: str, compiled: Any,
            mesh_geometry: Optional[str] = None) -> bool:
        """Serialize one compiled executable under ``stable_key``.
        ``mesh_geometry`` (mesh_geometry_signature of the program's
        inputs) is recorded in the manifest so a stale-by-mesh entry is
        diagnosable, not just a miss.  Returns False (counted as an
        error) when the executable refuses to serialize or the
        filesystem refuses the write."""
        yield_point("store.put")
        from jax.experimental import serialize_executable

        try:
            payload = pickle.dumps(serialize_executable.serialize(compiled))
            device_ids = [
                d.id for d in compiled.runtime_executable().local_devices()
            ]
        except Exception:  # noqa: BLE001 — unserializable program
            _count("errors")
            return False
        manifest = {
            "format": STORE_FORMAT,
            "stable_key": stable_key,
            "payload_sha256": hashlib.sha256(payload).hexdigest(),
            "payload_bytes": len(payload),
            # the devices the program was compiled for: load() must be
            # handed exactly these, or it spreads over every local device
            "device_ids": device_ids,
            **_environment(),
        }
        if mesh_geometry is not None:
            manifest["mesh_geometry"] = mesh_geometry
        man_path, bin_path = self._paths(stable_key)
        pid = os.getpid()
        try:
            with self._lock:
                # payload first, manifest last: the manifest is the
                # commit point (get() reads it first), so a crash
                # between the two replaces leaves no visible entry
                for path, data in (
                    (bin_path, payload),
                    (man_path, json.dumps(manifest, sort_keys=True).encode()),
                ):
                    tmp = f"{path}.tmp.{pid}"
                    try:
                        with open(tmp, "wb") as f:
                            f.write(data)
                        os.replace(tmp, path)
                    finally:
                        if os.path.exists(tmp):
                            os.remove(tmp)
        except OSError:
            _count("errors")
            return False
        _count("stores")
        return True

    # -- read -----------------------------------------------------------

    def get(self, stable_key: str,
            mesh_geometry: Optional[str] = None) -> Optional[Any]:
        """Load the executable stored under ``stable_key``, or None.
        None means "compile fresh": missing entry (miss), environment
        mismatch (stale) or undecodable entry (corrupt) all degrade the
        same way and are counted separately.  When ``mesh_geometry`` is
        given, an entry recorded under a different mesh shape — same
        device COUNT, different (axis, size) factorization, e.g. (2,4)
        vs (4,2) of 8 devices — is stale, never served."""
        yield_point("store.get")
        man_path, bin_path = self._paths(stable_key)
        try:
            with open(man_path, "rb") as f:
                manifest = json.loads(f.read())
        except FileNotFoundError:
            _count("misses")
            return None
        except (OSError, ValueError):
            _count("corrupt")
            return None
        if not isinstance(manifest, dict):
            _count("corrupt")
            return None
        if manifest.get("format") != STORE_FORMAT or manifest.get(
            "stable_key"
        ) != stable_key:
            _count("stale")
            return None
        env = _environment()
        if any(manifest.get(k) != v for k, v in env.items()):
            _count("stale")
            return None
        # symmetric: an entry recorded under a mesh shape is stale for a
        # caller that declares none, and vice versa — "I don't know the
        # mesh" must never adopt a partitioned executable
        if manifest.get("mesh_geometry") != mesh_geometry:
            _count("stale")
            return None
        try:
            with open(bin_path, "rb") as f:
                payload = f.read()
        except OSError:
            _count("corrupt")
            return None
        if (
            len(payload) != manifest.get("payload_bytes")
            or hashlib.sha256(payload).hexdigest()
            != manifest.get("payload_sha256")
        ):
            _count("corrupt")
            return None
        try:
            import jax
            from jax.experimental import serialize_executable

            by_id = {d.id: d for d in jax.local_devices()}
            loaded = serialize_executable.deserialize_and_load(
                *pickle.loads(payload),
                execution_devices=[by_id[i] for i in manifest["device_ids"]],
            )
        except Exception:  # noqa: BLE001 — any decode failure degrades
            _count("corrupt")
            return None
        _count("hits")
        return loaded

    # -- exposition ------------------------------------------------------

    def entries(self) -> list:
        """Manifest snapshots of every committed entry (diagnostics)."""
        out = []
        try:
            names = sorted(os.listdir(self.directory))
        except OSError:
            return out
        for name in names:
            if not name.endswith(".json"):
                continue
            try:
                with open(os.path.join(self.directory, name), "rb") as f:
                    out.append(json.loads(f.read()))
            except (OSError, ValueError):
                continue
        return out

    def stats(self) -> dict:
        return {
            "directory": self.directory,
            "entries": sum(
                1
                for n in (
                    os.listdir(self.directory)
                    if os.path.isdir(self.directory)
                    else ()
                )
                if n.endswith(".json")
            ),
            **compile_store_counters(),
        }


# ---------------------------------------------------------------------------
# process default

ENV_STORE = "WITT_COMPILE_STORE"

_DEFAULT: Optional[CompileStore] = None
_DEFAULT_RESOLVED = False
_DEFAULT_LOCK = threading.Lock()


def set_compile_store(store: "CompileStore | str | None") -> Optional[CompileStore]:
    """Install (or clear, with None) the process-wide store used by the
    run cache and durable chunk fns.  A string is a directory."""
    global _DEFAULT, _DEFAULT_RESOLVED
    with _DEFAULT_LOCK:
        _DEFAULT = CompileStore(store) if isinstance(store, str) else store
        _DEFAULT_RESOLVED = True
        return _DEFAULT


def get_compile_store() -> Optional[CompileStore]:
    """The process-wide store: whatever set_compile_store installed,
    else $WITT_COMPILE_STORE (resolved once), else None (store off)."""
    global _DEFAULT, _DEFAULT_RESOLVED
    with _DEFAULT_LOCK:
        if not _DEFAULT_RESOLVED:
            path = os.environ.get(ENV_STORE)
            if path:
                try:
                    _DEFAULT = CompileStore(path)
                except OSError:
                    _DEFAULT = None
            _DEFAULT_RESOLVED = True
        return _DEFAULT


# ---------------------------------------------------------------------------
# durable jit: the Supervisor chunk-fn integration


def geometry_signature(args: Any) -> str:
    """Restart-stable digest of an input pytree's geometry: leaf paths,
    shapes, dtypes and placements.  str(sharding) is deterministic for a
    given device topology (the device ids XLA mints under a fixed
    --xla_force_host_platform_device_count are stable), and topology
    itself is part of the store environment (device_count)."""
    import jax

    parts = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(args)[0]:
        sharding = getattr(leaf, "sharding", None)
        parts.append(
            f"{path}:{getattr(leaf, 'shape', ())}"
            f":{getattr(leaf, 'dtype', type(leaf).__name__)}"
            f":{sharding}"
        )
    return hashlib.blake2b(
        "|".join(parts).encode(), digest_size=12
    ).hexdigest()


def mesh_geometry_signature(args: Any) -> str:
    """Canonical tag of the mesh SHAPES an input pytree is committed to:
    every distinct (axis_names × axis_sizes) among the leaves' mesh-
    backed shardings, sorted, or ``"unmeshed"`` when no leaf carries
    one.  This is the key component _environment()'s ``device_count``
    cannot express: a (2,4) and a (4,2) mesh of the same 8 devices have
    equal device counts but partition a program differently, so their
    executables must never share a store entry."""
    import jax

    shapes = set()
    for leaf in jax.tree_util.tree_leaves(args):
        sharding = getattr(leaf, "sharding", None)
        mesh = getattr(sharding, "mesh", None)
        if mesh is None:
            continue
        try:
            shapes.add(
                ",".join(
                    f"{name}={int(mesh.shape[name])}"
                    for name in mesh.axis_names
                )
            )
        except (AttributeError, TypeError, KeyError):
            continue
    if not shapes:
        return "unmeshed"
    return ";".join(sorted(shapes))


class DurableJit:
    """jit semantics with store-backed compiles: per input geometry,
    try the compile store, else ``lower().compile()`` and publish.  The
    Supervisor's chunk fn uses this so a restarted server resumes a
    checkpointed batch without re-paying the chunk program's compile.

    ``compiles`` counts FRESH XLA compiles only (store hits don't tick
    it) — the warm-start smoke asserts on exactly this.
    """

    def __init__(self, fn: Callable, stable_key: str,
                 store: "CompileStore | None" = None):
        import jax

        self._jit = fn if hasattr(fn, "lower") else jax.jit(fn)
        self.stable_key = stable_key
        self._store = store
        self._programs: Dict[str, Any] = {}
        self._lock = threading.Lock()
        self.compiles = 0

    def _resolve_store(self) -> Optional[CompileStore]:
        return self._store if self._store is not None else get_compile_store()

    def __call__(self, *args):
        sig = geometry_signature(args)
        compiled = self._programs.get(sig)
        if compiled is None:
            with self._lock:
                compiled = self._programs.get(sig)
                if compiled is None:
                    store = self._resolve_store()
                    mesh_sig = mesh_geometry_signature(args)
                    key = (
                        f"{self.stable_key}/mesh-{mesh_sig}/geom-{sig}"
                    )
                    if store is not None:
                        compiled = store.get(key, mesh_geometry=mesh_sig)
                    if compiled is None:
                        compiled = self._jit.lower(*args).compile()
                        self.compiles += 1
                        if store is not None:
                            store.put(key, compiled,
                                      mesh_geometry=mesh_sig)
                    self._programs[sig] = compiled
        return compiled(*args)


def durable_jit(fn: Callable, stable_key: str,
                store: "CompileStore | None" = None) -> DurableJit:
    """Wrap ``fn`` (or an existing jit) in store-backed AOT dispatch."""
    return DurableJit(fn, stable_key, store)
