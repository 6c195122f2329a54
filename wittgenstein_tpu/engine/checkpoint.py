"""Checkpoint / resume for batched simulation states.

The reference has no checkpointing at all — Protocol.copy() + reseed
gives re-runs, not resume (Protocol.java:13-17; Envelope.java:55 only
muses about on-disk serialization).  Here the whole simulation state is
a pytree of arrays, so checkpointing is a flatten + np.savez: save at
any tick, load, continue — bit-identical to an uninterrupted run (the
engine is deterministic in (state, tick count)).

Format v2 adds durability on top of the bare flatten:

- an embedded JSON **manifest** (``__manifest__``) recording the engine
  layout generation, the side-car signature (telemetry / fault state
  attached or not), per-leaf crc32/shape/dtype, and caller metadata;
- **integrity checksums** — a flipped bit surfaces as
  ``CheckpointCorruptError`` with the offending leaf named, never as a
  numpy shape trace three frames deep;
- **atomic writes** (pid-suffixed temp + ``os.replace``) so a killed
  writer can never leave a torn checkpoint under the final name;
- ``CheckpointManager``: numbered checkpoints, an atomic LATEST
  pointer, bounded retention, and a restore that walks back past
  corrupt files to the newest loadable state.

Works for any pytree whose leaves are arrays/scalars and whose structure
is reproducible from a template state (SimState with nested proto dicts,
EthPowState, stacked/replicated variants).

Layout-stamp compatibility rules (also in docs/durability.md):

- ``ENGINE_LAYOUT`` names the current message-store generation and is
  stamped into every checkpoint.
- A checkpoint stamped with an unknown layout never loads.
- ``timewheel-v1`` (pre-side-car) checkpoints load **only** into a
  template with no telemetry/fault side-cars attached; against an
  instrumented template they fail with ``CheckpointLayoutError`` naming
  the reason, because the side-car counters they lack are part of the
  bit-identity contract.
- A v2 checkpoint whose side-car signature differs from the template's
  (e.g. saved with telemetry ON, loaded with telemetry OFF) fails the
  same way before any leaf is touched.
- ``timewheel-v2`` (pre-narrow-dtype) checkpoints store int32 where the
  v3 layout packs int16/int8 (engine.density); leaves whose shape
  matches cast on load under a range check, with the stored INT32_MAX
  sentinel remapped to the narrow dtype's max.  Handel-family v2
  checkpoints fail on SHAPE instead (``CheckpointShapeError``): the same
  generation regrouped their channel buckets to exact widths, so their
  in_sig leaves genuinely cannot resume — re-run those.
"""

from __future__ import annotations


import json
import os
import time
import zipfile
import zlib
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np

from .core import Census


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "name"):
            parts.append(str(p.name))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        elif hasattr(p, "key"):
            parts.append(str(p.key))
        else:
            parts.append(str(p))
    return "/".join(parts)


# message-store layout generation stamped into every checkpoint: the
# time-wheel rewrite changed SimState's ring fields ([C] flat ring ->
# [W, B] wheel + [V] overflow lane), so a checkpoint from the flat-ring
# era can never resume on this engine — fail with the reason, not with a
# leaf-by-leaf shape mismatch.  v2 = v1 wheel layout + side-car aware
# manifest (telemetry/fault state signatures + per-leaf checksums).
# v3 = v2 + narrow packed dtypes (engine.density): message lanes and
# declared NARROW_LEAVES store int16/int8 where int32 used to live, with
# INT32_MAX sentinels remapped to the narrow dtype's own max.
LAYOUT_KEY = "__engine_layout__"
MANIFEST_KEY = "__manifest__"
ENGINE_LAYOUT = "timewheel-v3"
# older stamps this engine can still load, with restrictions enforced in
# load_state (v1 predates the side-car signature, so it only loads into
# an uninstrumented template; v2 stored int32 where the template may now
# be narrow — leaves whose SHAPE matches cast on load under a range
# check, sentinel-mapped; a v2 Handel checkpoint fails on shape instead,
# because the exact-width channel buckets regrouped its in_sig leaves)
COMPAT_LAYOUTS = ("timewheel-v1", "timewheel-v2")
MANIFEST_FORMAT = 2

# SimState leaves that a checkpoint may legitimately omit.  simlint SL501
# asserts save/restore completeness against this set — a new SimState
# field must either checkpoint bitwise or be declared here with a reason.
# The work census (engine.core.Census, PR 41) is saved like any leaf, but
# the dynamics read none of it: a checkpoint written before it existed
# resumes with the template's counts and every other leaf bit for bit.
EPHEMERAL_LEAVES: frozenset = frozenset(f"census/{name}" for name in Census._fields)


class CheckpointError(Exception):
    """Base for every structured checkpoint failure."""


class CheckpointLayoutError(CheckpointError, ValueError):
    """Engine-layout or side-car signature mismatch: the checkpoint was
    written by an incompatible engine generation/configuration."""


class CheckpointCorruptError(CheckpointError, ValueError):
    """The checkpoint file is truncated, unreadable, or fails its
    integrity checksum."""


class CheckpointMissingLeafError(CheckpointError, KeyError):
    """The checkpoint lacks a leaf the template requires."""


class CheckpointShapeError(CheckpointError, ValueError):
    """A stored leaf's shape/dtype disagrees with the template."""


def _sidecar_name(leaf: Any) -> Optional[str]:
    """Side-car signature entry: the attached state's type name, or None
    when the side-car is disabled (an empty-tuple leaf)."""
    if isinstance(leaf, tuple) and len(leaf) == 0:
        return None
    return type(leaf).__name__


def _sidecar_signature(state: Any) -> Dict[str, Optional[str]]:
    sig: Dict[str, Optional[str]] = {}
    for name in ("tele", "faults"):
        if hasattr(state, name):
            sig[name] = _sidecar_name(getattr(state, name))
    return sig


def manifest_trace(manifest: Optional[dict]) -> dict:
    """The correlation ids of a manifest: its explicit ``trace`` block
    when present, else the run_id/job_id/tenant_id keys of its meta
    (how the supervisor stamps them).  Empty dict when untraced."""
    if not manifest:
        return {}
    block = manifest.get("trace")
    if block:
        return dict(block)
    meta = manifest.get("meta") or {}
    return {
        k: meta[k]
        for k in ("run_id", "job_id", "tenant_id")
        if meta.get(k) is not None
    }


def save_state(state: Any, dest: str, meta: Optional[dict] = None) -> dict:
    """Write a state pytree to `dest` (.npz), keyed by tree path.

    Embeds a manifest (layout stamp, side-car signature, per-leaf
    crc32/shape/dtype, caller `meta`) and writes atomically: a crashed
    writer leaves at most a stray temp file, never a torn `dest`.
    Returns the manifest dict.
    """
    leaves = jax.tree_util.tree_flatten_with_path(state)[0]
    arrays = {LAYOUT_KEY: np.asarray(ENGINE_LAYOUT)}
    leaf_info: Dict[str, dict] = {}
    for path, leaf in leaves:
        key = _path_str(path)
        arr = np.asarray(leaf)
        arrays[key] = arr
        leaf_info[key] = {
            "crc32": zlib.crc32(arr.tobytes()) & 0xFFFFFFFF,
            "shape": list(arr.shape),
            "dtype": str(arr.dtype),
        }
    manifest = {
        "format": MANIFEST_FORMAT,
        "layout": ENGINE_LAYOUT,
        "sidecars": _sidecar_signature(state),
        "leaves": leaf_info,
        "meta": dict(meta or {}),
        "created_unix": time.time(),
    }
    # first-class trace block: the obs correlation ids (run_id / job_id
    # / tenant_id) the supervisor stamps into meta, surfaced so ledger
    # tooling (scripts/obs_query.py) can join checkpoints to flight
    # recorder events without knowing the meta layout.  Absent when the
    # writer carried no trace context (format stays 2 — additive key).
    trace = manifest_trace(manifest)
    if trace:
        manifest["trace"] = trace
    arrays[MANIFEST_KEY] = np.asarray(json.dumps(manifest))
    # stream straight to a temp file (savez appends .npz when missing),
    # then atomically replace — never a torn checkpoint, no in-RAM copy;
    # pid suffix keeps concurrent writers off each other's temp file
    tmp = f"{dest}.tmp.{os.getpid()}.npz"
    try:
        np.savez_compressed(tmp, **arrays)
        os.replace(tmp, dest)
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)
    return manifest


def _open_npz(src: str):
    try:
        return np.load(src, allow_pickle=False)
    except FileNotFoundError:
        raise
    except (zipfile.BadZipFile, ValueError, OSError, EOFError) as e:
        raise CheckpointCorruptError(
            f"checkpoint {src} is unreadable (truncated or not an npz): {e}"
        ) from e


def read_manifest(src: str) -> Optional[dict]:
    """Return the embedded manifest dict, or None for a pre-manifest
    (v1) checkpoint.  Raises CheckpointCorruptError on unreadable files."""
    with _open_npz(src) as data:
        if MANIFEST_KEY not in data:
            return None
        try:
            return json.loads(str(data[MANIFEST_KEY]))
        except (json.JSONDecodeError, zlib.error, zipfile.BadZipFile) as e:
            raise CheckpointCorruptError(
                f"checkpoint {src} has a corrupt manifest: {e}"
            ) from e


def _check_layout(src: str, found: str, template: Any) -> None:
    if found == ENGINE_LAYOUT:
        return
    if found in COMPAT_LAYOUTS:
        # v1 predates the side-car signature: it can only resume an
        # uninstrumented run — telemetry/fault counters it never stored
        # are part of the bit-identity contract when armed
        sig = _sidecar_signature(template)
        armed = [k for k, v in sig.items() if v is not None]
        if armed:
            raise CheckpointLayoutError(
                f"checkpoint {src} was written by pre-side-car engine "
                f"layout {found!r}, but the template has "
                f"{'/'.join(armed)} side-car state attached; it cannot "
                "resume an instrumented run — re-run instead of resuming"
            )
        return
    raise CheckpointLayoutError(
        f"checkpoint {src} was written by engine layout {found!r}; this "
        f"engine is {ENGINE_LAYOUT!r} (compat: {COMPAT_LAYOUTS}) — "
        "re-run the simulation instead of resuming"
    )


def _coerce_dtype(src: str, key: str, arr, want_dtype):
    """The v2->v3 restore shim: cast a compat-era int32 leaf onto the
    template's narrow dtype (engine.density pattern).

    Valid only for integer->narrower-integer casts where every stored
    value is exactly representable: the source dtype's own max (the
    INT32_MAX "never"/empty sentinel) maps to the narrow dtype's max —
    the value the narrow layout reserves for the same role — and every
    other value must already fit the narrow range.  Anything else is a
    real layout mismatch and keeps the hard CheckpointShapeError."""
    a, w = arr.dtype, np.dtype(want_dtype)
    if not (
        np.issubdtype(a, np.integer)
        and np.issubdtype(w, np.integer)
        and np.iinfo(a).max > np.iinfo(w).max
    ):
        raise CheckpointShapeError(
            f"leaf {key!r}: checkpoint {src} stores dtype {a}, template "
            f"wants {w} — not a compat-era widening to cast down"
        )
    src_max = np.iinfo(a).max
    dst = np.iinfo(w)
    is_sent = arr == src_max
    rest = arr[~is_sent]
    if rest.size and (
        int(rest.min()) < dst.min or int(rest.max()) > dst.max
    ):
        raise CheckpointShapeError(
            f"leaf {key!r}: checkpoint {src} holds values in "
            f"[{int(rest.min())}, {int(rest.max())}] that do not fit the "
            f"template's {w} — the narrow layout cannot represent this "
            "state; re-run instead of resuming"
        )
    out = arr.astype(w)
    out[is_sent] = dst.max
    return out


def load_state(template: Any, src: str, verify: bool = True) -> Any:
    """Rebuild a state pytree with `template`'s structure from `src`.

    Shapes must match the template's leaves; dtypes must match too,
    except when a COMPAT-era checkpoint stores a wider integer than the
    template's narrow leaf (the timewheel-v3 dtype shrink) — those cast
    on load under a range check with sentinel remapping
    (``_coerce_dtype``).  With `verify` (default) every leaf is also
    checked against its manifest crc32 — computed on the STORED bytes,
    before any cast — so silent bit-rot surfaces as
    CheckpointCorruptError naming the leaf.

    Mesh portability: checkpoints store plain host bytes (np.asarray
    gathers every shard), so the file itself carries no mesh — a
    checkpoint written under a 1D replica mesh restores bitwise into a
    2D (replicas, nodes) mesh and back.  Resharding happens HERE, on
    load: when a template leaf is committed to a NamedSharding, the
    restored leaf is device_put onto that same sharding; an unsharded
    template restores exactly as before.  Geometry conflicts stay loud:
    shape/dtype mismatches raise CheckpointShapeError regardless of
    either side's mesh.
    """

    def _restore(arr, tmpl):
        sharding = getattr(tmpl, "sharding", None)
        if isinstance(sharding, jax.sharding.NamedSharding):
            return jax.device_put(jax.numpy.asarray(arr), sharding)
        return jax.numpy.asarray(arr)

    with _open_npz(src) as data:
        found_layout = str(data[LAYOUT_KEY]) if LAYOUT_KEY in data else None
        if found_layout is not None:
            _check_layout(src, found_layout, template)
        compat = found_layout in COMPAT_LAYOUTS
        manifest = None
        if MANIFEST_KEY in data:
            try:
                manifest = json.loads(str(data[MANIFEST_KEY]))
            except (json.JSONDecodeError, zlib.error, zipfile.BadZipFile) as e:
                raise CheckpointCorruptError(
                    f"checkpoint {src} has a corrupt manifest: {e}"
                ) from e
            want_sig = _sidecar_signature(template)
            have_sig = manifest.get("sidecars", {})
            for name, want in want_sig.items():
                have = have_sig.get(name)
                if have != want:
                    raise CheckpointLayoutError(
                        f"checkpoint {src} side-car mismatch on {name!r}: "
                        f"saved with {have!r}, template expects {want!r} — "
                        "arm the run the same way it was saved"
                    )
        leaves_t, _ = jax.tree_util.tree_flatten_with_path(template)
        leaves = []
        for path, leaf in leaves_t:
            key = _path_str(path)
            if key not in data:
                if key in EPHEMERAL_LEAVES:
                    leaves.append(_restore(np.asarray(leaf), leaf))
                    continue
                raise CheckpointMissingLeafError(
                    f"checkpoint {src} is missing leaf {key!r}"
                )
            try:
                arr = data[key]
            except (zipfile.BadZipFile, zlib.error, ValueError, EOFError) as e:
                raise CheckpointCorruptError(
                    f"checkpoint {src} leaf {key!r} is unreadable "
                    f"(truncated archive?): {e}"
                ) from e
            want = np.asarray(leaf)
            if arr.shape != want.shape or (
                arr.dtype != want.dtype and not compat
            ):
                raise CheckpointShapeError(
                    f"leaf {key!r}: checkpoint has {arr.shape}/{arr.dtype}, "
                    f"template wants {want.shape}/{want.dtype}"
                )
            if verify and manifest is not None:
                info = manifest.get("leaves", {}).get(key)
                if info is not None:
                    crc = zlib.crc32(arr.tobytes())
                    if (crc & 0xFFFFFFFF) != info.get("crc32"):
                        raise CheckpointCorruptError(
                            f"checkpoint {src} leaf {key!r} failed its "
                            f"integrity checksum (stored crc32 "
                            f"{info.get('crc32')}, recomputed {crc}) — "
                            "the file is corrupt; falling back to an "
                            "older checkpoint is safe, this one is not"
                        )
            if arr.dtype != want.dtype:
                arr = _coerce_dtype(src, key, arr, want.dtype)
            leaves.append(_restore(arr, leaf))
        return jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(template), leaves
        )


# ---------------------------------------------------------------------------
# CheckpointManager: numbered checkpoints + LATEST pointer + retention


LATEST_NAME = "LATEST"
_CKPT_FMT = "ckpt_{step:08d}.npz"


class CheckpointManager:
    """Numbered checkpoints in one directory with bounded retention.

    - ``save(state, step, meta)`` writes ``ckpt_{step:08d}.npz``
      atomically, then atomically updates the ``LATEST`` pointer file,
      then prunes to the ``keep`` newest files.  A crash between any two
      of those steps leaves a fully consistent directory.
    - ``restore_latest(template)`` walks newest -> oldest, skipping
      checkpoints that fail to load (corrupt / truncated / wrong
      side-car signature), and returns ``(state, step, manifest)`` for
      the newest loadable one, or ``None`` when nothing usable exists.
    """

    def __init__(self, directory: str, keep: int = 3):
        if keep < 1:
            raise ValueError(f"keep must be >= 1, got {keep}")
        self.directory = directory
        self.keep = keep
        os.makedirs(directory, exist_ok=True)

    def path_for(self, step: int) -> str:
        return os.path.join(self.directory, _CKPT_FMT.format(step=step))

    def steps(self) -> list:
        out = []
        for name in os.listdir(self.directory):
            if name.startswith("ckpt_") and name.endswith(".npz"):
                try:
                    out.append(int(name[len("ckpt_"):-len(".npz")]))
                except ValueError:
                    continue
        return sorted(out)

    def latest_step(self) -> Optional[int]:
        """Step named by the LATEST pointer, falling back to the newest
        file on disk when the pointer is missing/stale."""
        ptr = os.path.join(self.directory, LATEST_NAME)
        try:
            with open(ptr) as f:
                name = f.read().strip()
            step = int(name[len("ckpt_"):-len(".npz")])
            if os.path.exists(self.path_for(step)):
                return step
        except (OSError, ValueError):
            pass
        steps = self.steps()
        return steps[-1] if steps else None

    def save(self, state: Any, step: int, meta: Optional[dict] = None) -> dict:
        manifest = save_state(state, self.path_for(step), meta=meta)
        ptr = os.path.join(self.directory, LATEST_NAME)
        tmp = f"{ptr}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            f.write(_CKPT_FMT.format(step=step))
        os.replace(tmp, ptr)
        self._prune()
        return manifest

    def _prune(self) -> None:
        steps = self.steps()
        for step in steps[: max(0, len(steps) - self.keep)]:
            try:
                os.remove(self.path_for(step))
            except OSError:
                pass

    def restore_latest(
        self, template: Any
    ) -> Optional[Tuple[Any, int, Optional[dict]]]:
        errors = []
        for step in reversed(self.steps()):
            path = self.path_for(step)
            try:
                state = load_state(template, path)
                return state, step, read_manifest(path)
            except FileNotFoundError:
                continue
            except CheckpointError as e:
                errors.append((path, e))
                continue
        return None
