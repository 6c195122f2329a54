"""SL601: engine phase annotations — present AND bit-neutral.

The cost-attribution layer (profiling/, scripts/scope_profile.py) only
works if (a) every engine kernel phase is wrapped in its
`jax.named_scope` marker (engine.core.ENGINE_PHASE_SCOPES), so jaxprs /
HLO metadata / device profiles can attribute ops to phases, and (b) the
markers are trace-time metadata ONLY — flipping `annotate` off must not
change a single computed bit, or the profile measures a different
program than production runs.

Presence is checked on the real trace: `net.step` is traced to a jaxpr
and every equation's `source_info.name_stack` is collected, recursing
into sub-jaxprs (scan/while/cond bodies carry the scopes; the outer
control-flow equation's own stack is empty).  A phase scope is required
only when the corresponding protocol hook actually traces equations —
a trivial `tick_beat` that returns its input adds no ops, so there is
nothing to attribute and no scope to demand.

Neutrality mirrors SL406's two-level check: abstract (`eval_shape`
fingerprints of the annotated vs. un-annotated step must match) and
concrete (one full step must be bitwise identical with `annotate`
flipped off).

A protocol that sends through the shared channel send path
(`_send_stacked` of protocols/_agg_batched.py) must also carry every
sub-scope of engine.core.CHANNEL_SCOPES: the per-scope device times of
scripts/scope_profile.py are only as whole as these markers are live
(`compact` where the protocol makes an every-tick send, whose firing
rows it brings to the front: one that states a `firing_peak` limit,
Handel with a fast path and GSF with accelerated calls).
Every other protocol sends through the generic message store and must
carry every sub-scope of engine.core.STORE_SCOPES (a channel protocol
carries the view's and the repack's: its step still visits the store).
A Handel built with an attack (`track_bad`) must carry the sub-scopes of
engine.core.ATTACK_SCOPES that its attack runs, and every Handel the
deliver phase's engine.core.DELIVER_SCOPES (the due candidates' rank,
the candidate merge).  A protocol that keeps a
scope table of its own names the scopes its step must carry in a
`REQUIRED_SCOPES` attribute (Casper's `CHAIN_SCOPES`: the fork choice,
the block build, the committee's vote).  Every protocol carries
engine.core.REACH_SCOPES: the reach check where a row is sent and where
it is due.

If this jax version exposes no `name_stack` on source_info, the
presence half is skipped (API drift guard) — neutrality still runs.
"""

from __future__ import annotations

import copy
import os
from typing import List, Optional, Set

from .contracts import (
    _cpu_jax,
    _diff_fingerprints,
    _fingerprint,
    _leaf_paths,
    _mk,
    _proto_location,
)
from .findings import Finding

# scopes every annotated step must carry; the rest (telemetry, faults,
# jump, post) appear only when the matching feature / hook traces ops
_ALWAYS_REQUIRED = ("witt.delivery",)


def _sub_jaxprs(params: dict):
    """Sub-jaxprs reachable from an equation's params: scan/while/cond
    carry theirs as ClosedJaxpr (`.jaxpr`) or raw Jaxpr (`.eqns`) values,
    sometimes inside tuples (cond branches)."""
    stack = list(params.values())
    while stack:
        x = stack.pop()
        if isinstance(x, (tuple, list)):
            stack.extend(x)
        elif hasattr(x, "eqns"):
            yield x
        elif hasattr(x, "jaxpr") and hasattr(getattr(x, "jaxpr"), "eqns"):
            yield x.jaxpr


def _collect_scopes(jaxpr, out: Set[str]) -> bool:
    """Gather every equation's name-stack string into `out`, recursing
    through control-flow sub-jaxprs.  Returns False when this jax build
    exposes no name_stack at all (presence check must be skipped)."""
    saw_attr = not jaxpr.eqns  # vacuously fine on an empty body
    for eqn in jaxpr.eqns:
        ns = getattr(eqn.source_info, "name_stack", None)
        if ns is not None:
            saw_attr = True
            s = str(ns)
            if s:
                out.add(s)
        for sub in _sub_jaxprs(eqn.params):
            if _collect_scopes(sub, out):
                saw_attr = True
    return saw_attr


def _hook_traces_ops(jax, fn, state) -> bool:
    """Does `fn(state)` trace to at least one equation?  A hook that is
    a pure passthrough (pingpong's tick_beat) contributes no ops, so its
    phase scope cannot appear in the step jaxpr and must not be
    required.  Errors count as 'yes' — the step trace below will anchor
    the real finding."""
    try:
        closed = jax.make_jaxpr(fn)(state)
    except Exception:
        return True
    return bool(closed.jaxpr.eqns)


def _check_presence(jax, name, net, state, path, line, suppress):
    """Every live engine phase appears as a named scope in step()'s
    jaxpr (nested scopes substring-match, per ENGINE_PHASE_SCOPES)."""
    findings = []
    if not getattr(net, "annotate", True):
        f = _mk("SL601", path, line,
                f"[{name}] engine built with annotate=False by its "
                "registry factory — phase attribution is dark for this "
                "protocol; construct with annotate=True (the default)",
                suppress)
        return [f] if f else []
    try:
        closed = jax.make_jaxpr(net.step)(state)
    except Exception as e:
        f = _mk("SL601", path, line,
                f"[{name}] step() failed tracing for the annotation "
                f"scan: {type(e).__name__}: {e}", suppress)
        return [f] if f else []
    scopes: Set[str] = set()
    if not _collect_scopes(closed.jaxpr, scopes):
        return []  # jax without name stacks: nothing to assert against
    required = list(_ALWAYS_REQUIRED)
    if _hook_traces_ops(jax, lambda s: net.protocol.tick(net, s), state):
        required.append("witt.protocol_tick")
    if _hook_traces_ops(jax, lambda s: net.protocol.tick_beat(net, s), state):
        required.append("witt.beat")
    from ..engine.core import REACH_SCOPES, STORE_SCOPES

    # "can this row reach its receiver": every send draws its latency
    # through `latency_arrivals` or a fan-out's grid, every step builds
    # the delivery view and its re-check
    required.extend(REACH_SCOPES.values())
    if hasattr(net.protocol, "_send_stacked"):
        from ..engine.core import CHANNEL_SCOPES

        required.extend(
            scope for name, scope in CHANNEL_SCOPES.items()
            if name != "compact" or net.census_limits()["firing_peak"]
        )
        # the channel replaces the store's insert; every step still
        # gathers the (empty) delivery view and clears it
        required.extend(v for k, v in STORE_SCOPES.items() if k != "insert")
    else:
        required.extend(STORE_SCOPES.values())
    if getattr(net.protocol, "track_bad", False):
        # Handel built with an attack: what the attack adds to a tick
        from ..engine.core import ATTACK_SCOPES

        required.extend(
            scope for name, scope in ATTACK_SCOPES.items()
            if name != "inject" or net.protocol.params.byzantine_suicide
        )
    from ..protocols.handel_batched import BatchedHandel

    if isinstance(net.protocol, BatchedHandel):
        # the deliver phase's rank and candidate merge (ops/select.py)
        from ..engine.core import DELIVER_SCOPES

        required.extend(DELIVER_SCOPES.values())
    # a protocol's own scope table, kept beside the protocol
    required.extend(getattr(net.protocol, "REQUIRED_SCOPES", ()))
    for want in required:
        if not any(want in s for s in scopes):
            f = _mk("SL601", path, line,
                    f"[{name}] engine phase scope '{want}' is missing "
                    f"from step()'s jaxpr (saw: {sorted(scopes)[:6]}); "
                    "the phase body must run under "
                    "BatchedNetwork._scope(...)", suppress)
            if f:
                findings.append(f)
    return findings


def _check_neutrality(jax, name, net, state, path, line, suppress):
    """Annotations must be bit-neutral: the annotate=False twin of the
    same engine must produce identical avals (abstract) and identical
    bits after one concrete step (the SL406 pattern)."""
    import numpy as np

    findings = []
    net_off = copy.copy(net)
    net_off.annotate = False
    try:
        out_on = jax.eval_shape(net.step, state)
        out_off = jax.eval_shape(net_off.step, state)
    except Exception as e:
        f = _mk("SL601", path, line,
                f"[{name}] annotate-off step failed abstract "
                f"evaluation: {type(e).__name__}: {e}", suppress)
        return [f] if f else []
    diffs = _diff_fingerprints(_fingerprint(jax, out_on),
                               _fingerprint(jax, out_off))
    for d in diffs[:4]:
        f = _mk("SL601", path, line,
                f"[{name}] annotations change a leaf aval: {d}", suppress)
        if f:
            findings.append(f)
    if diffs:
        return findings

    s_on = net.step(state)
    s_off = net_off.step(state)
    for (p, a), (_, b) in zip(_leaf_paths(jax, s_on),
                              _leaf_paths(jax, s_off)):
        if not np.array_equal(np.asarray(a), np.asarray(b)):
            f = _mk("SL601", path, line,
                    f"[{name}] annotations are not bit-neutral: leaf "
                    f"{p} differs bitwise between annotate=True and "
                    "annotate=False after one step (a named_scope body "
                    "must not change computation)", suppress)
            if f:
                findings.append(f)
            break
    return findings


def check_annotations_entry(entry, root: str = ".") -> List[Finding]:
    """SL601 for one registry entry; [] when clean or when the entry
    opts out of contract checks (standalone engines have no phase
    scopes to audit)."""
    jax = _cpu_jax()
    if not entry.contract_checks:
        return []
    net, state = entry.factory()
    path, line = _proto_location(net.protocol)
    try:
        path = os.path.relpath(path, root)
    except ValueError:
        pass
    suppress = set(getattr(net.protocol, "SIMLINT_SUPPRESS", ()) or ())

    findings = _check_presence(jax, entry.name, net, state, path, line,
                               suppress)
    findings += _check_neutrality(jax, entry.name, net, state, path, line,
                                  suppress)
    return findings


def check_annotations(root: str = ".",
                      names: Optional[List[str]] = None) -> List[Finding]:
    """SL601 over every registered batched protocol (or the subset)."""
    from ..core.registries import registry_batched_protocols

    findings: List[Finding] = []
    for entry in registry_batched_protocols.entries():
        if names and entry.name not in names:
            continue
        findings.extend(check_annotations_entry(entry, root=root))
    return findings
