"""SL601: engine phase annotations are present.

The cost-attribution layer (profiling/, scripts/scope_profile.py) only
works if every kernel phase is wrapped in its `jax.named_scope` marker,
so jaxprs / HLO metadata / device profiles can attribute ops to phases.
The markers are trace-time metadata only: `BatchedNetwork._scope` has no
other arm, so there is no second program to hold them against.

Presence is checked on the real trace: `net.step` is traced to a jaxpr
and every equation's `source_info.name_stack` is collected, recursing
into sub-jaxprs (scan/while/cond bodies carry the scopes; the outer
control-flow equation's own stack is empty).  What a step must carry:

- `witt.delivery`, always; `witt.protocol_tick` and `witt.beat` only
  when the corresponding protocol hook actually traces equations — a
  trivial `tick_beat` that returns its input adds no ops, so there is
  nothing to attribute and no scope to demand;
- engine.core.REACH_SCOPES: the reach check where a row is sent and
  where it is due;
- engine.core.STORE_SCOPES: the view's and the repack's of every step
  (a protocol that bypasses the store still visits it), and the
  insert's where the step sends through the store — which the trace
  itself shows: the step is traced on a copy of the engine whose
  `apply_emission`, `apply_emissions` and `apply_fanout` note what
  they are handed;
- the protocol's own scopes, which it states beside itself as
  `REQUIRED_SCOPES` (an attribute or a property): the channel's of the
  aggregation protocols (protocols/_agg_batched.py `CHANNEL_SCOPES`),
  Handel's deliver pair and its attack's, Casper's `CHAIN_SCOPES`,
  Dfinity's `ROLE_SCOPES` and the fan-out's, SanFermin's emission
  compaction.  A new protocol's scopes need no edit here.

If this jax version exposes no `name_stack` on source_info, the check
is skipped (API drift guard).
"""

from __future__ import annotations

import copy
import os
from typing import List, Optional, Set

from .contracts import _cpu_jax, _mk, _proto_location
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
        if type(x) in (tuple, list):
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


def _noting_sends(net):
    """A copy of `net` whose entry points into the store note what they
    are handed, and the list they note in: a True for each call that
    carries an emission (`apply_emissions` is handed a step's list,
    empty where the protocol sends another way)."""
    probe, sends = copy.copy(net), []

    def noting(entry):
        def noted(state, handed):
            sends.append(bool(handed))
            return entry(state, handed)

        return noted

    for name in ("apply_emissions", "apply_emission", "apply_fanout"):
        # the class's own, bound to the copy, behind an attribute of the copy
        setattr(probe, name, noting(getattr(probe, name)))
    return probe, sends


def _check_presence(jax, name, net, state, path, line, suppress):
    """Every live engine phase appears as a named scope in step()'s
    jaxpr (nested scopes substring-match, per ENGINE_PHASE_SCOPES)."""
    findings = []
    probe, sends = _noting_sends(net)
    try:
        closed = jax.make_jaxpr(probe.step)(state)
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
    # every step gathers the delivery view (empty, for a protocol that
    # sends another way) and clears it; the insert is what a send runs
    required.extend(
        scope for phase, scope in STORE_SCOPES.items()
        if phase != "insert" or any(sends)
    )
    # the protocol's own scope tables, kept beside the protocol
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

    return _check_presence(jax, entry.name, net, state, path, line, suppress)


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
