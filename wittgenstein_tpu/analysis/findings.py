"""Finding model, rule catalog, and suppression comments.

Every check in the package reports through `Finding`: a rule id from the
catalog below, a file:line anchor, and a message.  AST findings anchor at
the offending node; abstract-eval findings anchor at the protocol class's
definition line so the report is always clickable.

Suppression is per-line (`# simlint: disable=SL104` on the flagged line,
comma-separated for several rules) or per-file
(`# simlint: disable-file=SL104` anywhere in the file).  Dynamic checks
(SL4xx) accept class-level suppression via the protocol's
`SIMLINT_SUPPRESS` contract metadata (engine/protocol.py).
"""

from __future__ import annotations

import dataclasses
import enum
import json
import re
from typing import Dict, List, Optional


class Severity(enum.Enum):
    ERROR = "error"
    WARNING = "warning"


# rule id -> one-line description (the catalog; docs/static_analysis.md is
# the prose version and tests assert the two stay in sync)
RULES: Dict[str, str] = {
    # -- AST: tracer safety / host purity / dtype drift ----------------------
    "SL101": "tracer-unsafe branch: Python `if`/`while`/`bool()` on a "
    "traced value inside kernel code",
    "SL102": "host impurity in a jit path: time.*/random.*/np.random/print "
    "inside kernel code",
    "SL103": "host conversion of a traced value: float()/int()/.item()/"
    "np.asarray(state...) inside kernel code",
    "SL104": "dtype-drift hazard: dtype-less jnp constructor "
    "(zeros/ones/arange) or weak-typed numeric literal in kernel code",
    # -- AST: protocol contract ---------------------------------------------
    "SL201": "deliver() writes an engine-owned msg_*/ovf_*/wheel column "
    "(the engine owns the message store)",
    "SL202": "tick_beat override without a BEAT_PERIOD/BEAT_SEND_CALLS "
    "declaration in the module (beat gating would corrupt the RNG stream)",
    "SL203": "self.mtype(name) with a name missing from the class's "
    "MSG_TYPES literal",
    "SL204": "payload contract mismatch: Emission(payload=...) with "
    "PAYLOAD_WIDTH 0, or msg_payload indexed past PAYLOAD_WIDTH",
    # -- registry / test coverage meta-rule ----------------------------------
    "SL301": "batched protocol not registered in core/registries.py or "
    "missing a tests/test_* parity file",
    # -- abstract-eval contract checks ---------------------------------------
    "SL401": "kernel hook does not preserve the SimState tree structure, "
    "shapes, or dtypes (weak-type promotion counts)",
    "SL402": "deliver() output msg store is not a passthrough of its input "
    "(jaxpr-level ownership check)",
    "SL403": "telemetry side-car perturbs non-tele state (instrumented run "
    "would not be bit-identical)",
    "SL404": "recompilation sentry: a second trace would miss the jit "
    "cache (output avals drift or trace is not reproducible)",
    "SL405": "RNG-stream audit: tick_beat's latency_arrivals draw count "
    "does not match the declared BEAT_SEND_CALLS",
    "SL406": "fault side-car is not neutral when idle: a fault-enabled "
    "engine on the neutral schedule perturbs non-fault state",
    "SL407": "deliver() writes the fault lane: state.faults leaves must "
    "be pure passthroughs on a fault-enabled delivery view",
    # -- checkpoint durability -----------------------------------------------
    "SL501": "checkpoint completeness: a state leaf is not persisted by "
    "save_state (and not declared in EPHEMERAL_LEAVES), an "
    "EPHEMERAL_LEAVES declaration is stale, or save/load does not "
    "roundtrip bitwise",
    # -- phase annotations ----------------------------------------------------
    "SL601": "engine phase annotations: a live kernel phase, or a scope the "
    "protocol states in REQUIRED_SCOPES, is missing its named-scope marker "
    "in the step jaxpr",
    # -- derived-cache consistency --------------------------------------------
    "SL701": "derived-cache consistency: a DERIVED_CACHE_LEAVES leaf is "
    "stale after concrete steps (carried cache differs bitwise from "
    "recompute_caches()), missing from proto_init, or uncovered by the "
    "recompute oracle",
    # -- serving scheduler contract -------------------------------------------
    "SL801": "serve batching contract: jobs packed into one batch must "
    "share the exact static-config digest and row leaf signature, and "
    "re-dispatching an identical workload must be a pure run-cache hit "
    "(no recompile-per-batch regression)",
    # -- narrow-dtype overflow audit -------------------------------------------
    "SL901": "narrow-dtype overflow audit: an engine message lane or a "
    "declared NARROW_LEAVES leaf (engine.density) cannot hold its bound "
    "— lane plan overridden past (N-1, n_msg_types-1), declared_max "
    "over the dtype's headroom (sentinel slot included), live leaf "
    "dtype diverging from its declaration, or concrete steps producing "
    "values outside [0, declared_max]",
    # -- 2D-mesh replicated-leaf audit -----------------------------------------
    "SL1001": "mesh replicated-leaf audit (parallel.mesh2d): a state "
    "leaf classifies differently single-state vs stacked, a "
    "protocol-owned proto-dict leaf collides with an engine "
    "_MESSAGE_STORE_FIELDS exclusion name (silently replicated along "
    "the node axis, forfeiting its 1/P memory share), or a store-field "
    "exclusion entry matches no live leaf of any registered protocol "
    "(stale exemption)",
    # -- SLO alert catalog audit ------------------------------------------------
    "SL1101": "SLO alert catalog audit (obs.slo): an alert-capable call "
    "site — fire_violation()/alert() first argument, SLOSpec(name=...), "
    "or an slo=... keyword — names a string literal missing from "
    "REGISTERED_SLOS, so a dashboard keyed on the catalog would "
    "silently miss its alerts",
    # -- jump-safety audit --------------------------------------------------------
    "SL1201": "jump-safety audit: a protocol declaring TICK_INTERVAL=None "
    "whose tick_beat jaxpr is not a no-op (or that also declares "
    "BEAT_PERIOD) — the next-arrival jump paths skip empty-occupancy "
    "ticks wholesale, so per-tick beat work would silently vanish",
    # -- concurrency contract checker (pass 10) ---------------------------------
    "SL1301": "undeclared lock: a threading.Lock/RLock/Condition "
    "construction site missing from the runtime/locks.py registry, or a "
    "make_lock/TracedLock name absent from LOCK_HIERARCHY",
    "SL1302": "lock-order inversion: an acquisition chain — direct or "
    "across function boundaries via call-graph inference — takes a lock "
    "at or below the rank of one already held, inverting the declared "
    "LOCK_HIERARCHY total order (the deadlock-order audit)",
    "SL1303": "blocking work under a dispatch-class lock: compile/lower/"
    "block_until_ready, file I/O, HTTP, time.sleep, or a timeout-less "
    "get()/wait()/join() reachable while a no_blocking lock is held "
    "(the PR-11 compile-race dual: compiles stay OUTSIDE _dispatch_lock)",
    "SL1304": "thread lifecycle: a spawned threading.Thread is neither "
    "daemonized nor joined, or its worker loop has no shutdown path "
    "reachable from stop()/drain (the PR-12 watchdog-leak class)",
    "SL1305": "unguarded shared write: a mutable attribute of a "
    "thread-spawning or lock-owning class is written without holding its "
    "class's named lock at every site, or guarded by different locks at "
    "different sites (UNGUARDED_OK declares documented single-writer "
    "fields)",
    "SL1306": "stale lock registry: a runtime/locks.py site declaration "
    "matches no live lock construction in the tree",
    "SL1307": "yield-point catalog drift: a yield_point() call site names "
    "a point missing from YIELD_POINTS, or a catalog entry has no call "
    "site left in the tree",
    "SL1401": "pinned-regression audit: a scenarios/regressions/*.json "
    "attack pin fails to load, names an unregistered protocol or unknown "
    "objective, carries a genome outside its declared bounds, no longer "
    "strictly beats its pinned baselines, or (contracts mode) lowers to "
    "a FaultState whose digest differs from the pinned plan_digest",
}


@dataclasses.dataclass(frozen=True)
class Finding:
    rule: str
    path: str  # repo-relative when produced by the CLI
    line: int
    message: str
    severity: Severity = Severity.ERROR

    def format(self) -> str:
        return (
            f"{self.path}:{self.line}: {self.rule} "
            f"[{self.severity.value}] {self.message}"
        )

    def to_dict(self) -> dict:
        return {
            "rule": self.rule,
            "path": self.path,
            "line": self.line,
            "severity": self.severity.value,
            "message": self.message,
            "summary": RULES.get(self.rule, ""),
        }

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), sort_keys=True)


_DISABLE_RE = re.compile(r"#\s*simlint:\s*disable=([A-Z0-9, ]+)")
_DISABLE_FILE_RE = re.compile(r"#\s*simlint:\s*disable-file=([A-Z0-9, ]+)")


def _ids(match_text: str) -> List[str]:
    return [t.strip() for t in match_text.split(",") if t.strip()]


def file_suppressions(source: str) -> List[str]:
    """Rule ids disabled for the whole file."""
    out: List[str] = []
    for m in _DISABLE_FILE_RE.finditer(source):
        out.extend(_ids(m.group(1)))
    return out


def line_suppressions(source_line: str) -> List[str]:
    out: List[str] = []
    for m in _DISABLE_RE.finditer(source_line):
        out.extend(_ids(m.group(1)))
    return out


def apply_suppressions(
    findings: List[Finding], source: str, lines: Optional[List[str]] = None
) -> List[Finding]:
    """Drop findings suppressed by file- or line-level comments."""
    if lines is None:
        lines = source.splitlines()
    file_off = set(file_suppressions(source))
    kept = []
    for f in findings:
        if f.rule in file_off:
            continue
        line = lines[f.line - 1] if 0 < f.line <= len(lines) else ""
        if f.rule in line_suppressions(line):
            continue
        kept.append(f)
    return kept
