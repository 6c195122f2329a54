"""Batched protocol contract.

The batched analog of core Protocol.java + Message.action: a protocol is a
set of vectorized kernels over the SoA state instead of per-object
callbacks.  `deliver` sees ALL due messages at once (masked rows of the
message ring) and must apply commutative updates; `tick` hosts
periodic-task masks ((t - start) % period == 0 — PeriodicTask.java:40-47
without the queue) and conditional-task predicates (Network.java:543-566)."""

from __future__ import annotations

from typing import Any, List, Tuple

import jax.numpy as jnp

# ---------------------------------------------------------------------------
# Machine-readable contract metadata (consumed by wittgenstein_tpu.analysis).
# These tuples ARE the contract prose above, in checkable form: simlint's
# AST rules and abstract-eval passes import them instead of hard-coding
# field lists, so an engine refactor that moves a column updates the
# checker automatically.
# ---------------------------------------------------------------------------

# SimState fields owned by the ENGINE: protocol hooks must never write them
# (`deliver` returns emissions instead of touching the store; the engine
# ticks counters and the clock).  A protocol with a genuine exception
# declares it in DELIVER_MAY_TOUCH.
ENGINE_OWNED_FIELDS = (
    "time",
    "seed",
    "send_ctr",
    "msg_valid",
    "msg_arrival",
    "msg_from",
    "msg_to",
    "msg_type",
    "msg_payload",
    "whl_fill",
    "ovf_valid",
    "ovf_arrival",
    "ovf_from",
    "ovf_to",
    "ovf_type",
    "ovf_payload",
    "msg_head",
    "dropped",
    "tele",
    "faults",
    "census",
)

# Hooks traced under jit (tracer-safety rules apply) vs host-side
# construction hooks (plain Python allowed).
KERNEL_HOOKS = ("deliver", "tick", "tick_beat", "tick_post", "all_done")
HOST_HOOKS = (
    "proto_init", "initial_emissions", "msg_size", "n_msg_types", "mtype", "census_limits",
)


class BatchedProtocol:
    """Subclass and override.  MSG_TYPES maps message-type names to the int
    codes stored in the ring."""

    MSG_TYPES: List[str] = []
    PAYLOAD_WIDTH: int = 0
    # None = tick() does nothing time-sensitive, so the engine may skip
    # empty milliseconds (jump to the next arrival).  Protocols with
    # periodic/conditional work must set 1 (or their smallest period).
    TICK_INTERVAL: int | None = 1
    # Time coarsening for event-driven protocols (TICK_INTERVAL None):
    # arrivals are delivered together at the next multiple of this grid,
    # delaying each by < TIME_QUANTUM ms.  For protocols whose observables
    # live at the seconds scale (ENR's record propagation), a quantum of
    # a few ms cuts loop iterations by that factor with distortion far
    # inside the distribution-parity tolerance.  1 = exact arrival times.
    TIME_QUANTUM: int = 1
    # Optional beat structure: periodic work (the PeriodicTask analog) that
    # fires only when t % BEAT_PERIOD is in BEAT_RESIDUES goes in
    # tick_beat().  Because every replica advances time in lockstep,
    # run_ms_batched hoists the time loop outside vmap and guards
    # tick_beat with a REAL lax.cond on the (replica-uniform) tick index —
    # off-beat ticks skip the work entirely instead of executing it
    # masked.  tick() must NOT include the beat work when these are set;
    # the generic paths (run_ms, fallback run_ms_batched) call tick_beat
    # every tick, relying on its own on-beat masks for exactness.
    BEAT_PERIOD: int | None = None
    BEAT_RESIDUES: tuple | None = None
    # Number of latency_arrivals calls tick_beat makes.  On off-beat ticks
    # the engine advances send_ctr by this amount so the per-event RNG
    # stream is IDENTICAL to the ungated path (where the masked beat call
    # still ticked the counter) — beat gating changes cost, never draws.
    BEAT_SEND_CALLS: int = 0
    # Engine-owned SimState fields this protocol's deliver() is ALLOWED to
    # write (empty for every current protocol; a future exception must be
    # declared here so simlint's ownership check stays exact).
    DELIVER_MAY_TOUCH: tuple = ()
    # simlint rule ids (e.g. "SL404") suppressed for this protocol's
    # abstract-eval checks — the dynamic analog of the per-line
    # `# simlint: disable=RULE` comment.  Use sparingly, with a comment.
    SIMLINT_SUPPRESS: tuple = ()
    # Names of state.proto leaves that are DERIVED caches: redundant
    # values (candidate-score caches, cached cardinalities) recomputable
    # from the authoritative leaves at any tick boundary.  A protocol
    # declaring leaves here must also override recompute_caches();
    # simlint SL701 steps the protocol concretely and asserts the carried
    # caches match a from-scratch recompute bitwise, so stale-cache bugs
    # can't ship silently.
    DERIVED_CACHE_LEAVES: tuple = ()
    # Narrow-storage declarations (engine.density.NarrowLeaf): proto
    # leaves CARRIED below int32, each with the dtype, the provable value
    # bound given the protocol's static geometry, and whether the leaf
    # uses the INT32_MAX "empty" sentinel (stored as the narrow dtype's
    # max, which is then reserved).  Kernel hooks must call
    # widen_proto()/narrow_proto() at their boundary so every kernel body
    # still computes in int32 — the narrowing is bit-identical by
    # construction.  simlint SL901 audits the declarations (static
    # headroom + concrete-step range check); docs/density.md is the
    # full story.  Usually set per-INSTANCE (the bounds depend on N).
    NARROW_LEAVES: tuple = ()

    def contract(self) -> dict:
        """Machine-readable contract summary (instance-level: factories may
        set BEAT_* dynamically).  This is what simlint audits against."""
        msg_types = self.MSG_TYPES
        return {
            "protocol": type(self).__name__,
            "msg_types": list(msg_types) if msg_types else [],
            "n_msg_types": self.n_msg_types(),
            "payload_width": int(self.PAYLOAD_WIDTH),
            "tick_interval": self.TICK_INTERVAL,
            "time_quantum": int(self.TIME_QUANTUM),
            "beat_period": self.BEAT_PERIOD,
            "beat_residues": (
                tuple(self.BEAT_RESIDUES) if self.BEAT_RESIDUES else None
            ),
            "beat_send_calls": int(self.BEAT_SEND_CALLS),
            "engine_owned_fields": list(ENGINE_OWNED_FIELDS),
            "deliver_may_touch": list(self.DELIVER_MAY_TOUCH),
            "simlint_suppress": list(self.SIMLINT_SUPPRESS),
            "derived_cache_leaves": list(self.DERIVED_CACHE_LEAVES),
            "narrow_leaves": [s.key() for s in self.NARROW_LEAVES],
        }

    def n_msg_types(self) -> int:
        return max(1, len(self.MSG_TYPES))

    def mtype(self, name: str) -> int:
        return self.MSG_TYPES.index(name)

    def msg_size(self, mtype: int) -> int:
        """Bytes per message type (Message.size, Message.java:28 default 1)."""
        return 1

    # -- hooks ---------------------------------------------------------------
    def proto_init(self, n_nodes: int) -> Any:
        """Protocol-state pytree for a fresh replica (Protocol.init)."""
        return ()

    def census_limits(self) -> dict:
        """The static limits of the protocol's OWN capacities, by the name
        of the peak the work census reads against each (engine.core
        `CENSUS_VECTOR_PEAKS`; the engine adds its store's): none here."""
        return {}

    def initial_emissions(self, net, state) -> List:
        """Messages injected at t=0 (the protocol's init() sends)."""
        return []

    def deliver(self, net, state, deliver_mask) -> Tuple[Any, List]:
        """Handle all due messages.  Returns (new state, emissions) — the
        state may update proto and node columns (done_at, down, ...) but must
        not touch msg_* (the engine owns the ring).  `deliver_mask` is
        bool[C] over the message ring; read message fields from state.msg_*."""
        return state, []

    def tick(self, net, state):
        """Per-millisecond hook after delivery (periodic/conditional tasks).
        Returns the full state (may emit via net.apply_emission)."""
        return state

    def tick_beat(self, net, state):
        """Beat-gated periodic work (see BEAT_PERIOD above).  Must be a
        no-op on off-beat ticks (its own masks), since the generic engine
        paths call it every tick."""
        return state

    def tick_post(self, net, state):
        """Per-tick work that must run AFTER tick_beat (protocols whose
        phase order interleaves dense and beat-gated phases, e.g.
        HandelEth2's commit -> start/stop+dissemination -> select)."""
        return state

    def widen_proto(self, proto):
        """NARROW_LEAVES -> int32 compute view of a proto dict (kernel-hook
        entry).  Identity when nothing is declared."""
        if not self.NARROW_LEAVES:
            return proto
        from .density import widen_tree

        return widen_tree(proto, self.NARROW_LEAVES)

    def narrow_proto(self, proto):
        """int32 compute view -> declared storage dtypes (kernel-hook exit
        and proto_init).  Identity when nothing is declared."""
        if not self.NARROW_LEAVES:
            return proto
        from .density import narrow_tree

        return narrow_tree(proto, self.NARROW_LEAVES)

    def recompute_caches(self, state) -> dict:
        """From-scratch values for every DERIVED_CACHE_LEAVES leaf, as a
        {leaf_name: array} dict computed from the authoritative proto
        leaves only.  The consistency oracle for simlint SL701 and the
        cache-equivalence tests; must be traceable."""
        return {}

    # -- termination ----------------------------------------------------------
    def all_done(self, state) -> jnp.ndarray:
        """bool scalar: replica finished (used by sweep drivers to stop)."""
        return jnp.asarray(False)
