"""Batched P2PHandel: Handel-style aggregation over a generic P2P graph —
periodic push of missing-signature sets to the neighbour with the largest
diff.

Reference semantics: protocols/P2PHandel.java (node logic :255-480, init
tasks :482-509) via the oracle port `protocols/p2phandel.py`.

TPU-first design:

  * signature sets are dense bool matrices: `verified[N, N]`,
    `pend[N, N]` (the to_verify pool, pre-aggregated), and the per-peer
    knowledge cube `peers_state[N, P, N]` (P = max degree);
  * the periodic sendSigs beat picks argmax over per-peer diff
    cardinalities ([N, P] popcounts) and ships the diff bitset AS the
    message payload (PAYLOAD_WIDTH = N/32 words);
  * checkSigs2 (the default double-aggregate strategy,
    P2PHandel.java:455-479): the pending pool is a single OR-aggregate,
    verified once per free verification register.  The oracle can
    overlap two scheduled updates (it re-checks every pairingTime while
    an update is in flight for 2*pairingTime); here a new verification
    starts only when the register is free — worst case one extra
    pairingTime of latency per batch, documented;
  * checkSigs1 (double_aggregate_strategy=False, :419-447): the
    to_verify pool is CAND_K distinct candidate bitsets [N, K, N]; the
    beat prunes zero-value entries and verifies the one adding the most
    signatures.  Same single-register policy as checkSigs2; same-ms
    arrivals for one receiver merge into one pool entry (the oracle
    keeps them distinct — single-arrival ms, the common case at the
    default sigsSendPeriod, is exact);
  * State broadcasts (send_state=True, :305-317 + init :497-501): every
    node broadcasts its verified set to all peers at t=1 and on every
    improving non-final commit; receivers fold it into peers_state only
    (on_peer_state, :281-283).

Engine-limit approximations: per-message wire sizes are dynamic in the
reference (diff cardinality / range compression, :160-229) but the
engine's traffic counters are per-type static — byte counters here use
size 1 per SendSigs/State, so bytes stats are NOT comparable to the
oracle (message counts are).  On the wire, "dif" ships the diff and all
three other strategies ship the full verified set, exactly like the
oracle's _create_send_sigs (:389-404) — the compressed variants only
change the byte-size model, which is not modeled here.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
import numpy as np

from ..core.node import build_node_columns
from ..core.registries import registry_network_latencies
from ..engine import BatchedNetwork, BatchedProtocol, Emission
from .p2pflood_batched import build_adjacency
from .p2phandel import P2PHandel, P2PHandelParameters


class BatchedP2PHandel(BatchedProtocol):
    MSG_TYPES = ["SEND_SIGS", "STATE"]
    TICK_INTERVAL = 1  # periodic beat + conditional checkSigs per ms
    CAND_K = 8  # checkSigs1 to_verify pool depth
    # ver_card cache (the score cache, p2phandel half):
    # `verified` changes only in tick's commit, and the merged cardinality
    # obeys |verified ∪ ver_sig| = |verified| + |ver_sig \ verified| — so
    # one carried int32[N] column replaces the two [N, N] bool reductions
    # per tick.  End-of-tick invariant (simlint SL701): ver_card equals
    # sum(verified, axis=1).  (peers_state cardinalities are NOT cacheable
    # this way: the delivery scatter-max can hit duplicate (to, slot)
    # destinations, which breaks the incremental identity.)
    # The program's only form; the constant mirrors BatchedHandel's.
    SCORE_CACHE = True
    CACHE_LEAF_NAMES = ("ver_card",)
    DERIVED_CACHE_LEAVES = CACHE_LEAF_NAMES

    def __init__(self, params: P2PHandelParameters, adjacency: np.ndarray, just_relay):
        self.params = params
        self.adj = jnp.asarray(adjacency, jnp.int32)
        self.n_nodes = params.signing_node_count + params.relaying_node_count
        self.just_relay = jnp.asarray(just_relay)
        self.PAYLOAD_WIDTH = (self.n_nodes + 31) // 32
        self.NARROW_LEAVES = self._narrow_plan()

    def _narrow_plan(self) -> tuple:
        """Density plan (engine.density, docs/density.md): ver_card is a
        verified-signature cardinality, provably <= N; carried narrow,
        computed in int32 inside the widen/narrow hook boundary."""
        from ..engine.density import NarrowLeaf, narrowest_int

        dt = narrowest_int(self.n_nodes)
        if dt.itemsize >= 4:
            return ()
        return (NarrowLeaf("ver_card", dt.name, self.n_nodes),)

    def msg_size(self, mtype: int) -> int:
        return 1  # dynamic in the reference; see the module docstring

    def _pack(self, bits):
        """bool[..., N] -> uint32 words [..., W] as int32 payload."""
        n = self.n_nodes
        pad = (-n) % 32
        b = jnp.pad(bits, [(0, 0)] * (bits.ndim - 1) + [(0, pad)])
        b = b.reshape(b.shape[:-1] + (self.PAYLOAD_WIDTH, 32))
        weights = (jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32)).astype(jnp.uint32)
        return jnp.sum(b.astype(jnp.uint32) * weights, axis=-1).astype(jnp.int32)

    def _unpack(self, words):
        """int32 words [..., W] -> bool[..., N]."""
        w = words.astype(jnp.uint32)
        bits = (w[..., None] >> jnp.arange(32, dtype=jnp.uint32)) & jnp.uint32(1)
        bits = bits.reshape(words.shape[:-1] + (self.PAYLOAD_WIDTH * 32,))
        return bits[..., : self.n_nodes] == 1

    def proto_init(self, n_nodes: int):
        n = self.n_nodes
        verified = jnp.zeros((n, n), bool)
        # signing nodes hold their own signature (ctor, :264-266)
        ids = jnp.arange(n)
        verified = verified.at[ids, ids].set(~self.just_relay)
        proto = {
            "verified": verified,
            "pend": jnp.zeros((n, n), bool),
            "peers_state": jnp.zeros((n, self.adj.shape[1], n), bool),
            "ver_active": jnp.zeros(n, bool),
            "ver_done_t": jnp.zeros(n, jnp.int32),
            "ver_sig": jnp.zeros((n, n), bool),
            "last_check": jnp.zeros(n, jnp.int32),
        }
        if not self.params.double_aggregate_strategy:
            proto["cand"] = jnp.zeros((n, self.CAND_K, n), bool)
        proto["ver_card"] = jnp.sum(verified, axis=1)
        return self.narrow_proto(proto)

    def recompute_caches(self, state) -> dict:
        # re-narrowed so the returned leaf matches the carried storage
        # dtype exactly (SL701 / checkpoint templates are dtype-strict)
        return self.narrow_proto(
            {"ver_card": jnp.sum(state.proto["verified"], axis=-1)}
        )

    def initial_emissions(self, net, state):
        if not self.params.send_state:
            return []
        # init registers sendStateToPeers at t=1 for every node (:497-501)
        n, n_peers = self.n_nodes, self.adj.shape[1]
        ids = jnp.arange(n, dtype=jnp.int32)
        return [
            Emission(
                mask=(self.adj >= 0).reshape(-1),
                from_idx=jnp.repeat(ids, n_peers),
                to_idx=jnp.maximum(self.adj, 0).reshape(-1),
                mtype=self.mtype("STATE"),
                payload=jnp.repeat(
                    self._pack(state.proto["verified"]), n_peers, axis=0
                ).reshape(n * n_peers, -1),
                send_time=jnp.int32(1),
            )
        ]

    # -- message handling ----------------------------------------------------
    def deliver(self, net, state, deliver_mask):
        # NARROW_LEAVES boundary (engine.density): hook bodies compute on
        # the int32 view, carried state stores the declared narrow dtypes
        state = state._replace(proto=self.widen_proto(state.proto))
        state, ems = self._deliver_impl(net, state, deliver_mask)
        return state._replace(proto=self.narrow_proto(state.proto)), ems

    def _deliver_impl(self, net, state, deliver_mask):
        proto = dict(state.proto)
        n = self.n_nodes
        to, frm = state.msg_to, state.msg_from
        sigs = self._unpack(state.msg_payload)  # [C, N]
        sigs = sigs & deliver_mask[:, None]
        is_ss = deliver_mask & (state.msg_type == self.mtype("SEND_SIGS"))

        # peers_state[to, slot(frm)] |= sigs — both SendSigs (onNewSig,
        # :330-334) and State (onPeerState, :281-283) fold in here
        slot_of = jnp.argmax(self.adj[to] == frm[:, None], axis=1)
        ok = jnp.take_along_axis(self.adj[to], slot_of[:, None], axis=1)[:, 0] == frm
        w_to = jnp.where(deliver_mask & ok, to, n)
        proto["peers_state"] = proto["peers_state"].at[w_to, slot_of].max(
            sigs, mode="drop"
        )
        ss_to = jnp.where(is_ss & ok, to, n)
        if self.params.double_aggregate_strategy:
            # checkSigs2 pool: one OR-aggregate
            proto["pend"] = proto["pend"].at[ss_to].max(sigs, mode="drop")
        else:
            # checkSigs1 pool: same-ms arrivals merge into ONE new entry,
            # which replaces the least-valuable slot if it adds more
            arrivals = jnp.zeros((n, n), bool).at[ss_to].max(sigs, mode="drop")
            has_new = jnp.any(arrivals, axis=1)
            cand = proto["cand"]
            verified = proto["verified"]
            v_k = jnp.sum(cand & ~verified[:, None, :], axis=2)  # [N, K]
            worst = jnp.argmin(v_k, axis=1)
            v_min = jnp.take_along_axis(v_k, worst[:, None], axis=1)[:, 0]
            v_new = jnp.sum(arrivals & ~verified, axis=1)
            insert = has_new & (v_new > v_min)
            proto["cand"] = cand.at[
                jnp.where(insert, jnp.arange(n, dtype=jnp.int32), n), worst
            ].set(arrivals, mode="drop")
        return state._replace(proto=proto), []

    # -- per-tick ------------------------------------------------------------
    def tick(self, net, state):
        state = state._replace(proto=self.widen_proto(state.proto))
        state = self._tick_impl(net, state)
        return state._replace(proto=self.narrow_proto(state.proto))

    def _tick_impl(self, net, state):
        p = self.params
        proto = dict(state.proto)
        n = self.n_nodes
        t = state.time
        ids = jnp.arange(n, dtype=jnp.int32)
        verified = proto["verified"]
        ps = proto["peers_state"]

        # 1. commit due verifications (updateVerifiedSignatures, :290-303)
        due = proto["ver_active"] & (t >= proto["ver_done_t"])
        # carried cardinality + the union identity — one [N, N]
        # reduction (the delta) instead of two full recounts
        old_card = proto["ver_card"]
        delta = jnp.sum(proto["ver_sig"] & ~verified, axis=1)
        verified = jnp.where(
            due[:, None], verified | proto["ver_sig"], verified
        )
        new_card = jnp.where(due, old_card + delta, old_card)
        proto["ver_card"] = new_card
        grew = due & (new_card > old_card)
        was_undone = state.done_at == 0
        reach = grew & was_undone & (new_card >= p.threshold)
        state = state._replace(done_at=jnp.where(reach, t, state.done_at))
        proto["ver_active"] = proto["ver_active"] & ~due

        # final aggregation to peers still short of threshold (:305-317)
        ps_card = jnp.sum(ps, axis=2)  # [N, P]
        needy = (ps_card < p.threshold) & (self.adj >= 0)
        fin = reach[:, None] & needy
        ps = jnp.where(fin[:, :, None], ps | verified[:, None, :], ps)
        n_peers = self.adj.shape[1]
        em_final = Emission(
            mask=fin.reshape(-1),
            from_idx=jnp.repeat(ids, n_peers),
            to_idx=jnp.maximum(self.adj, 0).reshape(-1),
            mtype=self.mtype("SEND_SIGS"),
            payload=jnp.repeat(
                self._pack(verified), n_peers, axis=0
            ).reshape(n * n_peers, -1),
        )
        em_state = None
        if p.send_state:
            # improving, non-final commit: broadcast State to all peers
            # (updateVerifiedSignatures elif branch, :299-301)
            st = grew & was_undone & ~reach
            em_state = Emission(
                mask=(st[:, None] & (self.adj >= 0)).reshape(-1),
                from_idx=jnp.repeat(ids, n_peers),
                to_idx=jnp.maximum(self.adj, 0).reshape(-1),
                mtype=self.mtype("STATE"),
                payload=jnp.repeat(
                    self._pack(verified), n_peers, axis=0
                ).reshape(n * n_peers, -1),
            )

        # 2. checkSigs beat: conditional task, min gap pairingTime
        # (init :505-509), single verification register (see header).
        # Known approximation: this reads same-tick state (arrivals of t,
        # phase-1 commits) where the reference's boundary-fired conditional
        # task sees end-of-(t-1) — a 1-tick information lead per
        # verification hop (handel/gsf _select got the boundary-view fix
        # in r5; here cand is [N, K, N]-dense and double-buffering it
        # costs more memory than the lead is worth at current parity)
        if p.double_aggregate_strategy:
            # checkSigs2 (:455-479): aggregate everything, verify once
            has_pend = jnp.any(proto["pend"], axis=1)
            check = (
                has_pend
                & (state.done_at == 0)
                & ~proto["ver_active"]
                & (t >= 1)
                & (t - proto["last_check"] >= p.pairing_time)
            )
            agg = proto["pend"]
            useful = jnp.any(agg & ~verified, axis=1) & check
            proto["pend"] = jnp.where(check[:, None], False, proto["pend"])
            chosen = agg
        else:
            # checkSigs1 (:419-447): prune zero-value entries, verify the
            # single best
            cand = proto["cand"]
            v_k = jnp.sum(cand & ~verified[:, None, :], axis=2)  # [N, K]
            occupied = jnp.any(cand, axis=2)
            cand = cand & (v_k > 0)[:, :, None]  # iterator discard
            check = (
                jnp.any(occupied, axis=1)
                & (state.done_at == 0)
                & ~proto["ver_active"]
                & (t >= 1)
                & (t - proto["last_check"] >= p.pairing_time)
            )
            best = jnp.argmax(v_k, axis=1)
            best_v = jnp.take_along_axis(v_k, best[:, None], axis=1)[:, 0]
            useful = check & (best_v > 0)
            chosen = jnp.take_along_axis(cand, best[:, None, None], axis=1)[:, 0]
            proto["cand"] = cand.at[
                jnp.where(useful, ids, n), best
            ].set(False, mode="drop")
        proto["last_check"] = jnp.where(check, t, proto["last_check"])
        proto["ver_active"] = proto["ver_active"] | useful
        proto["ver_done_t"] = jnp.where(
            useful, t + 2 * p.pairing_time, proto["ver_done_t"]
        )
        proto["ver_sig"] = jnp.where(useful[:, None], chosen, proto["ver_sig"])

        # 3. periodic sendSigs: push the largest diff (:336-354)
        beat = (t >= 1) & (
            jnp.equal((t - 1) % jnp.int32(p.sigs_send_period), 0)
        ) & (state.done_at == 0) & ~state.down
        diff = verified[:, None, :] & ~ps  # [N, P, N]
        dsz = jnp.sum(diff & (self.adj >= 0)[:, :, None], axis=2)
        best = jnp.argmax(dsz, axis=1)
        best_sz = jnp.take_along_axis(dsz, best[:, None], axis=1)[:, 0]
        send = beat & (best_sz > 0)
        dest = jnp.take_along_axis(self.adj, best[:, None], axis=1)[:, 0]
        to_send = jnp.take_along_axis(diff, best[:, None, None], axis=1)[:, 0]
        if p.strategy.value != "dif":
            # all / cmp_all / cmp_diff all ship the FULL verified set —
            # only their byte-size models differ (:389-404); the diff goes
            # on the wire for plain "dif" only
            to_send = verified
        w_n = jnp.where(send, ids, n)
        ps = ps.at[w_n, best].max(verified, mode="drop")
        em_push = Emission(
            mask=send,
            from_idx=ids,
            to_idx=jnp.maximum(dest, 0),
            mtype=self.mtype("SEND_SIGS"),
            payload=self._pack(to_send),
        )

        proto["verified"] = verified
        proto["peers_state"] = ps
        state = state._replace(proto=proto)
        state = net.apply_emission(state, em_push)
        state = net.apply_emission(state, em_final)
        if em_state is not None:
            state = net.apply_emission(state, em_state)
        return state

    def all_done(self, state):
        return jnp.all(jnp.where(~state.down, state.done_at > 0, True))


def make_p2phandel(
    params: Optional[P2PHandelParameters] = None,
    capacity: int = 1 << 13,
    seed: int = 0,
):
    """Host-side construction: oracle init builds the graph and the relay
    set (same JavaRandom stream)."""
    params = params or P2PHandelParameters()
    oracle = P2PHandel(params)
    oracle.init()
    net_o = oracle.network()
    adj = build_adjacency(net_o)
    just_relay = np.array([nd.just_relay for nd in net_o.all_nodes])
    latency = registry_network_latencies.get_by_name(params.network_latency_name)
    city_index = getattr(latency, "city_index", None)
    cols = build_node_columns(net_o.all_nodes, city_index)
    proto = BatchedP2PHandel(params, adj, just_relay)
    net = BatchedNetwork(proto, latency, proto.n_nodes, capacity=capacity)
    state = net.init_state(cols, seed=seed, proto=proto.proto_init(proto.n_nodes))
    return net, state
