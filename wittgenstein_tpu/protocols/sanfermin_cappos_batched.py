"""Batched SanFerminCappos: the San Fermin variant with multi-candidate
swaps, per-level signature caches and level timeouts.

Reference semantics: protocols/SanFerminCappos.java (onSwap :201-241,
tryNextNodes + timeout :248-296, goNextLevel with the live futur-skip
recursion :306-344, totalNumberOfSigs :351-358, putCachedSig threshold
check :382-393) via the oracle port `protocols/sanfermin_cappos.py`.

Differences from the batched SanFerminSignature worth naming:

  * there is no pending set at all — every Swap(level, value) at the
    receiver's level from a candidate triggers the transition, whether it
    was a request (wantReply) or a reply;
  * the aggregate is DERIVED, not stored: totalNumberOfSigs(l) = 1 + the
    sum over levels >= l of the best cached value — a masked row-sum over
    the [N, W+1] cache matrix;
  * goNextLevel's futur-skip recursion is LIVE here (case-A caching fills
    levels ahead), so the descent is a bounded unrolled loop over the
    log2(N) levels with shrinking masks.

The XOR candidate blocks are sanfermin_batched's; the position->partner
bijection (a uniform walk of the block) and the single live timeout are
this module's own approximations, which sanfermin_batched has replaced by
the reference's pick order and stacked timeouts."""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
import numpy as np

from ..core.node import build_node_columns
from ..core.registries import registry_network_latencies
from ..engine import BatchedNetwork, BatchedProtocol, Emission
from ..engine.rng import hash32
from ..utils.more_math import log2
from .sanfermin_cappos import SanFerminCappos, SanFerminParameters


class BatchedSanFerminCappos(BatchedProtocol):
    MSG_TYPES = ["SWAP"]
    PAYLOAD_WIDTH = 3  # (level, value, want_reply)
    TICK_INTERVAL = 1

    def __init__(self, params: SanFerminParameters):
        self.params = params
        self.n_nodes = params.node_count
        self.w = log2(self.n_nodes)
        assert 1 << self.w == self.n_nodes, "node_count must be a power of two"
        # contacts per send: the exact candidate + candidate_count walkers,
        # capped at the largest block
        self.k = 1 + min(params.candidate_count, self.n_nodes // 2)

    def msg_size(self, mtype: int) -> int:
        return 4 + self.params.signature_size  # Swap.size (:48-50)

    def proto_init(self, n_nodes: int, seed: int = 0):
        w = self.w
        return {
            "cpl": jnp.full(n_nodes, w - 1, jnp.int32),
            "done": jnp.zeros(n_nodes, bool),
            "thr_done": jnp.zeros(n_nodes, bool),
            "thr_at": jnp.zeros(n_nodes, jnp.int32),
            "swapping": jnp.zeros(n_nodes, bool),
            "swap_lvl": jnp.zeros(n_nodes, jnp.int32),
            "swap_val": jnp.zeros(n_nodes, jnp.int32),
            "swap_t": jnp.zeros(n_nodes, jnp.int32),
            "cache_best": jnp.zeros((n_nodes, w + 1), jnp.int32),
            "cache_any": jnp.zeros((n_nodes, w + 1), bool),
            "cursor": jnp.full(n_nodes, self.k, jnp.int32),
            "tmo_t": jnp.full(n_nodes, 1 + self.params.timeout, jnp.int32),
            "tmo_lvl": jnp.full(n_nodes, w - 1, jnp.int32),
        }

    # -- shared XOR-block candidate walk (see sanfermin_batched) -------------
    def _bs(self, cpl):
        return (jnp.int32(1) << (self.w - 1 - cpl)).astype(jnp.int32)

    def _partner(self, seed, ids, cpl, position):
        bs = self._bs(cpl)
        x = hash32(seed, ids, cpl, jnp.int32(0x5AFE)) & (bs - 1)
        q = position - 1
        p = q + (q >= x).astype(jnp.int32)
        r = jnp.where(position == 0, 0, p ^ x)
        return ids ^ (bs + r), position < bs

    def _total_sigs(self, proto, level):
        """totalNumberOfSigs(level): own sig + best cached per level >= l
        (:351-358)."""
        lr = jnp.arange(self.w + 1, dtype=jnp.int32)
        m = lr[None, :] >= level[:, None]
        return 1 + jnp.sum(jnp.where(m, proto["cache_best"], 0), axis=1)

    def _send_swaps(self, state, mask, proto):
        """tryNextNodes: Swap(cpl, totalSigs(cpl+1), wantReply=True) to the
        next k candidates; arm the (single live) timeout."""
        n = self.n_nodes
        ids = jnp.arange(n, dtype=jnp.int32)
        cpl, cursor = proto["cpl"], proto["cursor"]
        value = self._total_sigs(proto, cpl + 1)
        rows_mask, rows_to = [], []
        for j in range(self.k):
            partner, in_block = self._partner(state.seed, ids, cpl, cursor + j)
            rows_mask.append(mask & in_block)
            rows_to.append(partner)
        em = Emission(
            mask=jnp.stack(rows_mask, 1).reshape(-1),
            from_idx=jnp.repeat(ids, self.k),
            to_idx=jnp.clip(jnp.stack(rows_to, 1).reshape(-1), 0, n - 1),
            mtype=self.mtype("SWAP"),
            payload=jnp.stack(
                [
                    jnp.repeat(cpl[:, None], self.k, 1).reshape(-1),
                    jnp.repeat(value[:, None], self.k, 1).reshape(-1),
                    jnp.ones(n * self.k, jnp.int32),
                ],
                axis=1,
            ),
        )
        proto = dict(
            proto,
            cursor=jnp.where(mask, cursor + self.k, cursor),
            tmo_t=jnp.where(mask, state.time + 1 + self.params.timeout, proto["tmo_t"]),
            tmo_lvl=jnp.where(mask, cpl, proto["tmo_lvl"]),
        )
        return proto, em

    def initial_emissions(self, net, state):
        """The pre-applied t=1 goNextLevel sends (bookkeeping in proto_init)."""
        n = self.n_nodes
        ids = jnp.arange(n, dtype=jnp.int32)
        cpl = state.proto["cpl"]
        rows_mask, rows_to = [], []
        for j in range(self.k):
            partner, in_block = self._partner(
                state.seed, ids, cpl, jnp.full(n, j, jnp.int32)
            )
            rows_mask.append(in_block)
            rows_to.append(partner)
        return [
            Emission(
                mask=jnp.stack(rows_mask, 1).reshape(-1),
                from_idx=jnp.repeat(ids, self.k),
                to_idx=jnp.clip(jnp.stack(rows_to, 1).reshape(-1), 0, n - 1),
                mtype=self.mtype("SWAP"),
                payload=jnp.stack(
                    [
                        jnp.repeat(cpl[:, None], self.k, 1).reshape(-1),
                        jnp.ones(n * self.k, jnp.int32),  # totalSigs = 1 at init
                        jnp.ones(n * self.k, jnp.int32),
                    ],
                    axis=1,
                ),
            )
        ]

    # -- message handling (onSwap, :201-241) ---------------------------------
    def deliver(self, net, state, deliver_mask):
        p = self.params
        proto = dict(state.proto)
        n, c = self.n_nodes, deliver_mask.shape[0]
        t = state.time
        to, frm = state.msg_to, state.msg_from
        lvl_p = jnp.clip(state.msg_payload[:, 0], 0, self.w)
        val_p = state.msg_payload[:, 1]
        want = state.msg_payload[:, 2] == 1
        slot = jnp.arange(c, dtype=jnp.int32)

        is_swap = deliver_mask & (state.msg_type == self.mtype("SWAP"))
        cpl, done = proto["cpl"], proto["done"]
        xorv = to ^ frm
        bs_p = (jnp.int32(1) << jnp.clip(self.w - 1 - lvl_p, 0, self.w)).astype(jnp.int32)
        is_cand = (xorv >= bs_p) & (xorv < 2 * bs_p)

        mismatch = done[to] | (lvl_p != cpl[to])
        cached = proto["cache_any"][to, lvl_p]
        # case A: stale/done receiver — cached reply or cache the offer
        a_reply = is_swap & mismatch & want & cached
        a_store = is_swap & mismatch & ~(want & cached) & is_cand
        # case B: level match — reply when asked, then maybe transition
        b_reply = is_swap & ~mismatch & want
        trigger = is_swap & ~mismatch & is_cand & ~proto["swapping"][to] & ~done[to]

        # replies (both cases ship want_reply=False); case B answers with
        # totalNumberOfSigs(swap.level) — the level itself, not level+1
        # (:224-227)
        rep_val = jnp.where(
            a_reply,
            proto["cache_best"][to, lvl_p],
            self._total_sigs(proto, cpl)[to],
        )
        reply_em = Emission(
            mask=a_reply | b_reply,
            from_idx=to,
            to_idx=frm,
            mtype=self.mtype("SWAP"),
            payload=jnp.stack(
                [lvl_p, rep_val, jnp.zeros(c, jnp.int32)], axis=1
            ),
        )

        # case-A cache append: scatter-max per (node, level) + threshold
        proto["cache_best"] = proto["cache_best"].at[to, lvl_p].max(
            jnp.where(a_store, val_p, 0), mode="drop"
        )
        proto["cache_any"] = proto["cache_any"].at[to, lvl_p].max(
            a_store, mode="drop"
        )
        got_store = jnp.zeros(n, bool).at[to].max(a_store, mode="drop")
        thr = self._total_sigs(proto, cpl) >= p.threshold
        thr_now = got_store & thr & ~proto["thr_done"] & ~done
        proto["thr_done"] = proto["thr_done"] | thr_now
        proto["thr_at"] = jnp.where(thr_now, t + 2 * p.pairing_time, proto["thr_at"])

        # transition: lowest-slot winner per node
        twin = jnp.full(n, c, jnp.int32)
        twin = twin.at[to].min(jnp.where(trigger, slot, c), mode="drop")
        has_t = twin < c
        tslot = jnp.clip(twin, 0, c - 1)
        proto["swapping"] = proto["swapping"] | has_t
        proto["swap_lvl"] = jnp.where(has_t, lvl_p[tslot], proto["swap_lvl"])
        proto["swap_val"] = jnp.where(has_t, val_p[tslot], proto["swap_val"])
        proto["swap_t"] = jnp.where(has_t, t + p.pairing_time, proto["swap_t"])

        return state._replace(proto=proto), [reply_em]

    # -- per-tick: commit, descend (with futur skips), timeouts --------------
    def tick(self, net, state):
        p = self.params
        proto = dict(state.proto)
        t = state.time
        n = self.n_nodes
        w = self.w
        lr = jnp.arange(w + 1, dtype=jnp.int32)

        # commit: putCachedSig(swapLvl, swapVal) then goNextLevel
        commit = proto["swapping"] & (t >= proto["swap_t"]) & (proto["swap_t"] > 0)
        proto["cache_best"] = jnp.where(
            commit[:, None] & (lr[None, :] == proto["swap_lvl"][:, None]),
            jnp.maximum(proto["cache_best"], proto["swap_val"][:, None]),
            proto["cache_best"],
        )
        proto["cache_any"] = proto["cache_any"] | (
            commit[:, None] & (lr[None, :] == proto["swap_lvl"][:, None])
        )

        # goNextLevel with the futur-skip recursion, unrolled over levels
        active = commit
        descended = jnp.zeros(n, bool)
        for _ in range(w + 1):
            thr = self._total_sigs(proto, proto["cpl"]) >= p.threshold
            thr_now = active & thr & ~proto["thr_done"]
            proto["thr_done"] = proto["thr_done"] | thr_now
            proto["thr_at"] = jnp.where(
                thr_now, t + 2 * p.pairing_time, proto["thr_at"]
            )
            finish = active & (proto["cpl"] == 0)
            proto["done"] = proto["done"] | finish
            state = state._replace(
                done_at=jnp.where(finish, t + 2 * p.pairing_time, state.done_at)
            )
            active = active & ~finish
            proto["cpl"] = jnp.where(active, proto["cpl"] - 1, proto["cpl"])
            proto["swapping"] = proto["swapping"] & ~active
            proto["cursor"] = jnp.where(active, 0, proto["cursor"])
            descended = descended | active
            # continue descending only through already-cached levels
            active = active & proto["cache_any"][
                jnp.arange(n, dtype=jnp.int32), jnp.clip(proto["cpl"], 0, w)
            ]
        proto["swapping"] = proto["swapping"] & ~commit

        # timeout: re-pick while the level is unchanged (:282-291)
        tmo = (
            ~proto["done"]
            & (proto["tmo_t"] > 0)
            & (t >= proto["tmo_t"])
            & (proto["tmo_lvl"] == proto["cpl"])
        )
        stale = (proto["tmo_t"] > 0) & (t >= proto["tmo_t"])
        proto["tmo_t"] = jnp.where(stale, 0, proto["tmo_t"])

        send = (descended & ~proto["done"]) | tmo
        send = send & (proto["cursor"] < self._bs(proto["cpl"]))
        proto, em = self._send_swaps(state, send, proto)
        state = state._replace(proto=proto)
        return net.apply_emission(state, em)

    def all_done(self, state):
        return jnp.all(state.proto["done"])


def make_sanfermin_cappos(
    params: Optional[SanFerminParameters] = None,
    capacity: int = 1 << 14,
    seed: int = 0,
):
    params = params or SanFerminParameters()
    oracle = SanFerminCappos(params)
    oracle.init()
    latency = registry_network_latencies.get_by_name(params.network_latency_name)
    city_index = getattr(latency, "city_index", None)
    cols = build_node_columns(oracle.network().all_nodes, city_index)
    proto = BatchedSanFerminCappos(params)
    net = BatchedNetwork(proto, latency, params.node_count, capacity=capacity)
    state = net.init_state(
        cols, seed=seed, proto=proto.proto_init(params.node_count, seed=seed)
    )
    return net, state
