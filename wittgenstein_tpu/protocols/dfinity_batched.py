"""Batched Dfinity: the three-role random-beacon consensus on the batched
engine — block producers, attester committees, and beacon nodes driving a
notarized chain with 3-second rounds.

Reference semantics: protocols/Dfinity.java (comparator :107-130, messages
:132-186, BlockProducerNode :215-263, AttesterNode :265-351,
RandomBeaconNode :353-424, init :426-450), via the oracle port
`protocols/dfinity.py`.

TPU-first design:

  * the block DAG is a **preallocated block table** (SURVEY §7 step 7): a
    block's identity is its (height, producer) pair — each producer
    proposes at most once per height (BlockProducerNode.onRandomBeaconOnce
    guards on head.height == h-1 and the last_random_beacon once-guard) —
    so slot = (height-1) * n_bp + producer fixes every shape at
    `max_heights * n_bp` slots with (exists, proposal_time, parent) columns;
  * the Dfinity comparator collapses to height-with-incumbent-ties: the
    hasDirectLink branch only fires when heights differ, where it agrees
    with the height rule, and equal heights return 0 (the reference's
    producer-vs-itself quirk, Dfinity.java:128-129) — so fork choice is a
    scatter-max of (height, -slot) keys, no ancestor walks;
  * vote / beacon-exchange sets collapse to COUNTERS: every attester votes
    at most once per block and every beacon exchanges at most once per
    height (both structurally, on the sender side), so the receiver-side
    dedup sets of the reference are reachable by count alone (+ a
    self-vote / self-exchange flag);
  * all timing is message-driven (TICK_INTERVAL None): the reference's
    far-future beacon re-exchange (wt = parent.proposalTime + 2*roundTime,
    Dfinity.java:396-405) is an Emission with an explicit future
    send_time, and the engine's empty-ms jump skips the dead time.

  * every broadcast is a `FanOut` (engine/core.py): its rows are made for
    the senders that FIRE in a step, not for every sender that could.  At
    most one committee votes or notarises at a time (`votable` needs
    `vote_for_h`, which only the height's committee holds), so a step's
    firing senders are bounded by the deployment's shape, whatever the
    attester count: `attesters_per_round` for SEND_BLOCK, the beacon
    committee for RBE and RBR, `block_producers_per_round` for PROPOSAL,
    and for VOTE twice the committee over (producer slot, attester) PAIRS
    (each member votes once a proposal, five proposals a height, the most
    read in one ms 83 pairs at 4096 attesters in committees of 64:
    `_vote_capacity`).  A step that fires more takes another round and
    is counted (census `fanout_overflows`), never cut; the state is the
    dense spelling's bit for bit (`FanOut.dense()`, held in
    tests/test_dfinity_batched.py).

Same-tick semantics deltas (documented engine-wide): same-ms deliveries
are simultaneous; a beacon advances at most one height per tick (the
oracle can chain two notarized blocks in one ms — unobserved in practice
since consecutive notarizations are latency-separated).
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
import numpy as np

from ..core.node import build_node_columns
from ..core.registries import registry_network_latencies
from ..engine import BatchedNetwork, BatchedProtocol, FanOut
from ..engine.core import FANOUT_SCOPES
from .dfinity import (
    AttesterNode,
    BlockProducerNode,
    Dfinity,
    DfinityParameters,
    RandomBeaconNode,
)
from .dfinity_part import PartitionedDfinity, PartitionedDfinityParameters


# sub-scopes of Dfinity's deliver, by role (what the ops are FOR), nested
# under witt.delivery/witt.protocol_deliver
ROLE_SCOPES = {
    "propose": "witt.chain.propose",  # the producers: the beacon's arrival, the block table, PROPOSAL
    "notarize": "witt.chain.notarize",  # the attesters: block arrivals, committees, votes, the crossing
    "beacon": "witt.chain.beacon",  # the beacon committee: height advance, exchanges, results
}


def _vote_capacity(attesters_per_round: int) -> int:
    """(Producer slot, attester) pairs a round of the vote fan-out expands:
    twice the committee.  Each of a height's `attesters_per_round` members
    votes once a proposal as it arrives, `block_producers_per_round`
    proposals a height spread over the latency model's arrival ms; the
    most pairs read in one ms are 1.3 committees (83 at 4096 attesters in
    committees of 64 under IC3's six latency values, 20 at 256 in
    committees of 16: the reference DES, sandbox, PR 43)."""
    return 2 * attesters_per_round


class BatchedDfinity(BatchedProtocol):
    MSG_TYPES = ["PROPOSAL", "VOTE", "RBE", "RBR", "SEND_BLOCK"]
    # block slot | height.  One word: a beacon's rd IS its height (send_rb
    # :274-279) and nothing read the second, which made the wheel's payload
    # plane [rows, slots, 2]: XLA:TPU changed that plane's layout, a copy of
    # all of it, in every round of every send
    PAYLOAD_WIDTH = 1
    TICK_INTERVAL = None  # pure message protocol
    # simlint SL601 holds them live
    REQUIRED_SCOPES = tuple(ROLE_SCOPES.values()) + tuple(FANOUT_SCOPES.values())

    def __init__(self, params: DfinityParameters, roles: dict, max_heights: int):
        self.params = params
        self.max_heights = max_heights
        self.n_att = params.attesters_count
        self.n_bp = params.block_producers_count
        self.n_bcn = params.random_beacon_count
        self.n_nodes = 1 + self.n_att + self.n_bp + self.n_bcn  # + observer
        self.max_b = max_heights * self.n_bp
        # static role columns
        self.is_att = jnp.asarray(roles["is_att"])
        self.is_bp = jnp.asarray(roles["is_bp"])
        self.is_bcn = jnp.asarray(roles["is_bcn"])
        self.my_round = jnp.asarray(roles["my_round"], jnp.int32)
        self.bp_local = jnp.asarray(roles["bp_local"], jnp.int32)  # -1 if not BP
        self.att_ids = jnp.asarray(roles["att_ids"], jnp.int32)  # [n_att]
        self.bp_ids = jnp.asarray(roles["bp_ids"], jnp.int32)
        self.bcn_ids = jnp.asarray(roles["bcn_ids"], jnp.int32)
        self.all_ids = jnp.arange(self.n_nodes, dtype=jnp.int32)
        # the fan-outs' capacities, read from the shape (the module docstring)
        self.vote_capacity = _vote_capacity(params.attesters_per_round)
        self.block_capacity = params.attesters_per_round
        self.beacon_capacity = self.n_bcn
        self.proposal_capacity = params.block_producers_per_round

    def census_limits(self) -> dict:
        """`fanout_peak` is read against the largest of the fan-outs'
        capacities, the votes' (pairs); the others' own are the committee
        sizes under it."""
        return {"fanout_peak": self.vote_capacity}

    def proto_init(self, n_nodes: int):
        n, mb, mh = self.n_nodes, self.max_b, self.max_heights
        zi = lambda s: jnp.zeros(s, jnp.int32)
        return {
            "blk_exists": jnp.zeros(mb, bool),
            "blk_time": zi(mb),
            "blk_parent": jnp.full(mb, -1, jnp.int32),
            "seen": jnp.zeros((n, mb), bool),
            "head_slot": jnp.full(n, -1, jnp.int32),  # -1 = genesis
            "cm_blk": jnp.zeros((n, mb), bool),
            "cm_h": jnp.zeros((n, mh + 2), bool),
            "last_beacon": zi(n),
            "vote_for_h": jnp.full(n, -1, jnp.int32),
            "self_voted": jnp.zeros((n, mb), bool),
            "vote_cnt": zi((n, mb)),
            "prop_buf": jnp.zeros((n, mb), bool),
            # beacon state (send_rb already pre-applied for t=0 init)
            "bcn_height": jnp.ones(n, jnp.int32),
            "bcn_last_sent": jnp.ones(n, jnp.int32),
            "exch_cnt": zi((n, mh + 2)),
            "exch_self": jnp.zeros((n, mh + 2), bool),
            # head height x (attesters_per_round + 1) + the most votes counted
            # for one block: what the benchmark's twin reads from every node
            "chain_score": zi(n),
        }

    # -- helpers -------------------------------------------------------------
    def _slot_h(self, slot):
        return slot // self.n_bp + 1

    def _head_h(self, head_slot):
        return jnp.where(head_slot < 0, 0, self._slot_h(head_slot))

    def initial_emissions(self, net, state):
        """init (Dfinity.java:426-450): every beacon node send_rb()s the
        height-1 beacon to all nodes at t + attestation_construction_time."""
        p = self.params
        k = self.n_bcn
        return [
            FanOut(
                mask=jnp.ones(k, bool),
                from_idx=self.bcn_ids,
                receivers=self.all_ids,
                mtype=self.mtype("RBR"),
                capacity=k,
                payload=jnp.ones((k, 1), jnp.int32),
                send_time=jnp.full(k, p.attestation_construction_time, jnp.int32),
            )
        ]

    # -- the whole protocol runs in deliver ----------------------------------
    def deliver(self, net, state, deliver_mask):
        p = self.params
        proto = dict(state.proto)
        n, mb, mh = self.n_nodes, self.max_b, self.max_heights
        t = state.time
        ids = self.all_ids
        to, frm = state.msg_to, state.msg_from
        pay0 = jnp.clip(state.msg_payload[:, 0], 0, mb - 1)
        payh = jnp.clip(state.msg_payload[:, 0], 0, mh + 1)
        emissions = []
        scope = lambda name: net._scope(name, ROLE_SCOPES)

        is_prop = deliver_mask & (state.msg_type == self.mtype("PROPOSAL"))
        is_vote = deliver_mask & (state.msg_type == self.mtype("VOTE"))
        is_rbe = deliver_mask & (state.msg_type == self.mtype("RBE"))
        is_rbr = deliver_mask & (state.msg_type == self.mtype("RBR"))
        is_sblk = deliver_mask & (state.msg_type == self.mtype("SEND_BLOCK"))
        slots = jnp.arange(mb, dtype=jnp.int32)
        h_of = self._slot_h(slots)  # [mb]

        # ---- A. block arrivals (on_block, BlockChainNode + roles) ---------
        with scope("notarize"):
            new_blk = jnp.zeros((n, mb), bool).at[to, pay0].max(is_sblk, mode="drop")
            new_blk = new_blk & ~proto["seen"] & proto["blk_exists"][None, :]
            proto["seen"] = proto["seen"] | new_blk

            # fork choice: height-with-incumbent-ties (comparator :107-130)
            key = jnp.where(new_blk, h_of[None, :] * (mb + 1) + (mb - slots[None, :]), -1)
            best_key = jnp.max(key, axis=1)
            best_slot = jnp.where(
                best_key >= 0, mb - (best_key % (mb + 1)), -1
            ).astype(jnp.int32)
            best_h = jnp.where(best_key >= 0, best_key // (mb + 1), 0)
            cur_h = self._head_h(proto["head_slot"])
            adopt = best_h > cur_h
            proto["head_slot"] = jnp.where(adopt, best_slot, proto["head_slot"])
            head_h = self._head_h(proto["head_slot"])

            # attester on_block (:229-236): committee sets + vote reset
            att_new = new_blk & self.is_att[:, None]
            proto["cm_blk"] = proto["cm_blk"] | att_new
            # a height's slots are n_bp neighbours (slot = (h - 1) n_bp + j), so
            # "a block of height h arrived" is an any over each run of n_bp,
            # at columns 1..mh of [n, mh + 2]: no scatter of n x max_b rows
            got_h = jnp.pad(
                jnp.any(att_new.reshape(n, mh, self.n_bp), axis=2), ((0, 0), (1, 1))
            )
            proto["cm_h"] = proto["cm_h"] | got_h
            vreset = jnp.any(
                att_new & (h_of[None, :] == proto["vote_for_h"][:, None]), axis=1
            )
            proto["vote_for_h"] = jnp.where(vreset, -1, proto["vote_for_h"])

        # beacon on_block (:387-410): height advance + exchange/send_rb
        with scope("beacon"):
            bcn_adv = self.is_bcn & jnp.any(new_blk, axis=1) & (head_h == proto["bcn_height"])
            nh = jnp.clip(proto["bcn_height"] + 1, 0, mh + 1)
            proto["bcn_height"] = jnp.where(bcn_adv, nh, proto["bcn_height"])
            h_idx = jnp.where(bcn_adv, nh, 0)
            not_self = ~proto["exch_self"][ids, h_idx]
            add_self = bcn_adv & not_self
            proto["exch_self"] = proto["exch_self"].at[ids, h_idx].max(add_self, mode="drop")
            proto["exch_cnt"] = proto["exch_cnt"].at[ids, h_idx].add(
                add_self.astype(jnp.int32), mode="drop"
            )
            rb_now_a = add_self & (proto["exch_cnt"][ids, h_idx] >= p.majority)
            # not enough exchanges yet: schedule RandomBeaconExchange(newH) to
            # the beacon committee at wt = head.parent.proposalTime + 2*roundTime
            need_exch = bcn_adv & ~rb_now_a
            par = proto["blk_parent"][jnp.clip(proto["head_slot"], 0, mb - 1)]
            par_time = jnp.where(
                proto["head_slot"] < 0,
                0,
                jnp.where(par < 0, 0, proto["blk_time"][jnp.clip(par, 0, mb - 1)]),
            )
            wt = par_time + 2 * p.round_time
            wt = jnp.where(wt <= t, t + p.attestation_construction_time, wt)
            emissions.append(
                FanOut(
                    mask=need_exch[self.bcn_ids],
                    from_idx=self.bcn_ids,
                    receivers=self.bcn_ids,
                    mtype=self.mtype("RBE"),
                    capacity=self.beacon_capacity,
                    payload=nh[self.bcn_ids][:, None],
                    send_time=wt[self.bcn_ids],
                )
            )

        # ---- B. beacon results (on_random_beacon, :133-140) ---------------
        with scope("propose"):
            rbr_h = jnp.zeros(n, jnp.int32).at[to].max(
                jnp.where(is_rbr, payh, 0), mode="drop"
            )
            trig = rbr_h > proto["last_beacon"]
            # rd == height for every beacon (send_rb :274-279), so rd = rbr_h
            rd = rbr_h
            proto["last_beacon"] = jnp.where(trig, rbr_h, proto["last_beacon"])

            # BP: propose when selected and the parent is in hand (:177-181)
            bp_sel = (
                trig
                & self.is_bp
                & (rd % p.block_producers_round == self.my_round)
                & (head_h == rbr_h - 1)
                & (rbr_h <= mh)
            )
            new_slot = jnp.clip((rbr_h - 1) * self.n_bp + self.bp_local, 0, mb - 1)
            w_slot = jnp.where(bp_sel, new_slot, mb)
            proto["blk_exists"] = proto["blk_exists"].at[w_slot].set(True, mode="drop")
            proto["blk_time"] = proto["blk_time"].at[w_slot].set(t, mode="drop")
            proto["blk_parent"] = proto["blk_parent"].at[w_slot].set(
                proto["head_slot"], mode="drop"
            )
            emissions.append(
                FanOut(
                    mask=bp_sel[self.bp_ids],
                    from_idx=self.bp_ids,
                    receivers=self.att_ids,
                    mtype=self.mtype("PROPOSAL"),
                    capacity=self.proposal_capacity,
                    payload=new_slot[self.bp_ids][:, None],
                    send_time=jnp.broadcast_to(
                        t + p.block_construction_time, (self.n_bp,)
                    ).astype(jnp.int32),
                )
            )

        with scope("notarize"):
            # attester committee selection (:238-253)
            att_sel = (
                trig
                & self.is_att
                & (rd % p.attesters_round == self.my_round)
                & ~proto["cm_h"][ids, jnp.clip(rbr_h, 0, mh + 1)]
            )
            proto["vote_for_h"] = jnp.where(att_sel, rbr_h, proto["vote_for_h"])

        with scope("beacon"):
            # beacon: adopt a beacon someone else finished (:308-313)
            bcn_fwd = trig & self.is_bcn & (rbr_h > proto["bcn_height"])
            proto["bcn_last_sent"] = jnp.where(
                bcn_fwd, proto["bcn_height"], proto["bcn_last_sent"]
            )
            proto["bcn_height"] = jnp.where(bcn_fwd, rbr_h, proto["bcn_height"])

        # ---- C+D. proposals (arrived + unbuffered) and votes --------------
        with scope("notarize"):
            prop_ev = jnp.zeros((n, mb), bool).at[to, pay0].max(is_prop, mode="drop")
            # onRandomBeaconOnce replays buffered proposals at the new height
            # then clears the buffer (:243-253)
            at_vh = h_of[None, :] == proto["vote_for_h"][:, None]
            prop_ev = prop_ev | (att_sel[:, None] & proto["prop_buf"] & at_vh)
            proto["prop_buf"] = jnp.where(att_sel[:, None], False, proto["prop_buf"])

            votable = self.is_att[:, None] & at_vh
            do_vote = prop_ev & votable & ~proto["self_voted"]
            proto["self_voted"] = proto["self_voted"] | do_vote
            # buffer future proposals (:225-227)
            buf = prop_ev & self.is_att[:, None] & ~votable & (
                h_of[None, :] > self._head_h(proto["head_slot"])[:, None]
            )
            proto["prop_buf"] = proto["prop_buf"] | buf

            # the broadcast includes the sender (send_all semantics); the oracle
            # drops the self copy via its voter set ('voter not in voters',
            # :197-199) — here the self vote is already counted by do_vote
            vote_ev = jnp.zeros((n, mb), jnp.int32).at[to, pay0].add(
                (is_vote & (frm != to)).astype(jnp.int32), mode="drop"
            )
            vote_ev = jnp.where(votable, vote_ev, 0)  # on_vote height guard (:194-200)
            proto["vote_cnt"] = proto["vote_cnt"] + vote_ev + do_vote.astype(jnp.int32)

            # majority crossings -> notarize ONE block per attester (:202-206)
            crossing = votable & (proto["vote_cnt"] >= p.majority) & (
                do_vote | (vote_ev > 0)
            )
            cross_key = jnp.where(crossing, mb - slots[None, :], 0)
            cw = jnp.argmax(cross_key, axis=1).astype(jnp.int32)
            has_cross = jnp.max(cross_key, axis=1) > 0
            proto["cm_blk"] = proto["cm_blk"].at[ids, cw].max(has_cross, mode="drop")
            proto["cm_h"] = proto["cm_h"].at[
                ids, jnp.clip(self._slot_h(cw), 0, mh + 1)
            ].max(has_cross, mode="drop")
            proto["vote_for_h"] = jnp.where(has_cross, -1, proto["vote_for_h"])
            emissions.append(
                FanOut(
                    mask=has_cross[self.att_ids],
                    from_idx=self.att_ids,
                    receivers=self.all_ids,
                    mtype=self.mtype("SEND_BLOCK"),
                    capacity=self.block_capacity,
                    payload=cw[self.att_ids][:, None],
                )
            )

            # non-crossing self-votes broadcast Vote to the attesters (:216-224);
            # once an attester notarizes, its remaining same-tick votes are
            # dropped (the oracle's sequential processing stops at _send_block's
            # voteForHeight reset).  At most one votable height per attester, so
            # n_bp candidate slots: one send event a producer's slot j, and the
            # fan-out compacts over the (j, attester) pairs that fire
            vote_out = do_vote & ~has_cross[:, None]
            vh = jnp.clip(proto["vote_for_h"], 1, mh)
            sl = jnp.clip(
                (vh[None, :] - 1) * self.n_bp + jnp.arange(self.n_bp, dtype=jnp.int32)[:, None],
                0, mb - 1,
            )  # [n_bp, n]: slot j of each node's votable height
            # vote_out holds nothing off its node's votable height (`votable`),
            # so a node's slot j there is any-over-heights of slot j: a
            # reduction, where `vote_out[ids, sl]` is an indexed read
            m = jnp.any(vote_out.reshape(n, mh, self.n_bp), axis=1).T & self.is_att[None, :]
            pairs = self.n_bp * self.n_att
            emissions.append(
                FanOut(
                    mask=m[:, self.att_ids].reshape(pairs),
                    from_idx=jnp.tile(self.att_ids, self.n_bp),
                    receivers=self.att_ids,
                    mtype=self.mtype("VOTE"),
                    capacity=self.vote_capacity,
                    payload=sl[:, self.att_ids].reshape(pairs, 1),
                    send_time=jnp.broadcast_to(
                        t + p.attestation_construction_time, (pairs,)
                    ).astype(jnp.int32),
                    events=self.n_bp,
                )
            )

        # ---- E. beacon exchanges (:266-272) -------------------------------
        with scope("beacon"):
            # self copy dropped: the sender added itself at height advance
            # (exchanged set dedup, Dfinity.java:268-271)
            # an exchange counts by its receiver's height and last result
            # alone, so the arrivals are counted by (receiver, height) first
            # and the receiver's conditions applied to the counts: one pass
            # over the view, no read of a receiver's state a message
            heights = jnp.arange(mh + 2, dtype=jnp.int32)[None, :]
            rbe_ev = jnp.zeros((n, mh + 2), jnp.int32).at[to, payh].add(
                (is_rbe & (frm != to)).astype(jnp.int32), mode="drop"
            )
            rbe_ev = jnp.where(
                self.is_bcn[:, None]
                & (heights >= proto["bcn_height"][:, None])
                & (heights > proto["bcn_last_sent"][:, None]),
                rbe_ev, 0,
            )
            proto["exch_cnt"] = proto["exch_cnt"] + rbe_ev
            rb_now_b = (
                self.is_bcn
                & (
                    proto["exch_cnt"][ids, jnp.clip(proto["bcn_height"], 0, mh + 1)]
                    >= p.majority
                )
                & (proto["bcn_height"] > proto["bcn_last_sent"])
                & (jnp.any(rbe_ev > 0, axis=1) | rb_now_a)
            )
            proto["bcn_last_sent"] = jnp.where(
                rb_now_b, proto["bcn_height"], proto["bcn_last_sent"]
            )
            bh = proto["bcn_height"][self.bcn_ids]
            emissions.append(
                FanOut(
                    mask=rb_now_b[self.bcn_ids],
                    from_idx=self.bcn_ids,
                    receivers=self.all_ids,
                    mtype=self.mtype("RBR"),
                    capacity=self.beacon_capacity,
                    payload=bh[:, None],
                    send_time=jnp.broadcast_to(
                        t + p.attestation_construction_time, (self.n_bcn,)
                    ).astype(jnp.int32),
                )
            )

        with scope("notarize"):
            proto["chain_score"] = self._chain_score(proto)
        return state._replace(proto=proto), emissions

    def _chain_score(self, proto):
        """Head height x (attesters_per_round + 1) + the most votes the
        node has counted for one block (DfinityNode.chain_score)."""
        return self._head_h(proto["head_slot"]) * (
            self.params.attesters_per_round + 1
        ) + jnp.max(proto["vote_cnt"], axis=1)

    def all_done(self, state):
        return jnp.asarray(False)  # Dfinity runs open-ended, like the oracle

    def head_height(self, state):
        """Per-node head height (the print_stat observable)."""
        return self._head_h(state.proto["head_slot"])


def store_plan(n_nodes: int, attesters_per_round: int) -> dict:
    """The message store's sizes by rule from the deployment's shape
    (`make_dfinity(capacity=None)`); this repo's sizing, not the source's.

    A wave is one committee's broadcast to every node, `attesters_per_round
    x n_nodes` rows (the beacon results, the notarised blocks; the votes
    are five such to the attesters), and under a latency model of few
    distinct values it lands in a handful of milliseconds, waves
    overlapping: the fullest ms read is 0.65 of a wave (172,689 rows at
    4096 attesters in committees of 64 under IC3NetworkLatency, 1987 at
    256 in committees of 16; the reference DES, sandbox, PR 43).

    - `wheel_rows` 256: every registered model's bulk (IC3's largest value
      is 175 ms); a longer delay takes the lane, exactly.
    - `wheel_slots`: three quarters of a wave, up to a power of two; a
      fuller ms spills into the lane and is counted there, never dropped
      while the lane has room.
    - `overflow_capacity`: twice the beacon committee's exchange
      (`attesters_per_round` squared rows, real messages sent up to two
      rounds ahead), up to a power of two, for them and a spill.
    - `due_view_rows`: a step views the leading 1/64 or 1/8 of the due
      wheel row where its entries fit, the whole row where they do not
      (half the executed steps of a block hold under 4096 rows, an eighth
      over 32,768, at the full width).
    """
    pow2 = lambda x: 1 << max(0, int(np.ceil(np.log2(max(1, x)))))
    wave = attesters_per_round * n_nodes
    slots = max(256, pow2(3 * wave / 4))
    return {
        "wheel_rows": 256,
        "wheel_slots": slots,
        "overflow_capacity": max(256, pow2(2 * attesters_per_round**2)),
        "due_view_rows": (slots // 64, slots // 8),
    }


def make_dfinity(
    params: Optional[DfinityParameters] = None,
    max_heights: int = 64,
    capacity: Optional[int] = None,
    seed: int = 0,
    latency_name: Optional[str] = None,
    population_seed: Optional[int] = None,
):
    """Host-side construction: the oracle builds the node population (same
    RNG stream — observer, attesters, producers, beacons in id order).
    `capacity` None sizes the store by `store_plan`; an int is the
    engine's historical in-flight budget (its default wheel, no due
    view).
    `population_seed` seeds the oracle's generator before it builds the
    nodes, as a caller of the oracle does (`network().rd.set_seed(s)`,
    then `init()`): the same positions and producer order on both sides.
    Parameters that state a `population_seed` of their own
    (`PartitionedDfinityParameters`) have their oracle's `init()` seed
    the generator itself, after this one and in its place.

    `PartitionedDfinityParameters` (protocols/dfinity_part.py: upstream's
    `main()`, a fifth of the network cut off) builds the population from
    that oracle and returns the initial state with the line set
    (`BatchedNetwork.partition`, at `int(MAX_X * params.partition)`; none
    for a `partition` of 0), drawn as the oracle's `init()` draws it:
    before the beacon's first results leave, so that they are masked
    where they are sent like every later message.  The line is data in
    `state.partition_x`: the network and its compiled program are the
    ones `DfinityParameters` of the same shape gives, and who is behind
    the line is the population's."""
    params = params or DfinityParameters()
    partitioned = isinstance(params, PartitionedDfinityParameters)
    oracle = PartitionedDfinity(params) if partitioned else Dfinity(params)
    if population_seed is not None:
        oracle.network().rd.set_seed(population_seed)
    oracle.init()
    net_o = oracle.network()
    nodes = net_o.all_nodes
    n = len(nodes)

    roles = {
        "is_att": np.array([isinstance(nd, AttesterNode) for nd in nodes]),
        "is_bp": np.array([isinstance(nd, BlockProducerNode) for nd in nodes]),
        "is_bcn": np.array([isinstance(nd, RandomBeaconNode) for nd in nodes]),
        "my_round": np.array(
            [getattr(nd, "my_round", 0) for nd in nodes], dtype=np.int32
        ),
        "bp_local": np.full(n, -1, dtype=np.int32),
        "att_ids": np.array(
            [nd.node_id for nd in nodes if isinstance(nd, AttesterNode)],
            dtype=np.int32,
        ),
        "bp_ids": np.array(
            [nd.node_id for nd in nodes if isinstance(nd, BlockProducerNode)],
            dtype=np.int32,
        ),
        "bcn_ids": np.array(
            [nd.node_id for nd in nodes if isinstance(nd, RandomBeaconNode)],
            dtype=np.int32,
        ),
    }
    for j, nid in enumerate(roles["bp_ids"]):
        roles["bp_local"][nid] = j

    # the reference never applies networkLatencyName (Dfinity.java:86-90);
    # callers pick the model explicitly, like DfinityTest does
    latency = registry_network_latencies.get_by_name(latency_name)
    city_index = getattr(latency, "city_index", None)
    cols = build_node_columns(nodes, city_index)
    proto = BatchedDfinity(params, roles, max_heights)
    if capacity is None:
        store = store_plan(n, params.attesters_per_round)
        capacity = store["wheel_rows"] * store["wheel_slots"] // 2
    else:
        store = {}
    net = BatchedNetwork(proto, latency, n, capacity=capacity, **store)
    line = (params.partition or None) if partitioned else None  # 0, or no such parameter: no line
    state = net.init_state(cols, seed=seed, proto=proto.proto_init(n), partition=line)
    return net, state
