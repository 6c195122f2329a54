"""Batched CasperIMD: beacon-chain stage-1 fork choice on the batched
engine — slot-aligned block producers, attester committees, GHOST-like
attestation counting.

Reference semantics: protocols/CasperIMD.java (Attestation :105-149, fork
choice best/countAttestations :204-288, onBlock/onAttestation lazy
reevaluation :298-353, buildBlock :383-428, init schedule :472-508,
default ByzBlockProducerWF(0) producer :647-707) via the oracle port
`protocols/casper.py`.

TPU-first design — everything is a HEIGHT:

  * heights are unique per block by construction (producer i owns heights
    ≡ i+1 mod bpc; same-height forks are "slashable, unsupported",
    CasperIMD.java:214), so the block table is indexed BY height:
    exists/parent/time columns `[mH]`, genesis at 0;
  * ancestry is a dense `anc[mH, mH]` bool matrix updated incrementally
    at block creation (`anc[h] = anc[parent] | onehot(parent)`) — the
    reference's pointer walks (firstCommonAncestor, hasDirectLink,
    Attestation.hs construction) all become row ops:
      - first common ancestor of (a, b) = argmax height of anc[a] & anc[b]
      - attests(att, H)  =  anc[att_head, H] & (H >= att_head - cl)
        (hs = strict ancestors of the head within cycleLength, :113-119)
  * countAttestations(start, H) = one [N, mH] x [mH, mA] mat-product:
    branch row (anc[start] | start, heights > H) against the block
    inclusion matrix `blk_att[mH, mA]` windowed by att_height < cur,
    OR'd with directly-received attestations whose head lies on the
    branch — the count lands on the MXU instead of a pointer chase;
  * the periodic production/vote schedule (init :472-508) runs as size-0
    self-messages with explicit arrivals that re-arm themselves, so the
    engine's empty-ms jump skips the 8-second slots (TICK_INTERVAL None);
  * one attester committee votes per slot and its members share one
    arrival tick, so the attestation broadcast emission is [apr x N]
    rows, not [attesters x N];
  * the default init's producer 0 is ByzBlockProducerWF(delay=0)
    (:647-707): it waits for the parent block and replies at
    perfect_date = SLOT * toSend via a TWFB self-message.

Approximations (documented): tie-breaks compare (proposal_time, height)
instead of creation ids; `random_on_ties` uses the counter hash; the
oracle's same-ms LIFO interleavings of task vs arrival are simultaneous.

Byzantine producer variants (make_casper byz_variant/byz_delay): besides
the default "wf" (ByzBlockProducerWF :647-707), the head-start producer
"delay" (ByzBlockProducer :511-580 — fires delay ms into its slot and
builds on the best ancestor below toSend), "sf" (ByzBlockProducerSF
:583-604 — skips its direct father to steal its transactions), and "ns"
(ByzBlockProducerNS :610-640 — skips its father when the father skipped
the grandfather).  All run on the batched path, so Byzantine sweeps for
the blockchain family are replica-parallel like Handel's.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core.node import build_node_columns
from ..core.registries import registry_network_latencies
from ..engine import BatchedNetwork, BatchedProtocol, Emission
from ..engine.rng import hash32
from .casper import SLOT_DURATION, Attester, BlockProducer, CasperIMD, CasperParameters

# what this family's deliver does beside the store (the global block table
# by height, the dense ancestry matrix, the attestation planes), nested
# under the phase that delivers
CHAIN_SCOPES = {
    "forkchoice": "witt.chain.forkchoice",  # best / countAttestations: the [N, mH] x [mH, mA] product, the rec_att reads
    "build": "witt.chain.build",  # buildBlock: the included-attestation product, the block table and ancestry writes, the BLOCK rows
    "attest": "witt.chain.attest",  # a committee's vote: the attestation table's writes and the [apr x N] ATT rows
}


class BatchedCasper(BatchedProtocol):
    MSG_TYPES = ["BLOCK", "ATT", "TBP", "TATT", "TWF", "TWFB", "TBYZ"]
    PAYLOAD_WIDTH = 2
    TICK_INTERVAL = None  # all timing is explicit-arrival self-messages
    REQUIRED_SCOPES = tuple(CHAIN_SCOPES.values())  # simlint SL601 holds them live

    def __init__(
        self,
        params: CasperParameters,
        roles: dict,
        max_heights: int,
        byz_variant: str = "wf",
        byz_delay: int = 0,
    ):
        if byz_variant not in ("wf", "delay", "sf", "ns"):
            raise ValueError(f"unknown byz_variant {byz_variant!r}")
        self.byz_variant = byz_variant
        self.byz_delay = byz_delay
        self.params = params
        self.mh = max_heights
        self.apr = params.attesters_per_round
        self.cl = params.cycle_length
        self.bpc = params.block_producers_count
        self.ma = max_heights * self.apr  # attestation slots: (h-1)*apr + j
        self.n_nodes = int(roles["n_nodes"])
        self.is_att = jnp.asarray(roles["is_att"])
        self.is_bp = jnp.asarray(roles["is_bp"])  # honest producers (not bp0)
        self.bp0 = int(roles["bp0"])  # the default WF producer's node id
        self.att_ids = jnp.asarray(roles["att_ids"], jnp.int32)
        self.att_cidx = jnp.asarray(roles["att_cidx"], jnp.int32)  # i // cl
        self.committee = jnp.asarray(roles["committee"], jnp.int32)  # [cl, apr]
        self.prod_ids = jnp.asarray(roles["prod_ids"], jnp.int32)  # bp0 + honest
        self.all_ids = jnp.arange(self.n_nodes, dtype=jnp.int32)
        # static window matrix: attestation a may sit in block cur's count
        # window only when att_h(a) < cur (heights [H+1, cur-1], :271-276)
        att_h = np.arange(self.ma) // self.apr + 1
        self.att_h = jnp.asarray(att_h, jnp.int32)
        self.win = jnp.asarray(
            att_h[None, :] < np.arange(max_heights)[:, None]
        )  # [mH, mA]

    def msg_size(self, mtype: int) -> int:
        return 1 if self.MSG_TYPES[mtype] in ("BLOCK", "ATT") else 0

    def proto_init(self, n_nodes: int):
        mh, ma, n = self.mh, self.ma, n_nodes
        seen = jnp.zeros((n, mh), bool).at[:, 0].set(True)  # genesis known
        return {
            # global block table (one block per height; 0 = genesis)
            "blk_exists": jnp.zeros(mh, bool).at[0].set(True),
            "blk_parent": jnp.full(mh, -1, jnp.int32),
            "blk_time": jnp.zeros(mh, jnp.int32),
            "anc": jnp.zeros((mh, mh), bool),
            "blk_att": jnp.zeros((mh, ma), bool),
            # global attestation table
            "att_exists": jnp.zeros(ma, bool),
            "att_head": jnp.zeros(ma, jnp.int32),
            # per-node state
            "head": jnp.zeros(n, jnp.int32),
            # (head, countAttestations for it) as one integer: `_head_score`
            "head_score": jnp.zeros(n, jnp.int32),
            "seen": seen,
            "rec_att": jnp.zeros((n, ma), bool),
            "reeval": jnp.zeros((n, mh), bool),
            # ByzBlockProducer* bookkeeping (row bp0 only; :511-707):
            # wf_to_send doubles as every variant's toSend cursor
            "wf_to_send": jnp.full(n, 1, jnp.int32),
            "wf_late": jnp.zeros(n, jnp.int32),
            "wf_on_time": jnp.zeros(n, jnp.int32),
            "byz_direct": jnp.zeros(n, jnp.int32),  # onDirectFather
            "byz_older": jnp.zeros(n, jnp.int32),  # onOlderAncestor
            "byz_skipped": jnp.zeros(n, jnp.int32),  # NS skipped
        }

    # -- fork choice ---------------------------------------------------------
    def _count(self, proto, rec_att, start, hcn):
        """countAttestations(start, H) vectorized over nodes
        (CasperIMD.java:262-288).  start/hcn are [N] heights."""
        mh = self.mh
        hrange = jnp.arange(mh, dtype=jnp.int32)
        branch = (
            proto["anc"][start] | jax.nn.one_hot(start, mh, dtype=bool)
        ) & (hrange[None, :] > hcn[:, None])
        # from blocks: exists cur on the branch including a within window
        inc = (proto["blk_att"] & self.win).astype(jnp.int32)
        from_blocks = (branch.astype(jnp.int32) @ inc) > 0  # [N, mA]
        from_blocks = from_blocks & (self.att_h[None, :] > hcn[:, None])
        # from direct reception: attestation's head lies on the branch
        from_recv = rec_att & branch[:, proto["att_head"]]
        # attests(H): H strict ancestor of the head, within cycleLength
        att_ok = (
            proto["att_exists"][None, :]
            & proto["anc"][proto["att_head"]][:, hcn].T  # [N, mA]
            & (hcn[:, None] >= proto["att_head"][None, :] - self.cl)
        )
        return jnp.sum(att_ok & (from_blocks | from_recv), axis=1).astype(jnp.int32)

    def _head_score(self, proto):
        """(head height, head votes) as one integer, the height first
        (the oracle's `CasperNode.head_score`).  The votes are `_count`
        for the head against its ancestor `cycle_length` blocks back
        (genesis where the chain is shorter): what `_best` would weigh for
        this head in a fork at that ancestor.  No fork choice reads it;
        with one block a slot `_best` is decided by the direct link, and
        this leaf is where the count's value can be held against the
        reference."""
        head = proto["head"]
        hr = jnp.arange(self.mh, dtype=jnp.int32)
        anc = proto["anc"][head]  # [N, mH]: the head's strict ancestors
        from_top = jnp.cumsum(anc[:, ::-1], axis=1)[:, ::-1]  # ancestors at this height or above
        back = jnp.min(
            jnp.where(anc & (from_top <= self.cl), hr[None, :], self.mh), axis=1
        )
        hcn = jnp.where(back == self.mh, 0, back).astype(jnp.int32)  # genesis has none
        votes = self._count(proto, proto["rec_att"], head, hcn)
        return head * (self.apr * self.cl + 1) + votes

    def _best(self, state, proto, rec_att, o1, o2, mask):
        """Vectorized pairwise best(o1, o2) (CasperIMD.java:204-257)."""
        p = self.params
        anc = proto["anc"]
        same = o1 == o2
        direct = anc[o1, o2] | anc[o2, o1]
        hi = jnp.maximum(o1, o2)
        # first common (strict) ancestor
        common = anc[o1] & anc[o2]
        hr = jnp.arange(self.mh, dtype=jnp.int32)
        hcn = jnp.max(jnp.where(common, hr[None, :], 0), axis=1).astype(jnp.int32)
        v1 = self._count(proto, rec_att, o1, hcn)
        v2 = self._count(proto, rec_att, o2, hcn)
        if p.random_on_ties:
            coin = (
                hash32(state.seed, state.time, self.all_ids, o1, o2) & 1
            ) == 0
            tie = jnp.where(coin, o1, o2)
        else:
            k1 = proto["blk_time"][o1] * self.mh + o1
            k2 = proto["blk_time"][o2] * self.mh + o2
            tie = jnp.where(k1 >= k2, o1, o2)
        by_votes = jnp.where(v1 > v2, o1, jnp.where(v2 > v1, o2, tie))
        win = jnp.where(same, o1, jnp.where(direct, hi, by_votes))
        return jnp.where(mask, win, o1)

    def _reevaluate(self, state, proto, nodes_mask):
        """Lazy head re-election: fold best over the pending candidates
        (reevaluateHead, CasperIMD.java:348-353)."""
        rec_att = proto["rec_att"]

        def body(i, carry):
            head, reeval = carry
            cand = reeval[:, i] & nodes_mask
            head = self._best(
                state, proto, rec_att, head, jnp.full_like(head, i), cand
            )
            return head, reeval

        def fold():
            head, _ = lax.fori_loop(1, self.mh, body, (proto["head"], proto["reeval"]))
            return head, jnp.where(nodes_mask[:, None], False, proto["reeval"])

        # nobody acts on most executed steps (arrivals alone): the fold
        # would change no head and clear no candidate, so it is not run
        head, reeval = lax.cond(
            jnp.any(nodes_mask), fold, lambda: (proto["head"], proto["reeval"])
        )
        return dict(proto, head=head, reeval=reeval)

    # -- block building (buildBlock, :383-428) -------------------------------
    _BUILD_WRITES = (
        "blk_exists", "blk_parent", "blk_time", "anc", "blk_att", "head", "seen",
    )

    def _build_blocks(self, state, proto, mask, base, height):
        """`_build` where a producer of `mask` fires, and nothing where
        none does (all but two or three executed steps a slot): the tables
        as they are and BLOCK rows that are all masked out, which is what
        `_build` returns for an empty mask."""

        def build():
            new, em = self._build(state, proto, mask, base, height)
            return {k: new[k] for k in self._BUILD_WRITES}, (em.mask, em.payload)

        def skip():
            kp = self.prod_ids.shape[0] * self.n_nodes
            return {k: proto[k] for k in self._BUILD_WRITES}, (
                jnp.zeros(kp, bool), jnp.zeros((kp, 2), jnp.int32))

        writes, (em_mask, em_payload) = lax.cond(jnp.any(mask), build, skip)
        return dict(proto, **writes), self._block_rows(state, em_mask, em_payload)

    def _block_rows(self, state, mask, payload):
        """BLOCK broadcast rows restricted to the (few, static) producer ids."""
        kp = self.prod_ids.shape[0] * self.n_nodes
        return Emission(
            mask=mask,
            from_idx=jnp.repeat(self.prod_ids, self.n_nodes),
            to_idx=jnp.tile(self.all_ids, self.prod_ids.shape[0]),
            mtype=self.mtype("BLOCK"),
            payload=payload,
            send_time=jnp.broadcast_to(
                state.time + self.params.block_construction_time, (kp,)
            ).astype(jnp.int32),
        )

    def _build(self, state, proto, mask, base, height):
        """Producers in `mask` create block `height[n]` on parent `base[n]`:
        include every received attestation on the parent chain (within the
        cycle window) not already included in it."""
        mh = self.mh
        t = state.time
        hrange = jnp.arange(mh, dtype=jnp.int32)
        # parent-chain blocks within the window [height - cl, ...]
        chain = (
            proto["anc"][base] | jax.nn.one_hot(base, mh, dtype=bool)
        ) & (hrange[None, :] >= (height - self.cl)[:, None]) & (hrange[None, :] > 0)
        chain32 = chain.astype(jnp.int32)
        included = (chain32 @ proto["blk_att"].astype(jnp.int32)) > 0  # [N, mA]
        head_on_chain = chain[:, proto["att_head"]]  # [N, mA]
        mine = (
            proto["rec_att"]
            & head_on_chain
            & (self.att_h[None, :] < height[:, None])
            & ~included
        )
        # genesis-headed attestations: head 0 is never on `chain` (height>0
        # filter) but the oracle's walk does visit down to the window edge;
        # head==0 attestations only exist for votes made on genesis
        mine0 = (
            proto["rec_att"]
            & (proto["att_head"][None, :] == 0)
            & (0 >= height - self.cl)[:, None]
            & (self.att_h[None, :] < height[:, None])
            & ~included
        )
        mine = mine | mine0

        # scatter the new blocks into the global tables (heights unique)
        w_h = jnp.where(mask, height, mh)  # OOB -> dropped
        proto = dict(proto)
        proto["blk_exists"] = proto["blk_exists"].at[w_h].set(True, mode="drop")
        proto["blk_parent"] = proto["blk_parent"].at[w_h].set(base, mode="drop")
        proto["blk_time"] = proto["blk_time"].at[w_h].set(t, mode="drop")
        anc_new = proto["anc"][base] | jax.nn.one_hot(base, mh, dtype=bool)
        proto["anc"] = proto["anc"].at[w_h].set(anc_new, mode="drop")
        proto["blk_att"] = proto["blk_att"].at[w_h].set(mine, mode="drop")
        # the producer's head becomes its new block immediately (:425-427)
        proto["head"] = jnp.where(mask, height, proto["head"])
        proto["seen"] = proto["seen"].at[self.all_ids, w_h].set(True, mode="drop")

        kp = self.prod_ids.shape[0] * self.n_nodes
        em = self._block_rows(
            state,
            jnp.repeat(mask[self.prod_ids], self.n_nodes),
            jnp.stack(
                [
                    jnp.repeat(height[self.prod_ids], self.n_nodes),
                    jnp.zeros(kp, jnp.int32),
                ],
                axis=1,
            ),
        )
        return proto, em

    def initial_emissions(self, net, state):
        """The init task schedule (CasperIMD.java:472-508) as explicit
        arrivals: bp0 (WF) at SLOT, honest producer i at SLOT*(i+1),
        attester committee c at SLOT*(1+c)+4000."""
        n = self.n_nodes
        ids = self.all_ids
        arr_bp = jnp.where(
            self.is_bp, SLOT_DURATION * (ids - self.bp0 + 1), 1
        ).astype(jnp.int32)
        if self.byz_variant == "wf":
            em0 = Emission(  # WF producer kick-off tick
                mask=ids == self.bp0,
                from_idx=ids,
                to_idx=ids,
                mtype=self.mtype("TWF"),
                payload=jnp.zeros((n, 2), jnp.int32),
                arrival=jnp.full(n, SLOT_DURATION, jnp.int32),
            )
        else:
            # delay/sf/ns: periodic at SLOT + delay, period SLOT*bpc
            # (init registration, CasperIMD.java:486-492)
            em0 = Emission(
                mask=ids == self.bp0,
                from_idx=ids,
                to_idx=ids,
                mtype=self.mtype("TBYZ"),
                payload=jnp.zeros((n, 2), jnp.int32),
                arrival=jnp.full(n, SLOT_DURATION + self.byz_delay, jnp.int32),
            )
        ems = [
            em0,
            Emission(
                mask=self.is_bp,
                from_idx=ids,
                to_idx=ids,
                mtype=self.mtype("TBP"),
                payload=jnp.zeros((n, 2), jnp.int32),
                arrival=arr_bp,
            ),
        ]
        cidx = jnp.zeros(n, jnp.int32)
        cidx = cidx.at[self.att_ids].set(
            jnp.asarray(
                np.arange(len(np.asarray(self.att_ids))) % self.cl, jnp.int32
            )
        )
        arr_att = (SLOT_DURATION * (1 + cidx) + 4000).astype(jnp.int32)
        ems.append(
            Emission(
                mask=self.is_att,
                from_idx=ids,
                to_idx=ids,
                mtype=self.mtype("TATT"),
                payload=jnp.zeros((n, 2), jnp.int32),
                arrival=arr_att,
            )
        )
        return ems

    # -- per-event processing ------------------------------------------------
    def deliver(self, net, state, deliver_mask):
        p = self.params
        proto = dict(state.proto)
        n, mh, ma = self.n_nodes, self.mh, self.ma
        t = state.time
        ids = self.all_ids
        to = state.msg_to
        pay0 = state.msg_payload[:, 0]
        pay1 = state.msg_payload[:, 1]
        m_ = lambda s: deliver_mask & (state.msg_type == self.mtype(s))
        is_blk, is_att = m_("BLOCK"), m_("ATT")
        is_tbp, is_tatt, is_twf, is_twfb = m_("TBP"), m_("TATT"), m_("TWF"), m_("TWFB")
        emissions = []
        slot_now = (t // SLOT_DURATION).astype(jnp.int32)

        # ---- 1. attestation arrivals (onAttestation, :316-337) ------------
        h0 = jnp.clip(pay0, 0, ma - 1)
        new_att = jnp.zeros((n, ma), bool).at[to, h0].max(is_att, mode="drop")
        new_att = new_att & proto["att_exists"][None, :]
        proto["rec_att"] = proto["rec_att"] | new_att
        # reevaluate the attested head when the block is known; the
        # [N, mA] x [mA, mH] product beats a 2D scatter on TPU
        head_oh = jax.nn.one_hot(proto["att_head"], mh, dtype=jnp.int32)
        att_heads_hit = (new_att.astype(jnp.int32) @ head_oh) > 0
        proto["reeval"] = proto["reeval"] | (att_heads_hit & proto["seen"])

        # ---- 2. block arrivals (onBlock, :298-314; slot gate is dead
        # code in the reference — delta sign bug kept verbatim) -------------
        bh = jnp.clip(pay0, 0, mh - 1)
        new_blk = jnp.zeros((n, mh), bool).at[to, bh].max(is_blk, mode="drop")
        new_blk = new_blk & ~proto["seen"] & proto["blk_exists"][None, :]
        got_blk = jnp.any(new_blk, axis=1)
        proto["seen"] = proto["seen"] | new_blk
        # reevaluate old head later; immediate pairwise best against the
        # highest new block (BlockChainNode.onBlock head update)
        hr = jnp.arange(mh, dtype=jnp.int32)
        best_new = jnp.max(jnp.where(new_blk, hr[None, :], 0), axis=1).astype(jnp.int32)
        proto["reeval"] = proto["reeval"] | (
            jax.nn.one_hot(proto["head"], mh, dtype=bool) & got_blk[:, None]
        )
        proto["reeval"] = proto["reeval"] | new_blk
        with net._scope("forkchoice", CHAIN_SCOPES):
            # a step on which no block arrives (the wave's) has no pair to weigh
            proto["head"] = lax.cond(
                jnp.any(got_blk),
                lambda: self._best(
                    state, proto, proto["rec_att"], proto["head"], best_new, got_blk
                ),
                lambda: proto["head"],
            )

        if self.byz_variant == "wf":
            # WF producer response (:660-676): fires when the awaited parent
            # (toSend-1) is among THIS tick's new blocks — membership, not
            # the max, so a same-tick higher block cannot mask it
            want = jnp.clip(proto["wf_to_send"] - 1, 0, mh - 1)
            wf_hit = (ids == self.bp0) & new_blk[ids, want]
            th = proto["wf_to_send"]
            perfect = SLOT_DURATION * th + self.byz_delay
            fire_now = wf_hit & (t >= perfect)
            fire_later = wf_hit & ~fire_now
            proto["wf_late"] = proto["wf_late"] + fire_now.astype(jnp.int32)
            proto["wf_on_time"] = proto["wf_on_time"] + fire_later.astype(jnp.int32)
            proto["wf_to_send"] = jnp.where(wf_hit, th + self.bpc, proto["wf_to_send"])
            emissions.append(
                Emission(  # the scheduled build (registerTask(r, perfectDate))
                    mask=wf_hit,
                    from_idx=ids,
                    to_idx=ids,
                    mtype=self.mtype("TWFB"),
                    payload=jnp.stack([want, th], axis=1),
                    arrival=jnp.maximum(perfect, t + 1).astype(jnp.int32),
                )
            )

            # ---- 3. WF kick-off (periodic while nothing produced, :692-698)
            twf = jnp.zeros(n, bool).at[to].max(is_twf, mode="drop")
            wf_kick = twf & (proto["head"] == 0) & (proto["wf_to_send"] == 1)
            proto["wf_to_send"] = jnp.where(wf_kick, 1 + self.bpc, proto["wf_to_send"])
            emissions.append(
                Emission(  # re-arm the kick-off watchdog
                    mask=twf,
                    from_idx=ids,
                    to_idx=ids,
                    mtype=self.mtype("TWF"),
                    payload=jnp.zeros((n, 2), jnp.int32),
                    arrival=jnp.broadcast_to(
                        t + SLOT_DURATION * self.bpc, (n,)
                    ).astype(jnp.int32),
                )
            )
        else:
            twf = jnp.zeros(n, bool)
            wf_kick = jnp.zeros(n, bool)

        # ---- 4. honest producers fire (reevaluate + build, :365-381) ------
        tbp = jnp.zeros(n, bool).at[to].max(is_tbp, mode="drop")
        emissions.append(
            Emission(
                mask=tbp,
                from_idx=ids,
                to_idx=ids,
                mtype=self.mtype("TBP"),
                payload=jnp.zeros((n, 2), jnp.int32),
                arrival=jnp.broadcast_to(
                    t + SLOT_DURATION * self.bpc, (n,)
                ).astype(jnp.int32),
            )
        )

        # ---- 5. attesters fire (vote at 4 s, :444-464) --------------------
        tatt = jnp.zeros(n, bool).at[to].max(is_tatt, mode="drop")
        emissions.append(
            Emission(
                mask=tatt,
                from_idx=ids,
                to_idx=ids,
                mtype=self.mtype("TATT"),
                payload=jnp.zeros((n, 2), jnp.int32),
                arrival=jnp.broadcast_to(
                    t + SLOT_DURATION * self.cl, (n,)
                ).astype(jnp.int32),
            )
        )

        # byz head-start producers (delay/sf/ns) fire on their own beat
        is_tbyz = m_("TBYZ")
        tbyz = jnp.zeros(n, bool).at[to].max(is_tbyz, mode="drop")

        # one reevaluation pass for every node acting this tick
        acting = tbp | tatt | twf | tbyz
        with net._scope("forkchoice", CHAIN_SCOPES):
            proto = self._reevaluate(state, proto, acting)

        # honest production: height = slot index (:370-377)
        produce = tbp & (slot_now < mh)
        with net._scope("build", CHAIN_SCOPES):
            proto, em_b = self._build_blocks(
                state, proto, produce, proto["head"], jnp.broadcast_to(slot_now, (n,))
            )
        emissions.append(em_b)

        if self.byz_variant == "wf":
            # WF kick-off build: block 1 on genesis (reevaluateH at genesis)
            with net._scope("build", CHAIN_SCOPES):
                proto, em_k = self._build_blocks(
                    state,
                    proto,
                    wf_kick,
                    jnp.zeros(n, jnp.int32),
                    jnp.ones(n, jnp.int32),
                )
            emissions.append(em_k)

            # ---- 6. WF scheduled build lands (r(), :663-668) --------------
            twfb = jnp.zeros(n, bool).at[to].max(is_twfb, mode="drop")
            wf_base = jnp.zeros(n, jnp.int32).at[to].max(
                jnp.where(is_twfb, pay0, 0), mode="drop"
            )
            wf_th = jnp.zeros(n, jnp.int32).at[to].max(
                jnp.where(is_twfb, pay1, 0), mode="drop"
            )
            with net._scope("build", CHAIN_SCOPES):
                proto, em_w = self._build_blocks(
                    state, proto, twfb & (wf_th < mh), wf_base, wf_th
                )
            emissions.append(em_w)
        else:
            # ---- 6'. byz producer fires (reevaluateH + variant head tweak
            # + build, CasperIMD.java:529-542 + :285-300/:318-327/:342-356)
            th = proto["wf_to_send"]
            hr2 = jnp.arange(mh, dtype=jnp.int32)
            # deepest ancestor of head strictly below toSend (the
            # while-head.height>=toSend parent walk)
            head_oh2 = jax.nn.one_hot(proto["head"], mh, dtype=bool)
            cand = (proto["anc"][proto["head"]] | head_oh2) & (
                hr2[None, :] < th[:, None]
            )
            base = jnp.max(jnp.where(cand, hr2[None, :], 0), axis=1).astype(jnp.int32)
            direct = base == th - 1
            if self.byz_variant == "sf":
                # skip the direct father to steal its transactions
                skip = tbyz & (base != 0) & direct
                base = jnp.where(
                    skip, jnp.clip(proto["blk_parent"][base], 0, mh - 1), base
                )
                proto["byz_direct"] = proto["byz_direct"] + (tbyz & skip).astype(jnp.int32)
                proto["byz_older"] = proto["byz_older"] + (tbyz & ~skip).astype(jnp.int32)
            elif self.byz_variant == "ns":
                # skip the father when the father skipped the grandfather
                gp = jnp.clip(proto["blk_parent"][base], 0, mh - 1)
                cond = (
                    tbyz
                    & (base != 0)
                    & direct
                    & (gp == th - 3)
                    & proto["seen"][ids, jnp.clip(th - 2, 0, mh - 1)]
                    & proto["blk_exists"][jnp.clip(th - 2, 0, mh - 1)]
                )
                base = jnp.where(cond, jnp.clip(th - 2, 0, mh - 1), base)
                proto["byz_skipped"] = proto["byz_skipped"] + cond.astype(jnp.int32)
            else:  # plain delay: counters only
                proto["byz_direct"] = proto["byz_direct"] + (tbyz & direct).astype(
                    jnp.int32
                )
                proto["byz_older"] = proto["byz_older"] + (tbyz & ~direct).astype(
                    jnp.int32
                )
            with net._scope("build", CHAIN_SCOPES):
                proto, em_z = self._build_blocks(
                    state, proto, tbyz & (th < mh), base, th
                )
            emissions.append(em_z)
            proto["wf_to_send"] = jnp.where(tbyz, th + self.bpc, proto["wf_to_send"])
            emissions.append(
                Emission(  # re-arm the byz beat
                    mask=tbyz,
                    from_idx=ids,
                    to_idx=ids,
                    mtype=self.mtype("TBYZ"),
                    payload=jnp.zeros((n, 2), jnp.int32),
                    arrival=jnp.broadcast_to(
                        t + SLOT_DURATION * self.bpc, (n,)
                    ).astype(jnp.int32),
                )
            )

        # attester votes: create the attestation and broadcast it ------------
        with net._scope("attest", CHAIN_SCOPES):
            vote_h = slot_now
            can_vote = tatt & (vote_h >= 1) & (vote_h < mh)
            att_slot = jnp.clip(
                (vote_h - 1) * self.apr + jnp.where(self.is_att, self._att_j(), 0),
                0,
                ma - 1,
            )
            w_a = jnp.where(can_vote, att_slot, ma)
            proto["att_exists"] = proto["att_exists"].at[w_a].set(True, mode="drop")
            proto["att_head"] = proto["att_head"].at[w_a].set(proto["head"], mode="drop")
            # the voter counts its own attestation when its own copy of the
            # broadcast arrives, like every other node (the oracle's sendAll)

            # committee of this slot shares the tick: [apr x N] rows
            cm = self.committee[jnp.clip((vote_h - 1) % self.cl, 0, self.cl - 1)]
            cm_mask = can_vote[cm]  # [apr]
            emissions.append(
                Emission(
                    mask=jnp.repeat(cm_mask, n),
                    from_idx=jnp.repeat(cm, n),
                    to_idx=jnp.tile(ids, self.apr),
                    mtype=self.mtype("ATT"),
                    payload=jnp.stack(
                        [
                            jnp.repeat(att_slot[cm], n),
                            jnp.zeros(self.apr * n, jnp.int32),
                        ],
                        axis=1,
                    ),
                    send_time=jnp.broadcast_to(
                        t + p.attestation_construction_time, (self.apr * n,)
                    ).astype(jnp.int32),
                )
            )

        with net._scope("forkchoice", CHAIN_SCOPES):
            proto["head_score"] = self._head_score(proto)
        return state._replace(proto=proto), emissions

    def _att_j(self):
        """Attester committee-member index (i // cycle_length)."""
        j = jnp.zeros(self.n_nodes, jnp.int32)
        return j.at[self.att_ids].set(self.att_cidx)

    def all_done(self, state):
        return jnp.asarray(False)  # open-ended, like the oracle

    def head_height(self, state):
        return state.proto["head"]


def make_casper(
    params: Optional[CasperParameters] = None,
    max_heights: int = 24,
    capacity: Optional[int] = None,
    seed: int = 0,
    byz_variant: str = "wf",
    byz_delay: int = 0,
    due_view_rows: Optional[int] = None,
):
    """Host-side construction from the oracle's init (observer + the chosen
    Byzantine producer variant + honest producers + attesters, same RNG).
    byz_variant selects node 0's producer: "wf" (default,
    ByzBlockProducerWF), "delay", "sf", "ns" (CasperIMD.java:511-707)."""
    params = params or CasperParameters()
    oracle = CasperIMD(params)
    from .casper import (
        ByzBlockProducer,
        ByzBlockProducerNS,
        ByzBlockProducerSF,
        ByzBlockProducerWF,
    )

    byz_cls = {
        "wf": ByzBlockProducerWF,
        "delay": ByzBlockProducer,
        "sf": ByzBlockProducerSF,
        "ns": ByzBlockProducerNS,
    }[byz_variant]
    oracle.init(byz_cls(oracle, byz_delay, oracle.genesis))
    nodes = oracle.network().all_nodes
    n = len(nodes)
    att_ids = np.array(
        [nd.node_id for nd in nodes if isinstance(nd, Attester)], np.int32
    )
    is_bp = np.array(
        [
            isinstance(nd, BlockProducer)
            and nd is not oracle.bps[0]
            for nd in nodes
        ]
    )
    cl, apr = params.cycle_length, params.attesters_per_round
    committee = np.zeros((cl, apr), np.int32)
    for idx, aid in enumerate(att_ids):
        committee[idx % cl, idx // cl] = aid
    roles = {
        "n_nodes": n,
        "is_att": np.array([isinstance(nd, Attester) for nd in nodes]),
        "is_bp": is_bp,
        "bp0": oracle.bps[0].node_id,
        "att_ids": att_ids,
        "att_cidx": np.arange(len(att_ids), dtype=np.int32) // cl,
        "committee": committee,
        "prod_ids": np.array([nd.node_id for nd in oracle.bps], np.int32),
    }
    latency = registry_network_latencies.get_by_name(params.network_latency_name)
    city_index = getattr(latency, "city_index", None)
    cols = build_node_columns(nodes, city_index)
    proto = BatchedCasper(params, roles, max_heights, byz_variant, byz_delay)
    if capacity is None:
        # the peak in-flight load is one committee's attestation broadcast
        # ([apr x N] messages, all delivered well inside the 8 s slot) plus
        # scheduled self-messages; a full lane DROPS new sends (the store
        # is the flat overflow lane, below), so auto-size it to 1.5 waves
        # (the default 20x4 config keeps the old 1<<14)
        wave = apr * n + 4 * n
        capacity = max(1 << 14, 1 << int(np.ceil(np.log2(1.5 * wave))))
    # flat mode (wheel_rows=0): Casper's scheduling is dominated by
    # explicit-arrival self-messages whole 8 s slots ahead — far beyond any
    # useful wheel horizon, so the exact overflow-lane scan IS the store
    if due_view_rows is None:
        # a step delivers what is due at one ms, a wave spread over the
        # latency model's couple of hundred: 1/128 of the lane is a fifth
        # over the fullest ms read at 256 x 1027 (3457 rows, sandbox); a
        # fuller ms is viewed in the whole lane, exactly.  0: always that
        due_view_rows = max(256, capacity // 128)
    net = BatchedNetwork(
        proto, latency, n, capacity=capacity, wheel_rows=0,
        due_view_rows=due_view_rows or None,
    )
    state = net.init_state(cols, seed=seed, proto=proto.proto_init(n))
    return net, state
