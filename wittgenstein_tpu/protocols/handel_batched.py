"""Batched Handel: the north-star protocol on the TPU engine.

Re-expression of protocols/Handel.java for the batched time-stepped core.
State is packed uint32 bitsets in the XOR-relative layout (ops.bitops):
bit j of node i's vector is node i^j, so every node shares the same level
geometry — level l = bit block [2^(l-1), 2^l) (Handel.allSigsAtLevel,
Handel.java:634-647, becomes a static mask), and re-addressing a level-l
contribution from sender s into receiver i's space is the bit permutation
j -> j ^ r0 with r0 = (i^s) & (2^(l-1)-1).

Program-size layout (what makes the 4096-node program compile): levels
are grouped into WIDTH BUCKETS (BitsetAggBase) and every phase runs once
per bucket on a stacked [N, nl, ...] level axis instead of once per
level; the per-level dissemination/fastPath send calls collapse into one
stacked send over [N * levels] rows.  Channel and candidate content are
flat per-bucket 2D arrays, so nothing pays XLA's (8,128) tile padding.

Three buffer stages per (receiver, level), mirroring the reference's
message + toVerifyAgg + pairing pipeline:

  1. in-flight channel: D slots keyed by (arrival<<rel_bits | rel),
     slot = arrival mod D, earliest arrival wins; displaced sends are
     counted in proto["displaced"] and lost — Handel's periodic
     dissemination re-offers content every period, exactly the redundancy
     the reference relies on for its own dropped/filtered messages.
     Content is stored in the RECEIVER's block-local bit space,
     re-addressed at send time (see BitsetAggBase._send_stacked).
  2. candidate buffer (toVerifyAgg, Handel.java:447): K slots of arrived,
     not-yet-verified aggregate sigs in receiver block-local space,
     curated exactly like bestToVerify's pruning — a candidate survives
     only while sizeIfIncluded > |totalIncoming| and its sender is not
     blacklisted (Handel.java:592-612); arrivals beyond K displace the
     lowest-(sizeIfIncluded, -rank) entry.
  3. verification register: one in-progress verification per node;
     selection at time t commits its merge at t + pairingTime
     (checkSigs -> registerTask(updateVerifiedSignatures, now +
     nodePairingTime), Handel.java:833-836) — the node is busy meanwhile,
     preserving the 1-verification-per-pairingTime capacity model.

Semantics carried exactly (Handel.java refs):
  * windowed scoring: windowIndex = min rank in the queue, rank-based
    choice outside the window, score-based inside (bestToVerify,
    :566-630); score() = added-signature count with the
    non-intersecting/with-individuals cases (:650-664); exponential
    window adaptation ceil(*2)/floor(/4) clamped to [min, max] and the
    chosen level's size (WindowParameters/ScoringExp :150-210, applied at
    :823-825).
  * updateVerifiedSignatures (:686-750): blacklist on bad sigs;
    verifiedInd bit; the **improved guard** — lastAggVerified is only
    replaced/extended when |sig ∪ ind| > |ind|, so a verified aggregate
    can never shrink; totalIncoming = lastAgg | ind; fastPath burst to
    fast_path peers of the first higher level whose outgoing just
    completed (:738-742); doneAt when the cross-level union reaches the
    threshold (:747-749).
  * byzantineSuicide (:538-559): while un-blacklisted down Byzantine
    peers with rank inside windowIndex+window exist at a level, a forged
    full-block sig from one of them is returned as that level's
    bestToVerify result directly; verifying it wastes pairingTime and
    blacklists the sender (:687-694).
  * hiddenByzantine (:840-917): when the chosen best is at the top level,
    a valid single-bit sig from the lowest-rank down Byzantine peer not
    yet in totalIncoming competes by score; if it wins the node wastes a
    verification on a nearly-useless contribution.
  * uniform-random choice among per-level bests (chooseBestFromLevels,
    :788-790), extraCycle post-done dissemination (:331-338), done-node
    message filtering (msgFiltered, :752-756), desynchronizedStart,
    per-node pairing time scaled by speedRatio.

Distribution-parity approximations (deliberate, each noted inline):
  * reception ranks: the reference shuffles one global [N] permutation
    per receiver (setReceivingRanks :940-948); here rank(i, l, rel) is a
    keyed pseudorandom PERMUTATION of [0, N) per receiver evaluated at
    the sender's absolute id (see _rank) — globally distinct ranks whose
    level-block order statistics match the reference's shuffle.  The
    post-verification demotion (receptionRanks[from] += nodeCount,
    :826-830) becomes a +N penalty whenever the sender's individual sig
    is already verified.
  * emission order (:991-1013) is a counter-hash offset + cycling cursor
    per level rather than the rank-derived emission lists.  Of
    getRemainingPeers' two skips (:484-503) the BLACKLIST one is carried
    (PR 31, `_next_unlisted`, live only where track_bad is): the cursor
    moves on to the next peer the node has not blacklisted and a level
    whose every peer is blacklisted stops sending.  The FINISHED-PEER one
    is not (levelFinished/finishedPeers are not tracked), and that is
    what the program's extra traffic under attack is: at 256 nodes with
    51 down it sends 259.5 messages a live node where the reference
    sends 214.6 (+21%; the reference with both skips off sends 273.7,
    with the finished-peer skip alone off 272.9, with the blacklist skip
    alone off 232.0), less the fast-path burst it lacks (5.7 against
    20.3); the blacklist skip pays 17 of those messages only once
    levels can close (PERF.md section 6, PR 31; ROADMAP B11).  The
    fast-path burst itself still goes to a blacklisted peer now and then
    (0.2 messages a live node).
  * suicide-byz picks the lowest-block-index eligible peer, not the
    suicideBizAfter cursor order; hidden-byz re-attempts injection each
    selection instead of tracking the `last` candidate.
  * same-ms deliveries are simultaneous; per-ms LIFO order inside the
    oracle's buckets has no analog.

int32 packing guards: channel keys pack arrival << rel_bits | rel (sim
horizon 2^(31-rel_bits) ms — 524 s at 4096 nodes; later sends drop into
the displaced counter) and candidate sort keys pack sizeIfIncluded * 4N
+ rank, so node_count is capped at 2^14 (16384) — far above the
4096-node north star — and construction fails loudly beyond it.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core.node import Node, build_node_columns
from ..core.registries import registry_network_latencies, registry_node_builders
from ..engine import BatchedNetwork
from ..engine.rng import hash32
from ..ops.bitops import popcount_words, xor_shuffle
from ..ops.select import take_slot, top_k_merge
from ..utils.javarand import JavaRandom
from ._agg_batched import INT32_MAX, BitsetAggBase, firing_capacity, landing_capacity
from .handel import HandelParameters

# what the byzantineSuicide attack adds to a tick (live only where
# `track_bad` carries the `bl` and `byz` planes), nested under the phase
# that runs it; an attack-free program has none.
ATTACK_SCOPES = {
    "inject": "witt.attack.inject",  # the forged full-block sig that wins a level's choice
    "blacklist": "witt.attack.blacklist",  # the bl plane: written at commit, read in curation
    "emission": "witt.attack.emission",  # dissemination moves on past blacklisted peers
}

# the deliver phase (`_channel_deliver`, on every (node, level) of every
# tick): the due candidates' rank (`_rank` and the sender's bit of `ind`,
# `_level_bit`) and the candidate merge (ops/select.py `top_k_merge`),
# nested under the phase that delivers.
DELIVER_SCOPES = {
    "rank": "witt.deliver.rank",  # the 2 due candidates' reception rank and verified-sender demotion
    "merge": "witt.deliver.merge",  # keep the best K of the K resident and the 2 due candidates
}


class BatchedHandel(BitsetAggBase):
    CAND_SLOTS = 8  # K: arrived verification candidates per (receiver, level)
    # D=32 arrival slots (vs the base class's 8): the r5 residual
    # decomposition (scripts/parity_residual.py + parity_ablate.py)
    # measured displacement as the dominant CDF bias — 25% of received
    # traffic displaced at D=8 costs +3.8%/+7.7% on P50/P90 done_at;
    # D=32 cuts displacement to ~10% and the residual to |2.7|% worst-
    # case.  Delivery cost is O(1) in D (only 2 slots can be due per
    # tick); the price is channel memory, ~3.7x on in_sig — ~106 MiB per
    # 4096-node replica, still 32+ replicas inside a v5e chip's HBM.
    CHANNEL_DEPTH = 32
    # Candidate-score caching: carry the per-slot derived quantities
    # _select needs — sizeIfIncluded, cardinality, |sig ∪ ind| and the
    # agg-intersection flag — as int32 leaves in state.proto, refreshed
    # only where delivery merges new content and where _commit moves the
    # aggregates.  The selection and the channel merge then read cached
    # int32 columns instead of re-popcounting every candidate's signature
    # words each tick.  End-of-tick invariant, pinned by simlint SL701
    # and by the tests at every stop of whole runs: each cache leaf equals
    # its from-scratch recompute (_recompute_cache_dict) from (cand_sig*,
    # inc, ind, agg).  This is the program's only form; the constant is
    # what the benchmark's configs read through expect.protocol_attrs.
    SCORE_CACHE = True
    CACHE_LEAF_NAMES = ("cand_s", "cand_card", "cand_wind", "cand_aggi")
    DERIVED_CACHE_LEAVES = CACHE_LEAF_NAMES

    def __init__(self, params: HandelParameters):
        self.params = params
        if params.channel_depth is not None:
            if params.channel_depth <= 0:
                raise ValueError(
                    f"channel_depth={params.channel_depth} must be positive"
                )
            self.CHANNEL_DEPTH = params.channel_depth  # instance override
        if params.cand_slots is not None:
            if params.cand_slots <= 0:
                raise ValueError(
                    f"cand_slots={params.cand_slots} must be positive"
                )
            self.CAND_SLOTS = params.cand_slots  # instance override
        self._init_geometry(params.node_count)
        # blacklist + byzantine bitsets are carried only when an attack can
        # ever set a bit in them (byzantineSuicide writes bl, both attacks
        # read byz); attack-free replicas — the flagship density config —
        # drop both [N, n_words] planes from the carried state entirely.
        # Every read site is gated on this flag, so the attack-free program
        # is the all-zero-bl program with the (no-op) bl terms elided.
        self.track_bad = bool(
            params.byzantine_suicide or params.hidden_byzantine
        )
        self.NARROW_LEAVES = self._narrow_plan()

    @property
    def REQUIRED_SCOPES(self) -> tuple:
        """The channel's scopes, the deliver phase's and, built with an
        attack, those of what the attack runs (`inject` under
        byzantineSuicide)."""
        scopes = super().REQUIRED_SCOPES + tuple(DELIVER_SCOPES.values())
        if self.track_bad:
            scopes += tuple(
                scope for name, scope in ATTACK_SCOPES.items()
                if name != "inject" or self.params.byzantine_suicide
            )
        return scopes

    def _narrow_plan(self) -> tuple:
        """NARROW_LEAVES for this instance's geometry (engine.density,
        docs/density.md).  Every bound is provable from static parameters:

          cand_rank  rank = per-receiver permutation of [0, N) plus the
                     +N verified-sender demotion -> < 2N; INT32_MAX empty
                     sentinel (stored as the narrow dtype max)
          cand_rel / ver_rel  relative peer ids, < N
          ver_level / fp_level  level numbers, <= L-1
          fp_left    fastPath burst countdown, <= min(fast_path, N/2)
          window     clamped to [window_minimum, window_maximum] and the
                     selected level's size
          cand_s / cand_card / cand_wind  popcounts over one level block
                     (block size <= N/2; N is a safe static bound)
          cand_aggi  boolean flag carried as an integer

        Leaves whose bound already needs int32 are omitted (narrowing
        would be a no-op)."""
        from ..engine.density import NarrowLeaf, narrowest_int

        p, n, L = self.params, self.n_nodes, self.n_levels
        fp_max = max(1, min(p.fast_path, max(1, n // 2)))
        bounds = (
            ("cand_rank", 2 * n - 1, True),
            ("cand_rel", max(1, n - 1), False),
            ("ver_level", max(1, L - 1), False),
            ("ver_rel", max(1, n - 1), False),
            ("fp_level", max(1, L - 1), False),
            ("fp_left", fp_max, False),
            ("window", max(p.window_initial, p.window_maximum), False),
            ("cand_s", n, False),
            ("cand_card", n, False),
            ("cand_wind", n, False),
            ("cand_aggi", 1, False),
        )
        leaves = []
        for name, bound, sentinel in bounds:
            dt = narrowest_int(bound, reserve_sentinel=sentinel)
            if dt.itemsize < 4:
                leaves.append(NarrowLeaf(name, dt.name, bound, sentinel))
        return tuple(leaves)

    def census_limits(self) -> dict:
        """The fast path's send has N x ceil(fast_path / 2) rows:
        `firing_peak` is read against the rows a round of its arrivals
        and claim carries (`firing_capacity`), `landing_peak` against
        those a round of its commit does (`landing_capacity`); 0 without
        a fast path."""
        p, n = self.params, self.n_nodes
        if not (p.fast_path > 0 and self.n_levels > 1):
            return {"firing_peak": 0, "landing_peak": 0}
        r = (min(p.fast_path, max(1, n // 2)) + 1) // 2
        return {
            "firing_peak": firing_capacity((n, r)),
            "landing_peak": landing_capacity(n * r),
        }

    def msg_size(self, mtype: int) -> int:
        # Size = level + bit field + the signatures included + our own sig
        # (SendSigs, Handel.java:253-258)
        expected = 1 if mtype == 0 else 1 << (mtype - 1)
        return 1 + expected // 8 + 96 * 2

    # -- ranks ---------------------------------------------------------------
    def _rank(self, seed, ids, level, rel):
        """Stand-in for the reference's global reception-rank permutation
        (setReceivingRanks, Handel.java:940-948): one pseudorandom
        PERMUTATION of [0, N) per receiver, evaluated at the sender's
        absolute id.  Three keyed multiply/xorshift/add rounds over the
        n-bit domain — each round is bijective mod 2^n (odd multiplier,
        xorshift, add), so ranks are globally distinct per receiver and a
        level block's ranks have the order statistics of a uniform draw
        WITHOUT replacement from [0, N), matching the reference's shuffle.
        (The r4 stratified construction halved E[min rank] = windowIndex —
        measured -2% doneAt bias; see scripts/parity_residual.py.)

        ids/level/rel broadcast together; level may be a static int or a
        stacked [.., L-1, ..] axis."""
        level = jnp.asarray(level, jnp.int32)
        # bs_l = 2^(l-1) by arithmetic: a table read at a stacked level
        # axis would be a gather
        bs = jnp.int32(1) << (level - 1)
        r0 = rel & (bs - 1)
        # sender's absolute id: level-l peers of receiver i are i ^ j for
        # bit index j in [bs, 2*bs)
        x = (jnp.asarray(ids, jnp.int32) ^ (bs + r0)).astype(jnp.uint32)
        mask = jnp.uint32(self.n_nodes - 1)
        nbits = self.n_nodes.bit_length() - 1
        s1 = max(1, nbits // 2)
        x &= mask
        for rnd in range(3):
            mul = hash32(seed, ids, jnp.int32(0xA11CE + rnd)).astype(jnp.uint32) | jnp.uint32(1)
            add = hash32(seed, ids, jnp.int32(0xBEEF + rnd)).astype(jnp.uint32)
            x = (x * mul) & mask
            x = x ^ (x >> jnp.uint32(s1 + (rnd & 1)))
            x = (x + add) & mask
        return x.astype(jnp.int32)

    def _dyn_full_block(self, bs, w_pad: int):
        """[..,] dynamic block sizes -> [.., w_pad] all-ones-below-bs words."""
        bits = jnp.clip(
            bs[..., None] - 32 * jnp.arange(w_pad, dtype=jnp.int32), 0, 32
        )
        m = (jnp.uint32(1) << (bits & 31).astype(jnp.uint32)) - 1
        return jnp.where(bits >= 32, jnp.uint32(0xFFFFFFFF), m)

    # -- state ---------------------------------------------------------------
    def proto_init(
        self,
        n_nodes: int,
        pairing: np.ndarray,
        start_at: np.ndarray,
        byz_rel: Optional[np.ndarray] = None,
    ):
        n, L, K = self.n_nodes, self.n_levels, self.CAND_SLOTS
        own = np.zeros((n, self.n_words), dtype=np.uint32)
        own[:, 0] = 1  # bit 0 = own signature (level 0)
        in_key, in_sigs = self._channel_init(n)
        cand_sigs = {
            f"cand_sig{i}": jnp.zeros((n, b.nl * K * b.w_pad), jnp.uint32)
            for i, b in enumerate(self.buckets)
        }
        proto = {
            "agg": jnp.asarray(own),  # lastAggVerified per level block
            "ind": jnp.asarray(own),  # verifiedIndSignatures
            "inc": jnp.asarray(own),  # totalIncoming = agg | ind
            # stage 1: in-flight channel (D arrival slots + 1 fresh backstop
            # per level; see BitsetAggBase)
            "in_key": in_key,
            **in_sigs,
            "displaced": jnp.int32(0),
            # the fast path's commit (_commit_landed): rounds run, summed
            # over ticks, and the most rows that landed in one tick
            "commit_rounds": jnp.int32(0),
            "landing_peak": jnp.int32(0),
            **self._not_ok_init(n),
            # stage 2: candidate buffer (toVerifyAgg)
            "cand_rank": jnp.full((n, (L - 1) * K), INT32_MAX, jnp.int32),
            "cand_rel": jnp.zeros((n, (L - 1) * K), jnp.int32),
            **cand_sigs,
            # stage 3: verification register
            "ver_active": jnp.zeros(n, bool),
            "ver_done_t": jnp.zeros(n, jnp.int32),
            "ver_level": jnp.zeros(n, jnp.int32),
            "ver_rel": jnp.zeros(n, jnp.int32),
            "ver_bad": jnp.zeros(n, bool),
            "ver_sig": jnp.zeros((n, self.w_max), jnp.uint32),
            # fastPath burst register: peers left to contact, level, offset
            "fp_left": jnp.zeros(n, jnp.int32),
            "fp_level": jnp.zeros(n, jnp.int32),
            "fp_off": jnp.zeros(n, jnp.int32),
            "window": jnp.full(n, self.params.window_initial, jnp.int32),
            "pos": jnp.zeros((n, L), jnp.int32),
            "added_cycle": jnp.full(n, self.params.extra_cycle, jnp.int32),
            "sigs_checked": jnp.zeros(n, jnp.int32),
            "msg_filtered": jnp.zeros(n, jnp.int32),
            "pairing": jnp.asarray(pairing, jnp.int32),
            "start_at": jnp.asarray(start_at, jnp.int32),
        }
        if self.track_bad:
            # blacklist (rel space) + down Byzantine peers (rel space) —
            # carried only when an attack can set them (see __init__)
            proto["bl"] = jnp.zeros((n, self.n_words), jnp.uint32)
            if byz_rel is None:
                byz_rel = np.zeros((n, self.n_words), dtype=np.uint32)
            proto["byz"] = jnp.asarray(byz_rel)
        proto.update(self._recompute_cache_dict(proto))
        return self.narrow_proto(proto)

    # -- candidate-score caches ----------------------------------------------
    def _recompute_cache_dict(self, proto) -> dict:
        """From-scratch values of the four candidate-score cache leaves,
        computed only from (cand_sig*, inc, ind, agg) — the oracle the
        end-of-tick invariant is checked against (simlint SL701) and the
        initializer for proto_init.  Per slot k of (receiver, level):
          cand_s    = sizeIfIncluded (bestToVerify's curation quantity,
                      Handel.java:592-612): |merge(sig, inc) ∪ ind|
          cand_card = |sig|
          cand_wind = |sig ∪ ind|   (the score's with-individuals term)
          cand_aggi = 1 iff sig ∩ lastAgg ≠ ∅  (the score's branch flag)
        All int32 [N, (L-1)*K], addressed exactly like cand_rank."""
        n, L, K = self.n_nodes, self.n_levels, self.CAND_SLOTS
        inc, ind, agg = proto["inc"], proto["ind"], proto["agg"]
        s_p, card_p, wind_p, aggi_p = [], [], [], []
        for i, b in enumerate(self.buckets):
            c_sig = self._sig_view(proto, i, K, prefix="cand_sig")
            inc_b = self._blocks(inc, b)[:, :, None, :]
            ind_b = self._blocks(ind, b)[:, :, None, :]
            agg_b = self._blocks(agg, b)[:, :, None, :]
            inter = popcount_words(c_sig & inc_b) > 0
            cc = jnp.where(inter[..., None], c_sig, c_sig | inc_b)
            s_p.append(popcount_words(cc | ind_b))
            card_p.append(popcount_words(c_sig))
            wind_p.append(popcount_words(c_sig | ind_b))
            aggi_p.append(
                (popcount_words(c_sig & agg_b) > 0).astype(jnp.int32)
            )
        flat = lambda ps: jnp.concatenate(ps, axis=1).reshape(n, (L - 1) * K)
        return {
            "cand_s": flat(s_p),
            "cand_card": flat(card_p),
            "cand_wind": flat(wind_p),
            "cand_aggi": flat(aggi_p),
        }

    def recompute_caches(self, state) -> dict:
        # oracle recompute on the int32 view, re-narrowed so the returned
        # leaves match the carried storage dtypes exactly (the SL701 and
        # checkpoint-template comparisons are dtype-strict)
        caches = self._recompute_cache_dict(self.widen_proto(state.proto))
        return self.narrow_proto(caches)

    # -- tick phase 1: commit due verifications ------------------------------
    def _commit(self, net, state):
        """updateVerifiedSignatures at t = selection + pairingTime
        (Handel.java:686-750), one stacked body per width bucket."""
        p = self.params
        proto = state.proto
        t = state.time
        n, L = self.n_nodes, self.n_levels
        ids = jnp.arange(n, dtype=jnp.int32)

        due = proto["ver_active"] & (t >= proto["ver_done_t"])
        good = due & ~proto["ver_bad"]

        rel = proto["ver_rel"]
        new_bl = None
        if self.track_bad:
            # bad sig: blacklist the sender, nothing else (:687-694)
            with net._scope("blacklist", ATTACK_SCOPES):
                bad = due & proto["ver_bad"]
                oh_full = self._onehot(rel, self.n_words)
                new_bl = jnp.where(
                    bad[:, None], proto["bl"] | oh_full, proto["bl"]
                )

        agg, ind, inc = proto["agg"], proto["ind"], proto["inc"]
        lvl = proto["ver_level"]
        improved_any = jnp.zeros(n, bool)
        just_completed = jnp.zeros(n, bool)
        ind_pieces, agg_pieces, inc_pieces = [], [], []
        for i, b in enumerate(self.buckets):
            lv = jnp.asarray(b.levels, jnp.int32)
            bs = jnp.asarray([self.bs[l] for l in b.levels], jnp.int32)
            m = good[:, None] & (lvl[:, None] == lv[None, :])  # [N, nl]
            r0 = rel[:, None] & (bs[None, :] - 1)
            sig_b = proto["ver_sig"][:, None, : b.w_pad]  # zero above w[lvl]
            ind_b = self._blocks(ind, b)  # [N, nl, w_pad]
            agg_b = self._blocks(agg, b)
            inc_b = self._blocks(inc, b)
            sender = self._onehot(r0, b.w_pad)

            new_ind_b = ind_b | sender
            # the improved guard: extend/replace lastAgg ONLY when the
            # candidate plus individuals is strictly larger (:716-722)
            improved2 = popcount_words(sig_b | new_ind_b) > popcount_words(new_ind_b)
            inter = popcount_words(agg_b & sig_b) > 0
            new_agg_b = jnp.where(
                (improved2 & inter)[..., None],
                jnp.broadcast_to(sig_b, agg_b.shape),
                agg_b | jnp.where(improved2[..., None], sig_b, jnp.uint32(0)),
            )
            new_inc_b = jnp.where(
                improved2[..., None], new_agg_b | new_ind_b, inc_b | sender
            )
            improved1 = popcount_words(inc_b & sender) == 0
            improved = m & (improved1 | improved2)

            before_full = popcount_words(inc_b) == bs[None, :]
            after_full = popcount_words(new_inc_b) == bs[None, :]
            just_completed = just_completed | jnp.any(
                improved & after_full & ~before_full, axis=1
            )
            improved_any = improved_any | jnp.any(improved, axis=1)

            ind_pieces.append(jnp.where(m[..., None], new_ind_b, ind_b))
            agg_pieces.append(
                jnp.where((m & improved2)[..., None], new_agg_b, agg_b)
            )
            inc_pieces.append(jnp.where(m[..., None], new_inc_b, inc_b))

        ind = self._assemble(ind, ind_pieces)
        agg = self._assemble(agg, agg_pieces)
        inc = self._assemble(inc, inc_pieces)

        total = popcount_words(inc)
        done_now = (
            improved_any & (state.done_at == 0) & ~state.down & (total >= p.threshold)
        )
        # a good commit moves (inc, ind, agg) at exactly ver_level, so
        # the score caches of that one level's K slots are re-derived
        # against the NEW aggregates; every other level's caches stay
        # valid (cand_card depends on sig content only — untouched)
        K = self.CAND_SLOTS
        cs3 = proto["cand_s"].reshape(n, L - 1, K)
        cw3 = proto["cand_wind"].reshape(n, L - 1, K)
        ca3 = proto["cand_aggi"].reshape(n, L - 1, K)
        lv_rows = jnp.arange(L - 1, dtype=jnp.int32)
        for i, b in enumerate(self.buckets):
            mlev = good & (lvl >= b.lo) & (lvl <= b.hi)
            li = jnp.clip(lvl - b.lo, 0, b.nl - 1)
            c_sig = self._sig_view(proto, i, K, prefix="cand_sig")
            sig_lv = jnp.take_along_axis(
                c_sig, li[:, None, None, None], axis=1
            )[:, 0]  # [N, K, w_pad]
            inc_lv = jnp.take_along_axis(
                self._blocks(inc, b), li[:, None, None], axis=1
            )[:, 0]
            ind_lv = jnp.take_along_axis(
                self._blocks(ind, b), li[:, None, None], axis=1
            )[:, 0]
            agg_lv = jnp.take_along_axis(
                self._blocks(agg, b), li[:, None, None], axis=1
            )[:, 0]
            inter = popcount_words(sig_lv & inc_lv[:, None, :]) > 0
            cc = jnp.where(
                inter[..., None], sig_lv, sig_lv | inc_lv[:, None, :]
            )
            s_lv = popcount_words(cc | ind_lv[:, None, :])
            wind_lv = popcount_words(sig_lv | ind_lv[:, None, :])
            aggi_lv = (
                popcount_words(sig_lv & agg_lv[:, None, :]) > 0
            ).astype(jnp.int32)
            lm = mlev[:, None] & (lv_rows[None, :] == (lvl - 1)[:, None])
            cs3 = jnp.where(lm[..., None], s_lv[:, None, :], cs3)
            cw3 = jnp.where(lm[..., None], wind_lv[:, None, :], cw3)
            ca3 = jnp.where(lm[..., None], aggi_lv[:, None, :], ca3)
        upd = dict(
            agg=agg,
            ind=ind,
            inc=inc,
            cand_s=cs3.reshape(n, (L - 1) * K),
            cand_wind=cw3.reshape(n, (L - 1) * K),
            cand_aggi=ca3.reshape(n, (L - 1) * K),
            ver_active=proto["ver_active"] & ~due,
        )
        if self.track_bad:
            upd["bl"] = new_bl
        state = state._replace(
            done_at=jnp.where(done_now, t, state.done_at),
            proto=dict(proto, **upd),
        )

        # fastPath burst (:738-742): on completing a level's incoming set,
        # contact fast_path peers of the first higher level whose outgoing
        # is now complete but whose incoming is not.  The burst drains
        # through a register over two ticks (ceil(fp/2) peers per tick)
        # instead of fp simultaneous rows: the send's scatter costs
        # N*fp/2 rows/tick, and the <= 1 ms arrival spread stays inside
        # the parity suite's tolerance (1-peer-per-tick draining pushed
        # P90 to 9.6% vs the 8% bar; two-tick draining passes).  A new
        # completion overwrites a still-draining burst.
        if p.fast_path > 0 and L > 1:
            out_done = self._level_stats(
                [
                    popcount_words(self._lows(inc, b))
                    == jnp.asarray([self.bs[l] for l in b.levels], jnp.int32)[None, :]
                    for b in self.buckets
                ]
            )
            inc_done = self._level_stats(
                [
                    popcount_words(self._blocks(inc, b))
                    == jnp.asarray([self.bs[l] for l in b.levels], jnp.int32)[None, :]
                    for b in self.buckets
                ]
            )
            target_ok = out_done & ~inc_done  # [N, L-1]
            has_target = jnp.any(target_ok, axis=1)
            lsel = (jnp.argmax(target_ok, axis=1) + 1).astype(jnp.int32)
            fp_mask_base = just_completed & has_target
            fp = min(p.fast_path, max(1, self.n_nodes // 2))

            fp_left = jnp.where(fp_mask_base, fp, proto["fp_left"])
            fp_level = jnp.where(fp_mask_base, lsel, proto["fp_level"])
            fp_off = jnp.where(
                fp_mask_base, hash32(state.seed, ids, lsel, t), proto["fp_off"]
            )
            r = (fp + 1) // 2  # peers contacted per tick; burst drains in 2
            firing = fp_left > 0
            bs_sel = jnp.asarray(self.lv_bs)[jnp.maximum(fp_level - 1, 0)]
            ks = (fp - fp_left)[:, None] + jnp.arange(r, dtype=jnp.int32)[None, :]
            m_rows = (
                firing[:, None]
                & (jnp.arange(r, dtype=jnp.int32)[None, :] < fp_left[:, None])
                & (ks < bs_sel[:, None])
            )
            rel_fp = bs_sel[:, None] + ((fp_off[:, None] + ks) & (bs_sel[:, None] - 1))
            state = state._replace(
                proto=dict(
                    state.proto,
                    fp_left=jnp.maximum(fp_left - r, 0),
                    fp_level=fp_level,
                    fp_off=fp_off,
                )
            )
            # r rows a sender at the level of its register: the send cuts
            # each landing row's low block from `inc` itself
            state = self._send_stacked(
                net, state, m_rows, ids[:, None], ids[:, None] ^ rel_fp, fp_level, inc
            )
        return state

    # -- tick phase 2: deliver due channel slots into the candidate buffer ---
    def _channel_deliver(self, net, state):
        """onNewSig (Handel.java:752-786): due in-flight slots become
        verification candidates; the buffer keeps the top-K by
        (sizeIfIncluded, rank) among survivors of the curation rule."""
        proto = state.proto
        t = state.time
        n, L, D, K = self.n_nodes, self.n_levels, self.CHANNEL_DEPTH, self.CAND_SLOTS
        ids = jnp.arange(n, dtype=jnp.int32)
        rel_mask = (1 << self.rel_bits) - 1
        ss = D + 1
        lv_all = jnp.arange(1, L, dtype=jnp.int32)  # [L-1]

        in_key, due_all, empty_tpl = self._advance_channel(proto["in_key"], t)

        keys3 = self._keys_stacked(in_key)  # [N, L-1, ss]
        due3 = due_all.reshape(n, L - 1, ss)
        # only arrival slot (t mod D) and the fresh slot can be due at t
        keys2, due2 = self._due_pair_keys(keys3, due3, t)  # [N, L-1, 2]
        rel2 = keys2 & rel_mask

        # (receiver traffic counters tick at send time in _send_stacked)
        started = t >= proto["start_at"]
        not_done = state.done_at == 0
        filtered = jnp.sum(
            (due2 & ~not_done[:, None, None]).astype(jnp.int32), axis=(1, 2)
        )

        # onNewSig drop filters: not started, done, blacklisted sender
        accept = due2 & started[:, None, None] & not_done[:, None, None]
        if self.track_bad:
            with net._scope("blacklist", ATTACK_SCOPES):
                accept = accept & ~self._level_bit(proto["bl"], rel2)

        # rank + verified-sender demotion (receptionRanks += nodeCount):
        # the sender's bit of `ind` through the level's block, as `bl`'s
        # above (a slot that is not due carries a junk rel, reads some bit
        # of its block and is masked by `accept`)
        with net._scope("rank", DELIVER_SCOPES):
            ind_bit = self._level_bit(proto["ind"], rel2)
            rank2 = self._rank(
                state.seed, ids[:, None, None], lv_all[None, :, None], rel2
            ) + self.n_nodes * ind_bit.astype(jnp.int32)
            rank2 = jnp.where(accept, rank2, INT32_MAX)

        inc, ind = proto["inc"], proto["ind"]
        bl = proto["bl"] if self.track_bad else None
        agg = proto["agg"]
        rank_pieces, rel_pieces = [], []
        s_pieces, card_pieces, wind_pieces, aggi_pieces = [], [], [], []
        cand_sig_updates = {}
        for i, b in enumerate(self.buckets):
            sl = slice(b.lo - 1, b.hi)  # level rows of this bucket
            sig_new = self._due_pair_sig(proto, i, t)  # [N, nl, 2, w_pad]
            rank_new = rank2[:, sl, :]
            rel_new = rel2[:, sl, :]

            # merge [K existing + 2 new], keep top-K by (sizeIfIncluded, -rank)
            c_rank = proto["cand_rank"].reshape(n, L - 1, K)[:, sl, :]
            c_rel = proto["cand_rel"].reshape(n, L - 1, K)[:, sl, :]
            c_sig = self._sig_view(proto, i, K, prefix="cand_sig")

            all_rank = jnp.concatenate([c_rank, rank_new], axis=2)  # [N, nl, K+2]
            all_rel = jnp.concatenate([c_rel, rel_new], axis=2)
            all_sig = jnp.concatenate([c_sig, sig_new], axis=2)
            valid = all_rank != INT32_MAX

            inc_b = self._blocks(inc, b)  # [N, nl, w_pad]
            ind_b = self._blocks(ind, b)
            # only the two due slots pay popcounts: the K resident
            # slots' quantities ride in the caches, valid against the
            # pre-commit aggregates by the end-of-tick invariant
            # (deliver runs first; _commit re-fixes what it moves)
            agg_b = self._blocks(agg, b)
            inter2 = popcount_words(sig_new & inc_b[:, :, None, :]) > 0
            c2 = jnp.where(
                inter2[..., None], sig_new, sig_new | inc_b[:, :, None, :]
            )
            s_new = popcount_words(c2 | ind_b[:, :, None, :])
            all_s = jnp.concatenate(
                [proto["cand_s"].reshape(n, L - 1, K)[:, sl, :], s_new],
                axis=2,
            )
            all_card = jnp.concatenate(
                [
                    proto["cand_card"].reshape(n, L - 1, K)[:, sl, :],
                    popcount_words(sig_new),
                ],
                axis=2,
            )
            all_wind = jnp.concatenate(
                [
                    proto["cand_wind"].reshape(n, L - 1, K)[:, sl, :],
                    popcount_words(sig_new | ind_b[:, :, None, :]),
                ],
                axis=2,
            )
            all_aggi = jnp.concatenate(
                [
                    proto["cand_aggi"].reshape(n, L - 1, K)[:, sl, :],
                    (
                        popcount_words(sig_new & agg_b[:, :, None, :]) > 0
                    ).astype(jnp.int32),
                ],
                axis=2,
            )
            cur = popcount_words(inc_b)
            keep = valid & (all_s > cur[:, :, None])
            if self.track_bad:
                with net._scope("blacklist", ATTACK_SCOPES):
                    keep = keep & ~self._block_bit(bl, b, all_rel)

            # sort key: higher sizeIfIncluded first, then lower rank;
            # bounded (s <= bs <= N/2, rank < 3N) so s*4N + rank fits int32
            r4 = 4 * self.n_nodes
            skey = jnp.where(
                keep, all_s * r4 + (r4 - 1 - jnp.minimum(all_rank, r4 - 1)), -1
            )
            with net._scope("merge", DELIVER_SCOPES):
                top_key, (
                    sel_rank, sel_rel, sel_s, sel_card, sel_wind, sel_aggi, sel_sig
                ) = top_k_merge(
                    skey,
                    K,
                    [all_rank, all_rel, all_s, all_card, all_wind, all_aggi, all_sig],
                )

            rank_pieces.append(jnp.where(top_key >= 0, sel_rank, INT32_MAX))
            rel_pieces.append(sel_rel)
            cand_sig_updates[f"cand_sig{i}"] = sel_sig.reshape(
                n, b.nl * K * b.w_pad
            )
            s_pieces.append(sel_s)
            card_pieces.append(sel_card)
            wind_pieces.append(sel_wind)
            aggi_pieces.append(sel_aggi)

        flat = lambda ps: jnp.concatenate(ps, axis=1).reshape(n, (L - 1) * K)
        state = state._replace(
            proto=dict(
                proto,
                cand_s=flat(s_pieces),
                cand_card=flat(card_pieces),
                cand_wind=flat(wind_pieces),
                cand_aggi=flat(aggi_pieces),
                in_key=jnp.where(due_all, empty_tpl[None, :], in_key),
                cand_rank=flat(rank_pieces),
                cand_rel=flat(rel_pieces),
                msg_filtered=proto["msg_filtered"] + filtered,
                **cand_sig_updates,
            )
        )
        return state

    # -- tick phase 3: periodic dissemination --------------------------------
    def _dissemination(self, net, state):
        """Periodic doCycle over open levels (Handel.java:331-343, 452-480),
        all levels in ONE stacked send."""
        p = self.params
        proto = state.proto
        t = state.time
        n, L = self.n_nodes, self.n_levels
        ids = jnp.arange(n, dtype=jnp.int32)
        lv_all = jnp.arange(1, L, dtype=jnp.int32)
        bs_all = jnp.asarray(self.lv_bs)

        start = proto["start_at"] + 1
        on_beat = (t >= start) & (
            lax.rem(t - start, jnp.int32(p.dissemination_period_ms)) == 0
        )
        is_done = state.done_at > 0
        may_send = on_beat & ~state.down & (~is_done | (proto["added_cycle"] > 0))
        new_added = jnp.where(
            on_beat & is_done & (proto["added_cycle"] > 0),
            proto["added_cycle"] - 1,
            proto["added_cycle"],
        )

        inc = proto["inc"]
        opened = t >= (lv_all - 1) * jnp.int32(p.level_wait_time)  # [L-1]
        complete = self._level_stats(
            [
                popcount_words(self._lows(inc, b))
                == jnp.asarray([self.bs[l] for l in b.levels], jnp.int32)[None, :]
                for b in self.buckets
            ]
        )
        mask = may_send[:, None] & (opened[None, :] | complete)  # [N, L-1]

        offset = hash32(state.seed, ids[:, None], lv_all[None, :]) & (bs_all[None, :] - 1)
        pos = proto["pos"][:, 1:]
        peer = (pos + offset) & (bs_all[None, :] - 1)  # block-local, [N, L-1]
        step = 1
        if self.track_bad:
            # getRemainingPeers (:484-503) moves on to the next peer that
            # is not blacklisted and closes a level that has none left;
            # bl only grows, so "none left" needs no flag of its own
            with net._scope("emission", ATTACK_SCOPES):
                nxt, any_left = self._next_unlisted(proto["bl"], peer)
                mask = mask & any_left
                step = 1 + ((nxt - peer) & (bs_all[None, :] - 1))
                peer = nxt
        rel = (bs_all[None, :] + peer).astype(jnp.int32)
        new_pos = proto["pos"].at[:, 1:].set(jnp.where(mask, pos + step, pos))
        state = state._replace(
            proto=dict(proto, added_cycle=new_added, pos=new_pos)
        )

        # each level sends its outgoing prefix; one row per (node, level):
        # the [N, L-1, 1] level axis (see _send_stacked)
        state = self._send_stacked(
            net,
            state,
            mask[:, :, None],
            ids[:, None, None],
            (ids[:, None] ^ rel)[:, :, None],
            None,
            [self._lows(inc, b) for b in self.buckets],
        )
        return state

    def _next_unlisted(self, bl, peer):
        """For every (node, level): the first block-local peer index at or
        cyclically after `peer` whose bit of the rel-space blacklist is
        clear, and whether the level has any such peer.  [N, W], [N, L-1]
        -> int32[N, L-1], bool[N, L-1]; block views and two lowest-bit
        scans a bucket, no gather."""
        nxt_p, any_p = [], []
        for b in self.buckets:
            bs = jnp.asarray([self.bs[l] for l in b.levels], jnp.int32)
            free = ~self._blocks(bl, b) & self._dyn_full_block(bs, b.w_pad)[None]
            below = self._dyn_full_block(peer[:, b.lo - 1 : b.hi], b.w_pad)
            at_or_after, before = free & ~below, free & below
            ahead = popcount_words(at_or_after) > 0
            nxt_p.append(
                jnp.where(
                    ahead, self._lowest_bit(at_or_after), self._lowest_bit(before)
                )
            )
            any_p.append(ahead | (popcount_words(before) > 0))
        return self._level_stats(nxt_p), self._level_stats(any_p)

    # -- tick phase 4: start new verifications (checkSigs) -------------------
    def _select(self, net, state, view):
        """bestToVerify per level + uniform cross-level choice + attacks +
        window adaptation (Handel.java:566-630, 788-837).

        `view` holds the BOUNDARY state — candidates
        and aggregates as of the end of the previous tick — which is what
        the reference's boundary-fired checkSigs sees.  Candidate
        write-backs (curation removal, chosen-slot consumption) target
        the viewed ENTRY by (rank, cardinality) identity matched against
        any current slot of the level: delivery re-sorts the K slots on
        arrival ticks, so slot-index matching would both miss moved
        entries and clobber same-rank refreshes.  Rank is unique per
        (receiver, level, sender) and a refreshed aggregate differs in
        cardinality, so the only ambiguity is content-equal duplicates —
        clearing those loses nothing."""
        p = self.params
        proto = state.proto
        v = {**proto, **view}
        t = state.time
        n, L, K = self.n_nodes, self.n_levels, self.CAND_SLOTS
        ids = jnp.arange(n, dtype=jnp.int32)

        # busy gate from CURRENT state (a commit this tick frees the node,
        # preserving the reference's pairing-time cadence); everything the
        # selection SCORES on comes from the boundary view
        free = ~proto["ver_active"] & ~state.down & (t >= proto["start_at"] + 1)
        window = proto["window"]
        inc, ind, agg = v["inc"], v["ind"], v["agg"]
        bl = v["bl"] if self.track_bad else None
        byz = proto["byz"] if self.track_bad else None

        # per-level bests, one stacked body per bucket
        has_p, b_rank_p, b_rel_p, b_bad_p, b_kidx_p = [], [], [], [], []
        widx_p, insc_p = [], []
        condemn_pieces, vcard_pieces, ccard_pieces = [], [], []
        for i, b in enumerate(self.buckets):
            sl = slice(b.lo - 1, b.hi)
            lv = jnp.asarray(b.levels, jnp.int32)
            bs = jnp.asarray([self.bs[l] for l in b.levels], jnp.int32)
            c_rank = v["cand_rank"].reshape(n, L - 1, K)[:, sl, :]
            c_rel = v["cand_rel"].reshape(n, L - 1, K)[:, sl, :]
            valid = c_rank != INT32_MAX

            inc_b = self._blocks(inc, b)
            agg_b = self._blocks(agg, b)

            # curation (bestToVerify :592-612): drop blacklisted senders and
            # candidates that can no longer grow the aggregate.
            # sizeIfIncluded / cardinalities come from the carried int32
            # caches (the viewed snapshot for scoring, the current leaf
            # for entry identity) — no signature-word popcounts here
            s = v["cand_s"].reshape(n, L - 1, K)[:, sl, :]
            ccard_pieces.append(
                proto["cand_card"].reshape(n, L - 1, K)[:, sl, :]
            )
            curated = valid & (s > popcount_words(inc_b)[:, :, None])
            if self.track_bad:
                with net._scope("blacklist", ATTACK_SCOPES):
                    curated = curated & ~self._block_bit(bl, b, c_rel)
            # permanent removal, like replaceToVerifyAgg (:612-618) —
            # recorded as a condemn mask, applied by ENTRY IDENTITY below
            condemn_pieces.append(valid & ~curated)

            # windowIndex = min rank over the (pre-curation valid) queue
            window_index = jnp.min(
                jnp.where(valid, c_rank, INT32_MAX), axis=2
            )  # [N, nl]
            win_hi = jnp.where(
                window_index < INT32_MAX - window[:, None],
                window_index + window[:, None],
                INT32_MAX,
            )
            inside = curated & (c_rank <= win_hi[:, :, None])

            # score (:650-664)
            agg_card = popcount_words(agg_b)  # [N, nl]
            sig_card = v["cand_card"].reshape(n, L - 1, K)[:, sl, :]
            agg_inter = v["cand_aggi"].reshape(n, L - 1, K)[:, sl, :] > 0
            with_ind = v["cand_wind"].reshape(n, L - 1, K)[:, sl, :]
            vcard_pieces.append(sig_card)
            score = jnp.where(
                agg_card[:, :, None] >= bs[None, :, None],
                0,
                jnp.where(
                    ~agg_inter,
                    agg_card[:, :, None] + sig_card,
                    jnp.maximum(0, with_ind - agg_card[:, :, None]),
                ),
            )
            in_score = jnp.where(inside & (score > 0), score, -1)
            k_in = jnp.argmax(in_score, axis=2)
            sc_in = jnp.max(in_score, axis=2)
            exists_in = sc_in > 0

            out_rank = jnp.where(curated & ~inside, c_rank, INT32_MAX)
            k_out = jnp.argmin(out_rank, axis=2)
            rk_out = jnp.min(out_rank, axis=2)
            exists_out = rk_out < INT32_MAX

            kidx = jnp.where(exists_in, k_in, k_out)
            lrank = jnp.where(exists_in, take_slot(c_rank, k_in), rk_out)
            lrel = take_slot(c_rel, kidx)
            lhas = exists_in | exists_out
            lbad = jnp.zeros((n, b.nl), bool)

            if p.byzantine_suicide:
                # createSuicideByzantineSig (:538-559): a forged full-block
                # sig from an eligible Byzantine peer short-circuits the
                # level's choice.  Eligible = down+byz, not blacklisted,
                # rank inside windowIndex + currWindowSize, queue non-empty.
                with net._scope("inject", ATTACK_SCOPES):
                    eligible = self._blocks(byz, b) & ~self._blocks(bl, b)
                    any_valid = jnp.any(valid, axis=2)
                    has_byz = popcount_words(eligible) > 0
                    # lowest block-local index (stand-in for cursor order)
                    m_byz = self._lowest_bit(eligible)
                    rel_byz = bs[None, :] + (m_byz & (bs[None, :] - 1))
                    rank_byz = self._rank(
                        state.seed, ids[:, None], lv[None, :], rel_byz
                    )
                    inject = has_byz & any_valid & (rank_byz < win_hi)
                    lhas = lhas | inject
                    lbad = jnp.where(inject, True, lbad)
                    lrel = jnp.where(inject, rel_byz, lrel)
                    lrank = jnp.where(inject, rank_byz, lrank)
                    kidx = jnp.where(inject, -1, kidx)

            has_p.append(lhas)
            b_rank_p.append(lrank)
            b_rel_p.append(lrel)
            b_bad_p.append(lbad)
            b_kidx_p.append(kidx)
            widx_p.append(window_index)
            insc_p.append(jnp.where(exists_in, sc_in, -1))

        has = self._level_stats(has_p)  # [N, L-1]
        b_rank = self._level_stats(b_rank_p)
        b_rel = self._level_stats(b_rel_p)
        b_bad = self._level_stats(b_bad_p)
        b_kidx = self._level_stats(b_kidx_p)
        # curation removal by ENTRY IDENTITY (rank, cardinality) matched
        # against ANY current slot of the level: delivery re-sorts the K
        # slots on arrival ticks, so slot-index matching would miss moved
        # entries (surviving for a duplicate verification) and clobber
        # same-rank refreshes; rank is unique per (receiver, level,
        # sender) and a refreshed aggregate has a different cardinality,
        # so the pair identifies the viewed entry up to content-equal
        # duplicates (clearing those loses nothing)
        condemn3 = jnp.concatenate(condemn_pieces, axis=1)  # [N, L-1, K]
        vrank3 = v["cand_rank"].reshape(n, L - 1, K)
        vcard3 = jnp.concatenate(vcard_pieces, axis=1)
        crank3 = proto["cand_rank"].reshape(n, L - 1, K)
        ccard3 = jnp.concatenate(ccard_pieces, axis=1)

        cleared = self._entry_clear(crank3, ccard3, vrank3, vcard3, condemn3)
        new_rank3 = jnp.where(cleared, INT32_MAX, crank3)

        # chooseBestFromLevels: uniform among levels with a candidate (:788)
        vcount = jnp.sum(has, axis=1).astype(jnp.int32)
        can = free & (vcount > 0)
        rnd = (
            hash32(state.seed, t, ids, jnp.int32(0x5EED)).astype(jnp.uint32)
            >> jnp.uint32(8)
        ).astype(jnp.int32)
        pick = jnp.where(vcount > 0, lax.rem(rnd, jnp.maximum(vcount, 1)), 0)
        cum = jnp.cumsum(has, axis=1)
        lidx = jnp.argmax((cum == (pick + 1)[:, None]) & has, axis=1)  # 0-based
        level_sel = (lidx + 1).astype(jnp.int32)

        sel_rank = jnp.take_along_axis(b_rank, lidx[:, None], axis=1)[:, 0]
        sel_rel = jnp.take_along_axis(b_rel, lidx[:, None], axis=1)[:, 0]
        sel_bad = jnp.take_along_axis(b_bad, lidx[:, None], axis=1)[:, 0]
        sel_kidx = jnp.take_along_axis(b_kidx, lidx[:, None], axis=1)[:, 0]
        sel_single = jnp.zeros(n, bool)  # hidden-byz single-bit sig marker

        if p.hidden_byzantine and L > 1:
            # HiddenByzantine.attack (:840-917), modeled at selection time:
            # when the chosen best is at the top level, a valid single-bit
            # sig from the lowest-index down-byz peer not yet in
            # totalIncoming is appended and bestToVerify re-runs — the
            # injected sig wins when it lands inside the (possibly lowered)
            # window with a strictly higher score than any inside candidate
            # (appended last, so ties keep the incumbent, :578-584).
            l = L - 1
            bt = self.buckets[-1]
            bs = self.bs[l]
            inc_b = self._blocks(inc, bt)[:, -1]
            ind_b = self._blocks(ind, bt)[:, -1]
            agg_b = self._blocks(agg, bt)[:, -1]
            eligible = self._blocks(byz, bt)[:, -1] & ~inc_b
            has_byz = popcount_words(eligible) > 0
            m_byz = self._lowest_bit(eligible)
            rel_byz = bs + (m_byz & (bs - 1))
            rank_byz = self._rank(state.seed, ids, jnp.int32(l), rel_byz)

            # its score: single new bit (:650-664)
            agg_card = popcount_words(agg_b)
            oh = self._onehot(m_byz & (bs - 1), bt.w_pad)
            byz_inter = popcount_words(oh & agg_b) > 0
            byz_score = jnp.where(
                agg_card >= bs,
                0,
                jnp.where(
                    ~byz_inter,
                    agg_card + 1,
                    jnp.maximum(0, popcount_words(oh | ind_b) - agg_card),
                ),
            )
            widx_top = self._level_stats(widx_p)[:, -1]
            insc_top = self._level_stats(insc_p)[:, -1]
            new_widx = jnp.minimum(widx_top, rank_byz)
            win_hi = jnp.where(
                new_widx < INT32_MAX - window, new_widx + window, INT32_MAX
            )
            was_outside = insc_top < 0
            wins = (
                can
                & (level_sel == l)
                & (sel_kidx >= 0)
                & has_byz
                & (rank_byz < sel_rank)
                & (rank_byz <= win_hi)
                & (byz_score > 0)
                & (was_outside | (byz_score > insc_top))
            )
            sel_rel = jnp.where(wins, rel_byz, sel_rel)
            sel_rank = jnp.where(wins, rank_byz, sel_rank)
            sel_kidx = jnp.where(wins, -1, sel_kidx)
            sel_single = wins

        # window adaptation (:823-825): exp increase on correct, exp
        # decrease on bad, clamped to [min, max] and the level size
        grown = jnp.ceil(window.astype(jnp.float32) * p.window_increase_factor)
        shrunk = jnp.floor(window.astype(jnp.float32) / p.window_decrease_factor)
        adapted = jnp.where(sel_bad, shrunk, grown).astype(jnp.int32)
        adapted = jnp.clip(adapted, p.window_minimum, p.window_maximum)
        lsize = (
            jnp.uint32(1) << jnp.maximum(level_sel - 1, 0).astype(jnp.uint32)
        ).astype(jnp.int32)
        new_window = jnp.where(can, jnp.minimum(adapted, lsize), window)

        # load the chosen sig into the verification register
        bs_sel = jnp.asarray(self.lv_bs)[jnp.maximum(level_sel - 1, 0)]
        ver_sig = proto["ver_sig"]
        for i, b in enumerate(self.buckets):
            m = can & (level_sel >= b.lo) & (level_sel <= b.hi)
            c_sig = self._sig_view(v, i, K, prefix="cand_sig")
            li = jnp.clip(level_sel - b.lo, 0, b.nl - 1)
            c_lv = jnp.take_along_axis(
                c_sig, li[:, None, None, None], axis=1
            )[:, 0]  # [N, K, w_pad]
            safe_k = jnp.maximum(sel_kidx, 0)
            from_buf = jnp.take_along_axis(c_lv, safe_k[:, None, None], axis=1)[:, 0]
            full_block = self._dyn_full_block(bs_sel, b.w_pad)
            single = self._onehot(sel_rel & (bs_sel - 1), b.w_pad)
            sig_l = jnp.where(
                (sel_kidx >= 0)[:, None],
                from_buf,
                jnp.where(sel_single[:, None], single, full_block),
            )
            pad = jnp.zeros((n, self.w_max - b.w_pad), jnp.uint32)
            sig_l = jnp.concatenate([sig_l, pad], axis=1)
            ver_sig = jnp.where(m[:, None], sig_l, ver_sig)

        # remove the chosen buffer candidate (commit-time removal in the
        # reference; removal at selection avoids double-verification) —
        # matched by (rank, cardinality) entry identity against the
        # chosen level's CURRENT slots, like the curation clear above
        lvl_idx = jnp.maximum(level_sel - 1, 0)
        sel_card = jnp.take_along_axis(
            jnp.take_along_axis(vcard3, lvl_idx[:, None, None], axis=1)[:, 0],
            jnp.maximum(sel_kidx, 0)[:, None],
            axis=1,
        )[:, 0]
        remove = can & (sel_kidx >= 0)
        new_rank3 = self._remove_chosen(
            ids, new_rank3, ccard3, lvl_idx, sel_rank, sel_card, remove
        )
        new_cand_rank = new_rank3.reshape(n, (L - 1) * K)

        state = state._replace(
            proto=dict(
                proto,
                cand_rank=new_cand_rank,
                ver_active=jnp.where(can, True, proto["ver_active"]),
                ver_done_t=jnp.where(can, t + proto["pairing"], proto["ver_done_t"]),
                ver_level=jnp.where(can, level_sel, proto["ver_level"]),
                ver_rel=jnp.where(can, sel_rel, proto["ver_rel"]),
                ver_bad=jnp.where(can, sel_bad, proto["ver_bad"]),
                ver_sig=ver_sig,
                window=new_window,
                sigs_checked=proto["sigs_checked"] + can.astype(jnp.int32),
            )
        )
        return state

    # -- engine hooks --------------------------------------------------------
    def tick(self, net, state):
        # NARROW_LEAVES boundary (engine.density): the tick body — and the
        # boundary-view snapshots it takes — compute on the int32 view;
        # the carried state between ticks stores the declared narrow
        # dtypes.  Bit-identical by construction: widen/narrow is a
        # lossless sentinel-mapped cast both ways.
        state = state._replace(proto=self.widen_proto(state.proto))
        state = self._tick_impl(net, state)
        return state._replace(proto=self.narrow_proto(state.proto))

    def _tick_impl(self, net, state):
        # deliver FIRST: it decrements every occupied channel key by one
        # tick, so anything sent later in this tick (fastPath bursts in
        # _commit, dissemination in tick_beat) is first decremented next
        # tick and lands exactly at its sampled arrival.  Dissemination
        # runs as the beat hook (same-tick order vs _select is immaterial:
        # _select reads none of the channel/pos state dissemination
        # writes, and channel slot resolution is order-independent
        # min/max competition).
        #
        # _select runs on the BOUNDARY VIEW (r5): the reference's checkSigs
        # is a conditional task that fires at the ms boundary — after
        # time++ but BEFORE the new ms's arrivals and before that ms's
        # updateVerifiedSignatures task (Network.java:533-565) — so the
        # selection must see candidates and aggregates as of the END of
        # the previous tick.  Selecting on same-tick state gave the
        # batched engine a 1-tick information lead per verification hop,
        # measured as a -4..-9 ms CDF lead (r5, on the CPU).  The
        # busy gate stays post-commit (a commit at t frees the node for a
        # same-tick re-select, like the reference's minStartTime spacing).
        pre_cand = {k: state.proto[k] for k in self._cand_keys()}
        state = self._channel_deliver(net, state)
        merge_keys = ("inc", "ind", "agg") + (
            ("bl",) if self.track_bad else ()
        )
        pre_merge = {k: state.proto[k] for k in merge_keys}
        state = self._commit(net, state)
        state = self._select(net, state, view={**pre_cand, **pre_merge})
        return state

    def _cand_keys(self):
        # the boundary view scores on end-of-previous-tick caches, which
        # by the invariant equal a recompute from the viewed (cand_sig,
        # inc, ind, agg) exactly
        return (
            ("cand_rank", "cand_rel")
            + tuple(f"cand_sig{i}" for i in range(len(self.buckets)))
            + self.CACHE_LEAF_NAMES
        )

    def all_done(self, state):
        live = ~state.down
        return jnp.all(jnp.where(live, state.done_at > 0, True))


def make_handel(
    params: Optional[HandelParameters] = None,
    capacity: int = 8,  # generic ring unused by this protocol
    seed: int = 0,
    wheel_rows: int = 0,  # flat by default; >0 = time wheel (parity tests)
    telemetry=None,  # telemetry.TelemetryConfig (None = uninstrumented)
    fuse_step: bool = False,  # True = engine's fused delivery+tick path
):
    """Host-side construction: build the node population with the oracle's
    RNG stream (positions, speed ratios, down set), bake into the engine."""
    params = params or HandelParameters()
    n = params.node_count
    nb = registry_node_builders.get_by_name(params.node_builder_name)
    latency = registry_network_latencies.get_by_name(params.network_latency_name)
    rd = JavaRandom(0)

    from ..oracle.network import Network as ONetwork

    if params.bad_nodes is not None:
        bad_bits = params.bad_nodes
        bad = {i for i in range(n) if (bad_bits >> i) & 1}
    else:
        bad = ONetwork.choose_bad_nodes(rd, n, params.nodes_down)

    nodes = []
    start_at = np.zeros(n, dtype=np.int32)
    for i in range(n):
        if params.desynchronized_start != 0:
            start_at[i] = rd.next_int(params.desynchronized_start)
        nodes.append(Node(rd, nb))
    down = np.array([i in bad for i in range(n)])

    pairing = np.maximum(
        1, (params.pairing_time * np.array([nd.speed_ratio for nd in nodes]))
    ).astype(np.int32)

    proto = BatchedHandel(params)
    # beat structure for the engine's real-branch gating: dissemination
    # fires at t with (t - (start_at + 1)) % period == 0
    proto.BEAT_PERIOD = params.dissemination_period_ms
    proto.BEAT_RESIDUES = tuple(
        sorted({int((s + 1) % params.dissemination_period_ms) for s in start_at})
    )

    # Byzantine peers, as each receiver's rel-space bitset (nodes that are
    # both down and flagged byzantine — Handel.java:957-976 stops them and
    # the attacks impersonate them)
    byz_rel = None
    if params.byzantine_suicide or params.hidden_byzantine:
        byz_abs = np.zeros(proto.n_words, dtype=np.uint32)
        for i in sorted(bad):
            byz_abs[i // 32] |= np.uint32(1 << (i % 32))
        ids = np.arange(n, dtype=np.int32)
        byz_rel = np.asarray(
            xor_shuffle(jnp.broadcast_to(jnp.asarray(byz_abs), (n, proto.n_words)), ids)
        )

    city_index = getattr(latency, "city_index", None)
    cols = build_node_columns(nodes, city_index)
    # flat mode by default: aggregation messaging bypasses the generic
    # store entirely (the channel in _agg_batched), so keep the per-tick
    # scan minimal
    net = BatchedNetwork(
        proto, latency, n, capacity=capacity, wheel_rows=wheel_rows,
        telemetry=telemetry, fuse_step=fuse_step,
    )
    state = net.init_state(
        cols,
        seed=seed,
        proto=proto.proto_init(n, pairing, start_at, byz_rel),
        down=down,
    )
    return net, state
