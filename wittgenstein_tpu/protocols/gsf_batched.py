"""Batched GSFSignature: north-star config #2 on the TPU engine.

Re-expression of protocols/GSFSignature.java (via the oracle port
protocols/gsf.py) on the shared bitset-aggregation machinery
(_agg_batched.BitsetAggBase): XOR-relative packed bitsets, per-level
channel slots + freshest-offer backstop, and a one-slot verification
register committing at t + pairingTime.  Like batched Handel, every
per-level computation runs once per width BUCKET on a stacked level
axis, and the per-level send loops (dissemination, accelerated calls)
collapse into single stacked sends — the r4 program-size rewrite.

GSF specifics vs Handel:

  * a node's level-l sends carry its whole *completed prefix* — the union
    of consecutively complete levels is always the interval [0, 2^k) in
    the XOR layout (getLastFinishedLevel, GSFSignature.java:376-392), so
    the multi-level payload is transmitted as the level-confined content
    (w_l words) plus ONE integer k per message (`in_aux`/`cand_pk`); the
    receiver reconstructs the interval exactly, which is what drives the
    absorb-lower-levels path of updateVerifiedSignatures (:397-411).
  * level sends are budgeted: remainingCalls starts at the level size and
    is reset on improvement (:345-356, :438-443); dissemination stops
    when the budget is exhausted rather than cycling forever.
  * verification candidates are scored with evaluateSig (:478-520):
    completion bonus 1_000_000 - 10*level, otherwise 100_000 - 100*level
    + addedSigs, individual-sig fallback score 1 — and the *global* best
    across levels is verified (no per-level uniform choice, :524-558).
  * every first message from a sender enqueues that sender's individual
    single-bit signature as a separate verification candidate
    (onNewSig, :560-577), tracked here as pending/seen bitsets with the
    lowest-index pending bit as the level's representative candidate.
  * accelerated calls: on improvement, burst the completed prefix to
    acceleratedCallsCount fresh peers of each level the prefix now covers
    (:438-451).
  * no Byzantine attack modes, no desynchronized start, no blacklist
    (nodes can only be down); done nodes keep verifying their queues.

Distribution-parity approximations (as in batched Handel): counter-hash
emission order instead of the shuffled peer lists, channel displacement
instead of an unbounded queue (top-K score-curated candidates), send-time
receiver counters, simultaneous same-ms deliveries.
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
from ..ops.bitops import block_mask

# GSF's popcounts are the lax form under both backends.  On the chip the
# 2048-node program with `popcount_words_pallas` leaves the CPU's (and the
# reference) from t = 190 ms on, `ind_seen` and `pend_ind` first, then half
# the traffic by 350 ms; with this form it equals the CPU's leaf for leaf
# through 520 ms.  The kernel alone is exact at every shape this program
# passes it, and Handel's 4096-node program is the other way round: equal
# to the CPU's through 430 ms with the kernel, off from t = 200 ms with
# this form (PERF.md section 6, PR 32, my chip runs).  So it is not the
# kernel but how each compiled program holds its popcounts: not found
# (ROADMAP B0), and not a choice ops.bitops could make for both.
from ..ops.bitops import _popcount_words_lax as popcount_words
from ..utils.javarand import JavaRandom
from ._agg_batched import INT32_MAX, BitsetAggBase, firing_capacity
from .gsf import GSFSignatureParameters


class BatchedGSF(BitsetAggBase):
    CAND_SLOTS = 8  # K: score-curated verification candidates per level

    def __init__(self, params: GSFSignatureParameters):
        self.params = params
        self._init_geometry(params.node_count)
        # prefix interval masks: pref_masks[k] = bits [0, 2^k)
        self.pref_masks = np.stack(
            [block_mask(0, 1 << k, self.n_words) for k in range(self.n_levels)]
        )

    def census_limits(self) -> dict:
        """`firing_peak` is read against the rows a round of the
        accelerated calls' arrivals and claim carries: `firing_capacity`
        of their [N, L-1, accelerated_calls_count] send; 0 where no such
        send is made (no calls, or a single one: the whole-M body)."""
        k = self.params.accelerated_calls_count
        if not (k > 1 and self.n_levels > 2):
            return {"firing_peak": 0}
        return {"firing_peak": firing_capacity((self.n_nodes, self.n_levels - 1, k))}

    def msg_size(self, mtype: int) -> int:
        # Size = level byte + bit field + the aggregated sig + our own sig
        # (SendSigs, GSFSignature.java:143-164)
        expected = 1 if mtype == 0 else 1 << (mtype - 1)
        return 1 + expected // 8 + 96

    # -- state ---------------------------------------------------------------
    def proto_init(self, n_nodes: int, pairing: np.ndarray):
        n, L, K = self.n_nodes, self.n_levels, self.CAND_SLOTS
        own = np.zeros((n, self.n_words), dtype=np.uint32)
        own[:, 0] = 1  # bit 0 = own signature (level 0)
        in_key, in_sigs = self._channel_init(n)
        ss = self.CHANNEL_DEPTH + 1
        cand_sigs = {
            f"cand_sig{i}": jnp.zeros((n, b.nl * K * b.w_pad), jnp.uint32)
            for i, b in enumerate(self.buckets)
        }
        remaining = np.zeros((n, L), dtype=np.int32)
        for l in range(1, L):
            remaining[:, l] = 1 << (l - 1)
        return {
            "ver": jnp.asarray(own),  # verified union, per level blocks
            "indiv": jnp.zeros((n, self.n_words), jnp.uint32),
            "ind_seen": jnp.zeros((n, self.n_words), jnp.uint32),
            "pend_ind": jnp.zeros((n, self.n_words), jnp.uint32),
            "in_key": in_key,
            **in_sigs,
            "displaced": jnp.int32(0),
            **self._not_ok_init(n),
            "in_aux": jnp.zeros((n, (L - 1) * ss), jnp.int32),  # prefix k
            "cand_key": jnp.full((n, (L - 1) * K), INT32_MAX, jnp.int32),  # rel
            "cand_pk": jnp.zeros((n, (L - 1) * K), jnp.int32),
            **cand_sigs,
            "ver_active": jnp.zeros(n, bool),
            "ver_done_t": jnp.zeros(n, jnp.int32),
            "ver_level": jnp.zeros(n, jnp.int32),
            "ver_rel": jnp.zeros(n, jnp.int32),
            "ver_pk": jnp.zeros(n, jnp.int32),
            "ver_single": jnp.zeros(n, bool),  # individual-sig verification
            "ver_sig": jnp.zeros((n, self.w_max), jnp.uint32),
            "remaining": jnp.asarray(remaining),
            "pos": jnp.zeros((n, L), jnp.int32),
            "sig_checked": jnp.zeros(n, jnp.int32),
            "pairing": jnp.asarray(pairing, jnp.int32),
        }

    # -- helpers -------------------------------------------------------------
    def _prefix_k(self, ver):
        """Number of consecutively complete levels from level 1 up
        (getLastFinishedLevel): the verified union is then >= [0, 2^k)."""
        if self.n_levels == 1:
            return jnp.zeros(ver.shape[0], jnp.int32)
        comp = self._level_stats(
            [
                popcount_words(self._blocks(ver, b))
                == jnp.asarray([self.bs[l] for l in b.levels], jnp.int32)[None, :]
                for b in self.buckets
            ]
        )
        return jnp.sum(jnp.cumprod(comp.astype(jnp.int32), axis=1), axis=1)

    def _eval_sig(self, sig, vb, ib, bs, lv):
        """evaluateSig (GSFSignature.java:478-520), broadcast-generic:
        sig/vb/ib are [..., w] (broadcastable against each other), bs/lv
        broadcast against the popcount shapes."""
        ver_card = popcount_words(vb)
        sig_card = popcount_words(sig)
        inter = popcount_words(sig & vb) > 0
        with_ind = sig | ib
        with_ind_v = with_ind | vb
        new_total = jnp.where(
            ver_card == 0,
            sig_card,
            jnp.where(inter, popcount_words(with_ind), popcount_words(with_ind_v)),
        )
        added = jnp.where(ver_card == 0, sig_card, new_total - ver_card)
        indiv_fallback = (
            (sig_card == 1) & (popcount_words(sig & ib) == 0)
        ).astype(jnp.int32)
        score = jnp.where(
            added <= 0,
            indiv_fallback,
            jnp.where(
                new_total == bs,
                1_000_000 - lv * 10,
                100_000 - lv * 100 + added,
            ),
        )
        return jnp.where(ver_card >= bs, 0, score)

    def _bs_arr(self, b):
        return jnp.asarray([self.bs[l] for l in b.levels], jnp.int32)

    # -- tick phase 1: commit due verifications ------------------------------
    def _commit(self, net, state):
        """updateVerifiedSignatures (GSFSignature.java:379-460), stacked."""
        p = self.params
        proto = state.proto
        t = state.time
        n, L = self.n_nodes, self.n_levels
        ids = jnp.arange(n, dtype=jnp.int32)
        lv_all = jnp.arange(1, L, dtype=jnp.int32)
        bs_all = jnp.asarray(self.lv_bs)

        due = proto["ver_active"] & (t >= proto["ver_done_t"])
        ver, indiv = proto["ver"], proto["indiv"]
        remaining = proto["remaining"]
        rel = proto["ver_rel"]
        pk = proto["ver_pk"]
        lvl = proto["ver_level"]

        # absorb the completed prefix (:397-411) at full width first: the
        # sender's consecutive-complete levels cover [0, 2^pk), which
        # includes the committed block and the receiver's levels 1..pk
        absorb = due & (pk >= lvl)
        interval = jnp.asarray(self.pref_masks)[jnp.clip(pk, 0, L - 1)]
        newly = popcount_words(interval & ~ver) > 0
        reset_r = absorb & newly
        ver_a = jnp.where(absorb[:, None], ver | interval, ver)

        improved_any = jnp.zeros(n, bool)
        ver_pieces, indiv_pieces = [], []
        for i, b in enumerate(self.buckets):
            lv = jnp.asarray(b.levels, jnp.int32)
            bs = self._bs_arr(b)
            m = due[:, None] & (lvl[:, None] == lv[None, :])  # [N, nl]
            r0 = rel[:, None] & (bs[None, :] - 1)
            sig_b = proto["ver_sig"][:, None, : b.w_pad]
            ver_b = self._blocks(ver_a, b)  # post-absorb ("may now be complete")
            indiv_b = self._blocks(indiv, b)

            # individual sig: set the indiv bit first (:383-385)
            single = m & proto["ver_single"][:, None]
            oh = self._onehot(r0, b.w_pad)
            new_indiv_b = jnp.where(single[..., None], indiv_b | oh, indiv_b)
            # holder.sigs |= indivVerifiedSig (:386)
            sigs = sig_b | new_indiv_b

            # absorbed commits act as a full block at the committed level
            full_block = jnp.asarray(
                np.stack(
                    [
                        np.asarray(
                            [
                                0xFFFFFFFF
                                if (j + 1) * 32 <= self.bs[l]
                                else ((1 << (self.bs[l] % 32)) - 1 if j * 32 < self.bs[l] else 0)
                                for j in range(b.w_pad)
                            ],
                            np.uint32,
                        )
                        for l in b.levels
                    ]
                )
            )
            sigs = jnp.where(
                (m & absorb[:, None])[..., None], full_block[None, :, :], sigs
            )

            # disjoint sets aggregate (:413-417)
            disjoint = (popcount_words(ver_b) > 0) & (
                popcount_words(sigs & ver_b) == 0
            )
            sigs = jnp.where((m & disjoint)[..., None], sigs | ver_b, sigs)

            # replacement on improvement (:419-431)
            improve = m & (
                (popcount_words(sigs) > popcount_words(ver_b))
                | reset_r[:, None]
            )
            ver_pieces.append(jnp.where(improve[..., None], sigs, ver_b))
            indiv_pieces.append(jnp.where(m[..., None], new_indiv_b, indiv_b))
            improved_any = improved_any | jnp.any(improve, axis=1)

        ver = self._assemble(ver_a, ver_pieces)
        indiv = self._assemble(indiv, indiv_pieces)

        # reset send budgets for levels >= the committed level (:421-423)
        lv_idx = jnp.arange(L, dtype=jnp.int32)[None, :]
        sizes = jnp.asarray([0] + [1 << (j - 1) for j in range(1, L)], jnp.int32)
        remaining = jnp.where(
            improved_any[:, None] & (lv_idx >= lvl[:, None]), sizes[None, :], remaining
        )

        state = state._replace(
            proto=dict(proto, ver=ver, indiv=indiv, remaining=remaining)
        )

        # accelerated calls (:438-451): after the merges, burst the
        # completed prefix to fresh peers of each level it now covers.
        # Each node committed at exactly one level (ver_level); burst at
        # level mm iff the commit improved, mm > committed level, and the
        # new prefix k reaches mm-1.  One stacked send over [N, L-1, acc].
        if p.accelerated_calls_count > 0 and L > 2:
            k_new = self._prefix_k(ver)
            acc = p.accelerated_calls_count
            havings = ver | jnp.asarray(self.pref_masks)[jnp.clip(k_new, 0, L - 1)]
            fan = jnp.minimum(jnp.int32(acc), bs_all)  # [L-1]
            burst = (
                improved_any[:, None]
                & (lvl[:, None] < lv_all[None, :])
                & (k_new[:, None] >= lv_all[None, :] - 1)
                & (lv_all[None, :] >= 2)
            )  # [N, L-1]
            take = jnp.where(
                burst,
                jnp.minimum(jnp.maximum(remaining[:, 1:], 0), fan[None, :]),
                0,
            )
            remaining = remaining.at[:, 1:].add(-take)
            state = state._replace(proto=dict(state.proto, remaining=remaining))

            ks = jnp.arange(acc, dtype=jnp.int32)
            offset = hash32(state.seed, ids[:, None], lv_all[None, :], t) & (
                bs_all[None, :] - 1
            )  # [N, L-1]
            relb = bs_all[None, :, None] + (
                (proto["pos"][:, 1:, None] + offset[:, :, None] + ks[None, None, :])
                & (bs_all[None, :, None] - 1)
            )  # [N, L-1, acc]
            mask_b = ks[None, None, :] < take[:, :, None]
            # the rows lie on the [N, L-1, acc] level axis: handed over as
            # they are, with the senders' full-width vectors, of which the
            # send cuts each landing row's low block (see _send_stacked)
            state = self._send_stacked(
                net,
                state,
                mask_b,
                ids[:, None, None],
                ids[:, None, None] ^ relb,
                None,
                havings,
                aux=k_new[:, None, None],
            )

        proto = state.proto
        total = popcount_words(proto["ver"])
        done_now = (
            improved_any & (state.done_at == 0) & ~state.down & (total >= p.threshold)
        )
        state = state._replace(
            done_at=jnp.where(done_now, t, state.done_at),
            proto=dict(proto, ver_active=proto["ver_active"] & ~due),
        )
        return state

    # -- tick phase 2: deliver channel slots into candidates -----------------
    def _channel_deliver(self, net, state):
        """onNewSig (GSFSignature.java:560-577): enqueue the aggregate and,
        once per sender, its individual signature."""
        proto = state.proto
        n, L, K = self.n_nodes, self.n_levels, self.CAND_SLOTS
        rel_mask = (1 << self.rel_bits) - 1
        ss = self.CHANNEL_DEPTH + 1

        in_key, due_all, empty_tpl = self._advance_channel(
            proto["in_key"], state.time
        )
        keys3 = self._keys_stacked(in_key)
        due3 = due_all.reshape(n, L - 1, ss)
        # only arrival slot (t mod D) and the fresh slot can be due at t
        keys2, due2 = self._due_pair_keys(keys3, due3, state.time)
        rel2 = keys2 & rel_mask
        pk3 = proto["in_aux"].reshape(n, L - 1, ss)
        pk2, _ = self._due_pair_keys(pk3, due3, state.time)

        ver, indiv = proto["ver"], proto["indiv"]
        seen, pend = proto["ind_seen"], proto["pend_ind"]

        key_pieces, pk_pieces = [], []
        cand_sig_updates = {}
        seen_pieces, pend_pieces = [], []
        for i, b in enumerate(self.buckets):
            sl = slice(b.lo - 1, b.hi)
            lv = jnp.asarray(b.levels, jnp.int32)
            bs = self._bs_arr(b)
            due = due2[:, sl, :]
            rel = rel2[:, sl, :]
            r0 = rel & (bs[None, :, None] - 1)
            sig_new = self._due_pair_sig(proto, i, state.time)  # [N, nl, 2, w_pad]
            pk_new = pk2[:, sl, :]

            # individual sig enqueue: once per sender per level — the bit
            # lives in the level block, so track it block-locally and
            # reassemble (no full-width onehot per slot)
            oh = jnp.where(
                due[..., None], self._onehot(r0, b.w_pad), jnp.uint32(0)
            )  # [N, nl, 2, w_pad]
            arrived_bits = jnp.bitwise_or.reduce(oh, axis=2)  # [N, nl, w_pad]
            seen_b = self._blocks(seen, b)
            pend_b = self._blocks(pend, b)
            fresh = arrived_bits & ~seen_b
            seen_pieces.append(seen_b | fresh)
            pend_pieces.append(pend_b | fresh)

            # merge [K existing + 2 new] candidates, keep top-K by score
            c_key = proto["cand_key"].reshape(n, L - 1, K)[:, sl, :]
            c_pk = proto["cand_pk"].reshape(n, L - 1, K)[:, sl, :]
            c_sig = self._sig_view(proto, i, K, prefix="cand_sig")

            all_key = jnp.concatenate(
                [c_key, jnp.where(due, rel, INT32_MAX)], axis=2
            )
            all_pk = jnp.concatenate([c_pk, pk_new], axis=2)
            all_sig = jnp.concatenate([c_sig, sig_new], axis=2)
            valid = all_key != INT32_MAX

            ver_b = self._blocks(ver, b)
            indiv_b = self._blocks(indiv, b)
            # prefix-carrying candidates are full-block in this level, so
            # the exact evaluateSig on block content scores them correctly
            score = self._eval_sig(
                all_sig,
                ver_b[:, :, None, :],
                indiv_b[:, :, None, :],
                bs[None, :, None],
                lv[None, :, None],
            )
            score = jnp.where(valid, score, -1)
            # drop worthless entries (checkSigs' iterator remove, :532-537)
            score = jnp.where(score == 0, -1, score)

            order = jnp.argsort(-score, axis=2)[:, :, :K]
            top_ok = jnp.take_along_axis(score, order, axis=2) > 0
            sel_key = jnp.where(
                top_ok, jnp.take_along_axis(all_key, order, axis=2), INT32_MAX
            )
            sel_pk = jnp.take_along_axis(all_pk, order, axis=2)
            sel_sig = jnp.take_along_axis(all_sig, order[..., None], axis=2)

            key_pieces.append(sel_key)
            pk_pieces.append(sel_pk)
            cand_sig_updates[f"cand_sig{i}"] = sel_sig.reshape(n, b.nl * K * b.w_pad)

        state = state._replace(
            proto=dict(
                proto,
                in_key=jnp.where(due_all, empty_tpl[None, :], in_key),
                cand_key=jnp.concatenate(key_pieces, axis=1).reshape(n, (L - 1) * K),
                cand_pk=jnp.concatenate(pk_pieces, axis=1).reshape(n, (L - 1) * K),
                pend_ind=self._assemble(pend, pend_pieces),
                ind_seen=self._assemble(seen, seen_pieces),
                **cand_sig_updates,
            )
        )
        return state

    # -- tick phase 3: periodic dissemination --------------------------------
    def _dissemination(self, net, state):
        """doCycle over started levels with send budgets
        (GSFSignature.java:289-343), all levels in ONE stacked send."""
        p = self.params
        proto = state.proto
        t = state.time
        n, L = self.n_nodes, self.n_levels
        ids = jnp.arange(n, dtype=jnp.int32)
        lv_all = jnp.arange(1, L, dtype=jnp.int32)
        bs_all = jnp.asarray(self.lv_bs)

        on_beat = (t >= 1) & (lax.rem(t - 1, jnp.int32(p.period_duration_ms)) == 0)
        may_send = on_beat & ~state.down

        k = self._prefix_k(proto["ver"])
        havings = proto["ver"] | jnp.asarray(self.pref_masks)[
            jnp.clip(k, 0, L - 1)
        ]
        complete = self._level_stats(
            [
                popcount_words(self._lows(havings, b)) >= self._bs_arr(b)[None, :]
                for b in self.buckets
            ]
        )
        started = (t >= lv_all[None, :] * jnp.int32(p.timeout_per_level_ms)) | complete
        remaining = proto["remaining"][:, 1:]
        mask = may_send[:, None] & started & (remaining > 0)  # [N, L-1]

        offset = hash32(state.seed, ids[:, None], lv_all[None, :]) & (
            bs_all[None, :] - 1
        )
        pos = proto["pos"][:, 1:]
        rel = (bs_all[None, :] + ((pos + offset) & (bs_all[None, :] - 1))).astype(
            jnp.int32
        )
        new_pos = proto["pos"].at[:, 1:].set(jnp.where(mask, pos + 1, pos))
        new_remaining = proto["remaining"].at[:, 1:].add(-mask.astype(jnp.int32))
        state = state._replace(
            proto=dict(proto, pos=new_pos, remaining=new_remaining)
        )

        # one row per (node, level): the [N, L-1, 1] level axis
        state = self._send_stacked(
            net,
            state,
            mask[:, :, None],
            ids[:, None, None],
            (ids[:, None] ^ rel)[:, :, None],
            None,
            [self._lows(havings, b) for b in self.buckets],
            aux=k[:, None, None],
        )
        return state

    # -- tick phase 4: start verifications (checkSigs) -----------------------
    def _select(self, net, state, view=None):
        """Global best-scored candidate across levels
        (GSFSignature.java:524-558).

        `view` (tick() passes it) holds the BOUNDARY state — candidates,
        pending individuals and aggregates as of the end of the previous
        tick — matching the reference's boundary-fired checkSigs
        conditional task (GSFSignature.java:631-632, Network.java:533-565;
        same mechanism as handel_batched._select).  Write-backs are
        compare-and-clear (on the sender-rel key) / bit-clear merges.
        Write-backs target the viewed entry by (key, cardinality)
        identity matched against any current slot of the level — see the
        equivalent handel_batched._select note."""
        proto = state.proto
        v = proto if view is None else {**proto, **view}
        t = state.time
        n, L, K = self.n_nodes, self.n_levels, self.CAND_SLOTS
        ids = jnp.arange(n, dtype=jnp.int32)

        free = ~proto["ver_active"] & ~state.down & (t >= 1)
        ver, indiv, pend = v["ver"], v["indiv"], v["pend_ind"]

        score_p, rel_p, pk_p, kidx_p = [], [], [], []
        key_pieces, pend_pieces, vcard_pieces, ccard_pieces = [], [], [], []
        for i, b in enumerate(self.buckets):
            sl = slice(b.lo - 1, b.hi)
            lv = jnp.asarray(b.levels, jnp.int32)
            bs = self._bs_arr(b)
            c_key = v["cand_key"].reshape(n, L - 1, K)[:, sl, :]
            c_pk = v["cand_pk"].reshape(n, L - 1, K)[:, sl, :]
            c_sig = self._sig_view(v, i, K, prefix="cand_sig")
            valid = c_key != INT32_MAX
            ver_b = self._blocks(ver, b)
            indiv_b = self._blocks(indiv, b)
            score = self._eval_sig(
                c_sig,
                ver_b[:, :, None, :],
                indiv_b[:, :, None, :],
                bs[None, :, None],
                lv[None, :, None],
            )
            score = jnp.where(valid, score, -1)
            # curation: drop worthless entries permanently (condemn mask,
            # applied by entry identity below)
            key_pieces.append(valid & (score == 0))
            vcard_pieces.append(popcount_words(c_sig))
            cur_sig = self._sig_view(proto, i, K, prefix="cand_sig")
            ccard_pieces.append(popcount_words(cur_sig))
            kbest = jnp.argmax(score, axis=2)
            sbest = jnp.take_along_axis(score, kbest[..., None], axis=2)[..., 0]

            # individual pending representative: lowest pending bit
            pend_b = self._blocks(pend, b)
            has_pend = popcount_words(pend_b) > 0
            m_ind = self._lowest_bit(pend_b)
            oh = self._onehot(m_ind & (bs[None, :] - 1), b.w_pad)
            s_ind = self._eval_sig(
                oh, ver_b, indiv_b, bs[None, :], lv[None, :]
            )
            s_ind = jnp.where(has_pend, s_ind, -1)
            # worthless individuals are dropped too
            pend_pieces.append(
                jnp.where(
                    (has_pend & (s_ind == 0))[..., None], pend_b & ~oh, pend_b
                )
            )

            use_ind = s_ind > sbest
            score_p.append(jnp.maximum(sbest, s_ind))
            rel_p.append(
                jnp.where(
                    use_ind,
                    bs[None, :] + (m_ind & (bs[None, :] - 1)),
                    jnp.take_along_axis(c_key, kbest[..., None], axis=2)[..., 0],
                )
            )
            pk_p.append(
                jnp.where(
                    use_ind,
                    0,
                    jnp.take_along_axis(c_pk, kbest[..., None], axis=2)[..., 0],
                )
            )
            kidx_p.append(jnp.where(use_ind, -1, kbest))

        l_score = self._level_stats(score_p)  # [N, L-1]
        l_rel = self._level_stats(rel_p)
        l_pk = self._level_stats(pk_p)
        l_kidx = self._level_stats(kidx_p)
        # pend writes are pure bit-CLEARS on the view: merge as a clear
        # mask onto the current array (a bit deliver(t) set stays set)
        pend_after_view = self._assemble(pend, pend_pieces)
        pend_clear = v["pend_ind"] & ~pend_after_view
        pend = proto["pend_ind"] & ~pend_clear
        # curation removal by (key, cardinality) ENTRY IDENTITY matched
        # against any current slot of the level (the key alone is only the
        # sender rel; a same-sender refresh differs in cardinality — see
        # the handel_batched note)
        condemn3 = jnp.concatenate(key_pieces, axis=1)  # [N, L-1, K]
        vkey3 = v["cand_key"].reshape(n, L - 1, K)
        vcard3 = jnp.concatenate(vcard_pieces, axis=1)
        ckey3 = proto["cand_key"].reshape(n, L - 1, K)
        ccard3 = jnp.concatenate(ccard_pieces, axis=1)
        cleared = self._entry_clear(ckey3, ccard3, vkey3, vcard3, condemn3)
        new_key3 = jnp.where(cleared, INT32_MAX, ckey3)

        # global best across levels; ascending-level iteration with strict >
        # in the original = first maximum wins = argmax
        lidx = jnp.argmax(l_score, axis=1)
        best_score = jnp.take_along_axis(l_score, lidx[:, None], axis=1)[:, 0]
        best_level = (lidx + 1).astype(jnp.int32)
        best_rel = jnp.take_along_axis(l_rel, lidx[:, None], axis=1)[:, 0]
        best_pk = jnp.take_along_axis(l_pk, lidx[:, None], axis=1)[:, 0]
        best_kidx = jnp.take_along_axis(l_kidx, lidx[:, None], axis=1)[:, 0]

        can = free & (best_score > 0)
        sel_single = best_kidx < 0

        # load the chosen sig into the verification register
        bs_sel = jnp.asarray(self.lv_bs)[jnp.maximum(best_level - 1, 0)]
        ver_sig = proto["ver_sig"]
        for i, b in enumerate(self.buckets):
            m = can & (best_level >= b.lo) & (best_level <= b.hi)
            c_sig = self._sig_view(v, i, K, prefix="cand_sig")
            li = jnp.clip(best_level - b.lo, 0, b.nl - 1)
            c_lv = jnp.take_along_axis(c_sig, li[:, None, None, None], axis=1)[:, 0]
            safe_k = jnp.maximum(best_kidx, 0)
            from_buf = jnp.take_along_axis(c_lv, safe_k[:, None, None], axis=1)[:, 0]
            single = self._onehot(best_rel & (bs_sel - 1), b.w_pad)
            sig_l = jnp.where(sel_single[:, None], single, from_buf)
            pad = jnp.zeros((n, self.w_max - b.w_pad), jnp.uint32)
            ver_sig = jnp.where(
                m[:, None], jnp.concatenate([sig_l, pad], axis=1), ver_sig
            )

        # clear the individual pending bit on selection (bit best_rel of the
        # full-width rel-space vector)
        oh_full = self._onehot(best_rel, self.n_words)
        pend = jnp.where((can & sel_single)[:, None], pend & ~oh_full, pend)

        # remove the chosen buffer candidate by (key, cardinality) entry
        # identity against the chosen level's CURRENT slots
        lvl_idx = jnp.maximum(best_level - 1, 0)
        sel_card = jnp.take_along_axis(
            jnp.take_along_axis(vcard3, lvl_idx[:, None, None], axis=1)[:, 0],
            jnp.maximum(best_kidx, 0)[:, None],
            axis=1,
        )[:, 0]
        remove = can & ~sel_single
        new_key3 = self._remove_chosen(
            ids, new_key3, ccard3, lvl_idx, best_rel, sel_card, remove
        )
        new_cand_key = new_key3.reshape(n, (L - 1) * K)

        state = state._replace(
            proto=dict(
                proto,
                cand_key=new_cand_key,
                pend_ind=pend,
                ver_active=jnp.where(can, True, proto["ver_active"]),
                ver_done_t=jnp.where(can, t + proto["pairing"], proto["ver_done_t"]),
                ver_level=jnp.where(can, best_level, proto["ver_level"]),
                ver_rel=jnp.where(can, best_rel, proto["ver_rel"]),
                ver_pk=jnp.where(can, best_pk, proto["ver_pk"]),
                ver_single=jnp.where(can, sel_single, proto["ver_single"]),
                ver_sig=ver_sig,
                sig_checked=proto["sig_checked"] + can.astype(jnp.int32),
            )
        )
        return state

    # -- engine hooks --------------------------------------------------------
    def tick(self, net, state):
        # boundary-view selection, like handel_batched.tick: checkSigs is
        # a conditional task fired at the ms boundary, so it sees
        # candidates/pending/aggregates as of the END of the previous tick
        pre_cand = {
            k: state.proto[k]
            for k in ("cand_key", "cand_pk", "pend_ind")
            + tuple(f"cand_sig{i}" for i in range(len(self.buckets)))
        }
        state = self._channel_deliver(net, state)
        pre_merge = {k: state.proto[k] for k in ("ver", "indiv")}
        state = self._commit(net, state)
        state = self._select(net, state, view={**pre_cand, **pre_merge})
        return state

    def all_done(self, state):
        live = ~state.down
        return jnp.all(jnp.where(live, state.done_at > 0, True))


def make_gsf(
    params: Optional[GSFSignatureParameters] = None,
    capacity: int = 8,  # generic ring unused by this protocol
    seed: int = 0,
):
    """Host-side construction mirroring GSFSignature.init (gsf.py:init):
    same JavaRandom stream for node building and the down-node draw."""
    params = params or GSFSignatureParameters()
    n = params.node_count
    nb = registry_node_builders.get_by_name(params.node_builder_name)
    latency = registry_network_latencies.get_by_name(params.network_latency_name)
    rd = JavaRandom(0)

    nodes = [Node(rd, nb) for _ in range(n)]
    down = np.zeros(n, dtype=bool)
    set_down = 0
    while set_down < params.nodes_down:
        i = rd.next_int(n)
        if not down[i] and i != 1:
            # node 1 kept up to help debugging (GSFSignature.java:621)
            down[i] = True
            set_down += 1

    pairing = np.maximum(
        1, (params.pairing_time * np.array([nd.speed_ratio for nd in nodes]))
    ).astype(np.int32)

    proto = BatchedGSF(params)
    # dissemination fires at t >= 1 with (t - 1) % period == 0
    proto.BEAT_PERIOD = params.period_duration_ms
    proto.BEAT_RESIDUES = (1 % params.period_duration_ms,)
    city_index = getattr(latency, "city_index", None)
    cols = build_node_columns(nodes, city_index)
    # flat mode: aggregation messaging bypasses the generic store entirely
    # (the channel in _agg_batched), so keep the per-tick scan minimal
    net = BatchedNetwork(proto, latency, n, capacity=capacity, wheel_rows=0)
    state = net.init_state(
        cols,
        seed=seed,
        proto=proto.proto_init(n, pairing),
        down=down,
    )
    return net, state
