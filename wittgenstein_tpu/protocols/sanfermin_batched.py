"""Batched SanFerminSignature: binomial-tree pairwise aggregation as
vectorized per-tick kernels.

Reference semantics: protocols/SanFerminSignature.java — the swap
request/reply state machine (:229-323), timeout re-picks (:329-369),
goNextLevel descent (:379-419), pairingTime aggregation commit (:434-455) —
via the oracle port `protocols/sanfermin.py`.

TPU-first design:

  * binary-id interval sets (SanFerminHelper.java:46-96) are XOR blocks:
    with W = log2(N), the candidate set at prefix length `cpl` is
    { me ^ (bs + r) : r in [0, bs) } with bs = 2^(W-cpl-1), and the "exact"
    candidate (own-set index pick, SanFerminHelper.java:129-136) is r = 0
    (partner = me ^ bs).  No interval arithmetic at runtime — just XOR.
  * pickNextNodes' used-index set collapses to ONE cursor per node
    (levels never revisit), walked in the reference's own order
    (SanFerminHelper.java:123-157), quirks included: a level's first call
    takes the exact candidate (the block member at the node's own-set
    index `idx`) and then candidate_count more by their index in the list
    with that member REMOVED; every later call indexes the WHOLE list
    again; both skip the indices used so far, `idx` among them.  So the
    cursor is the next index to try, index i is member i + (i >= idx) in
    the first call and member i after it (the member after the exact one
    is never asked, the last member of the first call is asked twice),
    and a call that finds no index left sends nothing and arms nothing:
    the node is out of picks for good.  The order is load-bearing, not
    cosmetic: every re-picker of a block asks its members 0, 1, 2, ... in
    turn, and a node out of picks costs its exact partners of every later
    level a reply timeout (a uniform walk of the block finishes 4% more
    nodes than the reference and reads P50 12% early at 256 nodes).
    The reference shuffles each call's list; with counter-based latencies
    the order inside one multicast carries nothing.
  * pending_nodes is a packed absolute-id bitset [N, N/32]; reset on level
    entry, bit-tested on replies.
  * reply timeouts are STACKED as the reference's (:356-366): every send
    arms its own timeout at send time + reply_timeout in a per-node ring
    of TIMEOUT_RING (deadline, level) slots, and every one that comes due
    while `cpl` is still the level it was armed at re-picks, as does every
    NO from a pending node.  One pick event runs per node and tick; more
    wait in `resend` for the next tick.  A send that finds the ring full
    is counted in `state.dropped` (the store's own "this run is not
    exact" counter), never silently capped.  The ring's depth is from the
    oracle: at 256 and 4096 nodes (candidate_count 1 and 4) no node ever
    has a second live timeout at its level — a NO carries the REPLIER's
    level, which is behind the request's (a replier ahead has the level
    cached and answers OK), so the requester drops it at the level check
    (:274) and the NO branch never re-picks — and 4 slots leave room for
    the hand-made states of tests/test_sanfermin_batched.py that do stack.
  * same-tick transition races (multiple valid REQ/REP arrivals) resolve
    by lowest ring slot; the losers' content is simply not aggregated —
    the oracle's LIFO-in-ms processing picks an equally arbitrary winner
    (every reply is still answered).
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from ..core.node import build_node_columns
from ..core.registries import registry_network_latencies
from ..engine import BatchedNetwork, BatchedProtocol, Emission
from ..engine.core import EMISSION_SCOPES
from ..utils.more_math import log2
from .sanfermin import SanFerminSignature, SanFerminSignatureParameters

INT32_MAX = jnp.int32(2**31 - 1)


def emission_capacity(rows: int) -> int:
    """Rows a round of SanFermin's two every-tick emissions stores
    (`Emission.capacity`; one number for both, the limit of the census's
    `firing_peak`), from the K rows of the tick's requests alone
    (nodes x (1 + candidate_count)): 1/32 of them up to a multiple of
    128, and 256 at least (a small network's share swings more): 256 of
    the 8192 requests a tick at 4096 nodes.  The deliver's replies state
    the same: a reply answers a request that came due this millisecond,
    so a tick's replies are some earlier ticks' requests spread over the
    latency's width and peak under them, whatever rows the store's view
    has (1280 there).

    A node sends when it enters a level, when a reply timeout of its
    level comes due or a pending partner says NO, and it replies to the
    requests due this millisecond, so a tick fires a handful in a
    thousand of its rows.  Rows with their mask set, tick by tick over
    whole runs from t=0 (sandbox CPU, `sent_req`'s and `msg_sent`'s
    growth; the program's integers): at 4096 nodes on the deployment's
    store, 3 seeds x 2400 ticks (PR 47's issue), requests mean 35.8,
    p99 116, at most 144 / 138 / 150 (at t = 122-196 ms), replies mean
    26.9, p99 73, at most 84 / 84 / 89; at 256 nodes, 4 seeds x 2400
    ticks (PR 47), requests mean 1.39, p99 11, at most 16-20, replies
    mean 0.94, p99 7, at most 9-11.  So one round holds every tick seen
    with a factor of 1.7 to spare.  The store counts what fired, every
    emission that took a second round and the most one fired
    (engine.core.Census `fired_rows`, `firing_overflows`, `firing_peak`);
    none is cut."""
    return min(rows, max(256, -(-rows // 32 // 128) * 128))


class BatchedSanFermin(BatchedProtocol):
    MSG_TYPES = ["SWAP_REQ", "SWAP_REP_OK", "SWAP_REP_NO"]
    PAYLOAD_WIDTH = 2  # (level, agg_value)
    TICK_INTERVAL = 1  # timeouts + pairing commits need per-ms ticks
    # slots of the per-node ring of stacked reply timeouts (module docstring)
    TIMEOUT_RING = 4
    REQUIRED_SCOPES = tuple(EMISSION_SCOPES.values())  # simlint SL601 holds them live

    def __init__(self, params: SanFerminSignatureParameters):
        self.params = params
        self.n_nodes = params.node_count
        self.w = log2(self.n_nodes)
        assert 1 << self.w == self.n_nodes, "node_count must be a power of two"
        self.n_words = max(1, self.n_nodes // 32)

    def msg_size(self, mtype: int) -> int:
        return 4 + self.params.signature_size  # uint32 + sig (both types)

    @property
    def round_rows(self) -> int:
        """Both emissions' `capacity` (`emission_capacity` of the tick's requests)."""
        return emission_capacity(self.n_nodes * (1 + max(1, self.params.candidate_count)))

    def census_limits(self) -> dict:
        return {"firing_peak": self.round_rows}

    def proto_init(self, n_nodes: int, seed: int = 0):
        w = self.w
        cache_val = jnp.zeros((n_nodes, w + 1), jnp.int32)
        cache_ok = jnp.zeros((n_nodes, w + 1), bool)
        # the t=1 goNextLevel is pre-applied: cpl = W-1, cache[W-1] = 1
        cache_val = cache_val.at[:, w - 1].set(1)
        cache_ok = cache_ok.at[:, w - 1].set(True)
        # ... including its send bookkeeping (cursor/pending for the
        # exact-candidate + candidate_count initial contacts); the matching
        # emission rows are built by initial_emissions from the same picks
        cc = max(1, self.params.candidate_count)
        ids = jnp.arange(n_nodes, dtype=jnp.int32)
        cpl0 = jnp.full(n_nodes, w - 1, jnp.int32)
        pending = jnp.zeros((n_nodes, self.n_words), jnp.uint32)
        cursor0 = jnp.zeros(n_nodes, jnp.int32)
        picks, cursor0 = self._picks(ids, cpl0, cursor0, jnp.ones(n_nodes, bool), cc)
        for partner, ok in picks:
            pending = jnp.where(
                ok[:, None], pending | self._onehot_words(partner), pending
            )
        return {
            "cpl": jnp.full(n_nodes, w - 1, jnp.int32),
            "agg": jnp.ones(n_nodes, jnp.int32),
            "done": jnp.zeros(n_nodes, bool),
            "thr_done": jnp.zeros(n_nodes, bool),
            "thr_at": jnp.zeros(n_nodes, jnp.int32),
            "swapping": jnp.zeros(n_nodes, bool),
            "swap_add": jnp.zeros(n_nodes, jnp.int32),
            "swap_t": jnp.zeros(n_nodes, jnp.int32),
            "cache_val": cache_val,
            "cache_ok": cache_ok,
            "pending": pending,
            "cursor": cursor0,
            # pick events waiting for a tick of their own (NO replies from
            # pending nodes, timeouts that came due together)
            "resend": jnp.zeros(n_nodes, jnp.int32),
            # the ring of stacked reply timeouts: deadline (0 = free) and
            # the level it was armed at; slot 0 is the t=1 send's
            "tmo_t": jnp.zeros((n_nodes, self.TIMEOUT_RING), jnp.int32)
            .at[:, 0]
            .set(1 + self.params.reply_timeout),
            "tmo_lvl": jnp.full((n_nodes, self.TIMEOUT_RING), w - 1, jnp.int32),
            "sent_req": jnp.zeros(n_nodes, jnp.int32),
            "recv_req": jnp.zeros(n_nodes, jnp.int32),
        }

    # -- candidate enumeration ----------------------------------------------
    def _bs(self, cpl):
        """Candidate-block size at prefix length cpl: 2^(W-cpl-1)."""
        return (jnp.int32(1) << (self.w - 1 - cpl)).astype(jnp.int32)

    def _picks(self, ids, cpl, cursor, entering, cc):
        """One call of pickNextNodes (SanFerminHelper.java:123-157) for
        every node: `cursor` is the next index of the level's candidate
        list to try, `entering` says the call is the level's first.  Returns
        ([(partner, valid)] * (1 + cc), the cursor after the call): row 0
        is the exact candidate (the first call's alone), rows 1..cc the
        next cc indices that are neither used nor the own-set index `idx`;
        the first call indexes the list with the exact candidate removed
        (bs - 1 long: index i is member i + (i >= idx)), every later call
        the whole list (bs long: index i is member i)."""
        bs = self._bs(cpl)
        idx = ids & (bs - 1)
        block = (ids ^ bs) & ~(bs - 1)
        length = jnp.where(entering, bs - 1, bs)
        picks = [(block | idx, entering)]
        for _ in range(cc):
            i = cursor + (cursor == idx).astype(jnp.int32)
            valid = i < length
            member = i + (entering & (i >= idx)).astype(jnp.int32)
            picks.append((block | member, valid))
            cursor = jnp.where(valid, i + 1, cursor)
        return picks, cursor

    def _onehot_words(self, idx):
        """Absolute-id onehot over the packed [n_words] axis."""
        word = idx // 32
        bit = (jnp.uint32(1) << (idx % 32).astype(jnp.uint32)).astype(jnp.uint32)
        cols = jnp.arange(self.n_words, dtype=jnp.int32)
        return jnp.where(
            cols[None, :] == word[:, None], bit[:, None], jnp.uint32(0)
        )

    def _getbit(self, words, rows, idx):
        """Bit `idx[K]` of the packed row `words[rows[K]]`."""
        w = words[rows, idx // 32]
        return (w >> (idx % 32).astype(jnp.uint32)) & jnp.uint32(1)

    def _send_requests(self, state, mask, picks, cursor, proto):
        """_send_to_nodes (SanFerminSignature.java:329-369): contact the
        candidates of one `_picks` call (exact-first on level entry,
        candidate_count per re-pick), update pending and the cursor (to
        `cursor`, the call's), arm this send's own timeout.
        Returns (proto, emission, sends that found the ring full)."""
        k = len(picks)
        n = self.n_nodes
        ids = jnp.arange(n, dtype=jnp.int32)
        cpl, agg = proto["cpl"], proto["agg"]

        rows_mask, rows_from, rows_to = [], [], []
        pending = proto["pending"]
        for partner, valid in picks:
            m = mask & valid
            rows_mask.append(m)
            rows_from.append(ids)
            rows_to.append(partner)
            pending = jnp.where(
                m[:, None], pending | self._onehot_words(partner), pending
            )
        mask_k = jnp.stack(rows_mask, 1).reshape(-1)
        from_k = jnp.stack(rows_from, 1).reshape(-1)
        to_k = jnp.stack(rows_to, 1).reshape(-1)
        em = Emission(
            mask=mask_k,
            from_idx=from_k,
            to_idx=jnp.clip(to_k, 0, n - 1),
            mtype=self.mtype("SWAP_REQ"),
            payload=jnp.stack(
                [
                    jnp.repeat(cpl[:, None], k, 1).reshape(-1),
                    jnp.repeat(agg[:, None], k, 1).reshape(-1),
                ],
                axis=1,
            ),
            capacity=self.round_rows,
        )
        free = proto["tmo_t"] == 0
        arm = mask[:, None] & free & (jnp.cumsum(free.astype(jnp.int32), axis=1) == 1)
        proto = dict(
            proto,
            pending=pending,
            cursor=jnp.where(mask, cursor, proto["cursor"]),
            sent_req=proto["sent_req"]
            + jnp.sum(
                jnp.stack(rows_mask, 1).astype(jnp.int32), axis=1
            ),
            # this send's own reply timeout, in the ring's first free slot
            # (register_task at net.time + replyTimeout, :356-366)
            tmo_t=jnp.where(arm, state.time + self.params.reply_timeout, proto["tmo_t"]),
            tmo_lvl=jnp.where(arm, cpl[:, None], proto["tmo_lvl"]),
        )
        return proto, em, jnp.sum((mask & ~jnp.any(free, axis=1)).astype(jnp.int32))

    # -- message handling ----------------------------------------------------
    def deliver(self, net, state, deliver_mask):
        p = self.params
        proto = dict(state.proto)
        n = self.n_nodes
        c = deliver_mask.shape[0]
        t = state.time
        ids = jnp.arange(n, dtype=jnp.int32)
        to, frm = state.msg_to, state.msg_from
        lvl_p = jnp.clip(state.msg_payload[:, 0], 0, self.w)
        val_p = state.msg_payload[:, 1]
        slot = jnp.arange(c, dtype=jnp.int32)

        is_req = deliver_mask & (state.msg_type == self.mtype("SWAP_REQ"))
        is_ok = deliver_mask & (state.msg_type == self.mtype("SWAP_REP_OK"))
        is_no = deliver_mask & (state.msg_type == self.mtype("SWAP_REP_NO"))

        cpl, done, swapping = proto["cpl"], proto["done"], proto["swapping"]
        cache_ok, cache_val = proto["cache_ok"], proto["cache_val"]
        # sender in receiver's candidate set at level L:
        # (me ^ from) in [bs(L), 2*bs(L))  (SanFerminHelper.java:46-96)
        xorv = to ^ frm
        bs_p = (jnp.int32(1) << jnp.clip(self.w - 1 - lvl_p, 0, self.w)).astype(jnp.int32)
        is_cand_at_lvl = (xorv >= bs_p) & (xorv < 2 * bs_p)

        proto["recv_req"] = proto["recv_req"] + jnp.zeros(n, jnp.int32).at[to].add(
            is_req.astype(jnp.int32), mode="drop"
        )

        # ---- on_swap_request (:229-270) -----------------------------------
        lvl_mismatch = done[to] | (lvl_p != cpl[to])
        cached = cache_ok[to, lvl_p]
        # case A1: stale/done receiver with a cached value -> OK(cached)
        a1 = is_req & lvl_mismatch & cached
        # case A2: stale/done receiver, no cache -> NO(0) at receiver's cpl,
        # remembering the offered value when the sender is a candidate
        a2 = is_req & lvl_mismatch & ~cached
        # case B: level match while swapping -> optimistic OK(agg)
        b = is_req & ~lvl_mismatch & swapping[to]
        # case C: level match, idle -> valid swap request (transition)
        c_req = is_req & ~lvl_mismatch & ~swapping[to] & is_cand_at_lvl

        # replies: cases A1/A2/B only — a valid swap REQUEST (case C) is
        # absorbed into the receiver's transition and NEVER answered; the
        # requester is rescued by its reply timeout (the reference's
        # requester-loses asymmetry, SanFerminSignature.java:251-262)
        rep_ok = a1 | b
        rep_val = jnp.where(a1, cache_val[to, lvl_p], proto["agg"][to])
        rep_lvl = jnp.where(a2, cpl[to], lvl_p)
        reply_em = Emission(
            mask=a1 | a2 | b,
            from_idx=to,
            to_idx=frm,
            mtype=jnp.where(
                rep_ok, self.mtype("SWAP_REP_OK"), self.mtype("SWAP_REP_NO")
            ),
            payload=jnp.stack([rep_lvl, jnp.where(rep_ok, rep_val, 0)], axis=1),
            capacity=self.round_rows,  # the requests': `emission_capacity`
        )

        # A2 cache store (winner = lowest slot per (node, level))
        store = a2 & is_cand_at_lvl
        winner = jnp.full((n, self.w + 1), c, jnp.int32)
        winner = winner.at[to, lvl_p].min(jnp.where(store, slot, c), mode="drop")
        is_wstore = store & (winner[to, lvl_p] == slot)
        # scatter ONLY the winner rows (losers routed out of bounds):
        # writing `where(win, new, current)` for every row would race —
        # XLA's duplicate-index .set order is unspecified, so a stale row's
        # "current" write can clobber the winner's value
        w_to = jnp.where(is_wstore, to, n)
        cache_val = cache_val.at[w_to, lvl_p].set(val_p, mode="drop")
        cache_ok = cache_ok.at[w_to, lvl_p].set(True, mode="drop")
        proto["cache_val"], proto["cache_ok"] = cache_val, cache_ok

        # ---- on_swap_reply (:272-323) -------------------------------------
        live = ~done[to] & (lvl_p == cpl[to]) & ~swapping[to]
        in_pending = self._getbit(proto["pending"], to, frm) == 1
        ok_trigger = is_ok & live & (in_pending | is_cand_at_lvl)
        no_trigger = is_no & live & in_pending

        # ---- transitions: winner per node among C + OK triggers -----------
        trig = c_req | ok_trigger
        twin = jnp.full(n, c, jnp.int32)
        twin = twin.at[to].min(jnp.where(trig, slot, c), mode="drop")
        has_t = twin < c
        tslot = jnp.clip(twin, 0, c - 1)
        add_val = val_p[tslot]
        proto["swapping"] = swapping | has_t
        proto["swap_add"] = jnp.where(has_t, add_val, proto["swap_add"])
        proto["swap_t"] = jnp.where(has_t, t + p.pairing_time, proto["swap_t"])

        # every NO from a pending partner is one more pick event for the
        # tick phase (they wait in `resend` until consumed)
        proto["resend"] = proto["resend"].at[to].add(
            no_trigger.astype(jnp.int32), mode="drop"
        )

        return state._replace(proto=proto), [reply_em]

    # -- per-tick: commits, level descent, timeouts, sends -------------------
    def tick(self, net, state):
        p = self.params
        proto = dict(state.proto)
        t = state.time
        n = self.n_nodes
        w = self.w

        # 1. aggregation commit at swap_t (do_aggregate + goNextLevel,
        # :434-455, :379-419)
        commit = proto["swapping"] & (t >= proto["swap_t"]) & (proto["swap_t"] > 0)
        agg = jnp.where(commit, proto["agg"] + proto["swap_add"], proto["agg"])

        thr_now = commit & ~proto["thr_done"] & (agg >= p.threshold)
        proto["thr_done"] = proto["thr_done"] | thr_now
        proto["thr_at"] = jnp.where(thr_now, t + 2 * p.pairing_time, proto["thr_at"])

        finish = commit & (proto["cpl"] == 0)
        descend = commit & ~finish
        proto["done"] = proto["done"] | finish
        state = state._replace(
            done_at=jnp.where(finish, t + 2 * p.pairing_time, state.done_at)
        )

        new_cpl = jnp.where(descend, proto["cpl"] - 1, proto["cpl"])
        lvl_row = jnp.arange(w + 1, dtype=jnp.int32)[None, :]
        proto["cache_val"] = jnp.where(
            descend[:, None] & (lvl_row == new_cpl[:, None]),
            agg[:, None],
            proto["cache_val"],
        )
        proto["cache_ok"] = proto["cache_ok"] | (
            descend[:, None] & (lvl_row == new_cpl[:, None])
        )
        proto["agg"] = agg
        proto["cpl"] = new_cpl
        proto["swapping"] = proto["swapping"] & ~commit
        proto["pending"] = jnp.where(
            descend[:, None], jnp.uint32(0), proto["pending"]
        )
        proto["cursor"] = jnp.where(descend, 0, proto["cursor"])
        # a level left takes its waiting pick events and its timeouts along
        # (levels never revisit, so none of them could fire again)
        proto["resend"] = jnp.where(commit, 0, proto["resend"])
        tmo_t = jnp.where(commit[:, None], 0, proto["tmo_t"])

        # 2. reply timeouts: every one that comes due while the level is
        # the one it was armed at is a pick event (:356-366); due slots
        # are free again
        due = (tmo_t > 0) & (t >= tmo_t)
        fired = due & (proto["tmo_lvl"] == proto["cpl"][:, None])
        proto["tmo_t"] = jnp.where(due, 0, tmo_t)
        events = proto["resend"] + jnp.sum(fired.astype(jnp.int32), axis=1)

        # 3. sends: level entry (exact-first) or ONE re-pick (timeout / NO);
        # a node out of picks consumes its events and sends nothing
        # ("is OUT", :334-338), the others keep theirs for the next tick
        ids = jnp.arange(n, dtype=jnp.int32)
        cc = max(1, p.candidate_count)
        picks, cursor = self._picks(ids, proto["cpl"], proto["cursor"], descend, cc)
        has_pick = descend | picks[1][1]
        send = (descend | (events > 0)) & ~proto["done"] & has_pick
        proto["resend"] = jnp.where(
            has_pick & ~proto["done"], jnp.maximum(events - 1, 0), 0
        )
        proto, em, ring_full = self._send_requests(state, send, picks, cursor, proto)
        state = state._replace(proto=proto, dropped=state.dropped + ring_full)
        return net.apply_emission(state, em)

    def initial_emissions(self, net, state):
        """The pre-applied t=1 goNextLevel's sends: every node contacts its
        exact candidate (+ candidate_count more).  The matching cursor /
        pending / timeout bookkeeping is already baked into proto_init
        (the same _picks call), so this only builds the rows."""
        cc = max(1, self.params.candidate_count)
        k = 1 + cc
        n = self.n_nodes
        ids = jnp.arange(n, dtype=jnp.int32)
        cpl = state.proto["cpl"]
        picks, _ = self._picks(ids, cpl, jnp.zeros(n, jnp.int32), jnp.ones(n, bool), cc)
        rows_to, rows_mask = zip(*picks)
        return [
            Emission(
                mask=jnp.stack(rows_mask, 1).reshape(-1),
                from_idx=jnp.repeat(ids, k),
                to_idx=jnp.clip(jnp.stack(rows_to, 1).reshape(-1), 0, n - 1),
                mtype=self.mtype("SWAP_REQ"),
                payload=jnp.stack(
                    [
                        jnp.repeat(cpl[:, None], k, 1).reshape(-1),
                        jnp.repeat(state.proto["agg"][:, None], k, 1).reshape(-1),
                    ],
                    axis=1,
                ),
            )
        ]

    def all_done(self, state):
        return jnp.all(state.proto["done"])


def make_sanfermin(
    params: Optional[SanFerminSignatureParameters] = None,
    capacity: int = 1 << 14,
    seed: int = 0,
):
    """Host-side construction: the oracle builds the node population (same
    JavaRandom stream → same layout), baked into the engine."""
    params = params or SanFerminSignatureParameters()
    oracle = SanFerminSignature(params)
    net_o = oracle.network()
    latency = registry_network_latencies.get_by_name(params.network_latency_name)
    city_index = getattr(latency, "city_index", None)
    cols = build_node_columns(net_o.all_nodes, city_index)
    proto = BatchedSanFermin(params)
    net = BatchedNetwork(proto, latency, params.node_count, capacity=capacity)
    state = net.init_state(
        cols, seed=seed, proto=proto.proto_init(params.node_count, seed=seed)
    )
    return net, state
