"""Shared machinery for batched bitset-aggregation protocols (Handel, GSF).

Both protocols keep per-node contribution bitsets in the XOR-relative
layout (ops.bitops): bit j of node i's vector is node i^j, level l is the
static bit block [2^(l-1), 2^l), and re-addressing sender s's level-l
content into receiver i's space is the bit permutation j -> j ^ r0 with
r0 = (i^s) & (2^(l-1)-1).

The in-flight message channel is the finite-shape stand-in for the
oracle's per-ms message queue: per (receiver, level), D arrival-keyed
slots (earliest arrival wins; slot = arrival mod D) plus one freshest-
offer backstop slot that is always overwritten by the newest send — so
when a level's traffic dies out, the last content a laggard was offered
still delivers instead of being displaced.  Content is stored in the
RECEIVER's block-local bit space: the xor_shuffle re-addressing runs at
SEND time over the send rows (sparse — dissemination fires once per
period) instead of at delivery over every (level, slot) cell every tick,
which measured ~9x less shuffle work and took _channel_deliver from 80%
of the tick to a minority share.  Displacements (an ok send that wins
neither slot, or evicts a still-pending occupant) are counted in
proto["displaced"] — the channel analog of SimState.dropped.

Program-size design (the r4 rewrite, regrouped by the PR-11 density
pass): levels are grouped into WIDTH BUCKETS — consecutive levels of
EQUAL word width w_l = max(1, 2^(l-1)/32): the sub-word levels [1-6]
(w = 1) share one bucket and every wider level has its own, so w_pad is
always the exact width and no padding words are carried (see
_init_geometry) — and every per-level computation runs once per BUCKET
on a stacked [N, nl, ...] level axis instead of once per level.
Per-bucket channel/candidate content lives in flat 2D arrays
[N, nl*slots*w_pad] (large minor dims dodge XLA's (8,128) tile padding),
and block views of the full-width state vectors are pure
reshape/concat/shift pipelines — no gathers or scatters.  At 4096 nodes
this turns ~12 unrolled per-level bodies x 4 phases (plus ~24 per-level
send calls at ~700 StableHLO lines each) into 7 bucket bodies and 2
stacked sends, which is what lets the flagship config compile.

The send path (_send_stacked) has three entries and one algorithm,
arrive, claim by key, then commit by bucket; which rows each of the
three runs over is read from the shape of the input:

  * a row each — mask/from/to/level [M], content[i] [M, w_pad]: the
    level is DATA and a row may belong to any bucket, so every bucket
    carries all M rows and routes the rows of other buckets' levels to
    the dropped row, and arrivals and the claim run over all M rows.  No
    protocol sends this way any more; it is the whole-M body the other
    two are held against (in the tests) and reduce to under a node mesh;
  * rows by SENDER — mask/to [N, r], level [N], content the senders'
    full-width vectors [N, W] (Handel's fast path, every tick, whose
    level is a per-node register; r = ceil(fast_path / 2)): few rows
    FIRE — a node sends only in the two ticks after it completes a
    level — so arrivals and the claim run over the firing rows alone
    (_send_fired, below), and of those the rows that won a slot, each in
    ONE bucket, are listed in their turn and only they are read, cut to
    their level's low block (_dyn_low), re-addressed and scattered
    (_commit_landed), C rows a round, in as many rounds as they take (a
    loop whose trip count is data: none where nothing lands; under vmap,
    until the batch's slowest row is through).  No row is lost or
    deferred.  C is landing_capacity(M), a function of the send's shape
    alone: 7.5% of M up to a multiple of 128, 1536 of Handel-4096's
    M = 20,480, chosen from the landing rows a tick of whole 4096-node
    runs (PERF.md section 5: at most 1532 over eight honest runs, 156
    under the byz20 attack, none on 15% and 72% of the ticks), so that
    one round covers a tick.  Where the state carries them (Handel's),
    proto["commit_rounds"] sums the rounds run and proto["landing_peak"]
    keeps the most rows that landed in a tick;
  * level as an AXIS — mask/from/to [N, L-1, k] and no level: a row's
    position on axis 1 IS its level.  Content is either of two forms.
    The block stacks, content[i] [N, nl, w_pad] as _lows gives them (the
    dissemination beats: k = 1, one tick in a period, most rows firing):
    arrivals and the claim run over all M rows, and bucket i's rows are
    cut from the level axis by reshape and static slice, so only they
    are re-addressed and scattered — M_i = N x nl x k rows at w_pad
    words, about a tenth of the flat entry's word updates
    (tests/test_channel_rows.py).  Or the senders' full-width vectors
    [N, W] with k > 1 (GSF's accelerated calls, k =
    accelerated_calls_count, every tick, a node firing only on the tick
    its verified prefix improved): arrivals and the claim over the
    firing rows alone and the commit over those of them that land, as
    the sender-rows entry's (_send_fired, _commit_landed), a row's
    sender and level computed from its row number.  A row lands only if
    it fired, so C is firing_capacity(rows) here, the round that covers
    a tick's firing rows: 1024 of GSF-2048's M = 225,280, 2 x 1024 x 63
    word updates a round where the buckets' cuts carried 2,785,280 a
    tick.  (Vectors with k = 1, or under a node mesh, are cut to their
    block stacks first and take the beats' body.)

The firing rows (_send_fired; the two every-tick entries, not under a
node mesh): the rows whose mask is set are numbered to the front of a
row list by one sort of M row numbers and taken F rows a round through
the latency draw, the traffic counters, the keys and the in_key
min-max scatters, then, once every round's keys are in, through the
winners' reads, which list the round's landing rows behind those of
the rounds before for the commit — two loops whose trip count is data
(none where no row fires, 76% of the ticks under the byz20 attack),
and the commit's third.  The draw is keyed on
(seed, send time, sender, level, send counter, receiver), never on a
row's place, and a masked row adds nothing to any counter, key or
claim, so the state is the whole-M send's bit for bit, `displaced`
included, however many rounds a tick takes.  F is firing_capacity(rows),
a function of the mask's shape alone, chosen from mask.sum() tick by
tick over whole runs from t=0 (PERF.md section 5) as the smallest
multiple of 128 that one round covers on every tick seen: rows by
sender 2/25 of M (1664 of Handel-4096's 20,480: at most 1530-1635 fire
over eight honest runs, 186-208 under byz20); level axis 1/20 of N x k
(1024 of GSF-2048's 225,280 rows: at most 900-970 over eight runs, a
node bursts at a level or two of the eleven); 256 at least (at 256
nodes 135 and 198 fire at most).  The work census counts the rows that
fired, the sends that passed F and the most one send fired
(engine.core.Census `fired_rows`, `firing_overflows`, `firing_peak`).

Arrivals and the claim are scalar per row; the state after a send is
bit-identical in all three entries (the level-axis and the sender-rows
entries only lose updates addressed to the dropped row).  Under a node
mesh a row's level is data again after the all_to_all, so both pad
their rows back to [M, w_pad] there and keep the whole-M body.  The
work census counts what landed of both every-tick sends and the commit
rounds past a send's first (engine.core.Census `landed_rows`,
`extra_commit_rounds`).

Keys pack (absolute_arrival << rel_bits) | rel — no per-tick countdown
(see _advance_channel) — which bounds a sim at 2^(31-rel_bits) ms
(524 s at 4096 nodes; sends beyond it are dropped into the displaced
counter).  Node counts are capped at MAX_NODES = 2^14; construction
fails loudly beyond that.
"""

from __future__ import annotations

import dataclasses
import functools

import jax.numpy as jnp
import numpy as np
from jax import lax

from ..engine import BatchedProtocol
from ..engine.core import census_add
from ..ops.bitops import lowest_set_bit, popcount_words, xor_shuffle
from ..ops.select import run_rank, sort_with_order

INT32_MAX = np.int32(2**31 - 1)
MAX_NODES = 1 << 14  # int32 key-packing headroom

# sub-scopes of the channel send path (`_send_stacked`), nested under the
# engine phase that sends (witt.protocol_tick, witt.beat).  They name what
# the ops are FOR, not how XLA spells them, so a rewrite of the send path
# keeps its time under the same name.
CHANNEL_SCOPES = {
    "arrivals": "witt.channel.arrivals",  # who arrives when: latency, counters, keys, slot
    "readdress": "witt.channel.readdress",  # content from sender to receiver bit space
    "claim": "witt.channel.claim",  # which offer wins which slot; displacement
    "compact": "witt.channel.compact",  # an every-tick send: the firing rows (then the landing ones) to the front, a round's reads
    "commit": "witt.channel.commit",  # the in_sig / in_aux content planes' writes
}


def landing_capacity(m: int) -> int:
    """Rows a round of the sender-rows commit carries, from the send's M
    rows alone: 3/40 of them, up to a multiple of 128 (see the module
    docstring for the histogram behind it)."""
    return min(m, -(-3 * m // 40 // 128) * 128)


def firing_capacity(rows: tuple) -> int:
    """Rows a round of an every-tick send's arrivals and claim carries
    (_send_fired), from the shape of the send's mask alone.  Rows by
    sender [N, r]: 2/25 of the M rows; level axis [N, L-1, k]: 1/20 of
    the N x k rows a level has (a node fires at a level or two, whatever
    their number); each up to a multiple of 128 and 256 at least (a small
    network's share swings more).  The level axis's commit carries as
    many a round: a row lands only if it fired.  See the module docstring
    for the histograms behind them."""
    m = int(np.prod(rows))
    share = -(-2 * m // 25) if len(rows) == 2 else -(-rows[0] * rows[2] // 20)
    return min(m, max(256, -(-share // 128) * 128))


@dataclasses.dataclass(frozen=True)
class Bucket:
    """A run of consecutive levels sharing one padded word width."""

    levels: tuple  # level numbers, ascending
    w_pad: int  # padded width (max exact width in the bucket)

    @property
    def lo(self) -> int:
        return self.levels[0]

    @property
    def hi(self) -> int:
        return self.levels[-1]

    @property
    def nl(self) -> int:
        return len(self.levels)


"""Bucket grouping is by EXACT width (the PR-11 density pass): levels of
equal word width share a bucket (the sub-word levels, all w=1), and wider
levels get their own — so w_pad always equals the levels' exact width and
the channel/candidate arrays carry zero padding words.  The r4 rewrite
grouped width CLASSES ({2,4}, {8,16}, ...) instead, paying up to 2x
padding per bucket to halve the bucket count; with per-bucket bodies now
a minority of compile time, the padding was pure HBM waste (13.4 MB of
the 4096-node flagship's 124 MiB/replica).  Every phase iterates
`self.buckets` generically, so the regrouping is a pure layout change —
per-level arithmetic is untouched and results are bit-identical (padding
words were always zero)."""


class BitsetAggBase(BatchedProtocol):
    TICK_INTERVAL = 1  # verification capacity is modeled per-ms
    PAYLOAD_WIDTH = 0  # messaging bypasses the generic ring entirely
    CHANNEL_DEPTH = 8  # D: arrival-keyed in-flight slots per (receiver, level)
    BEAT_SEND_CALLS = 1  # _dissemination makes one stacked send

    @property
    def REQUIRED_SCOPES(self) -> tuple:
        """The scopes this protocol's step must carry (simlint SL601):
        the channel's, `compact` where it makes an every-tick send whose
        firing rows it brings to the front (one that states a
        `firing_peak` limit)."""
        return tuple(
            scope for name, scope in CHANNEL_SCOPES.items()
            if name != "compact" or self.census_limits().get("firing_peak")
        )

    def tick_beat(self, net, state):
        """Periodic dissemination as the engine's beat hook (subclasses
        implement _dissemination with exactly ONE stacked send, matching
        BEAT_SEND_CALLS; it commutes with _select — no shared proto keys,
        order-independent channel competition).  Wrapped in the
        NARROW_LEAVES widen/narrow boundary (identity for declarers of
        none, e.g. GSF) so the hook body computes on the int32 view."""
        state = state._replace(proto=self.widen_proto(state.proto))
        state = self._dissemination(net, state)
        return state._replace(proto=self.narrow_proto(state.proto))

    def _init_geometry(self, n: int) -> None:
        if n & (n - 1):
            raise ValueError("power-of-two node counts only")
        if n > MAX_NODES:
            raise NotImplementedError(
                f"node_count {n} > {MAX_NODES}: int32 channel/sort key packing "
                "would overflow; widen the keys before raising this cap"
            )
        self.n_nodes = n
        self.n_words = max(1, n // 32)
        self.n_levels = n.bit_length()  # levels 0..log2(n)
        self.rel_bits = max(1, (n - 1).bit_length())
        self.MSG_TYPES = [f"SIGS_L{l}" for l in range(self.n_levels)]

        # per-level content geometry: level l's payload is bits [0, 2^(l-1))
        # = w_l exact words; bs_l = block size in bits
        self.w = [0] * self.n_levels
        self.bs = [0] * self.n_levels
        for l in range(1, self.n_levels):
            self.bs[l] = 1 << (l - 1)
            self.w[l] = max(1, (1 << (l - 1)) // 32)
        self.w_max = self.w[self.n_levels - 1] if self.n_levels > 1 else 1

        # exact-width buckets over levels 1..L-1 (see module docstring):
        # consecutive levels of EQUAL width share a bucket, so w_pad is
        # always the exact width and no padding words are carried
        buckets = []
        for l in range(1, self.n_levels):
            if buckets and buckets[-1][1] == self.w[l]:
                buckets[-1][0].append(l)
            else:
                buckets.append([[l], self.w[l]])
        self.buckets = [Bucket(tuple(lv), wp) for lv, wp in buckets]

        # static per-level tables (stacked [L-1] vectors, level-1 at index 0)
        self.lv_bs = np.asarray(self.bs[1:], np.int32)  # block sizes

    # -- stacked block views -------------------------------------------------
    # Full-width [.., W] layout is the concatenation of level blocks:
    # word 0 = bit 0 (level 0) + sub-word blocks of levels with bs < 32;
    # each level with bs >= 32 owns words [bs/32, 2bs/32).

    def _blocks(self, x, b: Bucket):
        """Bucket view of full-width vectors: [N, W] -> [N, nl, w_pad],
        zero above each level's exact width."""
        outs = []
        for l in b.levels:
            bs, w = self.bs[l], self.w[l]
            if bs < 32:
                blk = (x[..., 0:1] >> jnp.uint32(bs)) & jnp.uint32((1 << bs) - 1)
            else:
                blk = x[..., bs // 32 : (2 * bs) // 32]
            if w < b.w_pad:
                blk = jnp.concatenate(
                    [blk, jnp.zeros(blk.shape[:-1] + (b.w_pad - w,), jnp.uint32)],
                    axis=-1,
                )
            outs.append(blk)
        return jnp.stack(outs, axis=-2)  # [.., nl, w_pad]

    def _lows(self, x, b: Bucket):
        """Bucket view of sender-space outgoing content (bits [0, 2^(l-1)))
        per level: [N, W] -> [N, nl, w_pad], zero-padded."""
        outs = []
        for l in b.levels:
            bs, w = self.bs[l], self.w[l]
            if bs < 32:
                blk = x[..., 0:1] & jnp.uint32((1 << bs) - 1)
            else:
                blk = x[..., : bs // 32]
            if w < b.w_pad:
                blk = jnp.concatenate(
                    [blk, jnp.zeros(blk.shape[:-1] + (b.w_pad - w,), jnp.uint32)],
                    axis=-1,
                )
            outs.append(blk)
        return jnp.stack(outs, axis=-2)

    def _assemble(self, x_old, pieces):
        """Rebuild full-width vectors from per-bucket block stacks.

        pieces: list aligned with self.buckets of [N, nl, w_pad] (zero above
        exact widths).  Level-0's bit 0 is preserved from x_old."""
        word0 = x_old[..., 0] & jnp.uint32(1)
        tail = []
        for b, pc in zip(self.buckets, pieces):
            for j, l in enumerate(b.levels):
                bs, w = self.bs[l], self.w[l]
                blk = pc[..., j, :w]
                if bs < 32:
                    word0 = word0 | (blk[..., 0] << jnp.uint32(bs))
                else:
                    tail.append(blk)
        return jnp.concatenate([word0[..., None]] + tail, axis=-1)

    def _level_stats(self, per_bucket):
        """Concat per-bucket [N, nl] level-axis stats into [N, L-1]."""
        return jnp.concatenate(per_bucket, axis=-1)

    def _width_mask(self, b: Bucket):
        """bool[nl, w_pad]: word j valid for the bucket's level row."""
        return (
            np.arange(b.w_pad, dtype=np.int32)[None, :]
            < np.asarray([self.w[l] for l in b.levels], np.int32)[:, None]
        )

    def _dyn_low(self, x, level, b: Bucket):
        """Sender-space outgoing content at a DYNAMIC per-node level
        (valid where level is inside bucket b): [N, W], [N] -> [N, w_pad]."""
        # bs_l = 2^(l-1) and w_l = max(1, bs_l / 32) by arithmetic: a
        # table read would be a gather a row
        bs = jnp.int32(1) << (jnp.clip(level, 1, self.n_levels - 1) - 1)
        w = jnp.maximum(bs >> 5, 1)
        out = x[..., : b.w_pad]
        if b.w_pad == 1 and self.bs[b.lo] < 32:
            # sub-word levels: bits [0, bs) of word 0 (bs may be 32; the
            # bs & 31 shift puts 0 in the lane the `full` select discards)
            m = (jnp.uint32(1) << (bs & 31).astype(jnp.uint32)) - 1
            m = jnp.where(bs >= 32, jnp.uint32(0xFFFFFFFF), m)
            return out & m[..., None]
        return out * (jnp.arange(b.w_pad, dtype=jnp.int32)[None, :] < w[..., None])

    # -- misc bit helpers (unchanged semantics) ------------------------------
    @staticmethod
    def _onehot(r0, w: int):
        """Block-local one-hot bit r0: [...] int32 -> [..., w] uint32."""
        word = r0 >> 5
        bit = (r0 & 31).astype(jnp.uint32)
        return jnp.where(
            jnp.arange(w, dtype=jnp.int32) == word[..., None],
            (jnp.uint32(1) << bit)[..., None],
            jnp.uint32(0),
        )

    @staticmethod
    def _lowest_bit(words):
        """Index of the lowest set bit over the last axis of packed [..., w]
        uint32 vectors (undefined when empty — gate on popcount > 0).
        Shared with the engine's wheel-occupancy scan (ops.bitops)."""
        return lowest_set_bit(words)

    def _block_bit(self, plane, b: Bucket, rel):
        """Bit `rel` of a rel-space plane (Handel's `bl`, `ind`), for the
        levels of bucket b: a level-l peer has rel in [bs_l, 2 bs_l), so
        its bit is bit rel & (bs_l - 1) of the level's block.  [N, W],
        [N, nl, k] -> bool[N, nl, k]; a block view and a one-hot mask, no
        gather: a gather of one word a candidate from the loop-carried
        plane cost 27.8 ms of a 111-ms tick at 4096 nodes for `bl`
        (PERF.md section 6, PR 31) and 2.33 of 8.87 for `ind` (PR 44), and
        ran faster or slower with the buffer the plane happened to be in."""
        bs = jnp.asarray([self.bs[l] for l in b.levels], jnp.int32)
        bit = self._onehot(rel & (bs[None, :, None] - 1), b.w_pad)
        return jnp.any((self._blocks(plane, b)[:, :, None, :] & bit) != 0, axis=-1)

    def _level_bit(self, plane, rel):
        """`_block_bit` for every level at once: [N, W], [N, L-1, k] ->
        bool[N, L-1, k], the buckets' pieces joined on the level axis."""
        return jnp.concatenate(
            [self._block_bit(plane, b, rel[:, b.lo - 1 : b.hi, :]) for b in self.buckets],
            axis=1,
        )

    # -- channel layout ------------------------------------------------------
    # in_key: [N, (L-1)*(D+1)] packed (arrival<<rel_bits | rel);
    # content per bucket i: proto[f"in_sig{i}"] = [N, nl*(D+1)*w_pad] flat,
    # level-major then slot then word.

    def _fresh_cols(self) -> np.ndarray:
        """bool[(L-1)*(D+1)]: which in_key columns are fresh-backstop slots."""
        ss = self.CHANNEL_DEPTH + 1
        cols = np.zeros((self.n_levels - 1) * ss, dtype=bool)
        cols[ss - 1 :: ss] = True
        return cols

    def _key_seg(self, in_key, l: int):
        ss = self.CHANNEL_DEPTH + 1
        return in_key[:, (l - 1) * ss : l * ss]

    def _keys_stacked(self, in_key):
        """[N, (L-1)*ss] -> [N, L-1, ss]."""
        ss = self.CHANNEL_DEPTH + 1
        return in_key.reshape(in_key.shape[0], self.n_levels - 1, ss)

    def _sig_view(self, proto, i: int, slots: int, prefix: str = "in_sig"):
        """Bucket i's content as [N, nl, slots, w_pad]."""
        b = self.buckets[i]
        a = proto[f"{prefix}{i}"]
        return a.reshape(a.shape[0], b.nl, slots, b.w_pad)

    def _channel_init(self, n: int):
        """Fresh in_key plus per-bucket in_sig arrays (fresh slots empty at
        -1, arrival slots at INT32_MAX)."""
        ss = self.CHANNEL_DEPTH + 1
        in_key = np.where(self._fresh_cols(), -1, INT32_MAX).astype(np.int32)
        sigs = {
            f"in_sig{i}": jnp.zeros((n, b.nl * ss * b.w_pad), jnp.uint32)
            for i, b in enumerate(self.buckets)
        }
        return (
            jnp.asarray(np.broadcast_to(in_key, (n, in_key.size)).copy()),
            sigs,
        )

    def _not_ok_init(self, n: int) -> dict:
        """proto["sent_not_ok"], int32[N], where the network is built with
        a node down, and nothing where it is not (as Handel's track_bad
        places bl and byz): for every sender, its masked sends that were
        not ok (the receiver down, or past the discard time), which tick
        msg_sent and never msg_received.  Over live senders msg_sent ==
        msg_received + sent_not_ok, exactly; with no node down every
        masked send is ok, so the honest programs carry no such leaf and
        stay the programs they were."""
        p = self.params
        if p.nodes_down > 0 or getattr(p, "bad_nodes", None):
            return {"sent_not_ok": jnp.zeros(n, jnp.int32)}
        return {}

    def _advance_channel(self, in_key, t):
        """Due mask at tick t; returns (in_key, due, empty_tpl).

        Keys pack the ABSOLUTE arrival (r5): the r4 relative packing
        needed a full read-modify-write of the key array every tick just
        to count down — at 4096 nodes x 32 replicas that decrement alone
        was ~450 MB/tick of pure HBM traffic.  Absolute keys keep every
        ordering property (min = earliest arrival, fresh-slot max =
        newest offer) and make the due test a compare against t."""
        occupied = (in_key >= 0) & (in_key != INT32_MAX)
        due = occupied & ((in_key >> self.rel_bits) <= t)
        empty_tpl = jnp.asarray(
            np.where(self._fresh_cols(), -1, INT32_MAX), jnp.int32
        )
        return in_key, due, empty_tpl

    # -- due-slot gather ------------------------------------------------------
    # Arrival slots are keyed slot = arrival mod D and a slot is due exactly
    # at its arrival tick, so at tick t the ONLY slots that can be due are
    # arrival slot (t mod D) and the fresh backstop.  Delivery therefore
    # gathers those two columns instead of processing all D+1 — the merge
    # runs at [K+2] instead of [K+D+1] width (pinned by
    # tests/test_agg_buckets.py::test_only_two_slots_can_be_due).

    def _due_pair_keys(self, keys3, due3, t):
        """[N, L-1, ss] stacked keys/due -> the two due-able columns as
        [N, L-1, 2] (index 0 = arrival slot t mod D, 1 = fresh)."""
        sidx = lax.rem(t, jnp.int32(self.CHANNEL_DEPTH))
        k_arr = lax.dynamic_index_in_dim(keys3, sidx, axis=2, keepdims=False)
        d_arr = lax.dynamic_index_in_dim(due3, sidx, axis=2, keepdims=False)
        d = self.CHANNEL_DEPTH
        return (
            jnp.stack([k_arr, keys3[:, :, d]], axis=2),
            jnp.stack([d_arr, due3[:, :, d]], axis=2),
        )

    def _due_pair_sig(self, proto, i: int, t, prefix: str = "in_sig"):
        """Bucket i's content for the two due-able slots: [N, nl, 2, w_pad]
        in receiver block-local space."""
        sig = self._sig_view(proto, i, self.CHANNEL_DEPTH + 1, prefix=prefix)
        sidx = lax.rem(t, jnp.int32(self.CHANNEL_DEPTH))
        s_arr = lax.dynamic_index_in_dim(sig, sidx, axis=2, keepdims=False)
        return jnp.stack([s_arr, sig[:, :, self.CHANNEL_DEPTH]], axis=2)

    # -- the stacked send path -----------------------------------------------
    def _send_stacked(self, net, state, mask, from_idx, to_idx, level, content, aux=None):
        """Send M messages (one per row, each at its own level) into the
        per-(receiver, level, slot) channel in ONE body: earliest arrival
        wins an arrival slot, the newest offer always takes the fresh slot.

        A row each: mask/from_idx/to_idx/level [M] (level in [1, L-1]);
        content: list aligned with self.buckets of [M, w_pad] SENDER-space
        words (only rows whose level lies in the bucket need valid values);
        aux: optional [M] int32 stored per slot in proto["in_aux"].

        Rows by sender: mask [N, r], from_idx/to_idx/aux anything that
        broadcasts to it, level [N] (a sender's r rows share its level),
        content the senders' full-width [N, W] vectors: row [n, c] carries
        the low block of content[n] at level[n].  The flat row order is
        the axis order; arrivals and the claim run over the rows that
        fire (_send_fired) and the commit over those of them that land
        (_commit_landed).

        Level as an axis: mask [N, L-1, k], from_idx/to_idx/aux anything
        that broadcasts to it, level None: row [n, j, c] is a level-(j+1)
        message, numbered here from the axis the bucket cut slices;
        content[i] [N, nl, w_pad], the bucket's block stack as _lows
        gives it, shared by a node's k rows of a level.  The flat row
        order is the axis order, so arrivals and the claim are the ones
        the flattened send would get.  Or content the senders'
        full-width [N, W] vectors, row [n, j, c] being node n's own
        (from_idx its number n) and carrying the low block of
        content[n] at level j + 1: with k > 1 arrivals and the claim
        run over the rows that fire (_send_fired) and the commit over
        those of them that land (_commit_landed).

        Content is re-addressed into the receiver's block-local space
        here, at send time.  See the module docstring for which rows each
        entry's arrivals, claim, re-addressing and commit run over.
        """
        proto = state.proto
        scope = functools.partial(net._scope, scopes=CHANNEL_SCOPES)
        mesh = getattr(net, "node_mesh", None)
        axis = mask.shape if mask.ndim == 3 else None  # rows on a level axis
        # the senders' full-width vectors of an every-tick send, whose
        # landing rows alone are committed
        words = None
        if mask.ndim == 2:
            if mesh is None:
                words = content
            else:
                # node-sharded, the exchange has its own capacity logic:
                # a row each, as the flat entry takes them
                content = [
                    jnp.repeat(self._dyn_low(content, level, b), mask.shape[1], axis=0)
                    for b in self.buckets
                ]
            level = level[:, None]
        elif axis is not None:
            if level is not None or axis[1] != self.n_levels - 1:
                raise ValueError(
                    "a level-axis send is [N, L-1, k] and numbers its own "
                    f"levels: got {axis} with level {level!r}"
                )
            level = jnp.arange(1, self.n_levels, dtype=jnp.int32)[None, :, None]
            if not isinstance(content, (list, tuple)):
                if mesh is None and axis[2] > 1:
                    words = content
                else:
                    # a single call a level, or node-sharded: the whole
                    # rows of the beats' form
                    content = [self._lows(content, b) for b in self.buckets]
        rows = mask.shape
        if mask.ndim > 1:
            mask, from_idx, to_idx, level = (
                jnp.broadcast_to(x, rows).reshape(-1)
                for x in (mask, from_idx, to_idx, level)
            )
            if aux is not None:
                aux = jnp.broadcast_to(aux, rows).reshape(-1)
        # node-sharded, a row's level is data again after the exchange:
        # every bucket carries all M rows there, whichever the entry
        cut = axis is not None and mesh is None
        # masked rows may carry junk levels; clamp so every computed index
        # is in range (their scatters are dropped via the n_nodes row)
        level = jnp.clip(level.astype(jnp.int32), 1, self.n_levels - 1)
        if words is not None:
            # the two every-tick entries, of whose rows few fire
            state, landed = self._send_fired(
                net, state, firing_capacity(rows), mask, from_idx, to_idx, level, aux, scope
            )
        else:
            with scope("arrivals"):
                state, ok, key, slot, time_overflow = self._arrive(
                    net, state, mask, from_idx, to_idx, level
                )
        proto = state.proto

        if words is None:
            with scope("readdress"):
                # re-address sender-space content into the receiver's
                # block-local space (bit j -> j ^ r0) for each bucket's
                # rows, shared by both commit passes; r0 < bs keeps the
                # permutation inside the level block, and rows routed away
                # from the bucket get r0 = 0 so the (dropped) shuffle stays
                # in range.  (The landing rows of an every-tick send are
                # re-addressed a round at a time, in _commit_landed.)
                r0_row = self._r0(from_idx, to_idx, level)
                if axis is not None:
                    content = [
                        self._level_rows(c, b, axis, whole=not cut)
                        for c, b in zip(content, self.buckets)
                    ]
                bucket_rows = [
                    self._bucket_rows(b, level, axis if cut else None)
                    for b in self.buckets
                ]
                cnt_list = [
                    xor_shuffle(c.astype(jnp.uint32), own(r0_row, 0))
                    for own, c in zip(bucket_rows, content)
                ]

        if mesh is not None:
            # node-axis sharding: the channel commit goes through an
            # explicit all_to_all exchange of update rows so the channel
            # shards never gather
            return self._channel_commit_sharded(
                mesh, net.node_axis, state, ok, to_idx, level, key, slot,
                cnt_list, aux,
                cap=getattr(net, "exchange_capacity", None),
                time_overflow=time_overflow, scope=scope,
            )

        updates = dict(proto)
        sig_names = [f"in_sig{i}" for i in range(len(self.buckets))]
        if words is None:
            with scope("claim"):
                updates["in_key"] = self._claim_keys(proto["in_key"], ok, to_idx, level, key, slot)
                win_to, fwin_to, displaced = self._claim_winners(
                    proto["in_key"], updates["in_key"], ok, to_idx, level, key, slot
                )
                updates["displaced"] = proto["displaced"] + displaced + time_overflow
            with scope("commit"):
                if aux is not None:
                    updates["in_aux"] = self._commit_aux(
                        proto["in_aux"], win_to, fwin_to, level, slot, aux
                    )
                for name, b, own, cnt in zip(sig_names, self.buckets, bucket_rows, cnt_list):
                    updates[name] = self._commit_bucket(
                        updates[name], b,
                        own(win_to, self.n_nodes), own(fwin_to, self.n_nodes),
                        own(level) - b.lo, own(slot), cnt,
                    )
        else:
            land_rows, land_info, landing = landed
            # rows a round, from the send's shape: a level-axis row lands
            # only if it fired, so the round that covers a tick's firing
            # rows covers its landing rows
            capacity = landing_capacity(mask.shape[0]) if axis is None else firing_capacity(rows)
            sigs, rounds = self._commit_landed(
                [updates[name] for name in sig_names], words,
                from_idx, to_idx, level, land_rows, land_info, landing,
                capacity, scope, axis,
            )
            updates.update(zip(sig_names, sigs))
            if "commit_rounds" in proto:
                updates["commit_rounds"] = proto["commit_rounds"] + rounds
                updates["landing_peak"] = jnp.maximum(proto["landing_peak"], landing)
            # the work census: what landed of the M rows this send carried,
            # and the rounds a landing count past the capacity added
            state = census_add(
                state, landed_rows=landing, extra_commit_rounds=jnp.maximum(rounds - 1, 0)
            )
        return state._replace(proto=updates)

    def _r0(self, from_idx, to_idx, level):
        """The xor of a row's re-addressing: the sender relative to the
        receiver inside the level's block, bs_l = 2^(l-1)."""
        rel = (to_idx ^ from_idx).astype(jnp.int32)
        return rel & ((jnp.int32(1) << (level - 1)) - 1)

    def _arrive(self, net, state, mask, from_idx, to_idx, level):
        """Who arrives when, for the rows handed over (all M of a send,
        or a round of its firing rows): the latency draw, the traffic
        counters, and a row's packed key and arrival slot.  Returns
        (state, ok, key, slot, sends lost to the packing horizon)."""
        proto = state.proto
        state, ok, arrival = net.latency_arrivals(
            state, mask, from_idx, to_idx, state.time + 1, level
        )
        # receiver traffic counters tick here, at send time: every ok
        # send is delivered by the oracle (Network.java:611-612), but
        # the channel may displace it — counting at send keeps
        # end-of-run totals exact at the cost of counters leading
        # arrivals by the latency
        okc = ok.astype(jnp.int32)
        sizes = jnp.asarray(self._size_table(), jnp.int32)[level]
        state = state._replace(
            msg_received=state.msg_received.at[to_idx].add(okc, mode="drop"),
            bytes_received=state.bytes_received.at[to_idx].add(
                okc * sizes, mode="drop"
            ),
        )
        if "sent_not_ok" in proto:
            # the other side of the same ledger, by sender (see
            # _not_ok_init): before fits_t, because a time_overflow
            # send was counted for its receiver just above
            proto = dict(
                proto,
                sent_not_ok=proto["sent_not_ok"]
                .at[from_idx]
                .add((mask & ~ok).astype(jnp.int32)),
            )
            state = state._replace(proto=proto)
        rel = (to_idx ^ from_idx).astype(jnp.int32)
        # ABSOLUTE arrival packing (no per-tick countdown — see
        # _advance_channel).  Sims running past the int32 packing
        # horizon (2^(31-rel_bits) ms: 524 s at 4096 nodes, 128 s at
        # the 16384 cap) would overflow the shift; such sends are
        # dropped and counted in proto["displaced"] so a too-long sim
        # fails loudly in the displacement stats rather than
        # corrupting arrival order.
        # strictly below the last in-horizon ms: at the boundary
        # arrival, a max-rel send would pack to exactly INT32_MAX —
        # the empty-slot sentinel — and vanish uncounted
        fits_t = arrival < (jnp.int32(1) << (31 - self.rel_bits)) - 1
        time_overflow = jnp.sum((ok & ~fits_t).astype(jnp.int32))
        ok = ok & fits_t
        key = jnp.where(ok, (arrival << self.rel_bits) | rel, INT32_MAX)
        slot = lax.rem(arrival, jnp.int32(self.CHANNEL_DEPTH))
        return state, ok, key, slot, time_overflow

    def _cols(self, level, slot):
        """A row's two in_key / in_aux columns: its level's arrival slot
        and its level's fresh slot."""
        ss = self.CHANNEL_DEPTH + 1
        return (level - 1) * ss + slot, (level - 1) * ss + ss - 1

    def _claim_keys(self, in_key, ok, to_idx, level, key, slot):
        """The claim's writes: every ok row's key min-scattered into its
        arrival slot and max-scattered into its level's fresh slot
        (empty at -1 so any real key wins the max)."""
        col, fcol = self._cols(level, slot)
        safe_to = jnp.where(ok, to_idx, self.n_nodes)
        in_key = in_key.at[safe_to, col].min(key, mode="drop")
        return in_key.at[safe_to, fcol].max(jnp.where(ok, key, -1), mode="drop")

    def _claim_winners(self, before, after, ok, to_idx, level, key, slot):
        """The claim's reads, once every key of the send is in `after`:
        a row's receiver where it holds its arrival slot (`win_to`) and
        where it holds the fresh slot (`fwin_to`), the dropped row
        n_nodes where it does not, and the displacement count (the
        channel's SimState.dropped analog): an ok send that won neither
        slot, or a winner that evicted a still-pending occupant of
        `before` with a later arrival."""
        col, fcol = self._cols(level, slot)
        prev = before.at[to_idx, col].get(mode="fill", fill_value=INT32_MAX)
        winner = ok & (after[to_idx, col] == key)
        fresh_win = ok & (after[to_idx, fcol] == key)
        lost_entry = ok & ~winner & ~fresh_win
        evicted = winner & (prev != INT32_MAX) & (prev > key)
        return (
            jnp.where(winner, to_idx, self.n_nodes),
            jnp.where(fresh_win, to_idx, self.n_nodes),
            jnp.sum((lost_entry | evicted).astype(jnp.int32)),
        )

    def _commit_aux(self, in_aux, win_to, fwin_to, level, slot, aux):
        """A row's aux word beside its key, in the slots it won."""
        col, fcol = self._cols(level, slot)
        aux = aux.astype(jnp.int32)
        return in_aux.at[win_to, col].set(aux, mode="drop").at[fwin_to, fcol].set(aux, mode="drop")

    def _send_fired(self, net, state, capacity, mask, from_idx, to_idx, level, aux, scope):
        """Arrivals and the claim of an every-tick send over the rows
        that FIRE: the flat [M] rows whose mask is set, a few in a
        hundred, are numbered to the front of a row list and taken
        `capacity` rows a round, as many rounds as they take (none for
        none; one on all but a handful of ticks, see firing_capacity).
        The latency draw is keyed on a row's sender, receiver, level and
        the send's counter, never on its place, and a masked row adds
        nothing to any counter, key or claim, so the state is the whole-M
        send's bit for bit.  Two loops: the first draws a round's
        arrivals and scatters its keys, the second reads the winners once
        EVERY round's keys are in (a row that holds a slot after its own
        round may lose it to a later round's).  Under vmap each runs
        until the batch's slowest row is through.

        Returns the state (counters, in_key, in_aux, displaced written;
        the census's fired rows) and what the commit needs
        (_commit_landed): the row numbers of the rows that won a slot,
        compacted in their turn, their slot and win bits beside them, and
        their count."""
        proto = state.proto
        m, n, d = mask.shape[0], self.n_nodes, self.CHANNEL_DEPTH
        with scope("compact"):
            fired = jnp.sum(mask.astype(jnp.int32))
            # firing rows' numbers first, ascending; m marks the rest and
            # the padding up to whole rounds (equal keys are all m: no
            # stable sort's second operand)
            order = lax.sort(jnp.where(mask, jnp.arange(m, dtype=jnp.int32), m), is_stable=False)
            order = jnp.concatenate([order, jnp.full(-m % capacity, m, jnp.int32)])

        def round_rows(k, *columns):
            with scope("compact"):
                sel = lax.dynamic_slice(order, (k * capacity,), (capacity,))
                live = sel < m
                return (sel, live) + tuple(x[jnp.where(live, sel, 0)] for x in columns)

        more = lambda carry: carry[0] * capacity < fired  # noqa: E731
        # what the arrivals write is small: the node columns and two
        # leaves of proto; the planes stay outside the loops
        slim = state._replace(
            proto={k: proto[k] for k in ("in_key", "sent_not_ok") if k in proto}
        )
        ctr = state.send_ctr  # one send, one draw counter, however many rounds

        def arrive(carry):
            k, slim, keys, time_overflow = carry
            _sel, live, frm, to, lvl = round_rows(k, from_idx, to_idx, level)
            with scope("arrivals"):
                slim, ok, key, slot, over = self._arrive(
                    net, slim._replace(send_ctr=ctr), live, frm, to, lvl
                )
            with scope("claim"):
                in_key = self._claim_keys(slim.proto["in_key"], ok, to, lvl, key, slot)
            slim = slim._replace(proto=dict(slim.proto, in_key=in_key))
            keys = lax.dynamic_update_slice(keys, key, (k * capacity,))
            return k + 1, slim, keys, time_overflow + over

        _, slim, keys, time_overflow = lax.while_loop(
            more, arrive,
            (jnp.int32(0), slim, jnp.full(order.shape, INT32_MAX, jnp.int32), jnp.int32(0)),
        )
        in_key = slim.proto["in_key"]
        state = slim._replace(send_ctr=ctr + 1, proto=dict(proto, **slim.proto))

        out = (jnp.full(order.shape, m, jnp.int32), jnp.zeros(order.shape, jnp.int32), jnp.int32(0))
        in_aux = proto["in_aux"] if aux is not None else ()

        def claim(carry):
            k, displaced, in_aux, out = carry
            sel, live, to, lvl, *aux_c = round_rows(
                k, to_idx, level, *(() if aux is None else (aux,))
            )
            with scope("claim"):
                key = lax.dynamic_slice(keys, (k * capacity,), (capacity,))
                ok = live & (key != INT32_MAX)  # an ok row's key is below it (_arrive)
                slot = lax.rem(key >> self.rel_bits, jnp.int32(d))
                win_to, fwin_to, lost = self._claim_winners(
                    proto["in_key"], in_key, ok, to, lvl, key, slot
                )
            if aux is not None:
                with scope("commit"):
                    in_aux = self._commit_aux(in_aux, win_to, fwin_to, lvl, slot, *aux_c)
            with scope("compact"):
                # the round's landing rows behind those of the rounds
                # before: row number, and slot and win bits beside it
                land_rows, land_info, landed = out
                winner, fresh_win = win_to < n, fwin_to < n
                lands = winner | fresh_win
                info = slot * 4 + winner.astype(jnp.int32) * 2 + fresh_win.astype(jnp.int32)
                first, info = lax.sort(
                    (jnp.where(lands, sel, m), info), num_keys=1, is_stable=False
                )
                out = (
                    lax.dynamic_update_slice(land_rows, first, (landed,)),
                    lax.dynamic_update_slice(land_info, info, (landed,)),
                    landed + jnp.sum(lands.astype(jnp.int32)),
                )
            return k + 1, displaced + lost, in_aux, out

        _, displaced, in_aux, out = lax.while_loop(
            more, claim, (jnp.int32(0), time_overflow, in_aux, out)
        )
        updates = dict(state.proto, displaced=proto["displaced"] + displaced)
        if aux is not None:
            updates["in_aux"] = in_aux
        state = census_add(
            state._replace(proto=updates),
            fired_rows=fired, firing_overflows=fired > capacity, firing_peak=fired,
        )
        return state, out

    def _level_rows(self, blocks, b: Bucket, axis, whole: bool):
        """Bucket b's content rows of a level-axis send: its [N, nl, w_pad]
        block stack shared by the k rows a node sends at a level,
        [N*nl*k, w_pad] in axis order; `whole` pads the other levels' rows
        back in as zeros, [N*(L-1)*k, w_pad]."""
        n, nlv, k = axis
        c = jnp.broadcast_to(
            blocks.astype(jnp.uint32)[:, :, None, :], (n, b.nl, k, b.w_pad)
        )
        if whole:
            c = (
                jnp.zeros((n, nlv, k, b.w_pad), jnp.uint32)
                .at[:, b.lo - 1 : b.hi]
                .set(c)
            )
        return c.reshape(-1, b.w_pad)

    @staticmethod
    def _bucket_rows(b: Bucket, level, axis):
        """Which of a send's M rows bucket b's re-addressing and commit run
        over, as own(x, fill): a per-row [M] vector -> the bucket's rows.
        Level as data (axis None: a row each, or the C rows of a round of
        _commit_landed): all of them, those of other buckets' levels
        replaced by `fill` where one is given (the dropped row, a zero
        shift).  Level as an axis [N, L-1, k]: the rows of levels
        b.lo..b.hi alone, cut by reshape and static slice (an index array
        would lower to a gather); nothing is left to route."""
        if axis is None:
            in_b = (level >= b.lo) & (level <= b.hi)
            return lambda x, fill=None: x if fill is None else jnp.where(in_b, x, fill)
        return lambda x, fill=None: x.reshape(axis)[:, b.lo - 1 : b.hi, :].reshape(-1)

    def _commit_bucket(self, sig, b: Bucket, win_to, fwin_to, li, slot, cnt):
        """A bucket's two content scatters, the one body of every commit:
        rows [m] write their w_pad receiver-space words `cnt` into the
        plane sig [rows, nl*ss*w_pad] at (win_to, level row li, slot) and
        at (fwin_to, li, the fresh slot); a row index past the plane (a
        loser, another bucket's row) is dropped."""
        ss = self.CHANNEL_DEPTH + 1
        cw = jnp.arange(b.w_pad, dtype=jnp.int32)
        cols = ((li * ss + slot) * b.w_pad)[:, None] + cw
        fcols = ((li * ss + ss - 1) * b.w_pad)[:, None] + cw
        sig = sig.at[win_to[:, None], cols].set(cnt, mode="drop")
        return sig.at[fwin_to[:, None], fcols].set(cnt, mode="drop")

    def _commit_landed(
        self, sigs, words, from_idx, to_idx, level, land_rows, land_info, landing,
        capacity, scope, axis=None,
    ):
        """The commit of an every-tick send over the rows that land: the
        claim's winners (each the only one at its plane cell, so their
        order is free), as _send_fired lists them (`land_rows` the flat
        row numbers of the `landing` rows that won a slot, m behind them;
        `land_info` a row's slot and win bits), committed `capacity`
        rows a round, as many rounds as the landing rows take (none for
        none): no row is lost or deferred.  A round reads its rows'
        senders' full-width `words` [N, W] (row m's sender is m // r),
        cuts and re-addresses each bucket's low block and runs
        `_commit_bucket` over `capacity` rows; rows of other buckets'
        levels and the list's tail go to the dropped row.  Rows by sender
        (`axis` None): a row's sender, receiver and level are read from
        the send's flat [M] columns.  Level as an axis (`axis` its
        [N, L-1, k]): row [n, j, c] is node n's at level j + 1, which is
        arithmetic on its number, and its receiver alone is read.  Under
        vmap the loop runs until the batch's slowest row is through (a
        finished row's rounds write nothing).  Returns the planes and
        the rounds this send took."""
        m, n = to_idx.shape[0], self.n_nodes
        r = m // words.shape[0]
        with scope("compact"):
            pad = -land_rows.shape[0] % capacity  # up to whole rounds
            land_rows = jnp.concatenate([land_rows, jnp.full(pad, m, jnp.int32)])
            land_info = jnp.concatenate([land_info, jnp.zeros(pad, jnp.int32)])

        def one_round(carry):
            k, sigs = carry
            with scope("compact"):
                sel = lax.dynamic_slice(land_rows, (k * capacity,), (capacity,))
                info = lax.dynamic_slice(land_info, (k * capacity,), (capacity,))
                live = sel < m
                sel = jnp.where(live, sel, 0)
                if axis is None:
                    from_c, to_c, level_c = (x[sel] for x in (from_idx, to_idx, level))
                else:
                    from_c, to_c = sel // r, to_idx[sel]
                    level_c = (sel // axis[2]) % axis[1] + 1
                slot_c = info >> 2
                win_to = jnp.where(live & ((info & 2) > 0), to_c, n)
                fwin_to = jnp.where(live & ((info & 1) > 0), to_c, n)
                words_c = words[sel // r]
            owns = [self._bucket_rows(b, level_c, None) for b in self.buckets]
            with scope("readdress"):
                r0_c = self._r0(from_c, to_c, level_c)
                cnts = [
                    xor_shuffle(self._dyn_low(words_c, level_c, b), own(r0_c, 0))
                    for own, b in zip(owns, self.buckets)
                ]
            with scope("commit"):
                sigs = [
                    self._commit_bucket(
                        sig, b, own(win_to, n), own(fwin_to, n),
                        level_c - b.lo, slot_c, cnt,
                    )
                    for sig, b, own, cnt in zip(sigs, self.buckets, owns, cnts)
                ]
            return k + 1, sigs

        rounds, sigs = lax.while_loop(
            lambda carry: carry[0] * capacity < landing,
            one_round,
            (jnp.int32(0), list(sigs)),
        )
        return sigs, rounds

    # -- node-sharded channel commit (explicit all_to_all exchange) ----------
    def _channel_commit_sharded(
        self, mesh, axis, state, ok, to_idx, level, key, slot, cnt_list, aux,
        cap=None, time_overflow=0, *, scope,
    ):
        """The channel commit of _send_stacked under node-axis sharding
        (SURVEY §7): each device owns N/P node rows of the
        channel arrays; update rows are BUCKETED BY DESTINATION DEVICE and
        exchanged with ONE lax.all_to_all per tensor, then committed with
        the same min/max-scatter semantics on the LOCAL shard.  GSPMD's
        alternative for these computed-index scatters is gathering the
        operand — which un-shards exactly the arrays this axis exists to
        split.  Bit-identical to the unsharded commit when cap is None:
        keys are unique per (receiver, level, rel), so winner selection is
        order-free, and the default per-destination bucket capacity is the
        full local row count (no overflow, nothing dropped).

        Exchange cost per device per send: meta [P, cap, 6] int32 +
        content [P, cap, w_pad] u32 per bucket.  The default cap = M/P
        makes the per-device transient the full global M rows (P x the
        resident sender rows) — fine for small meshes, quadratic-feeling
        at large P.  `cap` (engine attr `exchange_capacity`) bounds it;
        destinations are hash-spread so a few x the mean fan-in suffices,
        and bucket overflow is counted in proto["displaced"] — the same
        bounded-loss semantics as channel displacement, which the
        protocols' periodic re-offers are already designed to absorb
        (bit identity then becomes distribution parity).  `scope` is
        _send_stacked's CHANNEL_SCOPES context: the claim and the commit
        carry the names they carry unsharded; the exchange before them
        exists only here and stays under the engine's phase scope."""
        from functools import partial as _partial

        from jax import lax as _lax
        from jax import shard_map as _shard_map
        from jax.sharding import PartitionSpec as _P

        proto = state.proto
        n, d = self.n_nodes, self.CHANNEL_DEPTH
        ss = d + 1
        L = self.n_levels
        nb = len(self.buckets)
        p_sz = mesh.shape[axis]
        if n % p_sz:
            raise ValueError(f"n_nodes {n} not divisible by mesh axis {p_sz}")
        n_loc = n // p_sz
        have_aux = aux is not None
        aux_col = aux.astype(jnp.int32) if have_aux else jnp.zeros_like(to_idx)
        meta = jnp.stack(
            [to_idx, level, key, slot, aux_col, ok.astype(jnp.int32)], axis=1
        )  # [M, 6]

        sig_names = [f"in_sig{i}" for i in range(nb)]
        w_pads = [b.w_pad for b in self.buckets]

        in_specs = (
            [_P(axis)]  # meta rows
            + [_P(axis)] * nb  # content rows
            + [_P(axis)]  # in_key
            + [_P(axis)] * nb  # in_sig
            + ([_P(axis)] if have_aux else [])
        )
        out_specs = (
            [_P(axis)] + [_P(axis)] * nb + ([_P(axis)] if have_aux else []) + [_P()]
        )

        @_partial(
            _shard_map,
            mesh=mesh,
            in_specs=tuple(in_specs),
            out_specs=tuple(out_specs),
            check_vma=False,
        )
        def island(meta_l, *rest):
            cnts = rest[:nb]
            ikey = rest[nb]
            sigs = list(rest[nb + 1 : nb + 1 + nb])
            iaux = rest[nb + 1 + nb] if have_aux else None
            di = _lax.axis_index(axis)
            m_loc = meta_l.shape[0]
            bucket_cap = m_loc if cap is None else min(int(cap), m_loc)

            # 1. bucket local rows by destination device (invalid -> p_sz,
            # dropped by the scatter; beyond-capacity rows too, counted
            # below as displaced)
            dest = jnp.where(meta_l[:, 5] > 0, meta_l[:, 0] // n_loc, p_sz)
            dsort, order = sort_with_order(dest)
            pos = run_rank(dsort)  # place inside the destination's bucket
            overflow = jnp.sum(
                ((pos >= bucket_cap) & (dsort < p_sz)).astype(jnp.int32)
            )

            def to_buf(vals, fill):
                buf = jnp.full(
                    (p_sz, bucket_cap) + vals.shape[1:], fill, vals.dtype
                )
                return buf.at[dsort, jnp.where(pos < bucket_cap, pos, bucket_cap)].set(
                    vals[order], mode="drop"
                )

            # 2. one all_to_all per tensor: device j's bucket-for-me lands
            # in my row j
            meta_x = _lax.all_to_all(
                to_buf(meta_l, 0), axis, split_axis=0, concat_axis=0, tiled=True
            ).reshape(p_sz * bucket_cap, 6)
            cnt_x = [
                _lax.all_to_all(
                    to_buf(c, 0), axis, split_axis=0, concat_axis=0, tiled=True
                ).reshape(p_sz * bucket_cap, w)
                for c, w in zip(cnts, w_pads)
            ]

            # 3. local commit — the unsharded scatter code with local
            # receiver rows (buffer fill rows have ok=0 and are masked)
            with scope("claim"):
                to_r = meta_x[:, 0] - di * n_loc
                lvl = jnp.clip(meta_x[:, 1], 1, L - 1)
                key_r = meta_x[:, 2]
                slot_r = meta_x[:, 3]
                aux_r = meta_x[:, 4]
                ok_r = meta_x[:, 5] > 0
                col = (lvl - 1) * ss + slot_r
                fcol = (lvl - 1) * ss + d
                safe_to = jnp.where(ok_r, to_r, n_loc)
                prev = ikey.at[safe_to, col].get(mode="fill", fill_value=INT32_MAX)
                new_key = ikey.at[safe_to, col].min(
                    jnp.where(ok_r, key_r, INT32_MAX), mode="drop"
                )
                got = new_key.at[safe_to, col].get(
                    mode="fill", fill_value=INT32_MAX
                )
                winner = ok_r & (got == key_r)
                new_key = new_key.at[safe_to, fcol].max(
                    jnp.where(ok_r, key_r, -1), mode="drop"
                )
                fgot = new_key.at[safe_to, fcol].get(mode="fill", fill_value=-1)
                fresh_win = ok_r & (fgot == key_r)
                lost_entry = ok_r & ~winner & ~fresh_win
                evicted = winner & (prev != INT32_MAX) & (prev > key_r)
                displaced = jnp.sum((lost_entry | evicted).astype(jnp.int32))

            with scope("commit"):
                for i, b in enumerate(self.buckets):
                    in_b = (lvl >= b.lo) & (lvl <= b.hi) & ok_r
                    sigs[i] = self._commit_bucket(
                        sigs[i], b,
                        jnp.where(winner & in_b, to_r, n_loc),
                        jnp.where(fresh_win & in_b, to_r, n_loc),
                        lvl - b.lo, slot_r, cnt_x[i],
                    )
                outs = [new_key] + sigs
                if have_aux:
                    iaux = iaux.at[jnp.where(winner, to_r, n_loc), col].set(
                        aux_r, mode="drop"
                    )
                    iaux = iaux.at[jnp.where(fresh_win, to_r, n_loc), fcol].set(
                        aux_r, mode="drop"
                    )
                    outs.append(iaux)
            outs.append(_lax.psum(displaced + overflow, axis))
            return tuple(outs)

        args = (
            [meta]
            + cnt_list
            + [proto["in_key"]]
            + [proto[k] for k in sig_names]
            + ([proto["in_aux"]] if have_aux else [])
        )
        res = island(*args)
        updates = dict(proto, in_key=res[0])
        for i, k in enumerate(sig_names):
            updates[k] = res[1 + i]
        if have_aux:
            updates["in_aux"] = res[1 + nb]
        updates["displaced"] = proto["displaced"] + res[-1] + time_overflow
        return state._replace(proto=updates)

    # -- entry-identity candidate clears (shared by the _select
    # write-backs of handel_batched and gsf_batched: see the
    # handel_batched._select docstring for the semantics) ------------------
    @staticmethod
    def _entry_clear(cur_id3, cur_card3, tgt_id3, tgt_card3, tgt_mask3):
        """[N, L-1, K] clear mask: current entries equal in (id,
        cardinality) to any masked target entry of the same level."""
        m = (
            (cur_id3[..., :, None] == tgt_id3[..., None, :])
            & (cur_card3[..., :, None] == tgt_card3[..., None, :])
            & tgt_mask3[..., None, :]
        )
        return jnp.any(m, axis=-1)

    @staticmethod
    def _remove_chosen(ids, id3, card3, lvl_idx, sel_id, sel_card, remove):
        """Clear the chosen entry from its level's CURRENT slots by (id,
        cardinality) identity; returns the updated [N, L-1, K] id array
        (non-removing rows write their row back unchanged)."""
        row_id = jnp.take_along_axis(id3, lvl_idx[:, None, None], axis=1)[:, 0]
        row_card = jnp.take_along_axis(card3, lvl_idx[:, None, None], axis=1)[:, 0]
        mrow = (
            remove[:, None]
            & (row_id == sel_id[:, None])
            & (row_card == sel_card[:, None])
        )
        return id3.at[ids, lvl_idx].set(jnp.where(mrow, INT32_MAX, row_id))

    def _size_table(self):
        return np.asarray(
            [self.msg_size(t) for t in range(self.n_levels)], np.int32
        )

    # -- channel content accessor --------------------------------------------
    def _arrived_blocks(self, proto, i: int):
        """Bucket i's in-flight content, already in receiver block-local
        space (re-addressed at send time by _send_stacked):
        [N, nl, ss, w_pad].  Slots that are not `due` may hold stale
        content — consumers gate on the key/rank validity."""
        return self._sig_view(proto, i, self.CHANNEL_DEPTH + 1)
