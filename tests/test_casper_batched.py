"""Batched CasperIMD: chain-shape parity with the oracle, fork choice,
attestation accounting, determinism.

With the default parameters the honest run builds a linear chain — one
block per slot, each on its direct parent — and the traffic is
deterministic in aggregate, so the oracle comparison can be exact on
message counts and chain structure."""

import numpy as np
import pytest

from wittgenstein_tpu.engine import replicate_state
from wittgenstein_tpu.oracle.blockchain import Block
from wittgenstein_tpu.protocols import casper as oracle_casper
from wittgenstein_tpu.protocols.casper import CasperIMD, CasperParameters
from wittgenstein_tpu.protocols.casper_batched import make_casper

RUN_MS = 80000  # 10 slots
SLOT_MS = 8000


def oracle_run(params, run_ms=RUN_MS, seed=0):
    Block.reset_block_ids()
    o = CasperIMD(params)
    o.network().rd.set_seed(seed)
    o.init()
    o.network().run_ms(run_ms)
    heights = np.array([n.head.height for n in o.network().all_nodes])
    msgs = sum(n.msg_received for n in o.network().all_nodes)
    return o, heights, msgs


class TestBatchedCasper:
    @pytest.mark.slow
    def test_oracle_parity_linear_chain(self):
        """Default honest run: same per-height linear chain, the same
        total message count, heads within one slot of the oracle."""
        p = CasperParameters()
        _, oh, om = oracle_run(p)
        net, state = make_casper(p, max_heights=16)
        out = net.run_ms(state, RUN_MS)
        bh = np.asarray(out.proto["head"])
        parent = np.asarray(out.proto["blk_parent"])
        exists = np.asarray(out.proto["blk_exists"])
        n_blocks = int(exists.sum()) - 1  # minus genesis
        assert n_blocks >= 9
        # linear chain: block h sits on h-1
        for h in range(1, n_blocks + 1):
            assert parent[h] == h - 1
        assert abs(int(bh.max()) - int(oh.max())) <= 1
        bm = int(np.asarray(out.msg_received).sum())
        assert bm == om, (om, bm)
        assert int(out.dropped) == 0

    def test_attestations_complete(self):
        """Every slot's committee (attesters_per_round members) attests
        exactly once; blocks include the prior committee's attestations."""
        p = CasperParameters()
        net, state = make_casper(p, max_heights=16)
        out = net.run_ms(state, RUN_MS)
        att = np.asarray(out.proto["att_exists"])
        apr = p.attesters_per_round
        votes_per_height = att.reshape(-1, apr).sum(axis=1)
        full_heights = votes_per_height[votes_per_height > 0]
        assert (full_heights == apr).all()
        # each block (from height 2 on) carries its parent-height votes
        blk_att = np.asarray(out.proto["blk_att"])
        exists = np.asarray(out.proto["blk_exists"])
        for h in range(2, int(exists.sum()) - 1):
            assert blk_att[h].sum() >= apr, h

    def test_heads_advance_with_slots(self):
        net, state = make_casper(CasperParameters(), max_heights=16)
        s1 = net.run_ms(state, 40000)
        h1 = int(np.asarray(s1.proto["head"]).max())
        s2 = net.run_ms(s1, 40000)
        h2 = int(np.asarray(s2.proto["head"]).max())
        assert h1 >= 3
        assert h2 > h1

    @pytest.mark.slow
    def test_replicas_and_determinism(self):
        net, state = make_casper(CasperParameters(), max_heights=16)
        states = replicate_state(state, 4, seeds=[1, 2, 3, 4])
        a = net.run_ms_batched(states, 40000)
        ha = np.asarray(a.proto["head"])
        assert (ha.max(axis=1) >= 3).all()
        b = net.run_ms_batched(states, 40000)
        assert (np.asarray(b.proto["head"]) == ha).all()


class TestByzVariants:
    """Byzantine producer variants on the batched path (CasperIMD.java
    :511-640): head-start delay, skip-father, skip-on-skip."""

    def _oracle(self, variant, delay, run_ms=RUN_MS):
        from wittgenstein_tpu.protocols.casper import (
            ByzBlockProducer,
            ByzBlockProducerNS,
            ByzBlockProducerSF,
        )

        cls = {
            "delay": ByzBlockProducer,
            "sf": ByzBlockProducerSF,
            "ns": ByzBlockProducerNS,
        }[variant]
        Block.reset_block_ids()
        o = CasperIMD(CasperParameters())
        o.network().rd.set_seed(0)
        o.init(cls(o, delay, o.genesis))
        o.network().run_ms(run_ms)
        heights = np.array([n.head.height for n in o.network().all_nodes])
        msgs = sum(n.msg_received for n in o.network().all_nodes)
        return o, heights, msgs

    def test_delay_variant_oracle_parity(self):
        """Head-start producer with 3 s delay: same chain advance, same
        traffic, same direct/older-father accounting as the oracle."""
        o, oh, om = self._oracle("delay", 3000)
        net, state = make_casper(
            CasperParameters(), max_heights=16, byz_variant="delay", byz_delay=3000
        )
        out = net.run_ms(state, RUN_MS)
        bh = np.asarray(out.proto["head"])
        assert abs(int(bh.max()) - int(oh.max())) <= 1
        assert int(np.asarray(out.msg_received).sum()) == om
        bp0 = o.bps[0]
        b0 = int(np.asarray(out.proto["byz_direct"]).max())
        b1 = int(np.asarray(out.proto["byz_older"]).max())
        assert (b0, b1) == (bp0.on_direct_father, bp0.on_older_ancestor)

    def test_sf_variant_skips_father(self):
        """Skip-father producer: its blocks build on height-2 ancestors
        (stealing the father's transactions), matching the oracle's
        skip accounting."""
        o, oh, om = self._oracle("sf", 0)
        net, state = make_casper(
            CasperParameters(), max_heights=16, byz_variant="sf", byz_delay=0
        )
        out = net.run_ms(state, RUN_MS)
        parent = np.asarray(out.proto["blk_parent"])
        exists = np.asarray(out.proto["blk_exists"])
        bpc = CasperParameters().block_producers_count
        # bp0 owns heights 1, 1+bpc, ... — skipped parents show h-2
        skips = [
            h
            for h in range(1 + bpc, int(exists.sum()) - 1, bpc)
            if exists[h] and parent[h] == h - 2
        ]
        bp0 = o.bps[0]
        assert int(np.asarray(out.proto["byz_direct"]).max()) == bp0.on_direct_father
        assert len(skips) > 0 or bp0.on_direct_father == 0

    def test_ns_variant_oracle_parity(self):
        o, oh, om = self._oracle("ns", 0)
        net, state = make_casper(
            CasperParameters(), max_heights=16, byz_variant="ns", byz_delay=0
        )
        out = net.run_ms(state, RUN_MS)
        bh = np.asarray(out.proto["head"])
        assert abs(int(bh.max()) - int(oh.max())) <= 1
        bp0 = o.bps[0]
        assert int(np.asarray(out.proto["byz_skipped"]).max()) == bp0.skipped


def test_ring_capacity_autosizes_to_attestation_wave():
    """One committee broadcast is [apr x N] messages; a full ring DROPS new
    sends, so make_casper sizes the ring to 1.5 waves (the silent-capping
    bug behind the r4 1024-validator sweep failure).  Default config keeps
    the original 1<<14 (compile-cache stable)."""
    net, _ = make_casper(CasperParameters(), max_heights=12)
    assert net.capacity == 1 << 14
    net, _ = make_casper(
        CasperParameters(cycle_length=4, attesters_per_round=256),
        max_heights=12,
    )
    assert net.capacity == 1 << 19


# -- casper-1024 (PR 39): the validator count as a parameter, the oracle's
# head as a number, and the program against the benchmark's plain reference
# through the benchmark's entry point ---------------------------------------


def _reference_casper():
    """benchmark/reference's copy of the oracle: imports nothing of the
    program (benchmark/tests/test_reference_copy.py holds the copy equal)."""
    import os
    import sys

    ref_dir = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark", "reference"
    )
    if ref_dir not in sys.path:
        sys.path.insert(0, ref_dir)
    from witt_ref.protocols import casper

    return casper


def _casper_module(side):
    """The oracle port the program is built from, or the benchmark's copy."""
    return oracle_casper if side == "program" else _reference_casper()


@pytest.mark.parametrize("side", ["program", "reference"])
@pytest.mark.parametrize(
    "case", ["given", "not_given", "not_divisible", "json_round_trip", "disagree", "replace"]
)
def test_node_count_is_the_validator_count(case, side):
    """`node_count` stated sets the committee size; not stated it stays
    None and today's callers build what they built; both stated must
    agree, so no `dataclasses.replace` is silently dropped; the oracle
    port and the reference's copy alike."""
    import dataclasses

    casper = _casper_module(side)
    cls = casper.CasperParameters
    if case == "given":
        p = cls(node_count=64)
        assert (p.node_count, p.attesters_per_round, p.attesters_count) == (64, 16, 64)
        p = cls(node_count=1024, cycle_length=8)
        assert (p.attesters_per_round, p.attesters_count) == (128, 1024)
        if side == "program":
            net, state = make_casper(cls(node_count=64), max_heights=12)
            assert net.protocol.apr == 16 and state.down.shape == (1 + 2 + 64,)
        else:
            o = casper.CasperIMD(cls(node_count=64))
            o.init()
            assert len(o.attesters) == 64 and len(o.network().all_nodes) == 1 + 2 + 64
    elif case == "not_given":
        p = cls()
        assert (p.node_count, p.attesters_per_round, p.cycle_length) == (None, 20, 4)
        assert p.attesters_count == 80
        assert cls(attesters_per_round=256).attesters_count == 1024
    elif case == "not_divisible":
        with pytest.raises(ValueError, match="node_count"):
            cls(node_count=66)
        with pytest.raises(ValueError, match="node_count"):
            cls(node_count=0)
    elif case == "json_round_trip":
        for p in (cls(), cls(node_count=64), cls(attesters_per_round=7, cycle_length=3)):
            q = cls.from_json(p.to_json())
            assert q == p and q.attesters_count == p.attesters_count
        # a job's parameters arrive as a dict, through the class; an old
        # JSON has no node_count key
        assert cls.from_dict({"node_count": 1024}).attesters_per_round == 256
        assert cls.from_dict({"attesters_per_round": 30, "cycle_length": 4}) == cls(
            attesters_per_round=30)
    elif case == "disagree":
        assert cls(node_count=64, attesters_per_round=16).attesters_count == 64
        with pytest.raises(ValueError, match="attesters_per_round says 20"):
            cls(node_count=64, attesters_per_round=20)
    else:
        # on parameters that state the committee, replace() does what it did
        p = cls(attesters_per_round=16)
        assert dataclasses.replace(p, attesters_per_round=8).attesters_count == 32
        assert dataclasses.replace(p, cycle_length=8).attesters_count == 128
        # on parameters that state the validators, a replaced committee or
        # cycle contradicts them: an error, never a change silently dropped
        p = cls(node_count=64)
        with pytest.raises(ValueError, match="attesters_per_round says 8"):
            dataclasses.replace(p, attesters_per_round=8)
        with pytest.raises(ValueError, match="attesters_per_round says 16"):
            dataclasses.replace(p, cycle_length=8)
        q = dataclasses.replace(p, cycle_length=8, attesters_per_round=None)
        assert (q.node_count, q.attesters_per_round) == (64, 8)
        q = dataclasses.replace(p, attesters_per_round=8, node_count=None)
        assert (q.node_count, q.attesters_count) == (None, 32)
        assert dataclasses.replace(p, node_count=128, attesters_per_round=None).attesters_per_round == 32


@pytest.mark.parametrize("side", ["program", "reference"])
def test_head_height_is_the_oracles_head_as_a_number(side):
    casper = _casper_module(side)
    o = casper.CasperIMD(casper.CasperParameters(node_count=16))
    o.network().rd.set_seed(3)
    o.init()
    assert {n.head_height for n in o.network().all_nodes} == {0}
    assert {n.head_score for n in o.network().all_nodes} == {0}
    o.network().run_ms(30000)
    nodes = o.network().live_nodes()
    assert [n.head_height for n in nodes] == [n.head.height for n in nodes]
    assert max(n.head_height for n in nodes) == 3
    # 30,000 ms: block 3 has arrived, the committees of slots 1, 2 and 3
    # have voted (4 each) and every vote has arrived; the head's votes are
    # countAttestations against genesis, the third block back
    assert {n.head_votes for n in nodes} == {12}
    assert [n.head_score for n in nodes] == [n.head_height * 17 + n.head_votes for n in nodes]
    assert all(n.head_votes == n.count_attestations(n.head, o.genesis) for n in nodes)


@pytest.mark.parametrize("wrong", [
    {},
    {"attestation_construction_time": 5000},  # the last committee's wave is not out yet
], ids=["sound", "late_votes"])
def test_head_score_is_the_oracles_node_for_node(wrong):
    """`proto.head_score`, (head height, `_count` for the head against its
    ancestor a cycle back) as one integer, equals the oracle's
    `CasperNode.head_score` on every node, with the defaults and with
    votes that leave 5 s late.  Read 6 s into slot 10, where nothing is
    between a send and its count on either side; the seeds differ, the
    counts do not depend on them (no fork, so no coin is tossed)."""
    kw = dict(node_count=64, **wrong)
    net, state = make_casper(CasperParameters(**kw), max_heights=24)
    o = oracle_casper.CasperIMD(oracle_casper.CasperParameters(**kw))
    o.network().rd.set_seed(5)
    o.init()
    state = net.run_ms(state, 86000)
    o.network().run_ms(86000)
    have = np.asarray(state.proto["head_score"])
    want = np.array([n.head_score for n in o.network().all_nodes])
    assert (have == want).all(), (np.unique(have), np.unique(want))
    assert (have // 65 == np.asarray(state.proto["head"])).all() and (have % 65 <= 64).all()
    # head 10; four committees of 16, or three where committee 10's votes are not out
    assert set(have.tolist()) == {10 * 65 + (48 if wrong else 64)}


@pytest.mark.parametrize("at_ms", [30000, 60100, 86000])
def test_count_is_the_oracles_on_a_forked_chain(at_ms):
    """`_count` against the oracle's `count_attestations` on the SAME
    chain: the oracle runs with blocks that leave after the next slot
    began, so producers build on what they have and the chain forks;
    its blocks, attestations and every node's received set are written
    into the program's tables (heights are unique, an attestation's slot
    is its height's row), and `_head_score` must read the oracle's
    `head_score` on every node, in the middle of a wave too.  A whole run
    cannot be held node for node once heads fork: `best` tosses a coin on
    ties and the two sides draw different latencies."""
    import jax.numpy as jnp

    p = CasperParameters(node_count=64, block_construction_time=9000)
    net, state = make_casper(p, max_heights=24)
    proto, mh, apr = net.protocol, net.protocol.mh, net.protocol.apr
    o = oracle_casper.CasperIMD(p)
    o.network().rd.set_seed(11)
    o.init()
    o.network().run_ms(at_ms)
    nodes = o.network().all_nodes
    blocks = {}
    for n in nodes:  # a block its producer has not sent yet is nobody's but its head
        for b in [n.head, *n.blocks_received_by_block_id.values()]:
            assert blocks.setdefault(b.height, b) is b  # one block a height
    assert any(b.parent.height != h - 1 for h, b in blocks.items() if h)  # it did fork
    atts = {a for n in nodes for s in n.attestations_by_head.values() for a in s}
    atts |= {a for b in blocks.values() for s in b.attestations_by_height.values() for a in s}
    slot = {}
    for a in sorted(atts, key=lambda a: (a.height, a.attester.node_id)):
        slot[a] = (a.height - 1) * apr + sum(1 for k in slot if k.height == a.height)
    t = {k: np.array(v) for k, v in state.proto.items()}
    for h, b in blocks.items():
        t["blk_exists"][h] = True
        cur = b.parent
        while cur is not None:
            t["anc"][h, cur.height] = True
            cur = cur.parent
        for s in b.attestations_by_height.values():
            t["blk_att"][h, [slot[a] for a in s]] = True
    for a, k in slot.items():
        t["att_exists"][k], t["att_head"][k] = True, a.head.height
    for i, n in enumerate(nodes):
        t["head"][i] = n.head.height
        for s in n.attestations_by_head.values():
            t["rec_att"][i, [slot[a] for a in s]] = True
    have = np.asarray(proto._head_score({k: jnp.asarray(v) for k, v in t.items()}))
    want = np.array([n.head_score for n in nodes])
    assert (have == want).all(), (np.unique(have), np.unique(want))
    assert len(set(want.tolist())) > 1 or at_ms == 86000


class TestAgainstTheBenchmarksReference:
    """64 validators (16 a round), two seeded rows through
    `sharded_run_stats` one 8-s slot at a time, as the cell
    `casper-1024.single-r1-s8000` drives the program, against
    benchmark/reference's CasperIMD from the same seeds.  The store is the
    factory's own sizing (16,384 slots), not the cell's 524,288: the lane's
    length changes what a step costs, not what it computes."""

    SLOTS = 10
    SEEDS = [7001, 2147483659 % (2**31 - 1)]

    @pytest.fixture(scope="class")
    def rows_by_slot(self):
        from wittgenstein_tpu.parallel.replica_shard import sharded_run_stats

        net, state = make_casper(CasperParameters(node_count=64), max_heights=24)
        assert net.flat
        states = replicate_state(state, len(self.SEEDS), seeds=self.SEEDS)
        out = []
        for _ in range(self.SLOTS):
            states, _stats = sharded_run_stats(net, states, SLOT_MS)
            out.append({k: np.asarray(getattr(states, k)) for k in
                        ("time", "msg_sent", "msg_received", "dropped", "ovf_valid", "ovf_type")}
                       | {"head": np.asarray(states.proto["head"])})
        return net, out

    def test_nothing_but_the_tasks_is_in_the_store_at_a_slot_boundary(self, rows_by_slot):
        """Every message a node sent has been counted for its receiver,
        exactly, and the flat lane holds one re-armed self-message a
        scheduled node (2 producers, 64 attesters) and, before each of the
        WF producer's slots, its build task due on that very boundary, and
        nothing else: which is why the cell's conservation law names no
        leaf (PERF.md section 4)."""
        net, slots = rows_by_slot
        tasks = {net.protocol.mtype(t) for t in ("TBP", "TATT", "TWF", "TWFB")}
        for k, s in enumerate(slots):
            assert (s["time"] == SLOT_MS * (k + 1)).all()
            assert (s["dropped"] == 0).all()
            sent, received = s["msg_sent"].sum(-1), s["msg_received"].sum(-1)
            assert (sent == received).all(), (k, sent, received)
            in_store = s["ovf_valid"].sum(-1)
            assert ((in_store == 2 + 64) | (in_store == 2 + 64 + 1)).all(), (k, in_store)
            assert set(s["ovf_type"][s["ovf_valid"]].tolist()) <= tasks
        assert slots[-1]["msg_sent"].sum() > 0

    def test_heads_and_traffic_are_the_references_at_ten_slots(self, rows_by_slot):
        """Head-height quantiles within 1/9 (one block) and the mean
        messages sent within 1%: at a slot boundary the reference has sent
        the slot's block and the program sends it on the next tick, 1
        message in 154 here (PERF.md section 4, point 3 of PR 39)."""
        casper = _reference_casper()
        _net, slots = rows_by_slot
        heads, sent = [], []
        for seed in self.SEEDS:
            o = casper.CasperIMD(casper.CasperParameters(node_count=64))
            o.network().rd.set_seed(seed)
            o.init()
            o.network().run_ms(SLOT_MS * self.SLOTS)
            live = o.network().live_nodes()
            heads += [n.head_height for n in live]
            sent += [n.msg_sent for n in live]
        last = slots[-1]
        want = np.percentile(heads, [10, 50, 90])
        have = np.percentile(last["head"], [10, 50, 90])
        assert (np.abs(have - want) / want <= 0.12).all(), (have, want)
        assert abs(last["msg_sent"].mean() - np.mean(sent)) / np.mean(sent) <= 0.01
        assert np.mean(sent) > 150


# -- the flat store's due view (engine/core.py `due_view_rows`, PR 39) --------------


def _leaves_differ(a, b):
    """Every leaf but the work census's (PR 41): it counts the view's
    overflows, which the whole lane has none of."""
    import jax

    la, lb = (jax.tree_util.tree_leaves(x._replace(census=())) for x in (a, b))
    assert len(la) == len(lb)
    return [i for i, (x, y) in enumerate(zip(la, lb)) if not np.array_equal(np.asarray(x), np.asarray(y))]


@pytest.fixture(scope="module")
def whole_lane_rows():
    """64 validators through three slots with every step viewing the whole
    lane (`due_view_rows=0`): what each due view has to equal, leaf for
    leaf.  One row unbatched and two rows under `vmap`."""
    net, state = make_casper(CasperParameters(node_count=64), max_heights=24, due_view_rows=0)
    assert net.due_view_rows is None
    one = net.run_ms(state._replace(seed=state.seed + 7001), 3 * SLOT_MS)
    two = net.run_ms_batched(replicate_state(state, 2, seeds=[7001, 12]), 3 * SLOT_MS)
    return one, two


@pytest.fixture(scope="module")
def due_rows_a_step():
    """The lane rows due at each executed step of that one row, counted
    from outside: the whole-lane program stepped one jump at a time."""
    import jax

    net, state = make_casper(CasperParameters(node_count=64), max_heights=24, due_view_rows=0)
    state, end = state._replace(seed=state.seed + 7001), 3 * SLOT_MS
    jump = jax.jit(lambda s: net._step_jump(s, end))
    due = []
    while int(state.time) < end:
        due.append(int(np.sum(np.asarray(state.ovf_valid) & (np.asarray(state.ovf_arrival) <= int(state.time)))))
        state = jump(state)
    return due


@pytest.mark.parametrize("rows, fits, batched", [
    (None, "always", False), (8, "on the block's steps alone", False), (1, "never", False),
    (8, "on the block's steps alone", True),
])
def test_the_due_view_computes_what_the_whole_lane_does(
    whole_lane_rows, due_rows_a_step, rows, fits, batched
):
    """The factory's own view (256 rows of a 16,384-row lane: every step
    of a 1072-message wave fits), one that the wave's steps overflow (so
    both branches run in one simulation) and one that only an empty step
    fits: the same state, leaf for leaf, as the whole lane gives.  Batched,
    the branches are `vmap`'s selects over both sides; one row of a batch
    of one runs unbatched (`_run_ms_batched_impl`)."""
    net, state = make_casper(CasperParameters(node_count=64), max_heights=24, due_view_rows=rows)
    assert net.due_view_rows == (256 if rows is None else rows) and net.flat
    one, two = whole_lane_rows
    if batched:
        got = net.run_ms_batched(replicate_state(state, 2, seeds=[7001, 12]), 3 * SLOT_MS)
        assert _leaves_differ(got, two) == []
    else:
        got = net.run_ms_batched(replicate_state(state, 1, seeds=[7001]), 3 * SLOT_MS)
        assert np.asarray(got.time).shape == (1,)
        assert _leaves_differ(jax_first(got), one) == []
        # the work census counts the steps that took the whole-lane branch:
        # those with more rows due than the view holds, as counted outside
        census = {k: int(v[0]) for k, v in got.census._asdict().items()}
        over = sum(d > net.due_view_rows for d in due_rows_a_step)
        assert census["steps"] == len(due_rows_a_step) == int(one.census.steps)
        assert census["view_overflow_steps"] == over
        assert census["due_rows_peak"] == max(due_rows_a_step)
        assert {"always": over == 0, "never": over > len(due_rows_a_step) // 2}.get(fits, over > 0)
        assert int(one.census.view_overflow_steps) == int(one.census.due_rows_peak) == 0
    assert int(np.asarray(got.msg_sent).sum()) > 0 and int(np.asarray(got.dropped).max()) == 0


def jax_first(states):
    import jax

    return jax.tree_util.tree_map(lambda a: a[0], states)


def test_a_step_without_a_send_still_ticks_the_event_counter():
    """`apply_emissions` under a due view skips a step's emissions when
    every mask is empty; an empty emission sampled no latency but ticked
    `send_ctr`, which seeds every later draw: the skip ticks it as often
    (four sampled emissions a Casper step), and the slot's first executed
    step, on which only tasks re-arm, shows it."""
    for rows in (0, None):
        net, state = make_casper(CasperParameters(node_count=64), max_heights=24, due_view_rows=rows)
        s = net.run_ms(state, SLOT_MS)  # the empty slot 0: one jump to the boundary
        assert int(s.send_ctr) == 4 and int(np.asarray(s.msg_sent).sum()) == 0


@pytest.mark.parametrize("kwargs, message", [
    # since PR 43 a store with a wheel takes a due view too, of its due row's leading slots
    ({"wheel_rows": 32, "due_view_rows": 1 << 20}, "inside a wheel row"),
    ({"wheel_rows": 32, "due_view_rows": (64, 8)}, "must ascend"),
    ({"wheel_rows": 0, "due_view_rows": 1 << 14}, "inside the lane"),
    ({"wheel_rows": 0, "due_view_rows": 0}, "inside the lane"),
    ({"wheel_rows": 0, "due_view_rows": (8, 64)}, "inside the lane"),
])
def test_a_due_view_lies_inside_its_lane_or_its_wheel_row(kwargs, message):
    from wittgenstein_tpu.engine import BatchedNetwork

    net, _state = make_casper(CasperParameters(node_count=64), max_heights=24)
    with pytest.raises(ValueError, match=message):
        BatchedNetwork(net.protocol, net.latency, net.n_nodes, capacity=1 << 14, **kwargs)


# -- the scopes of Casper's deliver (casper_batched.CHAIN_SCOPES, PR 39) ---------


@pytest.mark.parametrize("dead", [None, "forkchoice", "build", "attest"])
def test_sl601_holds_the_chain_scopes_live(monkeypatch, dead):
    """The registered Casper carries `witt.chain.*` in `step()`'s jaxpr and
    is bit-neutral under them; a scope taken out is an SL601 finding that
    names it."""
    import contextlib
    import os

    from wittgenstein_tpu.analysis.annotations_check import check_annotations_entry
    from wittgenstein_tpu.core.registries import registry_batched_protocols
    from wittgenstein_tpu.engine.core import BatchedNetwork
    from wittgenstein_tpu.protocols.casper_batched import CHAIN_SCOPES

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    real = BatchedNetwork._scope

    def scope(self, name, scopes=None):
        if scopes is CHAIN_SCOPES and name == dead:
            return contextlib.nullcontext()
        return real(self, name) if scopes is None else real(self, name, scopes)

    monkeypatch.setattr(BatchedNetwork, "_scope", scope)
    findings = check_annotations_entry(registry_batched_protocols.get("casper"), root=root)
    if dead is None:
        assert findings == []
    else:
        assert [f.rule for f in findings] == ["SL601"]
        assert CHAIN_SCOPES[dead] in findings[0].message
