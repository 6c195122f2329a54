"""Batched SanFermin: convergence, agg-value exactness, oracle parity on
done-time quantiles, the reference's pick order and stacked reply
timeouts, the message store's conservation law, determinism.

The oracle itself leaves stragglers (~5% of nodes never finish at 64
nodes/6s: a node whose whole candidate block stops responding runs out of
picks, SanFerminSignature.java:334-338), so parity is measured on the done
population and the done fraction, not on all nodes."""

import jax.numpy as jnp
import numpy as np
import pytest

from wittgenstein_tpu.engine import Emission, replicate_state
from wittgenstein_tpu.protocols.sanfermin import (
    SanFerminNode,
    SanFerminSignature,
    SanFerminSignatureParameters,
    Status,
    SwapReply,
)
from wittgenstein_tpu.protocols.sanfermin_batched import make_sanfermin


def make_params(**kw):
    base = dict(
        node_count=64,
        threshold=64,
        pairing_time=2,
        signature_size=48,
        reply_timeout=300,
        candidate_count=1,
        shuffled_lists=False,
    )
    base.update(kw)
    return SanFerminSignatureParameters(**base)


def oracle_stats(params, seeds, run_ms):
    done, agg = [], []
    for seed in seeds:
        p = SanFerminSignature(params)
        p.network().rd.set_seed(seed)
        p.init()
        p.network().run_ms(run_ms)
        done += [n.done_at for n in p.network().all_nodes]
        agg += [n.agg_value for n in p.network().all_nodes]
    return np.asarray(done), np.asarray(agg)


class TestBatchedSanFermin:
    def test_converges_full_aggregation(self):
        """Done nodes descended all log2(N) levels with exact doubling:
        their aggregate is the full 64 (a finished node's every swap paired
        complementary halves)."""
        net, state = make_sanfermin(make_params())
        out = net.run_ms(state, 6000)
        done = np.asarray(out.done_at)
        agg = np.asarray(out.proto["agg"])
        assert (done > 0).mean() >= 0.9
        assert (agg[done > 0] >= 64).all()
        assert int(out.dropped.max()) == 0

    @pytest.mark.slow
    def test_oracle_parity(self):
        """The smallest size: done fraction within 5 points and P50/P90 of
        doneAt (among done nodes) within 8% of the oracle DES.  At 64 nodes
        a quantile of 16 x 64 done times still moves by a few percent from
        seed to seed; the limits with a reason are at 256 nodes, below."""
        p = make_params()
        od, oa = oracle_stats(p, range(8), 6000)
        net, state = make_sanfermin(p)
        states = replicate_state(state, 16)
        out = net.run_ms_batched(states, 6000)
        bd = np.asarray(out.done_at).ravel()
        assert abs((bd > 0).mean() - (od > 0).mean()) <= 0.05
        oq = np.percentile(od[od > 0], [50, 90])
        bq = np.percentile(bd[bd > 0], [50, 90])
        rel = np.abs(bq - oq) / oq
        assert (rel <= 0.08).all(), (oq, bq, rel)
        # done nodes aggregate fully in both engines
        ba = np.asarray(out.proto["agg"]).ravel()
        assert (oa[od > 0] >= 64).all()
        assert (ba[bd > 0] >= 64).all()
        assert int(out.dropped.max()) == 0

    def test_threshold_at(self):
        """threshold_at is stamped when agg crosses threshold, at or before
        the final descent (SanFerminSignature.java:393-398)."""
        p = make_params(threshold=32)
        net, state = make_sanfermin(p)
        out = net.run_ms(state, 6000)
        thr = np.asarray(out.proto["thr_at"])
        done = np.asarray(out.done_at)
        fin = done > 0
        assert fin.mean() >= 0.9
        assert (thr[fin] > 0).all()
        assert (thr[fin] <= done[fin]).all()

    @pytest.mark.slow
    def test_replicas_and_determinism(self):
        net, state = make_sanfermin(make_params(node_count=32, threshold=32))
        states = replicate_state(state, 4, seeds=[11, 12, 13, 14])
        a = net.run_ms_batched(states, 6000)
        done = np.asarray(a.done_at)
        assert (done > 0).mean() >= 0.9
        assert len({tuple(done[i]) for i in range(4)}) > 1
        b = net.run_ms_batched(states, 6000)
        assert (np.asarray(b.done_at) == done).all()


# ---- the reference's pick order --------------------------------------------


@pytest.mark.parametrize("candidate_count", [1, 4])
@pytest.mark.parametrize("n", [16, 64, 256])
def test_the_pick_order_is_pick_next_nodes(n, candidate_count):
    """`_picks` is SanFerminHelper.pickNextNodes call for call: the exact
    candidate and candidate_count more by the index of the list without it,
    then candidate_count a call by the index of the whole list, the used
    indices and the own index skipped, down to the call that finds nothing
    (the member after the exact one never asked, one member asked twice).
    The reference shuffles what a call returns, so a call's picks are
    compared as a set; with candidate_count 1 every later call is one pick,
    so the order is held exactly."""
    proto = make_sanfermin(make_params(node_count=n, threshold=n))[0].protocol
    oracle = SanFerminSignature(make_params(node_count=n, threshold=n))
    sample = oracle.all_nodes[:: max(1, n // 16)]
    ids = jnp.arange(n, dtype=jnp.int32)
    for cpl in range(proto.w):
        cursor, entering, live = jnp.zeros(n, jnp.int32), jnp.ones(n, bool), set(sample)
        for _call in range((n >> (cpl + 1)) + 2):
            picks, cursor = proto._picks(ids, jnp.full(n, cpl, jnp.int32), cursor, entering, candidate_count)
            partner = np.stack([np.asarray(q[0]) for q in picks], 1)
            valid = np.stack([np.asarray(q[1]) for q in picks], 1)
            for node in sorted(live, key=lambda x: x.node_id):
                want = {x.node_id for x in node.candidate_tree.pick_next_nodes(cpl, candidate_count)}
                i = node.node_id
                assert set(partner[i][valid[i]].tolist()) == want, (n, cpl, i, _call)
                assert valid[i].sum() == len(want)  # a member asked twice is two picks of two calls
                if not want:
                    live.discard(node)  # out of picks, in both
            entering = jnp.zeros(n, bool)
        assert not live


# ---- stacked reply timeouts, on a state made by hand -----------------------

X, FROM = 5, 13  # node 5 of 16 enters level 0 at t=1; 13 is its exact candidate there


def _hand_made_pair(monkeypatch, no_arrivals):
    """The oracle and the batched program with ONE live node, X, which
    descends to level 0 at t=1; every other node is done, at level 2 and
    with nothing cached, so it answers each request NO at its own level,
    which X drops at the level check.  What moves X is the NO replies put
    in by hand, at X's level and from a node it has asked, and its
    timeouts.  Returns (oracle send times of X, batched send ticks of X,
    the batched run's dropped count)."""
    params = make_params(node_count=16, threshold=16)
    # the oracle
    oracle = SanFerminSignature(params)
    onet = oracle.network()
    sends = []
    for node in oracle.all_nodes:
        node.current_prefix_length, node.done, node.pending_nodes = 2, True, set()
    x = oracle.all_nodes[X]
    x.current_prefix_length, x.done = 1, False
    send_to_nodes = SanFerminNode._send_to_nodes

    def recorded(node, cands):
        if node is x and cands:
            sends.append((onet.time, len(cands)))
        return send_to_nodes(node, cands)

    monkeypatch.setattr(SanFerminNode, "_send_to_nodes", recorded)
    onet.register_task(x.go_next_level, 1, x)
    for t in no_arrivals:
        onet.send_arrive_at(SwapReply(oracle, Status.NO, 0, 0), t, oracle.all_nodes[FROM], x)
    onet.run_ms(700)
    # the program: the same state, an empty store
    net, state = make_sanfermin(params)
    proto = dict(state.proto)
    others = jnp.arange(16) != X
    proto["done"] = others
    proto["cpl"] = jnp.where(others, 2, 1).astype(jnp.int32)
    proto["cache_ok"] = jnp.zeros_like(proto["cache_ok"])
    proto["swapping"], proto["swap_t"] = ~others, jnp.where(others, 0, 1).astype(jnp.int32)
    proto["tmo_t"] = jnp.zeros_like(proto["tmo_t"])
    state = state._replace(
        proto=proto,
        msg_valid=jnp.zeros_like(state.msg_valid),
        whl_fill=jnp.zeros_like(state.whl_fill),
        ovf_valid=jnp.zeros_like(state.ovf_valid),
    )
    k = len(no_arrivals)
    state = net.apply_emission(
        state,
        Emission(
            mask=jnp.ones(k, bool),
            from_idx=jnp.full(k, FROM, jnp.int32),
            to_idx=jnp.full(k, X, jnp.int32),
            mtype=net.protocol.mtype("SWAP_REP_NO"),
            payload=jnp.zeros((k, 2), jnp.int32),
            arrival=jnp.asarray(no_arrivals, jnp.int32),
        ),
    )
    ticks, sent = [], 0
    for t in range(700):
        state = net.run_ms(state, 1)
        now = int(state.proto["sent_req"][X])
        if now != sent:
            ticks.append((t, now - sent))
            sent = now
    return sends, ticks, int(state.dropped)


def test_two_no_replies_stack_two_more_timeouts_and_each_repicks_at_the_oracles_time(monkeypatch):
    """A node whose candidate answers NO twice: each NO re-picks at once
    and arms a timeout of its own, so the level has three (1, 40 and 90
    plus reply_timeout) and every one of them re-picks when it comes due,
    until the level's eight picks are used up (the exact candidate and seven
    indices of a block of eight, the own index skipped)."""
    oracle, program, dropped = _hand_made_pair(monkeypatch, [40, 90])
    assert oracle == [(1, 2), (40, 1), (90, 1), (301, 1), (340, 1), (390, 1), (601, 1)]
    assert program == oracle
    assert dropped == 0


def test_a_send_that_finds_the_timeout_ring_full_is_counted_not_capped(monkeypatch):
    """Four NO replies stack a fifth live timeout on a ring of four: the
    send goes out as the oracle's does, its timeout is lost, and the run
    says so in `dropped`."""
    oracle, program, dropped = _hand_made_pair(monkeypatch, [40, 60, 80, 100])
    assert program == oracle[: len(program)] and program[:5] == [(1, 2), (40, 1), (60, 1), (80, 1), (100, 1)]
    assert dropped == 1


# ---- parity at the twin's size, and the store's law -------------------------

SEEDS_256 = [11, 4242, 2**31 - 3]


@pytest.fixture(scope="module")
def sanfermin256():
    """256 nodes on a store scaled as the deployment's (`sanfermin-4096`
    states capacity 65536 for 4096 nodes: 16 slots a node)."""
    params = make_params(node_count=256, threshold=256)
    return params, make_sanfermin(params, capacity=65536 // 16)


@pytest.mark.parametrize("seed", SEEDS_256)
def test_oracle_parity_at_256_nodes(sanfermin256, seed):
    """4 rows of 256 nodes to 3000 ms against the oracle from the same 4
    seeds, quantiles among the done.  The limits are twice the largest gap
    of 12 seeds (P10 0.048, P50 0.016, P90 0.021, messages sent 0.012, done
    fraction 0.013; PR 37), far under what the uniform pick order this
    module had read on every seed (P50 0.10-0.14, done fraction 0.04-0.05):
    a pick order that is not the reference's fails here."""
    params, (net, state) = sanfermin256
    seeds = [(seed + i) % (2**31 - 1) for i in range(4)]
    od, osent = [], []
    for s in seeds:
        p = SanFerminSignature(params)
        p.network().rd.set_seed(s)
        p.init()
        p.network().run_ms(3000)
        od += [n.done_at for n in p.network().all_nodes]
        osent += [n.msg_sent for n in p.network().all_nodes]
    od = np.asarray(od)
    out = net.run_ms_batched(replicate_state(state, 4, seeds=seeds), 3000)
    bd = np.asarray(out.done_at).ravel()
    oq = np.percentile(od[od > 0], [10, 50, 90])
    bq = np.percentile(bd[bd > 0], [10, 50, 90])
    rel = np.abs(bq - oq) / oq
    assert (rel <= [0.10, 0.035, 0.045]).all(), (oq, bq, rel)
    assert abs((bd > 0).mean() - (od > 0).mean()) <= 0.03
    sent = float(np.asarray(out.msg_sent).mean())
    assert abs(sent - np.mean(osent)) / np.mean(osent) <= 0.025
    assert int(out.dropped.max()) == 0


def test_the_store_conserves_messages_at_every_100_ms(sanfermin256):
    """sent == received + what the wheel and the overflow lane hold, and
    nothing dropped, at every 100 ms of a whole simulation: the law the
    benchmark holds the timed rows to (`timed_rows.conservation`)."""
    _params, (net, state) = sanfermin256
    states = replicate_state(state, 2, seeds=[7001, 7002])
    in_store = []
    for _ in range(24):
        states = net.run_ms_batched(states, 100)
        sent = np.asarray(states.msg_sent).sum(-1)
        received = np.asarray(states.msg_received).sum(-1)
        held = np.asarray(states.msg_valid).sum((-2, -1)) + np.asarray(states.ovf_valid).sum(-1)
        assert (sent == received + held).all(), (int(states.time[0]), sent, received, held)
        assert int(np.asarray(states.dropped).max()) == 0
        in_store.append(int(held.max()))
    assert max(in_store) > 500 and in_store[-1] == 0  # a full store mid-run, empty at the horizon
