"""The x-threshold partition on the batched engine's normal path
(`BatchedNetwork.partition` / `end_partition`, `SimState.partition_x`),
against `oracle/network.py`'s own two calls on the same populations, and
the first runs with a line set: PingPong at 64 nodes under
IC3NetworkLatency, which draws nothing, so that program and oracle are one
deterministic system and agree to the message.

What is counted (`Census.masked_sends`, `.discarded_rows`): a row whose
ends cannot reach each other when it is SENT is masked there, as the
oracle's `dropped += 1`; a row that was in flight when the line was drawn
is discarded where it is DUE, which the oracle does without a count.  With
both the store's law closes exactly:
sent == received + in the store + masked + discarded."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from wittgenstein_tpu.engine import BatchedNetwork, replicate_state
from wittgenstein_tpu.engine.core import INT_MAX, MAX_PARTITIONS
from wittgenstein_tpu.oracle.network import Network
from wittgenstein_tpu.protocols.pingpong import PingPong, PingPongParameters
from wittgenstein_tpu.protocols.pingpong_batched import make_pingpong

IC3 = "IC3NetworkLatency"
N = 64
XS = [1, 399, 400, 401, 1199, 1200, 1201, 2000]  # left of, on and right of the lines at 0.2 and 0.6


@pytest.fixture(scope="module")
def pingpong():
    return make_pingpong(N, network_latency_name=IC3)


def _sides(state, xs=XS):
    return np.asarray(BatchedNetwork.partition_id(state, jnp.asarray(xs, jnp.int32))).tolist()


def _oracle_sides(parts, xs=XS):
    net = Network()
    for part in parts:
        net.partition(part)
    return [net.partition_id(types.SimpleNamespace(x=x)) for x in xs], net.partitions_in_x


@pytest.mark.parametrize("parts", [(0.2,), (0.2, 0.6), (0.6, 0.2), (0.5, 0.25, 0.75, 0.1)])
def test_sides_of_the_lines_are_the_oracles(pingpong, parts):
    """A node ON a line is right of it; lines drawn in any order are kept
    sorted; `end_partition` leaves none."""
    _net, state = pingpong
    for part in parts:
        state = BatchedNetwork.partition(state, part)
    want, lines = _oracle_sides(parts)
    assert _sides(state) == want
    kept = np.asarray(state.partition_x).tolist()
    assert kept[: len(lines)] == lines and set(kept[len(lines):]) <= {int(INT_MAX)}
    healed = BatchedNetwork.end_partition(state)
    assert np.asarray(healed.partition_x).tolist() == [int(INT_MAX)] * MAX_PARTITIONS
    assert _sides(healed) == [0] * len(XS)


def test_a_batched_state_takes_one_line_for_all_rows_or_a_line_a_row(pingpong):
    _net, state = pingpong
    rows = replicate_state(state, 3, seeds=[1, 2, 3])
    same = BatchedNetwork.partition(rows, 0.2)
    assert np.asarray(same.partition_x)[:, 0].tolist() == [400, 400, 400]
    each = BatchedNetwork.partition(rows, [0.2, 0.6, 0.5])
    assert np.asarray(each.partition_x)[:, 0].tolist() == [400, 1200, 1000]
    each = BatchedNetwork.partition(each, [0.6, 0.2, 0.25])
    assert np.asarray(each.partition_x)[:, :2].tolist() == [[400, 1200], [400, 1200], [500, 1000]]
    one = lambda k: jax.tree_util.tree_map(lambda a: a[k], each)
    assert _sides(one(2)) == _oracle_sides((0.5, 0.25))[0]
    assert (np.asarray(BatchedNetwork.end_partition(each).partition_x) == INT_MAX).all()


@pytest.mark.parametrize("part", [0, 1, -0.1, 1.5])
def test_a_part_outside_0_1_is_an_error_as_the_oracles(pingpong, part):
    with pytest.raises(ValueError, match="between 0 & 100"):
        Network().partition(part)
    with pytest.raises(ValueError, match="between 0 & 100"):
        BatchedNetwork.partition(pingpong[1], part)


def test_a_line_that_is_there_is_an_error_as_the_oracles(pingpong):
    net = Network()
    net.partition(0.2)
    with pytest.raises(ValueError, match="exists already"):
        net.partition(0.2)
    state = BatchedNetwork.partition(pingpong[1], 0.2)
    with pytest.raises(ValueError, match="exists already"):
        BatchedNetwork.partition(state, 0.2)
    rows = BatchedNetwork.partition(replicate_state(pingpong[1], 2, seeds=[1, 2]), [0.2, 0.6])
    with pytest.raises(ValueError, match="exists already"):
        BatchedNetwork.partition(rows, [0.3, 0.6])  # one row's is enough


def test_a_fifth_line_is_an_error(pingpong):
    state = pingpong[1]
    for part in (0.1, 0.2, 0.3, 0.4):
        state = BatchedNetwork.partition(state, part)
    with pytest.raises(ValueError, match=f"{MAX_PARTITIONS} partition lines"):
        BatchedNetwork.partition(state, 0.5)


# -- the first runs with a line set -----------------------------------------------


def _oracle():
    p = PingPong(PingPongParameters(node_ct=N, network_latency_name=IC3))
    return p, p.network()


def _held_to_the_oracle(onet, state, discarded):
    """Sent and received node for node, the masked count against the
    oracle's `dropped`, and the store's law with both counts."""
    nodes = onet.all_nodes
    assert np.asarray(state.msg_sent).tolist() == [n.msg_sent for n in nodes]
    assert np.asarray(state.msg_received).tolist() == [n.msg_received for n in nodes]
    assert int(state.proto["pong"][0]) == nodes[0].pong
    masked = int(state.census.masked_sends)
    assert masked == onet.dropped
    assert int(state.census.discarded_rows) == discarded
    in_store = int(state.msg_valid.sum()) + int(state.ovf_valid.sum())
    assert int(state.msg_sent.sum()) == int(state.msg_received.sum()) + in_store + masked + discarded
    assert int(state.dropped) == 0
    return masked


def _line_at_t0(net, state, part):
    """PingPong with the line drawn before the witness's pings leave, in
    the oracle (`partition`, then `init`) and in the program
    (`init_state(partition=...)` over the same node columns, as
    `make_dfinity` draws Dfinity's)."""
    p, onet = _oracle()
    onet.partition(part)
    p.init()
    cols = {k: np.asarray(getattr(state, k)) for k in ("x", "y", "extra_latency", "city_idx")}
    return onet, net.init_state(cols, seed=0, proto=net.protocol.proto_init(N), partition=part)


def test_a_line_from_t0_masks_the_crossing_sends_and_nothing_is_discarded(pingpong):
    """The witness (x 1633) pings 64 nodes, 32 of them left of the line at
    1000: those 32 pings are masked where they are sent, the 32 nodes on
    its side answer, no row is ever in flight across the line."""
    net, sound = pingpong
    onet, state = _line_at_t0(net, sound, 0.5)
    assert int(state.census.masked_sends) == onet.dropped == 32  # counted by `init_state` already
    onet.run_ms(500)
    out = net.run_ms(state, 501)
    assert _held_to_the_oracle(onet, out, discarded=0) == 32
    assert int(out.proto["pong"][0]) == 32 and int(out.msg_sent.sum()) == 64 + 32


def test_a_line_drawn_under_messages_in_flight_discards_them_where_they_are_due(pingpong):
    """Sound to 100 ms (the oracle's `run_ms` takes the boundary tick,
    the program's does not: one tick more there), cut at 0.5 to 150 ms,
    healed to 500: the pings that were in flight across the line when it
    was drawn and fell due under it are discarded at delivery (9 of
    them), the one place `discarded_rows` grows; after `end_partition`
    the pings still in flight cross again and are answered."""
    net, state = pingpong
    p, onet = _oracle()
    p.init()
    onet.run_ms(100)
    state = net.run_ms(state, 101)
    _held_to_the_oracle(onet, state, discarded=0)
    onet.partition(0.5)
    state = net.partition(state, 0.5)
    onet.run_ms(50)
    state = net.run_ms(state, 50)
    _held_to_the_oracle(onet, state, discarded=9)
    pongs_cut = int(state.proto["pong"][0])
    onet.end_partition()
    state = net.end_partition(state)
    onet.run_ms(350)
    state = net.run_ms(state, 350)
    _held_to_the_oracle(onet, state, discarded=9)
    # traffic flows again: every node but the 9 whose ping was discarded has answered
    assert int(state.proto["pong"][0]) == N - 9 > pongs_cut
    assert int(state.msg_valid.sum()) + int(state.ovf_valid.sum()) == 0


def test_pongs_in_flight_across_a_new_line_are_discarded_and_none_is_masked(pingpong):
    """The line drawn at 60 ms, between the first pings' arrival and their
    pongs': pings and pongs in flight across it are discarded where they
    are due, and the count is what the oracle's delivery skipped (sent
    less received less `dropped`, the store empty).  Nothing is masked: a
    node answers in the step its ping is delivered, so under the line
    only the witness's own side ever sends."""
    net, state = pingpong
    p, onet = _oracle()
    p.init()
    onet.run_ms(60)
    state = net.run_ms(state, 61)
    assert int(state.msg_received.sum()) > 0  # some pings are in, their pongs on the way
    onet.partition(0.7)
    state = net.partition(state, 0.7)
    onet.run_ms(500)
    out = net.run_ms(state, 500)
    nodes = onet.all_nodes
    skipped = sum(n.msg_sent for n in nodes) - sum(n.msg_received for n in nodes) - onet.dropped
    assert skipped > 0
    assert _held_to_the_oracle(onet, out, discarded=skipped) == 0


def test_rows_of_one_batch_each_under_its_own_line(pingpong):
    """`run_ms_batched` takes a state with a line a row as any other:
    each row is what it is alone, census and all."""
    net, state = pingpong
    rows = BatchedNetwork.partition(replicate_state(state, 2, seeds=[0, 0]), [0.5, 0.7])
    batch = net.run_ms_batched(rows, 300)
    counts = []
    for k, part in enumerate((0.5, 0.7)):
        alone = net.run_ms(net.partition(state, part), 300)
        one = jax.tree_util.tree_map(lambda a: a[k], batch)
        for a, b in zip(jax.tree_util.tree_leaves(one), jax.tree_util.tree_leaves(alone)):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        counts.append(int(one.census.discarded_rows))
    assert counts[0] != counts[1] and min(counts) > 0
