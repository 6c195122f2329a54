"""Batched Dfinity under upstream's own experiment (Dfinity.java `main()`:
a fifth of the network cut off by an x-threshold partition), PR 46: the
program against the PARTITIONED oracle (`protocols/dfinity_part.py`) from
the same populations, the fan-out's grid against the plain rows under the
line, and the deployment with no line against `make_dfinity`'s of before.

A file of its own beside tests/test_dfinity_batched.py, and like it off
JAX's persistent compilation cache (`no_compile_cache`, tests/conftest.py
has why: XLA:CPU's executable serialisation crashes now and then on
Dfinity's programs, where the cache reads an entry or writes one)."""

import numpy as np
import pytest
from test_dfinity_batched import IC3, _differing, _small, dense_twin  # the sound network's small build, the leaf comparison, the dense reference

from wittgenstein_tpu.oracle.blockchain import Block
from wittgenstein_tpu.protocols.dfinity_batched import make_dfinity
from wittgenstein_tpu.protocols.dfinity_part import (
    PartitionedDfinity,
    PartitionedDfinityParameters,
)


pytestmark = pytest.mark.usefixtures("no_compile_cache")


def _partitioned(committee, seed=None, partition=0.2, **kwargs):
    params = PartitionedDfinityParameters(
        node_count=kwargs.pop("node_count", 256), attesters_per_round=committee, partition=partition)
    return make_dfinity(params, max_heights=8, latency_name=IC3, population_seed=seed, **kwargs)


@pytest.mark.parametrize("committee", [16, 32])  # dfinity-4096's twin, and dfinity-4096-part20's
@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_oracle_parity_under_the_partition(seed, committee):
    """Program against the partitioned oracle from the same population,
    256 attesters, the line at 0.20 from t=0, through three blocks
    (9000 ms): heads equal on every node (0 behind the line, 3 on the
    larger side), sent and received equal NODE FOR NODE, the beacon height
    each node has heard (the twin's reading) equal on every node, the
    masked sends equal to the oracle's `dropped` to the message, no row
    discarded (under a line that never moves every crossing row is masked
    where it is sent), and the store's law exact with the masked count.
    chain_score as the unpartitioned cases hold it: never under the
    oracle's, apart where one ms brings a member several votes."""
    Block.reset_block_ids()
    o = PartitionedDfinity(PartitionedDfinityParameters(node_count=256, attesters_per_round=committee))
    o.network().rd.set_seed(seed)
    o.init()
    o.network().run_ms(9000)
    nodes = o.network().all_nodes
    net, state = _partitioned(committee, seed)
    assert np.asarray(state.partition_x).tolist()[0] == 400
    out = net.run_ms(state, 9000)
    assert int(out.dropped) == 0 and int(out.census.fanout_overflows) == 0
    behind = np.asarray(out.x) < 400
    assert 40 <= behind.sum() <= 80 and [n.x < 400 for n in nodes] == behind.tolist()
    heads = np.asarray(net.protocol.head_height(out))
    assert (heads == [n.head.height for n in nodes]).all()
    assert set(heads[behind].tolist()) == {0} and set(heads[~behind].tolist()) == {3}
    assert np.asarray(out.msg_sent).tolist() == [n.msg_sent for n in nodes]
    assert np.asarray(out.msg_received).tolist() == [n.msg_received for n in nodes]
    heard = np.asarray(out.proto["last_beacon"])
    assert heard.tolist() == [n.last_random_beacon for n in nodes]
    assert np.percentile(heard, [10, 50, 90]).tolist() == [1, 3, 3]
    masked = int(out.census.masked_sends)
    assert masked == o.network().dropped > 0.15 * int(out.msg_sent.sum())
    assert int(out.census.discarded_rows) == 0
    in_store = int(out.msg_valid.sum()) + int(out.ovf_valid.sum())
    assert int(out.msg_sent.sum()) == int(out.msg_received.sum()) + in_store + masked
    score = np.asarray(out.proto["chain_score"])
    want = np.array([n.chain_score for n in nodes])
    assert (score >= want).all() and (score != want).sum() <= 80
    assert np.percentile(score, [10, 50]).tolist() == np.percentile(want, [10, 50]).tolist()
    assert np.percentile(score, 10) == 0  # behind the line: no head, and all but a few hold no vote


def test_fanout_equals_the_dense_form_under_the_partition():
    """The grid's `ok` (K + R reads broadcast) against the plain rows'
    (`latency_arrivals`, a read a row) with a line set: every leaf, and
    the census's two counts with them."""
    net, state = _partitioned(16, node_count=64)
    runs = [n.run_ms(s, 7000) for n, s in (dense_twin(net, state), (net, state))]
    assert _differing(*runs) == []
    dense, grid = (r.census for r in runs)
    assert int(grid.masked_sends) == int(dense.masked_sends) > 0
    assert int(grid.discarded_rows) == int(dense.discarded_rows) == 0
    assert int(dense.fanout_senders) == 0 < int(grid.fanout_senders)


def test_a_partition_of_0_is_the_unpartitioned_program_leaf_for_leaf():
    """`partition` 0 draws no line: the state `make_dfinity` returns, the
    network's store plan and a run are those of `DfinityParameters` of
    the same shape, the census with them (what a control states to put
    the sound network in the partitioned one's place)."""
    net0, state0 = _partitioned(16, node_count=64, partition=0)
    net, state = _small()
    assert _differing(state0, state, but=()) == []
    assert (np.asarray(state0.partition_x) == np.iinfo(np.int32).max).all()
    keys = [k[2:] for k in (net0.stable_cache_key(), net.stable_cache_key())]
    assert keys[0] == keys[1]  # all but the protocol's name and the parameters' repr
    out0, out = net0.run_ms(state0, 7000), net.run_ms(state, 7000)
    assert _differing(out0, out, but=()) == []
    assert int(out.census.masked_sends) == int(out.census.discarded_rows) == 0


def test_the_line_is_drawn_before_the_beacons_first_results_leave():
    """`make_dfinity` hands `init_state` the line, so the t=0 results
    that cross it are masked in the initial state already; a line drawn
    on a sound initial state finds them in flight and the delivery
    discards them, the same rows under the other count."""
    net, state = _partitioned(16, node_count=64)
    masked = int(state.census.masked_sends)
    assert masked > 0 and int(state.msg_sent.sum()) == 16 * 91
    sound_net, sound = _small()
    late = sound_net.run_ms(sound_net.partition(sound, 0.2), 200)
    early = net.run_ms(state, 200)
    assert int(late.census.discarded_rows) == masked and int(early.census.discarded_rows) == 0
    assert np.array_equal(np.asarray(late.msg_received), np.asarray(early.msg_received))


@pytest.mark.parametrize("stated", [0, 2])
def test_a_stated_population_seed_is_the_population_whatever_the_caller_seeds(stated):
    """Who is behind the line is part of the deployment: parameters that
    state `population_seed` have `init()` seed the generator itself, so a
    harness that seeds it run by run (`rd.set_seed(seed)`, then `init()`,
    as benchmark/twin.py and timed_rows.py do) builds the stated
    population every time, and `make_dfinity` the same one: its state is
    `population_seed=`'s of the factory leaf for leaf.  Not stated, the
    caller's seed draws the population as before."""
    def positions(params, seeded):
        o = PartitionedDfinity(params)
        o.network().rd.set_seed(seeded)
        o.init()
        return [(n.x, n.y, n.city_name) for n in o.network().all_nodes]

    free = PartitionedDfinityParameters(node_count=64, attesters_per_round=16)
    fixed = PartitionedDfinityParameters(node_count=64, attesters_per_round=16, population_seed=stated)
    want = positions(free, stated)
    assert positions(fixed, 77) == positions(fixed, 2147483659) == want != positions(free, 77)
    _net, state = make_dfinity(fixed, max_heights=8, latency_name=IC3, population_seed=5)  # the stated one wins
    _net, as_before = _partitioned(16, seed=stated, node_count=64)
    assert _differing(state, as_before, but=()) == []
    assert np.asarray(state.x).tolist() == [p[0] for p in want]
