"""The conservation law of the benchmark's timed rows, guarded by the
tier-1 run: the numpy cases of benchmark/tests/test_conservation.py
(no program runs, a second in all), loaded from that file so that there
is one copy of them.

One of its cases states what was true when it was written, that no
configuration names a leaf; `handel-4096-byz20` (PR 31) names the
program's `proto.sent_not_ok` and `sanfermin-4096` (PR 37) the message
store's occupancy.  That case is replaced here by the rule it stood for:
a configuration names a per-node leaf exactly where its network is built
with nodes down and the store's planes exactly where its protocol sends
through the generic message store's time wheel, and every leaf it names
is one the program places.

A FLAT network (`casper-1024`, PR 39: the overflow lane is the whole
store) names no leaf, and not because its store is empty: the lane also
holds the protocol's periodic tasks, one size-0 self-message a scheduled
node, re-armed for ever and counted neither as sent nor as received, so
`sent == received + sum(ovf_valid)` is false at every time.  The law
closes where the rows are read, at slot boundaries: there nothing but
the tasks is in the lane and `sent == received` exactly, which the last
case here shows on a built rehearsal network.  Inside a slot it would take
a `received_minus` key or a count of sized messages in the store
(PERF.md section 7, for a `benchmark` issue).
"""

import importlib.util
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "benchmark")
if BENCH_DIR not in sys.path:
    sys.path.append(BENCH_DIR)  # `cells`, `timed_rows`: as benchmark/tests/conftest.py

_spec = importlib.util.spec_from_file_location(
    "benchmark_tests_test_conservation",
    os.path.join(BENCH_DIR, "tests", "test_conservation.py"),
)
_cases = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_cases)

STALE = "test_the_configurations_there_are_name_no_leaf"
globals().update(
    {name: fn for name, fn in vars(_cases).items() if name.startswith("test_") and name != STALE}
)


def test_a_configuration_names_a_leaf_exactly_where_nodes_are_down():
    """`per_node` leaves exactly where nodes are down (the program's count
    of sends that were not ok), `whole` leaves exactly where the factory's
    network is on the generic message store's wheel (`net.flat` false: a
    message is counted at delivery, so the store's occupancy stands beside
    the received total), none otherwise: the channel protocols count at
    the send, and a flat network's lane holds task rows beside the
    messages (the module's docstring; the next case).  A configuration
    whose parameters state a partition names, beside the occupancy, the
    census's two sums of what the line masks and discards (PR 46)."""
    import cells
    import timed_rows

    seen, built = set(), set()
    for workload in cells.load_benchmark()["workloads"]:
        if workload["config"] in built:  # one build a configuration, not a cell
            continue
        built.add(workload["config"])
        config = cells.load_cell(workload["name"]).config
        nodes_down = config["params"].get("nodes_down", 0)
        params = cells.build_params(
            config, config["params_class"], config.get("rehearsal", {}).get("params", {"node_count": 64})
        )
        net, state = cells.resolve(config["factory"])(params, **config["factory_kwargs"])
        seen.add((nodes_down > 0, not net.flat))
        want = {}
        if nodes_down:
            want["per_node"] = ["proto.sent_not_ok"]
        if not net.flat:
            want["whole"] = ["msg_valid", "ovf_valid"]
            if config["params"].get("partition"):
                want["whole"] += ["census.masked_sends", "census.discarded_rows"]
        if not want:
            assert "conservation" not in config["timed_rows"], workload["name"]
            assert timed_rows.named_leaves(config, _cases._state(15, 0)) == []
            continue
        assert config["timed_rows"]["conservation"]["received_plus"] == want, workload["name"]
        for path, leaf, per_node in timed_rows.named_leaves(config, state):
            if per_node:  # placed by the parameters alone, nothing counted yet
                assert leaf.shape == state.down.shape and int(leaf.sum()) == 0
            elif path.startswith("census."):  # one count a row; the t=0 wave's masked rows are in already
                assert leaf.shape == () and (path == "census.masked_sends") == (int(leaf) > 0)
            else:  # the store's planes, whatever the t=1 wave put there
                assert path in ("msg_valid", "ovf_valid") and leaf.dtype == bool
    assert seen == {(False, False), (True, False), (False, True)}


def test_a_flat_networks_lane_holds_its_tasks_while_sent_equals_received():
    """`casper-1024` at a rehearsal's 64 validators, as `run.py` builds it:
    the network is flat, the configuration names no leaf, and at t=0 and at
    the first two slot boundaries the lane holds the scheduled nodes' tasks
    (2 producers and 64 attesters; not the observer) while every message
    sent has been counted for its receiver: 0 == 0 after the empty slot 0,
    1139 == 1139 after slot 1 (one block and one committee of 16 to 67
    nodes each).  So `sum(ovf_valid)` can stand on neither side of the law."""
    import numpy as np

    import cells
    import timed_rows
    from wittgenstein_tpu.engine import replicate_state
    from wittgenstein_tpu.parallel.replica_shard import sharded_run_stats

    config = cells.load_cell("casper-1024.single-r1-s8000").config
    assert "conservation" not in config["timed_rows"]
    params = cells.build_params(config, config["params_class"], {"node_count": 64})
    net, state = cells.resolve(config["factory"])(params, **config["factory_kwargs"])
    assert net.flat and config["factory_kwargs"]["capacity"] is None
    assert net.capacity == 16384  # the factory's own rule at 64 validators (524,288 at 1024)
    states = replicate_state(state, 1, seeds=[7001])
    scheduled, totals = 2 + 64, []
    for _slot in range(3):
        counts = timed_rows.program_counts(states, config)
        assert counts["received_plus"] == {}
        assert counts["sent_total"] == counts["received_total"]
        assert int(np.asarray(states.ovf_valid).sum()) == scheduled
        assert int(np.asarray(states.dropped).max()) == 0
        totals.append(counts["sent_total"][0])
        states, _stats = sharded_run_stats(net, states, 8000)
    assert totals == [0, 0, (1 + 16) * 67]


def test_a_wheel_store_holds_far_future_messages_at_a_chunks_end(no_compile_cache):  # Dfinity's programs stay off the cache: tests/test_dfinity_batched.py
    """`dfinity-4096` at its rehearsal's 64 attesters in committees of 16,
    as `run.py` builds it, through the traffic file's three 6000-ms chunks:
    every chunk ends in the quiet part of a beacon cycle, with the wheel
    empty and the NEXT height's exchange in the lane (16 x 16 real
    messages, sent up to two rounds ahead, counted as sent when they were
    scheduled): `sent == received + sum(msg_valid) + sum(ovf_valid)` with
    256 rows in flight each time, and `sent == received` would be off by
    exactly those (PR 43: the first cell whose far-future rows are messages
    and not tasks)."""
    import numpy as np

    import cells
    import timed_rows
    from wittgenstein_tpu.engine import replicate_state
    from wittgenstein_tpu.parallel.replica_shard import sharded_run_stats

    cell = cells.load_cell("dfinity-4096.single-r1-c6000-h18000")
    config, chunk_ms = cell.config, cell.traffic["chunk_ms"]
    assert config["timed_rows"]["conservation"]["received_plus"] == {"whole": ["msg_valid", "ovf_valid"]}
    assert (chunk_ms, cell.traffic["horizon_ms"]) == (6000, 18000)
    params = cells.build_params(config, config["params_class"], config["rehearsal"]["params"])
    net, state = cells.resolve(config["factory"])(params, **config["factory_kwargs"])
    assert not net.flat and config["factory_kwargs"]["capacity"] is None
    states = replicate_state(state, 1, seeds=[7001])
    heads = []
    for _chunk in range(3):
        states, _stats = sharded_run_stats(net, states, chunk_ms)
        counts = timed_rows.program_counts(states, config)
        assert counts["received_plus"] == {"msg_valid": [0], "ovf_valid": [16 * 16]}
        assert counts["sent_total"][0] == counts["received_total"][0] + 16 * 16
        assert timed_rows.compare(counts, counts["sent_mean"][0], 0.0)["sent_minus_received"] == [0]
        assert int(np.asarray(states.dropped).max()) == 0
        heads.append(np.unique(np.asarray(states.proto["chain_score"]) // (16 + 1)).tolist())
    assert heads == [[1], [3], [5]]  # one block, then two a cycle


def test_a_partitioned_store_closes_its_law_with_the_masked_sends(no_compile_cache):  # Dfinity's programs stay off the cache: tests/test_dfinity_batched.py
    """`dfinity-4096-part20` at its rehearsal's 64 attesters in committees
    of 16 (18 of 91 nodes behind the line at 0.20, from t=0), as `run.py`
    builds it, through the traffic file's three 6000-ms chunks: the
    configuration names the census's two sums beside the store's
    occupancy (`leaf_at` walks `census` as any field: one value a row),
    `sent == received + msg_valid + ovf_valid + census.masked_sends +
    census.discarded_rows` exactly at every chunk's end, nothing is
    discarded, and without the masked count the law would be off by a
    fifth of the sends (PR 46: the first cell with a fault on the store
    path).  The larger side notarises as the sound network does; behind
    the line no head moves.  And the timed rows' own check of those rows:
    the population is a parameter, so the reference the harness seeds run
    by run is the program's own on every seed."""
    import numpy as np

    import cells
    import timed_rows
    from wittgenstein_tpu.engine import replicate_state
    from wittgenstein_tpu.parallel.replica_shard import sharded_run_stats

    cell = cells.load_cell("dfinity-4096-part20.single-r1-c6000-h18000")
    config, chunk_ms = cell.config, cell.traffic["chunk_ms"]
    assert config["timed_rows"]["conservation"]["received_plus"] == {
        "whole": ["msg_valid", "ovf_valid", "census.masked_sends", "census.discarded_rows"]}
    assert (cell.traffic_name, chunk_ms, cell.traffic["horizon_ms"]) == (
        "single-r1-c6000-h18000", 6000, 18000)
    assert config["params"]["partition"] == 0.2
    sound = cells.load_cell("dfinity-4096.single-r1-c6000-h18000").config
    assert {**sound["params"], "partition": 0.2, "population_seed": 0} == config["params"]
    assert sound["factory_kwargs"] == config["factory_kwargs"] and sound["expect"] == config["expect"]
    params = cells.build_params(config, config["params_class"], config["rehearsal"]["params"])
    net, state = cells.resolve(config["factory"])(params, **config["factory_kwargs"])
    assert np.asarray(state.partition_x).tolist()[0] == 400
    behind = np.asarray(state.x) < 400
    assert behind.sum() == 18
    states = replicate_state(state, 1, seeds=[7001])
    timed_rows.named_leaves(config, states)  # every path reaches a leaf that can be counted
    heads, masked = [], []
    for _chunk in range(3):
        states, _stats = sharded_run_stats(net, states, chunk_ms)
        counts = timed_rows.program_counts(states, config)
        plus = counts["received_plus"]
        assert plus["msg_valid"] == [0] and plus["census.discarded_rows"] == [0]
        assert 0 < plus["ovf_valid"][0] <= 16 * 16  # the exchange in flight, less its masked rows
        assert plus["census.masked_sends"][0] > 0.15 * counts["sent_total"][0]
        assert counts["sent_total"][0] == counts["received_total"][0] + sum(v[0] for v in plus.values())
        assert timed_rows.compare(counts, counts["sent_mean"][0], 0.0)["sent_minus_received"] == [0]
        assert int(np.asarray(states.dropped).max()) == 0
        head = np.asarray(states.proto["chain_score"])[0] // (16 + 1)
        heads.append((np.unique(head[behind]).tolist(), np.unique(head[~behind]).tolist()))
        masked.append(plus["census.masked_sends"][0])
    assert heads == [([0], [1]), ([0], [3]), ([0], [5])]
    assert masked == sorted(masked) and masked[0] < masked[2]
    # who is behind the line is the parameters' (`population_seed`): the rows' gap is 0 to
    # the message on every seed, also on the one whose moving reference population (six of
    # ten producers behind the line, a gap of 0.512) had the driver refuse the PR
    for seed in (7001, 974334658):
        rows = timed_rows.check(config, seed, counts, config["rehearsal"]["params"])
        assert rows["sent_rel_gap_worst"] == 0.0 and rows["sent_rel_gap_limit"] == 0.01 and rows["ok"]
    other = timed_rows.check(config, 7001, counts, {**config["rehearsal"]["params"], "population_seed": 1})
    assert other["sent_rel_gap_worst"] > 0.01 and not other["ok"]  # another population: another deployment
