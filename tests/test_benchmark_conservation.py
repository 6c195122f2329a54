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
through the generic message store, and every leaf it names is one the
program places.
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
    network is on the generic message store (`net.flat` false: a message
    is counted at delivery, so the store's occupancy stands beside the
    received total), none otherwise."""
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
        if not want:
            assert "conservation" not in config["timed_rows"], workload["name"]
            assert timed_rows.named_leaves(config, _cases._state(15, 0)) == []
            continue
        assert config["timed_rows"]["conservation"]["received_plus"] == want, workload["name"]
        for path, leaf, per_node in timed_rows.named_leaves(config, state):
            if per_node:  # placed by the parameters alone, nothing counted yet
                assert leaf.shape == state.down.shape and int(leaf.sum()) == 0
            else:  # the store's planes, whatever the t=1 wave put there
                assert path in ("msg_valid", "ovf_valid") and leaf.dtype == bool
    assert seen == {(False, False), (True, False), (False, True)}
