"""The conservation law of the timed rows, as the configuration states it.

`sent total == received total + the sums of the leaves that
`timed_rows.conservation.received_plus` names`, limit 0, on states made
by hand in numpy: no program runs here, so the whole file takes a
second.  Each sound case has its control beside it: the same state with
one update lost fails by exactly 1.  The same law on the program's own
rows, with nodes down, is in test_correct.py.
"""

import collections

import numpy as np
import pytest

import cells
import timed_rows

State = collections.namedtuple(
    "State", "time down msg_sent msg_received msg_valid ovf_valid proto")

ROWS, NODES, DOWN, SENDS = 2, 32, 8, 4000


def _state(seed: int, nodes_down: int, in_flight: int = 0) -> State:
    """ROWS rows of NODES nodes after SENDS random sends each, counted as
    the program counts them: the sender always (a down sender too, whose
    counts the law leaves out); the receiver of a send between two live
    nodes, but for the last `in_flight` of them, which sit in the store
    (`msg_valid`, its spill in `ovf_valid`); any other send of a live
    node in `proto["undelivered"]`, by sender."""
    rng = np.random.default_rng(seed)
    down = np.zeros((ROWS, NODES), bool)
    sent, received, undelivered = (np.zeros((ROWS, NODES), np.int32) for _ in range(3))
    msg_valid, ovf_valid = np.zeros((ROWS, 4, 8), bool), np.zeros((ROWS, NODES), bool)
    for r in range(ROWS):
        down[r, rng.choice(NODES, nodes_down, replace=False)] = True
        ok_sends = []
        for frm, to in rng.integers(0, NODES, (SENDS, 2)):
            sent[r, frm] += 1
            if down[r, frm] or down[r, to]:
                undelivered[r, frm] += 1
            else:
                ok_sends.append(to)
        for to in ok_sends[: len(ok_sends) - in_flight]:
            received[r, to] += 1
        msg_valid[r].reshape(-1)[: min(in_flight, 32)] = True
        ovf_valid[r, : max(0, in_flight - 32)] = True
    return State(np.full(ROWS, 40, np.int32), down, sent, received, msg_valid, ovf_valid,
                 {"undelivered": undelivered, "scale": np.ones((ROWS, NODES), np.float32)})


def _config(**received_plus) -> dict:
    return {"timed_rows": {"conservation": {"received_plus": received_plus}}}


def _lost(state: State, config: dict) -> list:
    counts = timed_rows.program_counts(state, config)
    mean = float(np.mean(counts["sent_mean"]))
    result = timed_rows.compare(counts, mean, 0.5)
    assert result["sent_minus_received_limit"] == 0
    assert result["ok"] == (max(result["sent_minus_received"]) == 0)
    return result["sent_minus_received"]


def _a_lost_update(state: State) -> State:
    """The fault the count is there for: one receiver's counter of the
    last row misses one update."""
    node = int(np.argmax(~state.down[-1] & (state.msg_received[-1] > 0)))
    received = state.msg_received.copy()
    received[-1, node] -= 1
    return state._replace(msg_received=received)


UNDELIVERED = _config(per_node=["proto.undelivered"])
STORE = _config(whole=["msg_valid", "ovf_valid"])

CASES = {
    # name: (nodes down, in flight, configuration, sound?)
    "no-node-down.no-leaf": (0, 0, {}, True),
    "no-node-down.leaf-of-zeros": (0, 0, UNDELIVERED, True),
    "nodes-down.no-leaf": (DOWN, 0, {}, False),  # the fault ISSUE 29 starts from
    "nodes-down.undelivered-named": (DOWN, 0, UNDELIVERED, True),
    "store.no-leaf": (0, 37, {}, False),  # counted at delivery: in flight reads as lost
    "store.occupancy-named": (0, 37, STORE, True),
    "store.half-named": (0, 37, _config(whole=["msg_valid"]), False),
    "nodes-down.store.both-named": (DOWN, 37, _config(per_node=["proto.undelivered"],
                                                      whole=["msg_valid", "ovf_valid"]), True),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_law_is_exact(case):
    nodes_down, in_flight, config, sound = CASES[case]
    state = _state(11, nodes_down, in_flight)
    lost = _lost(state, config)
    if sound:
        assert lost == [0] * ROWS
        assert _lost(_a_lost_update(state), config) == [0] * (ROWS - 1) + [1]
    else:
        assert min(lost) > 0


def test_what_a_row_with_nodes_down_misses_is_what_went_to_them():
    state = _state(12, DOWN)
    live = ~state.down
    owed = np.where(live, state.proto["undelivered"], 0).sum(-1).tolist()
    assert _lost(state, {}) == owed and min(owed) > SENDS * DOWN / NODES / 2
    counts = timed_rows.program_counts(state, UNDELIVERED)
    assert counts["received_plus"] == {"proto.undelivered": owed}
    # what a down node sent is in neither side: msg_sent and the leaf are masked alike
    assert (np.where(~live, state.msg_sent, 0).sum() > 0
            and np.where(~live, state.proto["undelivered"], 0).sum() > 0)


def test_the_named_sums_are_printed_beside_the_difference():
    counts = timed_rows.program_counts(_state(13, DOWN, 37), CASES["nodes-down.store.both-named"][2])
    result = timed_rows.compare(counts, counts["sent_mean"][0], 0.5)
    assert list(result["received_plus"]) == ["proto.undelivered", "msg_valid", "ovf_valid"]
    assert result["received_plus"]["msg_valid"] == [32] * ROWS
    assert result["received_plus"]["ovf_valid"] == [5] * ROWS  # a boolean leaf counts its trues
    for row in range(ROWS):
        plus = sum(leaf[row] for leaf in result["received_plus"].values())
        assert result["sent_total"][row] == result["received_total"][row] + plus
    # with no leaf named the line is what it was before the law could name any
    plain = timed_rows.compare(timed_rows.program_counts(_state(13, 0)), 1.0, 0.5)
    assert not {"received_plus", "sent_total", "received_total"} & set(plain)


@pytest.mark.parametrize("received_plus,why", [
    ({"per_node": ["proto.undelivered_sends"]}, "no 'undelivered_sends'"),
    ({"per_node": ["undelivered"]}, "no 'undelivered'"),
    ({"whole": ["proto.undelivered.sum"]}, "no 'sum'"),
    ({"per_node": ["msg_valid"]}, "shape"),  # not of down's shape: no node axis to mask
    ({"whole": ["proto.scale"]}, "whole numbers"),
    ({"whole": ["proto"]}, "whole numbers"),
    ({"by_receiver": ["proto.undelivered"]}, "per_node, whole"),
])
def test_a_leaf_that_cannot_be_counted_is_an_error_and_never_a_zero(received_plus, why):
    state = _state(14, DOWN)
    with pytest.raises(cells.BenchmarkFileError, match=why):
        timed_rows.named_leaves(_config(**received_plus), state)
    with pytest.raises(cells.BenchmarkFileError, match=why):
        timed_rows.program_counts(state, _config(**received_plus))


def test_the_configurations_there_are_name_no_leaf():
    for workload in cells.load_benchmark()["workloads"]:
        config = cells.load_cell(workload["name"]).config
        assert "conservation" not in config["timed_rows"]
        assert timed_rows.named_leaves(config, _state(15, 0)) == []
