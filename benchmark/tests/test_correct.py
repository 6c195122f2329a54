"""`correct` has been shown to fail.

1. The controls of the twin: the twin with a wrong parameter in the
   program's place (`twin.controls` of the configuration file) comes out
   as not correct, where the sound twin of the same seed passes.  The
   twin is its own size here (256 nodes, 4 rows): the size the chip runs.
2. The control of the timed rows: the reference with a wrong parameter
   (`timed_rows.controls[0]`, the one the limit was set against), put in
   the program's place, comes out as not correct where the program's own
   rows pass.  At the twin's node count here; `calibrate_timed_rows.py`
   reads the same at the cell's own.
3. The rest of a run, driven past the look for a chip (`--rehearse`, 64
   nodes), with the timed path broken underneath: a step that returns
   its state unchanged, one that leaves a row of the batch behind, one
   whose scatter into the receivers' counters loses an update.  The
   rehearsal of the sound path passes its checks; the broken ones come
   out false.
4. The conservation law with nodes down (ISSUE 29): a 64-node Handel
   with 16 nodes down, built by the harness's own `build` from the draft
   configuration under tests/data (not in BENCHMARK.json).  With no leaf
   named its row fails by what went to the down nodes; with the leaf
   named it passes at exactly 0; one lost update fails by 1; a leaf that
   is not there stops the run before its first chunk.  The program has no
   count of undelivered sends yet, so the test makes the leaf itself with
   no edit of the program: `make_handel_counting_undelivered` wraps the
   instance's `latency_arrivals` and `_send_stacked` and adds
   `mask & ~ok`, by sender, to a key it puts into `state.proto`.

Slow for unit tests (minutes on a CPU: each wrong twin is a program of
its own to compile): `python3 -m pytest benchmark/tests/test_correct.py -q`.
"""

import copy
import io
import json
import os
from contextlib import redirect_stdout

import pytest

import cells
import timed_rows
import twin

SEED = 2**31 + 77


def _config(name):
    return cells.load_cell(name).config


@pytest.mark.parametrize("cell", ["handel-4096.single-r1", "gsf-2048.single-r1"])
def test_controls_come_out_not_correct(cell):
    config = _config(cell)
    sound = twin.check(config, SEED)
    assert sound["ok"], sound
    for control in config["twin"]["controls"]:
        wrong = twin.check(config, SEED, overrides=control)
        assert not wrong["ok"], (control, wrong)


@pytest.mark.parametrize("cell,t_ms", [("handel-4096.sweep-r8", 100), ("gsf-2048.single-r1", 80)])
def test_the_timed_rows_control_comes_out_not_correct(cell, t_ms):
    import jax
    from wittgenstein_tpu.engine import replicate_state
    from wittgenstein_tpu.parallel.replica_shard import sharded_run_stats

    config = _config(cell)
    small = config["twin"]["params"]
    seeds = twin.row_seeds(SEED, 2)
    net, state = cells.resolve(config["factory"])(
        cells.build_params(config, config["params_class"], small), **config["factory_kwargs"])
    rows = replicate_state(state, len(seeds), seeds=seeds)
    for _ in range(t_ms // 10):  # the window's own call and feed
        rows, _stats = sharded_run_stats(net, rows, 10)
    counts = timed_rows.program_counts(jax.block_until_ready(rows))
    sound = timed_rows.check(config, seeds[0], counts, small)
    assert sound["ok"] and sound["rows_time_ms"] == t_ms, sound
    control = config["timed_rows"]["controls"][0]
    (wrong,) = timed_rows.reference_sent(config, seeds[0], [t_ms], {**small, **control})
    total = [round(wrong * 256)] * len(seeds)
    in_its_place = {**counts, "sent_mean": [wrong] * len(seeds), "sent_total": total,
                    "received_total": total}
    result = timed_rows.check(config, seeds[0], in_its_place, small)
    assert not result["ok"] and result["sent_rel_gap_worst"] > result["sent_rel_gap_limit"], result


def _rehearse(cell, trace=0):
    import run

    out = io.StringIO()
    with redirect_stdout(out):
        code = run.main(["--workload", cell, "--seed", str(SEED), "--seconds", "0.5",
                         "--trace", str(trace), "--rehearse"])
    lines = [json.loads(line) for line in out.getvalue().splitlines() if line.startswith("{")]
    notes = {line["note"]: line for line in lines if "note" in line}
    assert code == 4 and lines[-1]["correct"] is False  # a rehearsal never reports correct
    return notes, lines[-1]


def _unchanged(real):
    def step(net, states, sim_ms):
        _out, stats = real(net, states, sim_ms)
        return states, stats

    return step


def _a_row_left_behind(real):
    import jax

    def step(net, states, sim_ms):
        out, stats = real(net, states, sim_ms)
        stale = jax.tree_util.tree_map(lambda new, old: new.at[-1].set(old[-1]), out, states)
        return stale, stats

    return step


def _a_lost_update(real):
    def step(net, states, sim_ms):
        out, stats = real(net, states, sim_ms)
        node = (~out.down[-1]).argmax()  # a live one: the law leaves the down nodes' counts out
        return out._replace(msg_received=out.msg_received.at[-1, node].add(-1)), stats

    return step


@pytest.mark.parametrize("cell", ["handel-4096.sweep-r8"])
def test_a_broken_timed_path_comes_out_not_correct(cell, monkeypatch):
    from wittgenstein_tpu.parallel import replica_shard

    notes, _ = _rehearse(cell)
    assert notes["rehearsal"]["passed"], notes  # the sound path passes every check
    real = replica_shard.sharded_run_stats
    for breaker in (_unchanged, _a_row_left_behind, _a_lost_update):
        monkeypatch.setattr(replica_shard, "sharded_run_stats", breaker(real))
        notes, result = _rehearse(cell)
        assert not notes["rehearsal"]["passed"], breaker.__name__
        assert not notes["timed-rows"]["ok"], breaker.__name__
        if breaker is _a_lost_update:  # only the conservation count sees this one
            assert notes["invariants"]["ok"] and max(notes["timed-rows"]["sent_minus_received"]) > 0
        else:
            assert not notes["invariants"]["ok"], breaker.__name__
        assert result["failed"] > 0
        monkeypatch.setattr(replica_shard, "sharded_run_stats", real)


def test_a_twin_that_does_not_finish_is_not_correct():
    config = _config("gsf-2048.single-r1")
    config = {**config, "twin": {**config["twin"], "horizon_ms": 100}}
    result = twin.check(config, SEED)
    assert not result["program_all_done"] and not result["ok"]


def make_handel_counting_undelivered(params, **kwargs):
    """`make_handel`, and in `state.proto["undelivered"]` the count the
    program owes a deployment with nodes down: for every sender, its
    masked sends that were not ok (the receiver down, or past the
    discard time), which tick `msg_sent` and never `msg_received`.  Both
    wrappers are instance attributes, as analysis/rng_audit.py's."""
    import jax.numpy as jnp
    from wittgenstein_tpu.protocols.handel_batched import make_handel

    net, state = make_handel(params, **kwargs)
    arrivals, send, seen = net.latency_arrivals, net.protocol._send_stacked, []

    def counting_arrivals(state, mask, from_idx, to_idx, send_time, mtype):
        state, ok, arrival = arrivals(state, mask, from_idx, to_idx, send_time, mtype)
        seen.append((from_idx, (mask & ~ok).astype(jnp.int32)))
        return state, ok, arrival

    def counting_send(net, state, *args, **kw):
        state = send(net, state, *args, **kw)  # asks for its arrivals once, in the same trace
        from_idx, not_ok = seen.pop()
        assert not seen
        count = state.proto["undelivered"].at[from_idx].add(not_ok)
        return state._replace(proto=dict(state.proto, undelivered=count))

    net.latency_arrivals = counting_arrivals
    net.protocol._send_stacked = counting_send
    proto = dict(state.proto, undelivered=jnp.zeros(params.node_count, jnp.int32))
    return net, state._replace(proto=proto)


def _draft_cell(counting: bool):
    """The draft deployment with nodes down, as a cell: the files under
    tests/data, found as `load_cell` finds a cell's."""
    data = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
    with open(os.path.join(data, "handel-4096-byz20.json")) as f:
        config = json.load(f)
    with open(os.path.join(data, "single-r1-c20.json")) as f:
        traffic = json.load(f)
    if counting:
        config["factory"] = "test_correct.make_handel_counting_undelivered"
    real = cells.load_cell("handel-4096.single-r1")
    return cells.Cell("handel-4096-byz20.single-r1-c20", 1, "handel-4096-byz20", config,
                      "single-r1-c20", traffic, real.end_to_end, real.layer_metrics)


def test_the_law_with_nodes_down_on_the_programs_own_rows():
    import jax
    import numpy as np
    import run
    from wittgenstein_tpu.parallel.replica_shard import sharded_run_stats

    cell = _draft_cell(counting=True)
    named = cell.config
    unnamed = copy.deepcopy(named)
    del unnamed["timed_rows"]["conservation"]
    assert "nodes_down" in run.rehearsal_params(named)  # 819 of 64 would not build
    net, fresh, _replicas = run.build(cell, SEED, rehearse=True)
    rows = fresh(0)
    assert int(np.asarray(rows.down).sum()) == 16 and rows.down.shape == (1, 64)
    for _ in range(5):  # the window's own call and feed, five 20-ms chunks
        rows, _stats = sharded_run_stats(net, rows, 20)
    rows = jax.block_until_ready(rows)

    def lost(state, config):
        counts = timed_rows.program_counts(state, config)
        result = timed_rows.compare(counts, counts["sent_mean"][0], 0.5)
        assert result["ok"] == (result["sent_minus_received"] == [0])
        return result

    owed = lost(rows, unnamed)["sent_minus_received"][0]
    assert owed > 64  # the fault ISSUE 29 starts from: every live node keeps sending to the down
    sound = lost(rows, named)
    assert sound["sent_minus_received"] == [0] and sound["received_plus"] == {"proto.undelivered": [owed]}
    broken, _ = _a_lost_update(lambda net, states, sim_ms: (states, None))(net, rows, 0)
    assert lost(broken, named)["sent_minus_received"] == [1]


def test_a_rehearsal_with_nodes_down_runs_to_its_end(monkeypatch):
    cell = _draft_cell(counting=True)
    monkeypatch.setattr(cells, "load_cell", lambda workload: cell)
    notes, result = _rehearse("handel-4096-byz20.single-r1-c20", trace=1)
    assert notes["program"]["nodes"] == 64 and notes["timed-rows"]["sent_minus_received"] == [0]
    assert notes["timed-rows"]["received_plus"]["proto.undelivered"][0] > 0
    assert notes["invariants"]["ok"] and notes["determinism"]["ok"]
    assert "lower_s" in result["metrics"]


def test_a_leaf_that_is_not_there_stops_the_run_before_its_first_chunk(monkeypatch):
    from wittgenstein_tpu.parallel import replica_shard

    def no_chunk(*args, **kw):
        raise AssertionError("a chunk ran")

    monkeypatch.setattr(replica_shard, "sharded_run_stats", no_chunk)
    cell = _draft_cell(counting=False)
    monkeypatch.setattr(cells, "load_cell", lambda workload: cell)
    with pytest.raises(cells.BenchmarkFileError, match="no 'undelivered'"):
        _rehearse("handel-4096-byz20.single-r1-c20")
