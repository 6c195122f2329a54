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

Slow for unit tests (minutes on a CPU: each wrong twin is a program of
its own to compile): `python3 -m pytest benchmark/tests/test_correct.py -q`.
"""

import io
import json
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


def _rehearse(cell):
    import run

    out = io.StringIO()
    with redirect_stdout(out):
        code = run.main(["--workload", cell, "--seed", str(SEED), "--seconds", "0.5",
                         "--trace", "0", "--rehearse"])
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
        return out._replace(msg_received=out.msg_received.at[-1, 0].add(-1)), stats

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
