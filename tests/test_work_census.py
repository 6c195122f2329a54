"""The work census (PR 41): counts of what a chunk did, taken on the
device in `SimState.census` (engine/core.py `Census`), reduced to one
vector a chunk beside `stats` and folded into `run_cache_info()` a call
later (parallel/replica_shard.py `_harvest`).

What is held here: no other leaf moves by a bit (a state whose census
is `()` runs the same program without the counters), the counts equal
what can be counted from outside, and the harvest folds each chunk's
vector exactly once without waiting for a chunk.  The mechanisms' own
counts are beside them: Handel's landing rows and rounds in
tests/test_channel_rows.py, the due view's overflows in
tests/test_casper_batched.py.  Everything runs on the CPU at a small
size: counts, never a time.
"""

import gc
import inspect
import os
import sys

import jax
import numpy as np
import pytest

from wittgenstein_tpu.core.registries import registry_batched_protocols
from wittgenstein_tpu.engine import replicate_state
from wittgenstein_tpu.engine.core import CENSUS_VECTOR, CENSUS_VECTOR_PEAKS, Census
from wittgenstein_tpu.parallel import replica_shard as rs

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "benchmark")
if BENCH_DIR not in sys.path:
    sys.path.append(BENCH_DIR)  # `cells`: as benchmark/tests/conftest.py

SUMS = [f"census_{n}_total" for n in CENSUS_VECTOR if n not in CENSUS_VECTOR_PEAKS]
NEW_METRICS = {
    "steps_in_window": "census_steps_total",
    "store_rows_in_window": "census_store_rows_total",
    "view_overflow_steps_in_window": "census_view_overflow_steps_total",
    "landed_rows_in_window": "census_landed_rows_total",
    "extra_commit_rounds_in_window": "census_extra_commit_rounds_total",
    "census_s_in_window": "census_seconds_total",
    "gc_pause_s_in_window": "gc_pause_seconds_total",
    "fired_rows_in_window": "census_fired_rows_total",
    "firing_overflows_in_window": "census_firing_overflows_total",
    "fanout_senders_in_window": "census_fanout_senders_total",
    "fanout_overflows_in_window": "census_fanout_overflows_total",
    "masked_sends_in_window": "census_masked_sends_total",
    "discarded_rows_in_window": "census_discarded_rows_total",
    "level_axis_landed_rows_in_window": "census_landed_rows_total",
    "level_axis_extra_commit_rounds_in_window": "census_extra_commit_rounds_total",
}


def _delta(a: dict, b: dict) -> dict:
    return {k: b[k] - a[k] for k in SUMS}


# -- no leaf that was there moves ---------------------------------------------
# One case a protocol family: the channel send path (Handel, GSF), the
# time wheel under the lockstep loop (SanFermin) and under the jump loop
# (PingPong), the FLAT store under its due view (Casper).

FAMILIES = {"handel": 60, "gsf": 60, "sanfermin": 120, "pingpong": 200, "casper": 16000}


@pytest.mark.parametrize("name", sorted(FAMILIES))
def test_every_other_leaf_equals_a_census_free_runs(name):
    net, state = registry_batched_protocols.get(name).factory()
    ms = FAMILIES[name]
    assert isinstance(state.census, Census)
    bare = state._replace(census=())
    for run, with_census, without in (
        (lambda s: net.run_ms(s, ms), state, bare),
        (lambda s: net.run_ms_batched(s, ms), replicate_state(state, 2), replicate_state(bare, 2)),
    ):
        counted, plain = run(with_census), run(without)
        assert plain.census == ()
        a = jax.tree_util.tree_leaves_with_path(counted._replace(census=()))
        b = jax.tree_util.tree_leaves(plain)
        assert len(a) == len(b)
        for (path, x), y in zip(a, b):
            assert np.array_equal(np.asarray(x), np.asarray(y)), (name, jax.tree_util.keystr(path))
        assert int(np.asarray(counted.census.steps).max()) > 0
        assert int(np.asarray(counted.msg_received).sum()) > 0  # traffic ran


def test_a_checkpoint_from_before_the_census_resumes_with_the_templates(tmp_path):
    """What a tree without the census saved has no `census/*` leaves
    (`engine.checkpoint.EPHEMERAL_LEAVES`): it loads, every leaf it has
    bit for bit, the counts from the template."""
    from wittgenstein_tpu.engine.checkpoint import load_state, save_state

    net, state = registry_batched_protocols.get("pingpong").factory()
    ran = net.run_ms(state, 50)
    old = str(tmp_path / "before.npz")
    save_state(ran._replace(census=()), old)
    back = load_state(state, old)
    assert [int(x) for x in back.census] == [int(x) for x in state.census]
    for x, y in zip(jax.tree_util.tree_leaves(back._replace(census=())),
                    jax.tree_util.tree_leaves(ran._replace(census=()))):
        assert np.array_equal(np.asarray(x), np.asarray(y))
    new = str(tmp_path / "now.npz")
    save_state(ran, new)  # and one written now keeps its counts
    assert int(load_state(state, new).census.steps) == int(ran.census.steps) > 0


# -- the message store's counts (SanFermin at 256 nodes) ------------------------


@pytest.fixture(scope="module")
def sanfermin():
    from wittgenstein_tpu.protocols.sanfermin import SanFerminSignatureParameters
    from wittgenstein_tpu.protocols.sanfermin_batched import make_sanfermin

    return make_sanfermin(SanFerminSignatureParameters(node_count=256, threshold=256))


def test_store_rows_are_msg_heads_growth_and_the_peak_is_a_host_loops(sanfermin):
    net, state = sanfermin
    assert not net.flat
    states = replicate_state(state, 2, seeds=[3, 4])
    before = rs.run_cache_info()
    out, _ = rs.sharded_run_stats(net, states, 150)
    out, _ = rs.sharded_run_stats(net, out, 150)
    after = rs.run_cache_info()
    grown = int(np.asarray(out.msg_head).sum() - np.asarray(states.msg_head).sum())
    got = _delta(before, after)
    assert got["census_store_rows_total"] == grown > 0
    assert got["census_steps_total"] == 300  # two rows in lockstep count once
    assert got["census_landed_rows_total"] == got["census_view_overflow_steps_total"] == 0
    # both emissions state a capacity (PR 47): the rows that fired are counted in the channel
    # sends' slots, and here every one of them reaches its receiver and is stored
    assert got["census_fired_rows_total"] == grown and got["census_firing_overflows_total"] == 0
    assert 0 < int(np.asarray(out.census.firing_peak).max()) <= net.census_limits()["firing_peak"]
    # the wheel's fullest row and the lane's most live rows, against a host
    # loop over `net.step` that takes the maxima itself: after every tick's
    # step (no jump, so every tick is sampled), and of the t=0 fill
    step = jax.jit(net.step)
    for j, seed in enumerate((3, 4)):
        row = state._replace(seed=state.seed * 0 + seed)
        fill, live = int(np.asarray(row.whl_fill).max()), int(np.asarray(row.ovf_valid).sum())
        for _ in range(300):
            row = step(row)
            fill = max(fill, int(np.asarray(row.whl_fill).max()))
            live = max(live, int(np.asarray(row.ovf_valid).sum()))
        assert int(out.census.wheel_fill_peak[j]) == fill > 0
        assert int(out.census.lane_live_peak[j]) == live
    assert after["census_wheel_fill_peak"] >= int(np.asarray(out.census.wheel_fill_peak).max())
    assert int(np.asarray(out.census.wheel_fill_peak).max()) <= net.census_limits()["wheel_fill_peak"]
    assert net.census_limits() == {
        "due_rows_peak": 0, "wheel_fill_peak": net.wheel_slots,
        "lane_live_peak": net.overflow_capacity, "firing_peak": 256, "fanout_peak": 0,
        "landing_peak": 0}  # 256: `sanfermin_batched.emission_capacity` of the tick's 512 requests


# -- the harvest -----------------------------------------------------------------


def _pingpong(replicas):
    from wittgenstein_tpu.protocols.pingpong_batched import make_pingpong

    net, state = make_pingpong(32)
    return net, replicate_state(state, replicas)


@pytest.mark.parametrize("replicas", [1, 8])
def test_each_chunk_is_folded_exactly_once(replicas):
    """Five chunks through the run cache, none waited for: the steps of
    the five are in `run_cache_info()` once it is asked (it folds what is
    still on its way), a second asking adds nothing, and the counters
    pass a `clear_run_cache()` unmoved."""
    net, states = _pingpong(replicas)
    before = rs.run_cache_info()
    steps = 0  # a chunk's: the most any of its rows took (the jump loop's rows differ)
    for _ in range(5):
        out, stats = rs.sharded_run_stats(net, states, 40)
        assert set(stats) == {"done_min", "done_max", "done_avg", "msg_rcv_avg", "all_done"}
        steps += int(np.asarray(out.census.steps - states.census.steps).max())
        states = out
    after = rs.run_cache_info()
    assert not rs._PENDING_CENSUS
    got = _delta(before, after)
    assert got["census_steps_total"] == steps > 0  # the jump loop: fewer than 200
    assert got["census_store_rows_total"] > 0
    assert after["census_seconds_total"] > before["census_seconds_total"]
    again = rs.run_cache_info()
    assert _delta(after, again) == {k: 0 for k in SUMS}
    rs.clear_run_cache()
    cleared = rs.run_cache_info()
    assert {k: cleared[k] for k in after if k.startswith("census_") and k != "census_seconds_total"} == {
        k: after[k] for k in after if k.startswith("census_") and k != "census_seconds_total"}


def test_a_later_call_folds_an_earlier_chunks_vector_without_run_cache_info():
    net, states = _pingpong(2)
    base = rs.run_cache_info()  # nothing on its way after this
    out, _ = rs.sharded_run_stats(net, states, 40)
    jax.block_until_ready(out)  # the chunk is through, so its vector is there
    first = int(np.asarray(out.census.steps).max())
    rs.sharded_run_stats(net, out, 40)
    # the second call found the first chunk's vector arrived and folded it;
    # at most its own is still on its way
    assert rs._COUNTERS["census_steps_total"] - base["census_steps_total"] >= first > 0
    assert len(rs._PENDING_CENSUS) <= 1
    rs.run_cache_info()
    assert not rs._PENDING_CENSUS


def test_the_dispatch_path_reads_no_chunk_back():
    source = inspect.getsource(rs._CachedRun.__call__) + inspect.getsource(rs._harvest)
    for sync in ("block_until_ready", "device_get"):
        assert sync not in source
    assert "asarray" not in inspect.getsource(rs._CachedRun.__call__)
    assert "is_ready()" in inspect.getsource(rs._harvest)


def test_rows_without_a_census_harvest_zeros_and_the_stores_rows():
    net, states = _pingpong(2)
    before = rs.run_cache_info()
    out, _ = rs.sharded_run_stats(net, states._replace(census=()), 40)
    got = _delta(before, rs.run_cache_info())
    assert out.census == ()
    assert got["census_steps_total"] == 0
    assert got["census_store_rows_total"] == int(np.asarray(out.msg_head - states.msg_head).sum()) > 0


def test_a_peak_keeps_the_larger_and_the_limit_of_the_program_that_reached_it(monkeypatch):
    monkeypatch.setattr(rs, "_COUNTERS", dict(rs._COUNTERS, **{
        k: 0 for k in rs._COUNTERS if k.startswith("census_") and "seconds" not in k}))
    limits = {"due_rows_peak": 8, "wheel_fill_peak": 64, "lane_live_peak": 128,
              "firing_peak": 0, "fanout_peak": 0, "landing_peak": 0}
    vector = dict(zip(CENSUS_VECTOR, range(1, len(CENSUS_VECTOR) + 1)))
    rs._fold_census(np.asarray([vector[n] for n in CENSUS_VECTOR], np.int32), limits)
    rs._fold_census(np.asarray([vector[n] for n in CENSUS_VECTOR], np.int32), limits)
    info = rs._COUNTERS
    assert info["census_steps_total"] == 2 * vector["steps"]  # a sum adds
    assert info["census_wheel_fill_peak"] == vector["wheel_fill_peak"]  # a peak does not
    assert info["census_wheel_fill_peak_limit"] == 64
    # a program without the mechanism reads 0 and leaves the limit alone
    other = {"due_rows_peak": 0, "wheel_fill_peak": 0, "lane_live_peak": 16,
             "firing_peak": 0, "fanout_peak": 0, "landing_peak": 0}
    rs._fold_census(np.zeros(len(CENSUS_VECTOR), np.int32), other)
    assert info["census_wheel_fill_peak_limit"] == 64 and info["census_due_rows_peak_limit"] == 8
    # and a higher peak brings its own program's limit
    higher = np.zeros(len(CENSUS_VECTOR), np.int32)
    higher[CENSUS_VECTOR.index("lane_live_peak")] = 100
    rs._fold_census(higher, other)
    assert info["census_lane_live_peak"] == 100 and info["census_lane_live_peak_limit"] == 16


def test_the_collectors_pauses_are_counted():
    net, states = _pingpong(1)
    rs.sharded_run_stats(net, states, 10)  # installs the hook, once
    assert gc.callbacks.count(rs._gc_hook) == 1
    before = rs.run_cache_info()
    gc.collect()
    after = rs.run_cache_info()
    assert after["gc_collections_total"] == before["gc_collections_total"] + 1
    assert after["gc_pause_seconds_total"] > before["gc_pause_seconds_total"]


# -- the firing rows of the every-tick channel sends (PR 42) --------------------
# `_agg_batched.py` `_send_fired`: `fired_rows` the send's `mask.sum()`,
# `firing_overflows` the sends whose count passed `firing_capacity(rows)`,
# `firing_peak` the most rows one send fired, against that capacity.


def _channel(name):
    return registry_batched_protocols.get(name).factory()


def _channel256(name):
    """The benchmark's two channel deployments at 256 nodes."""
    import test_channel_rows

    return {"handel": test_channel_rows._handel_fused, "gsf": test_channel_rows._gsf}[name]()


@pytest.mark.parametrize("name", ["handel", "gsf"])
def test_fired_rows_are_the_masked_rows_of_a_run(name):
    """256 nodes tick by tick from t=0.  A masked row ticks its sender's
    `msg_sent` once, and only the beat sends besides: on every tick but
    the beat's (one in `period`) the growth of the summed `msg_sent` IS
    the send's `mask.sum()`, counted from outside; the beat's rows are
    not the census's.  No tick passes the shipped capacity."""
    net, state = _channel256(name)
    params = net.protocol.params
    period = getattr(params, "dissemination_period_ms", None) or params.period_duration_ms
    limit = net.census_limits()["firing_peak"]
    assert limit >= 256
    fired, sent = [], []
    for _ in range(300):
        new = net.run_ms(state, 1)
        fired.append(int(new.census.fired_rows) - int(state.census.fired_rows))
        sent.append(int(np.asarray(new.msg_sent).sum()) - int(np.asarray(state.msg_sent).sum()))
        state = new
    beats = {t % period for t, (f, s) in enumerate(zip(fired, sent)) if s != f}
    assert len(beats) == 1, beats  # the beat's phase, and no other tick
    assert all(s >= f for f, s in zip(fired, sent))
    assert sum(fired) == int(state.census.fired_rows) > 0
    assert int(state.census.firing_peak) == max(fired) <= limit
    assert int(state.census.firing_overflows) == 0


@pytest.mark.parametrize("name", ["handel", "gsf"])
def test_forced_overflows_are_counted_and_cost_nothing_else(name, monkeypatch):
    """The capacity patched to 3 rows a round: the ticks that fire more
    are the overflows the census counts, and every other leaf (the new
    `fired_rows` and `firing_peak` among them) is the shipped capacity's."""
    import test_channel_rows
    from wittgenstein_tpu.protocols import _agg_batched

    net, state = _channel(name)
    shipped = net.run_ms(state, 100)
    monkeypatch.setattr(_agg_batched, "firing_capacity", lambda rows: 3)
    net, state = _channel(name)  # a fresh program: the capacity is read at trace time
    fired = []
    for _ in range(100):
        new = net.run_ms(state, 1)
        fired.append(int(new.census.fired_rows) - int(state.census.fired_rows))
        state = new
    overflows = sum(f > 3 for f in fired)
    assert int(state.census.firing_overflows) == overflows > 0
    assert int(shipped.census.firing_overflows) == 0
    # on GSF's level axis a commit round carries `firing_capacity(rows)` too
    # (PR 49): three rows a round there, and the rounds past the first counted
    test_channel_rows._assert_same_state(
        state, shipped, name, but=("firing_overflows", "extra_commit_rounds"))
    if name == "gsf":
        assert int(state.census.extra_commit_rounds) > int(shipped.census.extra_commit_rounds) == 0
        assert 0 < int(state.census.landed_rows) <= int(state.census.fired_rows)


def test_the_run_cache_carries_the_firing_counts():
    net, state = _channel("handel")
    states = replicate_state(state, 2, seeds=[5, 6])
    before = rs.run_cache_info()
    out, _ = rs.sharded_run_stats(net, states, 40)
    out, _ = rs.sharded_run_stats(net, out, 40)
    after = rs.run_cache_info()
    got = _delta(before, after)
    assert got["census_fired_rows_total"] == int(np.asarray(out.census.fired_rows).sum()) > 0
    assert got["census_fired_rows_total"] >= got["census_landed_rows_total"] > 0
    assert got["census_firing_overflows_total"] == 0
    limit = net.census_limits()["firing_peak"]
    assert limit == net.protocol.census_limits()["firing_peak"] > 0
    assert after["census_firing_peak"] >= int(np.asarray(out.census.firing_peak).max()) > 0
    assert after["census_firing_peak_limit"] > 0
    if after["census_firing_peak"] == int(np.asarray(out.census.firing_peak).max()):
        assert after["census_firing_peak_limit"] == limit


# -- what the reach check masks and discards (PR 46) ----------------------------


def test_the_run_cache_carries_what_a_partition_masks_and_discards():
    """PingPong at 32 nodes, two rows, the line drawn at 0.5 on the
    initial state (the witness's pings are in flight under it): the
    crossing pings are discarded where they are due, and the run cache has
    that sum, and the masked one, without a sync; per row the store's law
    closes with them, and a sound row counts neither."""
    from wittgenstein_tpu.protocols.pingpong_batched import make_pingpong

    net, state = make_pingpong(32, network_latency_name="IC3NetworkLatency")
    for name in ("masked_sends", "discarded_rows"):
        assert name in Census._fields and name in CENSUS_VECTOR
        assert f"census_{name}_total" in SUMS
    states = replicate_state(state, 2, seeds=[5, 6])
    before = rs.run_cache_info()
    sound, _ = rs.sharded_run_stats(net, states, 400)
    mid = rs.run_cache_info()
    assert _delta(before, mid)["census_discarded_rows_total"] == 0
    assert _delta(before, mid)["census_masked_sends_total"] == 0
    cut, _ = rs.sharded_run_stats(net, net.partition(states, 0.5), 400)
    got = _delta(mid, rs.run_cache_info())
    discarded = np.asarray(cut.census.discarded_rows)
    assert got["census_discarded_rows_total"] == int(discarded.sum()) > 0
    assert got["census_masked_sends_total"] == int(np.asarray(cut.census.masked_sends).sum()) == 0
    for out, lost in ((sound, [0, 0]), (cut, discarded.tolist())):
        in_store = np.asarray(out.msg_valid).sum((1, 2)) + np.asarray(out.ovf_valid).sum(1)
        law = (np.asarray(out.msg_sent).sum(1) - np.asarray(out.msg_received).sum(1) - in_store
               - np.asarray(out.census.masked_sends) - np.asarray(out.census.discarded_rows))
        assert law.tolist() == [0, 0] and np.asarray(out.census.discarded_rows).tolist() == lost
    # a line from t=0 masks instead: the pings are counted where they are sent
    early = net.init_state(
        {k: np.asarray(getattr(state, k)) for k in ("x", "y", "extra_latency", "city_idx")},
        seed=0, proto=net.protocol.proto_init(32), partition=0.5)
    assert int(early.census.masked_sends) == int(discarded[0]) and int(early.census.discarded_rows) == 0


# -- the files that read the counters ------------------------------------------


def test_the_new_metric_files_name_counters_the_program_has():
    import cells

    files = {m["name"]: m for m in cells.load_layer_metrics()}
    entries = {m["name"]: m for m in cells.load_benchmark()["per_layer"]}
    info = rs.run_cache_info()
    for name, counter in NEW_METRICS.items():
        m = files[name]
        assert m["reducer"] == "counter_delta" and m["over"] == "window"
        assert m["source"] == "program_counter" and m["counter"] == counter
        assert counter in info and isinstance(info[counter], (int, float))
        assert m.get("workloads") == entries[name].get("workloads")
    handel = {"handel-4096.sweep-r8", "handel-4096.single-r1", "handel-4096-byz20.single-r1-c20"}
    assert set(files["landed_rows_in_window"]["workloads"]) == handel
    # the same two slots where GSF's accelerated calls write them (PR 49)
    for name in ("level_axis_landed_rows_in_window", "level_axis_extra_commit_rounds_in_window"):
        assert files[name]["workloads"] == ["gsf-2048.single-r1"]
    for name in ("fired_rows_in_window", "firing_overflows_in_window"):  # the channel cells
        assert set(files[name]["workloads"]) == handel | {"gsf-2048.single-r1"}
    assert files["view_overflow_steps_in_window"]["workloads"] == ["casper-1024.single-r1-s8000"]
    for name in ("fanout_senders_in_window", "fanout_overflows_in_window"):  # the fan-out's one cell
        assert files[name]["workloads"] == ["dfinity-4096.single-r1-c6000-h18000"]
    for name in ("masked_sends_in_window", "discarded_rows_in_window"):  # the partitioned cell's
        assert files[name]["workloads"] == ["dfinity-4096-part20.single-r1-c6000-h18000"]
    for name in ("steps_in_window", "census_s_in_window", "gc_pause_s_in_window"):
        assert "workloads" not in files[name]  # every cell
    # the peaks and their limits are beside the sums
    for peak in CENSUS_VECTOR_PEAKS:
        assert f"census_{peak}" in info and f"census_{peak}_limit" in info


def test_the_server_renders_the_census():
    from wittgenstein_tpu.server.server import Server

    text = Server().metrics_text()
    for family in (
        "witt_run_cache_census_steps_total", "witt_run_cache_census_store_rows_total",
        "witt_run_cache_census_view_overflow_steps_total", "witt_run_cache_census_landed_rows_total",
        "witt_run_cache_census_extra_commit_rounds_total", "witt_run_cache_census_seconds_total",
        "witt_run_cache_census_due_rows_peak", "witt_run_cache_census_due_rows_peak_limit",
        "witt_run_cache_census_wheel_fill_peak_limit", "witt_run_cache_census_lane_live_peak",
        "witt_run_cache_census_landing_peak_limit", "witt_run_cache_gc_pause_seconds_total",
        "witt_run_cache_census_fired_rows_total", "witt_run_cache_census_firing_overflows_total",
        "witt_run_cache_census_firing_peak", "witt_run_cache_census_firing_peak_limit",
        "witt_run_cache_census_fanout_senders_total", "witt_run_cache_census_fanout_overflows_total",
        "witt_run_cache_census_fanout_peak", "witt_run_cache_census_fanout_peak_limit",
        "witt_run_cache_census_masked_sends_total", "witt_run_cache_census_discarded_rows_total",
    ):
        assert family in text, family
