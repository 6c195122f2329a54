"""Each trace reducer on a small trace recorded on the CPU
(data/cpu_trace.xplane.pb: two 'chunks' of a jitted loop of ten
sort + scatter + elementwise steps under the benchmark's span names).
A CPU trace has no device plane, so the ops are read from the host
events (`allow_host_ops`); the numbers test the arithmetic and are not
device numbers."""

import os

import pytest

import xplane

TRACE = os.path.join(os.path.dirname(__file__), "data", "cpu_trace.xplane.pb")
TICKS = 20  # two chunks of ten loop steps


@pytest.fixture(scope="module")
def trace():
    return xplane.read_trace(TRACE, allow_host_ops=True)


def test_a_cpu_trace_is_refused_as_a_device_trace():
    with pytest.raises(xplane.TraceError):
        xplane.read_trace(TRACE)


def test_spans_and_window(trace):
    names = [s[0] for s in trace.spans]
    assert names == ["dispatch", "block", "readback"] * 2
    assert trace.window == (trace.spans[0][1], max(s[2] for s in trace.spans))
    assert trace.window_s > 0


def test_enclosing_ops_are_not_leaves(trace):
    ops = [o for plane in trace.ops.values() for o in plane]
    whiles = [o for o in ops if o.name.startswith("while")]
    sorts = [o for o in ops if o.name.startswith("sort")]
    assert len(whiles) == 2 and len(sorts) >= 20
    assert all(not w.leaf and w.self_ns < w.end - w.start for w in whiles)
    assert all(s.leaf and s.self_ns == s.end - s.start for s in sorts)
    # a while's self time is what its body's ops leave of it
    for w in whiles:
        inside = sum(o.end - o.start for o in ops if o is not w and w.start <= o.start and o.end <= w.end)
        assert w.self_ns == (w.end - w.start) - inside


def test_busy_is_the_union_of_leaf_ops(trace):
    busy = xplane.busy_s(trace)
    leaves = sum(o.end - o.start for p in trace.ops.values() for o in p if o.leaf)
    assert 0 < busy <= trace.window_s
    assert busy <= leaves / 1e9 + 1e-12  # a union never exceeds the sum


def test_reducers(trace):
    def metric(**kw):
        return {"name": "m", **kw}

    busy_ms = xplane.reduce_device_trace(metric(per="per_tick"), trace, TICKS)
    assert busy_ms == pytest.approx(1e3 * xplane.busy_s(trace) / TICKS)
    sort_ms = xplane.reduce_device_trace(metric(per="per_tick", regex="^sort"), trace, TICKS)
    share = xplane.reduce_device_trace(metric(per="share", regex="^sort"), trace, TICKS)
    assert 0 < sort_ms < busy_ms
    assert share == pytest.approx(100 * sort_ms / busy_ms)
    both = xplane.reduce_device_trace(metric(per="share", regex="sort|scatter"), trace, TICKS)
    assert share < both < 100
    idle = xplane.reduce_device_trace(metric(per="idle_share"), trace, TICKS)
    assert idle == pytest.approx(100 * (1 - xplane.busy_s(trace) / trace.window_s))
    # an enclosing op that matches counts with its whole body, once
    loop = xplane.reduce_device_trace(metric(per="share", regex="^while|^sort"), trace, TICKS)
    assert loop == pytest.approx(100 * xplane.matched_s(trace, "^while") / xplane.busy_s(trace))


@pytest.mark.parametrize("per", ["per_tick", "share"])
def test_a_regex_that_matches_no_op_reads_zero_and_is_reported(trace, per):
    m = {"name": "m", "per": per, "regex": "no_such_kernel"}
    assert xplane.reduce_device_trace(m, trace, TICKS) == 0.0


def test_breakdown(trace):
    top = xplane.top_ops(trace, 10)
    assert 0 < len(top) <= 10
    assert top[0][0].startswith("sort") and top == sorted(top, key=lambda t: -t[1])
    gaps = xplane.idle_gaps(trace, 5)
    assert 0 < len(gaps) <= 5
    assert all(name in {"dispatch", "block", "readback", "between-chunks"} for name, _ in gaps)
    assert gaps == sorted(gaps, key=lambda g: -g[1])
    assert sum(s for _, s in gaps) <= trace.window_s - xplane.busy_s(trace) + 1e-9
