"""Spans and counters inside the run cache, the channel send path's
sub-scopes, and the join of a trace with the compiled program's own
instruction -> scope table (PERF.md section 3; docs/profiling.md).

Everything runs on the CPU at 64 nodes: what is checked is what the
program counts and names, never a time.  Scopes are metadata that JAX
leaves out of the persistent compilation cache's key, so the traced
fixture compiles with that cache off: a hit would serve the names of
whichever tree compiled first.
"""

import functools
import importlib.util
import json
import os
import re

import jax
import pytest

from wittgenstein_tpu.core.registries import registry_batched_protocols
from wittgenstein_tpu.engine import replicate_state
from wittgenstein_tpu.parallel import replica_shard as rs
from wittgenstein_tpu.profiling.xla_cost import (
    _HLO_NAME,
    hlo_op_scopes,
    scope_chain,
    scope_self_times,
)
from wittgenstein_tpu.protocols._agg_batched import CHANNEL_SCOPES

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHUNK_MS = 10
TIMES = {
    "compile_seconds_total", "lower_seconds_total",
    "backend_compile_seconds_total", "lookup_seconds_total",
    "execute_seconds_total",
}


def _delta(a: dict, b: dict) -> dict:
    return {k: b[k] - a[k] for k in b if b[k] != a[k]}


@pytest.fixture(scope="module")
def cold_compiles():
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def _host_events(data, prefix: str) -> list:
    """[(name, start ns, end ns)] of the host planes' events by prefix."""
    return sorted(
        (e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
        for plane in data.planes if plane.name.startswith("/host:")
        for line in plane.lines for e in line.events
        if e.name.startswith(prefix)
    )


def _host_ops(data) -> list:
    """[(name, start ns, end ns)] of the op events of a CPU trace: the
    host events that carry an `hlo_op` stat (benchmark/xplane.py)."""
    return sorted(
        (
            (e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if any(k == "hlo_op" for k, _ in e.stats)
        ),
        key=lambda op: op[1],
    )


@pytest.fixture(scope="module", params=["handel", "gsf"])
def traced(request, cold_compiles, tmp_path_factory):
    """Two chunks of a 64-node program through the run cache under a
    profiler trace, the first of which compiles: the counters before,
    between and after, the trace, and the program's scope table."""
    from jax.profiler import ProfileData

    net, state = registry_batched_protocols.get(request.param).factory()
    states = replicate_state(state, 2)
    rs.clear_run_cache()
    trace_dir = str(tmp_path_factory.mktemp(f"trace-{request.param}"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    c0 = rs.run_cache_info()
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        out, stats = rs.sharded_run_stats(net, states, CHUNK_MS)
        jax.block_until_ready((out, stats))
        c1 = rs.run_cache_info()
        out, stats = rs.sharded_run_stats(net, out, CHUNK_MS)
        jax.block_until_ready((out, stats))
        c2 = rs.run_cache_info()
    finally:
        jax.profiler.stop_trace()
    path = _xplane().find_xplane(trace_dir)
    (op_scopes,) = rs.run_cache_op_scopes(net, CHUNK_MS).values()
    (program,) = rs._RUN_CACHE[rs._entry_key(net, CHUNK_MS, None)]._programs.values()
    return {
        # `compact` is the every-tick sends' (Handel's fast path, and
        # since PR 42 GSF's accelerated calls: the firing rows to the front)
        "channel_scopes": set(CHANNEL_SCOPES.values()),
        "counters": (c0, c1, c2),
        "trace_path": path,
        "data": ProfileData.from_file(path),
        "op_scopes": op_scopes,
        "text": program.as_text(),
    }


@functools.lru_cache(maxsize=None)
def _load_script(name: str):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "scripts", f"{name}.py")
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _xplane():
    """benchmark/xplane.py, the one reader of a trace: the script puts
    benchmark/ on the path as it is loaded."""
    _load_script("scope_profile")
    import xplane

    return xplane


# -- counters ----------------------------------------------------------------

def test_compile_ticks_each_new_counter_once(traced):
    c0, c1, _ = traced["counters"]
    first = _delta(c0, c1)
    assert first["compiles"] == 1 and first["calls"] == 1
    assert first["lower_seconds_total"] > 0
    assert first["backend_compile_seconds_total"] > 0
    assert first["lookup_seconds_total"] > 0 and first["execute_seconds_total"] > 0
    # compile_seconds_total stays, as the sum of the two it is split into
    assert first["compile_seconds_total"] == pytest.approx(
        first["lower_seconds_total"] + first["backend_compile_seconds_total"],
        rel=1e-9,
    )
    assert c1["compile_seconds_total"] == pytest.approx(
        c1["lower_seconds_total"] + c1["backend_compile_seconds_total"], rel=1e-9
    )


def test_second_call_adds_to_the_dispatch_counters_only(traced):
    _, c1, c2 = traced["counters"]
    second = _delta(c1, c2)
    # beside them only what the chunk did (the work census, PR 41) and
    # what the collector did meanwhile
    assert {k for k in second if not k.startswith(("census_", "gc_"))} == {
        "hits", "calls", "lookup_seconds_total", "execute_seconds_total"
    }
    assert second["census_steps_total"] == CHUNK_MS and second["census_seconds_total"] > 0
    assert second["calls"] == 1 and second["hits"] == 1
    assert second["lookup_seconds_total"] > 0 and second["execute_seconds_total"] > 0


def test_counters_are_monotonic_across_a_cache_clear(traced):
    before = rs.run_cache_info()
    rs.clear_run_cache()
    after = rs.run_cache_info()
    assert after["size"] == 0
    assert {k: after[k] for k in TIMES | {"calls"}} == {
        k: before[k] for k in TIMES | {"calls"}
    }


def test_a_compile_that_raises_keeps_the_sum(monkeypatch):
    """`host_span` books its seconds on the way out of a body that
    raises: `compile_seconds_total` is computed from its two parts, not
    stored beside them, so a refused compile cannot part the three."""
    from wittgenstein_tpu.protocols.pingpong_batched import make_pingpong

    class Refused(Exception):
        pass

    class Lowered:
        def compile(self):
            raise Refused

    class Jit:
        def lower(self, states):
            return Lowered()

    net, state = make_pingpong(16)
    states = replicate_state(state, 3)  # a signature no other test compiles
    before = rs.run_cache_info()
    with monkeypatch.context() as m:
        m.setattr(rs._CachedRun, "_jit_for", lambda self, states: Jit())
        with pytest.raises(Refused):
            rs.sharded_run_stats(net, states, 2)
    after = rs.run_cache_info()
    assert after["compiles"] == before["compiles"] and after["calls"] == before["calls"]
    assert after["lower_seconds_total"] > before["lower_seconds_total"]
    assert after["backend_compile_seconds_total"] > before["backend_compile_seconds_total"]
    assert after["compile_seconds_total"] == (
        after["lower_seconds_total"] + after["backend_compile_seconds_total"]
    )
    assert rs.run_cache_metrics()["compile_seconds_total"] == after["compile_seconds_total"]
    # and the retry compiles: nothing of the failure was cached
    jax.block_until_ready(rs.sharded_run_stats(net, states, 2))
    assert rs.run_cache_info()["compiles"] == before["compiles"] + 1


# -- spans on the profiler's clock -------------------------------------------

def test_host_spans_are_on_a_host_plane(traced):
    spans = _host_events(traced["data"], "witt.host.")
    count = {}
    for name, _, _ in spans:
        count[name] = count.get(name, 0) + 1
    assert count["witt.host.lower"] == 1 and count["witt.host.compile"] == 1
    assert count["witt.host.enqueue"] == 2
    assert count["witt.host.lookup"] == 4  # the entry's and the program's, per call
    by_name = {name: (start, end) for name, start, end in spans}
    # lower ends before compile starts, compile before the first enqueue
    first_enqueue = min(s for n, s, _ in spans if n == "witt.host.enqueue")
    assert by_name["witt.host.lower"][1] <= by_name["witt.host.compile"][0]
    assert by_name["witt.host.compile"][1] <= first_enqueue


def test_each_enqueue_span_starts_before_the_ops_it_enqueued(traced):
    """One clock: the program's spans and the op events order as the
    work does.  No op runs before the first enqueue opens, and between
    one enqueue's start and the next there are op events."""
    enqueues = _host_events(traced["data"], "witt.host.enqueue")
    ops = _host_ops(traced["data"])
    assert len(enqueues) == 2 and ops
    assert enqueues[0][1] <= ops[0][1]
    edges = [e[1] for e in enqueues] + [ops[-1][2] + 1]
    for lo, hi in zip(edges, edges[1:]):
        assert any(lo <= start < hi for _, start, _ in ops)


# -- the program's half of the join ------------------------------------------

def test_every_channel_scope_names_an_instruction(traced):
    scopes = {row["scope"].rsplit("/", 1)[-1] for row in traced["op_scopes"].values()}
    assert traced["channel_scopes"] <= scopes
    # nested under the engine phase that sends
    chains = {row["scope"] for row in traced["op_scopes"].values()}
    assert any(re.fullmatch(r"witt\.beat/witt\.channel\.commit", c) for c in chains)
    assert any(c.endswith("witt.protocol_tick/witt.channel.commit") for c in chains)


_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*->.*\{\s*$")
_CALLED = re.compile(r"(?:calls|to_apply)=%?([\w.\-]+)")


def gathers_under(text: str, op_scopes: dict, scope: str, dtype: str = "") -> list:
    """Names of the instructions of a compiled module's text whose scope
    chain ends in `scope` and that are a gather (of `dtype` elements,
    where one is named), or call a computation (a fusion's body) that
    holds one."""
    gather = re.compile(rf"= {dtype}\S* gather\(")
    holds_gather = set()
    scoped = []  # (name, line) under the scope
    computation = None
    for line in text.splitlines():
        header = _COMPUTATION.match(line)
        if header:
            computation = header.group(1)
            continue
        m = _HLO_NAME.match(line)
        if m is None:
            continue
        if gather.search(line):
            holds_gather.add(computation)
        if op_scopes.get(m.group(1), {}).get("scope", "").endswith(scope):
            scoped.append((m.group(1), line))
    return [
        name for name, line in scoped
        if gather.search(line) or holds_gather & set(_CALLED.findall(line))
    ]


def test_the_readdress_scope_gathers_no_words(traced):
    """PR 28's structural pin: `xor_shuffle` re-addresses the packed
    uint32 planes by select stages; its word gather was 26-44% of a tick
    on the chip (PERF.md section 5) and cannot come back unnoticed.  Since
    PR 32 the scope gathers nothing at all: a level's block size is
    arithmetic on the level (2^(l-1)), no longer `lv_bs[level - 1]`."""
    readdress = CHANNEL_SCOPES["readdress"]
    rows = [n for n, r in traced["op_scopes"].items() if r["scope"].endswith(readdress)]
    assert rows  # the scope is live: there is something to look at
    assert gathers_under(traced["text"], traced["op_scopes"], readdress) == []


def test_gathers_under_finds_a_gather_and_a_fusion_that_calls_one():
    text = """
%fused_computation.1 (p0: u32[4,8], p1: s32[4,8]) -> u32[4,8] {
  %p0 = u32[4,8]{1,0} parameter(0)
  %p1 = s32[4,8]{1,0} parameter(1)
  ROOT %gather.3 = u32[4,8]{1,0} gather(%p0, %p1), offset_dims={}, metadata={op_name="jit(f)/witt.protocol_tick/witt.channel.readdress/gather"}
}

ENTRY %main (a: u32[4,8], b: s32[4,8], t: s32[5]) -> u32[4,8] {
  %a = u32[4,8]{1,0} parameter(0)
  %b = s32[4,8]{1,0} parameter(1)
  %t = s32[5]{0} parameter(2)
  %gather.9 = u32[4,8]{1,0} gather(%a, %b), offset_dims={}, metadata={op_name="jit(f)/witt.protocol_tick/gather"}
  %gather.4 = s32[4,1]{1,0} gather(%t, %b), offset_dims={1}, metadata={op_name="jit(f)/witt.protocol_tick/witt.channel.readdress/gather"}
  %fusion.7 = u32[4,8]{1,0} fusion(%a, %b), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(f)/witt.protocol_tick/witt.channel.readdress/select_n"}
  ROOT %add.1 = u32[4,8]{1,0} add(%fusion.7, %gather.9), metadata={op_name="jit(f)/witt.protocol_tick/witt.channel.readdress/add"}
}
"""
    readdress = CHANNEL_SCOPES["readdress"]
    table = hlo_op_scopes(text)
    # not gather.9: another scope
    assert gathers_under(text, table, readdress) == ["gather.3", "gather.4", "fusion.7"]
    assert gathers_under(text, table, readdress, "u32") == ["gather.3", "fusion.7"]


def test_op_scopes_keep_each_program_apart():
    """Instruction names recur from program to program (`fusion.12` of
    one is not `fusion.12` of the next): a table per program, keyed by
    the input signature it was compiled for, and nothing of another
    network's entry."""
    from wittgenstein_tpu.protocols.pingpong_batched import make_pingpong

    net, state = make_pingpong(16)
    other, _ = make_pingpong(32)
    rs.clear_run_cache()
    assert rs.run_cache_op_scopes(net, 2) == {}
    for replicas in (2, 4):
        jax.block_until_ready(rs.sharded_run_stats(net, replicate_state(state, replicas), 2))
    tables = rs.run_cache_op_scopes(net, 2)
    sig2 = rs._CachedRun._signature(replicate_state(state, 2))
    sig4 = rs._CachedRun._signature(replicate_state(state, 4))
    assert set(tables) == {sig2, sig4}
    shared = set(tables[sig2]) & set(tables[sig4])
    assert shared  # the same names, in two programs
    assert all(any(r["scope"] for r in t.values()) for t in tables.values())
    assert rs.run_cache_op_scopes(other, 2) == {} and rs.run_cache_op_scopes(net, 3) == {}


def test_op_scopes_give_a_source_line_where_the_module_does(traced):
    rows = [r for r in traced["op_scopes"].values() if "witt.channel" in r["scope"]]
    sourced = [r for r in rows if r["source"]]
    assert sourced and all(re.search(r"\.py:\d+$", r["source"]) for r in sourced)
    assert any("_agg_batched.py" in r["source"] for r in sourced)


def test_join_partitions_the_ops_self_time(traced):
    sp = _load_script("scope_profile")
    tr = _xplane().read_trace(traced["trace_path"], allow_host_ops=True)
    events = [(o.name, o.self_ns) for plane in tr.ops.values() for o in plane]
    times = scope_self_times(events, traced["op_scopes"])
    assert times["total_ns"] == sum(ns for _, ns in events) > 0
    assert sum(times["chains"].values()) + times["unscoped_ns"] == times["total_ns"]
    assert sum(times["scopes"].values()) == sum(times["chains"].values())
    assert sum(sum(v.values()) for v in times["instructions"].values()) == times["total_ns"]
    # the ops ran the send path: its scopes hold time, and most time is scoped
    assert {s for s in times["scopes"] if s.startswith("witt.channel.")} == traced["channel_scopes"]
    assert times["unscoped_ns"] < times["total_ns"] / 2
    rows = sp.profile_rows(times, ticks=2 * CHUNK_MS)
    assert rows["coverage_pct"] == pytest.approx(
        100.0 * (1 - times["unscoped_ns"] / times["total_ns"])
    )
    assert sum(r["share_pct"] for r in rows["unscoped_fed_by"].values()) == pytest.approx(
        rows["unscoped"]["share_pct"]
    )
    assert all(len(r["heaviest"]) <= sp.HEAVIEST for r in rows["unscoped_fed_by"].values())
    assert sp.host_span_totals(traced["trace_path"])["witt.host.enqueue"]["count"] == 2


# -- the text parser, on the two spellings of metadata -------------------------

HLO_TEXT = """\
HloModule jit_fn, is_scheduled=true

FileNames
1 "/repo/protocols/_agg_batched.py"

FunctionNames
1 "BitsetAggBase._send_stacked"

FileLocations
1 {file_name_id=1 function_name_id=1 line=466 end_line=466 column=20 end_column=40}

StackFrames
1 {file_location_id=1 parent_frame_id=1}

%fused_computation (p: u32[8]) -> u32[8] {
  %p = u32[8]{0} parameter(0)
  ROOT %inner.1 = u32[8]{0} add(%p, %p), metadata={op_name="jit(fn)/while/body/vmap(witt.beat)/witt.channel.commit/add" stack_frame_id=1}
}

ENTRY %main (a: u32[8]) -> u32[8] {
  %a = u32[8]{0} parameter(0)
  %fusion.7 = u32[8]{0:T(1024)} fusion(%a), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(fn)/while/body/vmap(witt.beat)/witt.channel.commit/scatter" stack_frame_id=1}
  %sort.2 = u32[8]{0} sort(%fusion.7), metadata={op_name="jit(fn)/witt.fused_step/witt.protocol_tick/witt.channel.claim/sort" source_file="/repo/x.py" source_line=12}
  %copy.3 = u32[8]{0} copy(%sort.2)
  %bitcast.4 = u32[8]{0} bitcast(%copy.3)
  %fusion.8 = u32[8]{0} fusion(%a, %bitcast.4, %fusion.7), kind=kCustom, calls=%fused_computation
  ROOT %tuple = (u32[8]{0}) tuple(%fusion.8), metadata={op_name="jit(fn)/tuple"}
}
"""


def test_hlo_op_scopes_reads_both_spellings_of_metadata():
    table = hlo_op_scopes(HLO_TEXT)
    assert table["fusion.7"] == {
        "scope": "witt.beat/witt.channel.commit",
        "op_name": "jit(fn)/while/body/vmap(witt.beat)/witt.channel.commit/scatter",
        "source": "/repo/protocols/_agg_batched.py:466",
        "fed_by": "",
    }
    assert table["inner.1"]["scope"] == "witt.beat/witt.channel.commit"
    assert table["sort.2"]["scope"] == "witt.fused_step/witt.protocol_tick/witt.channel.claim"
    assert table["sort.2"]["source"] == "/repo/x.py:12"
    assert table["copy.3"] == {
        "scope": "", "op_name": "", "source": "", "fed_by": "witt.channel.claim"
    }
    # what XLA:TPU makes of a scatter: no op_name; its feed is read through
    # the unscoped bitcast and copy up to the nearest scoped producers
    assert table["fusion.8"]["scope"] == ""
    assert table["fusion.8"]["fed_by"] == "witt.channel.claim+witt.channel.commit"
    assert table["a"]["fed_by"] == ""  # a parameter: nothing feeds it
    assert table["tuple"]["scope"] == "" and table["tuple"]["op_name"] == "jit(fn)/tuple"
    assert "main" not in table and "fused_computation" not in table


@pytest.mark.parametrize("op_name, chain", [
    ("jit(fn)/while/body/vmap(witt.beat)/witt.channel.commit/scatter",
     "witt.beat/witt.channel.commit"),
    ("jit(fn)/witt.fused_step/witt.protocol_tick/add", "witt.fused_step/witt.protocol_tick"),
    ("jit(fn)/witt.send/witt.faults.send/select_n", "witt.send/witt.faults.send"),
    ("jit(fn)/jit(main)/while", ""),
    ("", ""),
])
def test_scope_chain(op_name, chain):
    assert scope_chain(op_name) == chain


def test_scope_self_times_joins_tpu_and_cpu_event_names():
    table = hlo_op_scopes(HLO_TEXT)
    events = [
        # a TPU event is named by its whole instruction, a CPU one by the name alone
        ("%fusion.7 = u32[8]{0:T(1024)} fusion(u32[8]{0} %a), kind=kLoop", 70),
        ("sort.2", 20),
        ("%copy.3 = u32[8]{0} copy(%sort.2)", 7),
        ("not-in-the-program.1", 3),
    ]
    times = scope_self_times(events, table)
    assert times["total_ns"] == 100 and times["unscoped_ns"] == 10
    assert times["scopes"] == {"witt.channel.commit": 70, "witt.channel.claim": 20}
    assert times["chains"]["witt.beat/witt.channel.commit"] == 70
    assert times["unscoped_fed_by"] == {"witt.channel.claim": 7, "": 3}
    assert times["instructions"]["fed_by:witt.channel.claim"] == {"copy.3": 7}
    assert times["instructions"]["fed_by:"] == {"not-in-the-program.1": 3}


# -- exports and the lint ------------------------------------------------------

@pytest.mark.parametrize("family", [
    "witt_run_cache_lower_seconds_total",
    "witt_run_cache_backend_compile_seconds_total",
    "witt_run_cache_lookup_seconds_total",
    "witt_run_cache_execute_seconds_total",
    "witt_run_cache_calls_total",
])
def test_server_metrics_carry_the_new_run_cache_families(family):
    from wittgenstein_tpu.server.server import Server

    text = Server().metrics_text()
    assert re.search(rf"^# TYPE {family} counter$", text, re.M)
    assert re.search(rf"^{family} \d", text, re.M)


@pytest.mark.parametrize("protocol", ["handel", "gsf"])
def test_sl601_passes_with_channel_scopes(protocol):
    from wittgenstein_tpu.analysis.annotations_check import check_annotations_entry

    entry = registry_batched_protocols.get(protocol)
    assert hasattr(entry.factory()[0].protocol, "_send_stacked")
    assert check_annotations_entry(entry, root=ROOT) == []


def test_sl601_detects_a_dead_channel_scope(monkeypatch):
    """The rule is live: a send path that drops one sub-scope is found."""
    import contextlib

    from wittgenstein_tpu.analysis.annotations_check import check_annotations_entry
    from wittgenstein_tpu.core.registries import BatchedProtocolEntry
    from wittgenstein_tpu.engine.core import BatchedNetwork

    real = BatchedNetwork._scope

    def scope(self, name, scopes=None):
        if scopes is CHANNEL_SCOPES and name == "claim":
            return contextlib.nullcontext()
        return real(self, name) if scopes is None else real(self, name, scopes)

    monkeypatch.setattr(BatchedNetwork, "_scope", scope)
    good = registry_batched_protocols.get("gsf")
    findings = check_annotations_entry(
        BatchedProtocolEntry("bad", "fixture_batched", good.factory), root=ROOT
    )
    assert [f.rule for f in findings] == ["SL601"]
    assert "witt.channel.claim" in findings[0].message


# -- the operator's script, end to end ----------------------------------------

def test_scope_profile_script_rehearses_on_the_cpu(capsys, tmp_path, cold_compiles):
    sp = _load_script("scope_profile")
    rs.clear_run_cache()
    rc = sp.main([
        "--config", os.path.join(ROOT, "benchmark", "configs", "gsf-2048.json"),
        "--replicas", "1", "--chunks", "2", "--nodes", "64", "--out", str(tmp_path),
    ])
    assert rc == 4  # a rehearsal never ends like a chip run
    doc = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert doc["rehearsal"] is True and doc["device"]["platform"] == "cpu"
    assert doc["config"] == "gsf-2048" and doc["nodes"] == 64 and doc["ticks_traced"] == 20
    assert doc["compile_was"] == "cold"
    assert doc["setup"]["lower_seconds_total"] > 0 and doc["setup"]["calls"] == 1
    assert doc["traced_chunks_counters"]["calls"] == 2
    assert set(CHANNEL_SCOPES.values()) - {CHANNEL_SCOPES["compact"]} <= set(doc["scopes"])
    shares = sum(r["share_pct"] for r in doc["chains"].values()) + doc["unscoped"]["share_pct"]
    assert shares == pytest.approx(100.0)
    assert doc["coverage_pct"] == pytest.approx(100.0 - doc["unscoped"]["share_pct"])
    assert doc["host_spans"]["witt.host.enqueue"]["count"] == 2
    assert doc["host_spans"]["bench.dispatch"]["count"] == 2
    assert os.path.exists(tmp_path / "gsf-2048-r1.json")
    assert os.path.exists(tmp_path / "gsf-2048-r1.rows.json.gz")


def test_scope_profile_script_refuses_a_cpu_at_full_size(capsys):
    """A TPU or no result (as benchmark/run.py): with no chip and no
    `--nodes` the script builds nothing and prints no document."""
    sp = _load_script("scope_profile")
    rc = sp.main([
        "--config", os.path.join(ROOT, "benchmark", "configs", "gsf-2048.json"),
        "--replicas", "1",
    ])
    io = capsys.readouterr()
    assert rc == 3 and io.out == "" and "no TPU" in io.err


# -- the counters under concurrent lanes ---------------------------------------

def test_counters_lose_no_add_under_concurrent_calls():
    """Serve lanes dispatch different entries at once, and `d[k] += x`
    is not atomic: with more threads than cores and a shortened switch
    interval every call must still be counted (runcache.counters)."""
    import sys
    import threading

    from wittgenstein_tpu.protocols.pingpong_batched import make_pingpong

    workers, calls_each = 16, 40
    net, state = make_pingpong(16)
    states = replicate_state(state, 2)
    jax.block_until_ready(rs.sharded_run_stats(net, states, 2))  # compile once
    before = rs.run_cache_info()
    failures = []

    def lane():
        try:
            for _ in range(calls_each):
                rs.sharded_run_stats(net, states, 2)
        except Exception as e:  # noqa: BLE001 — reported by the assert below
            failures.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=lane, daemon=True) for _ in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not failures and not any(t.is_alive() for t in threads)
    after = rs.run_cache_info()
    assert after["calls"] - before["calls"] == workers * calls_each
    assert after["hits"] - before["hits"] == workers * calls_each
    assert after["compiles"] == before["compiles"]
