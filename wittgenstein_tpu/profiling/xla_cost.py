"""Normalized XLA cost/memory accounting for compiled entry points.

jax 0.4.x API quirks this module absorbs so callers never touch them:

  * `compiled.cost_analysis()` returns a LIST of per-computation dicts
    (usually length 1) whose keys mix scalars ("flops", "bytes
    accessed", "transcendentals") with per-operand entries ("bytes
    accessed0{}", "bytes accessedout{}", ...);
  * `compiled.memory_analysis()` returns an opaque CompiledMemoryStats
    object (attrs, not a mapping), and either call may return None or
    raise on backends that don't implement it (the CPU backend DOES
    implement both as of jaxlib 0.4.37 — docs/profiling.md records the
    per-backend caveats).

Everything returned here is plain JSON-able floats/ints, ready for
BENCH records, BUDGET.json, and run_cache_metrics().
"""

from __future__ import annotations

import re
from typing import Any, Optional

_MEMORY_ATTRS = (
    "generated_code_size_in_bytes",
    "argument_size_in_bytes",
    "output_size_in_bytes",
    "alias_size_in_bytes",
    "temp_size_in_bytes",
    "host_generated_code_size_in_bytes",
    "host_argument_size_in_bytes",
    "host_output_size_in_bytes",
    "host_alias_size_in_bytes",
    "host_temp_size_in_bytes",
)


def cost_analysis_dict(compiled) -> Optional[dict]:
    """Scalar totals from compiled.cost_analysis(): {"flops",
    "bytes_accessed", "transcendentals", "optimal_seconds"} summed over
    the returned computations, per-operand breakdown entries dropped.
    None when the backend can't say."""
    try:
        cost = compiled.cost_analysis()
    except Exception:
        return None
    if cost is None:
        return None
    if isinstance(cost, dict):  # jax >= 0.5 flattens the list
        cost = [cost]
    wanted = {
        "flops": "flops",
        "bytes accessed": "bytes_accessed",
        "transcendentals": "transcendentals",
        "optimal_seconds": "optimal_seconds",
    }
    out: dict = {}
    for comp in cost:
        for src, dst in wanted.items():
            if src in comp:
                out[dst] = out.get(dst, 0.0) + float(comp[src])
    return out or None


def memory_analysis_dict(compiled) -> Optional[dict]:
    """CompiledMemoryStats as a plain dict (suffix _in_bytes kept), plus
    "live_bytes" = argument + output + temp — the footprint that must
    fit in device memory for one invocation (code size excluded: HBM vs
    host split varies by backend; aliased/donated bytes excluded since
    they overlap arguments)."""
    try:
        mem = compiled.memory_analysis()
    except Exception:
        return None
    if mem is None:
        return None
    out: dict = {}
    for attr in _MEMORY_ATTRS:
        v = getattr(mem, attr, None)
        if v is not None:
            out[attr] = int(v)
    if not out:
        return None
    out["live_bytes"] = (
        out.get("argument_size_in_bytes", 0)
        + out.get("output_size_in_bytes", 0)
        + out.get("temp_size_in_bytes", 0)
    )
    return out


def compiled_cost_summary(compiled, compile_seconds: Optional[float] = None) -> dict:
    """The record the run cache stores per compiled program: cost +
    memory normalized, compile wall-clock if the caller timed it."""
    out: dict = {
        "cost": cost_analysis_dict(compiled),
        "memory": memory_analysis_dict(compiled),
    }
    if compile_seconds is not None:
        out["compile_seconds"] = round(float(compile_seconds), 3)
    return out


def lower_and_summarize(fn, *args, static_argnums=(), **kw) -> dict:
    """Convenience: jit+lower+compile `fn` on example args and return
    its compiled_cost_summary (with measured compile seconds).  Used by
    scripts/budget_report.py to price run_ms without running it."""
    import time

    import jax

    t0 = time.perf_counter()
    compiled = (
        jax.jit(fn, static_argnums=static_argnums).lower(*args, **kw).compile()
    )
    return compiled_cost_summary(compiled, time.perf_counter() - t0)


def format_bytes(n: Any) -> str:
    """Human side-channel for reports: 111_149_056 -> '106.0 MiB'."""
    try:
        n = float(n)
    except (TypeError, ValueError):
        return str(n)
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024.0 or unit == "TiB":
            return f"{n:.1f} {unit}" if unit != "B" else f"{int(n)} B"
        n /= 1024.0
    return f"{n:.1f} TiB"


# -- instruction name -> witt.* scope, from the compiled module's text -------
# A device op event names an HLO instruction and carries no scope; the
# compiled module knows which trace-time scope emitted each instruction
# (metadata op_name) and, through its stack-frame tables, which source
# line.  Parsed on demand only: a 4096-node program's text is megabytes.

_HLO_NAME = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s+=\s")
_HLO_OP_NAME = re.compile(r'op_name="([^"]*)"')
_HLO_SOURCE = re.compile(r'source_file="([^"]*)"(?:\s+source_line=(\d+))?')
_HLO_FRAME = re.compile(r"stack_frame_id=(\d+)")
_SCOPE = re.compile(r"witt\.[A-Za-z0-9_.]*[A-Za-z0-9_]")
_TABLE_ROW = re.compile(r'^(\d+)\s+(?:"(.*)"|\{(.*)\})\s*$')
_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")
_HLO_REFERENCE = re.compile(r"%([\w.\-]+)")


def scope_chain(op_name: str) -> str:
    """The `witt.*` components of an op_name, outermost first, joined by
    "/": "jit(fn)/while/body/vmap(witt.beat)/witt.channel.commit/scatter"
    -> "witt.beat/witt.channel.commit"; "" where there is none."""
    return "/".join(_SCOPE.findall(op_name))


def _frame_sources(tables: dict) -> dict:
    """stack_frame_id -> "file:line" from the module's own tables (the
    frame's innermost location; what jax kept of the user's stack)."""

    def fields(row: str) -> dict:
        return {k: int(v) for k, v in re.findall(r"(\w+)=(\d+)", row)}

    out = {}
    for frame_id, row in tables["StackFrames"].items():
        loc = tables["FileLocations"].get(fields(row).get("file_location_id"))
        if loc is None:
            continue
        loc = fields(loc)
        name = tables["FileNames"].get(loc.get("file_name_id"))
        if name is not None:
            out[frame_id] = f"{name}:{loc.get('line', 0)}"
    return out


def hlo_op_scopes(hlo_text: str) -> dict:
    """{instruction name: {"scope", "op_name", "source", "fed_by"}} for
    every instruction of a compiled module's text
    (`compiled.as_text()`), fused computations' bodies included.
    `scope` is `scope_chain` of the instruction's op_name; `source` is
    "file:line" where the module gives one (source_file/source_line, or
    a stack_frame_id resolved through the module's tables), else "".

    The compiler leaves some instructions without an op_name: XLA:TPU
    rewrites every scatter into a sort and a custom fusion that carry
    none (PERF.md §5).  Such a row's `scope` stays "", and `fed_by`
    says what is known of it without guessing: the innermost scopes of
    its nearest scoped producers on every path up, "+"-joined
    ("witt.channel.commit+witt.channel.readdress": indices from the
    commit, content from the re-addressing); "" on a scoped row or where
    no producer has a scope.  What feeds an instruction is not what
    emitted it: a share read off `fed_by` is an inference and is
    reported as one."""
    tables: dict = {t: {} for t in _TABLES}
    table = None
    pending = []  # (entry, frame id) until the tables are read
    operands = {}  # unscoped instruction -> its operands' names
    out = {}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if stripped in tables:
            table = tables[stripped]
            continue
        if table is not None:
            row = _TABLE_ROW.match(stripped)
            if row:
                table[int(row.group(1))] = (
                    row.group(2) if row.group(2) is not None else row.group(3)
                )
                continue
            table = None
        m = _HLO_NAME.match(line)
        if m is None:
            continue
        op = _HLO_OP_NAME.search(line)
        op_name = op.group(1) if op else ""
        entry = {"scope": scope_chain(op_name), "op_name": op_name,
                 "source": "", "fed_by": ""}
        src = _HLO_SOURCE.search(line)
        if src:
            entry["source"] = src.group(1) + (f":{src.group(2)}" if src.group(2) else "")
        else:
            frame = _HLO_FRAME.search(line)
            if frame:
                pending.append((entry, int(frame.group(1))))
        if not entry["scope"]:
            # called computations' names are among these; they name no
            # instruction and drop out at the lookup below
            operands[m.group(1)] = _HLO_REFERENCE.findall(line[m.end():])
        out[m.group(1)] = entry
    if pending:
        sources = _frame_sources(tables)
        for entry, frame_id in pending:
            entry["source"] = sources.get(frame_id, "")
    for name, feed in _feeding_scopes(operands, out).items():
        out[name]["fed_by"] = "+".join(sorted(feed))
    return out


def _feeding_scopes(operands: dict, table: dict) -> dict:
    """{unscoped instruction: the innermost scopes of its nearest scoped
    producers}: up through unscoped producers (bitcasts, copies, tuple
    elements) to the first scoped one on each path, every instruction
    visited once (a module's instructions form a DAG)."""
    feeds: dict = {}
    for root in operands:
        if root in feeds:
            continue
        feeds[root] = set()
        stack = [(root, iter(operands[root]))]
        while stack:
            name, producers = stack[-1]
            for producer in producers:
                row = table.get(producer)
                if row is None:
                    continue
                if row["scope"]:
                    feeds[name].add(row["scope"].rsplit("/", 1)[-1])
                elif producer in feeds:
                    feeds[name] |= feeds[producer]
                else:
                    feeds[producer] = set()
                    stack.append((producer, iter(operands[producer])))
                    break
            else:
                stack.pop()
                if stack:
                    feeds[stack[-1][0]] |= feeds[name]
    return feeds


_EVENT_INSTRUCTION = re.compile(r"^%?([\w.\-]+)")


def scope_self_times(events, op_scopes: dict) -> dict:
    """The join: device op events against `hlo_op_scopes`.  `events` is
    an iterable of (event name, self ns); an event is named by its HLO
    instruction (on a TPU the whole instruction text, leading with the
    name).  Every event lands in exactly one row, so the rows partition
    the summed self time:

      {"total_ns", "unscoped_ns",
       "chains": {scope chain: ns},     # "witt.beat/witt.channel.commit"
       "scopes": {innermost scope: ns}, # "witt.channel.commit"
       "unscoped_fed_by": {fed_by: ns}, # the unscoped time, by its feed
       "instructions": {chain, or "fed_by:<feed>" if unscoped: {instruction: ns}}}
    """
    out = {"total_ns": 0, "unscoped_ns": 0, "chains": {}, "scopes": {},
           "unscoped_fed_by": {}, "instructions": {}}

    def add(table, key, ns):
        table[key] = table.get(key, 0) + ns

    for name, self_ns in events:
        m = _EVENT_INSTRUCTION.match(name)
        instruction = m.group(1) if m else name
        row = op_scopes.get(instruction, {})
        chain = row.get("scope", "")
        out["total_ns"] += self_ns
        if chain:
            add(out["chains"], chain, self_ns)
            add(out["scopes"], chain.rsplit("/", 1)[-1], self_ns)
        else:
            out["unscoped_ns"] += self_ns
            add(out["unscoped_fed_by"], row.get("fed_by", ""), self_ns)
            chain = "fed_by:" + row.get("fed_by", "")
        add(out["instructions"].setdefault(chain, {}), instruction, self_ns)
    return out
