"""From a profiler trace (.xplane.pb) to the benchmark's device numbers.

The reduction is kept here, with the benchmark, so that every PR
computes the same number in the same way.  `python benchmark/xplane.py
<file.xplane.pb>` prints what a trace holds: planes, lines, event
counts and the heaviest events, for looking at one by hand.

What is read:
  - device ops: the events of the line "XLA Ops" of each plane
    "/device:TPU:<n>".  Control-flow ops (while, conditional, call) are
    events too and enclose the ops of their bodies on the same line, so
    an op's self time is its duration less its children's, and the
    device is busy where a LEAF op runs.
  - benchmark spans: the TraceAnnotation events named "bench.<span>" on
    the host plane, which are on the device events' clock.
A trace recorded on a CPU has no device plane; its ops are the host
events that carry an `hlo_op` stat.  That reading exists for the
reducers' tests and the rehearsal only (`allow_host_ops`).
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
DEVICE_OP_LINE = "XLA Ops"
DEVICE_MODULE_LINE = "XLA Modules"
SPAN_PREFIX = "bench."


class TraceError(RuntimeError):
    """The trace does not hold what the reduction needs."""


@dataclasses.dataclass
class Op:
    name: str
    start: int  # ns
    end: int  # ns
    self_ns: int = 0
    leaf: bool = True


@dataclasses.dataclass
class Trace:
    ops: dict  # plane name -> [Op], each list in start order
    spans: list  # (name without prefix, start ns, end ns), in start order
    window: tuple  # (start ns, end ns) that the device numbers are taken over
    modules: list = dataclasses.field(default_factory=list)  # (start ns, end ns) of whole programs

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise TraceError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _nest(ops: list) -> None:
    """Fill self_ns and leaf for the ops of ONE line: an op that lies
    inside another is its child."""
    ops.sort(key=lambda o: (o.start, -(o.end - o.start)))
    stack = []
    for op in ops:
        op.self_ns = op.end - op.start
        while stack and stack[-1].end <= op.start:
            stack.pop()
        if stack and op.end <= stack[-1].end:
            stack[-1].self_ns -= op.end - op.start
            stack[-1].leaf = False
        stack.append(op)


def _op(event) -> Op:
    start = int(event.start_ns)
    return Op(event.name, start, start + int(event.duration_ns))


def read_trace(path: str, allow_host_ops: bool = False) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops, spans, modules = {}, [], []
    host_lines = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == DEVICE_OP_LINE:
                    line_ops = [_op(e) for e in line.events]
                    _nest(line_ops)
                    ops.setdefault(plane.name, []).extend(line_ops)
                elif line.name == DEVICE_MODULE_LINE:
                    modules += [(int(e.start_ns), int(e.start_ns + e.duration_ns)) for e in line.events]
        elif plane.name.startswith("/host:"):
            host_lines.extend(plane.lines)
    host_ops_wanted = allow_host_ops and not ops  # a CPU trace: no device plane
    for line in host_lines:
        line_ops = []
        for e in line.events:
            if e.name.startswith(SPAN_PREFIX):
                span = _op(e)
                spans.append((span.name[len(SPAN_PREFIX):], span.start, span.end))
            elif host_ops_wanted and any(k == "hlo_op" for k, _ in e.stats):
                line_ops.append(_op(e))
        if line_ops:
            _nest(line_ops)
            ops.setdefault("/host:ops", []).extend(line_ops)
    if not any(ops.values()):
        raise TraceError(f"{path}: no device op events (planes: {[p.name for p in data.planes]})")
    for plane_ops in ops.values():
        plane_ops.sort(key=lambda o: o.start)
    spans.sort(key=lambda s: s[1])
    if spans:
        window = (spans[0][1], max(s[2] for s in spans))
    else:
        window = (
            min(o.start for p in ops.values() for o in p),
            max(o.end for p in ops.values() for o in p),
        )
    return Trace(ops=ops, spans=spans, window=window, modules=modules)


def _union(intervals, lo: int, hi: int) -> list:
    """Merged intervals, clipped to [lo, hi]."""
    merged = []
    for start, end in sorted(intervals):
        start, end = max(start, lo), min(end, hi)
        if end <= start:
            continue
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def _length(merged) -> int:
    return sum(end - start for start, end in merged)


def busy_intervals(trace: Trace, plane: str) -> list:
    lo, hi = trace.window
    return _union(((o.start, o.end) for o in trace.ops[plane] if o.leaf), lo, hi)


def _mean_over_planes(trace: Trace, per_plane) -> float:
    values = [per_plane(plane) for plane in trace.ops]
    return sum(values) / len(values)


def busy_s(trace: Trace) -> float:
    """Seconds in which a leaf op ran, averaged over the chips traced."""
    return _mean_over_planes(trace, lambda p: _length(busy_intervals(trace, p)) / 1e9)


def matched_s(trace: Trace, regex: str) -> float:
    """Seconds covered by ops whose name matches, averaged over chips.
    An enclosing op that matches counts with all of its body, once."""
    pattern = re.compile(regex)
    lo, hi = trace.window

    def per_plane(plane):
        hit = ((o.start, o.end) for o in trace.ops[plane] if pattern.search(o.name))
        return _length(_union(hit, lo, hi)) / 1e9

    return _mean_over_planes(trace, per_plane)


def short_name(name: str, limit: int = 120) -> str:
    """On a TPU an op event is named by its whole HLO instruction.  Keep
    the instruction's name, its result type, its opcode and its operands'
    types; drop layouts, operand names and what follows the operands."""
    text = re.sub(r"\{[^{}]*\}", "", name)  # layouts
    text = re.sub(r" %[\w.\-]+", "", text)  # operand names
    text = text.split("), ")[0].lstrip("%")
    if not text.endswith(")") and "(" in text:
        text += ")"
    return text if len(text) <= limit else text[: limit - 3] + "..."


def module_busy_s(trace: Trace) -> float:
    """Seconds in which a whole program was running on the device: the
    looser reading of busy, beside the leaf ops' (PR 23 gave this one)."""
    lo, hi = trace.window
    return _length(_union(trace.modules, lo, hi)) / 1e9


def top_matching(trace: Trace, regex: str, n: int = 3) -> list:
    """[[short name, seconds]] of the ops that a metric's regex matches:
    printed beside the metric, so that what it counted can be read."""
    pattern = re.compile(regex)
    total = collections.Counter()
    for plane_ops in trace.ops.values():
        for o in plane_ops:
            if pattern.search(o.name):
                total[o.name] += o.end - o.start
    return [[short_name(name), ns / 1e9] for name, ns in total.most_common(n)]


def top_ops(trace: Trace, n: int = 10) -> list:
    """[[name, seconds]]: the ops with the most self time, summed by name
    over every plane."""
    total = collections.Counter()
    for plane_ops in trace.ops.values():
        for o in plane_ops:
            total[o.name] += o.self_ns
    return [[short_name(name), ns / 1e9] for name, ns in total.most_common(n)]


def idle_gaps(trace: Trace, n: int = 5) -> list:
    """[[what the host was doing, seconds]]: the longest stretches of the
    window in which no leaf op ran on the first chip, each under the
    benchmark span that covers most of it."""
    plane = sorted(trace.ops)[0]
    lo, hi = trace.window
    edges = [lo] + [t for iv in busy_intervals(trace, plane) for t in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)]
    gaps = sorted((g for g in gaps if g[1] > g[0]), key=lambda g: g[0] - g[1])[:n]

    def covering(gap):
        best, best_overlap = "between-chunks", 0
        for name, start, end in trace.spans:
            overlap = min(end, gap[1]) - max(start, gap[0])
            if overlap > best_overlap:
                best, best_overlap = name, overlap
        return best

    return [[covering(g), (g[1] - g[0]) / 1e9] for g in gaps]


def reduce_device_trace(metric: dict, trace: Trace, ticks: int):
    """The closed set of readings of a device trace that a layer-metric
    file can ask for with `per`:
      per_tick   ms per simulated tick under ops matching `regex`
                 (without a regex: device busy time)
      share      % of device busy time under ops matching `regex`
      idle_share % of the traced window in which no op ran
    Where ops ran and none matches `regex`, the reading is 0 and is
    reported: a cell of a protocol without the matched ops says so, and
    a change that respells the ops it was meant to speed up shows as a
    share fallen to 0, not as a metric gone from the line."""
    per, regex = metric["per"], metric.get("regex")
    busy = busy_s(trace)
    if per == "idle_share":
        return 100.0 * (1.0 - busy / trace.window_s)
    seconds = matched_s(trace, regex) if regex else busy
    if per == "per_tick":
        return 1e3 * seconds / ticks
    if per == "share":
        return 100.0 * seconds / busy
    raise ValueError(f"{metric['name']}: unknown per={per!r}")


def describe(path: str, top: int = 25) -> None:
    """Print what a trace holds, for reading one by hand."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    for plane in data.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print(f"  LINE {line.name!r}: {len(events)} events")
            by_name = collections.Counter()
            for e in events:
                by_name[e.name] += e.duration_ns
            for name, ns in by_name.most_common(top if DEVICE_PLANE.match(plane.name) else 6):
                print(f"      {ns / 1e6:12.3f} ms  {name[:120]}")
            if DEVICE_PLANE.match(plane.name) and events:
                e = max(events, key=lambda e: e.duration_ns)
                stats = {k: str(v)[:160] for k, v in e.stats}
                print(f"      stats of the longest event ({e.name[:60]}): {stats}")


if __name__ == "__main__":
    describe(sys.argv[1])
