"""AST lint rules over the package (SL1xx tracer/purity/dtype, SL2xx
protocol contract).

The rules only fire inside KERNEL SCOPE — code that runs under a jax
trace — so host-side construction (factories, oracle init, exports) can
keep using plain Python freely.  Kernel scope is:

  * kernel hooks of batched-protocol classes (engine.protocol.KERNEL_HOOKS)
    plus their underscore helper methods (helpers are called from hooks);
  * methods of the engine's BatchedNetwork except host-side construction
    (everything it runs is inside its own jit entry points);
  * any function/method decorated with `jax.jit` (bare or via
    functools.partial);
  * everything in `wittgenstein_tpu/ops/` (pure kernel helpers).

Protocol classes are recognized by a base-name fixpoint seeded with
{BatchedProtocol, BitsetAggBase}, so `class X(BatchedHandel)` in the same
file is covered too.  The field lists the contract rules check against come
from engine.protocol's machine-readable metadata, not from copies here.
"""

from __future__ import annotations

import ast
import os
from typing import Dict, Iterable, List, Optional, Set, Tuple

from ..engine.protocol import ENGINE_OWNED_FIELDS, HOST_HOOKS, KERNEL_HOOKS
from .findings import Finding, Severity, apply_suppressions

# base-class names that mark a batched-protocol class (extended per file by
# fixpoint over local inheritance)
PROTOCOL_BASE_SEEDS = {"BatchedProtocol", "BitsetAggBase"}

# protocol methods that are host-side even though they live on the class
HOST_METHODS = set(HOST_HOOKS) | {"__init__", "contract"}

# BatchedNetwork methods that are host-side construction/dispatch
ENGINE_HOST_METHODS = {
    "__init__",
    "init_state",
    "cache_key",
    "with_telemetry",
    "with_faults",
    "run_ms",
    "run_ms_batched",
    "_window",
    # the oracle's two partition calls: host-side, between runs
    "partition",
    "end_partition",
}

# SimState fields whose attribute access marks an expression as
# tracer-valued inside kernel code (import would drag jax in; the engine's
# contract metadata covers the owned subset, node columns complete it)
_SIMSTATE_FIELDS = set(ENGINE_OWNED_FIELDS) | {
    "down",
    "done_at",
    "msg_received",
    "msg_sent",
    "bytes_received",
    "bytes_sent",
    "extra_latency",
    "city_idx",
    "partition_x",
    "proto",
}
# too generic to key a traced-ref on their own (state.x/state.y exist, but
# `b.x` on host objects is everywhere)
_SIMSTATE_FIELDS -= {"x", "y"}

_TRACED_NAMES = {"state", "vstate", "pstate", "states", "deliver_mask"}

_IMPURE_CALLS = {
    ("time", "time"),
    ("time", "perf_counter"),
    ("time", "monotonic"),
    ("time", "sleep"),
}

_DTYPELESS_CTORS = {"zeros", "ones", "arange", "empty"}
# ctor -> positional index where dtype may appear
_CTOR_DTYPE_POS = {"zeros": 1, "ones": 1, "empty": 1, "full": 2, "array": 1,
                   "asarray": 1, "arange": 3}


def _dotted(node: ast.AST) -> Optional[str]:
    """`a.b.c` -> "a.b.c" (None for non-trivial expressions)."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _has_traced_ref(node: ast.AST) -> bool:
    """Does the expression reference a (likely) traced value: a SimState
    field access, a known traced name, a `proto[...]` subscript, or a
    jnp/lax call?"""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Attribute) and sub.attr in _SIMSTATE_FIELDS:
            # self.MSG_TYPES-style class config is not traced
            if not (
                isinstance(sub.value, ast.Name) and sub.value.id == "self"
            ):
                return True
        if isinstance(sub, ast.Name) and sub.id in _TRACED_NAMES:
            return True
        if isinstance(sub, ast.Subscript):
            base = sub.value
            if isinstance(base, ast.Name) and base.id == "proto":
                return True
        if isinstance(sub, ast.Call):
            name = _dotted(sub.func) or ""
            root = name.split(".")[0]
            if root in ("jnp", "lax"):
                return True
    return False


def _is_dtype_expr(node: ast.AST) -> bool:
    """Positional arg that plausibly IS a dtype (jnp.int32, np.uint8, bool)."""
    name = _dotted(node)
    if name is None:
        return isinstance(node, ast.Constant) and isinstance(node.value, str)
    root = name.split(".")[0]
    if root in ("jnp", "np", "numpy", "jax"):
        return "." in name  # jnp.int32, np.float32, ...
    return name in ("bool", "int", "float", "complex")


def _numeric_literal(node: ast.AST) -> bool:
    if isinstance(node, ast.UnaryOp) and isinstance(
        node.op, (ast.USub, ast.UAdd)
    ):
        node = node.operand
    return isinstance(node, ast.Constant) and isinstance(
        node.value, (int, float)
    ) and not isinstance(node.value, bool)


def _has_jit_decorator(fn: ast.AST) -> bool:
    for dec in getattr(fn, "decorator_list", []):
        target = dec.func if isinstance(dec, ast.Call) else dec
        name = _dotted(target) or ""
        if name.endswith("jax.jit") or name == "jit":
            return True
        if isinstance(dec, ast.Call) and (
            (_dotted(dec.func) or "").endswith("partial")
        ):
            for a in dec.args:
                if (_dotted(a) or "").endswith("jax.jit"):
                    return True
    return False


class _ClassInfo:
    def __init__(self, node: ast.ClassDef, is_protocol: bool):
        self.node = node
        self.is_protocol = is_protocol
        self.msg_types: Optional[List[str]] = None  # literal list, if any
        self.payload_width: Optional[int] = None  # literal int, if any
        self.defines_payload_width = False
        self.direct_protocol_base = any(
            isinstance(b, ast.Name) and b.id == "BatchedProtocol"
            for b in node.bases
        )
        for stmt in node.body:
            tgt = None
            if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1:
                tgt = stmt.targets[0]
                val = stmt.value
            elif isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
                tgt = stmt.target
                val = stmt.value
            else:
                continue
            if not isinstance(tgt, ast.Name):
                continue
            if tgt.id == "MSG_TYPES" and isinstance(val, (ast.List, ast.Tuple)):
                elems = []
                ok = True
                for e in val.elts:
                    if isinstance(e, ast.Constant) and isinstance(e.value, str):
                        elems.append(e.value)
                    else:
                        ok = False
                if ok:
                    self.msg_types = elems
            if tgt.id == "PAYLOAD_WIDTH":
                self.defines_payload_width = True
                if isinstance(val, ast.Constant) and isinstance(val.value, int):
                    self.payload_width = val.value
        # dynamic width: `self.PAYLOAD_WIDTH = ...` anywhere in the class
        # (instance-level, value unknowable statically — disables the
        # width-dependent checks rather than guessing)
        for sub in ast.walk(node):
            if isinstance(sub, (ast.Assign, ast.AnnAssign)):
                tgts = sub.targets if isinstance(sub, ast.Assign) else [sub.target]
                for t in tgts:
                    if (
                        isinstance(t, ast.Attribute)
                        and t.attr == "PAYLOAD_WIDTH"
                        and isinstance(t.value, ast.Name)
                        and t.value.id == "self"
                    ):
                        self.defines_payload_width = True
                        self.payload_width = None


def _protocol_classes(tree: ast.Module) -> Dict[str, _ClassInfo]:
    """Name -> info for every class, with protocol-ness by base fixpoint."""
    classes = {
        n.name: n for n in tree.body if isinstance(n, ast.ClassDef)
    }
    protocol: Set[str] = set()
    changed = True
    while changed:
        changed = False
        for name, node in classes.items():
            if name in protocol:
                continue
            for b in node.bases:
                bname = b.id if isinstance(b, ast.Name) else (_dotted(b) or "")
                bname = bname.split(".")[-1]
                if bname in PROTOCOL_BASE_SEEDS or bname in protocol:
                    protocol.add(name)
                    changed = True
                    break
    return {
        name: _ClassInfo(node, name in protocol)
        for name, node in classes.items()
    }


def _module_declares_beat(tree: ast.Module) -> bool:
    """Any binding of BEAT_PERIOD or BEAT_SEND_CALLS in the module: a class
    attribute, or a `proto.BEAT_PERIOD = ...` factory assignment."""
    for node in ast.walk(tree):
        targets: Iterable[ast.AST] = ()
        if isinstance(node, ast.Assign):
            targets = node.targets
        elif isinstance(node, ast.AnnAssign):
            targets = (node.target,)
        for t in targets:
            name = t.id if isinstance(t, ast.Name) else getattr(t, "attr", "")
            if name in ("BEAT_PERIOD", "BEAT_SEND_CALLS"):
                return True
    return False


def _is_trivial_body(fn: ast.FunctionDef) -> bool:
    """Docstring + bare `return state`-style body (the base-class no-op)."""
    body = [
        s
        for s in fn.body
        if not (
            isinstance(s, ast.Expr)
            and isinstance(s.value, ast.Constant)
            and isinstance(s.value.value, str)
        )
    ]
    if len(body) != 1:
        return False
    s = body[0]
    return isinstance(s, (ast.Return, ast.Pass))


class _KernelRuleVisitor(ast.NodeVisitor):
    """Applies the SL1xx/SL2xx body rules inside ONE kernel function."""

    def __init__(
        self,
        path: str,
        findings: List[Finding],
        cls: Optional[_ClassInfo],
        fn_name: str,
    ):
        self.path = path
        self.findings = findings
        self.cls = cls
        self.fn_name = fn_name

    def _add(self, rule: str, node: ast.AST, msg: str):
        self.findings.append(
            Finding(rule, self.path, getattr(node, "lineno", 1), msg)
        )

    # -- SL101: tracer-unsafe control flow -----------------------------------
    def _check_test(self, node, test):
        if _has_traced_ref(test):
            self._add(
                "SL101",
                node,
                f"`{type(node).__name__.lower()}` on a traced expression in "
                f"kernel `{self.fn_name}` — use jnp.where/lax.cond/masks",
            )

    def visit_If(self, node: ast.If):
        self._check_test(node, node.test)
        self.generic_visit(node)

    def visit_While(self, node: ast.While):
        self._check_test(node, node.test)
        self.generic_visit(node)

    def visit_IfExp(self, node: ast.IfExp):
        self._check_test(node, node.test)
        self.generic_visit(node)

    def visit_Assert(self, node: ast.Assert):
        self._check_test(node, node.test)
        self.generic_visit(node)

    def visit_Call(self, node: ast.Call):
        name = _dotted(node.func) or ""
        parts = tuple(name.split("."))
        attr = parts[-1]

        # -- SL102: host impurity -------------------------------------------
        if (
            parts[:2] in (("np", "random"), ("numpy", "random"))
            or parts[0] == "random"
            and len(parts) > 1
            or parts in _IMPURE_CALLS
            or name in ("print", "input", "breakpoint")
        ):
            self._add(
                "SL102",
                node,
                f"host-impure call `{name}` inside kernel `{self.fn_name}` "
                "(traced code must be pure; use jax.debug.print / the "
                "counter RNG)",
            )

        # -- SL103: host conversions of traced values ------------------------
        if name in ("float", "int", "bool") and node.args and _has_traced_ref(
            node.args[0]
        ):
            self._add(
                "SL103",
                node,
                f"`{name}()` on a traced value in kernel `{self.fn_name}` "
                "forces a host sync / fails under jit",
            )
        if attr == "item" and not node.args and isinstance(
            node.func, ast.Attribute
        ):
            self._add(
                "SL103",
                node,
                f"`.item()` in kernel `{self.fn_name}` forces a host sync "
                "/ fails under jit",
            )
        if parts[0] in ("np", "numpy") and len(parts) > 1 and any(
            _has_traced_ref(a) for a in list(node.args)
        ):
            self._add(
                "SL103",
                node,
                f"`{name}` applied to a traced value in kernel "
                f"`{self.fn_name}` — use the jnp equivalent",
            )

        # -- SL104: dtype-drift hazards --------------------------------------
        if parts[0] == "jnp" and len(parts) == 2:
            ctor = parts[1]
            kw_dtype = any(k.arg == "dtype" for k in node.keywords)
            pos = _CTOR_DTYPE_POS.get(ctor)
            pos_dtype = (
                pos is not None
                and len(node.args) > pos
                and _is_dtype_expr(node.args[pos])
            ) or any(_is_dtype_expr(a) for a in node.args[1:])
            if ctor in _DTYPELESS_CTORS and not kw_dtype and not pos_dtype:
                self._add(
                    "SL104",
                    node,
                    f"`jnp.{ctor}` without an explicit dtype in kernel "
                    f"`{self.fn_name}` (defaults drift: zeros/ones give "
                    "float, arange widths depend on inputs)",
                )
            if (
                ctor in ("array", "asarray", "full")
                and not kw_dtype
                and not pos_dtype
            ):
                lit_arg = node.args[1] if ctor == "full" and len(
                    node.args
                ) > 1 else (node.args[0] if node.args else None)
                if lit_arg is not None and _numeric_literal(lit_arg):
                    self._add(
                        "SL104",
                        node,
                        f"weak-typed numeric literal via `jnp.{ctor}` in "
                        f"kernel `{self.fn_name}` — pin the dtype "
                        "(weak-type promotion recompiles / drifts dtypes)",
                    )

        # -- SL201: deliver writing engine-owned columns ---------------------
        if (
            attr == "_replace"
            and self.cls is not None
            and self.cls.is_protocol
            and self.fn_name == "deliver"
        ):
            owned = set(ENGINE_OWNED_FIELDS)
            for k in node.keywords:
                if k.arg in owned:
                    self._add(
                        "SL201",
                        node,
                        f"deliver() writes engine-owned field `{k.arg}` "
                        "(return emissions instead; the engine owns the "
                        "message store)",
                    )

        # -- SL203: mtype name not in MSG_TYPES ------------------------------
        if (
            attr == "mtype"
            and self.cls is not None
            and self.cls.msg_types is not None
            and node.args
            and isinstance(node.args[0], ast.Constant)
            and isinstance(node.args[0].value, str)
            and node.args[0].value not in self.cls.msg_types
        ):
            self._add(
                "SL203",
                node,
                f"mtype({node.args[0].value!r}) not in MSG_TYPES "
                f"{self.cls.msg_types}",
            )

        # -- SL204: payload against PAYLOAD_WIDTH ----------------------------
        if (
            isinstance(node.func, ast.Name)
            and node.func.id == "Emission"
            and self.cls is not None
            and self.cls.is_protocol
        ):
            width = self.cls.payload_width
            if width is None and not self.cls.defines_payload_width and (
                self.cls.direct_protocol_base
            ):
                width = 0  # inherited default
            if width == 0:
                for k in node.keywords:
                    if k.arg == "payload" and not (
                        isinstance(k.value, ast.Constant)
                        and k.value.value is None
                    ):
                        self._add(
                            "SL204",
                            node,
                            "Emission(payload=...) but PAYLOAD_WIDTH is 0 "
                            "— the engine drops the payload silently",
                        )
        self.generic_visit(node)

    def visit_Subscript(self, node: ast.Subscript):
        # SL204: constant msg_payload index past PAYLOAD_WIDTH
        if (
            isinstance(node.value, ast.Attribute)
            and node.value.attr == "msg_payload"
            and self.cls is not None
            and self.cls.payload_width is not None
        ):
            width = self.cls.payload_width
            idx = node.slice
            elems = idx.elts if isinstance(idx, ast.Tuple) else [idx]
            last = elems[-1]
            if (
                isinstance(last, ast.Constant)
                and isinstance(last.value, int)
                and not isinstance(last.value, bool)
                and last.value >= width
                and len(elems) > 1  # [..., k] / [:, k] style column access
            ):
                self._add(
                    "SL204",
                    node,
                    f"msg_payload column {last.value} >= PAYLOAD_WIDTH "
                    f"{width}",
                )
        self.generic_visit(node)


def _kernel_functions(
    path: str, tree: ast.Module, classes: Dict[str, _ClassInfo]
):
    """Yield (fn_node, class_info_or_None, fn_name) for kernel scope."""
    rel = path.replace(os.sep, "/")
    in_engine = rel.endswith("engine/core.py")
    in_ops = "/ops/" in rel

    for cname, info in classes.items():
        is_engine_cls = in_engine and cname == "BatchedNetwork"
        for item in info.node.body:
            if not isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            name = item.name
            if info.is_protocol:
                if name in HOST_METHODS:
                    continue
                if name in KERNEL_HOOKS or name.startswith("_"):
                    yield item, info, name
            elif is_engine_cls:
                if name not in ENGINE_HOST_METHODS:
                    yield item, info, name
            elif _has_jit_decorator(item):
                yield item, info, name

    for item in tree.body:
        if isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if in_ops or _has_jit_decorator(item):
                yield item, None, item.name
            else:
                # module-level host function: still scan for NESTED
                # jit-decorated functions (chunked-run helpers)
                for sub in ast.walk(item):
                    if sub is not item and isinstance(
                        sub, (ast.FunctionDef, ast.AsyncFunctionDef)
                    ) and _has_jit_decorator(sub):
                        yield sub, None, sub.name


def lint_source(source: str, path: str = "<string>") -> List[Finding]:
    """Lint one file's source; returns suppression-filtered findings."""
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError as e:
        return [
            Finding(
                "SL101",
                path,
                e.lineno or 1,
                f"syntax error prevents linting: {e.msg}",
                Severity.ERROR,
            )
        ]
    classes = _protocol_classes(tree)
    findings: List[Finding] = []

    for fn, cls, name in _kernel_functions(path, tree, classes):
        v = _KernelRuleVisitor(path, findings, cls, name)
        for stmt in fn.body:
            v.visit(stmt)

    # SL202: tick_beat override without beat metadata in the module
    for cname, info in classes.items():
        if not info.is_protocol:
            continue
        for item in info.node.body:
            if (
                isinstance(item, ast.FunctionDef)
                and item.name == "tick_beat"
                and not _is_trivial_body(item)
                and not _module_declares_beat(tree)
            ):
                findings.append(
                    Finding(
                        "SL202",
                        path,
                        item.lineno,
                        f"{cname}.tick_beat overridden but the module never "
                        "binds BEAT_PERIOD/BEAT_SEND_CALLS — beat gating "
                        "would desynchronize the RNG stream",
                    )
                )

    return apply_suppressions(findings, source)


def lint_file(path: str) -> List[Finding]:
    with open(path, "r") as f:
        return lint_source(f.read(), path)


def iter_package_files(root: str) -> List[str]:
    """Python files of the package tree (skips caches and data)."""
    out = []
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [
            d for d in dirnames if d not in ("__pycache__", "data")
        ]
        for fn in sorted(filenames):
            if fn.endswith(".py"):
                out.append(os.path.join(dirpath, fn))
    return out


def lint_package(root: str) -> List[Finding]:
    findings: List[Finding] = []
    for path in iter_package_files(root):
        findings.extend(lint_file(path))
    return findings
