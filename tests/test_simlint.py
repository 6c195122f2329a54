"""simlint self-tests.

Two layers: (1) every AST rule fires on a known-bad fixture snippet and
stays quiet on the matching good one — the fixtures live HERE as strings,
outside the package tree the production lint walks; (2) the abstract-eval
and RNG passes detect deliberately broken protocols built from real
engine parts, and run clean on the registered seed protocols.  A final
whole-tree assertion keeps the package clean so CI's simlint gate and
this suite can't drift apart.
"""

from __future__ import annotations

import copy
import pathlib

import pytest

from wittgenstein_tpu.analysis.ast_lint import lint_package, lint_source
from wittgenstein_tpu.analysis.findings import RULES, Severity
from wittgenstein_tpu.analysis.registry_check import check_registry_coverage

REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]
PKG_ROOT = str(REPO_ROOT / "wittgenstein_tpu")
FIXTURE_PATH = "wittgenstein_tpu/protocols/fixture_batched.py"


def _rules(source: str) -> set:
    return {f.rule for f in lint_source(source, FIXTURE_PATH)}


# ---------------------------------------------------------------------------
# AST rules: one bad fixture per rule
# ---------------------------------------------------------------------------

def test_sl101_tracer_branch_fires():
    src = """
class P(BatchedProtocol):
    def tick(self, net, state):
        if state.time > 3:
            return state
        return state
"""
    assert "SL101" in _rules(src)


def test_sl101_quiet_on_host_branch():
    src = """
class P(BatchedProtocol):
    def tick(self, net, state):
        if self.n_nodes > 3:
            return state
        return state
"""
    assert _rules(src) == set()


def test_sl102_host_impurity_fires():
    src = """
class P(BatchedProtocol):
    def tick(self, net, state):
        print("tick", state.time)
        return state

    def _helper(self, state):
        t0 = time.time()
        r = np.random.rand()
        return state
"""
    findings = [f for f in lint_source(src, FIXTURE_PATH) if f.rule == "SL102"]
    assert len(findings) == 3  # print, time.time, np.random.rand


def test_sl103_host_conversion_fires():
    src = """
class P(BatchedProtocol):
    def tick(self, net, state):
        v = float(state.time)
        w = state.done_at.item()
        u = np.asarray(state.msg_received)
        return state
"""
    findings = [f for f in lint_source(src, FIXTURE_PATH) if f.rule == "SL103"]
    assert len(findings) == 3


def test_sl104_dtype_drift_fires():
    src = """
class P(BatchedProtocol):
    def tick(self, net, state):
        a = jnp.zeros(4)
        b = jnp.arange(n)
        c = jnp.array(1.5)
        return state
"""
    findings = [f for f in lint_source(src, FIXTURE_PATH) if f.rule == "SL104"]
    assert len(findings) == 3


def test_sl104_quiet_with_dtype():
    src = """
class P(BatchedProtocol):
    def tick(self, net, state):
        a = jnp.zeros(4, dtype=jnp.int32)
        b = jnp.arange(n, dtype=jnp.int32)
        c = jnp.array(1.5, jnp.float32)
        return state
"""
    assert _rules(src) == set()


def test_sl201_deliver_store_write_fires():
    src = """
class P(BatchedProtocol):
    def deliver(self, net, state, deliver_mask):
        return state._replace(msg_valid=state.msg_valid), []
"""
    assert "SL201" in _rules(src)


def test_sl201_quiet_on_proto_write():
    src = """
class P(BatchedProtocol):
    def deliver(self, net, state, deliver_mask):
        return state._replace(proto=state.proto), []
"""
    assert "SL201" not in _rules(src)


def test_sl202_beat_without_declaration_fires():
    src = """
class P(BatchedProtocol):
    def tick_beat(self, net, state):
        on = (state.time % 5) == 0
        return state._replace(proto=state.proto)
"""
    assert "SL202" in _rules(src)


def test_sl202_quiet_with_declaration():
    src = """
class P(BatchedProtocol):
    BEAT_PERIOD = 5
    BEAT_SEND_CALLS = 0

    def tick_beat(self, net, state):
        on = (state.time % 5) == 0
        return state._replace(proto=state.proto)
"""
    assert "SL202" not in _rules(src)


def test_sl203_unknown_mtype_fires():
    src = """
class P(BatchedProtocol):
    MSG_TYPES = ["PING"]

    def tick(self, net, state):
        m = self.mtype("PONG")
        return state
"""
    assert "SL203" in _rules(src)


def test_sl204_payload_contract_fires():
    src = """
class P(BatchedProtocol):
    def tick(self, net, state):
        e = Emission(mask=m, payload=p)
        return state


class Q(BatchedProtocol):
    PAYLOAD_WIDTH = 2

    def tick(self, net, state):
        v = state.msg_payload[:, 3]
        return state
"""
    findings = [f for f in lint_source(src, FIXTURE_PATH) if f.rule == "SL204"]
    assert len(findings) == 2


def test_sl204_quiet_with_dynamic_width():
    src = """
class P(BatchedProtocol):
    def __init__(self, w):
        self.PAYLOAD_WIDTH = w

    def tick(self, net, state):
        e = Emission(mask=m, payload=p)
        return state
"""
    assert "SL204" not in _rules(src)


def test_host_hooks_not_linted():
    # proto_init / initial_emissions / __init__ are host scope: plain
    # Python (loops, prints, numpy) is allowed there
    src = """
class P(BatchedProtocol):
    def __init__(self):
        self.t0 = time.time()

    def proto_init(self, n_nodes):
        if n_nodes > 4:
            print("big")
        return {"x": jnp.zeros(n_nodes)}

    def initial_emissions(self, net, state):
        return [Emission(mask=m, payload=p) for _ in range(3)]
"""
    assert _rules(src) == set()


def test_suppression_line_and_file():
    bad = """
class P(BatchedProtocol):
    def tick(self, net, state):
        a = jnp.zeros(4)
        return state
"""
    assert "SL104" in _rules(bad)
    line = bad.replace(
        "jnp.zeros(4)", "jnp.zeros(4)  # simlint: disable=SL104"
    )
    assert _rules(line) == set()
    filewide = "# simlint: disable-file=SL104\n" + bad
    assert _rules(filewide) == set()


def test_jit_decorated_function_is_kernel_scope():
    src = """
@jax.jit
def kernel(state):
    if state.time > 0:
        return state
    return state


def host(state):
    if state.time > 0:
        return state
    return state
"""
    findings = lint_source(src, "wittgenstein_tpu/utils/helper.py")
    assert {f.rule for f in findings} == {"SL101"}
    assert len(findings) == 1  # only the jitted one


# ---------------------------------------------------------------------------
# Abstract-eval + RNG passes on real engine parts
# ---------------------------------------------------------------------------

def _pingpong_entry():
    from wittgenstein_tpu.core.registries import registry_batched_protocols

    return registry_batched_protocols.get("pingpong")


def _entry_with_protocol(proto_cls):
    """Registry-style entry wrapping pingpong's net with a patched protocol."""
    from wittgenstein_tpu.core.registries import BatchedProtocolEntry
    from wittgenstein_tpu.protocols.pingpong_batched import make_pingpong

    def factory():
        net, state = make_pingpong(32)
        net = copy.copy(net)
        net.protocol = proto_cls(32)
        return net, state

    return BatchedProtocolEntry("bad", "fixture_batched", factory)


def test_contracts_clean_on_pingpong():
    from wittgenstein_tpu.analysis.contracts import check_entry
    from wittgenstein_tpu.analysis.rng_audit import audit_entry

    entry = _pingpong_entry()
    assert check_entry(entry, root=str(REPO_ROOT)) == []
    assert audit_entry(entry, root=str(REPO_ROOT)) == []


def test_sl402_detects_store_write():
    import jax.numpy as jnp

    from wittgenstein_tpu.analysis.contracts import check_entry
    from wittgenstein_tpu.protocols.pingpong_batched import BatchedPingPong

    class BadDeliver(BatchedPingPong):
        def deliver(self, net, state, deliver_mask):
            state, em = super().deliver(net, state, deliver_mask)
            return state._replace(
                msg_valid=jnp.zeros_like(state.msg_valid)
            ), em

    findings = check_entry(
        _entry_with_protocol(BadDeliver), root=str(REPO_ROOT)
    )
    assert any(
        f.rule == "SL402" and "msg_valid" in f.message for f in findings
    )


def test_sl401_detects_dtype_drift():
    import jax.numpy as jnp

    from wittgenstein_tpu.analysis.contracts import check_entry
    from wittgenstein_tpu.protocols.pingpong_batched import BatchedPingPong

    class DriftingTick(BatchedPingPong):
        def tick(self, net, state):
            return state._replace(
                done_at=state.done_at.astype(jnp.float32)
            )

    findings = check_entry(
        _entry_with_protocol(DriftingTick), root=str(REPO_ROOT)
    )
    assert any(f.rule == "SL401" for f in findings)


def test_sl405_detects_beat_rng_mismatch():
    from wittgenstein_tpu.analysis.rng_audit import audit_entry
    from wittgenstein_tpu.protocols.pingpong_batched import BatchedPingPong

    class BadBeat(BatchedPingPong):
        BEAT_PERIOD = 5
        BEAT_RESIDUES = (0,)
        BEAT_SEND_CALLS = 2  # lies: tick_beat below draws nothing

        def tick_beat(self, net, state):
            return state

    findings = audit_entry(
        _entry_with_protocol(BadBeat), root=str(REPO_ROOT)
    )
    assert [f.rule for f in findings] == ["SL405"]
    assert "BEAT_SEND_CALLS=2" in findings[0].message

    class SuppressedBadBeat(BadBeat):
        SIMLINT_SUPPRESS = ("SL405",)

    assert audit_entry(
        _entry_with_protocol(SuppressedBadBeat), root=str(REPO_ROOT)
    ) == []


def test_sl406_detects_fault_sensitive_protocol():
    import jax
    import jax.numpy as jnp

    from wittgenstein_tpu.analysis.contracts import check_entry
    from wittgenstein_tpu.protocols.pingpong_batched import BatchedPingPong

    class FaultSensitive(BatchedPingPong):
        # peeks at whether the fault side-car is armed: a neutral
        # schedule then changes non-fault state, breaking SL406
        def deliver(self, net, state, deliver_mask):
            state, em = super().deliver(net, state, deliver_mask)
            if len(jax.tree_util.tree_leaves(state.faults)) > 0:
                state = state._replace(
                    proto={"pong": state.proto["pong"] + jnp.int32(1)}
                )
            return state, em

    findings = check_entry(
        _entry_with_protocol(FaultSensitive), root=str(REPO_ROOT)
    )
    assert any(f.rule == "SL406" for f in findings)


def test_sl407_detects_deliver_fault_write():
    import jax.numpy as jnp

    from wittgenstein_tpu.analysis.contracts import check_entry
    from wittgenstein_tpu.protocols.pingpong_batched import BatchedPingPong

    class FaultWriter(BatchedPingPong):
        def deliver(self, net, state, deliver_mask):
            state, em = super().deliver(net, state, deliver_mask)
            if len(state.faults) > 0:  # only once SL407 arms the lane
                fs = state.faults
                state = state._replace(
                    faults=fs._replace(
                        dropped_by_fault=fs.dropped_by_fault + jnp.int32(1)
                    )
                )
            return state, em

    findings = check_entry(
        _entry_with_protocol(FaultWriter), root=str(REPO_ROOT)
    )
    assert any(
        f.rule == "SL407" and "dropped_by_fault" in f.message
        for f in findings
    )


def test_sl901_detects_live_dtype_mismatch():
    from wittgenstein_tpu.analysis.contracts import check_entry
    from wittgenstein_tpu.engine.density import NarrowLeaf
    from wittgenstein_tpu.protocols.pingpong_batched import BatchedPingPong

    class UnnarrowedInit(BatchedPingPong):
        # declares a narrow plan but proto_init (inherited) still seeds
        # the leaf at int32 — the narrow_proto() call was forgotten
        NARROW_LEAVES = (NarrowLeaf("pong", "int8", 100),)

    findings = check_entry(
        _entry_with_protocol(UnnarrowedInit), root=str(REPO_ROOT)
    )
    assert any(
        f.rule == "SL901" and "pong" in f.message and "int32" in f.message
        for f in findings
    )


def test_sl901_detects_headroom_violation():
    from wittgenstein_tpu.analysis.contracts import check_entry
    from wittgenstein_tpu.engine.density import NarrowLeaf
    from wittgenstein_tpu.protocols.pingpong_batched import BatchedPingPong

    class NoSentinelRoom(BatchedPingPong):
        # int8 max is 127, but the sentinel declaration reserves it:
        # declared_max 127 leaves no slot for the empty marker
        NARROW_LEAVES = (NarrowLeaf("pong", "int8", 127, sentinel=True),)

    findings = check_entry(
        _entry_with_protocol(NoSentinelRoom), root=str(REPO_ROOT)
    )
    assert any(
        f.rule == "SL901" and "127" in f.message and "sentinel" in f.message
        for f in findings
    )


def test_sl1201_detects_beating_jumpable_protocol():
    import jax.numpy as jnp

    from wittgenstein_tpu.analysis.contracts import check_entry
    from wittgenstein_tpu.protocols.pingpong_batched import BatchedPingPong

    assert BatchedPingPong.TICK_INTERVAL is None

    class LyingJumper(BatchedPingPong):
        # inherits TICK_INTERVAL=None (jumpable) but does per-tick work
        # the next-arrival jump paths would silently skip
        def tick_beat(self, net, state):
            return state._replace(
                proto={"pong": state.proto["pong"] + jnp.int32(1)}
            )

    findings = check_entry(
        _entry_with_protocol(LyingJumper), root=str(REPO_ROOT)
    )
    assert any(
        f.rule == "SL1201" and "not a no-op" in f.message
        for f in findings
    )


def test_sl1201_detects_beat_period_contradiction():
    from wittgenstein_tpu.analysis.contracts import check_entry
    from wittgenstein_tpu.protocols.pingpong_batched import BatchedPingPong

    class PeriodicJumper(BatchedPingPong):
        # periodic beat work declared on a jumpable protocol: the two
        # declarations contradict each other
        BEAT_PERIOD = 10
        BEAT_RESIDUES = (0,)
        BEAT_SEND_CALLS = 0

    findings = check_entry(
        _entry_with_protocol(PeriodicJumper), root=str(REPO_ROOT)
    )
    assert any(
        f.rule == "SL1201" and "BEAT_PERIOD" in f.message
        for f in findings
    )


def test_sl1201_quiet_on_declared_tick_interval():
    import jax.numpy as jnp

    from wittgenstein_tpu.analysis.contracts import check_entry
    from wittgenstein_tpu.protocols.pingpong_batched import BatchedPingPong

    class HonestBeater(BatchedPingPong):
        # the same mutating beat is fine once the protocol stops
        # claiming its ticks are skippable
        TICK_INTERVAL = 1

        def tick_beat(self, net, state):
            return state._replace(
                proto={"pong": state.proto["pong"] + jnp.int32(0)}
            )

    findings = check_entry(
        _entry_with_protocol(HonestBeater), root=str(REPO_ROOT)
    )
    assert not any(f.rule == "SL1201" for f in findings)


def test_sl601_clean_on_pingpong():
    from wittgenstein_tpu.analysis.annotations_check import (
        check_annotations_entry,
    )

    assert check_annotations_entry(_pingpong_entry(), root=str(REPO_ROOT)) == []


def test_sl601_detects_missing_scope():
    """An engine whose _scope is a no-op emits no markers — the
    delivery scope must be reported missing."""
    import contextlib

    from wittgenstein_tpu.analysis.annotations_check import (
        check_annotations_entry,
    )
    from wittgenstein_tpu.core.registries import BatchedProtocolEntry
    from wittgenstein_tpu.protocols.pingpong_batched import make_pingpong

    def factory():
        net, state = make_pingpong(32)
        net = copy.copy(net)
        net._scope = lambda name, scopes=None: contextlib.nullcontext()
        return net, state

    findings = check_annotations_entry(
        BatchedProtocolEntry("bad", "fixture_batched", factory),
        root=str(REPO_ROOT),
    )
    assert any(
        f.rule == "SL601" and "witt.delivery" in f.message for f in findings
    )


# ---------------------------------------------------------------------------
# Whole-tree cleanliness + catalog sync
# ---------------------------------------------------------------------------

def test_package_ast_clean():
    findings = lint_package(PKG_ROOT)
    assert findings == [], "\n".join(f.format() for f in findings)


def test_registry_coverage_clean():
    findings = check_registry_coverage(str(REPO_ROOT))
    assert findings == [], "\n".join(f.format() for f in findings)


def test_registry_enumerates_every_batched_module():
    from wittgenstein_tpu.core.registries import registry_batched_protocols

    mods = sorted(
        p.stem
        for p in (REPO_ROOT / "wittgenstein_tpu" / "protocols").glob(
            "*_batched.py"
        )
        if not p.stem.startswith("_")
    )
    assert sorted(registry_batched_protocols.modules()) == mods


def test_rule_catalog_docs_in_sync():
    doc = (REPO_ROOT / "docs" / "static_analysis.md").read_text()
    for rule in RULES:
        assert rule in doc, f"{rule} missing from docs/static_analysis.md"


def test_finding_json_round_trip():
    import json

    from wittgenstein_tpu.analysis.findings import Finding

    f = Finding("SL104", "a/b.py", 7, "msg", Severity.ERROR)
    d = json.loads(f.to_json())
    assert d["rule"] == "SL104" and d["line"] == 7
    assert d["summary"] == RULES["SL104"]


def test_cli_exit_codes_and_jsonl(tmp_path, capsys):
    """End-to-end CLI on a synthetic bad tree: nonzero exit, JSONL out."""
    import json

    from wittgenstein_tpu.analysis.cli import main

    pkg = tmp_path / "wittgenstein_tpu" / "protocols"
    pkg.mkdir(parents=True)
    (pkg / "bad_batched.py").write_text(
        "class P(BatchedProtocol):\n"
        "    def tick(self, net, state):\n"
        "        a = jnp.zeros(4)\n"
        "        return state\n"
    )
    out = tmp_path / "findings.jsonl"
    rc = main([
        "--root", str(tmp_path), "--strict", "--skip-contracts",
        "-o", str(out),
    ])
    capsys.readouterr()
    assert rc == 1
    rules = {json.loads(ln)["rule"] for ln in out.read_text().splitlines()}
    assert "SL104" in rules  # the dtype-less ctor
    assert "SL301" in rules  # unregistered + untested module

    # empty-but-valid tree is clean and exits 0
    bare = tmp_path / "clean"
    (bare / "wittgenstein_tpu").mkdir(parents=True)
    (bare / "wittgenstein_tpu" / "__init__.py").write_text("")
    assert main(["--root", str(bare), "--strict", "--skip-contracts"]) == 0
    capsys.readouterr()

    # missing package dir is a usage error
    assert main(["--root", str(tmp_path / "nope")]) == 2
    capsys.readouterr()


@pytest.mark.slow
def test_full_simlint_clean():
    """The CI gate, as a test: every pass over the real tree is clean."""
    from wittgenstein_tpu.analysis.cli import run

    findings = run(str(REPO_ROOT))
    assert findings == [], "\n".join(f.format() for f in findings)


def test_sl1001_clean_on_pingpong():
    from wittgenstein_tpu.analysis.mesh_check import check_entry_mesh

    assert check_entry_mesh(_pingpong_entry(), root=str(REPO_ROOT)) == []


def test_sl1001_detects_proto_store_name_collision():
    """A protocol minting a proto leaf under an engine store-field name
    would be silently replicated along the node axis — flagged."""
    import collections

    import jax.numpy as jnp

    from wittgenstein_tpu.analysis.mesh_check import check_entry_mesh
    from wittgenstein_tpu.core.registries import BatchedProtocolEntry
    from wittgenstein_tpu.protocols.pingpong_batched import make_pingpong

    Side = collections.namedtuple("Side", ["msg_valid"])

    def factory():
        net, state = make_pingpong(32)
        proto = dict(state.proto)
        proto["side"] = Side(msg_valid=jnp.zeros(32, jnp.int32))
        return net, state._replace(proto=proto)

    entry = BatchedProtocolEntry("bad", "fixture_batched", factory)
    findings = check_entry_mesh(entry, root=str(REPO_ROOT))
    assert any(
        f.rule == "SL1001"
        and "msg_valid" in f.message
        and "REPLICATE" in f.message
        for f in findings
    )


def test_sl1001_detects_stale_store_field_exclusion(monkeypatch):
    """An exclusion entry naming no live leaf anywhere is a stale
    exemption — anchored at node_shard.py over the full sweep."""
    from wittgenstein_tpu.analysis import mesh_check
    from wittgenstein_tpu.core.registries import registry_batched_protocols
    from wittgenstein_tpu.parallel import node_shard

    monkeypatch.setattr(
        node_shard,
        "_MESSAGE_STORE_FIELDS",
        node_shard._MESSAGE_STORE_FIELDS + (".ghost_field",),
    )
    # shrink the sweep to one entry: the stale logic only needs SOME
    # audited entry, and the full registry build belongs to the slow gate
    monkeypatch.setattr(
        registry_batched_protocols, "entries",
        lambda: [_pingpong_entry()],
    )
    findings = mesh_check.check_mesh_layout(root=str(REPO_ROOT))
    assert any(
        f.rule == "SL1001"
        and "ghost_field" in f.message
        and "node_shard" in f.path
        for f in findings
    )
    # the subset-restricted sweep must NOT report stale exclusions
    assert not any(
        "ghost_field" in f.message
        for f in mesh_check.check_mesh_layout(
            root=str(REPO_ROOT), names=["pingpong"]
        )
    )
