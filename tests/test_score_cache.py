"""The carried candidate-score caches against their from-scratch oracle.

Handel carries four cached candidate-slot quantities in `state.proto`
(`cand_s`/`cand_card`/`cand_wind`/`cand_aggi`) so the per-tick `_select`
reads int32 scores instead of re-popcounting signature words; P2PHandel
carries `ver_card`.  The caches are the programs' only form (no switch
builds an uncached one), so what is held here is the SL701 invariant:
the carried leaves always equal `recompute_caches()`'s from-scratch
oracle, and the factories hide no other choice of program.
"""

import glob
import inspect
import json
import os

import jax
import jax.numpy as jnp
import pytest

from wittgenstein_tpu.protocols.handel import HandelParameters
from wittgenstein_tpu.protocols.handel_batched import (
    BatchedHandel,
    make_handel,
)
from wittgenstein_tpu.protocols.p2phandel import P2PHandelParameters
from wittgenstein_tpu.protocols.p2phandel_batched import make_p2phandel

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = sorted(glob.glob(os.path.join(ROOT, "benchmark", "configs", "*.json")))


def _two_replicas(state):
    states = jax.tree_util.tree_map(lambda x: jnp.stack([x, x]), state)
    return states._replace(seed=states.seed.at[1].set(99))


def _assert_cache_consistent(net, out, tag):
    # out is replica-batched; recompute_caches is a per-replica kernel
    fresh = jax.vmap(net.protocol.recompute_caches)(out)
    assert set(fresh) == set(net.protocol.CACHE_LEAF_NAMES), tag
    for k, v in fresh.items():
        assert bool(jnp.array_equal(out.proto[k], v)), (
            f"{tag}: carried cache '{k}' differs from from-scratch"
            " recompute (stale cache)"
        )


def _handel(wheel_rows, attack):
    # the attack at the benchmark's rehearsal size: 16 of 64 down and
    # forging (handel-4096-byz20.json `rehearsal.params`), so `bl` grows
    # and the curation's blacklist reads run beside the cache reads
    kw = (
        dict(nodes_down=16, threshold=47, pairing_time=4,
             dissemination_period_ms=20, byzantine_suicide=True)
        if attack
        else {}
    )
    return make_handel(
        HandelParameters(node_count=64, **kw), seed=3, wheel_rows=wheel_rows
    )


def _p2phandel(das):
    return make_p2phandel(
        P2PHandelParameters(double_aggregate_strategy=das), seed=3
    )


CASES = {
    "handel-flat-honest": lambda: _handel(0, False),
    "handel-wheel64-honest": lambda: _handel(64, False),
    "handel-flat-byz16": lambda: _handel(0, True),
    "handel-wheel64-byz16": lambda: _handel(64, True),
    "p2phandel-checksigs2": lambda: _p2phandel(True),
    "p2phandel-checksigs1": lambda: _p2phandel(False),
}


@pytest.mark.parametrize("tag", sorted(CASES))
def test_carried_caches_equal_recompute_at_every_stop(tag):
    """Every carried cache leaf equals the from-scratch oracle at 50-ms
    stops of a 400-ms run: delivery merges, commits (levels complete in
    that span) and, under attack, blacklisting all happen between stops."""
    net, state = CASES[tag]()
    assert set(net.protocol.CACHE_LEAF_NAMES) <= set(state.proto), tag
    states = _two_replicas(state)
    _assert_cache_consistent(net, states, f"{tag} t=0")
    at_start = {k: states.proto[k] for k in net.protocol.CACHE_LEAF_NAMES}
    for stop in range(50, 401, 50):
        states = net.run_ms_batched(states, 50, stop_when_done=False)
        _assert_cache_consistent(net, states, f"{tag} t={stop}")
    # the update paths ran: every cache moved, verifications committed
    for k, v in at_start.items():
        assert not bool(jnp.array_equal(states.proto[k], v)), (tag, k)
    if tag.startswith("handel-"):
        assert int(jnp.sum(states.done_at > 0)) > 0, f"{tag}: nobody finished"
    if "byz" in tag:
        assert int(jnp.sum(states.proto["bl"] != 0)) > 0, f"{tag}: no blacklisting"


def test_handel_cache_survives_commits():
    """A long-enough run that levels actually complete: the _commit
    cache fix-up (recompute only the committed level) is the subtle
    invalidation path, so exercise it for real."""
    net, state = make_handel(HandelParameters(node_count=32), seed=5)
    states = _two_replicas(state)
    out = net.run_ms_batched(states, 400)
    assert int(jnp.sum(out.done_at > 0)) > 0, (
        "run too short to exercise commits — bump ms"
    )
    _assert_cache_consistent(net, out, "handel 32-node 400ms")


def _resolve(dotted):
    import importlib

    module, name = dotted.rsplit(".", 1)
    return getattr(importlib.import_module(module), name)


@pytest.mark.parametrize("path", CONFIGS, ids=os.path.basename)
def test_benchmark_factory_call_is_the_whole_choice_of_program(path):
    """The factory with the configuration's own `factory_kwargs` and no
    other keyword, on the CPU, builds the program the configuration
    expects on the chip (`expect.protocol_attrs`), and a second plain
    call builds the same one: same state tree, same cache key.  Built at
    the benchmark's rehearsal size; what is left to the environment is
    `bitops_backend()` alone."""
    with open(path) as f:
        config = json.load(f)
    small = config.get("rehearsal", {}).get("params", {"node_count": 64})
    params_class = _resolve(config["params_class"])
    factory = _resolve(config["factory"])

    def call():
        return factory(
            params_class(**{**config["params"], **small}),
            **config["factory_kwargs"],
        )

    (net, state), (net2, state2) = call(), call()
    for attr, want in config.get("expect", {}).get("protocol_attrs", {}).items():
        assert getattr(net.protocol, attr) == want, (attr, path)
    assert set(net.protocol.DERIVED_CACHE_LEAVES) <= set(state.proto)
    assert jax.tree_util.tree_structure(state) == jax.tree_util.tree_structure(state2)
    assert jax.tree_util.tree_map(
        lambda a: (a.shape, a.dtype), state
    ) == jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), state2)
    assert net.stable_cache_key() == net2.stable_cache_key()


def test_factories_take_no_switch_of_cache_or_view():
    """The whole signatures: a keyword that picks the cached or the
    uncached program, or the selection's view, would show here."""
    assert list(inspect.signature(make_handel).parameters) == [
        "params", "capacity", "seed", "wheel_rows", "telemetry", "fuse_step",
    ]
    assert list(inspect.signature(make_p2phandel).parameters) == [
        "params", "capacity", "seed",
    ]
    net, state = make_handel(HandelParameters(node_count=32), fuse_step=True)
    assert net.protocol.SCORE_CACHE is True
    assert net.protocol.DERIVED_CACHE_LEAVES == BatchedHandel.CACHE_LEAF_NAMES
    assert set(BatchedHandel.CACHE_LEAF_NAMES) <= set(state.proto)


# -- SL701: the simlint rule guarding these invariants ----------------------


def _mk_entry(factory):
    from wittgenstein_tpu.core.registries import BatchedProtocolEntry

    return BatchedProtocolEntry("cachefix", "fixture_batched", factory)


def _pingpong_with(proto_patch):
    """pingpong net with a protocol subclass carrying a derived cache."""
    import copy

    from wittgenstein_tpu.protocols.pingpong_batched import make_pingpong

    def factory():
        net, state = make_pingpong(32)
        net = copy.copy(net)
        net.protocol = proto_patch(32)
        state = state._replace(
            proto=dict(state.proto, **net.protocol.recompute_caches(state))
        )
        return net, state

    return factory


def test_sl701_detects_stale_cache():
    from wittgenstein_tpu.analysis.contracts import check_entry
    from wittgenstein_tpu.protocols.pingpong_batched import BatchedPingPong

    class StaleCache(BatchedPingPong):
        # declares pong_total as derived but never UPDATES it: the leaf
        # is carried through deliver unchanged, so after pongs arrive
        # the stale 0 differs from the recompute
        DERIVED_CACHE_LEAVES = ("pong_total",)

        def recompute_caches(self, state):
            return {
                "pong_total": jnp.sum(state.proto["pong"])[None].astype(
                    jnp.int32
                )
            }

        def deliver(self, net, state, deliver_mask):
            carried = state.proto["pong_total"]
            state, em = super().deliver(net, state, deliver_mask)
            return state._replace(
                proto=dict(state.proto, pong_total=carried)
            ), em

    findings = check_entry(_mk_entry(_pingpong_with(StaleCache)), root=".")
    assert any(
        f.rule == "SL701" and "STALE" in f.message for f in findings
    ), [f.message for f in findings]


def test_sl701_detects_missing_leaf():
    import copy

    from wittgenstein_tpu.analysis.contracts import check_entry
    from wittgenstein_tpu.protocols.pingpong_batched import (
        BatchedPingPong,
        make_pingpong,
    )

    class UndeclaredLeaf(BatchedPingPong):
        DERIVED_CACHE_LEAVES = ("not_in_proto",)

    def factory():
        net, state = make_pingpong(32)
        net = copy.copy(net)
        net.protocol = UndeclaredLeaf(32)
        return net, state

    findings = check_entry(_mk_entry(factory), root=".")
    assert any(
        f.rule == "SL701" and "not present" in f.message for f in findings
    ), [f.message for f in findings]


def test_sl701_clean_on_maintained_cache():
    from wittgenstein_tpu.analysis.contracts import check_entry
    from wittgenstein_tpu.protocols.pingpong_batched import BatchedPingPong

    class MaintainedCache(BatchedPingPong):
        DERIVED_CACHE_LEAVES = ("pong_total",)

        def recompute_caches(self, state):
            return {
                "pong_total": jnp.sum(state.proto["pong"])[None].astype(
                    jnp.int32
                )
            }

        def deliver(self, net, state, deliver_mask):
            state, em = super().deliver(net, state, deliver_mask)
            proto = dict(state.proto)
            proto["pong_total"] = jnp.sum(proto["pong"])[None].astype(
                jnp.int32
            )
            return state._replace(proto=proto), em

    findings = check_entry(
        _mk_entry(_pingpong_with(MaintainedCache)), root="."
    )
    assert [f for f in findings if f.rule == "SL701"] == [], [
        f.message for f in findings
    ]


def test_registered_cache_protocols_pass_sl701():
    """The real thing: handel and p2phandel registry entries are SL701
    clean (their carried caches survive 8 concrete engine steps)."""
    from wittgenstein_tpu.analysis.contracts import _check_derived_cache, _cpu_jax
    from wittgenstein_tpu.core.registries import registry_batched_protocols

    jx = _cpu_jax()
    for name in ("handel", "p2phandel"):
        entry = registry_batched_protocols.get(name)
        net, state = entry.factory()
        assert net.protocol.DERIVED_CACHE_LEAVES, name
        findings = _check_derived_cache(
            jx, name, net, state, "x", 1, set()
        )
        assert findings == [], [f.message for f in findings]
