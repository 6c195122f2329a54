"""Handel (and GSF) with nodes down, and Handel under byzantineSuicide:
what the deployment `handel-4096-byz20` of the benchmark works, held at
64 nodes on the CPU.

- the ledger of the channel send path: where the network is built with
  a node down, `proto["sent_not_ok"]` counts by sender the masked sends
  that were not ok, so that over live nodes sent == received + that,
  exactly; an honest build carries no such leaf;
- the attack path against the oracle on fixed seeds, under tolerances
  that the same committee with the attack switched off fails;
- the emission's blacklist term: dissemination moves on past the peers a
  node has blacklisted, and closes a level that has none left;
- the attack's named scopes are live and bit-neutral (simlint SL601).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from wittgenstein_tpu.core.registries import builder_name
from wittgenstein_tpu.engine import replicate_state
from wittgenstein_tpu.protocols.gsf import GSFSignatureParameters
from wittgenstein_tpu.protocols.gsf_batched import make_gsf
from wittgenstein_tpu.protocols.handel import Handel, HandelParameters
from wittgenstein_tpu.protocols.handel_batched import (
    ATTACK_SCOPES,
    DELIVER_SCOPES,
    BatchedHandel,
    make_handel,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NL = "NetworkLatencyByDistanceWJitter"
NB = builder_name("RANDOM", True, 0)
N, DOWN = 64, 16


def byz_params(**kw):
    """`handel-4096-byz20`'s parameters at 64 nodes: a quarter down, 99%
    of the live three quarters (the benchmark's rehearsal)."""
    base = dict(
        node_count=N, threshold=47, pairing_time=4, level_wait_time=50,
        extra_cycle=10, dissemination_period_ms=20, fast_path=10,
        nodes_down=DOWN, byzantine_suicide=True,
        node_builder_name=NB, network_latency_name=NL,
    )
    base.update(kw)
    return HandelParameters(**base)


def gsf_params(**kw):
    base = dict(node_count=N, nodes_down=DOWN, threshold=47,
                node_builder_name=NB, network_latency_name=NL)
    base.update(kw)
    return GSFSignatureParameters(**base)


BUILDS = {
    "handel": lambda **kw: make_handel(byz_params(**kw), fuse_step=True),
    "gsf": lambda **kw: make_gsf(gsf_params(**kw)),
}


# -- the counter ---------------------------------------------------------


@pytest.mark.parametrize("protocol", sorted(BUILDS))
def test_sent_is_received_plus_sent_not_ok_in_every_row(protocol):
    net, state = BUILDS[protocol]()
    out = net.run_ms_batched(replicate_state(state, 2, seeds=[7, 2**31 - 9]), 100)
    live = ~np.asarray(out.down)
    assert live.sum(-1).tolist() == [N - DOWN] * 2
    sent = np.where(live, np.asarray(out.msg_sent), 0).sum(-1)
    received = np.where(live, np.asarray(out.msg_received), 0).sum(-1)
    not_ok = np.where(live, np.asarray(out.proto["sent_not_ok"]), 0).sum(-1)
    assert (sent == received + not_ok).all(), (sent, received, not_ok)
    # live nodes keep sending to the down: in Handel more than one each by 100 ms
    assert (not_ok > (N if protocol == "handel" else 0)).all(), not_ok
    assert int(np.asarray(out.dropped).max()) == 0


@pytest.mark.parametrize("protocol", sorted(BUILDS))
def test_an_honest_build_has_no_such_leaf_and_the_tree_it_had(protocol):
    honest = dict(nodes_down=0, threshold=60)
    if protocol == "handel":
        honest["byzantine_suicide"] = False
    _, state = BUILDS[protocol](**honest)
    _, with_down = BUILDS[protocol]()
    assert "sent_not_ok" not in state.proto
    assert with_down.proto["sent_not_ok"].shape == (N,)
    assert with_down.proto["sent_not_ok"].dtype == jnp.int32
    # the leaves a node down adds, and nothing else
    added = set(with_down.proto) - set(state.proto)
    assert added == {"sent_not_ok"} | ({"bl", "byz"} if protocol == "handel" else set())
    assert set(state.proto) <= set(with_down.proto)


def test_nodes_down_without_the_attack_count_too():
    _, state = make_handel(byz_params(byzantine_suicide=False))
    assert "sent_not_ok" in state.proto and "bl" not in state.proto


def test_a_forced_down_set_places_the_leaf():
    _, state = make_handel(byz_params(nodes_down=0, bad_nodes=0b1010, byzantine_suicide=False))
    assert np.asarray(state.down).sum() == 2 and "sent_not_ok" in state.proto


def test_the_count_is_exported_where_msg_sent_is():
    from wittgenstein_tpu.telemetry import counters, prometheus_from_counters

    net, state = BUILDS["handel"]()
    out = net.run_ms(state, 60)
    c = counters(net, out)
    assert c["node"]["sent_not_ok"] == int(np.asarray(out.proto["sent_not_ok"]).sum()) > 0
    assert f'witt_node_sent_not_ok_total {c["node"]["sent_not_ok"]}' in prometheus_from_counters(c)
    net, state = BUILDS["handel"](nodes_down=0, threshold=60, byzantine_suicide=False)
    c = counters(net, net.run_ms(state, 20))
    assert "sent_not_ok" not in c["node"]
    assert "sent_not_ok" not in prometheus_from_counters(c)


# -- the attack path against the oracle ------------------------------------

SEEDS = list(range(16))
HORIZON = 1000  # every live node is done by 610 ms; ten extra cycles of 20 ms

# |program - oracle| / oracle, measured on these seeds (PR 31):
#   attack on   done_at 0.017   msg_sent 0.161   blacklist 0.06
#   attack off  done_at 0.047   msg_sent 0.131   blacklist 1 (none)
TOLERANCE = {
    # seed noise is 0.006 (384 and 768 live nodes' mean); without the
    # forged signatures a node wastes no pairing and finishes 3% sooner
    "done_at": 0.03,
    # the program keeps no finished-peer bookkeeping (ROADMAP B11): it
    # goes on sending on levels the oracle has closed, +16% here, +21% at
    # 256 nodes; a cadence fault (half the period) doubles the number
    "msg_sent": 0.20,
    # the oracle blacklists in suicideBizAfter cursor order, the program
    # the lowest block index first: the same count within a tenth
    "blacklist": 0.12,
}


def _program(params):
    net, state = make_handel(params, fuse_step=True)
    out = net.run_ms_batched(replicate_state(state, len(SEEDS), seeds=SEEDS), HORIZON)
    live = ~np.asarray(out.down)
    if "bl" in out.proto:
        bl = np.asarray(out.proto["bl"])[live]
        listed = np.unpackbits(bl.view(np.uint8), axis=-1).sum(-1).mean()
    else:
        listed = 0.0
    return {"done_at": np.asarray(out.done_at)[live].mean(),
            "msg_sent": np.asarray(out.msg_sent)[live].mean(),
            "blacklist": float(listed),
            "all_done": bool((np.asarray(out.done_at)[live] > 0).all())}


@pytest.fixture(scope="module")
def oracle():
    done, sent, listed = [], [], []
    for seed in SEEDS:
        p = Handel(byz_params())
        p.network().rd.set_seed(seed)
        p.init()
        p.network().run_ms(HORIZON)
        live = p.network().live_nodes()
        done += [n.done_at for n in live]
        sent += [n.msg_sent for n in live]
        listed += [bin(n.blacklist).count("1") for n in live]
    assert min(done) > 0
    return {"done_at": np.mean(done), "msg_sent": np.mean(sent), "blacklist": np.mean(listed)}


@pytest.fixture(scope="module")
def under_attack():
    return _program(byz_params())


def _gap(program, oracle, number):
    return abs(program[number] - oracle[number]) / oracle[number]


@pytest.mark.parametrize("number", sorted(TOLERANCE))
def test_the_attack_path_tracks_the_oracle(oracle, under_attack, number):
    assert under_attack["all_done"]
    gap = _gap(under_attack, oracle, number)
    assert gap <= TOLERANCE[number], (number, under_attack[number], oracle[number], gap)


def test_the_attack_switched_off_fails_those_tolerances(oracle):
    plain = _program(byz_params(byzantine_suicide=False))
    assert plain["all_done"]
    assert _gap(plain, oracle, "done_at") > TOLERANCE["done_at"], plain
    assert _gap(plain, oracle, "blacklist") > TOLERANCE["blacklist"], plain


# -- the emission's blacklist term -----------------------------------------


def _next_unlisted_by_hand(bl_bits, peer, bs):
    """First index at or cyclically after `peer` in [0, bs) whose bit of
    the level block [bs, 2*bs) is clear; None if every bit is set."""
    for k in range(bs):
        j = (peer + k) % bs
        if not bl_bits[bs + j]:
            return j
    return None


@pytest.mark.parametrize("n,density", [(64, 0.3), (64, 0.9), (256, 0.5), (4096, 0.97)])
def test_next_unlisted_is_the_cyclic_scan_of_the_reference(n, density):
    rng = np.random.default_rng(n + int(100 * density))
    proto = BatchedHandel(byz_params(node_count=n, nodes_down=n // 4, threshold=n // 2))
    rows = 8
    bits = rng.random((rows, n)) < density
    bits[0, :] = True  # nothing left anywhere
    bits[1, :] = False  # nothing listed
    bl = np.packbits(bits, axis=-1, bitorder="little").view(np.uint32)
    L = proto.n_levels
    peer = np.stack([rng.integers(0, 1 << (l - 1), rows) for l in range(1, L)], axis=1)
    bl_full = np.zeros((n, proto.n_words), np.uint32)
    bl_full[:rows] = bl
    peer_full = np.zeros((n, L - 1), np.int32)
    peer_full[:rows] = peer
    nxt, any_left = proto._next_unlisted(jnp.asarray(bl_full), jnp.asarray(peer_full))
    nxt, any_left = np.asarray(nxt), np.asarray(any_left)
    for r in range(rows):
        for l in range(1, L):
            want = _next_unlisted_by_hand(bits[r], int(peer[r, l - 1]), 1 << (l - 1))
            assert bool(any_left[r, l - 1]) == (want is not None), (r, l)
            if want is not None:
                assert int(nxt[r, l - 1]) == want, (r, l)


@pytest.mark.parametrize("n", [64, 256, 4096])
def test_listed_reads_the_bit_that_getbit_gathers(n):
    """The gather-free read of the blacklist (`_block_bit`: a block view
    and a one-hot mask) against the word a gather would fetch through the
    peer's full rel (what `_getbit` did until PR 44), for every level of
    every bucket."""
    rng = np.random.default_rng(n)
    proto = BatchedHandel(byz_params(node_count=n, nodes_down=n // 4, threshold=n // 2))
    rows, k = 16, 10
    bl = np.zeros((n, proto.n_words), np.uint32)
    bl[:rows] = rng.integers(0, 2**32, (rows, proto.n_words), dtype=np.uint32)
    bl[0], bl[1] = 0, 0xFFFFFFFF
    for b in proto.buckets:
        bs = np.asarray([proto.bs[l] for l in b.levels])
        rel = np.zeros((n, b.nl, k), np.int32)
        rel[:rows] = bs[None, :, None] + rng.integers(0, 2**30, (rows, b.nl, k)) % bs[None, :, None]
        got = np.asarray(proto._block_bit(jnp.asarray(bl), b, jnp.asarray(rel)))
        word = bl[np.arange(n)[:, None, None], rel >> 5]
        want = (word >> (rel & 31).astype(np.uint32)) & 1 == 1
        assert (got[:rows] == want[:rows]).all(), b
        assert got[0].sum() == 0 and got[1].all()


def test_a_blacklisted_peer_gets_nothing_more_from_the_node_that_listed_it():
    """Run until blacklists are non-empty, then take one dissemination
    beat apart: no masked row goes to a peer its sender has blacklisted,
    rows still go to down peers not yet blacklisted, and a level whose
    every peer is blacklisted sends nothing and leaves its cursor."""
    net, state = make_handel(byz_params())
    state = net.run_ms(state, 400)
    proto = net.protocol
    bl = np.asarray(state.proto["bl"])
    live = ~np.asarray(state.down)
    assert bl[live].any()
    # the next beat: t with (t - (start_at + 1)) % period == 0
    state = state._replace(time=jnp.int32(401))
    # blacklist the only level-1 peer (rel 1) of node 0, and all of level 2 of node 1
    forced = bl.copy()
    forced[0, 0] |= 0b10
    forced[1, 0] |= 0b1100
    state = state._replace(proto=dict(state.proto, bl=jnp.asarray(forced)))
    seen = {}

    def capture(net_, st, mask, from_idx, to_idx, level, content, aux=None):
        assert level is None  # a level-axis send: a row's level is its place on axis 1
        seen.update(mask=np.asarray(mask), frm=np.asarray(from_idx), to=np.asarray(to_idx),
                    level=np.arange(1, mask.shape[1] + 1)[None, :, None])
        return st

    proto._send_stacked = capture
    wide = state._replace(proto=proto.widen_proto(state.proto))
    after = proto._dissemination(net, wide)
    mask, frm, to, level = seen["mask"], seen["frm"], seen["to"], seen["level"]
    assert mask.sum() > N  # a beat: the live nodes send on their open levels
    rel = frm ^ to
    listed = (forced[frm, rel >> 5] >> (rel & 31).astype(np.uint32)) & 1
    assert not (mask & (listed == 1)).any()
    down = np.asarray(state.down)
    assert (mask & down[to]).any()  # the not yet blacklisted down still cost a send
    assert not mask[(frm == 0) & (level == 1)].any() and not mask[(frm == 1) & (level == 2)].any()
    pos0, pos1 = np.asarray(wide.proto["pos"]), np.asarray(after.proto["pos"])
    assert pos1[0, 1] == pos0[0, 1] and pos1[1, 2] == pos0[1, 2]
    # a cursor that skipped moves by more than one, and never backwards
    sent_rows = mask.reshape(N, proto.n_levels - 1)
    moved = (pos1 - pos0)[:, 1:]
    assert (moved[sent_rows] >= 1).all() and (moved[~sent_rows] == 0).all()
    assert (moved > 1).any()


# -- the attack's scopes -------------------------------------------------


def _take_out(monkeypatch, dead):
    """Every engine's `_scope` opens nothing for the scope named `dead`."""
    import contextlib

    from wittgenstein_tpu.engine.core import BatchedNetwork

    real = BatchedNetwork._scope

    def scope(self, name, scopes=None):
        if scopes is not None and scopes[name] == dead:
            return contextlib.nullcontext()
        return real(self, name) if scopes is None else real(self, name, scopes)

    monkeypatch.setattr(BatchedNetwork, "_scope", scope)


def _attack_entry():
    from wittgenstein_tpu.core.registries import BatchedProtocolEntry

    return BatchedProtocolEntry(
        "handel_byz", "fixture_batched", lambda: make_handel(byz_params())
    )


def test_sl601_passes_with_the_attack_scopes_live():
    from wittgenstein_tpu.analysis.annotations_check import check_annotations_entry

    assert check_annotations_entry(_attack_entry(), root=ROOT) == []


@pytest.mark.parametrize(
    "registry, dead",
    [
        pytest.param(registry, name, id=name)
        for registry in (ATTACK_SCOPES, DELIVER_SCOPES)
        for name in sorted(registry)
    ],
)
def test_sl601_detects_a_dead_attack_or_merge_scope(monkeypatch, registry, dead):
    from wittgenstein_tpu.analysis.annotations_check import check_annotations_entry

    _take_out(monkeypatch, registry[dead])
    findings = check_annotations_entry(_attack_entry(), root=ROOT)
    assert [f.rule for f in findings] == ["SL601"]
    assert registry[dead] in findings[0].message


CHANNEL = ["witt.channel.arrivals", "witt.channel.readdress", "witt.channel.claim", "witt.channel.commit"]
DELIVER = ["witt.deliver.rank", "witt.deliver.merge"]
# what the lint required of each build BY NAME until PR 50 (a method called
# `_send_stacked`, `track_bad`, `isinstance(..., BatchedHandel)`,
# `params.byzantine_suicide`), spelled out; the last of each is taken out below
STATED = {
    "gsf": CHANNEL + ["witt.channel.compact"],
    "gsf-one-call": CHANNEL,  # no every-tick send on the level axis: no firing rows to the front
    "handel": CHANNEL + DELIVER + ["witt.channel.compact"],
    "handel-no-fast-path": DELIVER + CHANNEL,
    "handel-hidden-byzantine": CHANNEL + ["witt.channel.compact"] + DELIVER
    + ["witt.attack.emission", "witt.attack.blacklist"],
    "handel-byzantine-suicide": CHANNEL + ["witt.channel.compact"] + DELIVER
    + ["witt.attack.blacklist", "witt.attack.emission", "witt.attack.inject"],
}


def _stated_entry(build):
    from wittgenstein_tpu.core.registries import BatchedProtocolEntry, registry_batched_protocols

    if build in ("gsf", "handel"):  # the registered ones, as simlint runs them
        return registry_batched_protocols.get(build)
    factory = {
        "gsf-one-call": lambda: make_gsf(gsf_params(nodes_down=0, accelerated_calls_count=1)),
        "handel-no-fast-path": lambda: make_handel(
            byz_params(nodes_down=0, byzantine_suicide=False, fast_path=0)),
        "handel-hidden-byzantine": lambda: make_handel(
            byz_params(byzantine_suicide=False, hidden_byzantine=True)),
        "handel-byzantine-suicide": lambda: make_handel(byz_params()),
    }[build]
    return BatchedProtocolEntry(build, "fixture_batched", factory)


@pytest.mark.parametrize("build", sorted(STATED))
def test_a_channel_protocol_states_its_scopes_and_sl601_holds_it_to_them(monkeypatch, build):
    """The protocol's own `REQUIRED_SCOPES` is what the lint asks of it (the
    lint names no protocol): exactly the by-name list, clean as built, and
    a finding for a scope that its step no longer carries."""
    from wittgenstein_tpu.analysis.annotations_check import check_annotations_entry

    entry = _stated_entry(build)
    stated = entry.factory()[0].protocol.REQUIRED_SCOPES
    assert sorted(stated) == sorted(STATED[build]) and len(set(stated)) == len(stated)
    assert check_annotations_entry(entry, root=ROOT) == []

    dead = STATED[build][-1]
    _take_out(monkeypatch, dead)
    findings = check_annotations_entry(entry, root=ROOT)
    assert [f.rule for f in findings] == ["SL601"] and dead in findings[0].message


def test_an_attack_free_program_carries_no_attack_scope():
    net, state = make_handel(byz_params(nodes_down=0, threshold=60, byzantine_suicide=False))
    text = jax.jit(net.step).lower(state).as_text(debug_info=True)
    assert "witt.protocol_tick" in text
    assert not any(s in text for s in ATTACK_SCOPES.values())
