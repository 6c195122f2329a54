"""Unit tests for the width-bucket machinery in _agg_batched (the r4
program-size rewrite): bucket assignment, block views, assembly, and
dynamic-level low views must agree with the straightforward per-level
bit arithmetic they replaced."""

import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")

from wittgenstein_tpu.protocols._agg_batched import BitsetAggBase


class _Agg(BitsetAggBase):
    def msg_size(self, mtype: int) -> int:
        return 1


def make(n):
    a = _Agg()
    a._init_geometry(n)
    return a


def ref_block(x_int, l):
    """Level-l block of a python-int bitset: bits [2^(l-1), 2^l) -> [0, bs)."""
    bs = 1 << (l - 1)
    return (x_int >> bs) & ((1 << bs) - 1)


def rand_vec(rng, n_words):
    return rng.integers(0, 2**32, size=n_words, dtype=np.uint32)


def to_int(words):
    return sum(int(w) << (32 * i) for i, w in enumerate(np.asarray(words)))


def words_of(v, n_words):
    return np.array([(v >> (32 * i)) & 0xFFFFFFFF for i in range(n_words)], np.uint32)


@pytest.mark.parametrize("n", [64, 256, 4096])
def test_bucket_assignment(n):
    a = make(n)
    # buckets cover levels 1..L-1 exactly once, consecutively
    seen = [l for b in a.buckets for l in b.levels]
    assert seen == list(range(1, a.n_levels))
    for b in a.buckets:
        assert b.w_pad == max(a.w[l] for l in b.levels)
        # same width class: pad never exceeds 4x the smallest exact width
        assert all(b.w_pad <= 4 * a.w[l] for l in b.levels)


@pytest.mark.parametrize("n", [64, 1024, 4096])
def test_blocks_and_lows_match_reference_bits(n):
    a = make(n)
    rng = np.random.default_rng(7)
    x = np.stack([rand_vec(rng, a.n_words) for _ in range(5)])
    xi = [to_int(r) for r in x]
    xj = jnp.asarray(x)
    for i, b in enumerate(a.buckets):
        blocks = np.asarray(a._blocks(xj, b))
        lows = np.asarray(a._lows(xj, b))
        for j, l in enumerate(b.levels):
            bs = a.bs[l]
            for r in range(5):
                assert to_int(blocks[r, j]) == ref_block(xi[r], l), (n, l)
                assert to_int(lows[r, j]) == xi[r] & ((1 << bs) - 1), (n, l)
            # padding above the exact width is zero
            assert not blocks[:, j, a.w[l]:].any()
            assert not lows[:, j, a.w[l]:].any()


@pytest.mark.parametrize("n", [64, 1024])
def test_assemble_roundtrip(n):
    a = make(n)
    rng = np.random.default_rng(3)
    x = np.stack([rand_vec(rng, a.n_words) for _ in range(4)])
    xj = jnp.asarray(x)
    pieces = [a._blocks(xj, b) for b in a.buckets]
    back = np.asarray(a._assemble(xj, pieces))
    # bit 0 (level 0) preserved, level blocks round-trip; the XOR layout
    # covers every bit, so the whole vector must round-trip
    assert (back == x).all()


@pytest.mark.parametrize("n", [64, 1024])
def test_dyn_low_matches_static(n):
    a = make(n)
    rng = np.random.default_rng(11)
    rows = 6
    x = np.stack([rand_vec(rng, a.n_words) for _ in range(rows)])
    xj = jnp.asarray(x)
    for lv in range(1, a.n_levels):
        level = jnp.full(rows, lv, jnp.int32)
        for b in a.buckets:
            got = np.asarray(a._dyn_low(xj, level, b))
            if not (b.lo <= lv <= b.hi):
                continue  # rows outside the bucket carry junk by contract
            for r in range(rows):
                want = to_int(x[r]) & ((1 << a.bs[lv]) - 1)
                assert to_int(got[r]) == want, (n, lv, b)


def test_only_two_slots_can_be_due():
    """Delivery gathers just arrival slot (t mod D) + fresh instead of all
    D+1 — valid because slot = arrival mod D and a slot is due exactly at
    its arrival tick.  Run real Handel traffic and assert no OTHER slot is
    ever due."""
    import jax
    from jax import lax
    from wittgenstein_tpu.protocols.handel import HandelParameters
    from wittgenstein_tpu.protocols.handel_batched import make_handel

    n = 64
    net, state = make_handel(
        HandelParameters(
            node_count=n,
            threshold=n - 4,
            pairing_time=3,
            level_wait_time=20,
            extra_cycle=5,
            dissemination_period_ms=10,
            fast_path=10,
            nodes_down=0,
        )
    )
    a = net.protocol
    d = a.CHANNEL_DEPTH
    ss = d + 1

    def step_and_check(s, _):
        in_key, due_all, _tpl = a._advance_channel(s.proto["in_key"], s.time)
        due3 = due_all.reshape(n, a.n_levels - 1, ss)
        sidx = lax.rem(s.time, jnp.asarray(d, jnp.int32))
        allowed = (jnp.arange(ss) == sidx) | (jnp.arange(ss) == d)
        stray = jnp.any(due3 & ~allowed[None, None, :])
        return net.step(s), stray

    state, strays = lax.scan(step_and_check, state, None, length=600)
    assert not bool(jnp.any(strays))
    assert int(np.asarray(state.done_at).min()) > 0  # traffic actually ran


@pytest.mark.parametrize("proto_name", ["handel", "gsf", "handeleth2"])
def test_beat_gated_run_bit_identical_to_ungated(proto_name):
    """run_ms_batched's beat path (time loop outside vmap, real lax.cond
    around dissemination, send_ctr compensation on off-beat ticks) must be
    BIT-identical to the generic every-tick path — for every protocol
    declaring a beat structure."""
    from wittgenstein_tpu.engine import replicate_state

    n = 64
    if proto_name == "handel":
        from wittgenstein_tpu.protocols.handel import HandelParameters
        from wittgenstein_tpu.protocols.handel_batched import make_handel

        net, state = make_handel(
            HandelParameters(
                node_count=n,
                threshold=n - 4,
                pairing_time=3,
                level_wait_time=20,
                extra_cycle=5,
                dissemination_period_ms=10,
                fast_path=10,
                nodes_down=0,
            )
        )
    elif proto_name == "gsf":
        from wittgenstein_tpu.protocols.gsf import GSFSignatureParameters
        from wittgenstein_tpu.protocols.gsf_batched import make_gsf

        net, state = make_gsf(
            GSFSignatureParameters(
                node_count=n,
                threshold=n - 4,
                pairing_time=3,
                timeout_per_level_ms=20,
                period_duration_ms=10,
                nodes_down=0,
            )
        )
    else:  # handeleth2: BEAT_SEND_CALLS = P*(nl-1) compensation under test
        from wittgenstein_tpu.protocols.handeleth2 import HandelEth2Parameters
        from wittgenstein_tpu.protocols.handeleth2_batched import (
            make_handeleth2,
        )

        net, state = make_handeleth2(
            HandelEth2Parameters(
                node_count=32,
                pairing_time=3,
                level_wait_time=100,
                period_duration_ms=50,
                nodes_down=0,
            )
        )
    assert net.protocol.BEAT_PERIOD and len(net.protocol.BEAT_RESIDUES) == 1
    states = replicate_state(state, 4)
    gated = net.run_ms_batched(states, 400)

    saved = (net.protocol.BEAT_PERIOD, net.protocol.BEAT_RESIDUES)
    net.protocol.BEAT_PERIOD = None
    net.protocol.BEAT_RESIDUES = None
    try:
        # self is hashed by id in the jit cache; a fresh jit wrapper keys
        # the trace on the cleared attrs
        import jax

        ungated = jax.jit(lambda s: jax.vmap(lambda x: net.run_ms(x, 400))(s))(
            states
        )
    finally:
        net.protocol.BEAT_PERIOD, net.protocol.BEAT_RESIDUES = saved

    for a, b in zip(jax.tree_util.tree_leaves(gated), jax.tree_util.tree_leaves(ungated)):
        assert (np.asarray(a) == np.asarray(b)).all()
    if proto_name == "handeleth2":
        # no threshold/done in eth2 mode — prove traffic actually ran
        assert int(np.asarray(gated.msg_sent).sum()) > 0
    else:
        assert int(np.asarray(gated.done_at).min()) > 0, proto_name


def test_send_stacked_stores_receiver_space_content():
    """The channel holds content re-addressed into the RECEIVER's
    block-local space at send time (bit j -> j ^ r0, r0 = (to^from) &
    (2^(l-1)-1)); _arrived_blocks is then a pure view.  Checked via the
    fresh-backstop slot, which every ok send overwrites."""
    from wittgenstein_tpu.protocols.handel import HandelParameters
    from wittgenstein_tpu.protocols.handel_batched import make_handel

    n = 64
    net, state = make_handel(
        HandelParameters(node_count=n, threshold=n, nodes_down=0)
    )
    a = net.protocol
    ss = a.CHANNEL_DEPTH + 1
    rng = np.random.default_rng(5)
    recv, sender = 3, 41
    for l in range(1, a.n_levels):
        bs, w = a.bs[l], a.w[l]
        bi, b = next(
            (i, b) for i, b in enumerate(a.buckets) if b.lo <= l <= b.hi
        )
        content_int = int(rng.integers(1, 1 << min(60, bs)))
        content = [
            jnp.asarray(
                words_of(content_int, bb.w_pad).reshape(1, bb.w_pad)
            )
            for bb in a.buckets
        ]
        out = a._send_stacked(
            net,
            state,
            jnp.asarray([True]),
            jnp.asarray([sender], jnp.int32),
            jnp.asarray([recv], jnp.int32),
            jnp.asarray([l], jnp.int32),
            content,
        )
        got = np.asarray(a._arrived_blocks(out.proto, bi))
        li = l - b.lo
        fresh = to_int(got[recv, li, ss - 1, :w])
        r0 = (recv ^ sender) & (bs - 1)
        want = 0
        for bit in range(bs):
            if (content_int >> bit) & 1:
                want |= 1 << (bit ^ r0)
        assert fresh == want, (l, r0)


# -- the send path's two entries (PR 32) ----------------------------------
# Level as an axis ([N, L-1, k] rows, each bucket's [N, nl, w_pad] blocks)
# against level as data (the same rows flattened, every bucket's content
# padded to all M rows, as the call sites built it until PR 32): the same
# state, leaf for leaf, from the same sends.


def _flat_send_args(a, mask, frm, to, level, blocks, aux):
    """The level-axis arguments as the dynamic entry takes them: [M]
    vectors in axis order and, per bucket, a zero plane over all L-1
    levels with the bucket's own blocks written in."""
    n, nlv, k = mask.shape
    flat = lambda x: jnp.broadcast_to(x, mask.shape).reshape(-1)
    content = []
    for b, lows in zip(a.buckets, blocks):
        full = jnp.zeros((n, nlv, b.w_pad), jnp.uint32)
        full = full.at[:, b.lo - 1 : b.hi, :].set(lows)
        content.append(
            jnp.broadcast_to(full[:, :, None, :], (n, nlv, k, b.w_pad)).reshape(-1, b.w_pad)
        )
    return (
        flat(mask), flat(frm), flat(to), flat(level), content,
        None if aux is None else flat(aux),
    )


def _random_send(a, rng, k, density, crowd=False):
    """A send on the level axis: every (node, level, c) row offers to a
    random level-l peer, or (crowd) a whole half-block to ONE receiver, so
    that slots are contested, winners displace and the fresh slot has
    many takers."""
    n, nlv = a.n_nodes, a.n_levels - 1
    ids = np.arange(n, dtype=np.int32)
    bs = a.lv_bs[None, :, None]
    if crowd:
        off = np.broadcast_to(ids[:, None, None] & (bs - 1), (n, nlv, k))
    else:
        off = rng.integers(0, 1 << 30, size=(n, nlv, k)) & (bs - 1)
    rel = (bs + off).astype(np.int32)
    mask = rng.random((n, nlv, k)) < density
    havings = jnp.asarray(rng.integers(0, 2**32, size=(n, a.n_words), dtype=np.uint32))
    return (
        jnp.asarray(mask),
        jnp.asarray(ids[:, None, None]),
        jnp.asarray(ids[:, None, None] ^ rel),
        jnp.asarray(np.arange(1, a.n_levels, dtype=np.int32)[None, :, None]),
        [a._lows(havings, b) for b in a.buckets],
        havings,  # the senders' full-width vectors the blocks are cut from
    )


def _handel(n, **kw):
    from wittgenstein_tpu.protocols.handel import HandelParameters
    from wittgenstein_tpu.protocols.handel_batched import make_handel

    return make_handel(HandelParameters(node_count=n, threshold=n // 2, **kw))


def _gsf(n, **kw):
    from wittgenstein_tpu.protocols.gsf import GSFSignatureParameters
    from wittgenstein_tpu.protocols.gsf_batched import make_gsf

    return make_gsf(GSFSignatureParameters(node_count=n, threshold=n // 2, **kw))


SEND_CASES = {
    # name: (factory, k, mask density, crowd, sends in a row)
    "handel-64-k1": (lambda: _handel(64, nodes_down=0), 1, 0.6, False, 1),
    "handel-256-k1": (lambda: _handel(256, nodes_down=0), 1, 0.6, False, 1),
    "gsf-64-k1-aux": (lambda: _gsf(64, nodes_down=0), 1, 0.6, False, 1),
    "gsf-256-k1-aux": (lambda: _gsf(256, nodes_down=0), 1, 0.6, False, 1),
    "gsf-64-k10-aux": (lambda: _gsf(64, nodes_down=0), 10, 0.5, False, 1),
    "gsf-256-k10-aux": (lambda: _gsf(256, nodes_down=0), 10, 0.5, False, 1),
    "handel-64-mask-all-false": (lambda: _handel(64, nodes_down=0), 1, 0.0, False, 1),
    "handel-256-crowded-cells": (lambda: _handel(256, nodes_down=0), 1, 0.9, True, 3),
    "gsf-64-k10-crowded-cells": (lambda: _gsf(64, nodes_down=0), 10, 0.9, True, 3),
    "handel-64-nodes-down": (lambda: _handel(64, nodes_down=16), 1, 0.7, False, 2),
    "gsf-64-k10-nodes-down": (lambda: _gsf(64, nodes_down=16), 10, 0.7, False, 2),
}


@pytest.mark.parametrize("form", ["blocks", "words"])
@pytest.mark.parametrize("case", sorted(SEND_CASES))
def test_level_axis_send_equals_the_flattened_send(case, form):
    """The level axis takes a bucket's `_lows` blocks (the beats' form:
    the whole-M_i body, whatever k) or the senders' full-width words
    [N, W] (GSF's accelerated calls: with k > 1 the firing rows' arrivals
    and claim and the landing rows' commit, PR 42 and PR 49; with k = 1
    the blocks are cut from them and the body is the beats')."""
    import jax

    factory, k, density, crowd, sends = SEND_CASES[case]
    net, state = factory()
    a = net.protocol
    k = getattr(a.params, "accelerated_calls_count", k) if k > 1 else k
    has_aux = "in_aux" in state.proto
    rng = np.random.default_rng(len(case))
    by_axis = by_data = state
    for j in range(sends):
        mask, frm, to, level, blocks, words = _random_send(a, rng, k, density, crowd)
        aux = jnp.asarray(rng.integers(0, 99, size=(a.n_nodes, 1, 1)), jnp.int32) if has_aux else None
        # later sends leave earlier: their arrivals evict pending occupants
        at = jnp.int32(2 * (sends - 1 - j))
        by_axis = a._send_stacked(
            net, by_axis._replace(time=at), mask, frm, to, None,
            words if form == "words" else blocks, aux=aux,
        )
        m, f, t, l, content, x = _flat_send_args(a, mask, frm, to, level, blocks, aux)
        by_data = a._send_stacked(net, by_data._replace(time=at), m, f, t, l, content, aux=x)

    paths = [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_leaves_with_path(by_axis)]
    for name in ["in_key", "displaced"] + [f"in_sig{i}" for i in range(len(a.buckets))]:
        assert any(name in p for p in paths), name
    for path, x, y in zip(
        paths, jax.tree_util.tree_leaves(by_axis), jax.tree_util.tree_leaves(by_data)
    ):
        # the work census counts the rows that fired and landed of a k > 1
        # send of words, which the flat entry (the whole-M body) does not
        # number (PR 42, PR 49)
        if ".census" not in path:
            assert (np.asarray(x) == np.asarray(y)).all(), (case, form, path)
    listed = k > 1 and form == "words"
    assert (int(by_axis.census.fired_rows) > 0) == listed
    assert (int(by_axis.census.landed_rows) > 0) == listed
    assert int(by_axis.census.landed_rows) <= int(by_axis.census.fired_rows)

    moved = int(np.asarray(by_axis.msg_received).sum())
    assert (moved > 0) == (density > 0)
    if density > 0:
        # content landed, and not only in the fresh slot's column
        assert any(np.asarray(by_axis.proto[f"in_sig{i}"]).any() for i in range(len(a.buckets)))
    if crowd:
        assert int(by_axis.proto["displaced"]) > 0
    if "nodes-down" in case:
        assert int(np.asarray(by_axis.proto["sent_not_ok"]).sum()) > 0
    if has_aux and density > 0:
        assert np.asarray(by_axis.proto["in_aux"]).any()


def test_level_axis_send_numbers_its_own_levels():
    """On the level axis a row's level is its position: a level argument
    beside it (another numbering would give arrivals and the claim one
    level and the content rows another) is refused, as is an axis that is
    not the protocol's L-1 levels."""
    net, state = _handel(64, nodes_down=0)
    a = net.protocol
    mask, frm, to, level, blocks, _words = _random_send(a, np.random.default_rng(0), 1, 0.5)
    with pytest.raises(ValueError, match="numbers its own levels"):
        a._send_stacked(net, state, mask, frm, to, level, blocks)
    with pytest.raises(ValueError, match="numbers its own levels"):
        a._send_stacked(net, state, mask[:, 1:], frm, to[:, 1:], None, blocks)
