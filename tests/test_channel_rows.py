"""The channel send path's row sets (PR 32, PR 38): whole-run pins taken
on the parent of the change that cut the commit scatters to each bucket's
own rows, the lowered programs' scatter update counts, and the
sender-rows entry's commit over the rows that land against the whole-M
commit of the same send.

The pins are a position-weighted 32-bit checksum of EVERY state leaf the
parent has after 300 simulated ms at 256 nodes (the benchmark's
fingerprint, in numpy): the level-axis entry of `_send_stacked` only
drops updates that were addressed to the dropped row, and the
sender-rows entry (Handel's fast path) commits the landing rows alone,
so no leaf may move by a bit.  The two counters PR 38 put beside
`displaced` are left out by name.

Since PR 42 arrivals and the claim of the two every-tick sends (Handel's
fast path, GSF's accelerated calls) run over the rows that FIRE,
`firing_capacity` of them a round (`_send_fired`): held here against the
whole-M body the flat entry keeps, state for state, with the capacity
patched small so that a send takes many rounds, and in the lowered
programs' scatter update counts.

Since PR 49 the level-axis every-tick send (GSF's accelerated calls) takes
the senders' full-width words and commits the rows that land as Handel's
fast path does, `firing_capacity(rows)` of them a round: the cases of the
landing commit, its census and its lowered scatters run over both.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from wittgenstein_tpu.protocols.gsf import GSFSignatureParameters
from wittgenstein_tpu.protocols.gsf_batched import make_gsf
from wittgenstein_tpu.protocols.handel import HandelParameters
from wittgenstein_tpu.protocols.handel_batched import BatchedHandel, make_handel

N = 256


LANDING_COUNTERS = ("commit_rounds", "landing_peak")  # PR 38's, Handel's alone
# leaves the pins' parents lacked: those, and the work census (PR 41,
# engine.core.Census: `.census.steps` and its siblings)
UNPINNED = LANDING_COUNTERS + ("census",)


def _assert_same_state(got, want, note, but=UNPINNED):
    for (path, x), y in zip(
        jax.tree_util.tree_leaves_with_path(got), jax.tree_util.tree_leaves(want)
    ):
        path = jax.tree_util.keystr(path)
        if not any(name in path for name in but):
            assert (np.asarray(x) == np.asarray(y)).all(), (note, path)


def checksum(tree) -> int:
    """One number for a whole state: per leaf the sum of its words times
    a position weight (mod 2^32), the leaves' sums weighted again; over
    the leaves the pins' parent had."""
    total = 0
    leaves = [
        leaf
        for path, leaf in jax.tree_util.tree_leaves_with_path(tree)
        if not any(name in jax.tree_util.keystr(path) for name in UNPINNED)
    ]
    for j, leaf in enumerate(leaves):
        x = np.asarray(leaf)
        if x.dtype == np.bool_:
            x = x.astype(np.uint8)
        u = x.reshape(-1).view(f"uint{8 * x.dtype.itemsize}").astype(np.uint64)
        w = (np.arange(u.size, dtype=np.uint64) * 2654435761 + 1) & 0xFFFFFFFF
        leaf_sum = int(np.sum((u & 0xFFFFFFFF) * w % (1 << 32)) % (1 << 32))
        total = (total + leaf_sum * (2 * j + 1)) % (1 << 32)
    return total


def handel_params(**kw):
    base = dict(
        node_count=N, threshold=int(N * 0.99), pairing_time=3, level_wait_time=50,
        extra_cycle=10, dissemination_period_ms=10, fast_path=10, nodes_down=0,
    )
    base.update(kw)
    return HandelParameters(**base)


def gsf_params(**kw):
    base = dict(
        node_count=N, threshold=int(N * 0.99), pairing_time=3, timeout_per_level_ms=50,
        period_duration_ms=10, accelerated_calls_count=10, nodes_down=0,
    )
    base.update(kw)
    return GSFSignatureParameters(**base)


def _handel_fused():
    return make_handel(handel_params(), fuse_step=True)


def _handel_byz():
    return make_handel(
        handel_params(
            nodes_down=51, threshold=int(N * 0.8 * 0.99), pairing_time=4,
            dissemination_period_ms=20, byzantine_suicide=True,
        )
    )


def _gsf():
    return make_gsf(gsf_params())


def _node_mesh(net, state):
    from wittgenstein_tpu.parallel import enable_node_sharding, shard_state_by_node

    mesh = Mesh(np.array(jax.devices()[:2]), ("nodes",))
    net = enable_node_sharding(net, mesh)
    return net, shard_state_by_node(net, state, mesh)


def _handel_node_mesh():
    return _node_mesh(*make_handel(handel_params()))


def _gsf_node_mesh():
    """The accelerated calls hand the senders' words (PR 49): under a node
    mesh the send cuts them to block stacks and keeps the whole-M body."""
    return _node_mesh(*_gsf())


# taken on the parent (commit 913a72b, PR 31), before the change; the
# Handel ones again at commit 96b30ec (PR 32) with the carried score
# caches, which every Handel state has held since PR 33 (they were
# 4133656018 and 3555050404 over the trees without those four leaves)
PINS = {
    "handel_fused": (_handel_fused, 3838452459),
    "handel_byz51": (_handel_byz, 882315166),
    "gsf": (_gsf, 999241418),
    "handel_node_mesh2": (_handel_node_mesh, 3838452459),
    "gsf_node_mesh2": (_gsf_node_mesh, 999241418),
}


@pytest.mark.parametrize("name", sorted(PINS))
def test_whole_run_checksum_is_the_parents(name):
    build, want = PINS[name]
    net, state = build()
    out = net.run_ms(state, 300)
    assert int(np.asarray(out.msg_received).sum()) > 0  # traffic ran
    assert checksum(out) == want, (name, checksum(out))
    # the fast path ran its rounds over landing rows, but for the node
    # mesh, whose exchange keeps all M rows
    ran = "node_mesh" not in name
    if name.startswith("gsf"):
        assert (int(out.census.landed_rows) > 0) == ran, name
    for counter in LANDING_COUNTERS if name.startswith("handel") else ():
        assert (int(out.proto[counter]) > 0) == ran, (name, counter)


# -- what the lowered programs scatter --------------------------------------
# The counter that says the mechanism engaged is static: the update
# operands of the scatters into the in_sig planes, read from StableHLO
# (no chip).  A commit pass costs 4.8-4.95 ns a word update on a v5e
# (PERF.md section 5), so these counts are the commit's time.

_SCATTER = re.compile(
    r'"stablehlo\.scatter"\(.*?\}\) : '
    r"\(tensor<([^>]+)>, tensor<[^>]+>, tensor<([^>]+)>\) ->",
    re.S,
)
# a scatter's indentation and operand: the ops of a `stablehlo.while`'s
# regions are printed deeper than the function's body
_SCATTER_LINE = re.compile(
    r'^( +)%\S+ = "stablehlo\.scatter"\(.*?\}\) : \(tensor<([^>]+)>', re.S | re.M
)
# stablehlo.gather ops in the lowered GSF tick at 256 nodes: 82 at the
# parent of PR 32 (commit 913a72b, PR 31; the cut is by reshape and slice,
# an index array would add to these) and since PR 42 the reads of the
# firing rows besides (sender, receiver and level of a round's rows in
# `arrive`, receiver, level and aux in `claim`: 10 by this count, which
# finds the generic form's name twice an op): 92 until PR 49, whose commit
# round reads its landing rows' receiver and their senders' full-width
# words (4 by this count; a row's sender and level are arithmetic on its
# number, its slot and win bits come with the landing list)
GSF_TICK_GATHERS = 96


def _dims(tensor: str):
    return tuple(int(d) for d in tensor.split("x")[:-1])


def plane_updates(text, a, state):
    """Per bucket, the word updates of every scatter whose operand is
    that bucket's in_sig plane (its shape is no other leaf's)."""
    found = {i: [] for i in range(len(a.buckets))}
    for operand, updates in _SCATTER.findall(text):
        for i in found:
            shape = tuple(state.proto[f"in_sig{i}"].shape)
            if operand.endswith("ui32") and _dims(operand) == shape:
                found[i].append(int(np.prod(_dims(updates))))
    return found


def _geometry(n: int):
    from wittgenstein_tpu.protocols._agg_batched import BitsetAggBase

    a = BitsetAggBase.__new__(BitsetAggBase)
    a._init_geometry(n)
    return a


def commit_updates(n: int, k: int, level_axis: bool) -> int:
    """Word updates of one send's two commit passes, from shapes alone:
    every bucket over all M = N x (L-1) x k rows at its w_pad, or over its
    own levels' rows."""
    a = _geometry(n)
    levels = a.n_levels - 1
    per_row = [(b.nl if level_axis else levels) * b.w_pad for b in a.buckets]
    return 2 * n * k * sum(per_row)


def round_updates(n: int, rows: int) -> int:
    """Word updates of ONE round of an every-tick send's two commit passes:
    every bucket over `rows` rows at its w_pad (the sender-rows send: the
    whole M before PR 38 and the landing capacity since; the level-axis
    one: the firing capacity since PR 49)."""
    return 2 * rows * sum(b.w_pad for b in _geometry(n).buckets)


@pytest.mark.parametrize(
    "send, n, k, before, after, a_round",
    [
        ("gsf-2048 accelerated calls, every tick", 2048, 10, 28_385_280, 2_785_280, 129_024),
        ("gsf-2048 dissemination, 1 tick in 10", 2048, 1, 2_838_528, 278_528, None),
        ("handel-4096 dissemination, 1 tick in 10", 4096, 1, 12_484_608, 1_081_344, None),
    ],
)
def test_commit_update_counts_from_shapes(send, n, k, before, after, a_round):
    """The benchmark's sends, word updates a send (both passes), before
    PR 32 and since: 10.2x, 10.2x and 11.5x fewer.  The every-tick one
    commits the rows that land since PR 49: 2 x 1024 x 63 word updates a
    round, 21.6x fewer again, and as many rounds as the landing rows take
    (one where any row lands, none where none does)."""
    from wittgenstein_tpu.protocols._agg_batched import firing_capacity

    assert commit_updates(n, k, level_axis=False) == before, send
    assert commit_updates(n, k, level_axis=True) == after, send
    if a_round is not None:
        c = firing_capacity((n, _geometry(n).n_levels - 1, k))
        assert c == 1024 and round_updates(n, c) == a_round, send


def test_fast_path_update_counts_from_shapes():
    """Handel-4096's fast path, every tick, level a per-node register:
    2 x 20,480 x 127 = 5,201,920 word updates a tick until PR 38; since,
    2 x 1,536 x 127 = 390,144 a round, 13.3x fewer, and as many rounds as
    the landing rows take (one on every tick of the measured runs, none
    where nothing lands)."""
    from wittgenstein_tpu.protocols._agg_batched import landing_capacity

    m = 4096 * 5
    assert round_updates(4096, m) == 5_201_920
    assert landing_capacity(m) == 1536
    assert round_updates(4096, landing_capacity(m)) == 390_144


@pytest.mark.parametrize("n", [256, 2048])
def test_a_landing_rows_low_block_is_the_static_cut_of_its_level(n):
    """What a commit round reads (PR 49): `_dyn_low(words[s], l, b)`, the
    low block of a row's sender cut at the row's level, against the block
    stack the level axis carried until then, `_lows(words, b)[s, l - b.lo]`,
    word for word at every level: the sub-word ones (bits [0, 2^(l-1)) of
    word 0), level 6's full word, and each wider level's own bucket."""
    a = _geometry(n)
    rng = np.random.default_rng(n)
    words = jnp.asarray(rng.integers(0, 2**32, size=(n, a.n_words), dtype=np.uint32))
    words = words.at[0].set(0xFFFFFFFF)  # every bit above a block's width is cut
    senders = jnp.asarray(np.r_[0, rng.integers(0, n, size=63)], jnp.int32)
    levels_seen = 0
    for b in a.buckets:
        stack = np.asarray(a._lows(words, b))  # [N, nl, w_pad]
        for l in b.levels:
            level = jnp.full(senders.shape, l, jnp.int32)
            got = np.asarray(a._dyn_low(words[senders], level, b))
            want = stack[np.asarray(senders), l - b.lo]
            assert got.shape == want.shape == (64, b.w_pad)
            assert (got == want).all(), (n, l, b)
            assert got[0, 0] == (0xFFFFFFFF if a.bs[l] >= 32 else (1 << a.bs[l]) - 1)
            levels_seen += 1
    assert levels_seen == a.n_levels - 1 and a.w[6] == 1 and a.bs[6] == 32


def _lowered(net, state, hook):
    fn = getattr(net.protocol, hook)
    return jax.jit(lambda s: fn(net, s)).lower(state).as_text()


@pytest.mark.parametrize(
    "name, build, hook, k",
    [
        ("gsf beat", _gsf, "tick_beat", 1),
        ("handel beat", _handel_fused, "tick_beat", 1),
    ],
)
def test_a_static_level_send_scatters_only_its_buckets_rows(name, build, hook, k):
    """The dissemination beats (most rows firing, one tick in a period):
    two scatters a bucket over its own M_i rows."""
    net, state = build()
    a = net.protocol
    text = _lowered(net, state, hook)
    found = plane_updates(text, a, state)
    for i, b in enumerate(a.buckets):
        assert len(found[i]) == 2, (name, i, found)  # winner pass, fresh pass
        assert max(found[i]) <= N * b.nl * k * b.w_pad, (name, i, found)
    widths = sum(a.w[1:])
    assert sum(sum(v) for v in found.values()) == 2 * N * k * widths
    assert 2 * N * k * widths == commit_updates(N, k, level_axis=True)


@pytest.mark.parametrize("name, build", [("handel tick", _handel_fused), ("gsf tick", _gsf)])
def test_the_fast_path_scatters_one_round_of_landing_rows(name, build):
    """The two every-tick sends.  Handel's (PR 38, ROADMAP A1 (b)(1)): its
    level is a per-node register, so the claim's winners are compacted
    and every scatter into an in_sig plane carries C = landing_capacity(M)
    rows at the bucket's w_pad, inside the rounds' loop; none carries the
    M = N x ceil(fast_path / 2) rows of the send.  GSF's accelerated calls
    (PR 49), on the [N, L-1, k] level axis: the same list and the same
    loop at C = firing_capacity(rows); none carries a bucket's
    M_i = N x nl x k rows, and nothing is scattered over the M rows of the
    send (the winners are no longer expanded back onto them)."""
    from wittgenstein_tpu.protocols._agg_batched import firing_capacity, landing_capacity

    net, state = build()
    a = net.protocol
    text = _lowered(net, state, "tick")
    found = plane_updates(text, a, state)
    if name == "handel tick":
        m = N * ((a.params.fast_path + 1) // 2)
        c = landing_capacity(m)
        refused = {m}
    else:
        k = a.params.accelerated_calls_count
        m = N * (a.n_levels - 1) * k
        c = firing_capacity((N, a.n_levels - 1, k))
        refused = {m} | {N * b.nl * k for b in a.buckets}
    assert c < m / 8
    for i, b in enumerate(a.buckets):
        assert found[i] == [c * b.w_pad] * 2, (name, i, found)
    assert sum(sum(v) for v in found.values()) == round_updates(N, c)
    # the rounds: a trip count that is data, and the planes' scatters inside
    assert text.count("stablehlo.while") >= 3  # arrive's rounds, claim's rounds, the commit's
    planes = {tuple(state.proto[f"in_sig{i}"].shape) for i in range(len(a.buckets))}
    inside = [
        len(indent) > 4  # deeper than the function's body: in a loop's region
        for indent, operand in _SCATTER_LINE.findall(text)
        if operand.endswith("ui32") and _dims(operand) in planes
    ]
    assert len(inside) == 2 * len(a.buckets) and all(inside), (name, inside)
    for _operand, updates in _SCATTER.findall(text):
        if name == "handel tick":
            assert _dims(updates)[0] != m or len(_dims(updates)) == 1, updates
        else:
            assert _dims(updates)[0] not in refused, updates
    if name == "gsf tick":
        gathers = len(re.findall(r"stablehlo\.(?:dynamic_)?gather", text))
        assert gathers <= GSF_TICK_GATHERS


# -- the commit over the rows that land (PR 38) ------------------------------
# The sender-rows entry against the whole-M commit of the same send: the
# flat entry, which every bucket still carries all M rows through, takes
# the rows one by one with their low blocks cut beforehand.


def _sender_rows_send(a, rng, r, density, crowd):
    """A send as Handel's fast path makes it: every node offers its low
    block of ITS level to r peers of that level, or (crowd) every node of
    a half-block to ONE receiver, so that slots are contested and few
    rows land."""
    n = a.n_nodes
    ids = np.arange(n, dtype=np.int32)
    level = rng.integers(1, a.n_levels, size=n).astype(np.int32)
    bs = a.lv_bs[level - 1][:, None]
    if crowd:
        off = np.broadcast_to(ids[:, None] & (bs - 1), (n, r))
    else:
        off = rng.integers(0, 1 << 30, size=(n, r)) & (bs - 1)
    rel = (bs + off).astype(np.int32)
    mask = rng.random((n, r)) < density
    words = rng.integers(0, 2**32, size=(n, a.n_words), dtype=np.uint32)
    return (
        jnp.asarray(mask), jnp.asarray(ids[:, None]), jnp.asarray(ids[:, None] ^ rel),
        jnp.asarray(level), jnp.asarray(words),
    )


def _flat(a, mask, frm, to, level, words, aux):
    """The same send a row each, as the flat entry takes it."""
    n, r = mask.shape
    flat = lambda x: jnp.broadcast_to(x, (n, r)).reshape(-1)  # noqa: E731
    content = [jnp.repeat(a._dyn_low(words, level, b), r, axis=0) for b in a.buckets]
    return (
        flat(mask), flat(frm), flat(to), flat(level[:, None]), content,
        None if aux is None else flat(aux),
    )


def _gsf_small():
    return make_gsf(gsf_params(node_count=64, threshold=32))


LANDED_BUILDS = {"honest": _handel_fused, "byz51": _handel_byz, "gsf-aux": _gsf_small}
# rows a round: 1 and 3 take many rounds, None is the send's own C, "M" one
# round over every row; the spread send lands hundreds of rows, more than
# its C = 128 (several rounds there too), the crowded ones few, with
# displacements and evictions
LANDED_CASES = [
    (build, cap, traffic)
    for build in sorted(LANDED_BUILDS)
    for cap in (1, 3, None, "M")
    for traffic in ("spread", "crowd", "none")
    if cap in (None, "M") or traffic != "spread"  # hundreds of rounds of 1 or 3
]


def _patched_capacity(monkeypatch, a, cap, r=5):
    """Rows a round for the case: `cap` to the internal helper through the
    one function it asks, which reads the send's shape and nothing else."""
    from wittgenstein_tpu.protocols import _agg_batched

    m = a.n_nodes * r
    own = _agg_batched.landing_capacity(m)
    capacity = {None: own, "M": m}.get(cap, cap)
    monkeypatch.setattr(_agg_batched, "landing_capacity", lambda rows: capacity)
    return own, capacity


def _send_both_ways(build, cap, traffic, monkeypatch):
    net, state = LANDED_BUILDS[build]()
    a = net.protocol
    _patched_capacity(monkeypatch, a, cap)
    rng = np.random.default_rng(0)
    has_aux = "in_aux" in state.proto
    landed = whole = state
    sends = 3 if traffic == "crowd" else 1
    density = {"spread": 0.6, "crowd": 0.9, "none": 0.0}[traffic]
    for j in range(sends):
        args = _sender_rows_send(a, rng, 5, density, traffic == "crowd")
        aux = jnp.asarray(rng.integers(0, 99, size=(a.n_nodes, 1)), jnp.int32) if has_aux else None
        # later sends leave earlier: their arrivals evict pending occupants
        at = jnp.int32(2 * (sends - 1 - j))
        landed = a._send_stacked(net, landed._replace(time=at), *args, aux=aux)
        *flat, x = _flat(a, *args, aux)
        whole = a._send_stacked(net, whole._replace(time=at), *flat, aux=x)
    return a, landed, whole


@pytest.mark.parametrize("build, cap, traffic", LANDED_CASES)
def test_the_landing_rows_commit_equals_the_whole_send(build, cap, traffic, monkeypatch):
    a, landed, whole = _send_both_ways(build, cap, traffic, monkeypatch)
    names = ["in_key", "displaced"] + [f"in_sig{i}" for i in range(len(a.buckets))]
    names += ["in_aux"] if "in_aux" in whole.proto else []
    paths = [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_leaves_with_path(whole)]
    for name in names:
        assert any(name in p for p in paths), name
    _assert_same_state(landed, whole, (build, cap, traffic))
    moved = int(np.asarray(whole.msg_received).sum())
    assert (moved > 0) == (traffic != "none")
    if traffic != "none":
        assert any(np.asarray(whole.proto[f"in_sig{i}"]).any() for i in range(len(a.buckets)))
    if traffic == "crowd":
        assert int(whole.proto["displaced"]) > 0
    if "in_aux" in whole.proto and traffic != "none":
        assert np.asarray(whole.proto["in_aux"]).any()


def _landing(monkeypatch, a, args, **kw) -> int:
    """Rows of a send that the claim lets land: the row numbers
    `_send_fired` lists for the commit (below M; M marks the list's
    tail), which are as many as it counted."""
    from wittgenstein_tpu.protocols._agg_batched import BitsetAggBase

    seen = []
    real = BitsetAggBase._commit_landed

    def spy(self, sigs, words, frm, to_idx, level, land_rows, land_info, landing, *rest):
        listed = np.asarray(land_rows)
        listed = listed[listed < to_idx.shape[0]]
        assert len(set(listed.tolist())) == listed.size == int(landing)
        seen.append(listed.size)
        return real(self, sigs, words, frm, to_idx, level, land_rows, land_info, landing, *rest)

    with monkeypatch.context() as patch:
        patch.setattr(BitsetAggBase, "_commit_landed", spy)
        a._send_stacked(*args, **kw)
    (landing,) = seen
    return landing


@pytest.mark.parametrize("build", ["honest", "byz51"])
@pytest.mark.parametrize("cap", [1, 3, None, "M"])
@pytest.mark.parametrize("traffic", ["dense", "none"])
def test_the_counters_read_the_rounds_and_the_landing_rows(build, cap, traffic, monkeypatch):
    """One send from a fresh state: `commit_rounds` reads ceil(landing /
    capacity) and `landing_peak` the landing count, which the dense send
    puts above the send's own C (many rounds at every capacity but M)."""
    net, state = LANDED_BUILDS[build]()
    a = net.protocol
    own, capacity = _patched_capacity(monkeypatch, a, cap)
    density = {"dense": 0.9, "none": 0.0}[traffic]
    args = _sender_rows_send(a, np.random.default_rng(1), 5, density, False)
    landing = _landing(monkeypatch, a, (net, state, *args))
    out = a._send_stacked(net, state, *args)
    assert int(out.proto["landing_peak"]) == landing
    assert int(out.proto["commit_rounds"]) == -(-landing // capacity)
    if traffic == "dense":
        assert landing > own  # a tick built so that more than C rows land
        assert (int(out.proto["commit_rounds"]) > 1) == (cap != "M")
    else:
        assert landing == 0 and int(out.proto["commit_rounds"]) == 0
    # a second, empty send adds no round and keeps the high-water mark
    none = _sender_rows_send(a, np.random.default_rng(2), 5, 0.0, False)
    again = a._send_stacked(net, out._replace(time=jnp.int32(3)), *none)
    for counter in LANDING_COUNTERS:
        assert int(again.proto[counter]) == int(out.proto[counter])


def _commit_capacity(monkeypatch, a, entry, cap):
    """Rows a round of the entry's landed commit for the case, patched
    through the function `_send_stacked` asks: `landing_capacity(M)` for
    rows by sender, `firing_capacity(rows)` on the level axis (there the
    firing rounds carry as many).  Returns (the send's own, the case's)."""
    from wittgenstein_tpu.protocols import _agg_batched

    if entry == "sender":
        return _patched_capacity(monkeypatch, a, cap)
    rows = (a.n_nodes, a.n_levels - 1, a.params.accelerated_calls_count)
    own = _agg_batched.firing_capacity(rows)
    _patched_firing(monkeypatch, cap)
    return own, own if cap is None else cap


def _every_tick_send(a, entry, seed, density):
    """(args, aux) of the entry's every-tick send, as the protocol makes it."""
    has_aux = entry == "axis"  # GSF's sends carry k_new beside the key
    return _fired_send(a, entry, np.random.default_rng(seed), density, False, has_aux)[0]


@pytest.mark.parametrize("build", ["honest", "byz51", "gsf"])
@pytest.mark.parametrize("cap", [3, None])
def test_rounds_under_vmap_equal_the_single_runs(build, cap, monkeypatch):
    """Two rows with different landing counts (one lands nothing at
    all): the batched loop runs until the slower row is through and each
    row's planes and counters equal its single run's."""
    factory, entry = FIRED_BUILDS[build]
    net, state = factory()
    a = net.protocol
    _commit_capacity(monkeypatch, a, entry, cap)
    # at 3 rows a round the level axis's firing rounds are 3 rows too: fewer fire
    busy_share, few_share = (0.02, 0.002) if (cap, entry) == (3, "axis") else (0.9, 0.01)
    busy = _every_tick_send(a, entry, 3, busy_share)
    quiet = _every_tick_send(a, entry, 4, 0.0)
    few = _every_tick_send(a, entry, 5, few_share)
    for pair in ((busy, quiet), (few, busy)):
        stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *pair)
        states = jax.tree_util.tree_map(lambda x: jnp.stack([x, x]), state)
        out = jax.vmap(lambda s, args, aux: a._send_stacked(net, s, *args, aux=aux))(states, *stacked)
        singles = [a._send_stacked(net, state, *args, aux=aux) for args, aux in pair]
        landed = [int(single.census.landed_rows) for single in singles]
        assert landed[0] != landed[1] and min(landed) in (0, landed[0])
        if entry == "sender":
            assert int(singles[0].proto["commit_rounds"]) != int(singles[1].proto["commit_rounds"])
        else:
            extra = [int(single.census.extra_commit_rounds) for single in singles]
            assert extra[0] != extra[1]
        for j, single in enumerate(singles):
            _assert_same_state(jax.tree_util.tree_map(lambda x: x[j], out), single, j, but=())


# -- the work census of the sender-rows send (PR 41) -------------------------
# `census.landed_rows` and `census.extra_commit_rounds` (engine.core.Census)
# are written where `landing_peak` and `commit_rounds` are, and reach
# `run_cache_info()` through the run cache (tests/test_work_census.py).


@pytest.mark.parametrize("build", ["honest", "byz51", "gsf"])
@pytest.mark.parametrize("cap", [3, None])
def test_the_census_sums_the_landing_rows_and_the_rounds_past_the_first(build, cap, monkeypatch):
    """Three sends in a row, the landing rows of each counted from outside
    (`winner | fresh_win` as the commit is handed them): the census holds
    their sum, and of the rounds those past each send's first: some with
    3 rows a round, none at the send's own capacity.  Both every-tick
    sends write the two slots (PR 49: GSF's accelerated calls too, whose
    state has no `commit_rounds` leaf: the census slots are its counters)."""
    factory, entry = FIRED_BUILDS[build]
    net, state = factory()
    a = net.protocol
    own, capacity = _commit_capacity(monkeypatch, a, entry, cap)
    landings, fired = [], 0
    # the level axis has 12 rows where the sender rows have one
    densities = (0.05, 0.0, 0.03) if entry == "sender" else (0.006, 0.0, 0.004)
    for j, density in enumerate(densities):
        args, aux = _every_tick_send(a, entry, 10 + j, density)
        fired += int(np.asarray(args[0]).sum())
        state = state._replace(time=jnp.int32(2 * j))
        landings.append(_landing(monkeypatch, a, (net, state, *args), aux=aux))
        state = a._send_stacked(net, state, *args, aux=aux)
    assert landings[0] > 3 and landings[1] == 0 and max(landings) <= own
    assert int(state.census.landed_rows) == sum(landings)
    # a row lands only if it fired
    assert sum(landings) <= int(state.census.fired_rows) == fired
    extra = sum(max(-(-n // capacity) - 1, 0) for n in landings)
    assert int(state.census.extra_commit_rounds) == extra
    assert (extra > 0) == (cap == 3)
    if entry == "sender":
        assert int(state.proto["commit_rounds"]) == sum(-(-n // capacity) for n in landings)
        assert a.census_limits()["landing_peak"] == own
    else:
        assert not set(LANDING_COUNTERS) & set(state.proto)
        assert a.census_limits()["firing_peak"] == own


def test_a_whole_handel_run_counts_a_step_a_tick_and_what_landed_on_it():
    """256 nodes tick by tick: a step a millisecond, the landing rows of a
    tick (the census's growth) are what `landing_peak` keeps the most of,
    a tick on which rows land takes its one round, and none takes two."""
    net, state = _handel_fused()
    landed, rounds = [], []
    for _ in range(120):
        new = net.run_ms(state, 1)
        landed.append(int(new.census.landed_rows) - int(state.census.landed_rows))
        rounds.append(int(new.proto["commit_rounds"]) - int(state.proto["commit_rounds"]))
        state = new
    assert int(state.census.steps) == 120
    assert sum(landed) > 0 and max(landed) == int(state.proto["landing_peak"])
    assert rounds == [int(n > 0) for n in landed]
    assert int(state.census.extra_commit_rounds) == 0
    assert max(landed) <= net.census_limits()["landing_peak"] == net.protocol.census_limits()["landing_peak"]


# -- arrivals and the claim over the rows that fire (PR 42) -------------------
# The two every-tick entries (rows by sender; the level axis with k > 1)
# number their firing rows to the front and run `_arrive`, `_claim_keys`
# and `_claim_winners` over `firing_capacity(rows)` of them a round; the
# flat entry keeps the whole-M body, so it is the reference, as above.


# build, and the entry its every-tick send takes
FIRED_BUILDS = {
    "honest": (_handel_fused, "sender"), "byz51": (_handel_byz, "sender"), "gsf": (_gsf_small, "axis"),
}


def _fired_send(a, entry, rng, density, crowd, has_aux):
    """(the entry's own arguments, the same send a row each)."""
    from test_agg_buckets import _flat_send_args, _random_send  # the level-axis send and its flat form

    if entry == "sender":
        args = _sender_rows_send(a, rng, 5, density, crowd)
        aux = jnp.asarray(rng.integers(0, 99, size=(a.n_nodes, 1)), jnp.int32) if has_aux else None
        *flat, x = _flat(a, *args, aux)
    else:
        mask, frm, to, level, blocks, words = _random_send(
            a, rng, a.params.accelerated_calls_count, density, crowd)
        # the axis numbers its own levels and cuts its landing rows' blocks
        args = (mask, frm, to, None, words)
        aux = jnp.asarray(rng.integers(0, 99, size=(a.n_nodes, 1, 1)), jnp.int32) if has_aux else None
        *flat, x = _flat_send_args(a, mask, frm, to, level, blocks, aux)
    return (args, aux), (flat, x)


def _patched_firing(monkeypatch, cap):
    """Rows a round of arrivals and the claim for the case, through the
    one function `_send_stacked` asks: None is the send's own F, "M" one
    round over every row."""
    from wittgenstein_tpu.protocols import _agg_batched

    real = _agg_batched.firing_capacity
    patched = {None: real, "M": lambda rows: int(np.prod(rows))}.get(cap, lambda rows: cap)
    monkeypatch.setattr(_agg_batched, "firing_capacity", patched)
    return real


# rows a round: 1 and 7 take a round a row or nearly (a key of a later
# round beats a row that held its slot after its own), 50 a few rounds of
# a spread send
FIRED_CASES = [
    (build, cap, traffic)
    for build in sorted(FIRED_BUILDS)
    for cap, traffic in (
        (1, "crowd"), (7, "crowd"), (7, "none"), (50, "spread"), (None, "spread"), ("M", "crowd"),
    )
]


@pytest.mark.parametrize("build, cap, traffic", FIRED_CASES)
def test_the_firing_rows_send_equals_the_whole_send(build, cap, traffic, monkeypatch):
    """State for state, `displaced` too: three crowded sends in a row (the
    later ones leave earlier, so winners evict pending occupants and rows
    lose both slots), a spread one, an empty one; at every capacity but
    the send's own and M the firing rows take many rounds."""
    factory, entry = FIRED_BUILDS[build]
    net, state = factory()
    a = net.protocol
    real = _patched_firing(monkeypatch, cap)
    rng = np.random.default_rng(0)
    has_aux = "in_aux" in state.proto
    fired = whole = state
    sends = 3 if traffic == "crowd" else 1
    density = {"spread": 0.6, "crowd": 0.9, "none": 0.0}[traffic]
    overflows = 0
    for j in range(sends):
        (args, aux), (flat, x) = _fired_send(a, entry, rng, density, traffic == "crowd", has_aux)
        at = jnp.int32(2 * (sends - 1 - j))
        fired = a._send_stacked(net, fired._replace(time=at), *args, aux=aux)
        whole = a._send_stacked(net, whole._replace(time=at), *flat, aux=x)
        rows = args[0].shape
        capacity = {None: real(rows), "M": int(np.prod(rows))}.get(cap, cap)
        overflows += int(np.asarray(args[0]).sum()) > capacity
    _assert_same_state(fired, whole, (build, cap, traffic))
    assert int(whole.census.fired_rows) == 0  # the flat entry is the whole-M body
    assert (int(np.asarray(whole.msg_received).sum()) > 0) == (traffic != "none")
    if traffic == "crowd":
        assert int(whole.proto["displaced"]) > 0
    if has_aux and traffic != "none":
        assert np.asarray(whole.proto["in_aux"]).any()
    assert int(fired.census.firing_overflows) == overflows
    assert (overflows > 0) == (traffic != "none" and cap not in ("M", None) or (cap is None and density > 0.5))


@pytest.mark.parametrize("build", sorted(FIRED_BUILDS))
def test_firing_rounds_under_vmap_equal_the_single_runs(build, monkeypatch):
    """Two rows, one that fires more than a round carries and one that
    fires less (and, the other way round, one that fires nothing): each
    batched loop runs until the slower row is through, and every leaf of
    each row, the counters and the census among them, equals its single
    run's."""
    factory, entry = FIRED_BUILDS[build]
    net, state = factory()
    a = net.protocol
    _patched_firing(monkeypatch, 64)
    has_aux = "in_aux" in state.proto
    send = lambda seed, density: _fired_send(  # noqa: E731
        a, entry, np.random.default_rng(seed), density, False, has_aux)[0]
    busy, few, quiet = send(3, 0.5), send(4, 0.01), send(5, 0.0)
    for pair in ((busy, few), (quiet, busy)):
        stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *pair)
        states = jax.tree_util.tree_map(lambda x: jnp.stack([x, x]), state)
        out = jax.vmap(lambda s, args, aux: a._send_stacked(net, s, *args, aux=aux))(states, *stacked)
        singles = [a._send_stacked(net, state, *args, aux=aux) for args, aux in pair]
        over = [int(np.asarray(args[0]).sum()) > 64 for args, _ in pair]
        assert sorted(over) == [False, True]
        assert [int(s.census.firing_overflows) for s in singles] == [int(o) for o in over]
        for j, single in enumerate(singles):
            _assert_same_state(jax.tree_util.tree_map(lambda x: x[j], out), single, (build, j), but=())


def _scatter_rows(text, operand_dims, dtype="i32"):
    """Update rows of every scatter into an operand of that shape."""
    return [
        _dims(updates)[0]
        for operand, updates in _SCATTER.findall(text)
        if operand.endswith(dtype) and _dims(operand) == tuple(operand_dims)
    ]


@pytest.mark.parametrize(
    "name, build, rows",
    [
        ("handel tick", _handel_fused, (N, 5)),
        ("gsf tick", _gsf, (N, 8, 10)),
    ],
)
def test_the_every_tick_sends_scatter_a_round_of_firing_rows(name, build, rows):
    """The counter that says the firing compaction engaged is static too:
    in the lowered tick every scatter into `in_key` (the claim's min and
    max; `in_aux` beside it) and into a node column (the traffic counters of `_arrive` and of
    `latency_arrivals`) carries F = firing_capacity(rows) update rows,
    inside the rounds' loops, and none the M rows of the send; the beat's
    send, whose rows mostly fire, still carries its M."""
    from wittgenstein_tpu.protocols._agg_batched import firing_capacity

    net, state = build()
    a = net.protocol
    m, f = int(np.prod(rows)), firing_capacity(rows)
    assert f <= m / 5 and net.census_limits()["firing_peak"] == f
    text = _lowered(net, state, "tick")
    # the claim's min and max into `in_key` and, where the sends carry an aux
    # word (GSF), the two writes of the `in_aux` plane, of the same shape
    planes = 4 if "in_aux" in state.proto else 2
    keys = _scatter_rows(text, state.proto["in_key"].shape)
    assert keys == [f] * planes, (name, keys)
    counters = _scatter_rows(text, (N,))
    # msg_sent, bytes_sent, msg_received, bytes_received (and sent_not_ok
    # where a node is down) of the send; the tick's other [N] scatters
    # (a node a row) are not the send's
    assert counters.count(f) >= 4 and m not in counters, (name, counters)
    assert text.count("stablehlo.while") >= 2  # arrive's rounds, claim's rounds
    beat = _lowered(net, state, "tick_beat")
    m_beat = N * (a.n_levels - 1)
    assert _scatter_rows(beat, state.proto["in_key"].shape) == [m_beat] * planes
    assert _scatter_rows(beat, (N,)).count(m_beat) >= 4
    assert "stablehlo.while" not in beat


# -- what the candidate merge lowers to (PR 34) ------------------------------
# The merge engages on every (node, level) of every tick, so its counter
# is static too: no sort and no gather under `witt.deliver.merge`, and
# none of the seven argsorts left anywhere in the tick.  On a v5e the
# gathers it replaced were 2.0 ms a tick each at 4096 nodes (PERF.md
# section 6, PR 34).

# stablehlo.gather bodies in the lowered Handel tick at 256 nodes since
# PR 34 (88 and 100 at the parent, commit e662364, with 2 stablehlo.sort
# each): a pick respelled as an index read would add to these.  74 and
# 86 until PR 38, which added the seven reads of a commit round under
# `witt.channel.compact` (the landing rows' receiver, level, slot, rel,
# two win flags and their senders' words: 14 by this count, which finds
# the generic form's name twice an op) and took out the six table reads
# of `_dyn_low` (block size and width are arithmetic on the level now).
# 82 and 94 until PR 42, whose rounds of firing rows read a row's sender,
# receiver and level in `arrive` and its receiver and level in `claim`
# (10 by this count), where a commit round reads four columns for seven
# (its rows' slot and win bits come with the landing list: 6 fewer).
# 86 and 98 until PR 44, which took out the deliver phase's read of the
# verified-sender bit (`_getbit`'s take_along_axis into `ind`: 2) and the
# table read of a level's block size in `_rank` (2 a call: one call in
# the honest tick, four under the attack)
HANDEL_TICK_GATHERS = {"handel_fused": 82, "handel_byz51": 88}


def _scoped_primitives(jaxpr, scope: str, inside: bool = False, out=None):
    """Names of the primitives traced under the named scope, through
    every sub-jaxpr (an inner jaxpr's name stacks are relative to its
    equation's)."""
    from wittgenstein_tpu.analysis.annotations_check import _sub_jaxprs

    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        here = inside or scope in str(eqn.source_info.name_stack)
        subs = list(_sub_jaxprs(eqn.params))
        if here and not subs:
            out.append(eqn.primitive.name)
        for sub in subs:
            _scoped_primitives(sub, scope, here, out)
    return out


@pytest.mark.parametrize("name", sorted(HANDEL_TICK_GATHERS))
def test_the_candidate_merge_has_no_sort_and_no_gather(name):
    from wittgenstein_tpu.protocols.handel_batched import DELIVER_SCOPES

    net, state = PINS[name][0]()
    tick = lambda s: net.protocol.tick(net, s)  # noqa: E731
    prims = _scoped_primitives(jax.make_jaxpr(tick)(state).jaxpr, DELIVER_SCOPES["merge"])
    assert len(prims) > 100, prims  # the scope is live: the merge is under it
    assert not {"sort", "gather", "dynamic_slice", "scatter"} & set(prims), sorted(set(prims))
    text = _lowered(net, state, "tick")
    # none of the merge's seven argsorts: the two sorts left in the tick
    # put the firing rows of the fast path's send first (PR 42) and, of a
    # round of them, the landing rows (PR 38)
    assert len(re.findall(r"stablehlo\.sort", text)) == 2
    assert _scoped_primitives(jax.make_jaxpr(tick)(state).jaxpr, "witt.channel.compact").count("sort") == 2
    gathers = len(re.findall(r"stablehlo\.(?:dynamic_)?gather", text))
    assert gathers <= HANDEL_TICK_GATHERS[name], gathers


@pytest.mark.parametrize("name", sorted(HANDEL_TICK_GATHERS))
def test_the_due_candidates_rank_has_no_gather(name):
    """The rank and the verified-sender demotion of the two due candidates
    (PR 44): the sender's bit of `ind` comes from the level's block view
    and a one-hot mask, so nothing under `witt.deliver.rank` is an indexed
    read.  On a v5e the gather it replaced was 2.33 ms of an 8.87-ms tick
    at 4096 nodes (PERF.md section 6, PR 44)."""
    from wittgenstein_tpu.protocols.handel_batched import DELIVER_SCOPES

    net, state = PINS[name][0]()
    tick = lambda s: net.protocol.tick(net, s)  # noqa: E731
    prims = _scoped_primitives(jax.make_jaxpr(tick)(state).jaxpr, DELIVER_SCOPES["rank"])
    assert len(prims) > 100, prims  # the scope is live: the rank is under it
    assert not {"sort", "gather", "dynamic_slice", "scatter"} & set(prims), sorted(set(prims))


def _bit_by_hand(plane, rel):
    """Bit `rel` of each row's packed plane, in numpy: [N, W], [N, ...]."""
    rows = np.arange(plane.shape[0]).reshape((-1,) + (1,) * (rel.ndim - 1))
    return ((plane[rows, rel >> 5] >> (rel & 31).astype(np.uint32)) & 1).astype(bool)


@pytest.mark.parametrize("n", [64, 256, 4096])
def test_level_bit_reads_the_verified_senders_bit(n):
    """`_level_bit` as `_channel_deliver` calls it on `ind`, [N, L-1, 2]:
    against the plane's bit at the sender's full rel, every level of every
    bucket (the sub-word ones share word 0).  A slot that is not due
    carries the junk rel of an empty key (every rel bit set): it reads
    bit bs - 1 of its own block, inside the plane, and `accept` masks it."""
    rng = np.random.default_rng(n)
    proto = BatchedHandel(handel_params(node_count=n, threshold=int(n * 0.99)))
    L, rows = proto.n_levels, 16
    ind = np.zeros((n, proto.n_words), np.uint32)
    ind[:rows] = rng.integers(0, 2**32, (rows, proto.n_words), dtype=np.uint32)
    ind[0], ind[1] = 0, 0xFFFFFFFF
    bs = np.asarray(proto.lv_bs)[None, :, None]
    rel2 = bs + rng.integers(0, 2**30, (n, L - 1, 2)) % bs
    due2 = rng.random((n, L - 1, 2)) < 0.5
    junk = (1 << proto.rel_bits) - 1  # INT32_MAX & rel_mask, and -1 & rel_mask
    rel2 = np.where(due2, rel2, junk).astype(np.int32)
    got = np.asarray(proto._level_bit(jnp.asarray(ind), jnp.asarray(rel2)))
    assert got.shape == (n, L - 1, 2)
    want = _bit_by_hand(ind, rel2)
    assert (got[due2] == want[due2]).all()
    in_block = np.broadcast_to(2 * bs - 1, rel2.shape)
    assert (got[~due2] == _bit_by_hand(ind, in_block)[~due2]).all()
    assert not got[0].any() and got[1].all()


def test_gsf_keeps_its_own_merge_until_it_claims_in_its_own_cell():
    """`gsf_batched.py` holds the same algorithm in its own lines, left as
    it is so that `gsf-2048.single-r1` is the cell in which nothing may
    move (ROADMAP A1 (c)): no merge scope in its program."""
    from wittgenstein_tpu.protocols.handel_batched import DELIVER_SCOPES

    net, state = _gsf()
    text = jax.jit(net.step).lower(state).as_text(debug_info=True)
    assert "witt.protocol_tick" in text and DELIVER_SCOPES["merge"] not in text
