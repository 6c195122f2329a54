"""The channel send path's row sets (PR 32): whole-run pins taken on the
parent of the change that cut the commit scatters to each bucket's own
rows, and the lowered programs' scatter update counts.

The pins are a position-weighted 32-bit checksum of EVERY state leaf
after 300 simulated ms at 256 nodes (the benchmark's fingerprint, in
numpy): the level-axis entry of `_send_stacked` only drops updates that
were addressed to the dropped row, so no leaf may move by a bit.
"""

import re

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from wittgenstein_tpu.protocols.gsf import GSFSignatureParameters
from wittgenstein_tpu.protocols.gsf_batched import make_gsf
from wittgenstein_tpu.protocols.handel import HandelParameters
from wittgenstein_tpu.protocols.handel_batched import make_handel

N = 256


def checksum(tree) -> int:
    """One number for a whole state: per leaf the sum of its words times
    a position weight (mod 2^32), the leaves' sums weighted again."""
    total = 0
    for j, leaf in enumerate(jax.tree_util.tree_leaves(tree)):
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


def _handel_node_mesh():
    from wittgenstein_tpu.parallel import enable_node_sharding, shard_state_by_node

    mesh = Mesh(np.array(jax.devices()[:2]), ("nodes",))
    net, state = make_handel(handel_params())
    net = enable_node_sharding(net, mesh)
    return net, shard_state_by_node(net, state, mesh)


# taken on the parent (commit 913a72b, PR 31), before the change; the
# Handel ones again at commit 96b30ec (PR 32) with the carried score
# caches, which every Handel state has held since PR 33 (they were
# 4133656018 and 3555050404 over the trees without those four leaves)
PINS = {
    "handel_fused": (_handel_fused, 3838452459),
    "handel_byz51": (_handel_byz, 882315166),
    "gsf": (_gsf, 999241418),
    "handel_node_mesh2": (_handel_node_mesh, 3838452459),
}


@pytest.mark.parametrize("name", sorted(PINS))
def test_whole_run_checksum_is_the_parents(name):
    build, want = PINS[name]
    net, state = build()
    out = net.run_ms(state, 300)
    assert int(np.asarray(out.msg_received).sum()) > 0  # traffic ran
    assert checksum(out) == want, (name, checksum(out))


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
# stablehlo.gather ops in the lowered GSF tick of the parent (commit
# 913a72b, PR 31) at 256 nodes: the cut is by reshape and slice, an index
# array would add to these
PARENT_GSF_TICK_GATHERS = 82


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


def commit_updates(n: int, k: int, level_axis: bool) -> int:
    """Word updates of one send's two commit passes, from shapes alone:
    every bucket over all M = N x (L-1) x k rows at its w_pad, or over its
    own levels' rows."""
    from wittgenstein_tpu.protocols._agg_batched import BitsetAggBase

    a = BitsetAggBase.__new__(BitsetAggBase)
    a._init_geometry(n)
    levels = a.n_levels - 1
    per_row = [(b.nl if level_axis else levels) * b.w_pad for b in a.buckets]
    return 2 * n * k * sum(per_row)


@pytest.mark.parametrize(
    "send, n, k, before, after",
    [
        ("gsf-2048 accelerated calls, every tick", 2048, 10, 28_385_280, 2_785_280),
        ("gsf-2048 dissemination, 1 tick in 10", 2048, 1, 2_838_528, 278_528),
        ("handel-4096 dissemination, 1 tick in 10", 4096, 1, 12_484_608, 1_081_344),
    ],
)
def test_commit_update_counts_from_shapes(send, n, k, before, after):
    """The benchmark's sends, word updates a send (both passes), before
    PR 32 and since: 10.2x, 10.2x and 11.5x fewer.  Handel's fast path
    (every tick, level is data) stays at 2 x 20,480 x 127 = 5,201,920."""
    assert commit_updates(n, k, level_axis=False) == before, send
    assert commit_updates(n, k, level_axis=True) == after, send


def _lowered(net, state, hook):
    fn = getattr(net.protocol, hook)
    return jax.jit(lambda s: fn(net, s)).lower(state).as_text()


@pytest.mark.parametrize(
    "name, build, hook, k",
    [
        ("gsf tick", _gsf, "tick", 10),  # the accelerated calls' send
        ("gsf beat", _gsf, "tick_beat", 1),
        ("handel beat", _handel_fused, "tick_beat", 1),
    ],
)
def test_a_static_level_send_scatters_only_its_buckets_rows(name, build, hook, k):
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
    if name == "gsf tick":
        gathers = len(re.findall(r"stablehlo\.(?:dynamic_)?gather", text))
        assert gathers <= PARENT_GSF_TICK_GATHERS


def test_the_fast_path_keeps_whole_rows():
    """Handel's every-tick send: its level is a per-node register, so
    each bucket still carries all N x ceil(fast_path / 2) rows (ROADMAP
    A1 (b), what is left)."""
    net, state = _handel_fused()
    a = net.protocol
    found = plane_updates(_lowered(net, state, "tick"), a, state)
    rows = N * ((net.protocol.params.fast_path + 1) // 2)
    for i, b in enumerate(a.buckets):
        assert found[i] == [rows * b.w_pad] * 2, (i, found)


# -- what the candidate merge lowers to (PR 34) ------------------------------
# The merge engages on every (node, level) of every tick, so its counter
# is static too: no sort and no gather under `witt.deliver.merge`, and
# none of the seven argsorts left anywhere in the tick.  On a v5e the
# gathers it replaced were 2.0 ms a tick each at 4096 nodes (PERF.md
# section 6, PR 34).

# stablehlo.gather bodies in the lowered Handel tick at 256 nodes since
# PR 34 (88 and 100 at the parent, commit e662364, with 2 stablehlo.sort
# each): a pick respelled as an index read would add to these
HANDEL_TICK_GATHERS = {"handel_fused": 74, "handel_byz51": 86}


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
    from wittgenstein_tpu.engine.core import DELIVER_SCOPES

    net, state = PINS[name][0]()
    tick = lambda s: net.protocol.tick(net, s)  # noqa: E731
    prims = _scoped_primitives(jax.make_jaxpr(tick)(state).jaxpr, DELIVER_SCOPES["merge"])
    assert len(prims) > 100, prims  # the scope is live: the merge is under it
    assert not {"sort", "gather", "dynamic_slice", "scatter"} & set(prims), sorted(set(prims))
    text = _lowered(net, state, "tick")
    assert len(re.findall(r"stablehlo\.sort", text)) == 0
    gathers = len(re.findall(r"stablehlo\.(?:dynamic_)?gather", text))
    assert gathers <= HANDEL_TICK_GATHERS[name], gathers


def test_gsf_keeps_its_own_merge_until_it_claims_in_its_own_cell():
    """`gsf_batched.py` holds the same algorithm in its own lines, left as
    it is so that `gsf-2048.single-r1` is the cell in which nothing may
    move (ROADMAP A1 (c)): no merge scope in its program."""
    from wittgenstein_tpu.engine.core import DELIVER_SCOPES

    net, state = _gsf()
    text = jax.jit(net.step).lower(state).as_text(debug_info=True)
    assert "witt.protocol_tick" in text and DELIVER_SCOPES["merge"] not in text
