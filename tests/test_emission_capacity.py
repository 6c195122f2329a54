"""An `Emission` that states a `capacity` (engine/core.py
`_apply_emission_rounds`): the store takes the rows that fire, `capacity`
a round, and leaves the state the dense form leaves, bit for bit, in one
round or in many.  Held on SanFermin, the protocol that states one for
both of its every-tick emissions (`sanfermin_batched.emission_capacity`),
at the 256-node fixture's size, tick by tick, against the same protocol
with `capacity=None`."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from wittgenstein_tpu.core.node import build_node_columns
from wittgenstein_tpu.core.registries import registry_network_latencies
from wittgenstein_tpu.engine import BatchedNetwork, Emission, replicate_state
from wittgenstein_tpu.engine.core import EMISSION_SCOPES, SimState
from wittgenstein_tpu.protocols import sanfermin_batched
from wittgenstein_tpu.protocols.sanfermin import SanFerminSignature, SanFerminSignatureParameters

TICKS = 400
FORCED = 8


def _build(wheel_slots=None):
    """`make_sanfermin` at 256 nodes on the store scaled as the
    deployment's (16 slots a node); with `wheel_slots`, on a wheel so
    narrow that its rows spill into the lane."""
    params = SanFerminSignatureParameters(
        node_count=256, threshold=256, pairing_time=2, signature_size=48,
        reply_timeout=300, candidate_count=1, shuffled_lists=False,
    )
    if wheel_slots is None:
        return sanfermin_batched.make_sanfermin(params, capacity=65536 // 16)
    latency = registry_network_latencies.get_by_name(params.network_latency_name)
    cols = build_node_columns(SanFerminSignature(params).network().all_nodes, None)
    proto = sanfermin_batched.BatchedSanFermin(params)
    net = BatchedNetwork(proto, latency, 256, capacity=4096, wheel_slots=wheel_slots)
    return net, net.init_state(cols, seed=0, proto=proto.proto_init(256))


def _history(monkeypatch, capacity, replicas, wheel_slots):
    """The states after each of TICKS ticks, as numpy, of `replicas` rows
    whose two emissions state `capacity` ("stated": the protocol's own)."""
    with monkeypatch.context() as patch:
        if capacity != "stated":
            patch.setattr(sanfermin_batched, "emission_capacity", lambda rows: capacity)
        net, state = _build(wheel_slots)
        states = replicate_state(state, replicas, seeds=[7001 + i for i in range(replicas)])
        out = [jax.tree_util.tree_map(np.asarray, states)]
        for _ in range(TICKS):
            states = net.run_ms_batched(states, 1)  # traced on the first call, under the patch
            out.append(jax.tree_util.tree_map(np.asarray, states))
    return out


def _equal_but_for_the_census(dense, rounds, where=""):
    la = jax.tree_util.tree_leaves_with_path(dense._replace(census=()))
    lb = jax.tree_util.tree_leaves_with_path(rounds._replace(census=()))
    assert len(la) == len(lb) >= len(SimState._fields) - 3
    for (path, x), (_, y) in zip(la, lb):
        assert np.array_equal(x, y), (where, jax.tree_util.keystr(path))


def _fired(history):
    """(requests, replies) [TICKS, rows]: the rows whose mask was set in
    the tick's emission (`sent_req`'s growth) and in the deliver's
    (`msg_sent`'s growth less that), from any run's states."""
    req = np.diff([s.proto["sent_req"].sum(-1) for s in history], axis=0)
    sent = np.diff([s.msg_sent.sum(-1) for s in history], axis=0)
    return req, sent - req


@pytest.mark.parametrize("replicas", [1, 4])
@pytest.mark.parametrize(
    "capacity, wheel_slots", [("stated", None), (FORCED, 2)], ids=["stated", "forced-8-narrow-wheel"]
)
def test_the_rounds_leave_the_dense_forms_state(monkeypatch, capacity, wheel_slots, replicas):
    """Every leaf of the state but the census equal after every tick,
    between the emissions as they state a capacity and the same with
    `capacity=None`.  Forced to 8 rows a round on a wheel of 2 slots a
    row, busy ticks take several rounds, wheel rows spill into the lane
    within one emission and the rounds straddle the spill; `send_ctr`
    grows by one an emission whatever the rounds, and the census's three
    firing slots say what the dense masks say."""
    dense = _history(monkeypatch, None, replicas, wheel_slots)
    rounds = _history(monkeypatch, capacity, replicas, wheel_slots)
    for t, (a, b) in enumerate(zip(dense, rounds)):
        _equal_but_for_the_census(a, b, where=t)
    # one emission, one send event: two a tick after the initial one
    assert (rounds[-1].send_ctr == rounds[0].send_ctr + 2 * TICKS).all()

    req, rep = _fired(dense)
    limit = sanfermin_batched.emission_capacity(512) if capacity == "stated" else capacity
    census, none = rounds[-1].census, dense[-1].census
    assert (census.fired_rows == (req + rep).sum(0)).all() and census.fired_rows.min() > 2000
    assert (census.firing_overflows == ((req > limit).sum(0) + (rep > limit).sum(0))).all()
    assert (census.firing_peak == np.maximum(req.max(0), rep.max(0))).all()
    assert not none.fired_rows.any() and not none.firing_peak.any()  # the dense form writes none of them
    for name in census._fields:
        if not name.startswith(("fired", "firing")):
            assert np.array_equal(getattr(census, name), getattr(none, name)), name

    lane = np.array([s.ovf_valid.sum(-1) for s in dense])
    if capacity == "stated":
        # the deployment's sizing: one round a tick, the lane never used
        assert not census.firing_overflows.any() and census.firing_peak.max() <= 20 and not lane.any()
    else:
        # several rounds on the busy ticks, and the lane taking rows on ticks whose requests took several
        assert census.firing_overflows.min() >= 40 and census.firing_peak.max() > 2 * FORCED
        # (a row's lane fills up and drops here and there: `dropped` is a leaf, compared above)
        assert ((np.diff(lane, axis=0) > 0) & (req > FORCED)).any()


def test_the_capacity_follows_the_emissions_shape(monkeypatch):
    """256 of the 8192 requests a tick at 4096 nodes; a share of the rows
    from there up, the rows themselves below.  Both emissions state the
    requests' (a reply answers a request), so the census's one limit is
    the capacity of each, whatever rows the store's view has."""
    capacity = sanfermin_batched.emission_capacity
    assert (capacity(8192), capacity(1280), capacity(512), capacity(128)) == (256, 256, 256, 128)
    assert (capacity(32768), capacity(4 * 32768)) == (1024, 4096)
    net, state = _build()
    limit = net.census_limits()["firing_peak"]
    assert limit == net.protocol.round_rows == capacity(512) == 256
    requests = []  # the tick stores its own emission
    monkeypatch.setattr(net, "apply_emission", lambda st, em: requests.append(em) or st)
    net.protocol.tick(net, state)
    view, _due, deliver, _ctx = net.delivery_view(state)
    _view, replies = net.protocol.deliver(net, view, deliver)
    assert [em.capacity for em in (*requests, *replies)] == [limit, limit]
    assert replies[0].mask.shape != requests[0].mask.shape  # the view's rows are not the requests'
    assert sanfermin_batched.BatchedSanFermin.REQUIRED_SCOPES == tuple(EMISSION_SCOPES.values())
    assert Emission(mask=None, from_idx=None, to_idx=None, mtype=0).capacity is None  # the default: all rows at once


def _pingpong(cls, due_view_rows=None):
    """64 nodes on the FLAT store (a lane of 256 rows)."""
    from wittgenstein_tpu.core.node import Node
    from wittgenstein_tpu.core.registries import registry_node_builders
    from wittgenstein_tpu.utils.javarand import JavaRandom

    rd = JavaRandom(0)
    cols = build_node_columns([Node(rd, registry_node_builders.get_by_name(None)) for _ in range(64)], None)
    proto = cls(64)
    net = BatchedNetwork(
        proto, registry_network_latencies.get_by_name(None), 64, capacity=256,
        wheel_rows=0, due_view_rows=due_view_rows,
    )
    return net, net.init_state(cols, seed=5, proto=proto.proto_init(64))


def test_a_capacity_on_the_flat_store():
    """PingPong on the FLAT store, the witness's 64 PINGs (a scalar
    `send_time`: no rows to read) in rounds of 16 and each step's PONGs
    in rounds of 4 of the view's 257 rows, against the same protocol
    stating none: every leaf but the census equal when the witness has
    heard everyone."""
    from wittgenstein_tpu.protocols.pingpong_batched import BatchedPingPong

    class Rounds(BatchedPingPong):
        def initial_emissions(self, net, state):
            return [dataclasses.replace(em, capacity=16) for em in super().initial_emissions(net, state)]

        def deliver(self, net, state, deliver_mask):
            state, emissions = super().deliver(net, state, deliver_mask)
            return state, [dataclasses.replace(em, capacity=4) for em in emissions]

    dense, rounds = (
        jax.tree_util.tree_map(np.asarray, net.run_ms(state, 1500))
        for net, state in (_pingpong(BatchedPingPong), _pingpong(Rounds))
    )
    assert int(dense.proto["pong"][0]) == 64 and int(dense.send_ctr) > 2
    _equal_but_for_the_census(dense, rounds)
    # 64 PINGs in four rounds of 16, then every PONG of a step in rounds of 4
    assert int(rounds.census.fired_rows) == 128 and int(rounds.census.firing_peak) == 64
    assert int(rounds.census.firing_overflows) >= 1 and not int(dense.census.fired_rows)


def test_a_capacity_is_kept_under_the_flat_stores_due_view():
    """Under a due view a step's emissions go through `apply_emissions`'
    branch (nothing sampled where every mask is empty) only where none
    states a capacity; one that does stores through its own rounds, with
    its census, and leaves what the branch leaves."""
    from wittgenstein_tpu.protocols.pingpong_batched import BatchedPingPong

    net, state = _pingpong(BatchedPingPong, due_view_rows=8)
    ids = jnp.arange(64, dtype=jnp.int32)

    def send(capacity, fire):
        em = Emission(mask=(ids % 5 == 0) & fire, from_idx=ids, to_idx=63 - ids, mtype=1, capacity=capacity)
        return jax.tree_util.tree_map(np.asarray, jax.jit(lambda s: net.apply_emissions(s, [em, em]))(state))

    for fire, fired in ((True, 2 * 13), (False, 0)):
        dense, rounds = send(None, fire), send(4, fire)
        _equal_but_for_the_census(dense, rounds)
        assert int(rounds.send_ctr) == int(state.send_ctr) + 2  # a send event an emission, firing or not
        assert int(rounds.census.fired_rows) == fired and int(rounds.census.firing_overflows) == (2 if fired else 0)
        assert int(rounds.msg_head) - int(state.msg_head) == fired and not int(dense.census.fired_rows)


@pytest.mark.parametrize("capacity", [64, 100], ids=["the-rows", "past-the-rows"])
def test_a_capacity_of_all_the_rows_is_the_dense_pass_counted(capacity):
    """A round that holds all K rows is the one dense pass: no numbering,
    no loop, the dense form's state, and the census says what fired."""
    from wittgenstein_tpu.protocols.pingpong_batched import BatchedPingPong

    net, state = _pingpong(BatchedPingPong)
    ids = jnp.arange(64, dtype=jnp.int32)
    em = Emission(mask=ids % 5 == 0, from_idx=ids, to_idx=63 - ids, mtype=1)
    dense = jax.jit(lambda s: net.apply_emission(s, em))
    whole = jax.jit(lambda s: net.apply_emission(s, dataclasses.replace(em, capacity=capacity)))
    a, b = (jax.tree_util.tree_map(np.asarray, f(state)) for f in (dense, whole))
    _equal_but_for_the_census(a, b)
    assert (int(b.census.fired_rows), int(b.census.firing_peak), int(b.census.firing_overflows)) == (13, 13, 0)
    text, dense_text = whole.lower(state).as_text(), dense.lower(state).as_text()
    assert "stablehlo.while" not in text and text.count("stablehlo.sort") == dense_text.count("stablehlo.sort")
