"""The chip's compiler, without the chip: Mosaic and XLA:TPU compile the
Pallas bitset kernels at the flagship word widths, and one 256-node
run_ms_batched program, for a DESCRIBED v5e:2x2 (nothing runs — results
are pinned by tests/test_bitops_pallas.py in interpret mode and by
chip_smoke.py on the chip).  Interpret mode cannot see what Mosaic
refuses (1-D blocks, 1-D iota, unsigned reductions, lane-splitting
reshapes all passed it), and GSPMD refuses to partition a Mosaic kernel,
so these compiles guard every later PR at no chip time.

This is the only file that loads the TPU compiler: one process may hold
libtpu, so the topology is described inside a module fixture (never at
import) and everything compiles in the test's own process.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from wittgenstein_tpu.ops import bitops_pallas
from wittgenstein_tpu.ops.bitops import BITOPS_ENV

# popcount shapes the 4096-node Handel program passes (per replica);
# lowest_set_bit / pack_bool at the occupancy shapes of a 512-row wheel
# and at the full node width
POPCOUNT_SHAPES = [
    (4096, 1, 2), (4096, 1, 2, 64), (4096, 6, 1), (4096, 8, 1),
    (4096, 8, 64), (4096, 128),
]
LOWEST_SHAPES = [(16,), (4096, 4), (4096, 128)]
PACK_SHAPES = [(512,), (4096,), (8, 4096)]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture
def mosaic(monkeypatch, topo, no_compile_cache):
    """Trace the kernels for Mosaic (not the interpreter) although the
    default backend here is the CPU."""
    monkeypatch.setattr(bitops_pallas, "_interpret", lambda: False)
    monkeypatch.setenv(BITOPS_ENV, "pallas")
    return topo


def _compile(fn, shapes):
    compiled = jax.jit(fn).lower(shapes).compile()
    return compiled.as_text()


def _one_chip(topo, shape, dtype):
    return jax.ShapeDtypeStruct(
        shape, dtype, sharding=SingleDeviceSharding(topo.devices[0])
    )


@pytest.mark.parametrize("shape", POPCOUNT_SHAPES, ids=str)
def test_popcount_kernel_compiles(mosaic, shape):
    x = _one_chip(mosaic, shape, jnp.uint32)
    assert "tpu_custom_call" in _compile(bitops_pallas.popcount_words_pallas, x)


@pytest.mark.parametrize("shape", LOWEST_SHAPES, ids=str)
def test_lowest_set_bit_kernel_compiles(mosaic, shape):
    x = _one_chip(mosaic, shape, jnp.uint32)
    assert "tpu_custom_call" in _compile(bitops_pallas.lowest_set_bit_pallas, x)


@pytest.mark.parametrize("shape", PACK_SHAPES, ids=str)
def test_pack_bool_kernel_compiles(mosaic, shape):
    x = _one_chip(mosaic, shape, jnp.bool_)
    assert "tpu_custom_call" in _compile(bitops_pallas.pack_bool_words_pallas, x)


def test_kernels_compile_under_vmap(mosaic):
    """The engine vmaps the whole step over replicas: the batching rule
    adds a squeezed grid axis that Mosaic must accept too."""
    for fn, shape, dtype in (
        (bitops_pallas.popcount_words_pallas, (8, 4096, 1, 2), jnp.uint32),
        (bitops_pallas.lowest_set_bit_pallas, (8, 16), jnp.uint32),
        (bitops_pallas.pack_bool_words_pallas, (8, 512), jnp.bool_),
    ):
        x = _one_chip(mosaic, shape, dtype)
        assert "tpu_custom_call" in _compile(jax.vmap(fn), x)


@pytest.fixture(scope="module")
def handel256():
    """The flagship configuration at 256 nodes, as the chip builds it
    (fused step), 4 replicas.  Built with the default
    (lax) kernels: construction runs eagerly on the CPU."""
    from wittgenstein_tpu.engine import replicate_state
    from wittgenstein_tpu.protocols.handel_batched import make_handel
    from wittgenstein_tpu.scenarios.handel_scenarios import flagship_params

    net, state = make_handel(flagship_params(256), fuse_step=True)
    return net, replicate_state(state, 4)


def _described(states, sharding):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), states
    )


def test_handel256_program_compiles_for_one_chip(mosaic, handel256):
    net, states = handel256
    shapes = _described(states, SingleDeviceSharding(mosaic.devices[0]))
    text = _compile(lambda s: net.run_ms_batched(s, 1000), shapes)
    assert "tpu_custom_call" in text


def test_handel256_replica_sharded_program_compiles_for_four_chips(mosaic, handel256):
    """sharded_run_stats' program for replica-sharded states: the Mosaic
    kernels sit inside a shard_map (GSPMD cannot partition them), and no
    state is gathered — the only collectives reduce the statistics."""
    from wittgenstein_tpu.parallel.replica_shard import _run_and_reduce

    net, states = handel256
    mesh = Mesh(np.array(mosaic.devices[:4]), ("replicas",))
    shapes = _described(states, NamedSharding(mesh, P("replicas")))
    compiled = _run_and_reduce(net, 1000)._jit_for(shapes).lower(shapes).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert " all-gather(" not in text and " all-gather-start(" not in text
    # the states, `stats`, and the chunk's census vector (PR 41), reduced
    # after the map as `stats` is
    out_shapes, _stats, _census = compiled.output_shardings
    assert out_shapes.done_at.spec == P("replicas")


# the attack program at the size this file's other programs use: at 4096
# nodes the compile takes 81 s here, more than the rest of the file (done
# by hand in PR 31: it compiles)
BYZ_SIZE = {"node_count": 256, "nodes_down": 51, "threshold": 202}


@pytest.fixture(scope="module")
def handel_byz():
    """`handel-4096-byz20` as the benchmark builds it (`make_handel` with
    its parameters, fused step), one row: the `bl` and
    `byz` planes, the forged-signature injection, the blacklist, the
    emission's blacklist term and the `sent_not_ok` counter."""
    import json
    import os

    from wittgenstein_tpu.engine import replicate_state
    from wittgenstein_tpu.protocols.handel import HandelParameters
    from wittgenstein_tpu.protocols.handel_batched import make_handel

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", "handel-4096-byz20.json")) as f:
        config = json.load(f)
    params = HandelParameters(**{**config["params"], **BYZ_SIZE})
    net, state = make_handel(params, **config["factory_kwargs"])
    assert net.protocol.track_bad and "sent_not_ok" in state.proto
    return net, replicate_state(state, 1)


def test_handel_under_attack_compiles_for_one_chip(mosaic, handel_byz):
    net, states = handel_byz
    shapes = _described(states, SingleDeviceSharding(mosaic.devices[0]))
    text = _compile(lambda s: net.run_ms_batched(s, 20), shapes)
    assert "tpu_custom_call" in text
    assert "witt.attack.inject" in text and "witt.attack.emission" in text


@pytest.fixture(scope="module")
def sanfermin256():
    """`sanfermin-4096` as the benchmark builds it (`make_sanfermin` with
    its parameters and `factory_kwargs`), at 256 nodes with the store
    scaled as the nodes are (capacity 65536 for 4096 nodes: 16 slots a
    node), 4 rows: the first program on the generic message store."""
    import json
    import os

    from wittgenstein_tpu.engine import replicate_state
    from wittgenstein_tpu.protocols.sanfermin import SanFerminSignatureParameters
    from wittgenstein_tpu.protocols.sanfermin_batched import make_sanfermin

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", "sanfermin-4096.json")) as f:
        config = json.load(f)
    scale = config["params"]["node_count"] // 256
    params = SanFerminSignatureParameters(**{**config["params"], "node_count": 256, "threshold": 256})
    net, state = make_sanfermin(params, capacity=config["factory_kwargs"]["capacity"] // scale)
    assert not net.flat and (net.wheel_rows, net.overflow_capacity) == (512, 512)
    return net, replicate_state(state, 4)


@pytest.fixture(scope="module")
def sanfermin256_compiled(topo, no_compile_cache, sanfermin256):
    """The chunk program of `sharded_run_stats` on the message store,
    compiled for one described v5e chip: the chip's own instructions."""
    from wittgenstein_tpu.parallel.replica_shard import _run_and_reduce

    net, states = sanfermin256
    shapes = _described(states, SingleDeviceSharding(topo.devices[0]))
    return _run_and_reduce(net, 20)._jit_for(shapes).lower(shapes).compile().as_text()


def test_sanfermin_chunk_program_compiles_for_one_chip(sanfermin256_compiled):
    """The time wheel's scatters, the insert ranks' sorts and the delivery
    view's gathers under the replica axis, each under its `witt.store.*`
    scope; no Mosaic call (ROADMAP B10).  The insert's same-row rank is a
    scan over the sorted keys (PR 40): no `searchsorted`, and no loop
    under the insert at all.  Both emissions state a capacity (PR 47):
    the firing rows' numbering and a round's reads are under
    `witt.store.compact`, and the loop of the rounds past the first is
    around the insert, not under it."""
    from wittgenstein_tpu.engine.core import EMISSION_SCOPES, STORE_SCOPES

    text = sanfermin256_compiled
    for scope in (*STORE_SCOPES.values(), *EMISSION_SCOPES.values()):
        assert scope in text, scope
    assert re.search(r'op_name="[^"]*witt\.send/while/body/witt\.store\.insert', text)
    assert " sort(" in text and "scatter" in text
    assert "tpu_custom_call" not in text
    assert "searchsorted" not in text
    assert not re.search(r'op_name="[^"]*witt\.store\.insert[^"]*/while', text)


def _gather_fusions(text, payload_width):
    """(rows of the result, op_name) of every gather fusion of a compiled
    program's text; a read of payload rows has that many rows, not
    `payload_width` times as many."""
    out = []
    for line in text.splitlines():
        shape = re.search(r"= \w+\[([\d,]*)\]\S* fusion\(", line)
        name = re.search(r'op_name="([^"]*/gather[^"]*)"', line)
        if shape and name:
            dims = [int(d) for d in shape.group(1).split(",") if d]
            if len(dims) > 1 and dims[-1] == payload_width:
                dims.pop()
            out.append((int(np.prod(dims)), name.group(1)))
    return out


def test_sanfermin_sends_read_a_rounds_rows_and_the_delivery_its_own(sanfermin256, sanfermin256_compiled):
    """Both of SanFermin's emissions state a capacity (PR 47), so a send
    reads its columns, the latency model's, `down` and `x` at a round's
    256 rows a replica: no gather under `witt.send` has a result of the
    emission's K x R rows (the tick's requests) or of the view's x R (the
    deliver's replies) any more.  Until PR 47 the reply send read `x` at
    the view's two ends, XLA made each read once for it and for
    `delivery_view`'s `checked`, and this test held that sharing (PR 45
    was refused for respelling one of its two users: two more gathers of
    81,920 rows a tick).  Now the delivery's reads are its own, and what
    is held is that they do not grow and that nothing under the send
    reads an emission's rows again.  Counts at this size, parent (PR 46's
    tree) -> PR 47: `gather` instructions 39 -> 79 (a round's appear twice
    in the text, the first round and the loop's body, so the issue's "at or
    under today's" cannot hold for the instruction count and is held for
    what costs: the result rows of all gather fusions outside the rounds'
    loop, 85,252 -> 66,052 a tick of the 4 rows); gather fusions
    under `witt.send` with a K x R or view x R result 11 + 10 -> 0, with
    a round's 1024 rows 1 -> 64; under `witt.reach.deliver` 4 -> 3; view
    x R results in the whole program 23 -> 12 (at 4096 nodes x 64 rows:
    `gather` 39 -> 80, under the send 14 of 524,288 rows and 10 of 81,920
    -> 0, a round's 16,384 rows 0 -> 60, the delivery's 4 -> 3, 81,920-row
    results in the whole program 22 -> 11)."""
    net, states = sanfermin256
    text = sanfermin256_compiled
    rows = states.time.shape[0]
    view = rows * (net.wheel_slots + net.overflow_capacity)
    whole = {rows * net.n_nodes * 2, view}
    a_round = rows * 256
    fusions = _gather_fusions(text, net.payload_width)
    sends = [size for size, name in fusions if "witt.send" in name]
    checked = [size for size, name in fusions if "witt.reach.deliver" in name]
    assert not [size for size in sends if size in whole], "a send reads every row of its emission again"
    assert sends.count(a_round) > 40 and checked, "the counts read nothing: the compiler's spelling moved"
    what_a_rise_means = (
        "an indexed read more a tick: 8.6 ns a row on the chip, and PR 45 was refused for two of "
        "81,920 rows (sanfermin-4096.sweep-r64 -1.27%); see `delivery_view`'s `checked` and "
        "`_apply_emission_rounds` in engine/core.py"
    )
    assert len(checked) <= 3, (len(checked), what_a_rise_means)
    # what a read costs is its rows a tick, not an instruction in the text: a round's reads are
    # there twice, and the loop's copy runs on no tick seen.  So the rows of every gather outside
    # the rounds' loop, and the reads of a whole view, at or under PR 47's (under the parent's)
    every_tick = sum(size for size, name in fusions if "witt.send/while/body" not in name)
    assert every_tick <= 66052, (every_tick, what_a_rise_means)
    assert [size for size, _ in fusions].count(view) <= 12, what_a_rise_means


@pytest.fixture(scope="module")
def casper1024():
    """`casper-1024` as the benchmark builds it (`make_casper` with its
    parameters and `factory_kwargs`, nothing scaled), one row: the first
    program on the FLAT store under the jump loop, at its full width
    (1027 nodes, a 524,288-row lane, a 262,912-row ATT emission)."""
    import json
    import os

    from wittgenstein_tpu.engine import replicate_state
    from wittgenstein_tpu.protocols.casper import CasperParameters
    from wittgenstein_tpu.protocols.casper_batched import make_casper

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", "casper-1024.json")) as f:
        config = json.load(f)
    net, state = make_casper(CasperParameters(**config["params"]), **config["factory_kwargs"])
    assert net.flat and net.protocol.TICK_INTERVAL is None
    assert (net.protocol.n_nodes, net.protocol.apr, net.capacity) == (1027, 256, 524288)
    return net, replicate_state(state, 1)


def _lowered(topo, net, states, chunk_ms):
    """The chunk program of `sharded_run_stats`, lowered for one described
    v5e chip, as text with the scopes' names."""
    from wittgenstein_tpu.parallel.replica_shard import _run_and_reduce

    shapes = _described(states, SingleDeviceSharding(topo.devices[0]))
    return _run_and_reduce(net, chunk_ms)._jit_for(shapes).lower(shapes).as_text(debug_info=True)


@pytest.fixture(scope="module")
def casper1024_lowered(topo, no_compile_cache, casper1024):
    return _lowered(topo, *casper1024, 8000)


def test_casper_chunk_program_lowers_for_one_chip(casper1024, casper1024_lowered):
    """The 8000-ms chunk program of `sharded_run_stats` for the cell
    `casper-1024.single-r1-s8000`, lowered for one described v5e chip at
    the cell's own width: the jump loop, the flat lane's planes, the
    committee's emission and the fork choice's product, each under its
    scope.  Lowered, not compiled: XLA:TPU takes 69 s over this program
    (sandbox, PR 39), which the tier-1 run does not have; what the
    compiler makes of it is PERF.md section 5's."""
    from wittgenstein_tpu.engine.core import ENGINE_PHASE_SCOPES, STORE_SCOPES
    from wittgenstein_tpu.protocols.casper_batched import CHAIN_SCOPES

    net, _states = casper1024
    text = casper1024_lowered
    for scope in (*CHAIN_SCOPES.values(), *STORE_SCOPES.values(), ENGINE_PHASE_SCOPES["jump"]):
        assert scope in text, scope
    assert net.due_view_rows == 4096  # the factory's rule: 1/128 of the lane
    assert "524288" in text and "524289" in text  # the lane, and the view over all of it (a step with more due than fits)
    assert "4097" in text  # the due view: what every other step hands to deliver
    assert "262912" in text  # the ATT emission at its static [apr x N]
    assert "1027x6144" in text  # rec_att, and the fork choice's product


@pytest.fixture(scope="module")
def dfinity4096():
    """`dfinity-4096` as the benchmark builds it (`make_dfinity` with its
    parameters and `factory_kwargs`, nothing scaled), one row: the first
    program whose broadcasts are fan-outs, on the wheel under the jump
    loop and the wheel's due view, at its full width (4171 nodes, 256
    wheel rows of 262,144 slots, an 8192-row lane)."""
    import json
    import os

    from wittgenstein_tpu.engine import replicate_state
    from wittgenstein_tpu.protocols.dfinity import DfinityParameters
    from wittgenstein_tpu.protocols.dfinity_batched import make_dfinity

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", "dfinity-4096.json")) as f:
        config = json.load(f)
    net, state = make_dfinity(DfinityParameters(**config["params"]), **config["factory_kwargs"])
    assert not net.flat and net.protocol.TICK_INTERVAL is None
    assert (net.protocol.n_nodes, net.protocol.n_bcn, net.protocol.n_bp) == (4171, 64, 10)
    assert (net.wheel_rows, net.wheel_slots, net.overflow_capacity) == (256, 262144, 8192)
    assert net.protocol.vote_capacity == 128
    # the one-word payload is what the configuration expects of the program: the parent's has two
    assert net.protocol.PAYLOAD_WIDTH == config["expect"]["protocol_attrs"]["PAYLOAD_WIDTH"] == 1
    return net, replicate_state(state, 1)


@pytest.fixture(scope="module")
def dfinity4096_lowered(topo, no_compile_cache, dfinity4096):
    return _lowered(topo, *dfinity4096, 6000)


@pytest.mark.parametrize("lowered", ["casper1024_lowered", "dfinity4096_lowered"])
def test_a_program_that_states_no_capacity_has_no_rounds(request, lowered):
    """`Emission.capacity` is the protocol's to state (PR 47): Casper's
    eight emissions and Dfinity's fan-outs state none, so their chunk
    programs carry nothing of `_apply_emission_rounds` (no sort of an
    emission's row numbers, no loop of rounds: a 262,912-row ATT emission
    whose rows all fire on the one step a slot that sends would pay a
    262,912-key sort and a thousand rounds for nothing)."""
    from wittgenstein_tpu.engine.core import EMISSION_SCOPES

    text = request.getfixturevalue(lowered)
    assert "witt.store.insert" in text
    for scope in EMISSION_SCOPES.values():
        assert scope not in text, scope


def test_dfinity_chunk_program_lowers_for_one_chip(dfinity4096, dfinity4096_lowered):
    """The 6000-ms chunk program of `sharded_run_stats` for the cell
    `dfinity-4096.single-r1-c6000-h18000`, lowered for one described v5e
    chip at the cell's own width: the jump loop, the wheel's planes, the
    three views of the due row, the five fan-outs' rounds and the three
    roles, each under its scope.  Lowered, not compiled: XLA:TPU takes
    150 s over this program (sandbox, PR 43), which the tier-1 run does
    not have; what the compiler makes of it is PERF.md section 5's."""
    from wittgenstein_tpu.engine.core import ENGINE_PHASE_SCOPES, FANOUT_SCOPES, STORE_SCOPES
    from wittgenstein_tpu.protocols.dfinity_batched import ROLE_SCOPES

    net, _states = dfinity4096
    text = dfinity4096_lowered
    for scope in (*ROLE_SCOPES.values(), *FANOUT_SCOPES.values(), *STORE_SCOPES.values(),
                  ENGINE_PHASE_SCOPES["jump"]):
        assert scope in text, scope
    assert net.due_view_rows == (4096, 32768)  # the factory's rule: 1/64 and 1/8 of a wheel row
    assert "256x262144" in text  # the wheel's planes
    for view in (4096 + 8192, 32768 + 8192, 262144 + 8192):  # a step's view: the row's leading slots and the lane
        assert f"tensor<{view}x" in text, view
    assert f"tensor<{64 * 4171}x" in text  # a round of the beacon's or the notarised block's fan-out
    assert f"tensor<{128 * 4096}x" in text  # a round of the votes': 128 (slot, attester) pairs
    assert f"tensor<{10 * 4096 * 4096}x" not in text  # and never the votes' static form
    assert "4171x80" in text  # the per-node planes by block slot


def test_the_partitioned_cell_runs_dfinity_4096s_program(topo, no_compile_cache, dfinity4096):
    """`dfinity-4096-part20` as the benchmark builds it (the partitioned
    parameters, the line in the initial state) lowers, for one described
    v5e chip, to the program `dfinity-4096` lowers to, text for text: the
    partition is data in `SimState.partition_x`, no static of the network.
    So the two cells time one executable on two initial states, and the
    reach check's scopes are in it under both (`witt.reach.deliver` in the
    branch a state with a line, or with a node down, takes)."""
    import json
    import os

    import numpy as np

    from wittgenstein_tpu.engine import replicate_state
    from wittgenstein_tpu.engine.core import INT_MAX, REACH_SCOPES
    from wittgenstein_tpu.parallel.replica_shard import _run_and_reduce
    from wittgenstein_tpu.protocols.dfinity_batched import make_dfinity
    from wittgenstein_tpu.protocols.dfinity_part import PartitionedDfinityParameters

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", "dfinity-4096-part20.json")) as f:
        config = json.load(f)
    assert config["factory"].endswith("dfinity_batched.make_dfinity")
    params = PartitionedDfinityParameters(**config["params"])
    net, state = make_dfinity(params, **config["factory_kwargs"])
    sound_net, sound = dfinity4096
    assert np.asarray(state.partition_x).tolist() == [400] + [int(INT_MAX)] * 3
    assert (np.asarray(sound.partition_x) == INT_MAX).all()
    behind = np.asarray(state.x) < 400
    # the population the parameters state (`population_seed` 0), not the caller's: 3 of the
    # 10 producers and 9 of the 64 beacon nodes behind the line
    assert behind.sum() == 880 and not np.array_equal(np.asarray(state.x), np.asarray(sound.x)[0])
    assert behind[net.protocol.bp_ids].sum() == 3 and behind[net.protocol.bcn_ids].sum() == 9
    # the t=0 beacon results that cross the line are masked in the initial state already
    crossing = int((behind[net.protocol.bcn_ids][:, None] != behind[None, :]).sum())
    assert int(state.census.masked_sends) == crossing == 78019
    assert int(state.msg_head) == 64 * 4171 - crossing and int(sound.msg_head[0]) == 64 * 4171
    sharding = SingleDeviceSharding(topo.devices[0])
    lowered = []
    for n, rows in ((net, replicate_state(state, 1)), (sound_net, sound)):
        shapes = _described(rows, sharding)
        lowered.append(_run_and_reduce(n, 6000)._jit_for(shapes).lower(shapes))
    # the program's text; the locations beside it vary with what was traced before
    assert lowered[0].as_text() == lowered[1].as_text()
    named = lowered[0].as_text(debug_info=True)
    for scope in REACH_SCOPES.values():
        assert scope in named, scope
