"""A stated completion and rows that end at a horizon, guarded by the
tier-1 run: the numpy cases of benchmark/tests/test_completion.py (its
parts 1 to 3: `twin.compare` with and without `twin.completion` on
recorded pairs and on arrays made by hand, `run_window` over a step made
by hand, `check_invariants` with `done_at_ahead_ms`; no program runs, a
second in all), loaded from that file so that there is one copy of them,
as tests/test_benchmark_conservation.py loads test_conservation.py's.

Part 4 of that file drives the draft `sanfermin-256` through the harness
(two minutes) and stays with the tests that are run by hand; the cells
that stand on these keys are `sanfermin-4096.*`.
"""

import importlib.util
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "benchmark")
for _p in (BENCH_DIR, os.path.join(BENCH_DIR, "tests")):
    if _p not in sys.path:
        sys.path.append(_p)  # `cells`, `run`, `twin`, `test_correct`: as benchmark/tests/conftest.py

_spec = importlib.util.spec_from_file_location(
    "benchmark_tests_test_completion",
    os.path.join(BENCH_DIR, "tests", "test_completion.py"),
)
_cases = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_cases)

# part 4 starts where the draft's cell is made
_PART_4 = _cases._draft_cell.__code__.co_firstlineno
globals().update(
    {
        name: fn
        for name, fn in vars(_cases).items()
        if name.startswith("test_") and fn.__code__.co_firstlineno < _PART_4
    }
)


def test_parts_one_to_three_are_loaded_and_part_four_is_not():
    loaded = {name for name in globals() if name.startswith("test_")}
    assert "test_with_no_completion_stated_compare_is_the_parents" in loaded
    assert "test_rows_are_replaced_at_the_horizon_and_the_next_chunk_is_marked_first" in loaded
    assert "test_the_drafts_rehearsal_runs_to_its_end_with_its_checks_passed" not in loaded
    assert len(loaded) >= 16  # fifteen functions of theirs (34 cases) and this one


def test_the_reading_of_casper_1024_resolves_on_both_sides():
    """`twin.completion.reading` of the real configuration, on a rehearsal
    build: `proto.head_score` (the head's height and the fork choice's
    count for it, one integer) is a per-node leaf of the program's state
    and `head_score` an attribute of every node of the reference, so
    `twin.check_stated` passes in set-up; a path or an attribute that is
    not there stops the run there (PR 36)."""
    import copy

    import pytest

    import cells
    import twin
    from wittgenstein_tpu.engine import replicate_state

    config = cells.load_cell("casper-1024.single-r1-s8000").config
    assert twin.reading(config) == {"program": "proto.head_score", "reference": "head_score"}
    params = cells.build_params(config, config["params_class"], {"node_count": 64})
    net, state = cells.resolve(config["factory"])(params, **config["factory_kwargs"])
    rows = replicate_state(state, 2, seeds=[1, 2])
    twin.check_stated(config, rows)
    assert twin.program_reading(config, rows).shape == (2, 1 + 2 + 64)
    for side, wrong in (("program", "proto.blk_parent"), ("reference", "head_skore")):
        broken = copy.deepcopy(config)
        broken["twin"]["completion"]["reading"][side] = wrong
        with pytest.raises(cells.BenchmarkFileError, match=wrong.split(".")[-1]):
            twin.check_stated(broken, rows)


def test_the_reading_of_dfinity_4096_resolves_on_both_sides(no_compile_cache):  # Dfinity's programs stay off the cache: tests/test_dfinity_batched.py
    """`twin.completion.reading` of `dfinity-4096`, on a rehearsal build:
    `proto.chain_score` (the head's height and the most votes the node
    has counted for one block, one integer) is a per-node leaf of the
    program's state and `chain_score` an attribute of every node of the
    reference's copy, whatever its role, so `twin.check_stated` passes in
    set-up; a path or an attribute that is not there stops the run there."""
    import copy

    import pytest

    import cells
    import twin
    from wittgenstein_tpu.engine import replicate_state

    config = cells.load_cell("dfinity-4096.single-r1-c6000-h18000").config
    assert twin.reading(config) == {"program": "proto.chain_score", "reference": "chain_score"}
    params = cells.build_params(config, config["params_class"], config["rehearsal"]["params"])
    net, state = cells.resolve(config["factory"])(params, **config["factory_kwargs"])
    rows = replicate_state(state, 2, seeds=[1, 2])
    twin.check_stated(config, rows)
    assert twin.program_reading(config, rows).shape == (2, 1 + 64 + 10 + 16)
    reference = cells.build_reference(config, config["twin"]["params"])
    reference.init()
    roles = {type(node).__name__ for node in reference.network().all_nodes}
    assert roles == {"_ObserverNode", "AttesterNode", "BlockProducerNode", "RandomBeaconNode"}
    assert reference.params.node_count == 256 and len(reference.network().all_nodes) == 283
    for side, wrong in (("program", "proto.blk_parent"), ("reference", "chain_skore")):
        broken = copy.deepcopy(config)
        broken["twin"]["completion"]["reading"][side] = wrong
        with pytest.raises(cells.BenchmarkFileError, match=wrong.split(".")[-1]):
            twin.check_stated(broken, rows)


def test_the_reading_of_dfinity_4096_part20_resolves_on_both_sides(no_compile_cache):  # Dfinity's programs stay off the cache: tests/test_dfinity_batched.py
    """`twin.completion.reading` of `dfinity-4096-part20`: the beacon
    height a node has heard, `proto.last_beacon` in the program and
    `last_random_beacon` on every node of the PARTITIONED reference's copy
    (`witt_ref.protocols.dfinity_part`, whose `init()` draws the line), and
    not `chain_score`: behind the line a head stays at genesis and all but
    a few nodes hold no vote, so chain_score's P10 is 0 on both sides and
    the twin's relative gap 0/0.  The beacon height is 1 behind the line
    (the first result, from the beacon nodes on that side) and the
    chain's on the larger side, so no quantile of the reference is 0.  The
    twin's controls put a wrong line, or none, in the program's place."""
    import copy

    import numpy as np
    import pytest

    import cells
    import twin
    from wittgenstein_tpu.engine import replicate_state

    config = cells.load_cell("dfinity-4096-part20.single-r1-c6000-h18000").config
    assert twin.reading(config) == {"program": "proto.last_beacon", "reference": "last_random_beacon"}
    params = cells.build_params(config, config["params_class"], config["rehearsal"]["params"])
    assert params.partition == 0.2 and type(params).__name__ == "PartitionedDfinityParameters"
    net, state = cells.resolve(config["factory"])(params, **config["factory_kwargs"])
    rows = replicate_state(state, 2, seeds=[1, 2])
    twin.check_stated(config, rows)
    assert twin.program_reading(config, rows).shape == (2, 1 + 64 + 10 + 16)
    reference = cells.build_reference(config, config["twin"]["params"])
    assert type(reference).__module__ == "witt_ref.protocols.dfinity_part"
    reference.init()
    assert reference.network().partitions_in_x == [400] and reference.network().dropped > 0
    assert (reference.params.node_count, reference.params.attesters_per_round) == (256, 32)
    assert len(reference.network().all_nodes) == 1 + 256 + 10 + 32
    controls = config["twin"]["controls"]
    # no line; the line elsewhere; one that leaves no side a majority; another population
    assert all(c in controls for c in ({"partition": 0}, {"partition": 0.4}, {"partition": 0.5}, {"population_seed": 1}))
    assert config["params"]["population_seed"] == 0  # who is behind the line: stated, the same on both sides
    unpartitioned = cells.build_reference(config, {**config["twin"]["params"], "partition": 0})
    unpartitioned.init()
    assert unpartitioned.network().partitions_in_x == [] and unpartitioned.network().dropped == 0
    # the reading through 9000 ms of the rehearsal's rows: 1 behind the line, the chain's elsewhere
    out = net.run_ms_batched(rows, 9000)
    heard = np.asarray(twin.program_reading(config, out))[0]
    behind = np.asarray(state.x) < 400
    assert set(heard[behind].tolist()) == {1} and set(heard[~behind].tolist()) == {3}
    for side, wrong in (("program", "proto.blk_parent"), ("reference", "last_random_bacon")):
        broken = copy.deepcopy(config)
        broken["twin"]["completion"]["reading"][side] = wrong
        with pytest.raises(cells.BenchmarkFileError, match=wrong.split(".")[-1]):
            twin.check_stated(broken, rows)
