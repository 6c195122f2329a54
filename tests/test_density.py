"""Replica-density war acceptance: narrow-vs-int32 bit-identity sweeps
(engine.density), the cand_slots reduction identity, and the
telemetry-sized capacity table's dropped==0 guard (engine.capacity).

The comparison rule everywhere: the narrow side is widened through
`widen_proto()` first — raw narrow leaves legitimately differ from the
int32 baseline at sentinel positions (the narrow dtype's max stands in
for INT32_MAX), and that encoding difference is exactly what the
widen/narrow pair is contracted to erase.
"""

from __future__ import annotations

import numpy as np
import pytest

from wittgenstein_tpu.core.registries import registry_batched_protocols

SWEEP_MS = 50

# the density-war protagonists run in tier-1; the long tail of
# registered protocols sweeps under -m slow (same assertion, pure
# compile-time cost)
_FAST = {"handel", "p2phandel", "pingpong", "p2pflood", "p2pflood_faults", "gsf"}
_ALL = [e.name for e in registry_batched_protocols.entries() if e.contract_checks]
_SWEEP = [
    n if n in _FAST else pytest.param(n, marks=pytest.mark.slow) for n in _ALL
]


def _int32_baseline(monkeypatch, proto_cls):
    """Force the pre-density engine: int32 lanes + empty narrow plans."""
    import wittgenstein_tpu.engine.core as core_mod
    from wittgenstein_tpu.engine import density

    monkeypatch.setattr(
        core_mod,
        "lane_plan",
        lambda n, t, narrow=None: density.lane_plan(n, t, False),
    )
    if hasattr(proto_cls, "_narrow_plan"):
        monkeypatch.setattr(proto_cls, "_narrow_plan", lambda self: ())


def _assert_states_equal(jax, narrow_net, out_n, out_w):
    """Bitwise equality after widening the narrow side's proto view.
    np.array_equal compares VALUES, so int16 lanes match their int32
    twins when (and only when) every element agrees."""
    wide = out_n._replace(proto=narrow_net.protocol.widen_proto(out_n.proto))
    for (pa, a), (_, b) in zip(
        jax.tree_util.tree_flatten_with_path(wide)[0],
        jax.tree_util.tree_flatten_with_path(out_w)[0],
    ):
        assert np.array_equal(np.asarray(a), np.asarray(b)), pa


@pytest.mark.parametrize("name", _SWEEP)
def test_narrow_vs_int32_bit_identity(name, monkeypatch):
    import jax

    entry = registry_batched_protocols.get(name)
    net_n, s_n = entry.factory()
    out_n = net_n.run_ms(s_n, SWEEP_MS)

    _int32_baseline(monkeypatch, type(net_n.protocol))
    net_w, s_w = entry.factory()
    assert np.dtype(net_w.lanes.idx) == np.int32
    assert getattr(net_w.protocol, "NARROW_LEAVES", ()) == ()
    out_w = net_w.run_ms(s_w, SWEEP_MS)

    _assert_states_equal(jax, net_n, out_n, out_w)


def test_narrow_bit_identity_fused_flat():
    """Flat-mode flagship protocol with the fused step: the narrow run's
    widened state matches the int32 baseline bitwise (score cache ON —
    the TPU production config)."""
    import jax

    from wittgenstein_tpu.protocols.handel import HandelParameters
    from wittgenstein_tpu.protocols.handel_batched import BatchedHandel, make_handel

    p = HandelParameters(
        node_count=64,
        threshold=57,
        pairing_time=3,
        level_wait_time=20,
        extra_cycle=5,
        dissemination_period_ms=10,
        fast_path=5,
        nodes_down=0,
    )
    net_n, s_n = make_handel(p, fuse_step=True)
    out_n = net_n.run_ms(s_n, 200)

    mp = pytest.MonkeyPatch()
    try:
        _int32_baseline(mp, BatchedHandel)
        net_w, s_w = make_handel(p, fuse_step=True)
        out_w = net_w.run_ms(s_w, 200)
    finally:
        mp.undo()
    _assert_states_equal(jax, net_n, out_n, out_w)


def test_narrow_bit_identity_telemetry_wheel():
    """Wheel-mode protocol, telemetry-armed: instrumentation and
    narrowing compose without perturbing either side (SL403 twin)."""
    import jax

    from wittgenstein_tpu.protocols.pingpong_batched import make_pingpong
    from wittgenstein_tpu.telemetry import TelemetryConfig

    net_n, s_n = make_pingpong(64)
    tnet_n, ts_n = net_n.with_telemetry(s_n, TelemetryConfig())
    out_n = tnet_n.run_ms(ts_n, SWEEP_MS)

    mp = pytest.MonkeyPatch()
    try:
        _int32_baseline(mp, type(net_n.protocol))
        net_w, s_w = make_pingpong(64)
        tnet_w, ts_w = net_w.with_telemetry(s_w, TelemetryConfig())
        out_w = tnet_w.run_ms(ts_w, SWEEP_MS)
    finally:
        mp.undo()
    _assert_states_equal(jax, tnet_n, out_n, out_w)


def test_cand_slots_reduction_bit_identity():
    """The autotuner's K lever: with cand_slots above the measured
    occupancy HWM, the reduced top-K buffer retains the same entries
    every tick (it is re-sorted), so observables are bit-identical."""
    from wittgenstein_tpu.protocols.handel_batched import make_handel
    from wittgenstein_tpu.scenarios.handel_scenarios import flagship_params

    import dataclasses

    p = flagship_params(256)
    net8, s8 = make_handel(p)
    net5, s5 = make_handel(dataclasses.replace(p, cand_slots=5))
    assert net5.protocol.CAND_SLOTS == 5
    out8 = net8.run_ms(s8, 400, True)
    out5 = net5.run_ms(s5, 400, True)
    assert np.array_equal(np.asarray(out8.done_at), np.asarray(out5.done_at))
    for leaf in ("agg", "ind", "window"):
        assert np.array_equal(
            np.asarray(net8.protocol.widen_proto(out8.proto)[leaf]),
            np.asarray(net5.protocol.widen_proto(out5.proto)[leaf]),
        ), leaf


# ---------------------------------------------------------------------------
# capacity table (engine.capacity / CAPACITY.json)
# ---------------------------------------------------------------------------


def test_capacity_table_checked_in_and_valid():
    from wittgenstein_tpu.engine.capacity import (
        capacity_path,
        load_capacity,
        validate_table,
    )

    table = load_capacity()
    assert table is not None, (
        f"{capacity_path()} missing/invalid — run scripts/density_autotune.py"
    )
    assert validate_table(table) == []
    # every probe must have been loss-free: dropped>0 means the sizing
    # evidence itself is dishonest
    for key, e in table["entries"].items():
        assert int(e.get("dropped", 0)) == 0, key


def test_sized_capacity_drops_nothing_and_matches():
    """dropped==0 regression pinning the recorded HWM table: a wheel
    sized to the table's knobs runs the probe horizon without losing a
    message and with bit-identical observables."""
    import jax

    from wittgenstein_tpu.engine.capacity import load_capacity, lookup, sized_overrides
    from wittgenstein_tpu.engine.core import BatchedNetwork
    from wittgenstein_tpu.protocols.pingpong_batched import make_pingpong

    net_d, s_d = make_pingpong(64)
    entry = lookup(load_capacity(), "pingpong", 64)
    assert entry is not None, "pingpong@64 missing from CAPACITY.json"
    eng = sized_overrides(entry)["engine"]
    assert "wheel_slots" in eng and "overflow_capacity" in eng

    orig_init = BatchedNetwork.__init__

    def sized_init(self, *args, **kwargs):
        for k, v in eng.items():
            kwargs.setdefault(k, v)
        orig_init(self, *args, **kwargs)

    mp = pytest.MonkeyPatch()
    try:
        mp.setattr(BatchedNetwork, "__init__", sized_init)
        net_s, s_s = make_pingpong(64)
    finally:
        mp.undo()
    assert net_s.wheel_slots == eng["wheel_slots"]
    assert net_s.overflow_capacity == eng["overflow_capacity"]

    ms = int(entry.probe.get("sim_ms", 200))
    out_s = net_s.run_ms(s_s, ms)
    assert int(out_s.dropped) == 0
    assert int(out_s.census.wheel_fill_peak) <= eng["wheel_slots"]
    assert int(out_s.census.lane_live_peak) <= eng["overflow_capacity"]
    # observables vs the default-sized wheel: store geometry differs, so
    # compare what the sim reports, not the raw store leaves
    out_d = net_d.run_ms(s_d, ms)
    assert np.array_equal(np.asarray(out_d.done_at), np.asarray(out_s.done_at))
    assert np.array_equal(
        np.asarray(out_d.proto["pong"]), np.asarray(out_s.proto["pong"])
    )


def test_size_from_hwm_rule():
    from wittgenstein_tpu.engine.capacity import size_from_hwm

    assert size_from_hwm(0) == 16  # floor
    assert size_from_hwm(5, floor=8) == 8  # ceil(7.5) -> floor 8 -> x8
    assert size_from_hwm(100) == 152  # ceil(150) -> 152 (multiple of 8)
    assert size_from_hwm(100, margin=1.0) == 104


# ---------------------------------------------------------------------------
# density primitives (engine.density)
# ---------------------------------------------------------------------------


def test_narrowest_int_and_lane_plan():
    from wittgenstein_tpu.engine.density import lane_plan, narrowest_int

    assert narrowest_int(100) == np.dtype(np.int8)
    assert narrowest_int(127) == np.dtype(np.int8)
    assert narrowest_int(127, reserve_sentinel=True) == np.dtype(np.int16)
    assert narrowest_int(32767) == np.dtype(np.int16)
    assert narrowest_int(2**31 - 1) == np.dtype(np.int32)
    with pytest.raises(ValueError):
        narrowest_int(2**31)

    plan = lane_plan(4096, 5)
    assert plan.idx == np.dtype(np.int16)  # lanes never go below int16
    assert plan.mtype == np.dtype(np.int8)
    assert lane_plan(40_000, 5).idx == np.dtype(np.int32)
    base = lane_plan(4096, 5, narrow=False)
    assert base.idx == base.mtype == np.dtype(np.int32)


def test_widen_narrow_sentinel_roundtrip():
    import jax.numpy as jnp

    from wittgenstein_tpu.engine.density import (
        INT32_MAX,
        NarrowLeaf,
        narrow_leaf,
        widen_leaf,
    )

    spec = NarrowLeaf("x", "int16", 1000, sentinel=True)
    x = jnp.array([0, 7, int(INT32_MAX), 1000], jnp.int32)
    nx = narrow_leaf(x, spec)
    assert nx.dtype == jnp.int16
    assert int(nx[2]) == np.iinfo(np.int16).max
    back = widen_leaf(nx, spec)
    assert np.array_equal(np.asarray(back), np.asarray(x))
