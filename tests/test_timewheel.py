"""Time-wheel message store: parity with the flat ring, occupancy-driven
jumps, TIME_QUANTUM window delivery, spill/drop accounting, and the
checkpoint layout marker (docs/engine_timewheel.md).

The flat store (wheel_rows=0) reproduces the pre-wheel full-scan ring
bit-for-bit, so flat-vs-wheel runs with the same seeds are the parity
oracle for the wheel's scheduling."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from wittgenstein_tpu.engine import BatchedNetwork, BatchedProtocol, Emission
from wittgenstein_tpu.engine.core import replicate_state
from wittgenstein_tpu.core.registries import registry_network_latencies
from wittgenstein_tpu.protocols.pingpong_batched import make_pingpong
from wittgenstein_tpu.telemetry.state import TelemetryConfig


def _cols(n):
    z = np.zeros(n, np.int32)
    return {"x": z, "y": z, "extra_latency": z}


class TestFlatWheelParity:
    def test_pingpong_1000_bit_parity(self):
        """PingPong 1000 nodes, WAN jitter, same seed: the wheel engine
        must reproduce the flat ring's done/pong/traffic columns exactly
        (acceptance criterion — same RNG stream, same delivery ticks)."""
        net_w, s_w = make_pingpong(1000, seed=3)
        net_f, s_f = make_pingpong(1000, seed=3, wheel_rows=0)
        assert not net_w.flat and net_f.flat
        for ms in (1, 300, 300, 300):
            s_w = net_w.run_ms(s_w, ms)
            s_f = net_f.run_ms(s_f, ms)
        assert int(s_w.proto["pong"][0]) == 1000
        for a, b in (
            (s_w.proto["pong"], s_f.proto["pong"]),
            (s_w.msg_received, s_f.msg_received),
            (s_w.msg_sent, s_f.msg_sent),
            (s_w.bytes_received, s_f.bytes_received),
            (s_w.send_ctr, s_f.send_ctr),
            (s_w.dropped, s_f.dropped),
        ):
            assert jnp.array_equal(a, b)
        assert int(s_w.dropped) == 0

    @pytest.mark.slow
    def test_handel_256_bit_parity(self):
        """Handel 256 nodes, same seed, flat vs wheel store: identical
        done_at / traffic columns (the agg channel bypasses the generic
        store, so this pins that the engine rewrite left the channel's
        tick scheduling untouched)."""
        from wittgenstein_tpu.protocols.handel_batched import make_handel
        from wittgenstein_tpu.scenarios.handel_scenarios import flagship_params

        p = flagship_params(256)
        net_f, s_f = make_handel(p)
        net_w, s_w = make_handel(p, wheel_rows=512)
        out_f = net_f.run_ms_batched(replicate_state(s_f, 1), 700)
        out_w = net_w.run_ms_batched(replicate_state(s_w, 1), 700)
        assert (np.asarray(out_f.done_at) > 0).all()
        for a, b in (
            (out_f.done_at, out_w.done_at),
            (out_f.msg_received, out_w.msg_received),
            (out_f.msg_sent, out_w.msg_sent),
            (out_f.proto["displaced"], out_w.proto["displaced"]),
        ):
            assert jnp.array_equal(a, b)


class _DelayProbe(BatchedProtocol):
    """Records, per delivery, how late each message was (time - arrival)
    and how many were delivered — the TIME_QUANTUM contract witness."""

    MSG_TYPES = ["EVT"]
    TICK_INTERVAL = None
    TIME_QUANTUM = 1

    def proto_init(self, n):
        return {
            "max_delay": jnp.int32(-1),
            "delivered": jnp.int32(0),
        }

    def deliver(self, net, state, deliver_mask):
        d = jnp.where(deliver_mask, state.time - state.msg_arrival, -1)
        proto = {
            "max_delay": jnp.maximum(state.proto["max_delay"], jnp.max(d)),
            "delivered": state.proto["delivered"]
            + jnp.sum(deliver_mask.astype(jnp.int32)),
        }
        return state._replace(proto=proto), []


def _probe_net(n=4, quantum=1, wheel_rows=64, **kw):
    proto = _DelayProbe()
    proto.TIME_QUANTUM = quantum
    latency = registry_network_latencies.get_by_name("NetworkFixedLatency(0)")
    net = BatchedNetwork(
        proto, latency, n, capacity=256, wheel_rows=wheel_rows, **kw
    )
    state = net.init_state(_cols(n), seed=0, proto=proto.proto_init(n))
    return net, state


def _schedule(net, state, arrivals):
    arr = jnp.asarray(arrivals, jnp.int32)
    k = arr.shape[0]
    em = Emission(
        mask=jnp.ones(k, bool),
        from_idx=jnp.zeros(k, jnp.int32),
        to_idx=jnp.arange(k, dtype=jnp.int32) % net.n_nodes,
        mtype=0,
        arrival=arr,
    )
    return net.apply_emission(state, em)


class TestTimeQuantum:
    """Satellite regression: a quantum > 1 never skips past `end` and
    never delays an arrival by >= quantum ms (previously only exercised
    implicitly through ENR)."""

    @pytest.mark.parametrize("wheel_rows", [64, 0])
    def test_quantum_rounds_up_without_skipping(self, wheel_rows):
        q = 5
        net, state = _probe_net(quantum=q, wheel_rows=wheel_rows)
        # arrivals off the quantum grid, spanning two run_ms calls, a
        # beyond-horizon entry (87 + 64 < 171) and one just before `end`
        arrivals = [3, 7, 11, 29, 30, 31, 87, 113, 170]
        state = _schedule(net, state, arrivals)
        end1, end2 = 101, 171  # neither a multiple of q
        state = net.run_ms(state, end1)
        assert int(state.time) == end1  # never skips past end
        state = net.run_ms(state, end2 - end1)
        assert int(state.time) == end2
        assert int(state.proto["delivered"]) == len(arrivals)
        md = int(state.proto["max_delay"])
        assert 0 <= md < q, md
        assert int(state.dropped) == 0
        assert int(net.pending_messages(state)) == 0

    def test_quantum_exact_when_one(self):
        net, state = _probe_net(quantum=1)
        state = _schedule(net, state, [2, 9, 33, 64 + 5, 200])
        state = net.run_ms(state, 300)
        assert int(state.proto["delivered"]) == 5
        assert int(state.proto["max_delay"]) == 0  # delivered on the tick
        assert int(state.dropped) == 0

    def test_quantum_larger_than_wheel_fails_loudly(self):
        net, state = _probe_net(quantum=128, wheel_rows=64)
        with pytest.raises(ValueError, match="TIME_QUANTUM"):
            net.run_ms(state, 10)


class TestWheelMechanics:
    def test_same_tick_burst_spills_to_overflow(self):
        """More same-arrival messages than a row holds: the excess spills
        to the overflow lane (exact delivery, nothing dropped)."""
        net, state = _probe_net(wheel_slots=4, overflow_capacity=16)
        state = _schedule(net, state, [10] * 9)
        assert int(jnp.max(state.whl_fill)) == 4  # row full
        assert int(jnp.sum(state.ovf_valid)) == 5  # spill
        state = net.run_ms(state, 20)
        assert int(state.proto["delivered"]) == 9
        assert int(state.proto["max_delay"]) == 0
        assert int(state.dropped) == 0

    def test_one_emission_ranks_its_rows_slot_for_slot(self):
        """A burst of more than `wheel_slots` rows to one wheel row and a
        handful to two others in ONE emission: rows of one wheel row take
        consecutive slots after what the row held, in row order; the spill
        and a beyond-horizon row go to the lane's free slots in row order;
        a masked row takes nothing.  Every value is written out."""
        net, state = _probe_net(n=16, wheel_slots=4, overflow_capacity=16)

        def emit(state, senders, arrivals, mask=None):
            k = len(senders)
            return net.apply_emission(state, Emission(
                mask=jnp.ones(k, bool) if mask is None else jnp.asarray(mask),
                from_idx=jnp.asarray(senders, jnp.int32),
                to_idx=jnp.arange(k, dtype=jnp.int32) % net.n_nodes,
                mtype=0,
                arrival=jnp.asarray(arrivals, jnp.int32),
            ))

        state = emit(state, [15], [10])  # wheel row 10 holds one row already
        senders = [1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13]
        arrivals = [10, 500, 10, 10, 9, 10, 7, 10, 10, 9, 10, 7, 10]
        mask = [True] * 13
        mask[3] = False  # sender 4 sends nothing
        state = emit(state, senders, arrivals, mask)

        fill = np.zeros(64, np.int32)
        fill[[7, 9, 10]] = [2, 2, 4]
        np.testing.assert_array_equal(np.asarray(state.whl_fill), fill)
        valid = np.zeros((64, 4), bool)
        valid[7, :2] = valid[9, :2] = valid[10, :] = True
        np.testing.assert_array_equal(np.asarray(state.msg_valid), valid)
        wheel_from = {7: [7, 12], 9: [5, 10], 10: [15, 1, 3, 6]}  # row 10: three of its seven fit
        for row, want in wheel_from.items():
            assert np.asarray(state.msg_from)[row, : len(want)].tolist() == want, row
            assert np.asarray(state.msg_arrival)[row, : len(want)].tolist() == [row] * len(want)
        # the lane, in row order: the far arrival, then row 10's spill
        assert np.asarray(state.ovf_valid).tolist() == [True] * 5 + [False] * 11
        assert np.asarray(state.ovf_from)[:5].tolist() == [2, 8, 9, 11, 13]
        assert np.asarray(state.ovf_arrival)[:5].tolist() == [500, 10, 10, 10, 10]
        assert int(state.dropped) == 0 and int(state.msg_head) == 13

        state = net.run_ms(state, 600)
        assert int(state.proto["delivered"]) == 13
        assert int(state.proto["max_delay"]) == 0
        assert int(state.dropped) == 0

    def test_genuine_overflow_counts_dropped(self):
        net, state = _probe_net(wheel_slots=2, overflow_capacity=4)
        state = _schedule(net, state, [10] * 9)
        assert int(state.dropped) == 3  # 2 wheel + 4 overflow fit
        state = net.run_ms(state, 20)
        assert int(state.proto["delivered"]) == 6

    def test_beyond_horizon_goes_to_overflow_and_delivers(self):
        net, state = _probe_net(wheel_rows=64)
        state = _schedule(net, state, [500, 1000])
        assert int(jnp.sum(state.ovf_valid)) == 2
        assert int(jnp.sum(state.whl_fill)) == 0
        state = net.run_ms(state, 1100)
        assert int(state.proto["delivered"]) == 2
        assert int(state.proto["max_delay"]) == 0

    def test_occupancy_jump_skips_empty_time(self):
        """The occupancy-word scan must find the exact next arrival (no
        spurious full-wheel scans, no missed rows near the wrap)."""
        net, state = _probe_net(wheel_rows=64)
        state = _schedule(net, state, [2, 63, 64, 65, 127, 128])
        state = net.run_ms(state, 200)
        assert int(state.proto["delivered"]) == 6
        assert int(state.proto["max_delay"]) == 0

    def test_pending_messages_popcount(self):
        net, state = _probe_net()
        assert int(net.pending_messages(state)) == 0
        state = _schedule(net, state, [5, 5, 9, 500])
        # two occupied rows + one overflow entry
        assert int(net.pending_messages(state)) == 3
        state = net.run_ms(state, 600)
        assert int(net.pending_messages(state)) == 0

    def test_census_peaks_report_high_water(self):
        net, state = _probe_net(wheel_slots=8)
        state = _schedule(net, state, [4, 4, 4, 30, 200])
        out = net.run_ms(state, 50)
        assert int(out.census.wheel_fill_peak) == 3
        assert int(out.census.lane_live_peak) == 1  # the 200 sits beyond horizon
        assert int(out.proto["delivered"]) == 4

    def test_donated_run_matches_undonated(self):
        net_a, s_a = make_pingpong(100, seed=5)
        net_b, s_b = make_pingpong(100, seed=5)
        out_a = net_a.run_ms(s_a, 400)
        out_b = net_b.run_ms(s_b, 400, donate=True)  # s_b consumed
        assert jnp.array_equal(out_a.proto["pong"], out_b.proto["pong"])
        assert jnp.array_equal(out_a.msg_received, out_b.msg_received)


class TestCacheKey:
    """The engine's identity is written once: `stable_cache_key` lists the
    statics, `cache_key` adds what tells two instances of one process
    apart."""

    def test_cache_key_is_the_stable_key_and_the_process_ids(self):
        net, _ = _probe_net()
        stable = net.stable_cache_key()
        assert net.cache_key() == stable + (id(net.protocol), id(net.latency), None)
        net.node_mesh = mesh = object()
        assert net.cache_key() == stable + (id(net.protocol), id(net.latency), id(mesh))
        assert not {id(net.protocol), id(net.latency), id(mesh)} & set(stable)

    @pytest.mark.parametrize(
        "base, static",
        [
            pytest.param({}, {"fuse_step": True}, id="fuse_step"),
            pytest.param({}, {"due_view_rows": (8,)}, id="due_view_rows"),
            pytest.param({}, {"telemetry": TelemetryConfig()}, id="telemetry"),
            pytest.param(
                {"telemetry": TelemetryConfig()}, {"telemetry": TelemetryConfig(snapshots=4)},
                id="telemetry-config",
            ),
        ],
    )
    def test_one_static_moves_both_keys(self, base, static):
        net, _ = _probe_net(**base)
        # the same protocol and latency objects: the ids agree
        other = BatchedNetwork(
            net.protocol, net.latency, net.n_nodes, capacity=256, wheel_rows=64,
            **{**base, **static},
        )
        assert other.stable_cache_key() != net.stable_cache_key()
        assert other.cache_key()[:-3] == other.stable_cache_key()
        assert other.cache_key()[-3:] == net.cache_key()[-3:]


class TestCheckpointLayout:
    def test_roundtrip_and_layout_guard(self, tmp_path, monkeypatch):
        from wittgenstein_tpu.engine import checkpoint as cp

        net, state = _probe_net()
        state = _schedule(net, state, [10, 90, 700])
        state = net.run_ms(state, 50)
        dest = str(tmp_path / "wheel.npz")
        cp.save_state(state, dest)
        loaded = cp.load_state(state, dest)
        resumed = net.run_ms(loaded, 700)
        direct = net.run_ms(state, 700)
        assert int(resumed.proto["delivered"]) == int(direct.proto["delivered"])
        assert jnp.array_equal(resumed.msg_received, direct.msg_received)

        # a checkpoint from a different store layout must fail with the
        # layout reason, not a leaf-shape mismatch
        monkeypatch.setattr(cp, "ENGINE_LAYOUT", "flatring-v0")
        stale = str(tmp_path / "stale.npz")
        cp.save_state(state, stale)
        monkeypatch.undo()
        with pytest.raises(ValueError, match="layout"):
            cp.load_state(state, stale)
