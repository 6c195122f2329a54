"""Batched sweep runner + HandelScenarios battery (HandelScenarios.java:22
rebuilt as stacked vmap sweeps with CSV output)."""

import numpy as np
import pytest

from wittgenstein_tpu.scenarios.handel_scenarios import (
    CSV_FIELDS,
    run_scenario,
)
from wittgenstein_tpu.scenarios.sweep import (
    SweepConfig,
    default_params,
    run_sweep,
)


class TestSweepRunner:
    def test_mixed_static_params_not_merged(self):
        """Configs with different traced-static parameters (threshold!)
        must not share a compiled program — the sweep that found this bug:
        different dead ratios imply different thresholds."""
        configs = [
            SweepConfig("byz", dr, default_params(64, dead_ratio=dr, byzantine_suicide=dr > 0))
            for dr in (0.1, 0.3)
        ]
        stats = run_sweep(configs, replicas=2, sim_ms=4000)
        for bs in stats:
            assert bs.done_at_min > 0  # every live node converged

    def test_tor_sweep_single_group(self):
        """Tor fractions share one program (only node columns differ), and
        more Tor nodes means slower aggregation."""
        configs = [
            SweepConfig("tor", tor, default_params(32, dead_ratio=0.0, tor=tor))
            for tor in (0.0, 0.5)
        ]
        stats = run_sweep(configs, replicas=2, sim_ms=6000)
        assert all(bs.done_at_min > 0 for bs in stats)
        assert stats[1].done_at_avg > stats[0].done_at_avg

    @pytest.mark.slow
    def test_scenario_csv(self, tmp_path):
        out = tmp_path / "byz.csv"
        stats = run_scenario(
            "byzantine", nodes=32, replicas=2, sim_ms=5000, out=str(out)
        )
        assert len(stats) == 6
        lines = out.read_text().strip().splitlines()
        assert lines[1] == ",".join(CSV_FIELDS)
        assert len(lines) == 2 + 6
        # attack slows aggregation vs the clean config
        assert stats[-1].done_at_avg > stats[0].done_at_avg


class TestOracleScenarioSuites:
    """P2PHandelScenarios + OptimisticP2PSignatureScenarios ports
    (P2PHandelScenarios.java:17-283, OptimisticP2PSignatureScenarios.java)."""

    def test_p2phandel_scaling(self):
        from wittgenstein_tpu.scenarios.oracle_scenarios import p2phandel_scaling

        stats = p2phandel_scaling(rounds=2, max_nodes=64)
        assert len(stats) == 2  # 32, 64
        assert all(bs.done_at_min > 0 for bs in stats)
        # more nodes -> more messages received on average
        assert stats[1].msg_rcv_avg > stats[0].msg_rcv_avg

    def test_optimistic_scaling(self):
        from wittgenstein_tpu.scenarios.oracle_scenarios import optimistic_scaling

        stats = optimistic_scaling(rounds=2, max_nodes=128)
        assert len(stats) == 2
        assert all(bs.done_at_min > 0 for bs in stats)

    def test_p2phandel_sigs_per_time(self, tmp_path):
        from wittgenstein_tpu.scenarios.oracle_scenarios import (
            p2phandel_sigs_per_time,
        )

        out = tmp_path / "sigs.png"
        g = p2phandel_sigs_per_time(node_ct=64, series=2, out=str(out))
        assert out.stat().st_size > 10_000
        # 3 series per run (min/max/avg) x 2 runs
        assert len(g.series) == 6


class TestGenAnim:
    def test_gen_anim_writes_gif(self, tmp_path):
        """genAnim (HandelScenarios.java:291 / Handel.drawImgs :700-768):
        a Handel run rendered through NodeDrawer to an animated GIF."""
        from PIL import Image

        from wittgenstein_tpu.scenarios.handel_scenarios import gen_anim

        dest = str(tmp_path / "handel.gif")
        out = gen_anim(nodes=32, sim_ms=200, frequency_ms=20, dest=dest)
        img = Image.open(out)
        assert img.format == "GIF"
        img.seek(0)
        frames = 1
        try:
            while True:
                img.seek(img.tell() + 1)
                frames += 1
        except EOFError:
            pass
        assert frames == 200 // 20


class TestDeepBattery:
    """The HandelScenarios deep battery: log* sweeps,
    delayedStartImpact arithmetic, window sweep, allScenarios plumbing."""

    def test_delayed_start_impact_arithmetic(self):
        """Pure arithmetic pin (HandelScenarios.java:300-322): 4096 nodes,
        waitTime 50, period 20 -> 612 sends without gating, 444 with."""
        from wittgenstein_tpu.scenarios.handel_scenarios import delayed_start_impact

        assert delayed_start_impact(4096, 50, 20) == (612, 444)
        # no gating (waitTime 0) saves nothing
        m_f, m_s = delayed_start_impact(256, 0, 100)
        assert m_f == m_s

    def test_battery_config_shapes(self):
        """Every battery produces the reference's sweep points."""
        from wittgenstein_tpu.scenarios import handel_scenarios as hs

        assert [c.value for c in hs.log_period_configs(64)] == [
            1, 5, 10, 15, 20, 40, 80, 160, 320, 640]
        assert [c.value for c in hs.log_start_time_configs(64)] == [0, 25, 50, 75, 100]
        assert [c.value for c in hs.log_extra_cycle_configs(64)] == [10, 15, 20, 30, 40, 50]
        assert [c.value for c in hs.log_contacted_configs(64)] == [0, 5, 10, 20, 40]
        assert [c.value for c in hs.log_delayed_start_configs(64)] == [0, 10, 20, 30, 50, 70, 100]
        assert [c.value for c in hs.log_configs(256)] == [64, 128, 256]
        assert len(hs.ALL_BATTERY) == 12  # allScenarios :633-656
        # the CITIES mapping reaches the city latency + builder
        p = hs.log_period_configs(64)[0].params
        assert p.network_latency_name == "NetworkLatencyByCityWJitter"
        assert "CITIES" in p.node_builder_name.upper() or "city" in p.node_builder_name.lower()

    def test_battery_row_oracle_parity(self):
        """One battery row pinned against the oracle DES: logStartTime at
        64 nodes, levelWaitTime=50 — done_at_avg within 15% (the battery
        uses CITIES placement + city latency, desynchronizedStart=100)."""
        from wittgenstein_tpu.protocols.handel import Handel
        from wittgenstein_tpu.scenarios.handel_scenarios import log_start_time_configs

        cfg = log_start_time_configs(64)[2]  # levelWaitTime = 50
        assert cfg.value == 50
        stats = run_sweep([cfg], replicas=4, sim_ms=4000)
        bs = stats[0]
        o_done = []
        for seed in range(4):
            pr = Handel(cfg.params)
            pr.network().rd.set_seed(seed)
            pr.init()
            pr.network().run_ms(4000)
            o_done += [n.done_at for n in pr.network().live_nodes()]
        o_avg = float(np.mean(o_done))
        assert (np.asarray(o_done) > 0).all()
        assert bs.done_at_min > 0
        assert abs(bs.done_at_avg - o_avg) <= 0.15 * o_avg, (bs.done_at_avg, o_avg)

    def test_run_all_plumbing(self, tmp_path):
        """allScenarios writes the combined CSV with the reference ids."""
        from wittgenstein_tpu.scenarios.handel_scenarios import (
            log_start_time_configs,
            run_all,
        )

        out = tmp_path / "all.csv"
        battery = [(lambda n, dead, tor, sid: log_start_time_configs(n, dead, tor, sid)[:2],
                    0.0, 0.0, "10")]
        run_all(32, 1, 3000, str(out), battery=battery)
        lines = out.read_text().strip().splitlines()
        assert len(lines) == 2 + 2  # header comment + fields + 2 rows
        assert lines[2].startswith("10,32,0")

    def test_battery_graphs(self, tmp_path):
        """The reference's PNG pair per battery (e.g. handel_startTime_*)."""
        from wittgenstein_tpu.scenarios.handel_scenarios import (
            BasicStats,
            log_start_time_configs,
            save_battery_graphs,
        )

        cfgs = log_start_time_configs(32)
        stats = [
            BasicStats(100 + i, 120 + i, 140 + i, 10, 20, 30, 1, 2)
            for i in range(len(cfgs))
        ]
        paths = save_battery_graphs("logStartTime", cfgs, stats, str(tmp_path))
        assert sorted(p.split("/")[-1] for p in paths) == [
            "handel_startTime_msg.png", "handel_startTime_time.png"]
        for p in paths:
            assert (tmp_path / p.split("/")[-1]).stat().st_size > 0

    def test_window_sweep_configs(self):
        from wittgenstein_tpu.scenarios.handel_scenarios import window_configs

        cfgs = window_configs(64)
        assert [c.params.window_initial for c in cfgs] == [1, 4, 16, 64, 128]


class TestGSFScenarios:
    """GSFSignature scenario mains (GSFSignature.java:668-768) as CLI
    subcommands."""

    def test_new_protocol_canonical_config(self):
        from wittgenstein_tpu.scenarios.gsf_scenarios import new_protocol

        p = new_protocol(64)
        assert p.params.threshold == int(0.85 * 64)
        assert p.params.nodes_down == 6
        assert p.params.network_latency_name == "AwsRegionNetworkLatency"
        assert "0.33" in p.params.node_builder_name

    def test_sigs_per_time_smoke(self, tmp_path, capsys):
        from wittgenstein_tpu.scenarios.gsf_scenarios import sigs_per_time

        out = tmp_path / "sigs.png"
        sigs_per_time(32, str(out))
        assert out.stat().st_size > 0
        cap = capsys.readouterr().out
        assert "sigChecked" in cap and "speedRatio" in cap

    def test_draw_imgs_smoke(self, tmp_path):
        from wittgenstein_tpu.scenarios.gsf_scenarios import draw_imgs

        out = tmp_path / "anim.gif"
        draw_imgs(32, str(out), freq=20)
        assert out.stat().st_size > 0
