"""Config-ablation matrix: price each engine/protocol lever per tick.

The r4→r5 CPU regression (round files since deleted: 1.463 → 1.174 sims/s at
256x4, ~20%) came from two parity fixes whose per-tick price was never
isolated: CHANNEL_DEPTH 8→32 and the boundary-view selection.  This
module measures each lever alone AND the combined pre-r5 configuration,
so the regression decomposes into named levers plus an interaction
residual instead of folklore.

Every config is a FRESH build (fresh jit identity — static flags are in
cache_key, but a fresh engine keeps the matrix honest even if a lever
forgets to register itself), warmed with a real run_ms_batched pass for
realistic channel occupancy, then timed with the shared
telemetry.phases harness (warmup-discarded, mean+stddev).  A lever's
delta is flagged untrustworthy when it is inside 2x the combined
stddev of the two configs it compares.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional

DEFAULT_WARM_MS = 120
WHEEL_LEVER_ROWS = 512  # engine.core.DEFAULT_WHEEL_ROWS


def flagship_params(node_ct: int):
    """The BASELINE.json flagship Handel configuration at `node_ct`
    (shared with bench.py — ONE definition of the headline config)."""
    from ..protocols.handel import HandelParameters

    return HandelParameters(
        node_count=node_ct,
        threshold=int(node_ct * 0.99),
        pairing_time=3,
        level_wait_time=50,
        extra_cycle=10,
        dissemination_period_ms=10,
        fast_path=10,
        nodes_down=0,
    )


def _lever_builders(node_ct: int) -> Dict[str, Callable]:
    """name -> () -> (net, state).  "base" is the CURRENT bench config
    (r5+: D=32, boundary view, flat store, no side-cars, annotations
    on); every other entry flips exactly one lever except "pre_r5",
    which flips both r5 parity levers at once for exact attribution."""
    from ..protocols.handel_batched import make_handel

    def p(channel_depth=None):
        params = flagship_params(node_ct)
        if channel_depth is not None:
            params.channel_depth = channel_depth
        return params

    def base():
        return make_handel(p())

    def channel_depth_8():
        return make_handel(p(channel_depth=8))

    def boundary_view_off():
        return make_handel(p(), boundary_view=False)

    def pre_r5():
        return make_handel(p(channel_depth=8), boundary_view=False)

    def wheel():
        return make_handel(p(), wheel_rows=WHEEL_LEVER_ROWS)

    def score_cache_on():
        return make_handel(p(), score_cache=True)

    def score_cache_off():
        return make_handel(p(), score_cache=False)

    def fuse_step():
        return make_handel(p(), fuse_step=True)

    def wheel_fused():
        return make_handel(p(), wheel_rows=WHEEL_LEVER_ROWS, fuse_step=True)

    def bitops_pallas():
        # the flip happens via LEVER_ENV (WITT_BITOPS=pallas is read at
        # trace time and folded into the engine cache_key)
        return make_handel(p())

    def telemetry_on():
        from ..telemetry import TelemetryConfig

        net, state = make_handel(p())
        return net.with_telemetry(state, TelemetryConfig())

    def faults_on():
        net, state = make_handel(p())
        return net.with_faults(state, plan=None)  # neutral schedule

    def annotations_off():
        return make_handel(p(), annotate=False)

    return {
        "base": base,
        "channel_depth_8": channel_depth_8,
        "boundary_view_off": boundary_view_off,
        "pre_r5": pre_r5,
        "wheel": wheel,
        "score_cache_on": score_cache_on,
        "score_cache_off": score_cache_off,
        "fuse_step": fuse_step,
        "wheel_fused": wheel_fused,
        "bitops_pallas": bitops_pallas,
        "telemetry_on": telemetry_on,
        "faults_on": faults_on,
        "annotations_off": annotations_off,
    }


LEVER_NOTES = {
    "base": "current flagship config (r5+): D=32, boundary view, flat, "
    "bare, score cache backend-auto",
    "channel_depth_8": "r4 channel depth (D=8 vs 32) — the displacement fix's price",
    "boundary_view_off": "pre-r5 same-tick selection (NOT parity-correct)",
    "pre_r5": "both r5 parity levers off — the r4 hot loop",
    "wheel": f"time-wheel store (wheel_rows={WHEEL_LEVER_ROWS}) vs flat",
    "score_cache_on": "carried candidate-score caches PINNED ON (base is "
    "backend-auto: on-TPU only) — on CPU this row prices the cache's "
    "maintenance cost, on TPU it ~= base",
    "score_cache_off": "carried candidate-score caches PINNED OFF — full "
    "popcount recompute (on TPU this row prices lever 1; on CPU it ~= "
    "base)",
    "fuse_step": "delivery+tick fused under one scope (flat: ~0 on CPU — "
    "run-to-run noise dominates; see wheel_fused)",
    "wheel_fused": "fused step on the wheel store — measured against `wheel`, not base",
    "bitops_pallas": "Pallas bitset kernels (interpret-mode penalty off-TPU; real lever on TPU)",
    "telemetry_on": "in-graph counter side-car armed",
    "faults_on": "fault side-car armed, neutral schedule",
    "annotations_off": "named-scope phase markers stripped (overhead bound)",
}

# per-lever env overrides, applied around BOTH the build and the timed
# trace (bitops_backend() is read at trace time) and restored afterwards
LEVER_ENV: Dict[str, Dict[str, str]] = {
    "bitops_pallas": {"WITT_BITOPS": "pallas"},
}

# levers whose delta is measured against a config OTHER than base
# (wheel_fused prices fusion where delivery is wide; against base it
# would mostly re-measure the wheel-vs-flat delta)
LEVER_BASELINE: Dict[str, str] = {
    "wheel_fused": "wheel",
}

SMOKE_LEVERS = (
    "base",
    "channel_depth_8",
    "boundary_view_off",
    "pre_r5",
    "score_cache_on",
    "score_cache_off",
    "fuse_step",
    "bitops_pallas",
)


def smoke_ablation_configs() -> List[str]:
    """The CI-tier subset: the levers the r4→r5 attribution needs."""
    return list(SMOKE_LEVERS)


def ablation_matrix(
    node_ct: int = 256,
    n_replicas: int = 4,
    scans: int = 25,
    repeats: int = 3,
    warm_ms: int = DEFAULT_WARM_MS,
    levers: Optional[List[str]] = None,
    tracer=None,
) -> dict:
    """Measure full-step tick cost for each lever config.  Returns
    {"config", "backend", "configs": {name: {tick_us, std_us, ...}}}."""
    import jax

    from ..engine import replicate_state
    from ..telemetry.phases import scan_phase_seconds

    builders = _lever_builders(node_ct)
    names = levers if levers is not None else list(builders)
    unknown = sorted(set(names) - set(builders))
    if unknown:
        raise ValueError(f"unknown ablation levers: {unknown}")
    if "base" not in names:
        names = ["base"] + list(names)

    import os

    configs: Dict[str, dict] = {}
    for name in names:
        env = LEVER_ENV.get(name, {})
        saved = {k: os.environ.get(k) for k in env}
        os.environ.update(env)
        try:
            net, state = builders[name]()
            states = replicate_state(state, n_replicas)
            states = net.run_ms_batched(states, warm_ms)  # realistic occupancy
            jax.block_until_ready(states)
            t = scan_phase_seconds(
                states, {"full_step": net.step}, scans, tracer, repeats=repeats
            )["full_step"]
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
        configs[name] = {
            "tick_us": round(t["mean_s"] * 1e6, 2),
            "std_us": round(t["std_s"] * 1e6, 2),
            "min_us": round(t["min_s"] * 1e6, 2),
            "note": LEVER_NOTES.get(name, ""),
        }
    return {
        "config": {
            "node_count": node_ct,
            "n_replicas": n_replicas,
            "scans": scans,
            "repeats": repeats,
            "warm_ms": warm_ms,
        },
        "backend": jax.default_backend(),
        "configs": configs,
    }


def lever_report(matrix: dict) -> dict:
    """Rank levers by |per-tick delta vs base| and decompose the r4→r5
    regression into its two named levers + interaction residual.

    Sign convention: delta_us > 0 means the LEVER CONFIG is cheaper
    than its comparison config (base, or LEVER_BASELINE[name]) by that
    much per tick — i.e. the comparison config PAYS delta_us for what
    the lever removes."""
    configs = matrix["configs"]
    base = configs["base"]
    levers = []
    for name, c in configs.items():
        if name == "base":
            continue
        cmp_name = LEVER_BASELINE.get(name, "base")
        cmp_cfg = configs.get(cmp_name, base)
        if cmp_name not in configs:
            cmp_name = "base"
        delta = cmp_cfg["tick_us"] - c["tick_us"]
        spread = 2.0 * (cmp_cfg["std_us"] + c["std_us"])
        row = {
            "lever": name,
            "tick_us": c["tick_us"],
            "delta_us": round(delta, 2),
            "delta_pct_of_base": (
                round(delta / cmp_cfg["tick_us"] * 100, 1)
                if cmp_cfg["tick_us"]
                else None
            ),
            "trustworthy": abs(delta) > spread,
            "note": c.get("note", ""),
        }
        if cmp_name != "base":
            row["vs"] = cmp_name
        levers.append(row)
    levers.sort(key=lambda r: -abs(r["delta_us"]))

    report = {
        "base_tick_us": base["tick_us"],
        "base_std_us": base["std_us"],
        "ranked_levers": levers,
    }

    # r4→r5 attribution: base (r5) vs pre_r5 (r4 levers), decomposed
    if "pre_r5" in configs:
        total = base["tick_us"] - configs["pre_r5"]["tick_us"]
        parts = {}
        if "channel_depth_8" in configs:
            parts["channel_depth_32_us"] = round(
                base["tick_us"] - configs["channel_depth_8"]["tick_us"], 2
            )
        if "boundary_view_off" in configs:
            parts["boundary_view_us"] = round(
                base["tick_us"] - configs["boundary_view_off"]["tick_us"], 2
            )
        interaction = total - sum(parts.values())
        report["r4_to_r5_attribution"] = {
            "total_regression_us_per_tick": round(total, 2),
            **parts,
            "interaction_us": round(interaction, 2),
            "note": (
                "positive = the r5 parity config pays this much more per"
                " tick than the r4 config; levers measured one-at-a-time"
                " from base, interaction = total - sum(parts)"
            ),
        }

    if "annotations_off" in configs:
        off = configs["annotations_off"]["tick_us"]
        if off:
            report["annotation_overhead_pct"] = round(
                (base["tick_us"] - off) / off * 100, 2
            )
    return report


def format_lever_report(report: dict) -> str:
    """Human rendering of lever_report() for bench --phase-profile's
    stderr and the CI artifact."""
    lines = [
        f"base full-step: {report['base_tick_us']:.1f} us/tick"
        f" (+-{report['base_std_us']:.1f})",
        f"{'lever':<20} {'us/tick':>9} {'delta':>8} {'%base':>6}  trust note",
    ]
    for r in report["ranked_levers"]:
        trust = "ok " if r["trustworthy"] else "~? "
        vs = f" [vs {r['vs']}]" if r.get("vs") else ""
        lines.append(
            f"{r['lever']:<20} {r['tick_us']:>9.1f} {r['delta_us']:>8.1f}"
            f" {r['delta_pct_of_base'] or 0:>5.1f}%  {trust} {r['note']}{vs}"
        )
    attr = report.get("r4_to_r5_attribution")
    if attr:
        lines.append("r4->r5 regression attribution (us/tick):")
        for k in (
            "total_regression_us_per_tick",
            "channel_depth_32_us",
            "boundary_view_us",
            "interaction_us",
        ):
            if k in attr:
                lines.append(f"  {k:<28} {attr[k]:>8.2f}")
    if "annotation_overhead_pct" in report:
        lines.append(
            f"annotation overhead: {report['annotation_overhead_pct']:+.2f}%"
        )
    return "\n".join(lines)
