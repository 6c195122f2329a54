"""Read the numbers the twin's tolerance is set from (steps 4 and 5 of
"How `correct` is decided"): for a configuration, the relative gaps of
P10/P50/P90 and of the messages sent over a list of seeds for the sound program, and for each
control in `twin.controls` (a wrong parameter put in the program's
place) over the first seeds.  Runs wherever JAX runs; the twin is small
enough for the CPU, and on the chip it costs seconds a seed.

    python3 benchmark/tests/calibrate_twin.py <config> [--seeds N] [--control-seeds M]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.dirname(BENCH_DIR), BENCH_DIR):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import twin  # noqa: E402

SEEDS = [11, 4242, 99991, 2**31 + 5, 1234567, 2**31 - 3, 31, 777777, 2**30 + 17,
         5550123, 808, 2**31 + 900001, 65537, 19, 3000000011, 424243]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("config")
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args()
    import jax

    with open(os.path.join(BENCH_DIR, "configs", args.config + ".json")) as f:
        config = json.load(f)
    config["twin"]["tolerance"] = [float("inf")] * 3  # read, do not judge
    config["twin"]["traffic_tolerance"] = float("inf")
    print(json.dumps({"device": jax.devices()[0].platform, "config": args.config}), flush=True)
    worst = {"sound": [0.0, 0.0, 0.0, 0.0]}  # P10, P50, P90, messages sent
    for overrides, seeds in [(None, SEEDS[: args.seeds])] + [
        (c, SEEDS[: args.control_seeds]) for c in config["twin"].get("controls", [])
    ]:
        key = json.dumps(overrides) if overrides else "sound"
        for seed in seeds:
            t0 = time.perf_counter()
            r = twin.check(config, seed, overrides)
            gaps = r["rel_gap"] if r["program_all_done"] else [float("inf")] * 3
            gaps = gaps + [r["msg_sent_rel_gap"]]
            if overrides is None:
                worst[key] = [max(a, b) for a, b in zip(worst[key], gaps)]
            else:
                worst[key] = [min(a, b) for a, b in zip(worst.get(key, [float("inf")] * 4), gaps)]
            print(json.dumps({"program": key, "seed": seed, "rel_gap": gaps,
                              "program_q": r["program_q"], "reference_q": r["reference_q"],
                              "msg_sent": [r["program_msg_sent"], r["reference_msg_sent"]],
                              "all_done": r["program_all_done"],
                              "seconds": round(time.perf_counter() - t0, 1)}), flush=True)
    print(json.dumps({"numbers": ["P10", "P50", "P90", "msg_sent"],
                      "largest_sound_gap": worst.pop("sound"),
                      "smallest_gap_per_control": worst}), flush=True)


if __name__ == "__main__":
    main()
