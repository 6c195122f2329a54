"""Read the numbers the timed-rows limits are set from (steps 4 and 5 of
"How `correct` is decided"): the reference's mean messages sent per live
node at the configuration's own node count, at every `--stops` time, for
sound seeds and, put in the program's place, for each control in
`timed_rows.controls` (the reference with one stated parameter wrong).
Host only: the program's side of the sound readings is what the chip
runs print (`timed-rows` and `invariants-chunks` notes).

    python3 benchmark/tests/calibrate_timed_rows.py <config> --seeds 7001,912367 --stops 10,20,30
"""

from __future__ import annotations

import argparse
import concurrent.futures
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.dirname(BENCH_DIR), BENCH_DIR):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import timed_rows  # noqa: E402
import twin  # noqa: E402


def _one(job):
    config, seed, stops, overrides = job
    return timed_rows.reference_sent(config, twin.row_seeds(seed, 1)[0], stops, overrides)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("config")
    ap.add_argument("--seeds", default="7001,912367,1500000001,2147483659,2147490001,3000000019")
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--stops", default="10,20,30")
    ap.add_argument("--workers", type=int, default=os.cpu_count())
    args = ap.parse_args()
    with open(os.path.join(BENCH_DIR, "configs", args.config + ".json")) as f:
        config = json.load(f)
    seeds = [int(s) for s in args.seeds.split(",")]
    stops = [int(s) for s in args.stops.split(",")]
    jobs = [(None, s) for s in seeds] + [
        (c, s) for c in config["timed_rows"]["controls"] for s in seeds[: args.control_seeds]
    ]
    with concurrent.futures.ProcessPoolExecutor(args.workers) as pool:
        rows = list(pool.map(_one, [(config, s, stops, c) for c, s in jobs]))
    sound = {s: r for (c, s), r in zip(jobs, rows) if c is None}
    print(json.dumps({"config": args.config, "stops_ms": stops}))
    for (c, s), r in zip(jobs, rows):
        line = {"reference": json.dumps(c) if c else "sound", "seed": s, "sent_mean": r}
        if c:
            line["rel_gap_to_sound"] = [abs(a - b) / b for a, b in zip(r, sound[s])]
        print(json.dumps(line), flush=True)
    means = [sum(col) / len(col) for col in zip(*sound.values())]
    print(json.dumps({"sound_seed_to_seed_rel_spread": [
        (max(col) - min(col)) / m for col, m in zip(zip(*sound.values()), means)]}))


if __name__ == "__main__":
    main()
