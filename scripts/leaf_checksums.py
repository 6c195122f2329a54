#!/usr/bin/env python3
"""Every state leaf's checksum at fixed simulated times, for one benchmark
configuration's program: the comparison that tells "right on the CPU,
different on the chip" (ROADMAP B0, PERF.md section 6).

    python3 scripts/leaf_checksums.py --config benchmark/configs/sanfermin-4096.json \\
        --rows 2 --seed 7001 --step-ms 200 --until-ms 2400 --out chiprun_out/leaves-tpu.jsonl
    python3 scripts/leaf_checksums.py --config benchmark/configs/dfinity-4096.json \
        --rows 1 --step-ms 6000 --until-ms 6000 --out chiprun_out/leaves-tpu.jsonl
    python3 scripts/leaf_checksums.py --config benchmark/configs/dfinity-4096-part20.json --twin \
        --rows 1 --step-ms 6000 --until-ms 18000 --out chiprun_out/leaves-tpu.jsonl   # the line set: `partition_x` and `census` are leaves
    python3 scripts/leaf_checksums.py --compare leaves-cpu.jsonl chiprun_out/leaves-tpu.jsonl

Builds the program by the configuration's own factory, parameters and
`factory_kwargs`, makes `--rows` rows from the row seeds `seed, seed+1, ...`
as the benchmark does, and advances them `--step-ms` at a time through
`sharded_run_stats`; after each step one JSON line: the rows' time and the
position-weighted 32-bit checksum of every leaf (`benchmark/run.py`
`fingerprint`), by the leaf's path.  The factory builds the same program on
every backend, so the lines of a CPU run (`JAX_PLATFORMS=cpu`) and of a run
on the chip are equal leaf for leaf or the chip's program is wrong.
`--compare` prints the first time and the leaves at which two such files
differ, and exits 1 if they do.  `--twin` builds the configuration at its
twin's width (`twin.params` over `params`): a program too slow for the
sandbox's CPU at full width (Casper-1024: 4.5 min a slot) is compared
through many steps there and through a few at full width (Dfinity-4096's
first 6000-ms chunk, one block of 1.84 million messages, takes 20 s there
with its compile and every later chunk a minute).  Each line also
has the step's wall seconds, which `--compare` does not read.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "benchmark")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def compare(path_a: str, path_b: str) -> int:
    with open(path_a) as fa, open(path_b) as fb:
        a, b = ([json.loads(line) for line in f] for f in (fa, fb))
    if [x["time_ms"] for x in a] != [x["time_ms"] for x in b]:
        print(json.dumps({"equal": False, "why": "the two files stop at other times"}))
        return 1
    for x, y in zip(a, b):
        differ = sorted(k for k in x["leaves"] if x["leaves"][k] != y["leaves"].get(k))
        if differ or len(x["leaves"]) != len(y["leaves"]):
            print(json.dumps({"equal": False, "first_at_ms": x["time_ms"], "leaves": differ,
                              "of": len(x["leaves"])}))
            return 1
    print(json.dumps({"equal": True, "stops": len(a), "leaves": len(a[0]["leaves"]),
                      "devices": [a[0]["device"], b[0]["device"]]}))
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--compare", nargs=2, metavar="FILE")
    ap.add_argument("--config", help="benchmark/configs/<name>.json")
    ap.add_argument("--rows", type=int, default=2)
    ap.add_argument("--seed", type=int, default=7001)
    ap.add_argument("--step-ms", type=int, default=200)
    ap.add_argument("--until-ms", type=int, default=2400)
    ap.add_argument("--twin", action="store_true", help="at the width of the configuration's twin")
    ap.add_argument("--out", help="file for the lines (they are printed too)")
    args = ap.parse_args(argv)
    if args.compare:
        return compare(*args.compare)

    import jax
    import numpy as np

    import cells
    import run
    import twin
    from wittgenstein_tpu.engine import replicate_state
    from wittgenstein_tpu.parallel.replica_shard import sharded_run_stats

    with open(args.config) as f:
        config = json.load(f)
    params = cells.build_params(config, config["params_class"],
                                config["twin"]["params"] if args.twin else None)
    net, state = cells.resolve(config["factory"])(params, **config["factory_kwargs"])
    states = replicate_state(state, args.rows, seeds=twin.row_seeds(args.seed, args.rows))
    paths = [jax.tree_util.keystr(path) for path, _ in jax.tree_util.tree_leaves_with_path(states)]
    fingerprint = jax.jit(run.fingerprint)
    device = jax.devices()[0].device_kind
    out = None
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        out = open(args.out, "w")
    for t in range(args.step_ms, args.until_ms + 1, args.step_ms):
        t0 = time.perf_counter()
        states, _stats = sharded_run_stats(net, states, args.step_ms)
        sums = np.asarray(fingerprint(states)).tolist()
        line = json.dumps({"time_ms": t, "device": device, "rows": args.rows, "seed": args.seed,
                           "seconds": round(time.perf_counter() - t0, 3),
                           "dropped": int(np.asarray(states.dropped).max()),
                           "leaves": dict(zip(paths, sums))})
        print(line, flush=True)
        if out:
            out.write(line + "\n")
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
