"""chip_smoke.py — the quickest proof that the flagship path still starts
on the chip.

    python chip_smoke.py              one TPU chip: device, kernels, flagship, twin
    python chip_smoke.py --chips 4    four chips: the replica-sharded path against
                                      one device, and no other phase
    python chip_smoke.py --rehearse   control-flow rehearsal on the CPU, tiny sizes

The flagship path is what sweeps and the serve scheduler's direct runner
use: profiling.flagship_params(4096) -> make_handel(fuse_step=True) ->
engine.replicate_state -> parallel.replica_shard.sharded_run_stats
(net.run_ms_batched inside one jit).  Weights there are none; the node
population and every replica's dynamics come from seeds.

One process, no children.  Every phase prints one JSON line; the last
line of stdout is {"ok": ..., "device": {...}}.  Exit 0 only when every
phase ran on a TPU and passed.  A rehearsal runs every phase on the CPU
with interpreted kernels, so it can only end with "ok": false (exit 4
when all phases passed, 1 otherwise).

The seconds printed here are set-up facts for whoever sizes a benchmark
next (how long the flagship program takes to compile and to run once),
not benchmark numbers.
"""

from __future__ import annotations

import argparse
import collections
import contextlib
import hashlib
import json
import os
import sys
import time

SIM_MS = 1000
FLAGSHIP_NODES = 4096
# Nodes and horizon are never cut; replicas are.  On the v5e one tick of
# the 4096-node program takes 1.2 s at R=8 (PERF.md, PR 23), so a
# 1000-tick run at R=8 alone is 20 minutes; R=1 is what lets a cold run
# of the whole script (compile + two full runs) stay near half of the
# 1200 s limit.
FLAGSHIP_REPLICAS = 1
TWIN_NODES, TWIN_REPLICAS = 256, 4
# --chips 4 keeps N=4096 and R=8 (2 rows per device) and cuts the horizon
# instead: the one-device side of the comparison runs all 8 replicas at
# 1.2 s per tick, and sharding is bit-identical or not at any tick
CHIPS4_REPLICAS = 8
CHIPS4_SIM_MS = 100
# sha256 of the twin's done_at (N=256, R=4, sim_ms=1000) as the CPU backend
# of the sandbox computes it; reported, never gated on
TWIN_CPU_DIGEST = "87bf79f48bdee3f1d4bd82fd44b9d2103a8f228bef5bc1dd6b278b42cdd3c35e"

# kernel shapes: what the 4096-node program passes (popcount only — the
# flat store never packs a wheel), the wheel-occupancy shapes of a
# 512-row wheel, and the odd shapes of tests/test_bitops_pallas.py
FLAGSHIP_WORD_SHAPES = [
    (4096, 1, 2), (4096, 1, 2, 64), (4096, 6, 1), (4096, 6, 8, 1),
    (4096, 8, 64), (4096, 128),
]
ODD_WORD_SHAPES = [
    (1, 1), (3, 2), (5, 4), (7, 3), (2, 7), (4, 64), (129, 5), (3, 2, 9), (16,),
]
PACK_SHAPES = [
    (512,), (1, 1), (3, 31), (5, 32), (2, 33), (7, 65), (4, 200), (3, 2, 40),
]

_CACHE_EVENTS = collections.Counter()


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def digest(a) -> str:
    import numpy as np

    return hashlib.sha256(np.ascontiguousarray(np.asarray(a)).tobytes()).hexdigest()


@contextlib.contextmanager
def bitops_env(value):
    """WITT_BITOPS is read at trace time: hold it around a build + trace.
    None = unset (the backend's own selection)."""
    from wittgenstein_tpu.ops.bitops import BITOPS_ENV

    old = os.environ.pop(BITOPS_ENV, None)
    if value is not None:
        os.environ[BITOPS_ENV] = value
    try:
        yield
    finally:
        os.environ.pop(BITOPS_ENV, None)
        if old is not None:
            os.environ[BITOPS_ENV] = old


def phase_device(args, result) -> None:
    import jax

    devs = jax.devices()
    d = devs[0]
    result["device"] = {
        "platform": d.platform,
        "kind": d.device_kind,
        "count": len(devs),
    }
    if not args.rehearse and d.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (jax.devices()[0] is {d.platform})")
    if len(devs) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} but {len(devs)} devices")

    from wittgenstein_tpu.profiling.hbm import DEFAULT_HBM_GIB
    from wittgenstein_tpu.utils.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    jax.monitoring.register_event_listener(
        lambda event, **kw: _CACHE_EVENTS.update([event])
    )
    emit(
        "device",
        **result["device"],
        bytes_limit=(d.memory_stats() or {}).get("bytes_limit"),
        hbm_assumed_bytes=int(DEFAULT_HBM_GIB * 2**30),
        jax=jax.__version__,
        compile_cache_dir=cache_dir,
        rehearse=args.rehearse,
    )


def phase_kernels(args) -> None:
    """The three Pallas kernels, compiled by Mosaic, against their lax
    twins on the same device: exact equality."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from wittgenstein_tpu.ops import bitops, bitops_pallas

    assert args.rehearse or not bitops_pallas._interpret()
    rng = np.random.RandomState(args.seed)
    cut = (lambda s: (16,) + s[1:]) if args.rehearse else (lambda s: s)
    word_shapes = [cut(s) for s in FLAGSHIP_WORD_SHAPES] + ODD_WORD_SHAPES

    def words(shape):
        w = rng.randint(0, 1 << 32, size=shape, dtype=np.uint32)
        # a sprinkling of all-zero vectors: the kernels must agree on
        # the empty sentinel too
        keep = rng.randint(0, 4, size=shape[:-1] + (1,)) != 0
        return jnp.asarray(w * keep.astype(np.uint32))

    cases = [
        ("popcount", bitops_pallas.popcount_words_pallas,
         bitops._popcount_words_lax, [words(s) for s in word_shapes]),
        ("lowest_set_bit", bitops_pallas.lowest_set_bit_pallas,
         bitops._lowest_set_bit_lax, [words(s) for s in word_shapes]),
        ("pack_bool", bitops_pallas.pack_bool_words_pallas,
         bitops._pack_bool_words_lax,
         [jnp.asarray(rng.rand(*s) < 0.4) for s in PACK_SHAPES]),
    ]
    t0 = time.perf_counter()
    checked = {}
    for name, pallas_fn, lax_fn, inputs in cases:
        for x in inputs:
            got = jax.jit(pallas_fn)(x)
            want = jax.jit(lax_fn)(x)
            assert got.dtype == want.dtype and got.shape == want.shape, (name, x.shape)
            assert np.array_equal(np.asarray(got), np.asarray(want)), (name, x.shape)
        # the engine runs them under vmap over replicas
        xb = jnp.stack([inputs[0], inputs[0][::-1]])
        assert np.array_equal(
            np.asarray(jax.jit(jax.vmap(pallas_fn))(xb)),
            np.asarray(jax.jit(jax.vmap(lax_fn))(xb)),
        ), (name, "vmap")
        checked[name] = len(inputs) + 1
    emit(
        "kernels",
        interpreted=bitops_pallas._interpret(),
        checked=checked,
        exact=True,
        seconds=round(time.perf_counter() - t0, 1),
    )


def _build(node_ct, n_replicas, args):
    from wittgenstein_tpu.engine import replicate_state
    from wittgenstein_tpu.protocols.handel_batched import make_handel
    from wittgenstein_tpu.scenarios.handel_scenarios import flagship_params

    net, state = make_handel(flagship_params(node_ct), fuse_step=True)
    seeds = [args.seed + i for i in range(n_replicas)]
    return net, lambda: replicate_state(state, n_replicas, seeds=seeds)


def _run(net, states, sim_ms=SIM_MS):
    """One call of the flagship entry point, timed around
    block_until_ready, with what it compiled."""
    import jax

    from wittgenstein_tpu.parallel.replica_shard import (
        run_cache_info,
        sharded_run_stats,
    )

    before, events = run_cache_info(), collections.Counter(_CACHE_EVENTS)
    t0 = time.perf_counter()
    out, _stats = sharded_run_stats(net, states, sim_ms)
    jax.block_until_ready(out)
    call_s = time.perf_counter() - t0
    after, events = run_cache_info(), _CACHE_EVENTS - events
    compile_s = after["compile_seconds_total"] - before["compile_seconds_total"]
    return out, {
        "compiles": after["compiles"] - before["compiles"],
        "compile_s": round(compile_s, 2),
        "run_s": round(call_s - compile_s, 3),
        "persistent_cache_hits": events["/jax/compilation_cache/cache_hits"],
        "persistent_cache_misses": events["/jax/compilation_cache/cache_misses"],
    }


def _check_converged(out) -> dict:
    import numpy as np

    done, down = np.asarray(out.done_at), np.asarray(out.down)
    assert (done[~down] > 0).all(), "a live node did not finish"
    assert int(np.asarray(out.dropped).max()) == 0, "message store overflow"
    return {
        "all_live_done": True,
        "dropped_max": 0,
        "done_at_max": int(done.max()),
        "done_at_digest": digest(done),
    }


def phase_flagship(args) -> None:
    from wittgenstein_tpu.ops.bitops import bitops_backend

    n = 64 if args.rehearse else FLAGSHIP_NODES
    r = 2 if args.rehearse else FLAGSHIP_REPLICAS
    with bitops_env("pallas" if args.rehearse else None):
        net, fresh_states = _build(n, r, args)
        backend = bitops_backend()
        assert backend == "pallas", backend
        out, first = _run(net, fresh_states())
        facts = _check_converged(out)
        # the compiled program again, on fresh copies of the same states
        out2, second = _run(net, fresh_states())
    assert second["compiles"] == 0, second
    assert digest(out2.done_at) == facts["done_at_digest"], "second run differs"
    emit(
        "flagship",
        nodes=n,
        replicas=r,
        sim_ms=SIM_MS,
        bitops_backend=backend,
        setup_compile_s=first["compile_s"],
        compile_was=(
            "persistent-cache hit"
            if first["persistent_cache_hits"] and not first["persistent_cache_misses"]
            else "cold"
        ),
        setup_first_run_s=first["run_s"],
        setup_second_run_s=second["run_s"],
        **facts,
        second_run_digest_equal=True,
    )


def phase_twin(args) -> None:
    """Both kernel backends through the same path at N=256: every state
    leaf bit-identical."""
    import jax
    import numpy as np

    from wittgenstein_tpu.ops.bitops import bitops_backend

    n = 32 if args.rehearse else TWIN_NODES
    r = 2 if args.rehearse else TWIN_REPLICAS
    outs = {}
    for env in ("lax", "pallas" if args.rehearse else None):
        with bitops_env(env):
            backend = bitops_backend()
            net, fresh_states = _build(n, r, args)
            out, info = _run(net, fresh_states())
        assert info["compiles"] == 1, info
        _check_converged(out)
        outs[backend] = out
    assert sorted(outs) == ["lax", "pallas"], sorted(outs)
    a, b = (jax.tree_util.tree_leaves(outs[k]) for k in ("lax", "pallas"))
    assert len(a) == len(b)
    assert all(np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(a, b))
    d = digest(outs["pallas"].done_at)
    emit(
        "twin",
        nodes=n,
        replicas=r,
        sim_ms=SIM_MS,
        leaves=len(a),
        lax_pallas_bit_identical=True,
        done_at_digest=d,
        cpu_done_at_digest=TWIN_CPU_DIGEST,
        equals_cpu_digest=d == TWIN_CPU_DIGEST,
    )


def phase_chips4(args) -> None:
    """The replica-sharded path on a four-device mesh against the same
    stacked states on one device: every state leaf bit-identical, and
    the output still sharded R/4 rows per device."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from wittgenstein_tpu.ops.bitops import bitops_backend
    from wittgenstein_tpu.parallel.replica_shard import shard_replicas

    n = 64 if args.rehearse else FLAGSHIP_NODES
    r = 4 if args.rehearse else CHIPS4_REPLICAS
    devs = jax.devices()[:4]
    mesh = Mesh(np.array(devs), ("replicas",))
    with bitops_env("pallas" if args.rehearse else None):
        backend = bitops_backend()
        assert backend == "pallas", backend
        net, fresh_states = _build(n, r, args)
        one, one_info = _run(
            net, jax.device_put(fresh_states(), devs[0]), CHIPS4_SIM_MS
        )
        four, four_info = _run(
            net, shard_replicas(fresh_states(), mesh), CHIPS4_SIM_MS
        )
    a, b = jax.tree_util.tree_leaves(one), jax.tree_util.tree_leaves(four)
    assert len(a) == len(b)
    assert all(np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(a, b))
    assert int(np.asarray(four.dropped).max()) == 0, "message store overflow"
    assert int(np.asarray(four.msg_received).sum()) > 0, "nothing was simulated"
    shards = [
        {"device": s.device.id, "rows": int(s.data.shape[0])}
        for s in four.done_at.addressable_shards
    ]
    assert len({s["device"] for s in shards}) == 4, shards
    assert all(s["rows"] == r // 4 for s in shards), shards
    assert len(one.done_at.sharding.device_set) == 1
    emit(
        "chips4",
        nodes=n,
        replicas=r,
        sim_ms=CHIPS4_SIM_MS,
        bitops_backend=backend,
        mesh={"replicas": 4},
        shards=shards,
        leaves=len(a),
        all_leaves_equal_one_device=True,
        done_at_equals_one_device=True,
        done_at_digest=digest(four.done_at),
        nodes_done=int((np.asarray(four.done_at) > 0).sum()),
        msg_received_total=int(np.asarray(four.msg_received).sum()),
        dropped_max=0,
        setup_one_device=one_info,
        setup_four_devices=four_info,
    )


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    result = {"ok": False, "device": None}
    t0 = time.perf_counter()
    try:
        phase_device(args, result)
        if args.chips == 4:
            phase_chips4(args)
        else:
            phase_kernels(args)
            phase_flagship(args)
            phase_twin(args)
        emit(
            "done",
            seconds=round(time.perf_counter() - t0, 1),
            rehearsal_passed=True if args.rehearse else None,
        )
        result["ok"] = not args.rehearse
    finally:
        # the last line, whatever happened above (a failure's traceback
        # goes to stderr and the exit code is non-zero)
        print(json.dumps(result), flush=True)
    return 0 if result["ok"] else 4


if __name__ == "__main__":
    sys.exit(main())
