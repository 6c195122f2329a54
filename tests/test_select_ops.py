"""ops/select.py against the spelling it replaced (PR 34): a stable
`argsort(-key)[..., :K]` with `take_along_axis` on every payload, and the
1-of-K `take_along_axis` picks of Handel's `_select`.  Exact equality:
the payload of a slot the caller masks is still written to the state, so
the order among tied keys (the many -1 among them) is part of the result.

And the message store's same-row rank (PR 40) against the search it
replaced: `run_rank` against `iota - searchsorted(keys, keys, "left")`,
`same_key_rank` against `_insert_rows`' lines as they stood, kept here as
the plain reference.
"""

import re
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from wittgenstein_tpu.ops.select import (
    descending_positions,
    run_rank,
    same_key_rank,
    sort_with_order,
    take_slot,
    top_k_merge,
)

N, K, NEW = 37, 8, 2
C = K + NEW


def reference(key, k, payloads):
    order = jnp.argsort(-key, axis=-1)[..., :k]
    take = lambda x: jnp.take_along_axis(  # noqa: E731
        x, order if x.ndim == key.ndim else order[..., None], axis=key.ndim - 1
    )
    return take(key), [take(x) for x in payloads]


def keys(kind: str, rng, shape):
    """Sort keys as `_channel_deliver` makes them: -1 where a candidate is
    not kept, else s * 4N + rank part, bounded by int32."""
    if kind == "all_minus_one":
        return np.full(shape, -1, np.int32)
    if kind == "all_equal":
        return np.full(shape, 12345, np.int32)
    if kind == "boundary_duplicates":
        # the K resident slots hold 5, 4, 3, ..: the two new ones repeat
        # residents, so a tie straddles the resident/new boundary and, in
        # half of the rows, the cut between kept and dropped
        k = np.broadcast_to(np.arange(5, 5 - C, -1, dtype=np.int32), shape).copy()
        k[..., K] = k[..., K - 1]
        k[..., K + 1] = np.where(rng.random(shape[:-1]) < 0.5, k[..., 0], k[..., K - 1])
        return k
    if kind == "sparse":  # mostly -1, a few kept
        k = rng.integers(0, 2**31 - 1, shape, dtype=np.int64).astype(np.int32)
        return np.where(rng.random(shape) < 0.7, -1, k)
    assert kind == "random_with_repeats"
    return rng.integers(-1, 4, shape).astype(np.int32)


KINDS = ["all_minus_one", "all_equal", "boundary_duplicates", "sparse", "random_with_repeats"]


def merge_inputs(kind, nl, w_pad, lead=()):
    rng = np.random.default_rng(zlib.crc32(f"{kind}-{nl}-{w_pad}".encode()))
    shape = lead + (N, nl, C)
    key = keys(kind, rng, shape)
    scalars = [rng.integers(-(2**31), 2**31 - 1, shape, dtype=np.int64).astype(np.int32) for _ in range(2)]
    words = rng.integers(0, 2**32, shape + (w_pad,), dtype=np.uint64).astype(np.uint32)
    return jnp.asarray(key), [jnp.asarray(x) for x in scalars] + [jnp.asarray(words)]


def assert_same(got, want):
    (gk, gp), (wk, wp) = got, want
    for g, w in zip([gk, *gp], [wk, *wp]):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("w_pad", [1, 2, 64])
@pytest.mark.parametrize("nl", [1, 6])
@pytest.mark.parametrize("kind", KINDS)
def test_top_k_merge_is_the_stable_argsort(kind, nl, w_pad):
    key, payloads = merge_inputs(kind, nl, w_pad)
    assert_same(jax.jit(top_k_merge, static_argnums=1)(key, K, payloads), reference(key, K, payloads))


@pytest.mark.parametrize("w_pad", [1, 64])
@pytest.mark.parametrize("nl", [1, 6])
@pytest.mark.parametrize("kind", KINDS)
def test_top_k_merge_under_vmap_over_replicas(kind, nl, w_pad):
    key, payloads = merge_inputs(kind, nl, w_pad, lead=(2,))
    got = jax.vmap(lambda k, p: top_k_merge(k, K, p))(key, payloads)
    want = jax.vmap(lambda k, p: reference(k, K, p))(key, payloads)
    assert_same(got, want)


@pytest.mark.parametrize("kind", KINDS)
def test_descending_positions_is_the_inverse_of_the_stable_argsort(kind):
    key = jnp.asarray(keys(kind, np.random.default_rng(3), (N, 6, C)))
    order = jnp.argsort(-key, axis=-1)  # stable
    want = jnp.argsort(order, axis=-1)
    np.testing.assert_array_equal(np.asarray(descending_positions(key)), np.asarray(want))


@pytest.mark.parametrize("dtype", [np.int32, np.uint32])
@pytest.mark.parametrize("nl", [1, 6])
def test_take_slot_is_take_along_axis(nl, dtype):
    """`_select`'s picks: c_rank[k_in], c_rel[kidx], over K slots."""
    rng = np.random.default_rng(nl)
    info = np.iinfo(dtype)
    x = jnp.asarray(rng.integers(info.min, info.max, (N, nl, K), dtype=np.int64).astype(dtype))
    idx = jnp.asarray(rng.integers(0, K, (N, nl)).astype(np.int32))
    want = jnp.take_along_axis(x, idx[..., None], axis=2)[..., 0]
    got = jax.jit(take_slot)(x, idx)
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_select_picks_equal_their_take_along_axis_spelling():
    """max / min for what argmax / argmin already scanned, as `_select`
    spells `sc_in` and `rk_out` since PR 34."""
    rng = np.random.default_rng(7)
    score = jnp.asarray(rng.integers(-1, 5, (N, 6, K)).astype(np.int32))
    k_in = jnp.argmax(score, axis=2)
    np.testing.assert_array_equal(
        np.asarray(jnp.max(score, axis=2)),
        np.asarray(jnp.take_along_axis(score, k_in[..., None], axis=2)[..., 0]),
    )
    k_out = jnp.argmin(score, axis=2)
    np.testing.assert_array_equal(
        np.asarray(jnp.min(score, axis=2)),
        np.asarray(jnp.take_along_axis(score, k_out[..., None], axis=2)[..., 0]),
    )


def test_the_helper_lowers_to_no_sort_and_no_gather():
    key, payloads = merge_inputs("sparse", 6, 64)
    text = jax.jit(top_k_merge, static_argnums=1).lower(key, K, payloads).as_text()
    assert not re.findall(r"stablehlo\.(?:sort|(?:dynamic_)?gather)", text)
    idx = jnp.zeros((N, 6), jnp.int32)
    text = jax.jit(take_slot).lower(payloads[0], idx).as_text()
    assert not re.findall(r"stablehlo\.(?:sort|(?:dynamic_)?gather)", text)


ROWS = 4  # the replica axis the store's insert runs under


def rank_keys(kind: str, k: int, buckets: int):
    """`[ROWS, k]` keys as `_insert_rows` makes them: a wheel row in
    `[0, buckets)` for a candidate, `buckets` for the rest."""
    rng = np.random.default_rng(zlib.crc32(f"{kind}-{k}-{buckets}".encode()))
    if kind == "all_equal":
        return np.full((ROWS, k), buckets, np.int32)
    if kind == "all_distinct":
        return np.stack([rng.permutation(k) for _ in range(ROWS)]).astype(np.int32)
    assert kind == "ties"
    return rng.integers(0, buckets + 1, (ROWS, k)).astype(np.int32)


def rank_by_search(rkey):
    """`engine/core.py` `_insert_rows`, the same-row rank as it stood
    before PR 40: the plain reference."""
    k = rkey.shape[0]
    order = jnp.argsort(rkey)
    rsort = rkey[order]
    pos_sorted = jnp.arange(k, dtype=jnp.int32) - jnp.searchsorted(
        rsort, rsort, side="left"
    ).astype(jnp.int32)
    return jnp.zeros(k, jnp.int32).at[order].set(pos_sorted)


RANK_CASES = [
    (kind, k, buckets)
    for kind in ("ties", "all_equal", "all_distinct")
    for k, buckets in ((1, 5), (7, 3), (1280, 512), (8192, 512))
]


@pytest.mark.parametrize("whole", [False, True], ids=["run_rank", "same_key_rank"])
@pytest.mark.parametrize("kind,k,buckets", RANK_CASES)
def test_rank_among_equal_keys_is_the_search_it_replaced(kind, k, buckets, whole):
    key = jnp.asarray(rank_keys(kind, k, buckets))
    if whole:  # sort, scan and write-back, in row order
        got = jax.jit(jax.vmap(same_key_rank))(key)
        want = jax.vmap(rank_by_search)(key)
    else:  # the scan alone, over sorted keys
        key = jnp.sort(key, axis=-1)
        got = jax.jit(jax.vmap(run_rank))(key)
        want = jnp.arange(k, dtype=jnp.int32) - jax.vmap(
            lambda s: jnp.searchsorted(s, s, side="left")
        )(key).astype(jnp.int32)
        # a leading axis is one more: the same without the vmap
        np.testing.assert_array_equal(np.asarray(run_rank(key)), np.asarray(want))
    assert got.dtype == want.dtype == jnp.int32 and got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_sort_with_order_is_the_stable_argsort():
    key = jnp.asarray(rank_keys("ties", 1280, 512)[0])
    skey, order = jax.jit(sort_with_order)(key)
    want = jnp.argsort(key)  # stable
    np.testing.assert_array_equal(np.asarray(order), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(skey), np.asarray(key[want]))


def test_the_rank_lowers_to_no_search():
    """No loop, no gather and no scatter: two sorts, a comparison with the
    shifted keys and a cumulative max."""
    key = jnp.asarray(rank_keys("ties", 8192, 512))
    text = jax.jit(jax.vmap(same_key_rank)).lower(key).as_text()
    assert "stablehlo.sort" in text
    assert not re.findall(r"stablehlo\.(?:while|(?:dynamic_)?gather|scatter)|searchsorted", text)
