"""Selections from a small static axis, computed and not looked up.

On a TPU an index gather out of a 10-wide axis runs at 10 ns an element
(Handel's candidate merge at 4096 nodes: seven `take_along_axis` after an
`argsort`, 2.0 ms a tick each at R=1 and 25-31 ms at R=8, PERF.md section
6, PR 34) and its speed rides on where XLA's memory-space assignment puts
its operand.  Comparison ranks and one-hot masked sums over the same axis
are plain elementwise work: no `sort`, no `gather`, no index array.  Every
size (K, the candidate count, trailing word widths) is read from the
shapes of the inputs, and every function maps over leading axes, so a
`vmap` over replicas is one more of them.

The same holds for a rank among equal keys over a long axis (the message
store's slot inside a wheel row, PR 40): a binary search of sorted keys
among themselves is a loop of whole-array gathers on a TPU (84 of a
235-ms tick of `sanfermin-4096` at R=64, PERF.md section 6), where the
sort that precedes it has already put every run of equal keys side by
side: `run_rank` reads the ranks off with one comparison and one
cumulative max.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax


def one_hot_take(x, sel):
    """`x[..., C]` (or `x[..., C, w]`) read through `sel`, bool
    `[..., k, C]` with one True a row: `[..., k]` (or `[..., k, w]`).
    A row of `sel` without a True reads 0."""
    if x.ndim == sel.ndim:  # trailing words ride along
        x, sel, axis = x[..., None, :, :], sel[..., None], -2
    else:
        x, axis = x[..., None, :], -1
    return jnp.sum(jnp.where(sel, x, 0), axis=axis, dtype=x.dtype)


def take_slot(x, idx):
    """`take_along_axis(x, idx[..., None], axis=-1)[..., 0]` for an index
    in range of a small static last axis, as a one-hot masked sum."""
    sel = idx[..., None] == jnp.arange(x.shape[-1], dtype=idx.dtype)
    return one_hot_take(x, sel[..., None, :])[..., 0]


def descending_positions(key):
    """The place of every entry of the last axis in a stable descending
    sort of it, `argsort(argsort(-key, stable))`, from pairwise
    comparisons: entry j comes after every larger key and after every
    equal key of a lower index."""
    c = key.shape[-1]
    i = jnp.arange(c, dtype=jnp.int32)
    a, b = key[..., :, None], key[..., None, :]  # [..., i, 1], [..., 1, j]
    ahead = jnp.where(i[:, None] < i[None, :], a >= b, a > b)
    return jnp.sum(ahead, axis=-2, dtype=jnp.int32)


def top_k_merge(key, k: int, payloads):
    """The best `k` of the C entries of the last axis by `key`, in
    descending order, ties in index order: exactly what
    `order = argsort(-key, axis=-1)[..., :k]` and `take_along_axis(x,
    order)` give for `key` and for every payload, `[..., C]` or, with
    trailing words, `[..., C, w]`.  Returns `(top_key, [top_payload, ...])`.
    """
    pos = descending_positions(key)
    sel = pos[..., None, :] == jnp.arange(k, dtype=jnp.int32)[:, None]  # [..., k, C]
    return one_hot_take(key, sel), [one_hot_take(x, sel) for x in payloads]


def sort_with_order(key):
    """A stable ascending sort of a 1-D `key` and the permutation that
    made it, `(key[order], order)` for `order = argsort(key)`, as one
    two-operand sort: the sorted keys come out of the sort itself and
    not out of a gather through `order`."""
    iota = jnp.arange(key.shape[0], dtype=jnp.int32)
    return lax.sort((key, iota), num_keys=1, is_stable=True)


def run_rank(keys):
    """For `keys` sorted ascending along the last axis, the place of
    every entry inside its run of equal keys: `iota - first`, where
    `first[i]` is the least j with `keys[j] == keys[i]`, the index a
    left-sided binary search of the keys among themselves returns,
    without the search.  A run starts where a key differs from the one
    before it (position 0 starts one), and the start of the run an entry
    lies in is the running maximum of the run starts up to it: one
    comparison with the shifted keys and one cumulative max, no gather
    and no loop."""
    axis = keys.ndim - 1
    iota = lax.broadcasted_iota(jnp.int32, keys.shape, axis)
    first = jnp.concatenate(
        [jnp.ones_like(keys[..., :1], bool), keys[..., 1:] != keys[..., :-1]], axis
    )
    return iota - lax.cummax(jnp.where(first, iota, 0), axis=axis)


def same_key_rank(key):
    """The place of every entry of a 1-D `key` among the entries of equal
    key, in index order: entry i gets the count of j < i with `key[j] ==
    key[i]`.  One stable sort brings equal keys together in index order,
    `run_rank` numbers each run, and a second sort keyed on the
    permutation (no two keys equal, so it need not be stable) carries
    the numbers back to the entries' own places."""
    skey, order = sort_with_order(key)
    return lax.sort((order, run_rank(skey)), num_keys=1, is_stable=False)[1]
