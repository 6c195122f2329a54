"""Packed-bitset kernels for aggregation protocols.

The batched Handel/GSF state keeps per-node contribution bitsets in an
XOR-relative layout: bit j of node i's vector refers to node (i ^ j).
Under that layout the binary-split level structure (Handel.allSigsAtLevel,
Handel.java:634-647) becomes uniform across nodes — level l occupies bit
block [2^(l-1), 2^l) for every node — and re-addressing a contribution
from sender s's space into receiver r's space is the bit permutation
j -> j ^ (r ^ s), implemented below as one butterfly over both levels:
log2(w) conditional swaps of word blocks (high bits) and 5 conditional
swaps of bit blocks inside each word (low bits), every stage a select —
no gather.  All ops are jnp-traceable and vmap over leading axes.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

WORD = 32
_BUTTERFLY_MASKS = np.array(
    [0x55555555, 0x33333333, 0x0F0F0F0F, 0x00FF00FF, 0x0000FFFF], dtype=np.uint32
)

BITOPS_ENV = "WITT_BITOPS"  # "lax" | "pallas" (anything else = auto)


def bitops_backend() -> str:
    """The bitset-kernel backend for the NEXT trace: "lax" or "pallas".

    `WITT_BITOPS=lax|pallas` overrides; otherwise pallas is auto-selected
    on a TPU backend only (the kernels interpret rather than compile
    anywhere else — correct but slow, so CPU/GPU default to lax).  Read
    at trace time, so it is a static program property; the engine folds
    it into `cache_key()` so a flipped env var cannot hit a stale jit
    cache.

    One caller does not follow it: `protocols/gsf_batched.py` takes
    `_popcount_words_lax` under both backends, because on the chip that is
    the only popcount its 2048-node program is right with, as the kernel
    is the only one Handel's 4096-node program is right with (PERF.md
    section 6, PR 32; cause not found: ROADMAP B0)."""
    env = os.environ.get(BITOPS_ENV, "").strip().lower()
    if env in ("lax", "pallas"):
        return env
    return "pallas" if jax.default_backend() == "tpu" else "lax"


def _popcount_words_lax(words) -> jnp.ndarray:
    return jnp.sum(
        lax.population_count(words.astype(jnp.uint32)).astype(jnp.int32), axis=-1
    )


def popcount_words(words) -> jnp.ndarray:
    """Total set bits over the last axis of packed uint32 words."""
    if bitops_backend() == "pallas":
        from .bitops_pallas import popcount_words_pallas

        return popcount_words_pallas(words)
    return _popcount_words_lax(words)


def _pack_bool_words_lax(bits) -> jnp.ndarray:
    bits = jnp.asarray(bits, bool)
    w = bits.shape[-1]
    pad = (-w) % WORD
    if pad:
        bits = jnp.concatenate(
            [bits, jnp.zeros(bits.shape[:-1] + (pad,), bool)], axis=-1
        )
    grouped = bits.reshape(bits.shape[:-1] + ((w + pad) // WORD, WORD))
    weights = (jnp.uint32(1) << jnp.arange(WORD, dtype=jnp.uint32))
    return jnp.sum(grouped.astype(jnp.uint32) * weights, axis=-1).astype(
        jnp.uint32
    )


def pack_bool_words(bits) -> jnp.ndarray:
    """Pack a bool vector into uint32 words over the last axis:
    [..., W] bool -> [..., ceil(W/32)] uint32, bit j of word k = element
    32k + j.  (The engine's wheel-occupancy summary; pairs with
    popcount_words / lowest_set_bit.)"""
    if bitops_backend() == "pallas":
        from .bitops_pallas import pack_bool_words_pallas

        return pack_bool_words_pallas(bits)
    return _pack_bool_words_lax(bits)


def _lowest_set_bit_lax(words) -> jnp.ndarray:
    words = words.astype(jnp.uint32)
    word_nz = words != 0
    widx = jnp.argmax(word_nz, axis=-1).astype(jnp.int32)
    wval = jnp.take_along_axis(words, widx[..., None], axis=-1)[..., 0]
    lowbit = _popcount_words_lax(
        ((wval & (-wval).astype(jnp.uint32)) - 1)[..., None]
    )
    return widx * WORD + lowbit


def lowest_set_bit(words) -> jnp.ndarray:
    """Index of the lowest set bit over the last axis of packed [..., w]
    uint32 vectors (undefined when empty — gate on popcount > 0)."""
    if bitops_backend() == "pallas":
        from .bitops_pallas import lowest_set_bit_pallas

        return lowest_set_bit_pallas(words)
    return _lowest_set_bit_lax(words)


def xor_shuffle(words, v):
    """Permute bit positions j -> j ^ v of packed vectors.

    words: [..., W] uint32, W a power of two (static; ValueError
    otherwise: j ^ v leaves the vector for any other width); v: int32
    scalar or [...] batch of xor values (dynamic).  A butterfly of
    conditional swaps: stage b of the word level exchanges adjacent
    blocks of 2^b words where bit b of v >> 5 is set (log2(W) stages,
    none at W == 1), then 5 bit-level stages do the same inside every
    word for v & 31.  Bits of v at or above log2(32 W) are ignored, so no
    row can reach outside its vector whatever junk a masked row carries.
    """
    words = words.astype(jnp.uint32)
    w = words.shape[-1]
    if w & (w - 1):
        raise ValueError(f"xor_shuffle needs a power-of-two word count, got {w}")
    v = jnp.asarray(v, jnp.int32)

    def where_bit(b, swapped, x):
        cond = (lax.shift_right_logical(v, b) & 1) == 1
        return jnp.where(cond[..., None] if v.ndim else cond, swapped, x)

    def shifted(x, s):
        # x[..., k - s], zero where that leaves the vector: one pad with
        # a negative edge, which XLA:TPU fuses into the stage's select (a
        # roll's two slices it writes out, each a full lane tile a row)
        edges = [(0, 0, 0)] * (x.ndim - 1) + [(s, -s, 0)]
        return lax.pad(x, jnp.uint32(0), edges)

    x = words
    k = np.arange(w)
    for b in range(w.bit_length() - 1):
        s = 1 << b
        # x[..., k ^ s]: the upper block of each pair comes from s below
        # it, the lower from s above; the zero-filled lanes of either
        # shift are never the ones selected
        swapped = jnp.where((k & s) != 0, shifted(x, s), shifted(x, -s))
        x = where_bit(5 + b, swapped, x)
    for b in range(5):
        m = jnp.uint32(_BUTTERFLY_MASKS[b])
        sh = jnp.uint32(1 << b)
        swapped = ((x & m) << sh) | (lax.shift_right_logical(x, sh) & m)
        x = where_bit(b, swapped, x)
    return x


def block_mask(start: int, end: int, n_words: int) -> np.ndarray:
    """Static mask with bits [start, end) set, as packed uint32 words."""
    bits = ((1 << end) - 1) ^ ((1 << start) - 1)
    out = np.zeros(n_words, dtype=np.uint32)
    for w in range(n_words):
        out[w] = (bits >> (32 * w)) & 0xFFFFFFFF
    return out


def level_block_mask(level: int, n_words: int) -> np.ndarray:
    """Mask of level `level`'s block in the XOR layout: bit 0 for level 0,
    bits [2^(l-1), 2^l) for level l >= 1."""
    if level == 0:
        return block_mask(0, 1, n_words)
    return block_mask(1 << (level - 1), 1 << level, n_words)
