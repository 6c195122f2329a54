"""Pallas kernels for the packed-bitset hot path (ops.bitops).

`ops.bitops` auto-selects these on a TPU backend (or when forced with
`WITT_BITOPS=pallas`); the lax implementations remain the bit-identity
reference.  Every kernel here must produce bit-identical results to its
lax twin — pinned by tests/test_bitops_pallas.py (interpret mode on CPU
over odd shapes and the all-zero / all-ones edge cases), by
tests/test_tpu_compile.py (Mosaic compiles them for a described v5e) and
by chip_smoke.py (compiled, on the chip, exact equality).

Geometry: callers pass arbitrary leading axes over a packed word axis
(`[..., w]` uint32).  All three kernels are column reductions: the
wrappers flatten to `[M, w]` rows and hand the kernel the TRANSPOSE
`[w, M]`, so the M rows ride the 128-lane axis and the short word axis
(1..128 words at 4096 nodes) rides the sublanes.  The grid tiles the
lane axis only; each block reduces over axis 0 into a lane-dense
`(1, block)` int32 output.  Mosaic refuses the row-major form this
replaced: 1-D `(bm,)` output blocks, 1-D iota, a lane-splitting
in-kernel reshape and unsigned reductions; and a 1..4-word axis padded
to 128 lanes cost 32-128x the operand bytes.

Inside kernels, population counts use the SWAR ladder instead of
`lax.population_count` — Mosaic has no popcount primitive, and the SWAR
form lowers on every backend with identical integer results.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

WORD = 32
LANE = 128  # TPU minor-dim tile
MAX_LANE_BLOCK = 2048
BLOCK_BYTES = 1 << 20  # VMEM budget of one (double-buffered) input block


def _interpret() -> bool:
    """Interpret off-TPU: these kernels only compile under Mosaic."""
    return jax.default_backend() != "tpu"


def _lane_block(k: int, c: int, lane_pad: bool) -> int:
    """Lane-block width for a [k, c] operand: a power-of-two multiple of
    LANE, capped by MAX_LANE_BLOCK and by BLOCK_BYTES of 32-bit sublane
    tiles, and no wider than the (padded) array itself."""
    k8 = -(-k // 8) * 8
    cap = max(LANE, min(MAX_LANE_BLOCK, BLOCK_BYTES // (4 * k8)))
    cap = 1 << (cap.bit_length() - 1)
    return min(cap, -(-c // LANE) * LANE if lane_pad else c)


def _reduce_columns(kernel, cols, lane_pad) -> jnp.ndarray:
    """Run `kernel` over lane blocks of a 32-bit [k, c] operand; each
    block reduces over axis 0.  Returns the [c] int32 column results.
    Columns are zero-padded to a block multiple (sliced off again);
    `lane_pad` also aligns a narrow operand to the 128-lane tile."""
    interpret = _interpret()
    if lane_pad is None:
        lane_pad = not interpret
    k, c = cols.shape
    bl = _lane_block(k, c, lane_pad)
    pad = (-c) % bl
    if pad:
        cols = jnp.pad(cols, ((0, 0), (0, pad)))
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((1, c + pad), jnp.int32),
        in_specs=[pl.BlockSpec((k, bl), lambda i: (0, i))],
        out_specs=pl.BlockSpec((1, bl), lambda i: (0, i)),
        grid=((c + pad) // bl,),
        interpret=interpret,
    )(cols)
    return out[0, :c]


def _swar_popcount(v):
    """Per-word set-bit count of uint32 lanes (SWAR ladder) -> int32."""
    v = v - ((v >> 1) & jnp.uint32(0x55555555))
    v = (v & jnp.uint32(0x33333333)) + ((v >> 2) & jnp.uint32(0x33333333))
    v = (v + (v >> 4)) & jnp.uint32(0x0F0F0F0F)
    return ((v * jnp.uint32(0x01010101)) >> 24).astype(jnp.int32)


def _word_columns(words):
    """[..., w] -> ([w, M] uint32 with the rows on the lane axis, lead)."""
    lead, w = words.shape[:-1], words.shape[-1]
    return words.astype(jnp.uint32).reshape(-1, w).T, lead


def _popcount_kernel(x_ref, o_ref):
    o_ref[...] = jnp.sum(_swar_popcount(x_ref[...]), axis=0, keepdims=True)


def popcount_words_pallas(words, lane_pad=None) -> jnp.ndarray:
    """Pallas twin of bitops.popcount_words: [..., w] uint32 -> [...]
    int32 total set bits.  Zero lane padding is count-neutral."""
    cols, lead = _word_columns(words)
    return _reduce_columns(_popcount_kernel, cols, lane_pad).reshape(lead)


def _pack_kernel(x_ref, o_ref):
    # rows are bit positions 0..31 of one output word (0/1 int32); the
    # shifted terms have disjoint bits, so the wrapping int32 sum is
    # their OR
    b = x_ref[...]
    o_ref[...] = jnp.sum(
        b << lax.broadcasted_iota(jnp.int32, b.shape, 0), axis=0, keepdims=True
    )


def pack_bool_words_pallas(bits, lane_pad=None) -> jnp.ndarray:
    """Pallas twin of bitops.pack_bool_words: [..., W] bool ->
    [..., ceil(W/32)] uint32.  The bit axis is padded to a word multiple
    exactly like the lax path; each output word is one kernel column of
    its 32 bits."""
    bits = jnp.asarray(bits, bool)
    lead, w = bits.shape[:-1], bits.shape[-1]
    nw = (w + WORD - 1) // WORD
    pad = nw * WORD - w
    if pad:
        bits = jnp.concatenate(
            [bits, jnp.zeros(lead + (pad,), bool)], axis=-1
        )
    cols = bits.astype(jnp.int32).reshape(-1, WORD).T  # [32, M * nw]
    out = _reduce_columns(_pack_kernel, cols, lane_pad)
    return lax.bitcast_convert_type(out, jnp.uint32).reshape(lead + (nw,))


def _lowest_kernel(x_ref, o_ref):
    v = x_ref[...]
    w = v.shape[0]
    # per-word lowest-bit index; a zero word yields 32 (popcount of ~0)
    low = v & (~v + jnp.uint32(1))
    lowbit = _swar_popcount(low - jnp.uint32(1))
    idx = lax.broadcasted_iota(jnp.int32, v.shape, 0) * WORD + lowbit
    # zero words can't shadow the first set word: any candidate from a
    # later word j > j0 is >= 32*j > 32*j0 + 31
    empty = jnp.int32(WORD * (w + 1))
    best = jnp.min(
        jnp.where(v != jnp.uint32(0), idx, empty), axis=0, keepdims=True
    )
    # empty vectors: the lax path lands on word 0 -> 0*32 + 32
    o_ref[...] = jnp.where(best == empty, jnp.int32(WORD), best)


def lowest_set_bit_pallas(words, lane_pad=None) -> jnp.ndarray:
    """Pallas twin of bitops.lowest_set_bit: [..., w] uint32 -> [...]
    int32 index of the lowest set bit (32 for the all-zero vector,
    matching the lax path's argmax-of-nothing behavior).  Zero lane
    padding only adds all-zero vectors, which are sliced off."""
    cols, lead = _word_columns(words)
    return _reduce_columns(_lowest_kernel, cols, lane_pad).reshape(lead)
