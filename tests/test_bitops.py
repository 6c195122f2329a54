"""Packed-bitset kernels vs plain python-int bitsets."""

import jax
import numpy as np
import pytest

from wittgenstein_tpu.ops.bitops import (
    level_block_mask,
    popcount_words,
    xor_shuffle,
)
from wittgenstein_tpu.utils.bitset import int_to_packed, packed_to_int


def ref_xor_shuffle(bits: int, v: int, n: int) -> int:
    out = 0
    for j in range(n):
        if (bits >> j) & 1:
            out |= 1 << (j ^ v)
    return out


class TestXorShuffle:
    @pytest.mark.parametrize("v", [0, 1, 5, 31, 32, 37, 63, 100, 255])
    def test_matches_reference(self, v):
        rng = np.random.default_rng(42)
        n = 256
        bits = int.from_bytes(rng.bytes(n // 8), "little")
        packed = int_to_packed(bits, n // 32)
        out = np.asarray(xor_shuffle(packed, v))
        assert packed_to_int(out) == ref_xor_shuffle(bits, v, n)

    def test_involution(self):
        rng = np.random.default_rng(0)
        packed = rng.integers(0, 2**32, size=8, dtype=np.uint32)
        out = np.asarray(xor_shuffle(xor_shuffle(packed, 77), 77))
        assert (out == packed).all()

    def test_batched_v(self):
        import jax.numpy as jnp

        rng = np.random.default_rng(1)
        words = rng.integers(0, 2**32, size=(4, 8), dtype=np.uint32)
        vs = np.array([0, 3, 64, 99], dtype=np.int32)
        out = np.asarray(xor_shuffle(jnp.asarray(words), jnp.asarray(vs)))
        for i in range(4):
            expect = ref_xor_shuffle(packed_to_int(words[i]), int(vs[i]), 256)
            assert packed_to_int(out[i]) == expect


def old_xor_shuffle(words: np.ndarray, v: np.ndarray) -> np.ndarray:
    """The formula `xor_shuffle` had before PR 28, kept here as the
    oracle: a word gather on index ^ (v >> 5), then the five bit-level
    butterfly stages for v & 31.  words [..., w] uint32, v [...] int."""
    w = words.shape[-1]
    idx = np.arange(w) ^ (v[..., None] >> 5)
    x = np.take_along_axis(words, idx, axis=-1)
    masks = [0x55555555, 0x33333333, 0x0F0F0F0F, 0x00FF00FF, 0x0000FFFF]
    for b, m in enumerate(masks):
        m = np.uint32(m)
        sh = np.uint32(1 << b)
        swapped = ((x & m) << sh) | ((x >> sh) & m)
        x = np.where(((v >> b) & 1 == 1)[..., None], swapped, x)
    return x


WIDTHS = [1, 2, 4, 8, 16, 32, 64, 128]


class TestXorShuffleStages:
    """The word permutation as log2(w) conditional swap stages (PR 28):
    bit-identical to the gather it replaced, at every bucket width the
    channel send path has, under vmap over a replica axis with per-row v."""

    R, M = 3, 40

    def _plane(self, w, seed=7):
        rng = np.random.default_rng(seed + w)
        words = rng.integers(0, 2**32, size=(self.R, self.M, w), dtype=np.uint32)
        v = rng.integers(0, 32 * w, size=(self.R, self.M), dtype=np.int32)
        return words, v

    @pytest.mark.parametrize("w", WIDTHS)
    def test_vmapped_rows_match_the_reference_and_the_old_gather(self, w):
        words, v = self._plane(w)
        out = np.asarray(jax.jit(jax.vmap(xor_shuffle))(words, v))
        assert out.dtype == np.uint32 and out.shape == words.shape
        assert (out == old_xor_shuffle(words, v)).all()
        for r in range(self.R):
            for i in range(0, self.M, 13):
                expect = ref_xor_shuffle(packed_to_int(words[r, i]), int(v[r, i]), 32 * w)
                assert packed_to_int(out[r, i]) == expect

    @pytest.mark.parametrize("w", [1, 4])
    def test_junk_high_bits_of_v_stay_in_range(self, w):
        """Bits of v at or above log2(32 w) are ignored: a masked row's
        junk cannot reach outside its vector (the caller still zeroes
        such rows, as it had to for the gather)."""
        words, v = self._plane(w)
        junk = v | np.int32(32 * w) | np.int32(1 << 20) | np.int32(1 << 30)
        out = np.asarray(jax.jit(jax.vmap(xor_shuffle))(words, junk))
        assert (out == old_xor_shuffle(words, v)).all()
        scalar = np.asarray(xor_shuffle(words[0, 0], int(junk[0, 0])))
        assert (scalar == out[0, 0]).all()

    @pytest.mark.parametrize("w", [3, 6, 96])
    def test_a_width_that_is_no_power_of_two_raises(self, w):
        with pytest.raises(ValueError, match="power-of-two"):
            xor_shuffle(np.zeros((2, w), np.uint32), np.zeros(2, np.int32))

    @pytest.mark.parametrize("w", WIDTHS)
    def test_compiled_text_holds_no_gather(self, w):
        """The structural pin: the gather cannot come back unnoticed
        (PERF.md section 5: it was 26-44% of a tick on the chip)."""
        words, v = self._plane(w)
        text = jax.jit(jax.vmap(xor_shuffle)).lower(words, v).compile().as_text()
        assert "gather(" not in text


class TestMasksAndCounts:
    def test_popcount(self):
        words = np.array([[0xFFFFFFFF, 0x1], [0x0, 0x80000000]], dtype=np.uint32)
        assert list(np.asarray(popcount_words(words))) == [33, 1]

    def test_level_block_mask(self):
        n_words = 4  # 128 bits
        assert packed_to_int(level_block_mask(0, n_words)) == 0b1
        assert packed_to_int(level_block_mask(1, n_words)) == 0b10
        assert packed_to_int(level_block_mask(2, n_words)) == 0b1100
        m3 = packed_to_int(level_block_mask(3, n_words))
        assert m3 == ((1 << 8) - 1) ^ ((1 << 4) - 1)
        # level 7: bits [64, 128) spans words 2-3
        m7 = packed_to_int(level_block_mask(7, n_words))
        assert m7 == ((1 << 128) - 1) ^ ((1 << 64) - 1)
