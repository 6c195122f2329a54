"""Pallas bitset kernels vs the lax reference (PR-8 lever 3).

The kernels run in interpret mode here (CPU backend), which executes the
same grid/block program Mosaic would compile on a TPU — equivalence under
interpret is the strongest off-device evidence available.  The sweep
covers odd row counts and word widths (both below and straddling the
8-row / 128-lane tile minimums), degenerate all-zero / all-ones inputs,
and both lane_pad settings; plus the backend-selection contract
(auto-lax off-TPU, WITT_BITOPS override).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from wittgenstein_tpu.ops.bitops import (
    BITOPS_ENV,
    _lowest_set_bit_lax,
    _pack_bool_words_lax,
    _popcount_words_lax,
    bitops_backend,
)
from wittgenstein_tpu.ops.bitops_pallas import (
    lowest_set_bit_pallas,
    pack_bool_words_pallas,
    popcount_words_pallas,
)

# odd shapes on purpose: single row/word, sub-tile, straddling the
# 8-row block and 128-lane minimums, and one 3-D batch
WORD_SHAPES = [
    (1, 1),
    (3, 2),
    (5, 4),
    (7, 3),
    (2, 7),
    (4, 64),
    (129, 5),
    (3, 2, 9),
]


def _rng_words(shape, seed):
    rng = np.random.RandomState(seed)
    return jnp.asarray(
        rng.randint(0, 1 << 32, size=shape, dtype=np.uint32)
    )


@pytest.mark.parametrize("shape", WORD_SHAPES, ids=str)
@pytest.mark.parametrize("lane_pad", [False, True], ids=["nopad", "lanepad"])
def test_popcount_matches_lax(shape, lane_pad):
    w = _rng_words(shape, seed=sum(shape))
    got = popcount_words_pallas(w, lane_pad=lane_pad)
    want = _popcount_words_lax(w)
    assert got.dtype == want.dtype
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("shape", WORD_SHAPES, ids=str)
@pytest.mark.parametrize("lane_pad", [False, True], ids=["nopad", "lanepad"])
def test_lowest_set_bit_matches_lax(shape, lane_pad):
    w = _rng_words(shape, seed=100 + sum(shape))
    # force a sprinkling of all-zero vectors into the sweep: both
    # implementations must agree on the sentinel too
    w = w.at[..., :].multiply(
        (_rng_words(shape[:-1], seed=7)[..., None] & 3 != 0).astype(jnp.uint32)
    )
    got = lowest_set_bit_pallas(w, lane_pad=lane_pad)
    want = _lowest_set_bit_lax(w)
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize(
    "shape",
    [(1, 1), (3, 31), (5, 32), (2, 33), (7, 65), (4, 200), (3, 2, 40)],
    ids=str,
)
@pytest.mark.parametrize("lane_pad", [False, True], ids=["nopad", "lanepad"])
def test_pack_bool_matches_lax(shape, lane_pad):
    rng = np.random.RandomState(sum(shape))
    bits = jnp.asarray(rng.rand(*shape) < 0.4)
    got = pack_bool_words_pallas(bits, lane_pad=lane_pad)
    want = _pack_bool_words_lax(bits)
    assert got.shape == want.shape
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("fill", [0, 0xFFFFFFFF], ids=["zeros", "ones"])
def test_degenerate_fills(fill):
    w = jnp.full((6, 9), fill, dtype=jnp.uint32)
    assert np.array_equal(
        np.asarray(popcount_words_pallas(w)),
        np.asarray(_popcount_words_lax(w)),
    )
    assert np.array_equal(
        np.asarray(lowest_set_bit_pallas(w)),
        np.asarray(_lowest_set_bit_lax(w)),
    )
    bits = jnp.full((6, 70), bool(fill))
    assert np.array_equal(
        np.asarray(pack_bool_words_pallas(bits)),
        np.asarray(_pack_bool_words_lax(bits)),
    )


def test_kernels_work_under_vmap_and_jit():
    w = _rng_words((4, 5, 6), seed=11)

    @jax.jit
    def f(x):
        return jax.vmap(popcount_words_pallas)(x)

    assert np.array_equal(
        np.asarray(f(w)), np.asarray(_popcount_words_lax(w))
    )


class _EnvGuard:
    def __init__(self, value):
        self.value = value

    def __enter__(self):
        self.saved = os.environ.get(BITOPS_ENV)
        if self.value is None:
            os.environ.pop(BITOPS_ENV, None)
        else:
            os.environ[BITOPS_ENV] = self.value

    def __exit__(self, *exc):
        if self.saved is None:
            os.environ.pop(BITOPS_ENV, None)
        else:
            os.environ[BITOPS_ENV] = self.saved


def test_backend_auto_disabled_off_tpu():
    """Without an override, the pallas path is auto-selected ONLY on a
    TPU backend — this suite runs on CPU, so auto must say lax."""
    with _EnvGuard(None):
        expected = "pallas" if jax.default_backend() == "tpu" else "lax"
        assert bitops_backend() == expected


def test_backend_env_override():
    with _EnvGuard("pallas"):
        assert bitops_backend() == "pallas"
    with _EnvGuard("lax"):
        assert bitops_backend() == "lax"
    with _EnvGuard("nonsense"):
        # unknown values fall back to auto-selection, never crash
        assert bitops_backend() in ("lax", "pallas")


def test_dispatch_follows_env():
    """The public bitops functions dispatch per-call on bitops_backend();
    forcing pallas on CPU must still give lax-identical results."""
    from wittgenstein_tpu.ops.bitops import lowest_set_bit, popcount_words

    w = _rng_words((5, 7), seed=3)
    want = np.asarray(_popcount_words_lax(w))
    low = np.asarray(_lowest_set_bit_lax(w))
    for backend, calls in (("pallas", 1), ("lax", 0)):
        with _EnvGuard(backend):
            assert np.array_equal(np.asarray(popcount_words(w)), want)
            assert np.array_equal(np.asarray(lowest_set_bit(w)), low)
            # a fresh function each time: make_jaxpr caches a trace
            for f in (popcount_words, lowest_set_bit):
                traced = str(jax.make_jaxpr(lambda x: f(x))(w))
                assert traced.count("pallas_call") == calls
