"""Property tests for ops.radix_sort.radix_argsort (the optimized
Process-stage sort attempt)."""

import numpy as np
import pytest

import jax.numpy as jnp

from locust_tpu.ops.radix_sort import radix_argsort


def _check(keys: np.ndarray, **kw):
    sidx = np.asarray(radix_argsort(jnp.asarray(keys), **kw))
    assert sorted(sidx.tolist()) == list(range(len(keys)))  # a permutation
    s = keys[sidx]
    assert np.all(s[:-1] <= s[1:])  # ascending
    # Stability: equal keys keep their original relative order.
    for v in np.unique(keys[:64]):
        pos = sidx[s == v]
        assert np.all(np.diff(pos) > 0), f"unstable at key {v:#x}"
    return sidx


@pytest.mark.parametrize("n", [1, 2, 7, 8192, 100_000])
def test_random_with_duplicates(n):
    rng = np.random.default_rng(n)
    k = rng.integers(0, 2**32, size=n, dtype=np.uint64).astype(np.uint32)
    k[::3] = k[0]  # plant heavy duplicates
    _check(k)


@pytest.mark.parametrize("bits,chunk", [(6, 1024), (8, 8192), (11, 4096)])
def test_digit_width_variants(bits, chunk):
    rng = np.random.default_rng(0)
    k = rng.integers(0, 2**32, size=20_000, dtype=np.uint64).astype(np.uint32)
    _check(k, bits=bits, chunk=chunk)


def test_extremes_and_sentinels():
    # The engine folds validity into 0xFFFFFFFF sentinels; they must sort
    # last and stay stable among themselves.
    k = np.array(
        [0xFFFFFFFF, 0, 0xFFFFFFFF, 1, 0x7FFFFFFF, 0xFFFFFFFF, 0x80000000],
        np.uint32,
    )
    sidx = _check(k)
    assert list(k[sidx][-3:]) == [0xFFFFFFFF] * 3
    assert list(sidx[-3:]) == [0, 2, 5]  # original order among sentinels


def test_already_sorted_and_reversed():
    k = np.arange(10_000, dtype=np.uint32)
    assert np.array_equal(np.asarray(radix_argsort(jnp.asarray(k))), k)
    _check(k[::-1].copy())


def test_narrow_key_bits_fewer_passes():
    # key_bits=16 sorts correctly when keys genuinely fit 16 bits.
    rng = np.random.default_rng(1)
    k = rng.integers(0, 2**16, size=10_000, dtype=np.uint64).astype(np.uint32)
    _check(k, key_bits=16)


def test_rejects_wrong_dtype_and_overflowing_config():
    with pytest.raises(TypeError):
        radix_argsort(jnp.zeros(4, jnp.int32))
    with pytest.raises(ValueError):
        radix_argsort(jnp.zeros(4, jnp.uint32), chunk=65536)
