"""``sort_and_compact`` on edge inputs, for the modes that group by a hash.

"hashp2" is what every WordCount cell of BENCHMARK.json runs and "hashp1"
what the hasht family's split stages are served; before PR 44 both were
covered at engine level only.  The inputs are the ones the removed modes'
unit files held (tiny n, heavy duplicates, all keys equal, sorted,
reversed, the extremes of the key and of the folded hash, invalid rows).

The contract, with ``sort_and_compact(batch, "lex")`` as the oracle: valid
rows form a prefix, no row is lost or invented, and the segment-reduced,
finalised table is the oracle's — which is also the table numpy counts
from the input rows.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from locust_tpu.core.kv import KVBatch
from locust_tpu.engine import finalize_host_pairs
from locust_tpu.ops.process_stage import _folded_key, sort_and_compact
from locust_tpu.ops.reduce_stage import segment_reduce

KEY_LANES = 8  # key_width 32, the published width
MODES = ["hash", "hashp2", "hashp1"]

_M32 = 0xFFFFFFFF
_C1, _C2 = 0x85EBCA6B, 0xC2B2AE35


def _fmix32(h: int) -> int:
    h ^= h >> 16
    h = (h * _C1) & _M32
    h ^= h >> 13
    h = (h * _C2) & _M32
    return h ^ (h >> 16)


def _fmix32_inv(h: int) -> int:
    h ^= h >> 16
    h = (h * pow(_C2, -1, 1 << 32)) & _M32
    h ^= (h >> 13) ^ (h >> 26)
    h = (h * pow(_C1, -1, 1 << 32)) & _M32
    return h ^ (h >> 16)


def _key_with_h1(h1: int, head: int) -> list[int]:
    """Key lanes whose primary hash (``packing.hash_pair``'s h1 =
    fmix32(sum_i fmix32(lane_i ^ (i+1)*0x9E3779B9))) is exactly ``h1``:
    seven lanes chosen freely (from ``head`` on, until no byte of the key
    is a NUL: the host decode reads a key as a C string), the last solved
    for."""
    salts = [((i + 1) * 0x9E3779B9) & _M32 for i in range(KEY_LANES)]
    while True:
        lanes = [(head + 0x01010101 * (i + 1)) & _M32 for i in range(KEY_LANES - 1)]
        rest = sum(_fmix32(ln ^ s) for ln, s in zip(lanes, salts)) & _M32
        lanes.append(_fmix32_inv((_fmix32_inv(h1) - rest) & _M32) ^ salts[-1])
        if all(b for ln in lanes for b in ln.to_bytes(4, "big")):
            return lanes
        head += 1


def _batch(lanes, values=None, valid=None) -> KVBatch:
    lanes = np.asarray(lanes, np.uint32).reshape(-1, KEY_LANES)
    n = lanes.shape[0]
    rng = np.random.default_rng(n)
    return KVBatch(
        key_lanes=jnp.asarray(lanes),
        values=jnp.asarray(
            rng.integers(1, 6, n, dtype=np.int32) if values is None else values
        ),
        valid=jnp.asarray(np.ones(n, bool) if valid is None else valid),
    )


def _random_with_duplicates(n: int) -> KVBatch:
    """``n`` rows drawn from about n/3 distinct NUL-free keys, every third
    row the same key (the removed radix file's planted duplicates)."""
    rng = np.random.default_rng(n)
    pool = rng.integers(1, 256, (n // 3 + 1, KEY_LANES * 4), dtype=np.uint8)
    pool = pool.view(">u4").astype(np.uint32)
    pick = rng.integers(0, pool.shape[0], n)
    pick[::3] = pick[0]
    return _batch(pool[pick])


def _in_lex_order() -> KVBatch:
    b = _random_with_duplicates(3000)
    valid = np.ones(3000, bool)
    valid[2500:] = False
    return sort_and_compact(
        KVBatch(b.key_lanes, b.values, jnp.asarray(valid)), "lex"
    )


def _reversed() -> KVBatch:
    b = _in_lex_order()  # invalid rows first, then descending keys
    return KVBatch(b.key_lanes[::-1], b.values[::-1], b.valid[::-1])


def _extremes() -> KVBatch:
    """All-0x00 and all-0xFF key lanes, and keys whose h1 is 0, 1,
    0xFFFFFFFE and 0xFFFFFFFF — the smallest folded key there is and the
    largest a valid row can have (0x7FFFFFFF), the pad sentinel's
    (0xFFFFFFFF) nearest neighbour — each twice, between invalid rows
    that hold the same extreme lanes."""
    keys = [[0] * KEY_LANES, [_M32] * KEY_LANES] + [
        _key_with_h1(h1, head)
        for h1 in (0, 1, 0xFFFFFFFE, 0xFFFFFFFF)
        for head in (0x61626364, 0x7A7A7A7A)
    ]
    lanes, valid = [], []
    for k in keys:
        lanes += [k, [_M32] * KEY_LANES, k, [0] * KEY_LANES]
        valid += [True, False, True, False]
    return _batch(lanes, valid=np.asarray(valid))


def _interleaved() -> KVBatch:
    b = _random_with_duplicates(5000)
    valid = np.arange(5000) % 3 != 1
    return KVBatch(b.key_lanes, b.values, jnp.asarray(valid))


def _no_valid_row() -> KVBatch:
    b = _random_with_duplicates(64)
    return KVBatch(b.key_lanes, b.values, jnp.zeros(64, bool))


INPUTS = {
    "dups-1": functools.partial(_random_with_duplicates, 1),
    "dups-2": functools.partial(_random_with_duplicates, 2),
    "dups-7": functools.partial(_random_with_duplicates, 7),
    "dups-8192": functools.partial(_random_with_duplicates, 8192),
    "dups-100000": functools.partial(_random_with_duplicates, 100_000),
    "all-equal": lambda: _batch(np.tile(_key_with_h1(12345, 7), (4096, 1))),
    "in-order": _in_lex_order,
    "reversed": _reversed,
    "extremes": _extremes,
    "invalid-interleaved": _interleaved,
    "no-valid-row": _no_valid_row,
}


def _live_rows(batch: KVBatch) -> np.ndarray:
    """The valid rows as one sorted ``[n, lanes + 1]`` array (a multiset)."""
    valid = np.asarray(batch.valid)
    rows = np.column_stack(
        [np.asarray(batch.key_lanes)[valid].astype(np.int64),
         np.asarray(batch.values)[valid].astype(np.int64)]
    )
    return rows[np.lexsort(rows.T[::-1])]


def _numpy_table(batch: KVBatch) -> list[tuple[bytes, int]]:
    rows = _live_rows(batch)
    if not len(rows):
        return []
    keys, inverse = np.unique(rows[:, :-1], axis=0, return_inverse=True)
    sums = np.bincount(inverse.ravel(), weights=rows[:, -1]).astype(np.int64)
    names = keys.astype(">u4").view(np.uint8).reshape(len(keys), -1)
    return sorted(
        (bytes(k).rstrip(b"\0"), int(v)) for k, v in zip(names, sums)
    )


def _table(sorted_batch: KVBatch) -> list[tuple[bytes, int]]:
    return finalize_host_pairs(segment_reduce(sorted_batch, "sum"), "sum")


def test_fmix32_inverse_is_the_packing_hash():
    """The constructed keys stand on this file's copy of the hash: hold it
    to ``packing.hash_pair`` itself."""
    from locust_tpu.core.packing import hash_pair

    for h1 in (0, 1, 0x7FFFFFFF, 0xFFFFFFFE, 0xFFFFFFFF, 0xDEADBEEF):
        assert _fmix32(_fmix32_inv(h1)) == h1
        lanes = jnp.asarray([_key_with_h1(h1, 0x41424344)], jnp.uint32)
        assert int(hash_pair(lanes)[0][0]) == h1


@pytest.mark.parametrize("name", list(INPUTS))
@pytest.mark.parametrize("mode", MODES)
def test_sort_and_compact_holds_on_edge_inputs(mode, name):
    batch = INPUTS[name]()
    n_valid = int(np.asarray(batch.valid).sum())
    if name == "extremes":
        # A valid row's folded key never is the sentinel, whatever its
        # hash; an invalid row's always is, whatever its lanes.
        folded = np.asarray(_folded_key(batch))
        valid = np.asarray(batch.valid)
        assert folded[valid].max() == 0x7FFFFFFF and folded[valid].min() == 0
        assert (folded[~valid] == 0xFFFFFFFF).all()

    out = jax.jit(functools.partial(sort_and_compact, mode=mode))(batch)
    oracle = sort_and_compact(batch, "lex")

    got_valid = np.asarray(out.valid)
    assert out.size == batch.size
    assert got_valid[:n_valid].all() and not got_valid[n_valid:].any()
    assert np.array_equal(_live_rows(out), _live_rows(batch))
    want = _table(oracle)
    assert _table(out) == want
    assert want == _numpy_table(batch)
