"""Unit tests for the byte-tensor string library, vs Python str oracles.

The reference ships its device libc (util.cu) with zero tests (SURVEY.md §4);
these property-style tests are the unit layer the rebuild adds.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from locust_tpu.config import DELIMITERS
from locust_tpu.core import bytes_ops, packing


WORDS = [b"", b"a", b"the", b"hamlet", b"to-be", b"or not", b"x" * 31, b"z" * 32]


def test_byte_length_matches_len():
    rows = bytes_ops.strings_to_rows(WORDS, width=32)
    lens = bytes_ops.byte_length(jnp.asarray(rows))
    expect = [min(len(w), 32) for w in WORDS]
    np.testing.assert_array_equal(np.asarray(lens), expect)


def test_byte_length_no_nul_row():
    row = jnp.full((1, 8), ord("a"), dtype=jnp.uint8)
    assert int(bytes_ops.byte_length(row)[0]) == 8


def test_delimiter_mask_matches_reference_set():
    text = b"to be, or not to-be: that's (the) \"question\"\t"
    row = jnp.asarray(np.frombuffer(text, dtype=np.uint8))[None, :]
    mask = np.asarray(bytes_ops.delimiter_mask(row))[0]
    expect = [bytes([c]) in DELIMITERS + b"\x00\n\r" for c in text]
    np.testing.assert_array_equal(mask, expect)


from helpers import strtok_tokens as _py_tokens


@pytest.mark.parametrize(
    "line",
    [
        b"to be or not to be",
        b"  leading and  double  spaces ",
        b"hyphen-split and 'quoted' (parens), punct.;:",
        b"",
        b"single",
        b"\t\ttabs\tonly\t",
    ],
)
def test_token_masks_match_oracle(line):
    row = jnp.asarray(bytes_ops.strings_to_rows([line], width=64))
    in_token = ~bytes_ops.delimiter_mask(row)
    starts = bytes_ops.token_starts(in_token)
    ends = bytes_ops.token_ends(in_token)
    n = int(bytes_ops.count_tokens(row)[0])
    toks = _py_tokens(line)
    assert n == len(toks)
    # Reconstruct tokens from the masks and compare bytes.
    s_idx = np.flatnonzero(np.asarray(starts)[0])
    e_idx = np.flatnonzero(np.asarray(ends)[0])
    got = [line[s : e + 1] for s, e in zip(s_idx, e_idx)]
    assert got == toks


def test_token_ids_are_cumulative():
    row = jnp.asarray(bytes_ops.strings_to_rows([b"a bb ccc"], width=16))
    in_token = ~bytes_ops.delimiter_mask(row)
    tid = np.asarray(bytes_ops.token_ids(bytes_ops.token_starts(in_token)))[0]
    assert tid[0] == 0  # 'a'
    assert tid[2] == 1 and tid[3] == 1  # 'bb'
    assert tid[5] == 2  # 'ccc'


@pytest.mark.parametrize("vals", [[0, 1, 9, 10, 12345, 2**31 - 1]])
def test_itoa_matches_str(vals):
    out = bytes_ops.itoa_bytes(jnp.asarray(vals, dtype=jnp.int32), width=12)
    got = bytes_ops.rows_to_strings(np.asarray(out))
    assert got == [str(v).encode() for v in vals]


def test_pack_unpack_roundtrip():
    rows = bytes_ops.strings_to_rows(WORDS, width=32)
    lanes = packing.pack_keys(jnp.asarray(rows))
    back = packing.unpack_keys(lanes)
    np.testing.assert_array_equal(np.asarray(back), rows)


def test_packed_lane_order_is_lexicographic():
    words = sorted([b"", b"a", b"aa", b"ab", b"b", b"the", b"thee", b"them", b"zz"])
    rows = bytes_ops.strings_to_rows(words, width=32)
    lanes = packing.pack_keys(jnp.asarray(rows))
    rng = np.random.default_rng(0)
    for _ in range(20):
        i, j = rng.integers(0, len(words), size=2)
        a, b = lanes[i][None], lanes[j][None]
        assert bool(packing.lanes_less(a, b)[0]) == (words[i] < words[j])
        assert bool(packing.lanes_equal(a, b)[0]) == (words[i] == words[j])


def test_fold_hash_distributes():
    words = [f"word{i}".encode() for i in range(256)]
    rows = bytes_ops.strings_to_rows(words, width=32)
    h = np.asarray(packing.fold_hash(packing.pack_keys(jnp.asarray(rows))))
    assert len(np.unique(h)) == len(words)  # no collisions on this set
    buckets = np.bincount(h % 8, minlength=8)
    assert buckets.min() > 0  # every bucket hit


def _rows_to_strings_loop(rows):
    """The row-at-a-time decode ``rows_to_strings`` had, kept as its reference."""
    out = []
    for row in np.asarray(rows):
        b = row.tobytes()
        i = b.find(b"\x00")
        out.append(b if i < 0 else b[:i])
    return out


@pytest.mark.parametrize("shape", [(0, 8), (5, 0), (1, 1), (257, 8), (1000, 32)])
def test_rows_to_strings_equals_the_row_at_a_time_decode(shape):
    """Full rows, empty rows, trailing NULs and NULs INSIDE a key (cut at
    the first, whatever follows): the whole-array decode is the loop's."""
    rng = np.random.default_rng(shape[0] + shape[1])
    rows = rng.integers(0, 4, size=shape).astype(np.uint8) * 40  # many NULs
    if shape[0] > 2 and shape[1]:
        rows[0] = 65                     # a full row, no NUL at all
        rows[1] = 0                      # an empty key
        rows[2, 1:] = 0                  # trailing NULs only
    assert bytes_ops.rows_to_strings(rows) == _rows_to_strings_loop(rows)
