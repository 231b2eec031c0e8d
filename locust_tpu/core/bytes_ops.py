"""Byte-tensor string primitives — the TPU-native device string library.

The reference hand-rolls a device libc (my_strlen/my_strcmp/my_strcpy/
my_strtok_r/my_reverse/my_itoa, reference MapReduce/src/util.cu:3-140) because
CUDA kernels have no libc.  On TPU the idiomatic formulation is data-parallel
ops over fixed-width ``uint8`` tensors: a "string" is a NUL-padded row, and
every libc routine becomes a vectorized mask/scan/gather:

  my_strlen   -> byte_length          (argmax of the NUL mask)
  my_strcmp   -> packed-lane compare  (see core/packing.py; big-endian uint32
                                       lane order == lexicographic byte order)
  my_strcpy   -> array slicing / take_along_axis gathers
  my_strtok_r -> token_starts/token_ids (delimiter mask + prefix-sum segment
                 ids, replacing the inherently sequential strtok_r loop at
                 util.cu:54-89 with one parallel pass)
  my_itoa     -> itoa_bytes           (vectorized decimal digit extraction,
                 replacing util.cu:106-140 + my_reverse at util.cu:91-104)

All functions are shape-polymorphic over leading batch dims and jit-safe
(static shapes, no data-dependent control flow).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from locust_tpu.config import DELIMITERS


def byte_length(x: jax.Array) -> jax.Array:
    """Length of each NUL-padded byte row: ``my_strlen`` (util.cu:3-9).

    Args:
      x: uint8 array ``[..., W]``, rows padded with 0 after the content.
    Returns:
      int32 array ``[...]`` — index of the first zero byte, or W if none.
    """
    w = x.shape[-1]
    is_nul = x == 0
    first = jnp.argmax(is_nul, axis=-1).astype(jnp.int32)
    return jnp.where(jnp.any(is_nul, axis=-1), first, w)


def delimiter_mask(x: jax.Array, delimiters: bytes = DELIMITERS) -> jax.Array:
    """Boolean mask of bytes that terminate tokens.

    Matches the reference's strtok delimiter set (main.cu:138) plus the NUL
    pad byte and newline/carriage-return, which in the reference never reach
    strtok because tokenization is per-getline-line.
    """
    from locust_tpu.config import TOKEN_BOUNDARY_EXTRA

    delims = np.frombuffer(delimiters + TOKEN_BOUNDARY_EXTRA, dtype=np.uint8)
    # Small membership test: [..., W, D] compare then any-reduce. D is ~13 so
    # this stays cheap and fuses into one VPU pass.
    return jnp.any(x[..., None] == jnp.asarray(delims), axis=-1)


def token_starts(in_token: jax.Array) -> jax.Array:
    """Mask of token first-bytes given an in-token (non-delimiter) mask.

    A byte starts a token iff it is in-token and its left neighbor is not
    (position 0 counts as having a delimiter neighbor) — the parallel
    equivalent of strtok_r's "skip leading delimiters" phase (util.cu:63-70).
    """
    prev = jnp.pad(in_token[..., :-1], [(0, 0)] * (in_token.ndim - 1) + [(1, 0)])
    return in_token & ~prev


def token_ends(in_token: jax.Array) -> jax.Array:
    """Mask of token last-bytes (right neighbor is a delimiter or row end)."""
    nxt = jnp.pad(in_token[..., 1:], [(0, 0)] * (in_token.ndim - 1) + [(0, 1)])
    return in_token & ~nxt


def token_ids(starts: jax.Array) -> jax.Array:
    """0-based token index at every byte position (valid where in-token).

    ``cumsum(starts) - 1`` — the prefix-sum segment-id trick that replaces
    the sequential token loop of strtok_r (util.cu:54-89).
    """
    return jnp.cumsum(starts.astype(jnp.int32), axis=-1) - 1


def count_tokens(lines: jax.Array, delimiters: bytes = DELIMITERS) -> jax.Array:
    """Number of tokens per row."""
    starts = token_starts(~delimiter_mask(lines, delimiters))
    return jnp.sum(starts.astype(jnp.int32), axis=-1)


def itoa_bytes(values: jax.Array, width: int = 12) -> jax.Array:
    """Non-negative int32 -> left-aligned ASCII decimal, NUL-padded.

    Vectorized ``my_itoa`` (util.cu:106-140): digit extraction by repeated
    division; the reference then reverses in place (my_reverse, util.cu:91-104)
    — here we extract most-significant-first and left-shift by the digit
    count instead, with a take_along_axis gather.

    Args:
      values: int32 ``[...]`` of non-negative integers (negatives clamp to 0).
      width: output byte width; >= 10 so any int32 fits.
    Returns:
      uint8 ``[..., width]``.
    """
    if width < 10:
        raise ValueError(f"width {width} cannot hold all int32 values (need >= 10)")
    v = jnp.maximum(values.astype(jnp.int32), 0)
    # Right-aligned digits, most significant first.  int32 holds <= 10 digits,
    # so powers beyond 10^9 are materialized as 10^9 and masked to digit 0.
    p_exp = list(range(width - 1, -1, -1))
    pows = jnp.asarray([10 ** min(p, 9) for p in p_exp], dtype=jnp.int32)
    in_range = jnp.asarray([p <= 9 for p in p_exp])
    digits = jnp.where(in_range, (v[..., None] // pows) % 10, 0)  # [..., width]
    ndig = jnp.maximum(
        jnp.sum((in_range & (v[..., None] >= pows)).astype(jnp.int32), axis=-1), 1
    )  # number of significant digits; v=0 -> 1
    # Left-align: output position k reads right-aligned position k+(width-ndig).
    k = jnp.arange(width, dtype=jnp.int32)
    src = k + (width - ndig)[..., None]
    gathered = jnp.take_along_axis(digits, jnp.clip(src, 0, width - 1), axis=-1)
    ascii_digits = (gathered + ord("0")).astype(jnp.uint8)
    return jnp.where(k < ndig[..., None], ascii_digits, jnp.uint8(0))



# ---------------------------------------------------------------- fields
# Delimited rows (PR 48): a line is fields apart by ONE delimiter byte —
# a Hive table's ``FIELDS TERMINATED BY ','`` — where every map before split
# on the reference's word delimiters.  Everything below is whole-array
# arithmetic over the ``[rows, width]`` bytes: a column read a digit would
# have XLA recompute the producer a column (core/packing._salted_fold's
# lesson), so a number is parsed by static weights over an ALIGNED field.


def field_ends(lines: jax.Array, delimiter: int, n_fields: int):
    """Where the first ``n_fields`` fields of every row end.

    Args:
      lines: uint8 ``[rows, W]``, NUL-padded.
      delimiter: the field delimiter, a byte value.
    Returns:
      ``(ends, n_found, length)``: int32 ``[rows, n_fields]`` — field k is
      ``row[ends[k-1] + 1 : ends[k]]`` (field 0 starts at 0), its end the
      k-th delimiter or, past the row's last, the row's length —, the
      fields the row holds up to ``n_fields`` (a row of no delimiter holds
      one) and the row's length.
    """
    w = lines.shape[-1]
    col = jnp.arange(w, dtype=jnp.int32)
    length = byte_length(lines)
    is_delim = (lines == jnp.uint8(delimiter)) & (col < length[:, None])
    ordinal = jnp.cumsum(is_delim.astype(jnp.int32), axis=-1)
    ends = jnp.stack(
        [jnp.min(jnp.where(is_delim & (ordinal == k + 1), col, length[:, None]),
                 axis=-1) for k in range(n_fields)], axis=-1)
    n_found = jnp.minimum(ordinal[:, -1] + 1, n_fields)
    return ends, n_found, length


def shift_left(rows: jax.Array, by: jax.Array, width: int) -> jax.Array:
    """``rows[i, by[i] : by[i] + width]``, zeros past the row's end: a
    per-row ``my_strcpy`` from a dynamic start with NO gather — a barrel
    shifter, one select a bit of ``by`` (a gather along the minor axis is
    the slowest thing a TPU does to a row).  Largest shift first, and after
    the bit of 2^b only the columns a smaller shift can still bring in are
    kept, so a narrow field costs about two passes over the row.

    Args:
      rows: uint8 ``[n, W]``; by: int32 ``[n]``, clipped to ``0 .. W``.
    """
    n, w = rows.shape
    by = jnp.clip(by.astype(jnp.int32), 0, w)
    x = rows
    for b in range(max(w, 1).bit_length() - 1, -1, -1):
        step = 1 << b
        moved = jnp.pad(x[:, step:], ((0, 0), (0, min(step, x.shape[1]))))
        x = jnp.where(((by >> b) & 1).astype(bool)[:, None], moved, x)
        x = x[:, : width + step - 1]  # what shifts under 2^b can still reach
    if x.shape[1] < width:
        x = jnp.pad(x, ((0, 0), (0, width - x.shape[1])))
    return x


def _digits(field: jax.Array):
    d = field.astype(jnp.int32) - ord("0")
    return d, (d >= 0) & (d <= 9)


def _weighted_digits(field: jax.Array, inside: jax.Array):
    """(the decimal number the ``inside`` columns of ``field`` spell, the
    last column the units; whether every one of them is a digit)."""
    d_max = field.shape[-1]
    if d_max > 9:
        raise ValueError(f"{d_max} digits do not fit an int32")
    d, is_digit = _digits(field)
    weights = jnp.asarray([10 ** (d_max - 1 - j) for j in range(d_max)], jnp.int32)
    return (jnp.sum(jnp.where(inside, d, 0) * weights, axis=-1),
            jnp.all(~inside | is_digit, axis=-1))


def parse_uint_right(field: jax.Array, n_digits: jax.Array):
    """A decimal number whose LAST digit stands in ``field``'s last column.

    Args:
      field: uint8 ``[n, D]`` (D <= 9): the ``D`` bytes that end where the
        number ends — whatever stands before its first digit is ignored.
      n_digits: int32 ``[n]``, the number's length.
    Returns:
      ``(value int32, ok)``: ok where ``1 <= n_digits <= D`` and every one of
      them is a digit.
    """
    d_max = field.shape[-1]
    col = jnp.arange(d_max, dtype=jnp.int32)
    value, digits = _weighted_digits(field, col >= d_max - n_digits[:, None])
    return value, (n_digits >= 1) & (n_digits <= d_max) & digits


def parse_fraction_left(field: jax.Array, n_digits: jax.Array):
    """The digits after a decimal point, ``field``'s first ``n_digits``
    columns, in units of ``10 ** -D`` (D = ``field``'s width, at most 9):
    ``.5`` over six columns is 500,000.  ``n_digits`` 0 is no fraction: 0, ok.
    """
    d_max = field.shape[-1]
    col = jnp.arange(d_max, dtype=jnp.int32)
    value, digits = _weighted_digits(field, col < n_digits[:, None])
    return value, (n_digits >= 0) & (n_digits <= d_max) & digits


_DATE_WEIGHTS = (10**7, 10**6, 10**5, 10**4, 0, 10**3, 10**2, 0, 10, 1)


def parse_date(field: jax.Array, length: jax.Array):
    """``YYYY-MM-DD`` in ``field``'s first ten columns as the integer
    ``yyyymmdd`` (dates compare as they do), and whether it IS a date of the
    proleptic Gregorian calendar (``datetime.date``'s: year 1 .. 9999, the
    month's own days, leap years by the 4/100/400 rule).

    Args:
      field: uint8 ``[n, >= 10]``; length: int32 ``[n]``, the field's length.
    """
    d, is_digit = _digits(field[:, :10])
    dash = jnp.asarray([w == 0 for w in _DATE_WEIGHTS])
    shaped = (length == 10) & jnp.all(
        jnp.where(dash, field[:, :10] == ord("-"), is_digit), axis=-1)
    ymd = jnp.sum(jnp.where(dash, 0, d) * jnp.asarray(_DATE_WEIGHTS, jnp.int32), axis=-1)
    year, month, day = ymd // 10000, (ymd // 100) % 100, ymd % 100
    leap = (year % 4 == 0) & ((year % 100 != 0) | (year % 400 == 0))
    thirty = (month == 4) | (month == 6) | (month == 9) | (month == 11)
    days = jnp.where(month == 2, 28 + leap.astype(jnp.int32), 31 - thirty.astype(jnp.int32))
    ok = (shaped & (year >= 1) & (month >= 1) & (month <= 12)
          & (day >= 1) & (day <= days))
    return ymd, ok


def rows_to_strings(rows: np.ndarray) -> list[bytes]:
    """Host-side: NUL-padded uint8 rows -> Python bytes (up to first NUL).

    One pass in numpy: a fixed-width bytes view drops the trailing NULs of
    every row at once (a table of 650,000 keys decodes in a fifth of a
    second, not one and a half), and only rows with a NUL INSIDE the key
    are cut again by hand."""
    rows = np.ascontiguousarray(rows, dtype=np.uint8)
    n, width = rows.shape
    if n == 0 or width == 0:
        return [b""] * n
    out = rows.view(f"S{width}").ravel().tolist()
    zero = rows == 0
    first = np.where(zero.any(axis=1), zero.argmax(axis=1), width)
    inside = np.count_nonzero(rows, axis=1) != first
    for i in np.flatnonzero(inside):
        out[i] = out[i][: out[i].find(b"\x00")]
    return out


def render_rows(keys: np.ndarray, values: np.ndarray) -> bytes:
    """Host-side: ordered table rows -> the ``key<TAB>count<LF>`` bytes the
    CLI prints, in numpy, with no Python object a row.

    ONE ``uint8 [n, width + digits + 2]`` matrix holds a row's NUL-padded
    key, TAB, its decimal digits right-aligned behind NULs, and LF; every
    NUL is then dropped by one boolean take.  The digits by repeated
    division by ten over the rows that still have a quotient (a WordCount
    table's counts are mostly one digit, so the second pass sees a few
    rows in a hundred).  Byte-equal to ``b"".join(k + b"\t" +
    str(v).encode() + b"\n")`` over ``rows_to_strings(keys)`` where
    ``render_blocker`` finds nothing in the way; the caller asks it first.

    Args:
      keys: uint8 ``[n, width]`` NUL-padded key rows, in print order.
      values: int32 ``[n]``, none negative.
    """
    n, width = keys.shape
    if n == 0:
        return b""
    digits = len(str(int(values.max())))
    out = np.zeros((n, width + digits + 2), dtype=np.uint8)
    out[:, :width] = keys
    out[:, width] = ord("\t")
    out[:, -1] = ord("\n")
    ten, zero = np.uint32(10), np.uint32(ord("0"))
    v = values.astype(np.uint32)
    q = v // ten
    out[:, -2] = v - q * ten + zero
    live = np.flatnonzero(q)
    v, col = q[live], -3
    while live.size:
        q = v // ten
        out[live, col] = v - q * ten + zero
        more = q != 0
        live, v, col = live[more], q[more], col - 1
    flat = out.ravel()
    return flat[flat != 0].tobytes()


def _write_digits(out: np.ndarray, cols, v: np.ndarray) -> None:
    """``v``'s decimal digits (uint32 ``[n]``), least significant first,
    into the columns ``cols`` of every row of ``out``."""
    ten, zero = np.uint32(10), np.uint32(ord("0"))
    for col in cols:
        q = v // ten
        out[:, col] = v - q * ten + zero
        v = q


def render_postings(words: np.ndarray, offsets: np.ndarray,
                    postings: np.ndarray) -> bytes | None:
    """Host-side: an inverted index as arrays (CSR) -> the
    ``word<TAB>d1,d2,...<LF>`` bytes the CLI prints, a line a word in the
    order given, in numpy, with no Python object a posting; byte-equal to
    ``plan.compile.iter_rendered("postings", ...)`` over the same index as
    a dict.  None where the arrays hold what this layout cannot spell (a
    negative doc id, a word with no posting): the caller then joins a row
    at a time.

    Two matrices, each flattened and rid of its NULs by one boolean take:
    a posting's cell is its decimal digits, leading zeros NUL, and its
    separator (a comma, LF after a word's last); a word's is its NUL-padded
    key and TAB.  The two streams are then laid into one buffer under a
    mask that alternates a word's key bytes and its postings' bytes.

    Args:
      words: uint8 ``[n_words, width]`` NUL-padded keys, none with a NUL
        inside, in print order.
      offsets: ``[n_words + 1]``, word ``w``'s postings are
        ``postings[offsets[w]:offsets[w + 1]]``.
      postings: int32 ``[n_pairs]`` doc ids.
    """
    n_words, width = words.shape
    if n_words == 0:
        return b""
    offsets = np.asarray(offsets, dtype=np.int64)
    n = postings.shape[0]
    if int(np.diff(offsets).min()) < 1 or int(postings.min()) < 0:
        return None
    v = postings.astype(np.uint32)
    digits = len(str(int(v.max())))
    cells = np.empty((n, digits + 1), dtype=np.uint8)
    cell_len = np.full(n, 2, dtype=np.uint8)  # the last digit and the separator
    ten, zero = np.uint32(10), np.uint32(ord("0"))
    q = v
    for place in range(digits):
        rest = q // ten
        digit = q - rest * ten + zero
        if place:  # a leading zero is a NUL, dropped below
            shown = v >= np.uint32(10 ** place)
            digit *= shown
            cell_len += shown
        cells[:, digits - 1 - place] = digit
        q = rest
    cells[:, digits] = ord(",")
    cells[offsets[1:] - 1, digits] = ord("\n")
    flat = cells.ravel()
    posting_bytes = flat[flat != 0]
    keys = np.empty((n_words, width + 1), dtype=np.uint8)
    keys[:, :width] = words
    keys[:, width] = ord("\t")
    flat = keys.ravel()
    key_bytes = flat[flat != 0]
    lens = np.empty(2 * n_words, dtype=np.int64)
    lens[0::2] = np.count_nonzero(words, axis=1) + 1
    lens[1::2] = np.add.reduceat(cell_len, offsets[:-1], dtype=np.int64)
    is_key = np.repeat(np.tile(np.array([True, False]), n_words), lens)
    out = np.empty(is_key.size, dtype=np.uint8)
    out[is_key] = key_bytes
    out[~is_key] = posting_bytes
    return out.tobytes()


_RANK_CHARS = 14  # a rank as plan.compile.rank_row spells it: d.dddddddde-XX
# 10**k as the nearest double for every two-digit decimal exponent e
# (k = 8 - e; a float32's e lies in -45 .. 38).
_POW10_BIAS = 100
_POW10 = np.array(
    [1.0 / float(10 ** -k) if k < 0 else float(10 ** k)
     for k in range(-_POW10_BIAS, 111)]
)


def _nine_digits(x: np.ndarray) -> np.ndarray | None:
    """Non-negative float64 ``[n]`` (n > 0) -> uint8 ``[n, 14]``, each row
    the number as ``format(x, ".8e")`` spells it (``d.dddddddde-XX``), in
    numpy with no Python object a row.  None where the vector holds what
    the fixed-width layout cannot spell (a negative, inf, nan, a
    three-digit exponent).

    A number is scaled to a nine-digit integer in float64 (exact for a
    float32 up to 4e-16 relative), rounded, and its digits written by
    division.  Where the scaled value lies within 1e-5 of a half — a few
    rows in a million — the double cannot say which way the exact decimal
    rounds, and that row's fourteen characters come from Python's own
    formatting.
    """
    n = x.shape[0]
    if not np.isfinite(x).all() or x.min() < 0:
        return None
    live = x > 0
    e = np.zeros(n, dtype=np.int64)
    e[live] = np.floor(np.log10(x[live]))
    if np.abs(e).max() > 98:
        return None
    scaled = x * _POW10[8 - e + _POW10_BIAS]
    for low, step in ((scaled < 1e8) & live, -1), (scaled >= 1e9, 1):
        e[low] += step  # log10's floor, off by one beside a power of ten
        scaled[low] = x[low] * _POW10[8 - e[low] + _POW10_BIAS]
    whole = np.floor(scaled)
    unsure = np.flatnonzero(np.abs(scaled - whole - 0.5) < 1e-5)
    mant = np.rint(scaled).astype(np.uint32)
    carried = mant == 1_000_000_000  # 9.999999996 prints as 1.00000000e+01
    mant[carried] = 100_000_000
    e[carried] += 1

    rank = np.empty((n, _RANK_CHARS), dtype=np.uint8)
    # d.dddddddd: column 1 is the point
    _write_digits(rank, (9, 8, 7, 6, 5, 4, 3, 2, 0), mant)
    ten, zero = np.uint32(10), np.uint32(ord("0"))
    rank[:, 1] = ord(".")
    rank[:, 10] = ord("e")
    rank[:, 11] = np.where(e < 0, ord("-"), ord("+"))
    mag = np.abs(e).astype(np.uint32)
    rank[:, 12] = mag // ten + zero
    rank[:, 13] = mag % ten + zero
    for i in unsure:
        rank[i] = np.frombuffer(format(x[i], ".8e").encode(), np.uint8)
    return rank


def render_rank_rows(ranks: np.ndarray) -> bytes | None:
    """Host-side: a rank vector -> the ``id<TAB>rank<LF>`` bytes of every
    node in id order, in numpy, with no Python object a row; byte-equal
    to ``plan.compile.rank_row`` a row.  None where the vector holds what
    the fixed-width layout cannot spell (``_nine_digits``): the caller then
    joins ``rank_row`` a row.
    """
    x = np.asarray(ranks, dtype=np.float64)
    n = x.shape[0]
    if n == 0:
        return b""
    digits = _nine_digits(x)
    if digits is None:
        return None
    id_digits = len(str(n - 1))
    out = np.empty((n, id_digits + _RANK_CHARS + 2), dtype=np.uint8)
    _write_digits(out, range(id_digits - 1, -1, -1), np.arange(n, dtype=np.uint32))
    for col in range(id_digits - 1):  # ids are 0 .. n-1: the short ones lead
        out[:10 ** (id_digits - 1 - col), col] = 0  # NUL, dropped below
    out[:, id_digits] = ord("\t")
    out[:, -1] = ord("\n")
    out[:, id_digits + 1:-1] = digits
    flat = out.ravel()
    return flat[flat != 0].tobytes()


def render_revenue_rows(ips: np.ndarray, averages: np.ndarray,
                        totals: np.ndarray) -> bytes | None:
    """Host-side: the join's table -> one ``sourceIP<TAB>avgPageRank<TAB>
    totalRevenue<LF>`` line a row, both numbers as ``format(x, ".8e")``
    spells them, in numpy.  None where a number cannot be spelled in the
    fixed-width layout (``_nine_digits``) or a sourceIP holds a NUL inside:
    the caller then formats a row at a time.

    Args:
      ips: uint8 ``[n, W]``, NUL-padded.  averages, totals: float64 ``[n]``.
    """
    n = ips.shape[0]
    if n == 0:
        return b""
    if ((ips[:, :-1] == 0) & (ips[:, 1:] != 0)).any():
        return None
    avg = _nine_digits(np.asarray(averages, np.float64))
    total = _nine_digits(np.asarray(totals, np.float64))
    if avg is None or total is None:
        return None
    w = ips.shape[1]
    out = np.empty((n, w + 2 * _RANK_CHARS + 3), dtype=np.uint8)
    out[:, :w] = ips
    out[:, w] = out[:, w + 1 + _RANK_CHARS] = ord("\t")
    out[:, w + 1:w + 1 + _RANK_CHARS] = avg
    out[:, w + 2 + _RANK_CHARS:-1] = total
    out[:, -1] = ord("\n")
    flat = out.ravel()
    return flat[flat != 0].tobytes()


def render_blocker(keys: np.ndarray, values: np.ndarray) -> str | None:
    """What stands between ordered rows and ``render_rows``, or None.

    ``keys`` are in byte order of the NUL-padded rows and that is the
    order and the spelling of the printed keys unless one of three things
    holds, each checked over whole arrays on the fixed-width view:
    ``"nul"`` — a key with a NUL inside it (``rows_to_strings`` cuts it
    there, which can make two keys equal and move a row); ``"duplicate"``
    — two neighbouring rows of one key (a 64-bit hash collision's second
    row, to be merged); ``"negative"`` — a value below zero (``min`` /
    ``max`` combines, int32 wraparound), whose sign the digits lack."""
    n, width = keys.shape
    if n == 0:
        return None
    fixed = np.ascontiguousarray(keys).view(f"S{width}").ravel()
    # A row's length to its last non-NUL byte is at least its count of
    # non-NUL bytes: the sums are equal only where every row's are.
    if int(np.char.str_len(fixed).sum()) != np.count_nonzero(keys):
        return "nul"
    if (fixed[1:] == fixed[:-1]).any():
        return "duplicate"
    if values.min() < 0:
        return "negative"
    return None


def strings_to_rows(strings: list[bytes], width: int) -> np.ndarray:
    """Host-side: byte strings -> NUL-padded uint8 rows, truncated to width."""
    out = np.zeros((len(strings), width), dtype=np.uint8)
    for i, s in enumerate(strings):
        s = s[:width]
        out[i, : len(s)] = np.frombuffer(s, dtype=np.uint8)
    return out
