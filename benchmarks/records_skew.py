"""TeraSort records with SKEWED, duplicate-heavy keys: the generator and the
oracle of ``terasort-skew-3.2GB-mesh4``.

The benchmark's OWN copies, as ``records.py`` is for the uniform keys:
nothing here imports the program or jax.  The record is ``records.py``'s
(gensort's 100 bytes as recalled: key 0-9, ``00 11``, the record's number
as 32 hex digits, ``88 99 AA BB``, twelve filler digits each four times,
``CC DD EE FF``); only the KEY LAW differs.  ``gensort -s`` (the skewed
keys sortbenchmark.org's Daytona class has to sort as well as the uniform
ones) cannot be looked up here, so its law is not guessed under its name:
the law below is this benchmark's, written out in the configuration's
``assumed.skew_law``:

* a record is HOT with probability 1/2.  A hot record's key is
  ``HOT[r]``, ``r`` drawn Zipf(1.0) over ``HOT_KEYS`` = 65,536 ranks by
  the inverse CDF (``zipf_text.zipf_ranks``' way); ``HOT`` is a fixed
  ``[65536, 10]`` byte table, uniform bytes from
  ``numpy.random.default_rng(HOT_TABLE_SEED)`` — a function of the rank
  alone, the same for every seed.  So half of all records tie with
  others, the commonest key holds 1 / (2 H_65536) = 4.3% of the records
  and the sixteen commonest 14.5%;
* a COLD record's key is 10 uniform bytes whose first byte is replaced by
  ``floor(256 v^2)``, ``v`` uniform in [0, 1): half the cold keys lie in
  the first quarter of the key space.

Everything but ``HOT`` is drawn from the seed.  ``oracle`` is the plain
reference, the benchmark's own: the rows STABLE-sorted by their first
``key_bytes`` bytes as unsigned bytes — equal keys in input order, which
on this law is the rule and not the exception.
"""

from __future__ import annotations

import numpy as np

import records
from records import KEY_BYTES, RECORD_BYTES, load, oracle  # noqa: F401  (the cell's driver asks this module for all four)

HOT_KEYS = 1 << 16
HOT_TABLE_SEED = 0x5EEDC0DE
HOT_SHARE = 0.5
ZIPF_EXPONENT = 1.0


def hot_table() -> np.ndarray:
    """The hot keys, ``[HOT_KEYS, KEY_BYTES]`` uint8, by rank."""
    return np.random.default_rng(HOT_TABLE_SEED).integers(
        0, 256, (HOT_KEYS, KEY_BYTES), dtype=np.uint8)


def hot_cdf() -> np.ndarray:
    """Unnormalised CDF of Zipf(``ZIPF_EXPONENT``) over the hot ranks."""
    return np.cumsum(np.arange(1, HOT_KEYS + 1, dtype=np.float64) ** -ZIPF_EXPONENT)


def draw_keys(rng, n: int, table: np.ndarray, cdf: np.ndarray) -> np.ndarray:
    """``n`` keys by the law, as columns: ``[KEY_BYTES, n]`` uint8."""
    keys = rng.integers(0, 256, (KEY_BYTES, n), dtype=np.uint8)
    v = rng.random(n)
    keys[0] = np.floor(256.0 * v * v).astype(np.uint8)
    hot = rng.random(n) < HOT_SHARE
    ranks = np.searchsorted(cdf, rng.random(n) * cdf[-1], side="right").clip(max=HOT_KEYS - 1)
    keys[:, hot] = table[ranks[hot]].T
    return keys


def build(path: str, n_records: int, seed: int) -> int:
    """Write ``n_records`` records drawn from ``seed`` to ``path``; returns
    the bytes written.  A function of (n_records, seed): ``records.build``'s
    loop with this law's keys in the place of its uniform ones."""
    rng = np.random.default_rng(seed)
    table, cdf = hot_table(), hot_cdf()
    hexd = records._HEX
    with open(path, "wb") as f:
        for start in range(0, n_records, records._CHUNK):
            n = min(records._CHUNK, n_records - start)
            cols = np.empty((RECORD_BYTES, n), np.uint8)
            cols[:KEY_BYTES] = draw_keys(rng, n, table, cdf)
            cols[10], cols[11] = 0x00, 0x11
            number = np.arange(start, start + n, dtype=np.uint64)
            cols[12:28] = hexd[0]
            for d in range(16):
                cols[28 + d] = hexd[(number >> np.uint64(4 * (15 - d))) & np.uint64(15)]
            cols[44:48] = np.array([0x88, 0x99, 0xAA, 0xBB], np.uint8)[:, None]
            cols[48:96] = np.repeat(hexd[rng.integers(0, 16, (12, n), dtype=np.uint8)], 4, axis=0)
            cols[96:100] = np.array([0xCC, 0xDD, 0xEE, 0xFF], np.uint8)[:, None]
            f.write(np.ascontiguousarray(cols.T).data)
    return n_records * RECORD_BYTES
