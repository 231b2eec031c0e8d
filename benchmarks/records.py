"""TeraSort records: the generator and the oracle of ``terasort-800MB``.

The benchmark's OWN copies, as ``yardstick.oracle_table`` is its copy of
the WordCount oracle: nothing here imports the program or jax, so no later
PR can move the measure by editing ``locust_tpu/``.

``build`` writes sortbenchmark.org's record as ``gensort`` lays it out
(recalled with no network; ``configs/terasort-800MB.json`` lists it under
``assumed``): 100 bytes — bytes 0-9 the binary key, uniform and
independent from the seed; 10-11 ``00 11``; 12-43 the record's number as
32 upper-case hex digits; 44-47 ``88 99 AA BB``; 48-95 twelve hex digits
each written four times; 96-99 ``CC DD EE FF``.  The number makes every
record distinct, so a lost, doubled or altered one shows in a byte
comparison.  ``oracle`` is the plain reference: the rows stable-sorted by
their first ``key_bytes`` bytes as unsigned bytes, as ``valsort`` and
Hadoop's TeraValidate hold a TeraSort's output to (order, count, nothing
lost or altered) — here all three at once, byte for byte.
"""

from __future__ import annotations

import numpy as np

RECORD_BYTES, KEY_BYTES = 100, 10
_HEX = np.frombuffer(b"0123456789ABCDEF", np.uint8)
_CHUNK = 1 << 20  # records drawn at a time: bounds the temporaries


def build(path: str, records: int, seed: int) -> int:
    """Write ``records`` gensort-format records drawn from ``seed`` to
    ``path``; returns the bytes written.  A function of (records, seed)."""
    rng = np.random.default_rng(seed)
    with open(path, "wb") as f:
        for start in range(0, records, _CHUNK):
            n = min(_CHUNK, records - start)
            # Columns first (each a contiguous row of ``cols``), then one
            # transposition into records.
            cols = np.empty((RECORD_BYTES, n), np.uint8)
            cols[:KEY_BYTES] = rng.integers(0, 256, (KEY_BYTES, n), dtype=np.uint8)
            cols[10], cols[11] = 0x00, 0x11
            number = np.arange(start, start + n, dtype=np.uint64)
            cols[12:28] = _HEX[0]                # a 128-bit number's high half
            for d in range(16):
                cols[28 + d] = _HEX[(number >> np.uint64(4 * (15 - d))) & np.uint64(15)]
            cols[44:48] = np.array([0x88, 0x99, 0xAA, 0xBB], np.uint8)[:, None]
            cols[48:96] = np.repeat(_HEX[rng.integers(0, 16, (12, n), dtype=np.uint8)], 4, axis=0)
            cols[96:100] = np.array([0xCC, 0xDD, 0xEE, 0xFF], np.uint8)[:, None]
            f.write(np.ascontiguousarray(cols.T).data)
    return records * RECORD_BYTES


def load(path: str, record_bytes: int = RECORD_BYTES) -> np.ndarray:
    """The file as ``[records, record_bytes]`` uint8 rows."""
    data = np.fromfile(path, np.uint8)
    if data.size == 0 or data.size % record_bytes:
        raise ValueError(f"{path}: {data.size} bytes is no whole number of "
                         f"{record_bytes}-byte records")
    return data.reshape(-1, record_bytes)


def oracle(rows: np.ndarray, key_bytes: int = KEY_BYTES) -> np.ndarray:
    """``rows`` stable-sorted by their first ``key_bytes`` bytes compared
    as unsigned bytes (ties keep input order): the key as big-endian
    64-bit columns, zero-padded, through ``numpy.lexsort``."""
    n = rows.shape[0]
    width = -(-key_bytes // 8) * 8
    key = np.zeros((n, width), np.uint8)
    key[:, :key_bytes] = rows[:, :key_bytes]
    cols = key.view(">u8")                       # [n, width / 8], most significant first
    order = np.lexsort(tuple(cols[:, j] for j in range(cols.shape[1] - 1, -1, -1)))
    return rows[order]
