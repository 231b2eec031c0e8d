"""The plain reference of the record sort: what ``python -m locust_tpu sort
IN OUT`` must write, computed the straightforward way on the host.

Independent of the code under test: no jax, nothing of ``locust_tpu``.
The file is ``N x record_bytes`` bytes; the answer is the same rows
stable-sorted by their first ``key_bytes`` bytes compared as UNSIGNED
bytes — equal keys keep their input order — which is sortbenchmark.org's
order for ``gensort`` records (100 bytes, 10 of key) and what ``valsort``
/ Hadoop's TeraValidate hold a TeraSort's output to.  The benchmark keeps
its own copy (``benchmarks/records.py``), as ``benchmarks/yardstick.py``
keeps the WordCount oracle's.
"""

from __future__ import annotations

import numpy as np

RECORD_BYTES, KEY_BYTES = 100, 10


def sorted_records(data: bytes, record_bytes: int = RECORD_BYTES,
                   key_bytes: int = KEY_BYTES) -> bytes:
    """``data`` with its records in key order.  Python's ``sorted`` on
    slices where that is quick (``bytes`` compare unsigned, ``sorted`` is
    stable); past that numpy's stable sort of an ``S`` view of the keys
    cannot be used as it stands — ``S`` drops trailing NULs, so the key
    gets one byte of 0x01 behind it, which changes no order."""
    if not 1 <= key_bytes <= record_bytes:
        raise ValueError(f"key_bytes {key_bytes} not in 1..{record_bytes}")
    if not data or len(data) % record_bytes:
        raise ValueError(f"{len(data)} bytes is no whole number of "
                         f"{record_bytes}-byte records")
    n = len(data) // record_bytes
    if n <= 1 << 16:
        rows = [data[i * record_bytes:(i + 1) * record_bytes] for i in range(n)]
        return b"".join(sorted(rows, key=lambda row: row[:key_bytes]))
    rows = np.frombuffer(data, np.uint8).reshape(n, record_bytes)
    keys = np.full((n, key_bytes + 1), 1, np.uint8)
    keys[:, :key_bytes] = rows[:, :key_bytes]
    order = np.argsort(keys.view(f"S{key_bytes + 1}").ravel(), kind="stable")
    return rows[order].tobytes()


def sorted_file(path: str, record_bytes: int = RECORD_BYTES,
                key_bytes: int = KEY_BYTES) -> bytes:
    with open(path, "rb") as f:
        return sorted_records(f.read(), record_bytes, key_bytes)
