"""Intermediate-result serde: the inter-stage / inter-process data plane.

The reference's only inter-process format is a ``key\\tvalue`` TSV at
``/tmp/out.txt`` written by the map stage (``writeKeyIntValues``, reference
MapReduce/src/main.cu:116-124) and re-read by the reduce stage
(``loadIntermediateFile``, main.cu:66-103).  That file is also its entire
checkpoint/resume story (SURVEY.md §5).

Kept for CLI/staged-mode parity, with fixes:
  Q5  — the reference writes a trailing space in every key (``"%s \\t%d"``,
        main.cu:121); we write clean ``key\\tvalue`` but *accept* trailing
        spaces on read for compatibility with reference-produced files.
  Q10 — the reference dumps the full uncompacted MAX_EMITS buffer; we write
        only live entries.

For TPU-shard checkpoints (stage-level resume at scale) the binary ``npz``
format stores the packed device representation directly.

The distributor's data plane (docs/DATAPLANE.md) stages intermediates in
the packed binary KV format below instead of TSV: columnar (lens blob /
key blob / values array) so the master decodes straight into padded key
rows + an int32 vector with ``np.frombuffer`` — no per-line text parse —
and so the post-combine stream compresses well on the wire (sorted keys,
shared prefixes).  ``read_intermediate`` sniffs the magic, so mixed
TSV/binary inputs (old workers, reference-produced files) reduce fine.
"""

from __future__ import annotations

import os
import stat
import struct

import numpy as np

from locust_tpu.core import bytes_ops
from locust_tpu.core.kv import KVBatch

# Packed binary KV intermediate ("LKVB" v1).  Layout, all little-endian:
#   0   4  magic b"LKVB"
#   4   1  version (1)
#   5   1  flags (0)
#   6   2  reserved (0)
#   8   4  count (u32)
#  12   4  key-blob length (u32)
#  16      u16[count] key lengths
#          key blob (concatenated raw key bytes)
#          i32[count] values
KVB_MAGIC = b"LKVB"
KVB_VERSION = 1
_KVB_HEADER = struct.Struct("<4sBBHII")

INTERMEDIATE_FORMATS = ("tsv", "bin")


def write_records(path: str, blocks) -> int:
    """Write sorted record blocks (``uint8`` arrays, in order) to ``path``
    in one pass, each as it arrives; returns the bytes written.  The whole
    data set goes OUT, where every other sink prints a table.

    An OUT that is already there is written over IN PLACE and cut to what
    was written at the end, also where a block raises: a job that writes
    over its last output fills the pages the file already holds, where
    truncating first frees every one of them and the write then allocates
    each anew (on a v5e host, 3.2 GB: the write of a new file 1.7-2.2 s
    by the host's memory state, PERF.md section 6, PR 39).  The file ends
    at the last byte written either way."""
    from locust_tpu import obs

    written = 0
    with open(os.open(path, os.O_WRONLY | os.O_CREAT, 0o666), "wb") as f:
        try:
            for block in blocks:
                with obs.span("sort.write", bytes=block.nbytes):
                    f.write(memoryview(block))
                written += block.nbytes
        finally:
            f.flush()
            st = os.fstat(f.fileno())
            if stat.S_ISREG(st.st_mode) and st.st_size != written:
                f.truncate(written)
    obs.metric_inc("sort.bytes_out", written)
    return written


def write_tsv(pairs: list[tuple[bytes, int]], path: str) -> None:
    """Write live (key, value) pairs as ``key\\tvalue`` lines."""
    with open(path, "wb") as f:
        for k, v in pairs:
            f.write(k + b"\t" + str(int(v)).encode() + b"\n")


def write_kvbin(pairs: list[tuple[bytes, int]], path: str) -> None:
    """Write live (key, value) pairs in the packed binary KV format."""
    for k, _ in pairs:
        if len(k) > 0xFFFF:
            raise ValueError(
                f"key of {len(k)} bytes exceeds the u16 length field"
            )
    lens = np.fromiter((len(k) for k, _ in pairs), np.uint16, len(pairs))
    values = np.fromiter((int(v) for _, v in pairs), np.int64, len(pairs))
    if len(values) and not (
        values.min() >= -(2**31) and values.max() < 2**31
    ):
        raise OverflowError(f"value outside int32 in {path!r}")
    blob = b"".join(k for k, _ in pairs)
    with open(path, "wb") as f:
        f.write(
            _KVB_HEADER.pack(KVB_MAGIC, KVB_VERSION, 0, 0, len(pairs), len(blob))
        )
        f.write(lens.astype("<u2").tobytes())
        f.write(blob)
        f.write(values.astype("<i4").tobytes())


def read_kvbin(path: str, key_width: int) -> tuple[np.ndarray, np.ndarray]:
    """Packed binary KV -> (padded key rows, int32 values).

    Same output contract as ``read_tsv`` (keys truncated to ``key_width``,
    NUL-padded uint8 rows) so the reduce stage is format-blind.  Any
    structural inconsistency raises ValueError — a truncated or corrupted
    file must never silently yield fewer/garbled pairs (the distributor
    additionally sha256-verifies end to end before this runs).
    """
    with open(path, "rb") as f:
        data = f.read()
    if len(data) < _KVB_HEADER.size:
        raise ValueError(f"{path!r}: truncated KVB header")
    magic, version, _flags, _resv, count, blob_len = _KVB_HEADER.unpack(
        data[: _KVB_HEADER.size]
    )
    if magic != KVB_MAGIC:
        raise ValueError(f"{path!r}: bad KVB magic {magic!r}")
    if version != KVB_VERSION:
        raise ValueError(f"{path!r}: unsupported KVB version {version}")
    want = _KVB_HEADER.size + 2 * count + blob_len + 4 * count
    if len(data) != want:
        raise ValueError(
            f"{path!r}: KVB size mismatch (have {len(data)}B, header "
            f"implies {want}B)"
        )
    off = _KVB_HEADER.size
    lens = np.frombuffer(data, "<u2", count, off).astype(np.int64)
    off += 2 * count
    if int(lens.sum()) != blob_len:
        raise ValueError(f"{path!r}: KVB key lengths do not sum to the blob")
    blob = np.frombuffer(data, np.uint8, blob_len, off)
    off += blob_len
    values = np.frombuffer(data, "<i4", count, off).astype(np.int32)
    rows = np.zeros((count, key_width), np.uint8)
    if count:
        # Vectorized scatter: byte i of the blob lands at (its key's row,
        # its offset within the key), dropped when past key_width.
        starts = np.concatenate(([0], np.cumsum(lens)[:-1]))
        row_of = np.repeat(np.arange(count), lens)
        col_of = np.arange(blob_len) - np.repeat(starts, lens)
        keep = col_of < key_width
        rows[row_of[keep], col_of[keep]] = blob[keep]
    return rows, values


def is_kvbin(path: str) -> bool:
    with open(path, "rb") as f:
        return f.read(len(KVB_MAGIC)) == KVB_MAGIC


def write_intermediate(
    pairs: list[tuple[bytes, int]], path: str, fmt: str = "tsv"
) -> None:
    if fmt not in INTERMEDIATE_FORMATS:
        raise ValueError(f"unknown intermediate format {fmt!r}")
    (write_kvbin if fmt == "bin" else write_tsv)(pairs, path)


def read_intermediate(
    path: str, key_width: int, use_native: bool = True
) -> tuple[np.ndarray, np.ndarray]:
    """Format-sniffing read: packed binary KV by magic, else TSV."""
    if is_kvbin(path):
        return read_kvbin(path, key_width)
    return read_tsv(path, key_width, use_native=use_native)


def read_tsv(
    path: str, key_width: int, use_native: bool = True
) -> tuple[np.ndarray, np.ndarray]:
    """Parse ``key\\tvalue`` TSV -> (padded key rows, int32 values).

    Split on the FIRST tab like the reference's parser (main.cu:84-97);
    tolerate reference-style trailing spaces in keys (Q5) and blank lines.
    A native streaming parser (native/ingest.cpp ``ingest_read_tsv``)
    handles multi-GB intermediates; this Python loop is the always-
    available fallback and the semantic reference.
    """
    if use_native and key_width <= 256:
        try:
            from locust_tpu.io import native_ingest

            return native_ingest.read_tsv(path, key_width)
        except (ImportError, OSError):
            pass
    import re

    # The strict value grammar (shared with the native parser): optional
    # ' '/'\t'/'\r' padding, sign, digits — nothing else.  int(b"1_2") or
    # form-feed padding would be accepted by bare int() but are malformed
    # TSV rows; both parsers must agree row-for-row or key/value alignment
    # would depend on which path ran.  Values beyond int32 raise (a wrap
    # would silently corrupt counts); fields > 63 bytes are malformed.
    val_re = re.compile(rb"[ \t\r]*([+-]?[0-9]+)[ \t\r]*\Z")

    keys: list[bytes] = []
    values: list[int] = []
    with open(path, "rb") as f:
        for line in f:
            line = line.rstrip(b"\n").rstrip(b"\r")
            if not line:
                continue
            key, _, val = line.partition(b"\t")
            key = key.rstrip(b" ")  # reference writes "key \t..." (Q5)
            if not key:
                continue
            m = val_re.fullmatch(val) if len(val) <= 63 else None
            if m is None:
                continue  # malformed row: skip, like the reference's atoi-0 rows
            v = int(m.group(1))
            if not (-(2**31) <= v < 2**31):
                raise OverflowError(
                    f"TSV value {v} in {path!r} does not fit int32"
                )
            values.append(v)
            keys.append(key)
    return bytes_ops.strings_to_rows(keys, key_width), np.asarray(
        values, dtype=np.int32
    )


def fingerprint_corpus(rows: np.ndarray, **extra) -> str:
    """Resume-identity string for a checkpointed run over ``rows``.

    Digests the corpus CONTENT, not just its shape — editing the corpus
    without changing the line count must not resume from a stale snapshot
    (round-1 advisor finding).  ``extra`` carries the pipeline identity
    (config repr, combine, mesh, ...); one shared recipe so the engine and
    the distributed runner can never drift apart.
    """
    import hashlib
    import json

    return json.dumps(
        {
            "n_rows": int(rows.shape[0]),
            "digest": hashlib.sha256(
                np.ascontiguousarray(rows).tobytes()
            ).hexdigest(),
            **extra,
        },
        sort_keys=True,
    )


def write_npz(batch: KVBatch, path: str) -> None:
    """Binary shard checkpoint: the packed device representation as-is."""
    np.savez_compressed(
        path,
        key_lanes=np.asarray(batch.key_lanes),
        values=np.asarray(batch.values),
        valid=np.asarray(batch.valid),
    )


def read_npz(path: str) -> KVBatch:
    import jax.numpy as jnp

    with np.load(path) as z:
        return KVBatch(
            key_lanes=jnp.asarray(z["key_lanes"]),
            values=jnp.asarray(z["values"]),
            valid=jnp.asarray(z["valid"]),
        )
