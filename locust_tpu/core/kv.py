"""KV data model: fixed-width key/value batches as JAX pytrees.

Replaces the reference's POD structs-of-char-arrays —
``KeyValuePair{char key[100]; char value[100]; int ind}`` and
``KeyIntValuePair{char key[30]; int value; int count}``
(reference MapReduce/src/KeyValue.h:6-18) — with structure-of-arrays
tensors: keys live as packed big-endian uint32 lanes (see core/packing.py),
values as int32, and validity as an explicit bool mask instead of the
empty-string sentinel that the reference's compaction predicates test
(KeyIntValueNotEmpty, KeyValue.h:79-84).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from locust_tpu.core import bytes_ops, packing


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class KVBatch:
    """A batch of (key, value) emits.

    Attributes:
      key_lanes: uint32 ``[N, L]`` — big-endian packed key bytes.
      values: int32 ``[N]``.
      valid: bool ``[N]`` — live entries; replaces empty-key sentinels.
    """

    key_lanes: jax.Array
    values: jax.Array
    valid: jax.Array

    @property
    def size(self) -> int:
        return self.key_lanes.shape[0]

    @property
    def num_lanes(self) -> int:
        return self.key_lanes.shape[-1]

    def num_valid(self) -> jax.Array:
        return jnp.sum(self.valid.astype(jnp.int32))

    def keys_bytes(self) -> jax.Array:
        """uint8 ``[N, 4L]`` NUL-padded key bytes."""
        return packing.unpack_keys(self.key_lanes)

    @classmethod
    def from_bytes(cls, keys: jax.Array, values: jax.Array, valid: jax.Array) -> "KVBatch":
        return cls(
            key_lanes=packing.pack_keys(keys),
            values=values.astype(jnp.int32),
            valid=valid.astype(bool),
        )

    @classmethod
    def concat(cls, *batches: "KVBatch") -> "KVBatch":
        return cls(
            key_lanes=jnp.concatenate([b.key_lanes for b in batches]),
            values=jnp.concatenate([b.values for b in batches]),
            valid=jnp.concatenate([b.valid for b in batches]),
        )

    @classmethod
    def empty(cls, n: int, key_lanes: int) -> "KVBatch":
        return cls(
            key_lanes=jnp.zeros((n, key_lanes), dtype=jnp.uint32),
            values=jnp.zeros((n,), dtype=jnp.int32),
            valid=jnp.zeros((n,), dtype=bool),
        )

    def to_host(self) -> "KVBatch":
        """The batch with numpy leaves: ONE device_get for all three (a
        single round trip — on remote TPU links per-array fetches each
        pay full latency).  Leaves that are on the host already (a table
        gathered from a mesh's shards) come back as they are."""
        return KVBatch(*jax.device_get(
            (self.key_lanes, self.values, self.valid)
        ))

    def host_rows(self, sort: bool = False) -> "HostRows":
        """The live entries of a batch that is ON THE HOST (``to_host``)
        as two arrays, in table order or (``sort``) ordered by key in
        numpy — byte order of the NUL-padded rows, which is the keys' own
        unless a key holds a NUL inside.

        Lane unpacking in numpy (big-endian reinterpret) over whole
        arrays: O(live entries), not O(table capacity), and no Python
        object a row.
        """
        valid = np.asarray(self.valid)
        live_lanes = np.asarray(self.key_lanes)[valid]
        live_values = np.asarray(self.values)[valid]
        # big-endian uint32 lanes -> the original NUL-padded key bytes
        n_live, n_lanes = live_lanes.shape
        keys = live_lanes.astype(">u4").view(np.uint8).reshape(n_live, n_lanes * 4)
        if sort and n_live:
            order = np.argsort(keys.view(f"S{n_lanes * 4}").ravel(), kind="stable")
            keys, live_values = keys[order], live_values[order]
        return HostRows(keys, live_values)

    def host_pairs(self, sort: bool = False) -> list[tuple[bytes, int]]:
        """``host_rows`` decoded to (key bytes, value) pairs — a ``bytes``,
        an ``int`` and a tuple a row — for the callers that merge dicts or
        write LKVB (serve, the distributor's workers, the plan evaluator's
        ``value``, ``apps/``, the staged map's dump).  Where a key holds a
        NUL inside, the caller's ``sorted`` has the last word on the order
        (linear on a list that is already in order).  The CLI's printed
        table takes ``host_rows`` and makes no pairs (engine
        ``finalize_host_rows``)."""
        return self.host_rows(sort).pairs()

    def to_host_pairs(self, sort: bool = False) -> list[tuple[bytes, int]]:
        """Host-side: the fetch (``to_host``) and the decode
        (``host_pairs``) in one call."""
        return self.to_host().host_pairs(sort)


@dataclasses.dataclass
class HostRows:
    """A table's live rows on the host, as arrays (``KVBatch.host_rows``).

    Attributes:
      keys: uint8 ``[n, key_width]`` — NUL-padded key bytes.
      values: int32 ``[n]``.
    """

    keys: np.ndarray
    values: np.ndarray

    def __len__(self) -> int:
        return self.keys.shape[0]

    def __getitem__(self, rows: slice) -> "HostRows":
        return HostRows(self.keys[rows], self.values[rows])

    def pairs(self) -> list[tuple[bytes, int]]:
        return list(zip(bytes_ops.rows_to_strings(self.keys), self.values.tolist()))


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class RecordBatch:
    """Fixed-width binary records, WHOLE: the value is the record, not an
    int32 beside the key (TeraSort's 100-byte record is 10 key bytes and
    90 of payload; every byte of input comes back out).

    Attributes:
      words: uint32 ``[N, W]`` — a record's bytes as the file holds them,
        four to a word, little-endian (word j = bytes 4j..4j+3, byte 4j
        lowest), zero-padded to a whole word: what ``ndarray.view`` gives
        on the host with no pass over the data.
    """

    words: jax.Array

    @property
    def size(self) -> int:
        return self.words.shape[0]

    @staticmethod
    def num_words(record_bytes: int) -> int:
        return -(-record_bytes // 4)

    @classmethod
    def empty(cls, n: int, record_bytes: int) -> "RecordBatch":
        return cls(jnp.zeros((n, cls.num_words(record_bytes)), jnp.uint32))

    def key_lanes(self, key_bytes: int) -> tuple[jax.Array, ...]:
        """The key cut from the records: its first ``key_bytes`` bytes as
        big-endian uint32 lanes, most significant first, the last lane
        zero-padded — tuple order on them IS unsigned byte order on the
        key (core/packing.py), and a padding byte cannot reorder keys of
        one width."""
        lanes = []
        for j in range(-(-key_bytes // 4)):
            w = self.words[:, j]
            lane = ((w << 24) | ((w & 0xFF00) << 8)
                    | ((w >> 8) & 0xFF00) | (w >> 24))
            keep = min(4, key_bytes - 4 * j)
            if keep < 4:
                lane = lane & jnp.uint32((0xFFFFFFFF << (8 * (4 - keep))) & 0xFFFFFFFF)
            lanes.append(lane)
        return tuple(lanes)

    def take(self, rows: jax.Array) -> "RecordBatch":
        """The records at ``rows``, in that order: the permutation of the
        payload by a sorted index."""
        return RecordBatch(self.words[rows])


# A table that grows with what it sees — the default path's one table
# (engine.timed_run) and the mesh's hash shards (parallel/shuffle.py) —
# grows by powers of this factor, to the first capacity that holds the
# count: a million-key job ends at 16 times its start, a handful of
# capacities (and compiled programs) whatever the vocabulary.
TABLE_GROWTH = 2


def rows_to_hold(rows: int, distinct: int) -> int:
    """The first capacity, ``TABLE_GROWTH``-fold steps up from ``rows``,
    that holds ``distinct`` keys (``rows`` itself if it does).  Growing
    AHEAD of work that, adding what the last stretch added, would pass
    the table is the same rule asked of ``distinct + added``."""
    while rows < distinct:
        rows *= TABLE_GROWTH
    return rows


def grow_table(table: KVBatch, rows: int) -> KVBatch:
    """``table`` with empty rows appended up to ``rows`` — every live row
    kept where it was, so a fold that re-sorts or rebuilds (every fold of
    this repo does) takes the grown table as it took the old one."""
    return KVBatch.concat(
        table, KVBatch.empty(rows - table.size, table.num_lanes)
    )
