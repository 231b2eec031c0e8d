"""Process stage: compaction + key-grouping sort in one ``lax.sort``.

The reference runs two device passes: ``thrust::partition`` to push empty
emit slots to the tail (reference MapReduce/src/main.cu:411) then
``thrust::sort`` with the byte-loop ``KIVComparator`` over the live prefix
(main.cu:414-415, KeyValue.h:20-33).  That stage is 94% of its GPU runtime
(reference README.md:72-80) and is the headline perf target (BASELINE.json).

TPU-native formulations, selected by ``EngineConfig.sort_mode`` (also
"hashp2"/"hashp1" = payload-carry at 2/1 hash key operands, and "hasht" =
the fold-level SORT-FREE hash-table aggregation (ops/hash_table.py; this
module serves its grouping-interface consumers via the hashp1
formulation); see the variant functions below):

* **"lex"** — ONE multi-operand ``jax.lax.sort`` whose most-significant key
  is the inverted validity bit and whose remaining keys are the big-endian
  uint32 key lanes.  Ascending sort yields "valid entries first, in
  lexicographic key order": partition and sort fused into a single XLA sort,
  integer lane compares instead of a data-dependent byte loop.

* **"hash"** (default) — sort by ``(invalid, hash64(key))`` with only an
  index payload, then gather rows into place.  3 sort keys + 1 payload
  instead of 1+key_lanes keys (speed not measured on this machine).
  Equal keys still land adjacent
  (equal keys => equal hash), which is the only property the downstream
  segment reduce needs; it compares FULL key lanes at segment boundaries, so
  hash collisions between distinct keys cannot merge counts — the worst case
  (a full 64-bit collision interleaving two keys, ~n^2/2^64) duplicates a
  table row, which the host-side finalize re-merges.  Device order is hash
  order; lexicographic output order is restored host-side on a table that is
  orders of magnitude smaller than the emit stream.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from locust_tpu.config import HASHT_FAMILY
from locust_tpu.core import packing
from locust_tpu.core.kv import KVBatch


def sort_and_compact(batch: KVBatch, mode: str = "hash") -> KVBatch:
    """Group equal keys adjacently with valid rows first, carrying values.

    Equivalent of partition+sort (main.cu:411-415) as one fused sort.
    ``mode`` as in ``EngineConfig.sort_mode``.
    """
    if mode == "hash":
        return _hash_sort(batch)
    if mode == "hashp2":
        return _hashp2_sort(batch)
    if mode == "hashp1":
        return _hashp1_sort(batch)
    if mode in HASHT_FAMILY:
        # The hasht family is a FOLD-level strategy
        # (ops/hash_table.aggregate_exact — "hasht-mxu" only changes the
        # fold's combine-scatter spelling; wired in engine.fold_block and
        # the mesh engines' merge / combiner sites); consumers of the
        # grouping interface proper (timed_run's split stages, the staged
        # CLI) get the stock formulation with the same key-grouping
        # guarantees.
        return _hashp1_sort(batch)
    if mode == "lex":
        return _lex_sort(batch)
    raise ValueError(f"unknown sort mode {mode!r}")


def order_by_lanes(lanes) -> tuple[tuple[jax.Array, ...], jax.Array]:
    """Order rows by uint32 key lanes (a sequence of ``[N]`` arrays, most
    significant first) and carry the permutation: ``(sorted lanes,
    perm)`` with ``perm[i]`` the input row that comes ``i``-th.

    ONE ``lax.sort`` whose last key is the row index: equal keys keep
    their input order and no two rows compare equal, so the result is
    defined to the row and the sort may be the unstable one (on a v5e it
    compiles in half the time and needs one operand less, PERF.md section
    6, PR 32).  Whatever rides along — an int32 value, a 100-byte record
    — is gathered by ``perm`` afterwards and never widens the sort.
    """
    lanes = tuple(lanes)
    idx = jnp.arange(lanes[0].shape[0], dtype=jnp.int32)
    out = jax.lax.sort((*lanes, idx), num_keys=len(lanes) + 1, is_stable=False)
    return out[:-1], out[-1]


def _lex_sort(batch: KVBatch) -> KVBatch:
    lanes = batch.key_lanes
    invalid = (~batch.valid).astype(jnp.uint32)            # 0 = valid, first
    (sorted_invalid, *sorted_lanes), perm = order_by_lanes(
        (invalid, *(lanes[:, i] for i in range(lanes.shape[-1])))
    )
    return KVBatch(
        key_lanes=jnp.stack(sorted_lanes, axis=-1),
        values=batch.values[perm],
        valid=sorted_invalid == 0,
    )


def _hash_sort(batch: KVBatch) -> KVBatch:
    lanes, values, valid = batch.key_lanes, batch.values, batch.valid
    n = lanes.shape[0]
    invalid = (~valid).astype(jnp.uint32)                  # 0 = valid, first
    h1, h2 = packing.hash_pair(lanes)
    idx = jnp.arange(n, dtype=jnp.int32)
    _, _, _, sidx = jax.lax.sort((invalid, h1, h2, idx), num_keys=3)
    return KVBatch(
        key_lanes=lanes[sidx], values=values[sidx], valid=valid[sidx]
    )


def _hashp2_sort(batch: KVBatch) -> KVBatch:
    """2 sort keys + payload-carry: validity folded into the primary hash.

    The key lanes and values travel through ``lax.sort`` as payload
    operands (no post-sort gather, unlike "hash"), and the invalid flag
    rides in the top bit of a 31-bit primary hash with the full h2 as
    tiebreaker — one key operand fewer than "hash".  Valid rows keep
    ``h1 >> 1`` (top bit 0, < 0x80000000), invalid rows get 0xFFFFFFFF,
    so ascending order is
    still valid-first and validity is reconstructed from the sorted key.
    Grouping tiebreak is 31+32 hash bits; as everywhere, the segment
    reduce compares full key lanes at boundaries so collisions only
    duplicate a table row (re-merged downstream).  The TPU default and
    the mode every benchmark cell runs (PERF.md section 5:
    ``sort_dev_ms.tput``).
    """
    lanes, values, valid = batch.key_lanes, batch.values, batch.valid
    n_lanes = lanes.shape[-1]
    h1, h2 = packing.hash_pair(lanes)
    folded = jnp.where(valid, h1 >> 1, jnp.uint32(0xFFFFFFFF))
    out = jax.lax.sort(
        (folded, h2, *(lanes[:, i] for i in range(n_lanes)), values),
        num_keys=2,
    )
    return KVBatch(
        key_lanes=jnp.stack(out[2 : 2 + n_lanes], axis=-1),
        values=out[2 + n_lanes],
        valid=out[0] < jnp.uint32(0x80000000),
    )


def _hashp1_sort(batch: KVBatch) -> KVBatch:
    """1 sort key + payload-carry: the minimum-traffic lax.sort formulation.

    One step further down the ladder from "hashp2": the single folded
    31-bit key (``_folded_key``: validity in the top bit) with NO h2
    tiebreaker, rows riding as payloads — 6 uint32 operands per pass vs
    hashp2's 7, i.e. ~14% less HBM traffic through the stage the whole
    pipeline is bottlenecked on.  Collision story is ``_folded_key``'s:
    ~C(n,2)/2^31 colliding pairs interleave within a hash run, the
    segment reduce's full-lane boundary compare splits them into duplicate table rows, and the next
    fold or the host finalize re-merges those — never a wrong count.
    Not measured on the current machine.
    """
    lanes, values = batch.key_lanes, batch.values
    n_lanes = lanes.shape[-1]
    out = jax.lax.sort(
        (_folded_key(batch), *(lanes[:, i] for i in range(n_lanes)), values),
        num_keys=1,
    )
    return KVBatch(
        key_lanes=jnp.stack(out[1 : 1 + n_lanes], axis=-1),
        values=out[1 + n_lanes],
        valid=out[0] < jnp.uint32(0x80000000),
    )


def _folded_key(batch: KVBatch) -> jax.Array:
    """ONE uint32 sort key: 31 hash bits + validity in the top bit.

    Invalid rows get the max key, so ascending order is valid-first —
    partition and grouping in a single-operand sort.  Collisions between
    distinct keys (~n^2/2^31 per sort) interleave within a hash run; the
    downstream segment reduce compares FULL key lanes at boundaries, so
    the worst case is a duplicated table row which the next fold (same
    hash -> adjacent again) or the host finalize re-merges — the same
    safety argument as the 64-bit "hash" mode at half the sort-key
    bandwidth.
    """
    h1, _ = packing.hash_pair(batch.key_lanes)
    return jnp.where(batch.valid, h1 >> 1, jnp.uint32(0xFFFFFFFF))
