"""Reduce stage: segment boundaries + segment combine.

The reference reduces in three device phases (reference
MapReduce/src/main.cu:161-238,447-465): ``kernFindUniqBool`` marks rows whose
key differs from the left neighbor, ``thrust::partition`` compacts the
boundary markers, and ``kernGetCount`` takes adjacent differences of boundary
indices to recover per-key counts.  This module is that construction,
generalised from counts to sums:

    boundary_i = valid_i & (i == 0 | key_i != key_{i-1})
    before_i   = sum of the live values of rows 0 .. i-1      (int32, wraps)
    start_j    = index of the j-th boundary row, n past the last one
    combined_j = before[start_{j+1}] - before[start_j]

The reference's difference of INDICES only counts because every value is 1;
a difference of the running sum is exact for any int32 values (modulo 2^32,
so bit for bit what ``jax.ops.segment_sum`` gives) and ``count`` is the same
difference over ones.  Compacting the starts is one narrow ``lax.sort`` that
carries ``before`` as its payload (``_at_segment_starts``): nothing scatters
or gathers over the ``n`` input rows.  ``min``/``max`` are no differences of
anything and keep a ``jax.ops.segment_*`` for their values.

Input must be key-sorted with valid rows first (ops/process_stage.py), the
same precondition the reference's reduce has — and which its distributed mode
silently violates (SURVEY.md Q6); our distributed path re-sorts after the
shuffle instead.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from locust_tpu.core.kv import KVBatch

# Monoid combiners available to reduce_fn. "count" treats every value as 1
# (the reference's WordCount semantics even if upstream emitted other values).
COMBINERS = ("sum", "min", "max", "count")


def normalize_combine(map_fn, combine: str):
    """Lower "count" to an associative form for MULTI-LEVEL engines.

    "count" is not a monoid over its own outputs: merging two per-key
    counts must SUM them, while a second ``segment_reduce(..., "count")``
    would count table ROWS — every engine that folds partial tables
    (block accumulator, cross-round shard carry, cross-slice combine)
    would return the number of partials holding the key, not the count.
    The associative equivalent is exact: emit value 1 at the leaves and
    sum at every level.  Returns ``(map_fn', combine')``; identity for
    the genuinely associative combiners.  Single-level uses (one
    ``segment_reduce`` over raw emits, e.g. the inverted index's postings
    counts) keep calling "count" directly.
    """
    if combine != "count":
        return map_fn, combine

    def count_map(lines, cfg, _base=map_fn):
        kv, overflow = _base(lines, cfg)
        return (
            KVBatch(
                key_lanes=kv.key_lanes,
                values=jnp.ones_like(kv.values),
                valid=kv.valid,
            ),
            overflow,
        )

    count_map.__name__ = f"count_of_{getattr(map_fn, '__name__', 'map_fn')}"
    return count_map, "sum"


def combine_scatters(combine: str) -> int:
    """Scatters over the INPUT rows that ``segment_reduce_into`` issues.

    0 for ``sum``/``count`` (a running sum carried to the compacted segment
    starts), 1 for ``min``/``max`` (their values keep ``jax.ops.segment_*``).
    The engine records it once as the gauge ``engine.combine_scatters``.
    """
    if combine not in COMBINERS:
        raise ValueError(f"combine must be one of {COMBINERS}, got {combine!r}")
    return 0 if combine in ("sum", "count") else 1


def _at_segment_starts(
    boundary: jax.Array, out_size: int, carried: jax.Array | None = None, fill=0
) -> tuple[jax.Array, jax.Array | None]:
    """Row index of the first ``out_size + 1`` set flags, in order — ``n``
    after the last — and ``carried`` read at those rows (``fill`` after the
    last).

    The compaction is ONE narrow sort: a flagged row's key is its index,
    every other row's its index plus ``n``, so the head of the sorted keys is
    the flagged indices in order and whatever follows is at least ``n``.
    ``carried`` rides the sort as its second operand, so reading it at the
    starts is no gather.  Slot ``j + 1`` is always one past segment ``j``'s
    last row: the next segment's first row, or ``n``.
    """
    n = boundary.shape[0]
    pos = jnp.arange(n, dtype=jnp.uint32)
    operands = [jnp.where(boundary, pos, pos + jnp.uint32(n))]
    fills = [n]
    if carried is not None:
        operands.append(jnp.where(boundary, carried, fill))
        fills.append(fill)
    # Every key is distinct, so stability would buy nothing: on a TPU it is
    # a third operand through the sort (half again its time, twice its compile).
    ordered = jax.lax.sort(operands, num_keys=1, is_stable=False)
    heads = [x[: out_size + 1] for x in ordered]
    short = out_size + 1 - n
    if short > 0:
        heads = [
            jnp.concatenate([h, jnp.full((short,), f, h.dtype)])
            for h, f in zip(heads, fills)
        ]
    start = jnp.minimum(heads[0], jnp.uint32(n)).astype(jnp.int32)
    return start, (heads[1] if carried is not None else None)


def segment_reduce_into(
    batch: KVBatch, out_size: int, combine: str = "sum"
) -> tuple[KVBatch, jax.Array]:
    """Segment-combine a key-grouped batch into a compact ``out_size`` table.

    Returns ``(table, num_segments)`` where ``table`` holds the first
    ``out_size`` segments (in input order) and ``num_segments`` is the TRUE
    distinct-key count (may exceed ``out_size`` — the caller's truncation
    signal).

    ``sum`` and ``count`` issue no scatter and no gather over the ``n`` input
    rows: an int32 running sum of the live values rides the two-operand sort
    that compacts the segment starts (``_at_segment_starts``), and a
    segment's total is the running sum before the NEXT start minus the one
    before its own — exact modulo 2^32, so a running sum that wraps still
    gives every segment the ``segment_sum`` result bit for bit.  Invalid rows
    add 0 to the running sum, wherever they lie.  ``min`` and ``max`` have no
    such difference and keep ``jax.ops.segment_*`` for the values.  What is
    left touches ``out_size`` rows: the key-row gather.
    """
    scatters = combine_scatters(combine)
    lanes, values, valid = batch.key_lanes, batch.values, batch.valid
    n = lanes.shape[0]

    prev = jnp.roll(lanes, 1, axis=0)
    neq = jnp.any(lanes != prev, axis=-1)
    first = jnp.arange(n) == 0
    boundary = valid & (first | neq)                        # [N]
    num_segments = jnp.sum(boundary.astype(jnp.int32))

    if not scatters:
        ones = jnp.ones_like(values)
        live = jnp.where(valid, ones if combine == "count" else values, 0)
        running = jnp.cumsum(live)                          # through row i
        start, before = _at_segment_starts(
            boundary, out_size, running - live, fill=running[-1]
        )
        combined = before[1:] - before[:-1]
    else:
        start, _ = _at_segment_starts(boundary, out_size)
        seg = jnp.cumsum(boundary.astype(jnp.int32)) - 1
        # Segments beyond out_size and invalid rows all fold into the dump slot.
        ids = jnp.where(valid, jnp.minimum(seg, out_size), out_size)
        scatter = jax.ops.segment_min if combine == "min" else jax.ops.segment_max
        combined = scatter(values, ids, num_segments=out_size + 1)[:out_size]

    out_valid = jnp.arange(out_size, dtype=jnp.int32) < num_segments
    safe_start = jnp.where(out_valid, start[:out_size], 0)
    out_lanes = lanes[safe_start] * out_valid[:, None].astype(lanes.dtype)
    return (
        KVBatch(
            key_lanes=out_lanes,
            values=jnp.where(out_valid, combined, 0),
            valid=out_valid,
        ),
        num_segments,
    )


def segment_reduce(batch: KVBatch, combine: str = "sum") -> KVBatch:
    """Combine values of equal adjacent keys; output keeps input key order.

    Returns a same-capacity KVBatch whose first ``num_segments`` rows are the
    unique keys (in order) with combined values; the tail is invalid.
    Same-capacity special case of ``segment_reduce_into``.
    """
    return segment_reduce_into(batch, batch.size, combine)[0]
