"""Sort-free Process+Reduce: multi-probe hash-table aggregation.

The reference's Process stage exists to group equal keys so a segment
pass can total them (thrust sort at reference MapReduce/src/main.cu:414-415,
94% of its GPU runtime) — but per-key totals do not inherently need a
sort.  This module aggregates an emit batch directly into a fixed-size
open-addressed hash table with XLA scatters:

  per probe round (double hashing, ``slot_p = (h1 + p*(h2|1)) % T``):
    1. rows COMPETE for their slot by scatter-min over a 31-bit folded
       hash (the winner per slot is deterministic: smallest folded);
    2. winners whose slot is EMPTY write their full key lanes
       (same-key writers write identical bytes, so duplicate-index
       write order cannot matter; two DISTINCT keys can both "win" only
       on a 31-bit folded-hash collision, and XLA does not promise the
       duplicate-index row write is atomic — the slot could then hold an
       interleaved chimera matching neither writer, so step 3's matched
       flag is what ultimately marks a slot used);
    3. every unresolved row gathers its slot's stored lanes and compares
       ALL lanes — a row is resolved only by an exact full-key match, so
       hash collisions can never merge distinct keys (same invariant as
       the sort modes' boundary compare, process_stage.py);
    4. resolved rows scatter-combine their values into the slot
       (sum/min/max — the same normalized combiners as segment_reduce).

  Rows still unresolved after all rounds (probe exhaustion under high
  load, or a pathological folded-hash fight) are returned as a mask; the
  engine routes them through the EXACT stock sort+segment-reduce
  fallback (engine.py fold path), so the mode degrades to today's
  behavior rather than to a wrong answer.

Traffic: ~4 rounds x ~11 row-sized gather/scatter sweeps vs the
incumbent sort's ~21 passes x 6 operands x read+write — less HBM
movement IF the backend's duplicate-index scatter is not serialized
(the fold is not measured on this machine).  The value
combine has a second spelling: a one-hot bf16 contraction on the
systolic MXU (``mxu_scatter_add``), selected per fold by
``scatter_impl`` / engine sort mode "hasht-mxu" (config.HASHT_FAMILY).
Both spellings produce BIT-identical tables.

Empty-slot sentinel: lane 0 == 0.  A valid emit's key starts with a
non-delimiter, non-NUL byte packed big-endian into lane 0, so lane 0 of
any real key is >= 0x01000000; rows violating this (impossible via the
tokenizer, but cheap to guard) are simply left to the exact fallback.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from locust_tpu.config import (
    HASHT_FAMILY,
    HASHT_MXU_CHUNK,
    HASHT_PROBES as DEFAULT_PROBES,
    hasht_mxu_grid,
)
from locust_tpu.core import packing
from locust_tpu.core.kv import KVBatch

# How the value-combine scatter of the probe loop is spelled, keyed by the
# sort mode that selected this fold (config.HASHT_FAMILY):
#   "xla" — ``.at[slot].add`` duplicate-index scatter (the incumbent);
#   "mxu" — the same sum as one-hot bf16 contractions on the systolic MXU
#           (``mxu_scatter_add``).  Neither is measured on this machine.
# The claim (scatter-min over folded hashes) and key-lane writes stay XLA
# scatters under BOTH impls — the MXU speaks only +, and those steps are
# what make the fold exact, not what prices it.
SCATTER_IMPLS = ("xla", "mxu")


def scatter_impl_for(sort_mode: str) -> str:
    """The fold family's mode -> combine-scatter spelling map (the one
    place "hasht-mxu" is interpreted; engines pass sort_mode strings)."""
    return "mxu" if sort_mode == "hasht-mxu" else "xla"


def mxu_scatter_add(
    slot: jax.Array,
    values: jax.Array,
    mask: jax.Array,
    out_size: int,
    chunk: int | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Duplicate-index scatter-add spelled as one-hot MXU contractions.

    Returns ``(sums, hit)``: ``sums[t]`` is the int32 sum (mod 2^32 —
    BIT-identical to XLA's wrapping ``.at[t].add``) of ``values`` over
    masked rows with ``slot == t``, and ``hit[t]`` is True iff any masked
    row landed on ``t``.  Rows with ``mask`` False (or an out-of-grid
    slot) contribute nothing.

    Formulation: decompose ``slot = hi * t_lo + lo`` on the
    ``config.hasht_mxu_grid`` and accumulate
    ``hist[w, hi, lo] = sum_n W[n, w] * onehot_hi[n, hi] * onehot_lo[n, lo]``
    as ONE ``[t_hi * 5, n_chunk] x [n_chunk, t_lo]`` bf16 contraction per
    chunk.  Exactness, unlike a bf16 cast of raw values, is
    unconditional: the 5 weight planes are the value's four unsigned
    8-bit limbs plus the hit count — every operand entry is <= 255 and
    hence bf16-exact, per-chunk partials accumulate in fp32 where a
    slot's limb sum stays < 255 * chunk <= 2^24 (config.HASHT_MXU_CHUNK's
    asserted ceiling), partials then convert to uint32 and accumulate
    with wraparound, and the final limb recombination is mod-2^32
    arithmetic — the same ring int32 scatter-add lives in.

    The n axis is chunked (``lax.scan``) so the materialized one-hot
    operands stay ~``chunk * (5 * t_hi + t_lo) * 2`` bytes regardless of
    the fold's row count.
    """
    t_hi, t_lo = hasht_mxu_grid(out_size)
    n = slot.shape[0]
    chunk = HASHT_MXU_CHUNK if chunk is None else chunk
    if not 1 <= chunk <= 65536:
        # The SAME exactness ceiling config asserts of its constant:
        # a slot's per-chunk limb partial must stay < 255 * chunk <= 2^24
        # or the fp32 einsum accumulation rounds and the bit-identity
        # contract silently breaks for direct callers.
        raise ValueError(
            f"chunk must be in [1, 65536] (fp32 partial-sum exactness "
            f"bound 2^24/255), got {chunk}"
        )

    # 5 weight planes, all bf16-exact: value limbs 0..3 (unsigned view of
    # the int32 — the limb recombination below restores wrapping-sum
    # semantics for negative values too) + the hit count.
    w_u = jax.lax.bitcast_convert_type(
        values.astype(jnp.int32), jnp.uint32
    )
    w_u = jnp.where(mask, w_u, jnp.uint32(0))
    planes = [(w_u >> jnp.uint32(8 * b)) & jnp.uint32(0xFF) for b in range(4)]
    planes.append(mask.astype(jnp.uint32))
    weights = jnp.stack(planes, axis=-1).astype(jnp.bfloat16)   # [n, 5]
    s32 = slot.astype(jnp.int32)
    hi = s32 // t_lo
    lo = s32 % t_lo

    def hist_chunk(hi_c, lo_c, w_c):
        # One-hot rows land in their grid cell; a masked or out-of-grid
        # row produces an all-zero one-hot / zero weight either way.
        oh_hi = (
            hi_c[:, None] == jnp.arange(t_hi, dtype=jnp.int32)[None, :]
        ).astype(jnp.bfloat16)
        oh_lo = (
            lo_c[:, None] == jnp.arange(t_lo, dtype=jnp.int32)[None, :]
        ).astype(jnp.bfloat16)
        lhs = (oh_hi[:, :, None] * w_c[:, None, :]).reshape(
            hi_c.shape[0], t_hi * 5
        )
        part = jnp.einsum(
            "nm,nl->ml", lhs, oh_lo, preferred_element_type=jnp.float32
        ).reshape(t_hi, 5, t_lo)
        # fp32 partials are exact integers < 2^24 here; uint32 conversion
        # is therefore exact, and uint32 accumulation wraps mod 2^32.
        return part.astype(jnp.uint32)

    if n <= chunk:
        acc = hist_chunk(hi, lo, weights)
    else:
        n_chunks = -(-n // chunk)
        pad = n_chunks * chunk - n
        hi_p = jnp.pad(hi, (0, pad), constant_values=-1)  # off-grid: no-op
        lo_p = jnp.pad(lo, (0, pad), constant_values=-1)
        w_p = jnp.pad(weights, ((0, pad), (0, 0)))

        def body(carry, xs):
            h, l, w = xs
            return carry + hist_chunk(h, l, w), None

        acc, _ = jax.lax.scan(
            body,
            jnp.zeros((t_hi, 5, t_lo), jnp.uint32),
            (
                hi_p.reshape(n_chunks, chunk),
                lo_p.reshape(n_chunks, chunk),
                w_p.reshape(n_chunks, chunk, 5),
            ),
        )

    sums_u = (
        acc[:, 0]
        + (acc[:, 1] << jnp.uint32(8))
        + (acc[:, 2] << jnp.uint32(16))
        + (acc[:, 3] << jnp.uint32(24))
    )
    sums = jax.lax.bitcast_convert_type(
        sums_u.reshape(-1)[:out_size], jnp.int32
    )
    hit = acc[:, 4].reshape(-1)[:out_size] > 0
    return sums, hit

# DEFAULT_PROBES (config.HASHT_PROBES = 4): at a load factor of 0.09
# (~5.6k distinct in 65,536 slots) the expected unresolved
# fraction after 4 rounds is ~0.09^4 ≈ 7e-5 of KEYS — in practice zero,
# so the engine's fallback `lax.cond` almost never fires.

# Associative combiners only: "count" is rejected at the aggregate_exact
# gate (it is not a monoid over its own outputs — a mixed batch of raw
# emits and pre-aggregated table rows has no correct single-pass count);
# normalize_combine lowers it to emit-1 + "sum" before any fold.
_COMBINE_INIT = {"sum": 0, "min": 2**31 - 1, "max": -(2**31)}


def hash_aggregate(
    batch: KVBatch,
    out_size: int,
    combine: str = "sum",
    probes: int = DEFAULT_PROBES,
    table: KVBatch | None = None,
    scatter_impl: str = "xla",
) -> tuple[KVBatch, jax.Array, jax.Array]:
    """Aggregate ``batch`` into an ``out_size``-slot table without sorting.

    ``scatter_impl`` selects how step 4's value combine is spelled (see
    ``SCATTER_IMPLS``): "xla" is the duplicate-index scatter, "mxu" the
    one-hot contraction — tables are BIT-identical either way (the "mxu"
    sum is exact mod 2^32, the ring int32 scatter-add lives in).  "mxu"
    applies to combine="sum" only; min/max have no matmul spelling and
    keep the XLA scatter (trivially identical).  Steps 1-3 (claim, key
    write, full-lane verify) are unchanged under both impls.

    With ``table`` (a KVBatch of capacity ``out_size`` produced by a
    previous hasht fold), aggregation is INCREMENTAL: prior keys keep
    their slots and batch rows combine into them, so a fold's scatter
    traffic scales with the BLOCK, not table+block — the concat +
    full-table re-aggregation the sort modes pay per fold disappears.
    Slot stability across folds follows from the probe invariant: a key
    resolved at round r found every earlier slot of its sequence
    occupied, and slots never empty out, so later rows of that key walk
    the same sequence to the same slot.

    EXCEPTION — keys that entered the table via the exactness ladder's
    residual/full branches sit at slots OFF their probe sequence; later
    batch rows of such a key cannot match there and may claim a second
    slot (or re-residual).  That SPLITS the key's total across rows —
    still exact, because every consumer merges duplicate key rows with
    the combine op (``finalize_host_pairs``; the ladder's own ``full``
    branch and the sort-mode merges consolidate them too) — but the
    ``used``/distinct count then OVERCOUNTS, so capacity truncation
    stays conservative (may flag early, never silently drops).

    Returns ``(table, used_count, unresolved_mask)``:

    * ``table`` — KVBatch of capacity ``out_size``; used slots hold one
      distinct key each with its combined value (device order is slot
      order, like the sort modes' hash order — host finalize re-sorts);
    * ``used_count`` — number of occupied slots == distinct keys
      resolved (every resolved key occupies exactly ONE slot: all rows
      of a key share (h1, h2), hence the same probe sequence and the
      same resolution round);
    * ``unresolved_mask`` — [N] bool, rows the caller must still fold in
      exactly (engine.py routes them through sort+segment-reduce).
    """
    if combine not in _COMBINE_INIT:
        raise ValueError(f"combine must be one of {sorted(_COMBINE_INIT)}")
    if scatter_impl not in SCATTER_IMPLS:
        raise ValueError(
            f"scatter_impl must be one of {SCATTER_IMPLS}, got {scatter_impl!r}"
        )
    lanes, values, valid = batch.key_lanes, batch.values, batch.valid
    n_lanes = lanes.shape[-1]
    T = out_size

    h1, h2 = packing.hash_pair(lanes)
    folded = h1 >> 1                       # < 0x7FFFFFFF < the empty sentinel
    step = h2 | jnp.uint32(1)              # odd: full cycle when T is 2^k
    sentinel = jnp.uint32(0xFFFFFFFF)

    # Belt-and-braces: a "valid" row whose lane0 is 0 would alias the
    # empty-slot sentinel; leave such rows to the exact fallback.
    unresolved = valid & (lanes[:, 0] != 0)

    if table is None:
        stored_lanes = jnp.zeros((T + 1, n_lanes), jnp.uint32)  # T = dump
        acc = jnp.full((T + 1,), _COMBINE_INIT[combine], jnp.int32)
    else:
        if table.size != T:
            raise ValueError(
                f"incremental table capacity {table.size} != out_size {T}"
            )
        # Existing slots keep their keys/values; EMPTY slots must hold
        # the combine identity (table.values stores 0 there), and a
        # stored key in an invalid slot must not block claims — masked
        # to the empty sentinel pattern.
        stored_lanes = jnp.concatenate(
            [
                jnp.where(table.valid[:, None], table.key_lanes, 0),
                jnp.zeros((1, n_lanes), jnp.uint32),
            ]
        )
        acc = jnp.concatenate(
            [
                jnp.where(
                    table.valid, table.values,
                    jnp.int32(_COMBINE_INIT[combine]),
                ),
                jnp.full((1,), _COMBINE_INIT[combine], jnp.int32),
            ]
        )
    # A slot counts as used only once some row has FULL-KEY-matched it.
    # Written-but-never-matched slots are possible in exactly one case:
    # two distinct keys collide on the 31-bit folded hash, both win the
    # same empty slot in the same round, and the duplicate-index row
    # write interleaves per element (XLA leaves this unspecified) — the
    # stored bytes then match neither writer.  Without this flag such a
    # slot would surface as a phantom output row holding the combine
    # init; with it, the slot is excluded and both writers resolve via
    # later probes or the exact fallback ladder.  Slots carried in from
    # a previous incremental fold were matched when first inserted.
    if table is None:
        matched_slot = jnp.zeros((T + 1,), bool)
    else:
        matched_slot = jnp.concatenate(
            [table.valid, jnp.zeros((1,), bool)]
        )

    for p in range(probes):
        slot = ((h1 + jnp.uint32(p) * step) % jnp.uint32(T)).astype(jnp.int32)
        # 1. Compete: smallest folded hash wins the slot this round.
        claim = jnp.full((T,), sentinel).at[slot].min(
            jnp.where(unresolved, folded, sentinel), mode="drop"
        )
        won = unresolved & (claim[slot] == folded)
        # 2. Winners write their key into EMPTY slots (dump row for the
        #    rest keeps the scatter shape static).
        empty = stored_lanes[:T, 0] == 0
        writer = won & empty[slot]
        stored_lanes = stored_lanes.at[
            jnp.where(writer, slot, T)
        ].set(lanes, mode="drop")
        # 3. Resolve by FULL-key equality with whatever the slot holds
        #    (this round's winner, or an earlier round's occupant).
        match = unresolved & jnp.all(
            stored_lanes[slot] == lanes, axis=-1
        )
        # 4. Combine resolved values into the slot.  "mxu" + sum: the
        #    scatter-add and the matched-slot flag both come out of one
        #    one-hot contraction (mxu_scatter_add's value limbs + hit
        #    plane); otherwise the duplicate-index scatter with a dump
        #    row.  Identical tables by construction either way.
        if scatter_impl == "mxu" and combine == "sum":
            sums, hit = mxu_scatter_add(slot, values, match, T)
            acc = acc.at[:T].add(sums)
            matched_slot = matched_slot.at[:T].set(matched_slot[:T] | hit)
        else:
            vslot = jnp.where(match, slot, T)
            matched_slot = matched_slot.at[vslot].set(True, mode="drop")
            if combine == "sum":
                acc = acc.at[vslot].add(values, mode="drop")
            elif combine == "min":
                acc = acc.at[vslot].min(values, mode="drop")
            else:
                acc = acc.at[vslot].max(values, mode="drop")
        unresolved = unresolved & ~match

    used = (stored_lanes[:T, 0] != 0) & matched_slot[:T]
    table = KVBatch(
        key_lanes=stored_lanes[:T],
        values=jnp.where(used, acc[:T], 0),
        valid=used,
    )
    # Rows guarded out of the probe rounds (lane0 == 0, sentinel alias)
    # re-enter the returned mask: the CONTRACT is that everything not in
    # the table comes back as unresolved, so no caller path can lose
    # them silently.
    unresolved = unresolved | (valid & (lanes[:, 0] == 0))
    return table, jnp.sum(used.astype(jnp.int32)), unresolved


# Residual-buffer capacity for ``place_residual``: unresolved rows are
# compacted into this many slots and sorted there (a 4096-row sort is
# milliseconds).  More unresolved rows than this sends the engine to the
# full-sort fallback instead — with 4 probes at sane load factors that is
# astronomically rare, but the bound is what keeps the mode EXACT.
RESIDUAL_CAP = 4096


def place_residual(
    table: KVBatch,
    used: jax.Array,
    batch: KVBatch,
    unresolved: jax.Array,
    combine: str = "sum",
) -> tuple[KVBatch, jax.Array]:
    """Exactly fold ``unresolved`` rows of ``batch`` into ``table``.

    The cheap middle path between "all rows resolved" and the full-sort
    fallback: probe exhaustion strands only a handful of rows (a key that
    deterministically loses every probe round re-fails every fold, so
    this path is on the steady-state fold of real corpora), and sorting
    a RESIDUAL_CAP-row buffer costs milliseconds where re-sorting the
    whole (table + emits) batch would cost more than the sort mode this
    mode exists to beat.

    Caller guarantees ``sum(unresolved) <= RESIDUAL_CAP``.  Steps:

      1. cumsum-compact the unresolved rows into a RESIDUAL_CAP buffer;
      2. group+total the buffer with the stock sort + segment reduce.
         A residual key failed the full-lane match at every PROBE slot,
         so its total is disjoint from any probe-resolved slot; with
         incremental folds it may still duplicate a row placed off its
         probe sequence by an EARLIER ladder descent — exact regardless,
         because all consumers merge duplicate key rows (see
         hash_aggregate's incremental exception note);
      3. place the k-th residual key into the k-th empty slot (rank maps
         built with one cumsum each).  Keys beyond the empty-slot count
         are dropped but still counted in the returned distinct total,
         so capacity truncation stays observable exactly like the sort
         path's head-slice (reduce_stage.segment_reduce_into).

    Returns ``(merged_table, distinct_total)``.
    """
    from locust_tpu.ops.process_stage import sort_and_compact
    from locust_tpu.ops.reduce_stage import segment_reduce_into

    T = table.size
    n_lanes = table.key_lanes.shape[-1]
    cap = RESIDUAL_CAP

    # 1. Compact unresolved rows into the small buffer (dump row = cap).
    pos = jnp.cumsum(unresolved.astype(jnp.int32)) - 1
    idx = jnp.where(unresolved & (pos < cap), pos, cap)
    rlanes = jnp.zeros((cap + 1, n_lanes), jnp.uint32).at[idx].set(
        batch.key_lanes, mode="drop"
    )
    rvals = jnp.zeros((cap + 1,), jnp.int32).at[idx].set(
        batch.values, mode="drop"
    )
    rvalid = jnp.zeros((cap + 1,), bool).at[idx].set(
        unresolved, mode="drop"
    )
    rbatch = KVBatch(rlanes[:cap], rvals[:cap], rvalid[:cap])

    # 2. Group + total the residual keys (tiny sort).
    rtab, rdist = segment_reduce_into(
        sort_and_compact(rbatch, "hashp1"), cap, combine
    )

    # 3. k-th residual key -> k-th empty slot.
    empty = ~table.valid
    erank = jnp.cumsum(empty.astype(jnp.int32)) - 1
    slot_by_rank = jnp.zeros((cap + 1,), jnp.int32).at[
        jnp.where(empty & (erank < cap), erank, cap)
    ].set(jnp.arange(T, dtype=jnp.int32), mode="drop")[:cap]
    n_empty = T - used
    placeable = rtab.valid & (
        jnp.arange(cap, dtype=jnp.int32) < jnp.minimum(n_empty, cap)
    )
    target = jnp.where(placeable, slot_by_rank, T)  # dump row = T

    lanes_pad = jnp.concatenate(
        [table.key_lanes, jnp.zeros((1, n_lanes), jnp.uint32)]
    ).at[target].set(rtab.key_lanes, mode="drop")
    vals_pad = jnp.concatenate(
        [table.values, jnp.zeros((1,), jnp.int32)]
    ).at[target].set(rtab.values, mode="drop")
    valid_pad = jnp.concatenate(
        [table.valid, jnp.zeros((1,), bool)]
    ).at[target].set(placeable, mode="drop")

    merged = KVBatch(lanes_pad[:T], vals_pad[:T], valid_pad[:T])
    return merged, used + rdist


def combine_or_passthrough(
    batch: KVBatch, combine: str, probes: int = 2,
    scatter_impl: str = "xla",
) -> KVBatch:
    """Opportunistic pre-aggregation with an O(n) worst case — no sort.

    For the mesh LOCAL COMBINER (shuffle.local_step): aggregation there
    is an optimization, not a contract — ungrouped rows ship fine
    (partition is order-agnostic and every destination re-reduces), so
    when probing fails the right fallback is not a sort but a cheap
    compaction: resolved table rows and still-raw unresolved rows are
    cumsum-packed into one batch-sized output (used + n_unres <= valid
    rows <= batch.size, so nothing can be dropped).  Worst case =
    ``probes`` scatter sweeps + one O(n) compaction, the bound the
    probes=2 choice at the call site is justified by.

    Same associativity gate as aggregate_exact ("count" must be lowered
    first — resolved slots hold partial sums that ship as single rows).
    """
    if combine == "count":
        raise ValueError(
            "combine_or_passthrough cannot take combine='count'; lower it "
            "via reduce_stage.normalize_combine to emit-1 + 'sum' first"
        )
    N = batch.size
    n_lanes = batch.key_lanes.shape[-1]
    table, used, unresolved = hash_aggregate(
        batch, N, combine, probes=probes, scatter_impl=scatter_impl
    )

    def fast(_):
        return table

    def passthrough(_):
        rank_t = jnp.cumsum(table.valid.astype(jnp.int32)) - 1
        dest_t = jnp.where(table.valid, rank_t, N)
        lanes = jnp.zeros((N + 1, n_lanes), jnp.uint32).at[dest_t].set(
            table.key_lanes, mode="drop"
        )
        vals = jnp.zeros((N + 1,), jnp.int32).at[dest_t].set(
            table.values, mode="drop"
        )
        valid = jnp.zeros((N + 1,), bool).at[dest_t].set(
            table.valid, mode="drop"
        )
        rank_u = jnp.cumsum(unresolved.astype(jnp.int32)) - 1 + used
        dest_u = jnp.where(unresolved, rank_u, N)
        lanes = lanes.at[dest_u].set(batch.key_lanes, mode="drop")
        vals = vals.at[dest_u].set(batch.values, mode="drop")
        valid = valid.at[dest_u].set(unresolved, mode="drop")
        return KVBatch(lanes[:N], vals[:N], valid[:N])

    return jax.lax.cond(
        jnp.sum(unresolved.astype(jnp.int32)) == 0,
        fast,
        passthrough,
        operand=None,
    )


def reduce_into(
    batch: KVBatch,
    out_size: int,
    combine: str,
    sort_mode: str,
) -> tuple[KVBatch, jax.Array]:
    """THE fold-level reduce dispatch: one place decides sort vs hasht.

    Every bounded-table fold site (engine block fold, mesh per-shard
    merge, hierarchical cross-slice combine) calls this instead of
    hand-rolling the ``if sort_mode in HASHT_FAMILY`` branch — a new
    fold-level strategy lands here once, not in four files.  (The mesh
    LOCAL COMBINER is the one deliberate exception: aggregation there is
    optional, so it uses ``combine_or_passthrough``.)
    """
    if sort_mode in HASHT_FAMILY:
        return aggregate_exact(
            batch, out_size, combine,
            scatter_impl=scatter_impl_for(sort_mode),
        )
    from locust_tpu.ops.process_stage import sort_and_compact
    from locust_tpu.ops.reduce_stage import segment_reduce_into

    return segment_reduce_into(
        sort_and_compact(batch, sort_mode), out_size, combine
    )


def fold_into(
    acc: KVBatch,
    batch: KVBatch,
    out_size: int,
    combine: str,
    sort_mode: str,
) -> tuple[KVBatch, jax.Array]:
    """Fold a batch of NEW rows into an existing bounded table.

    The accumulator-merge counterpart of :func:`reduce_into` — call
    this when ``acc`` is itself the output of a previous fold at the
    same ``(out_size, combine, sort_mode)``:

    * sort modes: ``concat(acc, batch)`` then one sort + segment reduce
      — the table IS sorted back in with the emits (one fused sort does
      grouping and merge);
    * the hasht family ("hasht" / "hasht-mxu", differing only in the
      combine-scatter spelling): ``aggregate_exact`` over the same
      concat — a per-fold REBUILD, deliberately NOT the incremental
      ``hash_aggregate(table=acc)`` mode.  Measured round 5 (CPU bench,
      hamlet-repeated 8MB): incremental wiring LOST — 8.1 -> 6.5 MB/s
      and distinct drifted 5608 -> 5631, because a key the probe rounds
      strand (all its slots taken; ~2 keys on hamlet) is placed OFF its
      probe sequence by the residual branch and then accumulates one
      duplicate row EVERY subsequent fold (linear growth; rebuild keeps
      exactly one row).  The distinct drift would additionally poison
      bench's lossless-side A/B guard (max-distinct anchor).  Wiring
      incremental for real needs a slot-stable STASH side-table for
      stranded keys — future work; the capability + its exactness
      contract stay tested at the hash_aggregate level.
    """
    if sort_mode in HASHT_FAMILY:
        return aggregate_exact(
            KVBatch.concat(acc, batch), out_size, combine,
            scatter_impl=scatter_impl_for(sort_mode),
        )
    from locust_tpu.ops.process_stage import sort_and_compact
    from locust_tpu.ops.reduce_stage import segment_reduce_into

    return segment_reduce_into(
        sort_and_compact(KVBatch.concat(acc, batch), sort_mode),
        out_size,
        combine,
    )


def aggregate_exact(
    batch: KVBatch,
    out_size: int,
    combine: str = "sum",
    probes: int | None = None,
    into: KVBatch | None = None,
    scatter_impl: str = "xla",
) -> tuple[KVBatch, jax.Array]:
    """The full sort-free fold with its exactness ladder, as one call.

    ``into`` (a table from a previous hasht fold at the same shape)
    switches :func:`hash_aggregate` to its incremental mode; the ladder
    below is unchanged — its ``small``/``full`` branches already merge
    residual rows into an arbitrary existing table.

    ``scatter_impl`` reaches only the probe loop's value combine
    (:func:`hash_aggregate`).  The residual/overflow branches stay
    sort-based under BOTH impls: they exist to be exact on the handful of
    rows the probes strand, their sorts are capacity-bounded
    (RESIDUAL_CAP), and — because the probe loop's table is bit-identical
    across impls — the branch a given batch takes, and the rows it sees,
    are identical too.

    ``hash_aggregate`` + the three-way unresolved-row ladder the engine's
    "hasht" fold documents (engine.fold_block_hasht): 0 unresolved → the
    table is the answer; <= RESIDUAL_CAP → ``place_residual``'s small
    compact-sort-place path; more → the full stock sort fallback.  The
    single shared implementation for every fold-level consumer (the
    single-device engine and the mesh shuffle's per-shard merge) — no
    collectives inside, so it traces under ``shard_map`` with per-shard
    branch selection.

    Returns ``(table[out_size], distinct)`` with the pre-capacity
    distinct count (truncation observable, like segment_reduce_into).
    """
    from locust_tpu.ops.process_stage import sort_and_compact
    from locust_tpu.ops.reduce_stage import segment_reduce_into

    if combine == "count":
        # Refuse, don't corrupt: "count" is not a monoid over its own
        # outputs (normalize_combine, reduce_stage.py), and this ladder's
        # fallback branches re-reduce batches that may contain
        # PRE-AGGREGATED table rows — a second "count" over those counts
        # rows, not occurrences (verified: wrong totals at >RESIDUAL_CAP
        # unresolved).  Callers must lower count -> emit-1 + "sum" at the
        # leaves first; every engine/mesh fold site already does.
        raise ValueError(
            "aggregate_exact cannot take combine='count' (not associative "
            "over partial tables); lower it via "
            "reduce_stage.normalize_combine to emit-1 + 'sum' first"
        )
    table, used, unresolved = hash_aggregate(
        batch, out_size, combine,
        probes=DEFAULT_PROBES if probes is None else probes,
        table=into,
        scatter_impl=scatter_impl,
    )
    n_unres = jnp.sum(unresolved.astype(jnp.int32))

    def fast(_):
        return table, used

    def small(_):
        return place_residual(table, used, batch, unresolved, combine)

    def full(_):
        resid = KVBatch(batch.key_lanes, batch.values, unresolved)
        return segment_reduce_into(
            sort_and_compact(KVBatch.concat(table, resid), "hashp1"),
            out_size,
            combine,
        )

    return jax.lax.cond(
        n_unres == 0,
        fast,
        lambda op: jax.lax.cond(n_unres <= RESIDUAL_CAP, small, full, op),
        operand=None,
    )
