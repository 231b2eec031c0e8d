"""The MapReduce engine: pluggable map/combine over blocked byte tensors.

Single-device orchestration — the TPU-native analog of the reference driver's
map -> process -> reduce sequencing (reference MapReduce/src/main.cu:397-473),
with three deliberate departures:

* **No global line cap.**  The reference truncates input at
  MAX_LINES_FILE_READ=5800 lines (main.cu:18).  Here the corpus streams
  through fixed-shape blocks of ``cfg.block_lines`` and partial result tables
  merge associatively (sort + segment-reduce is a monoid fold), so input
  size is unbounded (SURVEY.md §5 "long-context").
* **Pluggable semantics.**  ``map_fn(lines, cfg) -> (KVBatch, overflow)`` and
  a monoid ``combine`` replace the hardcoded WordCount map()/count-reduce
  (main.cu:136-153, 210-238); WordCount, PageRank and inverted-index are
  instances (locust_tpu/apps/).
* **One sort per block.**  The block's emits concatenate with the bounded
  running table (``cfg.resolved_table_size`` rows) and a SINGLE
  sort+segment-reduce both groups the new emits and merges them into the
  accumulator — the per-block sort and the cross-block merge sort of a
  naive formulation fused into one.  With ``sort_mode="hash"`` that sort has
  3 key operands regardless of key width (ops/process_stage.py).

Every stage is jit-compiled once per config; ``run_fused`` runs the whole
corpus in ONE dispatch (lax.scan over blocks), ``timed_run`` — the CLI's
default — runs map/process/reduce/merge as separate programs, stage by
stage over a GROUP of blocks with one host wait a stage, to reproduce the
reference's per-stage Map/Process/Reduce timing report (main.cu:405-468)
without leaving the device idle between blocks.
"""

from __future__ import annotations

import collections
import dataclasses
import itertools
import logging
import os
import threading
import time
from functools import partial
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from locust_tpu import backend as backend_mod
from locust_tpu import obs
from locust_tpu.config import DEFAULT_CONFIG, EngineConfig
from locust_tpu.core import bytes_ops
from locust_tpu.core.kv import HostRows, KVBatch, RecordBatch, grow_table, rows_to_hold
from locust_tpu.io.snapshot import AsyncCheckpointWriter, finalize_snapshot
from locust_tpu.ops.map_stage import wordcount_map
from locust_tpu.ops.process_stage import order_by_lanes, sort_and_compact
from locust_tpu.ops.reduce_stage import (
    combine_scatters,
    normalize_combine,
    segment_reduce,
    segment_reduce_into,
)

logger = logging.getLogger("locust_tpu")

MapFn = Callable[[jax.Array, EngineConfig], tuple[KVBatch, jax.Array]]

# Host-side monoid mirrors of ops/reduce_stage.COMBINERS, used to re-merge
# the (astronomically rare) duplicate table rows a 64-bit hash collision can
# produce in sort_mode="hash" (see core/packing.hash_pair).
_HOST_COMBINE = {
    "sum": lambda a, b: a + b,
    "count": lambda a, b: a + b,
    "min": min,
    "max": max,
}


def _fetch_host(table: KVBatch, fetch) -> KVBatch:
    with obs.span("engine.finalize.d2h", rows=table.size) as sp:
        host = fetch(table)
        sp.set(bytes=host.key_lanes.nbytes + host.values.nbytes
               + host.valid.nbytes)
    return host


def _exact_pairs(pairs, combine: str, sort: bool, sp) -> list[tuple[bytes, int]]:
    """Decoded pairs with duplicate keys merged and (``sort``) in key
    order: the Python half of a finalize, inside its ``order`` span."""
    merged = len(dict(pairs)) != len(pairs)
    sp.set(merged=int(merged))
    if merged:  # a duplicate row: merge by hand
        op = _HOST_COMBINE[combine]
        by_key: dict[bytes, int] = {}
        for k, v in pairs:
            by_key[k] = op(by_key[k], v) if k in by_key else v
        pairs = list(by_key.items())
    return sorted(pairs) if sort else pairs


def finalize_host_pairs(
    table: KVBatch, combine: str = "sum", sort: bool = True,
    fetch=KVBatch.to_host,
) -> list[tuple[bytes, int]]:
    """Decode a device table to host (key, value) pairs, exactly.

    Re-merges duplicate key rows (possible only via a full 64-bit hash
    collision in sort_mode="hash") and restores lexicographic key order —
    the reference's sorted final print (main.cu:473).

    The table's way to the host, in three spans that each name one piece
    of host work: the copy (``fetch``: one ``device_get``, or the mesh's
    gather of its shards), the decode to pairs, the Python check and sort.
    For the callers that need pairs; a table that is only printed takes
    ``finalize_host_rows``.
    """
    host = _fetch_host(table, fetch)
    with obs.span("engine.finalize.decode") as sp:
        pairs = host.host_pairs(sort=sort)
        sp.set(rows=len(pairs))
    with obs.span("engine.finalize.order", rows=len(pairs)) as sp:
        return _exact_pairs(pairs, combine, sort, sp)


def finalize_host_rows(
    table: KVBatch, combine: str = "sum", fetch=KVBatch.to_host,
) -> HostRows | list[tuple[bytes, int]]:
    """A device table as key-ordered host ROWS — two arrays, no Python
    object a row — for the one consumer that only prints them
    (``cli._print_table`` through ``bytes_ops.render_rows``).

    The same three spans as ``finalize_host_pairs``: the copy, the numpy
    decode (``KVBatch.host_rows``), and in ``order`` what makes the rows
    exact without Python (``bytes_ops.render_blocker``: no key with a NUL
    inside, no two rows of one key, no negative value; ``fast=1``).  Where
    the data holds one of the three, the rows go the pairs' way — decoded,
    merged by hand, ``sorted`` — and the sorted PAIRS come back
    (``fast=0`` and the ``reason`` on the span, one log line).
    """
    host = _fetch_host(table, fetch)
    with obs.span("engine.finalize.decode") as sp:
        rows = host.host_rows(sort=True)
        sp.set(rows=len(rows))
    with obs.span("engine.finalize.order", rows=len(rows)) as sp:
        reason = bytes_ops.render_blocker(rows.keys, rows.values)
        if reason is None:
            sp.set(fast=1, merged=0)
            return rows
        sp.set(fast=0, reason=reason)
        logger.info("table finalized a pair at a time, not as arrays "
                    "(%s in its rows)", reason)
        return _exact_pairs(rows.pairs(), combine, True, sp)


def _wrap_i32(v: int) -> int:
    """Two's-complement int32 wraparound — the device table's value
    dtype, so a host-side merge wraps exactly where a full device fold
    would."""
    v &= 0xFFFFFFFF
    return v - 0x100000000 if v >= 0x80000000 else v


def merge_host_pairs(
    base: list[tuple[bytes, int]],
    delta: list[tuple[bytes, int]],
    combine: str = "sum",
) -> list[tuple[bytes, int]]:
    """Merge two finalized host-pairs lists by key — the mergeable-table
    property the plan optimizer's incremental refold rides
    (plan/optimize.py ``incremental_fold``): an exact fold is a pure
    function of the line multiset, so fold(prefix) ⊕ fold(delta) ==
    fold(prefix + delta).  Sum/count merge with int32 WRAPAROUND to
    match the device accumulator's dtype bit-for-bit; ordering matches
    ``finalize_host_pairs`` (lexicographic key sort)."""
    op = _HOST_COMBINE[combine]
    wrap = combine in ("sum", "count")
    merged: dict[bytes, int] = dict(base)
    for k, v in delta:
        if k in merged:
            out = op(merged[k], v)
            merged[k] = _wrap_i32(int(out)) if wrap else out
        else:
            merged[k] = v
    return sorted(merged.items())


@partial(jax.jit, static_argnums=1)
def _grow_table(table: KVBatch, rows: int) -> KVBatch:
    """``table`` with empty rows appended up to ``rows`` (not donated:
    a growth step that falls short starts from ``table`` again).  The
    rule is the mesh shards' too (core/kv.grow_table)."""
    return grow_table(table, rows)


@dataclasses.dataclass
class StageTimes:
    """Per-stage wall-clock, the reference's timing report (main.cu:405-468)."""

    map_ms: float = 0.0
    process_ms: float = 0.0
    reduce_ms: float = 0.0

    @property
    def total_ms(self) -> float:
        return self.map_ms + self.process_ms + self.reduce_ms


@dataclasses.dataclass
class RunResult:
    table: KVBatch            # unique keys + combined values (device order)
    num_segments: int         # distinct keys found (<= table capacity)
    overflow_tokens: int      # emits dropped by the per-line cap
    truncated: bool           # True if distinct keys exceeded table capacity
    times: StageTimes
    combine: str = "sum"
    # run_stream only: hot-loop stall accounting + checkpoint-writer
    # stats (backpressure_stall_ms, ckpt.{mark_ms,written,skipped,
    # max_lag,...}) — the CLI's `[locust] stream:` line prints it.
    stream: dict | None = None
    # Which megakernel formulation actually served this run (ISSUE 19
    # operator visibility): "batch" = per-block fused_block_preagg,
    # "stream" = the persistent streaming segments, None = no kernel
    # (non-fused sort modes, or a demoted fused request).
    fused_kernel: str | None = None
    # True iff sort_mode="fused" was REQUESTED but the kernel did not
    # engage (eligibility miss / off-TPU interpret cap / mesh-on-CPU) —
    # the fold ran hasht-identically.  The mesh engines mirror this on
    # DistributedResult; previously the demotion was silent.
    fused_demoted: bool = False

    def to_host_pairs(self, sort: bool = True) -> list[tuple[bytes, int]]:
        """Decode the table; re-merge hash-collision duplicates; key-sort.

        The device table in sort_mode="hash" is hash-ordered; lexicographic
        output order (the reference's sorted print, main.cu:473) is restored
        here on the final table, which is orders of magnitude smaller than
        the emit stream.
        """
        with obs.span("engine.finalize", rows=self.table.size):
            return finalize_host_pairs(self.table, self.combine, sort)

    def to_host_rows(self) -> HostRows | list[tuple[bytes, int]]:
        """The table as key-ordered rows for printing
        (``finalize_host_rows``), under the same ``engine.finalize``:
        what the CLI's table on stdout asks for.  Everyone who merges or
        serializes pairs (serve, the distributor, plans, ``apps/``,
        ``dump_intermediate``) calls ``to_host_pairs``."""
        with obs.span("engine.finalize", rows=self.table.size):
            return finalize_host_rows(self.table, self.combine)

    def dump_intermediate(self, path: str, fmt: str = "tsv") -> None:
        """Stage-1 output plumbing: the combined local table as an
        intermediate file — ``tsv`` for reference parity, ``bin`` for the
        distributor's packed-KV data plane (io/serde.py)."""
        from locust_tpu.io import serde

        serde.write_intermediate(self.to_host_pairs(), path, fmt)


class _StagingRing:
    """Reusable host staging buffers for the streaming fold.

    ``slots`` pre-allocated ``[block_lines, width]`` uint8 buffers cycled
    round-robin: each block is padded into the next slot
    (normalize_round_chunk ``out=``) and handed straight to the device,
    so steady-state staging allocates nothing — the flat-RSS contract's
    allocation-free upgrade.

    Reuse safety: jax's CPU backend aliases host numpy buffers zero-copy
    at ``device_put``, so a slot must not be overwritten while its fold
    is in flight.  ``run_stream``'s bounded-inflight backpressure syncs
    the fold ``STREAM_DISPATCH_DEPTH`` blocks back before dispatching a
    new one; with ``STREAM_DISPATCH_DEPTH + 1`` slots, the slot being
    re-filled at block ``i`` was consumed by fold ``i - (slots)``, which
    that sync already proved complete.
    """

    def __init__(self, slots: int, block_lines: int, width: int):
        self._bufs = [
            np.zeros((block_lines, width), np.uint8) for _ in range(slots)
        ]
        self._next = 0

    def stage(self, chunk) -> np.ndarray:
        from locust_tpu.parallel.shuffle import normalize_round_chunk

        buf = self._bufs[self._next]
        self._next = (self._next + 1) % len(self._bufs)
        return normalize_round_chunk(chunk, *buf.shape, out=buf)


class _CheckpointPump:
    """Per-run snapshot scheduler for the single-device engine.

    Synchronous mode writes in the fold loop (the pre-existing
    behavior); async mode (cfg.async_checkpoint) marks a generation —
    an on-device copy of the accumulator, dispatched BEFORE the next
    fold donates its buffers — and hands the serialize+rename to the
    bounded background writer (io/snapshot.AsyncCheckpointWriter,
    latest-wins if the loop laps it).  The on-disk format and atomic-
    replace semantics are identical in both modes.
    """

    def __init__(self, engine: "MapReduceEngine", state_path: str,
                 fingerprint: str, use_async: bool):
        self._eng = engine
        self._path = state_path
        self._fp = fingerprint
        self._writer = AsyncCheckpointWriter() if use_async else None
        self.mark_ms = 0.0
        self._sync_writes = 0

    def mark(self, acc: KVBatch, next_block: int, overflow, max_distinct):
        t0 = time.perf_counter()
        obs.event(
            "ckpt.mark",
            generation=next_block,
            mode="async" if self._writer is not None else "sync",
        )
        obs.metric_inc("ckpt.marks")
        if self._writer is None:
            self._eng._save_state(
                self._path, acc, next_block, overflow, max_distinct, self._fp
            )
            self._sync_writes += 1
        else:
            # Device-to-device copy (async dispatch, no host sync): the
            # donated fold reuses acc's buffers next iteration, so the
            # writer must snapshot a buffer the loop will never touch.
            # The scalar counters are fresh eager arrays each fold and
            # are never donated — holding references suffices.
            snap = KVBatch(
                key_lanes=jnp.copy(acc.key_lanes),
                values=jnp.copy(acc.values),
                valid=jnp.copy(acc.valid),
            )
            self._writer.submit(
                next_block,
                partial(
                    self._eng._save_state, self._path, snap, next_block,
                    overflow, max_distinct, self._fp,
                ),
            )
        self.mark_ms += (time.perf_counter() - t0) * 1e3

    def finish(self) -> float:
        """Normal-path completion: block until the last marked generation
        is durably renamed; re-raises writer errors.  Returns the wait ms
        (the ONLY synchronous write cost the async mode keeps)."""
        t0 = time.perf_counter()
        if self._writer is not None:
            self._writer.flush()
        return (time.perf_counter() - t0) * 1e3

    def close(self) -> None:
        if self._writer is not None:
            self._writer.close()

    def stats(self) -> dict:
        out = {
            "mode": "async" if self._writer is not None else "sync",
            "mark_ms": round(self.mark_ms, 3),
        }
        if self._writer is not None:
            out.update(self._writer.stats())
        else:
            out["written"] = self._sync_writes
        return out


@dataclasses.dataclass(frozen=True)
class _Programs:
    """One configuration's jitted programs and the static facts decided
    with them (``_build_programs``); every engine of the configuration in
    the process holds this one record (``_programs_for``)."""

    map_fn: MapFn  # normalized (reduce_stage.normalize_combine)
    map: Callable
    process: Callable
    reduce: Callable
    merge: Callable
    fold_block: Callable
    fold_block_fallback: Callable
    fold_segment: Callable | None
    scan_blocks_into: Callable
    scan_blocks: Callable
    scan_blocks_batch: Callable
    fused_kernel_on: bool
    fused_demoted: bool
    fused_stream_seg: int


def _build_programs(cfg: EngineConfig, raw_map_fn: MapFn,
                    raw_combine: str) -> _Programs:
    """Define and jit every program of an engine of (``cfg``,
    ``raw_map_fn``, ``raw_combine``) on the current backend.  Nothing
    here names an engine: the closures hold the configuration alone, so
    engines share them and a dead engine is no reference cycle.  Nothing
    is traced here either; jax traces a program at its first call."""
    # "count" lowers to emit-1 + sum so the block-accumulator merge is
    # associative (reduce_stage.normalize_combine); the device pipeline
    # below uses the normalized pair throughout.  The RAW map_fn is
    # what the fused-kernel eligibility check identifies (the count
    # wrapper emits the same 1s the kernel counts).
    map_fn, combine = normalize_combine(raw_map_fn, raw_combine)
    tsize = cfg.resolved_table_size
    mode = cfg.sort_mode

    from locust_tpu.ops.hash_table import fold_into

    # sort_mode="fused": the Pallas map->aggregate megakernel
    # (ops/pallas/fused_fold.py) replaces the map stage + first
    # aggregation at THIS boundary only — everywhere else the mode
    # is "hasht" exactly (config.HASHT_FAMILY).  Eligibility is
    # fully static, decided (and logged) once here, never inside
    # traced code.
    fused_kernel_on = False
    fused_demoted = False
    # Persistent streaming segment length (megakernel v2): how many
    # staged blocks run_stream groups into ONE kernel launch with the
    # table VMEM-resident across the whole segment.  1 = per-block
    # (the v1 formulation); the clamp keeps the per-segment emit
    # budget f32-exact and bounds off-TPU interpret cost
    # (config.fused_stream_seg_blocks).
    fused_stream_seg = 1
    if mode == "fused":
        from locust_tpu.config import fused_stream_seg_blocks
        from locust_tpu.ops.pallas.fused_fold import (
            fused_engine_eligible,
        )

        ok, why = fused_engine_eligible(cfg, raw_map_fn, raw_combine)
        fused_kernel_on = ok
        fused_demoted = not ok
        if not ok:
            logger.info("sort_mode='fused': kernel not engaged — %s",
                        why)
        else:
            fused_stream_seg = fused_stream_seg_blocks(
                cfg.emits_per_block,
                cfg.block_lines,
                jax.default_backend() == "tpu",
            )

    def fold_block(acc: KVBatch, lines: jax.Array):
        """Map one block and merge its emits into the running table.

        Sort modes: ONE sort of (table_size + emits_per_block) rows
        does both the block's shuffle-grouping and the cross-block
        merge.  The hasht family ("hasht" = scatter combine,
        "hasht-mxu" = one-hot MXU combine, "fused" = the Pallas
        megakernel below, else hasht): the sort-free fold with its
        exactness ladder, rebuilt per fold (ops/hash_table.fold_into
        — see there for why the incremental variant measured worse
        and is not wired).  Either way the running distinct-key
        count is measured BEFORE the capacity slice so a truncation
        in any fold is observable.

        Fused kernel path: the block pre-aggregates IN VMEM (the
        [lines, emits, key_width] token tensor never touches HBM)
        and the settlement folds (acc + kernel table + residual)
        through the SAME aggregate_exact as "hasht" — the final
        table is a pure function of the distinct-key set and the
        per-key totals, so it is bit-identical to the hasht fold
        (ops/pallas/fused_fold.py module docstring; pinned by
        tests/test_fused_fold.py).  A residual-buffer overflow in
        the kernel re-folds the block through the stock path via
        lax.cond — exact either way, and the overflow counter is
        the kernel's under both branches (identical tokenize
        formulation).
        """
        if fused_kernel_on:
            from locust_tpu.ops.pallas.fused_fold import (
                fused_block_preagg,
            )

            interpret = jax.default_backend() != "tpu"
            ktab, kresid, overflow, bad = fused_block_preagg(
                lines, cfg, interpret=interpret
            )

            def fused_path(acc_in):
                return fold_into(
                    acc_in, KVBatch.concat(ktab, kresid), tsize,
                    combine, mode,
                )

            def stock_path(acc_in):
                kv, _ = map_fn(lines, cfg)
                return fold_into(acc_in, kv, tsize, combine, mode)

            merged, distinct = jax.lax.cond(
                bad, stock_path, fused_path, acc
            )
            return merged, overflow, distinct
        return stock_fold(acc, lines)

    def stock_fold(acc: KVBatch, lines: jax.Array):
        """The kernel-free fold — fold_block's non-kernel tail, and
        the breaker-failover executable: the CPU fallback must never
        trace the Mosaic kernel (at failover trace time
        jax.default_backend() is still the dead primary, so the
        in-fold interpret switch cannot see the migration;
        run_checkpointed dispatches THIS on the fallback device).
        Bit-identical outputs to the kernel path by the settlement
        argument, so mid-job migration changes nothing downstream.
        """
        kv, overflow = map_fn(lines, cfg)
        merged, distinct = fold_into(acc, kv, tsize, combine, mode)
        return merged, overflow, distinct

    def fold_segment(acc: KVBatch, seg_lines: jax.Array):
        """Persistent-kernel streaming fold (megakernel v2): ONE
        kernel launch over ``[seg_blocks * block_lines, width]``
        staged lines, table planes VMEM-resident across the whole
        segment (fused_block_preagg already supports any
        tile-multiple line count; its constant-index table BlockSpec
        IS the persistence).  The acc->settle->acc HBM round-trip
        and the table flush amortize by the segment length.

        Bit-identity carries over from fold_block unchanged: the
        settlement folds concat(acc, table, residual) through the
        same aggregate_exact, and hasht's final table is a pure
        function of the distinct-key set + per-key totals — which
        are grouping-invariant (emit overflow is per-line, counts
        are per-key sums).  A residual overflow re-folds the WHOLE
        segment through the stock path (map over the segment lines
        is exact at any length), so both cond branches stay exact.
        """
        from locust_tpu.ops.pallas.fused_fold import (
            fused_block_preagg,
        )

        interpret = jax.default_backend() != "tpu"
        ktab, kresid, overflow, bad = fused_block_preagg(
            seg_lines, cfg, interpret=interpret
        )

        def fused_path(acc_in):
            return fold_into(
                acc_in, KVBatch.concat(ktab, kresid), tsize,
                combine, mode,
            )

        def stock_path(acc_in):
            kv, _ = map_fn(seg_lines, cfg)
            return fold_into(acc_in, kv, tsize, combine, mode)

        merged, distinct = jax.lax.cond(bad, stock_path, fused_path, acc)
        return merged, overflow, distinct

    def scan_blocks_into(acc0: KVBatch, blocks: jax.Array):
        """Whole-corpus pipeline in ONE dispatch: fold blocks with lax.scan.

        One device dispatch per corpus instead of per block — essential
        when per-dispatch latency matters (many small blocks) and the XLA-
        idiomatic way to loop without data-dependent Python control flow.
        The init accumulator arrives as an ARGUMENT so the jit below
        can donate it into the scan carry: even the one-dispatch path
        allocates no second table.
        """

        def body(carry, blk):
            acc, overflow_acc, max_distinct = carry
            acc, overflow, distinct = fold_block(acc, blk)
            return (
                acc,
                overflow_acc + overflow,
                jnp.maximum(max_distinct, distinct),
            ), None

        init = (acc0, jnp.int32(0), jnp.int32(0))
        (acc, overflow, num), _ = jax.lax.scan(body, init, blocks)
        return acc, overflow, num

    # Donated fold state: the accumulator table — the largest live
    # array — is donated into every per-block dispatch and into the
    # scan init, so XLA aliases its buffers input->output (updated in
    # place, no per-fold re-allocation).
    # Callers therefore must treat the acc they passed as consumed;
    # every loop here rebinds it, and snapshot marks copy on device
    # first (_CheckpointPump.mark).
    # The two donating jits are bound here under the names the engine
    # calls them by: the analyzer's R010 knows a donating callable by the
    # name its jax.jit(..., donate_argnums=) is bound to.
    _fold_block = jax.jit(fold_block, donate_argnums=(0,))
    # Breaker-failover fold (run_checkpointed's on-CPU dispatch):
    # identical to _fold_block unless the fused kernel is on — then
    # it is the kernel-free stock fold (see stock_fold above).
    # Traced lazily, so non-failover runs never pay its compile.
    fold_block_fallback = (
        jax.jit(stock_fold, donate_argnums=(0,))
        if fused_kernel_on
        else _fold_block
    )
    # Streaming-segment executable (megakernel v2): traced lazily on
    # first run_stream use; None when the kernel is off or the clamp
    # leaves segments at one block (then run_stream's per-block loop
    # is already optimal).
    jit_fold_segment = (
        jax.jit(fold_segment, donate_argnums=(0,))
        if fused_kernel_on and fused_stream_seg > 1
        else None
    )
    _scan_blocks_into = jax.jit(scan_blocks_into, donate_argnums=(0,))
    # The export/compile-check surface (__graft_entry__.entry, the
    # TPU StableHLO lowering gates) keeps the one-argument signature.
    jit_scan_blocks = jax.jit(
        lambda blocks: scan_blocks_into(
            KVBatch.empty(tsize, cfg.key_lanes), blocks
        )
    )
    # Batched job executable (the serve tier's coalesced dispatch,
    # docs/SERVING.md): vmap the whole-corpus scan over a leading JOB
    # axis, so N compatible small jobs fold in ONE device dispatch
    # with per-job tables/counters out.  Each job slot gets its own
    # fresh accumulator (no donation: slots are independent and the
    # batch is rebuilt per dispatch); traced/compiled lazily on first
    # use per [njobs, nblocks] shape — non-serve users never pay it.
    jit_scan_blocks_batch = jax.jit(
        jax.vmap(
            lambda blocks: scan_blocks_into(
                KVBatch.empty(tsize, cfg.key_lanes), blocks
            )
        )
    )

    # Split stages for the timed path only: map, process and reduce
    # run once a block, the merge once a GROUP of blocks — the running
    # table and all of the group's block tables through one sort and
    # one segment combine, so the table is sorted again once a group
    # and not once a block.  The capacity is the accumulator's own
    # size and the fan-in the length of ``tables``, so the one jit
    # re-traces per capacity timed_run grows to (_regrow) and per rung
    # of the fan-in ladder (_timed_group_blocks).  ``distinct`` is the
    # TRUE count of keys in table + group, whatever the capacity.
    # ``acc`` is not donated: it is the way back when the merge passes
    # the capacity.
    def merge_tables(acc: KVBatch, tables: tuple[KVBatch, ...],
                     max_distinct: jax.Array):
        merged, distinct = segment_reduce_into(
            sort_and_compact(KVBatch.concat(acc, *tables), mode), acc.size, combine
        )
        return merged, jnp.maximum(max_distinct, distinct)

    return _Programs(
        map_fn=map_fn,
        map=jax.jit(lambda lines: map_fn(lines, cfg)),
        process=jax.jit(partial(sort_and_compact, mode=mode)),
        reduce=jax.jit(partial(segment_reduce, combine=combine)),
        merge=jax.jit(merge_tables),
        fold_block=_fold_block,
        fold_block_fallback=fold_block_fallback,
        fold_segment=jit_fold_segment,
        scan_blocks_into=_scan_blocks_into,
        scan_blocks=jit_scan_blocks,
        scan_blocks_batch=jit_scan_blocks_batch,
        fused_kernel_on=fused_kernel_on,
        fused_demoted=fused_demoted,
        fused_stream_seg=fused_stream_seg,
    )


@dataclasses.dataclass(frozen=True)
class _RecordPrograms:
    """The record sort's jitted programs (``_build_record_programs``): one
    record a (record width, key width) a process, like ``_Programs``."""

    empty: Callable      # rows -> RecordBatch of zeros
    place: Callable      # (records, a block's words, at) -> records (donated)
    sort_keys: Callable  # (records, n, block_rows) -> int32 [blocks, block_rows]
    permute: Callable    # (records, perm, block) -> that block's words, sorted


def _build_record_programs(record_bytes: int, key_bytes: int) -> _RecordPrograms:
    """Define and jit the record sort of ``record_bytes``-byte records by
    their first ``key_bytes`` bytes.  No compaction and no combine: every
    record in comes back out.  The records stay whole on the device; only
    ``key_bytes`` of each and a row index go through ``lax.sort``
    (``order_by_lanes``), and the payload is permuted by the sorted index
    a block at a time.  A block crosses the host boundary as a flat array
    of words, so neither transfer depends on the layout the compiler
    gives a narrow ``[N, W]`` array."""
    words = RecordBatch.num_words(record_bytes)

    def empty_records(rows: int) -> RecordBatch:
        return RecordBatch.empty(rows, record_bytes)

    def place_records(records: RecordBatch, block: jax.Array,
                      at: jax.Array) -> RecordBatch:
        return RecordBatch(jax.lax.dynamic_update_slice(
            records.words, block.reshape(-1, words), (at, 0)
        ))

    def sort_record_keys(records: RecordBatch, n: jax.Array,
                         block_rows: int) -> jax.Array:
        # Rows from n on are the last block's padding: a key of all ones
        # and their larger row index put them behind every record.
        row = jnp.arange(records.size, dtype=jnp.int32)
        lanes = [
            jnp.where(row < n, lane, jnp.uint32(0xFFFFFFFF))
            for lane in records.key_lanes(key_bytes)
        ]
        _, perm = order_by_lanes(lanes)
        return perm.reshape(-1, block_rows)

    def permute_records(records: RecordBatch, perm: jax.Array,
                        block: jax.Array) -> jax.Array:
        return records.take(perm[block]).words.reshape(-1)

    return _RecordPrograms(
        empty=jax.jit(empty_records, static_argnames="rows"),
        place=jax.jit(place_records, donate_argnums=0),
        sort_keys=jax.jit(sort_record_keys, static_argnames="block_rows"),
        permute=jax.jit(permute_records),
    )


# The process's programs, a record a key (_programs_for), least recently
# used last out; guarded by the lock beside it.
_PROGRAMS: "collections.OrderedDict[tuple, object]" = collections.OrderedDict()
_PROGRAMS_LOCK = threading.Lock()


def _programs_for(key: tuple, build: Callable[[], object]):
    """The programs of configuration ``key``: the process's one record of
    it, made by ``build()`` if the process holds none (counted:
    ``engine.programs_built`` / ``engine.programs_shared``).  ``key`` is
    everything the builder closes over or reads — for the map/reduce
    programs the config, the map function object and the user's combine
    (``_build_programs``), for the record sort the record and key widths
    (``_build_record_programs``) — and the backend joins it here.  Every
    later engine of the key takes the same jit objects, so jax's in-memory
    cache answers its first call as it answers an old engine's hundredth:
    nothing is traced, lowered or read back.  At most ``MapReduceEngine.PROGRAM_KEYS`` keys are kept; an
    evicted record lives as long as the engines that hold it, and jax
    frees its executables with the last.  Built under the lock: two
    threads of one key get one record."""
    key = (*key, jax.default_backend())
    with _PROGRAMS_LOCK:
        programs = _PROGRAMS.get(key)
        built = programs is None
        if built:
            programs = _PROGRAMS[key] = build()
            while len(_PROGRAMS) > MapReduceEngine.PROGRAM_KEYS:
                _PROGRAMS.popitem(last=False)
        else:
            _PROGRAMS.move_to_end(key)
    obs.metric_inc("engine.programs_built", int(built))
    obs.metric_inc("engine.programs_shared", int(not built))
    return programs


def clear_programs() -> None:
    """Forget every program the process has built: the next engine of any
    configuration builds (and jax traces, lowers and loads) anew.

    For tests, and for a host that changes something a program reads
    while it is TRACED — ``LOCUST_DEBUG_CHECKS``, a monkeypatched module
    constant: a jit object is traced under the process state of its FIRST
    call with a shape, and every engine that shares it runs what was
    traced then.  Engines already made keep their programs."""
    with _PROGRAMS_LOCK:
        _PROGRAMS.clear()


@dataclasses.dataclass
class StagedRecords:
    """A job's records on the device (``RecordSort.load``): ``n_records``
    real rows, then the last block's zero padding."""

    records: RecordBatch
    n_records: int
    block_rows: int


def fetch_record_block(flat: jax.Array, rows: int, record_bytes: int) -> np.ndarray:
    """A permuted block (a flat array of words on its way down) as the
    host ``uint8`` bytes of its first ``rows`` records: the block's one
    wait, and where the word padding of an odd record width goes."""
    words = RecordBatch.num_words(record_bytes)
    with obs.span("sort.d2h", bytes=rows * record_bytes):
        with obs.span("engine.sync", what="d2h"):
            host = np.asarray(flat)  # locust: noqa[R003] the block's one wait: the next blocks are already on their way
    host = host.view(np.uint8)
    if 4 * words == record_bytes:
        return host[: rows * record_bytes]
    return np.ascontiguousarray(
        host.reshape(-1, 4 * words)[:rows, :record_bytes]
    ).reshape(-1)


class SortedRecords:
    """A sorted job whose payload is still on the device in input order
    (``RecordSort.sort``): ``perm`` says which row comes where, and
    ``host_blocks`` permutes and brings back a block at a time."""

    def __init__(self, sorter: "RecordSort", staged: StagedRecords,
                 perm: jax.Array):
        self._sorter = sorter
        self._staged = staged
        self._perm = perm
        self.n_records = staged.n_records

    def host_blocks(self):
        """The sorted records as host ``uint8`` arrays, in order, a block
        each: at most ``RECORD_BLOCKS_IN_FLIGHT`` blocks are permuted and
        on their way down while the caller writes the one before, and a
        block's device copy is dropped once it is on the host."""
        sorter, staged = self._sorter, self._staged
        rb = sorter.record_bytes
        blocks = self._perm.shape[0]
        pending: collections.deque = collections.deque()
        launched = 0
        for b in range(blocks):
            while launched < blocks and len(pending) < MapReduceEngine.RECORD_BLOCKS_IN_FLIGHT:
                with obs.span("sort.permute", rows=staged.block_rows):
                    flat = sorter.programs.permute(
                        staged.records, self._perm, np.int32(launched)
                    )
                    flat.copy_to_host_async()
                pending.append(flat)
                launched += 1
            rows = min(staged.block_rows, self.n_records - b * staged.block_rows)
            yield fetch_record_block(pending.popleft(), rows, rb)


def record_block_rows(record_bytes: int, n_records: int) -> int:
    """Rows of one staged block of a device that takes ``n_records``: the
    largest power of two whose words fit ``RECORD_BLOCK_BYTES``, and for a
    job under one block the first power of two (at least 8) that holds it
    — a handful of shapes over all small jobs, one a block count above
    that.  The mesh sort (parallel/record_sort.py) asks it of a device's
    share of the records."""
    words = RecordBatch.num_words(record_bytes)
    full = 1 << ((MapReduceEngine.RECORD_BLOCK_BYTES // (4 * words)).bit_length() - 1)
    rows = 8
    while rows < min(n_records, full):
        rows *= 2
    return rows


class RecordSort:
    """The record sort of one (record width, key width) on one device:
    ``load`` the records, whole; ``sort`` their keys; read them back in
    key order from ``SortedRecords.host_blocks``.  Made by
    ``MapReduceEngine.record_sort``; holds the configuration's programs
    (``_programs_for``: a process builds them once) and no data."""

    def __init__(self, record_bytes: int, key_bytes: int):
        if not 1 <= key_bytes <= record_bytes:
            raise ValueError(
                f"key_bytes {key_bytes} must lie in 1..record_bytes "
                f"({record_bytes})"
            )
        self.record_bytes, self.key_bytes = record_bytes, key_bytes
        self.programs = _programs_for(
            ("records", record_bytes, key_bytes),
            lambda: _build_record_programs(record_bytes, key_bytes),
        )

    def block_rows(self, n_records: int) -> int:
        return record_block_rows(self.record_bytes, n_records)

    def load(self, source) -> StagedRecords:
        """``source`` (``io/loader.RecordSource``) onto the device: its
        blocks handed up one after another — ``device_put`` returns at
        once, so the next block's pages are found while this one's are on
        their way — and placed into ONE resident ``RecordBatch``; returns
        when the last is there."""
        if source.record_bytes != self.record_bytes:
            raise ValueError(
                f"source holds {source.record_bytes}-byte records, this "
                f"sort takes {self.record_bytes}"
            )
        rows = self.block_rows(source.n_records)
        blocks = -(-source.n_records // rows)
        records = self.programs.empty(rows=blocks * rows)
        at = 0
        for block in source.blocks(rows):
            with obs.span("sort.h2d", bytes=block.nbytes):
                records = self.programs.place(
                    records, jax.device_put(block), np.int32(at)
                )
            at += rows
        with obs.span("engine.sync", what="h2d"):
            jax.block_until_ready(records)
        obs.metric_inc("sort.records", source.n_records)
        return StagedRecords(records, source.n_records, rows)

    def sort(self, staged: StagedRecords) -> SortedRecords:
        """Order the staged records' keys: (key lanes, row index) through
        one ``lax.sort``; the payload does not move yet."""
        with obs.span("sort.keys", rows=staged.records.size):
            perm = self.programs.sort_keys(
                staged.records, np.int32(staged.n_records),
                block_rows=staged.block_rows,
            )
            with obs.span("engine.sync", what="keys"):
                jax.block_until_ready(perm)
        return SortedRecords(self, staged, perm)


class MapReduceEngine:
    """Blocked map/shuffle/reduce on one device (mesh version in parallel/)."""

    # run_stream keeps at most this many folds in flight before blocking:
    # pipeline overlap without per-corpus RSS growth (each in-flight fold
    # pins its staged host block).
    STREAM_DISPATCH_DEPTH = 4
    # timed_run launches a stage on a GROUP of blocks between two syncs;
    # this is the device memory one group's staged lines and stage
    # intermediates may hold (_timed_group_blocks derives the group's
    # size from the config's shapes).
    TIMED_GROUP_BYTES = 384 << 20
    # One merge program takes a whole group's block tables, so how many it
    # takes is part of its shape (a multi-operand lax.sort: minutes to
    # compile on a TPU).  A job of a full group and more always merges a
    # full group's worth (the last, short group padded with empty tables);
    # a shorter job pads to the next power of two — of a larger base where
    # a full group passes 2^6 (_timed_group_blocks) — so all job sizes
    # together run at most this many merge programs a capacity.
    MERGE_RUNGS = 7
    # Configurations whose programs the process keeps (_programs_for),
    # least recently used out first: a daemon's handful of workloads and
    # shapes, a plan's stages.  An engine past it builds, as every engine
    # did before the programs were shared.
    PROGRAM_KEYS = 8
    # The record sort stages its transfers in blocks of about this many
    # bytes (RecordSort.block_rows rounds down to a power of two of rows),
    # so that H2D, the device, D2H and the file write can overlap; and it
    # keeps this many sorted blocks permuted and on their way to the host
    # ahead of the one being written.  The size is the HOST's: a block
    # that comes down is a fresh numpy array, and one under glibc's
    # largest mmap threshold (32 MB) is carved from memory the last one
    # left, where a larger one is mapped anew and pays a page fault every
    # 4 kB — on a v5e's host a job took 0.55 s at 16 MB, 0.68 at 32 MB and
    # 1.89 s at 64 MB and 128 MB (PERF.md section 6, PR 35).
    RECORD_BLOCK_BYTES = 16 << 20
    RECORD_BLOCKS_IN_FLIGHT = 4

    def __init__(
        self,
        cfg: EngineConfig = DEFAULT_CONFIG,
        map_fn: MapFn = wordcount_map,
        combine: str = "sum",
    ):
        self.cfg = cfg
        if cfg.trace:
            # API-level telemetry opt-in (the CLI's --trace-out does the
            # same enable + an export at exit); idempotent, shares one
            # process timeline with any tracer already enabled.
            obs.enable()
        # A configuration's first engine is where jax traces, lowers and
        # reads back its programs: a traced run records that too.
        obs.watch_programs()
        self.combine = combine  # user-facing semantics (host finalize)
        # The programs belong to the configuration, not to this engine:
        # the process builds them once a key (_programs_for).
        programs = _programs_for(
            (cfg, map_fn, combine),
            lambda: _build_programs(cfg, map_fn, combine),
        )
        # Scatters over the emit stream that this configuration's segment
        # combine issues (0 for sum/count: ops/reduce_stage.py).
        obs.metric_set("engine.combine_scatters", combine_scatters(combine))
        self.map_fn = programs.map_fn
        self._map = programs.map
        self._process = programs.process
        self._reduce = programs.reduce
        self._merge = programs.merge
        self._fold_block = programs.fold_block
        # Breaker-failover fold (run_checkpointed's on-CPU dispatch).
        self._fold_block_fallback = programs.fold_block_fallback
        # Streaming-segment executable (megakernel v2); None when the
        # kernel is off or segments are one block long.
        self._fold_segment = programs.fold_segment
        self._scan_blocks_into = programs.scan_blocks_into
        self._scan_blocks = programs.scan_blocks
        self._scan_blocks_batch = programs.scan_blocks_batch
        self._fused_kernel_on = programs.fused_kernel_on
        self._fused_demoted = programs.fused_demoted
        self._fused_stream_seg = programs.fused_stream_seg
        self._table_size = cfg.resolved_table_size

    def record_sort(self, record_bytes: int, key_bytes: int) -> RecordSort:
        """This engine's sort of fixed-width records by their leading key
        bytes (no combiner: every record in comes back out)."""
        return RecordSort(record_bytes, key_bytes)

    # ---------------------------------------------------------------- ingest

    def rows_from_lines(self, lines: Sequence[bytes]) -> np.ndarray:
        return bytes_ops.strings_to_rows(list(lines), self.cfg.line_width)

    def _blocks(self, rows):
        """Yield fixed-shape [block_lines, line_width] blocks, zero-padded,
        on the device.  ``rows`` is the corpus as one array, cut here, or
        an iterable of its host blocks of at most ``block_lines`` rows."""
        bl = self.cfg.block_lines
        host = rows
        if isinstance(rows, np.ndarray):
            host = (rows[i : i + bl] for i in range(0, max(rows.shape[0], 1), bl))
        for blk in host:
            # The span opens AFTER the pull (a wait for the reader is
            # engine.ingest.wait's) and closes BEFORE the yield: a generator
            # suspended inside it would bill the consumer's work to staging.
            with obs.span("engine.h2d", bytes=bl * blk.shape[1]):
                if blk.shape[0] < bl:
                    pad = np.zeros((bl - blk.shape[0], blk.shape[1]), np.uint8)
                    blk = np.concatenate([blk, pad]) if blk.size else pad
                staged = jnp.asarray(blk)
            yield staged

    def _read_ahead(self, blocks, group: int):
        """Host blocks of an ITERABLE corpus for ``timed_run``, read a
        group ahead of the device.

        All but the last block of the first group are read here, inline:
        a source that ends among them is a job of one group, and no thread
        is started for it.  From the group's last block on a reader thread
        runs up to ``group`` blocks ahead of the pulls
        (``loader.prefetch_blocks``: ``timed_run`` pulls a group at once,
        inside its merge stage, and nothing while the next group's map,
        process and reduce stages run — a queue of a whole group lets the
        file be read during all of them).  Same blocks, same order; a
        reader's error is raised at the pull that reaches it; closing this
        generator (``timed_run`` does, however it ends) closes the reader's
        and stops its thread.  A source with no block at all gives one
        empty block, as an array of no rows does.  The source must not
        reuse a block's memory: up to ``group`` of them wait in the queue.
        """
        from locust_tpu.io.loader import prefetch_blocks, read_spans

        source = read_spans(blocks)
        inline = max(1, group - 1)
        head = list(itertools.islice(source, inline))
        if len(head) < inline:
            yield from head or [np.zeros((0, self.cfg.line_width), np.uint8)]
            return
        yield from head
        del head
        yield from prefetch_blocks(source, depth=group)

    # ------------------------------------------------------------------- run

    def run(self, rows: np.ndarray) -> RunResult:
        """Fused per-block fold, one dispatch per block.

        Keeps overflow/distinct counters on device across the loop — no
        host sync until the end, so block dispatches pipeline asynchronously.
        """
        acc = KVBatch.empty(self._table_size, self.cfg.key_lanes)
        overflow = jnp.int32(0)
        max_distinct = jnp.int32(0)
        t0 = time.perf_counter()
        for blk in self._blocks(rows):
            acc, blk_overflow, distinct = self._fold_block(acc, blk)
            overflow = overflow + blk_overflow
            max_distinct = jnp.maximum(max_distinct, distinct)
        jax.block_until_ready(acc.key_lanes)
        total_ms = (time.perf_counter() - t0) * 1e3
        return self._finish(
            acc, max_distinct, int(overflow), StageTimes(0, total_ms, 0)
        )

    def prepare_blocks(self, rows: np.ndarray) -> jax.Array:
        """Pad + reshape a host row array into device-resident scan blocks.

        Staging is split from ``run_blocks`` so callers can overlap/amortize
        the host->device transfer — the reference's published stage timings
        likewise start AFTER its H2D memcpy (main.cu:402-408).
        """
        bl, w = self.cfg.block_lines, self.cfg.line_width
        n = rows.shape[0]
        nblocks = max(1, -(-n // bl))
        padded = np.zeros((nblocks * bl, w), dtype=np.uint8)
        padded[:n] = rows[:, :w]
        return jax.device_put(padded.reshape(nblocks, bl, w))

    def run_blocks(self, blocks: jax.Array) -> RunResult:
        """One-dispatch run over pre-staged ``[nblocks, block_lines, width]``."""
        t0 = time.perf_counter()
        acc0 = KVBatch.empty(self._table_size, self.cfg.key_lanes)
        acc, overflow, num = self._scan_blocks_into(acc0, blocks)
        num = int(num)  # host sync: the scan (and everything before) is done
        total_ms = (time.perf_counter() - t0) * 1e3
        return self._finish(acc, num, int(overflow), StageTimes(0, total_ms, 0))

    def run_batch(self, blocks: jax.Array) -> list[RunResult]:
        """One dispatch over a JOB-batched ``[njobs, nblocks, block_lines,
        width]`` stack: every job folds independently (vmapped scan) and
        the per-job tables/counters demultiplex back into one RunResult
        per job.  The serve tier's coalesced executable (docs/SERVING.md):
        compatible queued small jobs share this single compiled program
        instead of paying one dispatch (and one compile shape) each.
        Zero-filled job slots (batch padding) fold to empty tables.
        ``StageTimes`` carries the WHOLE batch's wall per job — per-job
        wall latency is the caller's (the daemon times submit->done).
        """
        t0 = time.perf_counter()
        acc, overflow, num = self._scan_blocks_batch(blocks)
        num = np.asarray(num)  # host sync: the batch is done
        overflow = np.asarray(overflow)
        total_ms = (time.perf_counter() - t0) * 1e3
        return [
            self._finish(
                KVBatch(
                    key_lanes=acc.key_lanes[j],
                    values=acc.values[j],
                    valid=acc.valid[j],
                ),
                int(num[j]),
                int(overflow[j]),
                StageTimes(0, total_ms, 0),
            )
            for j in range(blocks.shape[0])
        ]

    def run_fused(self, rows: np.ndarray) -> RunResult:
        """Whole-corpus run as a single device dispatch (lax.scan over blocks).

        Preferred for throughput: amortizes dispatch latency and lets XLA
        pipeline block processing.  Compiles once per number-of-blocks; pad
        the corpus externally to a fixed block count to reuse the executable.
        """
        return self.run_blocks(self.prepare_blocks(rows))

    def _timed_full_group(self) -> int:
        """Blocks of a FULL group of ``timed_run``: the configuration's
        alone, whatever the input (``_timed_group_blocks`` has the rule)."""
        cfg = self.cfg
        kv_row = 4 * cfg.key_lanes + 4 + 1  # key lanes, int32 value, valid
        per_block = (cfg.block_lines * cfg.line_width
                     + 3 * cfg.emits_per_block * kv_row)
        return max(1, self.TIMED_GROUP_BYTES // per_block)

    def _timed_group_blocks(self, nblocks: int) -> tuple[int, int]:
        """``(group, fan_in)`` of a job of ``nblocks`` blocks.

        ``group``: blocks ``timed_run`` launches between two syncs of one
        stage — as many as fit ``TIMED_GROUP_BYTES`` (a block's staged
        lines plus three ``KVBatch`` intermediates of ``emits_per_block``
        rows: map output, sorted batch, block table), at least one, at
        most the job.  ``fan_in``: block tables a merge program takes, the
        full group for a job of at least a full group, else the first of
        1, b, b^2, ... that holds the job (b = 2 at CLI defaults: the least
        base whose ``MERGE_RUNGS`` - 1 powers reach a full group), capped at
        the full group; the tables that are missing are empty ones.

        The budget is what a group HOLDS between syncs.  The merge's own
        working set is transient and not counted: when it runs only the
        block tables are left of the three intermediates, and its sort
        works on (capacity + fan_in x ``emits_per_block``) rows — at CLI
        defaults a job that ends at 2^20 rows peaked at 0.4 GB on a v5e,
        under what the per-block merges and their kept copy held.
        """
        full = self._timed_full_group()
        if nblocks >= full:
            return full, full
        base = 2
        while base ** (self.MERGE_RUNGS - 1) < full:
            base += 1
        fan_in = 1
        while fan_in < nblocks:
            fan_in *= base
        return max(1, nblocks), min(fan_in, full)

    def timed_run(self, rows) -> RunResult:
        """Per-stage timing parity with the reference's report (main.cu:405-468).

        ``rows`` is the corpus: a ``[lines, width]`` array, or an iterable
        of its ``[<= block_lines, width]`` host blocks in order
        (``io.loader.StreamingCorpus``), which is read a group AHEAD of the
        device by a reader thread (``_read_ahead``) and never held whole.
        Either way the job is the same blocks through the same programs:
        the group and the merge's fan-in are those of the block count
        (``_timed_group_blocks``), known once the first group is pulled.

        Stage-major over GROUPS of blocks (``_timed_group_blocks``): each
        stage's program is launched on every block of the group back to
        back, then the host waits ONCE, so the device stays fed inside a
        stage and a job pays four round trips a group, not five a block.
        A stage's time is still host clock from its first launch to its
        work being done.  Map, process and reduce run once a block; the
        cross-block merge runs once a GROUP — the running table and the
        group's block tables (padded with empty tables up to the job's
        fan-in, ``_timed_group_blocks``) through one ``merge_tables``
        program — and is accounted to the Process stage (it is a sort),
        matching where the reference spends that time (main.cu:447).
        ``run`` stays the one-program-per-block fold with no report.

        The table is exact at any vocabulary: it starts at
        ``cfg.resolved_table_size`` and, when a group's merge counted
        more distinct keys than it holds, grows to the capacity that
        holds them and merges that group again (``_regrow``); from the
        third group on it grows AHEAD of a group that, adding what the
        last one added, would pass it.
        """
        acc = KVBatch.empty(self._table_size, self.cfg.key_lanes)
        distinct = 0  # keys counted so far: the host's copy of max_distinct
        added = 0     # ... of which by the last group (the job's first left out)
        grows = 0
        merges = 0
        overflows = []
        max_distinct = jnp.int32(0)
        times = StageTimes()
        full = self._timed_full_group()
        blocks = self._blocks(
            rows if isinstance(rows, np.ndarray)
            else self._read_ahead(rows, full)
        )
        # obs spans shadow the t0..t4 boundaries exactly (each stage's one
        # sync is inside its span), so an exported timeline and the
        # reference-parity StageTimes report can never disagree.  The wait
        # is a child span: a stage's self time is the host launching, its
        # engine.sync the host waiting on the device.  Every launch lies
        # inside a stage span, and a stage's inputs are dropped once it has
        # launched, so a group holds two intermediates a block at a time.
        # The next group is staged while the device works off this one's
        # merge: its engine.h2d spans lie inside engine.stage.merge.  The
        # first pull is a full group's worth: fewer blocks than that are the
        # whole job, whose count then gives the group and the fan-in.
        try:
            staged = list(itertools.islice(blocks, full))
            group, fan_in = self._timed_group_blocks(len(staged))
            while staged:
                n = len(staged)
                t0 = time.perf_counter()
                with obs.span("engine.stage.map", blocks=n):
                    mapped = [self._map(blk) for blk in staged]
                    del staged
                    with obs.span("engine.sync", what="map"):
                        jax.block_until_ready(mapped)  # locust: noqa[R003] stage-timing boundary (reference parity), once a stage a GROUP: the sync IS the measurement
                t1 = time.perf_counter()
                overflows += [blk_overflow for _, blk_overflow in mapped]
                with obs.span("engine.stage.process", blocks=n):
                    batches = [self._process(kv) for kv, _ in mapped]
                    del mapped
                    with obs.span("engine.sync", what="process"):
                        jax.block_until_ready(batches)  # locust: noqa[R003] stage-timing boundary (reference parity), once a stage a GROUP: the sync IS the measurement
                t2 = time.perf_counter()
                with obs.span("engine.stage.reduce", blocks=n):
                    tables = [self._reduce(kv) for kv in batches]
                    del batches
                    with obs.span("engine.sync", what="reduce"):
                        jax.block_until_ready(tables)  # locust: noqa[R003] stage-timing boundary (reference parity), once a stage a GROUP: the sync IS the measurement
                t3 = time.perf_counter()
                with obs.span("engine.stage.merge",
                              blocks=n, tables=fan_in) as stage:
                    if n < fan_in:
                        # A short group (a job's last, or a job under a full
                        # group): padded to the one shape the job's merges have.
                        tables += [
                            KVBatch.empty(tables[0].size, self.cfg.key_lanes)
                        ] * (fan_in - n)
                    if distinct:  # past the first group: acc is not empty
                        # A text adds fewer new keys a group as it goes on: a
                        # table that would not hold what the LAST group added
                        # once more is grown before this one merges into it.
                        # (The first group's count says nothing: it holds every
                        # common key, so it is left out of ``added``.)
                        ahead = rows_to_hold(acc.size, distinct + added)
                        if ahead > acc.size:
                            with obs.span("engine.table.grow", from_rows=acc.size,
                                          to_rows=ahead, distinct=distinct + added,
                                          blocks_redone=0):
                                acc = _grow_table(acc, ahead)
                            grows += 1
                    start, seen = acc, max_distinct
                    acc, max_distinct = self._merge(start, tuple(tables), seen)
                    staged = list(itertools.islice(blocks, group))
                    with obs.span("engine.sync", what="merge"):
                        jax.block_until_ready(acc)  # locust: noqa[R003] stage-timing boundary (reference parity), once a stage a GROUP: the sync IS the measurement
                        # Computed by the merge just waited for: the read
                        # is a scalar copy, no further wait on the device.
                        now = int(max_distinct)
                    redone = now > start.size
                    if redone:
                        # The merge dropped its tail, but counted every key:
                        # the group is merged again from the table it started
                        # with, grown to hold them.
                        acc, max_distinct = self._regrow(start, seen, tables, n, now)
                        grows += 1
                    stage.set(merges=1 + redone)
                    merges += 1 + redone
                    added = now - distinct if distinct else 0
                    distinct = now
                    del tables, start
                t4 = time.perf_counter()
                times.map_ms += (t1 - t0) * 1e3
                times.process_ms += (t2 - t1) * 1e3 + (t4 - t3) * 1e3
                times.reduce_ms += (t3 - t2) * 1e3
        finally:
            # However the job ends, the source is closed: a reader
            # thread stops, and its queued blocks are dropped.
            blocks.close()

        # One read a JOB, of values the map syncs have already waited for:
        # the copies cost no device op, and the total stays exact.
        with obs.span("engine.sync", what="overflow"):
            overflow = sum(int(v) for v in jax.device_get(overflows))
        obs.metric_set("engine.table_rows", acc.size)
        obs.metric_inc("engine.table_grows", grows)
        obs.metric_inc("engine.merges", merges)
        return self._finish(acc, max_distinct, overflow, times)

    def _regrow(self, start: KVBatch, seen: jax.Array,
                tables: list[KVBatch], blocks: int, distinct: int):
        """Merge a group of ``timed_run`` again into a table that holds it.

        ``start`` is the table as the group found it, ``seen`` the distinct
        count before the group, ``tables`` the group's block tables (padded
        to the fan-in, ``blocks`` of them real), ``distinct`` what the
        group's merge counted: the true count of table + group, so ONE
        step — ``core/kv.TABLE_GROWTH``-fold as often as it takes to hold
        ``distinct`` — and one merge give the exact table.  A handful of
        capacities whatever the vocabulary, each merge program compiled
        once and kept by the persistent cache.  Returns the table and its
        distinct count (on the device).
        """
        to_rows = rows_to_hold(start.size, distinct)
        with obs.span("engine.table.grow", from_rows=start.size, to_rows=to_rows,
                      distinct=distinct, blocks_redone=blocks):
            acc, max_distinct = self._merge(
                _grow_table(start, to_rows), tuple(tables), seen
            )
            with obs.span("engine.sync", what="merge"):
                jax.block_until_ready(acc)  # locust: noqa[R003] the redone group's one wait: the stage's clock ends with its work
        return acc, max_distinct

    def run_lines(self, lines: Sequence[bytes]) -> RunResult:
        return self.run(self.rows_from_lines(lines))

    def run_stream(
        self,
        blocks,
        checkpoint_dir: str | None = None,
        every: int = 8,
        fingerprint: str | None = None,
    ) -> RunResult:
        """Fold an ITERABLE of ``[<=block_lines, width]`` host row blocks.

        Bounded-memory ingest for corpora that don't fit RAM: pair
        with ``io.loader.StreamingCorpus`` and only one
        file window plus the accumulator table are ever resident.  Device
        counters stay on device across blocks (same pipelining as
        ``run``); blocks shorter than ``cfg.block_lines`` are zero-padded
        so every fold reuses the one compiled executable.

        With ``checkpoint_dir`` + ``fingerprint`` (e.g.
        ``StreamingCorpus.fingerprint()``, which hashes file identity
        without reading it fully), snapshots land every ``every`` blocks
        exactly as in ``run_checkpointed``; a resume re-READS but does not
        re-process already-folded blocks.

        Zero-stall executor (docs/DESIGN.md): the fold accumulator is
        DONATED into each dispatch (updated in place), blocks stage
        through a reusable host buffer ring instead of per-block
        allocations, and snapshots ride the background writer — the hot
        loop's only synchronous work is the bounded-inflight
        backpressure.  Stall accounting lands in ``RunResult.stream``.
        """
        from locust_tpu.io.loader import prefetch_blocks
        blocks = prefetch_blocks(blocks)  # overlap host reads with folds
        bl, w = self.cfg.block_lines, self.cfg.line_width
        acc = KVBatch.empty(self._table_size, self.cfg.key_lanes)
        overflow = jnp.int32(0)
        max_distinct = jnp.int32(0)
        start_block = 0
        pump = None
        if checkpoint_dir is not None:
            if every < 1:
                raise ValueError(f"checkpoint every must be >= 1, got {every}")
            if fingerprint is None:
                raise ValueError(
                    "run_stream needs an explicit corpus fingerprint to "
                    "checkpoint (e.g. StreamingCorpus.fingerprint())"
                )
            fingerprint = f"{fingerprint}:{self.cfg!r}:{self.combine}:" + getattr(
                self.map_fn, "__name__", str(self.map_fn)
            )
            os.makedirs(checkpoint_dir, exist_ok=True)
            state_path = os.path.join(checkpoint_dir, "state.npz")
            start_block, overflow, max_distinct, acc = self._load_state(
                state_path, fingerprint, acc
            )
            pump = _CheckpointPump(
                self, state_path, fingerprint, self.cfg.async_checkpoint
            )
        if self._fold_segment is not None:
            # Megakernel v2 persistent streaming: segments of
            # _fused_stream_seg staged blocks per kernel launch, table
            # VMEM-resident across each segment (fold_segment docstring).
            return self._run_stream_fused(
                blocks, acc, overflow, max_distinct, start_block, pump,
                every,
            )
        ring = _StagingRing(self.STREAM_DISPATCH_DEPTH + 1, bl, w)

        stall_ms = 0.0
        flush_ms = 0.0
        t0 = time.perf_counter()
        # Bound the async dispatch depth: without a sync, the host loop
        # races ahead of the device and EVERY staged block stays
        # referenced by its in-flight fold — RSS then grows with corpus
        # size, which is exactly what a streaming fold must not do
        # (measured: +55MB at 16MB vs +110MB at 64MB before this bound).
        # Blocking on the fold K steps back keeps K blocks of pipeline
        # overlap while releasing older staging buffers — and proves the
        # staging ring's slot about to be re-filled is no longer read by
        # any in-flight fold (_StagingRing).
        import collections as _collections

        inflight: _collections.deque = _collections.deque()
        # Start one before start_block: an exhausted/empty iterator then
        # advances nothing, writes no snapshot, and finishes with the
        # RESTORED counters instead of zeros.
        i = start_block - 1
        last_mark = start_block
        try:
            for i, blk in enumerate(blocks):
                if i < start_block:  # resume: re-read, don't re-fold
                    continue
                # Span covers staging + dispatch, NOT device completion
                # (folds are async; completion shows up as the later
                # stream.stall events) — docs/OBSERVABILITY.md.
                with obs.span("stream.block", i=i):
                    acc, blk_overflow, distinct = self._fold_block(
                        acc, jnp.asarray(ring.stage(blk))
                    )
                overflow = overflow + blk_overflow
                max_distinct = jnp.maximum(max_distinct, distinct)
                inflight.append(blk_overflow)
                if len(inflight) > self.STREAM_DISPATCH_DEPTH:
                    t_sync = time.perf_counter()
                    jax.block_until_ready(inflight.popleft())  # locust: noqa[R003] bounded-inflight backpressure: sync caps device queue depth, overlap stays STREAM_DISPATCH_DEPTH deep
                    sync_ms = (time.perf_counter() - t_sync) * 1e3
                    stall_ms += sync_ms
                    obs.event("stream.stall", block=i, ms=round(sync_ms, 3))
                    obs.metric_observe("stream.stall_ms", sync_ms)
                if pump is not None and (i + 1) % every == 0:
                    pump.mark(acc, i + 1, overflow, max_distinct)
                    last_mark = i + 1
            # Final-generation mark — only when folds ran past the last
            # cadence mark (a cadence-aligned corpus otherwise writes
            # its largest array twice back-to-back).
            if pump is not None and i + 1 > last_mark:
                pump.mark(acc, i + 1, overflow, max_distinct)
            if pump is not None:
                # The final generation must be durable before returning
                # (resume contract); this is the async mode's only wait.
                flush_ms = pump.finish()
        finally:
            if pump is not None:
                pump.close()
        jax.block_until_ready(acc.key_lanes)
        total_ms = (time.perf_counter() - t0) * 1e3
        obs.metric_inc("stream.blocks", max(0, i + 1 - start_block))
        stream = {
            "blocks": max(0, i + 1 - start_block),
            "backpressure_stall_ms": round(stall_ms, 3),
            "total_ms": round(total_ms, 3),
        }
        if pump is not None:
            stream["ckpt"] = dict(
                pump.stats(), every=every, final_flush_ms=round(flush_ms, 3)
            )
        return self._finish(
            acc, max_distinct, int(overflow), StageTimes(0, total_ms, 0),
            stream=stream,
        )

    def _run_stream_fused(
        self, blocks, acc, overflow, max_distinct, start_block: int,
        pump, every: int,
    ) -> RunResult:
        """run_stream's persistent-kernel tail (megakernel v2).

        Blocks stage into ``[seg_blocks * block_lines, width]`` segment
        buffers (a ring sized like _StagingRing) and each FULL segment
        folds in ONE ``_fold_segment`` dispatch — the kernel table stays
        VMEM-resident across the whole segment, so the per-block
        acc->settle->acc HBM round-trip and table flush amortize by
        ``seg_blocks``.  The
        trailing partial segment zero-pads its unfilled blocks (zero
        lines tokenize to nothing, the _blocks padding contract), so one
        executable serves every segment.  Checkpoint marks land at
        segment boundaries — which ARE block boundaries — once ``every``
        blocks have elapsed since the last mark, and resume re-forms
        segments from the restored block cursor: the fold is a pure
        function of the line multiset, so the regrouped resume stays
        byte-identical (tests/test_fused_fold.py crash-resume pin).
        Backpressure/stall accounting mirror run_stream at segment
        granularity.
        """
        import collections as _collections

        from locust_tpu.parallel.shuffle import normalize_round_chunk

        bl, w = self.cfg.block_lines, self.cfg.line_width
        seg = self._fused_stream_seg
        bufs = itertools.cycle(
            np.zeros((seg * bl, w), np.uint8)
            for _ in range(self.STREAM_DISPATCH_DEPTH + 1)
        )
        state = {
            "acc": acc, "overflow": overflow,
            "max_distinct": max_distinct, "segments": 0,
            "stall_ms": 0.0, "last_mark": start_block,
        }
        flush_ms = 0.0
        inflight: _collections.deque = _collections.deque()
        t0 = time.perf_counter()

        def dispatch(buf: np.ndarray, n_filled: int, seg_end: int) -> None:
            if n_filled < seg:
                buf[n_filled * bl:, :] = 0  # ring reuse: clear stale tail
            with obs.span("stream.block", i=seg_end - 1,
                          seg_blocks=n_filled):
                acc2, blk_overflow, distinct = self._fold_segment(
                    state["acc"], jnp.asarray(buf)
                )
            state["acc"] = acc2
            state["overflow"] = state["overflow"] + blk_overflow
            state["max_distinct"] = jnp.maximum(
                state["max_distinct"], distinct
            )
            state["segments"] += 1
            inflight.append(blk_overflow)
            if len(inflight) > self.STREAM_DISPATCH_DEPTH:
                t_sync = time.perf_counter()
                jax.block_until_ready(inflight.popleft())  # locust: noqa[R003] bounded-inflight backpressure: sync caps device queue depth, overlap stays STREAM_DISPATCH_DEPTH deep
                sync_ms = (time.perf_counter() - t_sync) * 1e3
                state["stall_ms"] += sync_ms
                obs.event("stream.stall", block=seg_end - 1,
                          ms=round(sync_ms, 3))
                obs.metric_observe("stream.stall_ms", sync_ms)
            if pump is not None and seg_end - state["last_mark"] >= every:
                pump.mark(state["acc"], seg_end, state["overflow"],
                          state["max_distinct"])
                state["last_mark"] = seg_end

        i = start_block - 1
        fill = 0
        cur: np.ndarray | None = None
        try:
            for i, blk in enumerate(blocks):
                if i < start_block:  # resume: re-read, don't re-fold
                    continue
                if fill == 0:
                    cur = next(bufs)
                normalize_round_chunk(
                    blk, bl, w, out=cur[fill * bl:(fill + 1) * bl]
                )
                fill += 1
                if fill == seg:
                    dispatch(cur, fill, i + 1)
                    fill = 0
            if fill:
                dispatch(cur, fill, i + 1)
            if pump is not None and i + 1 > state["last_mark"]:
                pump.mark(state["acc"], i + 1, state["overflow"],
                          state["max_distinct"])
            if pump is not None:
                flush_ms = pump.finish()
        finally:
            if pump is not None:
                pump.close()
        jax.block_until_ready(state["acc"].key_lanes)
        total_ms = (time.perf_counter() - t0) * 1e3
        obs.metric_inc("stream.blocks", max(0, i + 1 - start_block))
        stream = {
            "blocks": max(0, i + 1 - start_block),
            "backpressure_stall_ms": round(state["stall_ms"], 3),
            "total_ms": round(total_ms, 3),
            "fused": {
                "formulation": "stream",
                "seg_blocks": seg,
                "segments": state["segments"],
                "interpret": jax.default_backend() != "tpu",
            },
        }
        if pump is not None:
            stream["ckpt"] = dict(
                pump.stats(), every=every, final_flush_ms=round(flush_ms, 3)
            )
        return self._finish(
            state["acc"], state["max_distinct"], int(state["overflow"]),
            StageTimes(0, total_ms, 0), stream=stream, fused_kernel="stream",
        )

    def _load_state(self, state_path: str, fingerprint: str, acc: KVBatch):
        """Restore (start_block, overflow, max_distinct, acc) from a
        matching snapshot; pass-through fresh state otherwise.  Shared by
        ``run_stream`` and ``run_checkpointed``."""
        start_block = 0
        overflow = jnp.int32(0)
        max_distinct = jnp.int32(0)
        if os.path.exists(state_path):
            try:
                with np.load(state_path) as z:
                    if str(z["fingerprint"]) == fingerprint:
                        start_block = int(z["next_block"])
                        overflow = jnp.int32(int(z["overflow"]))
                        max_distinct = jnp.int32(int(z["max_distinct"]))
                        # jnp.array(copy=True), NOT asarray: on CPU, jax
                        # zero-copy aliases host numpy buffers, and the
                        # first resumed fold DONATES the accumulator —
                        # donating numpy-owned memory corrupts the heap
                        # (XLA frees what it never allocated; observed as
                        # nondeterministic segfaults under pytest).  The
                        # copy puts the restored table in jax-owned
                        # memory the donation machinery may reclaim.
                        acc = KVBatch(
                            key_lanes=jnp.array(z["key_lanes"], copy=True),
                            values=jnp.array(z["values"], copy=True),
                            valid=jnp.array(z["valid"], copy=True),
                        )
                        logger.info(
                            "resuming from checkpoint at block %d (%s)",
                            start_block,
                            state_path,
                        )
                    else:
                        logger.warning(
                            "checkpoint at %s belongs to a different run; "
                            "starting fresh",
                            state_path,
                        )
            except Exception as e:  # noqa: BLE001 - truncated/garbled npz
                # A corrupt snapshot costs a clean restart, never a crash
                # and never wrong counts (ISSUE 1; the mesh engines'
                # ShardedCheckpoint additionally falls back to a previous
                # generation — this single-file engine just starts over).
                logger.warning(
                    "checkpoint at %s is unreadable (%s: %s); starting "
                    "fresh", state_path, type(e).__name__, e,
                )
                start_block = 0
                overflow = jnp.int32(0)
                max_distinct = jnp.int32(0)
        return start_block, overflow, max_distinct, acc

    @staticmethod
    def _save_state(state_path, acc, next_block, overflow, max_distinct,
                    fingerprint) -> None:
        """One atomically-replaced npz: table + cursor + counters can never
        tear apart.  The tmp name keeps the .npz suffix (np.savez appends
        it otherwise).  Runs on the fold loop (sync mode) or the
        background writer (cfg.async_checkpoint) — the np.asarray
        conversions wait on the marked fold's readiness and copy
        device->host, then finalize_snapshot publishes atomically
        (io.ckpt_write / io.checkpoint chaos sites)."""
        tmp = state_path + ".tmp.npz"
        np.savez_compressed(
            tmp,
            key_lanes=np.asarray(acc.key_lanes),
            values=np.asarray(acc.values),
            valid=np.asarray(acc.valid),
            next_block=np.int64(next_block),
            overflow=np.asarray(overflow),
            max_distinct=np.asarray(max_distinct),
            fingerprint=np.str_(fingerprint),
        )
        finalize_snapshot(tmp, state_path, generation=int(next_block))

    # ---------------------------------------------------------- checkpointing

    def run_checkpointed(
        self,
        rows: np.ndarray,
        checkpoint_dir: str,
        every: int = 8,
        breaker=None,
    ) -> RunResult:
        """Block-granular fold with crash-resumable snapshots.

        The reference's entire persistence story is "map wrote /tmp/out.txt,
        re-run reduce from it" (main.cu:428-441, SURVEY.md §5).  This is the
        TPU-native upgrade: every ``every`` blocks, the bounded accumulator
        table, the block cursor and the running counters land in ONE npz
        replaced atomically — table and cursor can never tear apart, so a
        crash at any instant resumes without double-folding blocks.  A
        re-run with a different corpus/config fingerprint starts fresh.
        Snapshots are a few MB (table_size rows) regardless of corpus size.

        ``breaker`` (a ``backend.CircuitBreaker``) adds mid-job failover:
        every primary dispatch runs through ``backend.guarded_dispatch``
        (the ``backend.dispatch`` chaos site); a failed dispatch reloads
        the last durable checkpoint — the donated accumulator may have
        died with the dispatch, the snapshot cannot — and once the
        breaker is OPEN the fold continues on the CPU fallback device
        from that checkpoint.  When the half-open probe readmits the
        primary, the fold migrates back.  Fallback-side failures are
        REAL failures and re-raise (there is no second fallback).
        """
        from locust_tpu.io.serde import fingerprint_corpus

        if every < 1:
            raise ValueError(f"checkpoint every must be >= 1, got {every}")
        os.makedirs(checkpoint_dir, exist_ok=True)
        state_path = os.path.join(checkpoint_dir, "state.npz")
        fingerprint = fingerprint_corpus(
            rows,
            cfg=repr(self.cfg),
            combine=self.combine,
            map_fn=getattr(self.map_fn, "__name__", str(self.map_fn)),
        )

        # Counters stay DEVICE scalars between snapshots: no per-block host
        # sync, so dispatches pipeline exactly like run().
        start_block, overflow, max_distinct, acc = self._load_state(
            state_path,
            fingerprint,
            KVBatch.empty(self._table_size, self.cfg.key_lanes),
        )
        pump = _CheckpointPump(
            self, state_path, fingerprint, self.cfg.async_checkpoint
        )

        t0 = time.perf_counter()
        on_cpu = False
        cpu_dev = None  # resolved once at first failover, then cached
        try:
            while True:
                dispatch_died = None
                i = start_block - 1
                last_mark = start_block
                for i, blk in enumerate(self._blocks(rows)):
                    if i < start_block:
                        continue
                    if breaker is not None:
                        acc, on_cpu, cpu_dev = self._breaker_place(
                            breaker, acc, on_cpu, cpu_dev
                        )
                    # Only the FOLD dispatch is failover-retryable —
                    # checkpoint-writer errors re-raised by pump.mark
                    # must stay loud (retrying them from the same
                    # checkpoint would loop forever).
                    try:
                        if on_cpu:
                            blk = jax.device_put(blk, cpu_dev)
                            # _fold_block_fallback, not _fold_block: the
                            # fused kernel must not re-trace for the
                            # fallback device (stock_fold docstring).
                            acc, blk_overflow, distinct = (
                                self._fold_block_fallback(acc, blk)
                            )
                        elif breaker is not None:
                            acc, blk_overflow, distinct = (
                                backend_mod.guarded_dispatch(
                                    breaker,
                                    partial(self._fold_block, acc, blk),
                                    block=i, backend="primary",
                                )
                            )
                        else:
                            acc, blk_overflow, distinct = self._fold_block(
                                acc, blk
                            )
                    except Exception as e:
                        if breaker is None or on_cpu:
                            raise  # no breaker, or the FALLBACK died: real
                        dispatch_died = e
                        break
                    overflow = overflow + blk_overflow
                    max_distinct = jnp.maximum(max_distinct, distinct)
                    if (i + 1) % every == 0:
                        pump.mark(acc, i + 1, overflow, max_distinct)
                        last_mark = i + 1
                if dispatch_died is None:
                    if i + 1 > last_mark:  # skip cadence-aligned double write
                        pump.mark(acc, i + 1, overflow, max_distinct)
                    pump.finish()  # final generation durable before returning
                    break
                if (
                    breaker.state() != "closed"
                    and backend_mod.cpu_fallback_device() is None
                ):
                    # Tripped breaker and nothing to fail over TO (a
                    # TPU-only jax process): going around again would
                    # busy-loop re-reading the same snapshot against a
                    # dead primary forever — re-raise loud instead (the
                    # checkpoint survives for a later resume).  state(),
                    # not allow(): allow() would consume the half-open
                    # probe token this path never dispatches.
                    raise dispatch_died
                # Primary dispatch died (guarded_dispatch recorded the
                # failure).  The donated accumulator is suspect; the last
                # checkpoint is not: flush any pending async write
                # best-effort, reload, and go around — on the primary
                # while the breaker still allows it, on the CPU fallback
                # once it is open.
                try:
                    pump.finish()
                except Exception as e:  # noqa: BLE001 - reload decides
                    logger.warning(
                        "checkpoint flush during failover failed (%s); "
                        "resuming from the last durable generation", e,
                    )
                start_block, overflow, max_distinct, acc = self._load_state(
                    state_path, fingerprint,
                    KVBatch.empty(self._table_size, self.cfg.key_lanes),
                )
        finally:
            pump.close()
        total_ms = (time.perf_counter() - t0) * 1e3
        return self._finish(
            acc, max_distinct, int(overflow), StageTimes(0, total_ms, 0)
        )

    def _breaker_place(self, breaker, acc, on_cpu: bool, cpu_dev):
        """Move the fold accumulator to whichever device the breaker
        currently makes eligible; returns (acc, on_cpu, cpu_dev).  The
        device is resolved once and cached by the caller (the hot loop
        must not pay a local_devices lookup per block); the migration
        copies through ``jax.device_put`` (never a donation), so the
        reloaded-from-checkpoint table stays jax-owned either way."""
        primary_ok = breaker.allow()
        if primary_ok and on_cpu:
            # Half-open probe (or a closed breaker after recovery): the
            # next dispatch tries the primary again from the live state.
            acc = jax.device_put(acc)
            obs.event("backend.failover", direction="cpu_to_primary")
            return acc, False, cpu_dev
        if not primary_ok and not on_cpu:
            if cpu_dev is None:
                cpu_dev = backend_mod.cpu_fallback_device()
            if cpu_dev is None:
                return acc, False, None  # nothing to fail over to
            acc = jax.device_put(acc, cpu_dev)
            obs.event("backend.failover", direction="primary_to_cpu")
            logger.warning(
                "backend breaker open: fold continuing on the CPU "
                "fallback from the last checkpoint"
            )
            return acc, True, cpu_dev
        return acc, on_cpu, cpu_dev

    def _finish(self, acc, num_segments, overflow, times,
                stream: dict | None = None,
                fused_kernel: str | None = None) -> RunResult:
        if os.environ.get("LOCUST_DEBUG_CHECKS"):
            # Opt-in invariant sweep on the result table (the sanitizer
            # analog, SURVEY.md §5): valid-prefix layout + NUL-padded keys.
            # hasht-family tables are slot-ordered (valid entries
            # scattered by hash, not compacted to a prefix) — the layout
            # invariant is a property of the SORT folds, not of
            # correctness.
            from locust_tpu.config import HASHT_FAMILY
            from locust_tpu.utils.checks import validate_batch

            validate_batch(
                acc, expect_compact=self.cfg.sort_mode not in HASHT_FAMILY
            )
        with obs.span("engine.finalize", rows=acc.size):
            num = int(num_segments)
        truncated = num > acc.size
        if truncated:
            logger.warning(
                "distinct keys (%d) exceeded table capacity (%d); tail "
                "dropped — this path holds a table of fixed size (the "
                "default is min(65536, max(one block's emits, 4096))): "
                "raise table_size, or take the default path (timed_run), "
                "whose table grows with what it sees",
                num,
                acc.size,
            )
        if overflow:
            # Reference: "WARN: Exceeded emit limit" printf (main.cu:141-144).
            logger.warning(
                "WARN: Exceeded emit limit — %d tokens beyond %d-per-line cap dropped",
                overflow,
                self.cfg.emits_per_line,
            )
        if fused_kernel is None and self._fused_kernel_on:
            fused_kernel = "batch"
        return RunResult(
            table=acc,
            num_segments=min(num, acc.size),
            overflow_tokens=overflow,
            truncated=truncated,
            times=times,
            combine=self.combine,
            stream=stream,
            fused_kernel=fused_kernel,
            fused_demoted=self._fused_demoted,
        )
