"""Distributed shuffle: hash-partition + ICI all-to-all + per-shard reduce.

This is the component the reference never actually shipped: its multi-node
data plane is "write /tmp/out.txt, let an out-of-repo script move it"
(reference MapReduce/src/main.cu:421-446; the master is MISSING, SURVEY.md
C12), and its reduce stage doesn't even re-sort the merged input (Q6).

TPU-native design (BASELINE.json north star):

  1. Each device runs the local pipeline on its line shard — map, then a
     LOCAL combine (sort + segment-reduce).  Pre-aggregation is the classic
     MapReduce combiner: hot keys ("the") collapse to ONE (key, partial)
     entry per device before they ever hit the network, which is also what
     defuses the skewed-shuffle problem (SURVEY.md §7.3.3).
  2. Keys hash-partition across devices (fold_hash % n); entries scatter
     into equal-capacity per-destination bins (XLA all-to-all needs equal
     splits; capacity = fair share x skew_factor, overflow counted).
  3. One ``lax.all_to_all`` over the mesh axis — the ICI shuffle.
  4. Each device sorts + segment-reduces what it received: its hash shard
     of the global table, key-sorted within the shard.
  5. Scalar stats (overflow counters, distinct counts) combine via psum.

Deterministic: every stage is a sort or a segment op; shard contents are
fully determined by the hash function and key order.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import math
import threading
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from locust_tpu import obs
from locust_tpu.config import HASHT_FAMILY, EngineConfig
from locust_tpu.core import packing
from locust_tpu.core.kv import KVBatch, grow_table, rows_to_hold
from locust_tpu.engine import _programs_for
from locust_tpu.io.snapshot import AsyncCheckpointWriter, finalize_snapshot
from locust_tpu.ops.map_stage import wordcount_map
from locust_tpu.ops.hash_table import fold_into, reduce_into
from locust_tpu.parallel.mesh import DATA_AXIS

logger = logging.getLogger("locust_tpu")


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def sized_bins(total_rows: int, n_bins: int, skew_factor: float) -> int:
    """Default per-destination bin capacity: a fair share of ``total_rows``
    across ``n_bins``, padded for skew, TPU-lane aligned.  The ONE copy of
    the sizing rule used by every shuffle-shaped engine (flat,
    hierarchical, inverted index)."""
    return _round_up(
        max(1, math.ceil(total_rows / n_bins * skew_factor)), 8
    )


def normalize_round_chunk(chunk, lpr: int, width: int, out=None):
    """Validate + zero-pad one round's host chunk to ``[lpr, width]``.

    The single copy of the chunk contract shared by every round loop
    (flat/hierarchical engines, inverted index): wider-than-config rows
    are a caller error (silently slicing them would drop tokens), more
    rows than a round holds likewise; short/narrow chunks zero-pad.

    ``out`` (a caller-owned ``[lpr, width]`` uint8 buffer) makes the
    normalization allocation-free: the chunk is copied in and the
    remainder zeroed, and ``out`` is returned — the engine's staging
    ring (engine.run_stream) feeds these straight into ``device_put``,
    so the caller must not touch the buffer again until the consuming
    dispatch completed (jax on CPU aliases host buffers zero-copy).
    """
    import numpy as np

    chunk = np.asarray(chunk, dtype=np.uint8)
    if chunk.ndim != 2:
        raise ValueError(f"round chunk must be 2-D, got shape {chunk.shape}")
    if chunk.shape[1] > width:
        raise ValueError(
            f"round chunk rows are {chunk.shape[1]} bytes wide but "
            f"cfg.line_width={width}; ingest with the same width"
        )
    if chunk.shape[0] > lpr:
        raise ValueError(
            f"round chunk has {chunk.shape[0]} rows, more than its round "
            f"capacity of {lpr} (engine block_lines / mesh lines_per_round);"
            " size stream blocks to match"
        )
    if out is not None:
        if out.shape != (lpr, width) or out.dtype != np.uint8:
            raise ValueError(
                f"out buffer must be uint8 [{lpr}, {width}], got "
                f"{out.dtype} {out.shape}"
            )
        n, w = chunk.shape
        out[:n, :w] = chunk
        out[n:, :] = 0
        out[:n, w:] = 0
        return out
    if chunk.shape[0] < lpr or chunk.shape[1] < width:
        padded = np.zeros((lpr, width), np.uint8)
        padded[: chunk.shape[0], : chunk.shape[1]] = chunk
        chunk = padded
    return chunk


def checkpoint_digest(arrays: dict) -> str:
    """Content sha256 over a snapshot's payload entries, key-ordered.

    Covers dtype + shape + raw bytes of every entry, so bit-rot anywhere
    in the archive — not just zip-structure damage — fails validation.
    """
    import hashlib

    h = hashlib.sha256()
    for k in sorted(arrays):
        v = np.asarray(arrays[k])
        h.update(k.encode())
        h.update(str(v.dtype).encode())
        h.update(str(v.shape).encode())
        h.update(v.tobytes())
    return h.hexdigest()


class CheckpointInvalid(RuntimeError):
    """A snapshot file failed validation (corrupt/truncated/mismatched)."""


class ShardedCheckpoint:
    """Per-process atomic-npz snapshot protocol for sharded engine state.

    The ONE implementation behind both mesh engines' checkpoint/resume
    (the RoundStats principle: a protocol fix cannot silently diverge
    between them).  A snapshot holds the gathered accumulator + shuffle
    backlog, the round cursor, the run fingerprint, and whatever extra
    host counters the engine passes — restored as-is, so each engine
    keeps its own counter schema while sharing load/replace/atomicity.

    Durability (ISSUE 1): every snapshot embeds a content sha256 and the
    PREVIOUS generation is kept as ``<state>.prev.npz``.  ``load``
    VALIDATES before trusting — a truncated archive, a flipped bit, or a
    wrong-run fingerprint makes that candidate unusable and load falls
    back to the previous good generation, then to a clean fresh start;
    it never crashes the run and never resumes wrong state.  Chaos
    coverage: tests/test_faults.py corrupts snapshots both directly and
    via the ``io.checkpoint`` fault site.

    Asynchronous writes (``async_writes=True``, wired from
    ``cfg.async_checkpoint``): the round loop hands the snapshot to the
    bounded background writer (io/snapshot.AsyncCheckpointWriter,
    latest-wins when lapped) instead of stalling on the device->host
    gather + compressed npz write; the writer gathers lazily (the device
    buffers behind a round's tables stay valid — mesh folds are not
    donated).  SINGLE-PROCESS ONLY: on multi-process pods the request is
    downgraded to synchronous writes, for two reasons — the gather is a
    collective (process_allgather) that must issue on the main thread in
    round order on every process, and latest-wins writers are PER
    PROCESS, so under load skew they would publish DIFFERENT generations
    per process and a resume would start processes at different rounds
    (collective deadlock).  The synchronous path keeps every process
    writing every cadence in round-loop lockstep.  The on-disk format,
    checksum, ``.prev`` rotation and atomic replace are identical in
    both modes; the owning loop (drive_checkpointed_rounds) flushes
    before returning so the final generation is always durable.
    """

    _RESERVED = (
        "fingerprint", "next_round", "checksum",
        "acc_key_lanes", "acc_values", "acc_valid",
        "left_key_lanes", "left_values", "left_valid",
    )

    def __init__(self, checkpoint_dir: str, fingerprint: str, sharding,
                 async_writes: bool = False):
        import os

        os.makedirs(checkpoint_dir, exist_ok=True)
        self.path = os.path.join(
            checkpoint_dir, f"state.p{jax.process_index()}.npz"
        )
        self.prev_path = self.path + ".prev.npz"
        self.fingerprint = fingerprint
        self.sharding = sharding
        self._writer = (
            AsyncCheckpointWriter(name="sharded-ckpt-writer")
            if async_writes and jax.process_count() == 1
            else None
        )

    def load(self):
        """Returns ``(start_round, extras, acc, leftover)`` from the newest
        VALID matching snapshot (current, else previous generation), or
        None (missing / different run / all candidates corrupt)."""
        import os

        for path, label in ((self.path, "checkpoint"),
                            (self.prev_path, "previous-generation checkpoint")):
            if not os.path.exists(path):
                continue
            try:
                return self._load_validated(path)
            except CheckpointInvalid as e:
                # Fall through to the previous generation / fresh start:
                # a corrupt snapshot must cost re-computation, never a
                # crash and never wrong counts.
                logger.warning("%s at %s unusable (%s); falling back",
                               label, path, e)
        return None

    def _load_validated(self, path: str):
        """One candidate: open, checksum-verify, fingerprint-match, restore.
        Any failure — unreadable archive, missing keys, content digest
        mismatch, foreign fingerprint — raises CheckpointInvalid."""
        try:
            with np.load(path) as z:
                host = {k: z[k] for k in z.files}
        except Exception as e:  # noqa: BLE001 - truncated/garbled zip, bad pickle header, ...
            raise CheckpointInvalid(f"unreadable npz: {type(e).__name__}: {e}")
        try:
            fingerprint = str(host.pop("fingerprint"))
            recorded = str(host.pop("checksum"))
            payload = dict(host)
            start_round = int(host.pop("next_round"))
            acc_h = KVBatch(
                key_lanes=host.pop("acc_key_lanes"),
                values=host.pop("acc_values"),
                valid=host.pop("acc_valid"),
            )
            left_h = KVBatch(
                key_lanes=host.pop("left_key_lanes"),
                values=host.pop("left_values"),
                valid=host.pop("left_valid"),
            )
        except KeyError as e:
            raise CheckpointInvalid(f"snapshot missing entry {e}")
        if checkpoint_digest(payload) != recorded:
            raise CheckpointInvalid("content sha256 mismatch (bit-rot?)")
        if fingerprint != self.fingerprint:
            raise CheckpointInvalid("belongs to a different run")
        acc = _scatter_batch_from_host(acc_h, self.sharding)
        leftover = _scatter_batch_from_host(left_h, self.sharding)
        extras = {k: v for k, v in host.items()}
        logger.info(
            "resuming from checkpoint at round %d (%s)", start_round, path
        )
        return start_round, extras, acc, leftover

    def snapshot(self, next_round: int, acc, leftover, **extras) -> None:
        """One atomically-replaced npz: table, backlog, cursor and
        counters can never tear apart.  The outgoing generation survives
        as ``.prev.npz`` so one corrupted write never strands the run.
        With ``async_writes`` the work rides the background writer (see
        class docstring for the multi-process collective caveat)."""
        from functools import partial

        if self._writer is None:
            self._write(
                next_round, _gather_batch_host(acc),
                _gather_batch_host(leftover), extras,
            )
            return
        # Single-process by construction (__init__ downgrades pods to
        # sync).  Mesh folds are not donated, so this round's device
        # buffers stay valid while the loop moves on: the writer gathers
        # lazily (device_get waits on the round's readiness off the hot
        # loop).
        self._writer.submit(
            next_round,
            partial(
                self._gather_and_write, next_round, acc, leftover, extras
            ),
        )

    def _gather_and_write(self, next_round, acc, leftover, extras) -> None:
        self._write(
            next_round, _gather_batch_host(acc), _gather_batch_host(leftover),
            extras,
        )

    def _write(self, next_round, acc_h: KVBatch, left_h: KVBatch,
               extras: dict) -> None:
        payload = dict(
            acc_key_lanes=acc_h.key_lanes,
            acc_values=acc_h.values,
            acc_valid=acc_h.valid,
            left_key_lanes=left_h.key_lanes,
            left_values=left_h.values,
            left_valid=left_h.valid,
            next_round=np.int64(next_round),
            **extras,
        )
        tmp = self.path + ".tmp.npz"
        np.savez_compressed(
            tmp,
            fingerprint=np.str_(self.fingerprint),
            checksum=np.str_(checkpoint_digest(payload)),
            **payload,
        )
        # Rotation + io.ckpt_write chaos hook + atomic replace +
        # io.checkpoint damage hook, shared with the engine's writer.
        finalize_snapshot(
            tmp, self.path, prev_path=self.prev_path, generation=next_round
        )

    def flush(self) -> None:
        """Wait for the last submitted generation to land durably;
        re-raises writer errors.  No-op in synchronous mode."""
        if self._writer is not None:
            self._writer.flush()

    def close(self) -> None:
        """Stop the background writer (best-effort flush, never raises).
        Safe in ``finally``; no-op in synchronous mode."""
        if self._writer is not None:
            self._writer.close()

    def writer_stats(self) -> dict | None:
        return None if self._writer is None else self._writer.stats()


def stream_checkpoint_fingerprint(
    fingerprint: str | None, checkpoint_dir: str | None, identity: dict
) -> str | None:
    """The run_stream fingerprint rule, one copy: checkpointing requires
    an explicit corpus fingerprint, and the engine's identity is bound in
    so no other engine/mesh/pipeline can resume the snapshot."""
    if checkpoint_dir is not None and fingerprint is None:
        raise ValueError(
            "run_stream needs an explicit corpus fingerprint to "
            "checkpoint (e.g. StreamingCorpus.fingerprint())"
        )
    if fingerprint is not None:
        fingerprint = f"{fingerprint}:{identity}"
    return fingerprint


def drive_checkpointed_rounds(
    chunk_iter,
    body,
    round_stats: "RoundStats",
    ckpt: "ShardedCheckpoint | None",
    snapshot,
    checkpoint_every: int,
    start_round: int,
) -> None:
    """The loop half of the snapshot protocol, one copy for every round
    engine: resume-skip of already-folded rounds, stats flush BEFORE each
    snapshot (snapshots must persist correct counters), the snapshot
    cadence, the final-snapshot rule (only when rounds ran past the
    last snapshot), and the async-writer finalization — flush (surface
    writer errors, make the final generation durable) on the normal
    path, close in ``finally`` so the writer thread never outlives the
    run.  ``body(chunk)`` folds one round and pushes its stats; a body
    that raises leaves the last snapshot intact (no stale state).
    """
    if checkpoint_every < 1:
        raise ValueError(
            f"checkpoint_every must be >= 1, got {checkpoint_every}"
        )
    last_snapshot = nrounds = start_round
    try:
        for r, chunk in enumerate(chunk_iter):
            if r < start_round:  # resume: re-read, don't re-fold
                continue
            nrounds = r + 1
            body(chunk)
            if ckpt is not None and (r + 1) % checkpoint_every == 0:
                round_stats.flush()
                snapshot(r + 1)
                last_snapshot = r + 1
        round_stats.flush()
        if ckpt is not None and last_snapshot != nrounds:
            snapshot(nrounds)
        if ckpt is not None:
            ckpt.flush()
    finally:
        if ckpt is not None:
            ckpt.close()


class RoundStats:
    """Device-side stats accumulation with periodic host syncs.

    The shared half of the drain/sync protocol (used by
    DistributedMapReduce and apps.DistributedInvertedIndex): per-round
    replicated stat vectors fold together ON DEVICE via ``merge_fn`` and
    reach the host only every ``every`` rounds, when ``on_sync(host_row)``
    folds them into host counters and polices invariants.  Keeping this in
    one place means a protocol fix (what syncs, when, what raises) cannot
    silently diverge between the engines.
    """

    def __init__(self, merge_fn, on_sync, every: int, fetch_fn=None):
        if every < 1:
            raise ValueError(f"stats_sync_every must be >= 1, got {every}")
        # merge_fn should be jitted ONCE by its owner (per engine, not per
        # run) so repeated runs reuse the compiled combiner.  fetch_fn
        # overrides the device->host pull for stats that are NOT fully
        # replicated (the hierarchical engine's slice-varying stack spans
        # non-addressable devices on multi-process pods; its fetch runs a
        # replicating gather first).
        self._merge = merge_fn
        self._on_sync = on_sync
        self._every = every
        self._fetch = fetch_fn or jax.device_get
        self._acc = None
        self._rounds = 0

    def push(self, stats) -> None:
        self._acc = stats if self._acc is None else self._merge(self._acc, stats)
        self._rounds += 1
        if self._rounds >= self._every:
            self.flush()

    def flush(self) -> None:
        if self._acc is None:
            return
        st = self._fetch(self._acc)
        self._acc = None
        self._rounds = 0
        self._on_sync(st)


def _sort_by_bin(bucket: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Rows grouped by destination bin: ``(sb, sidx)``, the sorted bucket
    (int32) and the row that comes there — ONE single-key ``lax.sort``
    that carries only a row index, so a bin's rows keep their order.
    Whatever rides along is gathered by ``sidx`` afterwards — an
    ``int32`` value (``partition_to_bins``) or a whole record
    (``partition_words_to_bins``) — and never widens the sort."""
    idx = jnp.arange(bucket.shape[0], dtype=jnp.int32)
    sb_u, sidx = jax.lax.sort((bucket, idx), num_keys=1)
    return sb_u.astype(jnp.int32), sidx


def partition_words_to_bins(
    words: jax.Array,
    bucket: jax.Array,
    n_bins: int,
    bin_capacity: int,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """``partition_to_bins``' grouping with a WIDE payload: whole records
    (``words``, uint32 ``[N, W]`` — W = 25 for gensort's 100 bytes) into
    ``[n_bins, capacity, W]`` bins, with the row each slot was filled
    from (int32 ``[n_bins, capacity]``, -1 = a dead slot) and the rows
    each bin was SENT (int32 ``[n_bins]``).

    ``bucket`` is uint32 ``[N]`` in ``[0, n_bins]``, ``n_bins`` marking a
    row that goes nowhere (padding).  The same ONE single-key sort groups
    the rows by bin and keeps a bin's rows in ROW order; a bin is then a
    contiguous run of the sorted row indices — found by a binary search,
    cut out by a slice — and its records are read straight from where
    they lie: one gather of whole records, where the KV partition gathers
    its narrow rows and then scatters them.  A dead slot holds an
    arbitrary record.  Nothing is dropped in silence: where a count
    passes ``bin_capacity`` the caller redoes the partition with bins
    that hold (parallel/record_sort.py)."""
    sb, sidx = _sort_by_bin(bucket)
    starts = jnp.searchsorted(
        sb, jnp.arange(n_bins + 1, dtype=jnp.int32), side="left"
    ).astype(jnp.int32)
    counts = starts[1:] - starts[:-1]
    # Padded so that a bin's slice never runs off the end (a slice that
    # would is shifted back by dynamic_slice, not cut).
    run = jnp.pad(sidx, (0, bin_capacity))
    row = jnp.stack([
        jax.lax.dynamic_slice(run, (starts[b],), (bin_capacity,))
        for b in range(n_bins)
    ])                                                         # [B, C]
    live = jnp.arange(bin_capacity, dtype=jnp.int32)[None, :] < counts[:, None]
    return words[row], jnp.where(live, row, -1), counts


def partition_to_bins(
    batch: KVBatch,
    n_bins: int,
    bin_capacity: int,
    bucket: jax.Array | None = None,
    leftover_capacity: int = 0,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array, KVBatch]:
    """Scatter a batch into ``[n_bins, capacity]`` by key hash.

    ``bucket`` overrides the destination-bin assignment (uint32 ``[N]`` in
    ``[0, n_bins)``) — used by range partitioners (apps/sample_sort.py);
    default is the hash partition.  The value is ONE ``int32`` a row; a
    wide payload (whole records) takes the same grouping through
    ``partition_words_to_bins``.

    Live entries that do not fit their bin land in a compacted LEFTOVER
    buffer of ``leftover_capacity`` rows instead of being dropped — the
    caller re-shuffles them in a follow-up round (the SURVEY §7.3.3
    "overflow round" mitigation for skew; the reference's analogous
    WARN-and-drop at main.cu:141-144 is a bug, not a contract).  With
    ``leftover_capacity=0`` overspill is dropped and counted, the
    reference-style behavior.

    Returns (lanes [B,C,L], values [B,C], valid [B,C], overflow [],
    leftover KVBatch[leftover_capacity]); overflow counts live entries that
    fit neither their bin nor the leftover buffer — true data loss.
    """
    lanes, values, valid = batch.key_lanes, batch.values, batch.valid
    n, n_lanes = lanes.shape
    if bucket is None:
        bucket = (packing.fold_hash(lanes) % n_bins).astype(jnp.uint32)
    bucket = jnp.where(valid, bucket, n_bins)  # invalid -> sentinel bin

    # Group by bin: single-key sort carrying only a row index, then gather.
    # Within-bin order is arbitrary — the post-shuffle merge re-sorts by key
    # (local_step), so no multi-key sort is needed here.
    sb, sidx = _sort_by_bin(bucket)
    slanes = lanes[sidx]
    svals = values[sidx]
    svalid = sb < n_bins

    # Rank within bin = index - bin start offset.
    ones = jnp.ones_like(sb)
    counts = jax.ops.segment_sum(ones, sb, num_segments=n_bins + 1)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(counts)[:-1]])
    within = jnp.arange(n, dtype=jnp.int32) - offsets[sb]

    ok = svalid & (within < bin_capacity)
    spill = svalid & (within >= bin_capacity)
    dump = n_bins * bin_capacity
    dest = jnp.where(ok, sb * bin_capacity + within, dump)

    flat = n_bins * bin_capacity
    out_lanes = (
        jnp.zeros((flat + 1, n_lanes), lanes.dtype).at[dest].set(slanes)[:flat]
    ).reshape(n_bins, bin_capacity, n_lanes)
    out_vals = (
        jnp.zeros((flat + 1,), svals.dtype).at[dest].set(svals)[:flat]
    ).reshape(n_bins, bin_capacity)
    out_valid = (
        jnp.zeros((flat + 1,), bool).at[dest].set(ok)[:flat]
    ).reshape(n_bins, bin_capacity)

    # Compact spilled entries into the leftover buffer (same scatter trick).
    lcap = leftover_capacity
    lrank = jnp.cumsum(spill.astype(jnp.int32)) - 1
    kept = spill & (lrank < lcap)
    ldest = jnp.where(kept, lrank, lcap)
    leftover = KVBatch(
        key_lanes=jnp.zeros((lcap + 1, n_lanes), lanes.dtype)
        .at[ldest]
        .set(slanes)[:lcap],
        values=jnp.zeros((lcap + 1,), svals.dtype).at[ldest].set(svals)[:lcap],
        valid=jnp.zeros((lcap + 1,), bool).at[ldest].set(kept)[:lcap],
    )
    overflow = jnp.sum((spill & (lrank >= lcap)).astype(jnp.int32))
    return out_lanes, out_vals, out_valid, overflow, leftover


def build_shuffle_step(
    cfg: EngineConfig,
    map_fn,
    combine: str,
    n_bins: int,
    bin_capacity: int,
    shard_capacity: int,
    leftover_capacity: int,
    max_drains: int,
    shuffle_axis: str,
    stat_axes,
    fused_preagg: bool = False,
):
    """The per-device feed+drain body shared by the flat and hierarchical
    engines (one copy, so the drain/stats protocol cannot diverge).

    ``shuffle_axis`` carries the all-to-all; ``stat_axes`` is the axis
    tuple the stats/backlog reduce over — for the flat engine it is the
    (only) shuffle axis, for the hierarchical engine it is the intra-slice
    axis ONLY, so nothing in the round path ever crosses slices: the
    backlog psum stays intra-slice (each slice takes its own drain trip
    count — valid SPMD, since every collective inside the loop body is
    intra-slice too) and the stats vector leaves the step varying over the
    slice axis for the host to fold at sync points.

    Stats vector layout (shared): [emit_ovf_sum, shuf_ovf_sum,
    distinct_sum, backlog, distinct_max, drains], each reduced over
    ``stat_axes``.

    The caller is responsible for passing a NORMALIZED (map_fn, combine)
    pair (reduce_stage.normalize_combine): the shard carry and merge here
    re-apply ``combine`` across levels, which is only correct for
    associative combiners.

    ``fused_preagg`` (megakernel v2 mesh-native mode): replace
    map_fn + local combiner with ONE Pallas fused_block_preagg launch per
    shard — tokenize, dedupe, and pre-aggregate the shard's lines in VMEM
    so the [lines, emits, key_width] token tensor never touches HBM.  The
    caller gates this on :func:`fused_mesh_eligible` (TPU-only: the
    interpret kernel never runs inside a CPU mesh program — the check_vma
    segfault class, CLAUDE.md) and must disable check_vma on the wrapping
    shard_map (jax's vma machinery breaks inside the Pallas re-trace).
    The kernel output pads up to the local combiner's capacity contract (output size == raw emit count) and a
    residual overflow re-folds the shard's block through the stock path
    via lax.cond — bit-identity to "hasht" carries over shard-by-shard
    (the settlement argument, ops/pallas/fused_fold.py docstring).
    """
    n_lanes = cfg.key_lanes

    def shuffle_round(table_in: KVBatch, acc: KVBatch, leftover: KVBatch):
        """One partition + all-to-all + merge; shared by feed and drain.

        The carried backlog joins at the PARTITION (whose internal
        grouping sort is single-key — cheap), not the full local sort:
        a key present both in the backlog and in new emits is sent
        twice and merges at its destination's segment reduce.
        """
        send_lanes, send_vals, send_valid, shuf_ovf, new_leftover = (
            partition_to_bins(
                KVBatch.concat(table_in, leftover),
                n_bins,
                bin_capacity,
                leftover_capacity=leftover_capacity,
            )
        )
        # The ICI shuffle: one all-to-all per tensor.
        recv_lanes = jax.lax.all_to_all(send_lanes, shuffle_axis, 0, 0)
        recv_vals = jax.lax.all_to_all(send_vals, shuffle_axis, 0, 0)
        recv_valid = jax.lax.all_to_all(send_valid, shuffle_axis, 0, 0)

        received = KVBatch(
            key_lanes=recv_lanes.reshape(-1, n_lanes),
            values=recv_vals.reshape(-1),
            valid=recv_valid.reshape(-1),
        )
        # Merge what we received with our carried shard, re-reduce.
        # fold_into dispatches sort vs the "hasht" sort-free fold (no
        # collectives inside, so each shard branches its exactness
        # ladder independently under shard_map).
        new_acc, distinct = fold_into(
            acc, received, shard_capacity, combine, cfg.sort_mode
        )
        # The backlog rides psum over stat_axes so every device in the
        # shuffle group sees the same value — which is what lets the drain
        # loop run ON DEVICE: the group takes one lax.while_loop trip
        # count and its collectives stay in lockstep.
        backlog = jax.lax.psum(
            jnp.sum(new_leftover.valid.astype(jnp.int32)), stat_axes
        )
        return new_acc, new_leftover, shuf_ovf, distinct, backlog

    def local_step(lines: jax.Array, acc: KVBatch, leftover: KVBatch):
        """Per-device body (runs under shard_map): feed + on-device drain.

        The drain loop used to live on the HOST,
        costing one blocking device_get per feed round even when the
        backlog was empty — serializing dispatch on high-latency
        remote-TPU links.  Folding it into lax.while_loop makes the
        whole feed-plus-drain one device dispatch; the host only syncs
        stats every ``stats_sync_every`` rounds.
        """
        # Local combiner: same capacity contract either way (output size ==
        # kv.size, the shape partition_to_bins was sized for); partition is
        # order-agnostic, so neither hasht's slot-ordered table nor the
        # passthrough's raw rows need grouping.  The hasht family here
        # uses combine_or_passthrough: aggregation at this site is an
        # OPTIMIZATION (every destination re-reduces), so when probing
        # fails under a distinct-heavy load the fallback is an O(n)
        # compaction, not a sort — worst case = 2 probe sweeps + one
        # compaction, full win kept on duplicate-heavy (WordCount-like)
        # blocks.  "hasht-mxu" carries its combine-scatter spelling into
        # the combiner's probe rounds too (scatter_impl_for).
        if fused_preagg:
            # Mesh-native megakernel (v2): ONE Pallas launch does
            # tokenize + dedupe + pre-aggregate for this shard's lines;
            # the kernel table + residual ARE the local combiner output
            # (every destination re-reduces, so per-tile residual
            # duplicates merge downstream exactly like any duplicate
            # key rows).  interpret=False unconditionally: the caller's
            # eligibility gate guarantees a TPU backend here.
            from locust_tpu.ops.pallas.fused_fold import (
                fused_block_preagg,
            )

            ktab, kresid, emit_ovf, bad = fused_block_preagg(
                lines, cfg, interpret=False
            )
            pre = KVBatch.concat(ktab, kresid)
            cap = lines.shape[0] * cfg.emits_per_line
            fused_table = KVBatch.concat(
                pre, KVBatch.empty(cap - pre.size, n_lanes)
            )

            def stock_table(_):
                from locust_tpu.ops.hash_table import (
                    combine_or_passthrough,
                    scatter_impl_for,
                )

                kv, _ovf = map_fn(lines, cfg)  # same tokenize overflow
                return combine_or_passthrough(
                    kv, combine, probes=2,
                    scatter_impl=scatter_impl_for(cfg.sort_mode),
                )

            # Residual overflow: re-fold this shard's block through the
            # stock path — exact either way, and the overflow counter is
            # the kernel's under both branches (identical tokenize
            # formulation, fused_block_preagg docstring).
            local_table = jax.lax.cond(
                bad, stock_table, lambda _: fused_table, 0
            )
            return _shuffle_and_drain(local_table, emit_ovf, acc, leftover)
        kv, emit_ovf = map_fn(lines, cfg)
        if cfg.sort_mode in HASHT_FAMILY:
            from locust_tpu.ops.hash_table import (
                combine_or_passthrough,
                scatter_impl_for,
            )

            local_table = combine_or_passthrough(
                kv, combine, probes=2,
                scatter_impl=scatter_impl_for(cfg.sort_mode),
            )
        else:
            local_table = reduce_into(kv, kv.size, combine, cfg.sort_mode)[0]
        return _shuffle_and_drain(local_table, emit_ovf, acc, leftover)

    def _shuffle_and_drain(
        local_table: KVBatch, emit_ovf, acc: KVBatch, leftover: KVBatch
    ):
        """The step's combiner-independent tail: feed the local table
        into the shuffle, drain the backlog on device, stack stats —
        one copy shared by the stock and fused-preagg combiner paths."""
        acc, leftover, shuf_ovf, distinct, backlog = shuffle_round(
            local_table, acc, leftover
        )
        zero_table = KVBatch.empty(local_table.size, n_lanes)

        def cond(state):
            _, _, _, _, backlog, drains = state
            return (backlog > 0) & (drains < max_drains)

        def body(state):
            acc, leftover, shuf_ovf, _, _, drains = state
            acc, leftover, so, distinct, backlog = shuffle_round(
                zero_table, acc, leftover
            )
            return (acc, leftover, shuf_ovf + so, distinct, backlog, drains + 1)

        acc, leftover, shuf_ovf, distinct, backlog, drains = jax.lax.while_loop(
            cond,
            body,
            (acc, leftover, shuf_ovf, distinct, backlog, jnp.int32(0)),
        )
        # Truncation is a PER-SHARD event: distinct keys arriving at one
        # device beyond its table capacity are dropped there (mirror of
        # RunResult.truncated, engine._finish).  pmax surfaces the worst
        # shard's pre-slice distinct count.  psum/pmax over stat_axes make
        # the vector identical within the shuffle group; the caller's
        # out_spec decides whether that is fully replicated (flat) or
        # slice-varying (hierarchical).  backlog is already reduced;
        # nonzero after max_drains means the emits_per_block invariant was
        # violated (host raises at the next stats sync).
        stats = jnp.stack(
            [
                jax.lax.psum(emit_ovf, stat_axes),
                jax.lax.psum(shuf_ovf, stat_axes),
                jax.lax.psum(distinct, stat_axes),
                backlog,
                jax.lax.pmax(distinct, stat_axes),
                drains,
            ]
        )
        return acc, leftover, stats

    return local_step


# Across-round elementwise merge for the shared stats layout: overflows and
# drains ADD, distinct/backlog take the LAST round's value, worst-shard
# distinct takes the MAX.  Operates on [..., 6]-shaped stacks so the
# hierarchical engine's per-slice rows fold with the same code.
def merge_stats_vectors(a, b):
    a = a.reshape(-1, 6)
    b = b.reshape(-1, 6)
    return jnp.stack(
        [a[:, 0] + b[:, 0], a[:, 1] + b[:, 1], b[:, 2], b[:, 3],
         jnp.maximum(a[:, 4], b[:, 4]), a[:, 5] + b[:, 5]],
        axis=1,
    ).reshape(-1)


def _fused_mesh_gate(
    cfg: EngineConfig, map_fn, combine: str, engine: str
) -> tuple[bool, bool]:
    """Shared fused-mode construction gate for the mesh engines.

    Returns ``(kernel_on, demoted)``; logs the demotion ONCE at
    construction — outside any traced code — naming the engine and the
    reason, so operators can tell which kernel will serve their jobs
    (ISSUE 19: the fused->hasht fallback used to be silent).
    """
    if cfg.sort_mode != "fused":
        return False, False
    from locust_tpu.ops.pallas.fused_fold import fused_mesh_eligible

    ok, why = fused_mesh_eligible(cfg, map_fn, combine)
    if not ok:
        logger.info(
            "%s mesh sort_mode='fused': kernel not engaged — %s "
            "(results carry fused_demoted=True)", engine, why,
        )
    return ok, not ok


def _kv_spec(axis) -> KVBatch:
    """A KVBatch whose every leaf is sharded over ``axis``."""
    return KVBatch(key_lanes=P(axis), values=P(axis), valid=P(axis))


def _build_mesh_step(
    map_fn,
    combine: str,
    cfg: EngineConfig,
    mesh: jax.sharding.Mesh,
    axis: str,
    bin_capacity: int,
    leftover_capacity: int,
    max_drains: int,
    fused_preagg: bool,
    shard_capacity: int,
):
    """The flat mesh's step program at one shard capacity — the capacity
    is a shape of the compiled fold, so a run that grows runs one such
    program a capacity, each named ``jit_local_step``.  ``map_fn`` and
    ``combine`` are the NORMALIZED pair; nothing here names an engine
    (``_MeshPrograms``)."""
    local_step = build_shuffle_step(
        cfg,
        map_fn,
        combine,
        n_bins=mesh.shape[axis],
        bin_capacity=bin_capacity,
        shard_capacity=shard_capacity,
        leftover_capacity=leftover_capacity,
        max_drains=max_drains,
        shuffle_axis=axis,
        stat_axes=(axis,),
        fused_preagg=fused_preagg,
    )
    kv_spec = _kv_spec(axis)
    # Stats are reduced over the mesh's only axis, so they leave
    # shard_map REPLICATED (out_spec P()): every process can read them
    # without touching non-addressable shards.
    return jax.jit(
        jax.shard_map(
            local_step,
            mesh=mesh,
            in_specs=(P(axis), kv_spec, kv_spec),
            out_specs=(kv_spec, kv_spec, P()),
            # Off only with the Pallas kernel engaged (TPU-only,
            # fused_mesh_eligible): it cannot trace under the check.
            check_vma=not fused_preagg,
        )
    )


def _build_shard_grower(mesh: jax.sharding.Mesh, axis: str, shard_capacity: int):
    """The program that gives every shard of a table empty rows up to
    ``shard_capacity`` (``core/kv.grow_table`` a shard)."""

    def grow_shards(shard: KVBatch) -> KVBatch:
        return grow_table(shard, shard_capacity)

    kv_spec = _kv_spec(axis)
    return jax.jit(
        jax.shard_map(
            grow_shards, mesh=mesh, in_specs=(kv_spec,), out_specs=kv_spec,
        )
    )


class _ByCapacity:
    """A program a shard capacity, made by ``build(capacity)`` the first
    time one is asked for, under a lock: two engines of one record that
    ask at once get one program."""

    def __init__(self, build):
        self._build = build
        self._lock = threading.Lock()
        self._made: dict[int, object] = {}

    def __call__(self, shard_capacity: int):
        with self._lock:
            if shard_capacity not in self._made:
                self._made[shard_capacity] = self._build(shard_capacity)
            return self._made[shard_capacity]


@dataclasses.dataclass(frozen=True)
class _MeshPrograms:
    """The jitted programs of ONE flat-mesh configuration, the process's
    record of it (``engine._programs_for``).  The shard capacity is no
    part of the configuration's key: a job that grows finds its second
    step program in the SAME record, so it spends one key and an
    eviction never splits a configuration's programs."""

    step: _ByCapacity      # capacity -> jit_local_step at that capacity
    grower: _ByCapacity    # capacity -> every shard grown TO that capacity
    stats_merge: Callable  # merge_stats_vectors, on device across rounds


def _build_mesh_programs(map_fn, combine: str, *config) -> _MeshPrograms:
    """The record of one flat-mesh configuration: ``config`` is
    ``_build_mesh_step``'s arguments between the NORMALIZED (map_fn,
    combine) and the capacity.  Nothing here names an engine, so the
    record outlives every engine that took it and a dead engine is no
    reference cycle; nothing is traced here either."""
    _cfg, mesh, axis = config[:3]
    return _MeshPrograms(
        step=_ByCapacity(
            functools.partial(_build_mesh_step, map_fn, combine, *config)
        ),
        grower=_ByCapacity(functools.partial(_build_shard_grower, mesh, axis)),
        stats_merge=jax.jit(merge_stats_vectors),
    )


class DistributedMapReduce:
    """Mesh-parallel MapReduce: shard_map(local pipeline + all-to-all).

    Processes the corpus in rounds of ``n_devices * cfg.block_lines`` lines;
    each device carries its hash shard of the result table across rounds
    (consistent hash partitioning makes the per-shard merge local — no
    cross-device traffic outside the one all-to-all per round).

    The shards GROW with what they see, all together (``shard_capacity``
    left at None): a shard's capacity is a compiled shape of one SPMD
    program, so when the worst shard counted more distinct keys than a
    shard holds, every shard grows to the capacity that holds them
    (``core/kv.rows_to_hold``, the default path's rule) and the rounds
    folded since the last table known to be whole are folded again from
    it — the table is exact at any vocabulary (``_run_rounds``).  An
    explicit ``shard_capacity=`` is a fixed bound, reported loudly
    (``DistributedResult.truncated``) when passed.
    """

    def __init__(
        self,
        mesh: jax.sharding.Mesh,
        cfg: EngineConfig,
        axis_name: str = DATA_AXIS,
        map_fn=wordcount_map,
        combine: str = "sum",
        skew_factor: float = 2.0,
        on_overflow: str = "retry",
        shard_capacity: int | None = None,
        bin_capacity: int | None = None,
    ):
        if on_overflow not in ("retry", "drop"):
            raise ValueError(f"on_overflow must be 'retry' or 'drop', got {on_overflow!r}")
        # As MapReduceEngine does: the step programs' trace, lower and
        # load become engine.program.* spans of the round or the growth
        # step they happen in.
        obs.watch_programs()
        self.mesh = mesh
        self.cfg = cfg
        self.axis = axis_name
        self.map_fn = map_fn
        self.combine = combine
        self.on_overflow = on_overflow
        self.n_dev = mesh.shape[axis_name]
        # Per-destination bin capacity: fair share of the local table,
        # padded for skew, TPU-lane aligned.  The all-to-all always moves
        # FULL bins (XLA needs equal splits), so the default — sized for
        # the worst case of emits_per_block DISTINCT keys per device — is
        # mostly padding once the local combiner has collapsed a typical
        # corpus's emits.  Callers that know their per-block vocabulary
        # can pass a much smaller ``bin_capacity`` to shrink the wire
        # volume ~proportionally: in "retry" mode underestimates cost
        # extra drain rounds, never data (docs/DESIGN.md "shuffle sizing").
        if bin_capacity is not None and bin_capacity < 1:
            raise ValueError(f"bin_capacity must be >= 1, got {bin_capacity}")
        self.bin_capacity = (
            _round_up(int(bin_capacity), 8)
            if bin_capacity is not None
            else sized_bins(cfg.emits_per_block, self.n_dev, skew_factor)
        )
        # Result-table rows per device (its hash shard of the global table).
        # Decoupled from the per-round receive volume (n_dev * bin_capacity,
        # one floor of the default) so a long corpus can accumulate a
        # vocabulary far larger than one round's traffic; the OTHER floor
        # is this device's fair share of cfg.resolved_table_size (+ skew),
        # so an explicitly raised table_size carries over to the mesh
        # engines instead of silently truncating at the emits-derived
        # size (fuzz finding, r4).  The default is where a run STARTS:
        # its shards grow past it together (_run_rounds).  An explicit
        # capacity is a fixed bound; exceeding it is reported via
        # DistributedResult.truncated.
        self.grows = shard_capacity is None
        self.shard_capacity = (
            shard_capacity
            if shard_capacity is not None
            else max(
                self.n_dev * self.bin_capacity,
                sized_bins(cfg.resolved_table_size, self.n_dev, skew_factor),
            )
        )
        if self.shard_capacity < 1:
            raise ValueError(f"shard_capacity must be >= 1, got {self.shard_capacity}")
        # Carried backlog of entries whose destination bin was full; they
        # re-enter the shuffle next round ("retry" mode).  emits_per_block
        # bounds one round's distinct keys, and run() drains the backlog to
        # zero between rounds, so this never overflows (see run()).
        self.leftover_capacity = cfg.emits_per_block if on_overflow == "retry" else 0
        axis = axis_name

        self.max_drain_rounds = 2 + -(-cfg.emits_per_block // self.bin_capacity)

        # "count" lowers to emit-1 + sum so the shard carry and merge are
        # associative across rounds (reduce_stage.normalize_combine);
        # self.combine stays the user semantic for the host finalize.
        from locust_tpu.ops.reduce_stage import normalize_combine

        norm_map_fn, norm_combine = normalize_combine(map_fn, combine)
        # Checkpoints fingerprint the NORMALIZED map identity: a "count"
        # table written by the pre-normalization merge (different, broken
        # semantics) must not resume under the fixed one.
        self._norm_map_name = getattr(
            norm_map_fn, "__name__", str(norm_map_fn)
        )
        # sort_mode="fused" on the mesh (megakernel v2): run the Pallas
        # kernel per shard under shard_map when eligible; otherwise fold
        # as plain hasht with an EXPLICIT demotion — one construction
        # log + fused_demoted on every result (ISSUE 19 bugfix: the
        # fallback used to be silent).  Eligibility identifies the RAW
        # map_fn + user combine, like the single-device engine.
        self._fused_kernel_on, self.fused_demoted = _fused_mesh_gate(
            cfg, map_fn, combine, engine="flat"
        )
        # The programs belong to the configuration, not to this engine
        # (engine._programs_for, as MapReduceEngine's do): a process's
        # second engine of an equal configuration — the CLI makes one a
        # job — takes the SAME jit objects, so its first round traces,
        # lowers and reads back nothing.  The key is everything the step
        # closes over or reads while traced; the RAW (map_fn, combine)
        # stand for the normalized pair they determine ("count" makes its
        # wrapper anew each time); the shard capacity is no part of it
        # (_MeshPrograms).  The builder names no engine.
        config = (cfg, mesh, axis, self.bin_capacity, self.leftover_capacity,
                  self.max_drain_rounds, self._fused_kernel_on)
        self._programs: _MeshPrograms = _programs_for(
            ("mesh", map_fn, combine, *config),
            lambda: _build_mesh_programs(norm_map_fn, norm_combine, *config),
        )
        self._stats_merge = self._programs.stats_merge
        # Step programs put on THIS engine by hand (``dmr._step = f``: a
        # test's dying or counting step), by shard capacity; _step_at asks
        # here first, and nothing here reaches the process's record.
        self._own_steps: dict[int, object] = {}

    # ------------------------------------------------------------------ api

    @property
    def lines_per_round(self) -> int:
        return self.n_dev * self.cfg.block_lines

    def empty_table(self) -> KVBatch:
        """Global (sharded) empty accumulator: one shard per device."""
        return KVBatch.empty(self.n_dev * self.shard_capacity, self.cfg.key_lanes)

    def _step_at(self, shard_capacity: int):
        """The step program whose shards hold ``shard_capacity`` rows: the
        one put on this engine by hand, else the configuration's."""
        own = self._own_steps.get(shard_capacity)
        return own if own is not None else self._programs.step(shard_capacity)

    @property
    def _step(self):
        """The step program at the capacity a run starts with."""
        return self._step_at(self.shard_capacity)

    @_step.setter
    def _step(self, step) -> None:
        self._own_steps[self.shard_capacity] = step

    def _grow_shards(self, table: KVBatch, shard_capacity: int) -> KVBatch:
        """Every shard of ``table`` with empty rows up to ``shard_capacity``
        (``core/kv.grow_table`` a shard; nothing donated: a growth step
        that falls short starts from ``table`` again)."""
        return self._programs.grower(shard_capacity)(table)

    def empty_leftover(self) -> KVBatch:
        """Global (sharded) empty shuffle-backlog buffer (0 rows in drop mode)."""
        return KVBatch.empty(
            self.n_dev * self.leftover_capacity, self.cfg.key_lanes
        )

    def _identity(self) -> dict:
        """Engine/pipeline/mesh identity bound into every checkpoint
        fingerprint — both the corpus-digest path (``run``) and the
        caller-supplied stream fingerprint (``run_stream``), so a flat
        snapshot can never be resumed by a different engine, mesh, or
        pipeline over the same corpus (their npz schemas differ)."""
        return dict(
            engine="flat",
            cfg=repr(self.cfg),
            combine=self.combine,
            # Without the map_fn identity, a resume after changing map_fn
            # would silently reuse the stale table (ADVICE r2, medium).
            # The NORMALIZED name also invalidates pre-fix "count" tables.
            map_fn=self._norm_map_name,
            mesh=f"{self.n_dev}x{self.axis}",
            bin_capacity=self.bin_capacity,
            shard_capacity=self.shard_capacity,
            on_overflow=self.on_overflow,
        )

    def _fingerprint(self, rows) -> str:
        """Identity of a (corpus, pipeline, mesh) combination for resume."""
        from locust_tpu.io.serde import fingerprint_corpus

        return fingerprint_corpus(rows, **self._identity())

    def run(
        self,
        rows,
        shard_fn=None,
        checkpoint_dir: str | None = None,
        checkpoint_every: int = 1,
        stats_sync_every: int = 16,
    ) -> "DistributedResult":
        """Run the full corpus; ``rows`` is a host ``[n, line_width]`` array.

        In ``on_overflow="retry"`` mode (default) each feed round is
        followed by drain rounds — empty input, backlog only — until every
        device's shuffle backlog is empty, so bin overflow NEVER loses
        data.  Each drain moves >= 1 entry per backlogged destination, so
        at most ceil(emits_per_block / bin_capacity) drains are needed; a
        safety cap (``self.max_drain_rounds``, baked into the compiled
        step) stops instead of looping forever, surfacing the residue at
        the next stats sync.  The drain loop runs ON DEVICE
        (lax.while_loop inside the step) and stats accumulate on device,
        synced to the host only every ``stats_sync_every`` rounds — round
        dispatch pipelines with no per-round host round-trip.
        Invariant violations (data loss, undrained backlog)
        therefore surface up to ``stats_sync_every - 1`` rounds late, but
        no less loudly.

        With ``checkpoint_dir``, every ``checkpoint_every`` completed
        rounds the sharded accumulator + backlog + counters land in one
        atomically-replaced npz per process; a re-run with the same
        corpus/config/mesh fingerprint resumes after the last completed
        round (the distributed upgrade of the reference's "map wrote
        /tmp/out.txt, re-run reduce from it" persistence, main.cu:428-441).
        """
        lpr = self.lines_per_round
        nrounds = max(1, -(-rows.shape[0] // lpr))
        chunks = (rows[r * lpr : (r + 1) * lpr] for r in range(nrounds))
        return self._run_rounds(
            chunks,
            fingerprint=(
                self._fingerprint(rows) if checkpoint_dir is not None else None
            ),
            shard_fn=shard_fn,
            checkpoint_dir=checkpoint_dir,
            checkpoint_every=checkpoint_every,
            stats_sync_every=stats_sync_every,
        )

    def run_stream(
        self,
        blocks,
        fingerprint: str | None = None,
        shard_fn=None,
        checkpoint_dir: str | None = None,
        checkpoint_every: int = 1,
        stats_sync_every: int = 16,
    ) -> "DistributedResult":
        """Like ``run`` but over an ITERABLE of ``[<=lines_per_round, width]``
        host row blocks — bounded-memory ingest at mesh scale.
        Pair with ``io.loader.StreamingCorpus(path, width,
        block_lines=self.lines_per_round)``; pass its ``fingerprint()`` to
        enable checkpoint/resume (resume re-reads but does not re-process
        already-folded rounds).
        """
        from locust_tpu.io.loader import prefetch_blocks

        return self._run_rounds(
            prefetch_blocks(blocks),  # overlap host reads with rounds
            fingerprint=stream_checkpoint_fingerprint(
                fingerprint, checkpoint_dir, self._identity()
            ),
            shard_fn=shard_fn,
            checkpoint_dir=checkpoint_dir,
            checkpoint_every=checkpoint_every,
            stats_sync_every=stats_sync_every,
        )

    def _run_rounds(
        self,
        chunk_iter,
        fingerprint: str | None,
        shard_fn,
        checkpoint_dir: str | None,
        checkpoint_every: int,
        stats_sync_every: int,
    ) -> "DistributedResult":
        import os

        import numpy as np

        from locust_tpu.parallel.mesh import shard_rows

        if checkpoint_every < 1:
            raise ValueError(f"checkpoint_every must be >= 1, got {checkpoint_every}")
        if stats_sync_every < 1:
            raise ValueError(f"stats_sync_every must be >= 1, got {stats_sync_every}")
        lpr = self.lines_per_round
        width = self.cfg.line_width
        sharding = jax.sharding.NamedSharding(self.mesh, P(self.axis))
        acc = jax.device_put(self.empty_table(), sharding)
        leftover = jax.device_put(self.empty_leftover(), sharding)
        emit_ovf = shuf_ovf = 0
        distinct = 0
        drains_used = 0
        truncated = False
        start_round = 0

        ckpt = None
        if checkpoint_dir is not None:
            ckpt = ShardedCheckpoint(
                checkpoint_dir, fingerprint, sharding,
                async_writes=self.cfg.async_checkpoint,
            )
            restored = ckpt.load()
            if restored is not None:
                start_round, extras, acc, leftover = restored
                emit_ovf = int(extras["emit_ovf"])
                shuf_ovf = int(extras["shuf_ovf"])
                distinct = int(extras["distinct"])
                drains_used = int(extras["drains_used"])
                truncated = bool(extras["truncated"])

        # Rows a shard holds NOW.  A snapshot is taken of a settled table
        # (the stats flush before it grows and redoes first), so it carries
        # the capacity it was taken at in its shape, and a resume goes on
        # at that capacity — never back at the one the run started with.
        cap = acc.size // self.n_dev
        # What a redo starts from (only shards that grow keep it): the
        # table and backlog at the last sync that showed every key held,
        # and the rounds dispatched since (their lines already on the
        # devices; at most stats_sync_every).
        whole = (acc, leftover) if self.grows else None
        since: list = []
        worst_before = 0  # the worst shard's count at the last sync
        # The capacity the NEXT round wants (and the count it is for): a
        # sync only notes it, the round that follows grows — the last
        # sync of a run, and one ahead of a snapshot, grow nothing.
        ahead = (cap, 0)
        grows = rounds = 0

        def snapshot(next_round: int) -> None:
            ckpt.snapshot(
                next_round,
                acc,
                leftover,
                emit_ovf=np.int64(emit_ovf),
                shuf_ovf=np.int64(shuf_ovf),
                distinct=np.int64(distinct),
                drains_used=np.int64(drains_used),
                truncated=np.bool_(truncated),
            )

        def fetch(stats):
            with obs.span("mesh.sync", what="stats"):
                return jax.device_get(stats)  # locust: noqa[R003] the stats sync, once every stats_sync_every rounds: the loop's one wait

        def settle(st):
            """Stats of rounds that dropped no key: while the worst shard
            counted more keys than a shard holds, grow every shard to the
            capacity that holds them and fold the rounds since the last
            whole table again, from that table.  A fold past its capacity
            counts every key it saw but not those an earlier fold of the
            stretch had dropped, so a step can fall short and is taken
            again; the count only rises, so the loop ends.  The redone
            rounds' wait FOLLOWS the grow span (no time is in both)."""
            nonlocal acc, leftover, cap, grows
            while int(st[4]) > cap:
                worst = int(st[4])
                to = rows_to_hold(cap, worst)
                with obs.span("mesh.table.grow", from_rows=cap, to_rows=to,
                              worst_shard=worst, rounds_redone=len(since)):
                    acc = self._grow_shards(whole[0], to)
                    leftover = whole[1]
                    step, stats = self._step_at(to), None
                    for lines in since:
                        acc, leftover, one = step(lines, acc, leftover)
                        stats = one if stats is None else self._stats_merge(stats, one)
                with obs.span("mesh.sync", what="regrow"):
                    st = jax.device_get(stats)  # locust: noqa[R003] the redone rounds' one wait
                cap = to
                grows += 1
            return st

        # Device-side stats accumulator: rounds dispatch back-to-back and
        # the host folds the replicated stats vector in only at sync points.
        def on_sync(st) -> None:
            """Fold accumulated device stats into host counters; police
            the no-loss invariants (loudly, if a few rounds late)."""
            nonlocal emit_ovf, shuf_ovf, distinct, drains_used, truncated
            nonlocal whole, worst_before, ahead
            if self.grows:
                st = settle(st)
            emit_ovf += int(st[0])
            shuf_ovf += int(st[1])
            distinct = int(st[2])
            backlog = int(st[3])
            worst = int(st[4])
            truncated |= worst > cap
            drains_used += int(st[5])
            if backlog > 0:
                raise RuntimeError(
                    f"shuffle backlog failed to drain in "
                    f"{self.max_drain_rounds} rounds ({backlog} entries "
                    "remain); raise skew_factor"
                )
            if shuf_ovf and self.on_overflow == "retry":
                # Spill past the leftover buffer = data ALREADY lost;
                # retry mode must fail loudly, not tally quietly.  Only
                # reachable if a custom map_fn violates the emits_per_block
                # bound (the buffer is sized to make it impossible for the
                # built-in pipeline).
                raise RuntimeError(
                    f"shuffle lost {shuf_ovf} entries despite retry mode; "
                    "map_fn emitted more than cfg.emits_per_block live rows"
                )
            if self.grows:
                # A text adds fewer new keys as it goes on: shards that
                # would not hold what the LAST stretch added once more are
                # grown before the next one folds into them.  (The first
                # stretch's count says nothing: it holds every common key.)
                added = worst - worst_before if worst_before else 0
                ahead = (rows_to_hold(cap, worst + added), worst + added)
                worst_before = worst
                whole = (acc, leftover)
                since.clear()

        round_stats = RoundStats(
            self._stats_merge, on_sync, stats_sync_every, fetch_fn=fetch
        )

        def fold_round(chunk) -> None:
            nonlocal acc, leftover, cap, grows, rounds
            if ahead[0] > cap:
                with obs.span("mesh.table.grow", from_rows=cap, to_rows=ahead[0],
                              worst_shard=ahead[1], rounds_redone=0):
                    acc = self._grow_shards(acc, ahead[0])
                cap = ahead[0]
                grows += 1
            with obs.span("mesh.round", lines=len(chunk)):
                chunk = normalize_round_chunk(chunk, lpr, width)
                with obs.span("mesh.h2d", bytes=chunk.nbytes):
                    sharded = (shard_fn or shard_rows)(chunk, self.mesh, self.axis)
                acc, leftover, stats = self._step_at(cap)(sharded, acc, leftover)
            if self.grows:
                since.append(sharded)
            rounds += 1
            round_stats.push(stats)

        drive_checkpointed_rounds(
            chunk_iter, fold_round, round_stats, ckpt, snapshot,
            checkpoint_every, start_round,
        )
        obs.metric_inc("mesh.rounds", rounds)
        obs.metric_inc("mesh.table_grows", grows)
        obs.metric_inc("mesh.drain_rounds", drains_used)
        obs.metric_set("mesh.shard_rows", cap)
        if truncated:
            logger.warning(
                "a shard's distinct keys exceeded its table capacity (%d); "
                "tail keys dropped — raise shard_capacity, or leave it out: "
                "shards of the default capacity grow with what they see",
                cap,
            )
        return DistributedResult(
            table=acc,
            emit_overflow=emit_ovf,
            shuffle_overflow=shuf_ovf,
            distinct=distinct,
            combine=self.combine,
            drain_rounds=drains_used,
            truncated=truncated,
            fused_kernel="mesh" if self._fused_kernel_on else None,
            fused_demoted=self.fused_demoted,
            shard_capacity=cap,
            table_grows=grows,
        )


def _scatter_batch_from_host(batch: KVBatch, sharding) -> KVBatch:
    """Place a host-replicated full KVBatch onto a (multi-process) sharding.

    The checkpoint snapshot holds the FULL gathered table on every process
    (_gather_batch_host), so each process can serve its addressable shards
    by slicing (mesh.scatter_host_array).
    """
    from locust_tpu.parallel.mesh import scatter_host_array

    return KVBatch(
        key_lanes=scatter_host_array(batch.key_lanes, sharding),
        values=scatter_host_array(batch.values, sharding),
        valid=scatter_host_array(batch.valid, sharding),
    )


def _gather_batch_host(table: KVBatch) -> KVBatch:
    """Gather a (possibly multi-process sharded) KVBatch to host numpy
    (mesh.gather_host_array per leaf: process_allgather on a pod,
    device_get single-process)."""
    from locust_tpu.parallel.mesh import gather_host_array

    return KVBatch(
        key_lanes=gather_host_array(table.key_lanes),
        values=gather_host_array(table.values),
        valid=gather_host_array(table.valid),
    )


class DistributedResult:
    def __init__(
        self,
        table: KVBatch,
        emit_overflow: int,
        shuffle_overflow: int,
        distinct: int,
        combine: str = "sum",
        drain_rounds: int = 0,
        truncated: bool = False,
        fused_kernel: str | None = None,
        fused_demoted: bool = False,
        shard_capacity: int | None = None,
        table_grows: int = 0,
    ):
        self.table = table
        # Rows a hash shard holds (the table is shards x shard_capacity
        # rows); for the flat engine the capacity the run ENDED at, after
        # ``table_grows`` growth steps.  None: one shard, the whole table.
        self.shard_capacity = shard_capacity or table.size
        self.table_grows = table_grows
        self.emit_overflow = emit_overflow    # tokens beyond the per-line cap
        self.shuffle_overflow = shuffle_overflow  # entries LOST in the shuffle
        self.distinct = distinct
        self.combine = combine
        self.drain_rounds = drain_rounds      # extra all-to-all rounds used
        self.truncated = truncated            # a shard's table overflowed
        # Megakernel v2 visibility (mirror of RunResult.fused_kernel /
        # .fused_demoted): "mesh" when the Pallas kernel served the
        # per-shard combiner; fused_demoted=True when sort_mode="fused"
        # was requested but the engine folded as plain hasht (off-TPU /
        # ineligible shape) — previously invisible.
        self.fused_kernel = fused_kernel
        self.fused_demoted = fused_demoted

    def to_host_pairs(self, sort: bool = True) -> list[tuple[bytes, int]]:
        """Gather all shards; optionally re-sort to global key order.

        Shards are hash-partitioned (each internally grouped), so global
        lexicographic order needs this final host-side merge — the analog of
        the reference's final sorted print (main.cu:473).  Multi-process:
        every process gathers all shards (process_allgather over DCN) and
        returns the identical full table.
        """
        from locust_tpu.engine import finalize_host_pairs

        with obs.span("mesh.gather", rows=self.table.size,
                      shards=self.table.size // self.shard_capacity):
            return finalize_host_pairs(
                self.table, self.combine, sort, fetch=_gather_batch_host
            )

    def to_host_rows(self):
        """Gather all shards to key-ordered ROWS for printing
        (``engine.finalize_host_rows``: arrays, or the sorted pairs where
        the data cannot be printed from arrays), under the same
        ``mesh.gather`` — the CLI's table; ``to_host_pairs`` for everyone
        who needs pairs."""
        from locust_tpu.engine import finalize_host_rows

        with obs.span("mesh.gather", rows=self.table.size,
                      shards=self.table.size // self.shard_capacity):
            return finalize_host_rows(
                self.table, self.combine, fetch=_gather_batch_host
            )
