"""Inverted index: word -> sorted unique doc ids (BASELINE.json configs[4]).

The stretch workload: emits are (word, doc_id) and the reduce is "collect
the distinct values per key" — a variable-length output that stresses the
fixed-slot emit contract (SURVEY.md §7.2 M5).

TPU-native formulation with static shapes throughout:

  1. Map: tokenize lines (ops/map_stage), value = the line's doc id.
  2. Sort by (validity, hash64(key), value): the 64-bit grouping-hash trick
     from the Process stage (ops/process_stage "hash" mode) — 4 key
     operands regardless of key width groups words AND orders each word's
     doc ids; payload rows follow via one index gather.  Full-key compares
     drive all downstream boundaries, so hash collisions cannot merge
     words; host assembly re-merges the ~2^-64 duplicate-run case.
  3. Dedup (word, doc) pairs with a boundary mask on pair equality, then
     one more sort-compact pushes surviving pairs to the prefix.
  4. Word segment boundaries over the deduped prefix give the postings
     offsets: the index is (concatenated doc-id postings, per-word counts)
     — the standard CSR layout, assembled on host into {word: [doc ids]}.
"""

from __future__ import annotations

import logging

import jax
import jax.numpy as jnp
import numpy as np

from locust_tpu.config import EngineConfig
from locust_tpu.core import bytes_ops, packing
from locust_tpu.core.kv import KVBatch
from locust_tpu.ops.map_stage import tokenize_block
from locust_tpu.ops.reduce_stage import segment_reduce

logger = logging.getLogger("locust_tpu")


def _sort_pairs(batch: KVBatch) -> KVBatch:
    """Group by (validity, hash64(key)) with values as a tie-break sort key.

    4 sort operands + an index payload regardless of key width — the hash
    trick from ops/process_stage._hash_sort, extended with the value as the
    least-significant key so each word's doc ids come out ascending.
    """
    lanes, values, valid = batch.key_lanes, batch.values, batch.valid
    n = lanes.shape[0]
    invalid = (~valid).astype(jnp.uint32)
    h1, h2 = packing.hash_pair(lanes)
    idx = jnp.arange(n, dtype=jnp.int32)
    out = jax.lax.sort((invalid, h1, h2, values, idx), num_keys=4)
    sidx = out[4]
    return KVBatch(
        key_lanes=lanes[sidx], values=values[sidx], valid=valid[sidx]
    )


def _dedup_sorted_pairs(s: KVBatch) -> tuple[KVBatch, jax.Array]:
    """Mark the first of each identical (word, doc) run; return the
    re-compacted batch and the surviving-pair count."""
    n = s.size
    prev_lanes = jnp.roll(s.key_lanes, 1, axis=0)
    prev_vals = jnp.roll(s.values, 1)
    first = jnp.arange(n) == 0
    pair_new = first | jnp.any(s.key_lanes != prev_lanes, axis=-1) | (
        s.values != prev_vals
    )
    keep = s.valid & pair_new
    deduped = KVBatch(key_lanes=s.key_lanes, values=s.values, valid=keep)
    d = _sort_pairs(deduped)  # compact survivors to the prefix, still ordered
    return d, jnp.sum(keep.astype(jnp.int32))


def _fold_index_block(
    acc: KVBatch,
    lines: jax.Array,
    doc_ids: jax.Array,
    cfg: EngineConfig,
    cap: int,
):
    """Merge one block's (word, doc) pairs into the running deduped table.

    Same one-sort-per-block fold as the WordCount engine (engine.py
    fold_block), but the carried state is the PAIR set, which the final
    segment count turns into CSR postings.
    """
    res = tokenize_block(lines, cfg)
    flat_keys = res.keys.reshape(-1, cfg.key_width)
    flat_valid = res.valid.reshape(-1)
    values = jnp.repeat(doc_ids.astype(jnp.int32), cfg.emits_per_line)
    batch = KVBatch.from_bytes(flat_keys, values, flat_valid)

    d, n_pairs = _dedup_sorted_pairs(_sort_pairs(KVBatch.concat(acc, batch)))
    head = KVBatch(
        key_lanes=d.key_lanes[:cap], values=d.values[:cap], valid=d.valid[:cap]
    )
    return head, n_pairs, res.overflow


_fold_index_jit = jax.jit(_fold_index_block, static_argnames=("cfg", "cap"))


def default_pairs_capacity(cfg: EngineConfig, mult: int = 2) -> int:
    """Default distinct-(word, doc) pair capacity: ``mult`` rounds of
    emits with a 4096 floor.  The pair table is CORPUS-level state, not
    per-block — a small block size must not shrink it (r4 apps battery:
    tiny-block configs raised on ordinary vocabularies; the floor costs
    ~150KB).  The ONE sizing rule for the single-device index, the
    distributed index (``mult=4``: pairs accumulate across rounds), and
    the tf counter."""
    return max(mult * cfg.emits_per_block, 4096)


def build_inverted_index(
    lines: list[bytes] | np.ndarray,
    doc_ids: np.ndarray,
    cfg: EngineConfig | None = None,
    pairs_capacity: int | None = None,
) -> dict[bytes, list[int]]:
    """Host API: lines + per-line doc ids -> {word: sorted unique doc ids}.

    Streams the corpus through fixed-shape blocks like the WordCount engine
    — no line-count cap.  ``pairs_capacity`` bounds the distinct (word, doc)
    pair table carried across blocks (default ``default_pairs_capacity``:
    2x emits_per_block, floor 4096); exceeding it raises, since a
    truncated index is silently wrong.
    """
    cfg = cfg or EngineConfig()
    cap = pairs_capacity or default_pairs_capacity(cfg)
    if not isinstance(lines, np.ndarray):
        rows = bytes_ops.strings_to_rows(list(lines), cfg.line_width)
    else:
        rows = lines
    ids = np.asarray(doc_ids, np.int32)
    if rows.shape[0] != ids.shape[0]:
        raise ValueError(f"{rows.shape[0]} lines but {ids.shape[0]} doc ids")

    bl = cfg.block_lines
    nblocks = max(1, -(-rows.shape[0] // bl))
    pad = nblocks * bl - rows.shape[0]
    rows = np.concatenate([rows, np.zeros((pad, cfg.line_width), np.uint8)])
    ids = np.concatenate([ids, np.zeros(pad, np.int32)])

    acc = KVBatch.empty(cap, cfg.key_lanes)
    # The pair count stays a DEVICE scalar across the loop — an int() here
    # would host-sync every block and serialize dispatch (round-1 advisor
    # finding); the capacity check only needs the value once, after.
    n_pairs_dev = jnp.int32(0)
    overflow_dev = jnp.int32(0)
    for b in range(nblocks):
        sl = slice(b * bl, (b + 1) * bl)
        acc, blk_pairs, blk_ovf = _fold_index_jit(
            acc, jnp.asarray(rows[sl]), jnp.asarray(ids[sl]), cfg, cap
        )
        n_pairs_dev = jnp.maximum(n_pairs_dev, blk_pairs)
        overflow_dev = overflow_dev + blk_ovf
    n_pairs = int(n_pairs_dev)
    if int(overflow_dev):
        # Missing postings make a silently-wrong index; surface it loudly
        # (the WordCount per-line drop is reference semantics, but an index
        # user needs to know postings are absent).
        logger.warning(
            "inverted index dropped %d tokens beyond the %d-per-line cap; "
            "their postings are MISSING — raise emits_per_line",
            int(overflow_dev),
            cfg.emits_per_line,
        )
    if n_pairs > cap:
        raise ValueError(
            f"distinct (word, doc) pairs ({n_pairs}) exceed pairs_capacity "
            f"({cap}); pass a larger pairs_capacity"
        )
    d = acc
    counts = segment_reduce(d, "count")

    # Host assembly: postings prefix + per-word counts -> dict.
    pairs_keys = np.asarray(jax.device_get(d.keys_bytes()))
    pairs_vals = np.asarray(jax.device_get(d.values))
    pairs_valid = np.asarray(jax.device_get(d.valid))
    word_counts = counts.to_host_pairs()

    out: dict[bytes, list[int]] = {}
    pos = 0
    live_vals = pairs_vals[pairs_valid]
    for word, cnt in word_counts:
        run = [int(v) for v in live_vals[pos : pos + cnt]]
        if word in out:  # 64-bit hash collision split a word into two runs
            run = sorted(set(out[word] + run))
        out[word] = run
        pos += cnt
    assert pos == len(live_vals), "postings/count bookkeeping diverged"
    del pairs_keys
    return out


class DistributedInvertedIndex:
    """Mesh-parallel inverted index.

    The same collective recipe as parallel/shuffle.DistributedMapReduce —
    hash-partition, equal bins, one ``lax.all_to_all`` per round, carried
    per-device state, lossless backlog retry — but the shuffled unit is the
    (word, doc) PAIR and the per-shard merge is a dedup, not a segment
    reduce.  Partitioning hashes the WORD only, so every posting of a word
    lands on one shard and host assembly is a plain per-shard union.
    """

    def __init__(
        self,
        mesh: jax.sharding.Mesh,
        cfg: EngineConfig,
        axis_name: str | None = None,
        skew_factor: float = 2.0,
        pairs_capacity: int | None = None,
    ):
        from jax.sharding import PartitionSpec as P

        from locust_tpu.parallel.mesh import DATA_AXIS
        from locust_tpu.parallel.shuffle import partition_to_bins, sized_bins

        axis = axis_name or DATA_AXIS
        self.mesh = mesh
        self.cfg = cfg
        self.axis = axis
        self.n_dev = mesh.shape[axis]
        self.bin_capacity = sized_bins(
            cfg.emits_per_block, self.n_dev, skew_factor
        )
        self.leftover_capacity = cfg.emits_per_block
        # Distinct (word, doc) pairs carried per shard; exceeding it raises
        # (a truncated index is silently wrong, like the single-device API).
        # Pairs accumulate across ALL rounds, so the floor is deliberately
        # larger than one round's emits.
        self.pairs_capacity = pairs_capacity or default_pairs_capacity(cfg, mult=4)
        self.max_drain_rounds = 2 + -(-cfg.emits_per_block // self.bin_capacity)
        max_drains = self.max_drain_rounds
        n_lanes = cfg.key_lanes

        def shuffle_round(local: KVBatch, acc: KVBatch, leftover: KVBatch):
            """One partition + all-to-all + dedup-merge; feed and drain
            share it (mirror of shuffle.DistributedMapReduce)."""
            send_lanes, send_vals, send_valid, shuf_ovf, new_leftover = (
                partition_to_bins(
                    KVBatch.concat(local, leftover),
                    self.n_dev,
                    self.bin_capacity,
                    leftover_capacity=self.leftover_capacity,
                )
            )
            recv_lanes = jax.lax.all_to_all(send_lanes, axis, 0, 0)
            recv_vals = jax.lax.all_to_all(send_vals, axis, 0, 0)
            recv_valid = jax.lax.all_to_all(send_valid, axis, 0, 0)
            received = KVBatch(
                key_lanes=recv_lanes.reshape(-1, n_lanes),
                values=recv_vals.reshape(-1),
                valid=recv_valid.reshape(-1),
            )
            merged, n_pairs = _dedup_sorted_pairs(
                _sort_pairs(KVBatch.concat(acc, received))
            )
            cap = self.pairs_capacity
            new_acc = KVBatch(
                key_lanes=merged.key_lanes[:cap],
                values=merged.values[:cap],
                valid=merged.valid[:cap],
            )
            # psum'd so every device sees the same value — the while_loop
            # below then takes the same trip count on all devices.
            backlog = jax.lax.psum(
                jnp.sum(new_leftover.valid.astype(jnp.int32)), axis
            )
            return new_acc, new_leftover, shuf_ovf, n_pairs, backlog

        def local_step(
            lines: jax.Array, doc_ids: jax.Array, acc: KVBatch, leftover: KVBatch
        ):
            """Feed + ON-DEVICE drain (lax.while_loop): one dispatch per
            round with no host sync, like DistributedMapReduce.local_step."""
            res = tokenize_block(lines, cfg)
            flat_keys = res.keys.reshape(-1, cfg.key_width)
            flat_valid = res.valid.reshape(-1)
            values = jnp.repeat(doc_ids.astype(jnp.int32), cfg.emits_per_line)
            batch = KVBatch.from_bytes(flat_keys, values, flat_valid)
            # Local pre-dedup: repeated (word, doc) pairs within the shard
            # collapse before touching the network (the combiner analog).
            local, _ = _dedup_sorted_pairs(_sort_pairs(batch))

            acc, leftover, shuf_ovf, n_pairs, backlog = shuffle_round(
                local, acc, leftover
            )
            zero_local = KVBatch.empty(local.size, n_lanes)

            def cond(state):
                _, _, _, _, backlog, drains = state
                return (backlog > 0) & (drains < max_drains)

            def body(state):
                acc, leftover, shuf_ovf, _, _, drains = state
                acc, leftover, so, n_pairs, backlog = shuffle_round(
                    zero_local, acc, leftover
                )
                return (acc, leftover, shuf_ovf + so, n_pairs, backlog,
                        drains + 1)

            acc, leftover, shuf_ovf, n_pairs, backlog, drains = (
                jax.lax.while_loop(
                    cond,
                    body,
                    (acc, leftover, shuf_ovf, n_pairs, backlog, jnp.int32(0)),
                )
            )
            stats = jnp.stack(
                [
                    jax.lax.psum(res.overflow, axis),
                    jax.lax.psum(shuf_ovf, axis),
                    jax.lax.pmax(n_pairs, axis),
                    backlog,
                    drains,
                ]
            )
            return acc, leftover, stats

        kv_spec = KVBatch(key_lanes=P(axis), values=P(axis), valid=P(axis))
        self._step = jax.jit(
            jax.shard_map(
                local_step,
                mesh=mesh,
                in_specs=(P(axis), P(axis), kv_spec, kv_spec),
                out_specs=(kv_spec, kv_spec, P()),
            )
        )
        # Across-round stats combiner, jitted ONCE per index builder:
        # overflows/drains ADD, worst-shard pairs MAX, backlog LAST.
        self._stats_merge = jax.jit(
            lambda a, b: jnp.stack(
                [a[0] + b[0], a[1] + b[1], jnp.maximum(a[2], b[2]), b[3],
                 a[4] + b[4]]
            )
        )

    @property
    def lines_per_round(self) -> int:
        return self.n_dev * self.cfg.block_lines

    def run(
        self,
        lines: list[bytes] | np.ndarray,
        doc_ids: np.ndarray,
        stats_sync_every: int = 16,
        checkpoint_dir: str | None = None,
        checkpoint_every: int = 1,
    ) -> dict[bytes, list[int]]:
        cfg = self.cfg
        if not isinstance(lines, np.ndarray):
            rows = bytes_ops.strings_to_rows(list(lines), cfg.line_width)
        else:
            rows = lines
        ids = np.asarray(doc_ids, np.int32)
        if rows.shape[0] != ids.shape[0]:
            raise ValueError(f"{rows.shape[0]} lines but {ids.shape[0]} doc ids")

        lpr = self.lines_per_round
        nrounds = max(1, -(-rows.shape[0] // lpr))
        chunks = (
            (rows[r * lpr : (r + 1) * lpr], ids[r * lpr : (r + 1) * lpr])
            for r in range(nrounds)
        )
        fingerprint = None
        if checkpoint_dir is not None:
            from locust_tpu.io.serde import fingerprint_corpus

            # Doc ids are part of the corpus identity: the same lines with
            # different sharding produce a different index.
            fingerprint = fingerprint_corpus(
                rows, doc_ids=fingerprint_corpus(ids), **self._identity()
            )
        return self._run_rounds(
            chunks,
            stats_sync_every,
            fingerprint=fingerprint,
            checkpoint_dir=checkpoint_dir,
            checkpoint_every=checkpoint_every,
        )

    def _identity(self) -> dict:
        """Engine/pipeline/mesh identity bound into checkpoint
        fingerprints (shuffle.DistributedMapReduce._identity mirror)."""
        return dict(
            engine="inverted_index",
            cfg=repr(self.cfg),
            mesh=f"{self.n_dev}x{self.axis}",
            bin_capacity=self.bin_capacity,
            pairs_capacity=self.pairs_capacity,
        )

    def run_stream(
        self,
        blocks,
        stats_sync_every: int = 16,
        fingerprint: str | None = None,
        checkpoint_dir: str | None = None,
        checkpoint_every: int = 1,
    ) -> dict[bytes, list[int]]:
        """Bounded-memory variant: ``blocks`` yields
        ``(rows [<=lines_per_round, width], doc_ids [same length])`` chunk
        pairs — e.g. zip a ``StreamingCorpus(..., block_lines=
        self.lines_per_round)`` with a doc-id generator.  Only one chunk
        plus the sharded pair table are ever resident.  Pass a corpus
        ``fingerprint`` to enable checkpoint/resume.
        """
        from locust_tpu.io.loader import prefetch_blocks
        from locust_tpu.parallel.shuffle import stream_checkpoint_fingerprint

        fingerprint = stream_checkpoint_fingerprint(
            fingerprint, checkpoint_dir, self._identity()
        )
        return self._run_rounds(
            prefetch_blocks(blocks),
            stats_sync_every,
            fingerprint=fingerprint,
            checkpoint_dir=checkpoint_dir,
            checkpoint_every=checkpoint_every,
        )

    def _run_rounds(
        self,
        chunk_iter,
        stats_sync_every: int,
        fingerprint: str | None = None,
        checkpoint_dir: str | None = None,
        checkpoint_every: int = 1,
    ):
        from jax.sharding import PartitionSpec as P

        from locust_tpu.parallel.mesh import shard_rows
        from locust_tpu.parallel.shuffle import (
            ShardedCheckpoint,
            _gather_batch_host,
            drive_checkpointed_rounds,
        )

        cfg = self.cfg
        lpr = self.lines_per_round
        width = cfg.line_width

        sharding = jax.sharding.NamedSharding(self.mesh, P(self.axis))
        acc = jax.device_put(
            KVBatch.empty(self.n_dev * self.pairs_capacity, cfg.key_lanes), sharding
        )
        leftover = jax.device_put(
            KVBatch.empty(self.n_dev * self.leftover_capacity, cfg.key_lanes),
            sharding,
        )

        # Drains run ON DEVICE inside the step; the host only folds stats
        # in every ``stats_sync_every`` rounds, so round dispatch pipelines
        # (same RoundStats protocol as DistributedMapReduce.run).
        n_pairs = 0
        shuf_ovf = 0
        emit_ovf = 0
        start_round = 0

        ckpt = None
        if checkpoint_dir is not None:
            ckpt = ShardedCheckpoint(
                checkpoint_dir, fingerprint, sharding,
                async_writes=cfg.async_checkpoint,
            )
            restored = ckpt.load()
            if restored is not None:
                start_round, extras, acc, leftover = restored
                n_pairs = int(extras["n_pairs"])
                shuf_ovf = int(extras["shuf_ovf"])
                emit_ovf = int(extras["emit_ovf"])

        def snapshot(next_round: int) -> None:
            ckpt.snapshot(
                next_round,
                acc,
                leftover,
                n_pairs=np.int64(n_pairs),
                shuf_ovf=np.int64(shuf_ovf),
                emit_ovf=np.int64(emit_ovf),
            )

        def on_sync(st) -> None:
            nonlocal n_pairs, shuf_ovf, emit_ovf
            emit_ovf += int(st[0])
            shuf_ovf += int(st[1])
            n_pairs = max(n_pairs, int(st[2]))
            backlog = int(st[3])
            if backlog > 0:
                raise RuntimeError(
                    f"index backlog failed to drain in "
                    f"{self.max_drain_rounds} rounds ({backlog} pairs "
                    "remain); raise skew_factor"
                )
            if shuf_ovf:
                raise RuntimeError(
                    f"index shuffle lost {shuf_ovf} pairs; "
                    "emits exceeded cfg.emits_per_block"
                )

        from locust_tpu.parallel.shuffle import RoundStats

        round_stats = RoundStats(self._stats_merge, on_sync, stats_sync_every)
        from locust_tpu.parallel.shuffle import normalize_round_chunk

        def fold_round(chunk) -> None:
            nonlocal acc, leftover
            rows_chunk, ids_chunk = chunk
            ids_chunk = np.asarray(ids_chunk, dtype=np.int32)
            rows_chunk = np.asarray(rows_chunk, dtype=np.uint8)
            if rows_chunk.shape[0] != ids_chunk.shape[0]:
                raise ValueError(
                    f"chunk has {rows_chunk.shape[0]} lines but "
                    f"{ids_chunk.shape[0]} doc ids"
                )
            rows_chunk = normalize_round_chunk(rows_chunk, lpr, width)
            if ids_chunk.shape[0] < lpr:
                ids_chunk = np.concatenate(
                    [ids_chunk, np.zeros(lpr - ids_chunk.shape[0], np.int32)]
                )
            acc, leftover, stats = self._step(
                shard_rows(rows_chunk, self.mesh, self.axis),
                shard_rows(ids_chunk, self.mesh, self.axis),
                acc,
                leftover,
            )
            round_stats.push(stats)

        drive_checkpointed_rounds(
            chunk_iter, fold_round, round_stats, ckpt, snapshot,
            checkpoint_every, start_round,
        )
        if emit_ovf:
            # Missing postings make a silently-wrong index; unlike WordCount
            # (whose per-line cap is reference semantics, main.cu:141-144),
            # surface it loudly.
            logger.warning(
                "inverted index dropped %d tokens beyond the %d-per-line "
                "cap; their postings are MISSING — raise emits_per_line",
                emit_ovf,
                cfg.emits_per_line,
            )
        if n_pairs > self.pairs_capacity:
            raise ValueError(
                f"distinct (word, doc) pairs per shard ({n_pairs}) exceed "
                f"pairs_capacity ({self.pairs_capacity}); pass a larger one"
            )

        # Host assembly: shards are disjoint by word (hash partition) and
        # internally (hash, doc)-sorted + deduped, so a plain grouping union
        # yields ascending unique doc ids per word.
        out: dict[bytes, list[int]] = {}
        for k, v in _gather_batch_host(acc).to_host_pairs():
            out.setdefault(k, []).append(int(v))
        return out


def build_inverted_index_mesh(
    lines: list[bytes] | np.ndarray,
    doc_ids: np.ndarray,
    mesh: jax.sharding.Mesh,
    cfg: EngineConfig | None = None,
    **kw,
) -> dict[bytes, list[int]]:
    """Mesh convenience wrapper: build the index across all devices."""
    return DistributedInvertedIndex(mesh, cfg or EngineConfig(), **kw).run(
        lines, doc_ids
    )
