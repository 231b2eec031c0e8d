"""Hierarchical two-level shuffle: ICI all-to-all per round, DCN once.

The flat ``DistributedMapReduce`` runs its hash shuffle over ONE mesh axis
— correct everywhere, but on a multi-slice / multi-host pod that axis
spans DCN links, so every round's all-to-all pays cross-slice bandwidth.
The scaling-book layout rule is to keep the high-frequency collective on
ICI and cross DCN as rarely and as small as possible; for a MapReduce the
associative table merge makes that exact split available:

  * mesh ``[slice, data]`` (parallel/mesh.make_mesh_2d): ``data`` spans
    the ICI-connected devices of one slice, ``slice`` spans slices (DCN).
  * PER ROUND each slice runs the full local pipeline independently —
    map, local combine, hash-partition, ``all_to_all`` over the ``data``
    axis ONLY, per-shard merge.  NOTHING in the round path crosses
    slices: the drain backlog reduces over the intra-slice axis (each
    slice takes its own drain trip count — valid SPMD, every collective
    inside the loop body is intra-slice too) and the stats vector leaves
    the step VARYING over the slice axis; the host folds slice rows
    together only at sync points.  (Reference analog: each node wrote its
    own /tmp/out.txt, main.cu:428-441 — except these per-slice tables are
    already reduced and hash-sharded.)
  * ONCE at the end, the cross-slice combine: ``all_gather`` over the
    ``slice`` axis of each device's bounded table shard (a few MB), then
    one local sort + segment-reduce.  Identical keys hash to the same
    ``data`` position in every slice, so the gather is shard-aligned and
    the merge is local.  DCN moves ``n_slices * shard_capacity`` rows per
    device ONCE per corpus instead of per round.

The per-device step body is the SAME code as the flat engine
(shuffle.build_shuffle_step) parameterized by axes, so the drain/stats
protocol cannot diverge between the two.
"""

from __future__ import annotations

import dataclasses
import logging
import math
import os
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from locust_tpu.config import EngineConfig
from locust_tpu.core.kv import KVBatch
from locust_tpu.engine import _programs_for
from locust_tpu.ops.map_stage import wordcount_map
from locust_tpu.ops.hash_table import reduce_into
from locust_tpu.ops.reduce_stage import normalize_combine
from locust_tpu.parallel.mesh import DATA_AXIS, SLICE_AXIS
from locust_tpu.parallel.shuffle import (
    RoundStats,
    _fused_mesh_gate,
    _kv_spec,
    _round_up,
    build_shuffle_step,
    drive_checkpointed_rounds,
    merge_stats_vectors,
    normalize_round_chunk,
    sized_bins,
)

logger = logging.getLogger("locust_tpu")


@dataclasses.dataclass(frozen=True)
class _HierarchicalPrograms:
    """The jitted programs of one hierarchical-mesh configuration
    (``_build_hierarchical_programs``): the process's one record of it
    (``engine._programs_for``)."""

    step: Callable             # (lines, acc, leftover) -> (acc, leftover, stats)
    combine: Callable          # acc -> (table replicated over slices, stats)
    combine_dbg: Callable      # the same merge with the slice axis exposed
    stats_merge: Callable      # merge_stats_vectors
    replicate_stats: Callable  # slice-varying stats -> replicated


def _build_hierarchical_programs(
    map_fn,
    combine: str,
    cfg: EngineConfig,
    mesh: jax.sharding.Mesh,
    slice_axis: str,
    data_axis: str,
    bin_capacity: int,
    shard_capacity: int,
    leftover_capacity: int,
    max_drains: int,
    fused_preagg: bool,
) -> _HierarchicalPrograms:
    """Define and jit the two-level engine's programs.  ``map_fn`` and
    ``combine`` are the NORMALIZED pair; nothing here names an engine, so
    engines share the record and a dead engine is no reference cycle."""
    both = (slice_axis, data_axis)
    local_step = build_shuffle_step(
        cfg,
        map_fn,
        combine,
        n_bins=int(mesh.shape[data_axis]),
        bin_capacity=bin_capacity,
        shard_capacity=shard_capacity,
        leftover_capacity=leftover_capacity,
        max_drains=max_drains,
        shuffle_axis=data_axis,     # the ICI-only shuffle
        stat_axes=(data_axis,),     # stats stay intra-slice per round
        fused_preagg=fused_preagg,
    )

    def combine_step(acc: KVBatch):
        """The ONE cross-slice (DCN) collective: gather shard-aligned
        table copies over the slice axis, merge locally."""
        lanes = jax.lax.all_gather(
            acc.key_lanes, slice_axis, axis=0, tiled=True
        )
        values = jax.lax.all_gather(acc.values, slice_axis, axis=0, tiled=True)
        valid = jax.lax.all_gather(acc.valid, slice_axis, axis=0, tiled=True)
        gathered = KVBatch(key_lanes=lanes, values=values, valid=valid)
        # reduce_into dispatches sort vs the "hasht" sort-free fold
        # (no collectives inside; the all_gathers above already ran).
        merged, distinct = reduce_into(
            gathered, shard_capacity, combine, cfg.sort_mode
        )
        # Global distinct: shards are hash-disjoint within a slice
        # column, identical across slices post-merge -> sum over data.
        g_distinct = jax.lax.psum(distinct, data_axis)
        worst = jax.lax.pmax(distinct, both)
        return merged, jnp.stack([g_distinct, worst])

    kv_spec_2d = _kv_spec(both)
    kv_spec_data = _kv_spec(data_axis)
    # Stats are reduced over the DATA axis only, so the vector is
    # replicated within a slice but VARIES across slices — out_spec
    # P(slice) gives the host a [n_slices * 6] stack to fold at sync
    # time.  This keeps the round path free of cross-slice collectives.
    step = jax.jit(
        jax.shard_map(
            local_step,
            mesh=mesh,
            in_specs=(P(both), kv_spec_2d, kv_spec_2d),
            out_specs=(kv_spec_2d, kv_spec_2d, P(slice_axis)),
            # Off only with the Pallas kernel engaged (shuffle.py's rule).
            check_vma=not fused_preagg,
        )
    )
    # Output of the final combine is REPLICATED over the slice axis:
    # every device in a column runs the identical deterministic merge
    # of the identical all_gather result.  jax's varying-axes check
    # cannot infer replication through all_gather statically, so it is
    # disabled for THIS shard_map only (the claim is load-bearing and
    # tested: tests assert the combined table equals the oracle).
    combine_all = jax.jit(
        jax.shard_map(
            combine_step,
            mesh=mesh,
            in_specs=(kv_spec_2d,),
            out_specs=(kv_spec_data, P()),
            check_vma=False,
        )
    )
    # Debug-mode self-policing of the replication claim behind
    # check_vma=False above: the SAME combine
    # body, but with out_specs that EXPOSE the slice axis instead of
    # asserting replication over it, so the host can compare the
    # per-slice tables byte-for-byte at finalize under
    # LOCUST_DEBUG_CHECKS.  If a future combine edit lets
    # slice-varying data leak into the merge, the comment's argument
    # rots silently — this check fires loudly instead.
    combine_dbg = jax.jit(
        jax.shard_map(
            combine_step,
            mesh=mesh,
            in_specs=(kv_spec_2d,),
            out_specs=(kv_spec_2d, P(slice_axis)),
            check_vma=False,
        )
    )
    # Stats leave the step VARYING over the slice axis; on a
    # multi-process pod a plain device_get of that stack would touch
    # non-addressable devices.  This tiny replicating gather runs only
    # at SYNC time (every stats_sync_every rounds), so it — not the
    # round path — carries the cross-slice hop.
    replicate_stats = jax.jit(
        jax.shard_map(
            lambda s: jax.lax.all_gather(s, slice_axis, axis=0, tiled=True),
            mesh=mesh,
            in_specs=(P(slice_axis),),
            out_specs=P(),
            check_vma=False,
        )
    )

    return _HierarchicalPrograms(
        step=step,
        combine=combine_all,
        combine_dbg=combine_dbg,
        stats_merge=jax.jit(merge_stats_vectors),
        replicate_stats=replicate_stats,
    )


class HierarchicalMapReduce:
    """Two-level mesh MapReduce: per-slice ICI shuffle + one DCN combine.

    Mirrors ``DistributedMapReduce``'s contract (run(rows) ->
    ``DistributedResult``-shaped result) on a 2-D ``[slice, data]`` mesh.
    """

    def __init__(
        self,
        mesh: jax.sharding.Mesh,
        cfg: EngineConfig,
        slice_axis: str = SLICE_AXIS,
        data_axis: str = DATA_AXIS,
        map_fn=wordcount_map,
        combine: str = "sum",
        skew_factor: float = 2.0,
        shard_capacity: int | None = None,
        bin_capacity: int | None = None,
    ):
        if slice_axis not in mesh.shape or data_axis not in mesh.shape:
            raise ValueError(
                f"mesh must have axes ({slice_axis!r}, {data_axis!r}); "
                f"got {tuple(mesh.shape)}"
            )
        self.mesh = mesh
        self.cfg = cfg
        self.slice_axis = slice_axis
        self.data_axis = data_axis
        self.map_fn = map_fn
        self.combine = combine  # user semantics (host finalize)
        self.n_slices = int(mesh.shape[slice_axis])
        self.devs_per_slice = int(mesh.shape[data_axis])
        self.n_dev = self.n_slices * self.devs_per_slice
        # Intra-slice bins: fair share of one device's emits across the
        # slice's devices, padded for skew (same rule as the flat engine);
        # an explicit bin_capacity shrinks the per-round ICI wire volume
        # (underestimates cost drain rounds, never data — DESIGN.md §3).
        if bin_capacity is not None and bin_capacity < 1:
            raise ValueError(f"bin_capacity must be >= 1, got {bin_capacity}")
        self.bin_capacity = (
            _round_up(int(bin_capacity), 8)
            if bin_capacity is not None
            else sized_bins(cfg.emits_per_block, self.devs_per_slice, skew_factor)
        )
        # Same two-floor default as the flat engine: per-round receive
        # volume OR this device's fair share of cfg.resolved_table_size
        # (+ skew), whichever is larger — an explicitly raised table_size
        # must not truncate at the emits-derived size (fuzz finding, r4).
        self.shard_capacity = (
            shard_capacity
            if shard_capacity is not None
            else max(
                self.devs_per_slice * self.bin_capacity,
                sized_bins(
                    cfg.resolved_table_size, self.devs_per_slice, skew_factor
                ),
            )
        )
        if self.shard_capacity < 1:
            raise ValueError(f"shard_capacity must be >= 1, got {self.shard_capacity}")
        self.leftover_capacity = cfg.emits_per_block
        self.max_drain_rounds = 2 + -(-cfg.emits_per_block // self.bin_capacity)
        norm_map_fn, norm_combine = normalize_combine(map_fn, combine)
        # sort_mode="fused" (megakernel v2): per-shard Pallas kernel when
        # eligible, explicit logged demotion (fused_demoted on results)
        # otherwise — same gate as the flat engine (shuffle.py).
        self._fused_kernel_on, self.fused_demoted = _fused_mesh_gate(
            cfg, map_fn, combine, engine="hierarchical"
        )
        # The five programs belong to the configuration, not to this
        # engine (engine._programs_for, as the flat mesh's do): a process's
        # second engine of an equal configuration takes the same jit
        # objects and traces, lowers and reads back nothing.  The key is
        # what _build_hierarchical_programs takes, the RAW (map_fn,
        # combine) standing for the normalized pair they determine.  The
        # attributes below are this engine's own: a test that assigns
        # ``h._step`` changes no other engine.
        config = (
            cfg, mesh, slice_axis, data_axis, self.bin_capacity,
            self.shard_capacity, self.leftover_capacity,
            self.max_drain_rounds, self._fused_kernel_on,
        )
        programs: _HierarchicalPrograms = _programs_for(
            ("hierarchical", map_fn, combine, *config),
            lambda: _build_hierarchical_programs(
                norm_map_fn, norm_combine, *config
            ),
        )
        self._step = programs.step
        self._combine = programs.combine
        self._combine_dbg = programs.combine_dbg
        self._stats_merge = programs.stats_merge
        self._replicate_stats = programs.replicate_stats

    def _fetch_stats(self, stats):
        return jax.device_get(self._replicate_stats(stats))

    def _check_slice_replication(self, acc: KVBatch) -> None:
        """LOCUST_DEBUG_CHECKS backstop for ``check_vma=False`` on the
        combine: run the combine with the slice axis EXPOSED and assert
        every slice produced the identical table + stats on host.  Cheap
        (the table is bounded by shard_capacity) and loud — the
        replication argument stops being a comment and becomes a runtime
        invariant."""
        from locust_tpu.parallel.mesh import gather_host_array

        table, stats = self._combine_dbg(acc)
        # gather_host_array, NOT np.asarray: on a multi-process pod the
        # debug outputs span non-addressable devices and a plain fetch
        # would crash the check exactly where it matters most.
        parts = {
            "key_lanes": gather_host_array(table.key_lanes),
            "values": gather_host_array(table.values),
            "valid": gather_host_array(table.valid),
            "stats": gather_host_array(stats),
        }
        for name, arr in parts.items():
            per_slice = arr.reshape(self.n_slices, -1)
            bad = [
                s
                for s in range(1, self.n_slices)
                if not np.array_equal(per_slice[s], per_slice[0])
            ]
            if bad:
                raise RuntimeError(
                    "hierarchical combine produced a slice-varying "
                    f"'{name}' (slices {bad} differ from slice 0): the "
                    "replication claim behind check_vma=False is violated "
                    "— a slice-varying input leaked into the cross-slice "
                    "merge"
                )

    # ------------------------------------------------------------------ api

    @property
    def lines_per_round(self) -> int:
        return self.n_dev * self.cfg.block_lines

    def _identity(self) -> dict:
        """Engine/pipeline/mesh identity bound into every checkpoint
        fingerprint (both run and run_stream), so a hierarchical snapshot
        can never be resumed by a different engine/mesh/pipeline over the
        same corpus (shuffle.DistributedMapReduce._identity mirror)."""
        norm_map_fn, _ = normalize_combine(self.map_fn, self.combine)
        return dict(
            engine="hierarchical",
            cfg=repr(self.cfg),
            combine=self.combine,
            map_fn=getattr(norm_map_fn, "__name__", str(norm_map_fn)),
            mesh=(
                f"{self.n_slices}x{self.slice_axis},"
                f"{self.devs_per_slice}x{self.data_axis}"
            ),
            bin_capacity=self.bin_capacity,
            shard_capacity=self.shard_capacity,
        )

    def _fingerprint(self, rows) -> str:
        """Identity of a (corpus, pipeline, mesh) combination for resume."""
        from locust_tpu.io.serde import fingerprint_corpus

        return fingerprint_corpus(rows, **self._identity())

    def run(
        self,
        rows,
        stats_sync_every: int = 16,
        checkpoint_dir: str | None = None,
        checkpoint_every: int = 1,
    ):
        """Run a host ``[n, width]`` row array; returns ``DistributedResult``.

        ``truncated`` reflects both the per-slice partial tables and the
        FINAL combined table (worst shard's distinct keys vs capacity);
        ``drain_rounds`` reports the worst slice's full-run total (the
        wall-clock-relevant number — slices drain independently).

        With ``checkpoint_dir``, the same per-process atomic-npz protocol
        as the flat engine: every ``checkpoint_every`` completed rounds
        the sharded accumulator + backlog + counters snapshot; a re-run
        with the matching fingerprint resumes after the last completed
        round.
        """
        lpr = self.lines_per_round
        nrounds = max(1, -(-rows.shape[0] // lpr))
        chunks = (rows[r * lpr : (r + 1) * lpr] for r in range(nrounds))
        return self._run_rounds(
            chunks,
            stats_sync_every,
            fingerprint=(
                self._fingerprint(rows) if checkpoint_dir is not None else None
            ),
            checkpoint_dir=checkpoint_dir,
            checkpoint_every=checkpoint_every,
        )

    def run_stream(
        self,
        blocks,
        stats_sync_every: int = 16,
        fingerprint: str | None = None,
        checkpoint_dir: str | None = None,
        checkpoint_every: int = 1,
    ):
        """Like ``run`` over an ITERABLE of ``[<=lines_per_round, width]``
        host row blocks — bounded-memory ingest (pair with
        ``io.loader.StreamingCorpus(path, width, self.lines_per_round)``).
        Pass the stream's ``fingerprint()`` to enable checkpoint/resume
        (resume re-reads but does not re-process already-folded rounds).
        """
        from locust_tpu.io.loader import prefetch_blocks

        from locust_tpu.parallel.shuffle import stream_checkpoint_fingerprint

        return self._run_rounds(
            prefetch_blocks(blocks),
            stats_sync_every,
            fingerprint=stream_checkpoint_fingerprint(
                fingerprint, checkpoint_dir, self._identity()
            ),
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
        from locust_tpu.parallel.mesh import shard_rows
        from locust_tpu.parallel.shuffle import (
            DistributedResult,
            ShardedCheckpoint,
        )

        if checkpoint_every < 1:
            raise ValueError(
                f"checkpoint_every must be >= 1, got {checkpoint_every}"
            )
        cfg = self.cfg
        lpr = self.lines_per_round
        width = cfg.line_width
        both = P((self.slice_axis, self.data_axis))
        sharding = jax.sharding.NamedSharding(self.mesh, both)
        acc = jax.device_put(
            KVBatch.empty(self.n_dev * self.shard_capacity, cfg.key_lanes),
            sharding,
        )
        leftover = jax.device_put(
            KVBatch.empty(self.n_dev * self.leftover_capacity, cfg.key_lanes),
            sharding,
        )

        emit_ovf = shuf_ovf = 0
        # Per-slice running drain totals: the merge keeps per-slice sums
        # within a sync window, so summing windows per slice stays exact;
        # the reported number is the worst slice's full-run total.
        drains_by_slice = np.zeros(self.n_slices, np.int64)
        truncated = False
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
                emit_ovf = int(extras["emit_ovf"])
                shuf_ovf = int(extras["shuf_ovf"])
                drains_by_slice[:] = extras["drains_by_slice"]
                truncated = bool(extras["truncated"])

        def snapshot(next_round: int) -> None:
            ckpt.snapshot(
                next_round,
                acc,
                leftover,
                emit_ovf=np.int64(emit_ovf),
                shuf_ovf=np.int64(shuf_ovf),
                drains_by_slice=drains_by_slice,
                truncated=np.bool_(truncated),
            )

        def on_sync(st) -> None:
            """Fold the [n_slices, 6] per-slice stats stack into host
            counters; police the no-loss invariants per slice."""
            nonlocal emit_ovf, shuf_ovf, truncated
            rows_ = np.asarray(st).reshape(self.n_slices, 6)
            emit_ovf += int(rows_[:, 0].sum())
            shuf_ovf += int(rows_[:, 1].sum())
            backlog = int(rows_[:, 3].sum())
            truncated |= int(rows_[:, 4].max()) > self.shard_capacity
            drains_by_slice[:] += rows_[:, 5]
            if backlog > 0:
                raise RuntimeError(
                    f"shuffle backlog failed to drain in "
                    f"{self.max_drain_rounds} rounds ({backlog} entries "
                    "remain); raise skew_factor"
                )
            if shuf_ovf:
                raise RuntimeError(
                    f"shuffle lost {shuf_ovf} entries despite retry mode; "
                    "map_fn emitted more than cfg.emits_per_block live rows"
                )

        round_stats = RoundStats(
            self._stats_merge, on_sync, stats_sync_every,
            fetch_fn=self._fetch_stats,
        )

        def fold_round(chunk) -> None:
            nonlocal acc, leftover
            chunk = normalize_round_chunk(chunk, lpr, width)
            sharded = shard_rows(chunk, self.mesh, (self.slice_axis, self.data_axis))
            acc, leftover, stats = self._step(sharded, acc, leftover)
            round_stats.push(stats)

        drive_checkpointed_rounds(
            chunk_iter, fold_round, round_stats, ckpt, snapshot,
            checkpoint_every, start_round,
        )
        drains_used = int(drains_by_slice.max())

        # The one DCN hop: cross-slice merge of the bounded tables.
        if os.environ.get("LOCUST_DEBUG_CHECKS"):
            self._check_slice_replication(acc)
        table, cstats = self._combine(acc)
        cstats = jax.device_get(cstats)
        distinct = int(cstats[0])
        truncated |= int(cstats[1]) > self.shard_capacity
        if truncated:
            logger.warning(
                "a shard's distinct keys exceeded its table capacity (%d); "
                "tail keys dropped — raise shard_capacity",
                self.shard_capacity,
            )
        return DistributedResult(
            table=table,
            emit_overflow=emit_ovf,
            shuffle_overflow=shuf_ovf,
            distinct=distinct,
            combine=self.combine,
            drain_rounds=drains_used,
            truncated=truncated,
            fused_kernel="mesh" if self._fused_kernel_on else None,
            fused_demoted=self.fused_demoted,
            shard_capacity=self.shard_capacity,
        )
