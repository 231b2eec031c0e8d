"""Distributed sample sort: TeraSort-style global ordering over the mesh.

A capability the reference gestures at but never delivers: its "Process"
stage sorts one GPU's emits (thrust::sort, reference MapReduce/src/
main.cu:414-415) and its multi-node mode simply assumes globally sorted
intermediate input (SURVEY.md Q6).  This app provides the real thing — a
global sort of (key, value) records across all mesh devices — using the
classic sample-sort recipe on TPU collectives:

  1. SAMPLE   every device takes a strided sample of its local keys; one
              ``all_gather`` shares all samples; every device sorts the
              (small) sample set identically and picks n_dev-1 splitters.
  2. PARTITION bucket = #splitters <= key (lexicographic compare over the
              lanes, parallel/record_sort.range_bucket); scatter into
              equal-capacity bins; one ``all_to_all`` — the range shuffle.
  3. LOCAL SORT each device lex-sorts what it received (full-lane
              ``lax.sort``: exact byte order, ops/process_stage "lex" mode).

Device d then holds range-shard d, internally sorted, and every key on
device d precedes every key on device d+1 — a globally sorted sequence.

This is the LIBRARY sort of (key, ``int32``) pairs, with fixed bins: a
skewed input (duplicate-heavy keys) can overflow a range bin, the rows
that did not fit are counted (``SortResult.overflow``, psum'd like the
hash shuffle's, SURVEY.md §7.3.3) and ``sort_strings`` rebuilds the sort
with doubled bins until none is.  The CLI's ``sort IN OUT --mesh`` is NOT
this class: it carries whole records, cuts a duplicated key by input
position and redoes an overflowing exchange on the resident data
(parallel/record_sort.py) — it never drops a row.  Both take their
splitters and buckets from that module's ``range_splitters`` /
``range_bucket``: one range partition.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from locust_tpu.config import EngineConfig
from locust_tpu.core import bytes_ops
from locust_tpu.core.kv import KVBatch
from locust_tpu.ops.process_stage import sort_and_compact
from locust_tpu.parallel.mesh import DATA_AXIS, shard_rows
from locust_tpu.parallel.record_sort import range_bucket, range_splitters
from locust_tpu.parallel.shuffle import partition_to_bins


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


class DistributedSort:
    """Globally sort fixed-width byte keys (with int32 payloads) on a mesh."""

    def __init__(
        self,
        mesh: jax.sharding.Mesh,
        cfg: EngineConfig,
        rows_per_device: int,
        axis_name: str = DATA_AXIS,
        sample_per_device: int = 64,
        skew_factor: float = 2.0,
    ):
        self.mesh = mesh
        self.cfg = cfg
        self.axis = axis_name
        self.n_dev = mesh.shape[axis_name]
        self.rows_per_device = rows_per_device
        from locust_tpu.parallel.shuffle import sized_bins

        self.bin_capacity = sized_bins(rows_per_device, self.n_dev, skew_factor)
        self.shard_capacity = self.n_dev * self.bin_capacity
        n_lanes = cfg.key_lanes
        axis = axis_name
        n_dev = self.n_dev

        def local_sort(keys_rows: jax.Array, values: jax.Array, valid: jax.Array):
            """Per-device body (under shard_map): sample -> range shuffle -> sort."""
            kv = KVBatch.from_bytes(keys_rows, values, valid)
            lanes = kv.key_lanes

            # 1. SAMPLE: prefer VALID rows (padding rows would drag splitters
            # to zero and funnel every real key into one overflowing bin) —
            # compact valid rows to the front with a 1-key sort, then sample
            # the valid prefix AT A STRIDE: shard_rows hands each device a
            # contiguous (often internally clustered) line range, so a
            # prefix sample would bias the splitters and skew the bins.
            inv = (~valid).astype(jnp.uint32)
            row_idx = jnp.arange(lanes.shape[0], dtype=jnp.int32)
            _, compact_idx = jax.lax.sort((inv, row_idx), num_keys=1)
            n_valid_local = jnp.sum(valid.astype(jnp.int32))
            s = sample_per_device
            # floor(i*n/s) computed without the i*n product, which would
            # wrap int32 once rows_per_device * s exceeds 2^31 (x64 is off).
            i = jnp.arange(s, dtype=jnp.int32)
            stride_idx = i * (n_valid_local // s) + (i * (n_valid_local % s)) // s
            take = compact_idx[jnp.clip(stride_idx, 0, lanes.shape[0] - 1)]
            sample = lanes[take]                             # [s, L]
            sample_ok = valid[take]                          # [s]
            all_samples = jax.lax.all_gather(sample, axis)   # [n_dev, s, L]
            all_ok = jax.lax.all_gather(sample_ok, axis)     # [n_dev, s]
            # Samples sorted with the invalid LAST, the n_dev-1 splitters
            # at quantiles of the VALID prefix only.
            splitters = range_splitters(
                all_samples.reshape(-1, n_lanes), all_ok.reshape(-1), n_dev
            )                                                # [n_dev-1, L]

            # 2. PARTITION + all_to_all (range shuffle).
            bucket = range_bucket(
                [lanes[:, i] for i in range(n_lanes)], splitters
            )                                                # [N] in [0, n_dev)
            send_lanes, send_vals, send_valid, overflow, _ = partition_to_bins(
                kv, n_dev, self.bin_capacity, bucket=bucket
            )
            recv_lanes = jax.lax.all_to_all(send_lanes, axis, 0, 0)
            recv_vals = jax.lax.all_to_all(send_vals, axis, 0, 0)
            recv_valid = jax.lax.all_to_all(send_valid, axis, 0, 0)

            # 3. LOCAL SORT: exact lexicographic order within the range shard.
            received = KVBatch(
                key_lanes=recv_lanes.reshape(-1, n_lanes),
                values=recv_vals.reshape(-1),
                valid=recv_valid.reshape(-1),
            )
            srt = sort_and_compact(received, mode="lex")
            return srt, jax.lax.psum(overflow, axis)

        kv_spec = KVBatch(key_lanes=P(axis), values=P(axis), valid=P(axis))
        self._step = jax.jit(
            jax.shard_map(
                local_sort,
                mesh=mesh,
                in_specs=(P(axis), P(axis), P(axis)),
                out_specs=(kv_spec, P()),
            )
        )

    # ------------------------------------------------------------------ api

    def sort_rows(
        self, keys: np.ndarray, values: np.ndarray | None = None
    ) -> "SortResult":
        """Globally sort host ``[n, key_width]`` byte rows (+ optional values).

        n must be <= n_dev * rows_per_device; shorter inputs are padded with
        invalid rows.
        """
        total = self.n_dev * self.rows_per_device
        n = keys.shape[0]
        if n > total:
            raise ValueError(f"{n} rows > capacity {total}; raise rows_per_device")
        if values is None:
            values = np.arange(n, dtype=np.int32)  # original index payload
        pk = np.zeros((total, self.cfg.key_width), np.uint8)
        pk[:n] = keys[:, : self.cfg.key_width]
        pv = np.zeros((total,), np.int32)
        pv[:n] = values
        pvalid = np.zeros((total,), bool)
        pvalid[:n] = True
        table, overflow = self._step(
            shard_rows(pk, self.mesh, self.axis),
            shard_rows(pv, self.mesh, self.axis),
            shard_rows(pvalid, self.mesh, self.axis),
        )
        return SortResult(table, int(jax.device_get(overflow)), self.shard_capacity)


class SortResult:
    def __init__(self, table: KVBatch, overflow: int, shard_capacity: int):
        self.table = table
        self.overflow = overflow
        self.shard_capacity = shard_capacity

    def to_host_sorted(self) -> list[tuple[bytes, int]]:
        """Concatenate per-device sorted valid prefixes -> global order.

        Warns loudly if rows did not fit their range bins (``overflow``):
        the result is then NOT a permutation of the input and must not be
        used as one — re-sort with a higher skew_factor (``sort_strings``
        does, and raises rather than return a short list).  Only this
        library class has that outcome; the CLI's mesh sort redoes the
        exchange instead (parallel/record_sort.py).
        """
        if self.overflow:
            import logging

            logging.getLogger("locust_tpu").warning(
                "sample sort: %d rows did not fit their range bins; the "
                "output is NOT the whole input — raise skew_factor",
                self.overflow,
            )
        if jax.process_count() > 1:  # exercised by tests/test_multiprocess.py
            from jax.experimental import multihost_utils

            lanes, values, valid = multihost_utils.process_allgather(
                (self.table.key_lanes, self.table.values, self.table.valid),
                tiled=True,
            )
        else:
            lanes, values, valid = jax.device_get(
                (self.table.key_lanes, self.table.values, self.table.valid)
            )
        out: list[tuple[bytes, int]] = []
        n_shards = lanes.shape[0] // self.shard_capacity
        for d in range(n_shards):
            lo, hi = d * self.shard_capacity, (d + 1) * self.shard_capacity
            m = np.asarray(valid[lo:hi])
            shard_lanes = np.asarray(lanes[lo:hi])[m]
            shard_vals = np.asarray(values[lo:hi])[m]
            n_rows, n_lanes = shard_lanes.shape
            keys = shard_lanes.astype(">u4").view(np.uint8).reshape(n_rows, n_lanes * 4)
            out.extend(
                (k, int(v))
                for k, v in zip(bytes_ops.rows_to_strings(keys), shard_vals)
            )
        return out


def sort_strings(
    strings: list[bytes],
    mesh: jax.sharding.Mesh,
    cfg: EngineConfig | None = None,
    max_retries: int | None = None,
    **kw,
) -> list[bytes]:
    """Convenience: globally sort byte strings, truncated to key_width.

    Lossless: if a skewed/duplicate-heavy distribution overflows a range
    bin, the sort is retried with DOUBLED skew_factor (bigger bins).  The
    default budget doubles until ``skew_factor >= n_dev``, at which point
    one bin holds an entire device shard and overflow is impossible — so
    the default path cannot fail on ANY input that fits the mesh.  An
    explicit ``max_retries`` caps the doublings instead, raising
    ``ValueError`` rather than returning a silently truncated "sorted"
    list (round-1 advisor finding: the old code dropped rows with only a
    counter).
    """
    cfg = cfg or EngineConfig()
    n_dev = mesh.shape[DATA_AXIS]
    rows_per_device = _round_up(max(1, -(-len(strings) // n_dev)), 8)
    rows = bytes_ops.strings_to_rows(strings, cfg.key_width)
    skew = kw.pop("skew_factor", 2.0)
    if max_retries is None:
        max_retries = max(1, math.ceil(math.log2(max(2.0, n_dev / skew))) + 1)
    for _ in range(max_retries + 1):
        ds = DistributedSort(mesh, cfg, rows_per_device, skew_factor=skew, **kw)
        res = ds.sort_rows(rows)
        if res.overflow == 0:
            return [k for k, _ in res.to_host_sorted()]
        skew *= 2.0
    raise ValueError(
        f"sample sort still dropped {res.overflow} rows at "
        f"skew_factor={skew / 2}; input too skewed for this mesh"
    )
