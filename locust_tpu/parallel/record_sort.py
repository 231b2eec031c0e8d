"""The record sort across a mesh: TeraSort's range shuffle on TPU collectives.

``engine.RecordSort`` keeps the whole data set on ONE device.  Here the
file's blocks are dealt round the mesh's devices, every record moves to
the device that owns its key RANGE through one all-to-all, each device
sorts what it received, and the shards, laid end to end, are the sorted
file — byte-equal to the plain reference (``records_reference.py``),
ties in input order across devices too:

  1. STAGE    block ``b`` of the mapped file goes to device ``b % n_dev``
              and is placed at local block ``b // n_dev`` of ONE resident
              ``RecordBatch`` a device.  A local row's place in the input
              — its GLOBAL ROW INDEX — follows from where it lies, so
              input order is never stored: it is recomputed on the device.
              Dealt blocks, not contiguous quarters: an input that is
              already sorted then still sends every device a fair share
              of every range.
  2. SPLIT    every device samples ``SAMPLES_PER_DEVICE`` rows at a
              stride; one ``all_gather``; the samples sorted by (key
              lanes, global row index); ``n_dev - 1`` splitters at the
              quantiles.  A splitter is a (key, global row index) PAIR:
              no two rows compare equal, so a key that straddles a
              splitter is cut by input position — the partition is
              balanced under ANY duplication (all keys equal included)
              and equal keys land in input order across shards for free.
  3. EXCHANGE bucket = #splitters <= (key, index); whole records binned by
              ``shuffle.partition_words_to_bins`` (``partition_to_bins``'
              grouping with a ``[N, W]`` payload); one ``all_to_all`` of
              the record words and one of the row indices (-1 marks a dead
              slot).  Every device reports the rows it SENT each bin; where
              one passes ``bin_rows`` the exchange is redone, the records
              still resident, with bins that hold it — counted
              (``sort.mesh.retries``), never dropped.  At ``bin_rows`` = a
              device's rows nothing can overflow.
  4. SHARD SORT  (key lanes, global row index) through ONE ``lax.sort``
              (``ops/process_stage.order_by_lanes``, the one-chip sort's
              spelling); the payload does not move yet.
  5. SINK     ``MeshSortedRecords.host_blocks``: shard 0's blocks, then
              shard 1's, ...; the permutes run ahead of the sink on every
              device at once (each gathers its own shard, a round of
              blocks at a time), a few blocks' copies are in flight while
              the one before is written.

Single process (one host's chips): the sink reads every shard from its
device, which a multi-process pod would do a process a shard.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import math
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from locust_tpu import obs
from locust_tpu.core.kv import RecordBatch
from locust_tpu.engine import (
    StagedRecords,
    _programs_for,
    fetch_record_block,
    record_block_rows,
)
from locust_tpu.ops.process_stage import order_by_lanes
from locust_tpu.parallel.mesh import DATA_AXIS
from locust_tpu.parallel.shuffle import partition_words_to_bins

class BinOverflow(ValueError):
    """The exchange could not place every record within its retry budget."""


def range_splitters(samples: jax.Array, ok: jax.Array, n_parts: int) -> jax.Array:
    """``n_parts - 1`` splitters ``[n_parts - 1, L]`` from samples
    ``[M, L]`` (uint32 lanes, most significant first; ``ok`` ``[M]`` says
    which are real): the samples sorted, the real ones first, and the
    splitters at the quantiles of the real prefix."""
    n_lanes = samples.shape[1]
    inv = (~ok).astype(jnp.uint32)
    out = jax.lax.sort(
        (inv, *(samples[:, i] for i in range(n_lanes))), num_keys=1 + n_lanes
    )
    ordered = jnp.stack(out[1:], axis=-1)
    n_ok = jnp.sum(ok.astype(jnp.int32))
    j = jnp.arange(n_parts - 1, dtype=jnp.int32) + 1
    # j * n_ok stays under 2^31: M is a few thousand samples a device.
    at = jnp.clip(j * n_ok // n_parts, 0, samples.shape[0] - 1)
    return ordered[at]


def range_bucket(lanes: Sequence[jax.Array], splitters: jax.Array) -> jax.Array:
    """The range a row falls in: how many of ``splitters`` (``[S, L]``)
    are <= the row, lexicographically over its ``L`` lanes (``[N]``
    each) — uint32 ``[N]`` in ``[0, S]``.  Elementwise over the rows, a
    splitter at a time: S is the mesh's size less one."""
    bucket = jnp.zeros(lanes[0].shape, jnp.uint32)
    for s in range(splitters.shape[0]):
        ge = jnp.ones(lanes[0].shape, bool)          # equal => >=
        for i in range(len(lanes) - 1, -1, -1):
            ref = splitters[s, i]
            ge = (lanes[i] > ref) | ((lanes[i] == ref) & ge)
        bucket = bucket + ge.astype(jnp.uint32)
    return bucket


@dataclasses.dataclass(frozen=True)
class _MeshRecordPrograms:
    """The mesh record sort's jitted programs, one record a (record
    width, key width, mesh) a process (``engine._programs_for``).  Rows a
    device and bin rows are shapes and static arguments: jit keeps a
    program a value, all in this one record."""

    empty: Callable       # rows a device -> the resident RecordBatch, zeros
    place: Callable       # (records, a round's blocks, at) -> records (donated)
    split: Callable       # (records, n; block_rows, samples) -> splitters
    partition: Callable   # (records, splitters, n; block_rows, bin_rows) -> shards, indices, counts
    sort_shard: Callable  # (shard words, indices; block_rows) -> perm [blocks, block_rows] a device
    permute: Callable     # (ONE device's shard, its perm, block) -> that block's words, sorted


def _build_mesh_record_programs(record_bytes: int, key_bytes: int,
                                mesh: jax.sharding.Mesh,
                                axis: str) -> _MeshRecordPrograms:
    """Define and jit the mesh record sort of ``record_bytes``-byte records
    by their first ``key_bytes`` bytes over ``mesh``.  Nothing is traced
    here, and nothing names a sorter."""
    words = RecordBatch.num_words(record_bytes)
    n_dev = mesh.shape[axis]
    sharded = NamedSharding(mesh, P(axis))

    def global_rows(r: jax.Array, block_rows: int) -> jax.Array:
        """The input position of this device's local rows ``r``: local
        block ``k`` is the file's block ``k * n_dev + d``."""
        d = jax.lax.axis_index(axis).astype(jnp.int32)
        return ((r // block_rows) * n_dev + d) * block_rows + r % block_rows

    def all_global_rows(rows: int, block_rows: int) -> jax.Array:
        return global_rows(jnp.arange(rows, dtype=jnp.int32), block_rows)

    def keyed(rows_words: jax.Array, index: jax.Array) -> tuple[jax.Array, ...]:
        """(key lanes, global row index): the order of the whole sort."""
        return (*RecordBatch(rows_words).key_lanes(key_bytes),
                index.astype(jnp.uint32))

    def empty_mesh_records(rows: int) -> RecordBatch:
        return RecordBatch.empty(n_dev * rows, record_bytes)

    def place_mesh_records(rec_words: jax.Array, blocks: jax.Array,
                           at: jax.Array) -> jax.Array:
        return jax.lax.dynamic_update_slice(
            rec_words, blocks.reshape(-1, words), (at, 0)
        )

    def sample_mesh_records(rec_words: jax.Array, n: jax.Array,
                            block_rows: int, samples: int):
        rows = rec_words.shape[0]
        index = all_global_rows(rows, block_rows)
        # A device's real rows are a prefix of its local rows (whole
        # blocks, then the file's short last one, then none).
        n_local = jnp.sum((index < n).astype(jnp.int32))
        # ``take`` rows at a stride, each once: a device with few rows
        # has few votes.  floor(i * n_local / take) without the product
        # that would wrap int32 (x64 is off): samples^2 is far under 2^31.
        take = jnp.clip(n_local, 1, samples)
        i = jnp.arange(samples, dtype=jnp.int32)
        at = i * (n_local // take) + (i * (n_local % take)) // take
        at = jnp.clip(at, 0, rows - 1)
        sample = jnp.stack(keyed(rec_words[at], index[at]), axis=-1)
        return sample, i < n_local

    def partition_mesh_records(rec_words: jax.Array, splitters: jax.Array,
                               n: jax.Array, block_rows: int, bin_rows: int):
        index = all_global_rows(rec_words.shape[0], block_rows)
        bucket = jnp.where(
            index < n, range_bucket(keyed(rec_words, index), splitters),
            jnp.uint32(n_dev),
        )
        send_words, row, counts = partition_words_to_bins(
            rec_words, bucket, n_dev, bin_rows
        )
        # What marks a slot live, and what orders equal keys: the global
        # row index of the record in it (-1 in a dead slot).
        send_index = jnp.where(row >= 0, global_rows(row, block_rows), -1)
        recv_words = jax.lax.all_to_all(send_words, axis, 0, 0)
        recv_index = jax.lax.all_to_all(send_index, axis, 0, 0)
        return recv_words.reshape(-1, words), recv_index.reshape(-1), counts

    def sort_shard_keys(shard_words: jax.Array, index: jax.Array,
                        block_rows: int) -> jax.Array:
        # A dead slot (index -1) sorts behind every record: a key of all
        # ones and, as a lane, the row index no record has.
        lanes = [
            jnp.where(index >= 0, lane, jnp.uint32(0xFFFFFFFF))
            for lane in keyed(shard_words, index)
        ]
        _, perm = order_by_lanes(lanes)
        perm = jnp.pad(perm, (0, -perm.shape[0] % block_rows))
        return perm.reshape(-1, block_rows)

    def permute_shard_records(shard_words: jax.Array, perm: jax.Array,
                              block: jax.Array) -> jax.Array:
        return RecordBatch(shard_words).take(perm[block]).words.reshape(-1)

    def on_mesh(body, in_specs, out_specs, sizes=()):
        """``body`` under ``shard_map`` as ONE jitted mesh program named
        after it (``jit_<body's name>``: how the trace's ``XLA Modules``
        line tells programs apart); ``sizes`` are its static arguments."""
        def program(*arrays, **static):
            return jax.shard_map(
                functools.partial(body, **static), mesh=mesh,
                in_specs=in_specs, out_specs=out_specs,
            )(*arrays)

        program.__name__ = program.__qualname__ = body.__name__
        return jax.jit(program, static_argnames=sizes)

    def split_mesh_records(rec_words: jax.Array, n: jax.Array,
                           block_rows: int, samples: int) -> jax.Array:
        # The samples leave their devices sharded and the splitters are
        # chosen on the whole: the compiler gathers them (one all-gather).
        sample, ok = jax.shard_map(
            functools.partial(sample_mesh_records, block_rows=block_rows,
                              samples=samples),
            mesh=mesh, in_specs=(P(axis), P()), out_specs=(P(axis), P(axis)),
        )(rec_words, n)
        return range_splitters(sample, ok, n_dev)

    return _MeshRecordPrograms(
        empty=jax.jit(empty_mesh_records, static_argnames="rows",
                      out_shardings=RecordBatch(sharded)),
        place=jax.jit(
            jax.shard_map(place_mesh_records, mesh=mesh,
                          in_specs=(P(axis), P(axis), P()), out_specs=P(axis)),
            donate_argnums=0),
        split=jax.jit(split_mesh_records,
                      static_argnames=("block_rows", "samples"),
                      out_shardings=NamedSharding(mesh, P())),
        partition=on_mesh(partition_mesh_records, (P(axis), P(), P()),
                          (P(axis), P(axis), P(axis)),
                          sizes=("block_rows", "bin_rows")),
        sort_shard=on_mesh(sort_shard_keys, (P(axis), P(axis)), P(axis),
                           sizes=("block_rows",)),
        permute=jax.jit(permute_shard_records),
    )


@dataclasses.dataclass
class MeshStagedRecords(StagedRecords):
    """A job's records on the mesh (``MeshRecordSort.load``): ``records``
    is ONE global ``RecordBatch`` of ``n_dev x rows_per_device`` rows,
    device ``d``'s part holding the file's blocks ``d, d + n_dev, ...``."""

    rows_per_device: int


class MeshSortedRecords:
    """A sorted job whose shards are still on their devices in the order
    they arrived (``MeshRecordSort.sort``): ``shard_rows[d]`` records on
    device ``d``, every key of shard ``d`` before every key of shard
    ``d + 1``; ``host_blocks`` permutes and brings them back."""

    def __init__(self, sorter: "MeshRecordSort", shards: jax.Array,
                 perm: jax.Array, shard_rows: Sequence[int],
                 splitters: np.ndarray, block_rows: int):
        self._sorter = sorter
        self._shards, self._perm = shards, perm
        self.shard_rows = [int(r) for r in shard_rows]
        self.splitters = splitters  # [n_dev - 1, key lanes + 1]: (key, global row index)
        self.block_rows = block_rows
        self.n_records = sum(self.shard_rows)

    def host_blocks(self, shards: Sequence[int] | None = None):
        """The sorted records as host ``uint8`` arrays, in order, a block
        each: shard 0's, then shard 1's, ... (``shards``: only those, in
        that order — a shard as its device holds it).  The permutes run
        AHEAD of the sink on every device at once: ``ROUNDS_AHEAD`` rounds
        (block ``b`` of every shard) are launched first and one more with
        every block handed on, so the later shards' gathers are done
        while the earlier shards are written — a sorted copy of a shard is
        held on its device meanwhile — and the host never waits for a
        device queue to drain.  At most ``BLOCKS_IN_FLIGHT`` blocks'
        copies are on their way down."""
        sorter, rows = self._sorter, self.block_rows
        devices = list(sorter.mesh.devices.flat)
        shard_of = {s.device: s.data for s in self._shards.addressable_shards}
        perm_of = {s.device: s.data for s in self._perm.addressable_shards}
        shards = range(len(devices)) if shards is None else shards
        blocks = {d: -(-self.shard_rows[d] // rows) for d in shards}
        permuted = {}

        def launch_round(b: int) -> None:
            for d in shards:
                if b < blocks[d]:
                    with obs.span("sort.permute", rows=rows, device=d):
                        permuted[d, b] = sorter.programs.permute(
                            shard_of[devices[d]], perm_of[devices[d]], np.int32(b)
                        )

        order = [(d, b) for d in shards for b in range(blocks[d])]
        ahead = max(sorter.ROUNDS_AHEAD, sorter.BLOCKS_IN_FLIGHT + 1)
        for b in range(ahead):
            launch_round(b)
        pending: collections.deque = collections.deque()
        copying = 0
        for done, (d, b) in enumerate(order):
            launch_round(ahead + done)
            # Block k of the sink's order lies in a round <= k: launched.
            while copying < len(order) and len(pending) < sorter.BLOCKS_IN_FLIGHT:
                flat = permuted.pop(order[copying])
                flat.copy_to_host_async()
                pending.append(flat)
                copying += 1
            n = min(rows, self.shard_rows[d] - b * rows)
            yield fetch_record_block(pending.popleft(), n, sorter.record_bytes)


class MeshRecordSort:
    """The record sort of one (record width, key width) over a mesh:
    ``load`` the records round its devices, ``sort`` = split, exchange
    and the shards' key sorts, read back in key order from
    ``MeshSortedRecords.host_blocks``.  Holds the configuration's
    programs (``engine._programs_for``) and no data."""

    # Sample keys a device: the splitters' quantile error is
    # sqrt(p (1 - p) / samples taken) = 0.34% of the records at 4 x 4,096,
    # 1.4% of a shard — nothing next to sorting millions of records.
    SAMPLES_PER_DEVICE = 4096
    # Exchanges redone with larger bins before the job gives up; the first
    # retry already holds (the counts are exact), the second is slack.
    MAX_RETRIES = 2
    # Sorted blocks whose copies are on their way down while one is written.
    BLOCKS_IN_FLIGHT = 8
    # Rounds of permutes (a block of every shard) launched before the sink
    # starts; a device queue that fills blocks the launching host.
    ROUNDS_AHEAD = 16

    def __init__(self, mesh: jax.sharding.Mesh, record_bytes: int,
                 key_bytes: int, axis_name: str = DATA_AXIS):
        if not 1 <= key_bytes <= record_bytes:
            raise ValueError(
                f"key_bytes {key_bytes} must lie in 1..record_bytes "
                f"({record_bytes})"
            )
        if jax.process_count() > 1:
            raise ValueError(
                "the mesh record sort reads every shard from one process; "
                f"this is process {jax.process_index()} of {jax.process_count()}"
            )
        self.mesh, self.axis = mesh, axis_name
        self.n_dev = mesh.shape[axis_name]
        self.record_bytes, self.key_bytes = record_bytes, key_bytes
        self.programs: _MeshRecordPrograms = _programs_for(
            ("mesh_records", record_bytes, key_bytes, mesh, axis_name),
            lambda: _build_mesh_record_programs(
                record_bytes, key_bytes, mesh, axis_name),
        )

    def bin_rows(self, rows_per_device: int) -> int:
        """Rows of one (source, destination) bin at the first attempt: a
        fair share, an eighth over it (nine standard deviations of what
        the sample leaves a shard), and eight standard deviations of a
        binomial count — what a small job needs, where that is the larger
        part.  Never more than a device's rows, which always hold."""
        fair = -(-rows_per_device // self.n_dev)
        rows = fair + fair // 8 + 8 * math.isqrt(fair) + 8
        return min(rows_per_device, -(-rows // 8) * 8)

    def load(self, source) -> MeshStagedRecords:
        """``source`` (``io/loader.RecordSource``) onto the mesh: block
        ``b`` handed to device ``b % n_dev`` (``device_put`` returns at
        once) and, a round of ``n_dev`` blocks at a time, placed into the
        one resident ``RecordBatch``; returns when the last is there."""
        if source.record_bytes != self.record_bytes:
            raise ValueError(
                f"source holds {source.record_bytes}-byte records, this "
                f"sort takes {self.record_bytes}"
            )
        n_dev = self.n_dev
        rows = record_block_rows(self.record_bytes, -(-source.n_records // n_dev))
        rounds = -(-source.n_records // (rows * n_dev))
        if rounds * rows * n_dev >= 2 ** 31:
            raise ValueError(
                f"{source.n_records} records: a row's place in the input "
                "is an int32 on the device"
            )
        devices = list(self.mesh.devices.flat)
        sharding = NamedSharding(self.mesh, P(self.axis))
        words = RecordBatch.num_words(self.record_bytes)
        records = self.programs.empty(rows=rounds * rows).words
        nothing = np.zeros(rows * words, np.uint32)  # for the devices past the file's end
        blocks = source.blocks(rows)
        for at in range(0, rounds * rows, rows):
            parts = []
            for d, dev in enumerate(devices):
                block = next(blocks, None)
                size = 0 if block is None else block.nbytes
                with obs.span("sort.h2d", bytes=size, device=d):
                    parts.append(jax.device_put(nothing if block is None else block, dev))
            records = self.programs.place(
                records,
                jax.make_array_from_single_device_arrays(
                    (n_dev * rows * words,), sharding, parts),
                np.int32(at),
            )
        with obs.span("engine.sync", what="h2d"):
            jax.block_until_ready(records)
        obs.metric_inc("sort.records", source.n_records)
        return MeshStagedRecords(RecordBatch(records), source.n_records, rows,
                                 rounds * rows)

    def _exchange(self, words, splitters, n, block_rows: int, bin_rows: int,
                  attempt: int):
        """One range exchange at ``bin_rows``: ``(shards, row indices,
        sent)``, ``sent[s, d]`` the records device ``s`` had for device
        ``d`` — all of them arrived only where none passes ``bin_rows``."""
        with obs.span("sort.mesh.exchange", bin_rows=bin_rows,
                      attempt=attempt) as span:
            shards, index, counts = self.programs.partition(
                words, splitters, n, block_rows=block_rows, bin_rows=bin_rows)
            with obs.span("engine.sync", what="exchange"):
                sent = np.asarray(counts).reshape(self.n_dev, self.n_dev)  # locust: noqa[R003] the exchange's one wait: what each bin was sent decides whether it stands
            span.set(worst_bin=int(sent.max()))
        return shards, index, sent

    def sort(self, staged: MeshStagedRecords) -> MeshSortedRecords:
        """Split, exchange (redone while a bin overflows, within
        ``MAX_RETRIES``) and the shards' key sorts; the sorted payload
        moves in ``host_blocks``.  Raises ``BinOverflow`` where the
        budget ends before every record has a place."""
        n_dev, rows = self.n_dev, staged.block_rows
        n = np.int32(staged.n_records)
        words = staged.records.words
        with obs.span("sort.mesh.split", samples=n_dev * self.SAMPLES_PER_DEVICE,
                      splitters=n_dev - 1):
            splitters = self.programs.split(
                words, n, block_rows=rows, samples=self.SAMPLES_PER_DEVICE)
            with obs.span("engine.sync", what="split"):
                jax.block_until_ready(splitters)
        bin_rows, attempt = self.bin_rows(staged.rows_per_device), 0
        shards, index, sent = self._exchange(words, splitters, n, rows, bin_rows, attempt)
        while sent.max() > bin_rows:
            worst = int(sent.max())
            if attempt >= self.MAX_RETRIES:
                raise BinOverflow(
                    f"a bin of the range exchange was sent {worst} records and "
                    f"holds {bin_rows}; {attempt} of {self.MAX_RETRIES} retries "
                    "used: not every record has a place, nothing is written"
                )
            del shards, index  # the bins that did not hold go before larger ones come
            larger = bin_rows
            while larger < worst:
                larger *= 2
            larger = min(larger, staged.rows_per_device)
            with obs.span("sort.mesh.retry", from_bin_rows=bin_rows,
                          to_bin_rows=larger, worst_bin=worst):
                obs.metric_inc("sort.mesh.retries")
                bin_rows, attempt = larger, attempt + 1
                shards, index, sent = self._exchange(
                    words, splitters, n, rows, bin_rows, attempt)
        shard_rows = sent.sum(axis=0)
        with obs.span("sort.mesh.shard_sort", rows=int(shard_rows.max())):
            perm = self.programs.sort_shard(shards, index, block_rows=rows)
            with obs.span("engine.sync", what="keys"):
                jax.block_until_ready(perm)
        obs.metric_inc("sort.mesh.retries", 0)
        obs.metric_set("sort.mesh.bin_rows", bin_rows)
        obs.metric_set("sort.mesh.shard_rows_max", int(shard_rows.max()))
        obs.metric_set("sort.mesh.shard_rows_min", int(shard_rows.min()))
        moved = int(sent.sum() - np.trace(sent)) * self.record_bytes
        obs.metric_inc("sort.mesh.bytes_exchanged", moved)
        return MeshSortedRecords(self, shards, perm, shard_rows,
                                 np.asarray(splitters), rows)
