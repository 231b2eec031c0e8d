"""The segment combine against a plain reference kept HERE.

``ops/reduce_stage.segment_reduce_into`` computes ``sum`` and ``count``
without a scatter over its input rows (segment starts compacted by a
one-operand sort, totals read as differences of a running sum).  The
reference below is the two-scatter body it replaced, word for word: every
case holds the new function to it byte for byte — key lanes, values,
validity and the true segment count — and a second, numpy run-length
reference holds both on the cases where the precondition (valid rows
first, equal keys adjacent) is met.  The structural cases pin the
mechanism on the CPU: no scatter over the input rows in the lowered text
of the programs the benchmark's cells run.
"""

import re
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from locust_tpu import engine as engine_mod
from locust_tpu import obs
from locust_tpu.config import EngineConfig
from locust_tpu.core.kv import KVBatch
from locust_tpu.ops.process_stage import sort_and_compact
from locust_tpu.ops.reduce_stage import (
    COMBINERS,
    combine_scatters,
    segment_reduce,
    segment_reduce_into,
)

LANES = 3


def reference_into(batch: KVBatch, out_size: int, combine: str):
    """The parent's ``segment_reduce_into``: two scatters over the input."""
    lanes, values, valid = batch.key_lanes, batch.values, batch.valid
    n = lanes.shape[0]
    prev = jnp.roll(lanes, 1, axis=0)
    neq = jnp.any(lanes != prev, axis=-1)
    first = jnp.arange(n) == 0
    boundary = valid & (first | neq)
    seg = jnp.cumsum(boundary.astype(jnp.int32)) - 1
    num_segments = jnp.sum(boundary.astype(jnp.int32))
    ids = jnp.where(valid, jnp.minimum(seg, out_size), out_size)
    if combine == "sum":
        combined = jax.ops.segment_sum(values, ids, num_segments=out_size + 1)
    elif combine == "count":
        combined = jax.ops.segment_sum(
            jnp.ones_like(values), ids, num_segments=out_size + 1
        )
    elif combine == "min":
        combined = jax.ops.segment_min(values, ids, num_segments=out_size + 1)
    else:
        combined = jax.ops.segment_max(values, ids, num_segments=out_size + 1)
    combined = combined[:out_size]
    start = jax.ops.segment_min(
        jnp.arange(n, dtype=jnp.int32),
        jnp.where(boundary, jnp.minimum(seg, out_size), out_size),
        num_segments=out_size + 1,
    )[:out_size]
    out_valid = jnp.arange(out_size, dtype=jnp.int32) < num_segments
    safe_start = jnp.where(out_valid, start, 0)
    out_lanes = lanes[safe_start] * out_valid[:, None].astype(lanes.dtype)
    return (
        KVBatch(out_lanes, jnp.where(out_valid, combined, 0), out_valid),
        num_segments,
    )


def runlength_reference(lanes, values, valid, out_size, combine):
    """numpy run-length loop over a batch that meets the precondition."""
    rows = []
    i, n_valid = 0, int(valid.sum())
    while i < n_valid:
        j = i
        while j + 1 < n_valid and (lanes[j + 1] == lanes[i]).all():
            j += 1
        run = values[i : j + 1].astype(np.int64)
        total = {
            "sum": run.sum(),
            "count": len(run),
            "min": run.min(),
            "max": run.max(),
        }[combine]
        rows.append((lanes[i], np.int64(total).astype(np.int32)))
        i = j + 1
    out_lanes = np.zeros((out_size, lanes.shape[1]), np.uint32)
    out_vals = np.zeros((out_size,), np.int32)
    for k, (key, total) in enumerate(rows[:out_size]):
        out_lanes[k], out_vals[k] = key, total
    return out_lanes, out_vals, np.arange(out_size) < len(rows), len(rows)


def grouped(n, runs, rng, *, value_range=(1, 50), junk=True):
    """A batch of ``n`` rows: ``runs`` = run lengths of distinct keys, valid
    rows first; the invalid tail carries non-zero values and stale keys."""
    n_valid = int(sum(runs))
    assert n_valid <= n
    keys = np.sort(rng.choice(1 << 20, size=len(runs), replace=False))
    lanes = np.zeros((n, LANES), np.uint32)
    lanes[:n_valid, 0] = 7  # a shared leading lane: keys differ further in
    lanes[:n_valid, 1] = np.repeat(keys, runs)
    lanes[:n_valid, 2] = np.repeat(keys[::-1], runs)
    values = rng.integers(*value_range, size=n).astype(np.int32)
    valid = np.arange(n) < n_valid
    if junk and n_valid < n:
        lanes[n_valid:] = rng.integers(0, 5, size=(n - n_valid, LANES))
    return lanes, values, valid


def assert_same(got, want):
    (gt, gn), (wt, wn) = got, want
    assert int(gn) == int(wn)
    for name in ("key_lanes", "values", "valid"):
        g, w = np.asarray(getattr(gt, name)), np.asarray(getattr(wt, name))
        assert g.dtype == w.dtype and g.shape == w.shape, name
        assert g.tobytes() == w.tobytes(), name


def check(lanes, values, valid, out_size, combine, *, runlength=True):
    batch = KVBatch(jnp.asarray(lanes), jnp.asarray(values), jnp.asarray(valid))
    got = jax.jit(segment_reduce_into, static_argnums=(1, 2))(batch, out_size, combine)
    assert_same(got, reference_into(batch, out_size, combine))
    if runlength:
        rl, rv, rok, rn = runlength_reference(lanes, values, valid, out_size, combine)
        assert int(got[1]) == rn
        assert np.array_equal(np.asarray(got[0].key_lanes), rl)
        assert np.array_equal(np.asarray(got[0].values), rv)
        assert np.array_equal(np.asarray(got[0].valid), rok)


# n, run lengths, out_size: out_size equal to, under and far under n;
# num_segments below, equal to and above out_size.
SHAPES = {
    "out_eq_n-segs_below": (64, [3, 1, 5, 2, 9, 1, 1], 64),
    "out_eq_n-every_row_a_key": (48, [1] * 48, 48),
    "out_under_n-segs_below": (96, [4, 4, 1, 7, 2], 40),
    "out_under_n-segs_equal": (96, [2] * 40, 40),
    "out_under_n-segs_above": (96, [2] * 45, 40),
    "out_far_under_n-segs_far_above": (512, [1] * 300 + [2] * 100, 8),
    "out_far_under_n-segs_below": (512, [100, 200, 50], 8),
    "out_one": (32, [5, 6, 7], 1),
    "all_rows_one_key": (80, [80], 16),
    "one_valid_row": (80, [1], 16),
    "no_valid_row": (80, [], 16),
    "out_over_n": (24, [3, 3, 3], 40),
}


@pytest.mark.parametrize("combine", COMBINERS)
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_matches_the_two_scatter_reference(shape, combine):
    n, runs, out_size = SHAPES[shape]
    rng = np.random.default_rng(zlib.crc32(f"{shape}/{combine}".encode()))
    check(*grouped(n, runs, rng), out_size, combine)


@pytest.mark.parametrize("combine", COMBINERS)
def test_invalid_rows_with_values_between_valid_ones(combine):
    """Off the precondition (invalid rows NOT last) the reference still says
    what comes out: an invalid row splits a run and adds nothing to it."""
    rng = np.random.default_rng(11)
    lanes, values, valid = grouped(120, [6, 1, 9, 4, 20, 3], rng)
    valid = valid & (rng.random(120) > 0.3)
    values = np.where(valid, values, 1_000_003).astype(np.int32)
    check(lanes, values, valid, 30, combine, runlength=False)
    check(lanes, values, valid, 5, combine, runlength=False)


@pytest.mark.parametrize("out_size", [16, 4])
def test_running_sum_wraps_while_every_segment_fits(out_size):
    """Twelve segments of 2^29 to 2^30 each: the running sum passes 2^31 (and
    2^32), every segment's own sum fits, and the differences are exact."""
    rng = np.random.default_rng(5)
    lanes, values, valid = grouped(
        64, [4] * 12, rng, value_range=(1 << 27, 1 << 28)
    )
    assert values[:48].astype(np.int64).sum() > 1 << 32
    check(lanes, values, valid, out_size, "sum")


def test_segment_sum_that_itself_wraps_is_the_scatters_bit_for_bit():
    rng = np.random.default_rng(6)
    lanes, values, valid = grouped(
        40, [8, 8, 8], rng, value_range=((1 << 30), (1 << 31) - 1)
    )
    check(lanes, values, valid, 8, "sum", runlength=False)
    check(lanes, -values, valid, 8, "sum", runlength=False)


@pytest.mark.parametrize("combine", ["sum", "count"])
def test_hash_colliding_keys_interleaved_in_one_run(combine):
    """hash* sort modes order by a key hash and then by the lanes, so two
    distinct keys of one hash lie side by side; whatever the order, rows
    whose lanes alternate are segments of their own."""
    lanes = np.zeros((24, LANES), np.uint32)
    lanes[:12, 1] = [1, 2] * 6          # a, b, a, b ...: twelve segments
    lanes[12:18, 1] = 3
    values = np.arange(1, 25, dtype=np.int32)
    valid = np.arange(24) < 18
    check(lanes, values, valid, 24, combine)
    check(lanes, values, valid, 10, combine)


@pytest.mark.parametrize("mode", ["hashp2", "lex"])
@pytest.mark.parametrize("combine", ["sum", "count", "max"])
def test_after_the_sort_that_feeds_it(mode, combine):
    """Unsorted emits through sort_and_compact, as every caller feeds it."""
    rng = np.random.default_rng(21)
    n = 400
    lanes = np.zeros((n, LANES), np.uint32)
    lanes[:, 0] = rng.integers(0, 37, size=n)
    lanes[:, 2] = lanes[:, 0] % 5
    values = rng.integers(-9, 9, size=n).astype(np.int32)
    valid = rng.random(n) > 0.25
    batch = sort_and_compact(
        KVBatch(jnp.asarray(lanes), jnp.asarray(values), jnp.asarray(valid)), mode
    )
    for out_size in (n, 64, 20):
        got = segment_reduce_into(batch, out_size, combine)
        assert_same(got, reference_into(batch, out_size, combine))
    want = {}
    for key, v in zip(lanes[valid, 0], values[valid]):
        want.setdefault(int(key), []).append(int(v))
    table, count = segment_reduce_into(batch, 64, combine)
    fold = {"sum": sum, "count": len, "max": max}[combine]
    assert int(count) == len(want)
    got = {
        int(k): int(v)
        for k, v, ok in zip(table.key_lanes[:, 0], table.values, table.valid)
        if ok
    }
    assert got == {k: fold(v) for k, v in want.items()}


@pytest.mark.parametrize("combine", ["sum", "count", "min"])
def test_same_capacity_special_case(combine):
    rng = np.random.default_rng(3)
    lanes, values, valid = grouped(72, [5, 1, 1, 30, 2], rng)
    batch = KVBatch(jnp.asarray(lanes), jnp.asarray(values), jnp.asarray(valid))
    got = segment_reduce(batch, combine)
    want = reference_into(batch, 72, combine)[0]
    assert_same((got, 0), (want, 0))


@pytest.mark.parametrize("combine", ["sum", "count", "min"])
def test_under_vmap_as_the_serve_batch_folds(combine):
    rng = np.random.default_rng(9)
    jobs = [
        grouped(96, runs, rng)
        for runs in ([3, 4, 5], [1] * 50, [], [96], [2] * 30)
    ]
    batch = KVBatch(
        *(jnp.asarray(np.stack([job[k] for job in jobs])) for k in range(3))
    )
    got_t, got_n = jax.jit(
        jax.vmap(lambda b: segment_reduce_into(b, 24, combine))
    )(batch)
    for j in range(len(jobs)):
        one = jax.tree.map(lambda x: x[j], batch)
        got = (jax.tree.map(lambda x: x[j], got_t), got_n[j])
        assert_same(got, reference_into(one, 24, combine))


@pytest.mark.parametrize("combine", ["sum", "count", "min"])
@pytest.mark.parametrize("n_dev", [1, 4])
def test_under_shard_map_as_the_mesh_folds(n_dev, combine):
    """Per-shard rows mixed with what the function builds from ``arange``,
    under check_vma as parallel/shuffle.py's CPU engines run it."""
    rng = np.random.default_rng(13)
    shards = [
        grouped(64, runs, rng)
        for runs in ([3, 4, 5, 1], [1] * 40, [], [2] * 30)[:n_dev]
    ]
    batch = KVBatch(
        *(jnp.asarray(np.concatenate([s[k] for s in shards])) for k in range(3))
    )
    mesh = Mesh(np.array(jax.devices()[:n_dev]), ("data",))

    def body(b):
        table, count = segment_reduce_into(b, 16, combine)
        return table, count[None]

    got_t, got_n = jax.jit(
        jax.shard_map(body, mesh=mesh, in_specs=P("data"), out_specs=P("data"))
    )(batch)
    for d in range(n_dev):
        one = jax.tree.map(lambda x: x[d * 64 : (d + 1) * 64], batch)
        got = (jax.tree.map(lambda x: x[d * 16 : (d + 1) * 16], got_t), got_n[d])
        assert_same(got, reference_into(one, 16, combine))


def test_unknown_combine_is_refused():
    batch = KVBatch.empty(8, LANES)
    with pytest.raises(ValueError, match="combine must be one of"):
        segment_reduce_into(batch, 4, "mean")
    with pytest.raises(ValueError, match="combine must be one of"):
        combine_scatters("mean")


# ------------------------------------------------ the mechanism, pinned

def _scatter_operand_rows(text: str) -> list[int]:
    """Leading dimension of the UPDATES operand of every scatter in a
    lowered (StableHLO) module: the rows a scatter walks."""
    rows = []
    for m in re.finditer(r'"?stablehlo\.scatter"?\((.*?)\)', text):
        sig = re.search(
            re.escape(m.group(0)) + r".*?:\s*\((.*?)\)\s*->", text, flags=re.S
        )
        types = re.findall(r"tensor<([0-9x]*)x?[a-z0-9]+>", sig.group(1))
        updates = types[-1]
        rows.append(int(updates.split("x")[0]) if updates else 1)
    return rows


def _kv(rows, cfg):
    return KVBatch(
        jax.ShapeDtypeStruct((rows, cfg.key_lanes), jnp.uint32),
        jax.ShapeDtypeStruct((rows,), jnp.int32),
        jax.ShapeDtypeStruct((rows,), jnp.bool_),
    )


def _lowered(program, cfg):
    """(lowered text, rows its segment combine takes in) at ``cfg``'s shapes."""
    progs = engine_mod._build_programs(cfg, engine_mod.wordcount_map, "sum")
    block, table = _kv(cfg.emits_per_block, cfg), _kv(cfg.resolved_table_size, cfg)
    if program == "merge_tables":
        lowered = progs.merge.lower(
            table, (block, block), jax.ShapeDtypeStruct((), jnp.int32)
        )
        return lowered.as_text(), table.size + 2 * block.size
    if program == "segment_reduce":
        return progs.reduce.lower(block).as_text(), block.size
    lines = jax.ShapeDtypeStruct((cfg.block_lines, cfg.line_width), jnp.uint8)
    return progs.fold_block_fallback.lower(table, lines).as_text(), (
        table.size + block.size
    )


@pytest.mark.parametrize("program", ["merge_tables", "segment_reduce", "fold_block"])
def test_no_scatter_walks_the_input_rows(program):
    """At CLI shapes with combine="sum": whatever scatters the program keeps
    (the map stage's, none of them the combine's), none has the combine's
    input row count."""
    cfg = EngineConfig(sort_mode="hashp2")
    text, input_rows = _lowered(program, cfg)
    assert "stablehlo.sort" in text
    assert input_rows not in _scatter_operand_rows(text)


def test_the_scatter_reader_sees_the_reference():
    """The structural test's own control: the two-scatter body, lowered, DOES
    show scatters over its input rows."""
    batch = _kv(4096, EngineConfig())
    text = jax.jit(lambda b: reference_into(b, 512, "sum")).lower(batch).as_text()
    assert _scatter_operand_rows(text).count(4096) == 2
    text = jax.jit(lambda b: segment_reduce_into(b, 512, "min")).lower(batch).as_text()
    assert _scatter_operand_rows(text).count(4096) == 1
    text = jax.jit(lambda b: segment_reduce_into(b, 512, "sum")).lower(batch).as_text()
    assert 4096 not in _scatter_operand_rows(text)


@pytest.mark.parametrize(
    "combine,scatters", [("sum", 0), ("count", 0), ("min", 1), ("max", 1)]
)
def test_engine_records_the_combines_scatters(combine, scatters):
    assert combine_scatters(combine) == scatters
    obs.disable()
    obs.enable(process="combine")
    try:
        engine_mod.MapReduceEngine(EngineConfig(block_lines=8), combine=combine)
        assert obs.metrics_snapshot()["gauges"]["engine.combine_scatters"] == scatters
    finally:
        obs.disable()


def test_config_fingerprint_is_pinned():
    """No field came or went unnoticed (PR 44 took three: CHANGES.md)."""
    assert EngineConfig().fingerprint() == "49eee6bb9e67"
