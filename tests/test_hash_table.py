"""ops/hash_table.py — the sort-free Process+Reduce (sort_mode="hasht").

The aggregation must be EXACT (never merge distinct keys, never lose a
row silently): resolution requires a full-key-lane match, and anything
unresolved is handed back for the engine's stock sort fallback.  Oracles
are collections.Counter / dict folds, as everywhere in the suite.
"""

import collections

import jax.numpy as jnp
import numpy as np
import pytest

from helpers import py_wordcount

from locust_tpu.config import EngineConfig
from locust_tpu.core import bytes_ops
from locust_tpu.core.kv import KVBatch
from locust_tpu.engine import MapReduceEngine
from locust_tpu.ops.hash_table import hash_aggregate


def _batch(words, values=None, valid=None):
    keys = jnp.asarray(bytes_ops.strings_to_rows(list(words), 32))
    if values is None:
        values = jnp.ones(len(words), jnp.int32)
    else:
        values = jnp.asarray(values, jnp.int32)
    if valid is None:
        valid = jnp.asarray([bool(w) for w in words])
    else:
        valid = jnp.asarray(valid)
    return KVBatch.from_bytes(keys, values, valid)


def _table_dict(table):
    return {
        k: v
        for (k, v) in zip(
            bytes_ops.rows_to_strings(np.asarray(table.keys_bytes())),
            np.asarray(table.values),
        )
        if k
    }


def test_sum_matches_counter_oracle():
    rng = np.random.default_rng(7)
    vocab = [f"w{i}".encode() for i in range(300)]
    words = [vocab[i] for i in rng.integers(0, len(vocab), 5000)]
    table, used, unresolved = hash_aggregate(_batch(words), 1024)
    assert int(np.asarray(unresolved).sum()) == 0
    oracle = collections.Counter(words)
    assert _table_dict(table) == dict(oracle)
    assert int(used) == len(oracle)


@pytest.mark.parametrize("combine", ["min", "max"])
def test_min_max_combines(combine):
    rng = np.random.default_rng(11)
    words = [f"k{i % 37}".encode() for i in range(400)]
    values = rng.integers(-1000, 1000, len(words))
    table, _, unresolved = hash_aggregate(
        _batch(words, values=values), 256, combine=combine
    )
    assert int(np.asarray(unresolved).sum()) == 0
    op = min if combine == "min" else max
    oracle: dict[bytes, int] = {}
    for w, v in zip(words, values):
        oracle[w] = op(oracle[w], int(v)) if w in oracle else int(v)
    assert _table_dict(table) == oracle


def test_invalid_rows_ignored():
    words = [b"a", b"", b"b", b"", b"a"]
    table, used, unresolved = hash_aggregate(_batch(words), 64)
    assert int(np.asarray(unresolved).sum()) == 0
    assert _table_dict(table) == {b"a": 2, b"b": 1}
    assert int(used) == 2


def test_probe_exhaustion_returns_unresolved_not_wrong():
    """More distinct keys than slots: the overflow MUST surface as
    unresolved rows (for the engine's exact sort fallback), and every
    key that did land must still carry its exact total."""
    words = [f"key{i}".encode() for i in range(64)] * 3
    table, used, unresolved = hash_aggregate(_batch(words), 16)
    n_un = int(np.asarray(unresolved).sum())
    assert n_un > 0  # 64 distinct cannot fit 16 slots
    got = _table_dict(table)
    assert len(got) == int(used) <= 16
    # Resolved keys are exact; unresolved rows of a key are all-or-none
    # (same key => same probe sequence => same resolution round).
    for k, v in got.items():
        assert v == 3, (k, v)
    resolved_total = sum(got.values())
    assert resolved_total + n_un == len(words)


def test_distinct_keys_sharing_slots_never_merge():
    """Keys engineered to collide (tiny table forces shared probe paths)
    must either occupy separate slots or fall to unresolved — never sum
    into one slot."""
    rng = np.random.default_rng(3)
    vocab = [f"word{i}".encode() for i in range(40)]
    words = [vocab[i] for i in rng.integers(0, len(vocab), 400)]
    table, _, unresolved = hash_aggregate(_batch(words), 32)
    got = _table_dict(table)
    oracle = collections.Counter(words)
    for k, v in got.items():
        assert v == oracle[k], f"{k!r} merged or lost counts"


@pytest.mark.parametrize("n_lines", [37, 700])
def test_engine_hasht_oracle_exact(n_lines):
    """End-to-end WordCount with sort_mode='hasht' equals the pure-Python
    oracle — the same bar every sort mode passes (test_pipeline)."""
    import os

    path = "/root/reference/hamlet.txt"
    if not os.path.exists(path):
        pytest.skip("reference corpus not mounted")
    lines = open(path, "rb").read().splitlines()[:n_lines]
    eng = MapReduceEngine(EngineConfig(block_lines=512, sort_mode="hasht"))
    res = eng.run_lines(lines)
    got = dict(res.to_host_pairs())
    assert got == py_wordcount(lines)
    assert not res.truncated


def test_engine_hasht_fallback_under_capacity_pressure():
    """Table smaller than the vocabulary: the lax.cond sort fallback must
    fire and keep the answer exact (and flag truncation honestly when
    distinct exceeds capacity)."""
    lines = [b"alpha beta gamma delta epsilon zeta eta theta"] * 4 + [
        f"unique{i}".encode() for i in range(200)
    ]
    eng = MapReduceEngine(
        EngineConfig(block_lines=64, sort_mode="hasht", table_size=4096)
    )
    res = eng.run_lines(lines)
    assert dict(res.to_host_pairs()) == py_wordcount(lines)


def test_engine_hasht_truncation_flag():
    """Same truncation-observability bar as the sort modes
    (test_pipeline.test_truncation_flag_survives_later_merges): distinct
    beyond table capacity must set the flag even when a later fold's
    distinct fits."""
    cfg = EngineConfig(
        block_lines=2, emits_per_line=4, table_size=8, sort_mode="hasht"
    )
    lines = [
        b"a b c d",
        b"e f g h",
        b"i j k l",  # 12 distinct > 8 slots
        b"",
        b"a b c d",
        b"",
    ]
    for runner in ("run", "run_fused"):
        eng = MapReduceEngine(cfg)
        res = getattr(eng, runner)(eng.rows_from_lines(lines))
        assert res.truncated, runner


def test_place_residual_merges_exactly():
    """Direct middle-path check: force probe exhaustion with a tiny
    table, then verify place_residual lands every placeable key with its
    exact total and reports the true distinct count."""
    from locust_tpu.ops.hash_table import place_residual

    words = [f"key{i}".encode() for i in range(40)] * 5
    batch = _batch(words)
    table, used, unresolved = hash_aggregate(batch, 64)
    merged, distinct = place_residual(table, used, batch, unresolved)
    assert int(distinct) == 40
    got = _table_dict(merged)
    assert got == dict(collections.Counter(words))


def test_lane0_zero_rows_return_as_unresolved():
    """A valid row whose key lane 0 is zero aliases the empty-slot
    sentinel and is guarded out of the probe rounds — the contract is
    that it comes BACK in the unresolved mask (for the engine's exact
    fallback), never silently dropped (code-review finding, round 4)."""
    zero_key = jnp.zeros((2, 8), jnp.uint32)
    zero_key = zero_key.at[1, 1].set(0x61000000)  # lane0 still 0
    batch = KVBatch(
        key_lanes=zero_key,
        values=jnp.asarray([7, 1], jnp.int32),
        valid=jnp.asarray([True, True]),
    )
    table, used, unresolved = hash_aggregate(batch, 16)
    assert list(np.asarray(unresolved)) == [True, True]
    assert int(used) == 0


def test_degenerate_hash_exact_and_no_phantom_slots(monkeypatch):
    """Total hash collision (every key returns the same (h1, h2)): all
    rows fight for ONE slot per round, so at most `probes` keys resolve
    and everything else must surface as unresolved.  Exercises the
    matched-slot guard: a slot counts as used only after a full-key
    match, so resolved keys are exact and no phantom (written-but-never-
    matched) slot can surface in the table."""
    from locust_tpu.core import packing as packing_mod

    real = packing_mod.hash_pair

    def degenerate(lanes):
        h1, h2 = real(lanes)
        return jnp.full_like(h1, 123457), jnp.full_like(h2, 7)

    monkeypatch.setattr(packing_mod, "hash_pair", degenerate)
    words = [b"w%d" % (i % 25) for i in range(200)]
    table, used, unresolved = hash_aggregate(_batch(words), 64)
    got = _table_dict(table)
    oracle = collections.Counter(words)
    assert len(got) == int(used) <= 4  # one slot resolvable per probe round
    for k, v in got.items():
        assert v == oracle[k], f"{k!r} wrong under total collision"
    # Accounting: every valid row is either in a resolved key's total or
    # returned unresolved — nothing vanishes into a phantom slot.
    assert sum(got.values()) + int(np.asarray(unresolved).sum()) == len(words)


def test_incremental_aggregate_matches_oracle_across_blocks():
    """aggregate_exact(into=...) — the INCREMENTAL capability (not wired
    into the engines; see ops/hash_table.fold_into for the measured
    reason): folding three overlapping batches one after another must
    equal one aggregation of everything, prior keys combining into
    their existing slots."""
    from locust_tpu.core.kv import KVBatch
    from locust_tpu.ops.hash_table import aggregate_exact

    rng = np.random.default_rng(5)
    vocab = [f"w{i}".encode() for i in range(120)]
    batches = [
        [vocab[i] for i in rng.integers(0, len(vocab), 700)]
        for _ in range(3)
    ]
    acc = KVBatch.empty(1024, 8)
    for words in batches:
        acc, _ = aggregate_exact(_batch(words), 1024, "sum", into=acc)
    oracle = collections.Counter(b for ws in batches for b in ws)
    # finalize-equivalent merge (duplicate rows combine):
    merged: dict[bytes, int] = {}
    for k, v in _table_dict(acc).items():
        merged[k] = merged.get(k, 0) + v
    assert merged == dict(oracle)


@pytest.mark.parametrize("combine", ["min", "max"])
def test_incremental_fold_min_max_empty_slot_init(combine):
    """Carried empty slots must re-initialize to the combine identity
    (stored 0 would corrupt a later min over positive values)."""
    from locust_tpu.core.kv import KVBatch
    from locust_tpu.ops.hash_table import aggregate_exact

    acc = KVBatch.empty(64, 8)
    acc, _ = aggregate_exact(
        _batch([b"a", b"b"], values=[5, -7]), 64, combine, into=acc
    )
    acc, _ = aggregate_exact(
        _batch([b"a", b"c"], values=[9, 3]), 64, combine, into=acc
    )
    op = min if combine == "min" else max
    assert _table_dict(acc) == {b"a": op(5, 9), b"b": -7, b"c": 3}


def test_incremental_fold_under_capacity_pressure_is_loud_never_over():
    """Keys placed by the residual/full branches sit off their probe
    sequence; later incremental folds may split their totals across
    rows.  Under CAPACITY pressure the bounded table can then drop a
    key's residual placement — best-effort totals, same as the rebuild
    design's head-slice truncation — but the contract is (a) the
    distinct signal must exceed capacity (so the engine flags
    ``truncated``), and (b) no kept key may ever OVERCOUNT."""
    from locust_tpu.core.kv import KVBatch
    from locust_tpu.engine import finalize_host_pairs
    from locust_tpu.ops.hash_table import aggregate_exact

    rng = np.random.default_rng(9)
    vocab = [f"key{i}".encode() for i in range(60)]  # ~load factor 0.9
    acc = KVBatch.empty(64, 8)
    all_words = []
    max_distinct = 0
    for _ in range(4):
        words = [vocab[i] for i in rng.integers(0, len(vocab), 400)]
        all_words += words
        acc, distinct = aggregate_exact(_batch(words), 64, "sum", into=acc)
        max_distinct = max(max_distinct, int(distinct))
    got = dict(finalize_host_pairs(acc, "sum"))
    oracle = collections.Counter(all_words)
    wrong = {k: (v, oracle[k]) for k, v in got.items() if v != oracle[k]}
    if wrong:
        # Partial totals are only permitted when the loud truncation
        # signal fired (distinct count past capacity).
        assert max_distinct > 64, (max_distinct, wrong)
    for k, v in got.items():
        assert v <= oracle[k], f"{k!r} overcounted: {v} > {oracle[k]}"


def test_incremental_fold_exact_when_within_capacity():
    """Same shape of test WITHOUT capacity pressure: repeated incremental
    folds (including probe-failure residual descents at a high-ish load
    factor) must be byte-exact under the finalize merge."""
    from locust_tpu.core.kv import KVBatch
    from locust_tpu.engine import finalize_host_pairs
    from locust_tpu.ops.hash_table import aggregate_exact

    rng = np.random.default_rng(11)
    vocab = [f"key{i}".encode() for i in range(60)]
    acc = KVBatch.empty(256, 8)
    all_words = []
    for _ in range(4):
        words = [vocab[i] for i in rng.integers(0, len(vocab), 400)]
        all_words += words
        acc, _ = aggregate_exact(_batch(words), 256, "sum", into=acc)
    got = dict(finalize_host_pairs(acc, "sum"))
    assert got == dict(collections.Counter(all_words))


def test_debug_checks_accept_hasht_tables(monkeypatch):
    """LOCUST_DEBUG_CHECKS must not reject hasht's slot-ordered (non
    prefix-compact) tables — reproduces the round-4 review finding."""
    monkeypatch.setenv("LOCUST_DEBUG_CHECKS", "1")
    eng = MapReduceEngine(EngineConfig(block_lines=8, sort_mode="hasht"))
    res = eng.run_lines([b"a b a", b"c d"])
    assert dict(res.to_host_pairs()) == {b"a": 2, b"b": 1, b"c": 1, b"d": 1}


def test_hasht_scan_lowers_for_tpu():
    """The full-corpus hasht fold (scatters + nested lax.cond inside
    lax.scan) must lower to TPU StableHLO off-hardware — the
    pre-hardware gate, so a lowering regression is caught before it
    costs chip time."""
    import jax
    # 0.4.x has the module but not the lazy ``jax.export`` attribute.
    from jax import export as jax_export

    cfg = EngineConfig(
        block_lines=256, sort_mode="hasht", key_width=16, emits_per_line=8
    )
    eng = MapReduceEngine(cfg)
    shape = jax.ShapeDtypeStruct((2, 256, cfg.line_width), jnp.uint8)
    exp = jax_export.export(eng._scan_blocks, platforms=["tpu"])(shape)
    assert len(exp.mlir_module()) > 0


def test_count_combine_rejected_not_corrupted():
    """'count' is not a monoid over its own outputs: the ladder's
    fallback branches re-reduce batches containing pre-aggregated table
    rows, where a second count would return 1 instead of the true total
    (round-4 review repro: 50 of 64 entries wrong at >RESIDUAL_CAP
    unresolved).  The fold-level entry points must refuse it loudly."""
    from locust_tpu.ops.hash_table import (
        aggregate_exact,
        combine_or_passthrough,
    )

    batch = _batch([b"a", b"b"])
    with pytest.raises(ValueError, match="normalize_combine"):
        aggregate_exact(batch, 16, combine="count")
    with pytest.raises(ValueError, match="normalize_combine"):
        combine_or_passthrough(batch, combine="count")


def _total_multiset(table_or_batch):
    """Fold (key -> summed value) over all valid rows — the invariant a
    combiner (aggregated or passthrough) must preserve."""
    out: dict[bytes, int] = {}
    keys = bytes_ops.rows_to_strings(
        np.asarray(table_or_batch.keys_bytes())
    )
    for k, v, ok in zip(
        keys, np.asarray(table_or_batch.values),
        np.asarray(table_or_batch.valid),
    ):
        if ok:
            out[k] = out.get(k, 0) + int(v)
    return out


def test_combine_or_passthrough_duplicate_heavy_aggregates():
    from locust_tpu.ops.hash_table import combine_or_passthrough

    words = [b"dup%d" % (i % 7) for i in range(600)]
    out = combine_or_passthrough(_batch(words), "sum")
    assert _total_multiset(out) == dict(collections.Counter(words))
    # Genuinely aggregated: one row per key.
    assert int(np.asarray(out.valid).sum()) == 7


def test_combine_or_passthrough_distinct_heavy_never_drops():
    """Load factor 1.0 (every key distinct): probing mostly fails and the
    O(n) passthrough must carry every row — value-preserving, size
    contract intact, no sort fallback needed for correctness."""
    from locust_tpu.ops.hash_table import combine_or_passthrough

    words = [b"uniq%d" % i for i in range(800)]
    batch = _batch(words)
    out = combine_or_passthrough(batch, "sum", probes=2)
    assert out.size == batch.size
    assert _total_multiset(out) == dict(collections.Counter(words))
