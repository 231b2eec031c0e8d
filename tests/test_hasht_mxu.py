"""sort_mode="hasht-mxu" — the MXU-combine spelling of the sort-free fold.

The contract is BIT-identity: hash_table.mxu_scatter_add replaces the
probe loop's duplicate-index value scatter with one-hot bf16 contractions,
and because its limb arithmetic is
exact mod 2^32 — the ring int32 scatter-add lives in — every table,
counter, and unresolved mask must equal the "hasht" impl's byte for byte,
through every consumer path (engine fold, mesh shuffle, hierarchical
combine, streaming, checkpoint resume).  Oracles as everywhere:
collections.Counter / numpy folds, plus the hasht/hashp2 cross-mode table
comparison the acceptance bar names.
"""

import collections
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from helpers import py_wordcount

from locust_tpu.config import HASHT_FAMILY, SORT_MODES, EngineConfig
from locust_tpu.core import bytes_ops
from locust_tpu.core.kv import KVBatch
from locust_tpu.engine import MapReduceEngine
from locust_tpu.ops.hash_table import (
    aggregate_exact,
    hash_aggregate,
    mxu_scatter_add,
    scatter_impl_for,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def corpus_lines(n_lines=700):
    """Reference hamlet when mounted, else the shipped sample corpus, so
    the oracle battery runs in every environment."""
    for path in ("/root/reference/hamlet.txt",
                 os.path.join(REPO, "data", "sample_corpus.txt")):
        if os.path.exists(path):
            return open(path, "rb").read().splitlines()[:n_lines]
    pytest.skip("no corpus available")


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


def _assert_tables_identical(a: KVBatch, b: KVBatch, what=""):
    assert np.array_equal(np.asarray(a.key_lanes), np.asarray(b.key_lanes)), what
    assert np.array_equal(np.asarray(a.values), np.asarray(b.values)), what
    assert np.array_equal(np.asarray(a.valid), np.asarray(b.valid)), what


# --------------------------------------------------------- the primitive


@pytest.mark.parametrize("out_size", [1, 7, 100, 600, 4096])
def test_mxu_scatter_add_matches_numpy_oracle(out_size):
    """Exact mod-2^32 sums + hit mask against a host fold, including
    negative and near-overflow values and duplicate slots, at grid
    shapes below/at/above HASHT_MXU_LANES (non-power-of-two included)."""
    rng = np.random.default_rng(out_size)
    n = 3000
    slot = rng.integers(0, out_size, n).astype(np.int32)
    vals = rng.integers(-(2**31), 2**31, n, dtype=np.int64).astype(np.int32)
    mask = rng.random(n) < 0.6
    sums, hit = mxu_scatter_add(
        jnp.asarray(slot), jnp.asarray(vals), jnp.asarray(mask), out_size
    )
    oracle = np.zeros(out_size, np.int64)
    oracle_hit = np.zeros(out_size, bool)
    for s, v, m in zip(slot, vals, mask):
        if m:
            oracle[s] += int(v)
            oracle_hit[s] = True
    oracle = (oracle % (1 << 32)).astype(np.uint32).view(np.int32)
    assert np.array_equal(np.asarray(sums), oracle)
    assert np.array_equal(np.asarray(hit), oracle_hit)


def test_mxu_scatter_add_chunked_equals_single_shot():
    """The lax.scan chunk path (n > chunk, padded tail) must equal the
    one-shot path bit for bit — the fold's n is far past any chunk."""
    rng = np.random.default_rng(42)
    n, T = 5000, 512
    slot = jnp.asarray(rng.integers(0, T, n).astype(np.int32))
    vals = jnp.asarray(rng.integers(-1000, 1000, n, dtype=np.int64).astype(np.int32))
    mask = jnp.asarray(rng.random(n) < 0.8)
    one = mxu_scatter_add(slot, vals, mask, T, chunk=8192)
    for chunk in (512, 701):  # divides / doesn't divide n
        many = mxu_scatter_add(slot, vals, mask, T, chunk=chunk)
        assert np.array_equal(np.asarray(one[0]), np.asarray(many[0])), chunk
        assert np.array_equal(np.asarray(one[1]), np.asarray(many[1])), chunk


def test_mxu_scatter_add_masked_rows_contribute_nothing():
    slot = jnp.asarray([3, 3, 5], jnp.int32)
    vals = jnp.asarray([10, 7, 9], jnp.int32)
    sums, hit = mxu_scatter_add(
        slot, vals, jnp.asarray([True, False, False]), 8
    )
    assert np.asarray(sums).tolist() == [0, 0, 0, 10, 0, 0, 0, 0]
    assert np.asarray(hit).tolist() == [False] * 3 + [True] + [False] * 4


# ------------------------------------------------- scatter-impl parity


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_hash_aggregate_impl_parity_property(seed):
    """Random keys/counts, both impls: tables, used counts, and
    unresolved masks must be BIT-identical (the seam's whole contract)."""
    rng = np.random.default_rng(seed)
    vocab = [f"w{i}".encode() for i in range(250)]
    words = [vocab[i] for i in rng.integers(0, len(vocab), 4000)]
    values = rng.integers(-(2**20), 2**20, len(words))
    batch = _batch(words, values=values)
    t_x, u_x, un_x = hash_aggregate(batch, 1024, scatter_impl="xla")
    t_m, u_m, un_m = hash_aggregate(batch, 1024, scatter_impl="mxu")
    _assert_tables_identical(t_x, t_m, f"seed {seed}")
    assert int(u_x) == int(u_m)
    assert np.array_equal(np.asarray(un_x), np.asarray(un_m))


def test_aggregate_exact_impl_parity_through_residual_and_full_branches():
    """Capacity pressure drives the exactness ladder off its fast path
    (probe exhaustion -> place_residual / full-sort fallback); both
    impls must walk the identical ladder to identical tables, and both
    must still be Counter-exact after the host finalize merge."""
    from locust_tpu.engine import finalize_host_pairs

    rng = np.random.default_rng(9)
    # 60 distinct in 64 slots: high load factor strands keys every fold.
    vocab = [f"key{i}".encode() for i in range(60)]
    words = [vocab[i] for i in rng.integers(0, len(vocab), 1500)]
    batch = _batch(words)
    t_x, d_x = aggregate_exact(batch, 64, "sum", scatter_impl="xla")
    t_m, d_m = aggregate_exact(batch, 64, "sum", scatter_impl="mxu")
    _assert_tables_identical(t_x, t_m)
    assert int(d_x) == int(d_m)
    got = dict(finalize_host_pairs(t_m, "sum"))
    assert got == dict(collections.Counter(words))


@pytest.mark.parametrize("combine", ["min", "max"])
def test_mxu_impl_min_max_fall_back_identically(combine):
    """min/max have no matmul spelling; the mxu impl keeps the XLA
    scatter for them — trivially identical, pinned here so a future
    'optimization' can't silently change their semantics."""
    rng = np.random.default_rng(13)
    words = [f"k{i % 37}".encode() for i in range(400)]
    values = rng.integers(-1000, 1000, len(words))
    batch = _batch(words, values=values)
    t_x, _, _ = hash_aggregate(batch, 256, combine=combine)
    t_m, _, _ = hash_aggregate(batch, 256, combine=combine,
                               scatter_impl="mxu")
    _assert_tables_identical(t_x, t_m, combine)


def test_scatter_impl_validation():
    with pytest.raises(ValueError, match="scatter_impl"):
        hash_aggregate(_batch([b"a"]), 16, scatter_impl="tpu")
    # The fp32 exactness ceiling (255 * chunk < 2^24) must hold for
    # DIRECT callers too, not just the config-validated env knob — a
    # too-large chunk would round partials and silently break the
    # bit-identity contract.
    with pytest.raises(ValueError, match="exactness"):
        mxu_scatter_add(
            jnp.zeros(4, jnp.int32), jnp.ones(4, jnp.int32),
            jnp.ones(4, bool), 16, chunk=65537,
        )
    assert scatter_impl_for("hasht-mxu") == "mxu"
    assert scatter_impl_for("hasht") == "xla"
    assert "hasht-mxu" in SORT_MODES and "hasht-mxu" in HASHT_FAMILY


# ------------------------------------------ engine / mesh oracle battery


def test_engine_hasht_mxu_oracle_exact_vs_hasht_and_hashp2():
    """Single chip: hasht-mxu equals the Python oracle, produces the
    IDENTICAL device table as hasht (same slot layout), and the
    identical finalized pairs as hashp2 (the acceptance bar)."""
    lines = corpus_lines()
    res = {}
    for mode in ("hasht-mxu", "hasht", "hashp2"):
        eng = MapReduceEngine(EngineConfig(block_lines=512, sort_mode=mode))
        res[mode] = eng.run_lines(lines)
    want = sorted(py_wordcount(lines).items())
    assert res["hasht-mxu"].to_host_pairs() == want
    assert res["hasht-mxu"].to_host_pairs() == res["hashp2"].to_host_pairs()
    _assert_tables_identical(res["hasht-mxu"].table, res["hasht"].table)
    assert res["hasht-mxu"].num_segments == res["hasht"].num_segments


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 (virtual) devices")
def test_mesh_hasht_mxu_oracle_exact():
    """8-device all-to-all shuffle with the MXU combiner in BOTH the
    local-combiner and per-shard-merge probe rounds."""
    from locust_tpu.parallel import DistributedMapReduce, make_mesh

    lines = [ln[:64] for ln in corpus_lines(200)]
    got = {}
    for mode in ("hasht-mxu", "hasht", "hashp2"):
        cfg = EngineConfig(block_lines=32, line_width=64, emits_per_line=12,
                           sort_mode=mode)
        dmr = DistributedMapReduce(make_mesh(), cfg)
        rows = bytes_ops.strings_to_rows(lines, 64)
        got[mode] = dmr.run(rows).to_host_pairs()
    assert got["hasht-mxu"] == sorted(py_wordcount(lines, 12).items())
    assert got["hasht-mxu"] == got["hasht"] == got["hashp2"]


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 (virtual) devices")
def test_hierarchical_hasht_mxu_oracle_exact():
    """[2 slices x 4 devices]: the cross-slice combine's reduce_into also
    dispatches through the MXU spelling."""
    from locust_tpu.parallel.hierarchical import HierarchicalMapReduce
    from locust_tpu.parallel.mesh import make_mesh_2d

    lines = [ln[:64] for ln in corpus_lines(160)]
    got = {}
    for mode in ("hasht-mxu", "hashp2"):
        cfg = EngineConfig(block_lines=16, line_width=64, emits_per_line=12,
                           sort_mode=mode)
        dmr = HierarchicalMapReduce(make_mesh_2d(2), cfg)
        rows = bytes_ops.strings_to_rows(lines, 64)
        got[mode] = dmr.run(rows).to_host_pairs()
    assert got["hasht-mxu"] == sorted(py_wordcount(lines, 12).items())
    assert got["hasht-mxu"] == got["hashp2"]


def test_stream_hasht_mxu_oracle_exact(tmp_path):
    """Bounded-memory streaming ingest under the MXU fold."""
    from locust_tpu.io.loader import StreamingCorpus

    lines = corpus_lines(300)
    p = tmp_path / "c.txt"
    p.write_bytes(b"\n".join(lines) + b"\n")
    cfg = EngineConfig(block_lines=64, sort_mode="hasht-mxu")
    eng = MapReduceEngine(cfg)
    res = eng.run_stream(
        StreamingCorpus(str(p), cfg.line_width, cfg.block_lines)
    )
    assert dict(res.to_host_pairs()) == py_wordcount(lines)


def test_checkpoint_resume_hasht_mxu_round_trips_slot_ordered_table(tmp_path):
    """Crash mid-run, resume: hasht-mxu's slot-ordered (non prefix-
    compact) snapshots must restore and finish exact — the same bar the
    hasht checkpoint tests pin (test_cli / multiprocess rig)."""
    cfg = EngineConfig(block_lines=4, line_width=64, emits_per_line=8,
                       sort_mode="hasht-mxu")
    lines = [b"to be or not to be", b"that is the question",
             b"the rest is silence"] * 8
    eng = MapReduceEngine(cfg)
    rows = eng.rows_from_lines(lines)
    ckpt = str(tmp_path / "ckpt")

    calls = {"n": 0}
    real_fold = eng._fold_block

    def dying_fold(acc, blk):
        if calls["n"] >= 2:
            raise RuntimeError("injected crash")
        calls["n"] += 1
        return real_fold(acc, blk)

    eng._fold_block = dying_fold
    with pytest.raises(RuntimeError, match="injected crash"):
        eng.run_checkpointed(rows, ckpt, every=1)

    eng2 = MapReduceEngine(cfg)
    res = eng2.run_checkpointed(rows, ckpt, every=1)
    assert dict(res.to_host_pairs()) == py_wordcount(lines, 8)


def test_debug_checks_accept_hasht_mxu_tables(monkeypatch):
    """validate_batch(expect_compact=False) must extend to the whole
    hasht family — slot-ordered tables are not a layout violation."""
    monkeypatch.setenv("LOCUST_DEBUG_CHECKS", "1")
    eng = MapReduceEngine(EngineConfig(block_lines=8, sort_mode="hasht-mxu"))
    res = eng.run_lines([b"a b a", b"c d"])
    assert dict(res.to_host_pairs()) == {b"a": 2, b"b": 1, b"c": 1, b"d": 1}


def test_hasht_mxu_scan_lowers_for_tpu():
    """The fused fold (one-hot contractions + scatters + nested lax.cond
    inside lax.scan) must lower to TPU StableHLO off-hardware — the same
    pre-hardware gate hasht gets, so a lowering
    regression is caught before it costs chip time."""
    from jax import export as jax_export

    cfg = EngineConfig(
        block_lines=256, sort_mode="hasht-mxu", key_width=16, emits_per_line=8
    )
    eng = MapReduceEngine(cfg)
    shape = jax.ShapeDtypeStruct((2, 256, cfg.line_width), jnp.uint8)
    exp = jax_export.export(eng._scan_blocks, platforms=["tpu"])(shape)
    assert len(exp.mlir_module()) > 0
