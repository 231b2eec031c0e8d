"""Distributed shuffle tests on the 8-device virtual CPU mesh.

The all-to-all + psum path runs on real collectives here (XLA CPU backend),
which is the standard JAX recipe for testing multi-device code without a pod
(SURVEY.md §4, §7.3.5).
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from helpers import py_wordcount

from locust_tpu.config import EngineConfig
from locust_tpu.core import bytes_ops, packing
from locust_tpu.core.kv import KVBatch
from locust_tpu.parallel import DistributedMapReduce, make_mesh, partition_to_bins


pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 (virtual) devices"
)


def small_cfg(**kw):
    kw.setdefault("block_lines", 16)
    kw.setdefault("line_width", 64)
    kw.setdefault("emits_per_line", 8)
    return EngineConfig(**kw)


def test_partition_to_bins_routes_by_hash():
    words = [f"w{i}".encode() for i in range(50)]
    keys = jnp.asarray(bytes_ops.strings_to_rows(words, 32))
    batch = KVBatch.from_bytes(
        keys, jnp.arange(50), jnp.ones(50, bool)
    )
    lanes, vals, valid, overflow, _ = partition_to_bins(batch, 4, 32)
    assert lanes.shape == (4, 32, 8) and int(overflow) == 0
    # Every live entry landed in the bin its hash names.
    h = np.asarray(packing.fold_hash(batch.key_lanes)) % 4
    got_per_bin = [int(np.asarray(valid[b]).sum()) for b in range(4)]
    expect_per_bin = [int((h == b).sum()) for b in range(4)]
    assert got_per_bin == expect_per_bin


def test_partition_overflow_counted():
    words = [b"same"] * 20  # all hash to one bin
    keys = jnp.asarray(bytes_ops.strings_to_rows(words, 32))
    batch = KVBatch.from_bytes(keys, jnp.ones(20, jnp.int32), jnp.ones(20, bool))
    _, _, valid, overflow, leftover = partition_to_bins(batch, 4, 8)
    assert int(overflow) == 12 and int(np.asarray(valid).sum()) == 8
    assert leftover.key_lanes.shape[0] == 0  # no buffer requested -> dropped


def test_partition_spill_lands_in_leftover():
    """With a leftover buffer, bin overspill is captured, not lost."""
    words = [b"same"] * 20  # all hash to one bin
    keys = jnp.asarray(bytes_ops.strings_to_rows(words, 32))
    vals = jnp.arange(20, dtype=jnp.int32)
    batch = KVBatch.from_bytes(keys, vals, jnp.ones(20, bool))
    _, binned_vals, valid, overflow, leftover = partition_to_bins(
        batch, 4, 8, leftover_capacity=16
    )
    assert int(overflow) == 0
    assert int(np.asarray(valid).sum()) == 8
    assert int(np.asarray(leftover.valid).sum()) == 12
    # Every input value appears exactly once: in a bin or in the leftover.
    got = sorted(
        np.asarray(binned_vals)[np.asarray(valid)].tolist()
        + np.asarray(leftover.values)[np.asarray(leftover.valid)].tolist()
    )
    assert got == list(range(20))


def test_partition_leftover_overflow_still_counted():
    """Spill beyond the leftover buffer is true loss and must be counted."""
    words = [b"same"] * 20
    keys = jnp.asarray(bytes_ops.strings_to_rows(words, 32))
    batch = KVBatch.from_bytes(keys, jnp.ones(20, jnp.int32), jnp.ones(20, bool))
    _, _, valid, overflow, leftover = partition_to_bins(
        batch, 4, 8, leftover_capacity=5
    )
    assert int(np.asarray(valid).sum()) == 8
    assert int(np.asarray(leftover.valid).sum()) == 5
    assert int(overflow) == 7


def test_distributed_wordcount_matches_oracle():
    mesh = make_mesh(8)
    cfg = small_cfg()
    dmr = DistributedMapReduce(mesh, cfg)
    rng = np.random.default_rng(7)
    vocab = [f"word{i}".encode() for i in range(60)] + [b"the"] * 5
    lines = [
        b" ".join(rng.choice(vocab, size=rng.integers(0, 7)).tolist())
        for _ in range(300)
    ]
    rows = bytes_ops.strings_to_rows(lines, cfg.line_width)
    res = dmr.run(rows)
    expect = py_wordcount(lines, cfg.emits_per_line, cfg.key_width)
    assert dict(res.to_host_pairs()) == dict(expect)
    assert res.shuffle_overflow == 0
    assert res.distinct == len(expect)


def test_distributed_multi_round_carries_shards():
    mesh = make_mesh(8)
    cfg = small_cfg(block_lines=4)  # lines_per_round = 32 -> several rounds
    dmr = DistributedMapReduce(mesh, cfg)
    lines = [b"alpha beta", b"beta gamma", b"alpha"] * 40
    rows = bytes_ops.strings_to_rows(lines, cfg.line_width)
    res = dmr.run(rows)
    assert dict(res.to_host_pairs()) == dict(py_wordcount(lines, cfg.emits_per_line))


def test_distributed_hot_key_skew_pre_aggregated():
    """A pathologically hot key must NOT overflow the shuffle bins thanks to
    the local combiner (one entry per device per key)."""
    mesh = make_mesh(8)
    cfg = small_cfg()
    dmr = DistributedMapReduce(mesh, cfg, skew_factor=1.5)
    lines = [b"the the the the the the"] * 128
    rows = bytes_ops.strings_to_rows(lines, cfg.line_width)
    res = dmr.run(rows)
    assert res.shuffle_overflow == 0
    assert dict(res.to_host_pairs()) == {b"the": 6 * 128}


def test_distributed_overflow_accumulates_across_rounds():
    """Regression: emit overflow in an EARLY round must be reported even when
    later rounds are clean."""
    mesh = make_mesh(8)
    cfg = small_cfg(block_lines=2, emits_per_line=4)  # 16 lines per round
    dmr = DistributedMapReduce(mesh, cfg)
    busy = [b"a b c d e f"] * 16   # round 0: 2 dropped tokens per line
    clean = [b"x y"] * 16          # round 1: no overflow
    rows = bytes_ops.strings_to_rows(busy + clean, cfg.line_width)
    res = dmr.run(rows)
    assert res.emit_overflow == 2 * 16


def test_distributed_skew_beyond_bins_is_lossless():
    """Distinct-key skew exceeding bin_capacity used
    to silently drop counts.  retry mode drains the backlog in extra
    all-to-all rounds: the result must match the oracle EXACTLY."""
    mesh = make_mesh(8)
    cfg = small_cfg()
    # skew_factor well below 1 forces tiny bins: emits_per_block=128 over
    # 8 devices -> fair share 16; x0.1 -> bin_capacity 8 (after rounding).
    dmr = DistributedMapReduce(mesh, cfg, skew_factor=0.1)
    assert dmr.bin_capacity == 8
    rng = np.random.default_rng(11)
    vocab = [f"word{i}".encode() for i in range(300)]
    lines = [
        b" ".join(rng.choice(vocab, size=6).tolist()) for _ in range(256)
    ]
    rows = bytes_ops.strings_to_rows(lines, cfg.line_width)
    res = dmr.run(rows)
    expect = py_wordcount(lines, cfg.emits_per_line, cfg.key_width)
    assert dict(res.to_host_pairs()) == dict(expect)
    assert res.shuffle_overflow == 0
    assert res.drain_rounds > 0  # the skew actually exercised the backlog


def test_distributed_drop_mode_preserves_reference_behavior():
    """on_overflow='drop' keeps the counted-loss contract for comparison."""
    mesh = make_mesh(8)
    cfg = small_cfg()
    dmr = DistributedMapReduce(mesh, cfg, skew_factor=0.1, on_overflow="drop")
    rng = np.random.default_rng(11)
    vocab = [f"word{i}".encode() for i in range(300)]
    lines = [
        b" ".join(rng.choice(vocab, size=6).tolist()) for _ in range(256)
    ]
    rows = bytes_ops.strings_to_rows(lines, cfg.line_width)
    res = dmr.run(rows)
    assert res.shuffle_overflow > 0  # loss happened and was reported
    assert res.drain_rounds == 0


def test_distributed_truncation_flag_on_shard_table_overflow():
    """A vocabulary exceeding a shard's table used to
    drop keys with NO signal; now DistributedResult.truncated reports it."""
    mesh = make_mesh(8)
    cfg = small_cfg()
    dmr = DistributedMapReduce(mesh, cfg, shard_capacity=8)
    rng = np.random.default_rng(3)
    vocab = [f"word{i}".encode() for i in range(400)]  # ~50/shard > 8
    lines = [b" ".join(rng.choice(vocab, size=6).tolist()) for _ in range(128)]
    rows = bytes_ops.strings_to_rows(lines, cfg.line_width)
    res = dmr.run(rows)
    assert res.truncated
    # Same corpus with ample capacity: flag clear, result exact.
    dmr2 = DistributedMapReduce(mesh, cfg, shard_capacity=512)
    res2 = dmr2.run(rows)
    assert not res2.truncated
    expect = py_wordcount(lines, cfg.emits_per_line, cfg.key_width)
    assert dict(res2.to_host_pairs()) == dict(expect)


def test_distributed_shard_capacity_decoupled_from_round_volume():
    """A table larger than one round's receive volume accumulates a big
    vocabulary across many rounds without truncating."""
    mesh = make_mesh(8)
    cfg = small_cfg(block_lines=4)  # 32 lines/round -> many rounds
    dmr = DistributedMapReduce(mesh, cfg, skew_factor=1.0, shard_capacity=1024)
    assert dmr.shard_capacity > dmr.n_dev * dmr.bin_capacity
    vocab = [f"k{i:04d}".encode() for i in range(700)]
    lines = [b" ".join(vocab[i : i + 4]) for i in range(0, 700, 4)] * 2
    rows = bytes_ops.strings_to_rows(lines, cfg.line_width)
    res = dmr.run(rows)
    assert not res.truncated
    expect = py_wordcount(lines, cfg.emits_per_line, cfg.key_width)
    assert dict(res.to_host_pairs()) == dict(expect)
    assert res.distinct == len(expect)


def test_distributed_checkpoint_resume(tmp_path):
    """Crash mid-corpus on the 8-device mesh; a
    re-run resumes after the last completed round and matches exactly."""
    mesh = make_mesh(8)
    cfg = small_cfg(block_lines=4)  # 32 lines/round -> several rounds
    lines = [b"alpha beta", b"beta gamma", b"alpha delta epsilon"] * 40
    rows = bytes_ops.strings_to_rows(lines, cfg.line_width)
    want = dict(
        DistributedMapReduce(mesh, cfg).run(rows).to_host_pairs()
    )

    ckpt = str(tmp_path / "dckpt")
    dmr = DistributedMapReduce(mesh, cfg)
    real_step = dmr._step
    calls = {"n": 0}

    def dying_step(lines_, acc, leftover):
        if calls["n"] == 2:
            raise RuntimeError("simulated crash")
        calls["n"] += 1
        return real_step(lines_, acc, leftover)

    dmr._step = dying_step
    with pytest.raises(RuntimeError, match="simulated crash"):
        dmr.run(rows, checkpoint_dir=ckpt)
    dmr._step = real_step

    res = dmr.run(rows, checkpoint_dir=ckpt)
    assert dict(res.to_host_pairs()) == want
    # Resume skipped the completed rounds: a fully-checkpointed third run
    # steps zero times.
    calls["n"] = 2
    dmr._step = dying_step  # raises on any further step call
    res3 = dmr.run(rows, checkpoint_dir=ckpt)
    assert dict(res3.to_host_pairs()) == want


def test_distributed_checkpoint_fingerprint_content(tmp_path):
    """Same line count, different content -> fresh start, correct counts
    (round-1 advisor: shape-only fingerprints resumed stale snapshots)."""
    mesh = make_mesh(8)
    cfg = small_cfg(block_lines=4)
    ckpt = str(tmp_path / "dckpt")
    dmr = DistributedMapReduce(mesh, cfg)
    lines_a = [b"aaa bbb"] * 64
    dmr.run(bytes_ops.strings_to_rows(lines_a, cfg.line_width), checkpoint_dir=ckpt)
    lines_b = [b"ccc ddd"] * 64  # same shape, different corpus
    res = dmr.run(
        bytes_ops.strings_to_rows(lines_b, cfg.line_width), checkpoint_dir=ckpt
    )
    assert dict(res.to_host_pairs()) == {b"ccc": 64, b"ddd": 64}


def test_engine_checkpoint_fingerprint_content(tmp_path):
    """Single-device variant of the content-digest regression."""
    from locust_tpu.engine import MapReduceEngine

    cfg = small_cfg(block_lines=4)
    eng = MapReduceEngine(cfg)
    ckpt = str(tmp_path / "eckpt")
    eng.run_checkpointed(
        bytes_ops.strings_to_rows([b"aaa bbb"] * 16, cfg.line_width), ckpt
    )
    res = eng.run_checkpointed(
        bytes_ops.strings_to_rows([b"ccc ddd"] * 16, cfg.line_width), ckpt
    )
    assert dict(res.to_host_pairs()) == {b"ccc": 16, b"ddd": 16}


def test_distributed_output_sorted():
    mesh = make_mesh(8)
    cfg = small_cfg()
    dmr = DistributedMapReduce(mesh, cfg)
    lines = [b"zeta alpha mid", b"beta zeta"]
    res = dmr.run(bytes_ops.strings_to_rows(lines, cfg.line_width))
    keys = [k for k, _ in res.to_host_pairs()]
    assert keys == sorted(keys)


def test_explicit_tight_bins_lossless_via_drains():
    """A caller-supplied small bin_capacity shrinks the all-to-all wire
    volume; underestimates cost drain rounds, never data."""
    from locust_tpu.parallel.mesh import make_mesh

    cfg = EngineConfig(block_lines=8, line_width=128, emits_per_line=16)
    # Dense vocabulary: 12 unique words per line -> ~96 distinct keys per
    # device per round, far above the 8-row bins.
    lines = [
        b" ".join(b"w%04d" % (12 * i + j) for j in range(12)) for i in range(64)
    ]
    rows = bytes_ops.strings_to_rows(lines, cfg.line_width)
    want = dict(py_wordcount(lines, 16))

    dmr = DistributedMapReduce(
        make_mesh(8), cfg, bin_capacity=8, shard_capacity=256
    )
    assert dmr.bin_capacity == 8  # the override took (vs default ~32)
    res = dmr.run(rows)
    assert dict(res.to_host_pairs()) == want
    assert res.shuffle_overflow == 0
    assert res.drain_rounds > 0  # tight bins actually forced drains


def test_bin_capacity_validation():
    from locust_tpu.parallel.mesh import make_mesh

    cfg = EngineConfig(block_lines=8, line_width=64, emits_per_line=8)
    with pytest.raises(ValueError, match="bin_capacity"):
        DistributedMapReduce(make_mesh(8), cfg, bin_capacity=0)


class TestRoundStats:
    """Unit coverage of the shared accumulate/flush protocol."""

    def test_sync_cadence_and_merge(self):
        import jax.numpy as jnp

        from locust_tpu.parallel.shuffle import RoundStats, merge_stats_vectors

        synced = []
        rs = RoundStats(merge_stats_vectors, synced.append, every=3)
        # overflows ADD, distinct/backlog LAST, max MAX, drains ADD
        for i in range(1, 7):
            rs.push(jnp.asarray([1, 10, i, 100 + i, i, 2], jnp.int32))
        assert len(synced) == 2  # flushed at rounds 3 and 6
        a = np.asarray(synced[0])
        assert list(a) == [3, 30, 3, 103, 3, 6]
        b = np.asarray(synced[1])
        assert list(b) == [3, 30, 6, 106, 6, 6]

    def test_flush_idempotent_and_final(self):
        import jax.numpy as jnp

        from locust_tpu.parallel.shuffle import RoundStats, merge_stats_vectors

        synced = []
        rs = RoundStats(merge_stats_vectors, synced.append, every=100)
        rs.flush()  # nothing accumulated: no-op
        assert synced == []
        rs.push(jnp.asarray([1, 0, 5, 0, 5, 0], jnp.int32))
        rs.flush()
        rs.flush()  # second flush: no-op
        assert len(synced) == 1

    def test_custom_fetch_fn(self):
        import jax.numpy as jnp

        from locust_tpu.parallel.shuffle import RoundStats, merge_stats_vectors

        fetched, synced = [], []

        def fetch(x):
            fetched.append(True)
            return np.asarray(x)

        rs = RoundStats(merge_stats_vectors, synced.append, every=1, fetch_fn=fetch)
        rs.push(jnp.asarray([0, 0, 1, 0, 1, 0], jnp.int32))
        assert fetched and len(synced) == 1

    def test_rejects_bad_every(self):
        from locust_tpu.parallel.shuffle import RoundStats, merge_stats_vectors

        with pytest.raises(ValueError, match="stats_sync_every"):
            RoundStats(merge_stats_vectors, lambda s: None, every=0)


def test_shard_capacity_honors_table_size():
    """An explicitly raised cfg.table_size must carry over to the mesh
    engines' default shard capacity: with tiny blocks the emits-derived
    floor (n_dev * bin_capacity) is far below the user's table, and the
    defaults used to truncate a vocabulary the user explicitly sized for
    (r4 fuzz finding — loud, but wrong-by-surprise)."""
    from helpers import py_wordcount

    from locust_tpu.parallel.hierarchical import HierarchicalMapReduce
    from locust_tpu.parallel.mesh import make_mesh, make_mesh_2d

    cfg = small_cfg(block_lines=2, emits_per_line=4, table_size=4096)
    # ~300 distinct words >> the old emits-derived capacity (64/32 rows).
    lines = [b" ".join(b"w%d" % (7 * i + j) for j in range(4))
             for i in range(100)]
    rows = bytes_ops.strings_to_rows(lines, cfg.line_width)
    want = dict(py_wordcount(lines, cfg.emits_per_line))

    d = DistributedMapReduce(make_mesh(8), cfg)
    assert d.shard_capacity >= 4096 // 8
    res = d.run(rows)
    assert not res.truncated
    assert dict(res.to_host_pairs()) == want

    h = HierarchicalMapReduce(make_mesh_2d(2, 4), cfg)
    assert h.shard_capacity >= 4096 // 4
    hres = h.run(rows)
    assert not hres.truncated
    assert dict(hres.to_host_pairs()) == want


def test_mesh_engines_hasht_sort_free_fold():
    """sort_mode="hasht" runs the sort-free aggregate_exact at the
    per-shard merge AND the local combiner (flat) AND the cross-slice
    combine (hierarchical), each branching its exactness ladder
    per-shard under shard_map — oracle-exact on both engines."""
    from locust_tpu.parallel.hierarchical import HierarchicalMapReduce
    from locust_tpu.parallel.mesh import make_mesh, make_mesh_2d

    lines = [b"to be or not to be", b"that is the question", b"the the"] * 8
    cfg = small_cfg(sort_mode="hasht")
    rows = bytes_ops.strings_to_rows(lines, cfg.line_width)
    want = dict(py_wordcount(lines, cfg.emits_per_line))
    res = DistributedMapReduce(make_mesh(8), cfg).run(rows)
    assert dict(res.to_host_pairs()) == want
    res = HierarchicalMapReduce(make_mesh_2d(2, 4), cfg).run(rows)
    assert dict(res.to_host_pairs()) == want


def test_mesh_hasht_residual_branches_under_pressure():
    """Force the hasht exactness ladder OFF its fast path under
    shard_map: ~80% load factor on each shard's table makes probe
    exhaustion near-certain, so the place_residual (and possibly full
    sort) branches run inside the drain while_loop — the answer must
    stay oracle-exact (review finding: the fast path alone was tested)."""
    from locust_tpu.parallel.mesh import make_mesh

    # ~26k distinct words -> ~3.3k per shard against the 4096-row
    # shard-capacity floor (~0.8 load), far above the ~0.09 the probe
    # scheme is tuned for.
    words = [b"w%d" % i for i in range(26_000)]
    lines = [b" ".join(words[i : i + 8]) for i in range(0, len(words), 8)]
    cfg = small_cfg(
        block_lines=512,
        emits_per_line=8,
        line_width=128,
        table_size=4096,
        sort_mode="hasht",
    )
    rows = bytes_ops.strings_to_rows(lines, cfg.line_width)
    res = DistributedMapReduce(make_mesh(8), cfg).run(rows)
    assert dict(res.to_host_pairs()) == dict(
        py_wordcount(lines, cfg.emits_per_line)
    )
