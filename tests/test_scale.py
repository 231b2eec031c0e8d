"""Scale: corpora whose vocabulary exceeds the default table capacity.

Once nothing exercised >65,536 distinct keys (the
default ``resolved_table_size``), where truncation semantics actually
bite.  These tests build a synthetic corpus with a unique-heavy Zipf-ish
vocabulary larger than 2^16 and push it through the fused single-device
path and the mesh path held to an explicit ``shard_capacity`` — a fixed
bound, reported loudly when passed.  (Left at its default the mesh's
shards grow and stay exact: tests/test_mesh_growth.py.)
"""

import numpy as np
import jax
import pytest

from locust_tpu.config import EngineConfig
from locust_tpu.core import bytes_ops
from locust_tpu.engine import MapReduceEngine

N_KEYS = (1 << 16) + 1200  # just past the default table capacity


def big_vocab_lines(n_keys: int = N_KEYS, per_line: int = 8) -> list[bytes]:
    words = [b"k%06d" % i for i in range(n_keys)]
    return [
        b" ".join(words[i : i + per_line]) for i in range(0, n_keys, per_line)
    ]


@pytest.fixture(scope="module")
def corpus():
    return big_vocab_lines()


def test_fused_run_truncates_loudly_past_default_table(corpus):
    cfg = EngineConfig(block_lines=4096, line_width=128)
    assert cfg.resolved_table_size == 1 << 16  # the default under test
    eng = MapReduceEngine(cfg)
    res = eng.run_fused(eng.rows_from_lines(corpus))
    assert res.truncated
    assert res.num_segments == cfg.resolved_table_size
    # Surviving counts are still exact: every kept key appears once.
    pairs = res.to_host_pairs()
    assert len(pairs) == cfg.resolved_table_size
    assert all(v == 1 for _, v in pairs)


def test_fused_run_exact_with_explicit_table_size(corpus):
    cfg = EngineConfig(block_lines=4096, line_width=128, table_size=1 << 17)
    eng = MapReduceEngine(cfg)
    res = eng.run_fused(eng.rows_from_lines(corpus))
    assert not res.truncated
    assert res.num_segments == N_KEYS
    pairs = res.to_host_pairs()
    assert len(pairs) == N_KEYS and all(v == 1 for _, v in pairs)


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 (virtual) devices")
def test_mesh_run_past_2_16_distinct_keys(corpus):
    from locust_tpu.parallel import DistributedMapReduce, make_mesh

    mesh = make_mesh(8)
    cfg = EngineConfig(block_lines=512, line_width=128, emits_per_line=8)
    dmr = DistributedMapReduce(mesh, cfg, shard_capacity=16384)
    rows = bytes_ops.strings_to_rows(corpus, cfg.line_width)
    res = dmr.run(rows)
    assert not res.truncated
    assert res.distinct == N_KEYS
    pairs = res.to_host_pairs()
    assert len(pairs) == N_KEYS and all(v == 1 for _, v in pairs)


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 (virtual) devices")
def test_mesh_explicit_shard_capacity_truncates_loudly(corpus):
    from locust_tpu.parallel import DistributedMapReduce, make_mesh

    mesh = make_mesh(8)
    cfg = EngineConfig(block_lines=512, line_width=128, emits_per_line=8)
    dmr = DistributedMapReduce(mesh, cfg, shard_capacity=1024)  # ~8.4k/shard real
    rows = bytes_ops.strings_to_rows(corpus, cfg.line_width)
    res = dmr.run(rows)
    assert res.truncated


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 (virtual) devices")
def test_sharded_pagerank_scale():
    """100k nodes / 800k edges on the 8-device mesh: the static routing
    plan stays per-shard-sized and the result matches the dense oracle
    (BASELINE.json configs[3] at test scale)."""
    import numpy as np

    from locust_tpu.apps.pagerank import ShardedPageRank, pagerank
    from locust_tpu.parallel.mesh import make_mesh

    rng = np.random.default_rng(42)
    n_nodes, n_edges = 100_000, 800_000
    src = rng.integers(0, n_nodes, n_edges).astype(np.int32)
    dst = rng.integers(0, n_nodes, n_edges).astype(np.int32)
    spr = ShardedPageRank(make_mesh(), n_nodes)
    plan = spr._build_plan(src, dst)
    # Memory claim: per-device state is O(edges/n_dev) and O(nodes/n_dev).
    assert plan["e_max"] < n_edges / spr.n_dev * 1.1
    assert plan["cap"] <= spr.npd + 8
    got = spr.run(src, dst, num_iters=8)
    ref = np.asarray(pagerank(src, dst, num_nodes=n_nodes, num_iters=8))
    np.testing.assert_allclose(got, ref, atol=2e-6)
