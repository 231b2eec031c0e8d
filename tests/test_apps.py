"""PageRank + inverted index vs NumPy/pure-Python oracles."""

import numpy as np
import jax
import pytest

from locust_tpu import pagerank_reference
from locust_tpu.config import EngineConfig
from locust_tpu.apps import build_inverted_index, pagerank
from locust_tpu.apps.pagerank import DistributedPageRank
from locust_tpu.parallel import make_mesh

from helpers import strtok_tokens


def np_pagerank(src, dst, n, iters=20, d=0.85):
    """The repo's ONE float64 oracle (``locust_tpu/pagerank_reference.py``)."""
    return pagerank_reference.pagerank(src, dst, n, num_iters=iters, damping=d)


EDGES = np.array(
    [[0, 1], [0, 2], [1, 2], [2, 0], [3, 2], [4, 3], [4, 1], [5, 5]], np.int32
)


def test_pagerank_matches_numpy():
    src, dst = EDGES[:, 0], EDGES[:, 1]
    n = 7  # node 6 is dangling (no out-edges)
    got = np.asarray(pagerank(src, dst, num_nodes=n, num_iters=30))
    expect = np_pagerank(src, dst, n, iters=30)
    np.testing.assert_allclose(got, expect, rtol=1e-4)
    np.testing.assert_allclose(got.sum(), 1.0, rtol=1e-5)


def test_pagerank_ranking_sane():
    # Node 2 has the most in-links in EDGES; it should outrank leaf nodes.
    src, dst = EDGES[:, 0], EDGES[:, 1]
    r = np.asarray(pagerank(src, dst, num_nodes=7, num_iters=30))
    assert r[2] == max(r)


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 virtual devices")
def test_distributed_pagerank_matches_single():
    src, dst = EDGES[:, 0], EDGES[:, 1]
    n = 7
    mesh = make_mesh(8)
    dpr = DistributedPageRank(mesh, num_nodes=n)
    got = dpr.run(src, dst, num_iters=30)
    expect = np_pagerank(src, dst, n, iters=30)
    np.testing.assert_allclose(got, expect, rtol=1e-4)


DOCS = {
    0: b"the quick brown fox",
    1: b"the lazy dog",
    2: b"quick quick dog",
    3: b"",
}


def py_inverted_index(docs):
    out = {}
    for doc_id, text in docs.items():
        for w in strtok_tokens(text):
            out.setdefault(w, set()).add(doc_id)
    return {w: sorted(ids) for w, ids in out.items()}


def test_inverted_index_matches_oracle():
    cfg = EngineConfig(block_lines=8, line_width=64, emits_per_line=8)
    lines = list(DOCS.values())
    ids = np.asarray(list(DOCS.keys()), np.int32)
    got = build_inverted_index(lines, ids, cfg)
    assert got == py_inverted_index(DOCS)


def test_inverted_index_dedups_repeats():
    cfg = EngineConfig(block_lines=8, line_width=64, emits_per_line=8)
    got = build_inverted_index([b"a a a a", b"a a"], np.asarray([7, 9]), cfg)
    assert got == {b"a": [7, 9]}


def test_inverted_index_multiline_doc():
    cfg = EngineConfig(block_lines=8, line_width=64, emits_per_line=8)
    # Two lines of the same doc: postings dedup across lines.
    got = build_inverted_index(
        [b"x y", b"y z"], np.asarray([5, 5]), cfg
    )
    assert got == {b"x": [5], b"y": [5], b"z": [5]}


def test_inverted_index_streams_past_block_capacity():
    # Corpora larger than one block stream through the fold (no line cap).
    cfg = EngineConfig(block_lines=2, line_width=64, emits_per_line=4)
    got = build_inverted_index([b"a", b"b", b"c"], np.arange(3), cfg)
    assert got == {b"a": [0], b"b": [1], b"c": [2]}


def test_inverted_index_mismatched_doc_ids_raises():
    cfg = EngineConfig(block_lines=2, line_width=64, emits_per_line=4)
    with pytest.raises(ValueError, match="doc ids"):
        build_inverted_index([b"a", b"b"], np.arange(3), cfg)


@pytest.mark.parametrize("block_lines", [1, 2, 8])
def test_build_index_is_the_index_as_arrays(block_lines):
    """CSR: words in byte order, offsets into ascending distinct doc ids —
    whatever the block size cuts the documents into."""
    from locust_tpu.apps.inverted_index import build_index
    from locust_tpu.core import bytes_ops

    cfg = EngineConfig(block_lines=block_lines, line_width=64, emits_per_line=8)
    index = build_index(list(DOCS.values()), np.asarray(list(DOCS.keys()), np.int32), cfg)
    want = py_inverted_index(DOCS)
    words = bytes_ops.rows_to_strings(index.words)
    assert words == sorted(want) and len(index) == len(want)
    assert index.offsets.tolist() == np.concatenate(
        [[0], np.cumsum([len(want[w]) for w in words])]).tolist()
    assert index.postings.tolist() == [d for w in words for d in want[w]]
    assert index.to_dict() == want and index.head(2).to_dict() == {w: want[w] for w in words[:2]}
    assert (index.dropped_tokens, index.cut_keys) == (0, 0)


# ------------------------------------------------------- distributed index

def test_distributed_inverted_index_matches_oracle():
    """The mesh index must match the single-device
    oracle on a corpus spanning several shuffle rounds."""
    from locust_tpu.apps.inverted_index import build_inverted_index_mesh
    from locust_tpu.parallel import make_mesh

    rng = np.random.default_rng(5)
    vocab = [f"term{i}".encode() for i in range(40)] + [b"the"] * 4
    docs = {
        d: b" ".join(rng.choice(vocab, size=rng.integers(0, 7)).tolist())
        for d in range(200)
    }
    cfg = EngineConfig(block_lines=8, line_width=64, emits_per_line=8)
    got = build_inverted_index_mesh(
        list(docs.values()), np.asarray(list(docs.keys()), np.int32),
        make_mesh(8), cfg,
    )
    assert got == py_inverted_index(docs)


def test_distributed_inverted_index_skewed_bins_lossless():
    """Tiny bins force the backlog machinery; postings must stay exact."""
    from locust_tpu.apps.inverted_index import build_inverted_index_mesh
    from locust_tpu.parallel import make_mesh

    rng = np.random.default_rng(9)
    vocab = [f"w{i}".encode() for i in range(120)]
    docs = {d: b" ".join(rng.choice(vocab, size=5).tolist()) for d in range(64)}
    cfg = EngineConfig(block_lines=4, line_width=64, emits_per_line=8)
    got = build_inverted_index_mesh(
        list(docs.values()), np.asarray(list(docs.keys()), np.int32),
        make_mesh(8), cfg, skew_factor=0.2,
    )
    assert got == py_inverted_index(docs)


def test_distributed_inverted_index_capacity_raises():
    from locust_tpu.apps.inverted_index import build_inverted_index_mesh
    from locust_tpu.parallel import make_mesh

    vocab = [f"w{i}".encode() for i in range(100)]
    docs = {d: b" ".join(vocab[d % 50 : d % 50 + 6]) for d in range(64)}
    cfg = EngineConfig(block_lines=4, line_width=64, emits_per_line=8)
    with pytest.raises(ValueError, match="pairs_capacity"):
        build_inverted_index_mesh(
            list(docs.values()), np.asarray(list(docs.keys()), np.int32),
            make_mesh(8), cfg, pairs_capacity=4,
        )


# ---------------------------------------------------------------- sample sort

def test_distributed_sample_sort_random():
    from locust_tpu.apps.sample_sort import sort_strings
    from locust_tpu.parallel import make_mesh

    rng = np.random.default_rng(7)
    words = [
        bytes(rng.integers(97, 123, size=rng.integers(1, 12)).astype(np.uint8))
        for _ in range(4000)
    ]
    cfg = EngineConfig(block_lines=8, line_width=64, emits_per_line=8)
    got = sort_strings(words, make_mesh(8), cfg)
    assert got == sorted(words)


def test_distributed_sample_sort_carries_values():
    from locust_tpu.apps.sample_sort import DistributedSort
    from locust_tpu.core import bytes_ops
    from locust_tpu.parallel import make_mesh

    words = [b"delta", b"alpha", b"echo", b"charlie", b"bravo", b"foxtrot"]
    cfg = EngineConfig(block_lines=8, line_width=64, emits_per_line=8)
    ds = DistributedSort(make_mesh(8), cfg, rows_per_device=8)
    rows = bytes_ops.strings_to_rows(words, cfg.key_width)
    got = ds.sort_rows(rows).to_host_sorted()
    # values are the original indices: sort is a permutation we can invert
    assert [k for k, _ in got] == sorted(words)
    assert [words[v] for _, v in got] == sorted(words)
    assert ds.sort_rows(rows).overflow == 0


def test_distributed_sample_sort_duplicate_heavy():
    """Duplicate-heavy skew must be absorbed WITHOUT the caller hand-tuning
    skew_factor: sort_strings retries with doubled bins until lossless
    (round-1 advisor finding — the old default silently dropped rows)."""
    from locust_tpu.apps.sample_sort import sort_strings
    from locust_tpu.parallel import make_mesh

    words = [b"same"] * 300 + [b"other"] * 200 + [b"zz", b"aa"] * 50
    cfg = EngineConfig(block_lines=8, line_width=64, emits_per_line=8)
    got = sort_strings(words, make_mesh(8), cfg)
    assert got == sorted(words)


def test_distributed_sample_sort_raises_after_retry_budget():
    from locust_tpu.apps.sample_sort import sort_strings
    from locust_tpu.parallel import make_mesh

    words = [b"same"] * 512  # one range bin gets everything
    cfg = EngineConfig(block_lines=8, line_width=64, emits_per_line=8)
    with pytest.raises(ValueError, match="dropped"):
        sort_strings(words, make_mesh(8), cfg, max_retries=0, skew_factor=0.25)


def test_distributed_sample_sort_mostly_padding():
    """Regression: splitters must come from VALID samples only — zero-padding
    rows once dragged all splitters to zero, funneling every real key into
    one overflowing bin and silently dropping rows."""
    from locust_tpu.apps.sample_sort import DistributedSort
    from locust_tpu.core import bytes_ops
    from locust_tpu.parallel import make_mesh

    rng = np.random.default_rng(3)
    words = [
        bytes(rng.integers(97, 123, size=rng.integers(1, 12)).astype(np.uint8))
        for _ in range(1000)
    ]
    cfg = EngineConfig(block_lines=8, line_width=64, emits_per_line=8)
    ds = DistributedSort(make_mesh(8), cfg, rows_per_device=1024)  # 87% padding
    rows = bytes_ops.strings_to_rows(words, cfg.key_width)
    res = ds.sort_rows(rows)
    got = [k for k, _ in res.to_host_sorted()]
    assert res.overflow == 0
    assert got == sorted(words)


def test_inverted_index_multi_block_streaming():
    """The index streams blocks like the engine: corpora larger than one
    block fold into the carried pair table."""
    from locust_tpu.apps.inverted_index import build_inverted_index

    docs = [
        (0, b"alpha bravo charlie"),
        (1, b"bravo delta"),
        (2, b"alpha delta echo"),
        (3, b"charlie charlie alpha"),
        (4, b"echo foxtrot"),
        (5, b"bravo alpha"),
    ] * 4
    cfg = EngineConfig(block_lines=4, line_width=64, emits_per_line=6)
    got = build_inverted_index(
        [t for _, t in docs], np.asarray([d for d, _ in docs]), cfg
    )
    want: dict[bytes, set] = {}
    for d, text in docs:
        for w in text.split():
            want.setdefault(w, set()).add(d)
    assert {k: sorted(v) for k, v in want.items()} == got


def test_inverted_index_capacity_exceeded_raises():
    from locust_tpu.apps.inverted_index import build_inverted_index

    lines = [f"w{i} w{i+1} w{i+2}".encode() for i in range(0, 64, 1)]
    cfg = EngineConfig(block_lines=8, line_width=64, emits_per_line=4)
    with pytest.raises(ValueError, match="pairs_capacity"):
        build_inverted_index(
            lines, np.arange(len(lines)), cfg, pairs_capacity=16
        )


class TestShardedPageRank:
    """Node-partitioned PageRank: rank state is
    sharded O(nodes/n_dev) per device; routing is a static sparse plan."""

    def _mesh(self):
        from locust_tpu.parallel.mesh import make_mesh

        return make_mesh()

    @pytest.mark.parametrize("num_nodes", [64, 1000, 1003])  # incl. non-divisible
    def test_matches_single_device(self, num_nodes):
        from locust_tpu.apps.pagerank import ShardedPageRank

        rng = np.random.default_rng(1)
        E = num_nodes * 8
        src = rng.integers(0, num_nodes, E).astype(np.int32)
        dst = rng.integers(0, num_nodes, E).astype(np.int32)
        ref = np.asarray(pagerank(src, dst, num_nodes=num_nodes, num_iters=15))
        got = ShardedPageRank(self._mesh(), num_nodes).run(src, dst, num_iters=15)
        np.testing.assert_allclose(got, ref, atol=1e-5)

    def test_dangling_and_empty_shards(self):
        from locust_tpu.apps.pagerank import ShardedPageRank

        # All edges target node 0 from node 1; nodes 2..63 are dangling,
        # and most (sender, dest-shard) pairs carry no edges at all.
        n = 64
        src = np.array([1, 1, 1], np.int32)
        dst = np.array([0, 0, 0], np.int32)
        ref = np.asarray(pagerank(src, dst, num_nodes=n, num_iters=10))
        got = ShardedPageRank(self._mesh(), n).run(src, dst, num_iters=10)
        np.testing.assert_allclose(got, ref, atol=1e-6)
        assert abs(got.sum() - 1.0) < 1e-3  # probability mass conserved

    @staticmethod
    def _build_plan_loop(spr, src, dst):
        """The pre-r4 O(n_dev^2) per-(device, shard) np.unique builder,
        kept verbatim as the regression oracle for the vectorized
        lexsort builder."""
        n_dev, npd = spr.n_dev, spr.npd
        src = np.asarray(src, np.int64)
        dst = np.asarray(dst, np.int64)
        owner = src // npd
        order = np.argsort(owner, kind="stable")
        src, dst, owner = src[order], dst[order], owner[order]
        counts = np.bincount(owner, minlength=n_dev)
        e_max = max(1, int(counts.max()))
        src_l = np.zeros((n_dev, e_max), np.int32)
        mask = np.zeros((n_dev, e_max), np.float32)
        send_seg = np.zeros((n_dev, e_max), np.int32)
        starts = np.concatenate([[0], np.cumsum(counts)])
        per_pair = []
        cap = 1
        for d in range(n_dev):
            s, e = starts[d], starts[d + 1]
            dsts_d = dst[s:e]
            dest_shard = dsts_d // npd
            src_l[d, : e - s] = (src[s:e] - d * npd).astype(np.int32)
            mask[d, : e - s] = 1.0
            row = []
            for p in range(n_dev):
                sel = dest_shard == p
                uniq = np.unique(dsts_d[sel])
                row.append((sel, uniq))
                cap = max(cap, len(uniq))
            per_pair.append(row)
        cap = -(-cap // 8) * 8
        recv_map = np.full((n_dev, n_dev, cap), npd, np.int32)
        for d in range(n_dev):
            s, e = starts[d], starts[d + 1]
            dsts_d = dst[s:e]
            seg = np.full(e - s, n_dev * cap, np.int32)
            for p, (sel, uniq) in enumerate(per_pair[d]):
                if not len(uniq):
                    continue
                seg[sel] = p * cap + np.searchsorted(uniq, dsts_d[sel])
                recv_map[p, d, : len(uniq)] = (uniq - p * npd).astype(np.int32)
            send_seg[d, : e - s] = seg
        send_seg[mask == 0] = n_dev * cap
        return dict(
            src_l=src_l, mask=mask, send_seg=send_seg, recv_map=recv_map,
            cap=cap, e_max=e_max,
        )

    @pytest.mark.parametrize("num_nodes,n_edges", [(64, 0), (64, 3),
                                                   (1000, 4000), (1003, 9000)])
    def test_vectorized_plan_matches_loop_builder(self, num_nodes, n_edges):
        """The lexsort plan builder is equivalent to the old per-pair
        unique loop: recv_map/cap/e_max identical; per-edge arrays equal
        as (src_l, send_seg, mask) multisets per device (the intra-device
        edge ORDER may differ — every consumer is a segment_sum, so order
        is immaterial)."""
        from locust_tpu.apps.pagerank import ShardedPageRank

        spr = ShardedPageRank(self._mesh(), num_nodes)
        rng = np.random.default_rng(num_nodes + n_edges)
        src = rng.integers(0, num_nodes, n_edges).astype(np.int32)
        dst = rng.integers(0, num_nodes, n_edges).astype(np.int32)
        got = spr._build_plan(src, dst)
        want = self._build_plan_loop(spr, src, dst)
        assert got["cap"] == want["cap"]
        assert got["e_max"] == want["e_max"]
        np.testing.assert_array_equal(got["recv_map"], want["recv_map"])
        for d in range(spr.n_dev):
            g = sorted(zip(got["src_l"][d], got["send_seg"][d], got["mask"][d]))
            w = sorted(zip(want["src_l"][d], want["send_seg"][d], want["mask"][d]))
            assert g == w

    def test_state_is_sharded_not_replicated(self):
        from locust_tpu.apps.pagerank import ShardedPageRank

        n = 1000
        spr = ShardedPageRank(self._mesh(), n)
        rng = np.random.default_rng(2)
        src = rng.integers(0, n, 4000).astype(np.int32)
        dst = rng.integers(0, n, 4000).astype(np.int32)
        plan = spr._build_plan(src, dst)
        # Per-device edge shard + per-pair slot capacity, NOT num_nodes.
        assert plan["src_l"].shape[0] == spr.n_dev
        assert plan["src_l"].shape[1] < len(src)  # edges/n_dev-ish, padded
        assert plan["cap"] <= spr.npd + 8  # at most one slot per owned node


def test_inverted_index_warns_on_dropped_postings(caplog):
    """Tokens beyond emits_per_line mean MISSING postings; both index
    builders must warn loudly (code-review r3 finding)."""
    import logging

    from locust_tpu.config import EngineConfig
    from locust_tpu.parallel.mesh import make_mesh
    from locust_tpu.apps.inverted_index import (
        build_inverted_index,
        build_inverted_index_mesh,
    )

    lines = [b"a b c d e f"]  # 6 tokens > cap of 4
    ids = np.array([0], np.int32)
    cfg = EngineConfig(block_lines=8, line_width=64, emits_per_line=4)
    with caplog.at_level(logging.WARNING, logger="locust_tpu"):
        build_inverted_index(lines, ids, cfg)
    assert any("MISSING" in r.message for r in caplog.records)
    caplog.clear()
    with caplog.at_level(logging.WARNING, logger="locust_tpu"):
        build_inverted_index_mesh(lines, ids, make_mesh(), cfg)
    assert any("MISSING" in r.message for r in caplog.records)


def test_distributed_inverted_index_stream_matches_run():
    from locust_tpu.config import EngineConfig
    from locust_tpu.core import bytes_ops
    from locust_tpu.parallel.mesh import make_mesh
    from locust_tpu.apps.inverted_index import DistributedInvertedIndex

    lines = [b"alpha beta", b"beta gamma", b"gamma alpha", b"delta"] * 9
    ids = (np.arange(len(lines)) // 3).astype(np.int32)
    cfg = EngineConfig(block_lines=4, line_width=64, emits_per_line=8)
    rows = bytes_ops.strings_to_rows(lines, cfg.line_width)
    dii = DistributedInvertedIndex(make_mesh(8), cfg)
    want = dii.run(rows, ids)
    lpr = dii.lines_per_round
    got = dii.run_stream(
        (rows[i : i + lpr], ids[i : i + lpr]) for i in range(0, len(lines), lpr)
    )
    assert got == want


def test_distributed_inverted_index_checkpoint_resume(tmp_path):
    """Crash mid-corpus; a re-run resumes after the last completed round
    and the rebuilt index matches exactly (ShardedCheckpoint protocol)."""
    from locust_tpu.apps.inverted_index import DistributedInvertedIndex
    from locust_tpu.config import EngineConfig
    from locust_tpu.core import bytes_ops
    from locust_tpu.parallel.mesh import make_mesh

    lines = [b"alpha beta", b"beta gamma", b"gamma alpha", b"delta"] * 12
    ids = (np.arange(len(lines)) // 3).astype(np.int32)
    cfg = EngineConfig(block_lines=2, line_width=64, emits_per_line=8)
    rows = bytes_ops.strings_to_rows(lines, cfg.line_width)
    dii = DistributedInvertedIndex(make_mesh(8), cfg)
    want = dii.run(rows, ids)

    ckpt = str(tmp_path / "ickpt")
    real_step = dii._step
    calls = {"n": 0}

    def dying_step(*a):
        if calls["n"] == 1:
            raise RuntimeError("simulated crash")
        calls["n"] += 1
        return real_step(*a)

    dii._step = dying_step
    with pytest.raises(RuntimeError, match="simulated crash"):
        dii.run(rows, ids, checkpoint_dir=ckpt)
    dii._step = real_step

    assert dii.run(rows, ids, checkpoint_dir=ckpt) == want
    # Fully-checkpointed third run steps zero times.
    calls["n"] = 1
    dii._step = dying_step
    assert dii.run(rows, ids, checkpoint_dir=ckpt) == want
    dii._step = real_step

    # Different doc-id sharding over the SAME lines -> fresh start.
    other_ids = (np.arange(len(lines)) // 6).astype(np.int32)
    res = dii.run(rows, other_ids, checkpoint_dir=ckpt)
    assert res == dii.run(rows, other_ids)
