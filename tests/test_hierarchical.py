"""HierarchicalMapReduce: per-slice ICI shuffle + one cross-slice combine.

The two-level design keeps every per-round all-to-all inside a slice (ICI)
and crosses the slice axis (DCN on real pods) exactly once, with bounded
tables.  Correctness must hold for any [slice, data] factorization,
including the degenerate ones that reduce to the flat engine.
"""

import numpy as np
import pytest

import jax

from helpers import py_wordcount

from locust_tpu.config import EngineConfig
from locust_tpu.core import bytes_ops
from locust_tpu.parallel.hierarchical import HierarchicalMapReduce
from locust_tpu.parallel.mesh import make_mesh_2d

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 (virtual) devices"
)

LINES = [
    b"to be or not to be",
    b"that is the question",
    b"to be, to sleep; to dream",
    b"the the the the",
]


def _cfg(**kw):
    kw.setdefault("block_lines", 8)
    kw.setdefault("line_width", 64)
    kw.setdefault("emits_per_line", 8)
    return EngineConfig(**kw)


@pytest.mark.parametrize("shape", [(2, 4), (4, 2), (8, 1), (1, 8)])
def test_matches_oracle_across_mesh_shapes(shape):
    cfg = _cfg()
    h = HierarchicalMapReduce(make_mesh_2d(*shape), cfg)
    lines = LINES * 11
    rows = bytes_ops.strings_to_rows(lines, cfg.line_width)
    res = h.run(rows)
    want = py_wordcount(lines, cfg.emits_per_line)
    assert dict(res.to_host_pairs()) == dict(want)
    assert res.distinct == len(want)
    assert res.shuffle_overflow == 0 and not res.truncated


def test_multi_round_carries_per_slice_tables():
    cfg = _cfg()
    h = HierarchicalMapReduce(make_mesh_2d(2, 4), cfg)
    lines = LINES * (3 * h.lines_per_round // len(LINES))  # 3 rounds
    rows = bytes_ops.strings_to_rows(lines, cfg.line_width)
    res = h.run(rows)
    want = py_wordcount(lines, cfg.emits_per_line)
    assert dict(res.to_host_pairs()) == dict(want)
    assert res.distinct == len(want)


def test_skewed_bins_drain_losslessly():
    """Tiny bins force the on-device drain loop across BOTH slices."""
    cfg = _cfg(emits_per_line=16)
    # skew_factor shrinks the BINS (exercising the drain loop); the shard
    # tables get explicit headroom so truncation can't mask the result.
    h = HierarchicalMapReduce(
        make_mesh_2d(2, 4), cfg, skew_factor=0.1, shard_capacity=256
    )
    # One hot key everywhere + per-line unique keys = worst-case skew.
    lines = [b"hot w%03d w%03d" % (2 * i, 2 * i + 1) for i in range(64)]
    rows = bytes_ops.strings_to_rows(lines, cfg.line_width)
    res = h.run(rows)
    want = py_wordcount(lines, cfg.emits_per_line)
    assert dict(res.to_host_pairs()) == dict(want)
    assert res.drain_rounds > 0  # the skew actually exercised the backlog
    assert res.shuffle_overflow == 0 and not res.truncated


def test_distinct_counts_cross_slice_keys_once():
    """A key appearing in every slice must count ONCE globally after the
    cross-slice combine, with its counts summed."""
    cfg = _cfg()
    h = HierarchicalMapReduce(make_mesh_2d(2, 4), cfg)
    # Every line identical: the key lands in both slices' partial tables.
    lines = [b"same same same"] * h.lines_per_round
    rows = bytes_ops.strings_to_rows(lines, cfg.line_width)
    res = h.run(rows)
    assert res.distinct == 1
    assert dict(res.to_host_pairs()) == {b"same": 3 * len(lines)}


def test_mesh_axis_validation():
    from locust_tpu.parallel.mesh import make_mesh

    with pytest.raises(ValueError, match="axes"):
        HierarchicalMapReduce(make_mesh(8), _cfg())


def test_make_mesh_2d_validation():
    with pytest.raises(ValueError, match="divide"):
        make_mesh_2d(3)  # 8 devices don't divide into 3 slices
    with pytest.raises(ValueError, match="have"):
        make_mesh_2d(4, 4)  # 16 > 8


def test_cross_slice_combine_truncation_is_reported():
    """When the union of per-slice tables exceeds a column shard's
    capacity, keys drop — the result must say so (truncated=True)."""
    cfg = _cfg(emits_per_line=16)
    h = HierarchicalMapReduce(
        make_mesh_2d(2, 4), cfg, skew_factor=0.1, shard_capacity=8
    )
    lines = [b"hot w%03d w%03d" % (2 * i, 2 * i + 1) for i in range(64)]
    rows = bytes_ops.strings_to_rows(lines, cfg.line_width)
    res = h.run(rows)  # 129 distinct keys >> 8 per shard
    assert res.truncated


def test_count_combine_is_associative_across_all_levels():
    """combine="count" must return occurrence counts, not the number of
    partial tables holding the key (code-review r3 finding: the count
    monoid's merge is SUM; normalize_combine lowers it)."""
    cfg = _cfg()
    lines = [b"same same same"] * 64  # multiple blocks/rounds/slices

    from locust_tpu.engine import MapReduceEngine
    from locust_tpu.parallel.mesh import make_mesh
    from locust_tpu.parallel.shuffle import DistributedMapReduce

    rows = bytes_ops.strings_to_rows(lines, cfg.line_width)
    want = {b"same": 3 * len(lines)}

    eng = MapReduceEngine(EngineConfig(block_lines=8, line_width=64,
                                       emits_per_line=8), combine="count")
    assert dict(eng.run(rows).to_host_pairs()) == want

    flat = DistributedMapReduce(make_mesh(8), cfg, combine="count")
    assert dict(flat.run(rows).to_host_pairs()) == want

    hier = HierarchicalMapReduce(make_mesh_2d(2, 4), cfg, combine="count")
    assert dict(hier.run(rows).to_host_pairs()) == want


def test_hierarchical_run_stream_matches_run():
    cfg = _cfg()
    h = HierarchicalMapReduce(make_mesh_2d(2, 4), cfg)
    lines = LINES * (2 * h.lines_per_round // len(LINES))
    rows = bytes_ops.strings_to_rows(lines, cfg.line_width)
    want = h.run(rows).to_host_pairs()
    lpr = h.lines_per_round
    got = h.run_stream(
        rows[i : i + lpr] for i in range(0, rows.shape[0], lpr)
    ).to_host_pairs()
    assert got == want


def test_hierarchical_checkpoint_resume(tmp_path):
    """Crash mid-corpus on the [2,4] mesh; a re-run resumes after the
    last completed round and matches exactly (the flat engine's protocol,
    test_distributed.test_distributed_checkpoint_resume)."""
    cfg = _cfg(block_lines=2)  # 16 lines/round -> several rounds
    lines = [b"alpha beta", b"beta gamma", b"alpha delta epsilon"] * 20
    rows = bytes_ops.strings_to_rows(lines, cfg.line_width)
    want = dict(
        HierarchicalMapReduce(make_mesh_2d(2, 4), cfg).run(rows).to_host_pairs()
    )
    assert want == dict(py_wordcount(lines, cfg.emits_per_line, cfg.key_width))

    ckpt = str(tmp_path / "hckpt")
    h = HierarchicalMapReduce(make_mesh_2d(2, 4), cfg)
    real_step = h._step
    calls = {"n": 0}

    def dying_step(lines_, acc, leftover):
        if calls["n"] == 2:
            raise RuntimeError("simulated crash")
        calls["n"] += 1
        return real_step(lines_, acc, leftover)

    h._step = dying_step
    with pytest.raises(RuntimeError, match="simulated crash"):
        h.run(rows, checkpoint_dir=ckpt)
    h._step = real_step

    res = h.run(rows, checkpoint_dir=ckpt)
    assert dict(res.to_host_pairs()) == want
    # Resume skipped the completed rounds: a fully-checkpointed third run
    # steps zero times.
    calls["n"] = 2
    h._step = dying_step
    res3 = h.run(rows, checkpoint_dir=ckpt)
    assert dict(res3.to_host_pairs()) == want


def test_hierarchical_checkpoint_fingerprint_content(tmp_path):
    """Same shape, different corpus -> fresh start, correct counts."""
    cfg = _cfg(block_lines=2)
    ckpt = str(tmp_path / "hckpt")
    h = HierarchicalMapReduce(make_mesh_2d(2, 4), cfg)
    lines_a = [b"aaa bbb"] * 32
    h.run(bytes_ops.strings_to_rows(lines_a, cfg.line_width), checkpoint_dir=ckpt)
    lines_b = [b"ccc ddd"] * 32
    res = h.run(
        bytes_ops.strings_to_rows(lines_b, cfg.line_width), checkpoint_dir=ckpt
    )
    assert dict(res.to_host_pairs()) == {b"ccc": 32, b"ddd": 32}


def test_hierarchical_stream_checkpoint(tmp_path):
    """run_stream + checkpoint: resume re-reads but does not re-fold."""
    cfg = _cfg(block_lines=2)
    lines = [b"x y z", b"y z"] * 24
    rows = bytes_ops.strings_to_rows(lines, cfg.line_width)
    want = dict(py_wordcount(lines, cfg.emits_per_line, cfg.key_width))
    ckpt = str(tmp_path / "hsckpt")
    h = HierarchicalMapReduce(make_mesh_2d(2, 4), cfg)
    lpr = h.lines_per_round

    def blocks():
        for i in range(0, rows.shape[0], lpr):
            yield rows[i : i + lpr]

    res = h.run_stream(
        blocks(), fingerprint="fp1", checkpoint_dir=ckpt
    )
    assert dict(res.to_host_pairs()) == want
    # Second run with the same fingerprint: all rounds already folded.
    real_step = h._step
    h._step = lambda *a: (_ for _ in ()).throw(RuntimeError("stepped"))
    res2 = h.run_stream(blocks(), fingerprint="fp1", checkpoint_dir=ckpt)
    assert dict(res2.to_host_pairs()) == want
    h._step = real_step

    with pytest.raises(ValueError, match="fingerprint"):
        h.run_stream(blocks(), checkpoint_dir=ckpt)


def test_cross_engine_checkpoint_not_resumed(tmp_path):
    """A flat-engine snapshot in the same dir with the same corpus
    fingerprint must NOT be resumed by the hierarchical engine (their npz
    counter schemas differ — resuming used to KeyError; engine identity
    is bound into the stream fingerprint)."""
    from locust_tpu.parallel.mesh import make_mesh
    from locust_tpu.parallel.shuffle import DistributedMapReduce

    cfg = _cfg(block_lines=2)
    lines = [b"aa bb", b"bb cc"] * 16
    rows = bytes_ops.strings_to_rows(lines, cfg.line_width)
    ckpt = str(tmp_path / "shared")

    flat = DistributedMapReduce(make_mesh(8), cfg)

    def blocks(lpr):
        for i in range(0, rows.shape[0], lpr):
            yield rows[i : i + lpr]

    flat.run_stream(
        blocks(flat.lines_per_round), fingerprint="same-corpus",
        checkpoint_dir=ckpt,
    )

    h = HierarchicalMapReduce(make_mesh_2d(2, 4), cfg)
    res = h.run_stream(
        blocks(h.lines_per_round), fingerprint="same-corpus",
        checkpoint_dir=ckpt,
    )
    want = dict(py_wordcount(lines, cfg.emits_per_line, cfg.key_width))
    assert dict(res.to_host_pairs()) == want


def test_debug_checks_verify_slice_replication(monkeypatch):
    """LOCUST_DEBUG_CHECKS makes the check_vma=False replication claim
    self-policing: a healthy run passes the
    per-slice table-equality check; a combine that leaks slice-varying
    data into the merge fires it loudly."""
    monkeypatch.setenv("LOCUST_DEBUG_CHECKS", "1")
    cfg = _cfg()
    h = HierarchicalMapReduce(make_mesh_2d(2, 4), cfg)
    lines = LINES * 11
    rows = bytes_ops.strings_to_rows(lines, cfg.line_width)
    res = h.run(rows)  # healthy: check passes silently
    assert dict(res.to_host_pairs()) == dict(
        py_wordcount(lines, cfg.emits_per_line)
    )

    # Corrupt one slice: wrap the debug combine so slice 1's values are
    # perturbed — exactly the failure mode (slice-varying data reaching
    # the supposedly-replicated output) the check exists to catch.
    from jax.sharding import PartitionSpec as P

    orig = h._combine_dbg

    def doctored(acc):
        table, stats = orig(acc)
        vals = np.asarray(table.values).copy()
        per_slice = vals.reshape(h.n_slices, -1)
        per_slice[1] += 1
        import dataclasses

        table = dataclasses.replace(
            table, values=jax.numpy.asarray(per_slice.reshape(vals.shape))
        )
        return table, stats

    h._combine_dbg = doctored
    with pytest.raises(RuntimeError, match="slice-varying"):
        h.run(rows)
