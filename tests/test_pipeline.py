"""Stage-level and end-to-end WordCount tests vs Python oracles.

Golden strategy per SURVEY.md §4: the oracle is ``collections.Counter`` over
strtok-semantics splitting — NOT the reference binary, whose known bugs
(dropped last line, 32k-thread reduce cap; SURVEY.md Q1/Q2) we deliberately
do not reproduce.
"""

import time

import jax.numpy as jnp
import numpy as np
import pytest

from helpers import py_wordcount, strtok_tokens

from locust_tpu.config import SORT_MODES, EngineConfig
from locust_tpu.core import bytes_ops
from locust_tpu.engine import MapReduceEngine
from locust_tpu.ops import map_stage, process_stage, reduce_stage
from locust_tpu.core.kv import KVBatch


SAMPLE = [
    b"to be or not to be",
    b"that is the question",
    b"whether 'tis nobler in the mind to suffer",
    b"the slings and arrows of outrageous fortune",
    b"",
    b"to die - to sleep, no more;",
]


def small_cfg(**kw):
    kw.setdefault("block_lines", 8)
    kw.setdefault("line_width", 64)
    kw.setdefault("emits_per_line", 12)
    return EngineConfig(**kw)


def test_tokenize_block_extracts_exact_tokens():
    cfg = small_cfg()
    rows = jnp.asarray(bytes_ops.strings_to_rows(SAMPLE + [b""] * 2, cfg.line_width))
    res = map_stage.tokenize_block(rows, cfg)
    for i, line in enumerate(SAMPLE):
        toks = strtok_tokens(line)
        got_valid = np.asarray(res.valid[i])
        assert got_valid.sum() == len(toks)
        got_keys = bytes_ops.rows_to_strings(np.asarray(res.keys[i][: len(toks)]))
        assert got_keys == toks
    assert int(res.overflow) == 0


def test_tokenize_map_impls_equivalent():
    """The MXU einsum formulation (TPU default) and the scatter+gather
    formulation (CPU default) must produce identical
    keys/valid/overflow — including overflow lines, empty lines, NUL
    bytes mid-line, and tokens longer than key_width."""
    rng = np.random.default_rng(7)
    alphabet = b"abcde ,.-;:'()\"\t\x00\r"
    lines = [
        bytes(rng.choice(list(alphabet), size=rng.integers(0, 60)))
        for _ in range(32)
    ] + [b"", b"x" * 50, b"one two three four five six seven eight"]
    for kw in (8, 16):
        cfg_e = small_cfg(map_impl="einsum", key_width=kw, emits_per_line=5)
        cfg_g = small_cfg(map_impl="gather", key_width=kw, emits_per_line=5)
        rows = jnp.asarray(bytes_ops.strings_to_rows(lines, cfg_e.line_width))
        a = map_stage.tokenize_block(rows, cfg_e)
        b = map_stage.tokenize_block(rows, cfg_g)
        assert np.array_equal(np.asarray(a.keys), np.asarray(b.keys))
        assert np.array_equal(np.asarray(a.valid), np.asarray(b.valid))
        assert int(a.overflow) == int(b.overflow)


def test_tokenize_overflow_counted_and_dropped():
    cfg = small_cfg(emits_per_line=4)
    line = b"one two three four five six"
    rows = jnp.asarray(bytes_ops.strings_to_rows([line] * 8, cfg.line_width))
    res = map_stage.tokenize_block(rows, cfg)
    assert int(res.overflow) == 2 * 8  # five, six dropped per line
    assert np.asarray(res.valid).sum() == 4 * 8


def test_sort_and_compact_orders_valid_first_then_lex():
    words = [b"pear", b"", b"apple", b"fig", b"", b"apple", b"banana", b""]
    keys = jnp.asarray(bytes_ops.strings_to_rows(words, 32))
    valid = jnp.asarray([bool(w) for w in words])
    batch = KVBatch.from_bytes(keys, jnp.arange(len(words)), valid)
    out = process_stage.sort_and_compact(batch, mode="lex")
    got = bytes_ops.rows_to_strings(np.asarray(out.keys_bytes()))
    live = [w for w in words if w]
    assert got[: len(live)] == sorted(live)
    assert list(np.asarray(out.valid)) == [True] * len(live) + [False] * (
        len(words) - len(live)
    )


def test_sort_and_compact_hash_mode_groups_equal_keys():
    """Hash mode guarantees: valid-first compaction; equal keys adjacent;
    (key, value) multiset preserved.  Device order itself is hash order."""
    words = [b"pear", b"", b"apple", b"fig", b"", b"apple", b"banana", b"fig"]
    keys = jnp.asarray(bytes_ops.strings_to_rows(words, 32))
    valid = jnp.asarray([bool(w) for w in words])
    batch = KVBatch.from_bytes(keys, jnp.arange(len(words)), valid)
    out = process_stage.sort_and_compact(batch, mode="hash")
    got = bytes_ops.rows_to_strings(np.asarray(out.keys_bytes()))
    vals = list(np.asarray(out.values))
    live = [w for w in words if w]
    n_live = len(live)
    assert list(np.asarray(out.valid)) == [True] * n_live + [False] * (
        len(words) - n_live
    )
    # Multiset of live (key, value) pairs preserved.
    got_pairs = sorted(zip(got[:n_live], vals[:n_live]))
    want_pairs = sorted((w, i) for i, w in enumerate(words) if w)
    assert got_pairs == want_pairs
    # Equal keys are contiguous runs.
    seen = set()
    prev = None
    for w in got[:n_live]:
        if w != prev:
            assert w not in seen, f"key {w!r} split into nonadjacent runs"
            seen.add(w)
        prev = w


def test_segment_reduce_counts_runs():
    words = [b"a", b"a", b"b", b"c", b"c", b"c", b"", b""]
    keys = jnp.asarray(bytes_ops.strings_to_rows(words, 32))
    valid = jnp.asarray([bool(w) for w in words])
    batch = KVBatch.from_bytes(keys, jnp.ones(len(words), jnp.int32), valid)
    out = reduce_stage.segment_reduce(batch, "sum")
    pairs = out.to_host_pairs()
    assert pairs == [(b"a", 2), (b"b", 1), (b"c", 3)]


@pytest.mark.parametrize("combine,expect", [("min", 1), ("max", 3), ("count", 3)])
def test_segment_reduce_other_monoids(combine, expect):
    words = [b"k", b"k", b"k", b""]
    keys = jnp.asarray(bytes_ops.strings_to_rows(words, 32))
    batch = KVBatch.from_bytes(
        keys, jnp.asarray([1, 2, 3, 99]), jnp.asarray([1, 1, 1, 0], bool)
    )
    out = reduce_stage.segment_reduce(batch, combine)
    assert out.to_host_pairs() == [(b"k", expect)]


def test_engine_wordcount_matches_counter_single_block():
    cfg = small_cfg()
    eng = MapReduceEngine(cfg)
    res = eng.run_lines(SAMPLE)
    got = dict(res.to_host_pairs())
    expect = dict(py_wordcount(SAMPLE, cfg.emits_per_line))
    assert got == expect
    assert res.num_segments == len(expect)
    assert not res.truncated


def test_engine_wordcount_multi_block_merge():
    cfg = small_cfg(block_lines=4)  # forces 2+ blocks and merges
    eng = MapReduceEngine(cfg)
    lines = SAMPLE * 3
    res = eng.run_lines(lines)
    assert dict(res.to_host_pairs()) == dict(py_wordcount(lines, cfg.emits_per_line))


def test_engine_empty_input():
    eng = MapReduceEngine(small_cfg())
    res = eng.run_lines([])
    assert res.to_host_pairs() == []
    assert res.num_segments == 0


def test_engine_output_is_key_sorted():
    eng = MapReduceEngine(small_cfg())
    res = eng.run_lines(SAMPLE)
    keys = [k for k, _ in res.to_host_pairs()]
    assert keys == sorted(keys)


@pytest.mark.parametrize("runner", ["run", "run_fused", "timed_run"])
def test_truncation_flag_survives_later_merges(runner):
    """Regression: truncation in an EARLY merge must be reported even when the
    final merge's distinct count fits the table capacity.  ``timed_run`` no
    longer truncates: an early merge past the capacity grows its table and
    the result is exact (tests/test_table_growth.py)."""
    # Explicit tiny table: the DEFAULT now floors at 4096 (config.py), and
    # this test's subject is the truncation-flag carry, not the default.
    cfg = small_cfg(block_lines=2, emits_per_line=4, table_size=8)
    lines = [
        b"a b c d",       # block 1: 8 distinct
        b"e f g h",
        b"i j k l",       # block 2: 4 more -> 12 distinct > 8, truncates
        b"",
        b"a b c d",       # block 3: repeats, final merge fits capacity
        b"",
    ]
    eng = MapReduceEngine(cfg)
    res = getattr(eng, runner)(eng.rows_from_lines(lines))
    if runner == "timed_run":
        assert not res.truncated and res.num_segments == 12
        assert dict(res.to_host_pairs()) == dict(py_wordcount(lines, 4))
    else:
        assert res.truncated


def test_engine_run_fused_matches_run():
    cfg = small_cfg(block_lines=4)
    eng = MapReduceEngine(cfg)
    lines = SAMPLE * 3
    res = eng.run_fused(eng.rows_from_lines(lines))
    assert dict(res.to_host_pairs()) == dict(py_wordcount(lines, cfg.emits_per_line))
    assert not res.truncated


def test_engine_timed_run_reports_stages():
    eng = MapReduceEngine(small_cfg())
    rows = eng.rows_from_lines(SAMPLE * 4)
    eng.timed_run(rows)  # compile outside the clock
    t0 = time.perf_counter()
    res = eng.timed_run(rows)
    wall_ms = (time.perf_counter() - t0) * 1e3
    assert dict(res.to_host_pairs()) == dict(py_wordcount(SAMPLE * 4, 12))
    times = res.times
    assert times.map_ms > 0 and times.process_ms > 0 and times.reduce_ms > 0
    assert times.total_ms <= wall_ms


def _block_bytes(cfg):
    """What timed_run budgets a block at: staged lines + three KVBatch."""
    return (cfg.block_lines * cfg.line_width
            + 3 * cfg.emits_per_block * (cfg.key_width + 4 + 1))


@pytest.mark.parametrize("budget_blocks", [None, 2, 3, 0])
def test_engine_timed_run_matches_run_across_groups(monkeypatch, budget_blocks):
    """One group, groups of two, three and a short one, groups of one: the
    same table, distinct count and EXACT dropped-token total as ``run``,
    on a corpus whose lines pass the per-line cap in every group."""
    cfg = small_cfg(block_lines=2, emits_per_line=4)
    if budget_blocks is not None:
        monkeypatch.setattr(MapReduceEngine, "TIMED_GROUP_BYTES",
                            budget_blocks * _block_bytes(cfg))
    lines = [
        b"a b c d e f", b"g h",          # block 0 drops 2
        b"a a", b"b c d e f g h",        # block 1 drops 3
        b"i j k l m", b"",               # block 2 drops 1
        b"n o p q", b"a b c d e",        # block 3 drops 1
    ]
    eng = MapReduceEngine(cfg)
    rows = eng.rows_from_lines(lines)
    want = eng.run(rows)
    got = eng.timed_run(rows)
    assert got.overflow_tokens == want.overflow_tokens == 7
    assert got.num_segments == want.num_segments
    assert got.truncated is want.truncated is False
    assert got.to_host_pairs() == want.to_host_pairs()
    assert dict(got.to_host_pairs()) == dict(py_wordcount(lines, 4))


@pytest.mark.parametrize("cfg_kw, nblocks, lo, hi", [
    ({}, 470, 25, 55),                        # CLI defaults: 9-19 groups a 100 MB job
    ({}, 2, 2, 2),                            # never more than the job has
    ({}, 0, 1, 1),                            # an empty corpus is one padded block
    ({"block_lines": 65536}, 470, 1, 3),      # big blocks: the same rule, small groups
    ({"block_lines": 65536, "emits_per_line": 64, "key_width": 128},
     10, 1, 1),                               # one block over the budget: never 0
])
def test_timed_group_size_follows_the_configs_shapes(cfg_kw, nblocks, lo, hi):
    eng = MapReduceEngine(EngineConfig(**cfg_kw))
    assert lo <= eng._timed_group_blocks(nblocks)[0] <= hi


@pytest.mark.parametrize("seed", [0, 1])
def test_engine_random_corpus_property(seed):
    rng = np.random.default_rng(seed)
    vocab = [f"w{i}".encode() for i in range(40)] + [b"the", b"of", b"a"]
    lines = [
        b" ".join(rng.choice(vocab, size=rng.integers(0, 10)).tolist())
        for _ in range(100)
    ]
    cfg = small_cfg(block_lines=32)
    eng = MapReduceEngine(cfg)
    res = eng.run_lines(lines)
    assert dict(res.to_host_pairs()) == dict(py_wordcount(lines, cfg.emits_per_line))


def test_hamlet_golden_if_available():
    """Golden end-to-end on the reference's sample corpus (read-only mount)."""
    import os

    path = "/root/reference/hamlet.txt"
    if not os.path.exists(path):
        pytest.skip("reference corpus not mounted")
    lines = open(path, "rb").read().splitlines()[:700]  # the README's 700-line run
    cfg = EngineConfig(block_lines=256)
    eng = MapReduceEngine(cfg)
    res = eng.run_lines(lines)
    expect = py_wordcount(lines, cfg.emits_per_line, cfg.key_width)
    assert dict(res.to_host_pairs()) == dict(expect)


def test_engine_checkpoint_resume(tmp_path):
    """Interrupt mid-corpus; a re-run resumes from the snapshot and matches."""
    cfg = small_cfg(block_lines=4)
    lines = SAMPLE * 6
    eng = MapReduceEngine(cfg)
    rows = eng.rows_from_lines(lines)
    want = dict(eng.run(rows).to_host_pairs())

    ckpt = str(tmp_path / "ckpt")
    eng2 = MapReduceEngine(cfg)
    real_fold = eng2._fold_block
    calls = {"n": 0}

    def dying_fold(acc, blk):
        if calls["n"] == 2:
            raise RuntimeError("simulated crash")
        calls["n"] += 1
        return real_fold(acc, blk)

    eng2._fold_block = dying_fold
    with pytest.raises(RuntimeError):
        eng2.run_checkpointed(rows, ckpt, every=1)
    eng2._fold_block = real_fold

    res = eng2.run_checkpointed(rows, ckpt, every=1)
    assert dict(res.to_host_pairs()) == want
    # And the resume actually skipped completed blocks: a third run folds none.
    eng2._fold_block = dying_fold  # would raise on any further fold call
    calls["n"] = 2
    res3 = eng2.run_checkpointed(rows, ckpt, every=1)
    assert dict(res3.to_host_pairs()) == want


def test_engine_checkpoint_fingerprint_mismatch_starts_fresh(tmp_path):
    cfg = small_cfg(block_lines=4)
    eng = MapReduceEngine(cfg)
    rows = eng.rows_from_lines(SAMPLE * 2)
    ckpt = str(tmp_path / "ckpt")
    eng.run_checkpointed(rows, ckpt, every=1)

    other = eng.rows_from_lines(SAMPLE * 4)  # different corpus size
    res = eng.run_checkpointed(other, ckpt, every=1)
    assert dict(res.to_host_pairs()) == dict(
        py_wordcount(SAMPLE * 4, cfg.emits_per_line)
    )


@pytest.mark.parametrize("mode", list(SORT_MODES))
def test_engine_oracle_exact_across_sort_modes(mode):
    """Every Process-stage sort strategy must produce the identical table."""
    from locust_tpu.config import EngineConfig
    from locust_tpu.engine import MapReduceEngine

    lines = [
        b"to be or not to be",
        b"that is the question",
        b"to be, to sleep; to dream",
        b"the the the the",
    ] * 5
    cfg = EngineConfig(block_lines=8, line_width=64, emits_per_line=12,
                       sort_mode=mode)
    got = MapReduceEngine(cfg).run_lines(lines).to_host_pairs()
    assert got == sorted(py_wordcount(lines, 12).items())


REMOVED_SORT_MODES = ["hashp", "hash1", "radix", "bitonic"]  # PR 44


@pytest.mark.parametrize("name", REMOVED_SORT_MODES)
def test_removed_sort_modes_are_refused(name):
    """A removed mode's name is refused as any unknown mode is: no alias,
    no shim — the ValueError names the modes there are."""
    with pytest.raises(ValueError) as e:
        EngineConfig(sort_mode=name)
    assert repr(name) in str(e.value)
    assert all(repr(m) in str(e.value) for m in SORT_MODES)
    assert len(SORT_MODES) == 7 and name not in SORT_MODES


def test_removed_sort_mode_is_refused_by_the_cli(tmp_path):
    """``python -m locust_tpu FILE --sort-mode radix``: argparse's own
    exit 2, naming the choices, before anything runs."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    f = tmp_path / "in.txt"
    f.write_bytes(b"to be or not to be\n")
    r = subprocess.run(
        [sys.executable, "-m", "locust_tpu", str(f), "--sort-mode", "radix"],
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": repo},
        capture_output=True, text=True, timeout=120,
    )
    assert r.returncode == 2 and r.stdout == ""
    assert "invalid choice: 'radix'" in r.stderr
    assert "choose from " + ", ".join(SORT_MODES) in r.stderr


def test_cli_choices_are_sort_modes():
    """The two CLIs offer exactly ``SORT_MODES``, and every backend's
    default is one of them."""
    from locust_tpu import cli, cli_apps
    from locust_tpu.config import default_sort_mode

    def choices(parser):
        (action,) = [
            a for a in parser._actions if "--sort-mode" in a.option_strings
        ]
        return tuple(action.choices)

    assert choices(cli.build_parser()) == SORT_MODES
    for cmd in cli_apps.SUBCOMMANDS:
        parser = cli_apps.build_parser(cmd)
        if cmd in ("sort", "join"):  # one spelling each (order_by_lanes; the join's own sorts), and no flag
            assert "--sort-mode" not in parser._option_string_actions
        else:
            assert choices(parser) == SORT_MODES
    for backend in ("tpu", "cpu", "gpu"):
        assert default_sort_mode(backend) in SORT_MODES


@pytest.mark.parametrize("mode", ["hashp1", "hashp2", "hash"])
def test_single_key_sort_modes_group_equal_keys(mode):
    from locust_tpu.core import bytes_ops
    from locust_tpu.core.kv import KVBatch
    from locust_tpu.ops import process_stage

    words = [b"zz", b"aa", b"zz", b"mm", b"aa", b"zz"]
    keys = jnp.asarray(bytes_ops.strings_to_rows(words, 8))
    batch = KVBatch.from_bytes(
        keys, jnp.arange(6, dtype=jnp.int32), jnp.ones(6, bool)
    )
    import jax

    from locust_tpu.core.packing import unpack_keys

    out = process_stage.sort_and_compact(batch, mode=mode)
    names = bytes_ops.rows_to_strings(
        np.asarray(jax.device_get(unpack_keys(out.key_lanes)))
    )
    # Equal keys must be adjacent (grouping is all the reduce needs).
    seen = []
    for n in names:
        if not seen or seen[-1] != n:
            seen.append(n)
    assert len(seen) == 3  # zz, aa, mm in SOME hash order, each contiguous


def test_engine_stream_checkpoint_resume(tmp_path):
    """run_stream + checkpoint: crash mid-stream, resume folds only the
    remaining blocks and the final table is exact."""
    from locust_tpu.io.loader import StreamingCorpus

    cfg = small_cfg(block_lines=4)
    lines = SAMPLE * 6
    p = tmp_path / "c.txt"
    p.write_bytes(b"\n".join(lines) + b"\n")
    sc = lambda: StreamingCorpus(str(p), cfg.line_width, cfg.block_lines)  # noqa: E731
    eng = MapReduceEngine(cfg)
    want = dict(eng.run_stream(sc()).to_host_pairs())

    ckpt = str(tmp_path / "ckpt")
    fp = sc().fingerprint()
    eng2 = MapReduceEngine(cfg)
    real_fold = eng2._fold_block
    calls = {"n": 0}

    def dying_fold(acc, blk):
        if calls["n"] == 2:
            raise RuntimeError("simulated crash")
        calls["n"] += 1
        return real_fold(acc, blk)

    eng2._fold_block = dying_fold
    with pytest.raises(RuntimeError, match="simulated crash"):
        eng2.run_stream(sc(), checkpoint_dir=ckpt, every=1, fingerprint=fp)
    eng2._fold_block = real_fold
    res = eng2.run_stream(sc(), checkpoint_dir=ckpt, every=1, fingerprint=fp)
    assert dict(res.to_host_pairs()) == want
    # Resume skipped the completed blocks: a further run folds none at all.
    eng2._fold_block = dying_fold
    calls["n"] = 2
    res3 = eng2.run_stream(sc(), checkpoint_dir=ckpt, every=1, fingerprint=fp)
    assert dict(res3.to_host_pairs()) == want


def test_engine_stream_checkpoint_requires_fingerprint(tmp_path):
    cfg = small_cfg(block_lines=4)
    with pytest.raises(ValueError, match="fingerprint"):
        MapReduceEngine(cfg).run_stream(
            iter([]), checkpoint_dir=str(tmp_path / "c")
        )


def test_engine_stream_resume_with_exhausted_iterator_keeps_counters(tmp_path):
    """Regression: resuming with an empty/exhausted iterator must report the
    RESTORED table and counters, not zeros (code-review r3 finding)."""
    from locust_tpu.io.loader import StreamingCorpus

    cfg = small_cfg(block_lines=4)
    lines = SAMPLE * 6
    p = tmp_path / "c.txt"
    p.write_bytes(b"\n".join(lines) + b"\n")
    fp = StreamingCorpus(str(p), cfg.line_width, cfg.block_lines).fingerprint()
    ckpt = str(tmp_path / "ckpt")
    eng = MapReduceEngine(cfg)
    full = eng.run_stream(
        StreamingCorpus(str(p), cfg.line_width, cfg.block_lines),
        checkpoint_dir=ckpt, every=1, fingerprint=fp,
    )
    res = eng.run_stream(
        iter([]), checkpoint_dir=ckpt, every=1, fingerprint=fp
    )
    assert dict(res.to_host_pairs()) == dict(full.to_host_pairs())
    assert res.num_segments == full.num_segments
    assert res.overflow_tokens == full.overflow_tokens
