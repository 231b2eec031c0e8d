"""The default path merges a GROUP's block tables into its table at once.

``timed_run`` launches ``merge_tables`` once a group of blocks — the running
table and every block table of the group through one sort and one segment
combine — where it used to launch it once a block.  What must hold: the table
the per-block fold (``run``) and the host oracle give, for every combiner and
every layout of groups; a merge past the capacity counts every key, so the
table grows in ONE step and the group is merged once more; and the number of
tables a merge program takes comes from a short ladder, whatever the job's
size (``MapReduceEngine._timed_group_blocks``).  Tolerance: none.
"""

import threading
import time

import numpy as np
import pytest

from helpers import py_wordcount
from test_table_growth import (
    _BLOCK_BYTES, _SMALL, _grow_spans, _oracle, _table, distinct_lines,
    valued_lines, valued_map, valued_oracle,
)

from locust_tpu import obs
from locust_tpu.config import EngineConfig, default_sort_mode
from locust_tpu.engine import MapReduceEngine

G = 4  # blocks a full group holds in these tests (the budget below)
# The grouping mode of every benchmark cell; EngineConfig's own default
# ("hash") is what the rest of this file runs (ROADMAP Design item 2).
CHIP_MODE = default_sort_mode("tpu")


@pytest.fixture(autouse=True)
def _tracing_off():
    obs.disable()
    yield
    obs.disable()


@pytest.fixture
def group_of_four(monkeypatch):
    monkeypatch.setattr(MapReduceEngine, "TIMED_GROUP_BYTES", G * _BLOCK_BYTES)


def _spy_on_merge(eng) -> list[tuple[int, int]]:
    """Record (capacity, tables) of every merge program ``eng`` is asked for."""
    calls: list[tuple[int, int]] = []
    real = eng._merge

    def spy(acc, tables, seen):
        calls.append((acc.size, len(tables)))
        return real(acc, tables, seen)

    eng._merge = spy
    return calls


def _stage_merges(tracer):
    return [e["args"] for e in tracer.to_chrome()["traceEvents"]
            if e.get("ph") == "X" and e["name"] == "engine.stage.merge"]


@pytest.mark.parametrize(
    "sort_mode, combine, nblocks",
    [("hash", c, n) for c in ("sum", "min", "max", "count")
     for n in (1, 2, 3, G, G + 1, 2 * G + 3)]
    + [(CHIP_MODE, c, n) for c in ("sum", "min")
       for n in (1, G, G + 1, 2 * G + 3)],
)
def test_group_merge_gives_the_per_block_folds_table(
    group_of_four, sort_mode, combine, nblocks
):
    """Groups of 1, 2, 3, G, G + 1 (a padded last group of one) and two
    full groups and three blocks, values that differ from line to line, a
    vocabulary past the 128-row start in all but the one-block job: the
    table of ``run`` with room for every key, and the host's — under
    ``EngineConfig``'s default mode and under the mode the chip runs."""
    n_lines = 16 * nblocks
    # Five lines in eight are new keys, the rest repeat them at other values.
    lines = valued_lines(7 * -(-n_lines * 5 // 8), rounds=2)[:n_lines]
    assert len(lines) == n_lines
    want = valued_oracle(lines, combine)
    assert (len(want) > 128) == (nblocks > 1)
    shapes = dict(_SMALL, sort_mode=sort_mode)
    small = MapReduceEngine(EngineConfig(table_size=128, **shapes), valued_map, combine)
    roomy = MapReduceEngine(EngineConfig(table_size=4096, **shapes), valued_map, combine)
    rows = small.rows_from_lines(lines)
    got, ref = small.timed_run(rows), roomy.run(rows)
    assert not got.truncated and not ref.truncated
    assert got.num_segments == ref.num_segments == len(want)
    assert got.overflow_tokens == ref.overflow_tokens == 0
    assert got.to_host_pairs() == ref.to_host_pairs() == want


@pytest.mark.parametrize("sort_mode", ["hash", CHIP_MODE])
@pytest.mark.parametrize("first_group, to_rows", [(20, 1 << 18), (12, 1 << 17)])
def test_real_capacities_grow_in_one_step(monkeypatch, first_group, to_rows, sort_mode):
    """The CLI's own 65,536-row start (at smaller blocks).  A first group of 163,840 new keys
    passes two capacities at once: ONE step to 2^18 and one redone merge,
    where a chain of truncating merges took two steps and three merges.  A
    first group of 98,304 takes the one step to 2^17, and the second group
    the next.  The per-block fold in a table of 2^18 rows and the host
    agree on every row."""
    shapes = dict(block_lines=1024, line_width=64, key_width=8, emits_per_line=8,
                  sort_mode=sort_mode)
    block_bytes = 1024 * 64 + 3 * 8192 * (8 + 4 + 1)
    monkeypatch.setattr(MapReduceEngine, "TIMED_GROUP_BYTES", first_group * block_bytes)
    words = [b"k%06d" % i for i in range(170_000)]
    words += words[::3]                       # a third of them a second time
    lines = [b" ".join(words[i:i + 8]) for i in range(0, len(words), 8)]
    want = py_wordcount(lines, 8)
    assert 1 << 17 < len(want) <= 1 << 18
    tracer = obs.enable(process="real")
    eng = MapReduceEngine(EngineConfig(table_size=65_536, **shapes))
    calls = _spy_on_merge(eng)
    rows = eng.rows_from_lines(lines)
    res = eng.timed_run(rows)
    ref = MapReduceEngine(EngineConfig(table_size=1 << 18, **shapes)).run(rows)
    assert res.table.size == 1 << 18 and not res.truncated
    assert res.num_segments == ref.num_segments == len(want)
    assert res.to_host_pairs() == ref.to_host_pairs() == sorted(want.items())
    first = _grow_spans(tracer)[0]["args"]
    assert (first["from_rows"], first["to_rows"]) == (65_536, to_rows)
    assert first["blocks_redone"] == first_group
    assert first["distinct"] == first_group * 8192  # the TRUE count, not a bound
    # The first group: tried at 65,536, merged again at the capacity that holds it.
    assert calls[:2] == [(65_536, first_group), (to_rows, first_group)]
    assert _stage_merges(tracer)[0]["merges"] == 2
    assert obs.metrics_snapshot()["counters"]["engine.merges"] == len(calls)


def test_a_merge_past_the_capacity_never_returns_a_truncated_table(group_of_four):
    """Every group passes the table it finds: each is merged again from
    the table it started with, and no key or count is lost on the way."""
    lines = distinct_lines(16 * 8 * 9)         # 9 blocks, all keys distinct
    tracer = obs.enable(process="always")
    eng = MapReduceEngine(EngineConfig(table_size=128, **_SMALL))
    res = eng.timed_run(eng.rows_from_lines(lines))
    assert _table(res.to_host_pairs()) == _oracle(lines)
    assert res.num_segments == 16 * 8 * 9 and res.table.size == 2048
    steps = [(s["args"]["from_rows"], s["args"]["to_rows"], s["args"]["blocks_redone"])
             for s in _grow_spans(tracer)]
    # 512 keys, 1,024, then 1,152: the third group is grown AHEAD of.
    assert steps == [(128, 512, 4), (512, 1024, 4), (1024, 2048, 0)]
    assert [(m["blocks"], m["tables"], m["merges"]) for m in _stage_merges(tracer)] == [
        (4, 4, 2), (4, 4, 2), (1, 4, 1)]


# ------------------------------------------------------------ the fan-in ladder


@pytest.mark.parametrize("nblocks", list(range(1, G + 3)))
def test_jobs_of_any_size_share_the_ladders_merge_shapes(monkeypatch, nblocks):
    """Jobs of 1..G + 2 blocks under a budget of G = 5 blocks: a job of at
    least G blocks asks ``_merge`` for ONE shape (its short last group is
    padded), a shorter one for the next power of two."""
    monkeypatch.setattr(MapReduceEngine, "TIMED_GROUP_BYTES", 5 * _BLOCK_BYTES)
    lines = (distinct_lines(100) * 20)[:16 * nblocks]
    eng = MapReduceEngine(EngineConfig(table_size=128, **_SMALL))
    calls = _spy_on_merge(eng)
    res = eng.timed_run(eng.rows_from_lines(lines))
    assert _table(res.to_host_pairs()) == _oracle(lines)
    want = {1: 1, 2: 2, 3: 4, 4: 4}.get(nblocks, 5)
    assert set(calls) == {(128, want)}
    assert len(calls) == -(-nblocks // 5)      # one merge a group, none redone


@pytest.mark.parametrize("cfg_kw, full", [
    ({}, 41),                                  # CLI defaults
    ({"block_lines": 65536}, 2),               # big blocks
    (dict(table_size=128, **_SMALL), 66_930),  # toy blocks: a group of thousands
])
def test_the_ladder_has_at_most_seven_rungs(cfg_kw, full):
    """Every job size from one block to past two full groups: at most
    ``MERGE_RUNGS`` fan-ins in all, one for every job of a full group and
    more, never fewer tables than the group has blocks, and under twice
    the job's blocks at CLI defaults."""
    eng = MapReduceEngine(EngineConfig(**cfg_kw))
    sizes = list(range(0, 200)) + [full - 1, full, full + 1, 2 * full + 7]
    pairs = {n: eng._timed_group_blocks(n) for n in sizes}
    assert all(group == max(1, min(n, full)) for n, (group, _) in pairs.items())
    assert all(group <= fan_in <= full for group, fan_in in pairs.values())
    assert {fan_in for n, (_, fan_in) in pairs.items() if n >= full} == {full}
    fan_ins = {eng._timed_group_blocks(n)[1] for n in range(full + 2)}
    assert len(fan_ins) <= MapReduceEngine.MERGE_RUNGS == 7
    if not cfg_kw:
        assert fan_ins == {1, 2, 4, 8, 16, 32, 41}
        assert eng._timed_group_blocks(2) == (2, 2)          # ref4463.jobs
        assert eng._timed_group_blocks(470) == (41, 41)      # wc100.batch
        assert eng._timed_group_blocks(302) == (41, 41)      # wczipf.batch
        assert all(f < 2 * max(n, 1) for n, (_, f) in pairs.items())


# --------------------------------------- an iterator of blocks in place of rows


def _host_blocks(rows, bl=16):
    """``rows`` as ``io.loader.StreamingCorpus`` hands a file on: fresh
    arrays of ``bl`` rows in order, the last one short."""
    return (rows[i:i + bl].copy() for i in range(0, len(rows), bl))


def _threads_settle(before: int) -> bool:
    """Do the live threads come back to ``before`` within five seconds?"""
    deadline = time.time() + 5
    while threading.active_count() > before and time.time() < deadline:
        time.sleep(0.02)
    return threading.active_count() <= before


@pytest.mark.parametrize("sort_mode", ["hash", CHIP_MODE])
@pytest.mark.parametrize("n_lines", [
    0,                     # no line at all: one empty block, as an array of none
    16,                    # one block
    16 * 2 + 5,            # a short last block, inside the first group
    16 * (G - 1),          # the whole source read inline, one short of a group
    16 * G,                # exactly one group: the reader finds only the end
    16 * G + 1,            # a second group of one line
    16 * (2 * G + 3) + 7,  # three groups, the table grown on the way
])
def test_an_iterator_of_blocks_is_the_job_its_rows_are(
    group_of_four, n_lines, sort_mode
):
    """``timed_run(iterator of host blocks)`` against ``timed_run(rows)``
    on the same lines, pair for pair: table, ``num_segments``,
    ``overflow_tokens`` (every fourth line is two words over the cap),
    ``truncated``, the table's capacity; the SAME merge programs asked for
    in the same order (capacity, tables) and the same ``blocks`` /
    ``tables`` / ``merges`` on every merge stage — the group and the
    fan-in do not depend on how the corpus comes; and no reader thread
    left behind."""
    lines = valued_lines(7 * -(-max(n_lines, 8) * 5 // 8), rounds=2)[:n_lines]
    lines = [ln + b" x y z" * (i % 4 == 0) for i, ln in enumerate(lines)]
    shapes = dict(_SMALL, sort_mode=sort_mode)
    got = {}
    before = threading.active_count()
    for feed in ("rows", "blocks"):
        tracer = obs.enable(process=feed)
        eng = MapReduceEngine(EngineConfig(table_size=128, **shapes), valued_map)
        calls = _spy_on_merge(eng)
        rows = (eng.rows_from_lines(lines) if lines
                else np.zeros((0, _SMALL["line_width"]), np.uint8))
        res = eng.timed_run(rows if feed == "rows" else _host_blocks(rows))
        got[feed] = (
            res.to_host_pairs(), res.num_segments, res.overflow_tokens,
            res.truncated, res.table.size, calls,
            [(m["blocks"], m["tables"], m["merges"]) for m in _stage_merges(tracer)],
        )
        obs.disable()
    assert got["blocks"] == got["rows"]
    pairs, distinct, overflow, truncated, _, calls, _ = got["rows"]
    kept = [b" ".join(ln.split()[:8]) for ln in lines]  # the cap's eight emits
    assert pairs == valued_oracle(kept, "sum") and distinct == len(pairs)
    assert not truncated and (overflow > 0) == (n_lines > 0)
    assert (got["rows"][4] > 128) == (n_lines > 16)     # grown past its start
    nblocks = max(1, -(-n_lines // 16))
    assert {tables for _, tables in calls} == {
        MapReduceEngine(EngineConfig(**shapes))._timed_group_blocks(nblocks)[1]}
    assert _threads_settle(before)


def test_a_reader_that_raises_is_raised_by_the_job_and_leaves_no_thread(group_of_four):
    """A source that fails in its third group, while the reader thread is
    ahead of the device: the job raises THAT error (no table of half a
    file), and the thread is gone."""
    eng = MapReduceEngine(EngineConfig(table_size=128, **_SMALL))
    rows = eng.rows_from_lines(distinct_lines(16 * 8 * (2 * G + 2)))

    def failing():
        yield from _host_blocks(rows)
        raise OSError("disk on fire")

    before = threading.active_count()
    with pytest.raises(OSError, match="disk on fire"):
        eng.timed_run(failing())
    assert _threads_settle(before)


def test_a_job_that_fails_stops_its_reader(group_of_four):
    """The device side fails in the second group of a long source: the
    reader thread stops and the source is closed, far short of its end."""
    eng = MapReduceEngine(EngineConfig(table_size=4096, **_SMALL))
    block = eng.rows_from_lines(distinct_lines(16 * 8))
    state = {"yielded": 0, "closed": False}

    def source():
        try:
            for _ in range(1000):
                state["yielded"] += 1
                yield block.copy()
        finally:
            state["closed"] = True

    calls = _spy_on_merge(eng)
    real = eng._merge

    def merge(acc, tables, seen):
        if len(calls) == 1:
            raise RuntimeError("device lost")
        return real(acc, tables, seen)

    eng._merge = merge
    before = threading.active_count()
    with pytest.raises(RuntimeError, match="device lost"):
        eng.timed_run(source())
    assert _threads_settle(before)
    assert state["closed"] and state["yielded"] <= 3 * G + 2
