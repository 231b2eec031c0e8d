"""The default path's table grows with what it sees and stays exact.

``timed_run`` (the CLI's default path) starts at ``resolved_table_size``;
when a group's merge counted more distinct keys than the table holds, it
grows the table — in one step, geometric, to the capacity that holds them —
and merges that group again from the table the group started with
(``MapReduceEngine._regrow``; tests/test_group_merge.py has the merge).  Tolerance: none —
every table here is byte-equal to the ``py_wordcount`` oracle.  The flat
mesh's hash shards grow by the same rule, all together
(tests/test_mesh_growth.py); the other paths (``run``, ``run_fused``,
``--stream``, the hierarchical mesh, a mesh given an explicit
``shard_capacity``) keep a fixed table and their loud report
(tests/test_scale.py).
"""

import numpy as np
import pytest

from helpers import py_wordcount

from locust_tpu import cli, obs
from locust_tpu.config import EngineConfig
from locust_tpu.engine import MapReduceEngine

# 16 lines x 8 emits a block: a block's staged lines + three KVBatch.
_SMALL = dict(block_lines=16, line_width=64, key_width=8, emits_per_line=8)
_BLOCK_BYTES = 16 * 64 + 3 * (16 * 8) * (8 + 4 + 1)


@pytest.fixture(autouse=True)
def _tracing_off():
    obs.disable()
    yield
    obs.disable()


def zipf_lines(n_tokens: int, vocab: int, seed: int, per_line: int = 8) -> list[bytes]:
    """Zipf(1.1) ranks cut at ``vocab`` by the inverse CDF, 8 words a line."""
    rng = np.random.default_rng(seed)
    cdf = np.cumsum(np.arange(1, vocab + 1, dtype=np.float64) ** -1.1)
    ranks = np.searchsorted(cdf, rng.random(n_tokens) * cdf[-1]).clip(max=vocab - 1)
    words = [b"w%d" % r for r in ranks]
    return [b" ".join(words[i:i + per_line]) for i in range(0, n_tokens, per_line)]


def distinct_lines(n_keys: int, per_line: int = 8) -> list[bytes]:
    words = [b"k%05d" % i for i in range(n_keys)]
    return [b" ".join(words[i:i + per_line]) for i in range(0, n_keys, per_line)]


def _table(pairs) -> bytes:
    return b"".join(k + b"\t" + str(v).encode() + b"\n" for k, v in pairs)


def _oracle(lines, emits=8) -> bytes:
    return _table(sorted(py_wordcount(lines, emits).items()))


def _grow_spans(tracer):
    return [e for e in tracer.to_chrome()["traceEvents"]
            if e.get("ph") == "X" and e["name"] == "engine.table.grow"]


@pytest.mark.parametrize("feed", ["rows", "blocks"])
@pytest.mark.parametrize("times, n_tokens, min_steps", [(3, 1500, 2), (20, 16000, 5)])
def test_timed_run_is_exact_past_its_starting_capacity(monkeypatch, times, n_tokens,
                                                       min_steps, feed):
    """A Zipf text whose vocabulary passes the 128-row start 3x and 20x:
    two and more doubling steps, several groups, the oracle's table —
    from the rows as one array, and from an iterator of their blocks read
    a group ahead by a thread (the CLI's default path)."""
    monkeypatch.setattr(MapReduceEngine, "TIMED_GROUP_BYTES", 5 * _BLOCK_BYTES)
    lines = zipf_lines(n_tokens, 1 << 16, seed=times)
    want = py_wordcount(lines, 8)
    assert len(want) > times * 128
    tracer = obs.enable(process="grow")
    eng = MapReduceEngine(EngineConfig(table_size=128, **_SMALL))
    rows = eng.rows_from_lines(lines)
    res = eng.timed_run(rows if feed == "rows" else (
        rows[i:i + 16].copy() for i in range(0, len(rows), 16)))
    assert not res.truncated and res.num_segments == len(want)
    assert _table(res.to_host_pairs()) == _oracle(lines)
    steps = _grow_spans(tracer)
    metrics = obs.metrics_snapshot()
    assert len(steps) == metrics["counters"]["engine.table_grows"] >= min_steps
    assert metrics["gauges"]["engine.table_rows"] == res.table.size >= len(want)
    # Geometric from the start: 128 * 2^k, each step from where the last ended.
    assert [s["args"]["from_rows"] for s in steps] == (
        [128] + [s["args"]["to_rows"] for s in steps[:-1]])
    assert all(s["args"]["to_rows"] in [s["args"]["from_rows"] << k for k in range(1, 12)]
               for s in steps)
    assert steps[-1]["args"]["to_rows"] == res.table.size
    # The first group can only find out by merging, and is merged again;
    # a later group that would pass the table is grown for AHEAD of its merges.
    redone = [s["args"]["blocks_redone"] for s in steps]
    assert redone[0] > 0 and (times < 20 or 0 in redone)
    # A step lies inside a merge stage and holds its own wait.
    by_id = {e["args"]["id"]: e for e in tracer.to_chrome()["traceEvents"]
             if e.get("ph") == "X"}
    assert all(by_id[s["args"]["parent"]]["name"] == "engine.stage.merge" for s in steps)


@pytest.mark.parametrize("times, n_tokens", [(3, 70_000), (20, 500_000)])
def test_cli_default_path_prints_the_exact_table(tmp_path, capsysbinary, times, n_tokens):
    """``python -m locust_tpu FILE``: no flag sizes the table, the start is
    4,096 rows at these shapes, and the printed table is the oracle's with
    no word about truncation."""
    lines = zipf_lines(n_tokens, 1 << 20, seed=100 + times)
    assert len(py_wordcount(lines, 8)) > times * 4096
    path = tmp_path / "zipf.txt"
    path.write_bytes(b"\n".join(lines) + b"\n")
    rc = cli.main([str(path), "--backend", "cpu", "--block-lines", "512",
                   "--emits-per-line", "8"])
    got = capsysbinary.readouterr()
    assert rc == 0
    assert got.out == _oracle(lines)
    assert b"WARN" not in got.err and b"truncat" not in got.err


@pytest.mark.parametrize("over", [0, 1])
def test_landing_on_the_capacity_and_one_key_over(over):
    """Exactly 128 distinct keys fill the table and nothing grows; the
    129th key is one growth step."""
    lines = distinct_lines(128 + over) * 2
    obs.enable(process="edge")
    eng = MapReduceEngine(EngineConfig(table_size=128, **_SMALL))
    res = eng.timed_run(eng.rows_from_lines(lines))
    assert not res.truncated and res.num_segments == 128 + over
    assert _table(res.to_host_pairs()) == _oracle(lines)
    metrics = obs.metrics_snapshot()
    assert metrics["counters"]["engine.table_grows"] == over
    assert metrics["gauges"]["engine.table_rows"] == 128 * (1 + over)


def valued_map(block, cfg):
    """WordCount's map with value = the line's first byte, so equal keys
    carry other values in other lines."""
    import jax.numpy as jnp

    from locust_tpu.core.kv import KVBatch
    from locust_tpu.ops.map_stage import wordcount_map

    kv, overflow = wordcount_map(block, cfg)
    per_line = jnp.repeat(block[:, 0].astype(jnp.int32), cfg.emits_per_line)
    return KVBatch(kv.key_lanes, jnp.where(kv.valid, per_line, 0), kv.valid), overflow


def valued_lines(n_keys: int, rounds: int = 3) -> list[bytes]:
    """``rounds`` passes over ``n_keys`` keys, 7 a line behind a letter
    that differs from pass to pass: the value ``valued_map`` gives them."""
    keys = [b"k%04d" % i for i in range(n_keys)]
    return [bytes([65 + (i * 7 + j) % 26]) + b" " + b" ".join(keys[i:i + 7])
            for j in range(rounds) for i in range(0, n_keys, 7)]


def valued_oracle(lines, combine: str) -> list[tuple[bytes, int]]:
    fold = {"sum": sum, "min": min, "max": max, "count": len}[combine]
    seen: dict[bytes, list[int]] = {}
    for ln in lines:
        for tok in ln.split():
            seen.setdefault(tok, []).append(ln[0])
    return sorted((k, fold(v)) for k, v in seen.items())


@pytest.mark.parametrize("combine", ["sum", "min", "max", "count"])
def test_every_combine_survives_a_grow(monkeypatch, combine):
    """Values that differ a block: a group merged twice must not fold a
    block in twice (sum, count) nor lose its extreme (min, max)."""
    monkeypatch.setattr(MapReduceEngine, "TIMED_GROUP_BYTES", 3 * _BLOCK_BYTES)
    lines = valued_lines(700)
    eng = MapReduceEngine(EngineConfig(table_size=128, **_SMALL), valued_map, combine)
    res = eng.timed_run(eng.rows_from_lines(lines))
    assert res.table.size > 128 and not res.truncated
    assert res.to_host_pairs() == valued_oracle(lines, combine)


def test_a_group_merged_again_is_counted_once(monkeypatch):
    """Totals equal the oracle's token count, and the per-line cap's
    dropped tokens are counted once although their group ran twice.  One
    merge program a group of four blocks and one more a group redone: 38
    blocks are 10 merges and the redone ones, where they were 38 and four
    a group redone."""
    monkeypatch.setattr(MapReduceEngine, "TIMED_GROUP_BYTES", 4 * _BLOCK_BYTES)
    lines = zipf_lines(6000, 1 << 14, seed=9, per_line=10)  # 10 words, cap 8
    want = py_wordcount(lines, 8)
    tracer = obs.enable(process="once")
    eng = MapReduceEngine(EngineConfig(table_size=128, **_SMALL))
    res = eng.timed_run(eng.rows_from_lines(lines))
    pairs = res.to_host_pairs()
    assert res.table.size > 128
    assert sum(v for _, v in pairs) == sum(want.values()) == 8 * len(lines)
    assert res.overflow_tokens == 2 * len(lines)
    assert dict(pairs) == dict(want)
    redone = [s["args"]["blocks_redone"] for s in _grow_spans(tracer)]
    assert set(redone) <= {0, 4} and redone[0] == 4
    assert obs.metrics_snapshot()["counters"]["engine.merges"] == (
        10 + sum(1 for r in redone if r))


def test_under_the_capacity_nothing_grows_and_no_wait_is_added(monkeypatch):
    """A job that stays under its capacity: no ``engine.table.grow`` span,
    the table it started with, four waits a group plus the overflow read,
    and one merge program a group (three for five blocks, the last group's
    missing table an empty one) — no wait more than before it could grow."""
    monkeypatch.setattr(MapReduceEngine, "TIMED_GROUP_BYTES", 2 * _BLOCK_BYTES)
    lines = distinct_lines(100) * 5            # 65 lines: 5 blocks, 3 groups
    tracer = obs.enable(process="steady")
    eng = MapReduceEngine(EngineConfig(table_size=128, **_SMALL))
    res = eng.timed_run(eng.rows_from_lines(lines))
    assert res.table.size == 128 and res.num_segments == 100
    spans = [e for e in tracer.to_chrome()["traceEvents"] if e.get("ph") == "X"]
    assert not _grow_spans(tracer)
    assert [e["args"]["what"] for e in spans if e["name"] == "engine.sync"] == (
        ["map", "process", "reduce", "merge"] * 3 + ["overflow"])
    assert [(e["args"]["blocks"], e["args"]["tables"], e["args"]["merges"])
            for e in spans if e["name"] == "engine.stage.merge"] == [
        (2, 2, 1), (2, 2, 1), (1, 2, 1)]
    counters = obs.metrics_snapshot()["counters"]
    assert counters["engine.table_grows"] == 0 and counters["engine.merges"] == 3
    assert _table(res.to_host_pairs()) == _oracle(lines)


def test_stream_past_its_capacity_still_says_so(tmp_path, capsys):
    """``--stream`` holds a table of fixed size: past it the CLI prints
    the WARN line and a truncated table, as before."""
    lines = distinct_lines(4096 + 300)
    path = tmp_path / "wide.txt"
    path.write_bytes(b"\n".join(lines) + b"\n")
    rc = cli.main([str(path), "--stream", "--backend", "cpu", "--block-lines", "512",
                   "--emits-per-line", "8"])
    got = capsys.readouterr()
    assert rc == 0
    assert "[locust] WARN: table capacity exceeded; tail keys dropped" in got.err
    assert len(got.out.splitlines()) == 4096
