"""The CLI's table goes from the device to stdout as arrays (PR 40).

``KVBatch.host_rows`` is the numpy half of ``host_pairs``;
``bytes_ops.render_rows`` prints ordered rows with no Python object a
row; ``engine.finalize_host_rows`` hands the CLI those rows where three
checks over whole arrays pass and the pairs' way where one fails.
Tolerance: none — every table here is byte-equal to the row-at-a-time
rendering of ``finalize_host_pairs``, the loop kept as the reference.
"""

import functools
import json
import logging

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import yardstick
from test_table_growth import zipf_lines

from locust_tpu import cli, obs
from locust_tpu.core import bytes_ops
from locust_tpu.core.kv import HostRows, KVBatch
from locust_tpu.engine import (
    RunResult,
    StageTimes,
    finalize_host_pairs,
    finalize_host_rows,
)
from locust_tpu.plan.compile import _render


@pytest.fixture(autouse=True)
def _tracing_off():
    obs.disable()
    yield
    obs.disable()


def _spans_of(doc):
    return [e for e in doc["traceEvents"] if e.get("ph") == "X"]


def _spans(tracer):
    return _spans_of(tracer.to_chrome())


def _table(keys, values, valid=None, key_width=8) -> KVBatch:
    """A device table with ``keys`` in the rows given, in that order."""
    n = len(keys)
    return KVBatch.from_bytes(
        jnp.asarray(bytes_ops.strings_to_rows(keys, key_width)),
        jnp.asarray(np.asarray(values, np.int32).reshape(n)),
        jnp.asarray(np.ones(n, bool) if valid is None else np.asarray(valid, bool)),
    )


def _want(table: KVBatch, combine: str = "sum") -> bytes:
    """Today's bytes: sorted pairs joined a row at a time."""
    return _render("table", finalize_host_pairs(table, combine))


def _printed(rows, limit, capsysbinary) -> bytes:
    cli._print_table(rows, limit)
    return capsysbinary.readouterr().out


def _fuzzed(seed: int, key_width: int, n: int):
    """Distinct keys of 1..key_width bytes over every byte but NUL, hash-
    scattered over a table twice their number with dead rows between."""
    rng = np.random.default_rng(seed)
    keys = set()
    while len(keys) < n:
        size = int(rng.integers(1, key_width + 1))
        keys.add(rng.integers(1, 256, size, dtype=np.uint8).tobytes())
    keys = sorted(keys)
    values = rng.choice(
        [0, 1, 1, 1, 2, 9, 10, 99, 100, 12345, 2**31 - 1], n).tolist()
    slots = rng.permutation(2 * n)[:n]
    table_keys, table_values = [b""] * (2 * n), [7] * (2 * n)
    valid = np.zeros(2 * n, bool)
    for slot, k, v in zip(slots, keys, values):
        table_keys[slot], table_values[slot], valid[slot] = k, v, True
    return table_keys, table_values, valid


_FAST = {
    # keys, values, valid (None: every row live), key_width
    "an empty table": ([], [], None, 8),
    "nothing live": ([b"x", b"y"], [1, 2], [0, 0], 8),
    "one row": ([b"solo"], [1], None, 8),
    "keys of the full width, no NUL at all": (
        [b"abcdefgh", b"abcdefgg", b"zzzzzzzz"], [3, 10, 1], None, 8),
    "keys that are prefixes of each other": (
        [b"abc", b"a", b"abcd", b"ab", b"abcde"], [1, 2, 3, 4, 5], None, 8),
    "bytes from 0x80 up": (
        [b"\xff\x80", b"\x7f", b"\x80", b"caf\xc3\xa9", b"\xfe\xff"],
        [5, 4, 3, 2, 1], None, 8),
    "values 0, 9, 10, 2^31 - 1": (
        [b"d", b"c", b"b", b"a"], [0, 9, 10, 2**31 - 1], None, 8),
    "every number of digits": (
        [b"k%02d" % i for i in range(10)], [10**i - (i > 0) for i in range(10)],
        None, 8),
    "a TAB and a newline inside keys": ([b"a\tb", b"a\nb", b"a"], [1, 2, 3], None, 8),
    "the empty key among others": ([b"b", b"", b"a"], [1, 2, 3], None, 8),
    "32-byte keys": (
        [b"q" * 32, b"q" * 31, b"p" * 32, b"supercalifragilistic"], [1, 22, 333, 4],
        None, 32),
    "fuzzed, 8 bytes wide": (*_fuzzed(8, 8, 300), 8),
    "fuzzed, 32 bytes wide": (*_fuzzed(32, 32, 500), 32),
    "fuzzed, 32 bytes wide, another seed": (*_fuzzed(2147483649, 32, 257), 32),
}


@pytest.mark.parametrize("case", sorted(_FAST))
def test_rows_rendered_in_numpy_are_the_bytes_of_the_pair_loop(case, capsysbinary):
    keys, values, valid, width = _FAST[case]
    table = _table(keys, values, valid, width)
    want = _want(table)
    tracer = obs.enable(process="rows")
    rows = finalize_host_rows(table)
    assert isinstance(rows, HostRows) and rows.keys.shape == (len(rows), width)
    assert rows.keys.dtype == np.uint8 and rows.values.dtype == np.int32
    assert bytes_ops.render_rows(rows.keys, rows.values) == want
    assert _printed(rows, None, capsysbinary) == want
    order = [e for e in _spans(tracer) if e["name"] == "engine.finalize.order"]
    assert [e["args"]["fast"] for e in order] == [1]
    assert "reason" not in order[0]["args"] and order[0]["args"]["merged"] == 0
    [render] = [e for e in _spans(tracer) if e["name"] == "cli.output.render"]
    assert render["args"]["fast"] == 1
    assert render["args"]["rows"] == len(rows) == (
        len(keys) if valid is None else int(np.sum(valid)))
    # The pairs everyone else takes are these rows, decoded.
    assert rows.pairs() == finalize_host_pairs(table)
    assert table.to_host().host_rows(sort=True).pairs() == table.to_host_pairs(sort=True)
    assert table.to_host().host_rows().pairs() == table.to_host_pairs()


_FALL_BACK = {
    # keys, values, combine, the reason
    "a NUL inside a key": ([b"ab\0z", b"zz", b"a"], [1, 2, 3], "sum", "nul"),
    "a NUL inside keys, cut to one key": (
        [b"ab\0z", b"ab", b"ab\0y", b"a"], [1, 2, 4, 8], "sum", "nul"),
    "a key that starts with a NUL": ([b"\0ab", b"b"], [1, 2], "sum", "nul"),
    "two rows of one key under sum": (
        [b"dup", b"solo", b"dup", b"dup"], [5, 1, 7, 30], "sum", "duplicate"),
    "two rows of one key under max": (
        [b"dup", b"solo", b"dup"], [5, 1, 7], "max", "duplicate"),
    "a negative value": ([b"pear", b"apple", b"fig"], [3, -1, 2], "min", "negative"),
    "int32 wrapped round": ([b"big", b"small"], [-2**31, 1], "sum", "negative"),
}


@pytest.mark.parametrize("case", sorted(_FALL_BACK))
def test_a_table_numpy_cannot_print_takes_the_pairs_way_and_says_so(
        case, capsysbinary, caplog):
    keys, values, combine, reason = _FALL_BACK[case]
    table = _table(keys, values)
    pairs = finalize_host_pairs(table, combine)
    want = _render("table", pairs)
    tracer = obs.enable(process="fallback")
    with caplog.at_level(logging.INFO, logger="locust_tpu"):
        got = finalize_host_rows(table, combine)
    assert got == pairs  # merged and sorted
    assert _printed(got, None, capsysbinary) == want
    assert reason in caplog.text
    spans = _spans(tracer)
    [order] = [e for e in spans if e["name"] == "engine.finalize.order"]
    assert order["args"]["fast"] == 0 and order["args"]["reason"] == reason
    assert order["args"]["merged"] == int(case.startswith(("two rows", "a NUL inside keys")))
    [render] = [e for e in spans if e["name"] == "cli.output.render"]
    assert render["args"]["fast"] == 0 and render["args"]["rows"] == want.count(b"\n")
    # The blocker is named from the ordered rows alone.
    rows = table.to_host().host_rows(sort=True)
    assert bytes_ops.render_blocker(rows.keys, rows.values) == reason


@pytest.mark.parametrize("limit", [None, 0, 3, 99])
@pytest.mark.parametrize("fast", [True, False], ids=["rows", "pairs"])
def test_limit_cuts_the_ordered_table_before_it_is_rendered(fast, limit, capsysbinary):
    keys = [b"fig", b"apple", b"pear", b"kiwi", b"date"]
    table = _table(keys, [1, 20, 300, -4 if not fast else 4, 5])
    got = finalize_host_rows(table, "min")
    assert isinstance(got, HostRows) == fast
    lines = _want(table, "min").splitlines(keepends=True)
    want = b"".join(lines[:limit])
    tracer = obs.enable(process="limit")
    assert _printed(got, limit, capsysbinary) == want
    render, write = sorted(_spans(tracer), key=lambda e: e["ts"])
    assert render["args"] == {**render["args"], "rows": len(lines[:limit]), "fast": int(fast)}
    assert write["args"]["bytes"] == len(want)


@pytest.mark.parametrize("method", ["to_host_rows", "to_host_pairs"])
def test_a_result_hands_out_rows_or_pairs_under_one_parent(method):
    """``RunResult.to_host_rows`` beside ``to_host_pairs``: the same
    ``engine.finalize`` parent, the same three children, rows or pairs."""
    keys, values, valid = _fuzzed(40, 8, 64)
    table = _table(keys, values, valid)
    res = RunResult(table, 64, 0, False, StageTimes())
    want = finalize_host_pairs(table)
    tracer = obs.enable(process="result")
    got = getattr(res, method)()
    assert (got.pairs() if method == "to_host_rows" else got) == want
    parent, d2h, decode, order = sorted(_spans(tracer), key=lambda e: e["ts"])
    assert [e["name"] for e in (parent, d2h, decode, order)] == [
        "engine.finalize", "engine.finalize.d2h", "engine.finalize.decode",
        "engine.finalize.order"]
    assert {e["args"]["parent"] for e in (d2h, decode, order)} == {parent["args"]["id"]}
    assert ("fast" in order["args"]) == (method == "to_host_rows")


_SHAPES = ["--backend", "cpu", "--block-lines", "64", "--emits-per-line", "8",
           "--key-width", "8", "--line-width", "64"]
_CLI_PATHS = {
    # flags, the Zipf text's tokens and vocabulary, what stderr says of growth
    "default": ([], 24_000, 1 << 16, None),  # 4,253 words: the 4,096-row table grows
    "stream": (["--stream"], 24_000, 1 << 11, None),  # a table of fixed size holds it
    "no-timing": (["--no-timing"], 24_000, 1 << 11, None),
    "mesh4": (["--mesh"], 60_000, 1 << 20, b"shards grew"),
}


@pytest.mark.parametrize("path", sorted(_CLI_PATHS))
def test_cli_prints_the_oracles_bytes_from_rows(path, tmp_path, capsysbinary,
                                                monkeypatch):
    """End to end against the benchmark's oracle on a Zipf text — one that
    grows the default path's table, and on four virtual devices the
    mesh's shards; every job renders from rows (``fast`` 1 on both
    spans), and ``--limit`` cuts the same ordered table."""
    from locust_tpu.parallel import mesh

    monkeypatch.setattr(mesh, "make_mesh", functools.partial(mesh.make_mesh, 4))
    flags, n_tokens, vocab, said = _CLI_PATHS[path]
    corpus = tmp_path / "zipf.txt"
    corpus.write_bytes(b"\n".join(zipf_lines(n_tokens, vocab, seed=40)) + b"\n")
    want = yardstick.oracle_table(str(corpus))
    argv = [str(corpus), *_SHAPES, *flags]
    trace = tmp_path / "t.trace.json"
    assert cli.main([*argv, "--trace-out", str(trace)]) == 0
    got = capsysbinary.readouterr()
    assert got.out == want and want.count(b"\n") > 1500
    assert b"WARN" not in got.err and (said is None or said in got.err)
    if "--mesh" in flags:
        assert b"mesh: 4 device(s)" in got.err
    with open(trace) as f:
        spans = _spans_of(json.load(f))
    for name in ("engine.finalize.order", "cli.output.render"):
        assert [e["args"]["fast"] for e in spans if e["name"] == name] == [1]
    [render] = [e for e in spans if e["name"] == "cli.output.render"]
    assert render["args"]["rows"] == want.count(b"\n")
    if path == "default":
        assert [e for e in spans if e["name"] == "engine.table.grow"]
    for limit in (0, 3, 100_000):
        assert cli.main([*argv, "--limit", str(limit)]) == 0
        assert capsysbinary.readouterr().out == b"".join(
            want.splitlines(keepends=True)[:limit])


def test_cli_reduce_stage_prints_negative_counts_the_pairs_way(tmp_path, capsysbinary):
    """The reduce stage's final table goes through the same finalize: an
    intermediate file with a negative count is printed with its sign,
    from pairs (``fast`` 0, ``negative``); one without, from rows."""
    argv = ["ignored.txt", "-1", "-1", "0", "2", *_SHAPES]
    for body, want, fast in [
        (b"zebra\t1\napple\t2\nzebra\t-3\nmid\t5\n", b"apple\t2\nmid\t5\nzebra\t-2\n", 0),
        (b"zebra\t1\napple\t2\nzebra\t3\nmid\t5\n", b"apple\t2\nmid\t5\nzebra\t4\n", 1),
    ]:
        inter, trace = tmp_path / f"x{fast}.tsv", tmp_path / f"t{fast}.trace.json"
        inter.write_bytes(body)
        assert cli.main([*argv, "-i", str(inter), "--trace-out", str(trace)]) == 0
        assert capsysbinary.readouterr().out == want
        with open(trace) as f:
            spans = _spans_of(json.load(f))
        [order] = [e for e in spans if e["name"] == "engine.finalize.order"]
        [render] = [e for e in spans if e["name"] == "cli.output.render"]
        assert order["args"]["fast"] == render["args"]["fast"] == fast
        assert order["args"].get("reason") == (None if fast else "negative")
