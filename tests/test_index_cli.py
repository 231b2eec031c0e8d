"""``python -m locust_tpu index`` held to the plain reference (PR 45).

At CPU size — seeded Zipf text from the benchmark's own generator, at the
vocabulary law and line shape of ``index-zipf-100MB``: the CLI's stdout
byte-equal to ``locust_tpu/index_reference.py`` (Python sets, no jax) for
several document sizes, across block boundaries, with documents that span
two blocks, with more distinct pairs than the fixed table the parent held
(the case that raised); the array renderer against the dict renderer; a
forced 64-bit hash collision; the control (``--emits-per-line 8``) and the
other two cuts said aloud on stderr; and the spans and counters a
``--trace-out`` file of an index job holds.
"""

import json
import os
import re
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from locust_tpu import cli, index_reference
from locust_tpu.apps import inverted_index
from locust_tpu.apps.inverted_index import Postings, build_index, build_inverted_index
from locust_tpu.config import FULL_DELIMITERS, EngineConfig
from locust_tpu.core import bytes_ops, packing
from locust_tpu.plan import compile as plan_compile
from locust_tpu.plan import compile_plan, index_plan

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

import zipf_text  # noqa: E402

with open(os.path.join(REPO, "benchmarks", "configs", "index-zipf-100MB.json")) as _f:
    CONFIG = json.load(_f)
GENERATOR = {k: v for k, v in CONFIG["generator"].items() if k != "module"}
# What yardstick.BAD_STDERR holds every CLI job to: a drop must match it.
BAD_STDERR = re.compile(r"overflow=[1-9]|truncated=True|\[locust\] WARN")
LINES = 3000
ARGV = ["--block-lines", "256", "--backend", "cpu"]


@pytest.fixture(scope="module")
def text(tmp_path_factory):
    """(path, lines) of 3,000 lines of the configuration's text."""
    path = str(tmp_path_factory.mktemp("zipf") / "text.txt")
    zipf_text.build(path, LINES, 2147483659, **GENERATOR)
    return path, index_reference.file_lines(path)


def run_index(capsysbinary, path, *argv):
    capsysbinary.readouterr()
    rc = cli.main(["index", path, *argv])
    got = capsysbinary.readouterr()
    return rc, got.out, got.err.decode()


def test_the_reference_splits_as_the_program_does():
    assert index_reference.DELIMITERS == FULL_DELIMITERS
    assert index_reference.inverted_index([b"b a,a", b"", b"c-b"], 2) == {
        b"a": [0], b"b": [0, 1], b"c": [1]}


def test_reference_render_and_parse_are_inverse(text):
    _, lines = text
    index = index_reference.inverted_index(lines, 64)
    table = index_reference.render(index)
    words, offsets, postings = index_reference.parse(table)
    assert words == sorted(index)
    assert [postings[offsets[i]:offsets[i + 1]].tolist() for i in range(len(words))] == [
        index[w] for w in words]
    with pytest.raises(ValueError):
        index_reference.parse(b"word 1,2\n")


# 3,000 lines in blocks of 256: eleven full blocks and one of 184 lines.  A
# document of 3 lines spans a block boundary at every block's end (256 = 85 x
# 3 + 1), one of 64 never does, one of 1 is a line.
@pytest.mark.parametrize("lines_per_doc", [1, 3, 64])
def test_cli_stdout_is_the_reference_render(text, capsysbinary, lines_per_doc):
    path, lines = text
    rc, out, err = run_index(capsysbinary, path, "--lines-per-doc", str(lines_per_doc), *ARGV)
    assert rc == 0
    want = index_reference.inverted_index(lines, lines_per_doc)
    assert out == index_reference.render(want)
    said = re.search(r"\[locust\] index: words=(\d+) pairs=(\d+) docs=(\d+) "
                     r"emit_overflow=0 key_overflow=0 line_overflow=0 truncated=False", err)
    assert said, err
    assert [int(g) for g in said.groups()] == [
        len(want), sum(map(len, want.values())), -(-LINES // lines_per_doc)]
    assert not BAD_STDERR.search(err)


def test_a_word_in_every_document_and_a_word_in_one(text):
    _, lines = text
    index = index_reference.inverted_index(lines, 64)
    n_docs = -(-LINES // 64)
    assert any(len(docs) == n_docs for docs in index.values())   # the commonest words
    assert sum(len(docs) == 1 for docs in index.values()) > 1000  # the tail
    got = build_inverted_index(
        bytes_ops.strings_to_rows(lines, 128), np.arange(LINES) // 64,
        EngineConfig(block_lines=256))
    assert got == index


def test_more_pairs_than_the_fixed_table_held(text):
    """The parent carried ``default_pairs_capacity`` rows and raised past
    them; the store grows instead, a step a group at this size."""
    _, lines = text
    cfg = EngineConfig(block_lines=16)
    fixed = inverted_index.default_pairs_capacity(cfg)
    rows = bytes_ops.strings_to_rows(lines, cfg.line_width)
    index = build_index(rows, np.arange(LINES), cfg)
    assert index.postings.shape[0] > 4 * fixed
    assert index.grows >= 2 and index.store_rows >= index.postings.shape[0]
    assert index.to_dict() == index_reference.inverted_index(lines, 1)
    assert index.offsets[-1] == index.postings.shape[0] and len(index) == index.words.shape[0]


def test_pairs_capacity_is_a_limit_a_caller_may_set(text):
    _, lines = text
    cfg = EngineConfig(block_lines=256)
    with pytest.raises(ValueError, match="pairs_capacity"):
        build_inverted_index(lines[:200], np.arange(200), cfg, pairs_capacity=100)
    assert len(build_inverted_index(lines[:200], np.arange(200), cfg, pairs_capacity=10**6)) > 100


def test_a_document_that_spans_blocks_is_listed_once():
    """The cross-block duplicate: the same (word, doc) from two blocks."""
    cfg = EngineConfig(block_lines=2, line_width=64, emits_per_line=4)
    got = build_inverted_index(
        [b"a b", b"b c", b"a c", b"c d", b"a"], np.asarray([0, 0, 0, 0, 0]), cfg)
    assert got == {b"a": [0], b"b": [0], b"c": [0], b"d": [0]}


def test_a_forced_hash_collision_still_yields_two_words(monkeypatch):
    """Every key hashes alike: the in-block sort then groups nothing, and
    the collect — which orders by the key itself — must not care."""
    import jax.numpy as jnp

    def constant(lanes):
        h = jnp.zeros(lanes.shape[:-1], jnp.uint32)
        return h, h

    monkeypatch.setattr(packing, "hash_pair", constant)
    cfg = EngineConfig(block_lines=4, line_width=64, emits_per_line=4)
    lines = [b"x y x", b"y x", b"z x y", b"y", b"x z"]
    docs = np.asarray([0, 0, 1, 2, 2])
    want = {b"x": [0, 1, 2], b"y": [0, 1, 2], b"z": [1, 2]}
    assert build_inverted_index(lines, docs, cfg) == want


def _constant_hash(lanes):
    h = jnp.zeros(lanes.shape[:-1], jnp.uint32)
    return h, h


def _key_length_parity_hash(lanes):
    """Two hash runs: the words of an even and of an odd number of bytes."""
    h = jnp.sum(packing.unpack_keys(lanes) != 0, axis=-1).astype(jnp.uint32) % 2
    return h, h


@pytest.mark.parametrize("hash_pair", [_constant_hash, _key_length_parity_hash, None],
                         ids=["constant", "key-length-parity", "true"])
def test_colliding_hashes_split_words_and_the_cut_folds_them_back(
        text, monkeypatch, hash_pair):
    """The collect groups the store by ``hash_pair`` and compares full keys
    only between neighbours, so words that share a hash and alternate by
    doc id are cut into pieces; the pieces meet again where the entries
    are ordered by their bytes.  Several blocks, documents cut across
    them, a store that grows: the reference byte for byte under any hash,
    and ``index.hash_splits`` says how often a word was cut."""
    from locust_tpu import obs

    if hash_pair is not None:
        monkeypatch.setattr(packing, "hash_pair", hash_pair)
    _, lines = text
    cfg = EngineConfig(block_lines=40)
    docs = np.arange(LINES) // 64  # 40 lines a block: most documents span two
    obs.disable()
    obs.enable(process="collisions")
    index = build_index(bytes_ops.strings_to_rows(lines, cfg.line_width), docs, cfg)
    counters = obs.metrics_snapshot()["counters"]
    obs.disable()
    assert index.grows >= 1 and LINES // cfg.block_lines > 2 * inverted_index.COLLECT_GROUP_BLOCKS
    want = index_reference.inverted_index(lines, 64)
    assert plan_compile.render_postings(index) == index_reference.render(want)
    assert counters["index.words"] == len(want)
    assert (counters["index.hash_splits"] > 0) == (hash_pair is not None)


@pytest.mark.parametrize("hash_pair", [_constant_hash, None], ids=["constant", "true"])
def test_a_word_of_0xff_bytes_stands_last_beside_the_empty_slots(monkeypatch, hash_pair):
    """The cut orders ``rows=`` slots of which the entries fill the head;
    an empty slot takes the largest key there is, and a word may have it."""
    if hash_pair is not None:
        monkeypatch.setattr(packing, "hash_pair", hash_pair)
    cfg = EngineConfig(block_lines=2, line_width=32, key_width=4, emits_per_line=4)
    ff = b"\xff" * 4
    lines = [ff + b" a", b"b " + ff, b"a", ff + b"\xff z", b""]
    index = build_index(lines, np.asarray([0, 1, 1, 2, 3]), cfg)
    assert index.to_dict() == {b"a": [0, 1], b"b": [1], b"z": [2], ff: [0, 1, 2]}
    assert list(index.to_dict()) == [b"a", b"b", b"z", ff] and index.cut_keys == 1


def _dict_render(index: dict) -> bytes:
    return b"".join(plan_compile.iter_rendered("postings", index))


@pytest.mark.parametrize("top", [0, 7, 10, 99, 100, 12345, 999_999, 1_000_000, 9_999_999])
def test_array_renderer_equals_the_dict_renderer(top):
    """Doc ids of 1 to 7 digits, words of 1 to 32 bytes."""
    rng = np.random.default_rng(top)
    words = sorted({bytes(rng.integers(97, 123, size=int(n)).astype(np.uint8))
                    for n in rng.integers(1, 33, size=300)} | {b"a" * 32, b"z"})
    counts = rng.integers(1, 40, size=len(words))
    index = {w: sorted({int(d) for d in rng.integers(0, top + 1, size=c)} | {top})
             for w, c in zip(words, counts)}
    index[words[0]] = [0] if top else index[words[0]]
    csr = Postings(
        words=bytes_ops.strings_to_rows(words, 32),
        offsets=np.concatenate([[0], np.cumsum([len(index[w]) for w in words])]),
        postings=np.concatenate([index[w] for w in words]).astype(np.int32))
    assert csr.to_dict() == index
    assert bytes_ops.render_postings(csr.words, csr.offsets, csr.postings) == _dict_render(index)
    assert plan_compile.render_postings(csr) == _dict_render(index)
    assert plan_compile.render_postings(csr, limit=5) == _dict_render(
        {w: index[w] for w in words[:5]})
    assert plan_compile.render_postings(csr, limit=10**6) == _dict_render(index)


def test_array_renderer_leaves_what_it_cannot_spell_to_the_dict():
    words = bytes_ops.strings_to_rows([b"a", b"b"], 8)
    assert bytes_ops.render_postings(words[:0], np.zeros(1, np.int64), np.zeros(0, np.int32)) == b""
    negative = Postings(words, np.asarray([0, 1, 2]), np.asarray([-3, 4], np.int32))
    assert bytes_ops.render_postings(negative.words, negative.offsets, negative.postings) is None
    assert plan_compile.render_postings(negative) == b"a\t-3\nb\t4\n"
    assert bytes_ops.render_postings(words, np.asarray([0, 0, 1]), np.asarray([1], np.int32)) is None


def test_limit_prints_the_first_words(text, capsysbinary):
    path, lines = text
    rc, out, _ = run_index(capsysbinary, path, "--lines-per-doc", "64", "--limit", "17", *ARGV)
    want = index_reference.render(index_reference.inverted_index(lines, 64))
    assert rc == 0 and out == b"".join(want.splitlines(keepends=True)[:17])


def test_the_control_says_what_it_dropped_and_differs(text, capsysbinary):
    """``--emits-per-line 8``: lines hold up to 20 words, so postings go
    missing; the CLI must say so in words a driver reads, and the table
    must differ from the reference's."""
    path, lines = text
    rc, out, err = run_index(capsysbinary, path, "--lines-per-doc", "64",
                             "--emits-per-line", "8", *ARGV)
    dropped = sum(max(0, len([w for w in index_reference._SPLIT.split(ln) if w]) - 8)
                  for ln in lines)
    assert rc == 0 and dropped > 0
    assert f"emit_overflow={dropped} " in err and BAD_STDERR.search(err)
    assert "MISSING" in err or "no posting" in err
    assert out != index_reference.render(index_reference.inverted_index(lines, 64))


def test_cut_keys_and_cut_lines_are_counted(tmp_path, capsysbinary):
    long_word = b"k" * 40
    lines = [b"short " + long_word, long_word + b" " + long_word, b"x" * 10 + b" " + b"y " * 100,
             b"fits", b"w" * 32]
    path = tmp_path / "cuts.txt"
    path.write_bytes(b"\n".join(lines) + b"\r\n")
    rc, out, err = run_index(capsysbinary, str(path), "--block-lines", "4", "--backend", "cpu")
    assert rc == 0
    # three keys of 40 bytes are cut at 32; line 2 (211 bytes) at 128, which
    # leaves it 60 of its 101 words, of which the first 20 are emitted
    assert "key_overflow=3 " in err and "line_overflow=1 " in err
    assert re.search(r"emit_overflow=40 ", err), err
    assert BAD_STDERR.search(err)
    assert out.startswith(b"fits\t3\n" + b"k" * 32 + b"\t0,1\n")
    assert (b"w" * 32 + b"\t4\n") in out


def test_mesh_index_prints_the_same_table(text, capsysbinary):
    path, lines = text
    rc, out, _ = run_index(capsysbinary, path, "--lines-per-doc", "64", "--mesh",
                           "--limit", "400", "--block-lines", "128", "--backend", "cpu")
    want = index_reference.render(index_reference.inverted_index(lines, 64))
    assert rc == 0 and out == b"".join(want.splitlines(keepends=True)[:400])


def test_plan_values_dict_by_default_arrays_for_the_cli(text):
    _, lines = text
    cfg = EngineConfig(block_lines=256)
    plan = compile_plan(index_plan(64), cfg)
    rows = bytes_ops.strings_to_rows(lines, cfg.line_width)
    res = plan.run(rows)
    want = index_reference.inverted_index(lines, 64)
    assert res.value == want and res.output == index_reference.render(want)
    assert res.distinct == len(want) and res.overflow_tokens == 0
    raw = plan.run(rows, render=False, finalize=False)
    assert isinstance(raw.value, Postings) and raw.output is None
    assert plan_compile.render_postings(raw.value) == res.output


def test_trace_holds_the_index_spans_and_counters(text, tmp_path, capsysbinary):
    path, lines = text
    trace = tmp_path / "t.json"
    rc, _, _ = run_index(capsysbinary, path, "--block-lines", "64",  # a document a line
                         "--backend", "cpu", "--trace-out", str(trace))
    assert rc == 0
    doc = json.loads(trace.read_text())
    spans = [e["name"] for e in doc["traceEvents"] if e.get("ph") == "X"]
    blocks = -(-LINES // 64)
    groups = -(-blocks // inverted_index.COLLECT_GROUP_BLOCKS)
    for name, count in [("cli.setup", 1), ("cli.load", 1), ("index.read", 1), ("cli.run", 1),
                        ("index.h2d", blocks), ("index.map", groups), ("index.collect", 1),
                        ("index.d2h", 1), ("cli.output", 1), ("index.render", 1),
                        ("index.write", 1),
                        # a fill read a later group, the entries' count, the collect
                        ("engine.sync", groups + 1)]:
        assert spans.count(name) == count, (name, spans.count(name))
    by_id = {e["args"]["id"]: e for e in doc["traceEvents"] if e.get("ph") == "X"}
    waits = [e["args"]["what"] for e in by_id.values() if e["name"] == "engine.sync"
             and by_id[e["args"]["parent"]]["name"] == "index.collect"]
    assert waits == ["index.entries", "index.collect"]
    counters = doc["otherData"]["metrics"]["counters"]
    want = index_reference.inverted_index(lines, 1)
    assert counters["index.words"] == len(want)
    assert counters["index.hash_splits"] == 0
    assert counters["index.pairs"] == sum(map(len, want.values()))
    assert counters["index.docs"] == LINES
    assert counters["index.dropped_tokens"] == 0
    assert counters["index.grows"] == spans.count("index.grow") >= 1
