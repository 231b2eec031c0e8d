"""CLI contract tests: single mode, staged map/reduce, robust args (Q9)."""

import numpy as np
import pytest

from helpers import py_wordcount

from locust_tpu import cli


CORPUS = b"""to be or not to be
that is the question
to be, to sleep
"""


@pytest.fixture
def corpus_file(tmp_path):
    p = tmp_path / "in.txt"
    p.write_bytes(CORPUS)
    return str(p)


def _cfg_args():
    return ["--block-lines", "8", "--line-width", "64", "--emits-per-line", "8"]


def _parse_table(out: bytes) -> dict[bytes, int]:
    table = {}
    for line in out.splitlines():
        if not line:
            continue
        k, _, v = line.partition(b"\t")
        table[k] = int(v)
    return table


def test_cli_single_mode(corpus_file, capsysbinary):
    rc = cli.main([corpus_file] + _cfg_args())
    assert rc == 0
    got = _parse_table(capsysbinary.readouterr().out)
    assert got == dict(py_wordcount(CORPUS.splitlines(), 8))


def test_cli_line_range_sharding(corpus_file, capsysbinary):
    rc = cli.main([corpus_file, "0", "1"] + _cfg_args())
    assert rc == 0
    got = _parse_table(capsysbinary.readouterr().out)
    assert got == dict(py_wordcount([CORPUS.splitlines()[0]], 8))


@pytest.mark.parametrize("span", [(), ("0", "1"), ("37", "171"), ("100", "-1"),
                                  ("300", "400")])
def test_cli_default_path_reads_ahead_and_prints_what_the_loaded_rows_give(
    tmp_path, capsysbinary, monkeypatch, span
):
    """The default path opens the file as a block iterator and never loads
    it whole (``loader.load_rows`` is not called): 26 blocks of 8 lines in
    groups of 4, so a reader thread runs ahead of the device — CRLF ends,
    a line over the width, an empty line, no newline at the end.  Its
    stdout is byte-equal to the table of the same lines LOADED and run as
    one array (what the CLI did before), whole and under
    ``line_start/line_end`` slices, one past the end among them; stderr
    says how many lines were read."""
    from locust_tpu.config import EngineConfig
    from locust_tpu.engine import MapReduceEngine
    from locust_tpu.io import loader

    words = [b"w%03d" % (i * i % 311) for i in range(1800)]
    lines = [b" ".join(words[i:i + 9]) for i in range(0, 1800, 9)]
    lines += [b"", b"x" * 70 + b" tail", b"cr lf\r", b"last line, no newline"]
    path = tmp_path / "in.txt"
    path.write_bytes(b"\n".join(lines))
    start, end = (int(span[0]), int(span[1])) if span else (-1, -1)
    eng = MapReduceEngine(EngineConfig(block_lines=8, line_width=64, emits_per_line=8))
    rows = loader.load_rows(str(path), 64, start, end)
    want = b"".join(k + b"\t" + str(v).encode() + b"\n"
                    for k, v in eng.timed_run(rows).to_host_pairs())
    block_bytes = 8 * 64 + 3 * (8 * 8) * (32 + 4 + 1)
    monkeypatch.setattr(MapReduceEngine, "TIMED_GROUP_BYTES", 4 * block_bytes)

    def no_load(*a, **k):
        raise AssertionError("the default path loaded the corpus whole")

    monkeypatch.setattr(loader, "load_rows", no_load)
    assert cli.main([str(path), *span] + _cfg_args()) == 0
    got = capsysbinary.readouterr()
    assert got.out == want and (want or rows.shape[0] == 0)
    assert b"[locust] %d lines loaded\n" % rows.shape[0] in got.err


@pytest.mark.parametrize("flags", [["--no-timing"], ["--auto-caps"], ["--checkpoint-dir"]])
def test_cli_flags_whose_loops_index_the_rows_still_load_them(
    corpus_file, tmp_path, capsysbinary, monkeypatch, flags
):
    """``run_fused``, ``run_checkpointed`` and the ``--auto-caps`` measure
    take the corpus as one array: those flags keep ``load_rows``, and the
    table is the default path's."""
    from locust_tpu.io import loader

    loads = []
    real = loader.load_rows
    monkeypatch.setattr(loader, "load_rows",
                        lambda *a, **k: loads.append(a) or real(*a, **k))
    if flags == ["--checkpoint-dir"]:
        flags = flags + [str(tmp_path / "ckpt")]
    assert cli.main([corpus_file] + flags + _cfg_args()) == 0
    assert len(loads) == 1
    got = _parse_table(capsysbinary.readouterr().out)
    assert got == dict(py_wordcount(CORPUS.splitlines(), 8))


def test_cli_staged_map_then_reduce(corpus_file, tmp_path, capsysbinary):
    """Two map nodes shard the file; the reduce node merges both TSVs —
    the reference's distributed flow (SURVEY.md §3.2-3.3) minus the bugs."""
    t1, t2 = str(tmp_path / "n1.tsv"), str(tmp_path / "n2.tsv")
    assert cli.main([corpus_file, "0", "2", "1", "1", "-i", t1] + _cfg_args()) == 0
    assert cli.main([corpus_file, "2", "-1", "2", "1", "-i", t2] + _cfg_args()) == 0
    capsysbinary.readouterr()  # drop map-stage stdout
    rc = cli.main([corpus_file, "-1", "-1", "0", "2", "-i", t1, "-i", t2] + _cfg_args())
    assert rc == 0
    got = _parse_table(capsysbinary.readouterr().out)
    assert got == dict(py_wordcount(CORPUS.splitlines(), 8))


def test_cli_reduce_reorders_unsorted_input(tmp_path, capsysbinary):
    """Q6 fix: reduce must be correct for ANY intermediate ordering."""
    t = str(tmp_path / "x.tsv")
    with open(t, "wb") as f:
        f.write(b"zebra\t1\napple\t2\nzebra\t3\napple\t1\nmid\t5\n")
    rc = cli.main(["ignored.txt", "-1", "-1", "0", "2", "-i", t] + _cfg_args())
    assert rc == 0
    out = capsysbinary.readouterr().out
    got = _parse_table(out)
    assert got == {b"apple": 3, b"mid": 5, b"zebra": 4}
    assert list(got) == sorted(got)  # output sorted even from unsorted input


def test_cli_bad_stage_rejected(corpus_file, capsys):
    with pytest.raises(SystemExit):
        cli.main([corpus_file, "0", "1", "0", "9"])


def test_cli_limit(corpus_file, capsysbinary):
    assert cli.main([corpus_file, "--limit", "2"] + _cfg_args()) == 0
    assert len(capsysbinary.readouterr().out.splitlines()) == 2


def test_cli_auto_caps_output_identical(corpus_file, capsysbinary):
    """--auto-caps shrinks key_width/emits_per_line to the corpus's
    measured maxima; output must be byte-identical to the flag caps."""
    assert cli.main([corpus_file] + _cfg_args()) == 0
    plain = capsysbinary.readouterr().out
    assert cli.main([corpus_file, "--auto-caps"] + _cfg_args()) == 0
    auto = capsysbinary.readouterr().out
    assert auto == plain
    assert _parse_table(auto) == dict(py_wordcount(CORPUS.splitlines(), 8))


def test_cli_auto_caps_stream_detects_corpus_mutation(tmp_path, monkeypatch,
                                                      capsysbinary):
    """A corpus rewritten between the measuring pass and the run must be
    caught (under-sized caps would silently drop the new tokens)."""
    import locust_tpu.io.loader as loader_mod

    p = tmp_path / "in.txt"
    p.write_bytes(CORPUS)
    orig = loader_mod.measure_caps_stream

    def measure_then_mutate(stream):
        out = orig(stream)
        p.write_bytes(CORPUS + b"appended muchlongertokenthanmeasured line\n")
        return out

    monkeypatch.setattr(loader_mod, "measure_caps_stream", measure_then_mutate)
    rc = cli.main([str(p), "--stream", "--auto-caps"] + _cfg_args())
    assert rc == 1
    out, err = capsysbinary.readouterr()
    assert b"corpus changed" in err


def test_cli_auto_caps_lossless_on_cr_and_nul(tmp_path, capsysbinary):
    """A mid-line \\r (or NUL) is data to the loader but a token boundary
    to the device tokenizer; auto-caps must count tokens the engine's way
    or a too-small emits_per_line silently drops emits."""
    # One line whose strtok-split token count (1) undercounts the engine's
    # (\r-separated) count of 6; all other lines single-token.
    p = tmp_path / "cr.txt"
    p.write_bytes(b"a\rb\rc\rd\re\rf\nword\nword\n")
    args = [str(p), "--block-lines", "4", "--line-width", "32",
            "--emits-per-line", "8"]
    assert cli.main(args) == 0
    plain = capsysbinary.readouterr().out
    assert cli.main(args + ["--auto-caps"]) == 0
    auto = capsysbinary.readouterr().out
    assert auto == plain
    assert _parse_table(auto) == {b"a": 1, b"b": 1, b"c": 1, b"d": 1,
                                  b"e": 1, b"f": 1, b"word": 2}


def test_cli_auto_caps_mesh_matches_oracle(corpus_file, capsysbinary):
    rc = cli.main([corpus_file, "--mesh", "--auto-caps"] + _cfg_args())
    assert rc == 0
    got = _parse_table(capsysbinary.readouterr().out)
    assert got == dict(py_wordcount(CORPUS.splitlines(), 8))


def test_cli_auto_caps_with_stream(corpus_file, capsysbinary):
    """--auto-caps composes with --stream via the bounded-memory
    measuring pass; output identical to a plain --stream run."""
    assert cli.main([corpus_file, "--stream"] + _cfg_args()) == 0
    plain = capsysbinary.readouterr().out
    rc = cli.main([corpus_file, "--stream", "--auto-caps"] + _cfg_args())
    assert rc == 0
    out, err = capsysbinary.readouterr()
    assert b"auto-caps:" in err
    assert out == plain
    assert _parse_table(out) == dict(py_wordcount(CORPUS.splitlines(), 8))


def test_cli_mesh_mode_matches_oracle(corpus_file, capsysbinary):
    """--mesh routes stage 0 through the all-to-all engine on all 8
    virtual devices and must match the oracle exactly."""
    rc = cli.main([corpus_file, "--mesh"] + _cfg_args())
    assert rc == 0
    got = _parse_table(capsysbinary.readouterr().out)
    assert got == dict(py_wordcount(CORPUS.splitlines(), 8))


def test_cli_mesh_reports_per_shard_stats(corpus_file, capfd):
    rc = cli.main([corpus_file, "--mesh"] + _cfg_args())
    assert rc == 0
    err = capfd.readouterr().err
    assert "shard 0:" in err and "shard 7:" in err
    assert "distinct=" in err and "drain_rounds=" in err


def test_cli_mesh_staged_map_writes_tsv(corpus_file, tmp_path, capsysbinary):
    t = str(tmp_path / "mesh.tsv")
    rc = cli.main([corpus_file, "-1", "-1", "0", "1", "--mesh", "-i", t] + _cfg_args())
    assert rc == 0
    capsysbinary.readouterr()
    rc = cli.main([corpus_file, "-1", "-1", "0", "2", "-i", t] + _cfg_args())
    assert rc == 0
    got = _parse_table(capsysbinary.readouterr().out)
    assert got == dict(py_wordcount(CORPUS.splitlines(), 8))


def test_cli_stream_mode_matches_oracle(corpus_file, capsysbinary):
    rc = cli.main([corpus_file, "--stream"] + _cfg_args())
    assert rc == 0
    got = _parse_table(capsysbinary.readouterr().out)
    assert got == dict(py_wordcount(CORPUS.splitlines(), 8))


def test_cli_mesh_stream_matches_oracle(corpus_file, capsysbinary):
    rc = cli.main([corpus_file, "--mesh", "--stream"] + _cfg_args())
    assert rc == 0
    got = _parse_table(capsysbinary.readouterr().out)
    assert got == dict(py_wordcount(CORPUS.splitlines(), 8))


def test_cli_stream_with_checkpoint(corpus_file, tmp_path, capsysbinary):
    ckpt = str(tmp_path / "ck")
    rc = cli.main([corpus_file, "--stream", "--checkpoint-dir", ckpt] + _cfg_args())
    assert rc == 0
    first = _parse_table(capsysbinary.readouterr().out)
    assert first == dict(py_wordcount(CORPUS.splitlines(), 8))
    # Second run resumes from the final snapshot and must match exactly.
    rc = cli.main([corpus_file, "--stream", "--checkpoint-dir", ckpt] + _cfg_args())
    assert rc == 0
    assert _parse_table(capsysbinary.readouterr().out) == first


def test_cli_mesh_slices_matches_oracle(corpus_file, capsysbinary):
    """--mesh --slices 2 routes through the hierarchical engine."""
    rc = cli.main([corpus_file, "--mesh", "--slices", "2"] + _cfg_args())
    assert rc == 0
    got = _parse_table(capsysbinary.readouterr().out)
    assert got == dict(py_wordcount(CORPUS.splitlines(), 8))


def test_cli_mesh_slices_stream(corpus_file, capsysbinary):
    rc = cli.main([corpus_file, "--mesh", "--slices", "2", "--stream"] + _cfg_args())
    assert rc == 0
    got = _parse_table(capsysbinary.readouterr().out)
    assert got == dict(py_wordcount(CORPUS.splitlines(), 8))


def test_cli_mesh_slices_checkpoint(corpus_file, tmp_path, capsysbinary):
    """--slices now composes with --checkpoint-dir (hierarchical resume);
    a second run with the same corpus resumes and matches exactly."""
    ckpt = str(tmp_path / "hckpt")
    args = [corpus_file, "--mesh", "--slices", "2",
            "--checkpoint-dir", ckpt] + _cfg_args()
    assert cli.main(args) == 0
    first = _parse_table(capsysbinary.readouterr().out)
    assert first == dict(py_wordcount(CORPUS.splitlines(), 8))
    assert cli.main(args) == 0  # resumes from the completed snapshot
    assert _parse_table(capsysbinary.readouterr().out) == first


def test_cli_mesh_slices_stream_checkpoint(corpus_file, tmp_path,
                                           capsysbinary):
    """The full composition: hierarchical engine + streaming ingest +
    resumable snapshots."""
    ckpt = str(tmp_path / "hsckpt")
    args = [corpus_file, "--mesh", "--slices", "2", "--stream",
            "--checkpoint-dir", ckpt] + _cfg_args()
    assert cli.main(args) == 0
    first = _parse_table(capsysbinary.readouterr().out)
    assert first == dict(py_wordcount(CORPUS.splitlines(), 8))
    assert cli.main(args) == 0  # resumes from the completed snapshot
    assert _parse_table(capsysbinary.readouterr().out) == first


def test_cli_slices_implies_mesh(corpus_file, capfd):
    """--slices without --mesh must not silently fall back to the
    single-device engine (code-review r3 finding)."""
    rc = cli.main([corpus_file, "--slices", "2"] + _cfg_args())
    assert rc == 0
    captured = capfd.readouterr()
    assert "hierarchical mesh: 2 slice(s)" in captured.err
    got = _parse_table(captured.out.encode())
    assert got == dict(py_wordcount(CORPUS.splitlines(), 8))


# ---------------------------------------------------------- workload ladder


@pytest.fixture
def edges_file(tmp_path):
    """Small digraph with a comment line and a dangling node (3)."""
    p = tmp_path / "edges.txt"
    p.write_bytes(
        b"# snap-style comment\n"
        b"0 1\n1 2\n2 0\n0 2\n4 3\n4 0\n"
    )
    return str(p)


def test_cli_pagerank_single_and_mesh_match(edges_file, capsysbinary):
    """BASELINE.json configs[3] from the entrypoint: single-device and
    --mesh (ShardedPageRank) agree with the library oracle."""
    from locust_tpu.apps.pagerank import pagerank

    src = np.array([0, 1, 2, 0, 4, 4], np.int32)
    dst = np.array([1, 2, 0, 2, 3, 0], np.int32)
    want = np.asarray(pagerank(src, dst, num_nodes=5, num_iters=10))

    def parse(out: bytes) -> np.ndarray:
        vals = {}
        for ln in out.splitlines():
            n, _, r = ln.partition(b"\t")
            vals[int(n)] = float(r)
        return np.asarray([vals[i] for i in range(len(vals))])

    rc = cli.main(["pagerank", edges_file, "--num-iters", "10"])
    assert rc == 0
    got = parse(capsysbinary.readouterr().out)
    np.testing.assert_allclose(got, want, atol=1e-6)

    rc = cli.main(["pagerank", edges_file, "--num-iters", "10", "--mesh"])
    assert rc == 0
    got_mesh = parse(capsysbinary.readouterr().out)
    np.testing.assert_allclose(got_mesh, want, atol=1e-5)


def test_cli_pagerank_top_and_errors(edges_file, tmp_path, capsysbinary):
    rc = cli.main(["pagerank", edges_file, "--top", "2"])
    assert rc == 0
    out = capsysbinary.readouterr().out.splitlines()
    assert len(out) == 2
    # Malformed edge file: loud failure, not a crash.
    bad = tmp_path / "bad.txt"
    bad.write_bytes(b"0 1\nnot an edge line\n")
    assert cli.main(["pagerank", str(bad)]) == 1
    # --num-nodes too small for the file's ids.
    assert cli.main(["pagerank", edges_file, "--num-nodes", "2"]) == 1


DOC_CORPUS = b"""the cat sat
the dog ran
cats and dogs
the end
"""


def _index_oracle(lines, lines_per_doc=1):
    import re

    from locust_tpu.config import DELIMITERS

    oracle = {}
    for i, ln in enumerate(lines):
        d = i // lines_per_doc
        for t in re.split(b"[" + re.escape(DELIMITERS + b"\n\r\x00") + b"]+", ln):
            if t:
                docs = oracle.setdefault(t, [])
                if d not in docs:
                    docs.append(d)
    return {k: sorted(v) for k, v in oracle.items()}


def test_cli_index_single_and_mesh_match(tmp_path, capsysbinary):
    """BASELINE.json configs[4] from the entrypoint."""
    p = tmp_path / "docs.txt"
    p.write_bytes(DOC_CORPUS)
    want = _index_oracle(DOC_CORPUS.splitlines())

    def parse(out: bytes):
        got = {}
        for ln in out.splitlines():
            w, _, docs = ln.partition(b"\t")
            got[w] = [int(d) for d in docs.split(b",")]
        return got

    args = ["index", str(p), "--block-lines", "8", "--line-width", "64",
            "--emits-per-line", "8"]
    assert cli.main(args) == 0
    assert parse(capsysbinary.readouterr().out) == want
    assert cli.main(args + ["--mesh"]) == 0
    assert parse(capsysbinary.readouterr().out) == want
    # Multi-line documents.
    assert cli.main(args + ["--lines-per-doc", "2"]) == 0
    assert parse(capsysbinary.readouterr().out) == _index_oracle(
        DOC_CORPUS.splitlines(), 2
    )


def test_cli_tfidf_matches_library(tmp_path, capsysbinary):
    p = tmp_path / "docs.txt"
    p.write_bytes(DOC_CORPUS)
    from locust_tpu.apps.tfidf import build_tfidf
    from locust_tpu.config import EngineConfig
    from locust_tpu.io import loader

    cfg = EngineConfig(block_lines=8, line_width=64, emits_per_line=8)
    rows = loader.load_rows(str(p), 64)
    ids = np.arange(rows.shape[0], dtype=np.int32)
    want = build_tfidf(rows, ids, cfg)

    assert cli.main(["tfidf", str(p), "--block-lines", "8", "--line-width",
                     "64", "--emits-per-line", "8"]) == 0
    out = capsysbinary.readouterr().out
    got = {}
    for ln in out.splitlines():
        w, d, s = ln.split(b"\t")
        got[(w, int(d))] = float(s)
    assert set(got) == set(want)
    for k in want:
        assert abs(got[k] - want[k]) < 1e-4
    # tfidf --mesh is a loud unsupported error, not silence.
    assert cli.main(["tfidf", str(p), "--mesh"]) == 2


def test_cli_stream_checkpoint_hasht(corpus_file, tmp_path, capsysbinary):
    """--stream + --checkpoint-dir + the sort-free fold: snapshots of
    hasht's slot-ordered tables must resume exactly through the CLI
    path too (single-device analog of the rig's hasht_checkpoint)."""
    ckpt = str(tmp_path / "ck")
    args = [corpus_file, "--stream", "--checkpoint-dir", ckpt,
            "--sort-mode", "hasht"] + _cfg_args()
    rc = cli.main(args)
    assert rc == 0
    first = _parse_table(capsysbinary.readouterr().out)
    assert first == dict(py_wordcount(CORPUS.splitlines(), 8))
    rc = cli.main(args)
    assert rc == 0
    assert _parse_table(capsysbinary.readouterr().out) == first
