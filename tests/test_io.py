"""IO tests: loader slicing (incl. Q1 fix), TSV/npz serde, native ingest parity."""

import numpy as np
import pytest

from helpers import py_wordcount

from locust_tpu.core import bytes_ops
from locust_tpu.core.kv import KVBatch
from locust_tpu.io import loader, serde


CORPUS = b"first line\nsecond, line\nthird-line\r\nfourth\nlast without newline"


@pytest.fixture
def corpus_file(tmp_path):
    p = tmp_path / "corpus.txt"
    p.write_bytes(CORPUS)
    return str(p)


def test_load_lines_whole_file_keeps_last_line(corpus_file):
    # Q1: the reference drops the final line; we must not.
    lines = loader.load_lines(corpus_file)
    assert len(lines) == 5
    assert lines[-1] == b"last without newline"


def test_load_lines_slice_semantics(corpus_file):
    assert loader.load_lines(corpus_file, 1, 3) == [b"second, line", b"third-line"]
    assert loader.load_lines(corpus_file, 3, 100) == [
        b"fourth",
        b"last without newline",
    ]
    assert loader.load_lines(corpus_file, 99, 200) == []


def test_load_rows_python_fallback(corpus_file):
    rows = loader.load_rows(corpus_file, 32, use_native=False)
    assert rows.shape == (5, 32)
    assert bytes_ops.rows_to_strings(rows)[0] == b"first line"
    # CR stripped from CRLF line
    assert bytes_ops.rows_to_strings(rows)[2] == b"third-line"


def test_native_ingest_matches_python(corpus_file):
    pytest.importorskip("locust_tpu.io.native_ingest")
    from locust_tpu.io import native_ingest

    try:
        native = native_ingest.load_rows(corpus_file, 32)
    except (OSError, Exception) as e:  # toolchain missing
        pytest.skip(f"native build unavailable: {e}")
    py = loader.load_rows(corpus_file, 32, use_native=False)
    np.testing.assert_array_equal(native, py)
    for sl in [(-1, -1), (1, 3), (0, 2), (4, 99), (2, 2)]:
        np.testing.assert_array_equal(
            native_ingest.load_rows(corpus_file, 16, *sl),
            loader.load_rows(corpus_file, 16, *sl, use_native=False),
        )


def test_native_library_is_keyed_by_the_source_sha(tmp_path, monkeypatch):
    """A copied checkout carries arbitrary mtimes and possibly a stale
    untracked .so: the built file's NAME says which source it matches."""
    import hashlib

    from locust_tpu.io import native_ingest

    src = native_ingest._SRC.read_bytes()
    sha = hashlib.sha256(src).hexdigest()[:12]
    so = native_ingest.so_path()
    assert so.name == f"libingest-{sha}.so"
    assert so.parent == native_ingest._NATIVE_DIR / "build"
    if not native_ingest.available():
        pytest.skip("native build unavailable")
    assert so.exists() and native_ingest._build() == so
    # An edited source is a different file name, whatever the mtimes say.
    edited = tmp_path / "ingest.cpp"
    edited.write_bytes(src + b"\n// edited\n")
    monkeypatch.setattr(native_ingest, "_SRC", edited)
    assert native_ingest.so_path().name != so.name


def test_native_stale_unkeyed_library_is_never_loaded():
    """The old un-keyed name, however new its mtime, is not what loads."""
    from locust_tpu.io import native_ingest

    if not native_ingest.available():
        pytest.skip("native build unavailable")
    stale = native_ingest._NATIVE_DIR / "build" / "libingest.so"
    assert native_ingest._build() != stale
    assert native_ingest._build().name.startswith("libingest-")


def test_native_ingest_long_line_truncates(tmp_path):
    from locust_tpu.io import native_ingest

    p = tmp_path / "long.txt"
    p.write_bytes(b"x" * 300 + b"\nshort\n")
    try:
        rows = native_ingest.load_rows(str(p), 64)
    except Exception as e:
        pytest.skip(f"native build unavailable: {e}")
    assert bytes_ops.rows_to_strings(rows) == [b"x" * 64, b"short"]


def test_tsv_roundtrip(tmp_path):
    pairs = [(b"the", 143), (b"to", 123), (b"question", 1)]
    path = str(tmp_path / "out.tsv")
    serde.write_tsv(pairs, path)
    keys, values = serde.read_tsv(path, 32)
    assert bytes_ops.rows_to_strings(keys) == [k for k, _ in pairs]
    assert values.tolist() == [v for _, v in pairs]


def test_tsv_accepts_reference_trailing_space(tmp_path):
    # Q5: the reference writes "key \tvalue"; we must read it cleanly.
    path = str(tmp_path / "ref.tsv")
    with open(path, "wb") as f:
        f.write(b"word \t7\n\n junk-no-tab\nvalid\t3\n")
    keys, values = serde.read_tsv(path, 32)
    assert bytes_ops.rows_to_strings(keys) == [b"word", b"valid"]
    assert values.tolist() == [7, 3]


def test_npz_roundtrip(tmp_path):
    import jax.numpy as jnp

    keys = jnp.asarray(bytes_ops.strings_to_rows([b"alpha", b"beta"], 32))
    batch = KVBatch.from_bytes(keys, jnp.asarray([1, 2]), jnp.asarray([1, 1], bool))
    path = str(tmp_path / "shard.npz")
    serde.write_npz(batch, path)
    back = serde.read_npz(path)
    assert back.to_host_pairs() == [(b"alpha", 1), (b"beta", 2)]


# ---------------------------------------------------------- streaming ingest

class TestStreamingCorpus:
    """StreamingCorpus (both backends) must match load_rows exactly."""

    def _assert_stream_matches(self, path, width, block_lines, start=-1,
                               end=-1, use_native=False):
        sc = loader.StreamingCorpus(
            path, width, block_lines, start, end,
            chunk_bytes=1 << 16, use_native=use_native,
        )
        blocks = list(sc)
        got = (
            np.concatenate(blocks)
            if blocks
            else np.zeros((0, width), np.uint8)
        )
        want = loader.load_rows(path, width, start, end, use_native=False)
        np.testing.assert_array_equal(got, want)
        # every block except the last is full
        for b in blocks[:-1]:
            assert b.shape[0] == block_lines

    @pytest.mark.parametrize("use_native", [False, True])
    @pytest.mark.parametrize("block_lines", [1, 2, 3, 100])
    def test_matches_load_rows(self, corpus_file, block_lines, use_native):
        if use_native:
            pytest.importorskip("locust_tpu.io.native_ingest")
        self._assert_stream_matches(
            corpus_file, 32, block_lines, use_native=use_native
        )

    @pytest.mark.parametrize("use_native", [False, True])
    @pytest.mark.parametrize("start,end", [(1, 3), (3, 100), (99, 200), (0, 0)])
    def test_slices(self, corpus_file, start, end, use_native):
        if use_native:
            pytest.importorskip("locust_tpu.io.native_ingest")
        self._assert_stream_matches(
            corpus_file, 32, 2, start, end, use_native=use_native
        )

    @pytest.mark.parametrize("use_native", [False, True])
    def test_chunk_boundaries_and_long_lines(self, tmp_path, use_native):
        if use_native:
            pytest.importorskip("locust_tpu.io.native_ingest")
        # Lines crossing every chunk boundary + one line far beyond the
        # python reader's 64KB test chunk (and width), + empty lines.
        p = tmp_path / "stress.txt"
        lines = [b"x" * n for n in (0, 1, 31, 32, 33, 200_000, 0, 5)]
        p.write_bytes(b"\n".join(lines) + b"\n")
        self._assert_stream_matches(str(p), 32, 3, use_native=use_native)

    @pytest.mark.parametrize("use_native", [False, True])
    def test_engine_run_stream_matches_run(self, corpus_file, use_native):
        if use_native:
            pytest.importorskip("locust_tpu.io.native_ingest")
        from locust_tpu.config import EngineConfig
        from locust_tpu.engine import MapReduceEngine

        cfg = EngineConfig(block_lines=2, line_width=32)
        eng = MapReduceEngine(cfg)
        res_full = eng.run(loader.load_rows(corpus_file, 32, use_native=False))
        res_stream = eng.run_stream(
            loader.StreamingCorpus(corpus_file, 32, 2, use_native=use_native)
        )
        assert res_stream.to_host_pairs() == res_full.to_host_pairs()

    def test_empty_file(self, tmp_path):
        p = tmp_path / "empty.txt"
        p.write_bytes(b"")
        assert list(loader.StreamingCorpus(str(p), 32, 4, use_native=False)) == []

    def test_fingerprint_changes_with_content(self, tmp_path):
        p = tmp_path / "f.txt"
        p.write_bytes(b"hello\n")
        f1 = loader.StreamingCorpus(str(p), 32, 4).fingerprint()
        import os, time
        time.sleep(0.01)
        p.write_bytes(b"world\n")
        f2 = loader.StreamingCorpus(str(p), 32, 4).fingerprint()
        assert f1 != f2


@pytest.mark.parametrize("use_native", [False, True])
def test_cr_semantics_canonical(tmp_path, use_native):
    """Split on \\n ONLY; strip exactly one trailing \\r; a lone \\r is
    data; a \\r at a truncated position is data (not CRLF)."""
    if use_native:
        pytest.importorskip("locust_tpu.io.native_ingest")
    p = tmp_path / "cr.txt"
    w = 8
    long_line = b"x" * (w - 1) + b"\r" + b"yyy"     # \r at width-1 is DATA
    p.write_bytes(
        b"a\rb\n"          # lone \r inside a line: data
        b"crlf\r\n"        # CRLF: strip one
        b"two\r\r\n"       # \r\r\n: strip ONE, keep the first \r
        + long_line + b"\n"
    )
    want = [b"a\rb", b"crlf", b"two\r", long_line[:w]]
    rows = loader.load_rows(str(p), w, use_native=use_native)
    assert bytes_ops.rows_to_strings(rows) == [ln[:w] for ln in want]
    blocks = list(
        loader.StreamingCorpus(str(p), w, 2, use_native=use_native,
                               chunk_bytes=1 << 16)
    )
    got = [r for b in blocks for r in bytes_ops.rows_to_strings(b)]
    assert got == [ln[:w] for ln in want]


# ------------------------------------------ one line loop, three readers

_W = 8  # the row width of every case below


def _soup(seed: int, pieces: int) -> bytes:
    rng = np.random.default_rng(seed)
    parts = [b"\n", b"\r", b"\r\n", b"\x00", b"a", b"bc", b" ", b"q" * (_W - 1),
             b"q" * _W, b"q" * (_W + 1)]
    return b"".join(parts[i] for i in rng.integers(0, len(parts), pieces))


# name -> the file's bytes: what the three readers must agree on, byte for
# byte (PR 49: the native scanner finds its lines with memchr in a 1 MB
# buffer it fills by pread; ``_iter_python`` and the Python ``load_rows``
# are the oracles).
_FILL = (b"x" * 63 + b"\n") * 16383  # 64 bytes short of the scanner's buffer
SCANNER_CASES = {
    "crlf": b"a\r\nbb\r\n\r\nccc\r\n",
    "cr_twice_and_alone": b"a\rb\ntwo\r\r\n\r\n\r",
    # '\r' one byte before the cut, exactly at it (the row's last byte),
    # and the first byte past it — of lines that go on: data, every time.
    "cr_before_the_cut": b"x" * (_W - 2) + b"\ryyyy\nz\n",
    "cr_at_the_cut": b"x" * (_W - 1) + b"\ryyyy\nz\n",
    "cr_past_the_cut": b"x" * _W + b"\ryyyy\nz\n",
    # ... and as the line's true last byte there: a CRLF's, stripped.
    "crlf_at_the_cut": b"x" * (_W - 1) + b"\r\n" + b"x" * _W + b"\r\n"
                       + b"x" * (_W + 1) + b"\r\nz\r\n",
    "width_exactly": b"x" * _W + b"\n" + b"y" * (_W + 1) + b"\n" + b"z" * (_W - 1) + b"\n",
    "over_a_megabyte": b"ab\n" + b"L" * ((1 << 20) + 17) + b"\ncd\n"
                       + b"M" * ((1 << 20) + 3) + b"\r\nef",
    # the Python reader's 64 KB test chunk ends INSIDE this line, right
    # before its LF: its carried prefix must not end in the cut's '\r'
    "cr_at_the_cut_of_a_carried_line": b"x" * (_W - 1) + b"\r" + b"y" * ((1 << 16) - _W)
                                       + b"\nrest\n",
    # the native reader's 1 MB buffer ends INSIDE a line, between a CRLF's
    # two bytes, and right after a line's LF
    "a_line_across_the_buffers_end": _FILL + b"y" * 100 + b"\r\nzz\n",
    "a_crlf_across_the_buffers_end": _FILL + b"y" * 63 + b"\r\nzz\r\n",
    "an_lf_at_the_buffers_end": _FILL + b"y" * 63 + b"\nzz",
    "empty_lines": b"\n\na\n\n\nb\n\n",
    "lfs_only": b"\n" * 7,
    "no_lf_at_the_end": b"a\nbb\nccc",
    "no_lf_and_a_cr_at_the_end": b"a\nbb\r",
    "one_line_no_lf": b"alone",
    "empty_file": b"",
    "nul_bytes": b"a\x00b\n\x00\n\x00\x00c\r\n" + b"\x00" * (_W + 2) + b"\nd",
    "soup_1": _soup(1, 300),
    "soup_2": _soup(2, 300),
}


@pytest.fixture(params=["file", "memfd"])
def scanner_path(request, tmp_path):
    """A way to hand the readers some bytes by a path: a file, or an
    anonymous memory file by its ``/proc/self/fd/N`` (how the benchmark's
    drivers hand the CLI its input)."""
    import os

    fds = []

    def put(data: bytes) -> str:
        if request.param == "file":
            p = tmp_path / "scanned.txt"
            p.write_bytes(data)
            return str(p)
        if not hasattr(os, "memfd_create") or not os.path.isdir("/proc/self/fd"):
            pytest.skip("no os.memfd_create / no /proc/self/fd here")
        fd = os.memfd_create("scanned")
        fds.append(fd)
        os.write(fd, data)
        return f"/proc/self/fd/{fd}"

    yield put
    for fd in fds:
        os.close(fd)


@pytest.mark.parametrize("case", SCANNER_CASES)
def test_the_native_scanner_is_the_python_readers_byte_for_byte(case, scanner_path):
    """Native window reader == ``_iter_python`` == ``load_rows`` (native
    and Python), block for block and byte for byte, at ``block_lines`` of
    1, of 3 and of more lines than the file, over EVERY ``[line_start,
    line_end)`` of a small file — so each starts and ends inside a block,
    on its edge, and past the file — and ``count_lines`` by the split."""
    from locust_tpu.io import native_ingest

    if not native_ingest.available():
        pytest.skip("native build unavailable")
    data = SCANNER_CASES[case]
    path = scanner_path(data)
    n = len(data.split(b"\n")) - (data == b"" or data.endswith(b"\n"))
    assert native_ingest.count_lines(path) == n
    assert loader.count_lines(path) == n
    edges = range(-1, n + 2) if n <= 8 else (-1, 0, 1, 2, 3, n // 2, n - 1, n, n + 3)
    sizes = (1, 3, n + 5)
    if n > 1000:  # a megabyte of short lines: fewer, larger readings
        edges, sizes = (-1, n - 3, n - 1, n + 3), (4096, n + 5)
    for block_lines in sizes:
        for start in edges:
            for end in edges:
                what = f"{case}: block_lines {block_lines}, [{start}, {end})"
                want = loader.load_rows(path, _W, start, end, use_native=False)
                got = native_ingest.load_rows(path, _W, start, end)
                assert got.shape == want.shape and np.array_equal(got, want), what
                py = list(loader.StreamingCorpus(
                    path, _W, block_lines, start, end, chunk_bytes=1 << 16,
                    use_native=False))
                nat = list(native_ingest.iter_blocks(path, _W, block_lines, start, end))
                assert [b.shape for b in nat] == [b.shape for b in py], what
                assert all(np.array_equal(a, b) for a, b in zip(nat, py)), what
                rows = np.concatenate(nat) if nat else np.zeros((0, _W), np.uint8)
                assert np.array_equal(rows, want), what


def _descriptors_of(path: str) -> int:
    import os

    real, n = os.path.realpath(path), 0
    for fd in os.listdir("/proc/self/fd"):
        try:
            n += os.readlink(f"/proc/self/fd/{fd}") == real
        except OSError:  # the listing's own descriptor, closed by now
            pass
    return n


def test_an_abandoned_stream_leaves_no_descriptor_and_no_reader(tmp_path):
    """``prefetch_blocks`` over a ``StreamingCorpus`` dropped after two
    blocks: the native scanner's descriptor of the file is closed (its
    buffer freed with it) and the reader thread is gone — nothing
    outlives the consumer."""
    import os
    import threading
    import time as _time

    from locust_tpu.io import native_ingest

    if not native_ingest.available() or not os.path.isdir("/proc/self/fd"):
        pytest.skip("native build or /proc/self/fd unavailable")
    p = tmp_path / "streamed.txt"
    p.write_bytes(b"".join(b"line %d\n" % i for i in range(4000)))
    before = threading.active_count()
    assert _descriptors_of(str(p)) == 0
    it = loader.prefetch_blocks(iter(loader.StreamingCorpus(str(p), 16, 8)), depth=2)
    first, second = next(it), next(it)
    assert bytes_ops.rows_to_strings(second)[0] == b"line 8"
    assert _descriptors_of(str(p)) == 1  # the file IS held open while it is read
    it.close()  # what dropping it does
    deadline = _time.time() + 5
    while threading.active_count() > before and _time.time() < deadline:
        _time.sleep(0.02)
    assert threading.active_count() <= before
    assert _descriptors_of(str(p)) == 0
    # ... and a stream read to its end closes its file as well.
    assert sum(b.shape[0] for b in loader.StreamingCorpus(str(p), 16, 8)) == 4000
    assert _descriptors_of(str(p)) == 0


def test_a_path_that_is_no_regular_file_goes_to_the_python_reader(tmp_path):
    """A FIFO: the native open refuses it BEFORE the first block (and
    without opening it: its writer keeps its reader), ``StreamingCorpus``
    reads it with ``_iter_python``, the blocks are the file's."""
    import os
    import threading

    from locust_tpu.io import native_ingest

    if not native_ingest.available() or not hasattr(os, "mkfifo"):
        pytest.skip("native build or os.mkfifo unavailable")
    data = b"first\r\nsecond line is long\n\nlast"
    plain, fifo = tmp_path / "plain.txt", tmp_path / "fifo"
    plain.write_bytes(data)
    os.mkfifo(fifo)
    with pytest.raises(OSError):
        next(native_ingest.iter_blocks(str(fifo), _W, 2))
    with pytest.raises(OSError):
        native_ingest.count_lines(str(fifo))

    def write():
        with open(fifo, "wb") as f:  # blocks until the reader opens
            f.write(data)

    t = threading.Thread(target=write, daemon=True)
    t.start()
    got = list(loader.StreamingCorpus(str(fifo), _W, 2))
    t.join(timeout=5)
    assert not t.is_alive()
    want = list(loader.StreamingCorpus(str(plain), _W, 2))
    assert len(got) == len(want) == 2
    assert all(np.array_equal(a, b) for a, b in zip(got, want))


# ------------------------------------------------------------- native TSV

class TestNativeTsvParity:
    """ingest_read_tsv must match serde.read_tsv's Python path exactly."""

    CASES = [
        # (file content, description)
        (b"word\t3\nother\t-7\n", "clean"),
        (b"key \t5\n", "reference trailing-space key (Q5)"),
        (b"a b \t5\nab c\t6\n", "interior spaces kept, trailing stripped"),
        (b"\nword\t1\n\n", "blank lines skipped"),
        (b"noval\nword\t2\n", "line without tab skipped"),
        (b"word\tnotint\nok\t9\n", "malformed value skipped"),
        (b"word\t 12 \n", "whitespace-padded value accepted"),
        (b"word\t5", "trailing line without newline (Q1)"),
        (b"crlf\t4\r\n", "CRLF value"),
        (b"verylongkey_beyond_width\t8\n", "key truncated to width"),
        (b"  \t5\n", "all-space key skipped"),
        (b"tab\t5\t6\n", "second tab makes value malformed: skipped"),
        (b"", "empty file"),
        (b"u\t1_2\nok\t3\n", "underscore value malformed (strict grammar)"),
        (b"v\t5\x0b\nok\t3\n", "vertical-tab padding malformed"),
        (b"n\t5\x006\nok\t3\n", "NUL byte in value malformed"),
        (b"L\t" + b" " * 70 + b"5\nok\t3\n", "value field >63 bytes malformed"),
        (b"z\t+7\nneg\t-0\n", "signs accepted"),
        (b"lead\t0005\n", "leading zeros accepted"),
        (b"edge\t" + b" " * 62 + b"5\r\n", "63-byte value + CRLF kept"),
        (b"crs\t5" + b"\r" * 80 + b"\n", "many terminator CRs stripped"),
        (b"icr\t \r 5\nok\t1\n", "interior CR accepted as padding"),
    ]

    @pytest.mark.parametrize("content,desc", CASES, ids=[c[1] for c in CASES])
    def test_parity(self, tmp_path, content, desc):
        pytest.importorskip("locust_tpu.io.native_ingest")
        from locust_tpu.io import native_ingest

        p = tmp_path / "t.tsv"
        p.write_bytes(content)
        for width in (8, 32):
            pk, pv = serde.read_tsv(str(p), width, use_native=False)
            nk, nv = native_ingest.read_tsv(str(p), width)
            np.testing.assert_array_equal(nk, pk, err_msg=desc)
            np.testing.assert_array_equal(nv, pv, err_msg=desc)

    def test_int32_overflow_raises_in_both(self, tmp_path):
        pytest.importorskip("locust_tpu.io.native_ingest")
        from locust_tpu.io import native_ingest

        p = tmp_path / "o.tsv"
        p.write_bytes(b"word\t3000000000\n")
        with pytest.raises(OverflowError):
            serde.read_tsv(str(p), 16, use_native=False)
        with pytest.raises(OverflowError):
            native_ingest.read_tsv(str(p), 16)

    def test_parity_on_real_wordcount_output(self, tmp_path):
        pytest.importorskip("locust_tpu.io.native_ingest")
        from locust_tpu.io import native_ingest

        pairs = [(b"w%05d" % i, i * 7 - 3) for i in range(5000)]
        p = tmp_path / "big.tsv"
        serde.write_tsv(pairs, str(p))
        pk, pv = serde.read_tsv(str(p), 32, use_native=False)
        nk, nv = native_ingest.read_tsv(str(p), 32)
        np.testing.assert_array_equal(nk, pk)
        np.testing.assert_array_equal(nv, pv)
        assert len(nv) == 5000


class TestMeasureCaps:
    """measure_caps (regex over lines) and measure_caps_rows (vectorized
    over padded row blocks) must agree — cli.py --auto-caps uses one for
    materialized runs and the other for --stream."""

    def test_rows_variant_matches_regex_oracle(self):
        rng = np.random.default_rng(7)
        from locust_tpu.config import DELIMITERS
        from locust_tpu.io.loader import measure_caps, measure_caps_rows

        alphabet = b"abcdefgh" + DELIMITERS[:4] + b"\r"
        for trial in range(20):
            n = int(rng.integers(1, 40))
            lines = [
                bytes(alphabet[i] for i in rng.integers(0, len(alphabet), size=int(rng.integers(0, 60))))
                for _ in range(n)
            ]
            width = int(rng.choice([16, 32, 64]))
            rows = bytes_ops.strings_to_rows(lines, width)
            # The regex oracle must see the same width-truncated view.
            got = measure_caps_rows([rows[:n // 2], rows[n // 2:]])
            want = measure_caps([ln[:width] for ln in lines])
            assert got == want, f"trial={trial} width={width}"

    def test_rows_variant_counts_post_nul_tokens(self):
        from locust_tpu.io.loader import measure_caps, measure_caps_rows

        # Embedded NUL: loader keeps it as data; the device tokenizer
        # splits there.  Both measures must count 2 tokens.
        rows = bytes_ops.strings_to_rows([b"abc\x00defgh"], 16)
        assert measure_caps_rows([rows]) == (5, 2)
        assert measure_caps([b"abc\x00defgh"]) == (5, 2)

    def test_empty_and_all_delim_blocks(self):
        from locust_tpu.io.loader import measure_caps_rows

        assert measure_caps_rows([]) == (1, 1)
        rows = bytes_ops.strings_to_rows([b"", b" , .", b"\t\t"], 8)
        assert measure_caps_rows([rows]) == (1, 1)


class TestPrefetchBlocks:
    def test_order_preserved(self):
        from locust_tpu.io.loader import prefetch_blocks

        items = [np.full((2, 4), i, np.uint8) for i in range(50)]
        out = list(prefetch_blocks(iter(items), depth=3))
        assert len(out) == 50
        for i, blk in enumerate(out):
            np.testing.assert_array_equal(blk, items[i])

    def test_exception_propagates(self):
        from locust_tpu.io.loader import prefetch_blocks

        def gen():
            yield np.zeros((1, 1), np.uint8)
            raise RuntimeError("disk on fire")

        it = prefetch_blocks(gen())
        next(it)
        with pytest.raises(RuntimeError, match="disk on fire"):
            list(it)

    def test_tuple_items_pass_through(self):
        """(rows, doc_ids) chunk pairs (the index's stream unit) must not
        be confused with the internal error sentinel."""
        from locust_tpu.io.loader import prefetch_blocks

        pairs = [(np.zeros((2, 4), np.uint8), np.arange(2)) for _ in range(5)]
        out = list(prefetch_blocks(iter(pairs)))
        assert len(out) == 5 and isinstance(out[0], tuple)

    def test_empty(self):
        from locust_tpu.io.loader import prefetch_blocks

        assert list(prefetch_blocks(iter([]))) == []

    def test_abandoned_generator_stops_reader(self):
        """Dropping the generator mid-stream (consumer raised) must stop
        the reader thread and release the source iterator promptly —
        a leak per retry would accumulate in bench's TPU retry loop."""
        import gc
        import threading
        import time as _time

        from locust_tpu.io.loader import prefetch_blocks

        state = {"yielded": 0, "closed": False}

        def slow_source():
            try:
                for i in range(1000):
                    state["yielded"] += 1
                    yield np.full((1, 1), i % 250, np.uint8)
            finally:
                state["closed"] = True

        before = threading.active_count()
        it = prefetch_blocks(slow_source(), depth=2)
        next(it)
        it.close()  # what GC does when the consumer abandons it
        deadline = _time.time() + 5
        while threading.active_count() > before and _time.time() < deadline:
            _time.sleep(0.05)
        gc.collect()
        assert threading.active_count() <= before
        # The reader stopped far short of draining the 1000-item source.
        assert state["yielded"] < 50


def test_native_measure_caps_parity(tmp_path):
    """ingest_measure_caps == measure_caps_rows over the staged blocks —
    on adversarial input (CR/NUL bytes, tokens spanning the truncation
    boundary, empty lines, a trailing fragment without newline) across
    widths and node slices.  The native scan is the --auto-caps --stream
    fast path (~12x the numpy block path at 512MB)."""
    pytest.importorskip("locust_tpu.io.native_ingest")
    from locust_tpu.io import native_ingest

    rng = np.random.default_rng(5)
    alphabet = b"abcdef ,.-;:'()\"\t\r\x00"
    lines = [
        bytes(rng.choice(list(alphabet), size=int(rng.integers(0, 200))))
        for _ in range(120)
    ] + [b"", b"x" * 500, b"tok " * 60, (b"y" * 127) + b" zz",
         (b"w" * 128) + b"qq more toks"]
    p = tmp_path / "caps.txt"
    p.write_bytes(b"\n".join(lines) + b"\ntail_without_newline")
    try:
        native_ingest._load()  # probe the TOOLCHAIN only: a measure_caps
        # that errors on valid input must FAIL the parity suite below,
        # not skip it (code-review r4 finding).
    except OSError as e:  # toolchain missing
        pytest.skip(f"native build unavailable: {e}")
    for width in (64, 128):
        for sl in ((-1, -1), (3, 60), (0, 1)):
            want = loader.measure_caps_rows(
                loader.StreamingCorpus(str(p), width, 32, *sl)
            )
            got = native_ingest.measure_caps(str(p), width, *sl)
            assert got == want, (width, sl, got, want)
    # measure_caps_stream prefers the native path and agrees too.
    stream = loader.StreamingCorpus(str(p), 128, 32)
    assert loader.measure_caps_stream(stream) == loader.measure_caps_rows(
        loader.StreamingCorpus(str(p), 128, 32)
    )


def test_count_distinct_tokens_engine_semantics():
    lines = [b"to be, or not to-be", b"to be, or not to-be", b"that\tis"]
    # strtok semantics: ',' '-' '\t' split; duplicates (incl. whole
    # duplicate lines) count once: to, be, or, not, that, is
    assert loader.count_distinct_tokens(lines) == 6
    assert loader.count_distinct_tokens([]) == 0
    assert loader.count_distinct_tokens([b"", b"  , "]) == 0
