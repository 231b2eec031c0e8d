"""ctypes bindings for the native ingest library (native/ingest.cpp).

Builds the shared object on first use with the system g++ (cached in
``native/build/`` under a name keyed by the source's SHA-256); callers go
through io/loader.load_rows which falls back to the pure-Python path if
the toolchain is unavailable.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import pathlib
import subprocess
import threading

import numpy as np

_NATIVE_DIR = pathlib.Path(__file__).resolve().parents[2] / "native"
_SRC = _NATIVE_DIR / "ingest.cpp"

_lock = threading.Lock()
_lib = None


def so_path() -> pathlib.Path:
    """``native/build/libingest-<sha12>.so``, keyed by the SOURCE's
    content: a copied checkout carries arbitrary mtimes (and possibly a
    stale untracked .so), so only a content key can say whether a built
    file matches ``ingest.cpp``."""
    sha = hashlib.sha256(_SRC.read_bytes()).hexdigest()[:12]
    return _NATIVE_DIR / "build" / f"libingest-{sha}.so"


def _build() -> pathlib.Path:
    so = so_path()
    if so.exists():
        return so
    so.parent.mkdir(parents=True, exist_ok=True)
    # Build to a private name, then rename: a concurrent builder (xdist
    # workers, serve pool) never loads a half-written file.
    tmp = so.with_name(f"{so.name}.tmp.{os.getpid()}")
    try:
        subprocess.run(
            ["g++", "-O3", "-shared", "-fPIC", "-o", str(tmp), str(_SRC)],
            check=True,
            capture_output=True,
        )
        os.replace(tmp, so)
    except (subprocess.CalledProcessError, FileNotFoundError) as e:
        # Surface as OSError so io/loader falls back to the Python path.
        raise OSError(f"native ingest build failed: {e}") from e
    return so


def available() -> bool:
    """Did (or does) the native library load?  io/loader's callers fall
    back to the Python path when it does not; chip_smoke.py prints which
    path ingest took."""
    try:
        _load()
    except OSError:
        return False
    return True


def _load() -> ctypes.CDLL:
    global _lib
    with _lock:
        if _lib is None:
            lib = ctypes.CDLL(str(_build()))
            lib.ingest_count_lines.restype = ctypes.c_long
            lib.ingest_count_lines.argtypes = [ctypes.c_char_p]
            lib.ingest_load_rows.restype = ctypes.c_long
            lib.ingest_load_rows.argtypes = [
                ctypes.c_char_p,
                ctypes.POINTER(ctypes.c_ubyte),
                ctypes.c_long,
                ctypes.c_long,
                ctypes.c_long,
                ctypes.c_long,
            ]
            lib.ingest_open.restype = ctypes.c_void_p
            lib.ingest_open.argtypes = [ctypes.c_char_p]
            lib.ingest_close.restype = None
            lib.ingest_close.argtypes = [ctypes.c_void_p]
            lib.ingest_window.restype = ctypes.c_long
            lib.ingest_window.argtypes = [
                ctypes.c_void_p,
                ctypes.POINTER(ctypes.c_long),
                ctypes.POINTER(ctypes.c_long),
                ctypes.POINTER(ctypes.c_ubyte),
                ctypes.c_long,
                ctypes.c_long,
                ctypes.c_long,
                ctypes.c_long,
            ]
            lib.ingest_measure_caps.restype = ctypes.c_long
            lib.ingest_measure_caps.argtypes = [
                ctypes.c_char_p,
                ctypes.c_long,
                ctypes.c_long,
                ctypes.c_long,
                ctypes.POINTER(ctypes.c_ubyte),
                ctypes.c_long,
                ctypes.POINTER(ctypes.c_long),
                ctypes.POINTER(ctypes.c_long),
            ]
            lib.ingest_read_tsv.restype = ctypes.c_long
            lib.ingest_read_tsv.argtypes = [
                ctypes.c_char_p,
                ctypes.POINTER(ctypes.c_ubyte),
                ctypes.POINTER(ctypes.c_int),
                ctypes.c_long,
                ctypes.c_long,
            ]
            lib.ingest_parse_edges.restype = ctypes.c_long
            lib.ingest_parse_edges.argtypes = [
                ctypes.c_char_p,
                ctypes.c_long,
                ctypes.POINTER(ctypes.c_int),
                ctypes.POINTER(ctypes.c_int),
                ctypes.c_long,
                ctypes.POINTER(ctypes.c_longlong),
            ]
            lib.ingest_count_lf.restype = ctypes.c_long
            lib.ingest_count_lf.argtypes = [ctypes.c_char_p, ctypes.c_long]
            _lib = lib
    return _lib


def measure_caps(
    path: str, width: int, line_start: int = -1, line_end: int = -1
) -> tuple[int, int]:
    """Single-pass (max token bytes, max tokens/line) over the
    width-truncated [line_start, line_end) slice — the native fast path
    behind io/loader.measure_caps_stream.  The delimiter set travels from
    config.FULL_DELIMITERS so it can never drift from the device
    tokenizer."""
    from locust_tpu.config import FULL_DELIMITERS

    lib = _load()
    delims = (ctypes.c_ubyte * len(FULL_DELIMITERS)).from_buffer_copy(
        FULL_DELIMITERS
    )
    max_tok = ctypes.c_long(0)
    max_per_line = ctypes.c_long(0)
    rc = lib.ingest_measure_caps(
        str(path).encode(),
        width,
        line_start,
        line_end,
        delims,
        len(FULL_DELIMITERS),
        ctypes.byref(max_tok),
        ctypes.byref(max_per_line),
    )
    if rc != 0:
        raise OSError(f"native measure_caps failed on {path!r}")
    return int(max_tok.value), int(max_per_line.value)


def count_lines(path: str) -> int:
    n = _load().ingest_count_lines(str(path).encode())
    if n < 0:
        raise OSError(f"native ingest failed to read {path!r}")
    return n


def load_rows(
    path: str, line_width: int, line_start: int = -1, line_end: int = -1
) -> np.ndarray:
    """File -> padded [rows, line_width] uint8, sliced [line_start, line_end)."""
    lib = _load()
    total = count_lines(path)
    start = max(line_start, 0) if line_start >= 0 else 0
    end = total if line_end < 0 else min(line_end, total)
    n_rows = max(end - start, 0)
    # The scanner writes every byte of the rows it returns.
    out = np.empty((n_rows, line_width), dtype=np.uint8)
    if n_rows == 0:
        return out
    wrote = lib.ingest_load_rows(
        str(path).encode(),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_ubyte)),
        n_rows,
        line_width,
        line_start,
        line_end,
    )
    if wrote < 0:
        raise OSError(f"native ingest failed to read {path!r}")
    return out[:wrote] if wrote < n_rows else out


def read_tsv(path: str, key_width: int) -> tuple[np.ndarray, np.ndarray]:
    """Native "key\\tvalue" TSV parse -> (padded key rows, int32 values).

    Two passes over the file (count, then fill) with a fixed 1MB buffer —
    semantics identical to io/serde.read_tsv's Python path (parity-tested).
    """
    lib = _load()

    def check(rc: int) -> int:
        if rc == -2:
            # Same exception class as the Python path's int32 check.
            raise OverflowError(f"TSV value in {path!r} does not fit int32")
        if rc < 0:
            raise OSError(f"native TSV read failed for {path!r}")
        return rc

    null_keys = ctypes.POINTER(ctypes.c_ubyte)()
    null_vals = ctypes.POINTER(ctypes.c_int)()
    n = check(
        lib.ingest_read_tsv(str(path).encode(), null_keys, null_vals, 0, key_width)
    )
    keys = np.zeros((n, key_width), dtype=np.uint8)
    values = np.zeros((n,), dtype=np.int32)
    if n:
        wrote = check(
            lib.ingest_read_tsv(
                str(path).encode(),
                keys.ctypes.data_as(ctypes.POINTER(ctypes.c_ubyte)),
                values.ctypes.data_as(ctypes.POINTER(ctypes.c_int)),
                n,
                key_width,
            )
        )
        if wrote < n:  # file shrank between passes
            keys, values = keys[:wrote], values[:wrote]
    return keys, values


def parse_edges(corpus: bytes):
    """A CLEAN SNAP-style edge list -> ``(src, dst, top)``: two int32
    arrays and the largest id as a Python int, in one native walk of the
    bytes; ``None`` for "not clean" (``ingest_parse_edges`` has the
    grammar: ``plan.compile._edges_clean``'s, and numbers of at most 18
    digits).  ``top`` may lie past int32 — the arrays then hold narrowed
    values and the caller must refuse them (``plan.compile._check_top_id``).
    The outputs are sized by the LF count: pages a parse never writes
    are never touched."""
    lib = _load()
    cap = lib.ingest_count_lf(corpus, len(corpus)) + 1
    src = np.empty(cap, np.int32)
    dst = np.empty(cap, np.int32)
    top = ctypes.c_longlong(0)
    int_p = ctypes.POINTER(ctypes.c_int)
    n = lib.ingest_parse_edges(
        corpus,
        len(corpus),
        src.ctypes.data_as(int_p),
        dst.ctypes.data_as(int_p),
        cap,
        ctypes.byref(top),
    )
    if n < 0:
        return None
    return src[:n], dst[:n], int(top.value)


def iter_blocks(
    path: str,
    line_width: int,
    block_lines: int,
    line_start: int = -1,
    line_end: int = -1,
):
    """Yield ``[<=block_lines, line_width]`` row blocks via the native
    windowed scanner: the file opened ONCE (``ingest_open``: one
    descriptor and one 1 MB read buffer), a window of it scanned a block
    (``ingest_window``), closed when the generator ends or is closed —
    nothing of the file outlives the consumer.  A path that is no regular
    file (a FIFO, ``/dev/stdin``) is an ``OSError`` before the first
    block.  Every block is a fresh array: the default path keeps a group
    of them queued ahead of the device (``engine.timed_run``)."""
    lib = _load()
    handle = lib.ingest_open(str(path).encode())
    if not handle:
        raise OSError(f"native ingest cannot open {path!r}")
    offset = ctypes.c_long(0)
    line_no = ctypes.c_long(0)
    try:
        while True:
            # The scanner writes every byte of ``out``.
            out = np.empty((block_lines, line_width), dtype=np.uint8)
            wrote = lib.ingest_window(
                handle,
                ctypes.byref(offset),
                ctypes.byref(line_no),
                out.ctypes.data_as(ctypes.POINTER(ctypes.c_ubyte)),
                block_lines,
                line_width,
                line_start,
                line_end,
            )
            if wrote < 0:
                raise OSError(f"native ingest failed to read {path!r}")
            if wrote == 0:
                return
            if wrote < block_lines:
                # A window comes back short only at the end of the file or
                # of the slice: the call that would find nothing left is
                # spared (a third of a two-block job's reads).
                yield out[:wrote]
                return
            yield out
    finally:
        lib.ingest_close(handle)
