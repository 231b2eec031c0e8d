"""Corpus ingest: text file -> NUL-padded uint8 line tensors.

Host-side replacement for ``loadFile`` (reference MapReduce/src/main.cu:40-64):
reads a text file line-by-line honoring a ``[line_start, line_end)`` slice for
per-node sharding (main.cu:47-54) and produces the padded ``[lines, width]``
uint8 tensor the device pipeline consumes.

Deliberate fixes vs the reference (SURVEY.md Appendix A):
  Q1 — the reference drops the final line (``*length = line_num - line_start``
       with a 0-based max index, main.cu:63); we count correctly.
  — no MAX_LINES_FILE_READ=5800 hard cap (main.cu:18): ingest streams; the
    engine blocks the corpus downstream.

A native C++ fast path (native/ingest.cpp, ctypes-loaded) handles large
corpora; this module is the always-available pure-Python fallback and the
single public API for both.
"""

from __future__ import annotations

import numpy as np

from locust_tpu import obs
from locust_tpu.core import bytes_ops


def load_lines(
    path: str, line_start: int = -1, line_end: int = -1
) -> list[bytes]:
    """Read lines, applying the reference's [start, end) node-shard slice.

    ``line_start/line_end of -1`` means "whole file" (reference CLI default,
    main.cu:369-374).  Out-of-range ends clamp; start beyond EOF yields [].

    Line semantics (canonical for every reader in this package, matching
    the reference's getline loop, main.cu:43-61): records split on ``\\n``
    ONLY; exactly one trailing ``\\r`` is stripped (CRLF).  A lone ``\\r``
    is data, not a separator — bytes.splitlines would disagree, which is
    why it is not used here.
    """
    with open(path, "rb") as f:
        data = f.read()
    lines = data.split(b"\n")
    if lines and lines[-1] == b"":
        lines.pop()  # trailing newline, not an empty final record
    lines = [ln[:-1] if ln.endswith(b"\r") else ln for ln in lines]
    if line_start < 0 and line_end < 0:
        return lines
    start = max(line_start, 0)
    end = len(lines) if line_end < 0 else min(line_end, len(lines))
    return lines[start:end]


def measure_caps(lines) -> tuple[int, int]:
    """One host pass: (max token bytes, max tokens per line) over ``lines``.

    Feeds lossless capacity auto-sizing (``auto_caps`` below):
    ``key_width`` / ``emits_per_line`` set to these maxima change NOTHING
    about the output table relative to any larger caps — no token is
    truncated or dropped that the larger config would keep — they only
    shrink the fixed-shape arrays every sort and reduce pays for.

    Splits on the ENGINE's full delimiter set — ``DELIMITERS`` plus
    ``\\x00\\n\\r`` (core/bytes_ops.delimiter_mask) — not just the strtok
    set: a mid-line ``\\r`` or embedded NUL is data to the loader but a
    token boundary to the device tokenizer, and undercounting tokens
    here would let an auto-sized ``emits_per_line`` drop real emits.
    Deduplicates first: replicated corpora (the bench's) measure each
    unique line once.
    """
    import re

    from locust_tpu.config import FULL_DELIMITERS

    pat = re.compile(b"[" + re.escape(FULL_DELIMITERS) + b"]+")
    max_tok, max_per_line = 1, 1
    for ln in set(lines):
        toks = [t for t in pat.split(ln) if t]
        if toks:
            max_per_line = max(max_per_line, len(toks))
            max_tok = max(max_tok, max(len(t) for t in toks))
    return max_tok, max_per_line


def size_caps(
    max_tok: int, max_per_line: int, key_cap: int, emits_cap: int
) -> tuple[int, int]:
    """The one lossless sizing rule: measured maxima, lane-rounded key
    width (floor 8), never above the caller's caps."""
    kw = min(key_cap, max(8, -(-max_tok // 4) * 4))
    epl = min(emits_cap, max_per_line)
    return kw, epl


def count_distinct_tokens(lines) -> int:
    """Exact distinct-token count under the ENGINE's tokenization
    (FULL_DELIMITERS split, empties dropped), deduplicating lines first
    so replicated corpora count each unique line once.

    Upper-bounds the engine's distinct-key count: per-line emit
    overflow can only DROP tokens, and key-width truncation never
    applies when paired with ``auto_caps`` (key_width >= max token).  A
    table sized >= this count therefore cannot truncate.
    """
    import re

    from locust_tpu.config import FULL_DELIMITERS

    pat = re.compile(b"[" + re.escape(FULL_DELIMITERS) + b"]+")
    toks: set[bytes] = set()
    for ln in set(lines):
        toks.update(t for t in pat.split(ln) if t)
    return len(toks)


def auto_caps(lines, key_cap: int, emits_cap: int) -> tuple[int, int, int, int]:
    """Lossless capacity sizing: the policy behind ``--auto-caps``
    (cli.py).

    Returns ``(key_width, emits_per_line, max_tok, max_per_line)`` with
    the caps at their measured lossless floors — max token bytes rounded
    up to a uint32 lane multiple (floor 8), max tokens/line — but never
    above the caller's ``key_cap`` / ``emits_cap``, so the output table
    is byte-identical to a run at the original caps.
    """
    max_tok, max_per_line = measure_caps(lines)
    kw, epl = size_caps(max_tok, max_per_line, key_cap, emits_cap)
    return kw, epl, max_tok, max_per_line


def measure_caps_rows(row_blocks) -> tuple[int, int]:
    """Bounded-memory (max token bytes, max tokens per line) over an
    iterable of padded ``[n, width]`` uint8 row blocks.

    The streaming analog of ``measure_caps`` — vectorized numpy per
    block, no dedup set, O(block) memory — so ``--auto-caps`` composes
    with ``--stream`` on corpora that don't fit RAM.  Tokenizes exactly
    as the device does: the full delimiter set incl. NUL (so the padding
    contributes nothing), scanning column-by-column (width ~128 steps of
    whole-block vector ops).
    """
    from locust_tpu.config import FULL_DELIMITERS

    lut = np.zeros(256, dtype=bool)
    for b in FULL_DELIMITERS:
        lut[b] = True
    max_tok, max_per_line = 1, 1
    for blk in row_blocks:
        rows = np.asarray(blk, dtype=np.uint8)
        if rows.size == 0:
            continue
        is_delim = lut[rows]                        # [n, w] bool
        starts = ~is_delim
        starts[:, 1:] &= is_delim[:, :-1]           # non-delim after delim
        max_per_line = max(max_per_line, int(starts.sum(axis=1).max()))
        run = np.zeros(rows.shape[0], dtype=np.int32)
        longest = np.zeros(rows.shape[0], dtype=np.int32)
        for c in range(rows.shape[1]):              # width steps, vector rows
            run = np.where(is_delim[:, c], 0, run + 1)
            np.maximum(longest, run, out=longest)
        max_tok = max(max_tok, int(longest.max()))
    return max_tok, max_per_line


def measure_caps_stream(stream) -> tuple[int, int]:
    """Caps measure for a ``StreamingCorpus``: native single-pass scan
    (``ingest_measure_caps`` — ~12x the numpy block path at 512MB scale)
    when the toolchain is available and the stream allows the native
    path (``use_native``, the same opt-out its block reader honors),
    else ``measure_caps_rows`` over the staged blocks.  Both measure the
    width-truncated [line_start, line_end) view; parity is pinned by
    tests/test_io.py."""
    if getattr(stream, "use_native", True):
        try:
            from locust_tpu.io import native_ingest

            return native_ingest.measure_caps(
                stream.path, stream.line_width,
                stream.line_start, stream.line_end,
            )
        except (ImportError, OSError):
            pass
    return measure_caps_rows(stream)


class _PrefetchError:
    """Wraps an exception crossing the reader thread (a private type no
    legitimate block iterator yields, so the isinstance check in
    ``prefetch_blocks`` cannot misfire on real items)."""

    def __init__(self, exc: BaseException):
        self.exc = exc


def prefetch_blocks(blocks, depth: int = 2):
    """Iterate ``blocks`` with a daemon reader thread ``depth`` items ahead.

    Streaming folds alternate host file reads with device dispatches; the
    reader thread overlaps the next window's read+pad with the current
    fold's device time.  Semantically transparent: same items, same
    order, exceptions re-raised at the consuming ``next()``.  Memory grows
    by at most ``depth`` staged blocks — 2 for a loop that pulls a block a
    dispatch; ``timed_run``, which pulls a whole group at once and then
    nothing while the group's stages run, asks for a group's worth.

    Telemetry (the consumer's tracer, which the reader thread records
    into as well: ``obs`` tracers are thread-local): a pull that found
    the queue empty is an ``engine.ingest.wait`` span, and the blocks
    handed over count into ``engine.ingest.blocks_ahead`` (read before
    they were asked for) or ``engine.ingest.blocks_waited``.

    Abandoning the generator early (consumer raised mid-loop, e.g. a
    shuffle-overflow RuntimeError) stops the reader promptly: its puts
    poll a stop event, and the generator's ``finally`` sets it and drains
    the queue — no thread, source iterator, or staged blocks outlive the
    consumer (a leak per retry would accumulate in bench's TPU retry
    loop).
    """
    import queue
    import threading

    q: queue.Queue = queue.Queue(maxsize=max(1, depth))
    end = object()
    stop = threading.Event()
    tracer = obs.current()

    def put_or_stop(item) -> bool:
        while not stop.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def reader():
        try:
            with obs.scoped(tracer):
                for b in blocks:
                    if not put_or_stop(b):
                        return
            put_or_stop(end)
        except BaseException as e:  # noqa: BLE001 - relayed to consumer
            put_or_stop(_PrefetchError(e))

    t = threading.Thread(target=reader, daemon=True)
    t.start()
    handed = [0, 0]  # blocks that were ahead of their pull, that were waited for
    try:
        while True:
            try:
                item, waited = q.get_nowait(), False
            except queue.Empty:
                with obs.span("engine.ingest.wait"):
                    item, waited = q.get(), True
            if item is end:
                return
            if isinstance(item, _PrefetchError):
                raise item.exc
            handed[waited] += 1
            yield item
    finally:
        obs.metric_inc("engine.ingest.blocks_ahead", handed[0])
        obs.metric_inc("engine.ingest.blocks_waited", handed[1])
        stop.set()
        # Drain until the reader has exited: a single drain can race a
        # put that was already past the stop check, leaving one staged
        # block referenced by the queue until the daemon thread's next
        # loop iteration (ADVICE r3).  When the reader is blocked on a
        # put it polls stop every 0.1s, so a few join attempts suffice;
        # BOUNDED because a reader stalled inside next(blocks) (wedged
        # host read) never observes stop, and an unbounded join here
        # would trade a one-block reference for a permanent hang of the
        # consumer's own exception path.
        for _ in range(5):
            try:
                while True:
                    q.get_nowait()
            except queue.Empty:
                pass
            if not t.is_alive():
                break
            t.join(timeout=0.2)
        try:
            while True:
                q.get_nowait()
        except queue.Empty:
            pass


def read_spans(blocks):
    """Iterate ``blocks`` with every pull from it — a block read, split
    and padded, or the end of the file found — under an
    ``engine.ingest.read`` span of the thread that pulls.  The span
    closes before the block is handed on: a generator suspended inside it
    would bill the consumer's work to the read."""
    it, end = iter(blocks), object()
    while True:
        with obs.span("engine.ingest.read"):
            blk = next(it, end)
        if blk is end:
            return
        yield blk


def count_lines(path: str) -> int:
    """Streaming line count (O(1) memory; multi-GB corpora are fine).

    The canonical trailing-fragment rule (Q1 semantics): a final line
    without a newline still counts.  Single source of truth — the
    distributor master and the native ingest parity tests both use this
    (two drifting copies).
    """
    n = 0
    last = b"\n"
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            n += chunk.count(b"\n")
            last = chunk[-1:]
    if last != b"\n":
        n += 1
    return n


def load_rows(
    path: str,
    line_width: int,
    line_start: int = -1,
    line_end: int = -1,
    use_native: bool = True,
) -> np.ndarray:
    """File -> padded ``[lines, line_width]`` uint8 rows (native if built)."""
    if use_native:
        try:
            from locust_tpu.io import native_ingest

            return native_ingest.load_rows(path, line_width, line_start, line_end)
        except (ImportError, OSError):
            pass
    return bytes_ops.strings_to_rows(
        load_lines(path, line_start, line_end), line_width
    )


class RecordSource:
    """Fixed-length binary records — a file of them (``open``) or bytes in
    memory — as the record sort takes them: no line splitting, no
    tokenizer, every byte of every record.

    A size that is no whole number of records, or no record at all, is a
    ``ValueError`` HERE, before anything is read: a cut record is never
    passed on in silence (what the line loader does to an over-long line
    is ROADMAP A2's lesson).  ``blocks(rows)`` yields the records ``rows``
    at a time as flat ``uint32`` arrays of ``rows x ceil(record_bytes / 4)``
    words.  A file is MAPPED, not read: a whole block of a width that is a
    multiple of four is a view of the page cache's own pages, so the
    transfer to the device is the one pass over the bytes (on a v5e's host
    a read into a fresh array first cost 0.5-1.0 s of a job's 0.1 s load,
    PERF.md section 6, PR 35).  The last, short block and every block of
    another width are copies: zero-padded to ``rows``, each record to
    whole words.
    """

    def __init__(self, record_bytes: int, nbytes: int,
                 path: str | None = None, data: bytes | None = None):
        what = path or "input"
        if record_bytes < 1:
            raise ValueError(f"record_bytes must be >= 1, got {record_bytes}")
        if nbytes == 0:
            raise ValueError(f"{what}: no records (0 bytes)")
        if nbytes % record_bytes:
            raise ValueError(
                f"{what}: {nbytes} bytes is no whole number of "
                f"{record_bytes}-byte records ({nbytes % record_bytes} bytes "
                "over); refusing to cut a record"
            )
        self.record_bytes = record_bytes
        self.nbytes = nbytes
        self.n_records = nbytes // record_bytes
        self._path, self._data = path, data

    @classmethod
    def open(cls, path: str, record_bytes: int) -> "RecordSource":
        import os

        return cls(record_bytes, os.path.getsize(path), path=path)

    @classmethod
    def from_bytes(cls, data: bytes, record_bytes: int) -> "RecordSource":
        return cls(record_bytes, len(data), data=data)

    def blocks(self, rows: int):
        rb = self.record_bytes
        words = -(-rb // 4)
        raw = (np.memmap(self._path, np.uint8, "r") if self._path is not None
               else np.frombuffer(self._data, np.uint8))
        if raw.size != self.nbytes:
            raise OSError(f"{self._path}: {raw.size} bytes now, {self.nbytes} "
                          "when the sort began: the file changed under it")
        for start in range(0, self.n_records, rows):
            n = min(rows, self.n_records - start)
            with obs.span("sort.read", bytes=n * rb):
                chunk = raw[start * rb:(start + n) * rb]
                if n == rows and 4 * words == rb:
                    block = chunk.view(np.uint32)
                else:
                    block = np.zeros(rows * words, np.uint32)
                    block.view(np.uint8).reshape(rows, 4 * words)[:n, :rb] = (
                        chunk.reshape(n, rb)
                    )
            yield block


class StreamingCorpus:
    """Iterate ``[<=block_lines, line_width]`` row blocks of a file in
    bounded memory.

    ``load_rows`` materializes the whole corpus — fine for hamlet, fatal
    for the 1GB+ north star (BASELINE.json).  This reader holds a block
    at a time, the streaming upgrade of the reference's whole-file
    ``loadFile`` slicing (reference MapReduce/src/main.cu:40-64).  Uses
    the native windowed scanner (native/ingest.cpp: the file opened once,
    ``ingest_window`` a block) when built and the path is a regular file,
    else a pure-Python chunked read that holds one ``chunk_bytes`` window
    plus one carried partial line; both honor the ``[line_start,
    line_end)`` node-shard slice.

    A line longer than ``chunk_bytes`` is truncated to ``line_width``
    (the device contract anyway) and its remainder skipped — progress is
    guaranteed for any input.

    Iterating yields numpy arrays; every block except possibly the last
    has exactly ``block_lines`` rows.  ``fingerprint()`` hashes identity
    metadata + first window content for checkpoint/resume without a full
    read.
    """

    def __init__(
        self,
        path: str,
        line_width: int,
        block_lines: int,
        line_start: int = -1,
        line_end: int = -1,
        chunk_bytes: int = 32 << 20,
        use_native: bool = True,
    ):
        if block_lines < 1 or line_width < 1:
            raise ValueError("block_lines and line_width must be >= 1")
        self.path = path
        self.line_width = line_width
        self.block_lines = block_lines
        self.line_start = line_start
        self.line_end = line_end
        self.chunk_bytes = max(chunk_bytes, 1 << 16)
        self.use_native = use_native

    def fingerprint(self) -> str:
        """Cheap corpus identity: path + size + mtime + head digest."""
        import hashlib
        import os

        st = os.stat(self.path)
        h = hashlib.sha256()
        with open(self.path, "rb") as f:
            h.update(f.read(1 << 20))
        return (
            f"{os.path.abspath(self.path)}:{st.st_size}:{st.st_mtime_ns}:"
            f"{h.hexdigest()[:16]}:{self.line_start}:{self.line_end}"
        )

    def __iter__(self):
        if self.use_native:
            # Fall back to the Python reader ONLY if the native path fails
            # before producing anything; a mid-stream error after blocks
            # were already yielded must propagate — restarting from the top
            # would silently double-count every already-folded block.
            started = False
            try:
                from locust_tpu.io import native_ingest

                for blk in native_ingest.iter_blocks(
                    self.path,
                    self.line_width,
                    self.block_lines,
                    self.line_start,
                    self.line_end,
                ):
                    started = True
                    yield blk
                return
            except (ImportError, OSError):
                if started:
                    raise
        yield from self._iter_python()

    def _iter_python(self):
        start = max(self.line_start, 0) if self.line_start >= 0 else 0
        end = self.line_end if self.line_end >= 0 else None
        line_no = 0
        pending: list[bytes] = []
        carry = b""
        with open(self.path, "rb") as f:
            while True:
                chunk = f.read(self.chunk_bytes)
                if not chunk:
                    break
                data = carry + chunk
                lines = data.split(b"\n")
                carry = lines.pop()  # partial (or empty) trailing piece
                if len(carry) > self.line_width + 1:
                    # Keep only the prefix the device can see (the row is
                    # truncated to line_width anyway); bounds memory for
                    # pathologically long lines while the rest streams past.
                    # ONE byte more than the row: cut to the row itself, a
                    # '\r' at the cut would become the line's last byte
                    # and be stripped as a CRLF's, where it is data.
                    carry = carry[: self.line_width + 1]
                for ln in lines:
                    if end is not None and line_no >= end:
                        break
                    if line_no >= start:
                        pending.append(ln[:-1] if ln.endswith(b"\r") else ln)
                    line_no += 1
                    if len(pending) >= self.block_lines:
                        yield bytes_ops.strings_to_rows(
                            pending[: self.block_lines], self.line_width
                        )
                        pending = pending[self.block_lines :]
                if end is not None and line_no >= end:
                    carry = b""
                    break
        if carry and (end is None or line_no < end):
            if line_no >= start:
                pending.append(carry[:-1] if carry.endswith(b"\r") else carry)
        while pending:
            yield bytes_ops.strings_to_rows(
                pending[: self.block_lines], self.line_width
            )
            pending = pending[self.block_lines :]
