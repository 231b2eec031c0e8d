"""The plain reference of the inverted index: what ``python -m locust_tpu
index FILE --lines-per-doc K`` must print, computed the straightforward way
on the host.

Independent of the code under test: Python sets and numpy, no jax, nothing
of ``locust_tpu`` (it lies beside ``pagerank_reference.py`` and
``records_reference.py``, outside ``apps/``, whose package imports jax).
The semantics are PUMA's Inverted-Index, to the letter of
``apps/inverted_index.py`` and ``cli_apps.py``:

* line ``i`` of the file belongs to document ``i // lines_per_doc``;
* a line is split on the reference's delimiters (strtok semantics: runs
  collapse, empties are dropped), and every word of it is a posting of its
  document;
* the result gives every word of the file once, with the documents that
  hold it, each once, ascending;
* printed, that is one ``word<TAB>d1,d2,...<LF>`` line a word, the words in
  byte order.

It knows no widths: where the program's fixed widths cut a line, a key or
a line's emits, the program says so on stderr and its table differs from
this one.

The benchmark keeps its own copy (``benchmarks/index_oracle.py``), as
``benchmarks/rmat_edges.py`` keeps PageRank's.
"""

from __future__ import annotations

import re

import numpy as np

# The reference's delimiter set, written out: this module imports nothing of
# the program.  Equal to locust_tpu.config.FULL_DELIMITERS, which
# tests/test_index_cli.py asserts.
DELIMITERS = b" ,.-;:'()\"\t\x00\n\r"
_SPLIT = re.compile(b"[" + re.escape(DELIMITERS) + b"]+")


def inverted_index(lines, lines_per_doc: int = 1) -> dict[bytes, list[int]]:
    """``{word: its documents, distinct and ascending}`` of ``lines`` (an
    iterable of ``bytes``, a line each)."""
    holds: dict[bytes, set[int]] = {}
    for i, line in enumerate(lines):
        doc = i // lines_per_doc
        for word in _SPLIT.split(line):
            if word:
                holds.setdefault(word, set()).add(doc)
    return {word: sorted(docs) for word, docs in holds.items()}


def file_lines(path: str) -> list[bytes]:
    """The lines of a file as the CLI counts them: split at LF, a last line
    without one counted, a CR before the LF no part of the line."""
    with open(path, "rb") as f:
        data = f.read()
    lines = data.split(b"\n")
    if lines and lines[-1] == b"":
        lines.pop()
    return [ln[:-1] if ln.endswith(b"\r") else ln for ln in lines]


def render(index: dict[bytes, list[int]]) -> bytes:
    """The table the CLI prints: ``word<TAB>d1,d2,...<LF>``, words in byte
    order."""
    return b"".join(
        word + b"\t" + ",".join(map(str, index[word])).encode() + b"\n"
        for word in sorted(index)
    )


def parse(table: bytes) -> tuple[list[bytes], np.ndarray, np.ndarray]:
    """The CLI's table back as ``(words, offsets, postings)``: word ``w``'s
    documents are ``postings[offsets[w]:offsets[w + 1]]`` (int64 both).
    A line that is not ``word<TAB>numbers`` is a ``ValueError``."""
    words, counts, docs = [], [], []
    for n, line in enumerate(table.split(b"\n")[:-1]):
        word, tab, rest = line.partition(b"\t")
        if not (word and tab and rest):
            raise ValueError(f"line {n + 1} is not word<TAB>d1,d2,...: {line[:60]!r}")
        words.append(word)
        docs.append(rest)
        counts.append(rest.count(b",") + 1)
    if not table.endswith(b"\n") and table:
        raise ValueError("the table does not end with a line feed")
    postings = (np.array(b",".join(docs).split(b","), dtype=np.int64)
                if docs else np.zeros(0, np.int64))
    offsets = np.concatenate([[0], np.cumsum(counts, dtype=np.int64)])
    if postings.size != offsets[-1]:
        raise ValueError("an empty doc id in the table")
    return words, offsets, postings
