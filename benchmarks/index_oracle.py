"""The inverted index of a text file: the oracle of ``index-zipf-100MB``.

The benchmark's OWN copy, as ``rmat_edges.py`` holds PageRank's and
``records.py`` the record sort's: nothing here imports the program or jax,
so no later PR can move the measure by editing ``locust_tpu/``
(``locust_tpu/index_reference.py`` is the program's copy of the same
semantics, and ``benchmarks/tests/test_index_cell.py`` holds the two equal).

PUMA's Inverted-Index: the map emits ``<word, docId>`` for every word of a
document, the reduce lists each word's distinct docIds.  Here a document is
``lines_per_doc`` consecutive lines of the file (line ``i`` belongs to
document ``i // lines_per_doc``), a line is split on the reference's
delimiters as every text cell's oracle splits it (``yardstick.DELIMITERS``:
runs collapse, empties are dropped), and the table is one
``word<TAB>d1,d2,...<LF>`` line a word — the words in byte order, a word's
documents ascending, each once.

``Oracle`` keeps what the driver compares and what the roofline prices: the
rendered table, and the counts of the DATA — tokens (every emitted pair),
pairs (the distinct ones: the postings), words, documents.
"""

from __future__ import annotations

import dataclasses
import re

import numpy as np

# The reference's delimiter set (strtok semantics), written out: equal to
# yardstick.DELIMITERS and to locust_tpu.config.FULL_DELIMITERS
# (tests/test_index_cell.py).
DELIMITERS = b" ,.-;:'()\"\t\x00\n\r"
_SPLIT = re.compile(b"[" + re.escape(DELIMITERS) + b"]+")


@dataclasses.dataclass
class Oracle:
    table: bytes      # what the CLI must print
    tokens: int       # words of the text, repeats counted: the pairs the map emits
    pairs: int        # distinct (word, document) pairs: the postings
    words: int        # distinct words: the table's lines
    documents: int
    lines: int

    def counts(self) -> dict:
        return {"tokens": self.tokens, "pairs": self.pairs, "words": self.words,
                "documents": self.documents, "lines": self.lines}


def file_lines(path: str) -> list[bytes]:
    """The file's lines: split at LF, a last line without one counted, a CR
    before the LF no part of the line."""
    with open(path, "rb") as f:
        data = f.read()
    lines = data.split(b"\n")
    if lines and lines[-1] == b"":
        lines.pop()
    return [ln[:-1] if ln.endswith(b"\r") else ln for ln in lines]


def inverted_index(lines, lines_per_doc: int) -> tuple[dict[bytes, list[int]], int]:
    """``({word: its documents, ascending}, tokens)`` of ``lines``.  A
    document's lines are split as one text (a line's end is a delimiter, so
    that is each line split alone) and its words go through a ``set``;
    documents are visited in order, so each list is ascending as appended."""
    index: dict[bytes, list[int]] = {}
    tokens = 0
    for doc, at in enumerate(range(0, len(lines), lines_per_doc)):
        found = _SPLIT.split(b"\n".join(lines[at:at + lines_per_doc]))
        tokens += len(found) - found.count(b"")
        for word in set(found):
            if word:
                index.setdefault(word, []).append(doc)
    return index, tokens


def render(index: dict[bytes, list[int]]) -> bytes:
    return b"".join(
        word + b"\t" + ",".join(map(str, index[word])).encode() + b"\n"
        for word in sorted(index)
    )


def oracle(path: str, lines_per_doc: int) -> Oracle:
    lines = file_lines(path)
    index, tokens = inverted_index(lines, lines_per_doc)
    return Oracle(table=render(index), tokens=tokens,
                  pairs=sum(map(len, index.values())), words=len(index),
                  documents=-(-len(lines) // lines_per_doc), lines=len(lines))


def parse(table: bytes):
    """A printed table as arrays ``(words, offsets, postings)``: word ``w``
    (``words[w]``, bytes) has the documents
    ``postings[offsets[w]:offsets[w + 1]]``.  In numpy over the whole
    buffer, for tables of tens of MB: the TABs and LFs found, the doc ids
    read by one ``numpy.fromstring`` over the table with the words blanked.
    Raises ``ValueError`` on a table of another shape."""
    if not table:
        return [], np.zeros(1, np.int64), np.zeros(0, np.int64)
    buf = np.frombuffer(table, np.uint8)
    tabs = np.flatnonzero(buf == 9)
    ends = np.flatnonzero(buf == 10)
    starts = np.concatenate([[0], ends[:-1] + 1])
    if (buf[-1] != 10 or tabs.size != ends.size
            or not ((starts < tabs) & (tabs + 1 < ends)).all()):
        raise ValueError("the table is not word<TAB>d1,d2,...<LF> a line")
    words = [table[a:b] for a, b in zip(starts.tolist(), tabs.tolist())]
    # 1 inside a word and its TAB, 0 inside the doc ids and their LF
    edge = np.zeros(buf.size + 1, np.int8)
    edge[starts] += 1
    edge[tabs + 1] -= 1
    in_word = np.cumsum(edge[:-1], dtype=np.int8).astype(bool)
    ids_text = np.where(in_word, np.uint8(32), buf)
    if not np.isin(ids_text, np.frombuffer(b"0123456789, \n", np.uint8)).all():
        raise ValueError("a doc id that is not a number")
    commas = np.concatenate([[0], np.cumsum(buf == 44)])
    counts = commas[ends] - commas[tabs] + 1
    postings = np.fromstring(
        ids_text.tobytes().replace(b",", b" "), dtype=np.int64, sep=" ")
    offsets = np.concatenate([[0], np.cumsum(counts)])
    if postings.size != offsets[-1]:
        raise ValueError("an empty doc id in the table")
    return words, offsets, postings


def first_difference(got: bytes, want: bytes) -> str:
    """One line on where a printed table leaves the oracle's, from both as
    arrays."""
    try:
        words, offsets, postings = parse(got)
    except ValueError as e:
        return f"the printed table cannot be read: {e}"
    want_words, want_offsets, want_postings = parse(want)
    if len(words) != len(want_words):
        return f"{len(words)} words printed, the text has {len(want_words)}"
    if words != want_words:
        at = next(i for i, (a, b) in enumerate(zip(words, want_words)) if a != b)
        return f"word {at} is {words[at][:40]!r}, the oracle has {want_words[at][:40]!r}"
    if postings.size != want_postings.size:
        counts, want_counts = np.diff(offsets), np.diff(want_offsets)
        at = int(np.flatnonzero(counts != want_counts)[0])
        return (f"{postings.size} postings printed, the text has {want_postings.size}: the "
                f"first word that differs, {words[at][:40]!r}, lists {counts[at]} documents "
                f"for {want_counts[at]}")
    at = int(np.flatnonzero(postings != want_postings)[0])
    word = int(np.searchsorted(offsets, at, side="right")) - 1
    return (f"posting {at} (of word {words[word][:40]!r}) is document {postings[at]}, "
            f"the oracle has {want_postings[at]}")
