"""TF-IDF app vs a pure-Python oracle (composite-key generality check)."""

import math
import re

import numpy as np
import pytest

from locust_tpu.apps.tfidf import build_tfidf, term_doc_counts
from locust_tpu.config import FULL_DELIMITERS, EngineConfig

_PAT = re.compile(b"[" + re.escape(FULL_DELIMITERS) + b"]+")


def _oracle_tf(lines, doc_ids, emits_per_line, key_width=32):
    tf = {}
    for ln, doc in zip(lines, doc_ids):
        toks = [t for t in _PAT.split(ln) if t][:emits_per_line]
        for t in toks:
            pair = (t[:key_width], int(doc))
            tf[pair] = tf.get(pair, 0) + 1
    return tf


LINES = [
    b"to be or not to be",
    b"that is the question",
    b"to be, to sleep; to dream",
    b"the dream of the question",
    b"sleep",
]
# Two lines per document (doc = line sharding unit).
DOCS = np.array([0, 0, 1, 1, 2], dtype=np.int32)


@pytest.mark.parametrize("mode", ["hash", "hashp2", "hashp1", "lex", "hasht"])
def test_term_doc_counts_oracle_exact(mode):
    cfg = EngineConfig(block_lines=2, line_width=64, emits_per_line=8,
                       sort_mode=mode)
    got = term_doc_counts(LINES, DOCS, cfg)
    assert got == _oracle_tf(LINES, DOCS, 8)


def test_term_doc_counts_nul_heavy_doc_ids():
    """Doc ids whose big-endian bytes contain NULs (256, 65536) must
    survive the host decode — the to_host_pairs NUL-strip pitfall."""
    docs = np.array([256, 256, 65536, 65536, 7], dtype=np.int32)
    cfg = EngineConfig(block_lines=4, line_width=64, emits_per_line=8)
    got = term_doc_counts(LINES, docs, cfg)
    assert got == _oracle_tf(LINES, docs, 8)
    assert any(d == 65536 for _, d in got)


def test_build_tfidf_scores():
    cfg = EngineConfig(block_lines=4, line_width=64, emits_per_line=8)
    scores = build_tfidf(LINES, DOCS, cfg)
    tf = _oracle_tf(LINES, DOCS, 8)
    df = {}
    for w, _ in tf:
        df[w] = df.get(w, 0) + 1
    n_docs = 3
    want = {
        (w, d): c * math.log(n_docs / df[w]) for (w, d), c in tf.items()
    }
    assert set(scores) == set(want)
    for pair in want:
        assert scores[pair] == pytest.approx(want[pair])
    # "the" appears in docs 0 and 1 of 3 -> positive idf; a word in every
    # doc would score 0; "question" in 2 docs same as "the".
    assert scores[(b"sleep", 2)] > 0


def test_negative_doc_ids_rejected():
    with pytest.raises(ValueError, match="doc ids must be >= 0"):
        term_doc_counts(LINES, np.array([0, 1, -1, 2, 3], np.int32))


def test_emit_overflow_raises_by_default():
    cfg = EngineConfig(block_lines=4, line_width=64, emits_per_line=2)
    with pytest.raises(ValueError, match="MISSING"):
        term_doc_counts(LINES, DOCS, cfg)
    # allow_overflow downgrades to a warning and returns the partial table.
    got = term_doc_counts(LINES, DOCS, cfg, allow_overflow=True)
    assert got == _oracle_tf(LINES, DOCS, 2)


def test_pairs_capacity_exceeded_raises():
    cfg = EngineConfig(block_lines=8, line_width=64, emits_per_line=8)
    with pytest.raises(ValueError, match="pairs_capacity"):
        term_doc_counts(LINES, DOCS, cfg, pairs_capacity=4)


def test_multi_block_fold_matches_single_block():
    lines = LINES * 7
    docs = np.arange(len(lines), dtype=np.int32) // 2
    small = EngineConfig(block_lines=3, line_width=64, emits_per_line=8)
    big = EngineConfig(block_lines=64, line_width=64, emits_per_line=8)
    assert term_doc_counts(lines, docs, small, pairs_capacity=256) == (
        term_doc_counts(lines, docs, big, pairs_capacity=256)
    )


def test_stream_matches_in_memory():
    from locust_tpu.apps.tfidf import term_doc_counts_stream

    lines = LINES * 9
    docs = (np.arange(len(lines)) // 4).astype(np.int32)
    cfg = EngineConfig(block_lines=4, line_width=64, emits_per_line=8)
    want = term_doc_counts(lines, docs, cfg, pairs_capacity=512)

    from locust_tpu.core import bytes_ops

    rows = bytes_ops.strings_to_rows(lines, cfg.line_width)

    def chunks():
        for i in range(0, rows.shape[0], cfg.block_lines):
            yield rows[i : i + cfg.block_lines], docs[i : i + cfg.block_lines]

    got = term_doc_counts_stream(chunks(), cfg, pairs_capacity=512)
    assert got == want


def test_stream_rejects_negative_ids_and_overflow():
    from locust_tpu.apps.tfidf import term_doc_counts_stream
    from locust_tpu.core import bytes_ops

    cfg = EngineConfig(block_lines=4, line_width=64, emits_per_line=2)
    rows = bytes_ops.strings_to_rows(LINES[:4], cfg.line_width)
    with pytest.raises(ValueError, match="doc ids must be >= 0"):
        term_doc_counts_stream(
            [(rows, np.array([0, 1, -2, 3], np.int32))], cfg
        )
    with pytest.raises(ValueError, match="MISSING"):
        term_doc_counts_stream(
            [(rows, np.arange(4, dtype=np.int32))], cfg
        )
