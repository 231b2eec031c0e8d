"""The least bytes one JOB's COLLECT of an index configuration has to move
through HBM, priced from the DATA — the oracle's own counts of the text's
tokens, distinct (word, document) pairs and words (``index_oracle.Oracle
.counts``) and the configuration's key width — so that it reads the same
whatever implements the collect: not from the program's padded shapes, its
store's capacity or its number of passes.  (``least_bytes.py`` prices the
WordCount stages a block, ``record_least_bytes.py`` the record sort and
``pagerank_least_bytes.py`` PageRank a job; a PR that adds a cell edits no
file, so the index's prices live here.)"""

DOC = 4      # a document id, int32
OFFSET = 4   # a word's offset into the postings, int32


def collect(counts, sizes) -> int:
    """Every emitted pair read once — its key (``key_width`` bytes, as the
    map emits it) and its doc id; every distinct pair's doc id written once
    (the postings); every word's key and offset written once."""
    key = sizes["key_width"]
    return (counts["tokens"] * (key + DOC)
            + counts["pairs"] * DOC
            + counts["words"] * (key + OFFSET))
