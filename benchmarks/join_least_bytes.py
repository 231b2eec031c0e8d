"""The least bytes one JOB of a join configuration has to move through HBM,
priced from the DATA — the oracle's own counts of the two files' lines and
bytes, of the visits that pass the window, of the pages and of the groups
(``join_oracle.Oracle.counts``) and the schema's field widths — so that it
reads the same whatever implements the job: a parse on the host (its device
time is then less and its share higher, which is the truth), padded rows,
a store's capacity or a number of passes are not in it.
(``index_least_bytes.py`` prices the index's collect, ``pagerank_least_bytes.py``
PageRank a job; a PR that adds a cell edits no file, so the join's prices
live here.)"""

URL = 100      # pageURL / destURL VARCHAR(100): a key as the join compares it
IP = 16        # sourceIP VARCHAR(16)
NUMBER = 8     # adRevenue, a sum, a count or a rank: one 64-bit word


def job(counts, sizes) -> int:
    """Every input byte read once (both files); every passed visit's
    projected fields (destURL, sourceIP, adRevenue) written once and read
    once; every page's URL and rank read once; every group (sourceIP, the
    three aggregates) written once."""
    return (counts["bytes"]
            + 2 * counts["passed"] * (URL + IP + NUMBER)
            + counts["pages"] * (URL + NUMBER)
            + counts["groups"] * (IP + 3 * NUMBER))
