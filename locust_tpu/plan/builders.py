"""Canonical plans for the workload ladder.

The ONE spelling of each workload as a logical DAG — the CLI drivers
(``cli_apps.py``, ``cli.py``), the serve smoke/tests and the bench
``plan`` sub-dict all construct these instead of re-wiring stage chains
by hand, so "the wordcount pipeline" has exactly one definition whose
``fingerprint()`` everything keys off (docs/PLAN.md).  jax-free.
"""

from __future__ import annotations

from locust_tpu.plan.nodes import Plan, node


def wordcount_plan() -> Plan:
    """source → tokenize → group → sum → table: the reference pipeline
    (main.cu:397-473) as a plan.  Compiles onto the engine's fused
    one-sort-per-block fold (plan/compile.py)."""
    return Plan((
        node("corpus", "source", "text"),
        node("tokenize", "map", "tokenize_count", ("corpus",)),
        node("group", "shuffle", "by_key", ("tokenize",)),
        node("counts", "reduce", "sum", ("group",)),
        node("out", "sink", "table", ("counts",)),
    ))


def tfidf_plan(lines_per_doc: int = 1) -> Plan:
    """The two-stage tf-idf pipeline: a (word, doc)-keyed count fold,
    then a table-level rescore — tf from the device, df/n_docs as host
    folds over the (tiny) pair table (apps/tfidf.py)."""
    return Plan((
        node("corpus", "source", "text", lines_per_doc=lines_per_doc),
        node("pairs", "map", "tokenize_pairs", ("corpus",)),
        node("group", "shuffle", "by_key", ("pairs",)),
        node("tf", "reduce", "sum", ("group",)),
        node("score", "map", "tfidf_score", ("tf",)),
        node("out", "sink", "tfidf", ("score",)),
    ))


def index_plan(lines_per_doc: int = 1) -> Plan:
    """Inverted index: (word, doc) pairs grouped by word, reduced to the
    distinct sorted posting list (apps/inverted_index.py)."""
    return Plan((
        node("corpus", "source", "text", lines_per_doc=lines_per_doc),
        node("pairs", "map", "tokenize_pairs", ("corpus",)),
        node("group", "shuffle", "by_key", ("pairs",)),
        node("postings", "reduce", "collect_docs", ("group",)),
        node("out", "sink", "postings", ("postings",)),
    ))


def pagerank_plan(num_iters: int = 20, damping: float = 0.85) -> Plan:
    """Iterative PageRank over an edge list: the iterate node wraps the
    damped power iteration the apps tier already lowers to a dense
    segment-sum + psum (apps/pagerank.py)."""
    return Plan((
        node("edges", "source", "edges"),
        node("ranks", "iterate", "pagerank", ("edges",),
             num_iters=num_iters, damping=damping),
        node("out", "sink", "ranks", ("ranks",)),
    ))


# gensort's record (sortbenchmark.org): 100 bytes, the first 10 the key.
RECORD_BYTES, KEY_BYTES = 100, 10


def records_sort_plan(record_bytes: int = RECORD_BYTES,
                      key_bytes: int = KEY_BYTES) -> Plan:
    """TeraSort: fixed-width binary records ordered by their leading key
    bytes (unsigned, ties in input order) and every one of them written
    back out — the shuffle WITHOUT a combiner (engine.RecordSort)."""
    return Plan((
        node("records", "source", "records", record_bytes=record_bytes),
        node("order", "sort", "by_key", ("records",), key_bytes=key_bytes),
        node("out", "sink", "records", ("order",)),
    ))


# HiBench's window (sql/join's query): a year, both ends in.
DATE_FROM, DATE_TO = "1999-01-01", "2000-01-01"


def join_visits_plan(date_from: str = DATE_FROM, date_to: str = DATE_TO) -> Plan:
    """HiBench's ``sql/join`` — Pavlo et al.'s Join Task: UserVisits
    filtered by date and joined with Rankings on the URL, regrouped by
    sourceIP into ``avg(pageRank)`` and ``sum(adRevenue)``, ordered by the
    sum descending.  Two sources of delimited rows with distinct inputs,
    and two shuffles in a row on different keys: the join's on the URL,
    the reduce's on a VALUE of the join's output (apps/join.py)."""
    return Plan((
        node("rankings", "source", "delimited", input="rankings"),
        node("uservisits", "source", "delimited", input="uservisits"),
        node("visits", "map", "select_visits", ("uservisits",),
             date_from=date_from, date_to=date_to),
        node("pages", "map", "select_pages", ("rankings",)),
        node("ranked", "join", "inner", ("visits", "pages")),
        node("by_ip", "shuffle", "by_key", ("ranked",)),
        node("revenue", "reduce", "sum_avg", ("by_ip",)),
        node("order", "sort", "by_value", ("revenue",)),
        node("out", "sink", "revenue", ("order",)),
    ))
