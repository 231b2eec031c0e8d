"""Plan-layer battery: typed-DAG validation, JSON round-trips, the
content-addressed fingerprint, compile lowering byte-identity against
every hand-wired driver (single-device AND mesh), and the ladder CLI
parity satellite (docs/PLAN.md).
"""

from __future__ import annotations

import json
import os
import random
import subprocess
import sys

import numpy as np
import pytest

from locust_tpu.config import EngineConfig
from locust_tpu.plan import (
    NODE_KINDS,
    NODE_OPS,
    Plan,
    PlanError,
    from_doc,
    from_json,
    index_plan,
    join_visits_plan,
    node,
    pagerank_plan,
    records_sort_plan,
    tfidf_plan,
    wordcount_plan,
)
from locust_tpu.plan.compile import compile_plan

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CFG = EngineConfig(
    block_lines=8, line_width=64, key_width=16, emits_per_line=8,
)
LINES = [
    b"alpha beta gamma", b"beta gamma delta", b"alpha alpha",
    b"epsilon zeta", b"gamma zeta zeta", b"delta",
] * 4


def _rows():
    from locust_tpu.core import bytes_ops

    return bytes_ops.strings_to_rows(LINES, CFG.line_width)


# ----------------------------------------------------------- validation


def test_registry_is_closed_and_typed():
    assert NODE_KINDS == (
        "source", "map", "shuffle", "reduce", "sort", "join", "iterate",
        "sink",
    )
    assert set(NODE_OPS) == set(NODE_KINDS)


def test_builders_validate_and_roundtrip():
    for p in (wordcount_plan(), tfidf_plan(3), index_plan(2),
              pagerank_plan(7, 0.9), records_sort_plan(), records_sort_plan(64, 8)):
        p2 = from_json(p.canonical_json())
        assert p2 == p
        assert p2.fingerprint() == p.fingerprint()
        assert p2.to_doc() == p.to_doc()


def _chain_templates(rng):
    """Random valid plans: the supported chains with randomized ids,
    params and NODE ORDER (validation must not require topological
    input order)."""
    k = rng.randint(1, 9)
    uid = lambda tag: f"{tag}{rng.randint(0, 10**6)}"  # noqa: E731
    s, m, g, r, o = (uid(t) for t in "smgro")
    picks = [
        [
            node(s, "source", "text", lines_per_doc=k),
            node(m, "map", "tokenize_count", (s,)),
            node(g, "shuffle", "by_key", (m,)),
            node(r, "reduce", "sum", (g,)),
            node(o, "sink", "table", (r,)),
        ],
        [
            node(s, "source", "text", lines_per_doc=k),
            node(m, "map", "tokenize_pairs", (s,)),
            node(g, "shuffle", "by_key", (m,)),
            node(r, "reduce", "collect_docs", (g,)),
            node(o, "sink", "postings", (r,)),
        ],
        [
            node(s, "source", "edges"),
            node(r, "iterate", "pagerank", (s,),
                 num_iters=rng.randint(1, 30),
                 damping=rng.uniform(0.05, 0.95)),
            node(o, "sink", "ranks", (r,)),
        ],
    ]
    nodes = rng.choice(picks)
    rng.shuffle(nodes)
    return Plan(tuple(nodes))


def test_random_valid_plans_roundtrip_identical_fingerprint():
    """Property: random valid DAG -> JSON -> Plan -> identical
    fingerprint and document, across orders, ids and params."""
    rng = random.Random(1234)
    seen = set()
    for _ in range(50):
        p = _chain_templates(rng)
        q = from_json(p.canonical_json())
        assert q.fingerprint() == p.fingerprint()
        assert q.to_doc() == p.to_doc()
        seen.add(p.fingerprint())
    assert len(seen) > 30  # params/ids actually vary the identity


def test_canonical_plan_fingerprints_are_pinned():
    """The serve caches and the WAL key on these: the new ``sort`` kind
    moved no plan that was there, and the record sort's is a constant."""
    assert wordcount_plan().fingerprint() == "f8785ffaf2ea"
    assert records_sort_plan().fingerprint() == "9b8ff3d58901"
    assert records_sort_plan(100, 10).fingerprint() == "9b8ff3d58901"
    assert records_sort_plan(100, 2).fingerprint() == "4cbc4a7313cc"
    # PR 44: three one-valued fields left ``repr(cfg)``, so every
    # configuration's fingerprint moved once (CHANGES.md).
    assert EngineConfig().fingerprint() == "49eee6bb9e67"


# --------------------------------------------------------- the sort kind


def _gensort_like(n: int, seed: int = 0) -> bytes:
    rng = np.random.default_rng(seed)
    rows = rng.integers(0, 256, (n, 100), dtype=np.uint8)
    rows[:, :9] &= 0x81  # ties down to the tenth byte
    return rows.tobytes()


def test_sort_plan_types_and_lowers_onto_the_record_sort():
    p = records_sort_plan()
    assert [n.kind for n in p.nodes] == ["source", "sort", "sink"]
    assert p.node_types() == {
        "records": "records", "order": "sorted_records", "out": "output",
    }
    cp = compile_plan(p, CFG)
    assert cp._stages["order"] == ("record_sort", "records", 100, 10)


@pytest.mark.parametrize("nodes,frag", [
    ([node("t", "source", "text"), node("o", "sort", "by_key", ("t",)),
      node("k", "sink", "records", ("o",))], "cannot consume"),
    ([node("r", "source", "records"), node("k", "sink", "records", ("r",))],
     "cannot consume"),
    ([node("r", "source", "records"), node("o", "sort", "by_key", ("r",)),
      node("k", "sink", "table", ("o",))], "cannot consume"),
    ([node("r", "source", "records"),
      node("o", "sort", "by_key", ("r",), key_bytes=0),
      node("k", "sink", "records", ("o",))], "key_bytes"),
    ([node("r", "source", "records"),
      node("o", "sort", "by_rank", ("r",)),
      node("k", "sink", "records", ("o",))], "unknown op"),
    ([node("r", "source", "records"),  # an op from PR 48 on, of a table's rows
      node("o", "sort", "by_value", ("r",)),
      node("k", "sink", "records", ("o",))], "cannot consume"),
    ([node("r", "source", "records", width=100),
      node("o", "sort", "by_key", ("r",)),
      node("k", "sink", "records", ("o",))], "unknown param"),
])
def test_sort_plan_validation_is_loud(nodes, frag):
    with pytest.raises(PlanError, match=frag):
        Plan(tuple(nodes))


def test_sort_plan_refuses_a_key_wider_than_the_record_at_compile():
    with pytest.raises(PlanError, match="more than the source's record_bytes"):
        compile_plan(records_sort_plan(8, 10), CFG)
    with pytest.raises(PlanError, match="needs an EngineConfig"):
        compile_plan(records_sort_plan())


def test_sort_plan_runs_to_the_reference_bytes():
    """``run`` on a RecordSource, ``run_corpus`` on raw bytes (the serve
    tier's entry) and a pre-staged source (the CLI's) give the plain
    reference's bytes; accounting says every record came out."""
    from locust_tpu import records_reference
    from locust_tpu.io.loader import RecordSource

    data = _gensort_like(5000, seed=3)
    want = records_reference.sorted_records(data)
    cp = compile_plan(records_sort_plan(), CFG)
    res = cp.run(RecordSource.from_bytes(data, 100))
    assert res.output == want
    assert (res.distinct, res.truncated, res.overflow_tokens) == (5000, False, 0)
    assert cp.run_corpus(data).output == want
    staged = cp.load_records(RecordSource.from_bytes(data, 100))
    ordered = cp.run(staged, render=False)
    assert ordered.output is None
    assert b"".join(b.tobytes() for b in ordered.value.host_blocks()) == want
    with pytest.raises(PlanError, match="no whole number"):
        cp.run_corpus(data[:-1])


def test_sort_plan_stays_on_the_solo_engine_and_says_why():
    from locust_tpu.plan.distribute import SOLO_ONLY, plan_shape

    assert "sort" in SOLO_ONLY
    assert plan_shape(records_sort_plan()) == (None, "solo_only_kind")
    assert plan_shape(wordcount_plan())[1] is None


def test_fingerprint_is_content_addressed():
    assert tfidf_plan(2).fingerprint() == tfidf_plan(2).fingerprint()
    assert tfidf_plan(2).fingerprint() != tfidf_plan(3).fingerprint()
    assert wordcount_plan().fingerprint() != index_plan().fingerprint()


@pytest.mark.parametrize("mutate,frag", [
    (lambda: Plan((node("a", "sorce", "text"),)), "unknown kind"),
    (lambda: Plan((node("a", "source", "txet"),)), "unknown op"),
    (lambda: Plan((
        node("a", "source", "text"),
        node("a", "sink", "table", ("a",)),
    )), "duplicate node id"),
    (lambda: Plan((
        node("a", "source", "text"),
        node("b", "map", "tokenize_count", ("a", "a")),
    )), "input(s)"),
    (lambda: Plan((
        node("a", "source", "text"),
        node("b", "map", "tokenize_count", ("zz",)),
    )), "names no node"),
    (lambda: Plan((node("b", "map", "tokenize_count", ("b",)),)),
     "self-referential"),
    (lambda: Plan((
        node("a", "source", "text"),
        node("out", "sink", "ranks", ("a",)),
    )), "cannot consume"),
    (lambda: Plan((node("a", "source", "text"),)), "exactly one sink"),
    (lambda: Plan((
        node("a", "source", "text", lines_per_doc=0),
        node("out", "sink", "table", ("a",)),
    )), "param"),
    (lambda: Plan((
        node("a", "source", "text", bogus=1),
        node("out", "sink", "table", ("a",)),
    )), "unknown param"),
])
def test_structured_validation_errors(mutate, frag):
    with pytest.raises(PlanError) as e:
        mutate()
    assert frag in str(e.value)


def test_cycle_detected():
    # Hand-built doc: a map/shuffle 2-cycle no builder can produce.
    doc = {
        "plan_version": 1,
        "nodes": [
            {"id": "m", "kind": "map", "op": "tokenize_count",
             "inputs": ["g"]},
            {"id": "g", "kind": "shuffle", "op": "by_key",
             "inputs": ["m"]},
        ],
    }
    with pytest.raises(PlanError) as e:
        from_doc(doc)
    assert "cycle" in str(e.value)


def test_orphan_nodes_rejected():
    with pytest.raises(PlanError) as e:
        Plan((
            node("a", "source", "text"),
            node("m", "map", "tokenize_count", ("a",)),
            node("g", "shuffle", "by_key", ("m",)),
            node("r", "reduce", "sum", ("g",)),
            node("out", "sink", "table", ("r",)),
            node("stray", "source", "edges"),
        ))
    assert "do not feed the sink" in str(e.value)


def test_reserved_param_keys_are_structured_plan_errors():
    """A params key colliding with node()'s own arguments must surface
    as a PlanError (the serve bad_spec contract), not a raw TypeError
    through **params (review finding)."""
    doc = {
        "plan_version": 1,
        "nodes": [{"id": "a", "kind": "source", "op": "text",
                   "params": {"kind": "x"}}],
    }
    with pytest.raises(PlanError) as e:
        from_doc(doc)
    assert "reserved" in str(e.value)


def test_finalize_false_skips_wordcount_decode_only():
    rows = _rows()
    pres = compile_plan(wordcount_plan(), CFG).run(
        rows, render=False, finalize=False
    )
    assert pres.value is None and pres.output is None
    assert pres.run_result is not None
    assert pres.distinct == pres.run_result.num_segments
    with pytest.raises(PlanError):
        compile_plan(tfidf_plan(2), CFG).run(
            rows, render=False, finalize=False
        )
    with pytest.raises(PlanError, match="requires render=False"):
        compile_plan(wordcount_plan(), CFG).run(rows, finalize=False)


def test_load_edges_delegates_to_the_one_parser(tmp_path):
    from locust_tpu.cli_apps import load_edges

    f = tmp_path / "e.txt"
    f.write_bytes(b"# c\n0 1\n1 0\n")
    src, dst = load_edges(str(f))
    assert list(src) == [0, 1] and list(dst) == [1, 0]
    f.write_bytes(b"0 1 2\n")
    with pytest.raises(ValueError) as e:
        load_edges(str(f))
    assert str(f) in str(e.value)  # path context preserved for the CLI


def test_version_skew_and_malformed_docs():
    with pytest.raises(PlanError):
        from_doc({"plan_version": 99, "nodes": []})
    with pytest.raises(PlanError):
        from_doc({"plan_version": 1, "nodes": "nope"})
    with pytest.raises(PlanError):
        from_json("not json {")
    with pytest.raises(PlanError):
        from_doc([1, 2, 3])


def test_parse_spec_maps_plan_errors_to_bad_spec():
    from locust_tpu.serve.jobs import parse_spec

    import base64

    req = {
        "corpus_b64": base64.b64encode(b"a b c\n").decode(),
        "plan": {"plan_version": 1,
                 "nodes": [{"id": "a", "kind": "sorce", "op": "text"}]},
    }
    with pytest.raises(ValueError) as e:
        parse_spec(req)
    assert str(e.value).startswith("bad_spec\n")
    assert "unknown kind" in str(e.value)
    # plan + explicit workload name is also a bad_spec
    req["plan"] = wordcount_plan().to_doc()
    req["workload"] = "wordcount"
    with pytest.raises(ValueError) as e:
        parse_spec(req)
    assert str(e.value).startswith("bad_spec\n")


def test_one_corpus_contract_rejects_named_input_plans():
    """A serve submit carries ONE corpus: a plan whose sources name
    distinct inputs must be rejected structured at admission AND at
    run_corpus — feeding the same bytes to both sources would be a
    silent self-join (review finding)."""
    import base64

    from locust_tpu.serve.jobs import parse_spec

    named = Plan((
        node("a", "source", "text", input="left"),
        node("m", "map", "tokenize_count", ("a",)),
        node("g", "shuffle", "by_key", ("m",)),
        node("r", "reduce", "sum", ("g",)),
        node("out", "sink", "table", ("r",)),
    ))
    with pytest.raises(ValueError) as e:
        parse_spec({
            "corpus_b64": base64.b64encode(b"a b\n").decode(),
            "plan": named.to_doc(),
        })
    assert str(e.value).startswith("bad_spec\n")
    assert "left" in str(e.value)
    with pytest.raises(PlanError) as e:
        compile_plan(named, CFG).run_corpus(b"a b\n")
    assert "left" in str(e.value)


def test_parse_spec_builds_plan_spec_with_canonical_identity():
    import base64

    from locust_tpu.serve.jobs import PLAN_WORKLOAD, parse_spec

    p = tfidf_plan(2)
    req = {
        "corpus_b64": base64.b64encode(b"a b c\n").decode(),
        "plan": p.to_doc(),
    }
    spec, corpus = parse_spec(req)
    assert spec.workload == PLAN_WORKLOAD
    assert spec.plan == p.canonical_json()
    assert spec.plan_fingerprint() == p.fingerprint()
    # JSON-text plans parse identically (the CLI --plan path).
    spec2, _ = parse_spec(dict(req, plan=p.canonical_json()))
    assert spec2.fingerprint() == spec.fingerprint()


# ------------------------------------------------- compile lowering


def test_unsupported_compositions_fail_at_compile():
    # A bare shuffle feeding nothing downstream of a reduce is already
    # unconstructible (type check); a reduce over a non-shuffle input is
    # the compile-time gate.
    p = Plan((
        node("a", "source", "text"),
        node("m", "map", "tokenize_count", ("a",)),
        node("g", "shuffle", "by_key", ("m",)),
        node("r", "reduce", "sum", ("g",)),
        node("out", "sink", "table", ("r",)),
    ))
    compile_plan(p, CFG)  # supported: fine
    with pytest.raises(PlanError):
        compile_plan(p)  # text source without a config
    with pytest.raises(PlanError):
        compile_plan(tfidf_plan(2), CFG, mesh=True)  # tf has no mesh


def test_wordcount_plan_byte_identical_single_device():
    from locust_tpu.engine import MapReduceEngine

    rows = _rows()
    res = MapReduceEngine(CFG).run_fused(rows)
    pres = compile_plan(wordcount_plan(), CFG).run(rows)
    assert pres.value == res.to_host_pairs()
    assert pres.distinct == res.num_segments
    assert pres.truncated == res.truncated
    assert pres.output == b"".join(
        k + b"\t" + str(v).encode() + b"\n" for k, v in res.to_host_pairs()
    )
    # timed path returns the engine RunResult for the stage report
    t = compile_plan(wordcount_plan(), CFG).run(rows, timed=True)
    assert t.run_result is not None and t.value == pres.value


@pytest.mark.parametrize("plan_kw, run_kw", [
    ({}, dict(timed=True)),                       # the one fold that reads it
    ({}, {}),                                     # run_fused stages the rows whole
    ({}, dict(timed=True, checkpoint_dir="ck")),  # run_checkpointed indexes them
    (dict(mesh=True), dict(timed=True)),
    ("tfidf", {}),                                # needs doc ids a line
])
def test_a_block_iterator_feeds_the_timed_wordcount_fold_and_no_other(
    tmp_path, plan_kw, run_kw
):
    """The source node hands an ITERATOR of host row blocks on as it hands
    rows on; ``timed_run`` reads it (same value, same accounting as from
    the rows), and every fold that indexes its rows refuses it by name."""
    from locust_tpu.plan import PlanError

    rows = _rows()
    blocks = iter([rows[i:i + CFG.block_lines]
                   for i in range(0, rows.shape[0], CFG.block_lines)])
    if "checkpoint_dir" in run_kw:
        run_kw = dict(run_kw, checkpoint_dir=str(tmp_path / "ck"))
    if plan_kw == "tfidf":
        cp = compile_plan(tfidf_plan(3), CFG)
    else:
        cp = compile_plan(wordcount_plan(), CFG, **plan_kw)
    if run_kw == dict(timed=True) and plan_kw == {}:
        want = compile_plan(wordcount_plan(), CFG).run(rows, timed=True)
        got = cp.run(blocks, **run_kw)
        assert (got.value, got.output, got.distinct, got.truncated,
                got.overflow_tokens) == (want.value, want.output, want.distinct,
                                         want.truncated, want.overflow_tokens)
        assert got.run_result is not None
    else:
        with pytest.raises(PlanError, match="iterator of row blocks"):
            cp.run(blocks, **run_kw)


def test_wordcount_plan_byte_identical_mesh():
    from locust_tpu.parallel.mesh import make_mesh
    from locust_tpu.parallel.shuffle import DistributedMapReduce

    rows = _rows()
    res = DistributedMapReduce(make_mesh(), CFG).run(rows)
    pres = compile_plan(wordcount_plan(), CFG, mesh=True).run(rows)
    assert pres.value == res.to_host_pairs()


def test_tfidf_plan_byte_identical():
    from locust_tpu.apps.tfidf import build_tfidf

    rows = _rows()
    ids = (np.arange(rows.shape[0]) // 3).astype(np.int32)
    scores = build_tfidf(rows, ids, CFG)
    pres = compile_plan(tfidf_plan(3), CFG).run(rows)
    assert pres.value == scores
    expect = b"".join(
        w + b"\t" + str(d).encode() + b"\t"
        + f"{scores[(w, d)]:.6f}".encode() + b"\n"
        for w, d in sorted(scores)
    )
    assert pres.output == expect


def test_index_plan_byte_identical_single_and_mesh():
    from locust_tpu.apps.inverted_index import (
        build_inverted_index,
        build_inverted_index_mesh,
    )
    from locust_tpu.parallel.mesh import make_mesh

    rows = _rows()
    ids = (np.arange(rows.shape[0]) // 2).astype(np.int32)
    idx = build_inverted_index(rows, ids, CFG)
    pres = compile_plan(index_plan(2), CFG).run(rows)
    assert pres.value == idx
    expect = b"".join(
        w + b"\t" + b",".join(str(d).encode() for d in idx[w]) + b"\n"
        for w in sorted(idx)
    )
    assert pres.output == expect
    midx = build_inverted_index_mesh(rows, ids, make_mesh(), CFG)
    mpres = compile_plan(index_plan(2), CFG, mesh=True).run(rows)
    assert mpres.value == midx


@pytest.mark.parametrize("mesh", [False, True])
def test_index_plan_unfinalized_is_arrays_on_one_device_and_the_dict_on_the_mesh(mesh):
    """``finalize=False`` (the CLI's way): the single-device index fold
    hands its CSR arrays on and builds no dict; the mesh's value is the
    shards' union, a dict either way.  Both render to the same bytes."""
    from locust_tpu.apps.inverted_index import Postings
    from locust_tpu.plan.compile import _render

    rows = _rows()
    plan = compile_plan(index_plan(2), CFG, mesh=mesh)
    want = plan.run(rows)
    raw = plan.run(rows, render=False, finalize=False)
    assert raw.output is None and raw.distinct == want.distinct == len(want.value)
    assert isinstance(raw.value, dict if mesh else Postings)
    assert _render("postings", raw.value) == want.output
    if not mesh:
        assert raw.value.to_dict() == want.value
        assert raw.value.offsets[-1] == raw.value.postings.shape[0]


def test_unfinalized_runs_are_for_the_wordcount_and_index_folds_alone():
    with pytest.raises(PlanError, match="wordcount or the index fold"):
        compile_plan(tfidf_plan(2), CFG).run(_rows(), render=False, finalize=False)
    with pytest.raises(PlanError, match="requires render=False"):
        compile_plan(index_plan(2), CFG).run(_rows(), finalize=False)


def test_pagerank_plan_byte_identical_single_and_mesh():
    from locust_tpu.apps.pagerank import ShardedPageRank, pagerank
    from locust_tpu.parallel.mesh import make_mesh

    src = np.array([0, 1, 2, 2, 3, 4, 4], np.int64)
    dst = np.array([1, 2, 0, 3, 0, 1, 2], np.int64)
    n = 5
    ranks = np.asarray(pagerank(
        np.asarray(src, np.int32), np.asarray(dst, np.int32),
        num_nodes=n, num_iters=8, damping=0.85,
    ))
    pres = compile_plan(pagerank_plan(8, 0.85)).run(
        (src, dst), num_nodes=n
    )
    assert np.array_equal(pres.value, ranks)
    assert pres.output == b"".join(
        f"{i}\t{ranks[i]:.8e}\n".encode() for i in range(n)
    )
    mranks = ShardedPageRank(make_mesh(), n, damping=0.85).run(
        src, dst, num_iters=8
    )
    mpres = compile_plan(pagerank_plan(8, 0.85), mesh=True).run(
        (src, dst), num_nodes=n
    )
    assert np.array_equal(mpres.value, mranks)


def test_join_inner_combines_two_tables():
    from locust_tpu.engine import MapReduceEngine

    rows = _rows()
    counts = dict(MapReduceEngine(CFG).run_fused(rows).to_host_pairs())

    def chain(prefix, input_name):
        return [
            node(f"{prefix}s", "source", "text", input=input_name),
            node(f"{prefix}m", "map", "tokenize_count", (f"{prefix}s",)),
            node(f"{prefix}g", "shuffle", "by_key", (f"{prefix}m",)),
            node(f"{prefix}c", "reduce", "sum", (f"{prefix}g",)),
        ]

    p = Plan(tuple(
        chain("l", "left") + chain("r", "right") + [
            node("j", "join", "inner", ("lc", "rc"), combine="sum"),
            node("out", "sink", "table", ("j",)),
        ]
    ))
    pres = compile_plan(p, CFG).run({"left": _rows(), "right": _rows()})
    assert pres.value == sorted((k, 2 * v) for k, v in counts.items())
    # min-combine over disjoint halves: only shared keys survive.
    half = len(LINES) // 2
    from locust_tpu.core import bytes_ops

    left = bytes_ops.strings_to_rows(LINES[:half], CFG.line_width)
    right = bytes_ops.strings_to_rows(LINES[half:], CFG.line_width)
    pmin = Plan(tuple(
        chain("l", "left") + chain("r", "right") + [
            node("j", "join", "inner", ("lc", "rc"), combine="min"),
            node("out", "sink", "table", ("j",)),
        ]
    ))
    got = dict(
        compile_plan(pmin, CFG).run({"left": left, "right": right}).value
    )
    from helpers import py_wordcount

    lc = py_wordcount(LINES[:half], CFG.emits_per_line, CFG.key_width)
    rc = py_wordcount(LINES[half:], CFG.emits_per_line, CFG.key_width)
    assert got == {
        k: min(lc[k], rc[k]) for k in lc if k in rc
    }


def test_run_stream_passthrough_and_checkpoint(tmp_path):
    from locust_tpu.engine import MapReduceEngine

    rows = _rows()
    cp = compile_plan(wordcount_plan(), CFG)
    bl = CFG.block_lines
    res = cp.run_stream(
        (rows[i:i + bl] for i in range(0, rows.shape[0], bl))
    )
    assert res.to_host_pairs() == \
        MapReduceEngine(CFG).run_fused(rows).to_host_pairs()
    with pytest.raises(PlanError):
        compile_plan(pagerank_plan()).run_stream(iter(()))
    # checkpoint placement at the fold-stage boundary
    ck = cp.run(rows, checkpoint_dir=str(tmp_path / "ck"), every=1)
    assert (tmp_path / "ck" / "state.npz").exists()
    assert ck.value == res.to_host_pairs()


def test_resource_bounds_on_plan_params_and_corpus_derived_state():
    """Multi-tenant safety (review finding): num_iters is capped at
    validation, and the SERVE path bounds pagerank's corpus-derived
    dense state — a 12-byte submit naming node 2e9 must reject, not
    allocate multi-GB vectors inside the daemon.  The CLI run() path
    stays unbounded like the pre-plan driver."""
    from locust_tpu.plan.nodes import MAX_ITERS

    with pytest.raises(PlanError, match=str(MAX_ITERS)):
        pagerank_plan(MAX_ITERS + 1)
    pagerank_plan(MAX_ITERS)  # at the cap: fine
    ep = compile_plan(pagerank_plan(2))
    with pytest.raises(PlanError) as e:
        ep.run_corpus(b"0 2000000000\n")
    assert "cap" in str(e.value)


def test_run_corpus_matches_rows_run_and_parses_edges():
    corpus = b"".join(ln + b"\n" for ln in LINES)
    cp = compile_plan(tfidf_plan(2), CFG)
    assert cp.run_corpus(corpus).output == cp.run(_rows()).output
    ep = compile_plan(pagerank_plan(4, 0.85))
    edges = b"# comment\n0 1\n1 2\n2 0\n"
    out = ep.run_corpus(edges)
    assert out.distinct == 3
    with pytest.raises(PlanError):
        ep.run_corpus(b"0 1 2\n")  # malformed edge line
    with pytest.raises(PlanError):
        ep.run_corpus(b"# empty\n")


# --------------------------------------------- ladder CLI parity satellite


def test_ladder_cli_accepts_sort_mode_and_trace_out(tmp_path):
    """Satellite (ISSUE 12): pagerank|index|tfidf take --trace-out and
    --sort-mode like the main WordCount CLI, so plan-compiled ladder
    runs are traceable with zero new plumbing."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO}
    corpus = tmp_path / "c.txt"
    corpus.write_bytes(b"".join(ln + b"\n" for ln in LINES))
    trace = tmp_path / "t.trace.json"
    proc = subprocess.run(
        [sys.executable, "-m", "locust_tpu", "tfidf", str(corpus),
         "--backend", "cpu", "--lines-per-doc", "2",
         "--block-lines", "8", "--line-width", "64", "--key-width", "16",
         "--emits-per-line", "8", "--sort-mode", "hashp1",
         "--trace-out", str(trace)],
        env=env, capture_output=True, timeout=240,
    )
    assert proc.returncode == 0, proc.stderr[-800:]
    doc = json.loads(trace.read_text())
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"plan.compile", "plan.run"} <= names, names
    # the sorted-mode run still matches the default-mode output exactly
    base = subprocess.run(
        [sys.executable, "-m", "locust_tpu", "tfidf", str(corpus),
         "--backend", "cpu", "--lines-per-doc", "2",
         "--block-lines", "8", "--line-width", "64", "--key-width", "16",
         "--emits-per-line", "8"],
        env=env, capture_output=True, timeout=240,
    )
    assert base.returncode == 0, base.stderr[-800:]
    assert proc.stdout == base.stdout


def test_pagerank_cli_accepts_parity_flags(tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO}
    edges = tmp_path / "e.txt"
    edges.write_bytes(b"0 1\n1 2\n2 0\n")
    trace = tmp_path / "pr.trace.json"
    proc = subprocess.run(
        [sys.executable, "-m", "locust_tpu", "pagerank", str(edges),
         "--backend", "cpu", "--num-iters", "3",
         "--sort-mode", "hasht", "--trace-out", str(trace)],
        env=env, capture_output=True, timeout=240,
    )
    assert proc.returncode == 0, proc.stderr[-800:]
    assert trace.exists()
    assert len(proc.stdout.splitlines()) == 3


# ----------------------------------------------------- the rows join (PR 48)


def _join_nodes(**override):
    """``join_visits_plan``'s nodes, one of them replaced."""
    nodes = {n.id: n for n in join_visits_plan().nodes}
    nodes.update(override)
    return tuple(nodes.values())


def test_join_visits_plan_types_round_trips_and_stays_solo():
    """Two delimited sources with distinct inputs, two shuffles in a row on
    different keys: the signatures validate, the plan round-trips through
    JSON, and the distributed planner keeps it on the solo path by a NAMED
    refusal (its sink is fed by a sort, a SOLO_ONLY kind)."""
    from locust_tpu.plan.distribute import plan_shape

    plan = join_visits_plan()
    assert plan.node_types() == {
        "rankings": "field_rows", "uservisits": "field_rows",
        "visits": "visit_rows", "pages": "page_rows", "ranked": "ranked_visits",
        "by_ip": "grouped_visits", "revenue": "revenue_table",
        "order": "ordered_revenue", "out": "output",
    }
    again = from_json(plan.canonical_json())
    assert again == plan and again.fingerprint() == plan.fingerprint()
    assert join_visits_plan("1999-01-01", "1999-06-30").fingerprint() != plan.fingerprint()
    assert plan_shape(plan) == (None, "solo_only_kind")


def test_join_inner_keeps_its_two_signatures_apart():
    """``join(inner)`` over two TABLES is the host fold it was; over keyed
    ROWS it is the device join.  Neither takes the other's inputs."""
    from locust_tpu.plan.nodes import _SIGNATURES

    assert _SIGNATURES[("join", "inner")] == (
        (("table", "table"), "table"),
        (("visit_rows", "page_rows"), "ranked_visits"),
    )
    with pytest.raises(PlanError, match="cannot consume"):  # the sides swapped
        Plan(_join_nodes(ranked=node("ranked", "join", "inner", ("pages", "visits"))))
    with pytest.raises(PlanError, match="cannot consume"):  # a table sink on rows
        Plan(_join_nodes(out=node("out", "sink", "table", ("order",))))
    with pytest.raises(PlanError, match="cannot consume"):  # the order skipped
        Plan(tuple(n for n in _join_nodes(out=node("out", "sink", "revenue", ("revenue",)))
                   if n.id != "order"))


def test_two_delimited_sources_with_one_input_are_refused():
    with pytest.raises(PlanError, match="distinct inputs"):
        Plan(_join_nodes(uservisits=node("uservisits", "source", "delimited",
                                         input="rankings")))
    with pytest.raises(PlanError, match="distinct inputs"):  # both at the default
        Plan(_join_nodes(rankings=node("rankings", "source", "delimited"),
                         uservisits=node("uservisits", "source", "delimited")))


@pytest.mark.parametrize("params, wrong", [
    (dict(date_from="1999-1-1"), "must be a date YYYY-MM-DD"),
    (dict(date_to="1999-02-30"), "a date of the calendar"),
    (dict(date_from=19990101), "must be a date YYYY-MM-DD"),
    (dict(window="1999"), "unknown param"),
])
def test_select_visits_params_are_validated(params, wrong):
    with pytest.raises(PlanError, match=wrong):
        Plan(_join_nodes(visits=node("visits", "map", "select_visits", ("uservisits",), **params)))


def test_the_rows_join_refuses_what_it_does_not_lower():
    with pytest.raises(PlanError, match="unknown param"):  # ',' is the format, no option
        Plan(_join_nodes(rankings=node("rankings", "source", "delimited",
                                       input="rankings", delimiter="|")))
    with pytest.raises(PlanError, match="lies after"):
        compile_plan(join_visits_plan("2000-01-02", "2000-01-01"), EngineConfig())
    with pytest.raises(PlanError, match="needs an EngineConfig"):
        compile_plan(join_visits_plan())
    with pytest.raises(PlanError, match="no mesh lowering"):
        compile_plan(join_visits_plan(), EngineConfig(), mesh=True)


def test_join_visits_plan_runs_onto_the_device_join():
    """The whole chain is one lowered stage; its sink renders the table the
    plain reference prints, from lists of lines as from padded rows."""
    from locust_tpu import join_reference
    from locust_tpu.core import bytes_ops

    pages = [b"http://a,10,1", b"http://b,30,1", b"http://c,50,1"]
    visits = [b"1.1.1.1,http://a,1999-03-03,1.500000,x", b"1.1.1.1,http://b,1999-03-04,2.250000,x",
              b"2.2.2.2,http://b,2000-01-01,9.000000,x", b"2.2.2.2,http://b,2000-01-02,9.000000,x",
              b"3.3.3.3,http://z,1999-03-03,4.000000,x"]
    cfg = EngineConfig(block_lines=4, line_width=64, key_width=32)
    compiled = compile_plan(join_visits_plan(), cfg)
    assert [ln.split(":")[1].split("(")[0].strip() for ln in compiled.explain().splitlines()] == [
        "source", "source", "visit_join", "render"]
    want = join_reference.render(join_reference.join(pages, visits).rows)
    assert want == b"2.2.2.2\t3.00000000e+01\t9.00000000e+00\n1.1.1.1\t2.00000000e+01\t3.75000000e+00\n"
    res = compiled.run({"rankings": pages, "uservisits": visits})
    assert res.output == want and res.distinct == 2 and not res.truncated
    rows = {name: bytes_ops.strings_to_rows(lines, 64)
            for name, lines in (("rankings", pages), ("uservisits", visits))}
    assert compiled.run(rows).output == want
    with pytest.raises(PlanError, match="no input named"):
        compiled.run({"rankings": pages})
    with pytest.raises(PlanError, match="distinct inputs"):
        compiled.run_corpus(b"one corpus\n")
