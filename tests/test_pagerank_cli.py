"""``python -m locust_tpu pagerank`` held to the plain reference (PR 41).

At CPU size — a seeded R-MAT graph of some 10^4 nodes and 10^5 edges from
the benchmark's own generator, with hubs, dangling nodes and ids no edge
names: the CLI's printed ranks against ``locust_tpu/pagerank_reference.py``
(float64, no jax) within the benchmark cell's tolerance, the fast edge
parser against the line loop on clean files and on every malformed shape,
the native pass (PR 43) against numpy's ``_edges_clean`` on all of those and
the ONE parser with the library loaded against it with the library missing,
the numpy rank renderer against ``rank_row`` a row, and the spans and
counters a ``--trace-out`` file of a pagerank job holds.
"""

import json
import os
import sys

import jax
import numpy as np
import pytest

from helpers import native_ingest_missing

from locust_tpu import cli, pagerank_reference
from locust_tpu.apps.pagerank import (
    CHUNK, LANES, _contributions, _edge_chunks, _gather_share, pagerank, pagerank_prep,
    pagerank_step,
)
from locust_tpu.core import bytes_ops
from locust_tpu.io import native_ingest
from locust_tpu.plan import PlanError
from locust_tpu.plan import compile as plan_compile
from locust_tpu.plan.compile import edges_from_bytes, rank_row, render_ranks

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

import rmat_edges  # noqa: E402

# ``locust_tpu.apps.pagerank`` the MODULE: the package exports the function
# under the same name.
pagerank_module = sys.modules[pagerank.__module__]

with open(os.path.join(REPO, "benchmarks", "configs", "pagerank-rmat-5M.json")) as _f:
    CONFIG = json.load(_f)
TOLERANCE = CONFIG["tolerance"]


@pytest.fixture(scope="module")
def graph(tmp_path_factory):
    """(path, src, dst, N) of a 100,000-edge R-MAT edge list."""
    path = str(tmp_path_factory.mktemp("rmat") / "edges.txt")
    rmat_edges.build(path, 100_000, 2147483659)
    src, dst = rmat_edges.load(path)
    return path, src, dst, int(max(src.max(), dst.max())) + 1


def test_the_graph_has_hubs_dangling_nodes_and_unnamed_ids(graph):
    _, src, dst, n = graph
    out_degree = np.bincount(src, minlength=n)
    in_degree = np.bincount(dst, minlength=n)
    assert src.size == 100_000 and n > 10_000
    assert len({(s, d) for s, d in zip(src.tolist(), dst.tolist())}) == src.size
    assert (src != dst).all()
    assert in_degree.max() > 200 and out_degree.max() > 200          # hubs
    assert ((out_degree == 0) & (in_degree > 0)).sum() > 1000          # dangling
    assert ((out_degree == 0) & (in_degree == 0)).sum() > 100          # unnamed ids
    assert out_degree[n - 1] + in_degree[n - 1] > 0                    # N is fixed by a named id


def test_cli_ranks_match_the_float64_reference(graph, capsysbinary):
    path, src, dst, n = graph
    assert cli.main(["pagerank", path, "--backend", "cpu"]) == 0
    table = capsysbinary.readouterr().out
    assert table.count(b"\n") == n
    ids, ranks = pagerank_reference.parse_ranks(table)
    assert np.array_equal(ids, np.arange(n))
    want = pagerank_reference.pagerank(src, dst, n)
    assert (np.abs(ranks - want) / want).max() <= TOLERANCE["rank_rel"]
    assert abs(ranks.sum() - 1.0) <= TOLERANCE["sum_abs"]
    # Nine significant digits carry the float32 itself.
    from locust_tpu.apps.pagerank import pagerank

    device = np.asarray(pagerank(src.astype(np.int32), dst.astype(np.int32), num_nodes=n,
                                 num_iters=20, damping=0.85))  # traced, as the plan passes it
    assert np.array_equal(ranks.astype(np.float32), device)


def test_the_reference_agrees_with_the_benchmarks_own_copy(graph):
    _, src, dst, n = graph
    ours = pagerank_reference.pagerank(src, dst, n, num_iters=7, damping=0.9)
    np.testing.assert_allclose(ours, rmat_edges.oracle((src, dst), 7, 0.9), rtol=1e-13)
    assert abs(ours.sum() - 1.0) < 1e-12


@pytest.mark.parametrize("rounds, rounding", [(10, None), (20, "bfloat16")],
                         ids=["ten-rounds", "bfloat16"])
def test_the_tolerance_tells_a_short_or_a_coarse_result_from_a_sound_one(
        graph, rounds, rounding):
    import ml_dtypes

    _, src, dst, n = graph
    want = pagerank_reference.pagerank(src, dst, n)
    got = pagerank_reference.pagerank(src, dst, n, num_iters=rounds)
    if rounding:
        got = got.astype(ml_dtypes.bfloat16).astype(np.float64)
    assert (np.abs(got - want) / want).max() > 5 * TOLERANCE["rank_rel"]


# ------------------------------------------------------------ the parser

CLEAN = {
    "tabs": b"0\t1\n1\t2\n2\t0\n",
    "spaces": b"0 1\n1 2\n2 0\n",
    "mixed-separators": b"0 1\n1\t2\n2 0\n",
    "no-last-newline": b"0\t1\n1\t2\n2\t0",
    "snap-header": b"# Directed graph\n# Nodes: 3 Edges: 3\n# FromNodeId\tToNodeId\n0\t1\n1\t2\n2\t0\n",
    "one-edge": b"7 3",
    "long-ids": b"2147483647\t0\n12\t2147483646\n",
}


@pytest.mark.parametrize("name", sorted(CLEAN))
def test_a_clean_file_is_read_in_numpy_and_equals_the_line_loop(name):
    data = CLEAN[name]
    fast = plan_compile._edges_clean(data)
    assert fast is not None
    slow = plan_compile._edges_by_line(data)
    assert np.array_equal(fast[0], slow[0]) and np.array_equal(fast[1], slow[1])
    src, dst = edges_from_bytes(data)
    assert src.dtype == dst.dtype == np.int32
    assert np.array_equal(src, slow[0]) and np.array_equal(dst, slow[1])


# name -> (bytes, the edges the line loop reads, or the error it words)
NOT_CLEAN = {
    "comment-after-data": (b"0 1\n# later\n1 0\n", ([0, 1], [1, 0])),
    "blank-line": (b"0 1\n\n1 0\n", ([0, 1], [1, 0])),
    "crlf": (b"0 1\r\n1 0\r\n", ([0, 1], [1, 0])),
    "leading-space": (b" 0 1\n1 0\n", ([0, 1], [1, 0])),
    "trailing-space": (b"0 1 \n1 0\n", ([0, 1], [1, 0])),
    "two-tabs": (b"0\t\t1\n1 0\n", ([0, 1], [1, 0])),
    "indented-comment": (b"  # c\n0 1\n", ([0], [1])),
    "plus-sign": (b"0 +1\n", ([0], [1])),
    "underscore": (b"1_0 1\n", ([10], [1])),
    "third-field": (b"0 1\n0 1 2\n", "edge list line 2: expected 'src dst', got b'0 1 2'"),
    "lone-field": (b"0 1\n5\n", "edge list line 2: expected 'src dst', got b'5'"),
    "lone-field-pair": (b"0\n1\n", "edge list line 1: expected 'src dst', got b'0'"),
    "field-split-over-lines": (b"0 1\n2\n3 4 5\n", "edge list line 2: expected 'src dst', got b'2'"),
    "non-integer": (b"# c\n0 1\n1 x\n", "edge list line 3: non-integer node id b'1 x'"),
    "decimal-point": (b"0 1.5\n", "edge list line 1: non-integer node id b'0 1.5'"),
    "negative": (b"0 1\n-1 0\n", "edge list has a negative node id"),
    "empty": (b"", "edge list has no edges"),
    "comments-only": (b"# a\n# b", "edge list has no edges"),
    "past-int32": (b"0 2147483648\n", "edge list has a node id past int32 (2147483648 > 2147483647)"),
    "past-int64-by-line": (b"0 1\n\n99999999999999999999 0\n",
                           "edge list has a node id past int32 (99999999999999999999 > 2147483647)"),
}


@pytest.mark.parametrize("name", sorted(NOT_CLEAN))
def test_any_other_file_goes_through_the_line_loop_with_its_messages(name):
    data, want = NOT_CLEAN[name]
    if name != "past-int32":  # clean, and a loud error all the same
        assert plan_compile._edges_clean(data) is None
    if isinstance(want, str):
        with pytest.raises(PlanError) as err:
            edges_from_bytes(data)
        assert str(err.value) == want
    else:
        src, dst = edges_from_bytes(data)
        assert src.tolist() == want[0] and dst.tolist() == want[1]


def test_a_clean_number_past_int64_is_a_loud_error_not_a_wrapped_index():
    with pytest.raises(PlanError, match="past int32"):
        edges_from_bytes(b"0\t1\n99999999999999999999999\t0\n")


def test_the_generated_file_takes_the_fast_path(graph):
    path, src, dst, _ = graph
    with open(path, "rb") as f:
        fast = plan_compile._edges_clean(f.read())
    assert fast is not None
    assert np.array_equal(fast[0], src) and np.array_equal(fast[1], dst)


# ------------------------------------------------ the native pass (PR 43)

# Every case above, and the native pass's own: what it must read as numpy
# reads it, and what both must leave to the line loop.
PARSER_CASES = {
    **CLEAN,
    **{name: data for name, (data, _) in NOT_CLEAN.items()},
    "mixed-separators-no-last-newline": b"3\t4\n5 6\n7\t8",
    "leading-zeros": b"007\t01\n0\t0000\n00 10\n",
    "top-of-int32": b"0\t2147483647\n2147483647 1\n",
    "int32-plus-one-as-src": b"2147483648\t0\n",
    "digits-18": b"0\t1\n999999999999999999\t2\n",
    "header-then-no-last-newline": b"# h\n1 2\n3 4",
    "comment-without-newline-after-data": b"0 1\n# c",
    "comment-line-alone-with-newline": b"#\n",
    "newline-only": b"\n",
    "digits-only": b"12",
    "separator-ends-the-file": b"0 1\n5\t",
    "lone-field-last-line": b"0 1\n5",
    "starts-with-separator": b"\t0 1\n",
    "starts-with-newline": b"\n0 1\n",
    "two-newlines-at-the-end": b"0 1\n\n",
    "cr-alone": b"0 1\r1 0\n",
    "minus-zero": b"0 -0\n",
    "nul-byte": b"0 1\n1\x000\n",
    "vertical-tab": b"0\x0b1\n",
    "form-feed": b"0\x0c1\n",
    "utf8-digits": "0 \u0661\n".encode(),
    "hex": b"0 0x10\n",
    "exponent": b"0 1e3\n",
}
# More than 18 digits: numpy reads the number (past int64 as int64's largest),
# the native pass hands the file to the line loop, whose Python ints read it.
OVER_18_DIGITS = {
    "digits-19": b"0\t1\n1000000000000000000\t2\n",
    "digits-19-past-int64": b"0\t1\n9999999999999999999\t2\n",
    "digits-23": b"0\t1\n99999999999999999999999\t0\n",
    "zeros-19": b"0000000000000000001\t2\n",
}
PARSER_CASES.update(OVER_18_DIGITS)


def _assert_reads_as_numpy(got, want):
    """The native pass's ``(src, dst, top)`` against ``_edges_clean``'s int64
    pair: int32 arrays, a value past int32 narrowed as numpy narrows it, and
    the top id in full, which is what says so."""
    src, dst, top = got
    assert src.dtype == dst.dtype == np.int32
    assert src.tobytes() == want[0].astype(np.int32).tobytes()
    assert dst.tobytes() == want[1].astype(np.int32).tobytes()
    assert top == max(int(want[0].max()), int(want[1].max()))


@pytest.mark.parametrize("name", sorted(PARSER_CASES))
def test_the_native_pass_reads_what_numpy_reads_and_refuses_what_it_refuses(name):
    data = PARSER_CASES[name]
    want = plan_compile._edges_clean(data)
    got = native_ingest.parse_edges(data)
    if name in OVER_18_DIGITS:
        assert want is not None and got is None
    elif want is None:
        assert got is None
    else:
        _assert_reads_as_numpy(got, want)


def test_the_native_pass_agrees_with_numpy_on_seeded_damage_to_a_clean_file():
    """A clean file with a few bytes swapped for the grammar's own and its
    neighbours': whichever way each falls, both readers say the same."""
    rng = np.random.default_rng(43)
    alphabet = np.frombuffer(b"0123456789\t \n\n\t#-+\r9", np.uint8)
    clean = not_clean = 0
    for _ in range(1500):
        edges = rng.integers(0, 3000, (int(rng.integers(1, 12)), 2))
        data = np.frombuffer(
            b"# head\n" * int(rng.integers(0, 2))
            + b"".join(b"%d%s%d\n" % (a, b"\t "[i % 2:i % 2 + 1], b)
                       for i, (a, b) in enumerate(edges.tolist())), np.uint8).copy()
        hits = rng.integers(0, data.size, int(rng.integers(0, 3)))
        data[hits] = rng.choice(alphabet, hits.size)
        data = data[:data.size - int(rng.integers(0, 2))].tobytes()
        want = plan_compile._edges_clean(data)
        got = native_ingest.parse_edges(data)
        if want is None:
            assert got is None, data
            not_clean += 1
        else:
            assert got is not None, data
            _assert_reads_as_numpy(got, want)
            clean += 1
    assert clean > 300 and not_clean > 300


def _parsed_or_refused(data):
    try:
        src, dst = edges_from_bytes(data)
    except PlanError as e:
        return str(e)
    assert src.dtype == dst.dtype == np.int32
    return src.tolist(), dst.tolist()


@pytest.mark.parametrize("name", sorted(PARSER_CASES))
def test_the_one_parser_is_the_same_with_the_library_and_without(name, monkeypatch):
    data = PARSER_CASES[name]
    loaded = _parsed_or_refused(data)
    native_ingest_missing(monkeypatch)
    missing = _parsed_or_refused(data)
    if name in ("digits-19-past-int64", "digits-23"):
        # The one wording that differs: numpy names int64's largest, the line loop the number.
        digits = data.split()[2].decode()
        assert loaded == f"edge list has a node id past int32 ({digits} > 2147483647)"
        assert missing == "edge list has a node id past int32 (9223372036854775807 > 2147483647)"
    else:
        assert loaded == missing


def test_the_generated_file_takes_the_native_pass(graph):
    path, src, dst, n = graph
    with open(path, "rb") as f:
        got = native_ingest.parse_edges(f.read())
    assert np.array_equal(got[0], src) and np.array_equal(got[1], dst)
    assert got[2] == n - 1 and got[0].dtype == got[1].dtype == np.int32


def test_the_cli_prints_the_same_bytes_with_the_library_and_without(
        graph, capsysbinary, monkeypatch):
    assert cli.main(["pagerank", graph[0], "--backend", "cpu"]) == 0
    loaded = capsysbinary.readouterr()
    native_ingest_missing(monkeypatch)
    assert cli.main(["pagerank", graph[0], "--backend", "cpu"]) == 0
    missing = capsysbinary.readouterr()
    assert loaded.out == missing.out and loaded.out.count(b"\n") == graph[3]
    assert loaded.err == missing.err


# ------------------------------------------------------------ the renderer

def _vectors():
    rng = np.random.default_rng(41)
    bits = rng.integers(1, 0x7F7FFFFF, 50_000, dtype=np.uint32)
    return {
        "random-bits": bits.view(np.float32),
        "ranks-like": (rng.random(30_000) ** 6 / 9e5).astype(np.float32),
        "powers-of-two": np.float32(2.0) ** -np.arange(0, 149, dtype=np.float32),
        "powers-of-ten": np.array([float(f"1e{e}") for e in range(-44, 39)], np.float32),
        "ties-and-carries": np.array([2.0 ** -13, 9.9999999e-7, 0.99999999, 1.0, 0.0,
                                      1e-45, 3.4e38, 1.220703125e-4, 9.9999999949e-5]),
        "uniform": np.full(1001, 1 / 875713, np.float32),
        "one": np.array([0.25], np.float32),
        "none": np.zeros(0, np.float32),
    }


@pytest.mark.parametrize("name", sorted(_vectors()))
def test_rank_rows_rendered_in_numpy_equal_rank_row_a_row(name):
    ranks = _vectors()[name]
    want = b"".join(rank_row(i, r) for i, r in enumerate(ranks))
    assert bytes_ops.render_rank_rows(ranks) == want
    assert render_ranks(ranks) == want


@pytest.mark.parametrize("bad", [-1e-6, float("nan"), float("inf"), 1e-120],
                         ids=["negative", "nan", "inf", "three-digit-exponent"])
def test_what_the_fixed_layout_cannot_spell_is_joined_a_row(bad):
    ranks = np.array([0.5, bad, 0.25])
    assert bytes_ops.render_rank_rows(ranks) is None
    assert render_ranks(ranks) == b"".join(rank_row(i, r) for i, r in enumerate(ranks))


def test_a_rank_row_carries_nine_significant_digits():
    assert rank_row(3, np.float32(1 / 875713)) == b"3\t1.14192665e-06\n"
    assert np.float32(float(rank_row(0, np.float32(1 / 875713)).split()[1])) == np.float32(1 / 875713)


# ------------------------------------------------------------ the CLI's checks

def test_num_nodes_is_checked_against_both_bounds_before_the_device(tmp_path, capsys):
    edges = tmp_path / "e.txt"
    edges.write_bytes(b"0 1\n1 5\n")
    assert cli.main(["pagerank", str(edges), "--num-nodes", "5", "--backend", "cpu"]) == 1
    err = capsys.readouterr().err
    assert "--num-nodes 5 but max node id is 5" in err and "int32" in err
    assert cli.main(["pagerank", str(edges), "--num-nodes", str(1 << 32),
                     "--backend", "cpu"]) == 1
    assert "--num-nodes under 2147483648" in capsys.readouterr().err
    edges.write_bytes(b"0 1\n1 2147483648\n")
    assert cli.main(["pagerank", str(edges), "--backend", "cpu"]) == 1
    assert f"{edges}: edge list has a node id past int32" in capsys.readouterr().err
    edges.write_bytes(b"0 1\n1 2\n")
    assert cli.main(["pagerank", str(edges), "--num-nodes", "6", "--backend", "cpu"]) == 0
    out, err = capsys.readouterr()
    assert out.count("\n") == 6 and "2 edges loaded, 6 nodes" in err


def test_top_prints_the_highest_ranks_in_the_one_spelling(graph, capsysbinary):
    path, src, dst, n = graph
    assert cli.main(["pagerank", path, "--top", "5", "--backend", "cpu"]) == 0
    ids, ranks = pagerank_reference.parse_ranks(capsysbinary.readouterr().out)
    want = pagerank_reference.pagerank(src, dst, n)
    assert ids.tolist() == np.argsort(-want, kind="stable")[:5].tolist()
    assert (np.diff(ranks) <= 0).all()


# ------------------------------------------------------------ spans and counters

SPANS_ONCE = ("cli.setup", "cli.load", "pagerank.read", "pagerank.parse", "cli.run",
              "pagerank.h2d", "pagerank.iterate", "pagerank.d2h", "cli.output",
              "cli.output.render", "cli.output.write")


@pytest.mark.parametrize("native", [1, 0], ids=["native", "library-missing"])
def test_a_traced_pagerank_job_records_every_span_once_and_its_counters(
        graph, tmp_path, capsysbinary, monkeypatch, native):
    path, src, _, n = graph
    if not native:
        native_ingest_missing(monkeypatch)
    trace = tmp_path / "pr.trace.json"
    assert cli.main(["pagerank", path, "--backend", "cpu", "--trace-out", str(trace)]) == 0
    capsysbinary.readouterr()
    doc = json.loads(trace.read_text())
    spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    names = [e["name"] for e in spans]
    for name in SPANS_ONCE:
        assert names.count(name) == 1, (name, names)
    by = {e["name"]: e for e in spans}
    ids = {e["args"]["id"]: e["name"] for e in spans}

    def parent(name):
        return ids.get(by[name]["args"].get("parent"))

    assert parent("pagerank.read") == parent("pagerank.parse") == "cli.load"
    assert parent("pagerank.h2d") == parent("pagerank.iterate") == "plan.run"
    assert parent("plan.run") == "cli.run" and parent("engine.sync") == "pagerank.iterate"
    assert parent("cli.output.render") == parent("cli.output.write") == "cli.output"
    assert by["engine.sync"]["args"]["what"] == "iterate"
    parse = by["pagerank.parse"]["args"]
    assert (parse["edges"], parse["fast"], parse["native"]) == (src.size, 1, native)
    assert parse["bytes"] == by["pagerank.read"]["args"]["bytes"] == os.path.getsize(path)
    iterate = by["pagerank.iterate"]["args"]
    assert (iterate["nodes"], iterate["edges"], iterate["iters"]) == (n, src.size, 20)
    assert by["cli.output.render"]["args"]["rows"] == n
    assert by["cli.setup"]["ts"] + by["cli.setup"]["dur"] <= by["cli.load"]["ts"] + 1e3
    counters = doc["otherData"]["metrics"]["counters"]
    assert {k: v for k, v in counters.items() if k.startswith("pagerank.")} == {
        "pagerank.edges": src.size, "pagerank.nodes": n, "pagerank.iterations": 20,
        "pagerank.parse.native": native}


def test_an_untraced_pagerank_job_opens_no_span(graph, capsysbinary, monkeypatch):
    from locust_tpu import obs
    from locust_tpu.obs import trace as obs_trace

    opened = []
    monkeypatch.setattr(obs_trace._Span, "__init__",
                        lambda self, *a, **k: opened.append(a))
    assert cli.main(["pagerank", graph[0], "--backend", "cpu"]) == 0
    capsysbinary.readouterr()
    assert not opened and obs.current() is None


# ------------------------------------------------ the round gathers once (PR 42)

def _hub_graph(n, edges, seed):
    """A seeded multigraph over ``n`` slots: sources and destinations drawn
    with replacement from a steep distribution over the first 70% of the
    ids (hubs, repeated edges), the next 20% named only as destinations
    (dangling), the last 10% named by no edge."""
    rng = np.random.default_rng(seed)
    body, named = n * 7 // 10, n * 9 // 10
    src = (body * rng.random(edges) ** 3).astype(np.int32)
    dst = (named * rng.random(edges) ** 2).astype(np.int32)
    return src, dst, n


def _probe_graph(tmp_path):
    probe = {k: v for k, v in CONFIG["probe"].items() if k != "why"}
    path = str(tmp_path / "probe.txt")
    rmat_edges.build_probe(path, 2147483659, **probe)
    src, dst = rmat_edges.load(path)
    assert (probe["ids"], src.size) == (8192, 22_304)         # the shape the cell's set-up ranks
    return src.astype(np.int32), dst.astype(np.int32), probe["ids"]


GRAPHS = {
    "seven-nodes": lambda tmp_path: _hub_graph(7, 40, 1),
    "hubs-3001": lambda tmp_path: _hub_graph(3001, 50_000, 2),
    "probe-8192": _probe_graph,
}


@pytest.fixture(params=sorted(GRAPHS))
def small_graph(request, tmp_path):
    src, dst, n = GRAPHS[request.param](tmp_path)
    out_degree, in_degree = np.bincount(src, minlength=n), np.bincount(dst, minlength=n)
    assert ((out_degree == 0) & (in_degree > 0)).any()       # dangling
    assert ((out_degree == 0) & (in_degree == 0)).any()      # slots no edge names
    assert in_degree.max() > 1.5 * in_degree.mean()          # a hub
    return src, dst, n


def _poisoned(ranks, src):
    """``ranks`` with an ``inf`` and a ``nan`` at nodes NO edge gathers
    from, each in a 128-lane row that holds a node some edge does."""
    ranks = ranks.copy()
    sourced = np.zeros(ranks.size, bool)
    sourced[src] = True
    row_named = np.repeat(np.add.reduceat(sourced, np.arange(0, ranks.size, LANES)) > 0, LANES)
    beside = np.flatnonzero(~sourced & row_named[:ranks.size])
    assert beside.size >= 2
    ranks[beside[0::2]], ranks[beside[1::2]] = np.inf, np.nan
    return ranks


def test_one_gather_of_the_share_is_the_two_gather_round_bit_for_bit(small_graph):
    src, dst, n = small_graph
    if n != CONFIG["probe"]["ids"]:    # the probe's edges are distinct, the others' repeat
        assert len(set(zip(src.tolist(), dst.tolist()))) < src.size
    inv_deg, _ = pagerank_prep(src, num_nodes=n)

    @jax.jit
    def two_gathers(src, dst, ranks, inv_deg):
        return jax.ops.segment_sum(ranks[src] * inv_deg[src], dst, num_segments=n)

    @jax.jit
    def one_gather(src, dst, ranks, inv_deg):
        return _contributions(_edge_chunks(src), dst, ranks, inv_deg, n)

    rng = np.random.default_rng(n)
    skewed = (rng.random(n) ** 4 / n).astype(np.float32)
    # The rows an edge takes whole hold its neighbours too: an inf or a nan
    # in a lane NO edge selects must not reach any sum (the ``where``; a
    # product with a one-hot would let it through).
    for ranks in (np.full(n, 1.0 / n, np.float32), skewed, _poisoned(skewed, src)):
        got = np.asarray(one_gather(src, dst, ranks, inv_deg))
        want = np.asarray(two_gathers(src, dst, ranks, inv_deg))
        assert got.dtype == np.float32 and got.tobytes() == want.tobytes()
        assert np.isfinite(got).all()


@pytest.mark.parametrize("rounds", [1, 4])
def test_the_scan_is_as_many_steps_bit_for_bit(small_graph, rounds):
    src, dst, n = small_graph
    damping = np.float32(0.85)
    inv_deg, dangling = pagerank_prep(src, num_nodes=n)
    ranks = np.full(n, 1.0 / n, np.float32)
    for _ in range(rounds):
        ranks = pagerank_step(src, dst, ranks, inv_deg, dangling, damping, num_nodes=n)
    whole = pagerank(src, dst, num_nodes=n, num_iters=rounds, damping=damping)
    assert np.asarray(whole).tobytes() == np.asarray(ranks).tobytes()


def _gathers(jaxpr, in_loops=0):
    """``(result shape, loops it stands inside)`` of every ``gather``
    equation of ``jaxpr``, sub-programs included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "gather":
            found.append((eqn.outvars[0].aval.shape, in_loops))
        depth = in_loops + (eqn.primitive.name in ("scan", "while"))
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += _gathers(sub, depth)
    return found


def test_a_round_of_the_program_gathers_rows_a_chunk_at_a_time():
    """The guard against a one-word gather coming back (a gathered word
    costs a v5e three times a gathered row): no gather of the program yields
    a word an edge or a word an edge of a chunk, and ONE yields a chunk's
    128-lane rows, inside the round's inner loop."""
    src, dst, n = _hub_graph(3001, 2 * CHUNK + 7, 2)        # three chunks, the last padded
    program = jax.make_jaxpr(
        lambda s, d, damping: pagerank(s, d, num_nodes=n, num_iters=3, damping=damping)
    )(src, dst, np.float32(0.85))
    assert _gathers(program.jaxpr) == [((CHUNK, LANES), 2)]   # the scan's round, the chunks' loop
    node = np.zeros(n, np.float32)
    step = jax.make_jaxpr(
        lambda s, d, r, i, g, damping: pagerank_step(s, d, r, i, g, damping, num_nodes=n)
    )(src, dst, node, node, node > 0, np.float32(0.85))
    assert _gathers(step.jaxpr) == [((CHUNK, LANES), 1)]


# ------------------------------------------- the share gathered as rows (PR 46)

def _share_and_ids(nodes, edges, seed):
    """A share vector and ``edges`` ids into it: the even ids are named, the
    odd ones — neighbours in the same rows — hold ``inf`` and ``nan``."""
    rng = np.random.default_rng([nodes, edges, seed])
    share = (rng.random(nodes) ** 4).astype(np.float32)
    share[1::4], share[3::4] = np.inf, np.nan
    src = 2 * rng.integers(0, (nodes + 1) // 2, edges)
    return share, src.astype(np.int32)


@pytest.mark.parametrize("nodes", [1, 127, 128, 129, 916_428])
@pytest.mark.parametrize(
    "edges", [0, 1, CHUNK - 1, CHUNK, CHUNK + 1, 3 * CHUNK + 7],
    ids=["none", "one", "chunk-less-1", "chunk", "chunk-and-1", "3-chunks-and-7"])
def test_the_row_gather_is_the_one_word_gather_bit_for_bit(nodes, edges):
    share, src = _share_and_ids(nodes, edges, 1)
    got = np.asarray(jax.jit(_gather_share)(share, src))
    assert got.dtype == np.float32 and got.shape == (edges,)
    assert got.tobytes() == share[src].tobytes() and np.isfinite(got).all()


@pytest.mark.parametrize("chunk", [1, 8, 1000])
@pytest.mark.parametrize("nodes", [129, 916_428])
def test_the_row_gather_holds_at_any_chunk_size(nodes, chunk, monkeypatch):
    """Many chunks, the last one short by all but seven (or by none)."""
    monkeypatch.setattr(pagerank_module, "CHUNK", chunk)
    share, src = _share_and_ids(nodes, 5 * chunk + 7, chunk)
    # A jit of its own: one of ``_gather_share`` could answer from a trace
    # of these shapes made under another CHUNK.
    got = np.asarray(jax.jit(lambda share, src: _gather_share(share, src))(share, src))
    assert got.tobytes() == share[src].tobytes()


def test_an_id_out_of_range_reads_the_padding_or_the_last_row():
    """Callers pass valid ids (the CLI refuses the others before the
    device).  What the row spelling makes of the rest, so that nobody has to
    guess: ids are read unsigned and the row index clamps — never a wrap to
    ``share[-1]`` as numpy's indexing has it."""
    share = np.arange(1, 130, dtype=np.float32)              # two rows, 127 zeros of padding
    ids = np.array([-1, 129, 1000, 2 ** 31 - 1, 128, -2 ** 31], np.int32)
    got = np.asarray(_gather_share(share, ids))
    assert got.tolist() == [0.0, 0.0, 0.0, 0.0, 129.0, 129.0]


def test_the_cli_prints_the_bytes_the_one_word_gather_printed(
        graph, capsysbinary, monkeypatch):
    """Same bits, not merely inside the tolerance: one seeded graph through
    the CLI under the row spelling and under ``share[src]``, which is what
    every release before PR 46 ran."""
    argv = ["pagerank", graph[0], "--backend", "cpu"]
    assert cli.main(argv) == 0
    rows = capsysbinary.readouterr().out
    words = []

    def one_word(share, chunks):
        words.append(chunks.size)
        return share[chunks.reshape(-1)]

    monkeypatch.setattr(pagerank_module, "_gather_chunks", one_word)
    pagerank.clear_cache()                 # its trace of this shape holds the rows
    try:
        assert cli.main(argv) == 0
    finally:
        pagerank.clear_cache()             # and now the words
    assert words == [-(-graph[1].size // CHUNK) * CHUNK]    # traced once: every edge, in whole chunks
    assert capsysbinary.readouterr().out == rows and rows.count(b"\n") == graph[3]
