"""``pagerank5M.batch`` on the CPU at rehearsal size (100,000 edges; kept
out of ``tests/`` like its neighbours):

    env JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_edges_cell.py -q

the generator is a function of its seed and writes a SNAP-style list of
distinct edges, the oracle is Graphalytics' PageRank by hand, the least
bytes are what the page says, a rehearsal of the cell is correct and reads
every metric's name, the control (one round short) is NOT correct — by the
probe graph, which tells 19 rounds from 20 where the cell's own cannot — a
traced job is held to 20 rounds by its counter, a captured table with one
rank altered is not correct, and a program whose ranks are off fails in set-up with
exit code 4 and no result line.
"""

import json
import os
import subprocess
import sys
import types

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import pagerank_least_bytes  # noqa: E402
import rmat_edges  # noqa: E402
import yardstick  # noqa: E402
from drivers import closed_loop_cli_edges  # noqa: E402

CELL = "pagerank5M.batch"
TOLERANCE = {"rank_rel": 3e-5, "sum_abs": 3e-6}


def test_the_generator_is_a_function_of_its_seed_and_writes_snaps_format(tmp_path):
    a, b, c = (str(tmp_path / n) for n in "abc")
    nbytes = rmat_edges.build(a, 30000, 2147483659)
    rmat_edges.build(b, 30000, 2147483659)
    rmat_edges.build(c, 30000, 2147483660)
    data = open(a, "rb").read()
    assert len(data) == nbytes and data == open(b, "rb").read() != open(c, "rb").read()
    head, body = data.split(b"# FromNodeId\tToNodeId\n")
    assert head.startswith(b"# Directed graph") and b" Edges: 30000\n" in head
    lines = body.split(b"\n")
    assert lines[-1] == b"" and len(lines) == 30001
    assert all(ln.count(b"\t") == 1 and ln.replace(b"\t", b"").isdigit() for ln in lines[:-1])
    src, dst = rmat_edges.load(a)
    assert src.size == 30000 and (src != dst).all()
    assert np.unique(src * (1 << 32) + dst).size == 30000          # distinct
    named = np.union1d(src, dst).size
    n = int(max(src.max(), dst.max())) + 1
    assert b"# Nodes: %d Edges" % named in head
    assert 0.03 < (n - named) / n < 0.06                             # web-Google's 4.4% of ids unnamed
    assert np.bincount(dst).max() > 50 * src.size / n                # hubs


def test_the_oracle_is_graphalytics_pagerank_by_hand():
    src = np.array([0, 0, 1, 2, 4, 4, 4, 6])   # 3 dangles, 5 is named by no edge,
    dst = np.array([1, 2, 2, 0, 3, 0, 0, 4])   # 4 -> 0 stands twice and counts twice
    n, d = 7, 0.85
    ranks = [1.0 / n] * n
    out = [list(dst[src == i]) for i in range(n)]
    for _ in range(3):
        new = [(1 - d) / n] * n
        lost = sum(ranks[i] for i in range(n) if not out[i])
        for i in range(n):
            for j in out[i]:
                new[j] += d * ranks[i] / len(out[i])
        ranks = [r + d * lost / n for r in new]
    got = rmat_edges.oracle((src, dst), 3, d)
    assert got.shape == (7,) and np.allclose(got, ranks, rtol=1e-14)
    assert abs(got.sum() - 1.0) < 1e-14


def test_least_bytes_are_the_pages_arithmetic():
    sizes = {"edges": 5_105_039, "nodes": 916_428, "num_iters": 20}
    a_round = 4 * (3 * 5_105_039 + 3 * 916_428)
    assert pagerank_least_bytes.job(sizes) == 4 * (5_105_039 + 916_428) + 20 * a_round
    assert 1.46e9 < pagerank_least_bytes.job(sizes) < 1.48e9


def _run(*argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    p = subprocess.run([sys.executable, *argv], env=env, capture_output=True, text=True,
                       timeout=600)
    return p, (p.stdout.strip().splitlines() or [""])[-1]


def test_a_rehearsal_of_the_cell_is_correct_and_reads_every_metric():
    p, last = _run(os.path.join(BENCH, "run.py"), "--workload", CELL, "--seed",
                   "2147483661", "--seconds", "2", "--trace", "1", "--rehearse")
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    doc = json.loads(last)
    assert doc["correct"] is True and doc["failed"] == 0 and "metrics" not in doc
    assert "compared: worst relative error of a rank" in p.stdout
    read = next(ln for ln in p.stdout.splitlines() if "metrics read" in ln)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    on_the_cpu = [m["name"] for m in bench["per_layer"]
                  if CELL in m.get("workloads", ()) and m["source"] == "program_span"]
    assert len(on_the_cpu) == 11 and "syncs_per_job.tput" in on_the_cpu, on_the_cpu
    assert "probe (" in p.stdout and "argv tail []" in p.stdout      # ranked once, in set-up
    assert p.stdout.count("probe (") == 1
    for name in on_the_cpu + ["compiles_in_window.tput"]:
        assert name in read, (name, read)


def test_every_metric_of_the_cell_has_a_file_and_a_reader():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert {m["name"] for m in mine} == {
        "edge_read_ms.pr", "edge_parse_ms.pr", "edge_h2d_ms.pr", "iterate_dev_ms.pr",
        "rank_d2h_ms.pr", "iterate_roofline.pr"}
    for name in ("sync_wait_ms.tput", "syncs_per_job.tput"):    # engine.sync's accepted readers
        assert CELL in next(m for m in bench["per_layer"] if m["name"] == name)["workloads"]
    for m in mine:
        assert m["layer"] == "iterate" and m["moves"] == "input_MB_per_s"
        with open(os.path.join(BENCH, "layer_metrics", m["name"].rpartition(".")[0] + ".json")) as f:
            spec = json.load(f)
        assert os.path.exists(os.path.join(BENCH, "readers", spec["reader"] + ".py"))
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("pagerank-rmat-5M", "batch_edges", 1)
    assert CELL in next(m for m in bench["end_to_end"] if m["name"] == "input_MB_per_s")["workloads"]


def test_the_control_is_not_correct():
    p, last = _run(os.path.join(BENCH, "control.py"), "--workload", CELL,
                   "--seeds", "5,2147483659", "--rehearse")
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    doc = json.loads(last)
    assert doc["control_holds"] is True
    for row in doc["seeds"]:
        assert row["sound_correct"] and not row["control_correct"], row
        assert row["control_verdict"].startswith("on the probe graph, where a round shows: "
                                                 "the ranks differ from the reference")


def test_the_probe_tells_19_rounds_from_20_where_the_cells_graph_cannot(tmp_path):
    with open(os.path.join(BENCH, "configs", "pagerank-rmat-5M.json")) as f:
        probe = json.load(f)["probe"]
    probe.pop("why")
    a, b, c = (str(tmp_path / n) for n in "abc")
    nbytes = rmat_edges.build_probe(a, 2147483659, **probe)
    rmat_edges.build_probe(b, 2147483659, **probe)
    rmat_edges.build_probe(c, 2147483660, **probe)
    assert os.path.getsize(a) == nbytes and open(a, "rb").read() == open(b, "rb").read()
    edges, other = rmat_edges.load(a), rmat_edges.load(c)
    for src, dst in (edges, other):                                   # one shape for every seed
        assert src.size == probe["edges"] + probe["chains"] * probe["chain_nodes"]
        assert max(src.max(), dst.max()) + 1 == probe["ids"]
    body = rmat_edges.edge_list(probe["edges"], 2147483659)
    assert all(np.array_equal(e[:probe["edges"]], r) for e, r in zip(edges, body))
    chain = edges[1][probe["edges"]:]                                  # every chain node, fed once
    assert np.array_equal(chain, np.arange(probe["ids"] - chain.size, probe["ids"]))
    assert max(body[0].max(), body[1].max()) < chain[0]
    feeders = edges[0][probe["edges"]:][::probe["chain_nodes"]]
    assert feeders.max() < chain[0] and np.unique(feeders).size > probe["chains"] // 2
    assert (np.bincount(edges[0], minlength=probe["ids"])[chain] == 0).sum() == probe["chains"]
    try:
        rmat_edges.build_probe(a, 5, **dict(probe, ids=probe["ids"] - 1000))
        raise AssertionError("a body that reaches into the chains was written")
    except ValueError as err:
        assert "the chains start at" in str(err)
    want = rmat_edges.oracle(edges, 20)
    for rounds, off in ((19, 1e-2), (21, 1e-2), (15, 4e-2)):
        short = rmat_edges.oracle(edges, rounds)
        verdict, worst, _ = closed_loop_cli_edges.compare(_table(short), want, TOLERANCE)
        assert "differ from the reference" in verdict and worst > off, (rounds, worst)
    assert closed_loop_cli_edges.compare(_table(want.astype(np.float32)), want, TOLERANCE)[0] is None
    flat = [rmat_edges.oracle(body, rounds) for rounds in (20, 19)]   # R-MAT alone: nothing to see
    assert closed_loop_cli_edges.compare(_table(flat[1]), flat[0], TOLERANCE)[0] is None


def test_a_traced_job_is_held_to_the_configurations_rounds_by_its_counter(tmp_path):
    env = types.SimpleNamespace(config={"algorithm": {"num_iters": 20}})
    trace = tmp_path / "spans.json"

    def counted(counters):
        trace.write_text(json.dumps({"traceEvents": [], "otherData": {"metrics": {"counters": counters}}}))
        return closed_loop_cli_edges.rounds_verdict(env, str(trace))

    assert counted({"pagerank.iterations": 20, "pagerank.edges": 7}) is None
    assert "counted 19 rounds" in counted({"pagerank.iterations": 19})
    assert "no counter pagerank.iterations" in counted({"pagerank.edges": 7})
    trace.write_text("{}")
    assert "no counter" in closed_loop_cli_edges.rounds_verdict(env, str(trace))
    assert "no counter" in closed_loop_cli_edges.rounds_verdict(env, str(tmp_path / "none.json"))


def _table(ranks) -> bytes:
    return b"".join(b"%d\t%.8e\n" % (i, r) for i, r in enumerate(ranks))


def test_a_table_with_one_rank_altered_is_not_correct():
    want = rmat_edges.oracle(rmat_edges.edge_list(20000, 9))
    config = {"tolerance": TOLERANCE}
    env = types.SimpleNamespace(expect_ranks=want, platform="cpu", config=config)
    sound = want.astype(np.float32)
    job = yardstick.JobResult(0, _table(sound), "[locust] backend: cpu (cpu x 1)\n", 0.0, 1.0, None)
    assert closed_loop_cli_edges.check_job(env, job) is None
    altered = sound.copy()
    altered[1234] *= np.float32(1.0001)
    job.stdout = _table(altered)
    assert "node 1234" in closed_loop_cli_edges.check_job(env, job)
    job.stdout = _table(sound)[:-30]                                  # a line cut
    assert "lines" in closed_loop_cli_edges.check_job(env, job)
    job.stdout = _table(sound[:-1]) + b"7\t1.0e-06\n"                 # an id out of order
    assert "not 0 .. N-1" in closed_loop_cli_edges.check_job(env, job)
    job.stdout = _table(sound).replace(b"e-", b"x-", 1)               # not a number
    assert "numbers" in closed_loop_cli_edges.check_job(env, job)
    job.stdout = _table(sound)[:-15] + b"\n"                          # the last rank missing
    assert "numbers" in closed_loop_cli_edges.check_job(env, job)
    job.stdout = _table(sound)[:-1] + b"-0\n"                         # a word numpy reads in part
    assert "numbers" in closed_loop_cli_edges.check_job(env, job)
    job.stdout = _table(sound * np.float32(1 + 1e-5))               # every rank inside, the sum outside
    assert "sum" in closed_loop_cli_edges.check_job(env, job)
    job.stdout = _table(sound)
    job.stderr = ""
    assert "device line" in closed_loop_cli_edges.check_job(env, job)
    job.stderr = "[locust] backend: cpu (cpu x 1)\n[locust] WARN edges skipped\n"
    assert "lost or demoted" in closed_loop_cli_edges.check_job(env, job)


def test_bfloat16_ranks_are_not_correct():
    import ml_dtypes

    want = rmat_edges.oracle(rmat_edges.edge_list(20000, 9))
    coarse = want.astype(ml_dtypes.bfloat16).astype(np.float64)
    verdict, worst, _ = closed_loop_cli_edges.compare(_table(coarse), want, TOLERANCE)
    assert verdict is not None and worst > 50 * TOLERANCE["rank_rel"]


def test_the_configurations_tolerance_is_the_one_these_cases_use():
    with open(os.path.join(BENCH, "configs", "pagerank-rmat-5M.json")) as f:
        config = json.load(f)
    assert {k: config["tolerance"][k] for k in TOLERANCE} == TOLERANCE
    assert config["tolerance"]["rank_rel"] <= 1e-4                    # Graphalytics' own epsilon
    assert config["reduced"] == [] and config["sizes"]["edges"] == rmat_edges.FULL_EDGES
    assert config["sizes"]["nodes"] == rmat_edges.FULL_IDS


BREAK = '''
import sys
sys.path.insert(0, {bench!r}); sys.path.insert(0, {root!r})
from locust_tpu.plan import compile as plan_compile
real = plan_compile.render_ranks
def altered(ranks):
    ranks = ranks.copy()
    ranks[57] *= 1.001                  # one node's rank a thousandth off
    return real(ranks)
plan_compile.render_ranks = altered
import run
raise SystemExit(run.main(["--workload", "pagerank5M.batch", "--seed", "77", "--seconds", "2",
                           "--trace", "0", "--rehearse"]))
'''


def test_a_program_whose_ranks_are_off_fails_in_set_up(tmp_path):
    script = tmp_path / "altered.py"
    script.write_text(BREAK.format(bench=BENCH, root=ROOT))
    p, last = _run(str(script))
    assert p.returncode == 4, p.stdout[-2000:] + p.stderr[-2000:]
    assert "cannot run configuration pagerank-rmat-5M" in last and "node 57" in last
    assert not any(ln.startswith("{") for ln in p.stdout.splitlines())
