"""``indexzipf.batch`` on the CPU at rehearsal size (3,000 lines; kept out of
``tests/`` like its neighbours):

    env JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_index_cell.py -q

the configuration, traffic and metric files load and say what the cell is,
the oracle is an inverted index by hand and agrees with the program's plain
reference (``locust_tpu/index_reference.py``: two copies, held equal here),
its ``parse`` reads a table back and says where one differs, the least bytes
are a function of the oracle's counts alone, a rehearsal of the cell is
correct and reads every metric's name, the control (``--emits-per-line 8``)
is NOT correct by the TABLE, a captured table with one posting altered is
not correct, and a program whose index is off fails in set-up with exit
code 4 and no result line.
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
sys.path.insert(0, ROOT)

import index_least_bytes  # noqa: E402
import index_oracle  # noqa: E402
import yardstick  # noqa: E402
import zipf_text  # noqa: E402
from drivers import closed_loop_cli_index  # noqa: E402
from readers import roofline_index_job, stderr_number  # noqa: E402

CELL = "indexzipf.batch"
MINE = {"idx_map_dev_ms.idx", "idx_collect_dev_ms.idx", "idx_h2d_ms.idx", "postings_d2h_ms.idx",
        "postings_render_ms.idx", "postings_write_ms.idx", "idx_pairs_per_job.idx",
        "idx_grows_per_job.idx", "idx_collect_roofline.idx"}
JOINED = {"load_ms.tput", "output_ms.tput", "run_ms.tput", "compiles_in_window.tput",
          "peak_hbm_MB.tput", "sync_wait_ms.tput", "syncs_per_job.tput"}


def _config():
    with open(os.path.join(BENCH, "configs", "index-zipf-100MB.json")) as f:
        return json.load(f)


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_configuration_is_wc_zipfs_text_cut_into_documents_of_64_lines():
    config = _config()
    with open(os.path.join(BENCH, "configs", "wc-zipf-100MB.json")) as f:
        zipf = json.load(f)
    assert config["generator"] == zipf["generator"]                 # the block verbatim
    for key in ("block_lines", "line_width", "emits_per_line", "key_width", "corpus_lines"):
        assert config["sizes"][key] == zipf["sizes"][key], key      # the CLI's defaults, 302 blocks
    assert config["sizes"]["lines_per_doc"] == 64
    assert config["sizes"]["documents"] == -(-config["sizes"]["corpus_lines"] // 64) == 19328
    assert config["reduced"] == [] and set(config["assumed"]) >= {"text", "lines_per_doc"}
    assert set(config["guarantees"]) == {"result", "nothing_dropped", "nothing_truncated", "device"}
    assert list(config["layout"]) == ["1"]
    for lo, hi in (config["sizes"]["pairs"], config["sizes"]["words"]):
        assert 0 < lo <= hi
    entry = next(c for c in _bench()["configs"] if c["name"] == "index-zipf-100MB")
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == config["reduced"]


def test_the_traffic_is_the_index_command_at_its_defaults_with_the_emit_cap_as_control():
    with open(os.path.join(BENCH, "traffic", "batch_index.json")) as f:
        traffic = json.load(f)
    assert traffic["argv"] == ["index", "{file}", "--lines-per-doc", "64", "--backend", "{platform}"]
    assert int(traffic["argv"][3]) == _config()["sizes"]["lines_per_doc"]
    assert traffic["control_argv"] == ["--emits-per-line", "8"]
    assert traffic["driver"] == "closed_loop_cli_index" and traffic["clients"] == 1
    assert traffic["trace_slice"] == {"skip": 1, "jobs": 1}
    cell = next(w for w in _bench()["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("index-zipf-100MB", "batch_index", 1)


def test_every_metric_of_the_cell_has_a_file_and_a_reader():
    bench = _bench()
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert {m["name"] for m in mine} == MINE
    for name in JOINED:                                              # the accepted readers it joins
        assert CELL in next(m for m in bench["per_layer"] if m["name"] == name)["workloads"], name
    assert CELL in next(m for m in bench["end_to_end"] if m["name"] == "input_MB_per_s")["workloads"]
    for m in mine:
        assert m["layer"] == "index collect" and m["moves"] == "input_MB_per_s"
        with open(os.path.join(BENCH, "layer_metrics", m["name"].rpartition(".")[0] + ".json")) as f:
            spec = json.load(f)
        assert os.path.exists(os.path.join(BENCH, "readers", spec["reader"] + ".py"))
    assert len(bench["workloads"]) == 8 and sum(w["chips"] == 4 for w in bench["workloads"]) == 2


def test_the_oracle_is_an_inverted_index_by_hand_and_the_programs_reference_agrees(tmp_path):
    from locust_tpu import index_reference
    from locust_tpu.config import FULL_DELIMITERS

    assert index_oracle.DELIMITERS == yardstick.DELIMITERS == FULL_DELIMITERS
    path = tmp_path / "t.txt"
    path.write_bytes(b"b a,a\n\nc-b (d)\r\nlast line a")           # no LF at the end, a CR, a blank
    lines = index_oracle.file_lines(str(path))
    assert lines == [b"b a,a", b"", b"c-b (d)", b"last line a"] == index_reference.file_lines(str(path))
    o = index_oracle.oracle(str(path), 2)
    assert o.table == b"a\t0,1\nb\t0,1\nc\t1\nd\t1\nlast\t1\nline\t1\n"
    assert o.counts() == {"tokens": 9, "pairs": 8, "words": 6, "documents": 2, "lines": 4}
    text = str(tmp_path / "z.txt")
    generator = {k: v for k, v in _config()["generator"].items() if k != "module"}
    zipf_text.build(text, 2000, 2147483659, **generator)
    for lines_per_doc in (1, 3, 64):
        o = index_oracle.oracle(text, lines_per_doc)
        theirs = index_reference.inverted_index(index_reference.file_lines(text), lines_per_doc)
        assert o.table == index_reference.render(theirs)
        assert (o.words, o.pairs) == (len(theirs), sum(map(len, theirs.values())))
        words, offsets, postings = index_oracle.parse(o.table)
        ref_words, ref_offsets, ref_postings = index_reference.parse(o.table)
        assert words == ref_words and np.array_equal(offsets, ref_offsets)
        assert np.array_equal(postings, ref_postings) and postings.size == o.pairs


def test_parse_says_where_a_table_differs():
    want = b"a\t0,1,22\nbb\t1\nccc\t0,333\n"
    words, offsets, postings = index_oracle.parse(want)
    assert words == [b"a", b"bb", b"ccc"] and offsets.tolist() == [0, 3, 4, 6]
    assert postings.tolist() == [0, 1, 22, 1, 0, 333]
    assert index_oracle.parse(b"")[1].tolist() == [0]
    diff = index_oracle.first_difference
    assert "2 words printed, the text has 3" in diff(b"a\t0,1,22\nbb\t1\n", want)
    assert "word 1 is b'bx'" in diff(want.replace(b"bb", b"bx"), want)
    assert "5 postings printed" in diff(want.replace(b"0,1,22", b"0,22"), want) \
        and "lists 2 documents for 3" in diff(want.replace(b"0,1,22", b"0,22"), want)
    assert "posting 2 (of word b'a') is document 23" in diff(want.replace(b"22", b"23"), want)
    for broken in (b"a 0,1\n", b"a\t0,1", b"a\t\n", b"\t1\n", b"a\t1,x\n", b"a\t1,,2\n"):
        assert "cannot be read" in diff(broken, want), broken


def test_least_bytes_are_a_function_of_the_oracles_counts_alone():
    sizes = _config()["sizes"]
    counts = {"tokens": 13_553_153, "pairs": 7_469_395, "words": 652_599,
              "documents": 19_328, "lines": 1_236_992}
    want = 13_553_153 * 36 + 7_469_395 * 4 + 652_599 * 36
    assert index_least_bytes.collect(counts, sizes) == want and 5.4e8 < want < 5.5e8
    # neither the store's capacity, the block shape nor the pass count moves it
    assert index_least_bytes.collect(
        counts, dict(sizes, block_lines=1, emits_per_line=99, corpus_lines=7)) == want
    assert index_least_bytes.collect(dict(counts, tokens=counts["tokens"] + 1), sizes) == want + 36


def test_the_new_readers_read_nothing_where_there_is_nothing():
    job = types.SimpleNamespace(stderr="[locust] index: words=5 pairs=17 docs=2 emit_overflow=0\n")
    spec = {"pattern": r"\[locust\] index: words=\d+ pairs=(\d+) "}
    env = types.SimpleNamespace(jobs=[job, types.SimpleNamespace(stderr="nothing")], trace=None)
    assert stderr_number.read(spec, env) == 17.0
    env.jobs = env.jobs[1:]
    assert stderr_number.read(spec, env) is None                     # the parent: no such line
    assert roofline_index_job.read({}, env) is None                  # no trace, no oracle


def _run(*argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    p = subprocess.run([sys.executable, *argv], env=env, capture_output=True, text=True,
                       timeout=900)
    return p, (p.stdout.strip().splitlines() or [""])[-1]


def test_a_rehearsal_of_the_cell_is_correct_and_reads_every_metric():
    p, last = _run(os.path.join(BENCH, "run.py"), "--workload", CELL, "--seed",
                   "2147483661", "--seconds", "2", "--trace", "1", "--rehearse")
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    doc = json.loads(last)
    assert doc["correct"] is True and doc["failed"] == 0 and "metrics" not in doc
    assert "distinct (word, document) pairs" in p.stdout
    read = next(ln for ln in p.stdout.splitlines() if "metrics read" in ln)
    on_the_cpu = [m["name"] for m in _bench()["per_layer"]
                  if CELL in m.get("workloads", ())
                  and m["source"] in ("program_span", "program_counter")
                  and m["name"] != "peak_hbm_MB.tput"]
    assert len(on_the_cpu) == 12, on_the_cpu
    for name in on_the_cpu:
        assert name in read, (name, read)


def test_the_control_is_not_correct_by_its_table():
    p, last = _run(os.path.join(BENCH, "control.py"), "--workload", CELL,
                   "--seeds", "5,2147483659", "--rehearse")
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    doc = json.loads(last)
    assert doc["control_holds"] is True
    for row in doc["seeds"]:
        assert row["sound_correct"] and not row["control_correct"], row
        assert row["control_verdict"].startswith("table differs from the oracle")


def test_a_table_with_one_posting_altered_is_not_correct():
    want = b"a\t0,1,22\nbb\t1\nccc\t0,333\n"
    said = ("[locust] backend: cpu (cpu x 1)\n[locust] index: words=3 pairs=6 docs=334 "
            "emit_overflow=0 key_overflow=0 line_overflow=0 truncated=False store_rows=8 grows=0 "
            "total=1.0 ms\n")
    with open(os.path.join(BENCH, "traffic", "batch_index.json")) as f:
        traffic = json.load(f)
    env = types.SimpleNamespace(oracle=types.SimpleNamespace(table=want), platform="cpu",
                                traffic=traffic)
    job = yardstick.JobResult(0, want, said, 0.0, 1.0, None)
    assert closed_loop_cli_index.check_job(env, job) is None
    job.stdout = want.replace(b"333", b"334")
    assert "posting 5 (of word b'ccc') is document 334" in closed_loop_cli_index.check_job(env, job)
    job.stdout = want.replace(b"0,1,22", b"0,1,1,22")                # a document listed twice
    assert "7 postings printed" in closed_loop_cli_index.check_job(env, job)
    job.stdout = b"bb\t1\na\t0,1,22\nccc\t0,333\n"                   # words out of order
    assert "word 0 is b'bb'" in closed_loop_cli_index.check_job(env, job)
    job.stdout = want
    for lost in ("emit_overflow=3 key_overflow=0 line_overflow=0", "emit_overflow=0 key_overflow=2 "
                 "line_overflow=0", "emit_overflow=0 key_overflow=0 line_overflow=1"):
        job.stderr = said.replace("emit_overflow=0 key_overflow=0 line_overflow=0", lost)
        assert "lost or demoted" in closed_loop_cli_index.check_job(env, job), lost
    job.stderr = said.replace("truncated=False", "truncated=True")
    assert "lost or demoted" in closed_loop_cli_index.check_job(env, job)
    job.stderr = said.splitlines()[0] + "\n"                          # no result line at all
    assert "stderr lacks" in closed_loop_cli_index.check_job(env, job)
    job.stderr = said.replace("backend: cpu", "backend: tpu")
    assert "device line" in closed_loop_cli_index.check_job(env, job)


BREAK = '''
import sys
sys.path.insert(0, {bench!r}); sys.path.insert(0, {root!r})
from locust_tpu.apps import inverted_index
real = inverted_index.build_index
def altered(*a, **kw):
    index = real(*a, **kw)
    index.postings = index.postings.copy()
    index.postings[57] += 1             # one posting names the next document
    return index
inverted_index.build_index = altered
import run
raise SystemExit(run.main(["--workload", "indexzipf.batch", "--seed", "77", "--seconds", "2",
                           "--trace", "0", "--rehearse"]))
'''


def test_a_program_whose_index_is_off_fails_in_set_up(tmp_path):
    script = tmp_path / "altered.py"
    script.write_text(BREAK.format(bench=BENCH, root=ROOT))
    p, last = _run(str(script))
    assert p.returncode == 4, p.stdout[-2000:] + p.stderr[-2000:]
    assert "cannot run configuration index-zipf-100MB" in last and "posting 57" in last
    assert not any(ln.startswith("{") for ln in p.stdout.splitlines())
