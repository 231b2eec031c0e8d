"""``joinvisits.batch`` on the CPU at rehearsal size (400 pages, 3,000
visits; kept out of ``tests/`` like its neighbours):

    env JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_join_cell.py -q

the configuration, traffic and metric files load and say what the cell is,
the generator is a function of the seed alone and obeys its stated laws, the
oracle is the query by hand and agrees with the program's plain reference
(``locust_tpu/join_reference.py``: two copies, held equal here), its
``compare`` says where a table differs, the least bytes are a function of
the oracle's counts alone, a rehearsal of the cell is correct and reads
every metric's name, the control (``--line-width 128``) is NOT correct by
the TABLE, and a program whose sums are off fails in set-up with exit code
4 and no result line.
"""

import datetime
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

import join_least_bytes  # noqa: E402
import join_oracle  # noqa: E402
import visits_tables  # noqa: E402
import yardstick  # noqa: E402
from drivers import closed_loop_cli_join  # noqa: E402
from readers import roofline_join_job, stderr_number  # noqa: E402

CELL = "joinvisits.batch"
MINE = {"join_map_dev_ms.join", "join_probe_dev_ms.join", "join_h2d_ms.join", "join_d2h_ms.join",
        "join_render_ms.join", "join_write_ms.join", "join_passed_per_job.join",
        "join_groups_per_job.join", "join_roofline.join"}
JOINED = {"load_ms.tput", "output_ms.tput", "run_ms.tput", "compiles_in_window.tput",
          "peak_hbm_MB.tput", "sync_wait_ms.tput", "syncs_per_job.tput"}


def _config():
    with open(os.path.join(BENCH, "configs", "join-visits-1M.json")) as f:
        return json.load(f)


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _generator():
    return {k: v for k, v in _config()["generator"].items() if k != "module"}


def _build(tmp, seed, pages=400, visits=3000):
    r, v = str(tmp / f"r{seed}.txt"), str(tmp / f"v{seed}.txt")
    return r, v, visits_tables.build(r, v, seed, pages=pages, visits=visits, **_generator())


def test_the_configuration_is_hibenchs_large_profile_with_nothing_reduced():
    config = _config()
    assert (config["sizes"]["pages"], config["sizes"]["visits"]) == (120_000, 1_000_000)
    assert (config["sizes"]["line_width"], config["sizes"]["key_width"],
            config["sizes"]["block_lines"]) == (256, 128, 4096)      # the join CLI's defaults
    assert config["reduced"] == [] and "large" in config["reduced_why"]
    assert (config["query"]["date_from"], config["query"]["date_to"]) == ("1999-01-01", "2000-01-01")
    assert set(config["schema"]) == {"rankings", "uservisits", "files"}
    assert set(config["assumed"]) >= {"files", "urls", "page_ranks", "dest_urls", "source_ips",
                                      "visit_dates", "ad_revenue", "other_fields", "file_system",
                                      "counts_over_five_seeds"}
    for law in config["assumed"].values():
        assert "hides" in law or "counted by" in law, law[:60]
    assert set(config["guarantees"]) == {"result", "numbers", "order", "join", "nothing_cut",
                                         "nothing_truncated", "device"}
    assert 0 < config["tolerance"]["relative"] <= 1e-5 and list(config["layout"]) == ["1"]
    entry = next(c for c in _bench()["configs"] if c["name"] == "join-visits-1M")
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == config["reduced"]


def test_the_traffic_is_the_join_command_at_its_defaults_with_the_old_row_as_control():
    with open(os.path.join(BENCH, "traffic", "batch_join.json")) as f:
        traffic = json.load(f)
    assert traffic["argv"] == ["join", "{rankings}", "{uservisits}", "--backend", "{platform}"]
    assert traffic["control_argv"] == ["--line-width", "128"]
    assert traffic["driver"] == "closed_loop_cli_join" and traffic["clients"] == 1
    assert traffic["trace_slice"] == {"skip": 1, "jobs": 1}
    cell = next(w for w in _bench()["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("join-visits-1M", "batch_join", 1)


def test_every_metric_of_the_cell_has_a_file_and_a_reader():
    bench = _bench()
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert {m["name"] for m in mine} == MINE
    for name in JOINED:                                              # the accepted readers it joins
        assert CELL in next(m for m in bench["per_layer"] if m["name"] == name)["workloads"], name
    assert CELL in next(m for m in bench["end_to_end"] if m["name"] == "input_MB_per_s")["workloads"]
    for m in mine:
        assert m["layer"] == "join" and m["moves"] == "input_MB_per_s"
        with open(os.path.join(BENCH, "layer_metrics", m["name"].rpartition(".")[0] + ".json")) as f:
            spec = json.load(f)
        assert os.path.exists(os.path.join(BENCH, "readers", spec["reader"] + ".py"))
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 2     # no four-chip cell added


def test_the_generator_is_a_function_of_the_seed_alone_and_obeys_its_laws(tmp_path):
    r, v, sizes = _build(tmp_path, 4294967295, pages=2000, visits=40_000)
    with open(r, "rb") as f:
        rankings = f.read()
    with open(v, "rb") as f:
        visits = f.read()
    assert sizes == (len(rankings), len(visits))
    r2, v2, _ = _build(tmp_path / "..", 4294967295, pages=2000, visits=40_000)
    with open(r2, "rb") as f, open(v2, "rb") as g:
        assert f.read() == rankings and g.read() == visits           # the seed alone
    _, v3, _ = _build(tmp_path, 7, pages=2000, visits=40_000)
    with open(v3, "rb") as f:
        assert f.read() != visits
    pages = [ln.split(b",") for ln in rankings.split(b"\n")[:-1]]
    rows = [ln.split(b",") for ln in visits.split(b"\n")[:-1]]
    assert len(pages) == 2000 and len(rows) == 40_000
    assert {len(p) for p in pages} == {3} and {len(row) for row in rows} == {9}   # no ',' in a field
    urls = [p[0] for p in pages]
    assert len(set(urls)) == 2000 and all(u.startswith(b"http://") for u in urls)
    assert min(map(len, urls)) >= 30 and max(map(len, urls)) <= 100
    ranks = sorted(int(p[1]) for p in pages)
    assert ranks[0] == 100_000 // 2000 and ranks[-1] == 100_000 and ranks[-2] == 50_000  # Zipf, exponent 1
    assert all(1 <= int(p[2]) <= 100 for p in pages)
    assert max(len(ln) for ln in visits.split(b"\n")) <= 255
    assert {row[1] for row in rows} <= set(urls)                     # every destURL a Rankings URL
    first, last = datetime.date(1990, 1, 1), datetime.date(2011, 12, 31)
    days = [datetime.date.fromisoformat(row[2].decode()) for row in rows]
    assert first <= min(days) and max(days) <= last
    window = sum(datetime.date(1999, 1, 1) <= d <= datetime.date(2000, 1, 1) for d in days)
    assert abs(window / len(rows) - 0.045) < 0.01                     # a year of about twenty-two
    for row in rows:
        whole, _, places = row[3].partition(b".")
        assert whole.isdigit() and 0 <= int(whole) < 1000 and len(places) == 6 and places.isdigit()
        assert 7 <= len(row[0]) <= 15 and all(0 <= int(o) <= 255 for o in row[0].split(b"."))
        assert 20 <= len(row[4]) <= 64 and len(row[5]) == 3 and 5 <= len(row[6]) <= 6
        assert 3 <= len(row[7]) <= 32 and 1 <= int(row[8]) <= 10_000
    # the hottest sourceIP has about a thirteenth of the visits (Zipf 1 over 250,000)
    top = max(np.unique([row[0] for row in rows], return_counts=True)[1])
    assert 0.05 < top / len(rows) < 0.11


def test_the_oracle_is_the_query_by_hand_and_the_programs_reference_agrees(tmp_path):
    from locust_tpu import join_reference

    r, v = tmp_path / "r.txt", tmp_path / "v.txt"
    r.write_bytes(b"http://a,10,5\nhttp://b,20,7\r\nhttp://c,30,1\nbad\n\nhttp://d,x,1")
    v.write_bytes(
        b"1.1.1.1,http://a,1999-05-05,10.500000,ua,US,en,w,3\n"
        b"1.1.1.1,http://b,1999-01-01,0.250000,ua,US,en,w,3\n"       # the window's first day
        b"2.2.2.2,http://b,2000-01-01,99.000001,ua,US,en,w,3\n"      # its last
        b"2.2.2.2,http://b,2000-01-02,99.000001,ua,US,en,w,3\n"      # a day late
        b"3.3.3.3,http://nopage,1999-06-06,5.0,ua\n"                 # a visit to no page
        b"3.3.3.3,http://a,1999-02-30,5.0,ua\n"                      # no date of the calendar
        b"\n"
        b"too,few,fields")                                           # no LF at the end
    o = join_oracle.oracle(str(r), str(v), "1999-01-01", "2000-01-01")
    assert o.table == (b"2.2.2.2\t2.00000000e+01\t9.90000010e+01\n"
                       b"1.1.1.1\t1.50000000e+01\t1.07500000e+01\n")
    assert o.counts() == {"pages": 6, "visits": 8, "bytes": len(r.read_bytes()) + len(v.read_bytes()), "passed": 4, "matched": 3,
                          "groups": 2, "largest_group": 2, "pages_visited": 2, "malformed": 4}
    mine = join_reference.join(join_reference.file_lines(str(r)), join_reference.file_lines(str(v)))
    assert join_reference.render(mine.rows) == o.table
    rp, vp, _ = _build(tmp_path, 2147483659)
    o = join_oracle.oracle(rp, vp, "1999-01-01", "2000-01-01")
    mine = join_reference.join(join_reference.file_lines(rp), join_reference.file_lines(vp))
    assert mine.rows == o.rows and join_reference.render(mine.rows) == o.table
    for key in ("pages", "visits", "passed", "matched", "pages_visited", "malformed"):
        assert getattr(mine, key) == getattr(o, key), key
    assert join_oracle.parse(o.table) == join_reference.parse(o.table)
    inside = [v for v in map(join_reference.parse_visit, join_reference.file_lines(vp))
              if datetime.date(1999, 1, 1) <= v[2] <= datetime.date(2000, 1, 1)]
    assert o.groups == len(o.rows) > 50
    assert o.largest_group == max(np.unique([v[0] for v in inside], return_counts=True)[1])


def test_compare_says_where_a_table_differs():
    rows = [(b"9.9.9.9", 30.0, 500.25), (b"1.1.1.1", 12.5, 20.0), (b"2.2.2.2", 1.0, 20.0)]
    want = types.SimpleNamespace(rows=rows)
    table = join_oracle.render(rows)
    limit = {"relative": _config()["tolerance"]["relative"]}
    verdict, worst = join_oracle.compare(table, want, limit)
    assert verdict is None and worst == 0.0
    assert join_oracle.compare(b"", types.SimpleNamespace(rows=[]), limit) == (None, 0.0)
    said = lambda t: join_oracle.compare(t, want, limit)[0]  # noqa: E731
    assert "1 missing" in said(table.replace(b"2.2.2.2\t1.00000000e+00\t2.00000000e+01\n", b""))
    assert "1 not the oracle's" in said(table + b"3.3.3.3\t1.00000000e+00\t1.00000000e+00\n")
    assert "printed twice" in said(table + b"2.2.2.2\t1.00000000e+00\t2.00000000e+01\n")
    assert "relative error 3.998e-08 > 2.0e-08" in said(table.replace(b"5.00250000e+02", b"5.00250020e+02"))
    assert "relative error" in said(table.replace(b"1.25000000e+01", b"1.25000005e+01"))
    assert said(table.replace(b"5.00250000e+02", b"5.00250001e+02")) is None   # the ninth digit
    swapped = join_oracle.render([rows[1], rows[0], rows[2]])
    assert "not by the total, descending: line 2" in said(swapped)
    assert said(join_oracle.render([rows[0], rows[2], rows[1]])) is None        # a tie, either way
    for broken in (b"9.9.9.9 30 500\n", table[:-1], b"9.9.9.9\tx\t1\n", b"9.9.9.9\t1\n"):
        assert "does not parse" in said(broken), broken


def test_least_bytes_are_a_function_of_the_oracles_counts_by_hand():
    # a ten-line pair: 4 pages, 6 visits of which 3 pass, 2 groups, 413 bytes
    counts = {"pages": 4, "visits": 6, "bytes": 413, "passed": 3, "matched": 2, "groups": 2,
              "largest_group": 2, "pages_visited": 2, "malformed": 0}
    want = 413 + 2 * 3 * (100 + 16 + 8) + 4 * (100 + 8) + 2 * (16 + 24)
    sizes = _config()["sizes"]
    assert join_least_bytes.job(counts, sizes) == want == 1669
    # neither the rows' width, the block shape nor a store's capacity moves it
    assert join_least_bytes.job(counts, dict(sizes, line_width=1, block_lines=7, key_width=4)) == want
    assert join_least_bytes.job(dict(counts, passed=4), sizes) == want + 2 * 124
    full = {"pages": 120_000, "visits": 1_000_000, "bytes": 187_183_809, "passed": 45_627,
            "matched": 45_627, "groups": 16_567}
    assert 2.11e8 < join_least_bytes.job(full, sizes) < 2.13e8


def test_the_new_readers_read_nothing_where_there_is_nothing():
    job = types.SimpleNamespace(stderr="[locust] join: pages=4 visits=6 passed=3 matched=2 groups=2 "
                                       "pages_visited=2 line_overflow=0\n")
    env = types.SimpleNamespace(jobs=[job, types.SimpleNamespace(stderr="nothing")], trace=None)
    for name, value in (("join_passed_per_job", 3.0), ("join_groups_per_job", 2.0)):
        with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
            spec = json.load(f)
        env.jobs = [job, types.SimpleNamespace(stderr="nothing")]
        assert stderr_number.read(spec, env) == value
        env.jobs = env.jobs[1:]
        assert stderr_number.read(spec, env) is None                 # the parent: no such line
    assert roofline_join_job.read({}, env) is None                   # no trace, no oracle


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
    assert "visits passed" in p.stdout and "worst relative error" in p.stdout
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
        assert row["control_verdict"].startswith(("the sourceIPs differ", "the numbers differ"))


def test_a_sound_table_with_a_cut_said_on_stderr_is_not_correct():
    rows = [(b"9.9.9.9", 30.0, 500.25)]
    table = join_oracle.render(rows)
    said = ("[locust] backend: cpu (cpu x 1)\n[locust] join: pages=4 visits=6 passed=3 matched=2 "
            "groups=1 pages_visited=2 line_overflow=0 key_overflow=0 malformed=0 truncated=False "
            "store_rows=4096 grows=0 total=1.0 ms\n")
    with open(os.path.join(BENCH, "traffic", "batch_join.json")) as f:
        traffic = json.load(f)
    env = types.SimpleNamespace(oracle=types.SimpleNamespace(rows=rows), platform="cpu",
                                traffic=traffic, config=_config())
    job = yardstick.JobResult(0, table, said, 0.0, 1.0, None)
    assert closed_loop_cli_join.check_job(env, job) is None
    for lost in ("line_overflow=1 key_overflow=0 malformed=0", "line_overflow=0 key_overflow=2 "
                 "malformed=0"):
        job.stderr = said.replace("line_overflow=0 key_overflow=0 malformed=0", lost)
        assert "lost or demoted" in closed_loop_cli_join.check_job(env, job), lost
    job.stderr = said.replace("malformed=0", "malformed=3")
    assert "stderr lacks" in closed_loop_cli_join.check_job(env, job)
    job.stderr = said.replace("truncated=False", "truncated=True")
    assert "lost or demoted" in closed_loop_cli_join.check_job(env, job)
    job.stderr = said.splitlines()[0] + "\n"                          # no result line at all
    assert "stderr lacks" in closed_loop_cli_join.check_job(env, job)
    job.stderr = said.replace("backend: cpu", "backend: tpu")
    assert "device line" in closed_loop_cli_join.check_job(env, job)
    job.stderr, job.rc = said, 1
    assert closed_loop_cli_join.check_job(env, job) == "returned 1"


BREAK = '''
import sys
sys.path.insert(0, {bench!r}); sys.path.insert(0, {root!r})
from locust_tpu.apps import join
real = join.join_tables
def altered(*a, **kw):
    joined = real(*a, **kw)
    joined.revenue_millionths = joined.revenue_millionths.copy()
    joined.revenue_millionths[-1] += joined.revenue_millionths[-1] // 10 ** 6 + 1  # a millionth of it off
    return joined
join.join_tables = altered
import run
raise SystemExit(run.main(["--workload", "joinvisits.batch", "--seed", "77", "--seconds", "2",
                           "--trace", "0", "--rehearse"]))
'''


def test_a_program_whose_sums_are_off_fails_in_set_up(tmp_path):
    script = tmp_path / "altered.py"
    script.write_text(BREAK.format(bench=BENCH, root=ROOT))
    p, last = _run(str(script))
    assert p.returncode == 4, p.stdout[-2000:] + p.stderr[-2000:]
    assert "cannot run configuration join-visits-1M" in last and "relative error" in last
    assert not any(ln.startswith("{") for ln in p.stdout.splitlines())
