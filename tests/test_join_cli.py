"""``python -m locust_tpu join RANKINGS USERVISITS`` held to the plain
reference (PR 48).

At CPU size — a seeded 3,000-visit / 400-page pair from the benchmark's own
generator, at the laws of ``join-visits-1M``: the CLI's stdout against
``locust_tpu/join_reference.py`` (a dict, a loop, ``datetime.date``, float64;
no jax) at five seeds — the sourceIPs equal as sets, both numbers within the
configuration's tolerance, the printed order non-increasing — and by
construction: a key on one side only, the window's edges, URLs that share a
prefix or a 64-bit hash, a sourceIP in several blocks, the three cuts and
the rows that do not parse said aloud on stderr, empty inputs, the visit
store's growth, sums no float holds, and the spans and counters a
``--trace-out`` file of a join job holds.

The tolerance is 2e-8 relative, as ``benchmarks/configs/join-visits-1M.json``
states it and for its reason: the program's sums are exact integers, both
sides print nine significant digits (5e-9 each way), the reference adds in
float64 (1e-13 over 3,500 addends) — and a float32 sum of the hottest group
(1e-7 and more) or a bfloat16 one (1e-3) fails it.
"""

import datetime
import json
import os
import re
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from locust_tpu import cli, join_reference
from locust_tpu.apps import join as join_app
from locust_tpu.config import EngineConfig
from locust_tpu.core import bytes_ops, packing
from locust_tpu.plan import compile as plan_compile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

import visits_tables  # noqa: E402

with open(os.path.join(REPO, "benchmarks", "configs", "join-visits-1M.json")) as _f:
    CONFIG = json.load(_f)
GENERATOR = {k: v for k, v in CONFIG["generator"].items() if k != "module"}
TOLERANCE = CONFIG["tolerance"]["relative"]
# What yardstick.BAD_STDERR holds every CLI job to: a cut must match it.
BAD_STDERR = re.compile(r"overflow=[1-9]|truncated=True|\[locust\] WARN")
PAGES, VISITS = 400, 3000
ARGV = ["--block-lines", "256", "--backend", "cpu"]
SEEDS = [1, 2, 3, 2147483659, 4294967295]
TAIL = b",Mozilla/5.0 (agent),USA,en-us,word,7"


def generated(tmp, seed):
    r, v = str(tmp / f"rankings_{seed}.txt"), str(tmp / f"uservisits_{seed}.txt")
    visits_tables.build(r, v, seed, pages=PAGES, visits=VISITS, **GENERATOR)
    return r, v


@pytest.fixture(scope="module")
def pair(tmp_path_factory):
    return generated(tmp_path_factory.mktemp("join"), 2147483659)


def run_join(capsysbinary, rankings, uservisits, *argv):
    capsysbinary.readouterr()
    rc = cli.main(["join", rankings, uservisits, *ARGV, *argv])
    got = capsysbinary.readouterr()
    return rc, got.out, got.err.decode()


def run_lines(capsysbinary, tmp_path, pages, visits, *argv):
    """The CLI on two tables given as lists of lines."""
    r, v = tmp_path / "rankings.txt", tmp_path / "uservisits.txt"
    r.write_bytes(b"".join(ln + b"\n" for ln in pages))
    v.write_bytes(b"".join(ln + b"\n" for ln in visits))
    return run_join(capsysbinary, str(r), str(v), *argv)


def said(err: str) -> dict:
    line = next(ln for ln in err.splitlines() if ln.startswith("[locust] join: "))
    return {k: v for k, v in (f.split("=") for f in line.split()[2:] if "=" in f)}


def visit(ip: bytes, url: bytes, day: bytes, revenue: bytes) -> bytes:
    return b",".join([ip, url, day, revenue]) + TAIL


def held(out: bytes, want) -> float:
    """The printed table against the reference's rows, as the benchmark's
    driver holds it; returns the worst relative error."""
    got = join_reference.parse(out)
    mine = {ip: (avg, total) for ip, avg, total in want.rows}
    assert {ip for ip, _, _ in got} == mine.keys() and len(got) == len(mine)
    worst = 0.0
    for ip, avg, total in got:
        for x, y in zip((avg, total), mine[ip]):
            worst = max(worst, abs(x - y) / abs(y) if y else abs(x))
    assert worst <= TOLERANCE, worst
    totals = [total for _, _, total in got]
    assert all(a >= b for a, b in zip(totals, totals[1:]))
    return worst


@pytest.mark.parametrize("seed", SEEDS)
def test_the_cli_prints_the_references_table(tmp_path, capsysbinary, seed):
    r, v = generated(tmp_path, seed)
    want = join_reference.join(join_reference.file_lines(r), join_reference.file_lines(v))
    rc, out, err = run_join(capsysbinary, r, v)
    assert rc == 0 and not BAD_STDERR.search(err), err
    assert 100 < len(want.rows) and want.passed == want.matched
    held(out, want)
    line = said(err)
    assert (int(line["pages"]), int(line["visits"])) == (PAGES, VISITS)
    for key in ("passed", "matched", "pages_visited", "malformed"):
        assert int(line[key]) == getattr(want, key), key
    assert int(line["groups"]) == len(want.rows) and line["truncated"] == "False"


def test_the_window_passes_one_row_in_twenty_two(pair):
    """The generator's law the cell is sized by: a year of about twenty-two."""
    want = join_reference.join(*(join_reference.file_lines(p) for p in pair))
    assert 0.035 < want.passed / VISITS < 0.056


def test_a_key_on_one_side_only_is_dropped_and_counted(tmp_path, capsysbinary):
    pages = [b"http://a.example/visited,7,1", b"http://a.example/nobody-comes,9,1"]
    visits = [visit(b"10.0.0.1", b"http://a.example/visited", b"1999-06-01", b"2.500000"),
              visit(b"10.0.0.2", b"http://a.example/no-such-page", b"1999-06-01", b"4.000000")]
    rc, out, err = run_lines(capsysbinary, tmp_path, pages, visits)
    assert rc == 0 and out == b"10.0.0.1\t7.00000000e+00\t2.50000000e+00\n"
    line = said(err)
    assert (line["pages"], line["pages_visited"], line["passed"], line["matched"],
            line["groups"]) == ("2", "1", "2", "1", "1")
    assert not BAD_STDERR.search(err)  # an inner join's drops are no fault


@pytest.mark.parametrize("day, inside", [
    (b"1999-01-01", True), (b"2000-01-01", True), (b"1998-12-31", False),
    (b"2000-01-02", False), (b"1999-02-28", True), (b"2000-02-29", False)])
def test_both_ends_of_the_window_are_in(tmp_path, capsysbinary, day, inside):
    rc, out, err = run_lines(
        capsysbinary, tmp_path, [b"http://p,3,1"],
        [visit(b"1.2.3.4", b"http://p", day, b"1.000000")])
    assert rc == 0 and said(err)["passed"] == str(int(inside))
    assert out == (b"1.2.3.4\t3.00000000e+00\t1.00000000e+00\n" if inside else b"")


def test_another_window_is_a_flag_and_no_new_program(tmp_path, capsysbinary):
    visits = [visit(b"1.2.3.4", b"http://p", b"2005-05-05", b"1.000000")]
    rc, out, _ = run_lines(capsysbinary, tmp_path, [b"http://p,3,1"], visits,
                           "--date-from", "2005-05-05", "--date-to", "2005-05-05")
    assert rc == 0 and out.startswith(b"1.2.3.4\t")


SHARED = b"http://the-same-thirty-two-bytes/"  # 33 bytes: past the old key width
assert len(SHARED) > 32


def test_urls_that_share_their_first_32_bytes_stay_two_keys(tmp_path, capsysbinary):
    pages = [SHARED + b"one,10,1", SHARED + b"two,30,1"]
    visits = [visit(b"1.1.1.1", SHARED + b"one", b"1999-03-03", b"1.000000"),
              visit(b"2.2.2.2", SHARED + b"two", b"1999-03-03", b"2.000000"),
              visit(b"3.3.3.3", SHARED + b"three", b"1999-03-03", b"4.000000")]
    rc, out, err = run_lines(capsysbinary, tmp_path, pages, visits)
    assert rc == 0 and out == (b"2.2.2.2\t3.00000000e+01\t2.00000000e+00\n"
                               b"1.1.1.1\t1.00000000e+01\t1.00000000e+00\n")
    assert said(err)["key_overflow"] == "0"


def _constant_hash(lanes):
    h = jnp.zeros(lanes.shape[:-1], jnp.uint32)
    return h, h


def _length_parity_hash(lanes):
    """Two runs: the keys of an even number of non-empty lanes, and the rest."""
    h = (jnp.sum((lanes != 0).astype(jnp.uint32), axis=-1) & 1).astype(jnp.uint32)
    return h, h


@pytest.mark.parametrize("hash_pair", [_constant_hash, _length_parity_hash, None],
                         ids=["constant", "parity", "true"])
def test_a_forced_64_bit_collision_joins_nothing_it_should_not(
        pair, capsysbinary, monkeypatch, hash_pair):
    """The probe groups pages and visits by ``hash_pair`` and compares the
    full key lanes: under a hash that says nothing every page stands in one
    run and each visit walks back to ITS page — the same table."""
    if hash_pair is not None:
        monkeypatch.setattr(packing, "hash_pair", hash_pair)
    want = join_reference.join(*(join_reference.file_lines(p) for p in pair))
    rc, out, err = run_join(capsysbinary, *pair)
    assert rc == 0 and not BAD_STDERR.search(err), err
    held(out, want)
    assert int(said(err)["matched"]) == want.matched


def test_a_source_ip_in_several_blocks_is_one_group(tmp_path, capsysbinary):
    pages = [b"http://p/%d,%d,1" % (i, i + 1) for i in range(5)]
    visits = []
    for i in range(700):  # three blocks of 256 lines
        ip = b"9.9.9.9" if i % 100 == 0 else b"8.8.%d.%d" % (i // 250, i % 250)
        visits.append(visit(ip, b"http://p/%d" % (i % 5), b"1999-07-07", b"1.250000"))
    rc, out, _ = run_lines(capsysbinary, tmp_path, pages, visits)
    rows = join_reference.parse(out)
    assert rc == 0 and rows[0] == (b"9.9.9.9", 1.0, 7 * 1.25)
    assert len(rows) == len({ip for ip, _, _ in rows}) == 694


def test_a_255_byte_line_fits_and_a_300_byte_line_is_counted(tmp_path, capsysbinary):
    head = visit(b"1.2.3.4", b"http://p", b"1999-01-01", b"1.000000")
    fits = head + b"x" * (255 - len(head))
    long_line = visit(b"5.6.7.8", b"http://p", b"1999-01-01", b"2.000000")
    long_line += b"y" * (300 - len(long_line))
    rc, out, err = run_lines(capsysbinary, tmp_path, [b"http://p,3,1"], [fits])
    assert rc == 0 and said(err)["line_overflow"] == "0" and not BAD_STDERR.search(err)
    assert out == b"1.2.3.4\t3.00000000e+00\t1.00000000e+00\n"
    rc, out, err = run_lines(capsysbinary, tmp_path, [b"http://p,3,1"], [fits, long_line])
    assert rc == 0 and said(err)["line_overflow"] == "1"
    assert "[locust] WARN" in err and "1 line(s) past --line-width 256 were cut" in err
    # Its first four fields end inside the row: they are the line's own.
    assert out.startswith(b"5.6.7.8\t3.00000000e+00\t2.00000000e+00\n")


def test_a_line_cut_inside_its_first_four_fields_takes_no_part(tmp_path, capsysbinary):
    """The control's case: a row filled to its last byte whose fourth field
    no delimiter ends is not a revenue to trust — never half a number."""
    url = b"http://p/" + b"u" * 92
    cut_in_revenue = visit(b"1.2.3.4", url, b"1999-01-01", b"123.456789")
    assert cut_in_revenue[:128].endswith(b",123.456")  # a number, but not the line's
    rc, out, err = run_lines(capsysbinary, tmp_path, [url + b",3,1"], [cut_in_revenue],
                             "--line-width", "128")
    line = said(err)
    assert rc == 0 and out == b"" and "[locust] WARN" in err
    assert (line["line_overflow"], line["malformed"], line["passed"]) == ("1", "1", "0")


def test_a_url_past_the_key_width_is_counted(tmp_path, capsysbinary):
    url = b"http://p/" + b"k" * 60
    rc, out, err = run_lines(
        capsysbinary, tmp_path, [url + b",3,1"],
        [visit(b"1.2.3.4", url, b"1999-01-01", b"1.000000")], "--key-width", "32")
    assert rc == 0 and said(err)["key_overflow"] == "2" and "[locust] WARN" in err
    assert "2 key(s) past --key-width 32" in err
    rc, _, err = run_lines(
        capsysbinary, tmp_path, [b"http://p,3,1"],
        [visit(b"1234.1234.1234.1234", b"http://p", b"1999-01-01", b"1.000000")])
    assert rc == 0 and said(err)["key_overflow"] == "1"  # a sourceIP past VARCHAR(16)


MALFORMED_VISITS = [b"1.2.3.4,http://p,1999-01-01", b"1.2.3.4", b"1.2.3.4,http://p,1999-1-1,1.0" + TAIL,
                    visit(b"1.2.3.4", b"http://p", b"1999-02-29", b"1.0"),
                    visit(b"1.2.3.4", b"http://p", b"1999-13-01", b"1.0"),
                    visit(b"1.2.3.4", b"http://p", b"0000-01-01", b"1.0"),
                    visit(b"1.2.3.4", b"http://p", b"1999/01/01", b"1.0"),
                    visit(b"1.2.3.4", b"http://p", b"1999-01-01", b"1.0e3"),
                    visit(b"1.2.3.4", b"http://p", b"1999-01-01", b"12."),
                    visit(b"1.2.3.4", b"http://p", b"1999-01-01", b".5"),
                    visit(b"1.2.3.4", b"http://p", b"1999-01-01", b"-1.0"),
                    visit(b"1.2.3.4", b"http://p", b"1999-01-01", b"1.0000001"),
                    visit(b"1.2.3.4", b"http://p", b"1999-01-01", b"1234567890.0"),
                    visit(b"1.2.3.4", b"http://p", b"1999-01-01", b"")]


@pytest.mark.parametrize("line", MALFORMED_VISITS, ids=lambda ln: ln[:44].decode())
def test_a_visit_that_does_not_parse_is_malformed_counted_and_no_crash(
        tmp_path, capsysbinary, line):
    assert join_reference.parse_visit(line) is None
    good = visit(b"4.3.2.1", b"http://p", b"1999-01-01", b"7")
    rc, out, err = run_lines(capsysbinary, tmp_path, [b"http://p,3,1"], [line, good, b""])
    assert rc == 0 and said(err)["malformed"] == "1" and "[locust] WARN" in err
    assert out == b"4.3.2.1\t3.00000000e+00\t7.00000000e+00\n"


@pytest.mark.parametrize("line", [b"http://p", b"http://p,", b"http://p,x3,1", b"http://p,-3,1",
                                  b"http://p,1234567890,1", b"http://p,3.5,1"],
                         ids=lambda ln: ln.decode())
def test_a_page_that_does_not_parse_is_malformed_and_joins_nothing(
        tmp_path, capsysbinary, line):
    assert join_reference.parse_page(line) is None
    rc, out, err = run_lines(
        capsysbinary, tmp_path, [line, b"http://q,5"],
        [visit(b"1.2.3.4", b"http://p", b"1999-01-01", b"1.0"),
         visit(b"1.2.3.4", b"http://q", b"1999-01-01", b"2.0")])
    assert rc == 0 and said(err)["malformed"] == "1"
    assert out == b"1.2.3.4\t5.00000000e+00\t2.00000000e+00\n"


def test_of_a_url_listed_twice_the_last_row_stands(tmp_path, capsysbinary):
    pages = [b"http://p,3,1", b"http://q,4,1", b"http://p,9,1"]
    visits = [visit(b"1.2.3.4", b"http://p", b"1999-01-01", b"1.0")]
    assert join_reference.join(pages, visits).rows == [(b"1.2.3.4", 9.0, 1.0)]
    rc, out, _ = run_lines(capsysbinary, tmp_path, pages, visits)
    assert rc == 0 and out == b"1.2.3.4\t9.00000000e+00\t1.00000000e+00\n"


@pytest.mark.parametrize("which", ["no pages", "no visits", "nothing in the window"])
def test_an_empty_side_gives_an_empty_table_and_exit_0(tmp_path, capsysbinary, which):
    pages = [] if which == "no pages" else [b"http://p,3,1"]
    day = b"1980-01-01" if which == "nothing in the window" else b"1999-01-01"
    visits = [] if which == "no visits" else [visit(b"1.2.3.4", b"http://p", day, b"1.0")]
    rc, out, err = run_lines(capsysbinary, tmp_path, pages, visits)
    assert rc == 0 and out == b"" and said(err)["groups"] == "0"
    assert not BAD_STDERR.search(err)


def test_a_store_that_grows_gives_the_table_of_one_that_never_does(
        pair, capsysbinary, monkeypatch):
    rc, want, err = run_join(capsysbinary, *pair, "--date-from", "1990-01-01",
                             "--date-to", "2011-12-31")
    assert rc == 0 and said(err)["grows"] == "0" and said(err)["passed"] == str(VISITS)
    # Blocks of 256 lines, every one passing: a store that starts at one
    # group of two blocks must double twice and then once more.
    monkeypatch.setattr(join_app, "VISIT_ROWS", 256)
    monkeypatch.setattr(join_app, "GROUP_BLOCKS", 2)
    rc, out, err = run_join(capsysbinary, *pair, "--date-from", "1990-01-01",
                            "--date-to", "2011-12-31")
    line = said(err)
    assert rc == 0 and out == want
    assert int(line["grows"]) == 3 and int(line["store_rows"]) == 4096
    assert line["truncated"] == "False" and not BAD_STDERR.search(err)


def test_the_sums_are_exact_where_no_float_is(tmp_path, capsysbinary):
    """Three revenues of nine digits and six places: 2,999,999,999.999997 has
    sixteen significant digits; the device adds millionths as 64-bit counts
    in two words, a byte limb at a time."""
    visits = [visit(b"1.2.3.4", b"http://p", b"1999-01-01", b"999999999.999999")] * 3
    visits += [visit(b"4.3.2.1", b"http://p", b"1999-01-01", b"0.000001")] * 5
    cfg = EngineConfig(block_lines=256, line_width=256, key_width=128)
    joined = join_app.join_tables(
        bytes_ops.strings_to_rows([b"http://p,999999999,1"], 256),
        bytes_ops.strings_to_rows(visits, 256), cfg)
    assert joined.revenue_millionths.tolist() == [2_999_999_999_999_997, 5]
    assert joined.rank_sums.tolist() == [3 * 999_999_999, 5 * 999_999_999]
    assert joined.counts.tolist() == [3, 5]
    assert bytes_ops.rows_to_strings(joined.ips) == [b"1.2.3.4", b"4.3.2.1"]


def test_ties_of_the_total_stand_in_source_ip_byte_order(tmp_path, capsysbinary):
    ips = [b"9.9.9.9", b"10.0.0.1", b"1.1.1.1", b"100.2.3.4"]
    visits = [visit(ip, b"http://p", b"1999-01-01", b"5.0") for ip in ips]
    rc, out, _ = run_lines(capsysbinary, tmp_path, [b"http://p,3,1"], visits)
    assert rc == 0 and [ip for ip, _, _ in join_reference.parse(out)] == sorted(ips)


def test_a_float_sum_of_the_hottest_group_fails_the_tolerance(pair):
    """What the tolerance is tight against: the same addends in bfloat16, as
    addend or as accumulator, stand 1e-3 off; in float32 added in file order
    past 2e-8 too.  The window is the whole file's, so the hottest sourceIP
    has hundreds of visits."""
    rank_of = dict(filter(None, map(join_reference.parse_page, join_reference.file_lines(pair[0]))))
    groups = {}
    for line in join_reference.file_lines(pair[1]):
        ip, url, _, revenue = join_reference.parse_visit(line)
        if url in rank_of:
            groups.setdefault(ip, []).append(revenue)
    addends = np.asarray(max(groups.values(), key=len))
    assert addends.size > 100
    exact = float(np.sum(addends.astype(np.float64)))
    as_addend = float(np.sum(np.asarray(jnp.asarray(addends, jnp.bfloat16), np.float64)))
    as_accumulator = float(jnp.sum(jnp.asarray(addends, jnp.bfloat16)))
    in_float32 = np.float32(0)
    for x in addends.astype(np.float32):
        in_float32 += x
    for low in (as_addend, as_accumulator, float(in_float32)):
        assert abs(low - exact) / exact > TOLERANCE, (low, exact)
    assert abs(as_accumulator - exact) / exact > 1e-4


def test_mesh_is_an_argument_error_and_so_is_a_window_turned_round(pair, capsysbinary):
    with pytest.raises(SystemExit) as e:
        cli.main(["join", *pair, "--mesh"])
    assert e.value.code == 2
    capsysbinary.readouterr()
    assert cli.main(["join", *pair, "--date-from", "2001-01-01"]) == 2
    assert b"--date-from lies after --date-to" in capsysbinary.readouterr().err
    assert cli.main(["join", *pair, "--date-to", "1999-02-30"]) == 2
    assert b"a date of the calendar" in capsysbinary.readouterr().err
    with pytest.raises(SystemExit):
        cli.main(["join", pair[0]])  # one file is no join


def test_no_capacity_is_a_flag():
    from locust_tpu import cli_apps

    flags = {a.dest for a in cli_apps.build_parser("join")._actions}
    assert not {f for f in flags if "capacity" in f or "rows" in f or "table" in f}
    assert {"rankings", "uservisits", "date_from", "date_to", "line_width",
            "key_width", "block_lines", "backend", "trace_out"} <= flags


def test_the_array_renderer_equals_the_row_renderer(pair):
    cfg = EngineConfig(block_lines=256, line_width=256, key_width=128)
    from locust_tpu.io import loader

    joined = join_app.join_tables(loader.load_rows(pair[0], 256),
                                  loader.load_rows(pair[1], 256), cfg,
                                  date_from="1990-01-01", date_to="2011-12-31")
    fast = bytes_ops.render_revenue_rows(joined.ips, joined.averages, joined.totals)
    assert fast == b"".join(plan_compile.iter_rendered("revenue", joined))
    assert fast == plan_compile.render_revenue(joined) and fast.count(b"\n") == len(joined) > 500
    assert bytes_ops.render_revenue_rows(joined.ips[:0], joined.averages[:0], joined.totals[:0]) == b""
    # What the fixed-width layout cannot spell goes a row at a time.
    odd = joined.totals.copy()
    odd[0] = np.inf
    assert bytes_ops.render_revenue_rows(joined.ips, joined.averages, odd) is None


def test_a_traced_job_holds_the_joins_spans_and_counters(pair, capsysbinary, tmp_path):
    trace = tmp_path / "trace.json"
    rc, _, err = run_join(capsysbinary, *pair, "--trace-out", str(trace))
    assert rc == 0
    doc = json.loads(trace.read_text())
    spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    names = [e["name"] for e in spans]
    for name in ("cli.setup", "cli.load", "cli.run", "cli.output", "plan.run", "join.probe",
                 "join.d2h", "join.render", "join.write"):
        assert names.count(name) == 1, name
    # Rankings read whole; UserVisits a pull a block and one that finds the end
    reads = [e["args"] for e in spans if e["name"] == "join.read"]
    assert [a["table"] for a in reads] == ["rankings"] + ["uservisits"] * 13
    assert sum(a.get("lines", 0) for a in reads[1:]) == VISITS
    assert names.count("join.h2d") == 2 + 12      # 400 and 3,000 lines in blocks of 256
    assert names.count("join.map") == 1 + 1       # the pages, one group of visits
    assert [e["args"]["what"] for e in spans if e["name"] == "engine.sync"] == ["join.probe"]
    counters = doc["otherData"]["metrics"]["counters"]
    line = said(err)
    for name in ("pages", "visits", "passed", "matched", "groups", "line_overflow",
                 "key_overflow", "malformed", "grows"):
        assert counters["join." + name] == int(line[name]), name


@pytest.mark.parametrize("reader", ["slow", "fast"])
def test_the_read_ahead_counters_say_who_waited_for_whom(
        pair, capsysbinary, tmp_path, monkeypatch, reader):
    """``engine.ingest.blocks_waited`` / ``blocks_ahead`` of a join job:
    a reader slower than the job's own thread (its source sleeps) has
    every UserVisits block waited for, a faster one is ahead of it, and
    the two always sum to the blocks of the file — what a trace's reader
    takes the two names to mean (PR 49)."""
    import time

    from locust_tpu import cli_apps
    from locust_tpu.io import loader

    assert run_join(capsysbinary, *pair)[0] == 0   # the programs made: no compile below
    blocks = -(-VISITS // 256)
    visit_blocks = cli_apps._visit_blocks
    pause = {"slow": (0.1, 0.0), "fast": (0.0, 0.05)}[reader]

    def paced(path, cfg, full):
        for blk in visit_blocks(path, cfg, full):
            time.sleep(pause[0])   # on the reader thread, before the hand-over
            yield blk

    def consumed(gen):
        for blk in gen:
            yield blk
            time.sleep(pause[1])   # on the job's own thread, between its pulls

    real_prefetch = loader.prefetch_blocks
    monkeypatch.setattr(cli_apps, "_visit_blocks", paced)
    monkeypatch.setattr(loader, "prefetch_blocks",
                        lambda blocks, depth=2: consumed(real_prefetch(blocks, depth)))
    trace = tmp_path / "trace.json"
    rc, out, _ = run_join(capsysbinary, *pair, "--trace-out", str(trace))
    assert rc == 0
    doc = json.loads(trace.read_text())
    counters = doc["otherData"]["metrics"]["counters"]
    ahead = counters.get("engine.ingest.blocks_ahead", 0)
    waited = counters.get("engine.ingest.blocks_waited", 0)
    assert ahead + waited == blocks
    waits = [e for e in doc["traceEvents"]
             if e.get("ph") == "X" and e["name"] == "engine.ingest.wait"]
    # every block waited for is a wait span; the pull that finds the end may be one more
    assert waited <= len(waits) <= waited + 1
    if reader == "slow":
        assert waited >= blocks - 2
    else:
        # the first pull starts the reader and may wait for it; the rest were read ahead
        assert ahead >= blocks - 2


# ------------------------------------------------ the device's field parser


def test_field_ends_and_the_barrel_shifter():
    rows = bytes_ops.strings_to_rows(
        [b"ab,cde,,f,g", b"no delimiter", b"", b",", b"x" * 16], 16)
    ends, found, length = bytes_ops.field_ends(jnp.asarray(rows), ord(","), 4)
    assert np.asarray(ends).tolist() == [[2, 6, 7, 9], [12] * 4, [0] * 4, [0, 1, 1, 1], [16] * 4]
    assert np.asarray(found).tolist() == [4, 1, 1, 2, 1]
    assert np.asarray(length).tolist() == [11, 12, 0, 1, 16]
    by = jnp.asarray([3, 0, 5, 16, 15])
    for width in (1, 4, 16, 20):
        moved = np.asarray(bytes_ops.shift_left(jnp.asarray(rows), by, width))
        for row, shift, got in zip(rows, [3, 0, 5, 16, 15], moved):
            assert got.tobytes() == (row.tobytes()[shift:] + bytes(width))[:width]


def test_parse_date_is_datetime_dates_calendar():
    days = [b"1999-01-01", b"2000-02-29", b"1900-02-29", b"2100-02-29", b"2400-02-29",
            b"1999-04-31", b"1999-12-31", b"1999-00-10", b"1999-10-00", b"0001-01-01",
            b"0000-12-31", b"9999-12-31", b"1999-1-01", b"19990101", b"1999-01-011",
            b"1999-06-3x", b"abcd-ef-gh"]
    rows = jnp.asarray(bytes_ops.strings_to_rows(days, 12))
    ymd, ok = bytes_ops.parse_date(rows, bytes_ops.byte_length(rows))
    for day, number, fine in zip(days, np.asarray(ymd), np.asarray(ok)):
        try:
            want = datetime.date.fromisoformat(day.decode()) if len(day) == 10 else None
        except ValueError:
            want = None
        assert bool(fine) == (want is not None), day
        if want is not None:
            assert number == want.year * 10000 + want.month * 100 + want.day
            assert join_app.date_number(day.decode()) == number


def test_numbers_parse_by_static_weights():
    fields = [b"7", b"999999999", b"000000012", b"12a", b"", b"1234567890"]
    rows = jnp.asarray(bytes_ops.strings_to_rows(fields, 12))
    n = bytes_ops.byte_length(rows)
    right = bytes_ops.shift_left(jnp.pad(rows, ((0, 0), (9, 0))), n, 9)
    value, ok = bytes_ops.parse_uint_right(right, n)
    assert np.asarray(ok).tolist() == [True, True, True, False, False, False]
    assert np.asarray(value)[:3].tolist() == [7, 999999999, 12]
    frac, ok = bytes_ops.parse_fraction_left(rows[:, :6], jnp.asarray([1, 6, 6, 3, 0, 6]))
    assert np.asarray(ok).tolist() == [True, True, True, False, True, True]
    assert np.asarray(frac).tolist()[:3] == [700000, 999999, 0] and int(frac[4]) == 0
    whole = jnp.asarray([0, 1, 999, 999999999], jnp.int32)
    hi, lo = join_app._millionths(whole, jnp.asarray([0, 5, 999999, 999999], jnp.int32))
    got = (np.asarray(hi).astype(np.uint64) << np.uint64(32)) | np.asarray(lo).astype(np.uint64)
    assert got.tolist() == [0, 1_000_005, 999_999_999, 999_999_999_999_999]
