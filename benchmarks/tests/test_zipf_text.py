"""The generated corpus of ``wc-zipf-100MB`` and its driver, on the CPU;
kept out of ``tests/`` like ``test_controls.py``:

    env JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_zipf_text.py -q

1. the generator is a function of its seed, and what it writes fits the
   CLI's default widths: lines <= 127 bytes and <= 20 words, words <= 32
   bytes, no two ranks one word;
2. its oracle (``yardstick.oracle_table``) is the table of the repo's plain
   WordCount (``tests/helpers.py`` ``py_wordcount``) at rehearsal size;
3. a rehearsal of ``wczipf.batch`` is correct, and with the program broken
   underneath the run ends in set-up with exit code 4 and no result line
   (``test_controls.py`` holds the cell's control, as every cell's).
"""

import json
import os
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "tests"))

import yardstick  # noqa: E402
import zipf_text  # noqa: E402

with open(os.path.join(BENCH, "configs", "wc-zipf-100MB.json")) as _f:
    CONFIG = json.load(_f)
GEN = {k: v for k, v in CONFIG["generator"].items() if k != "module"}
LINES = CONFIG["rehearsal"]["sizes"]["corpus_lines"]


def _build(tmp_path, name, seed, lines=LINES):
    path = str(tmp_path / name)
    return path, zipf_text.build(path, lines, seed, **GEN)


def test_a_seed_gives_one_text_and_another_seed_another(tmp_path):
    a, na = _build(tmp_path, "a", 2147483659)
    b, nb = _build(tmp_path, "b", 2147483659)
    c, _ = _build(tmp_path, "c", 2147483660)
    with open(a, "rb") as fa, open(b, "rb") as fb, open(c, "rb") as fc:
        data = fa.read()
        assert data == fb.read() and len(data) == na == nb
        assert data != fc.read()


@pytest.mark.parametrize("seed", [0, 7, 4294967295 + 12])
def test_every_line_fits_the_cli_defaults(tmp_path, seed):
    sizes = CONFIG["sizes"]
    path, n = _build(tmp_path, "t", seed, lines=20000)
    with open(path, "rb") as f:
        data = f.read()
    assert n == len(data) and data.endswith(b"\n")
    lines = data.split(b"\n")[:-1]
    assert len(lines) == 20000
    words = [ln.split(b" ") for ln in lines]
    assert max(len(ln) for ln in lines) <= sizes["line_width"] - 1
    assert 1 <= min(len(w) for w in words) and max(len(w) for w in words) <= sizes["emits_per_line"]
    assert all(1 <= len(t) <= sizes["key_width"] and t.isalpha() and t.islower()
               for w in words for t in w)


def test_no_two_ranks_give_one_word():
    flat, offsets = zipf_text.vocabulary(GEN["vocab"])
    data = flat.tobytes()
    words = {data[offsets[r]:offsets[r + 1] - 1] for r in range(GEN["vocab"])}
    assert len(words) == GEN["vocab"]
    lengths = offsets[1:] - offsets[:-1] - 1
    assert lengths.min() == 1 and lengths.max() == 32
    assert lengths[:3].tolist() == [1, 1, 1] and lengths[-1] > lengths[200]


def test_the_oracle_is_the_plain_wordcounts_table(tmp_path):
    from helpers import py_wordcount

    path, _ = _build(tmp_path, "t", 31)
    with open(path, "rb") as f:
        counts = py_wordcount(f.read().split(b"\n"))
    plain = b"".join(k + b"\t" + str(v).encode() + b"\n" for k, v in sorted(counts.items()))
    assert yardstick.oracle_table(path) == plain
    assert plain.count(b"\n") > 5120  # past the rehearsal's starting capacity: growth runs


def _run(*argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    p = subprocess.run([sys.executable, *argv], env=env, capture_output=True, text=True,
                       timeout=600)
    return p, (p.stdout.strip().splitlines() or [""])[-1]


def test_a_rehearsal_of_the_cell_is_correct():
    p, last = _run(os.path.join(BENCH, "run.py"), "--workload", "wczipf.batch", "--seed",
                   "2147483661", "--seconds", "2", "--trace", "1", "--rehearse")
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    doc = json.loads(last)
    assert doc["correct"] is True and doc["failed"] == 0 and "metrics" not in doc
    read = next(ln for ln in p.stdout.splitlines() if "metrics read" in ln)
    assert "table_grow_ms.tput" in read and "table_grows_per_job.tput" in read


BREAK = '''
import sys
sys.path.insert(0, {bench!r}); sys.path.insert(0, {root!r})
from locust_tpu.engine import MapReduceEngine
MapReduceEngine.timed_run = MapReduceEngine.run      # a table of fixed size
import run
raise SystemExit(run.main(["--workload", "wczipf.batch", "--seed", "77", "--seconds", "2",
                           "--trace", "0", "--rehearse"]))
'''


def test_a_program_that_cannot_hold_the_vocabulary_fails_in_set_up(tmp_path):
    script = tmp_path / "fixed_table.py"
    script.write_text(BREAK.format(bench=BENCH, root=ROOT))
    p, last = _run(str(script))
    assert p.returncode == 4, p.stdout[-2000:] + p.stderr[-2000:]
    assert "cannot run configuration wc-zipf-100MB" in last and "table differs" in last
    assert not any(ln.startswith("{") for ln in p.stdout.splitlines())
