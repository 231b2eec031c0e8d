"""The two tests the benchmark keeps of its own ``correct`` (CPU, tiny sizes;
kept out of ``tests/`` so tier-1's count and time do not move):

    env JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

1. the control — the traffic file's lossy argv — comes out NOT correct
   while the same seeds' sound jobs come out correct;
2. a whole run, the look for a chip skipped (``--rehearse``), with the timed
   path broken underneath — one count altered where the table is printed —
   reports ``correct`` false and counts every job as failed.
"""

import json
import os
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    _WORKLOADS = json.load(_f)["workloads"]
CELLS = [w["name"] for w in _WORKLOADS]
ONE_CHIP = [w["name"] for w in _WORKLOADS if w["chips"] == 1]


def _run(script, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    p = subprocess.run([sys.executable, os.path.join(BENCH, script), *args],
                       env=env, capture_output=True, text=True, timeout=600)
    last = p.stdout.strip().splitlines()[-1] if p.stdout.strip() else ""
    return p, last


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    p, last = _run("control.py", "--workload", cell, "--seeds", "5,2147483659,3000000019",
                   "--rehearse")
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    doc = json.loads(last)
    assert doc["control_holds"] is True
    for row in doc["seeds"]:
        assert row["sound_correct"] and not row["control_correct"], row
        assert "table differs" in row["control_verdict"] or "reported" in row["control_verdict"]


BREAK = '''
import sys
sys.path.insert(0, {bench!r}); sys.path.insert(0, {root!r})
import locust_tpu.cli as cli
real = cli._print_table
def altered(pairs, limit=None):
    pairs = list(pairs)
    k, v = pairs[len(pairs) // 2]
    pairs[len(pairs) // 2] = (k, v + 1)      # one count off by one
    return real(pairs, limit)
cli._print_table = altered
import run
raise SystemExit(run.main(["--workload", {cell!r}, "--seed", "77", "--seconds", "2",
                           "--trace", "0", "--rehearse"]))
'''


@pytest.mark.parametrize("cell", ONE_CHIP[:1])
def test_broken_timed_path_is_not_correct(cell, tmp_path):
    script = tmp_path / "broken.py"
    script.write_text(BREAK.format(bench=BENCH, root=ROOT, cell=cell))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    p = subprocess.run([sys.executable, str(script)], env=env, capture_output=True,
                       text=True, timeout=600)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    doc = json.loads(p.stdout.strip().splitlines()[-1])
    assert doc["correct"] is False
    assert doc["attempted"] >= 1 and doc["failed"] == doc["attempted"]


def test_sound_run_is_correct():
    p, last = _run("run.py", "--workload", ONE_CHIP[0], "--seed", "78", "--seconds", "2",
                   "--trace", "0", "--rehearse")
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    doc = json.loads(last)
    assert doc["correct"] is True and doc["failed"] == 0 and "metrics" not in doc
