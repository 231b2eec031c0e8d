"""``tera800.batch`` on the CPU at rehearsal size (20,000 records; kept out
of ``tests/`` like its neighbours):

    env JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_records_cell.py -q

the generator is a function of its seed and lays gensort's record out, the
oracle is the plain stable sort, a rehearsal of the cell is correct and
reads the cell's span metrics, the control is not, an OUT with one byte
altered is not, and a program whose ``sort`` cannot keep the guarantee
fails in set-up with exit code 4 and no result line.
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

import records  # noqa: E402
import yardstick  # noqa: E402
from drivers import closed_loop_cli_records  # noqa: E402


def test_the_generator_is_a_function_of_its_seed_and_lays_gensort_out(tmp_path):
    a, b, c = (str(tmp_path / n) for n in "abc")
    assert records.build(a, 3000, 2147483659) == 300000
    records.build(b, 3000, 2147483659)
    records.build(c, 3000, 2147483660)
    assert open(a, "rb").read() == open(b, "rb").read() != open(c, "rb").read()
    rows = records.load(a)
    assert rows.shape == (3000, 100)
    assert bytes(rows[2999, 10:48]) == b"\x00\x11" + b"%032X" % 2999 + b"\x88\x99\xaa\xbb"
    assert bytes(rows[7, 96:]) == b"\xcc\xdd\xee\xff"
    filler = rows[:, 48:96].reshape(3000, 12, 4)
    assert (filler == filler[:, :, :1]).all() and set(filler.ravel()) <= set(b"0123456789ABCDEF")
    assert len({bytes(r[:10]) for r in rows}) == 3000  # 80-bit keys: no tie at this size
    assert 100 < rows[:, :10].mean() < 155


def test_the_oracle_is_the_plain_stable_sort(tmp_path):
    path = str(tmp_path / "r")
    records.build(path, 4000, 5)
    rows = records.load(path).copy()
    rows[:, :9] &= 0x81  # ties down to the tenth byte, 0x00 and bytes over 0x7F
    for key_bytes in (10, 2, 1):
        plain = b"".join(sorted((bytes(r) for r in rows), key=lambda r: r[:key_bytes]))
        assert records.oracle(rows, key_bytes).tobytes() == plain


def _run(*argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    p = subprocess.run([sys.executable, *argv], env=env, capture_output=True, text=True,
                       timeout=600)
    return p, (p.stdout.strip().splitlines() or [""])[-1]


def test_a_rehearsal_of_the_cell_is_correct():
    p, last = _run(os.path.join(BENCH, "run.py"), "--workload", "tera800.batch", "--seed",
                   "2147483661", "--seconds", "2", "--trace", "1", "--rehearse")
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    doc = json.loads(last)
    assert doc["correct"] is True and doc["failed"] == 0 and "metrics" not in doc
    read = next(ln for ln in p.stdout.splitlines() if "metrics read" in ln)
    for name in ("read_ms.rec", "rec_h2d_ms.rec", "rec_d2h_ms.rec", "write_ms.rec",
                 "rec_sync_wait_ms.rec", "load_ms.tput", "run_ms.tput", "output_ms.tput"):
        assert name in read, (name, read)


def test_the_control_is_not_correct():
    p, last = _run(os.path.join(BENCH, "control.py"), "--workload", "tera800.batch",
                   "--seeds", "5,2147483659", "--rehearse")
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    doc = json.loads(last)
    assert doc["control_holds"] is True
    for row in doc["seeds"]:
        assert row["sound_correct"] and not row["control_correct"], row
        assert "differs from the oracle" in row["control_verdict"]


def test_an_out_with_one_byte_altered_is_not_correct(tmp_path):
    path, out = str(tmp_path / "r"), str(tmp_path / "out")
    records.build(path, 2000, 9)
    want = records.oracle(records.load(path)).reshape(-1)
    env = types.SimpleNamespace(out_path=out, expect_records=want, platform="cpu")
    job = yardstick.JobResult(0, b"", "[locust] backend: cpu (cpu x 1)\n", 0.0, 1.0, None)
    want.tofile(out)
    assert closed_loop_cli_records.check_job(env, job) is None
    altered = want.copy()
    altered[123456] ^= 1
    altered.tofile(out)
    assert "differs from the oracle" in closed_loop_cli_records.check_job(env, job)
    want[:-100].tofile(out)  # a record lost
    assert "records lost" in closed_loop_cli_records.check_job(env, job)
    os.unlink(out)
    assert "no output file" in closed_loop_cli_records.check_job(env, job)
    want.tofile(out)
    job.stdout = b"a table"
    assert "stdout" in closed_loop_cli_records.check_job(env, job)


BREAK = '''
import sys
sys.path.insert(0, {bench!r}); sys.path.insert(0, {root!r})
from locust_tpu.io import serde
real = serde.write_records
def altered(path, blocks):
    def flip(blocks):
        for i, block in enumerate(blocks):
            if i == 0:
                block = block.copy()
                block[57] ^= 0x20      # one byte of one record's payload
            yield block
    return real(path, flip(blocks))
serde.write_records = altered
import run
raise SystemExit(run.main(["--workload", "tera800.batch", "--seed", "77", "--seconds", "2",
                           "--trace", "0", "--rehearse"]))
'''


def test_a_program_that_alters_a_byte_fails_in_set_up(tmp_path):
    script = tmp_path / "altered.py"
    script.write_text(BREAK.format(bench=BENCH, root=ROOT))
    p, last = _run(str(script))
    assert p.returncode == 4, p.stdout[-2000:] + p.stderr[-2000:]
    assert "cannot run configuration terasort-800MB" in last and "differs from the oracle" in last
    assert not any(ln.startswith("{") for ln in p.stdout.splitlines())
