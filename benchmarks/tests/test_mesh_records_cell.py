"""``tera-skew.mesh4`` on the CPU at rehearsal size (20,000 records on four
virtual devices; kept out of ``tests/`` like its neighbours):

    env JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_mesh_records_cell.py -q

the generator is a function of its seed and keeps the key law, the oracle
is the plain stable sort, a rehearsal of the cell is correct and reads the
cell's span metrics, the control is not, an OUT with a byte altered or a
record lost is not, a job that names three shards is not, an OUT left by
the job before is spoiled before the next job writes over it (so a job that
wrote nothing, or a part, is not correct), OUT is a memory file where the
platform has one and the work directory's file where not, and a program whose ``sort`` takes no ``--mesh`` fails in set-up with exit code 4 and no
result line.
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

import mesh_record_least_bytes  # noqa: E402
import records_skew  # noqa: E402
import yardstick  # noqa: E402
from drivers import closed_loop_cli_records_mesh  # noqa: E402
from readers import roofline_device_job, span_count_of  # noqa: E402

CELL = "tera-skew.mesh4"


def test_the_generator_is_a_function_of_its_seed_and_keeps_the_law(tmp_path):
    a, b, c = (str(tmp_path / n) for n in "abc")
    n = 400_000
    assert records_skew.build(a, n, 2147483659) == n * 100
    records_skew.build(b, n, 2147483659)
    records_skew.build(c, n, 2147483660)
    assert open(a, "rb").read() == open(b, "rb").read() != open(c, "rb").read()
    rows, other = records_skew.load(a), records_skew.load(c)
    assert bytes(rows[n - 1, 10:48]) == b"\x00\x11" + b"%032X" % (n - 1) + b"\x88\x99\xaa\xbb"
    assert bytes(rows[7, 96:]) == b"\xcc\xdd\xee\xff"
    table = records_skew.hot_table()
    assert table.shape == (65536, 10) and (table == records_skew.hot_table()).all()
    for drawn in (rows, other):
        keys = np.ascontiguousarray(drawn[:, :10]).view("S10").ravel()
        _, counts = np.unique(keys, return_counts=True)
        # the commonest key is HOT[0] on every seed: 1 / (2 H_65536) = 4.285%
        top = (drawn[:, :10] == table[0]).all(axis=1).mean()
        assert abs(top - 0.04285) < 0.003 and counts.max() == round(top * n)
        # half the records are hot; nearly all hot records tie at this size
        # (the tail's ranks are drawn once or not at all: 46% tie at 400,000)
        assert 0.42 < counts[counts > 1].sum() / n < 0.50
        # the first quarter of the key space: 35.94% (25.00 cold + 10.94 hot)
        assert abs((drawn[:, 0] < 64).mean() - 0.3594) < 0.005


def test_the_oracle_is_the_plain_stable_sort(tmp_path):
    path = str(tmp_path / "r")
    records_skew.build(path, 4000, 5)
    rows = records_skew.load(path)
    for key_bytes in (10, 2, 1):
        plain = b"".join(sorted((bytes(r) for r in rows), key=lambda r: r[:key_bytes]))
        assert records_skew.oracle(rows, key_bytes).tobytes() == plain
    assert records_skew.oracle(rows, 2).tobytes() != records_skew.oracle(rows, 10).tobytes()


def _run(*argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    p = subprocess.run([sys.executable, *argv], env=env, capture_output=True, text=True,
                       timeout=900)
    return p, (p.stdout.strip().splitlines() or [""])[-1]


def test_a_rehearsal_of_the_cell_is_correct():
    p, last = _run(os.path.join(BENCH, "run.py"), "--workload", CELL, "--seed",
                   "2147483661", "--seconds", "2", "--trace", "1", "--rehearse")
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    doc = json.loads(last)
    assert doc["correct"] is True and doc["failed"] == 0 and "metrics" not in doc
    read = next(ln for ln in p.stdout.splitlines() if "metrics read" in ln)
    for name in ("split_ms.rmesh", "exchange_ms.rmesh", "exchange_retries_per_job.rmesh",
                 "shard_skew.rmesh", "read_ms.rec", "rec_h2d_ms.rec", "rec_d2h_ms.rec",
                 "write_ms.rec", "rec_sync_wait_ms.rec", "load_ms.tput", "run_ms.tput",
                 "output_ms.tput", "compiles_in_window.tput"):
        assert name in read, (name, read)


def test_the_control_is_not_correct():
    p, last = _run(os.path.join(BENCH, "control.py"), "--workload", CELL,
                   "--seeds", "5,2147483659", "--rehearse")
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    doc = json.loads(last)
    assert doc["control_holds"] is True
    for row in doc["seeds"]:
        assert row["sound_correct"] and not row["control_correct"], row
        assert "differs from the oracle" in row["control_verdict"]


STDERR = ("[locust] backend: cpu (cpu x 4)\n"
          + "".join(f"[locust] shard {d}: 500 records\n" for d in range(4))
          + "[locust] sorted by the first 10 bytes: 2000 records, 200000 bytes written "
            "to out; 0 bytes lost\n")


def test_an_out_or_a_report_that_breaks_the_guarantee_is_not_correct(tmp_path):
    path, out = str(tmp_path / "r"), str(tmp_path / "out")
    records_skew.build(path, 2000, 9)
    want = records_skew.oracle(records_skew.load(path)).reshape(-1)
    traffic = json.load(open(os.path.join(BENCH, "traffic", "mesh_records.json")))
    env = types.SimpleNamespace(out_path=out, expect_records=want, platform="cpu",
                                traffic=traffic)
    check = closed_loop_cli_records_mesh.check_job

    def job(stderr=STDERR):
        return yardstick.JobResult(0, b"", stderr, 0.0, 1.0, None)

    want.tofile(out)
    assert check(env, job()) is None
    altered = want.copy()
    altered[123456] ^= 1
    altered.tofile(out)
    assert "differs from the oracle" in check(env, job())
    want[:-100].tofile(out)  # a record lost
    assert "records lost" in check(env, job())
    want.tofile(out)
    assert "0 bytes lost" in check(env, job(STDERR.replace("; 0 bytes lost", "; 100 bytes lost")))
    assert "non-empty shards" in check(env, job(STDERR.replace("shard 3: 500", "shard 3: 0")))
    assert "non-empty shards" in check(env, job(STDERR.replace("[locust] shard 3: 500 records\n", "")))
    assert "non-empty shards" in check(env, job(STDERR.replace(" records\n", " keys\n")))


def test_the_last_out_stays_in_place_and_cannot_pass_for_the_next_job(tmp_path):
    """The driver leaves OUT for the next job to write over in place; what it
    leaves differs from the oracle in every MiB, and an OUT of another size goes."""
    path, out = str(tmp_path / "r"), str(tmp_path / "out")
    records_skew.build(path, 30_000, 11)  # 3,000,000 bytes: three spoiled bytes
    want = records_skew.oracle(records_skew.load(path)).reshape(-1)
    traffic = json.load(open(os.path.join(BENCH, "traffic", "mesh_records.json")))
    env = types.SimpleNamespace(out_path=out, expect_records=want, platform="cpu",
                                traffic=traffic)
    check, spoil = closed_loop_cli_records_mesh.check_job, closed_loop_cli_records_mesh._spoil_out
    res = yardstick.JobResult(0, b"", STDERR, 0.0, 1.0, None)
    spoil(env)  # no OUT yet: nothing to do
    assert not os.path.exists(out)
    want.tofile(out)
    inode = os.stat(out).st_ino
    assert check(env, res) is None
    spoil(env)
    assert os.stat(out).st_ino == inode and os.path.getsize(out) == want.size
    assert "differs from the oracle" in check(env, res)  # a job that wrote nothing
    left = np.fromfile(out, np.uint8)
    differ = np.flatnonzero(left != want)
    stride = closed_loop_cli_records_mesh.SPOIL_STRIDE
    assert differ.tolist() == list(range(0, want.size, stride))
    with open(out, "r+b") as f:  # a job that wrote the first two MiB only
        f.write(want[:2 * stride].tobytes())
    assert "differs from the oracle" in check(env, res)
    want[:-100].tofile(out)
    spoil(env)
    assert os.path.getsize(out) == 0  # emptied, not unlinked: a memory file has no name to remove


def test_out_is_a_memory_file_where_the_platform_has_one(tmp_path, monkeypatch):
    """OUT lies in no file system of the measuring machine: an anonymous memory
    file, reopened by its /proc path as the program opens any OUT; the work
    directory's file where the platform has none."""
    said = []
    env = types.SimpleNamespace(say=said.append)
    driver = closed_loop_cli_records_mesh
    path = driver._memory_out(env)
    assert path == f"/proc/self/fd/{env.out_memfd}" and os.path.getsize(path) == 0 and not said
    assert driver._memory_out(env) == path  # one a run: control.py generates once a seed
    want = np.arange(3 << 20, dtype=np.uint8)
    with open(os.open(path, os.O_WRONLY | os.O_CREAT, 0o666), "wb") as f:  # serde.write_records' open
        f.write(memoryview(want))
    env.out_path, env.expect_records = path, want
    assert driver.records_driver._equal(path, want)
    driver._spoil_out(env)
    assert os.path.getsize(path) == want.size and not driver.records_driver._equal(path, want)
    env.expect_records = want[:-1]
    driver._spoil_out(env)
    assert os.path.getsize(path) == 0
    os.close(env.out_memfd)

    def no_memfd(name):
        raise OSError(38, "Function not implemented")

    monkeypatch.setattr(os, "memfd_create", no_memfd)
    env = types.SimpleNamespace(say=said.append)
    assert driver._memory_out(env) is None and not hasattr(env, "out_memfd")
    assert "OUT is a file of the work directory" in said[0]


def test_a_program_without_mesh_fails_in_set_up(tmp_path):
    """argparse refuses ``--mesh``: exit 4, no result line."""
    script = tmp_path / "no_mesh.py"
    script.write_text(f'''
import sys
sys.path.insert(0, {BENCH!r}); sys.path.insert(0, {ROOT!r})
from locust_tpu import cli_apps
real = cli_apps.build_parser
def without_mesh(cmd):
    p = real(cmd)
    if cmd == "sort":
        p._option_string_actions.pop("--mesh")
    return p
cli_apps.build_parser = without_mesh
import run
raise SystemExit(run.main(["--workload", {CELL!r}, "--seed", "77", "--seconds", "2",
                           "--trace", "0", "--rehearse"]))
''')
    p, last = _run(str(script))
    assert p.returncode == 4, p.stdout[-2000:] + p.stderr[-2000:]
    assert "cannot run configuration terasort-skew-3.2GB-mesh4" in last
    assert "SystemExit: 2" in last
    assert not any(ln.startswith("{") for ln in p.stdout.splitlines())


def test_the_new_readers_read_nothing_where_there_is_nothing_and_price_a_share():
    sizes = {"records": 32_000_000, "record_bytes": 100, "chips": 4}
    assert mesh_record_least_bytes.partition(sizes) == 8_000_000 * 204
    assert mesh_record_least_bytes.shard_permute(sizes) == 8_000_000 * 204
    assert mesh_record_least_bytes.all_to_all(sizes) == 8_000_000 * 100 * 0.75

    def jobs(*span_lists):
        return [types.SimpleNamespace(spans=[(n, 0, 1) for n in names]) for names in span_lists]

    spec = {"span": "sort.mesh.retry", "within": "sort.mesh.exchange"}
    env = types.SimpleNamespace(jobs=jobs(["sort.keys"], ["cli.run"]))
    assert span_count_of.read(spec, env) is None          # the one-chip sort: no exchange
    env.jobs = jobs(["sort.mesh.exchange"], ["sort.mesh.exchange"])
    assert span_count_of.read(spec, env) == 0.0           # no retry is a 0, not a silence
    env.jobs = jobs(["sort.mesh.exchange", "sort.mesh.retry", "sort.mesh.exchange"])
    assert span_count_of.read(spec, env) == 1.0

    peaks = {"hbm_GB_per_s": 819, "ici_Gbit_per_s": 1600}
    trace = {"devices": {0: {"busy_s": 1.0, "modules": {"jit_partition_mesh_records": [0.4, 1]},
                             "ops": {"%all_to_all.7 = u32[4,8,25] all-to-all(%x)": [0.02, 1]}},
                         1: {"busy_s": 2.0, "modules": {"jit_partition_mesh_records": [0.5, 1]},
                             "ops": {"%all_to_all.7 = u32[4,8,25] all-to-all(%x)": [0.03, 1],
                                     "%all_to_all.9 = reshape(%y)": [9.0, 1]}}},
             "slice_jobs": [object()]}
    env = types.SimpleNamespace(trace=trace, sizes=dict(sizes), cell={"chips": 4},
                                device={"peaks": peaks})
    hbm = {"peak": "hbm_GB_per_s", "peak_unit": "GB_per_s", "least_bytes": "partition",
           "programs": ["^jit_partition_mesh_records$"]}
    # the busiest device (1) divides the mean share: 1.632 GB / 819 GB/s over 0.5 s
    assert abs(roofline_device_job.read(hbm, env) - 100 * (1.632 / 819) / 0.5) < 1e-9
    ici = {"peak": "ici_Gbit_per_s", "peak_unit": "Gbit_per_s", "least_bytes": "all_to_all",
           "ops": ["\\sall-to-all\\("]}
    assert abs(roofline_device_job.read(ici, env) - 100 * (0.6 / 200) / 0.03) < 1e-9
    assert roofline_device_job.read(dict(hbm, programs=["^jit_gone$"]), env) is None
    env.trace = None
    assert roofline_device_job.read(hbm, env) is None
