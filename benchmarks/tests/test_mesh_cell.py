"""The mesh cell ``wczipf250.mesh4`` on the CPU (four virtual devices);
kept out of ``tests/`` like ``test_controls.py``:

    env JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_mesh_cell.py -q

1. a rehearsal of the cell is correct — four non-empty shards, both
   overflows 0, ``truncated=False`` — and reads every ``.mesh`` metric that
   needs no chip (a shard grows at rehearsal size, so the growth metrics
   too);
2. its control (``--emits-per-line 8``) is NOT correct while the same
   seeds' sound jobs are;
3. a program whose shards hold a fixed number of rows (the parent of
   PR 30) ends in set-up with exit code 4 and no result line;
4. the two readers this cell brought read what they say they read.
"""

import json
import os
import subprocess
import sys
import types

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

from readers import stderr_regex, xla_op  # noqa: E402

CELL = "wczipf250.mesh4"


def _run(*argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    p = subprocess.run([sys.executable, *argv], env=env, capture_output=True, text=True,
                       timeout=600)
    return p, (p.stdout.strip().splitlines() or [""])[-1]


def test_a_rehearsal_of_the_cell_is_correct():
    p, last = _run(os.path.join(BENCH, "run.py"), "--workload", CELL, "--seed",
                   "2147483661", "--seconds", "2", "--trace", "1", "--rehearse")
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    doc = json.loads(last)
    assert doc["correct"] is True and doc["failed"] == 0 and "metrics" not in doc
    assert "count 4" in p.stdout  # run.py gave the cell its four (virtual) devices
    read = next(ln for ln in p.stdout.splitlines() if "metrics read" in ln)
    for name in ("round_ms.mesh", "mesh_sync_ms.mesh", "gather_ms.mesh", "shard_skew.mesh",
                 "shard_grow_ms.mesh", "shard_grows_per_job.mesh", "run_ms.tput"):
        assert name in read, (name, read)


def test_the_control_is_not_correct():
    p, last = _run(os.path.join(BENCH, "control.py"), "--workload", CELL, "--seeds",
                   "5,2147483659", "--rehearse")
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    doc = json.loads(last)
    assert doc["control_holds"] is True
    assert all(r["sound_correct"] and not r["control_correct"] for r in doc["seeds"])


BREAK = '''
import sys
sys.path.insert(0, {bench!r}); sys.path.insert(0, {root!r})
from locust_tpu.parallel import shuffle
real = shuffle.DistributedMapReduce.__init__
def fixed(self, mesh, cfg, *a, **kw):
    real(self, mesh, cfg, *a, **kw)
    self.grows = False                      # shards of fixed size: the parent of PR 30
shuffle.DistributedMapReduce.__init__ = fixed
import run
raise SystemExit(run.main(["--workload", {cell!r}, "--seed", "77", "--seconds", "2",
                           "--trace", "0", "--rehearse"]))
'''


def test_a_program_with_fixed_shards_fails_in_set_up(tmp_path):
    script = tmp_path / "fixed_shards.py"
    script.write_text(BREAK.format(bench=BENCH, root=ROOT, cell=CELL))
    p, last = _run(str(script))
    assert p.returncode == 4, p.stdout[-2000:] + p.stderr[-2000:]
    assert "cannot run configuration wc-zipf-250MB-mesh4" in last
    assert "table differs" in last or "truncated=True" in last or "WARN" in last
    assert not any(ln.startswith("{") for ln in p.stdout.splitlines())


def _env(**kw):
    return types.SimpleNamespace(**kw)


def test_stderr_regex_reads_the_skew_or_nothing():
    jobs = [_env(stderr="[locust] shard 0: 90 keys\n[locust] shard 1: 110 keys\n"),
            _env(stderr="[locust] shard 0: 100 keys\n[locust] shard 1: 100 keys\n"),
            _env(stderr="[locust] shard 0: 80 keys\n[locust] shard 1: 120 keys\n"),
            _env(stderr="no such line")]
    skew = {"pattern": r"shard \d+: (\d+) keys", "per_job": "max_over_mean"}
    assert stderr_regex.read(skew, _env(jobs=jobs)) == 1.1  # median of 1.1, 1.0, 1.2
    assert stderr_regex.read(skew, _env(jobs=jobs[3:])) is None  # nothing to read: no metric
    with pytest.raises(ValueError, match="unknown per_job"):
        stderr_regex.read(dict(skew, per_job="first"), _env(jobs=jobs))


def test_xla_op_sums_the_operations_of_the_busiest_device():
    # Names as a v5e trace has them (PR 30's first traced run).
    busy = {"%all_to_all.32 = u32[4,40960,8]{1,2,0:T(8,128)S(1)} all-to-all(u32[4,40960,8]"
            "{1,2,0:T(8,128)S(1)} %bitcast.328), channel_id=1": (0.002, 4),
            "%all_to_all.34 = s32[4,1,40960]{2,1,0} all-to-all(s32[4,1,40960]{2,1,0} "
            "%bitcast.342), channel_id=1": (0.001, 2),
            "%all_to_all.36 = pred[4,1,40960]{2,1,0} reshape(pred[163840]{0} %slice.536)":
                (0.25, 4),                                    # named after it, is not it
            "%reduce.23 = pred[4,40960]{1,0} reduce(pred[4,1,40960]{2,1,0} %all_to_all.36, "
            "pred[] %broadcast.961)": (0.5, 4)}                # takes it, is not it
    other = {"%all_to_all.32 = u32[4,8]{1,0} all-to-all(u32[4,8]{1,0} %p)": (9.0, 1)}
    trace = {"devices": {0: {"busy_s": 1.0, "ops": other}, 1: {"busy_s": 2.0, "ops": busy}},
             "slice_jobs": [object(), object()]}
    with open(os.path.join(BENCH, "layer_metrics", "a2a_dev_ms.json")) as f:
        spec = json.load(f)
    assert abs(xla_op.read(spec, _env(trace=trace)) - 1.5) < 1e-9  # 3 ms over 2 jobs
    assert xla_op.read({"patterns": [r"\sall-gather\("]}, _env(trace=trace)) is None
    assert xla_op.read(spec, _env(trace=None)) is None
