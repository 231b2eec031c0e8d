"""True multi-process distributed tests: 2 OS processes x 2 CPU devices.

Validates the full multi-host stack — ``jax.distributed.initialize``
coordination, ``make_array_from_process_local_data`` ingest sharding, the
shard_map all-to-all shuffle across PROCESS boundaries, replicated psum
stats, and the cross-process ``process_allgather`` result gather — the
parts a single-process 8-device mesh cannot exercise.  The reference's
analogous layer (TCP slave + missing master, SURVEY.md C11/C12) had no
test at all.

Round 3: the r2 features now run under
``process_count > 1`` too — distributed checkpoint/resume (multihost
snapshot gather + resume scatter), the mesh inverted index, and the
sample sort's multihost result gather.
"""

import collections
import json
import os
import re
import socket
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent

# The oracles reconstruct the worker corpus from its line count, so the
# base lines must be the worker's own (tests/ is importable).
from multiprocess_worker import BASE_LINES as BASE  # noqa: E402

from locust_tpu.config import compile_cache_dir  # noqa: E402


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _run_workers(tmp_path, mode, extra_args=(), n_procs=2):
    """Launch coordinated worker processes; return process-0's JSON."""
    coordinator = f"127.0.0.1:{_free_port()}"
    out_json = tmp_path / "result.json"
    env = dict(os.environ)
    env.update(
        {
            # Workers must come up on pure CPU, importing THIS checkout.
            "PYTHONPATH": str(REPO),
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
            "JAX_COMPILATION_CACHE_DIR": compile_cache_dir(".jax_cache_cpu"),
        }
    )
    # Worker output goes to FILES, not pipes: interdependent collective
    # participants + un-drained PIPEs is a deadlock waiting to happen.
    pids = range(n_procs)
    logs = [(tmp_path / f"w{pid}.out", tmp_path / f"w{pid}.err") for pid in pids]
    procs = []
    try:
        for pid in pids:
            out_f = open(logs[pid][0], "wb")
            err_f = open(logs[pid][1], "wb")
            procs.append(
                subprocess.Popen(
                    [
                        sys.executable,
                        str(REPO / "tests" / "multiprocess_worker.py"),
                        coordinator,
                        str(n_procs),
                        str(pid),
                        str(out_json),
                        mode,
                        *extra_args,
                    ],
                    env=env,
                    stdout=out_f,
                    stderr=err_f,
                )
            )
        for p in procs:
            p.wait(timeout=300)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for pid, p in enumerate(procs):
        assert p.returncode == 0, (
            f"worker {pid} failed rc={p.returncode}\n"
            f"stdout:{logs[pid][0].read_bytes().decode()[-2000:]}\n"
            f"stderr:{logs[pid][1].read_bytes().decode()[-2000:]}"
        )
    result = json.loads(out_json.read_text())
    assert result["n_devices"] == n_procs * 2  # 2 virtual devices each
    return result


def _wordcount_oracle(n_lines):
    from locust_tpu.config import DELIMITERS

    reps = n_lines // len(BASE)
    blob = b"\n".join(BASE * reps)
    toks = re.split(b"[" + re.escape(DELIMITERS + b"\n\r\x00") + b"]+", blob)
    return collections.Counter(t for t in toks if t)


@pytest.mark.slow
def test_two_process_wordcount(tmp_path):
    result = _run_workers(tmp_path, "wordcount")
    got = {k.encode(): v for k, v in result["pairs"]}
    assert got == dict(_wordcount_oracle(result["n_lines"]))


@pytest.mark.slow
def test_two_process_checkpoint_resume(tmp_path):
    """Crash mid-run + resume with a fresh engine, across 2 processes:
    per-process snapshots (process_allgather) and the multi-controller
    resume scatter must reproduce the exact table."""
    ckpt = tmp_path / "ckpt"
    ckpt.mkdir()
    result = _run_workers(tmp_path, "checkpoint", (str(ckpt),))
    got = {k.encode(): v for k, v in result["pairs"]}
    assert got == dict(_wordcount_oracle(result["n_lines"]))
    # The resume actually skipped the completed rounds.
    # Crash fires before round 2 of 4 with per-round snapshots, so a
    # correct resume replays EXACTLY the two remaining rounds.
    assert result["resumed_rounds"] == result["nrounds"] - 2
    # Both processes produced snapshot files.
    assert (ckpt / "state.p0.npz").exists()
    assert (ckpt / "state.p1.npz").exists()


@pytest.mark.slow
def test_two_process_inverted_index(tmp_path):
    result = _run_workers(tmp_path, "invindex")
    lines = [ln.encode() for ln in result["lines"]]
    doc_ids = result["doc_ids"]
    from locust_tpu.config import DELIMITERS

    oracle: dict[str, list[int]] = {}
    for ln, d in zip(lines, doc_ids):
        for t in re.split(b"[" + re.escape(DELIMITERS) + b"]+", ln):
            if t:
                docs = oracle.setdefault(t.decode(), [])
                if d not in docs:
                    docs.append(d)
    oracle = {k: sorted(v) for k, v in oracle.items()}
    assert result["index"] == oracle


@pytest.mark.slow
def test_two_process_sample_sort(tmp_path):
    result = _run_workers(tmp_path, "samplesort")
    got = [k for k, _ in result["sorted"]]
    assert got == sorted(result["input"])
    # Payloads are a permutation of the original indices.
    assert sorted(v for _, v in result["sorted"]) == list(range(len(got)))


@pytest.mark.slow
def test_two_process_hierarchical_checkpoint_resume(tmp_path):
    """Hierarchical crash+resume with the slice axis across processes:
    the shared ShardedCheckpoint gather/scatter must round-trip the 2-D
    [slice, data] sharding through per-process npz snapshots."""
    ckpt = tmp_path / "hckpt"
    ckpt.mkdir()
    result = _run_workers(tmp_path, "hier_checkpoint", (str(ckpt),))
    got = {k.encode(): v for k, v in result["pairs"]}
    assert got == dict(_wordcount_oracle(result["n_lines"]))
    assert result["resumed_rounds"] == result["nrounds"] - 2
    assert (ckpt / "state.p0.npz").exists()
    assert (ckpt / "state.p1.npz").exists()


@pytest.mark.slow
def test_two_process_hierarchical(tmp_path):
    """[2 slices x 2 devices] with the SLICE axis across process
    boundaries: per-round collectives stay intra-process (ICI analog),
    the slice-varying stats fetch must replicate before device_get, and
    the one cross-slice combine crosses processes (DCN analog)."""
    result = _run_workers(tmp_path, "hierarchical")
    got = {k.encode(): v for k, v in result["pairs"]}
    oracle = _wordcount_oracle(result["n_lines"])
    assert got == dict(oracle)
    assert result["distinct"] == len(oracle)


@pytest.mark.slow
def test_two_process_sharded_pagerank(tmp_path):
    """ShardedPageRank with the device axis across processes: plan
    scatter via make_array_from_callback, per-iteration all_to_all over
    process boundaries, result via process_allgather (the newest mesh
    program had no multi-process scenario)."""
    result = _run_workers(tmp_path, "spagerank")
    import numpy as np

    from locust_tpu.apps.pagerank import pagerank

    n = result["num_nodes"]
    rng = np.random.default_rng(result["edge_seed"])
    src = rng.integers(0, n, result["n_edges"]).astype(np.int32)
    dst = rng.integers(0, n, result["n_edges"]).astype(np.int32)
    ref = np.asarray(pagerank(src, dst, num_nodes=n, num_iters=10))
    np.testing.assert_allclose(np.asarray(result["ranks"]), ref, atol=1e-5)


@pytest.mark.slow
def test_four_process_checkpoint_resume(tmp_path):
    """The crash+resume scenario at 4 processes x 2 devices: catches
    process-count-dependent assumptions (snapshot file fan-out, gather
    shapes, shard alignment) the 2-process rig cannot."""
    ckpt = tmp_path / "ckpt4"
    ckpt.mkdir()
    result = _run_workers(tmp_path, "checkpoint", (str(ckpt),), n_procs=4)
    got = {k.encode(): v for k, v in result["pairs"]}
    assert got == dict(_wordcount_oracle(result["n_lines"]))
    assert result["resumed_rounds"] == result["nrounds"] - 2
    for pid in range(4):
        assert (ckpt / f"state.p{pid}.npz").exists()


@pytest.mark.slow
def test_cli_pod_launch(tmp_path):
    """The pod-launch CLI contract end-to-end: the SAME command line on
    every process (own --process-id), coordination via --coordinator,
    and exactly one table on the pod's combined stdout (process 0's).
    Multi-process launch existed only inside the
    test rig, with no CLI surface."""
    corpus = tmp_path / "pod.txt"
    corpus.write_bytes(b"\n".join(BASE * 8) + b"\n")
    coordinator = f"127.0.0.1:{_free_port()}"
    env = dict(os.environ)
    env.update(
        {
            "PYTHONPATH": str(REPO),
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
            "JAX_COMPILATION_CACHE_DIR": compile_cache_dir(".jax_cache_cpu"),
        }
    )
    outs = [tmp_path / f"cli{pid}.out" for pid in (0, 1)]
    errs = [tmp_path / f"cli{pid}.err" for pid in (0, 1)]
    procs = []
    try:
        for pid in (0, 1):
            procs.append(
                subprocess.Popen(
                    [
                        sys.executable, "-m", "locust_tpu", str(corpus),
                        "--mesh", "--backend", "cpu",
                        "--block-lines", "8", "--line-width", "64",
                        "--emits-per-line", "8",
                        "--coordinator", coordinator,
                        "--num-processes", "2", "--process-id", str(pid),
                    ],
                    env=env,
                    stdout=open(outs[pid], "wb"),
                    stderr=open(errs[pid], "wb"),
                )
            )
        for p in procs:
            p.wait(timeout=300)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for pid, p in enumerate(procs):
        assert p.returncode == 0, (
            f"cli proc {pid} rc={p.returncode}\n"
            f"stderr:{errs[pid].read_bytes().decode()[-2000:]}"
        )
    # The Gloo CPU collective transport writes rank-connection noise to
    # stdout in multi-process CPU mode; the table lines are the ones with
    # a tab.  (Real pods use a different transport; this is rig-only.)
    def table_of(raw: bytes):
        got = {}
        for ln in raw.splitlines():
            if b"\t" not in ln:
                continue
            k, _, v = ln.partition(b"\t")
            got[k] = int(v)
        return got

    assert table_of(outs[0].read_bytes()) == dict(
        _wordcount_oracle(len(BASE * 8))
    )
    assert table_of(outs[1].read_bytes()) == {}  # only process 0 prints


def test_two_process_hasht(tmp_path):
    """The sort-free fold's scatters + nested lax.cond ladder under REAL
    cross-process collectives (not just the single-process virtual
    mesh) — oracle-exact."""
    result = _run_workers(tmp_path, "hasht")
    got = {k.encode(): v for k, v in result["pairs"]}
    assert got == dict(_wordcount_oracle(result["n_lines"]))


@pytest.mark.slow
def test_two_process_hasht_checkpoint_resume(tmp_path):
    """Crash+resume with hasht: snapshots hold SLOT-ORDERED (non
    prefix-compact) accumulator tables; the scatter-resume and the
    continued sort-free folds must still reproduce the exact table."""
    ckpt = tmp_path / "ckpt"
    ckpt.mkdir()
    result = _run_workers(tmp_path, "hasht_checkpoint", (str(ckpt),))
    got = {k.encode(): v for k, v in result["pairs"]}
    assert got == dict(_wordcount_oracle(result["n_lines"]))
    assert result["resumed_rounds"] == result["nrounds"] - 2
