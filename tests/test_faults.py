"""Chaos matrix: the distributor + checkpoint paths under injected faults.

ISSUE 1 contract: for EVERY fault class the deterministic fault plan can
inject (connect refusal, frame corruption/truncation, worker crash
mid-map, stragglers, corrupted intermediate chunks, corrupted/truncated
checkpoints), the distributed WordCount job either produces BYTE-IDENTICAL
output to the fault-free run or raises a structured ``MasterError`` —
never a hang (everything here is bounded by small socket/RPC timeouts)
and never silent corruption.

All loopback, in-proc map runners (shared JAX runtime), tiny corpus.
"""

import os
import socket
import time

import numpy as np
import pytest

from helpers import py_wordcount, serve_abandon

from locust_tpu import cli
from locust_tpu.distributor import master, protocol
from locust_tpu.distributor.master import (
    IntegrityError,
    JobResult,
    MasterError,
    WorkerHealth,
)
from locust_tpu.distributor.worker import Worker
from locust_tpu.utils import faultplan

SECRET = b"chaos-secret"

CORPUS = b"""alpha beta gamma
beta gamma delta
gamma delta epsilon
delta epsilon alpha
epsilon alpha beta
zeta eta theta iota
"""

# Small, bounded control-plane timings: a hung test IS a failed test.
WORKER_KW = dict(secret=SECRET, conn_timeout=3.0)
JOB_KW = dict(
    rpc_timeout=15.0,
    heartbeat_interval=0.2,
    poll_s=0.02,
    max_retries=2,
)


@pytest.fixture
def corpus_file(tmp_path):
    p = tmp_path / "corpus.txt"
    p.write_bytes(CORPUS)
    return str(p)


@pytest.fixture(autouse=True)
def _no_leftover_plan():
    """A chaos plan must never leak across tests."""
    yield
    faultplan.deactivate()


def make_inproc_runner():
    """Map runner invoking the CLI in-process (fast: shared JAX runtime)."""

    def runner(req):
        args = [
            req["file"],
            str(req["line_start"]),
            str(req["line_end"]),
            str(req["node_num"]),
            "1",
            "-i",
            req["intermediate"],
            "--block-lines", "8",
            "--line-width", "64",
            "--emits-per-line", "8",
            "--no-timing",
        ]
        if req.get("inter_format"):  # the master's negotiated data plane
            args += ["--inter-format", req["inter_format"]]
        rc = cli.main(args)
        return {"status": "ok" if rc == 0 else "error", "returncode": rc,
                "log": "", "intermediate": req["intermediate"]}

    return runner


def _shutdown(w: Worker):
    try:
        master._rpc(w.addr, {"cmd": "shutdown"}, SECRET, timeout=5)
    except Exception:
        pass


def _reduce_bytes(corpus_file, tsvs, capsysbinary) -> bytes:
    """Stage-2 reduce over the collected TSVs; returns raw stdout bytes."""
    capsysbinary.readouterr()
    rc = cli.main(
        [corpus_file, "-1", "-1", "0", "2", "--block-lines", "8",
         "--line-width", "64", "--emits-per-line", "8", "--no-timing"]
        + sum((["-i", t] for t in tsvs), [])
    )
    assert rc == 0
    return capsysbinary.readouterr().out


def _run_wordcount(corpus_file, tmp_path, capsysbinary, plan=None,
                   n_workers=2, job_kw=None, rpc=None):
    """Full loopback job (optionally under a fault plan) -> (bytes, JobResult)."""
    runner = make_inproc_runner()
    workers = [Worker(map_runner=runner, **WORKER_KW) for _ in range(n_workers)]
    for w in workers:
        w.serve_in_thread()
    kw = dict(JOB_KW, **(job_kw or {}))
    # Fast, fresh health per job: short backoffs keep the chaos matrix
    # quick without changing the scheduling logic under test.
    kw.setdefault(
        "health", WorkerHealth(n_workers, base_s=0.05, cap_s=2.0, seed=1)
    )
    if rpc is not None:
        kw["rpc"] = rpc
    try:
        if plan is not None:
            with faultplan.active_plan(plan):
                res = master.run_job(
                    [w.addr for w in workers], corpus_file, SECRET,
                    workdir=str(tmp_path / "m"), **kw,
                )
        else:
            res = master.run_job(
                [w.addr for w in workers], corpus_file, SECRET,
                workdir=str(tmp_path / "m"), **kw,
            )
        out = _reduce_bytes(corpus_file, res, capsysbinary)
        return out, res, workers
    finally:
        for w in workers:
            _shutdown(w)


def plan(rules, seed=7) -> faultplan.FaultPlan:
    return faultplan.FaultPlan(rules, seed=seed)


# --------------------------------------------------------------- plan parsing


def test_fault_plan_parse_sources(tmp_path, monkeypatch):
    spec = '{"seed": 5, "rules": [{"site": "rpc.connect", "action": "refuse"}]}'
    p = faultplan.FaultPlan.parse(spec)
    assert p.seed == 5 and p.rules[0].site == "rpc.connect"
    f = tmp_path / "plan.json"
    f.write_text(spec)
    assert faultplan.FaultPlan.parse(str(f)).seed == 5
    # env activation (install), and explicit spec winning over env
    monkeypatch.setenv(faultplan.ENV_VAR, spec)
    try:
        got = faultplan.install()
        assert got is not None and faultplan.active() is got
    finally:
        faultplan.deactivate()
    monkeypatch.delenv(faultplan.ENV_VAR)
    assert faultplan.install() is None  # nothing to install
    assert faultplan.active() is None


def test_fault_plan_rejects_typos():
    with pytest.raises(ValueError, match="unknown site"):
        plan([{"site": "rpc.conect", "action": "refuse"}])
    with pytest.raises(ValueError, match="invalid for site"):
        plan([{"site": "rpc.connect", "action": "corrupt"}])
    with pytest.raises(ValueError, match="unknown keys"):
        plan([{"site": "rpc.connect", "action": "refuse", "portt": 1}])
    with pytest.raises(ValueError, match="prob"):
        plan([{"site": "rpc.connect", "action": "refuse", "prob": 0.0}])
    with pytest.raises(ValueError, match="delay_s"):
        plan([{"site": "rpc.delay", "action": "delay"}])


def test_fault_plan_deterministic_decisions_and_mutations():
    spec = [{"site": "rpc.frame", "action": "corrupt", "prob": 0.5}]
    runs = []
    for _ in range(2):
        p = plan(spec, seed=11)
        with faultplan.active_plan(p):
            runs.append([
                faultplan.mangle("rpc.frame", bytes(range(256)), keep_prefix=4)
                for _ in range(20)
            ])
    assert runs[0] == runs[1]  # same seed -> same gates, same byte flips
    assert any(r != bytes(range(256)) for r in runs[0])  # fired sometimes
    assert any(r == bytes(range(256)) for r in runs[0])  # and skipped sometimes
    # a different seed decides differently
    p = plan(spec, seed=12)
    with faultplan.active_plan(p):
        other = [
            faultplan.mangle("rpc.frame", bytes(range(256)), keep_prefix=4)
            for _ in range(20)
        ]
    assert other != runs[0]


def test_hooks_are_noops_without_plan():
    data = b"payload-bytes"
    assert faultplan.mangle("rpc.frame", data) is data  # not even a copy
    assert faultplan.fire("worker.map", shard=0) is None
    faultplan.check_connect("h", 1)   # no raise
    faultplan.delay("rpc.delay", cmd="map")  # no sleep
    faultplan.damage_file("io.checkpoint", "/nonexistent")  # no touch


# ---------------------------------------------------- health unit (fake clock)


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def test_worker_health_exponential_backoff_fake_clock():
    clk = FakeClock()
    h = WorkerHealth(2, clock=clk, base_s=1.0, cap_s=8.0, jitter=0.0, seed=1)
    assert h.healthy(0) and not h.quarantined(0)
    assert h.fail(0) == 1.0
    assert h.quarantined(0) and not h.probe_due(0) and not h.healthy(0)
    clk.advance(0.5)
    assert not h.probe_due(0)
    clk.advance(0.6)
    assert h.probe_due(0)        # backoff expired: eligible for a probe
    assert not h.healthy(0)      # ...but NOT healthy until a good pong
    # consecutive failures double, capped at cap_s
    assert h.fail(0) == 2.0
    assert h.fail(0) == 4.0
    assert h.fail(0) == 8.0
    assert h.fail(0) == 8.0
    # recovery clears the slate entirely
    h.ok(0)
    assert h.healthy(0) and h.failures(0) == 0
    assert h.fail(0) == 1.0
    # worker 1 was never touched
    assert h.healthy(1)


def test_worker_health_jitter_deterministic_and_bounded():
    clk = FakeClock()
    a = WorkerHealth(1, clock=clk, base_s=1.0, jitter=0.5, seed=3)
    b = WorkerHealth(1, clock=clk, base_s=1.0, jitter=0.5, seed=3)
    backs = [a.fail(0) for _ in range(4)]
    assert backs == [b.fail(0) for _ in range(4)]  # seeded, reproducible
    for i, back in enumerate(backs):
        base = min(8.0 * 4, 1.0 * 2**i)
        assert base <= back <= base * 1.5  # jitter stretches, never shrinks
    c = WorkerHealth(1, clock=clk, base_s=1.0, jitter=0.5, seed=4)
    assert [c.fail(0) for _ in range(4)] != backs  # different seed, different noise


def test_heartbeat_unquarantines_recovered_worker():
    """The heartbeat loop pings a quarantine-expired worker and clears it."""
    import threading

    h = WorkerHealth(1, base_s=0.01, jitter=0.0)
    h.fail(0)
    stop = threading.Event()
    pings = []

    def rpc(node, req, secret):
        pings.append(req["cmd"])
        return {"status": "ok", "pong": True}

    t = threading.Thread(
        target=master._heartbeat_loop,
        args=(stop, h, [("127.0.0.1", 1)], rpc, SECRET, 0.02),
        daemon=True,
    )
    t.start()
    deadline = time.monotonic() + 5.0
    while not h.healthy(0) and time.monotonic() < deadline:
        time.sleep(0.01)
    stop.set()
    t.join(timeout=2)
    assert h.healthy(0), "heartbeat should un-quarantine on a good pong"
    assert "ping" in pings


def test_heartbeat_deepens_backoff_while_down():
    import threading

    h = WorkerHealth(1, base_s=0.01, jitter=0.0)
    h.fail(0)
    stop = threading.Event()

    def rpc(node, req, secret):
        raise ConnectionRefusedError("still down")

    t = threading.Thread(
        target=master._heartbeat_loop,
        args=(stop, h, [("127.0.0.1", 1)], rpc, SECRET, 0.02),
        daemon=True,
    )
    t.start()
    deadline = time.monotonic() + 5.0
    while h.failures(0) < 3 and time.monotonic() < deadline:
        time.sleep(0.01)
    stop.set()
    t.join(timeout=2)
    assert h.failures(0) >= 3 and not h.healthy(0)


# ------------------------------------------------------------- chaos matrix


def _fault_free(corpus_file, tmp_path, capsysbinary):
    out, res, _ = _run_wordcount(
        corpus_file, tmp_path / "clean", capsysbinary
    )
    # sanity: matches the oracle too
    got = {k: int(v) for k, _, v in
           (line.partition(b"\t") for line in out.splitlines())}
    assert got == dict(py_wordcount(CORPUS.splitlines(), 8))
    return out


def test_chaos_connect_refusal_byte_identical(corpus_file, tmp_path, capsysbinary):
    want = _fault_free(corpus_file, tmp_path, capsysbinary)
    # Refuse the first two connects anywhere: the shard fails over.
    p = plan([{"site": "rpc.connect", "action": "refuse", "times": 2}])
    out, res, _ = _run_wordcount(
        corpus_file, tmp_path / "f", capsysbinary, plan=p
    )
    assert out == want
    assert p.rules[0].fired == 2


def test_chaos_frame_corruption_byte_identical(corpus_file, tmp_path, capsysbinary):
    want = _fault_free(corpus_file, tmp_path, capsysbinary)
    # One corrupted map frame: HMAC rejects it, the connection drops, the
    # shard is retried — output unchanged.
    p = plan([{"site": "rpc.frame", "action": "corrupt",
               "match": {"cmd": "map"}, "times": 1}])
    out, res, _ = _run_wordcount(
        corpus_file, tmp_path / "f", capsysbinary, plan=p
    )
    assert out == want
    assert p.rules[0].fired == 1


def test_chaos_frame_truncation_byte_identical(corpus_file, tmp_path, capsysbinary):
    want = _fault_free(corpus_file, tmp_path, capsysbinary)
    # One truncated map frame: the worker's bounded read times out (3s),
    # it answers a structured error, the shard is retried.
    p = plan([{"site": "rpc.frame", "action": "truncate",
               "match": {"cmd": "map"}, "times": 1}])
    out, res, _ = _run_wordcount(
        corpus_file, tmp_path / "f", capsysbinary, plan=p
    )
    assert out == want


def test_chaos_worker_crash_mid_map_byte_identical(corpus_file, tmp_path, capsysbinary):
    want = _fault_free(corpus_file, tmp_path, capsysbinary)
    # Shard 0's first map attempt dies like a SIGKILL (connection dropped,
    # no reply); the master reassigns it.
    p = plan([{"site": "worker.map", "action": "crash",
               "match": {"shard": 0}, "times": 1}])
    out, res, _ = _run_wordcount(
        corpus_file, tmp_path / "f", capsysbinary, plan=p
    )
    assert out == want
    shard0 = next(s for s in res.shards if s.shard == 0)
    assert len(shard0.attempts) >= 2  # the crash cost an attempt


def test_chaos_map_error_byte_identical(corpus_file, tmp_path, capsysbinary):
    want = _fault_free(corpus_file, tmp_path, capsysbinary)
    p = plan([{"site": "worker.map", "action": "error",
               "match": {"shard": 1}, "times": 1}])
    out, res, _ = _run_wordcount(
        corpus_file, tmp_path / "f", capsysbinary, plan=p
    )
    assert out == want


def test_chaos_straggler_speculative_backup_wins(corpus_file, tmp_path, capsysbinary):
    want = _fault_free(corpus_file, tmp_path, capsysbinary)
    # Every map is delayed 6s on whichever worker serves shard 1's home.
    # We can't know the ephemeral port up front, so key the delay on the
    # shard instead: shard 1's FIRST map attempt stalls; the speculative
    # backup on the other worker wins long before the stall ends.
    # The stall (12s) comfortably exceeds a warm in-proc map (~1-2s incl.
    # re-trace), so the backup must win; the elapsed bound proves the job
    # never waited the stall out (it includes the reduce + teardown).
    p = plan([{"site": "rpc.delay", "action": "delay",
               "match": {"cmd": "map", "shard": 1}, "times": 1,
               "delay_s": 12.0}])
    t0 = time.monotonic()
    out, res, _ = _run_wordcount(
        corpus_file, tmp_path / "f", capsysbinary, plan=p,
        job_kw=dict(speculate_after=0.4),
    )
    elapsed = time.monotonic() - t0
    assert out == want
    shard1 = next(s for s in res.shards if s.shard == 1)
    assert shard1.speculated, "straggling shard should have speculated"
    # first finisher wins: the stalled PRIMARY lost, the backup won
    assert shard1.attempts[0]["outcome"] == "cancelled"
    winner = next(a for a in shard1.attempts if a["outcome"] == "ok")
    assert winner["speculative"]
    assert elapsed < 11.0


def test_chaos_intermediate_corruption_byte_identical(corpus_file, tmp_path, capsysbinary):
    want = _fault_free(corpus_file, tmp_path, capsysbinary)
    # One fetch chunk rots on 'disk': the end-to-end sha256 (recorded at
    # map time) catches it, the worker is quarantined, the shard re-runs.
    p = plan([{"site": "io.intermediate", "action": "corrupt", "times": 1}])
    out, res, _ = _run_wordcount(
        corpus_file, tmp_path / "f", capsysbinary, plan=p
    )
    assert out == want
    assert p.rules[0].fired == 1
    outcomes = [a["outcome"] for s in res.shards for a in s.attempts]
    assert "integrity" in outcomes


def test_chaos_compressed_chunk_corruption_byte_identical(corpus_file, tmp_path, capsysbinary):
    """ISSUE 2 site: the ENCODED (zlib/raw) fetch payload rots after the
    worker hashed the raw window — the master sees a zlib error or a
    chunk-sha mismatch, the shard re-runs, output unchanged."""
    want = _fault_free(corpus_file, tmp_path, capsysbinary)
    p = plan([{"site": "io.chunk", "action": "corrupt", "times": 1}])
    out, res, _ = _run_wordcount(
        corpus_file, tmp_path / "f", capsysbinary, plan=p
    )
    assert out == want
    assert p.rules[0].fired == 1
    outcomes = [a["outcome"] for s in res.shards for a in s.attempts]
    assert "integrity" in outcomes or "error" in outcomes


def test_chaos_chunk_truncation_byte_identical(corpus_file, tmp_path, capsysbinary):
    want = _fault_free(corpus_file, tmp_path, capsysbinary)
    p = plan([{"site": "io.chunk", "action": "truncate", "times": 1}])
    out, res, _ = _run_wordcount(
        corpus_file, tmp_path / "f", capsysbinary, plan=p
    )
    assert out == want
    assert p.rules[0].fired == 1


def test_chaos_chunk_delay_absorbed(corpus_file, tmp_path, capsysbinary):
    """Latency at the pipelined-fetch site: a stalled chunk delays the
    transfer but never changes the bytes."""
    want = _fault_free(corpus_file, tmp_path, capsysbinary)
    p = plan([{"site": "io.chunk", "action": "delay", "times": 1,
               "delay_s": 1.0}])
    out, res, _ = _run_wordcount(
        corpus_file, tmp_path / "f", capsysbinary, plan=p
    )
    assert out == want
    assert p.rules[0].fired == 1


def test_chaos_persistent_chunk_corruption_structured_error(corpus_file, tmp_path):
    """Corruption on EVERY encoded chunk: the binary data plane must turn
    it into a structured MasterError, like the raw-window site."""
    runner = make_inproc_runner()
    w1 = Worker(map_runner=runner, **WORKER_KW)
    w2 = Worker(map_runner=runner, **WORKER_KW)
    w1.serve_in_thread()
    w2.serve_in_thread()
    p = plan([{"site": "io.chunk", "action": "corrupt"}])  # unlimited
    try:
        with faultplan.active_plan(p):
            with pytest.raises(MasterError):
                master.run_job(
                    [w1.addr, w2.addr], corpus_file, SECRET,
                    workdir=str(tmp_path / "m"),
                    health=WorkerHealth(2, base_s=0.05, cap_s=0.5, seed=1),
                    **JOB_KW,
                )
        assert p.rules[0].fired >= 1
    finally:
        _shutdown(w1)
        _shutdown(w2)


def test_dataplane_defaults_binary_packed(corpus_file, tmp_path, capsysbinary):
    """The new data plane is the DEFAULT: fault-free jobs move packed-KV
    intermediates over binary frames, and the per-fetch stats land in
    JobResult.shards."""
    from locust_tpu.io import serde

    out, res, _ = _run_wordcount(corpus_file, tmp_path, capsysbinary)
    assert all(serde.is_kvbin(p) for p in res)
    dp = res.dataplane()
    assert dp["binary"] and dp["fetches"] == 2 and dp["payload_bytes"] > 0
    for s in res.shards:
        ok = next(a for a in s.attempts if a["outcome"] == "ok")
        f = ok["fetch"]
        assert f["bytes"] > 0 and f["chunks"] >= 1 and f["binary"]
        assert f["elapsed_s"] > 0 and f["wire_bytes"] > 0


def test_chaos_everything_down_structured_error(corpus_file, tmp_path):
    """When no worker can ever serve, the job fails FAST with MasterError
    — the structured arm of the matrix contract (not a hang)."""
    runner = make_inproc_runner()
    w1 = Worker(map_runner=runner, **WORKER_KW)
    w2 = Worker(map_runner=runner, **WORKER_KW)
    w1.serve_in_thread()
    w2.serve_in_thread()
    p = plan([{"site": "rpc.connect", "action": "refuse"}])  # unlimited
    try:
        t0 = time.monotonic()
        with faultplan.active_plan(p):
            with pytest.raises(MasterError, match="failed on every tried"):
                master.run_job(
                    [w1.addr, w2.addr], corpus_file, SECRET,
                    workdir=str(tmp_path / "m"),
                    health=WorkerHealth(2, base_s=0.05, cap_s=0.5, seed=1),
                    **JOB_KW,
                )
        assert time.monotonic() - t0 < 30.0
    finally:
        _shutdown(w1)
        _shutdown(w2)


def test_chaos_persistent_corruption_structured_error(corpus_file, tmp_path):
    """Corruption on EVERY fetch chunk: integrity verification must turn
    would-be silent corruption into a structured MasterError."""
    runner = make_inproc_runner()
    w1 = Worker(map_runner=runner, **WORKER_KW)
    w2 = Worker(map_runner=runner, **WORKER_KW)
    w1.serve_in_thread()
    w2.serve_in_thread()
    p = plan([{"site": "io.intermediate", "action": "corrupt"}])  # unlimited
    try:
        with faultplan.active_plan(p):
            with pytest.raises(MasterError):
                master.run_job(
                    [w1.addr, w2.addr], corpus_file, SECRET,
                    workdir=str(tmp_path / "m"),
                    health=WorkerHealth(2, base_s=0.05, cap_s=0.5, seed=1),
                    **JOB_KW,
                )
        assert p.rules[0].fired >= 1
    finally:
        _shutdown(w1)
        _shutdown(w2)


def test_master_detects_tampered_chunk_via_chunk_digest(corpus_file, tmp_path, capsysbinary):
    """Per-chunk sha256: a chunk tampered BETWEEN worker and master (after
    the worker hashed it) is caught immediately, shard reassigned."""
    import base64

    want = _fault_free(corpus_file, tmp_path, capsysbinary)
    tampered = {"n": 0}

    def tampering_rpc(node, req, secret):
        resp = master._rpc(node, req, secret, timeout=JOB_KW["rpc_timeout"])
        if req.get("cmd") == "fetch" and tampered["n"] == 0 and resp.get("data_b64"):
            raw = bytearray(base64.b64decode(resp["data_b64"]))
            if raw:
                raw[0] ^= 0xFF
                resp["data_b64"] = base64.b64encode(bytes(raw)).decode()
                tampered["n"] += 1
        return resp

    out, res, _ = _run_wordcount(
        corpus_file, tmp_path / "f", capsysbinary, rpc=tampering_rpc
    )
    assert out == want
    assert tampered["n"] == 1
    outcomes = [a["outcome"] for s in res.shards for a in s.attempts]
    assert "integrity" in outcomes


def test_job_result_is_still_a_path_list(corpus_file, tmp_path, capsysbinary):
    """Back-compat: JobResult behaves as the list of TSV paths, with the
    per-shard timing stats riding along (ISSUE 1 'stats in job result')."""
    out, res, _ = _run_wordcount(corpus_file, tmp_path, capsysbinary)
    assert isinstance(res, JobResult) and isinstance(res, list)
    assert len(res) == 2 and all(os.path.exists(t) for t in res)
    assert len(res.shards) == 2
    for s in res.shards:
        assert s.winner is not None and s.elapsed_s > 0
        assert s.attempts and s.attempts[0]["t1"] is not None
        assert s.as_dict()["shard"] == s.shard


# ----------------------------------------------------- checkpoint corruption

import jax  # noqa: E402

from locust_tpu.config import EngineConfig  # noqa: E402
from locust_tpu.core import bytes_ops  # noqa: E402

needs8 = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 (virtual) devices"
)


def _mesh_cfg():
    return EngineConfig(block_lines=4, line_width=64, emits_per_line=8)


def _mesh_fixture(tmp_path):
    """A mesh engine mid-corpus with two checkpoint generations on disk.

    Pinned to SYNCHRONOUS snapshots: the fixture's assertions depend on
    exactly one snapshot per completed round (two generations on disk
    after two rounds), and the async writer's latest-wins contract makes
    that count timing-dependent.  The async path has its own chaos
    coverage below (io.ckpt_write) and rides the default config in
    test_chaos_checkpoint_fault_site_never_wrong_counts."""
    import dataclasses

    from locust_tpu.parallel.mesh import make_mesh
    from locust_tpu.parallel.shuffle import DistributedMapReduce

    cfg = dataclasses.replace(_mesh_cfg(), async_checkpoint=False)
    lines = [b"alpha beta", b"beta gamma", b"alpha delta epsilon"] * 40
    rows = bytes_ops.strings_to_rows(lines, cfg.line_width)
    mesh = make_mesh(8)
    want = dict(DistributedMapReduce(mesh, cfg).run(rows).to_host_pairs())

    ckpt = str(tmp_path / "dckpt")
    dmr = DistributedMapReduce(mesh, cfg)
    real_step = dmr._step
    calls = {"n": 0}

    def dying_step(lines_, acc, leftover):
        if calls["n"] == 2:
            raise RuntimeError("simulated crash")
        calls["n"] += 1
        return real_step(lines_, acc, leftover)

    dmr._step = dying_step
    with pytest.raises(RuntimeError, match="simulated crash"):
        dmr.run(rows, checkpoint_dir=ckpt, checkpoint_every=1)
    dmr._step = real_step
    state = os.path.join(ckpt, f"state.p{jax.process_index()}.npz")
    prev = state + ".prev.npz"
    assert os.path.exists(state) and os.path.exists(prev)
    return dmr, rows, ckpt, state, prev, want


@needs8
def test_mesh_checkpoint_truncated_falls_back_to_prev(tmp_path, caplog):
    """A truncated current snapshot: resume falls back to the previous
    good generation — exact counts, no crash (ISSUE 1 tentpole)."""
    import logging

    dmr, rows, ckpt, state, prev, want = _mesh_fixture(tmp_path)
    data = open(state, "rb").read()
    open(state, "wb").write(data[: len(data) // 2])
    with caplog.at_level(logging.WARNING, logger="locust_tpu"):
        res = dmr.run(rows, checkpoint_dir=ckpt, checkpoint_every=1)
    assert dict(res.to_host_pairs()) == want
    assert any("unusable" in r.message for r in caplog.records)


@needs8
def test_mesh_checkpoint_both_generations_corrupt_fresh_start(tmp_path):
    """Current AND previous snapshots corrupt: clean fresh start, never
    wrong counts."""
    dmr, rows, ckpt, state, prev, want = _mesh_fixture(tmp_path)
    for path in (state, prev):
        data = bytearray(open(path, "rb").read())
        for i in range(0, len(data), 37):  # scribble everywhere
            data[i] ^= 0x5A
        open(path, "wb").write(bytes(data))
    res = dmr.run(rows, checkpoint_dir=ckpt, checkpoint_every=1)
    assert dict(res.to_host_pairs()) == want


@needs8
def test_mesh_checkpoint_bad_checksum_detected(tmp_path):
    """A snapshot whose arrays load fine but whose content digest does not
    match is rejected (bit-rot the zip layer cannot see)."""
    from locust_tpu.parallel.shuffle import (
        CheckpointInvalid,
        ShardedCheckpoint,
    )

    dmr, rows, ckpt, state, prev, want = _mesh_fixture(tmp_path)
    with np.load(state) as z:
        entries = {k: z[k] for k in z.files}
    entries["checksum"] = np.str_("0" * 64)  # wrong digest, valid archive
    np.savez_compressed(state + ".tmp.npz", **entries)
    os.replace(state + ".tmp.npz", state)
    sc = ShardedCheckpoint.__new__(ShardedCheckpoint)
    sc.fingerprint = str(entries["fingerprint"])
    sc.sharding = None  # _load_validated raises before scattering
    with pytest.raises(CheckpointInvalid, match="sha256 mismatch"):
        sc._load_validated(state)
    # end-to-end: the run falls back to prev and stays exact
    res = dmr.run(rows, checkpoint_dir=ckpt, checkpoint_every=1)
    assert dict(res.to_host_pairs()) == want


@needs8
def test_mesh_checkpoint_stale_fingerprint_prev_rescues(tmp_path):
    """Another run's snapshot occupies the current slot; the previous
    generation (ours) still resumes — fingerprints select, not crash."""
    from locust_tpu.parallel.mesh import make_mesh
    from locust_tpu.parallel.shuffle import DistributedMapReduce

    cfg = _mesh_cfg()
    mesh = make_mesh(8)
    ckpt = str(tmp_path / "shared")
    dmr = DistributedMapReduce(mesh, cfg)
    lines_a = [b"aaa bbb"] * 64
    rows_a = bytes_ops.strings_to_rows(lines_a, cfg.line_width)
    dmr.run(rows_a, checkpoint_dir=ckpt)  # run A's snapshot lands
    # run B fits ONE round (one snapshot): it rotates A's snapshot into
    # .prev exactly once and installs its own as current.
    lines_b = [b"ccc ddd"] * 32
    rows_b = bytes_ops.strings_to_rows(lines_b, cfg.line_width)
    res_b = dmr.run(rows_b, checkpoint_dir=ckpt)
    assert dict(res_b.to_host_pairs()) == {b"ccc": 32, b"ddd": 32}
    # run A again: current snapshot is B's (foreign fingerprint), prev is
    # A's fully-completed snapshot -> resumes it, zero steps, exact output
    res_a = dmr.run(rows_a, checkpoint_dir=ckpt)
    assert dict(res_a.to_host_pairs()) == {b"aaa": 64, b"bbb": 64}


@needs8
def test_chaos_checkpoint_fault_site_never_wrong_counts(tmp_path):
    """io.checkpoint faults damage EVERY snapshot as written: the run's
    output is unaffected (snapshots are durability, not correctness) and
    a resume survives the damaged files via fallback/fresh start."""
    from locust_tpu.parallel.mesh import make_mesh
    from locust_tpu.parallel.shuffle import DistributedMapReduce

    cfg = _mesh_cfg()
    lines = [b"alpha beta", b"beta gamma"] * 40
    rows = bytes_ops.strings_to_rows(lines, cfg.line_width)
    mesh = make_mesh(8)
    want = dict(DistributedMapReduce(mesh, cfg).run(rows).to_host_pairs())
    dmr = DistributedMapReduce(mesh, cfg)
    ckpt = str(tmp_path / "chaos_ckpt")
    p = plan([{"site": "io.checkpoint", "action": "truncate"}])
    with faultplan.active_plan(p):
        res = dmr.run(rows, checkpoint_dir=ckpt, checkpoint_every=1)
    assert dict(res.to_host_pairs()) == want
    assert p.rules[0].fired >= 1
    # resume over the damaged snapshots: falls back (possibly to fresh)
    res2 = dmr.run(rows, checkpoint_dir=ckpt, checkpoint_every=1)
    assert dict(res2.to_host_pairs()) == want


# ------------------------------------------- async checkpoint writer chaos
#
# The io.ckpt_write site fires between the fully-written tmp snapshot and
# its atomic rename — the one new failure point the background writer
# adds (io/snapshot.finalize_snapshot).  Contract: output byte-identical
# (a lost snapshot is lost durability, never lost correctness) or, on the
# synchronous path where the fold loop IS the writer, a structured error.


def _stream_engine(block_lines=4, **cfg_kw):
    from locust_tpu.engine import MapReduceEngine

    cfg = EngineConfig(
        block_lines=block_lines, line_width=64, emits_per_line=8, **cfg_kw
    )
    return MapReduceEngine(cfg), cfg


def _stream_corpus(tmp_path, reps=8):
    p = tmp_path / "stream_corpus.txt"
    if not p.exists():
        p.write_bytes(CORPUS * reps)
    return str(p)


def _stream_blocks(path, cfg):
    from locust_tpu.io.loader import StreamingCorpus

    return StreamingCorpus(path, cfg.line_width, cfg.block_lines)


def test_chaos_async_ckpt_writer_crash_before_rename(tmp_path):
    """An injected writer crash between tmp write and rename: the
    snapshot is abandoned (previous generation survives), the run's
    output is byte-identical, and a resume over the debris is exact."""
    eng, cfg = _stream_engine()
    path = _stream_corpus(tmp_path)
    want = dict(
        eng.run_stream(_stream_blocks(path, cfg)).to_host_pairs()
    )
    ck = str(tmp_path / "async_crash_ck")
    fp = _stream_blocks(path, cfg).fingerprint()
    p = plan([{"site": "io.ckpt_write", "action": "crash", "times": 1}])
    with faultplan.active_plan(p):
        res = eng.run_stream(
            _stream_blocks(path, cfg), checkpoint_dir=ck, every=1,
            fingerprint=fp,
        )
    assert dict(res.to_host_pairs()) == want
    assert p.rules[0].fired == 1
    assert res.stream["ckpt"]["mode"] == "async"
    assert res.stream["ckpt"]["abandoned"] == 1
    # Resume over whatever generation survived: exact, no re-fold drift.
    res2 = eng.run_stream(
        _stream_blocks(path, cfg), checkpoint_dir=ck, every=1, fingerprint=fp
    )
    assert dict(res2.to_host_pairs()) == want


def test_chaos_async_ckpt_delayed_writer_lapped_generation(tmp_path):
    """A slow writer (injected delay on every publish): the fold loop
    laps it, latest-wins skips intermediate generations, the final
    generation still lands at flush, and output/resume stay exact."""
    eng, cfg = _stream_engine()
    path = _stream_corpus(tmp_path)
    want = dict(
        eng.run_stream(_stream_blocks(path, cfg)).to_host_pairs()
    )
    ck = str(tmp_path / "async_delay_ck")
    fp = _stream_blocks(path, cfg).fingerprint()
    p = plan([{"site": "io.ckpt_write", "action": "delay",
               "delay_s": 0.25}])  # unlimited: every publish stalls
    with faultplan.active_plan(p):
        res = eng.run_stream(
            _stream_blocks(path, cfg), checkpoint_dir=ck, every=1,
            fingerprint=fp,
        )
    assert dict(res.to_host_pairs()) == want
    assert p.rules[0].fired >= 1
    cks = res.stream["ckpt"]
    assert cks["skipped"] >= 1, "the loop should have lapped the writer"
    assert cks["max_lag"] >= 2
    # The FINAL generation was flushed before return: a resume with an
    # exhausted iterator reports the restored (complete) counters.
    res2 = eng.run_stream(
        iter([]), checkpoint_dir=ck, every=1, fingerprint=fp
    )
    assert dict(res2.to_host_pairs()) == want
    assert res2.num_segments == res.num_segments


def test_chaos_sync_ckpt_write_crash_structured_error(tmp_path):
    """Synchronous mode (cfg.async_checkpoint=False): the fold loop IS
    the writer, so an injected crash at the publish point surfaces as a
    structured FaultInjected error — the 'or error' arm — and a later
    clean run resumes exactly from the surviving generation."""
    eng, cfg = _stream_engine(async_checkpoint=False)
    path = _stream_corpus(tmp_path)
    want = dict(
        eng.run_stream(_stream_blocks(path, cfg)).to_host_pairs()
    )
    ck = str(tmp_path / "sync_crash_ck")
    fp = _stream_blocks(path, cfg).fingerprint()
    p = plan([{"site": "io.ckpt_write", "action": "crash", "times": 1}])
    with faultplan.active_plan(p):
        with pytest.raises(faultplan.FaultInjected):
            eng.run_stream(
                _stream_blocks(path, cfg), checkpoint_dir=ck, every=1,
                fingerprint=fp,
            )
    assert p.rules[0].fired == 1
    res = eng.run_stream(
        _stream_blocks(path, cfg), checkpoint_dir=ck, every=1, fingerprint=fp
    )
    assert dict(res.to_host_pairs()) == want


def test_chaos_engine_stream_checkpoint_damage_clean_restart(tmp_path):
    """io.checkpoint damage on EVERY published engine snapshot (fired on
    the background writer thread): the streaming run's output is
    unaffected and a resume over the damaged state costs a clean fresh
    start, never wrong counts."""
    eng, cfg = _stream_engine()
    path = _stream_corpus(tmp_path)
    want = dict(
        eng.run_stream(_stream_blocks(path, cfg)).to_host_pairs()
    )
    ck = str(tmp_path / "damage_ck")
    fp = _stream_blocks(path, cfg).fingerprint()
    p = plan([{"site": "io.checkpoint", "action": "truncate"}])
    with faultplan.active_plan(p):
        res = eng.run_stream(
            _stream_blocks(path, cfg), checkpoint_dir=ck, every=1,
            fingerprint=fp,
        )
    assert dict(res.to_host_pairs()) == want
    assert p.rules[0].fired >= 1
    res2 = eng.run_stream(
        _stream_blocks(path, cfg), checkpoint_dir=ck, every=1, fingerprint=fp
    )
    assert dict(res2.to_host_pairs()) == want


def test_engine_checkpoint_truncated_clean_restart(tmp_path):
    """Single-device engine: a truncated state.npz costs a clean restart
    with exact counts — never a crash, never wrong counts (satellite)."""
    from locust_tpu.engine import MapReduceEngine

    cfg = EngineConfig(block_lines=4, line_width=64, emits_per_line=8)
    eng = MapReduceEngine(cfg)
    ckpt = str(tmp_path / "eckpt")
    rows = bytes_ops.strings_to_rows([b"aaa bbb ccc"] * 32, cfg.line_width)
    eng.run_checkpointed(rows, ckpt, every=2)
    state = os.path.join(ckpt, "state.npz")
    data = open(state, "rb").read()
    open(state, "wb").write(data[: len(data) // 3])
    res = eng.run_checkpointed(rows, ckpt, every=2)
    assert dict(res.to_host_pairs()) == {b"aaa": 32, b"bbb": 32, b"ccc": 32}


# ---------------------------------------------------------------- serve tier
#
# The serving-layer guarantee (docs/SERVING.md): under injected faults at
# the serve.admit / serve.dispatch sites, a client observes either a
# CORRECT result or a STRUCTURED error (jobs.ERROR_CODES reason code) —
# never a silent wrong answer, never a dead daemon.

SERVE_CFG = {
    "block_lines": 8, "line_width": 64, "key_width": 16,
    "emits_per_line": 8,
}
SERVE_CORPUS = CORPUS * 3


def _serve_rig():
    from locust_tpu.serve import ServeClient, ServeConfig, ServeDaemon

    daemon = ServeDaemon(
        secret=SECRET,
        cfg=ServeConfig(max_queue=8, max_batch=2, dispatch_poll_s=0.02),
    )
    daemon.serve_in_thread()
    return daemon, ServeClient(daemon.addr, SECRET, timeout=30.0)


def _serve_oracle():
    return dict(py_wordcount(SERVE_CORPUS.splitlines(),
                             max_tokens_per_line=8, key_width=16))


def test_chaos_serve_admit_error_structured_rejection(tmp_path):
    """serve.admit error: the submit is REJECTED with the structured
    fault_injected code; the daemon survives and the next submit runs
    to an exact result."""
    from locust_tpu.serve import ServeError

    daemon, client = _serve_rig()
    try:
        p = plan([{"site": "serve.admit", "action": "error", "times": 1}])
        with faultplan.active_plan(p):
            with pytest.raises(ServeError) as e:
                client.submit(corpus=SERVE_CORPUS, config=SERVE_CFG)
            assert e.value.code == "fault_injected"
            assert p.rules[0].fired == 1
            # Retry INSIDE the plan: the one-shot rule is spent, the
            # daemon is healthy, the result is exact.
            ack = client.submit(corpus=SERVE_CORPUS, config=SERVE_CFG)
            res = client.wait(ack["job_id"], timeout=60.0)
        assert dict(res["pairs"]) == _serve_oracle()
    finally:
        daemon.close()


def test_chaos_serve_dispatch_crash_retries_to_exact_result(tmp_path):
    """serve.dispatch crash, transient (times: 1): the retry ladder
    (docs/SERVING.md) re-dispatches with backoff and the SAME submit
    still lands the exact result — the client never has to know the
    first dispatch died.  The attempt count is visible in status."""
    daemon, client = _serve_rig()
    try:
        p = plan([{"site": "serve.dispatch", "action": "crash", "times": 1}])
        with faultplan.active_plan(p):
            ack = client.submit(
                corpus=SERVE_CORPUS, config=SERVE_CFG, no_cache=True
            )
            res = client.wait(ack["job_id"], timeout=60.0)
        assert dict(res["pairs"]) == _serve_oracle()
        assert p.rules[0].fired == 1
        st = client.status(ack["job_id"])
        assert st["state"] == "done" and st["attempts"] >= 1
    finally:
        daemon.close()


def test_chaos_serve_dispatch_crash_exhausted_budget_structured(tmp_path):
    """serve.dispatch crash, persistent: a job whose max_attempts budget
    is 1 gets NO retry — the failure is immediately the structured
    fault-injected error (never a silent wrong answer), the dispatcher
    survives, and a resubmission runs exact."""
    from locust_tpu.serve import ServeError

    daemon, client = _serve_rig()
    try:
        p = plan([{"site": "serve.dispatch", "action": "crash", "times": 1}])
        with faultplan.active_plan(p):
            ack = client.submit(
                corpus=SERVE_CORPUS, config=SERVE_CFG, no_cache=True,
                max_attempts=1,
            )
            with pytest.raises(ServeError) as e:
                client.wait(ack["job_id"], timeout=60.0)
            assert e.value.code == "poison_job"
            assert client.status(ack["job_id"])["state"] == "failed"
            assert p.rules[0].fired == 1
            ack2 = client.submit(
                corpus=SERVE_CORPUS, config=SERVE_CFG, no_cache=True
            )
            res = client.wait(ack2["job_id"], timeout=60.0)
        assert dict(res["pairs"]) == _serve_oracle()
    finally:
        daemon.close()


def test_chaos_serve_dispatch_delay_straggler_still_exact(tmp_path):
    """serve.dispatch delay (the straggling-dispatch model): the job is
    late but the result stays exact and complete."""
    daemon, client = _serve_rig()
    try:
        p = plan([{"site": "serve.dispatch", "action": "delay",
                   "delay_s": 0.4, "times": 1}])
        with faultplan.active_plan(p):
            t0 = time.monotonic()
            ack = client.submit(
                corpus=SERVE_CORPUS, config=SERVE_CFG, no_cache=True
            )
            res = client.wait(ack["job_id"], timeout=60.0)
            elapsed = time.monotonic() - t0
        assert dict(res["pairs"]) == _serve_oracle()
        assert p.rules[0].fired == 1
        assert elapsed >= 0.4  # the straggle actually happened
    finally:
        daemon.close()


def test_chaos_serve_warm_state_writer_crash_durability_only(tmp_path):
    """io.ckpt_write crash on the serve warm-state writer: the snapshot
    is abandoned (previous generation survives), results stay exact, and
    a restart simply cold-starts the result cache — durability lost for
    one cadence, correctness untouched."""
    from locust_tpu.serve import ServeClient, ServeConfig, ServeDaemon

    warm_dir = str(tmp_path / "serve_warm")
    daemon = ServeDaemon(
        secret=SECRET,
        cfg=ServeConfig(max_queue=8, max_batch=2, warm_dir=warm_dir,
                        warm_every=1, dispatch_poll_s=0.02),
    )
    daemon.serve_in_thread()
    client = ServeClient(daemon.addr, SECRET, timeout=30.0)
    p = plan([{"site": "io.ckpt_write", "action": "crash"}])  # every publish
    try:
        with faultplan.active_plan(p):
            ack = client.submit(corpus=SERVE_CORPUS, config=SERVE_CFG)
            res = client.wait(ack["job_id"], timeout=60.0)
            assert dict(res["pairs"]) == _serve_oracle()
            daemon.close()  # final mark also dies on the injected crash
        assert p.rules[0].fired >= 1
    finally:
        daemon.close()
    d2 = ServeDaemon(
        secret=SECRET,
        cfg=ServeConfig(warm_dir=warm_dir, dispatch_poll_s=0.02),
    )
    d2.serve_in_thread()
    c2 = ServeClient(d2.addr, SECRET, timeout=30.0)
    try:
        ack = c2.submit(corpus=SERVE_CORPUS, config=SERVE_CFG)
        assert ack["cached"] is False  # cold start: no warm file landed
        res = c2.wait(ack["job_id"], timeout=60.0)
        assert dict(res["pairs"]) == _serve_oracle()
    finally:
        d2.close()


# --------------------------------------------- durability tier (ISSUE 10)
#
# serve.journal faults hit the write-ahead append that makes the accept
# ack a durable promise; backend.dispatch faults model a device that
# initialized and then fails a dispatch.  Contract: a journal fault is a structured rejection or a
# replay that skips only the damaged record; a dispatch fault trips the
# circuit breaker and the job finishes on the CPU fallback from its last
# checkpoint, oracle-exact.


def _journal_rig(tmp_path, **cfg_kw):
    from locust_tpu.serve import ServeClient, ServeConfig, ServeDaemon

    cfg = ServeConfig(
        max_queue=8, max_batch=2, dispatch_poll_s=0.02,
        journal_dir=str(tmp_path / "journal"), retry_base_s=0.02,
        **cfg_kw,
    )
    daemon = ServeDaemon(secret=SECRET, cfg=cfg)
    daemon.serve_in_thread()
    return daemon, ServeClient(daemon.addr, SECRET, timeout=30.0)


_abandon = serve_abandon


def test_chaos_serve_journal_crash_rejects_structured_then_replays(tmp_path):
    """serve.journal crash: the append dies mid-record (a TORN line lands
    on disk), the submit is rejected STRUCTURED — never acked, so no
    durability promise was broken — the daemon survives, a retry runs
    exact, and a restart replays over the torn record without crashing."""
    from locust_tpu.serve import ServeClient, ServeConfig, ServeDaemon
    from locust_tpu.serve import ServeError

    daemon, client = _journal_rig(tmp_path)
    abandoned = False
    try:
        p = plan([{"site": "serve.journal", "action": "crash", "times": 1}])
        with faultplan.active_plan(p):
            with pytest.raises(ServeError) as e:
                client.submit(
                    corpus=SERVE_CORPUS, config=SERVE_CFG, no_cache=True
                )
            assert e.value.code == "fault_injected"
            assert p.rules[0].fired == 1
            ack = client.submit(
                corpus=SERVE_CORPUS, config=SERVE_CFG, no_cache=True
            )
            res = client.wait(ack["job_id"], timeout=60.0)
        assert dict(res["pairs"]) == _serve_oracle()
        _abandon(daemon)
        abandoned = True
    finally:
        if not abandoned:
            daemon.close()
    # Restart over the journal that holds the torn record: replay must
    # skip it and come up clean.
    d2 = ServeDaemon(
        secret=SECRET,
        cfg=ServeConfig(journal_dir=str(tmp_path / "journal"),
                        dispatch_poll_s=0.02),
    )
    d2.serve_in_thread()
    c2 = ServeClient(d2.addr, SECRET, timeout=30.0)
    try:
        ack = c2.submit(corpus=SERVE_CORPUS, config=SERVE_CFG)
        res = c2.wait(ack["job_id"], timeout=60.0)
        assert dict(res["pairs"]) == _serve_oracle()
    finally:
        d2.close()


def test_chaos_serve_journal_corrupt_replay_skips_only_bad_record(tmp_path):
    """serve.journal corrupt: ONE admit record rots silently on disk.
    The ack still lands (corruption is not detectable at write time);
    after a simulated kill -9 the restart's replay skips the damaged
    record with a warning and recovers every OTHER journaled job — the
    chaos matrix's never-a-crash, never-a-silent-wrong-answer stance."""
    from locust_tpu.serve import ServeClient, ServeConfig, ServeDaemon

    daemon, client = _journal_rig(tmp_path)
    abandoned = False
    try:
        daemon.scheduler.pause()  # keep both jobs queued = unfinished
        p = plan([{"site": "serve.journal", "action": "corrupt",
                   "times": 1}])
        with faultplan.active_plan(p):
            doomed = client.submit(
                corpus=SERVE_CORPUS, config=SERVE_CFG, no_cache=True
            )["job_id"]
        survivor = client.submit(
            corpus=CORPUS * 2, config=SERVE_CFG, no_cache=True
        )["job_id"]
        assert p.rules[0].fired == 1
        _abandon(daemon)
        abandoned = True
    finally:
        if not abandoned:
            daemon.close()
    d2 = ServeDaemon(
        secret=SECRET,
        cfg=ServeConfig(journal_dir=str(tmp_path / "journal"),
                        dispatch_poll_s=0.02),
    )
    d2.serve_in_thread()
    c2 = ServeClient(d2.addr, SECRET, timeout=30.0)
    try:
        # The survivor replays to an exact result under its ORIGINAL id.
        res = c2.wait(survivor, timeout=60.0)
        want = dict(py_wordcount((CORPUS * 2).splitlines(),
                                 max_tokens_per_line=8, key_width=16))
        assert dict(res["pairs"]) == want
        # The corrupt record's job answers STRUCTURED — which flavor
        # depends on which byte rotted (an unparseable/unusable record
        # is dropped -> unknown_job; a parseable record whose corpus sha
        # rotted replays as a failed job with a structured error; a
        # record whose damage is semantically harmless replays to the
        # exact result) — but never a silent wrong answer or a crash.
        from locust_tpu.serve import ServeError

        try:
            st = c2.status(doomed)
            if st["state"] == "done":
                res = c2.result(doomed)
                assert dict(res["pairs"]) == _serve_oracle()
            else:
                assert st["state"] in ("failed", "queued", "running")
                if st["state"] == "failed":
                    assert st["error"]["code"] in (
                        "dispatch_failed", "deadline_exceeded"
                    )
        except ServeError as e:
            assert e.code == "unknown_job"
    finally:
        d2.close()


def test_chaos_backend_dispatch_breaker_trips_failover_exact(tmp_path):
    """backend.dispatch errors on consecutive primary dispatches: the
    circuit breaker trips, the checkpointed run RELOADS its last durable
    snapshot and finishes on the CPU fallback device, oracle-exact —
    and the whole ladder (trip, failover, half-open probe) lands on the
    trace timeline."""
    from locust_tpu import obs
    from locust_tpu.backend import CircuitBreaker
    from locust_tpu.engine import MapReduceEngine

    cfg = EngineConfig(block_lines=4, line_width=64, emits_per_line=8)
    eng = MapReduceEngine(cfg)
    lines = [b"aaa bbb ccc", b"bbb ccc ddd"] * 64  # 32 blocks
    rows = bytes_ops.strings_to_rows(lines, cfg.line_width)
    want = dict(eng.run(rows).to_host_pairs())
    br = CircuitBreaker(threshold=2, cooldown_s=0.05)
    ckpt = str(tmp_path / "breaker_ck")
    p = plan([{"site": "backend.dispatch", "action": "error", "times": 3}])
    obs.enable(process="breaker-test")
    try:
        with faultplan.active_plan(p):
            res = eng.run_checkpointed(rows, ckpt, every=2, breaker=br)
        doc = obs.export(str(tmp_path / "breaker.trace.json"))
    finally:
        obs.disable()
    assert dict(res.to_host_pairs()) == want  # oracle-exact through failover
    st = br.stats()
    assert st["trips"] == 1 and st["failures"] == 3
    names = {e["name"] for e in doc["traceEvents"]}
    assert "backend.breaker_open" in names
    assert "backend.failover" in names
    # The plan is exhausted, so the first half-open probe after the
    # cooldown succeeds — in-run when the fold lasted past the cooldown,
    # otherwise driven here; either way the primary is restored.
    if br.state() != "closed":
        time.sleep(0.06)
        assert br.allow() is True  # half-open: TPU eligibility restored
        br.record_success()
    assert br.state() == "closed"


def test_chaos_backend_dispatch_delay_absorbed(tmp_path):
    """backend.dispatch delay (slow device): the run is late but exact,
    and a slow dispatch alone never trips the breaker."""
    from locust_tpu.backend import CircuitBreaker
    from locust_tpu.engine import MapReduceEngine

    cfg = EngineConfig(block_lines=4, line_width=64, emits_per_line=8)
    eng = MapReduceEngine(cfg)
    rows = bytes_ops.strings_to_rows([b"aaa bbb"] * 16, cfg.line_width)
    want = dict(eng.run(rows).to_host_pairs())
    br = CircuitBreaker(threshold=2, cooldown_s=5.0)
    p = plan([{"site": "backend.dispatch", "action": "delay",
               "delay_s": 0.2, "times": 1}])
    t0 = time.monotonic()
    with faultplan.active_plan(p):
        res = eng.run_checkpointed(
            rows, str(tmp_path / "delay_ck"), every=2, breaker=br
        )
    assert dict(res.to_host_pairs()) == want
    assert time.monotonic() - t0 >= 0.2
    assert p.rules[0].fired == 1
    assert br.state() == "closed" and br.stats()["trips"] == 0


# ------------------------------------------- scale-out serve pool (ISSUE 11)
#
# With a worker pool beneath the dispatcher (serve/pool.py), the same
# guarantee must hold: a placement failure (serve.place), an injected
# dispatch kill on one worker (serve.dispatch with worker ctx), or a
# REAL worker death mid-serve-batch all end in a byte-identical result
# (local floor / surviving worker via the retry ladder) or a structured
# error — never a silent wrong answer, never a dead daemon.


def _serve_pool_rig(n_workers=2, **cfg_kw):
    from locust_tpu.serve import ServeClient, ServeConfig, ServeDaemon

    workers = []
    for _ in range(n_workers):
        w = Worker(secret=SECRET, serve=True)
        w.serve_in_thread()
        workers.append(w)
    cfg = ServeConfig(
        max_queue=8, max_batch=2, dispatch_poll_s=0.02, retry_base_s=0.02,
        workers=tuple(f"127.0.0.1:{w.addr[1]}" for w in workers),
        **cfg_kw,
    )
    daemon = ServeDaemon(secret=SECRET, cfg=cfg)
    daemon.serve_in_thread()
    return daemon, workers, ServeClient(daemon.addr, SECRET, timeout=30.0)


def test_chaos_serve_place_error_falls_back_to_local_exact():
    """serve.place error: the placement decision fails, the batch runs
    on the daemon's LOCAL engine instead — the result is byte-identical
    to a pool placement (the floor is a full engine, not a degraded
    one), and the pool keeps serving afterwards."""
    daemon, workers, client = _serve_pool_rig()
    try:
        p = plan([{"site": "serve.place", "action": "error", "times": 1}])
        with faultplan.active_plan(p):
            ack = client.submit(
                corpus=SERVE_CORPUS, config=SERVE_CFG, no_cache=True
            )
            res = client.wait(ack["job_id"], timeout=60.0)
        assert dict(res["pairs"]) == _serve_oracle()
        assert p.rules[0].fired == 1
        st = client.status(ack["job_id"])
        assert st["placed_on"] == "local"
        assert client.stats()["pool"]["local_fallbacks"] >= 1
        # The spent rule leaves the pool healthy: the next job places.
        ack2 = client.submit(
            corpus=SERVE_CORPUS + b"extra tail line\n", config=SERVE_CFG,
            no_cache=True,
        )
        res2 = client.wait(ack2["job_id"], timeout=60.0)
        assert client.status(ack2["job_id"])["placed_on"] != "local"
        assert res2["state"] == "done"
    finally:
        daemon.close()
        for w in workers:
            _shutdown(w)


def test_chaos_serve_place_delay_only_slows_placement():
    """serve.place delay: a slow placement decision delays the dispatch,
    nothing else changes — the result stays exact."""
    daemon, workers, client = _serve_pool_rig()
    try:
        p = plan([{"site": "serve.place", "action": "delay",
                   "delay_s": 0.3, "times": 1}])
        with faultplan.active_plan(p):
            t0 = time.monotonic()
            ack = client.submit(
                corpus=SERVE_CORPUS, config=SERVE_CFG, no_cache=True
            )
            res = client.wait(ack["job_id"], timeout=60.0)
            assert time.monotonic() - t0 >= 0.3
        assert dict(res["pairs"]) == _serve_oracle()
        assert p.rules[0].fired == 1
    finally:
        daemon.close()
        for w in workers:
            _shutdown(w)


def test_chaos_serve_dispatch_worker_kill_retries_exact():
    """serve.dispatch with worker ctx: a plan targeting ONE worker's
    dispatches models that worker dying mid-serve-batch.  The retry
    ladder re-places the batch (rule spent / other worker / local
    floor) and the SAME submit still lands the exact result."""
    daemon, workers, client = _serve_pool_rig()
    try:
        name = f"127.0.0.1:{workers[0].addr[1]}"
        p = plan([{"site": "serve.dispatch", "action": "crash",
                   "match": {"worker": name}, "times": 1}])
        with faultplan.active_plan(p):
            ack = client.submit(
                corpus=SERVE_CORPUS, config=SERVE_CFG, no_cache=True
            )
            res = client.wait(ack["job_id"], timeout=60.0)
        assert dict(res["pairs"]) == _serve_oracle()
        assert p.rules[0].fired == 1
        st = client.status(ack["job_id"])
        assert st["state"] == "done" and st["attempts"] >= 1
    finally:
        daemon.close()
        for w in workers:
            _shutdown(w)


def test_chaos_serve_pool_worker_death_mid_batch_recovers_exact():
    """REAL worker death mid-serve-batch: the worker is held inside the
    dispatch by an rpc.delay rule while its connection is cut and its
    accept loop shut down — the daemon sees the peer die mid-frame,
    quarantines it (WorkerHealth backoff), and the retry lands the
    byte-identical result on the survivor or the local floor."""
    daemon, workers, client = _serve_pool_rig()
    try:
        victim = daemon.pool.workers[0]
        p = plan([{"site": "rpc.delay", "action": "delay", "delay_s": 1.0,
                   "match": {"cmd": "serve_batch"}, "times": 1}])
        with faultplan.active_plan(p):
            ack = client.submit(
                corpus=SERVE_CORPUS, config=SERVE_CFG, no_cache=True
            )
            # Wait until the dispatch RPC is IN FLIGHT on the victim
            # (the rpc.delay rule holds the worker for 1s and the RPC
            # holds the connection lock for its duration), then kill it
            # for real: accept loop down + the established socket cut
            # mid-frame.  The socket is closed WITHOUT taking the lock —
            # the inflight RPC owns it, and close() is exactly what cuts
            # its pending recv (taking the lock would mean politely
            # waiting for the dispatch we are trying to kill).
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline:
                if victim._conn_lock.locked():
                    break
                time.sleep(0.02)
            assert victim._conn_lock.locked(), "dispatch never reached the victim"
            workers[0]._shutdown.set()
            workers[0]._sock.close()
            conn = victim._conn
            if conn is not None:
                conn.close()
            res = client.wait(ack["job_id"], timeout=60.0)
        assert dict(res["pairs"]) == _serve_oracle()
        st = client.status(ack["job_id"])
        assert st["state"] == "done" and st["attempts"] >= 1
        pool_stats = client.stats()["pool"]
        assert pool_stats["dispatch_failures"] >= 1
        # The survivor (or the local floor) answered: never the victim.
        assert st["placed_on"] != victim.name
    finally:
        daemon.close()
        for w in workers[1:]:
            _shutdown(w)


# ------------------------------------ distributed plan execution (ISSUE 16)
#
# Plan jobs fan map/reduce stages across the pool with a cross-worker
# shuffle (plan/distribute.py; docs/PLAN.md "Distributed execution").
# The same guarantee, STAGE-granular: an injected stage failure, a real
# worker crash mid-stage-RPC, a shuffle partition lost or corrupted
# between the waves, and a fenced zombie's stage publish all end
# byte-identical (stage recompute on a survivor / solo floor) or
# structured — never a silent wrong answer, never a full-plan restart.


def _dplan_rig(**cfg_kw):
    return _serve_pool_rig(shard_min_blocks=1, **cfg_kw)


def _dplan_oracle() -> bytes:
    from locust_tpu.config import EngineConfig
    from locust_tpu.plan import tfidf_plan
    from locust_tpu.plan.compile import compile_plan

    return compile_plan(
        tfidf_plan(2), EngineConfig(**SERVE_CFG)
    ).run_corpus(SERVE_CORPUS).output


def _dplan_submit(client, timeout=60.0):
    from locust_tpu.plan import tfidf_plan

    ack = client.submit(corpus=SERVE_CORPUS, config=SERVE_CFG,
                        plan=tfidf_plan(2).to_doc(), no_cache=True)
    return ack, client.wait(ack["job_id"], timeout=timeout)


def test_chaos_plan_stage_error_recomputes_on_survivor_exact():
    """plan.stage error: one injected stage failure mid-plan — the
    coordinator recomputes that stage on a survivor (never restarts the
    plan) and the distributed result stays byte-identical to solo."""
    daemon, workers, client = _dplan_rig()
    try:
        p = plan([{"site": "plan.stage", "action": "error",
                   "match": {"phase": "map"}, "times": 1}])
        with faultplan.active_plan(p):
            ack, res = _dplan_submit(client)
        assert res["pairs"][0][0] == _dplan_oracle()
        assert p.rules[0].fired == 1
        st = client.status(ack["job_id"])
        assert st["state"] == "done"
        assert st["placed_on"].startswith("plan:")
        assert client.stats()["pool"]["plan"]["recomputes"] >= 1
    finally:
        daemon.close()
        for w in workers:
            _shutdown(w)


def test_chaos_plan_stage_worker_crash_mid_stage_recovers_exact():
    """plan.stage crash scoped to ONE worker's port: that worker's
    connection drops mid-stage-RPC with no reply (the SIGKILL model) —
    the coordinator marks it dead for this plan, recomputes the stage
    on the survivor, and the result stays exact."""
    daemon, workers, client = _dplan_rig()
    try:
        p = plan([{"site": "plan.stage", "action": "crash",
                   "match": {"port": workers[0].addr[1]}, "times": 1}])
        with faultplan.active_plan(p):
            ack, res = _dplan_submit(client)
        assert res["pairs"][0][0] == _dplan_oracle()
        assert p.rules[0].fired == 1
        st = client.status(ack["job_id"])
        assert st["state"] == "done"
        assert client.stats()["pool"]["plan"]["recomputes"] >= 1
    finally:
        daemon.close()
        for w in workers:
            _shutdown(w)


def test_chaos_plan_partition_drop_recomputes_split_exact():
    """plan.partition drop: a shuffle partition file vanishes between
    the map and reduce waves (spill GC race / disk loss).  The reduce
    worker's read fails naming the lost_split, the coordinator
    recomputes exactly that map split from the durable corpus spill —
    a recompute, never a wrong answer."""
    daemon, workers, client = _dplan_rig()
    try:
        p = plan([{"site": "plan.partition", "action": "drop",
                   "times": 1}])
        with faultplan.active_plan(p):
            ack, res = _dplan_submit(client)
        assert res["pairs"][0][0] == _dplan_oracle()
        assert p.rules[0].fired == 1
        assert client.status(ack["job_id"])["state"] == "done"
        assert client.stats()["pool"]["plan"]["recomputes"] >= 1
    finally:
        daemon.close()
        for w in workers:
            _shutdown(w)


def test_chaos_plan_partition_corrupt_detected_and_recomputed_exact():
    """plan.partition corrupt: flipped bytes in a published partition
    are caught by the sha256 gate on read (a torn file can never fold)
    — same lost_split recovery, byte-identical result."""
    daemon, workers, client = _dplan_rig()
    try:
        p = plan([{"site": "plan.partition", "action": "corrupt",
                   "times": 1}])
        with faultplan.active_plan(p):
            ack, res = _dplan_submit(client)
        assert res["pairs"][0][0] == _dplan_oracle()
        assert p.rules[0].fired == 1
        assert client.stats()["pool"]["plan"]["recomputes"] >= 1
    finally:
        daemon.close()
        for w in workers:
            _shutdown(w)


def test_chaos_plan_stage_stale_epoch_publish_fenced():
    """Zombie stage publish: every pool worker has served a NEWER
    primary (their fencing guards sit above this daemon's epoch), so
    the zombie coordinator's first stage RPC answers structured
    stale_epoch — no stale partition is accepted — and the daemon
    demotes itself to standby instead of split-braining."""
    from locust_tpu.plan import tfidf_plan
    from locust_tpu.serve import ServeError

    daemon, workers, client = _dplan_rig()
    try:
        for w in workers:
            w._epoch_guard.observe(daemon.epoch + 7)
        ack = client.submit(corpus=SERVE_CORPUS, config=SERVE_CFG,
                            plan=tfidf_plan(2).to_doc(), no_cache=True,
                            max_attempts=1)
        with pytest.raises(ServeError):
            client.wait(ack["job_id"], timeout=60.0)
        assert daemon.role == "standby"
        assert daemon._seen_epoch >= daemon.epoch + 7
    finally:
        daemon.close()
        for w in workers:
            _shutdown(w)


def test_chaos_serve_journal_plan_job_replays_byte_identical(tmp_path):
    """Chaos-matrix row for PLAN jobs (docs/PLAN.md): an admitted plan
    job — the WAL admit record carries the whole plan document — is
    SIGKILL'd mid-dispatch (serve.dispatch delay holds it in flight)
    and must replay byte-identically under its ORIGINAL id after a
    restart on the same journal, exactly like a named-workload job."""
    from locust_tpu.plan import tfidf_plan
    from locust_tpu.plan.compile import compile_plan
    from locust_tpu.config import EngineConfig
    from locust_tpu.serve import ServeClient, ServeConfig, ServeDaemon

    daemon, client = _journal_rig(tmp_path)
    abandoned = False
    plan_doc = tfidf_plan(2).to_doc()
    try:
        p = plan([{"site": "serve.dispatch", "action": "delay",
                   "delay_s": 30.0, "times": 1}])
        with faultplan.active_plan(p):
            ack = client.submit(
                corpus=SERVE_CORPUS, config=SERVE_CFG,
                plan=plan_doc, no_cache=True,
            )
            _abandon(daemon)
            abandoned = True
    finally:
        if not abandoned:
            daemon.close()
    d2 = ServeDaemon(
        secret=SECRET,
        cfg=ServeConfig(journal_dir=str(tmp_path / "journal"),
                        dispatch_poll_s=0.02),
    )
    d2.serve_in_thread()
    c2 = ServeClient(d2.addr, SECRET, timeout=30.0)
    try:
        res = c2.wait(ack["job_id"], timeout=120.0)
        assert res["plan"] is True
        oracle = compile_plan(
            tfidf_plan(2), EngineConfig(**SERVE_CFG)
        ).run_corpus(SERVE_CORPUS).output
        assert res["pairs"][0][0] == oracle
    finally:
        d2.close()


# --------------------------------------- HA replication tier (ISSUE 14)
#
# serve.ship faults hit the primary->standby WAL shipping stream
# (serve/replicate.py; docs/SERVING.md "High availability").  Contract:
# shipping is ASYNC off the admit path, so every injected fault leaves
# the primary's answers byte-identical — the standby either converges
# (drop -> gap -> snapshot catch-up; corrupt -> checksum reject ->
# resync, the damaged records are NEVER applied) or honestly reports
# lag (delay).  Fencing: an old epoch's ship attempts and worker RPCs
# are rejected with the structured stale_epoch code, and a promote on a
# daemon that is already primary is refused — no double-answering
# split brain, ever.


def _ha_chaos_pair(tmp_path, standby_kw=None, primary_kw=None):
    from locust_tpu.serve import ServeConfig, ServeDaemon

    standby = ServeDaemon(secret=SECRET, cfg=ServeConfig(
        journal_dir=str(tmp_path / "standby-journal"),
        standby_of="127.0.0.1:9", dispatch_poll_s=0.02,
        **(standby_kw or {}),
    ))
    standby.serve_in_thread()
    primary = ServeDaemon(secret=SECRET, cfg=ServeConfig(
        journal_dir=str(tmp_path / "primary-journal"),
        ship_to=f"{standby.addr[0]}:{standby.addr[1]}",
        dispatch_poll_s=0.02, ship_heartbeat_s=0.2, retry_base_s=0.02,
        **(primary_kw or {}),
    ))
    primary.serve_in_thread()
    return primary, standby


def _ship_converged(primary, standby, min_seq, timeout=20.0):
    """Replication caught up: every enqueued record acked, and the
    standby's sequence high-water mark reached ``min_seq`` (a catch-up
    of an already-terminal job legitimately applies zero records, so
    the mark — not a record count — is the convergence signal)."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        ps = primary.shipper.stats()
        ss = standby.receiver.stats()
        if ps["acked_seq"] >= ps["shipped_seq"] and \
                ss["applied_seq"] >= min_seq and \
                ss["missing_spills"] == 0:
            return True
        time.sleep(0.05)
    return False


def test_chaos_serve_ship_drop_gap_converges_via_catchup(tmp_path):
    """serve.ship drop: a ship batch vanishes in flight.  The primary's
    answer is untouched (async shipping), the standby detects the
    sequence gap, and the snapshot catch-up converges — dropped
    replication costs a resync, never divergence."""
    from locust_tpu.serve import ServeClient

    primary, standby = _ha_chaos_pair(tmp_path)
    try:
        p = plan([{"site": "serve.ship", "action": "drop",
                   "match": {"cmd": "ship"}, "times": 1}])
        with faultplan.active_plan(p):
            client = ServeClient(primary.addr, SECRET, timeout=30.0)
            ack = client.submit(corpus=SERVE_CORPUS, config=SERVE_CFG,
                                no_cache=True)
            res = client.wait(ack["job_id"], timeout=60.0)
            assert dict(res["pairs"]) == _serve_oracle()  # primary exact
            assert _ship_converged(primary, standby, 1)
        assert p.rules[0].fired == 1
        assert standby.receiver.stats()["resyncs_answered"] >= 1
    finally:
        primary.close()
        standby.close()


def test_chaos_serve_ship_corrupt_never_applied_then_converges(tmp_path):
    """serve.ship corrupt: the shipped records rot between the journal
    and the frame (inside the HMAC boundary).  The standby's checksum
    rejects the batch — a corrupt record is NEVER applied — and the
    primary re-syncs through a snapshot; the standby's replayable state
    ends exactly equal to the primary's live set."""
    from locust_tpu.serve import ServeClient

    primary, standby = _ha_chaos_pair(tmp_path)
    try:
        primary.scheduler.pause()  # keep the job LIVE on both sides
        p = plan([{"site": "serve.ship", "action": "corrupt",
                   "match": {"cmd": "ship"}, "times": 1}])
        with faultplan.active_plan(p):
            client = ServeClient(primary.addr, SECRET, timeout=30.0)
            jid = client.submit(corpus=SERVE_CORPUS, config=SERVE_CFG,
                                no_cache=True)["job_id"]
            assert _ship_converged(primary, standby, 1)
        assert p.rules[0].fired == 1
        assert standby.receiver.stats()["resyncs_answered"] >= 1
        # Converged state is the primary's: same live job, same spill.
        live = standby.journal.live_records()
        assert [r["job_id"] for r in live] == [jid]
        assert standby.journal.spill_exists(live[0]["corpus_sha"])
    finally:
        primary.close()
        standby.close()


def test_chaos_serve_ship_spill_drop_retried_until_standby_has_it(tmp_path):
    """serve.ship drop on the SPILL path (cmd="spill"): the corpus
    bytes vanish in flight.  Regression (PR 18, found by R018 — the
    spill leg was the one chaos-blind hop on the data plane): a dropped
    spill must raise into the shipper's retry ladder, and the standby
    re-asks for the sha until it actually holds the bytes — never a
    silent "sent" for bytes that never arrived."""
    from locust_tpu.serve import ServeClient

    primary, standby = _ha_chaos_pair(tmp_path)
    try:
        primary.scheduler.pause()  # keep the job LIVE: its spill must ship
        p = plan([{"site": "serve.ship", "action": "drop",
                   "match": {"cmd": "spill"}, "times": 1}])
        with faultplan.active_plan(p):
            client = ServeClient(primary.addr, SECRET, timeout=30.0)
            jid = client.submit(corpus=SERVE_CORPUS, config=SERVE_CFG,
                                no_cache=True)["job_id"]
            assert _ship_converged(primary, standby, 1)
        assert p.rules[0].fired == 1
        live = standby.journal.live_records()
        assert [r["job_id"] for r in live] == [jid]
        assert standby.journal.spill_exists(live[0]["corpus_sha"])
    finally:
        primary.close()
        standby.close()


def test_chaos_serve_ship_delay_lag_reported_admits_unaffected(tmp_path):
    """serve.ship delay: a slow standby link.  Admits must not slow
    down (shipping is off the admit path by construction) and the lag
    is REPORTED while the delay holds — the operator's signal is the
    stats lag, not a mystery stall."""
    from locust_tpu.serve import ServeClient

    primary, standby = _ha_chaos_pair(tmp_path)
    try:
        primary.scheduler.pause()
        p = plan([{"site": "serve.ship", "action": "delay",
                   "delay_s": 1.5, "match": {"cmd": "ship"},
                   "times": 1}])
        with faultplan.active_plan(p):
            client = ServeClient(primary.addr, SECRET, timeout=30.0)
            t0 = time.monotonic()
            client.submit(corpus=SERVE_CORPUS, config=SERVE_CFG,
                          no_cache=True)
            admit_s = time.monotonic() - t0
            assert admit_s < 1.0, admit_s  # the 1.5s delay never billed
            assert _ship_converged(primary, standby, 1)
        assert p.rules[0].fired == 1
    finally:
        primary.close()
        standby.close()


def test_chaos_zombie_primary_fenced_structured_and_demotes(tmp_path):
    """Zombie-primary fencing: after a takeover, the old primary's ship
    attempts are rejected with the structured stale_epoch code and it
    DEMOTES itself — its job plane then answers not_primary naming the
    new primary, never a second answer for the same jobs."""
    from locust_tpu.serve import ServeClient, ServeConfig, ServeDaemon

    primary, standby = _ha_chaos_pair(tmp_path)
    promoted = False
    try:
        primary.scheduler.pause()
        pc = ServeClient(primary.addr, SECRET, timeout=30.0)
        jid = pc.submit(corpus=SERVE_CORPUS, config=SERVE_CFG,
                        no_cache=True)["job_id"]
        assert _ship_converged(primary, standby, 1)
        serve_abandon(primary)
        sc = ServeClient(standby.addr, SECRET, timeout=30.0)
        sc.promote()
        promoted = True
        assert dict(sc.wait(jid, timeout=60.0)["pairs"]) == _serve_oracle()
        # The zombie restarts on its old journal, still shipping at the
        # promoted standby: its first ship is fenced ("stale_epoch")
        # and it must demote instead of split-braining.
        zombie = ServeDaemon(secret=SECRET, cfg=ServeConfig(
            journal_dir=str(tmp_path / "primary-journal"),
            ship_to=f"{standby.addr[0]}:{standby.addr[1]}",
            dispatch_poll_s=0.02, ship_heartbeat_s=0.2,
        ))
        zombie.serve_in_thread()
        try:
            deadline = time.monotonic() + 20.0
            while time.monotonic() < deadline and \
                    zombie.role != "standby":
                time.sleep(0.05)
            assert zombie.role == "standby"
            zrep = ServeClient(zombie.addr, SECRET,
                               timeout=30.0).stats()["replication"]
            assert zrep["fenced_by"] == standby.epoch
            zc = ServeClient(zombie.addr, SECRET, timeout=30.0)
            raw = zc._rpc_one(zombie.addr,
                              {"cmd": "submit", "corpus_b64": "YQo="})
            assert raw.get("code") == "not_primary"
            assert raw.get("primary") == \
                f"{standby.addr[0]}:{standby.addr[1]}"
        finally:
            zombie.close()
    finally:
        if not promoted:
            primary.close()
        standby.close()


def test_chaos_stale_epoch_ship_rejected_without_demote_confusion(tmp_path):
    """Direct fence pin: a ship frame carrying an older epoch than the
    receiver's is answered with the structured stale_epoch code and the
    receiver's epoch — nothing is applied."""
    from locust_tpu.distributor import protocol
    from locust_tpu.serve import ServeClient, ServeConfig, ServeDaemon
    from locust_tpu.serve.replicate import records_blob

    standby = ServeDaemon(secret=SECRET, cfg=ServeConfig(
        journal_dir=str(tmp_path / "standby-journal"),
        standby_of="127.0.0.1:9", dispatch_poll_s=0.02,
    ))
    standby.serve_in_thread()
    try:
        standby._promote(reason="test")  # epoch >= 2 now
        sc = ServeClient(standby.addr, SECRET, timeout=30.0)
        text, checksum = records_blob(
            [{"rec": "admit", "job_id": "zombie-job", "v": 1,
              "corpus_sha": ""}]
        )
        raw = sc._rpc_one(standby.addr, {
            "cmd": "ship", protocol.EPOCH_KEY: 1, "seq_from": 1,
            "records": text, "sum": checksum, "from": "127.0.0.1:9",
        })
        assert raw.get("code") == "stale_epoch"
        assert raw.get("epoch") == standby.epoch
        assert all(r["job_id"] != "zombie-job"
                   for r in standby.journal.live_records())
    finally:
        standby.close()


def test_chaos_double_promotion_refused(tmp_path):
    """Promote on a daemon that is already primary — the second promote
    of a takeover runbook, or a mistyped target — is a loud structured
    refusal, not a silent epoch bump that fences a healthy peer."""
    from locust_tpu.serve import ServeClient, ServeError

    primary, standby = _ha_chaos_pair(tmp_path)
    try:
        # The live primary refuses promote (a mistyped target) FIRST —
        # after the standby's takeover below it is legitimately fenced
        # down to standby, where promote would rightly succeed again.
        pc = ServeClient(primary.addr, SECRET, timeout=30.0)
        with pytest.raises(ServeError) as e:
            pc.promote()
        assert e.value.code == "bad_spec"
        sc = ServeClient(standby.addr, SECRET, timeout=30.0)
        first = sc.promote()
        assert first["role"] == "primary"
        with pytest.raises(ServeError) as e:
            sc.promote()
        assert e.value.code == "bad_spec"
        assert "already the primary" in str(e.value)
    finally:
        primary.close()
        standby.close()


def test_chaos_compaction_racing_catchup_does_not_strand_standby(tmp_path):
    """The ISSUE 14 satellite regression: the primary compacts (and GCs
    a spill) while a catch-up snapshot is IN FLIGHT to the standby.
    The stale snapshot still lists the job live and its spill is gone —
    the primary answers the spill pull with `gone`, the terminal record
    (behind the snapshot in the stream) retires the job, and the
    compaction's own barrier re-syncs the standby to the compacted live
    set.  Stranded = lag never drains; the pin is full convergence with
    zero shipper errors."""
    from locust_tpu.serve import ServeClient

    primary, standby = _ha_chaos_pair(tmp_path)
    try:
        client = ServeClient(primary.addr, SECRET, timeout=30.0)
        jid = client.submit(corpus=SERVE_CORPUS, config=SERVE_CFG,
                            no_cache=True)["job_id"]
        client.wait(jid, timeout=60.0)
        assert _ship_converged(primary, standby, 1)
        sha = primary._jobs[jid].corpus_digest
        # Model a standby that never got this spill (it fell behind):
        os.unlink(standby.journal.spill_path(sha))
        # Hold the NEXT catch-up in flight for 1s: the snapshot is read
        # before the delay, so the compaction below races it for real.
        p = plan([{"site": "serve.ship", "action": "delay",
                   "delay_s": 1.0, "match": {"cmd": "catchup"},
                   "times": 1}])
        with faultplan.active_plan(p):
            catchups_before = standby.receiver.stats()["catchups"]
            primary.shipper.barrier()          # catch-up takes off ...
            time.sleep(0.3)                    # ... snapshot read, held
            primary._compact_journal()         # GC the spill mid-flight
            deadline = time.monotonic() + 20.0
            while time.monotonic() < deadline:
                if standby.receiver.stats()["catchups"] > \
                        catchups_before and _ship_converged(
                            primary, standby, 1, timeout=0.1):
                    break
                time.sleep(0.05)
        assert p.rules[0].fired == 1
        assert _ship_converged(primary, standby, 1)
        assert primary.shipper.stats()["ship_errors"] == 0
        # Terminal on the primary -> the standby's replayable set is
        # empty; nothing waits on a spill that no longer exists.
        assert standby.journal.live_records() == []
    finally:
        primary.close()
        standby.close()


def test_chaos_serve_ship_drop_quiescent_stream_still_converges(tmp_path):
    """The drop with NOTHING behind it: the dropped batch carries the
    LAST records before the stream goes idle.  The next heartbeat's
    sequence gap must trigger the resync — without the gap check ahead
    of the heartbeat early-return, the standby would report a fresh
    lease forever while permanently missing the acked job."""
    from locust_tpu.serve import ServeClient

    primary, standby = _ha_chaos_pair(tmp_path)
    try:
        primary.scheduler.pause()  # the admit is the LAST record
        p = plan([{"site": "serve.ship", "action": "drop",
                   "match": {"cmd": "ship"}, "times": 1}])
        with faultplan.active_plan(p):
            client = ServeClient(primary.addr, SECRET, timeout=30.0)
            jid = client.submit(corpus=SERVE_CORPUS, config=SERVE_CFG,
                                no_cache=True)["job_id"]
            assert _ship_converged(primary, standby, 1)
        assert p.rules[0].fired == 1
        # The standby holds the admit + spill: promotion-safe.
        live = standby.journal.live_records()
        assert [r["job_id"] for r in live] == [jid]
        assert standby.journal.spill_exists(live[0]["corpus_sha"])
    finally:
        primary.close()
        standby.close()
