"""Serve tier battery: scheduler fairness, the two cache layers, batched
dispatch demux, warm-state restart persistence, and the loopback daemon's
command protocol (docs/SERVING.md).

All loopback / in-process, tiny configs; every wait is bounded (a hung
daemon IS a failed test — same stance as the chaos matrix).
"""

import collections
import os
import time

import pytest

from helpers import py_wordcount, serve_abandon

from locust_tpu.config import EngineConfig
from locust_tpu.serve import (
    AdmitReject,
    ExecutableCache,
    FairScheduler,
    ResultCache,
    ServeClient,
    ServeConfig,
    ServeDaemon,
    ServeError,
    WarmState,
    bucket_blocks,
)
from locust_tpu.serve.jobs import (
    ERROR_CODES,
    Job,
    JobSpec,
    parse_spec,
    structured_error,
)

SECRET = b"serve-test"

# Tiny pipeline shapes: every engine-touching test compiles small.
CFG_OVR = {
    "block_lines": 8, "line_width": 64, "key_width": 16, "emits_per_line": 8,
}
CFG = EngineConfig(**CFG_OVR)


def mk_job(tenant="t", weight=1.0, bucket=1, cfg=CFG, job_id=None):
    spec = JobSpec(tenant=tenant, workload="wordcount", cfg=cfg,
                   weight=weight)
    return Job(
        job_id=job_id or f"{tenant}-{time.monotonic_ns()}",
        spec=spec, corpus_digest="d", n_lines=1, n_blocks=bucket,
        bucket=bucket,
    )


def const_key(job):
    return ("k", job.bucket)


# --------------------------------------------------------------- buckets


def test_bucket_ladder():
    assert [bucket_blocks(n) for n in (0, 1, 2, 3, 4, 5, 8, 9, 100)] == [
        1, 1, 2, 4, 4, 8, 8, 16, 128,
    ]


# ------------------------------------------------------------- scheduler


def test_admission_rejects_when_full_with_reason():
    s = FairScheduler(max_queue=2, max_batch=1)
    s.admit(mk_job())
    s.admit(mk_job())
    with pytest.raises(AdmitReject) as e:
        s.admit(mk_job())
    assert e.value.code == "queue_full"
    assert e.value.code in ERROR_CODES
    assert s.stats()["rejected"] == 1


def test_admission_tenant_quota():
    s = FairScheduler(max_queue=10, max_batch=1, tenant_quota=2)
    s.admit(mk_job("hog"))
    s.admit(mk_job("hog"))
    with pytest.raises(AdmitReject) as e:
        s.admit(mk_job("hog"))
    assert e.value.code == "tenant_quota"
    s.admit(mk_job("polite"))  # other tenants unaffected


def test_fairness_light_tenant_not_starved():
    """10 queued heavy-tenant jobs must not starve a late light tenant:
    stride scheduling serves the light tenant's jobs within its share."""
    s = FairScheduler(max_queue=32, max_batch=1)
    heavy = [mk_job("heavy", job_id=f"h{i}") for i in range(10)]
    light = [mk_job("light", job_id=f"l{i}") for i in range(2)]
    for j in heavy + light:
        s.admit(j)
    order = []
    while True:
        batch = s.next_batch(const_key, timeout=0.0)
        if not batch:
            break
        order.extend(j.job_id for j in batch)
    assert len(order) == 12
    # Both light jobs dispatch within the first four slots, not after
    # the heavy backlog drains.
    assert set(order[:4]) >= {"l0", "l1"}


def test_weighted_fairness_ratio():
    """weight=2 buys ~2x the dispatch share against weight=1."""
    s = FairScheduler(max_queue=64, max_batch=1)
    for i in range(12):
        s.admit(mk_job("fast", weight=2.0, job_id=f"f{i}"))
    for i in range(12):
        s.admit(mk_job("slow", weight=1.0, job_id=f"s{i}"))
    first9 = [
        s.next_batch(const_key, timeout=0.0)[0].job_id for _ in range(9)
    ]
    fast = sum(1 for j in first9 if j.startswith("f"))
    assert fast >= 5, first9  # ~2:1 share, not round-robin


def test_batch_coalesces_same_key_in_fair_order():
    s = FairScheduler(max_queue=16, max_batch=3)
    a = mk_job("a", bucket=1, job_id="a1")
    b = mk_job("b", bucket=1, job_id="b1")
    big = mk_job("a", bucket=4, job_id="a-big")
    c = mk_job("c", bucket=1, job_id="c1")
    for j in (a, big, b, c):
        s.admit(j)
    batch = s.next_batch(lambda j: ("k", j.bucket), timeout=0.0)
    # Head is fair-order first; the bucket-4 job cannot join the bucket-1
    # batch; max_batch=3 caps the coalesce.
    assert sorted(j.job_id for j in batch) == ["a1", "b1", "c1"]
    batch2 = s.next_batch(lambda j: ("k", j.bucket), timeout=0.0)
    assert [j.job_id for j in batch2] == ["a-big"]


def test_cancel_pending_only():
    s = FairScheduler(max_queue=4, max_batch=1)
    j = mk_job("t", job_id="doomed")
    s.admit(j)
    assert s.cancel("doomed") is j
    assert s.cancel("doomed") is None  # already gone
    assert s.next_batch(const_key, timeout=0.0) is None


# -------------------------------------------------------------- caches


def test_exec_cache_hit_miss_and_fingerprint_invalidation():
    cache = ExecutableCache(max_engines=2)
    spec = JobSpec(tenant="t", workload="wordcount", cfg=CFG)
    eng, hit = cache.lookup(spec, 1, 1)
    assert not hit and cache.stats()["builds"] == 1
    cache.mark_compiled(spec, 1, 1)
    eng2, hit2 = cache.lookup(spec, 1, 1)
    assert hit2 and eng2 is eng  # same engine, compiled shape: a hit
    assert cache.stats() == {
        "engines": 1, "shapes": 1, "hits": 1, "misses": 1,
        "builds": 1, "compiles": 1, "evictions": 0,
        "fused_on": 0, "fused_demoted": 0,
    }
    # An EngineConfig change invalidates the executable identity.
    spec2 = JobSpec(
        tenant="t", workload="wordcount",
        cfg=EngineConfig(**dict(CFG_OVR, emits_per_line=4)),
    )
    assert spec2.fingerprint() != spec.fingerprint()
    _, hit3 = cache.lookup(spec2, 1, 1)
    assert not hit3 and cache.stats()["builds"] == 2


def test_exec_cache_stats_surface_fused_kernel_state():
    """Megakernel visibility on the warm-cache path: stats count the
    warm engines actually running the fused kernel vs demoted at
    construction — on CPU a fused spec at an interpret-eligible shape
    shows fused_on, and one past the interpret cap shows
    fused_demoted (the engine logs the reason; stats keep it visible)."""
    cache = ExecutableCache(max_engines=4)
    on = JobSpec(
        tenant="t", workload="wordcount",
        cfg=EngineConfig(
            **dict(CFG_OVR, sort_mode="fused", block_lines=32,
                   line_width=128)
        ),
    )
    cache.lookup(on, 1, 1)
    st = cache.stats()
    assert st["fused_on"] == 1 and st["fused_demoted"] == 0
    demoted = JobSpec(
        tenant="t", workload="wordcount",
        cfg=EngineConfig(
            **dict(CFG_OVR, sort_mode="fused", block_lines=32768,
                   line_width=128)
        ),
    )
    cache.lookup(demoted, 1, 1)
    st = cache.stats()
    assert st["fused_on"] == 1 and st["fused_demoted"] == 1


def test_exec_cache_shape_bucket_sharing():
    """Different corpus sizes that round into the SAME bucket share one
    compiled shape: the second lookup is a hit without a new compile."""
    cache = ExecutableCache()
    spec = JobSpec(tenant="t", workload="wordcount", cfg=CFG)
    # job A: 3 blocks -> bucket 4; job B: 4 blocks -> bucket 4.
    assert bucket_blocks(3) == bucket_blocks(4) == 4
    _, hit = cache.lookup(spec, 1, 4)
    cache.mark_compiled(spec, 1, 4)
    _, hit_b = cache.lookup(spec, 1, 4)
    assert not hit and hit_b
    assert cache.stats()["compiles"] == 1


def test_exec_cache_lru_eviction_drops_shapes():
    cache = ExecutableCache(max_engines=1)
    s1 = JobSpec(tenant="t", workload="wordcount", cfg=CFG)
    s2 = JobSpec(
        tenant="t", workload="wordcount",
        cfg=EngineConfig(**dict(CFG_OVR, block_lines=16)),
    )
    cache.lookup(s1, 1, 1)
    cache.mark_compiled(s1, 1, 1)
    cache.lookup(s2, 1, 1)  # evicts s1's engine AND its shapes
    st = cache.stats()
    assert st["evictions"] == 1 and st["engines"] == 1 and st["shapes"] == 0


def test_result_cache_hit_invalidate_and_cap():
    rc = ResultCache(max_entries=2)
    rc.put("d1", "f", [(b"a", 1)])
    rc.put("d2", "f", [(b"b", 2)])
    assert rc.get("d1", "f") == [(b"a", 1)]
    assert rc.get("nope", "f") is None
    assert rc.invalidate(digest="d1") == 1
    assert rc.get("d1", "f") is None
    rc.put("d3", "f", [(b"c", 3)])
    rc.put("d4", "f", [(b"d", 4)])  # cap 2: oldest (d2) evicted
    assert rc.get("d2", "f") is None
    assert rc.stats()["invalidations"] == 1


def test_result_cache_byte_cap_evicts_lru():
    from locust_tpu.serve.jobs import pairs_bytes

    entry = [(b"k" * 20, 1)]           # 36 bytes under the estimator
    assert pairs_bytes(entry) == 36
    rc = ResultCache(max_entries=10, max_bytes=100)
    rc.put("d1", "f", entry)
    rc.put("d2", "f", entry)
    rc.put("d3", "f", entry)           # 108 > 100: oldest (d1) evicted
    assert rc.get("d1", "f") is None
    assert rc.get("d2", "f") is not None
    assert rc.stats()["bytes"] == 72
    # replacing an entry must not leak its old bytes
    rc.put("d2", "f", entry)
    assert rc.stats()["bytes"] == 72
    rc.invalidate()
    assert rc.stats()["bytes"] == 0
    # a single entry larger than the whole cap is kept: it still
    # serves hits, and evicting it would cache nothing at all
    small = ResultCache(max_entries=10, max_bytes=10)
    small.put("big", "f", entry)
    assert small.get("big", "f") == entry


def test_warm_state_roundtrips_through_async_writer(tmp_path):
    rc = ResultCache()
    rc.put("dig", "fp", [(b"key", 7), (b"\x00odd\xffbytes", 1)])
    warm = WarmState(str(tmp_path), rc)
    warm.mark(1)
    warm.flush()
    warm.close()
    rc2 = ResultCache()
    warm2 = WarmState(str(tmp_path), rc2)
    assert warm2.load() == 1
    assert rc2.get("dig", "fp") == [(b"key", 7), (b"\x00odd\xffbytes", 1)]
    warm2.close()


def test_warm_state_corrupt_file_is_cold_start(tmp_path):
    from locust_tpu.serve.cache import WARM_FILE

    (tmp_path / WARM_FILE).write_bytes(b"{definitely not json")
    rc = ResultCache()
    warm = WarmState(str(tmp_path), rc)
    assert warm.load() == 0  # logged cold start, no crash
    warm.close()


# ------------------------------------------------------- spec validation


def test_parse_spec_rejects_with_structured_codes():
    import base64

    good = {"corpus_b64": base64.b64encode(b"a b c\n").decode()}
    for req, code in [
        ({"workload": "nope", **good}, "unknown_workload"),
        ({}, "bad_spec"),                              # no corpus at all
        ({"corpus_b64": "!!!"}, "bad_spec"),           # bad base64
        ({"config": {"bogus_knob": 1}, **good}, "bad_spec"),
        ({"config": {"sort_mode": "nope"}, **good}, "bad_spec"),
        ({"config": {"sort_mode": "radix"}, **good}, "bad_spec"),  # removed, PR 44
        ({"weight": -1, **good}, "bad_spec"),
    ]:
        with pytest.raises(ValueError) as e:
            parse_spec(req)
        got = str(e.value).partition("\n")[0]
        assert got == code and got in ERROR_CODES


# ---------------------------------------------------------- daemon rig


CORPUS_A = b"alpha beta gamma\nbeta gamma delta\ngamma delta alpha\n" * 4
CORPUS_B = b"zeta eta theta\niota kappa zeta\n" * 6


def oracle(corpus: bytes) -> dict:
    return dict(py_wordcount(corpus.splitlines(),
                             max_tokens_per_line=8, key_width=16))


@pytest.fixture
def rig(tmp_path):
    daemon = ServeDaemon(
        secret=SECRET,
        cfg=ServeConfig(
            max_queue=8, max_batch=4, warm_dir=str(tmp_path / "warm"),
            warm_every=1, dispatch_poll_s=0.02,
        ),
    )
    daemon.serve_in_thread()
    client = ServeClient(daemon.addr, SECRET, timeout=60.0)
    yield daemon, client
    daemon._shutdown.set()
    daemon.close()


def test_daemon_submit_result_roundtrip(rig):
    _, client = rig
    ack = client.submit(corpus=CORPUS_A, config=CFG_OVR)
    assert ack["state"] == "queued" and not ack["cached"]
    res = client.wait(ack["job_id"], timeout=120.0)
    assert dict(res["pairs"]) == oracle(CORPUS_A)
    assert res["cache"] == "cold" and res["distinct"] == len(oracle(CORPUS_A))
    st = client.status(ack["job_id"])
    assert st["state"] == "done"
    assert st["latency_ms"] is not None and st["queue_ms"] is not None


def test_daemon_repeat_job_hits_result_cache(rig):
    _, client = rig
    a1 = client.submit(corpus=CORPUS_A, config=CFG_OVR)
    client.wait(a1["job_id"], timeout=120.0)
    a2 = client.submit(corpus=CORPUS_A, config=CFG_OVR)
    assert a2["cached"] is True and a2["state"] == "done"
    res = client.result(a2["job_id"])
    assert dict(res["pairs"]) == oracle(CORPUS_A)
    assert res["cache"] == "result"
    assert client.stats()["result_cache"]["hits"] == 1


def test_daemon_result_cache_replays_truncation_flags(rig):
    """A LOSSY first run (tokens dropped past the emits-per-line cap)
    must stay flagged lossy when the result cache answers the repeat —
    a clean-looking replay of truncated data would be the silent wrong
    answer the tier forbids."""
    _, client = rig
    lossy = b"a b c d e f g h i j k l m\n" * 4  # 13 tokens > cap of 8
    a1 = client.submit(corpus=lossy, config=CFG_OVR)
    r1 = client.wait(a1["job_id"], timeout=120.0)
    assert r1["overflow_tokens"] > 0
    a2 = client.submit(corpus=lossy, config=CFG_OVR)
    assert a2["cached"] is True
    r2 = client.result(a2["job_id"])
    assert r2["cache"] == "result"
    assert r2["overflow_tokens"] == r1["overflow_tokens"]
    assert r2["truncated"] == r1["truncated"]
    assert r2["distinct"] == r1["distinct"]
    assert r2["pairs"] == r1["pairs"]


def test_next_batch_returns_none_after_stop_with_pending():
    """stop() beats a non-empty queue: the daemon's close() must never
    be answered with a fresh dispatch (a cold compile there would blow
    the bounded dispatcher join and race the warm-state flush)."""
    s = FairScheduler(max_queue=4, max_batch=2)
    s.admit(mk_job())
    s.stop()
    assert s.next_batch(const_key, timeout=0.2) is None


def test_admit_after_stop_is_shutting_down_not_queue_full():
    """A stopped scheduler's rejection is PERMANENT: "queue_full" would
    tell a well-behaved client to back off and retry a daemon that will
    never accept again.  The rejection is also counted in stats."""
    s = FairScheduler(max_queue=4, max_batch=2)
    s.stop()
    with pytest.raises(AdmitReject) as e:
        s.admit(mk_job())
    assert e.value.code == "shutting_down"
    assert e.value.code in ERROR_CODES
    assert s.stats()["rejected"] == 1


def test_warm_mark_cadence_is_distance_based(tmp_path):
    """``completed`` advances by BATCH SIZE, so the dispatcher may never
    observe a multiple of warm_every — the cadence must be distance
    (completed - last_marked >= warm_every), or batches of 2 under
    warm_every=3 would never persist until clean shutdown."""
    daemon = ServeDaemon(
        secret=SECRET,
        cfg=ServeConfig(
            max_queue=8, max_batch=4, warm_dir=str(tmp_path / "w"),
            warm_every=3, dispatch_poll_s=0.02,
        ),
    )
    daemon.serve_in_thread()
    client = ServeClient(daemon.addr, SECRET, timeout=60.0)
    try:
        # Two batches of 2: completed observes 2, then 4 — never %3==0.
        for wave in range(2):
            daemon.scheduler.pause()
            acks = [
                client.submit(corpus=b"wave%d job%d\n" % (wave, i) * 4,
                              config=CFG_OVR)
                for i in range(2)
            ]
            daemon.scheduler.resume()
            for a in acks:
                client.wait(a["job_id"], timeout=120.0)
        assert daemon.warm.stats()["submitted"] >= 1
    finally:
        daemon._shutdown.set()
        daemon.close()


def test_fail_batch_preserves_already_done_jobs():
    """A batch failing mid-demux fails only the jobs that had not
    finished: results already demuxed stand (the client may have seen
    "done" — it must never flip to "failed" afterwards)."""
    daemon = ServeDaemon(secret=SECRET)
    try:
        done_job, pending = mk_job(job_id="d1"), mk_job(job_id="p1")
        done_job.state = "done"
        daemon._fail_batch(
            [done_job, pending], structured_error("dispatch_failed", "boom")
        )
        assert done_job.state == "done" and done_job.error is None
        assert pending.state == "failed"
        assert pending.error["code"] == "dispatch_failed"
    finally:
        daemon._shutdown.set()
        daemon.close()


def test_daemon_second_identical_job_skips_compilation(rig):
    """The acceptance-criteria pin: a repeat job (forced through the
    engine with no_cache) reuses the warm executable — ``compiles`` does
    NOT advance and the job reports cache="warm"."""
    daemon, client = rig
    a1 = client.submit(corpus=CORPUS_A, config=CFG_OVR, no_cache=True)
    client.wait(a1["job_id"], timeout=120.0)
    before = daemon.executables.stats()
    assert before["compiles"] == 1
    a2 = client.submit(corpus=CORPUS_A, config=CFG_OVR, no_cache=True)
    res = client.wait(a2["job_id"], timeout=120.0)
    after = daemon.executables.stats()
    assert res["cache"] == "warm"
    assert after["compiles"] == before["compiles"]  # no new compile
    assert after["hits"] == before["hits"] + 1
    assert dict(res["pairs"]) == oracle(CORPUS_A)


def test_daemon_explicit_invalidation_recomputes(rig):
    daemon, client = rig
    a1 = client.submit(corpus=CORPUS_A, config=CFG_OVR)
    client.wait(a1["job_id"], timeout=120.0)
    n = client.invalidate(job_id=a1["job_id"])
    assert n == 1
    a2 = client.submit(corpus=CORPUS_A, config=CFG_OVR)
    assert a2["cached"] is False  # really recomputed
    res = client.wait(a2["job_id"], timeout=120.0)
    assert dict(res["pairs"]) == oracle(CORPUS_A)


def test_daemon_invalidate_unknown_job_is_structured_not_wipe(rig):
    """An unknown/history-evicted job_id must NOT fall through to
    ResultCache.invalidate(None, None) — the wipe-everything match —
    and silently destroy every tenant's cached results."""
    daemon, client = rig
    a1 = client.submit(corpus=CORPUS_A, config=CFG_OVR)
    client.wait(a1["job_id"], timeout=120.0)
    resp = client.rpc({"cmd": "invalidate", "job_id": "no-such-id"})
    assert resp["status"] == "error" and resp["code"] == "unknown_job"
    a2 = client.submit(corpus=CORPUS_A, config=CFG_OVR)
    assert a2["cached"] is True  # cache survived the bad invalidate


def test_daemon_admission_bounds_buffered_corpus_bytes(rig):
    """max_queue bounds job COUNT; the byte cap must reject before
    max_queue * max_corpus_bytes of in-flight corpora OOM the daemon."""
    daemon, client = rig
    daemon.cfg.max_queue_bytes = 16
    rejected_before = client.stats()["queue"]["rejected"]
    with pytest.raises(ServeError) as e:
        client.submit(corpus=CORPUS_A, config=CFG_OVR, no_cache=True)
    assert e.value.code == "queue_full"
    # The stat must match the emitted code, even though the byte cap is
    # decided in the daemon, not in FairScheduler.admit().
    assert client.stats()["queue"]["rejected"] == rejected_before + 1
    daemon.cfg.max_queue_bytes = 256 << 20
    ack = client.submit(corpus=CORPUS_A, config=CFG_OVR, no_cache=True)
    res = client.wait(ack["job_id"], timeout=120.0)
    assert dict(res["pairs"]) == oracle(CORPUS_A)
    # Accounting drains with the jobs: nothing left buffered afterwards.
    assert client.stats()["queued_corpus_bytes"] == 0


def test_oversized_reply_is_structured_result_too_large(rig, monkeypatch):
    """A reply frame over protocol.MAX_FRAME raises FrameTooLarge BEFORE
    any bytes hit the wire; _try_reply must answer with a small
    structured error, not drop the connection — else a completed job
    whose result JSON exceeds the frame cap is permanently unfetchable
    through bare ConnectionErrors."""
    import socket as socket_mod

    from locust_tpu.distributor import protocol

    daemon, _ = rig
    monkeypatch.setattr(protocol, "MAX_FRAME", 4096)
    a, b = socket_mod.socketpair()
    try:
        big = {"status": "ok", "pairs": [["k" * 64, 1]] * 500}
        assert daemon._try_reply(a, big) is True  # error frame delivered
        b.settimeout(5.0)
        reply = protocol.recv_frame(b, SECRET)
        assert reply["status"] == "error"
        assert reply["code"] == "result_too_large"
        assert reply["code"] in ERROR_CODES
    finally:
        a.close()
        b.close()


def test_serve_control_plane_imports_are_jax_free():
    """The thin client (submit/stats/shutdown against a remote daemon)
    must import without jax: a jax init costs seconds and, on a machine
    with a chip, reaches for the device the daemon holds, so a pure
    control-plane command must never touch it (docstring contract in
    serve/__init__ and jobs.py; WarmState + distributor.master/worker
    stay lazy)."""
    import subprocess
    import sys

    code = (
        "import sys\n"
        "import locust_tpu.serve\n"
        "import locust_tpu.serve.client\n"
        "import locust_tpu.serve.cache\n"
        "assert 'jax' not in sys.modules, 'serve import pulled jax in'\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code],
        env={
            "PYTHONPATH": os.path.dirname(os.path.dirname(__file__)),
            "JAX_PLATFORMS": "cpu",
            "PATH": os.environ.get("PATH", ""),
        },
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 0, proc.stderr


def test_tenant_quota_zero_means_unlimited():
    """CLI --tenant-quota 0 must read as 'off' (0-disables convention);
    a literal 0 would reject every tenant's FIRST job."""
    s = FairScheduler(max_queue=4, max_batch=2, tenant_quota=0)
    assert s.tenant_quota is None
    s.admit(mk_job())  # would raise tenant_quota before the fix


def test_daemon_batches_compatible_jobs_and_demuxes(rig):
    """Distinct corpora submitted back-to-back coalesce into one
    dispatch (same executable key + bucket) and each job still gets
    exactly ITS OWN counts back."""
    daemon, client = rig
    # All three land in the SAME shape bucket (<=16 lines at
    # block_lines=8 -> 2 blocks -> bucket 2), so they are coalescable.
    # Pausing the dispatcher while they queue makes the coalesce
    # deterministic: ONE dispatch serves all three.
    corpora = [CORPUS_A, CORPUS_B, CORPUS_A + b"delta extra words\n"]
    daemon.scheduler.pause()
    acks = [
        client.submit(corpus=c, tenant=f"t{i}", config=CFG_OVR,
                      no_cache=True)
        for i, c in enumerate(corpora)
    ]
    daemon.scheduler.resume()
    results = [client.wait(a["job_id"], timeout=120.0) for a in acks]
    for c, res in zip(corpora, results):
        assert dict(res["pairs"]) == oracle(c)
    sizes = [client.status(a["job_id"])["batch_size"] for a in acks]
    assert sizes == [3, 3, 3], sizes  # one coalesced dispatch, demuxed


def test_daemon_admission_rejects_structured(rig):
    daemon, client = rig
    # Choke the queue: a huge pile of jobs against a stopped dispatcher
    # would be flaky; instead shrink the bound directly.
    daemon.scheduler.max_queue = 0
    with pytest.raises(ServeError) as e:
        client.submit(corpus=CORPUS_A, config=CFG_OVR, no_cache=True)
    assert e.value.code == "queue_full"
    daemon.scheduler.max_queue = 8  # restore for teardown


def test_daemon_rejects_oversized_corpus(rig):
    daemon, client = rig
    daemon.cfg.max_corpus_bytes = 64
    with pytest.raises(ServeError) as e:
        client.submit(corpus=b"x" * 100, config=CFG_OVR)
    assert e.value.code == "corpus_too_large"


def test_daemon_rejects_oversized_corpus_path_bounded_read(rig, tmp_path):
    """The path branch must reject BEFORE the bytes land in daemon
    memory: parse_spec reads at most cap+1 bytes, so a submit naming a
    huge server-side file can't OOM the daemon ahead of the rejection."""
    daemon, client = rig
    daemon.cfg.max_corpus_bytes = 64
    big = tmp_path / "big.txt"
    big.write_bytes(b"y" * 4096)
    with pytest.raises(ServeError) as e:
        client.submit(path=str(big), config=CFG_OVR)
    assert e.value.code == "corpus_too_large"
    # Direct proof of the bounded read: parse_spec never materializes
    # more than cap+1 bytes even for a much larger file.
    spec_req = {"path": str(big), "workload": "wordcount"}
    with pytest.raises(ValueError) as pe:
        parse_spec(spec_req, max_corpus_bytes=64)
    assert str(pe.value).startswith("corpus_too_large")
    _, corpus = parse_spec(spec_req, max_corpus_bytes=8192)
    assert corpus == b"y" * 4096


def test_daemon_unknown_job_and_commands(rig):
    _, client = rig
    with pytest.raises(ServeError) as e:
        client.status("nope")
    assert e.value.code == "unknown_job"
    # raw rpc doesn't raise; check the structured reply directly
    resp = client.rpc({"cmd": "bogus"})
    assert resp["status"] == "error" and resp["code"] == "unknown_command"


def test_daemon_result_before_done_is_structured(rig):
    daemon, client = rig
    # Park the dispatcher on a job by filling the queue while asking for
    # the LAST job's result immediately: use a quick status race instead.
    ack = client.submit(corpus=CORPUS_A, config=CFG_OVR, no_cache=True)
    resp = client.rpc({"cmd": "result", "job_id": ack["job_id"]})
    if resp["status"] == "error":  # still queued/running at ask time
        assert resp["code"] in ("not_done",)
    client.wait(ack["job_id"], timeout=120.0)


def test_daemon_cancel_queued_job(rig):
    daemon, client = rig
    # Pause the dispatcher so the job STAYS queued.
    daemon.scheduler.pause()
    ack = client.submit(corpus=CORPUS_A, config=CFG_OVR, no_cache=True)
    got = client.cancel(ack["job_id"])
    assert got["cancelled"] is True and got["state"] == "cancelled"
    with pytest.raises(ServeError) as e:
        client.result(ack["job_id"])
    assert e.value.code == "cancelled"
    assert client.status(ack["job_id"])["state"] == "cancelled"


def test_daemon_stats_shape(rig):
    _, client = rig
    st = client.stats()
    for key in ("uptime_s", "completed", "jobs_by_state", "queue",
                "exec_cache", "result_cache", "warm",
                "queued_corpus_bytes", "history_result_bytes"):
        assert key in st
    assert st["queue"]["max_batch"] == 4


def test_close_fails_stranded_queued_jobs_structured():
    """Teardown is not exempt from correct-result-or-structured-error:
    jobs still queued when close() stops the scheduler must end failed
    with `shutting_down`, not abandoned in state "queued" forever."""
    import base64

    daemon = ServeDaemon(secret=SECRET, cfg=ServeConfig(
        dispatch_poll_s=0.02,
    ))
    daemon.scheduler.pause()  # hold dispatch so the jobs stay queued
    job_ids = []
    for corpus in (CORPUS_A, CORPUS_B):
        ack = daemon._cmd_submit({
            "cmd": "submit",
            "corpus_b64": base64.b64encode(corpus).decode(),
            "config": dict(CFG_OVR),
        })
        assert ack["status"] == "ok" and ack["state"] == "queued"
        job_ids.append(ack["job_id"])
    daemon._shutdown.set()
    daemon.close()
    for jid in job_ids:
        job = daemon._jobs[jid]
        assert job.state == "failed"
        assert job.error["code"] == "shutting_down"
    assert daemon._corpus_total == 0  # buffered corpora freed


def test_rejected_submit_with_invalidate_preserves_cache(rig):
    """A rejected submit must have NO side effects: the old
    invalidate-before-admission order let one tenant's queue_full
    request wipe the cached entry every other tenant was served from."""
    daemon, client = rig
    a1 = client.submit(corpus=CORPUS_A, config=CFG_OVR)
    client.wait(a1["job_id"], timeout=120.0)  # entry now cached
    daemon.scheduler.pause()
    for i in range(8):  # rig max_queue=8: fill the admission bound
        client.submit(corpus=b"filler %d\n" % i, config=CFG_OVR)
    with pytest.raises(ServeError) as e:
        client.submit(corpus=CORPUS_A, config=CFG_OVR, invalidate=True)
    assert e.value.code == "queue_full"
    st = daemon.results.stats()
    assert st["invalidations"] == 0 and st["entries"] >= 1
    # and an accepted invalidate still wipes: admit the same submit
    # with room in the queue
    daemon.scheduler.resume()
    a2 = client.submit(corpus=CORPUS_A, config=CFG_OVR, invalidate=True)
    assert a2["cached"] is False
    assert daemon.results.stats()["invalidations"] == 1


def test_daemon_history_byte_cap_evicts_oldest_finished():
    daemon = ServeDaemon(
        secret=SECRET,
        cfg=ServeConfig(
            max_queue=8, max_batch=1, dispatch_poll_s=0.02,
            max_history_bytes=1,  # any retained result over-caps
        ),
    )
    daemon.serve_in_thread()
    try:
        client = ServeClient(daemon.addr, SECRET, timeout=60.0)
        a1 = client.submit(corpus=CORPUS_A, config=CFG_OVR)
        r1 = client.wait(a1["job_id"], timeout=120.0)
        # the keep guard: a job's own completion never evicts its
        # record, so the done-ack stayed fetchable even over-cap
        assert dict(r1["pairs"]) == oracle(CORPUS_A)
        a2 = client.submit(corpus=CORPUS_B, config=CFG_OVR)
        r2 = client.wait(a2["job_id"], timeout=120.0)
        assert dict(r2["pairs"]) == oracle(CORPUS_B)
        # admitting/finishing job2 evicted job1's finished record WHOLE
        with pytest.raises(ServeError) as e:
            client.status(a1["job_id"])
        assert e.value.code == "unknown_job"
        st = client.stats()
        assert st["jobs_by_state"] == {"done": 1}
        assert st["history_result_bytes"] > 0  # job2 only (keep guard)
    finally:
        daemon._shutdown.set()
        daemon.close()


def test_cli_result_fetches_no_wait_submit(rig, tmp_path, capsysbinary,
                                           monkeypatch):
    """submit --no-wait prints an id the `result` subcommand can fetch
    later — without it a detached submit would be a CLI dead end."""
    from locust_tpu.serve.__main__ import main

    daemon, _ = rig
    host, port = daemon.addr
    monkeypatch.setenv("LOCUST_SECRET", SECRET.decode())
    corpus = tmp_path / "corpus.txt"
    corpus.write_bytes(CORPUS_A)
    common = ["--host", host, "--port", str(port)]
    assert main([
        "submit", str(corpus), *common, "--no-wait",
        "--block-lines", "8", "--line-width", "64",
        "--key-width", "16", "--emits-per-line", "8",
    ]) == 0
    job_id = capsysbinary.readouterr().out.decode().strip()
    assert job_id
    assert main(["result", job_id, "--wait", *common]) == 0
    got = {}
    for line in capsysbinary.readouterr().out.splitlines():
        k, _, v = line.rpartition(b"\t")
        got[k] = int(v)
    assert got == oracle(CORPUS_A)
    # structured errors are an exit code + one line, not a traceback
    assert main(["result", "no-such-job", *common]) == 1


def test_daemon_warm_state_survives_restart(tmp_path):
    """The restart-resume acceptance pin: daemon 1 computes + persists;
    daemon 2 on the same warm dir answers the SAME job from the restored
    result cache without ever touching an engine."""
    warm_dir = str(tmp_path / "warm")
    d1 = ServeDaemon(
        secret=SECRET,
        cfg=ServeConfig(warm_dir=warm_dir, warm_every=1,
                        dispatch_poll_s=0.02),
    )
    d1.serve_in_thread()
    c1 = ServeClient(d1.addr, SECRET, timeout=60.0)
    ack = c1.submit(corpus=CORPUS_A, config=CFG_OVR)
    expect = dict(c1.wait(ack["job_id"], timeout=120.0)["pairs"])
    d1._shutdown.set()
    d1.close()  # final warm generation flushes through the async writer

    d2 = ServeDaemon(
        secret=SECRET,
        cfg=ServeConfig(warm_dir=warm_dir, dispatch_poll_s=0.02),
    )
    d2.serve_in_thread()
    c2 = ServeClient(d2.addr, SECRET, timeout=60.0)
    try:
        ack2 = c2.submit(corpus=CORPUS_A, config=CFG_OVR)
        assert ack2["cached"] is True, "restart lost the warm result cache"
        res = c2.result(ack2["job_id"])
        assert dict(res["pairs"]) == expect == oracle(CORPUS_A)
        assert d2.executables.stats()["builds"] == 0  # engine never built
    finally:
        d2._shutdown.set()
        d2.close()


def test_daemon_mixed_tenant_stream_all_exact(rig):
    """A small mixed stream across tenants: every job's result is exact,
    nothing starves, and the queue drains."""
    _, client = rig
    jobs = []
    rng_corpora = []
    for i in range(6):
        c = CORPUS_A if i % 2 else CORPUS_B
        c = c + (b"extra%d word\n" % i)
        rng_corpora.append(c)
        jobs.append(client.submit(
            corpus=c, tenant=f"t{i % 3}", config=CFG_OVR, no_cache=True,
        ))
    for c, ack in zip(rng_corpora, jobs):
        res = client.wait(ack["job_id"], timeout=120.0)
        assert dict(res["pairs"]) == oracle(c)
    assert client.stats()["queue"]["depth"] == 0


# ------------------------------------------------------------ run_batch


def test_engine_run_batch_demux_matches_single_runs():
    """engine.run_batch: per-job tables from one vmapped dispatch are
    identical to per-job run() results (padded slots fold to empty)."""
    import numpy as np

    from locust_tpu.engine import MapReduceEngine
    from locust_tpu.serve.batch import dispatch_batch, split_lines

    eng = MapReduceEngine(CFG)
    corpora = {"a": CORPUS_A, "b": CORPUS_B}
    jobs = []
    for digest, corpus in corpora.items():
        lines = split_lines(corpus)
        n_blocks = max(1, -(-len(lines) // CFG.block_lines))
        jobs.append(Job(
            job_id=digest,
            spec=JobSpec(tenant="t", workload="wordcount", cfg=CFG),
            corpus_digest=digest, n_lines=len(lines),
            n_blocks=n_blocks, bucket=bucket_blocks(n_blocks),
        ))
    assert jobs[0].bucket == jobs[1].bucket  # compatible by construction
    results = dispatch_batch(eng, jobs, corpora)
    assert len(results) == 2
    for job, res in zip(jobs, results):
        single = eng.run_lines(split_lines(corpora[job.corpus_digest]))
        assert dict(res.to_host_pairs()) == dict(single.to_host_pairs())
        assert res.num_segments == single.num_segments


def test_rejoin_after_idle_queue_not_starved():
    """A tenant whose past usage predates an EMPTY queue must not be
    starved by a tenant that first joined while the queue was idle: the
    rejoin floor is the global virtual time advanced at dispatch, not 0."""
    s = FairScheduler(max_queue=64, max_batch=1)
    for i in range(12):
        s.admit(mk_job("a", bucket=8, job_id=f"a{i}"))
    while s.next_batch(const_key, timeout=0.0):
        pass  # tenant a banks vt 96; the queue drains to empty
    for i in range(12):
        s.admit(mk_job("b", job_id=f"b{i}"))  # joins the IDLE queue
    for i in range(4):
        s.admit(mk_job("a", job_id=f"r{i}"))  # a returns
    order = []
    while True:
        batch = s.next_batch(const_key, timeout=0.0)
        if not batch:
            break
        order.extend(j.job_id for j in batch)
    # a's returning jobs interleave near the front instead of waiting
    # out b's entire backlog (the un-floored behavior: all 12 b's first).
    assert any(j.startswith("r") for j in order[:8]), order


def test_idle_tenant_vt_entries_pruned():
    """Client-chosen tenant names must not grow scheduler state forever:
    an idle tenant at/below the global floor is dropped after dispatch."""
    s = FairScheduler(max_queue=64, max_batch=1)
    for i in range(50):
        s.admit(mk_job(f"tenant-{i}", job_id=f"t{i}"))
    while s.next_batch(const_key, timeout=0.0):
        pass
    assert len(s.stats()["virtual_time"]) <= 1  # at most the last head


def test_count_lines_matches_splitlines():
    from locust_tpu.serve.batch import count_lines

    cases = [
        b"", b"\n", b"a", b"a\n", b"a\nb", b"a\nb\n", b"a\r\nb\r\n",
        b"a\rb", b"a\r", b"a\r\n", b"\r\n\r\n", b"\r\r", b"x\n\ry\r\nz",
        b"word " * 1000 + b"\n" + b"tail",
    ]
    for c in cases:
        assert count_lines(c) == len(c.splitlines()), c[:40]


# ------------------------------------------------- durability (ISSUE 10)
#
# The write-ahead journal + retry/deadline ladder: accepted work survives
# kill -9 byte-identically, one poison job cannot crash-loop a batch's
# innocent neighbors, and a deadline expires to a structured answer in
# any state (docs/SERVING.md).

from locust_tpu.utils import faultplan


_abandon = serve_abandon


def _journal_daemon(tmp_path, **kw):
    cfg = ServeConfig(
        max_queue=16, max_batch=4, dispatch_poll_s=0.02,
        journal_dir=str(tmp_path / "journal"), retry_base_s=0.02,
        **kw,
    )
    daemon = ServeDaemon(secret=SECRET, cfg=cfg)
    daemon.serve_in_thread()
    return daemon, ServeClient(daemon.addr, SECRET, timeout=60.0)


def test_journal_replay_reenqueues_under_original_ids(tmp_path):
    """In-process kill -9 rehearsal: acked-but-unfinished jobs replay
    under their ORIGINAL ids on restart and land byte-identical results
    (the fold is deterministic) — plus the journal compacts and the
    spilled corpora are GC'd once the jobs finish and shutdown is
    clean."""
    daemon, client = _journal_daemon(tmp_path)
    abandoned = False
    try:
        daemon.scheduler.pause()  # acked, never dispatched = mid-batch
        ja = client.submit(corpus=CORPUS_A, config=CFG_OVR)["job_id"]
        jb = client.submit(corpus=CORPUS_B, config=CFG_OVR)["job_id"]
        _abandon(daemon)
        abandoned = True
    finally:
        if not abandoned:
            daemon.close()
    d2, c2 = _journal_daemon(tmp_path)
    try:
        ra = c2.wait(ja, timeout=60.0)
        rb = c2.wait(jb, timeout=60.0)
        assert dict(ra["pairs"]) == oracle(CORPUS_A)
        assert dict(rb["pairs"]) == oracle(CORPUS_B)
        stats = c2.stats()
        assert stats["journal"]["appends"] >= 2
    finally:
        d2.close()
    # Clean shutdown: nothing live -> compacted journal, spills GC'd.
    jdir = tmp_path / "journal"
    assert (jdir / "journal.jsonl").read_bytes() == b""
    assert list((jdir / "corpus").glob("*.bin")) == []


def test_journal_replay_done_job_restored_from_warm_state(tmp_path):
    """A job that FINISHED before the crash, with its result persisted by
    the warm writer, is restored as done — the result fetch crosses the
    restart byte-identically without recomputing."""
    daemon, client = _journal_daemon(
        tmp_path, warm_dir=str(tmp_path / "warm"), warm_every=1
    )
    abandoned = False
    try:
        ack = client.submit(corpus=CORPUS_A, config=CFG_OVR)
        res = client.wait(ack["job_id"], timeout=60.0)
        assert dict(res["pairs"]) == oracle(CORPUS_A)
        daemon.warm.flush()  # the async mark must land before the "kill"
        _abandon(daemon)
        abandoned = True
    finally:
        if not abandoned:
            daemon.close()
    d2, c2 = _journal_daemon(
        tmp_path, warm_dir=str(tmp_path / "warm"), warm_every=1
    )
    try:
        r2 = c2.result(ack["job_id"])
        assert dict(r2["pairs"]) == oracle(CORPUS_A)
        assert r2["cache"] == "result"  # restored, not recomputed
    finally:
        d2.close()


def test_sigkill_daemon_mid_batch_restart_replays_byte_identical(tmp_path):
    """The real thing: a subprocess daemon is SIGKILL'd after acking
    jobs, a fresh daemon on the same journal replays them, and every
    result is byte-identical to the uninterrupted oracle."""
    import signal
    import subprocess
    import sys

    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.path.dirname(os.path.dirname(__file__)),
           "LOCUST_SECRET": SECRET.decode()}
    jdir = str(tmp_path / "journal")

    def spawn(env=env):  # param: the caller owns the env pin (R006)
        proc = subprocess.Popen(
            [sys.executable, "-m", "locust_tpu.serve", "--port", "0",
             "--journal-dir", jdir],
            env=env, stderr=subprocess.PIPE, text=True,
        )
        # The daemon prints "[serve] listening on host:port" once up.
        line = proc.stderr.readline()
        assert "listening on" in line, line
        host, _, port = line.rsplit(" ", 1)[1].strip().partition(":")
        return proc, (host, int(port))

    proc, addr = spawn()
    ids = []
    try:
        client = ServeClient(addr, SECRET, timeout=30.0)
        for corpus in (CORPUS_A, CORPUS_B, CORPUS_A + CORPUS_B):
            ids.append(client.submit(
                corpus=corpus, config=CFG_OVR, no_cache=True
            )["job_id"])
        # SIGKILL right behind the acks: the jobs are somewhere between
        # queued and mid-dispatch — exactly the lost-work window the
        # journal closes.
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=10)
    finally:
        if proc.poll() is None:
            proc.kill()
    proc2, addr2 = spawn()
    try:
        c2 = ServeClient(addr2, SECRET, timeout=30.0)
        wants = [oracle(CORPUS_A), oracle(CORPUS_B),
                 oracle(CORPUS_A + CORPUS_B)]
        for jid, want in zip(ids, wants):
            res = c2.wait(jid, timeout=120.0)
            assert dict(res["pairs"]) == want
        c2.shutdown()
        proc2.wait(timeout=30)
    finally:
        if proc2.poll() is None:
            proc2.kill()


def test_poison_job_bisection_quarantines_only_the_poison(tmp_path):
    """One poison job in a coalesced batch: the batch bisects, the
    innocent neighbors complete exactly, and only the poison job is
    quarantined with the structured poison_job code after its attempts
    budget — it can no longer crash-loop the whole batch."""
    daemon = ServeDaemon(
        secret=SECRET,
        cfg=ServeConfig(max_queue=16, max_batch=4, dispatch_poll_s=0.02,
                        retry_base_s=0.01),
    )
    daemon.serve_in_thread()
    client = ServeClient(daemon.addr, SECRET, timeout=60.0)
    try:
        daemon.scheduler.pause()  # let all four coalesce into one batch
        corpora = [CORPUS_A, CORPUS_B, CORPUS_A * 2, CORPUS_B * 2]
        ids = [
            client.submit(corpus=c, config=CFG_OVR, no_cache=True)["job_id"]
            for c in corpora
        ]
        poison = ids[1]
        p = faultplan.FaultPlan([
            {"site": "serve.dispatch", "action": "error",
             "match": {"job": poison}},
        ], seed=3)
        with faultplan.active_plan(p):
            daemon.scheduler.resume()
            for jid, c in zip(ids, corpora):
                if jid == poison:
                    with pytest.raises(ServeError) as e:
                        client.wait(jid, timeout=60.0)
                    assert e.value.code == "poison_job"
                else:
                    res = client.wait(jid, timeout=60.0)
                    assert dict(res["pairs"]) == oracle(c)
        st = client.status(poison)
        assert st["state"] == "failed"
        assert st["attempts"] == st["max_attempts"] == 4
        assert p.rules[0].fired >= 2  # the batch failed more than once
    finally:
        daemon.close()


def test_deadline_expires_in_queue_structured(tmp_path):
    """A queued job whose deadline passes answers deadline_exceeded from
    the dispatcher's sweep — it never has to reach a dispatch to die."""
    daemon = ServeDaemon(
        secret=SECRET,
        cfg=ServeConfig(max_queue=8, max_batch=2, dispatch_poll_s=0.02),
    )
    daemon.serve_in_thread()
    client = ServeClient(daemon.addr, SECRET, timeout=30.0)
    try:
        daemon.scheduler.pause()  # the job can never dispatch
        ack = client.submit(
            corpus=CORPUS_A, config=CFG_OVR, deadline_s=0.2, no_cache=True
        )
        with pytest.raises(ServeError) as e:
            client.wait(ack["job_id"], timeout=30.0)
        assert e.value.code == "deadline_exceeded"
        st = client.status(ack["job_id"])
        assert st["state"] == "failed"
        assert st["error"]["code"] == "deadline_exceeded"
    finally:
        daemon.close()


def test_deadline_cannot_fit_retry_structured(tmp_path):
    """A failed dispatch whose backoff would land past the deadline is
    not retried — the job answers deadline_exceeded immediately instead
    of burning the client's budget on a doomed wait."""
    daemon = ServeDaemon(
        secret=SECRET,
        cfg=ServeConfig(max_queue=8, max_batch=2, dispatch_poll_s=0.02,
                        retry_base_s=30.0),  # any retry overshoots
    )
    daemon.serve_in_thread()
    client = ServeClient(daemon.addr, SECRET, timeout=30.0)
    try:
        p = faultplan.FaultPlan(
            [{"site": "serve.dispatch", "action": "error", "times": 1}],
            seed=3,
        )
        with faultplan.active_plan(p):
            ack = client.submit(
                corpus=CORPUS_A, config=CFG_OVR, deadline_s=5.0,
                no_cache=True,
            )
            with pytest.raises(ServeError) as e:
                client.wait(ack["job_id"], timeout=30.0)
        assert e.value.code == "deadline_exceeded"
    finally:
        daemon.close()


def test_wait_timeout_error_reports_state_and_attempts(rig):
    """Satellite: the client's bounded wait names the daemon-reported
    state and attempt budget instead of a bare 'still running'."""
    daemon, client = rig
    daemon.scheduler.pause()
    ack = client.submit(corpus=CORPUS_A, config=CFG_OVR, no_cache=True)
    t0 = time.monotonic()
    with pytest.raises(TimeoutError) as e:
        client.wait(ack["job_id"], timeout=0.4, poll_s=0.02)
    assert time.monotonic() - t0 < 5.0
    msg = str(e.value)
    assert "queued" in msg and "attempt 0/4" in msg
    daemon.scheduler.resume()


def test_parse_spec_budget_validation():
    import base64

    good = {"corpus_b64": base64.b64encode(b"a b c\n").decode()}
    for req, code in [
        ({"deadline_s": 0, **good}, "bad_spec"),
        ({"deadline_s": "soon", **good}, "bad_spec"),
        ({"deadline_s": 1e9, **good}, "bad_spec"),
        ({"max_attempts": 0, **good}, "bad_spec"),
        ({"max_attempts": 99, **good}, "bad_spec"),
    ]:
        with pytest.raises(ValueError) as e:
            parse_spec(req)
        assert str(e.value).partition("\n")[0] == code
    spec, _ = parse_spec({"deadline_s": 2.5, "max_attempts": 2, **good})
    assert spec.deadline_s == 2.5 and spec.max_attempts == 2


def test_journal_append_failure_rejects_structured(rig, tmp_path,
                                                   monkeypatch):
    """A REAL journal append failure (disk full, permissions) must reject
    the submit with the structured journal_failed code — acking
    unjournaled work would silently demote the durability promise."""
    daemon, client = rig
    from locust_tpu.serve.journal import JobJournal

    daemon.journal = JobJournal(str(tmp_path / "j"))

    def boom(job, corpus):
        raise OSError("disk full")

    monkeypatch.setattr(daemon.journal, "append_admit", boom)
    with pytest.raises(ServeError) as e:
        client.submit(corpus=CORPUS_B, config=CFG_OVR, no_cache=True)
    assert e.value.code == "journal_failed"
    daemon.journal.close()
    daemon.journal = None
    # The rejected job left no residue: a fresh submit runs exact.
    ack = client.submit(corpus=CORPUS_B, config=CFG_OVR, no_cache=True)
    res = client.wait(ack["job_id"], timeout=60.0)
    assert dict(res["pairs"]) == oracle(CORPUS_B)


def test_scheduler_requeue_and_expire():
    s = FairScheduler(max_queue=4, max_batch=2)
    j1, j2 = mk_job("a"), mk_job("b")
    s.admit(j1)
    s.admit(j2)
    # Requeued jobs hold their admission slot (caps see them).
    popped = s.next_batch(const_key, timeout=0.1)
    assert popped is not None
    for j in popped:
        assert s.requeue(j, not_before=time.monotonic() + 30.0)
    assert s.depth() == 2
    stats = s.stats()
    assert stats["retrying"] == len(popped)
    # Unripe delayed jobs never pop...
    got = s.next_batch(const_key, timeout=0.05)
    assert got is None or all(j not in popped for j in got)
    # ...but expire() reaps them once their deadline passes.
    spec = JobSpec(tenant="t", workload="wordcount", cfg=CFG,
                   deadline_s=0.001)
    j3 = Job(job_id="dl", spec=spec, corpus_digest="d", n_lines=1,
             n_blocks=1, bucket=1)
    time.sleep(0.01)
    assert s.requeue(j3, not_before=time.monotonic() + 30.0)
    dead = s.expire(time.monotonic())
    assert j3 in dead
    s.stop()
    assert s.requeue(j1, 0.0) is False  # stopped: caller fails structured


def test_journal_compaction_never_drops_concurrent_admit(tmp_path):
    """Review-round regression: compaction decides liveness from the
    journal's OWN records under its lock — an admit fsync'd by a
    handler thread while the dispatcher compacts must survive the
    rewrite (and its spill the GC).  The old design snapshotted the
    daemon's job table first and dropped anything admitted after."""
    from locust_tpu.serve.journal import JobJournal

    j = JobJournal(str(tmp_path / "j"))
    spec = JobSpec(tenant="t", workload="wordcount", cfg=CFG)
    import hashlib

    def mk(job_id, corpus):
        return Job(
            job_id=job_id, spec=spec,
            corpus_digest=hashlib.sha256(corpus).hexdigest(),
            n_lines=1, n_blocks=1, bucket=1, config_overrides={},
        ), corpus

    done_job, done_corpus = mk("done0", b"aa bb\n")
    j.append_admit(done_job, done_corpus)
    j.append_state("done0", "done")
    live_job, live_corpus = mk("live0", b"cc dd\n")
    j.append_admit(live_job, live_corpus)  # the "concurrent" admit
    j.compact()
    entries = {e.admit["job_id"]: e for e in j.replay()}
    assert list(entries) == ["live0"]  # terminal retired, live kept
    assert entries["live0"].terminal is None
    assert j.read_spill(live_job.corpus_digest) == live_corpus
    assert j.read_spill(done_job.corpus_digest) is None  # GC'd
    # Re-asserted liveness past a terminal record (the done-but-
    # unpersisted replay path): a fresh admit AFTER a done record makes
    # the job live again for both compact and replay.
    j.append_state("live0", "done")
    j.append_admit(live_job, live_corpus)
    j.compact()
    entries = {e.admit["job_id"]: e for e in j.replay()}
    assert list(entries) == ["live0"]
    j.close()


def test_journal_torn_append_does_not_glue_next_record(tmp_path):
    """Review-round regression: a torn (chaos-crash) append leaves no
    trailing newline; the NEXT append must start on a fresh line or an
    fsync'd acked record glues onto the debris and replay drops BOTH."""
    from locust_tpu.serve.journal import JobJournal
    import hashlib

    j = JobJournal(str(tmp_path / "j"))
    spec = JobSpec(tenant="t", workload="wordcount", cfg=CFG)

    def mk(job_id, corpus):
        return Job(
            job_id=job_id, spec=spec,
            corpus_digest=hashlib.sha256(corpus).hexdigest(),
            n_lines=1, n_blocks=1, bucket=1, config_overrides={},
        ), corpus

    doomed, doomed_corpus = mk("torn0", b"aa bb\n")
    p = faultplan.FaultPlan(
        [{"site": "serve.journal", "action": "crash", "times": 1}], seed=7
    )
    with faultplan.active_plan(p):
        with pytest.raises(faultplan.FaultCrash):
            j.append_admit(doomed, doomed_corpus)
    survivor, survivor_corpus = mk("live1", b"cc dd\n")
    j.append_admit(survivor, survivor_corpus)  # same process, post-torn
    entries = {e.admit["job_id"] for e in j.replay()}
    assert "live1" in entries
    j.close()
    # And across a restart: a NEW journal on the same file also repairs
    # the dirty tail before its first append.
    j2 = JobJournal(str(tmp_path / "j2"))
    with faultplan.active_plan(faultplan.FaultPlan(
        [{"site": "serve.journal", "action": "crash", "times": 1}], seed=7
    )):
        with pytest.raises(faultplan.FaultCrash):
            j2.append_admit(doomed, doomed_corpus)
    j2.close()
    j3 = JobJournal(str(tmp_path / "j2"))  # inherits the torn tail
    j3.append_admit(survivor, survivor_corpus)
    assert "live1" in {e.admit["job_id"] for e in j3.replay()}
    j3.close()


def test_cancelled_job_replays_cancelled_code_across_restart(tmp_path):
    """Review-round regression: a cancelled job's structured code must
    survive the restart — replay rewrote it to dispatch_failed when the
    journal record carried no error payload."""
    daemon, client = _journal_daemon(tmp_path)
    abandoned = False
    try:
        daemon.scheduler.pause()
        jid = client.submit(corpus=CORPUS_A, config=CFG_OVR,
                            no_cache=True)["job_id"]
        assert client.cancel(jid)["cancelled"] is True
        _abandon(daemon)
        abandoned = True
    finally:
        if not abandoned:
            daemon.close()
    d2, c2 = _journal_daemon(tmp_path)
    try:
        with pytest.raises(ServeError) as e:
            c2.result(jid)
        assert e.value.code == "cancelled"
        assert c2.status(jid)["state"] == "cancelled"
    finally:
        d2.close()


# ----------------------------------------- scale-out worker pool (ISSUE 11)
#
# The placement layer (serve/pool.py) + the distributor worker's
# serve_batch surface: placement units, the loopback multi-worker
# battery (byte-identical to single-worker), the cache-affinity and
# spill-over pins, and large-job sharding through the engine's combine.


def _pool_rig(n_workers=2, **cfg_kw):
    from locust_tpu.distributor.worker import Worker

    ws = []
    for _ in range(n_workers):
        w = Worker(secret=SECRET, serve=True)
        w.serve_in_thread()
        ws.append(w)
    cfg = ServeConfig(
        max_queue=16, max_batch=4, dispatch_poll_s=0.02, retry_base_s=0.02,
        workers=tuple(f"127.0.0.1:{w.addr[1]}" for w in ws),
        **cfg_kw,
    )
    daemon = ServeDaemon(secret=SECRET, cfg=cfg)
    daemon.serve_in_thread()
    return daemon, ws, ServeClient(daemon.addr, SECRET, timeout=60.0)


def _stop_workers(ws):
    for w in ws:
        w._shutdown.set()
        try:
            w._sock.close()
        except OSError:
            pass


def _pool_oracle(corpus: bytes) -> dict:
    return dict(py_wordcount(corpus.splitlines(),
                             max_tokens_per_line=8, key_width=16))


def test_worker_pool_place_affinity_spillover_units(tmp_path):
    from locust_tpu.serve.pool import WorkerPool

    pool = WorkerPool(("h1:1", "h2:2"), SECRET,
                      spill_dir=str(tmp_path / "sp"))
    key = (("wordcount", "fp"), 1)
    w = pool.place(key)
    assert w is not None and w.idx == 0  # least-loaded, ties by index
    pool.mark_warm(w, key)
    pool.release(w)
    w2 = pool.place(key)
    assert w2.idx == 0  # affinity: the warm worker wins
    # Affine worker saturated (slot held): spill-over to least-loaded.
    w3 = pool.place(key)
    assert w3.idx == 1
    # Everyone saturated: None = the local-engine floor.
    assert pool.place(key) is None
    st = pool.stats()
    assert st["affinity_hits"] == 1
    assert st["spill_overs"] == 1
    assert st["local_fallbacks"] == 1
    # exclude: the shard fan-out never double-places one worker.
    pool.release(w2)
    pool.release(w3)
    assert pool.place(key, exclude={0}).idx == 1
    pool.close(timeout=1.0)
    assert pool.place(key) is None  # closed pools never place


def test_worker_pool_rejects_bad_addr_and_empty(tmp_path):
    from locust_tpu.serve.pool import WorkerPool, parse_worker_addr

    with pytest.raises(ValueError):
        parse_worker_addr("no-port-here")
    with pytest.raises(ValueError):
        WorkerPool((), SECRET, spill_dir=str(tmp_path / "sp"))
    assert parse_worker_addr("127.0.0.1:80") == ("127.0.0.1", 80)
    assert parse_worker_addr(("h", 9)) == ("h", 9)


def test_shard_ranges_cover_align_and_are_stable():
    from locust_tpu.serve.pool import shard_ranges, stable_shard_id

    for n_lines in (1, 7, 8, 9, 63, 64, 65, 257):
        for shards in (1, 2, 3, 4):
            rs = shard_ranges(n_lines, 8, shards)
            assert rs[0][0] == 0 and rs[-1][1] == n_lines
            assert len(rs) <= shards
            for (a, b), (a2, _b2) in zip(rs, rs[1:]):
                assert b == a2
            for a, b in rs:
                assert a % 8 == 0 and b > a
    assert stable_shard_id("j", 0, 8) == stable_shard_id("j", 0, 8)
    assert stable_shard_id("j", 0, 8) != stable_shard_id("j", 8, 16)


def test_next_batches_pops_disjoint_batches_in_fair_order():
    s = FairScheduler(max_queue=16, max_batch=2)
    a1, a2 = mk_job("a"), mk_job("a")
    b1, b2 = mk_job("b"), mk_job("b")
    for j in (a1, a2, b1, b2):
        s.admit(j)
    batches = s.next_batches(const_key, max_batches=2, timeout=0.1)
    # Tenant "a" is first (vt tie broken by name) and coalesces its two
    # jobs; the SECOND batch is picked after "a" was charged, so it is
    # tenant "b"'s — exactly two sequential next_batch picks.
    assert [j.job_id for j in batches[0]] == [a1.job_id, a2.job_id]
    assert [j.job_id for j in batches[1]] == [b1.job_id, b2.job_id]
    assert s.stats()["dispatched"] == 4
    assert s.next_batches(const_key, max_batches=2, timeout=0.05) is None


def test_worker_serve_batch_requires_opt_in():
    from locust_tpu.distributor.worker import Worker

    w = Worker(secret=SECRET)  # no serve=True
    assert w._handle({"cmd": "serve_stats"})["status"] == "error"
    assert "not enabled" in w._handle({"cmd": "serve_batch"})["error"]


def test_pool_mixed_tenant_stream_byte_identical_to_single_worker():
    corpora = [
        (f"w{i} alpha beta\ngamma w{i} delta\n" * 4).encode()
        for i in range(8)
    ]
    big = b"".join(
        f"t{i % 29} common x{i % 7}\n".encode() for i in range(80)
    )

    def run(client):
        ids = [
            client.submit(corpus=c, config=CFG_OVR,
                          tenant=f"t{i % 3}")["job_id"]
            for i, c in enumerate(corpora)
        ]
        out = []
        for j in ids:
            r = client.wait(j, timeout=120.0)
            out.append((r["pairs"], r["distinct"], r["truncated"],
                        r["overflow_tokens"]))
        # The big job goes out over a DRAINED pool so its shard fan-out
        # deterministically finds both workers placeable (under load it
        # may legitimately fall back to fewer shards or local).
        big_id = client.submit(corpus=big, config=CFG_OVR, tenant="big",
                               weight=2.0)["job_id"]
        r = client.wait(big_id, timeout=120.0)
        out.append((r["pairs"], r["distinct"], r["truncated"],
                    r["overflow_tokens"]))
        return out, big_id

    daemon, ws, client = _pool_rig(shard_min_blocks=4, shard_max=2)
    try:
        pooled, big_id = run(client)
        big_st = client.status(big_id)
        pool_stats = client.stats()["pool"]
    finally:
        daemon.close()
        _stop_workers(ws)
    single = ServeDaemon(
        secret=SECRET,
        cfg=ServeConfig(max_queue=16, max_batch=4, dispatch_poll_s=0.02),
    )
    single.serve_in_thread()
    c2 = ServeClient(single.addr, SECRET, timeout=60.0)
    try:
        local, _ = run(c2)
    finally:
        single.close()
    # Byte-identical across the pool, AND exact against the host oracle.
    assert pooled == local
    for (pairs, _d, _t, _o), c in zip(pooled, corpora + [big]):
        assert dict(pairs) == _pool_oracle(c)
    # The pool actually served (placements happened) and the large job
    # fanned out across both workers.
    assert sum(pool_stats["placements"]) > 0
    assert big_st["shards"] == 2 and big_st["placed_on"].startswith("shard:")


def test_pool_affinity_repeat_jobs_land_warm_compiles_unchanged():
    from locust_tpu.distributor.master import rpc

    daemon, ws, client = _pool_rig()
    try:
        wave1 = [(f"one{i} aa bb\ncc dd e{i}\n" * 3).encode()
                 for i in range(4)]
        for c in wave1:  # drained one at a time: deterministic placement
            client.wait(client.submit(corpus=c, config=CFG_OVR)["job_id"],
                        timeout=120.0)
        def worker_stats():
            return [
                rpc(("127.0.0.1", w.addr[1]), {"cmd": "serve_stats"},
                    SECRET, timeout=10.0)
                for w in ws
            ]
        compiles1 = [s["exec_cache"]["compiles"] for s in worker_stats()]
        hits_before = client.stats()["pool"]["affinity_hits"]
        warm_idx = max(range(len(ws)), key=lambda i: compiles1[i])
        warm_name = f"127.0.0.1:{ws[warm_idx].addr[1]}"
        # NEW corpora, same shape bucket: every one must land on the
        # warm worker (affinity pin) without a single fresh compile.
        wave2 = [(f"two{i} qq rr\nss tt u{i}\n" * 3).encode()
                 for i in range(4)]
        for c in wave2:
            jid = client.submit(corpus=c, config=CFG_OVR)["job_id"]
            res = client.wait(jid, timeout=120.0)
            st = client.status(jid)
            assert st["placed_on"] == warm_name
            assert res["cache"] == "warm"
            assert dict(res["pairs"]) == _pool_oracle(c)
        compiles2 = [s["exec_cache"]["compiles"] for s in worker_stats()]
        assert sum(compiles2) == sum(compiles1), (compiles1, compiles2)
        assert client.stats()["pool"]["affinity_hits"] > hits_before
    finally:
        daemon.close()
        _stop_workers(ws)


def test_pool_spillover_saturated_affine_worker_doesnt_block():
    daemon, ws, client = _pool_rig()
    try:
        warm = (b"warm aa bb\ncc dd ee\n" * 3)
        jid = client.submit(corpus=warm, config=CFG_OVR)["job_id"]
        client.wait(jid, timeout=120.0)
        warm_name = client.status(jid)["placed_on"]
        victim = next(
            w for w in daemon.pool.workers if w.name == warm_name
        )
        # Saturate the affine worker (its slot held as if mid-dispatch):
        # the next same-bucket job must SPILL to the other worker, not
        # queue behind the busy one.
        with daemon.pool._lock:
            daemon.pool._inflight[victim.idx] = daemon.pool.max_inflight
        try:
            c2 = b"spill ff gg\nhh ii jj\n" * 3
            j2 = client.submit(corpus=c2, config=CFG_OVR)["job_id"]
            res = client.wait(j2, timeout=120.0)
            st = client.status(j2)
            assert dict(res["pairs"]) == _pool_oracle(c2)
            assert st["placed_on"] not in (warm_name, "local")
            assert client.stats()["pool"]["spill_overs"] >= 1
        finally:
            with daemon.pool._lock:
                daemon.pool._inflight[victim.idx] = 0
    finally:
        daemon.close()
        _stop_workers(ws)


def test_pool_seed_affinity_survives_daemon_restart():
    from locust_tpu.distributor.worker import Worker

    w = Worker(secret=SECRET, serve=True)
    w.serve_in_thread()
    addr = (f"127.0.0.1:{w.addr[1]}",)
    corpus = b"seed aa bb\ncc dd ee\n" * 3
    d1 = ServeDaemon(secret=SECRET, cfg=ServeConfig(
        dispatch_poll_s=0.02, workers=addr))
    d1.serve_in_thread()
    c1 = ServeClient(d1.addr, SECRET, timeout=60.0)
    try:
        c1.wait(c1.submit(corpus=corpus, config=CFG_OVR)["job_id"],
                timeout=120.0)
    finally:
        d1.close()
    # A NEW daemon against the still-warm worker re-learns its affinity
    # home from the serve_stats warm-cache RPC at startup.
    d2 = ServeDaemon(secret=SECRET, cfg=ServeConfig(
        dispatch_poll_s=0.02, workers=addr))
    c2 = ServeClient(d2.addr, SECRET, timeout=60.0)
    d2.serve_in_thread()
    try:
        spec = JobSpec(tenant="x", workload="wordcount", cfg=CFG)
        key = (ExecutableCache.engine_key(spec), 1)
        assert d2.pool.preferred(key) == (addr[0],)
        jid = c2.submit(corpus=corpus + b"more ff\n",
                        config=CFG_OVR)["job_id"]
        res = c2.wait(jid, timeout=120.0)
        assert res["cache"] == "warm"  # the worker's executable was warm
        assert d2.pool.stats()["affinity_hits"] >= 1
    finally:
        d2.close()
        _stop_workers([w])


def test_pool_close_stops_placements_and_executor():
    daemon, ws, client = _pool_rig()
    try:
        jid = client.submit(corpus=b"close aa bb\n" * 3,
                            config=CFG_OVR)["job_id"]
        client.wait(jid, timeout=120.0)
    finally:
        daemon._shutdown.set()
        daemon.close()
        _stop_workers(ws)
    assert daemon.pool.place((("wordcount", "fp"), 1)) is None
    with pytest.raises(RuntimeError):
        daemon.pool.submit(lambda: None)


# --------------------------------------------------------------- plan jobs


def _tfidf_plan_doc():
    from locust_tpu.plan import tfidf_plan

    return tfidf_plan(2).to_doc()


def _plan_oracle(corpus: bytes) -> bytes:
    from locust_tpu.plan import tfidf_plan
    from locust_tpu.plan.compile import compile_plan

    return compile_plan(tfidf_plan(2), CFG).run_corpus(corpus).output


def test_daemon_plan_submit_roundtrip(rig):
    """A plan submit answers the pipeline's sink-rendered output as ONE
    (bytes, 0) pair flagged ``plan`` — byte-identical to the locally
    compiled plan over the same corpus (docs/PLAN.md)."""
    _, client = rig
    ack = client.submit(
        corpus=CORPUS_A, config=CFG_OVR, plan=_tfidf_plan_doc()
    )
    assert ack["state"] == "queued" and not ack["cached"]
    res = client.wait(ack["job_id"], timeout=120.0)
    assert res["plan"] is True
    assert len(res["pairs"]) == 1 and res["pairs"][0][1] == 0
    assert res["pairs"][0][0] == _plan_oracle(CORPUS_A)
    st = client.status(ack["job_id"])
    assert st["workload"] == "plan" and st["placed_on"] == "local"


def test_daemon_plan_repeat_hits_result_cache_by_plan_fingerprint(rig):
    """The result cache keys off the PLAN fingerprint: a repeat of the
    same (plan, config, corpus) answers at admission; a DIFFERENT plan
    over the same corpus+config recomputes."""
    _, client = rig
    a1 = client.submit(corpus=CORPUS_A, config=CFG_OVR,
                       plan=_tfidf_plan_doc())
    client.wait(a1["job_id"], timeout=120.0)
    a2 = client.submit(corpus=CORPUS_A, config=CFG_OVR,
                       plan=_tfidf_plan_doc())
    assert a2["cached"] is True
    res = client.result(a2["job_id"])
    assert res["plan"] is True
    assert res["pairs"][0][0] == _plan_oracle(CORPUS_A)
    # A different lines_per_doc is a different plan fingerprint: miss.
    from locust_tpu.plan import tfidf_plan

    a3 = client.submit(corpus=CORPUS_A, config=CFG_OVR,
                       plan=tfidf_plan(3).to_doc())
    assert a3["cached"] is False
    client.wait(a3["job_id"], timeout=120.0)


def test_daemon_plan_repeat_new_bytes_is_warm_executable_hit(rig):
    """Same plan over NEW bytes skips lowering: the warm-executable
    cache holds the CompiledPlan keyed by (plan fp, cfg fp, bucket) and
    the repeat reports cache='warm' with compiles unchanged."""
    daemon, client = rig
    a1 = client.submit(corpus=CORPUS_A, config=CFG_OVR,
                       plan=_tfidf_plan_doc(), no_cache=True)
    client.wait(a1["job_id"], timeout=120.0)
    compiles = daemon.executables.stats()["compiles"]
    corpus2 = CORPUS_A.replace(b"alpha", b"omega")
    a2 = client.submit(corpus=corpus2, config=CFG_OVR,
                       plan=_tfidf_plan_doc(), no_cache=True)
    res = client.wait(a2["job_id"], timeout=120.0)
    assert res["cache"] == "warm"
    assert daemon.executables.stats()["compiles"] == compiles
    assert res["pairs"][0][0] == _plan_oracle(corpus2)


def test_daemon_plan_bad_spec_is_structured(rig):
    _, client = rig
    with pytest.raises(ServeError) as e:
        client.submit(corpus=CORPUS_A, plan={
            "plan_version": 1,
            "nodes": [{"id": "a", "kind": "window", "op": "text"}],
        })
    assert e.value.code == "bad_spec"
    assert "unknown kind" in str(e.value)
    # The client mirrors the daemon rule instead of silently dropping a
    # conflicting workload (review finding).
    with pytest.raises(ValueError, match="not both"):
        client.submit(corpus=CORPUS_A, workload="other",
                      plan=_tfidf_plan_doc())
    # The client API sends plan OR workload; a raw peer naming both is
    # still rejected structured at parse_spec.
    import base64

    resp = client.rpc({
        "cmd": "submit", "workload": "wordcount",
        "plan": _tfidf_plan_doc(),
        "corpus_b64": base64.b64encode(CORPUS_A).decode(),
    })
    assert resp["status"] == "error" and resp["code"] == "bad_spec"


def test_daemon_plan_jobs_never_coalesce_or_shard(rig):
    daemon, _ = rig
    from locust_tpu.serve.jobs import JobSpec, PLAN_WORKLOAD
    from locust_tpu.plan import tfidf_plan

    spec = JobSpec(tenant="t", workload=PLAN_WORKLOAD, cfg=CFG,
                   plan=tfidf_plan(2).canonical_json())
    job = Job(job_id="p1", spec=spec, corpus_digest="d", n_lines=999,
              n_blocks=256, bucket=256)
    other = Job(job_id="p2", spec=spec, corpus_digest="d", n_lines=999,
                n_blocks=256, bucket=256)
    assert daemon._batch_key(job) != daemon._batch_key(other)  # solo
    assert not daemon._shardable(job)  # plan jobs stay local
    # and the engine key folds the plan fingerprint in
    key = ExecutableCache.engine_key(spec)
    assert spec.plan_fingerprint() in key


def test_daemon_plan_deterministic_error_fails_structured_not_poison(rig):
    """A pagerank plan over a corpus that does not parse as an edge
    list is a DETERMINISTIC rejection: it must answer structured
    bad_spec on the first dispatch, not burn the retry ladder and end
    as a misleading poison_job (review finding)."""
    from locust_tpu.plan import pagerank_plan

    _, client = rig
    ack = client.submit(
        corpus=b"alpha beta gamma\nnot an edge list\n",
        plan=pagerank_plan(3).to_doc(),
    )
    with pytest.raises(ServeError) as e:
        client.wait(ack["job_id"], timeout=60.0)
    assert e.value.code == "bad_spec"
    assert "edge list" in str(e.value)
    st = client.status(ack["job_id"])
    assert st["state"] == "failed"
    assert st["attempts"] == 0  # never entered the retry ladder
    # Corpus-derived dense state is bounded on the serve path: a tiny
    # edge list naming a huge node id rejects structured, never an OOM.
    a2 = client.submit(corpus=b"0 2000000000\n",
                       plan=pagerank_plan(3).to_doc())
    with pytest.raises(ServeError) as e:
        client.wait(a2["job_id"], timeout=60.0)
    assert e.value.code == "bad_spec"
    assert "cap" in str(e.value)


def test_daemon_plan_job_replays_from_journal(tmp_path):
    """Durability: a journaled plan job SIGKILL'd mid-dispatch replays
    under its original id after restart, byte-identical (the WAL admit
    record carries the whole plan document)."""
    from locust_tpu.utils import faultplan

    jd = str(tmp_path / "journal")
    daemon = ServeDaemon(secret=SECRET, cfg=ServeConfig(
        journal_dir=jd, dispatch_poll_s=0.02))
    daemon.serve_in_thread()
    client = ServeClient(daemon.addr, SECRET, timeout=60.0)
    p = faultplan.FaultPlan(
        [{"site": "serve.dispatch", "action": "delay",
          "delay_s": 30.0, "times": 1}], seed=3,
    )
    with faultplan.active_plan(p):
        ack = client.submit(corpus=CORPUS_A, config=CFG_OVR,
                            plan=_tfidf_plan_doc(), no_cache=True)
        serve_abandon(daemon)
    d2 = ServeDaemon(secret=SECRET, cfg=ServeConfig(
        journal_dir=jd, dispatch_poll_s=0.02))
    d2.serve_in_thread()
    c2 = ServeClient(d2.addr, SECRET, timeout=60.0)
    try:
        res = c2.wait(ack["job_id"], timeout=120.0)
        assert res["plan"] is True
        assert res["pairs"][0][0] == _plan_oracle(CORPUS_A)
    finally:
        d2.close()


# ---------------------------------- distributed plan execution (ISSUE 16)
#
# The plan layer's scale-out path (daemon._dispatch_plan_distributed +
# plan/distribute.py + the workers' plan_stage surface): shape
# recognition units, the atomic partition spill format, distributed-vs-
# solo byte identity for every covered fold, the local-engine floor, and
# WAL-replay resume from journaled stage records (docs/PLAN.md
# "Distributed execution").  The chaos side — stage crash/error/delay,
# partition drop/corrupt, stale-epoch fencing — lives in
# tests/test_faults.py.


def _compiled_oracle(plan, corpus: bytes) -> bytes:
    from locust_tpu.plan.compile import compile_plan

    return compile_plan(plan, CFG).run_corpus(corpus).output


def _join_plan(combine="sum", deep=False):
    """A join tree of wordcount fold leaves; deep=True chains a second
    join on top (3 stages source->sink, the deep-pipeline shape)."""
    from locust_tpu.plan.nodes import Plan, node

    nodes = [
        node("c1", "source", "text"),
        node("m1", "map", "tokenize_count", ("c1",)),
        node("s1", "shuffle", "by_key", ("m1",)),
        node("r1", "reduce", "sum", ("s1",)),
        node("c2", "source", "text"),
        node("m2", "map", "tokenize_count", ("c2",)),
        node("s2", "shuffle", "by_key", ("m2",)),
        node("r2", "reduce", "sum", ("s2",)),
        node("j1", "join", "inner", ("r1", "r2"), combine=combine),
    ]
    if deep:
        nodes += [
            node("c3", "source", "text"),
            node("m3", "map", "tokenize_count", ("c3",)),
            node("s3", "shuffle", "by_key", ("m3",)),
            node("r3", "reduce", "sum", ("s3",)),
            node("j2", "join", "inner", ("j1", "r3"), combine="mul"),
            node("out", "sink", "table", ("j2",)),
        ]
    else:
        nodes.append(node("out", "sink", "table", ("j1",)))
    return Plan(tuple(nodes))


def test_distribute_plan_shape_recognizes_covered_spines():
    """plan_shape answers (shape, reason): a StageShape / JoinShape /
    IterateShape for every covered plan, and (None, reason) naming WHY
    for everything else (None = the solo path, byte-identical by
    refusal — never an error, never silent)."""
    from locust_tpu.plan import (
        index_plan,
        pagerank_plan,
        tfidf_plan,
        wordcount_plan,
    )
    from locust_tpu.plan.distribute import (
        IterateShape,
        JoinShape,
        plan_shape,
    )
    from locust_tpu.plan.nodes import Plan, node

    wc, reason = plan_shape(wordcount_plan())
    assert reason is None and wc.node_fp
    assert (wc.fold, wc.score, wc.sink_op) == ("wordcount", False, "table")
    tf, _ = plan_shape(tfidf_plan(2))
    assert (tf.fold, tf.lines_per_doc, tf.score, tf.sink_op) == \
        ("tf", 2, True, "tfidf")
    ix, _ = plan_shape(index_plan(3))
    assert (ix.fold, ix.lines_per_doc, ix.sink_op) == ("index", 3, "postings")
    pr, reason = plan_shape(pagerank_plan(3, damping=0.9))
    assert reason is None and isinstance(pr, IterateShape)
    assert (pr.num_iters, pr.damping, pr.sink_op) == (3, 0.9, "ranks")
    jn, reason = plan_shape(_join_plan("min"))
    assert reason is None and isinstance(jn, JoinShape)
    assert (jn.depth, jn.sink_op, jn.tree.combine) == (1, "table", "min")
    assert len(jn.leaves) == 2  # distinct spines = distinct leaves
    deep, _ = plan_shape(_join_plan(deep=True))
    assert deep.depth == 2 and len(deep.leaves) == 3
    # A named-input join is valid (run() with a data dict) but not a
    # covered shape: structured refusal naming the reason, not an error.
    wide = Plan((
        node("c1", "source", "text"),
        node("m1", "map", "tokenize_count", ("c1",)),
        node("s1", "shuffle", "by_key", ("m1",)),
        node("r1", "reduce", "sum", ("s1",)),
        node("c2", "source", "text", input="aux"),
        node("m2", "map", "tokenize_count", ("c2",)),
        node("s2", "shuffle", "by_key", ("m2",)),
        node("r2", "reduce", "sum", ("s2",)),
        node("j", "join", "inner", ("r1", "r2")),
        node("out", "sink", "table", ("j",)),
    ))
    sh, reason = plan_shape(wide)
    assert sh is None and reason == "source_named_input"


def test_distribute_partition_publish_read_roundtrip(tmp_path):
    """The shuffle spill discipline: composite keys round-trip through
    the LKVB encode, publish is atomic with a sha over the bytes, every
    partition file exists (absence means LOSS, not emptiness), and the
    read gate rejects corrupt or missing files loudly."""
    from locust_tpu.plan import distribute

    # key codec: raw words for wordcount, word NUL doc for composites.
    assert distribute.encode_key("wordcount", b"alpha") == b"alpha"
    enc = distribute.encode_key("tf", (b"alpha", 7))
    assert distribute.decode_key("tf", enc) == (b"alpha", 7)
    assert distribute.partition_key_width(CFG, "wordcount") == 16
    assert distribute.partition_key_width(CFG, "tf") == 16 + 11
    # The partitioner is deterministic and total.
    parts = {distribute.partition_of(enc, 4) for _ in range(3)}
    assert len(parts) == 1 and parts.pop() in range(4)

    pairs = [(distribute.encode_key("tf", (w, d)), c)
             for w, d, c in ((b"alpha", 0, 3), (b"beta", 1, 2),
                             (b"gamma", 0, 1), (b"alpha", 1, 5))]
    refs = distribute.publish_split(str(tmp_path), "fp0", 0, 0, pairs, 3)
    assert [r["part"] for r in refs] == [0, 1, 2]
    assert sum(r["pairs"] for r in refs) == len(pairs)
    got = {}
    for ref in refs:
        assert os.path.exists(ref["path"])  # empty partitions included
        rows = distribute.read_partition(
            ref["path"], ref["sha256"],
            distribute.partition_key_width(CFG, "tf"))
        distribute.merge_pairs(got, rows)
    assert {distribute.decode_key("tf", k): v for k, v in got.items()} == \
        {(b"alpha", 0): 3, (b"beta", 1): 2, (b"gamma", 0): 1,
         (b"alpha", 1): 5}
    # Corruption trips the sha gate; a vanished file is the same loss.
    victim = next(r for r in refs if r["pairs"])
    with open(victim["path"], "r+b") as f:
        f.write(b"\xff\xff")
    with pytest.raises(ValueError, match="sha mismatch"):
        distribute.read_partition(victim["path"], victim["sha256"], 27)
    os.unlink(victim["path"])
    with pytest.raises(ValueError, match="unreadable"):
        distribute.read_partition(victim["path"], victim["sha256"], 27)


def test_pool_distributed_plan_byte_identical_every_covered_fold():
    """The tentpole identity pin: each covered fold's plan submitted
    against a 2-worker pool runs DISTRIBUTED (placed_on names the
    workers) and answers byte-for-byte what the solo compiled plan
    renders over the same corpus."""
    from locust_tpu.plan import index_plan, tfidf_plan, wordcount_plan

    daemon, ws, client = _pool_rig(shard_min_blocks=1)
    corpus = CORPUS_A + CORPUS_B
    try:
        for plan in (tfidf_plan(2), wordcount_plan(), index_plan(2)):
            ack = client.submit(corpus=corpus, config=CFG_OVR,
                                plan=plan.to_doc(), no_cache=True)
            res = client.wait(ack["job_id"], timeout=120.0)
            assert res["plan"] is True
            assert res["pairs"][0][0] == _compiled_oracle(plan, corpus)
            st = client.status(ack["job_id"])
            assert st["placed_on"].startswith("plan:")
        pl = client.stats()["pool"]["plan"]
        assert pl["stages"] >= 6  # >= (map+reduce) x 3 plans
        assert pl["recomputes"] == 0 and pl["speculated"] == 0
    finally:
        _stop_workers(ws)
        daemon.close()


def test_pool_distributed_plan_local_floor_cases():
    """Every refusal lands on the solo local engine, never an error —
    and never silently: each demotion bumps the plan_solo_fallbacks
    counter (once-per-reason logged on the daemon).  Cases: a job under
    the shard floor, a pool with a single live worker (a distributed
    run needs >= 2), and a join whose fold overflows the configured
    table (the identity gate — distributed can't reproduce solo's
    truncation order, so it must not try)."""
    from locust_tpu.plan import tfidf_plan

    # Under the shard floor: a 2-block corpus with shard_min_blocks=8.
    daemon, ws, client = _pool_rig(shard_min_blocks=8)
    try:
        ack = client.submit(corpus=CORPUS_A, config=CFG_OVR,
                            plan=tfidf_plan(2).to_doc(), no_cache=True)
        res = client.wait(ack["job_id"], timeout=120.0)
        assert res["pairs"][0][0] == _compiled_oracle(tfidf_plan(2),
                                                      CORPUS_A)
        assert client.status(ack["job_id"])["placed_on"] == "local"
    finally:
        _stop_workers(ws)
        daemon.close()
    # One worker: the coordinator can't place two stages, releases the
    # slot and takes the solo floor mid-dispatch — counted, not silent.
    daemon, ws, client = _pool_rig(n_workers=1, shard_min_blocks=1)
    try:
        ack = client.submit(corpus=CORPUS_A, config=CFG_OVR,
                            plan=tfidf_plan(2).to_doc(), no_cache=True)
        res = client.wait(ack["job_id"], timeout=120.0)
        assert res["pairs"][0][0] == _compiled_oracle(tfidf_plan(2),
                                                      CORPUS_A)
        assert client.status(ack["job_id"])["placed_on"] == "local"
        assert client.stats()["pool"]["plan"]["plan_solo_fallbacks"] >= 1
    finally:
        _stop_workers(ws)
        daemon.close()
    # Join capacity gate: a table too small for the joined vocabulary
    # demotes to solo (which applies its own truncation discipline) and
    # still answers byte-identically to the solo compiled plan.
    from locust_tpu.config import EngineConfig

    tiny = dict(CFG_OVR, table_size=8)
    tiny_cfg = EngineConfig(**tiny)
    daemon, ws, client = _pool_rig(shard_min_blocks=1)
    corpus = CORPUS_A + CORPUS_B  # 10 distinct words > 8 slots
    try:
        plan = _join_plan("sum")
        ack = client.submit(corpus=corpus, config=tiny,
                            plan=plan.to_doc(), no_cache=True)
        res = client.wait(ack["job_id"], timeout=120.0)
        from locust_tpu.plan.compile import compile_plan
        want = compile_plan(plan, tiny_cfg).run_corpus(corpus).output
        assert res["pairs"][0][0] == want
        assert client.status(ack["job_id"])["placed_on"] == "local"
        assert client.stats()["pool"]["plan"]["plan_solo_fallbacks"] >= 1
    finally:
        _stop_workers(ws)
        daemon.close()


def test_journal_stage_records_replay_with_admit():
    """Unit for the WAL side: stage records are flush-only riders on the
    fsync'd admit record and replay() hands them back in order on the
    surviving entry."""
    import tempfile

    from locust_tpu.serve.journal import JobJournal

    with tempfile.TemporaryDirectory() as jd:
        j = JobJournal(jd)
        job = mk_job(job_id="dp1")
        j.append_admit(job, b"corpus bytes\n")
        j.append_stage("dp1", {"split": 0, "attempt": 0, "parts": []})
        j.append_stage("dp1", {"split": 1, "attempt": 0, "parts": []})
        j.append_stage("ghost", {"split": 9})  # no admit: dropped
        j.close()
        entries = JobJournal(jd).replay()
        by_id = {e.admit["job_id"]: e for e in entries}
        assert [s["split"] for s in by_id["dp1"].stages] == [0, 1]
        assert "ghost" not in by_id


def test_pool_distributed_plan_wal_replay_resumes_from_stage_records(
        tmp_path):
    """Machine-death durability for the distributed path: the daemon is
    abandoned AFTER the map wave journaled its stage records but before
    the reduce wave finished.  The restarted daemon's replay resumes the
    plan from the surviving partitions (partitions_reused counts them)
    and the answer is byte-identical to the solo compiled plan."""
    from locust_tpu.plan import tfidf_plan
    from locust_tpu.utils import faultplan

    jd = str(tmp_path / "journal")
    mk = dict(max_queue=16, max_batch=4, dispatch_poll_s=0.02,
              retry_base_s=0.02, journal_dir=jd, shard_min_blocks=1)
    from locust_tpu.distributor.worker import Worker

    ws = []
    for _ in range(2):
        w = Worker(secret=SECRET, serve=True)
        w.serve_in_thread()
        ws.append(w)
    addrs = tuple(f"127.0.0.1:{w.addr[1]}" for w in ws)
    daemon = ServeDaemon(secret=SECRET,
                         cfg=ServeConfig(workers=addrs, **mk))
    daemon.serve_in_thread()
    client = ServeClient(daemon.addr, SECRET, timeout=60.0)
    # Stall every reduce-stage RPC on the daemon side: the map wave
    # lands (stage records + partitions durable), the reduce wave never
    # does — the abandon models the machine dying mid-shuffle.
    p = faultplan.FaultPlan(
        [{"site": "plan.stage", "action": "delay", "delay_s": 60.0,
          "match": {"phase": "reduce"}, "times": 8}], seed=11,
    )
    try:
        with faultplan.active_plan(p):
            ack = client.submit(corpus=CORPUS_A, config=CFG_OVR,
                                plan=tfidf_plan(2).to_doc(), no_cache=True)
            deadline = time.monotonic() + 30.0
            while time.monotonic() < deadline:
                with open(daemon.journal.path, "rb") as f:
                    if f.read().count(b'"rec":"stage"') >= 2:
                        break
                time.sleep(0.05)
            else:
                pytest.fail("map wave never journaled its stage records")
            serve_abandon(daemon)
        d2 = ServeDaemon(secret=SECRET,
                         cfg=ServeConfig(workers=addrs, **mk))
        d2.serve_in_thread()
        c2 = ServeClient(d2.addr, SECRET, timeout=60.0)
        try:
            res = c2.wait(ack["job_id"], timeout=120.0)
            assert res["plan"] is True
            assert res["pairs"][0][0] == _compiled_oracle(tfidf_plan(2),
                                                          CORPUS_A)
            st = c2.status(ack["job_id"])
            assert st["placed_on"].startswith("plan:")
            assert c2.stats()["pool"]["plan"]["partitions_reused"] >= 2
        finally:
            d2.close()
    finally:
        _stop_workers(ws)
        daemon.close()


def test_pool_distributed_join_iterate_deep_byte_identical():
    """Plan surface v2 identity pins: a join tree (both combines), a
    3-stage deep pipeline, and an iterate (pagerank) all run DISTRIBUTED
    across the 2-worker pool and answer byte-for-byte what the solo
    compiled plan renders.  A warm repeat then lands every map stage on
    the workers' cached fold-node executables: compiles stay flat and
    map_warm_hits counts the skips — the perf contract, test-pinned."""
    from locust_tpu.plan import pagerank_plan

    daemon, ws, client = _pool_rig(shard_min_blocks=1)
    corpus = CORPUS_A + CORPUS_B
    edges = b"0 1\n1 2\n2 0\n0 2\n3 1\n2 3\n" * 3
    cases = [
        (_join_plan("sum"), corpus),
        (_join_plan("min"), corpus),
        (_join_plan(deep=True), corpus),
        (pagerank_plan(4), edges),
    ]
    try:
        for plan, cdata in cases:
            ack = client.submit(corpus=cdata, config=CFG_OVR,
                                plan=plan.to_doc(), no_cache=True)
            res = client.wait(ack["job_id"], timeout=120.0)
            assert res["plan"] is True
            assert res["pairs"][0][0] == _compiled_oracle(plan, cdata)
            st = client.status(ack["job_id"])
            assert st["placed_on"].startswith("plan:")
        # Warm repeat: resubmitting the join must hit the workers' warm
        # fold-node executables — zero new compiles, counted hits.
        pre = [w._serve_cache.stats()["compiles"] for w in ws]
        plan, cdata = cases[0]
        ack = client.submit(corpus=cdata, config=CFG_OVR,
                            plan=plan.to_doc(), no_cache=True)
        res = client.wait(ack["job_id"], timeout=120.0)
        assert res["pairs"][0][0] == _compiled_oracle(plan, cdata)
        post = [w._serve_cache.stats()["compiles"] for w in ws]
        assert post == pre, f"warm repeat recompiled: {pre} -> {post}"
        pl = client.stats()["pool"]["plan"]
        assert pl["map_warm_hits"] > 0
        assert pl["plan_solo_fallbacks"] == 0
    finally:
        _stop_workers(ws)
        daemon.close()


def test_pool_distributed_plan_random_dag_property():
    """Seeded property test: randomly generated distributed-eligible
    plans (fold spines, join trees one and two levels deep with random
    combines, pagerank with random iteration counts and damping) are
    byte-identical to the solo compiled plan under the 2-worker pool —
    and stay byte-identical when one worker dies mid-stage (a chaos
    crash on the shape's own stage phase; the survivor recomputes)."""
    import random

    from locust_tpu.plan import (
        index_plan,
        pagerank_plan,
        tfidf_plan,
        wordcount_plan,
    )
    from locust_tpu.utils import faultplan

    rng = random.Random(0x20)
    corpus = CORPUS_A + CORPUS_B
    edges = b"0 1\n1 2\n2 0\n0 2\n3 1\n2 3\n" * 3

    def rand_fold():
        k = rng.choice(("wc", "tf", "ix"))
        if k == "wc":
            return wordcount_plan(), corpus, "map"
        if k == "tf":
            return tfidf_plan(rng.randint(1, 3)), corpus, "map"
        return index_plan(rng.randint(1, 3)), corpus, "reduce"

    def rand_join():
        deep = rng.random() < 0.5
        return (_join_plan(rng.choice(("sum", "mul", "min")), deep=deep),
                corpus, "join")

    def rand_iterate():
        return (pagerank_plan(rng.randint(1, 4),
                              damping=rng.choice((0.85, 0.9, 0.6))),
                edges, "iterate")

    shapes = [rand_fold(), rand_join(), rand_join(), rand_iterate(),
              rand_iterate(), rand_fold()]
    daemon, ws, client = _pool_rig(shard_min_blocks=1)
    try:
        for i, (plan, cdata, phase) in enumerate(shapes):
            # One injected mid-stage death per shape, on its own phase.
            p = faultplan.FaultPlan(
                [{"site": "plan.stage", "action": "crash", "times": 1,
                  "match": {"phase": phase}}], seed=i,
            )
            with faultplan.active_plan(p):
                ack = client.submit(corpus=cdata, config=CFG_OVR,
                                    plan=plan.to_doc(), no_cache=True)
                res = client.wait(ack["job_id"], timeout=120.0)
            assert res["pairs"][0][0] == _compiled_oracle(plan, cdata), \
                f"shape {i} ({phase}) diverged from solo"
            st = client.status(ack["job_id"])
            assert st["placed_on"].startswith("plan:"), (i, st["placed_on"])
        pl = client.stats()["pool"]["plan"]
        assert pl["recomputes"] >= len(shapes)  # every crash was repaired
        assert pl["plan_solo_fallbacks"] == 0
    finally:
        _stop_workers(ws)
        daemon.close()


def test_pool_distributed_iterate_wal_replay_resumes_from_epoch(tmp_path):
    """Machine-death durability for iterate: the daemon is abandoned
    after epoch 1 journaled its rank-shard records but while epoch 2 is
    stalled in flight.  The restarted daemon's replay seeds the sweep
    from the surviving epoch-1 partitions (partitions_reused counts
    them) and finishes byte-identical to the solo compiled plan."""
    from locust_tpu.plan import pagerank_plan
    from locust_tpu.utils import faultplan

    jd = str(tmp_path / "journal")
    mk = dict(max_queue=16, max_batch=4, dispatch_poll_s=0.02,
              retry_base_s=0.02, journal_dir=jd, shard_min_blocks=1)
    from locust_tpu.distributor.worker import Worker

    ws = []
    for _ in range(2):
        w = Worker(secret=SECRET, serve=True)
        w.serve_in_thread()
        ws.append(w)
    addrs = tuple(f"127.0.0.1:{w.addr[1]}" for w in ws)
    daemon = ServeDaemon(secret=SECRET,
                         cfg=ServeConfig(workers=addrs, **mk))
    daemon.serve_in_thread()
    client = ServeClient(daemon.addr, SECRET, timeout=60.0)
    edges = b"0 1\n1 2\n2 0\n0 2\n3 1\n2 3\n" * 3
    plan = pagerank_plan(3)
    # Stall every epoch-2 sweep RPC: epoch 1 lands (WAL epoch record +
    # rank shards durable), epoch 2 never does — the abandon models the
    # machine dying mid-iteration.
    p = faultplan.FaultPlan(
        [{"site": "plan.stage", "action": "delay", "delay_s": 60.0,
          "match": {"phase": "iterate", "split": 2}, "times": 16}],
        seed=13,
    )
    try:
        with faultplan.active_plan(p):
            ack = client.submit(corpus=edges, config=CFG_OVR,
                                plan=plan.to_doc(), no_cache=True)
            deadline = time.monotonic() + 30.0
            while time.monotonic() < deadline:
                with open(daemon.journal.path, "rb") as f:
                    if f.read().count(b'"rec":"stage"') >= 1:
                        break
                time.sleep(0.05)
            else:
                pytest.fail("epoch 1 never journaled its stage record")
            serve_abandon(daemon)
        d2 = ServeDaemon(secret=SECRET,
                         cfg=ServeConfig(workers=addrs, **mk))
        d2.serve_in_thread()
        c2 = ServeClient(d2.addr, SECRET, timeout=60.0)
        try:
            res = c2.wait(ack["job_id"], timeout=120.0)
            assert res["plan"] is True
            assert res["pairs"][0][0] == _compiled_oracle(plan, edges)
            st = c2.status(ack["job_id"])
            assert st["placed_on"].startswith("plan:")
            assert c2.stats()["pool"]["plan"]["partitions_reused"] >= 1
        finally:
            d2.close()
    finally:
        _stop_workers(ws)
        daemon.close()


# --------------------------------------------- high availability (ISSUE 14)
#
# WAL shipping to a hot standby + fenced promotion (docs/SERVING.md
# "High availability"): the primary ships every fsync'd journal record
# asynchronously, the standby refuses the job plane with not_primary
# until promoted, promotion bumps the fencing epoch and replays exactly
# like the restart path, and the client roster follows redirects.


def _ha_pair(tmp_path, standby_kw=None, primary_kw=None):
    """One primary shipping to one warm standby, both journaled."""
    standby = ServeDaemon(secret=SECRET, cfg=ServeConfig(
        journal_dir=str(tmp_path / "standby-journal"),
        standby_of="127.0.0.1:9",  # seed; ship traffic refines it
        dispatch_poll_s=0.02, **(standby_kw or {}),
    ))
    standby.serve_in_thread()
    primary = ServeDaemon(secret=SECRET, cfg=ServeConfig(
        journal_dir=str(tmp_path / "primary-journal"),
        ship_to=f"{standby.addr[0]}:{standby.addr[1]}",
        dispatch_poll_s=0.02, ship_heartbeat_s=0.3, retry_base_s=0.02,
        **(primary_kw or {}),
    ))
    primary.serve_in_thread()
    return primary, standby


def _wait_replicated(standby, n_records, timeout=20.0):
    """Bounded wait until the standby has applied >= n_records AND holds
    every referenced spill — an applied admit is only failover-safe once
    its corpus bytes landed too (the ack-before-spill window)."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        st = standby.receiver.stats()
        if st["applied_records"] >= n_records and \
                st["missing_spills"] == 0:
            return
        time.sleep(0.02)
    raise AssertionError(
        f"standby never replicated {n_records} records + spills: "
        f"{standby.receiver.stats()}"
    )


def test_ha_requires_journal_dir():
    with pytest.raises(ValueError, match="journal"):
        ServeDaemon(secret=SECRET, cfg=ServeConfig(ship_to="127.0.0.1:1"))
    with pytest.raises(ValueError, match="journal"):
        ServeDaemon(secret=SECRET,
                    cfg=ServeConfig(standby_of="127.0.0.1:1"))


def test_standby_refuses_job_plane_answers_control_plane(tmp_path):
    """A standby answers stats/ping (that is what "hot" means) but
    refuses every job-plane command with the structured not_primary
    code naming the primary — "not_primary" and the redirect address
    are what roster clients switch on."""
    primary, standby = _ha_pair(tmp_path)
    try:
        sc = ServeClient(standby.addr, SECRET, timeout=30.0)
        assert sc.ping() is True
        st = sc.stats()
        assert st["replication"]["role"] == "standby"
        for cmd in ("submit", "status", "result", "cancel", "invalidate"):
            raw = sc._rpc_one(standby.addr, {"cmd": cmd, "job_id": "x",
                                             "corpus_b64": "YQo="})
            assert raw.get("code") == "not_primary", (cmd, raw)
        # The redirect names the REAL primary once ship traffic has
        # flowed (the static seed is only the cold-start hint).
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            raw = sc._rpc_one(standby.addr,
                              {"cmd": "submit", "corpus_b64": "YQo="})
            if raw.get("primary") == \
                    f"{primary.addr[0]}:{primary.addr[1]}":
                break
            time.sleep(0.05)
        assert raw.get("primary") == f"{primary.addr[0]}:{primary.addr[1]}"
    finally:
        primary.close()
        standby.close()


def test_ha_promote_replays_under_original_ids_byte_identical(tmp_path):
    """The machine-death drill, in-process: jobs acked on the primary,
    WAL shipped, primary killed without any graceful path, standby
    promoted — the jobs replay under their ORIGINAL ids and answer
    byte-identically (the deterministic-fold guarantee, now surviving
    the machine, not just the process)."""
    primary, standby = _ha_pair(tmp_path)
    abandoned = False
    try:
        primary.scheduler.pause()  # acked, never dispatched: the window
        pc = ServeClient(primary.addr, SECRET, timeout=30.0)
        ja = pc.submit(corpus=CORPUS_A, config=CFG_OVR,
                       no_cache=True)["job_id"]
        jb = pc.submit(corpus=CORPUS_B, config=CFG_OVR,
                       no_cache=True)["job_id"]
        _wait_replicated(standby, 2)
        serve_abandon(primary)
        abandoned = True
        sc = ServeClient(standby.addr, SECRET, timeout=30.0)
        res = sc.promote()
        assert res["role"] == "primary" and res["epoch"] >= 2
        ra = sc.wait(ja, timeout=120.0)
        rb = sc.wait(jb, timeout=120.0)
        assert dict(ra["pairs"]) == oracle(CORPUS_A)
        assert dict(rb["pairs"]) == oracle(CORPUS_B)
        # Promotion persisted the bumped epoch: a restart of the
        # promoted standby must stay ABOVE the fenced-out zombie.
        from locust_tpu.serve import replicate

        assert replicate.load_epoch(str(tmp_path / "standby-journal")) \
            == standby.epoch
    finally:
        if not abandoned:
            primary.close()
        standby.close()


def test_ha_lease_expiry_auto_promotes(tmp_path):
    """The unattended takeover: heartbeats stop (primary machine dead),
    the standby's lease expires, it promotes itself and answers the
    acked job exactly."""
    primary, standby = _ha_pair(tmp_path, standby_kw={"lease_s": 1.0})
    abandoned = False
    try:
        primary.scheduler.pause()
        pc = ServeClient(primary.addr, SECRET, timeout=30.0)
        jid = pc.submit(corpus=CORPUS_A, config=CFG_OVR,
                        no_cache=True)["job_id"]
        _wait_replicated(standby, 1)
        serve_abandon(primary)
        abandoned = True
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline and standby.role != "primary":
            time.sleep(0.05)
        assert standby.role == "primary"
        sc = ServeClient(standby.addr, SECRET, timeout=30.0)
        assert dict(sc.wait(jid, timeout=120.0)["pairs"]) == \
            oracle(CORPUS_A)
    finally:
        if not abandoned:
            primary.close()
        standby.close()


def test_ha_shipping_is_async_dead_standby_never_fails_admits(tmp_path):
    """The no-slow-admit guarantee: with the standby address pointing at
    a dead port, submits still ack immediately and run exactly — the
    shipper degrades to lag + warnings, never into the admit path."""
    daemon = ServeDaemon(secret=SECRET, cfg=ServeConfig(
        journal_dir=str(tmp_path / "journal"),
        ship_to="127.0.0.1:1",  # nothing listens there
        dispatch_poll_s=0.02,
    ))
    daemon.serve_in_thread()
    try:
        client = ServeClient(daemon.addr, SECRET, timeout=30.0)
        t0 = time.monotonic()
        ack = client.submit(corpus=CORPUS_A, config=CFG_OVR, no_cache=True)
        admit_s = time.monotonic() - t0
        res = client.wait(ack["job_id"], timeout=120.0)
        assert dict(res["pairs"]) == oracle(CORPUS_A)
        assert admit_s < 5.0  # nowhere near a connect-retry stall
        rep = client.stats()["replication"]
        assert rep["role"] == "primary"
        assert rep["ship"]["connected"] is False
        assert rep["ship"]["lag_records"] >= 1
    finally:
        daemon.close()


def test_ha_late_standby_converges_via_catchup(tmp_path):
    """A standby that joins AFTER the primary has history: the first
    contact is a full live-journal snapshot plus on-demand spill pulls,
    and promotion from that state replays the live job exactly."""
    # Primary alone first, shipping into the void.
    standby_dir = str(tmp_path / "standby-journal")
    primary = None
    standby = None
    try:
        # Reserve the standby's port by building it first but treat the
        # primary's early life as "standby down": point the primary at
        # the standby, then only assert AFTER the late catch-up.
        standby = ServeDaemon(secret=SECRET, cfg=ServeConfig(
            journal_dir=standby_dir, standby_of="127.0.0.1:9",
            dispatch_poll_s=0.02,
        ))
        primary = ServeDaemon(secret=SECRET, cfg=ServeConfig(
            journal_dir=str(tmp_path / "primary-journal"),
            ship_to=f"{standby.addr[0]}:{standby.addr[1]}",
            dispatch_poll_s=0.02, ship_heartbeat_s=0.3,
        ))
        primary.serve_in_thread()
        pc = ServeClient(primary.addr, SECRET, timeout=30.0)
        done = pc.submit(corpus=CORPUS_B, config=CFG_OVR,
                         no_cache=True)["job_id"]
        pc.wait(done, timeout=120.0)  # finished history
        primary.scheduler.pause()
        live = pc.submit(corpus=CORPUS_A, config=CFG_OVR,
                         no_cache=True)["job_id"]
        # NOW the standby starts serving: the shipper's next pass
        # catches it up from the snapshot.
        standby.serve_in_thread()
        deadline = time.monotonic() + 20.0
        while time.monotonic() < deadline:
            if standby.receiver.stats()["catchups"] >= 1 and \
                    standby.journal.spill_exists(
                        primary._jobs[live].corpus_digest):
                break
            time.sleep(0.05)
        assert standby.receiver.stats()["catchups"] >= 1
        serve_abandon(primary)
        sc = ServeClient(standby.addr, SECRET, timeout=30.0)
        sc.promote()
        assert dict(sc.wait(live, timeout=120.0)["pairs"]) == \
            oracle(CORPUS_A)
    finally:
        if primary is not None:
            primary.close()
        if standby is not None:
            standby.close()


def test_client_roster_fails_over_and_follows_redirect(tmp_path):
    """ServeClient with a roster: a dead first address is skipped, and a
    standby's not_primary redirect lands the request on the primary —
    submit/result/stats survive without the caller renaming anything."""
    primary, standby = _ha_pair(tmp_path)
    try:
        dead = ("127.0.0.1", 1)
        roster = ServeClient(
            [dead, standby.addr], SECRET, timeout=30.0,
        )
        # Wait until the standby knows the real primary address.
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline and \
                standby.receiver.primary() is None:
            time.sleep(0.05)
        ack = roster.submit(corpus=CORPUS_A, config=CFG_OVR)
        res = roster.wait(ack["job_id"], timeout=120.0)
        assert dict(res["pairs"]) == oracle(CORPUS_A)
        # Sticky: the client now talks to the primary directly.
        assert roster.addr == (primary.addr[0], primary.addr[1])
        assert roster.stats()["replication"]["role"] == "primary"
    finally:
        primary.close()
        standby.close()


def test_client_single_address_behavior_unchanged():
    """The pre-HA spelling still works: one (host, port), connection
    errors re-raise to the caller."""
    c = ServeClient(("127.0.0.1", 1), SECRET, timeout=0.5)
    assert c.roster == [("127.0.0.1", 1)]
    with pytest.raises(OSError):
        c.ping()


def test_epoch_guard_monotone():
    from locust_tpu.distributor import protocol

    g = protocol.EpochGuard()
    assert g.observe(1) is None
    assert g.observe(3) is None
    assert g.observe(2) == 3      # stale: names the fence
    assert g.observe(3) is None   # equal to the high-water mark: current
    assert g.highest() == 3


def test_ship_receiver_never_applies_corrupt_records(tmp_path):
    """Unit pin for the corrupt-ship contract: a records blob whose
    checksum fails is answered resync and nothing touches the journal."""
    from locust_tpu.serve.journal import JobJournal
    from locust_tpu.serve.replicate import ShipReceiver, records_blob

    j = JobJournal(str(tmp_path / "j"))
    r = ShipReceiver(j)
    text, checksum = records_blob(
        [{"rec": "admit", "job_id": "a", "v": 1, "corpus_sha": ""}]
    )
    mangled = text.replace("admit", "admxt")
    reply = r.handle_ship({"seq_from": 1, "records": mangled,
                           "sum": checksum})
    assert reply["resync"] is True and reply["acked_seq"] == 0
    assert j.live_records() == []
    # The intact blob applies.
    reply = r.handle_ship({"seq_from": 1, "records": text,
                           "sum": checksum})
    assert "resync" not in reply and reply["acked_seq"] == 1
    assert [rec["job_id"] for rec in j.live_records()] == ["a"]
    # A sequence GAP is a resync, applied out of order never.
    text2, sum2 = records_blob(
        [{"rec": "admit", "job_id": "b", "v": 1, "corpus_sha": ""}]
    )
    reply = r.handle_ship({"seq_from": 5, "records": text2, "sum": sum2})
    assert reply["resync"] is True
    assert [rec["job_id"] for rec in j.live_records()] == ["a"]
    j.close()


def test_stats_replication_and_journal_subdicts(tmp_path):
    """The HA operator surface: stats carries a replication sub-dict
    (role/epoch/ship lag or standby application state) and the journal
    sub-dict reports live records, spill bytes and the last compaction
    — readable without logs (the ISSUE 14 satellite)."""
    primary, standby = _ha_pair(tmp_path)
    try:
        pc = ServeClient(primary.addr, SECRET, timeout=30.0)
        jid = pc.submit(corpus=CORPUS_A, config=CFG_OVR,
                        no_cache=True)["job_id"]
        pc.wait(jid, timeout=120.0)
        _wait_replicated(standby, 1)
        ps = pc.stats()
        rep = ps["replication"]
        assert rep["role"] == "primary" and rep["epoch"] >= 1
        ship = rep["ship"]
        for key in ("standby", "connected", "shipped_seq", "acked_seq",
                    "lag_records", "lag_bytes", "last_catchup_t"):
            assert key in ship, key
        jstats = ps["journal"]
        for key in ("live", "spill_bytes", "last_compact_t"):
            assert key in jstats, key
        ss = ServeClient(standby.addr, SECRET, timeout=30.0).stats()
        srep = ss["replication"]
        assert srep["role"] == "standby"
        for key in ("applied_seq", "applied_records", "catchups",
                    "primary", "contact_age_s"):
            assert key in srep["standby"], key
    finally:
        primary.close()
        standby.close()


def test_equal_epoch_dual_primary_tie_break(tmp_path):
    """Two daemons that BOTH believe they are primary at the same epoch
    (a misconfigured ring, or a partition healing pre-promotion): the
    address tie-break demotes exactly ONE of them — a mutual first-ship
    race must not demote both and leave the pair with no primary."""
    a = ServeDaemon(secret=SECRET, cfg=ServeConfig(
        journal_dir=str(tmp_path / "a-journal"),
        ship_to="127.0.0.1:9",  # nothing there; A stays epoch-1 primary
        dispatch_poll_s=0.02, ship_heartbeat_s=0.2,
    ))
    a.serve_in_thread()
    b = ServeDaemon(secret=SECRET, cfg=ServeConfig(
        journal_dir=str(tmp_path / "b-journal"),
        ship_to=f"{a.addr[0]}:{a.addr[1]}",  # B ships AT primary A
        dispatch_poll_s=0.02, ship_heartbeat_s=0.2,
    ))
    b.serve_in_thread()
    try:
        deadline = time.monotonic() + 20.0
        while time.monotonic() < deadline:
            roles = {a.role, b.role}
            if roles == {"primary", "standby"}:
                break
            time.sleep(0.05)
        assert {a.role, b.role} == {"primary", "standby"}, (a.role, b.role)
    finally:
        a.close()
        b.close()


def test_client_legacy_string_port_tuple_still_single_address():
    """The pre-roster constructor coerced ('host', '1347') with int():
    the roster heuristic must not reinterpret that tuple as two
    addresses."""
    c = ServeClient(("127.0.0.1", "1347"), SECRET)
    assert c.roster == [("127.0.0.1", 1347)]


def test_client_promote_never_fails_over(tmp_path):
    """promote() targets EXACTLY roster[0]: an epoch bump fences the
    other pair member, so a silent roster fail-over (dead standby A ->
    accidentally promoting B) would be the misfire the double-promotion
    guard exists to prevent.  A dead target raises, never redirects."""
    primary, standby = _ha_pair(tmp_path)
    try:
        dead_first = ServeClient(
            [("127.0.0.1", 1), standby.addr], SECRET, timeout=0.5,
        )
        with pytest.raises(OSError):
            dead_first.promote()
        assert standby.role == "standby"  # the live standby untouched
    finally:
        primary.close()
        standby.close()
