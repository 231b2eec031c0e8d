"""Loopback distributor tests — master + workers on 127.0.0.1.

The reference's shipped code could only ever run on loopback anyway
(hardcoded 127.0.0.1:1337, slave.py:6-7); we make that a real test harness
(SURVEY.md §4).  Workers run with an injected in-process map runner so the
test doesn't spawn a fresh JAX process per node.
"""

import socket

import pytest

from helpers import py_wordcount

from locust_tpu import cli
from locust_tpu.distributor import master, protocol
from locust_tpu.distributor.worker import Worker

SECRET = b"test-secret"

CORPUS = b"""alpha beta gamma
beta gamma delta
gamma delta epsilon
delta epsilon alpha
epsilon alpha beta
"""


def make_inproc_runner(tmp_path):
    """Map runner that invokes the CLI in-process (fast: shared JAX runtime)."""

    def runner(req):
        args = [
            req["file"],
            str(req["line_start"]),
            str(req["line_end"]),
            str(req["node_num"]),
            "1",
            "-i",
            req["intermediate"],
            "--block-lines",
            "8",
            "--line-width",
            "64",
            "--emits-per-line",
            "8",
            "--no-timing",
        ]
        if req.get("inter_format"):  # the master's negotiated data plane
            args += ["--inter-format", req["inter_format"]]
        rc = cli.main(args)
        return {"status": "ok" if rc == 0 else "error", "returncode": rc,
                "log": "", "intermediate": req["intermediate"]}

    return runner


@pytest.fixture
def corpus_file(tmp_path):
    p = tmp_path / "corpus.txt"
    p.write_bytes(CORPUS)
    return str(p)


def test_cluster_file_parser(tmp_path):
    p = tmp_path / "cluster.txt"
    p.write_text("# comment\n127.0.0.1 4001\n127.0.0.1 4002\n\n")
    assert protocol.parse_cluster_file(str(p)) == [
        ("127.0.0.1", 4001),
        ("127.0.0.1", 4002),
    ]
    bad = tmp_path / "bad.txt"
    bad.write_text("127.0.0.1\n")
    with pytest.raises(ValueError):
        protocol.parse_cluster_file(str(bad))


def test_worker_requires_secret():
    with pytest.raises(ValueError):
        Worker(secret=b"")


def test_worker_rejects_bad_mac():
    w = Worker(secret=SECRET)
    w.serve_in_thread()
    try:
        with socket.create_connection(w.addr, timeout=5) as s:
            protocol.send_frame(s, {"cmd": "ping"}, b"wrong-secret")
            s.settimeout(1.0)
            with pytest.raises((ConnectionError, socket.timeout, OSError)):
                protocol.recv_frame(s, b"wrong-secret")
    finally:
        _shutdown(w)


def test_worker_ping_and_unknown_command():
    w = Worker(secret=SECRET)
    w.serve_in_thread()
    try:
        assert master._rpc(w.addr, {"cmd": "ping"}, SECRET)["pong"] is True
        resp = master._rpc(w.addr, {"cmd": "rm -rf /"}, SECRET)
        assert resp["status"] == "error"  # Q8: no arbitrary commands
    finally:
        _shutdown(w)


def test_worker_survives_malformed_frames():
    """Regression: garbage frames must not kill the daemon (remote DoS)."""
    import struct

    w = Worker(secret=SECRET)
    w.serve_in_thread()
    try:
        for garbage in [b"\x00\x00\x00\x03abc", b"\x00\x00\x00\x10[1]\nnot-json-at-all"]:
            with socket.create_connection(w.addr, timeout=5) as s:
                s.sendall(garbage)
        # Daemon must still answer an authenticated ping afterwards.
        assert master._rpc(w.addr, {"cmd": "ping"}, SECRET)["pong"] is True
    finally:
        _shutdown(w)


def test_worker_accept_loop_survives_thread_spawn_failure(monkeypatch):
    """Regression (PR 18, R017): a connection thread that fails to SPAWN
    must not kill the accept loop, and must release its connection slot
    and close the orphaned socket.  max_connections=1 makes a leaked
    slot a deadlock: three consecutive spawn failures would wedge the
    acquire forever if any release were missed."""
    import threading

    import locust_tpu.distributor.worker as worker_mod

    w = Worker(secret=SECRET, max_connections=1)
    w.serve_in_thread()
    real_thread = threading.Thread
    fails = {"left": 3}

    class FlakyThread(real_thread):
        def __init__(self, *args, target=None, **kwargs):
            if (
                getattr(target, "__name__", "") == "_serve_one"
                and fails["left"] > 0
            ):
                fails["left"] -= 1
                raise RuntimeError("injected spawn failure")
            super().__init__(*args, target=target, **kwargs)

    try:
        monkeypatch.setattr(worker_mod.threading, "Thread", FlakyThread)
        while fails["left"]:
            before = fails["left"]
            # The dropped connection surfaces client-side as a closed
            # socket mid-rpc; the worker must already be accepting again.
            with pytest.raises(Exception):
                master._rpc(w.addr, {"cmd": "ping"}, SECRET, timeout=5)
            assert fails["left"] == before - 1
        monkeypatch.setattr(worker_mod.threading, "Thread", real_thread)
        assert master._rpc(
            w.addr, {"cmd": "ping"}, SECRET, timeout=5
        )["pong"] is True
    finally:
        monkeypatch.setattr(worker_mod.threading, "Thread", real_thread)
        _shutdown(w)


def test_worker_fetch_path_containment(tmp_path):
    w = Worker(secret=SECRET)
    w.serve_in_thread()
    try:
        # The request cannot choose its own boundary: workdir is server-side.
        resp = master._rpc(
            w.addr, {"cmd": "fetch", "path": "/etc/passwd", "workdir": "/"}, SECRET
        )
        assert resp["status"] == "error" and "outside" in resp["error"]
    finally:
        _shutdown(w)


def test_worker_rejects_replayed_frame():
    """A recorded frame (same nonce) must be dropped the second time."""
    import time as _time

    w = Worker(secret=SECRET)
    w.serve_in_thread()
    try:
        frozen = {"cmd": "ping", "_ts": _time.time(), "_nonce": "fixed-nonce-1"}
        with socket.create_connection(w.addr, timeout=5) as s:
            protocol.send_frame(s, frozen, SECRET, sign_fresh=False)
            assert protocol.recv_frame(s, SECRET)["pong"] is True
        with socket.create_connection(w.addr, timeout=5) as s:
            protocol.send_frame(s, frozen, SECRET, sign_fresh=False)
            s.settimeout(1.0)
            with pytest.raises((ConnectionError, socket.timeout, OSError)):
                protocol.recv_frame(s, SECRET)
        # Stale timestamp also rejected.
        stale = {"cmd": "ping", "_ts": _time.time() - 9999, "_nonce": "n2"}
        with socket.create_connection(w.addr, timeout=5) as s:
            protocol.send_frame(s, stale, SECRET, sign_fresh=False)
            s.settimeout(1.0)
            with pytest.raises((ConnectionError, socket.timeout, OSError)):
                protocol.recv_frame(s, SECRET)
    finally:
        _shutdown(w)


def test_master_end_to_end_loopback(corpus_file, tmp_path, capsysbinary):
    """Two workers, sharded map, fetch, local reduce — the full missing-master
    flow of SURVEY.md §3.2-3.3 on loopback."""
    runner = make_inproc_runner(tmp_path)
    w1 = Worker(secret=SECRET, map_runner=runner)
    w2 = Worker(secret=SECRET, map_runner=runner)
    w1.serve_in_thread()
    w2.serve_in_thread()
    try:
        tsvs = master.run_job(
            [w1.addr, w2.addr], corpus_file, SECRET, workdir=str(tmp_path / "m")
        )
        assert len(tsvs) == 2
        capsysbinary.readouterr()
        rc = cli.main(
            [corpus_file, "-1", "-1", "0", "2", "--block-lines", "8",
             "--line-width", "64", "--emits-per-line", "8"]
            + sum((["-i", t] for t in tsvs), [])
        )
        assert rc == 0
        out = capsysbinary.readouterr().out
        got = {}
        for line in out.splitlines():
            k, _, v = line.partition(b"\t")
            got[k] = int(v)
        assert got == dict(py_wordcount(CORPUS.splitlines(), 8))
    finally:
        _shutdown(w1)
        _shutdown(w2)


def _shutdown(w: Worker):
    try:
        master._rpc(w.addr, {"cmd": "shutdown"}, SECRET, timeout=5)
    except Exception:
        pass


def _reduce_and_check(corpus_file, tsvs, capsysbinary):
    capsysbinary.readouterr()
    rc = cli.main(
        [corpus_file, "-1", "-1", "0", "2", "--block-lines", "8",
         "--line-width", "64", "--emits-per-line", "8"]
        + sum((["-i", t] for t in tsvs), [])
    )
    assert rc == 0
    got = {}
    for line in capsysbinary.readouterr().out.splitlines():
        k, _, v = line.partition(b"\t")
        got[k] = int(v)
    assert got == dict(py_wordcount(CORPUS.splitlines(), 8))


def test_master_reassigns_shard_of_dead_worker(corpus_file, tmp_path, capsysbinary):
    """A worker killed before its shard runs: the master reassigns the
    shard to a live worker and the job still yields the exact table
    (the reference aborts the whole job)."""
    runner = make_inproc_runner(tmp_path)
    w1 = Worker(secret=SECRET, map_runner=runner)
    w2 = Worker(secret=SECRET, map_runner=runner)
    w1.serve_in_thread()
    w2.serve_in_thread()
    _shutdown(w2)  # kill node 1; its shard must fail over to node 0
    try:
        tsvs = master.run_job(
            [w1.addr, w2.addr], corpus_file, SECRET,
            workdir=str(tmp_path / "m"),
        )
        assert len(tsvs) == 2
        _reduce_and_check(corpus_file, tsvs, capsysbinary)
    finally:
        _shutdown(w1)


def test_master_reassigns_on_map_failure(corpus_file, tmp_path, capsysbinary):
    """A worker whose map RUNS but fails (rc != 0) is quarantined and its
    shard is retried on a healthy node."""
    good = make_inproc_runner(tmp_path)

    def bad(req):
        return {"status": "error", "returncode": 1, "log": "boom",
                "intermediate": req["intermediate"]}

    w1 = Worker(secret=SECRET, map_runner=good)
    w2 = Worker(secret=SECRET, map_runner=bad)
    w1.serve_in_thread()
    w2.serve_in_thread()
    try:
        tsvs = master.run_job(
            [w1.addr, w2.addr], corpus_file, SECRET,
            workdir=str(tmp_path / "m"),
        )
        assert len(tsvs) == 2
        _reduce_and_check(corpus_file, tsvs, capsysbinary)
    finally:
        _shutdown(w1)
        _shutdown(w2)


def test_master_raises_when_all_workers_dead(corpus_file, tmp_path):
    runner = make_inproc_runner(tmp_path)
    w1 = Worker(secret=SECRET, map_runner=runner)
    w1.serve_in_thread()
    _shutdown(w1)
    with pytest.raises(master.MasterError, match="failed on every tried"):
        master.run_job([w1.addr], corpus_file, SECRET,
                       workdir=str(tmp_path / "m"))


def test_chunked_fetch_roundtrips_beyond_frame_limit(tmp_path):
    """A >64MB intermediate streams in bounded chunks — the old single-frame
    fetch raised 'chunk the transfer' at protocol.MAX_FRAME."""
    import numpy as np

    big = tmp_path / "big.tsv"
    data = np.random.default_rng(0).integers(
        32, 127, size=protocol.MAX_FRAME + (1 << 20), dtype=np.uint8
    ).tobytes()
    big.write_bytes(data)
    w = Worker(secret=SECRET, workdir=str(tmp_path))
    w.serve_in_thread()
    try:
        local = tmp_path / "got.tsv"
        chunks = 0
        offset = 0
        with open(local, "wb") as f:
            while True:
                got = master._rpc(
                    w.addr,
                    {"cmd": "fetch", "path": str(big), "offset": offset},
                    SECRET,
                )
                assert got["status"] == "ok"
                import base64 as b64

                blob = b64.b64decode(got["data_b64"])
                f.write(blob)
                offset += len(blob)
                chunks += 1
                if got["eof"]:
                    break
        assert chunks > 1  # actually exercised the windowing
        assert local.read_bytes() == data
    finally:
        _shutdown(w)


def test_worker_serves_fetch_during_long_map(tmp_path):
    """Connections are served concurrently: a slow map must not block a
    ping or a fetch (the master needs both for retries/chunked transfer)."""
    import threading as _threading
    import time as _time

    release = _threading.Event()

    def slow_map(req):
        release.wait(timeout=30)
        return {"status": "ok", "returncode": 0, "log": "",
                "intermediate": req["intermediate"]}

    f = tmp_path / "x.tsv"
    f.write_bytes(b"word\t1\n")
    w = Worker(secret=SECRET, map_runner=slow_map, workdir=str(tmp_path))
    w.serve_in_thread()
    try:
        map_resp = {}

        def do_map():
            map_resp["r"] = master._rpc(
                w.addr,
                {"cmd": "map", "file": "f", "intermediate": "i"},
                SECRET, timeout=60,
            )

        t = _threading.Thread(target=do_map, daemon=True)
        t.start()
        _time.sleep(0.3)  # let the map start and block
        t0 = _time.monotonic()
        got = master._rpc(w.addr, {"cmd": "fetch", "path": str(f)}, SECRET,
                          timeout=10)
        assert got["status"] == "ok"
        assert _time.monotonic() - t0 < 5  # did NOT wait for the map
        release.set()
        t.join(timeout=30)
        assert map_resp["r"]["status"] == "ok"
    finally:
        _shutdown(w)
