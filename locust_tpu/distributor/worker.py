"""Distributor worker: the hardened descendant of Distributor/slave.py.

Same topology as the reference slave — a TCP daemon on each node that runs
the staged MapReduce binary on command (reference Distributor/slave.py:1-38)
— with the Q8 fixes: HMAC-authenticated frames, a closed command set
(``ping``/``map``/``fetch``/``shutdown``) instead of arbitrary
``subprocess.call(cmd[1:])`` (slave.py:30-32), structured status replies
instead of the unconditional "ACK" (slave.py:19-20), and the subprocess
exit code actually propagated (the reference discards it, slave.py:32).

``fetch`` is the piece of the data plane the reference left out entirely:
it returns the node's intermediate file so the master can stage it to the
reduce node (SURVEY.md §3.2 "unspecified transport, missing from repo").
Connections are persistent (docs/DATAPLANE.md): the master pipelines
windowed fetch requests down one connection and this daemon answers them
in order — binary frames with raw (optionally zlib) payloads when the
request negotiates them, base64 JSON for pre-binary masters — keeping
ONE open file handle per transfer instead of re-open+seek per chunk.
"""

from __future__ import annotations

import argparse
import base64
import hashlib
import os
import socket
import subprocess
import sys
import threading
import time
import zlib

from locust_tpu import obs
from locust_tpu.distributor import protocol
from locust_tpu.utils import faultplan


def _file_sha256(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


def default_map_runner(req: dict) -> dict:
    """Run the staged map via the CLI in a subprocess (one JAX process/node)."""
    out = req.get("intermediate", "/tmp/out.txt")
    cmd = [
        sys.executable,
        "-m",
        "locust_tpu",
        req["file"],
        str(req.get("line_start", -1)),
        str(req.get("line_end", -1)),
        str(req.get("node_num", 0)),
        "1",
        "-i",
        out,
    ]
    if req.get("inter_format"):  # packed-KV data plane (docs/DATAPLANE.md)
        cmd += ["--inter-format", str(req["inter_format"])]
    cmd += [str(a) for a in req.get("extra_args", [])]
    proc = subprocess.run(cmd, capture_output=True, timeout=req.get("timeout", 1800))
    return {
        "status": "ok" if proc.returncode == 0 else "error",
        "returncode": proc.returncode,
        "log": proc.stderr.decode(errors="replace")[-4000:],
        "intermediate": out,
    }


class Worker:
    """One worker daemon.  ``map_runner`` is injectable for loopback tests."""

    # Per-connection open-handle cap: a fetch transfer needs one handle;
    # a peer cycling paths on one connection must not leak descriptors.
    MAX_CACHED_FILES = 8

    def __init__(
        self,
        host: str = "127.0.0.1",
        port: int = 0,
        secret: bytes = b"",
        map_runner=default_map_runner,
        workdir: str = "/tmp",
        conn_timeout: float = 30.0,
        max_connections: int = 32,
        support_binary: bool = True,
        serve: bool = False,
        serve_max_engines: int = 4,
    ):
        if not secret:
            raise ValueError("worker requires a shared secret (Q8: no open RCE)")
        self.secret = secret
        self.map_runner = map_runner
        # Scale-out serve dispatch (docs/SERVING.md): with serve=True the
        # worker answers ``serve_batch`` — an in-process engine fold over
        # a coalesced job batch, behind its OWN warm-executable cache
        # (serve/cache.py), which is what makes pool cache-affinity a
        # real scheduling input.  Lazy: the cache (and jax, at first
        # dispatch) only enters a worker that opted in.
        self._serve_cache = None
        if serve:
            from locust_tpu.serve.cache import ExecutableCache

            self._serve_cache = ExecutableCache(
                max_engines=serve_max_engines
            )
            # Tiny verified-corpus cache (sha -> split lines): a sharded
            # job sends several requests referencing ONE spill, and a
            # retried batch re-references its sha — without this every
            # request re-reads, re-hashes and re-splits the full corpus
            # on the dispatch critical path.  Content-addressed keys
            # can never go stale; 2 entries bound the memory.
            self._serve_corpus: dict[str, list] = {}
            self._serve_corpus_lock = threading.Lock()
            # Iterate-stage loop invariants (parsed edges, shard-
            # filtered columns, prep vectors) keyed by (sha, n, shard
            # layout): an N-epoch sweep sends N stage RPCs referencing
            # ONE graph — without this every epoch re-parses and
            # re-preps.  Content-addressed keys never go stale.
            self._iterate_graphs: dict[tuple, tuple] = {}
            self._iterate_lock = threading.Lock()
        # support_binary=False emulates a pre-binary (JSON-only) peer:
        # negotiation requests are ignored and every reply is a JSON
        # frame — the version-skew interop tests pin that an old worker
        # and a new master still complete jobs together.
        self.support_binary = support_binary
        # Fetch containment boundary is WORKER-side configuration; a request
        # must not be able to choose its own boundary.
        self.workdir = os.path.realpath(workdir)
        self.conn_timeout = conn_timeout
        self._replay_guard = protocol.ReplayGuard()
        # Fencing epoch high-water mark (docs/SERVING.md "High
        # availability"): serve daemons stamp dispatches with their
        # promotion epoch; once a newer primary has dispatched here, a
        # fenced-out zombie's RPCs are rejected structured stale_epoch.
        self._epoch_guard = protocol.EpochGuard()
        self._map_lock = threading.Lock()
        # Bounded concurrency: without a cap, an unauthenticated peer
        # opening idle connections would spawn unbounded threads (each
        # alive up to conn_timeout in recv) — a resource-exhaustion DoS.
        # When the cap is reached the accept loop stalls, pushing further
        # peers into the (small) listen backlog instead of into memory.
        self._conn_slots = threading.BoundedSemaphore(max_connections)
        self._sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._sock.bind((host, port))
        self._sock.listen(5)
        self.addr = self._sock.getsockname()
        self._shutdown = threading.Event()

    def serve_forever(self) -> None:
        """Accept loop: one thread per connection.

        A node's map runs for minutes; with a serial loop that would block
        the master's pings and chunked fetches (and a reassigned shard's
        RPC) for the whole duration.  Connections are served concurrently;
        ``map`` commands still serialize under ``self._map_lock`` — the
        node has ONE accelerator and concurrent maps would contend for it.
        """
        while not self._shutdown.is_set():
            try:
                self._sock.settimeout(0.5)
                conn, peer = self._sock.accept()
            except socket.timeout:
                continue
            except OSError:
                break
            self._conn_slots.acquire()
            try:
                t = threading.Thread(
                    target=self._serve_one, args=(conn,), daemon=True
                )
                t.start()
            except Exception as e:  # noqa: BLE001 - spawn can fail under
                # thread/fd pressure; the ACCEPT LOOP must survive it (a
                # dead accept loop is a dead worker the master sees only
                # as timeouts), and it must not leak the slot or conn.
                self._conn_slots.release()
                try:
                    conn.close()
                except OSError:
                    pass
                print(
                    f"[worker] connection thread spawn failed "
                    f"({type(e).__name__}: {e}); dropped conn from "
                    f"{peer}, still accepting",
                    file=sys.stderr, flush=True,
                )
        self._sock.close()

    def _serve_one(self, conn: socket.socket) -> None:
        try:
            self._serve_conn(conn)
        finally:
            self._conn_slots.release()

    def _serve_conn(self, conn: socket.socket) -> None:
        """Serve REQUESTS on this connection until the peer closes or goes
        silent — the persistent-connection contract the master's pipelined
        fetch rides (it keeps several chunk requests in flight; we answer
        strictly in order, so responses need no sequence numbers).

        ``files`` caches one open handle per fetched path for the
        connection's lifetime: a windowed transfer of a multi-GB
        intermediate costs one open(), not one per chunk.
        """
        files: dict[str, tuple] = {}
        try:
            with conn:
                while not self._shutdown.is_set():
                    try:
                        # A silent peer must not hang the daemon: bound the
                        # read.  A clean peer close lands here too (recv of
                        # 0 bytes -> ConnectionError) — the loop exit.
                        conn.settimeout(self.conn_timeout)
                        req = protocol.recv_frame(conn, self.secret)
                    except PermissionError:
                        return  # unauthenticated/replayed peer: drop silently
                    except (ConnectionError, socket.timeout, OSError):
                        return  # peer closed / idled out
                    except Exception as e:
                        # Malformed frame: the stream cannot be resynced,
                        # but the daemon must survive (no remote DoS) —
                        # structured reply, then drop the connection.
                        self._try_reply(
                            conn, {"status": "error", "error": str(e)}
                        )
                        return
                    try:
                        self._replay_guard.check(req)
                        conn.settimeout(None)  # map subprocesses may run long
                        resp = self._handle(req, files)
                    except PermissionError:
                        return  # replayed frame: drop silently
                    except faultplan.FaultCrash:
                        return  # injected 'process crash': drop, no reply
                    except Exception as e:
                        resp = {"status": "error", "error": str(e)}
                    if not self._try_reply(conn, resp):
                        return
        finally:
            for fh, _ in files.values():
                try:
                    fh.close()
                except OSError:
                    pass

    def _try_reply(self, conn: socket.socket, resp) -> bool:
        """Send one reply frame — JSON, or binary when the handler returned
        a ``(meta, encoded_body, flags)`` triple.  False on a dead peer."""
        try:
            if isinstance(resp, tuple):
                meta, body, flags = resp
                protocol.send_bin_frame_encoded(
                    conn, meta, body, self.secret, flags=flags
                )
            else:
                protocol.send_frame(conn, resp, self.secret, sign_fresh=False)
            return True
        except OSError:
            return False

    def serve_in_thread(self) -> threading.Thread:
        t = threading.Thread(target=self.serve_forever, daemon=True)
        t.start()
        return t

    def _handle(self, req: dict, files: dict | None = None):
        cmd = req.get("cmd")
        if cmd not in protocol.COMMANDS:
            return {"status": "error", "error": f"unknown command {cmd!r}"}
        # Chaos: straggler model — the worker stalls before handling
        # (tests/test_faults.py; no-op without an active plan).
        faultplan.delay(
            "rpc.delay",
            cmd=cmd, shard=req.get("node_num"), port=self.addr[1],
        )
        if cmd == "ping":
            return {"status": "ok", "pong": True}
        if cmd == "shutdown":
            self._shutdown.set()
            return {"status": "ok", "bye": True}
        if cmd == "map":
            return self._traced_map(req)
        if cmd == "serve_batch":
            return self._serve_batch(req)
        if cmd == "serve_stats":
            return self._serve_stats()
        if cmd == "plan_stage":
            return self._plan_stage(req)
        # fetch: stream back an intermediate file this worker produced, one
        # bounded window per request so arbitrarily large intermediates fit
        # the frame limit (the master pipelines ``offset`` windows until
        # ``eof``).  Containment boundary = self.workdir (server config,
        # NOT the request).
        path = req.get("path", "")
        real = os.path.realpath(path)
        if not real.startswith(self.workdir + os.sep):
            return {"status": "error", "error": "path outside workdir"}
        try:
            offset = int(req.get("offset", 0))
            max_bytes = int(req.get("max_bytes", protocol.FETCH_CHUNK))
        except (TypeError, ValueError):
            return {"status": "error", "error": "bad offset/max_bytes"}
        if offset < 0:
            return {"status": "error", "error": "negative offset"}
        max_bytes = max(1, min(max_bytes, protocol.FETCH_CHUNK_MAX))
        try:
            data, size = self._read_window(real, offset, max_bytes, files)
        except OSError as e:
            return {"status": "error", "error": str(e)}
        # eof/total reflect the REAL read (pre-fault): an injected disk-rot
        # corruption/truncation must look like a worker that believes it
        # delivered the bytes — the master's sha256 verification is what
        # catches it, not the fault being polite about itself.
        eof = offset + len(data) >= size
        data = faultplan.mangle(
            "io.intermediate", data,
            path=real, offset=offset, port=self.addr[1],
        )
        # Per-chunk digest over the RAW window: covers the wire encoding
        # round-trip (base64 or zlib) and anything between this read and
        # the master's disk write.
        meta = {
            "status": "ok",
            "sha256": hashlib.sha256(data).hexdigest(),
            "offset": offset,
            "total": size,
            "eof": eof,
        }
        tctx = req.get(protocol.TRACE_KEY)
        if isinstance(tctx, dict) and tctx.get("id"):
            # Correlation echo in the reply (binary frame) meta: every
            # fetched chunk is attributable to the job's trace_id.
            meta["trace_id"] = str(tctx["id"])
        if not (req.get("bin") and self.support_binary):
            # Pre-binary master (or a worker pinned JSON-only): the
            # original base64 JSON reply, byte for byte.
            return dict(meta, data_b64=base64.b64encode(data).decode())
        # Binary data plane: raw payload, zlib'd when the master accepts
        # it and it actually shrinks the chunk.
        flags, body, enc = 0, data, "raw"
        if req.get("accept_zlib") and data:
            packed = zlib.compress(data, 1)
            if len(packed) < len(data):
                flags, body, enc = protocol.FLAG_ZLIB, packed, "zlib"
        # Chaos: the ENCODED payload about to be framed (docs/DATAPLANE.md).
        # The frame MAC is computed AFTER this, so an injected corruption
        # reaches the master as a zlib error or chunk-sha mismatch — the
        # data-plane failure mode, distinct from rpc.frame's MAC reject.
        rule = faultplan.fire(
            "io.chunk", path=real, offset=offset, port=self.addr[1], enc=enc
        )
        if rule is not None:
            if rule.action == "delay":
                time.sleep(rule.delay_s)
            else:
                body = faultplan.active().mutate(rule, body)
        return dict(meta, enc=enc, clen=len(body)), body, flags

    def _traced_map(self, req: dict) -> dict:
        """Run one map command under a REQUEST-scoped tracer when the
        master stamped a trace context into the request.

        The tracer is per-request (not the process tracer): a loopback
        cluster shares one process with its master, and the worker's
        spans must travel the same path as a remote worker's — serialized
        in the reply ("spans") with the worker's wall clock ("clock") for
        the master's offset estimate — never leak directly into a tracer
        enabled in this process (``obs.scoped`` masks it either way).
        An error reply ships its spans too (a failed attempt is exactly
        the part of a chaos timeline worth reading); an injected CRASH
        drops the connection before any reply, so those spans are lost
        with the "process" — faithful to what a real SIGKILL leaves.
        """
        tctx = req.get(protocol.TRACE_KEY)
        tracer = None
        if isinstance(tctx, dict) and tctx.get("id"):
            tracer = obs.Tracer(
                trace_id=str(tctx["id"]),
                process=f"worker:{self.addr[1]}",
            )
        with obs.scoped(tracer):
            with obs.span(
                "worker.map",
                shard=req.get("node_num"),
                port=self.addr[1],
            ):
                resp = self._run_map(req)
        if tracer is not None and isinstance(resp, dict):
            resp["spans"] = tracer.serialize()
            resp["clock"] = time.time()
        return resp

    def _run_map(self, req: dict) -> dict:
        rule = faultplan.fire(
            "worker.map", shard=req.get("node_num"), port=self.addr[1]
        )
        if rule is not None:
            if rule.action == "crash":
                raise faultplan.FaultCrash("injected crash mid-map")
            if rule.action == "error":
                return {"status": "error", "returncode": -9,
                        "log": "[faultplan] injected map failure",
                        "error": "injected map failure"}
            if rule.action == "delay":
                time.sleep(rule.delay_s)
        try:
            with self._map_lock:  # one accelerator: maps serialize
                resp = self.map_runner(req)
        except Exception as e:  # propagate failure, don't fake-ACK
            return {"status": "error", "error": repr(e)}
        if resp.get("status") == "ok" and "sha256" not in resp:
            # End-to-end integrity anchor: hash the intermediate at
            # map time so the master can verify the assembled fetch
            # against what the map actually wrote (Dean & Ghemawat's
            # checksummed intermediates).  A runner that wrote no
            # file (injected test runners) just ships no digest —
            # the master skips the end-to-end check then, and a
            # truly missing intermediate still fails at fetch time.
            inter = resp.get("intermediate") or req.get("intermediate")
            try:
                resp["sha256"] = _file_sha256(inter)
            except (OSError, TypeError):
                pass
        return resp

    # ------------------------------------------------- serve-batch surface

    def _serve_stats(self) -> dict:
        """The pool's warm-cache RPC (serve/pool.py seed_affinity): which
        shapes this worker already holds compiled.  A daemon restarting
        against a warm fleet re-learns affinity homes from this instead
        of cold-spraying its first batches."""
        if self._serve_cache is None:
            return {"status": "error",
                    "error": "serve dispatch not enabled (start with --serve)"}
        return {
            "status": "ok",
            "exec_cache": self._serve_cache.stats(),
            "warm_shapes": self._serve_cache.warm_shapes(),
        }

    def _serve_batch(self, req: dict) -> dict:
        """Fold one coalesced serve batch on this worker's engine.

        The daemon's pool (serve/pool.py) sends the batch meta plus
        content-addressed corpus REFERENCES — ``spill_dir/<sha>.bin``
        files the journal/pool already wrote once — and this handler
        verifies every sha before folding, so a stale, torn, or
        misdirected spill is a structured error, never a silent wrong
        answer.  Shard entries carry ``line_start``/``line_end`` (the
        same half-open line-range contract as the map command) and fold
        just that slice.  Dispatches serialize under ``_map_lock`` (one
        accelerator per node, same stance as map)."""
        if self._serve_cache is None:
            return {"status": "error",
                    "error": "serve dispatch not enabled (start with --serve)"}
        if protocol.EPOCH_KEY in req:
            try:
                stale = self._epoch_guard.observe(req[protocol.EPOCH_KEY])
            except (TypeError, ValueError):
                return {"status": "error",
                        "error": f"bad fencing epoch "
                                 f"{req[protocol.EPOCH_KEY]!r}"}
            if stale is not None:
                # The zombie-primary fence: this worker has already
                # served a newer primary — obeying the old one would be
                # the split-brain double-answer HA forbids.  The ONE
                # fencing-reply shape (serve/replicate.py): the reply
                # carries the high-water epoch so the fenced daemon
                # adopts the REAL fence instead of guessing.
                from locust_tpu.serve.replicate import stale_reply

                return stale_reply(stale, None)
        from locust_tpu.config import EngineConfig
        from locust_tpu.serve import batch as batching
        from locust_tpu.serve.jobs import (
            SPEC_CONFIG_KEYS,
            WORKLOADS,
            Job,
            JobSpec,
        )

        workload = req.get("workload")
        if workload not in WORKLOADS:
            return {"status": "error",
                    "error": f"unknown workload {workload!r}"}
        overrides = req.get("config") or {}
        if not isinstance(overrides, dict) or (
            set(overrides) - set(SPEC_CONFIG_KEYS)
        ):
            return {"status": "error",
                    "error": f"bad config overrides {overrides!r}"}
        try:
            cfg = EngineConfig(**overrides)
            bucket = int(req["bucket"])
            spill_dir = str(req["spill_dir"])
            jobs_meta = list(req["jobs"])
        except (KeyError, TypeError, ValueError) as e:
            return {"status": "error", "error": f"bad serve_batch: {e}"}
        if not jobs_meta:
            return {"status": "error", "error": "serve_batch with no jobs"}
        spec = JobSpec(tenant="pool", workload=workload, cfg=cfg)
        corpora: dict[str, list] = {}
        jobs: list[Job] = []
        for jm in jobs_meta:
            try:
                sha = str(jm["sha"])
                job_id = str(jm["job_id"])
                a = jm.get("line_start")
                b = jm.get("line_end")
            except (KeyError, TypeError):
                return {"status": "error", "error": f"bad job entry {jm!r}"}
            try:
                lines = self._serve_corpus_lines(sha, spill_dir)
            except ValueError as e:
                return {"status": "error", "error": str(e)}
            if a is not None or b is not None:
                lines = lines[int(a or 0):
                              int(b) if b is not None else len(lines)]
            # Each (sha, slice) is its own staging key: two shards of one
            # corpus must not alias each other's lines.
            ckey = f"{sha}:{a}:{b}"
            n_lines = len(lines)
            n_blocks, jbucket = batching.job_shape(n_lines, cfg)
            if jbucket > bucket:
                return {"status": "error",
                        "error": f"job {job_id}: {n_lines} lines need "
                                 f"bucket {jbucket} > batch bucket {bucket}"}
            corpora[ckey] = lines
            jobs.append(Job(
                job_id=job_id, spec=spec, corpus_digest=ckey,
                n_lines=n_lines, n_blocks=n_blocks, bucket=bucket,
            ))
        njobs_padded = batching.bucket_blocks(len(jobs))
        try:
            with self._map_lock:  # one accelerator: folds serialize
                engine, hit = self._serve_cache.lookup(
                    spec, njobs_padded, bucket
                )
                results = batching.dispatch_batch(engine, jobs, corpora)
                self._serve_cache.mark_compiled(spec, njobs_padded, bucket)
                out = []
                for job, res in zip(jobs, results):
                    pairs = res.to_host_pairs()
                    out.append({
                        "job_id": job.job_id,
                        "pairs": [
                            [base64.b64encode(k).decode(), int(v)]
                            for k, v in pairs
                        ],
                        "distinct": int(res.num_segments),
                        "truncated": bool(res.truncated),
                        "overflow_tokens": int(res.overflow_tokens),
                    })
        except Exception as e:  # noqa: BLE001 - structured, worker survives
            return {"status": "error",
                    "error": f"serve dispatch failed: "
                             f"{type(e).__name__}: {e}"}
        return {"status": "ok", "warm": bool(hit), "results": out}

    # ------------------------------------------------ plan-stage surface

    def _plan_stage(self, req: dict) -> dict:
        """One distributed-plan stage on this worker (docs/PLAN.md
        "Distributed execution"): phase "map" folds one source split and
        publishes its shuffle partitions atomically into the spill dir;
        phase "reduce" pulls one partition's inputs from their map
        workers over the binary data plane and returns the combined
        table.  Epoch-fenced like serve_batch: a fenced-out zombie
        primary can never get a stale partition published."""
        if self._serve_cache is None:
            return {"status": "error",
                    "error": "serve dispatch not enabled (start with --serve)"}
        if protocol.EPOCH_KEY in req:
            try:
                stale = self._epoch_guard.observe(req[protocol.EPOCH_KEY])
            except (TypeError, ValueError):
                return {"status": "error",
                        "error": f"bad fencing epoch "
                                 f"{req[protocol.EPOCH_KEY]!r}"}
            if stale is not None:
                from locust_tpu.serve.replicate import stale_reply

                return stale_reply(stale, None)
        phase = req.get("phase")
        # Chaos: the stage RPC boundary (docs/FAULTS.md).  "crash" models
        # the worker SIGKILL'd mid-stage (connection dropped, no reply —
        # the coordinator recomputes the stage on a survivor); "error" a
        # structured stage failure; "delay" a straggler the coordinator's
        # speculative backup races.
        rule = faultplan.fire(
            "plan.stage", phase=phase, split=req.get("split"),
            part=req.get("part"), port=self.addr[1],
        )
        if rule is not None:
            if rule.action == "crash":
                raise faultplan.FaultCrash("injected crash mid-plan-stage")
            if rule.action == "error":
                return {"status": "error",
                        "error": "[faultplan] injected plan stage failure"}
            if rule.action == "delay":
                time.sleep(rule.delay_s)
        try:
            with obs.span(
                "plan.stage", phase=phase, split=req.get("split"),
                part=req.get("part"), port=self.addr[1],
            ):
                if phase == "map":
                    return self._plan_map_stage(req)
                if phase == "reduce":
                    return self._plan_reduce_stage(req)
                if phase == "join":
                    return self._plan_join_stage(req)
                if phase == "iterate":
                    return self._plan_iterate_stage(req)
                return {"status": "error",
                        "error": f"unknown plan stage phase {phase!r}"}
        except Exception as e:  # noqa: BLE001 - structured, worker survives
            return {"status": "error",
                    "error": f"plan stage failed: {type(e).__name__}: {e}"}

    def _plan_map_stage(self, req: dict) -> dict:
        """Fold one source split and publish its shuffle partitions.

        The split's lines come from the content-addressed corpus spill
        (sha-verified, like serve_batch); doc ids are GLOBAL
        (``(line_start + i) // lines_per_doc``) so the per-split fold is
        exactly a restriction of the solo fold.  Partition files publish
        atomically under (plan fp, split, partition, attempt) — a
        recompute or speculative backup can never clobber a live file.
        """
        import numpy as np

        from locust_tpu.config import EngineConfig
        from locust_tpu.plan import distribute
        from locust_tpu.serve import batch as batching
        from locust_tpu.serve.jobs import SPEC_CONFIG_KEYS, Job, JobSpec

        overrides = req.get("config") or {}
        if not isinstance(overrides, dict) or (
            set(overrides) - set(SPEC_CONFIG_KEYS)
        ):
            return {"status": "error",
                    "error": f"bad config overrides {overrides!r}"}
        try:
            cfg = EngineConfig(**overrides)
            fold = str(req["fold"])
            sha = str(req["sha"])
            spill_dir = str(req["spill_dir"])
            plan_fp = str(req["plan_fp"])
            split = int(req["split"])
            attempt = int(req["attempt"])
            n_parts = int(req["n_parts"])
            a = int(req["line_start"])
            b = int(req["line_end"])
            lines_per_doc = int(req.get("lines_per_doc", 1))
        except (KeyError, TypeError, ValueError) as e:
            return {"status": "error", "error": f"bad plan_stage: {e}"}
        try:
            lines = self._serve_corpus_lines(sha, spill_dir)
        except ValueError as e:
            return {"status": "error", "error": str(e)}
        sl = lines[a:b]
        truncated, overflow = False, 0
        warm = False
        if fold == "wordcount":
            spec = JobSpec(tenant="pool", workload="wordcount", cfg=cfg)
            n_blocks, bucket = batching.job_shape(len(sl), cfg)
            ckey = f"{sha}:{a}:{b}"
            node_fp = str(req.get("node_fp") or "")
            job = Job(
                job_id=f"plan-{plan_fp}-s{split}", spec=spec,
                corpus_digest=ckey, n_lines=len(sl), n_blocks=n_blocks,
                bucket=bucket,
            )
            with self._map_lock:  # one accelerator: folds serialize
                if node_fp:
                    # Warm by the fold node's CLOSURE fingerprint
                    # (cache.fold_node_key): a repeat distributed plan
                    # — alpha-renamed included — lands every map split
                    # on this worker's already-compiled executable, so
                    # ``compiles`` stays flat on resubmit (the warm
                    # economics PR 11 proved for whole serve jobs).
                    engine, warm = self._serve_cache.lookup_fold_node(
                        node_fp, cfg, 1, bucket
                    )
                else:
                    engine, warm = self._serve_cache.lookup(
                        spec, 1, bucket
                    )
                res = batching.dispatch_batch(
                    engine, [job], {ckey: sl}
                )[0]
                if node_fp:
                    self._serve_cache.mark_compiled_fold_node(
                        node_fp, cfg.fingerprint(), 1, bucket
                    )
                else:
                    self._serve_cache.mark_compiled(spec, 1, bucket)
                pairs = res.to_host_pairs()
                truncated = bool(res.truncated)
                overflow = int(res.overflow_tokens)
            enc = pairs
        elif fold in ("tf", "index"):
            from locust_tpu.apps.tfidf import term_doc_counts

            ids = ((a + np.arange(len(sl))) // lines_per_doc).astype(
                np.int32
            )
            with self._map_lock:
                # The index fold tolerates per-line emit overflow the
                # way build_inverted_index does (warn, drop) — the solo
                # path's exact semantics; tf raises, also solo-exact.
                tf = term_doc_counts(
                    sl, ids, cfg, allow_overflow=(fold == "index")
                )
            enc = [
                (distribute.encode_key(fold, k), v)
                for k, v in tf.items()
            ]
        else:
            return {"status": "error", "error": f"unknown fold {fold!r}"}
        parts = distribute.publish_split(
            spill_dir, plan_fp, split, attempt, enc, n_parts
        )
        return {
            "status": "ok",
            "split": split,
            "attempt": attempt,
            "worker": f"{self.addr[0]}:{self.addr[1]}",
            "parts": parts,
            "truncated": truncated,
            "overflow_tokens": overflow,
            "warm": bool(warm),
        }

    def _plan_reduce_stage(self, req: dict) -> dict:
        """Combine one shuffle partition from its per-split input files.

        Inputs published by OTHER workers move worker-to-worker over the
        distributor's binary HMAC'd data plane (master.fetch_file:
        pipelined windows, sha-verified end to end) — the daemon never
        relays partition bytes.  ANY lost/damaged input answers a
        structured error naming ``lost_split`` so the coordinator
        recomputes exactly that map split from its durable corpus split,
        not the whole plan."""
        try:
            part = int(req["part"])
            key_width = int(req["key_width"])
            inputs = list(req["inputs"])
        except (KeyError, TypeError, ValueError) as e:
            return {"status": "error", "error": f"bad plan_stage: {e}"}
        me = f"{self.addr[0]}:{self.addr[1]}"
        acc, err = self._merge_partition_inputs(inputs, key_width, part)
        if err is not None:
            return err
        return {
            "status": "ok",
            "part": part,
            "worker": me,
            "pairs": [
                [base64.b64encode(k).decode(), int(v)]
                for k, v in sorted(acc.items())
            ],
        }

    def _merge_partition_inputs(
        self, inputs: list, key_width: int, part: int
    ) -> tuple[dict | None, dict | None]:
        """The reduce/join stages' shared input gather: read (local) or
        pull (remote) every per-split partition file for one bin and
        sum-merge.  Returns (table, None) or (None, structured error
        reply naming ``lost_split``)."""
        from locust_tpu.plan import distribute

        me = f"{self.addr[0]}:{self.addr[1]}"
        acc: dict = {}
        for ref in inputs:
            try:
                path = str(ref["path"])
                sha = str(ref["sha256"])
                owner = str(ref["worker"])
                split = int(ref["split"])
            except (KeyError, TypeError, ValueError):
                return None, {"status": "error",
                              "error": f"bad partition ref {ref!r}"}
            if int(ref.get("pairs", 1)) == 0:
                continue  # published empty: nothing to move or merge
            try:
                if owner == me:
                    pairs = distribute.read_partition(path, sha, key_width)
                else:
                    pairs = self._pull_partition(
                        owner, path, sha, key_width, part
                    )
            except Exception as e:  # noqa: BLE001 - structured loss report
                return None, {
                    "status": "error",
                    "lost_split": split,
                    "error": f"partition input lost (split {split}, "
                             f"part {part}, {owner}): "
                             f"{type(e).__name__}: {e}",
                }
            distribute.merge_pairs(acc, pairs)
        return acc, None

    def _plan_join_stage(self, req: dict) -> dict:
        """Evaluate one co-partitioned hash-join bin, tree-deep.

        The bin's wordcount table merges from its per-split partition
        inputs exactly like a reduce stage; then the WHOLE join tree
        evaluates over it locally (``distribute.eval_tree_doc`` — host
        Python ints, the solo ``_eval_join`` semantics) — however deep
        the tree, the bin never returns to the master between joins
        (docs/PLAN.md "Distributed execution").  ``distinct`` reports
        the bin's pre-join table size so the coordinator can prove the
        solo fold would not have truncated (its capacity gate)."""
        from locust_tpu.plan import distribute

        try:
            part = int(req["part"])
            key_width = int(req["key_width"])
            inputs = list(req["inputs"])
            tree = list(req["tree"])
        except (KeyError, TypeError, ValueError) as e:
            return {"status": "error", "error": f"bad plan_stage: {e}"}
        me = f"{self.addr[0]}:{self.addr[1]}"
        acc, err = self._merge_partition_inputs(inputs, key_width, part)
        if err is not None:
            return err
        try:
            joined = distribute.eval_tree_doc(tree, acc)
        except (KeyError, IndexError, TypeError, ValueError) as e:
            return {"status": "error",
                    "error": f"bad join tree {tree!r}: {e}"}
        return {
            "status": "ok",
            "part": part,
            "worker": me,
            "distinct": len(acc),
            "pairs": [
                [base64.b64encode(k).decode(), int(v)]
                for k, v in sorted(joined.items())
            ],
        }

    def _plan_iterate_stage(self, req: dict) -> dict:
        """One pagerank epoch on one rank shard (docs/PLAN.md
        "Distributed execution").

        The worker holds the loop-invariant graph state (edge arrays,
        inv_deg, dangling mask — cached per corpus sha, shard-filtered
        to ``dst in [lo, hi)``), reconstructs the previous epoch's full
        rank vector from ALL shards' published partitions (shard order
        is node order), runs ONE bit-exact ``pagerank_step`` and
        publishes its own slice for the next epoch.  Epoch 1 starts
        from the solo path's exact ``ranks0``.  A lost input partition
        answers structured ``(lost_epoch, lost_split)`` so the
        coordinator recomputes exactly that (epoch, shard) stage."""
        import numpy as np

        from locust_tpu.plan import distribute

        try:
            sha = str(req["sha"])
            spill_dir = str(req["spill_dir"])
            plan_fp = str(req["plan_fp"])
            epoch = int(req["epoch"])       # 1-based sweep number
            shard = int(req["shard"])
            n_shards = int(req["n_shards"])
            num_nodes = int(req["num_nodes"])
            damping = float(req["damping"])
            attempt = int(req["attempt"])
            inputs = req.get("inputs")      # None on epoch 1
        except (KeyError, TypeError, ValueError) as e:
            return {"status": "error", "error": f"bad plan_stage: {e}"}
        try:
            src_sub, dst_sub, inv_deg, dangling = self._iterate_graph(
                sha, spill_dir, num_nodes, shard, n_shards
            )
        except ValueError as e:
            return {"status": "error", "error": str(e)}
        me = f"{self.addr[0]}:{self.addr[1]}"
        if inputs is None:
            # The solo scan's exact ranks0: 1/n rounded double->f32.
            ranks = np.full(
                (num_nodes,), 1.0 / num_nodes, dtype=np.float32
            )
        else:
            slices = []
            for ref in sorted(inputs, key=lambda r: int(r["part"])):
                try:
                    path = str(ref["path"])
                    rsha = str(ref["sha256"])
                    owner = str(ref["worker"])
                    part = int(ref["part"])
                except (KeyError, TypeError, ValueError):
                    return {"status": "error",
                            "error": f"bad partition ref {ref!r}"}
                try:
                    if owner == me or os.path.exists(path):
                        pairs = distribute.read_partition(
                            path, rsha, distribute.RANK_KEY_WIDTH
                        )
                    else:
                        pairs = self._pull_partition(
                            owner, path, rsha,
                            distribute.RANK_KEY_WIDTH, part,
                        )
                except Exception as e:  # noqa: BLE001 - structured loss
                    return {
                        "status": "error",
                        "lost_split": part,
                        "lost_epoch": epoch - 1,
                        "error": f"rank partition lost (epoch "
                                 f"{epoch - 1}, shard {part}, {owner}): "
                                 f"{type(e).__name__}: {e}",
                    }
                slices.append(distribute.decode_rank_values(pairs))
            ranks = np.concatenate(slices) if slices else np.zeros(
                0, np.float32
            )
            if len(ranks) != num_nodes:
                return {"status": "error",
                        "error": f"rank vector reassembled {len(ranks)} "
                                 f"of {num_nodes} nodes"}
        from locust_tpu.apps.pagerank import pagerank_step

        lo, hi = distribute.shard_ranges(num_nodes, n_shards)[shard]
        with self._map_lock:  # one accelerator: device steps serialize
            new = np.asarray(pagerank_step(
                src_sub, dst_sub, ranks, inv_deg, dangling,
                damping, num_nodes,
            ))
        ref = distribute.publish_partition(
            distribute.partition_path(
                spill_dir, plan_fp, epoch, shard, attempt
            ),
            distribute.encode_rank_pairs(lo, new[lo:hi]),
        )
        ref["part"] = shard
        return {
            "status": "ok",
            "epoch": epoch,
            "shard": shard,
            "attempt": attempt,
            "worker": me,
            "ref": ref,
        }

    def _iterate_graph(
        self, sha: str, spill_dir: str, num_nodes: int, shard: int,
        n_shards: int,
    ) -> tuple:
        """The iterate stages' loop-invariant state, cached per (corpus
        sha, num_nodes, shard layout): parsed edge arrays restricted to
        this shard's dst range plus the FULL inv_deg/dangling vectors
        (``pagerank_prep``, bit-exact vs the solo kernel's prologue).
        Raises ``ValueError`` on a missing/damaged spill or a corpus
        that does not parse as an edge list."""
        import numpy as np

        from locust_tpu.plan import distribute

        key = (sha, int(num_nodes), int(n_shards), int(shard))
        with self._iterate_lock:
            ent = self._iterate_graphs.pop(key, None)
            if ent is not None:
                self._iterate_graphs[key] = ent  # LRU touch
                return ent
        path = os.path.join(spill_dir, f"{sha}.bin")
        try:
            with open(path, "rb") as f:
                data = f.read()
        except OSError as e:
            raise ValueError(f"corpus spill unreadable: {e}")
        if hashlib.sha256(data).hexdigest() != sha:
            raise ValueError(f"corpus spill {sha} fails its content hash")
        from locust_tpu.apps.pagerank import pagerank_prep
        from locust_tpu.plan.compile import PlanError, edges_from_bytes

        try:
            src, dst = edges_from_bytes(data)
        except PlanError as e:
            raise ValueError(f"corpus is not an edge list: {e}")
        src = np.asarray(src, np.int32)
        dst = np.asarray(dst, np.int32)
        with self._map_lock:
            inv_deg, dangling = pagerank_prep(src, num_nodes)
            inv_deg = np.asarray(inv_deg)
            dangling = np.asarray(dangling)
        lo, hi = distribute.shard_ranges(num_nodes, n_shards)[shard]
        mask = (dst >= lo) & (dst < hi)
        ent = (src[mask], dst[mask], inv_deg, dangling)
        with self._iterate_lock:
            self._iterate_graphs[key] = ent
            while len(self._iterate_graphs) > 4:
                self._iterate_graphs.pop(next(iter(self._iterate_graphs)))
        return ent

    def _pull_partition(
        self, owner: str, path: str, sha: str, key_width: int, part: int
    ) -> list:
        """Fetch one remote partition over the binary data plane and
        decode it.  The transfer verifies the file sha end-to-end
        (fetch_file's expect_sha) and the local decode re-verifies —
        a mangled wire or disk byte is a loss, never a wrong answer."""
        from locust_tpu.distributor import master
        from locust_tpu.plan import distribute

        host, _, port = owner.rpartition(":")
        local = os.path.join(
            self.workdir,
            f"pull_{os.path.basename(path)}.{os.getpid()}."
            f"{threading.get_ident()}",
        )
        with obs.span("plan.shuffle", part=part, src=owner):
            try:
                master.fetch_file(
                    (host, int(port)), path, local, self.secret,
                    expect_sha=sha, rpc_timeout=120.0,
                )
                return distribute.read_partition(local, sha, key_width)
            finally:
                try:
                    os.unlink(local)
                except OSError:
                    pass

    def _serve_corpus_lines(self, sha: str, spill_dir: str) -> list:
        """One spilled corpus read+verified+split, through the tiny LRU
        cache.  Raises ``ValueError`` with the structured message on a
        missing/damaged spill — a stale or torn spill must never fold."""
        with self._serve_corpus_lock:
            ent = self._serve_corpus.pop(sha, None)
            if ent is not None:
                self._serve_corpus[sha] = ent  # LRU touch
                return ent
        path = os.path.join(spill_dir, f"{sha}.bin")
        try:
            with open(path, "rb") as f:
                data = f.read()
        except OSError as e:
            raise ValueError(f"corpus spill unreadable: {e}")
        if hashlib.sha256(data).hexdigest() != sha:
            raise ValueError(f"corpus spill {sha} fails its content hash")
        lines = data.splitlines()
        with self._serve_corpus_lock:
            self._serve_corpus[sha] = lines
            while len(self._serve_corpus) > 2:
                self._serve_corpus.pop(next(iter(self._serve_corpus)))
        return lines

    def _read_window(
        self, real: str, offset: int, max_bytes: int, files: dict | None
    ) -> tuple[bytes, int]:
        """One bounded window, through the per-connection handle cache."""
        if files is None:  # direct _handle call (unit tests): no cache
            with open(real, "rb") as f:
                size = os.fstat(f.fileno()).st_size
                f.seek(offset)
                return f.read(max_bytes), size
        ent = files.get(real)
        if ent is None:
            while len(files) >= self.MAX_CACHED_FILES:
                _, (old, _) = files.popitem()
                try:
                    old.close()
                except OSError:
                    pass
            fh = open(real, "rb")
            ent = files[real] = (fh, os.fstat(fh.fileno()).st_size)
        fh, size = ent
        fh.seek(offset)
        return fh.read(max_bytes), size


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="locust-worker")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=1337)  # reference port, slave.py:7
    p.add_argument("--secret-env", default="LOCUST_SECRET",
                   help="env var holding the shared secret")
    p.add_argument("--fault-plan", default=None,
                   help="chaos-test fault plan: JSON text or a path "
                        f"(also ${faultplan.ENV_VAR}); see docs/FAULTS.md")
    p.add_argument("--workdir", default="/tmp",
                   help="fetch containment boundary (server-side config)")
    p.add_argument("--serve", action="store_true",
                   help="answer serve_batch dispatches from a serve "
                        "daemon's worker pool (docs/SERVING.md "
                        "scale-out dispatch); holds warm engines")
    p.add_argument("--serve-max-engines", type=int, default=4,
                   help="warm engines kept by the serve cache (LRU)")
    args = p.parse_args(argv)
    from locust_tpu.config import compile_cache_dir

    compile_cache_dir()  # before the first `import jax` (and for children)
    faultplan.install(args.fault_plan)
    secret = os.environ.get(args.secret_env, "").encode()
    if not secret:
        print(f"error: set ${args.secret_env} (refusing unauthenticated mode)",
              file=sys.stderr)
        return 2
    w = Worker(args.host, args.port, secret, workdir=args.workdir,
               serve=args.serve, serve_max_engines=args.serve_max_engines)
    print(f"[worker] listening on {w.addr[0]}:{w.addr[1]}", file=sys.stderr)
    w.serve_forever()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
