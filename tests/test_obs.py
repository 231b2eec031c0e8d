"""Unified telemetry (locust_tpu.obs) — tracer, merge, schema, overhead.

The acceptance scenario lives here: a loopback 2-worker chaos WordCount
must produce ONE merged Chrome-trace document — master spans, both
workers' map child spans correlated by trace_id, a checkpoint-lifecycle
event, and the injected fault as an instant — validated against the
checked-in schema (locust_tpu/obs/trace.schema.json).  Plus the tier-1 overhead
guard: telemetry disabled (the default) is a no-op path whose cost is
negligible against a single block fold.
"""

from __future__ import annotations

import json
import os
import time

import numpy as np
import pytest

from helpers import py_wordcount

from locust_tpu import cli, obs
from locust_tpu.config import EngineConfig
from locust_tpu.distributor import master, protocol
from locust_tpu.distributor.worker import Worker
from locust_tpu.engine import MapReduceEngine
from locust_tpu.obs import attribution
from locust_tpu.obs.schema import validate_trace
from locust_tpu.utils import faultplan

SECRET = b"obs-secret"

CORPUS = b"""alpha beta gamma
beta gamma delta
gamma delta epsilon
delta epsilon alpha
epsilon alpha beta
alpha beta beta
"""


@pytest.fixture(autouse=True)
def _clean_tracer():
    """Every test starts and ends with telemetry disabled — a leaked
    global tracer would silently change other tests' hot paths."""
    obs.disable()
    yield
    obs.disable()


# ------------------------------------------------------------- tracer unit


def test_span_event_metrics_roundtrip(tmp_path):
    t = obs.enable(process="unit")
    with obs.span("cli.run", phase="outer"):
        with obs.span("cli.load"):
            pass
        obs.event("ckpt.mark", generation=7)
    obs.metric_inc("stream.blocks", 3)
    obs.metric_set("job.workers", 2)
    obs.metric_observe("stream.stall_ms", 1.25)
    obs.metric_observe("stream.stall_ms", 0.75)
    doc = obs.export(str(tmp_path / "t.trace.json"))
    validate_trace(doc)
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    names = [e["name"] for e in spans]
    assert names.count("cli.run") == 1 and names.count("cli.load") == 1
    outer = next(e for e in spans if e["name"] == "cli.run")
    inner = next(e for e in spans if e["name"] == "cli.load")
    # Chrome nesting contract: the child's interval is contained.
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1
    m = doc["otherData"]["metrics"]
    assert m["counters"]["stream.blocks"] == 3
    assert m["gauges"]["job.workers"] == 2
    h = m["histograms"]["stream.stall_ms"]
    assert h["count"] == 2 and h["min"] == 0.75 and h["max"] == 1.25
    assert doc["otherData"]["trace_id"] == t.trace_id
    # The exported file parses back to the same document.
    on_disk = json.load(open(tmp_path / "t.trace.json"))
    assert on_disk["otherData"]["trace_id"] == t.trace_id


def test_closed_registry_rejects_unknown_and_mismatched_names():
    t = obs.enable()
    with pytest.raises(ValueError, match="not in the obs NAMES registry"):
        t.span("no.such.name")
    with pytest.raises(ValueError, match="kind mismatch"):
        t.event("cli.run")  # registered as a span
    with pytest.raises(ValueError, match="not in the obs NAMES registry"):
        obs.metric_inc("no.such.counter")  # locust: noqa[R009] deliberate bad name: exercises the runtime validator R009 mirrors
    with pytest.raises(ValueError, match="kind mismatch"):
        obs.metric_observe("stream.blocks", 1.0)  # locust: noqa[R009] deliberate kind mismatch: exercises the runtime validator R009 mirrors


def test_ingest_shifts_clock_offset_and_assigns_pids():
    t = obs.enable(process="master")
    w = obs.Tracer(trace_id=t.trace_id, process="worker:1")
    with obs.scoped(w):
        with obs.span("worker.map", shard=0):
            pass
    [span] = [e for e in w.serialize() if e["ph"] == "X"]
    # A worker whose clock runs 2s ahead must land 2s earlier.
    t.ingest([span], offset_s=2.0, process="worker a")
    t.ingest([span], offset_s=0.0, process="worker b")
    doc = t.to_chrome()
    merged = [e for e in doc["traceEvents"] if e["name"] == "worker.map"]
    assert len(merged) == 2
    assert abs((merged[1]["ts"] - merged[0]["ts"]) - 2e6) < 1.0
    assert merged[0]["pid"] != merged[1]["pid"] != 0
    labels = {
        e["args"]["name"]
        for e in doc["traceEvents"]
        if e["ph"] == "M" and e["name"] == "process_name"
    }
    assert {"master", "worker a", "worker b"} <= labels
    # Malformed entries are skipped, never raised on.
    assert t.ingest([{"ph": "X"}, "junk", {"ph": "q", "ts": 1}]) == 0


def test_scoped_masks_and_restores():
    g = obs.enable(process="global")
    assert obs.current() is g
    with obs.scoped(None):
        assert obs.current() is None
        assert obs.span("cli.run") is obs.span("cli.load")  # null singleton
    inner = obs.Tracer(process="req")
    with obs.scoped(inner):
        assert obs.current() is inner
        with obs.span("worker.map"):
            pass
    assert obs.current() is g
    assert inner.counts()["spans"] == 1
    assert g.counts()["spans"] == 0


# ------------------------------------------------- disabled-path overhead


def test_disabled_path_is_noop_and_within_bench_noise():
    """Tier-1 overhead guard for the acceptance bound: with telemetry
    disabled (the default), the instrumentation must cost a negligible
    fraction of one block fold — the bench's throughput stays within its
    ±5% noise band by arithmetic, not by luck.

    run_stream's hot loop pays ~4 hook calls per block (span + stall
    event + 2 metrics); a fold is >= 1 ms even at toy shapes.  So the
    guard: (a) the disabled span is one shared singleton (no per-call
    allocation of tracer state), (b) measured per-block hook cost is
    under 5% of a MEASURED small-engine fold time, with an absolute
    ceiling that fails loudly if someone puts real work on the disabled
    path."""
    assert obs.current() is None
    s = obs.span("stream.block", i=0)
    assert s is obs.span("engine.stage.map") is obs.span("cli.run")
    assert obs.event("stream.stall", ms=0.0) is None
    assert obs.metric_inc("stream.blocks") is None

    n = 20_000
    t0 = time.perf_counter()
    for i in range(n):
        with obs.span("stream.block", i=i, staging="ring"):
            pass
        obs.event("stream.stall", block=i, ms=0.0)
        obs.metric_inc("stream.blocks")
        obs.metric_observe("stream.stall_ms", 0.0)
    per_block_s = (time.perf_counter() - t0) / n
    assert per_block_s < 50e-6, (
        f"disabled telemetry costs {per_block_s*1e6:.1f}µs per block — "
        "not a no-op path any more"
    )

    # In-situ: against a real (tiny, hence fastest-case) fold.
    eng = MapReduceEngine(
        EngineConfig(block_lines=64, line_width=32, key_width=8,
                     emits_per_line=4)
    )
    rows = eng.rows_from_lines([b"alpha beta gamma"] * 64)
    eng.run(rows)  # compile
    t0 = time.perf_counter()
    for _ in range(3):
        eng.run(rows)
    fold_s = (time.perf_counter() - t0) / 3
    assert per_block_s / fold_s < 0.05, (
        f"disabled hooks are {100 * per_block_s / fold_s:.2f}% of even a "
        "toy fold — the zero-overhead contract is broken"
    )


# ------------------------------------------------ loopback cross-node trace


def make_runner(tmp_path):
    """In-process map runner (shared JAX runtime) WITH checkpointing, so
    worker-side ckpt lifecycle events land in the request trace."""

    def runner(req):
        ck = os.path.join(
            str(tmp_path), "ck_" + os.path.basename(req["intermediate"])
        )
        args = [
            req["file"],
            str(req["line_start"]), str(req["line_end"]),
            str(req["node_num"]), "1",
            "-i", req["intermediate"],
            "--block-lines", "2", "--line-width", "64",
            "--emits-per-line", "8", "--no-timing",
            "--checkpoint-dir", ck, "--checkpoint-every", "1",
        ]
        if req.get("inter_format"):
            args += ["--inter-format", req["inter_format"]]
        rc = cli.main(args)
        return {"status": "ok" if rc == 0 else "error", "returncode": rc,
                "log": "", "intermediate": req["intermediate"]}

    return runner


def test_loopback_two_worker_chaos_run_produces_merged_schema_valid_trace(
    tmp_path,
):
    corpus = tmp_path / "corpus.txt"
    corpus.write_bytes(CORPUS)
    tracer = obs.enable(process="master")
    workers = [
        Worker(secret=SECRET, map_runner=make_runner(tmp_path))
        for _ in range(2)
    ]
    for w in workers:
        w.serve_in_thread()
    cluster = [w.addr for w in workers]
    plan = faultplan.FaultPlan(
        [{"site": "worker.map", "action": "error",
          "match": {"shard": 0}, "times": 1}],
        seed=3,
    )
    try:
        with faultplan.active_plan(plan):
            result = master.run_job(
                cluster, str(corpus), SECRET,
                workdir=str(tmp_path / "wd"), max_retries=2,
            )
        doc = result.timeline()
        assert doc is not None
        validate_trace(doc)
        assert doc["otherData"]["trace_id"] == tracer.trace_id

        events = doc["traceEvents"]
        names = {e["name"] for e in events}
        # Master spans + worker child spans + ckpt lifecycle + the fault.
        assert {"job.run", "master.map_rpc", "master.fetch",
                "worker.map", "cli.run", "ckpt.mark",
                "fault.injected"} <= names

        # Both workers' maps, merged under distinct pids with labels.
        wm_pids = {e["pid"] for e in events if e["name"] == "worker.map"}
        assert len(wm_pids) == 2 and 0 not in wm_pids
        labels = {
            e["args"]["name"] for e in events
            if e["ph"] == "M" and e["name"] == "process_name"
        }
        assert sum(lbl.startswith("worker ") for lbl in labels) == 2

        # The injected fault is an instant event with its site/action —
        # shipped in the ERROR reply's span list (failed attempts are
        # the part of a chaos timeline worth reading).
        faults = [e for e in events if e["name"] == "fault.injected"]
        assert faults and faults[0]["ph"] == "i"
        assert faults[0]["args"]["site"] == "worker.map"
        assert faults[0]["args"]["action"] == "error"
        # ... and the shard-0 retry means >= 3 map RPC spans total.
        assert sum(1 for e in events if e["name"] == "master.map_rpc") >= 3

        # The job still produced the right answer under chaos.
        expect = py_wordcount(CORPUS.splitlines(), 8)
        got = {}
        for path in result:
            from locust_tpu.io import serde

            k, v = serde.read_intermediate(path, 32)
            for key_row, val in zip(k, v):
                key = bytes(key_row).rstrip(b"\x00")
                got[key] = got.get(key, 0) + int(val)
        assert got == dict(expect)
    finally:
        for w in workers:
            w._shutdown.set()


def test_untraced_job_has_no_timeline_and_no_trace_keys(tmp_path):
    """Telemetry off (default): requests carry no trace key, replies ship
    no spans, timeline() is None — the wire is byte-for-byte the
    pre-telemetry wire."""
    corpus = tmp_path / "c.txt"
    corpus.write_bytes(CORPUS)
    seen = []

    w = Worker(secret=SECRET, map_runner=make_runner(tmp_path))
    w.serve_in_thread()

    def spy_rpc(node, req, s):
        seen.append(dict(req))
        return master._rpc(node, req, s, timeout=60)

    try:
        result = master.run_job(
            [w.addr], str(corpus), SECRET,
            workdir=str(tmp_path / "wd"), rpc=spy_rpc,
        )
        assert result.timeline() is None
        assert all(protocol.TRACE_KEY not in r for r in seen)
    finally:
        w._shutdown.set()


# -------------------------------------------------- device-time attribution


def test_attributed_run_joins_families_onto_stage_spans(tmp_path):
    eng = MapReduceEngine(
        EngineConfig(block_lines=8, line_width=32, key_width=8,
                     emits_per_line=4, sort_mode="hash")
    )
    rows = eng.rows_from_lines([b"alpha beta alpha", b"beta gamma"] * 8)
    eng.timed_run(rows)  # compile outside the capture
    tracer = obs.enable(process="attr")
    res, summary, xplane, join = attribution.attributed_run(
        lambda: eng.timed_run(rows), str(tmp_path / "prof"), "hash"
    )
    assert "error" not in summary, summary
    assert join["process_family"] == "sort"
    # The engine's hash mode IS a sort: the family must be measured.
    assert join["process_device_ms"] and join["process_device_ms"] > 0
    doc = tracer.to_chrome()
    proc = [
        e for e in doc["traceEvents"]
        if e["name"] == "engine.stage.process" and e["ph"] == "X"
    ]
    assert proc, "timed_run under the tracer must emit process spans"
    assert all(
        e["args"].get("process_family") == "sort"
        and e["args"].get("process_device_ms") == join["process_device_ms"]
        for e in proc
    )
    joins = [
        e for e in doc["traceEvents"] if e["name"] == "obs.device_join"
    ]
    assert joins and joins[0]["args"]["spans_annotated"] == len(proc)


def test_attribution_family_join_pairs_hasht_mxu_with_all_three_families():
    """hasht-mxu's Process time is sort + scatter + the one-hot dots:
    pairing its bytes with a dot-free time would inflate utilization."""
    join = attribution.family_join(
        {"sort_ms": 5.0, "scatter_ms": 2.0, "dot_ms": 1.0,
         "device_total_ms": 10.0, "device_plane": "/host:CPU"},
        "hasht-mxu",
    )
    assert join["process_family"] == "scatter+sort+dot"
    assert join["process_device_ms"] == 8.0


def test_engine_config_trace_knob_enables_process_tracer():
    assert obs.current() is None
    eng = MapReduceEngine(
        EngineConfig(block_lines=8, line_width=32, key_width=8,
                     emits_per_line=4, trace=True)
    )
    tracer = obs.current()
    assert tracer is not None
    eng.timed_run(eng.rows_from_lines([b"a b a"]))
    assert any(
        e["name"] == "engine.stage.process"
        for e in tracer.to_chrome()["traceEvents"]
    )


# ------------------------------------------------------------ bench summary


def test_obs_summary_shape_for_bench_subdict():
    assert obs.summary() == {"enabled": False}
    obs.enable(process="bench")
    with obs.span("cli.run"):
        obs.metric_inc("stream.blocks")
    s = obs.summary()
    assert s["enabled"] is True and s["spans"] == 1
    assert s["metrics"]["counters"]["stream.blocks"] == 1
    assert isinstance(s["trace_id"], str)
