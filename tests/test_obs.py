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

from helpers import native_ingest_missing, py_wordcount

from locust_tpu import cli, obs
from locust_tpu.config import EngineConfig
from locust_tpu.distributor import master, protocol
from locust_tpu.distributor.worker import Worker
from locust_tpu.engine import MapReduceEngine
from locust_tpu.obs import trace as obs_trace
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
    assert s is obs.span("engine.h2d", bytes=1) is obs.span("engine.finalize")
    assert s is obs.span("engine.sync", what="map")
    # the job's tail, the mesh round's staging and the CLI's set-up (PR 37)
    assert s is obs.span("engine.finalize.d2h", rows=1)
    assert s is obs.span("engine.finalize.decode")
    assert s is obs.span("engine.finalize.order", rows=1)
    assert s is obs.span("cli.output.render", rows=1)
    assert s is obs.span("cli.output.write", bytes=1)
    assert s is obs.span("mesh.h2d", bytes=1)
    assert s.set(bytes=1, merged=0) is None
    assert obs.span_at("cli.setup", 0.0, 1.0) is None
    assert obs.event("stream.stall", ms=0.0) is None
    assert obs.metric_inc("stream.blocks") is None
    assert obs.span_at("engine.program.load", 0.0, 1.0) is None
    assert obs.watch_programs() is None

    n = 20_000
    t0 = time.perf_counter()
    for i in range(n):
        with obs.span("stream.block", i=i, staging="ring"):
            pass
        obs.event("stream.stall", block=i, ms=0.0)
        obs.metric_inc("stream.blocks")
        obs.metric_observe("stream.stall_ms", 0.0)
        # timed_run's additions: staging once a block, four waits once a
        # GROUP of blocks — charged to every block here, the worst case.
        with obs.span("engine.h2d", bytes=i):
            pass
        for what in ("map", "process", "reduce", "merge"):
            with obs.span("engine.sync", what=what):
                pass
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


# ------------------------------------------------ the span record (PR 24)


def _spans(doc_or_tracer):
    doc = (doc_or_tracer if isinstance(doc_or_tracer, dict)
           else doc_or_tracer.to_chrome())
    return [e for e in doc["traceEvents"] if e["ph"] == "X"]


def _encloses(outer, inner, slack_us=1.0):
    return (outer["ts"] - slack_us <= inner["ts"]
            and inner["ts"] + inner["dur"]
            <= outer["ts"] + outer["dur"] + slack_us)


def test_ids_are_unique_and_parents_enclose_on_the_same_thread():
    import threading

    t = obs.enable(process="ids")

    def work():
        with obs.span("cli.run"):
            for _ in range(3):
                with obs.span("cli.load"):
                    with obs.span("cli.output"):
                        pass

    threads = [threading.Thread(target=work) for _ in range(4)]
    for th in threads:
        th.start()
    work()
    for th in threads:
        th.join(timeout=30)
        assert not th.is_alive()
    spans = _spans(t)
    assert len(spans) == 5 * 7
    by_id = {e["args"]["id"]: e for e in spans}
    assert len(by_id) == len(spans), "span ids must be unique"
    roots = [e for e in spans if "parent" not in e["args"]]
    assert len(roots) == 5 and all(e["name"] == "cli.run" for e in roots)
    # The root span carries the job's identifier, and only the root.
    assert all(e["args"]["trace_id"] == t.trace_id for e in roots)
    for e in spans:
        if e in roots:
            continue
        assert "trace_id" not in e["args"]
        parent = by_id[e["args"]["parent"]]
        assert parent["tid"] == e["tid"], "a parent is on the same thread"
        assert _encloses(parent, e), (parent, e)
        assert parent["name"] == {"cli.load": "cli.run",
                                  "cli.output": "cli.load"}[e["name"]]


def test_self_times_on_a_hand_built_nest():
    """A layer's self time is its duration minus what its children cover
    — the UNION of their intervals, cut to the parent."""

    def x(sid, ts, dur, parent=None):
        args = {"id": sid} if parent is None else {"id": sid, "parent": parent}
        return {"name": "n", "ph": "X", "ts": ts, "dur": dur, "pid": 0,
                "tid": 0, "args": args}

    events = [
        x(1, 0.0, 100.0),
        x(2, 10.0, 20.0, parent=1),        # [10, 30]
        x(3, 25.0, 15.0, parent=1),        # [25, 40] overlaps span 2
        x(4, 90.0, 20.0, parent=1),        # [90, 110] sticks out: cut at 100
        x(5, 12.0, 4.0, parent=2),
        x(6, 500.0, 7.0),                  # a second root, no children
        {"name": "i", "ph": "i", "ts": 5.0, "pid": 0, "tid": 0, "args": {}},
    ]
    got = obs_trace.self_times(events)
    assert got == {1: 100.0 - 30.0 - 10.0, 2: 16.0, 3: 15.0, 4: 20.0,
                   5: 4.0, 6: 7.0}
    # The tracer's method is the same rule over its own records.
    t = obs.enable(process="self")
    with obs.span("cli.run"):
        with obs.span("cli.load"):
            time.sleep(0.01)
    st = t.self_times()
    run, load = (next(e for e in _spans(t) if e["name"] == n)
                 for n in ("cli.run", "cli.load"))
    assert st[load["args"]["id"]] == load["dur"] >= 10_000
    assert abs(st[run["args"]["id"]] - (run["dur"] - load["dur"])) < 0.2


def test_clock_is_monotonic_under_a_stepped_wall_clock(monkeypatch):
    """Timestamps are perf_counter anchored ONCE to the epoch: a wall
    clock stepped back an hour mid-run cannot fold a span."""
    t = obs.enable(process="clock")
    real = time.time
    with obs.span("cli.run"):
        with obs.span("cli.load"):
            pass
        monkeypatch.setattr(time, "time", lambda: real() - 3600.0)
        with obs.span("cli.output"):
            pass
    obs.event("ckpt.mark")
    monkeypatch.undo()
    run, load, out = (next(e for e in _spans(t) if e["name"] == n)
                      for n in ("cli.run", "cli.load", "cli.output"))
    assert load["ts"] <= out["ts"] and _encloses(run, out)
    mark = next(e for e in t.to_chrome()["traceEvents"] if e["ph"] == "i")
    assert mark["ts"] >= out["ts"]
    assert abs(run["ts"] / 1e6 - real()) < 60, "still epoch microseconds"


def test_span_at_lands_inside_the_open_span_on_the_tracers_clock():
    t = obs.enable(process="at")
    with obs.span("engine.stage.merge"):
        t0 = time.time()
        time.sleep(0.02)
        t1 = time.time()
        obs.span_at("engine.program.load", t0, t1, fun_name="jit(f)")
    merge, load = (next(e for e in _spans(t) if e["name"] == n)
                   for n in ("engine.stage.merge", "engine.program.load"))
    assert load["args"]["parent"] == merge["args"]["id"]
    assert load["args"]["fun_name"] == "jit(f)"
    assert abs(load["dur"] - (t1 - t0) * 1e6) < 1.0
    assert _encloses(merge, load, slack_us=500.0)
    with pytest.raises(ValueError, match="not in the obs NAMES registry"):
        t.span_at("engine.program.nope", t0, t1)
    obs.disable()
    assert obs.span_at("engine.program.load", t0, t1) is None  # a no-op


def test_ingest_gives_remote_spans_fresh_ids_and_repoints_parents():
    t = obs.enable(process="master")
    with obs.span("job.run", job="j"):
        pass
    w = obs.Tracer(trace_id=t.trace_id, process="worker:1")
    with obs.scoped(w):
        with obs.span("worker.map", shard=0):
            with obs.span("cli.run"):
                pass
    t.ingest(w.serialize(), process="worker a")
    t.ingest(w.serialize(), process="worker b")
    spans = _spans(t)
    ids = [e["args"]["id"] for e in spans]
    assert len(set(ids)) == len(ids) == 5
    for child in (e for e in spans if e["name"] == "cli.run"):
        parent = next(e for e in spans
                      if e["args"]["id"] == child["args"]["parent"])
        assert parent["name"] == "worker.map"
        assert parent["pid"] == child["pid"] != 0
    validate_trace(t.to_chrome())


def test_schema_accepts_the_span_record_and_rejects_a_broken_one():
    t = obs.enable(process="schema")
    with obs.span("cli.run", phase="x"):
        with obs.span("engine.h2d", bytes=512):
            pass
        obs.span_at("engine.program.trace", time.time(), time.time(),
                    fun_name="f")
    doc = t.to_chrome(obs.metrics_snapshot())
    validate_trace(doc)
    bad = json.loads(json.dumps(doc))
    span = next(e for e in bad["traceEvents"] if e["name"] == "engine.h2d")
    span["args"]["id"] = "seven"
    with pytest.raises(ValueError, match=r"args\.id: expected integer"):
        validate_trace(bad)
    bad = json.loads(json.dumps(doc))
    del next(e for e in bad["traceEvents"]
             if e["name"] == "engine.h2d")["args"]["id"]
    with pytest.raises(ValueError, match="span needs an integer args.id"):
        validate_trace(bad)
    bad = json.loads(json.dumps(doc))
    next(e for e in bad["traceEvents"]
         if e["name"] == "engine.h2d")["args"]["parent"] = 10_000
    with pytest.raises(ValueError, match="parent 10000 is no span"):
        validate_trace(bad)


# ------------------------------------------ timed_run under a tracer (PR 24)

_SMALL = dict(block_lines=8, line_width=32, key_width=8, emits_per_line=4)


def _listeners():
    import jax._src.monitoring as m

    return (len(m.get_event_time_span_listeners()),
            len(m.get_event_listeners()))


_SMALL_BLOCK_BYTES = 8 * 32 + 3 * (8 * 4) * (8 + 4 + 1)  # staged + 3 KVBatch


@pytest.mark.parametrize("budget_blocks, groups, fan_in", [
    (None, [4], 9),    # the shipped budget: the whole job is one group, under a full one
    (2, [2, 2], 2),    # several full groups
    (3, [3, 1], 3),    # a last group that is short
    (0, [1, 1, 1, 1], 1),  # a budget under one block still makes progress
])
def test_timed_run_names_staging_waits_and_finalize_per_group(
    monkeypatch, budget_blocks, groups, fan_in,
):
    """Once a block engine.h2d; per GROUP exactly four stage spans, each
    saying how many blocks it launched and holding exactly one engine.sync
    CHILD of its own ``what`` (so a stage's self time is host launch plus,
    in a merge stage, the next group's staging); every launch of a stage
    program inside its stage span, the merge's once a group and taking as
    many tables as a full group has blocks; one overflow read a JOB, under no
    stage; one engine.finalize; children never outlast their stage."""
    if budget_blocks is not None:
        monkeypatch.setattr(MapReduceEngine, "TIMED_GROUP_BYTES",
                            budget_blocks * _SMALL_BLOCK_BYTES)
    eng = MapReduceEngine(EngineConfig(**_SMALL))
    rows = eng.rows_from_lines([b"alpha beta alpha", b"beta gamma"] * 16)
    nblocks = -(-rows.shape[0] // 8)
    assert nblocks == 4 == sum(groups)
    t = obs.enable(process="timed")
    launched = []  # (stage, id of the span open at the launch)

    def recording(stage, program):
        def launch(*args):
            launched.append((stage, t._open_spans()[-1]))
            return program(*args)
        return launch

    for stage in ("map", "process", "reduce", "merge"):
        monkeypatch.setattr(eng, f"_{stage}",
                            recording(stage, getattr(eng, f"_{stage}")))
    res = eng.timed_run(rows)
    spans = _spans(t)
    names = [e["name"] for e in spans]
    assert [e["args"]["bytes"] for e in spans
            if e["name"] == "engine.h2d"] == [8 * 32] * nblocks
    stages = sorted((e for e in spans if e["name"].startswith("engine.stage.")),
                    key=lambda e: e["ts"])
    assert [e["name"].rsplit(".", 1)[1] for e in stages] == [
        "map", "process", "reduce", "merge"] * len(groups)
    assert [e["args"]["blocks"] for e in stages] == [
        g for g in groups for _ in range(4)]
    syncs = [e for e in spans if e["name"] == "engine.sync"]
    assert [e["args"]["what"] for e in syncs] == [
        "map", "process", "reduce", "merge"] * len(groups) + ["overflow"]
    assert names.count("engine.finalize") == 1
    by_id = {e["args"]["id"]: e for e in spans}
    st = t.self_times()
    for stage in stages:
        kids = [e for e in spans
                if e["args"].get("parent") == stage["args"]["id"]]
        mine = [e for e in kids if e["name"] == "engine.sync"]
        assert len(mine) == 1
        assert mine[0]["args"]["what"] == stage["name"].rsplit(".", 1)[1]
        assert sum(e["dur"] for e in kids) <= stage["dur"] + 0.2
        assert all(_encloses(stage, e) for e in kids)
        assert 0 <= st[stage["args"]["id"]] <= stage["dur"]
    parent = by_id.get(syncs[-1]["args"].get("parent"))
    assert parent is None or not parent["name"].startswith("engine.stage")
    # The first group is staged before any stage runs, each later one
    # while the device works off the merges of the group before it.
    staged_in = [by_id.get(e["args"].get("parent")) for e in spans
                 if e["name"] == "engine.h2d"]
    assert not any(p and p["name"].startswith("engine.stage")
                   for p in staged_in[:groups[0]])
    merges = [e for e in stages if e["name"] == "engine.stage.merge"]
    assert [p["args"]["id"] for p in staged_in[groups[0]:]] == [
        m["args"]["id"] for m, g in zip(merges, groups[1:]) for _ in range(g)]
    # Map, process and reduce once a block, the merge once a group.
    assert len(launched) == 3 * nblocks + len(groups)
    assert [(m["args"]["tables"], m["args"]["merges"]) for m in merges] == [
        (fan_in, 1)] * len(groups)
    assert all(by_id[open_id]["name"] == f"engine.stage.{stage}"
               for stage, open_id in launched)
    # The decode is the same span name, so a metric sums both per job.
    assert res.to_host_pairs() == [(b"alpha", 32), (b"beta", 32), (b"gamma", 16)]
    assert [e["name"] for e in _spans(t)].count("engine.finalize") == 2
    validate_trace(t.to_chrome())


def test_program_spans_in_a_fresh_engines_first_job_only():
    """What jax reports of its own pipeline lands in the timeline: a
    configuration's first engine traces, lowers and loads its programs in
    its first job (each span names its function), a second run on the
    same engine does none of it, and neither does a second fresh engine
    of the configuration: it takes the process's programs.  The counters
    are the operator's copy of the benchmark's compiles_in_window."""
    t = obs.enable(process="programs")
    eng = MapReduceEngine(EngineConfig(**_SMALL))
    rows = eng.rows_from_lines([b"alpha beta alpha", b"beta gamma"] * 4)
    eng.timed_run(rows)
    first = [e for e in _spans(t) if e["name"].startswith("engine.program.")]
    kinds = {e["name"].rsplit(".", 1)[1] for e in first}
    assert kinds == {"trace", "lower", "load"}
    assert all(e["args"]["fun_name"] for e in first)
    by_id = {e["args"]["id"]: e for e in _spans(t)}
    under_stage = [e for e in first if "parent" in e["args"]
                   and by_id[e["args"]["parent"]]["name"].startswith("engine.stage.")]
    assert under_stage, "a stage's first call holds its program's reload"
    counters = obs.metrics_snapshot()["counters"]
    loads = sum(1 for e in first if e["name"] == "engine.program.load")
    assert counters["engine.compile_requests"] >= 1
    assert 0 <= counters.get("engine.cache_hits", 0) <= counters["engine.compile_requests"] <= loads
    mark = len(_spans(t))
    eng.timed_run(rows)
    again = _spans(t)[mark:]
    assert again and not [e for e in again
                          if e["name"].startswith("engine.program.")]
    assert counters["engine.merges"] == 1      # one group, one merge a job
    assert (counters["engine.programs_built"], counters["engine.programs_shared"]) == (1, 0)
    assert obs.metrics_snapshot()["counters"] == dict(counters, **{"engine.merges": 2})
    mark = len(_spans(t))
    shared = MapReduceEngine(EngineConfig(**_SMALL))
    assert shared.timed_run(rows).to_host_pairs() == eng.timed_run(rows).to_host_pairs()
    assert not [e for e in _spans(t)[mark:]
                if e["name"].startswith("engine.program.")]
    assert obs.metrics_snapshot()["counters"] == dict(
        counters, **{"engine.merges": 4, "engine.programs_shared": 1})


def test_enable_disable_cycles_leave_no_monitoring_listener():
    """The benchmark calls cli.main 130 times a process: a listener per
    job would pile up.  One pair while tracing, none after disable()."""
    before = _listeners()
    for _ in range(5):
        obs.enable(process="cycle")
        MapReduceEngine(EngineConfig(**_SMALL))
        MapReduceEngine(EngineConfig(**_SMALL))  # a second engine: same pair
        assert _listeners() == (before[0] + 1, before[1] + 1)
        obs.disable()
        assert _listeners() == before
    obs.disable()  # idempotent
    assert _listeners() == before


@pytest.mark.parametrize("scope", ["process", "request"])
def test_timed_run_records_its_reader_thread_into_the_jobs_tracer(
    monkeypatch, scope,
):
    """An iterator of 9 blocks under a budget of 3 a group.  The first two
    are read inline, the other seven and the end of the source by the
    reader thread — every pull an engine.ingest.read span, on two threads,
    all in the tracer the JOB records into: the process's, or a
    request-scoped one (``obs.scoped``: tracers are thread-local, so the
    reader is scoped into its consumer's).  A pull that found the queue
    empty is an engine.ingest.wait span on the consumer's thread, outside
    engine.h2d, and the blocks handed over are counted ahead or waited."""
    monkeypatch.setattr(MapReduceEngine, "TIMED_GROUP_BYTES",
                        3 * _SMALL_BLOCK_BYTES)
    eng = MapReduceEngine(EngineConfig(**_SMALL))
    rows = eng.rows_from_lines([b"alpha beta alpha", b"beta gamma"] * 36)
    assert rows.shape[0] == 9 * 8
    process = obs.enable(process="timed")
    request = obs_trace.Tracer(process="request")
    t = process if scope == "process" else request

    def blocks():
        for i in range(0, 72, 8):
            if i == 2 * 8:       # the reader's first: its consumer must wait
                time.sleep(0.2)
            yield rows[i:i + 8].copy()

    with obs.scoped(t):
        pairs = eng.timed_run(blocks()).to_host_pairs()
    assert pairs == [(b"alpha", 72), (b"beta", 72), (b"gamma", 36)]
    events = [e for e in t.to_chrome()["traceEvents"] if e.get("ph") == "X"]
    reads = [e for e in events if e["name"] == "engine.ingest.read"]
    assert len(reads) == 9 + 1               # every block, and the end found
    main = next(e["tid"] for e in events if e["name"] == "engine.stage.map")
    assert [e["tid"] == main for e in sorted(reads, key=lambda e: e["ts"])] == (
        [True] * 2 + [False] * 8)
    assert len({e["tid"] for e in reads}) == 2
    assert all("parent" not in e["args"] for e in reads if e["tid"] != main)
    waits = [e for e in events if e["name"] == "engine.ingest.wait"]
    assert waits and all(e["tid"] == main for e in waits)
    by_id = {e["args"]["id"]: e for e in events}
    assert not any(by_id[e["args"]["parent"]]["name"] == "engine.h2d"
                   for e in waits if "parent" in e["args"])
    assert len([e for e in events if e["name"] == "engine.h2d"]) == 9
    other = request if scope == "process" else process
    assert not [e for e in other.to_chrome()["traceEvents"]
                if e.get("name", "").startswith("engine.")]
    counters = obs.metrics_snapshot()["counters"]
    if scope == "process":
        # Metrics are the process's: a request-scoped job counts nothing.
        ahead = counters["engine.ingest.blocks_ahead"]
        waited = counters["engine.ingest.blocks_waited"]
        assert ahead + waited == 7 and 1 <= waited <= len(waits)
    else:
        assert "engine.ingest.blocks_ahead" not in counters
    validate_trace(t.to_chrome())


def test_timed_run_with_tracing_off_allocates_no_span_and_no_listener(
    monkeypatch,
):
    def boom(*a, **k):
        raise AssertionError("the disabled path built a span")

    monkeypatch.setattr(obs_trace._Span, "__init__", boom)
    monkeypatch.setattr(obs_trace.Tracer, "span_at", boom)
    before = _listeners()
    assert obs.current() is None
    eng = MapReduceEngine(EngineConfig(**_SMALL))
    assert obs.watch_programs() is None
    rows = eng.rows_from_lines([b"a b a"] * 20)
    for corpus in (rows, iter([rows[:8], rows[8:16], rows[16:]])):
        res = eng.timed_run(corpus)
        assert res.to_host_pairs() == [(b"a", 40), (b"b", 20)]
    assert _listeners() == before


# ---------------------------- the job's tail and the CLI's set-up (PR 37)


TAIL = ("engine.finalize.d2h", "engine.finalize.decode", "engine.finalize.order")


def _tail_children(spans, parent):
    """The three spans of the table's way to the host under ``parent``,
    held to: one each, in order, inside the parent, covering it."""
    kids = sorted((e for e in spans
                   if e["args"].get("parent") == parent["args"]["id"]),
                  key=lambda e: e["ts"])
    assert [e["name"] for e in kids] == list(TAIL)
    assert all(_encloses(parent, e) for e in kids)
    assert all(a["ts"] + a["dur"] <= b["ts"] + 1.0 for a, b in zip(kids, kids[1:]))
    assert sum(e["dur"] for e in kids) >= 0.9 * parent["dur"], (kids, parent)
    return kids


def _many_words(n):
    return [b" ".join(b"w%05d" % (7 * i + j) for j in range(4))
            for i in range(n)]


@pytest.mark.parametrize("method", ["to_host_pairs", "to_host_rows"])
def test_the_tables_way_to_the_host_is_three_children_of_engine_finalize(method):
    """engine.finalize (the one of ``to_host_pairs`` or ``to_host_rows``;
    the count read of ``_finish`` keeps its own, childless) resolves into
    the copy, the numpy decode and the check (+ sort, for pairs): one
    each, in that order, none outlasting it, together at least nine
    tenths of it.  Rows say ``fast`` on the last; pairs do not."""
    eng = MapReduceEngine(EngineConfig(block_lines=256, line_width=32,
                                       key_width=8, emits_per_line=4))
    lines = _many_words(4096)
    rows = eng.rows_from_lines(lines)
    eng.timed_run(rows).to_host_pairs()  # programs built, caches warm
    t = obs.enable(process="tail")
    pairs = getattr(eng.timed_run(rows), method)()
    if method == "to_host_rows":
        pairs = pairs.pairs()
    assert dict(pairs) == py_wordcount(lines) and pairs == sorted(pairs)
    spans = _spans(t)
    count_read, decode = [e for e in spans if e["name"] == "engine.finalize"]
    assert not [e for e in spans
                if e["args"].get("parent") == count_read["args"]["id"]]
    d2h, dec, order = _tail_children(spans, decode)
    table = eng.timed_run(rows).table
    assert d2h["args"]["rows"] == table.size == decode["args"]["rows"]
    assert d2h["args"]["bytes"] == table.size * (table.num_lanes * 4 + 4 + 1)
    assert dec["args"]["rows"] == len(pairs) == order["args"]["rows"]
    assert order["args"]["merged"] == 0
    assert order["args"].get("fast") == (1 if method == "to_host_rows" else None)
    assert [e["name"] for e in spans if e["name"] in TAIL] == list(TAIL)
    validate_trace(t.to_chrome())


def _parent_to_host_pairs(batch, sort):
    """``KVBatch.to_host_pairs`` as the parent of PR 37 had it, in one
    piece: the reference for the cut into ``to_host`` + ``host_pairs``."""
    import jax

    from locust_tpu.core import bytes_ops

    lanes, values, valid = jax.device_get(
        (batch.key_lanes, batch.values, batch.valid))
    valid = np.asarray(valid)
    live_lanes = np.asarray(lanes)[valid]
    live_values = np.asarray(values)[valid]
    n_live, n_lanes = live_lanes.shape
    keys = live_lanes.astype(">u4").view(np.uint8).reshape(n_live, n_lanes * 4)
    if sort and n_live:
        order = np.argsort(keys.view(f"S{n_lanes * 4}").ravel(), kind="stable")
        keys, live_values = keys[order], live_values[order]
    return list(zip(bytes_ops.rows_to_strings(keys), live_values.tolist()))


def _parent_finalize(batch, combine, sort):
    pairs = _parent_to_host_pairs(batch, sort)
    if len(dict(pairs)) != len(pairs):
        op = {"sum": lambda a, b: a + b, "count": lambda a, b: a + b,
              "min": min, "max": max}[combine]
        merged = {}
        for k, v in pairs:
            merged[k] = op(merged[k], v) if k in merged else v
        pairs = list(merged.items())
    return sorted(pairs) if sort else pairs


_LAYOUTS = {
    # keys (8 bytes wide), values, valid, whether the merge by hand fires
    "plain": ([b"pear", b"apple", b"fig"], [3, 1, 2], [1, 1, 1], 0),
    "a NUL inside a key": ([b"ab\0z", b"ab", b"a\0b", b"a"], [1, 2, 3, 4], [1, 1, 1, 1], 1),  # cut at the NUL, so two pairs of equal keys
    "a forced duplicate row": ([b"dup", b"solo", b"dup", b"dup"], [5, 1, 7, -2], [1, 1, 1, 1], 1),
    "dead rows between live ones": ([b"x", b"", b"y", b"x"], [1, 9, 2, 4], [1, 0, 1, 0], 0),
    "nothing live": ([b"x", b"y"], [1, 2], [0, 0], 0),
}


@pytest.mark.parametrize("on_host", [False, True], ids=["device", "gathered"])
@pytest.mark.parametrize("combine", ["sum", "min"])
@pytest.mark.parametrize("sort", [True, False])
@pytest.mark.parametrize("layout", sorted(_LAYOUTS))
def test_finalize_equals_the_parents_in_one_piece(layout, sort, combine, on_host):
    """The cut of ``KVBatch.to_host_pairs`` into fetch and decode, and of
    ``finalize_host_pairs`` into three spans, changes no table: pair for
    pair the parent's, traced and untraced, for a device table and for
    one the mesh has gathered to numpy leaves already."""
    import jax.numpy as jnp

    from locust_tpu.core import bytes_ops
    from locust_tpu.core.kv import KVBatch
    from locust_tpu.engine import finalize_host_pairs

    keys, values, valid, merged = _LAYOUTS[layout]
    batch = KVBatch.from_bytes(
        jnp.asarray(bytes_ops.strings_to_rows(keys, 8)),
        jnp.asarray(np.asarray(values, np.int32)),
        jnp.asarray(np.asarray(valid, bool)))
    want = _parent_finalize(batch, combine, sort)
    fetch = {}
    if on_host:  # as DistributedResult.to_host_pairs hands its gather in
        fetch = {"fetch": lambda table: KVBatch(
            np.asarray(table.key_lanes), np.asarray(table.values),
            np.asarray(table.valid))}
    assert batch.to_host_pairs(sort) == _parent_to_host_pairs(batch, sort)
    assert batch.to_host().host_pairs(sort) == _parent_to_host_pairs(batch, sort)
    assert finalize_host_pairs(batch, combine, sort, **fetch) == want
    t = obs.enable(process="cut")
    assert finalize_host_pairs(batch, combine, sort, **fetch) == want
    spans = _spans(t)
    assert [e["name"] for e in spans] == list(TAIL)
    live = sum(valid)
    assert spans[1]["args"]["rows"] == live == spans[2]["args"]["rows"]
    assert spans[2]["args"]["merged"] == merged


@pytest.mark.parametrize("limit", [None, 0, 2, 99])
def test_print_table_writes_the_parents_bytes_in_two_spans(limit, capsysbinary):
    pairs = [(b"a\0b", 3), (b"apple", -1), (b"fig", 2147483647)]
    want = b"".join(k + b"\t" + str(v).encode() + b"\n"
                    for k, v in pairs[: limit if limit is not None else len(pairs)])
    cli._print_table(pairs, limit)
    assert capsysbinary.readouterr().out == want
    t = obs.enable(process="print")
    with obs.span("cli.output"):
        cli._print_table(pairs, limit)
    assert capsysbinary.readouterr().out == want
    out, render, write = sorted(_spans(t), key=lambda e: e["ts"])
    assert [render["name"], write["name"]] == ["cli.output.render", "cli.output.write"]
    assert render["args"]["parent"] == write["args"]["parent"] == out["args"]["id"]
    assert _encloses(out, render) and _encloses(out, write)
    assert render["args"]["rows"] == want.count(b"\n")
    assert write["args"]["bytes"] == len(want)


@pytest.mark.parametrize("flags", [[], ["--mesh"], ["--stream"], ["--no-timing"]],
                         ids=["default", "mesh", "stream", "no-timing"])
def test_cli_setup_ends_where_the_first_load_starts(flags, tmp_path, capsysbinary):
    """cli.setup runs from main's entry to the job's first cli.load and is
    recorded once: the plan's compile lies inside it (the default path's;
    the mesh makes its engine there instead), cli.load starts where it
    ends, and cli.output holds the render and the write."""
    corpus = tmp_path / "c.txt"
    corpus.write_bytes(CORPUS * 4)
    argv = [str(corpus), "--backend", "cpu", "--block-lines", "8",
            "--line-width", "32", "--key-width", "8", "--emits-per-line", "4"]
    assert cli.main(argv + flags) == 0
    untraced = capsysbinary.readouterr().out
    out = tmp_path / "t.trace.json"
    t0 = time.time()
    assert cli.main(argv + flags + ["--trace-out", str(out)]) == 0
    assert capsysbinary.readouterr().out == untraced  # stdout byte-equal
    doc = json.load(open(out))
    validate_trace(doc)
    spans = _spans(doc)
    [setup] = [e for e in spans if e["name"] == "cli.setup"]
    load = min((e for e in spans if e["name"] == "cli.load"), key=lambda e: e["ts"])
    assert "parent" not in setup["args"]
    assert t0 * 1e6 - 1e3 <= setup["ts"] and setup["dur"] > 0
    assert setup["ts"] + setup["dur"] <= load["ts"] + 50.0
    assert load["ts"] - (setup["ts"] + setup["dur"]) < 5e3
    compiled = [e for e in spans if e["name"] == "plan.compile"]
    assert len(compiled) == (0 if "--mesh" in flags else 1)
    assert all(_encloses(setup, e, slack_us=50.0) for e in compiled)
    [output] = [e for e in spans if e["name"] == "cli.output"]
    kids = sorted((e for e in spans if e["name"].startswith("cli.output.")),
                  key=lambda e: e["ts"])
    assert [e["name"] for e in kids] == ["cli.output.render", "cli.output.write"]
    assert all(e["args"]["parent"] == output["args"]["id"] for e in kids)
    assert kids[1]["args"]["bytes"] == len(untraced)
    assert kids[0]["args"]["rows"] == untraced.count(b"\n")
    parents = {e["args"]["id"]: e["name"] for e in spans}
    tails = [parents[e["args"]["parent"]] for e in spans if e["name"] in TAIL]
    assert tails == ["mesh.gather" if "--mesh" in flags else "engine.finalize"] * 3
    # Once a job each, the table printed from rows: fast on the check and
    # on the render, and the rows asked for inside cli.run.
    assert sorted(e["name"] for e in spans if e["name"] in TAIL) == sorted(TAIL)
    [order] = [e for e in spans if e["name"] == "engine.finalize.order"]
    assert order["args"]["fast"] == kids[0]["args"]["fast"] == 1
    assert "reason" not in order["args"]
    [run] = [e for e in spans if e["name"] == "cli.run"]
    assert _encloses(run, order) and run["ts"] + run["dur"] <= output["ts"] + 1.0


def test_sort_command_records_cli_setup_before_its_load(tmp_path, capsys):
    src, dst, out = tmp_path / "in", tmp_path / "out", tmp_path / "t.trace.json"
    src.write_bytes(np.random.default_rng(5).integers(
        0, 256, 64 * 100, dtype=np.uint8).tobytes())
    assert cli.main(["sort", str(src), str(dst), "--backend", "cpu",
                     "--trace-out", str(out)]) == 0
    capsys.readouterr()
    spans = _spans(json.load(open(out)))
    [setup] = [e for e in spans if e["name"] == "cli.setup"]
    [load] = [e for e in spans if e["name"] == "cli.load"]
    assert setup["ts"] + setup["dur"] <= load["ts"] + 50.0
    compiled = [e for e in spans if e["name"] == "plan.compile"]
    assert compiled and all(_encloses(setup, e, slack_us=50.0) for e in compiled)


def _host_annotations(xplane_path):
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(xplane_path)
    host = pd.find_plane_with_name("/host:CPU")
    assert host is not None
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for line in host.lines for e in line.events]


def _one_xplane(profile_dir):
    import glob

    found = glob.glob(os.path.join(str(profile_dir), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    assert len(found) == 1, found
    return found[0]


def test_spans_are_annotations_on_the_profilers_own_clock(tmp_path):
    """With a jax.profiler session open, every span is an event of
    /host:CPU in the session's .xplane.pb — same names, same nesting,
    durations equal to the exported ones within the annotation's own
    enter/exit cost."""
    import jax.profiler

    eng = MapReduceEngine(EngineConfig(**_SMALL))
    rows = eng.rows_from_lines([b"alpha beta alpha", b"beta gamma"] * 8)
    eng.timed_run(rows)  # compile outside the session
    t = obs.enable(process="anno")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with obs.span("cli.run"):
            eng.timed_run(rows)
    finally:
        jax.profiler.stop_trace()
    events = _host_annotations(_one_xplane(tmp_path))
    spans = _spans(t)
    for name in ("cli.run", "engine.h2d", "engine.stage.map",
                 "engine.stage.process", "engine.stage.reduce",
                 "engine.stage.merge", "engine.sync", "engine.finalize"):
        want = [e for e in spans if e["name"] == name]
        got = sorted((a, b) for n, a, b in events if n == name)
        assert len(got) == len(want) > 0, name
        for (a, b), e in zip(got, sorted(want, key=lambda e: e["ts"])):
            assert 0 <= (b - a) / 1e3 - e["dur"] < 500.0, (name, b - a, e)
    [(lo, hi)] = [(a, b) for n, a, b in events if n == "cli.run"]
    assert all(lo <= a and b <= hi for n, a, b in events
               if n.startswith("engine."))


def test_cli_profile_dir_and_trace_out_share_one_xplane(tmp_path, capsys):
    """python -m locust_tpu FILE --profile-dir DIR --trace-out T.json:
    one .xplane.pb holds the program's spans beside the device's lines;
    every exported span has an id, and a parent unless it is a root;
    the run leaves no jax.monitoring listener behind."""
    corpus = tmp_path / "c.txt"
    corpus.write_bytes(CORPUS * 4)
    before = _listeners()
    out = tmp_path / "t.trace.json"
    rc = cli.main([str(corpus), "--backend", "cpu", "--block-lines", "8",
                   "--line-width", "32", "--key-width", "8",
                   "--emits-per-line", "4", "--profile-dir",
                   str(tmp_path / "prof"), "--trace-out", str(out)])
    capsys.readouterr()
    assert rc == 0 and obs.current() is None
    assert _listeners() == before
    doc = json.load(open(out))
    validate_trace(doc)
    spans = _spans(doc)
    assert all(isinstance(e["args"]["id"], int) for e in spans)
    roots = [e for e in spans if "parent" not in e["args"]]
    assert {e["name"] for e in roots} <= {
        "cli.setup", "cli.load", "cli.run", "cli.output", "plan.optimize",
        "plan.compile",
        "engine.program.trace", "engine.program.lower", "engine.program.load",
    }
    assert all(e["args"]["trace_id"] == doc["otherData"]["trace_id"]
               for e in roots)
    names = {n for n, _, _ in _host_annotations(_one_xplane(tmp_path / "prof"))}
    assert {"cli.load", "cli.run", "plan.run", "engine.h2d",
            "engine.stage.map", "engine.stage.process",
            "engine.stage.reduce", "engine.stage.merge", "engine.sync",
            "engine.finalize", "cli.output"} <= names
    assert "engine.compile_requests" in doc["otherData"]["metrics"]["counters"]


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


# ------------------------------------- the edge parser says who read (PR 43)


@pytest.mark.parametrize("library, data, fast, native", [
    (True, b"# h\n0\t1\n1 2\n", 1, 1),
    (False, b"# h\n0\t1\n1 2\n", 1, 0),
    (True, b"0 1\n\n1 2\n", 0, 0),
    (False, b"0 1\n\n1 2\n", 0, 0),
], ids=["native", "numpy", "line-loop", "line-loop-library-missing"])
def test_pagerank_parse_names_its_reader_in_an_arg_and_a_counter(
        library, data, fast, native, monkeypatch):
    from locust_tpu.plan.compile import edges_from_bytes

    if not library:
        native_ingest_missing(monkeypatch)
    tracer = obs.enable(process="parse")
    src, dst = edges_from_bytes(data)
    assert (src.tolist(), dst.tolist()) == ([0, 1], [1, 2])
    (span,) = [e for e in tracer.to_chrome()["traceEvents"]
               if e.get("ph") == "X" and e["name"] == "pagerank.parse"]
    got = span["args"]
    assert (got["bytes"], got["edges"], got["fast"], got["native"]) == (len(data), 2, fast, native)
    assert obs.metrics_snapshot()["counters"]["pagerank.parse.native"] == native


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
