"""The record sort across a mesh — ``python -m locust_tpu sort IN OUT
--mesh`` — held to the plain reference (``locust_tpu/records_reference.py``)
on four of conftest's virtual devices: OUT byte-equal on records drawn by
``terasort-skew-3.2GB-mesh4``'s law (half the records tie), stability
across devices, the four shards as their devices hold them, skew that no
sample can save, the retry that drops nothing, the plan's lowering, the
programs built once a process, the spans and counters of a traced job.
"""

import contextlib
import io
import json
import os
import sys

import numpy as np
import pytest

from locust_tpu import engine, obs, records_reference
from locust_tpu.cli import main as cli_main
from locust_tpu.io.loader import RecordSource
from locust_tpu.obs import names
from locust_tpu.parallel import make_mesh
from locust_tpu.parallel import mesh as mesh_mod
from locust_tpu.parallel.record_sort import BinOverflow, MeshRecordSort

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir, "benchmarks"))
import records_skew  # noqa: E402  the deployment's own generator (numpy only)

RB, KB, DEVICES = 100, 10, 4


@pytest.fixture(autouse=True)
def four_devices(monkeypatch):
    """``--mesh`` takes every visible device (8 under conftest): the
    deployment has four."""
    whole = mesh_mod.make_mesh
    monkeypatch.setattr(
        mesh_mod, "make_mesh", lambda n=DEVICES, *a, **kw: whole(n, *a, **kw))


def _skewed(n: int, seed: int = 0, tmp=None) -> bytes:
    """``n`` records by the deployment's law."""
    path = os.path.join(str(tmp), f"law_{n}_{seed}.bin")
    records_skew.build(path, n, seed)
    with open(path, "rb") as f:
        return f.read()


def _number(rows: np.ndarray) -> np.ndarray:
    rows[:, -4:] = np.arange(rows.shape[0], dtype=">u4").view(np.uint8).reshape(-1, 4)
    return rows


def _sort(tmp_path, data: bytes, *flags):
    """(exit status, OUT's bytes or None, stderr) of one ``sort`` job."""
    src, out = tmp_path / "in.bin", tmp_path / "out.bin"
    src.write_bytes(data)
    err = io.StringIO()
    with contextlib.redirect_stderr(err):
        rc = cli_main(["sort", str(src), str(out), "--backend", "cpu", *flags])
    got = out.read_bytes() if out.exists() else None
    return rc, got, err.getvalue()


def _shard_lines(err: str) -> list[int]:
    return [int(line.split(": ")[1].split()[0]) for line in err.splitlines()
            if line.startswith("[locust] shard ")]


# under one block a device; no multiple of the devices or of the block;
# the rehearsal's 20,000; several rounds of blocks with a short last one
@pytest.mark.parametrize("n", [1, 3, 1001, 20000, 40003])
def test_mesh_out_is_the_reference_and_the_one_chip_sort(tmp_path, n, monkeypatch):
    if n == 40003:  # 4 KiB blocks of 32 rows: many rounds, a short last block
        monkeypatch.setattr(engine.MapReduceEngine, "RECORD_BLOCK_BYTES", 4096)
    data = _skewed(n, seed=n, tmp=tmp_path)
    rc, got, err = _sort(tmp_path, data, "--mesh")
    assert rc == 0, err
    assert got == records_reference.sorted_records(data)
    assert f"{n} records, {n * RB} bytes written" in err and "; 0 bytes lost" in err
    shards = _shard_lines(err)
    assert len(shards) == DEVICES and sum(shards) == n
    rc1, solo, err1 = _sort(tmp_path, data)
    assert rc1 == 0 and solo == got and not _shard_lines(err1)


@pytest.mark.parametrize("n", [1001, 20000])
def test_ties_come_out_in_input_order_across_devices(tmp_path, n):
    """A 2-byte key: records tie by the hundred wherever they were staged."""
    data = _skewed(n, seed=5, tmp=tmp_path)
    rc, got, err = _sort(tmp_path, data, "--mesh", "--key-bytes", "2")
    assert rc == 0, err
    assert got == records_reference.sorted_records(data, RB, 2)
    assert got != records_reference.sorted_records(data, RB, KB)
    assert min(_shard_lines(err)) > 0


def _lanes(row: bytes, index: int, key_bytes: int = KB) -> tuple:
    """(big-endian key lanes, global row index): a splitter's order."""
    key = row[:key_bytes] + b"\0" * (-key_bytes % 4)
    return (*(int.from_bytes(key[i:i + 4], "big") for i in range(0, len(key), 4)), index)


@pytest.mark.parametrize("n", [20000, 777])
def test_the_four_shards_are_the_references_rows_between_their_splitters(tmp_path, n):
    data = _skewed(n, seed=11, tmp=tmp_path)
    sorter = MeshRecordSort(make_mesh(DEVICES), RB, KB)
    ordered = sorter.sort(sorter.load(RecordSource.from_bytes(data, RB)))
    held = [b"".join(bytes(b) for b in ordered.host_blocks(shards=[d]))
            for d in range(DEVICES)]
    assert [len(h) // RB for h in held] == ordered.shard_rows
    reference = records_reference.sorted_records(data)
    assert b"".join(held) == reference                      # together the reference
    rows = [data[i * RB:(i + 1) * RB] for i in range(n)]
    splitters = [tuple(int(x) for x in s) for s in ordered.splitters]
    assert splitters == sorted(splitters) and len(splitters) == DEVICES - 1
    bounds = [None, *splitters, None]
    seen = set()
    for d in range(DEVICES):
        lo, hi = bounds[d], bounds[d + 1]
        mine = [i for i in sorted(range(n), key=lambda i: _lanes(rows[i], i))
                if (lo is None or _lanes(rows[i], i) >= lo)
                and (hi is None or _lanes(rows[i], i) < hi)]
        assert held[d] == b"".join(rows[i] for i in mine)   # the reference's rows between its splitters
        assert not seen & set(mine)                         # disjoint: every record once
        seen |= set(mine)
    assert len(seen) == n


def _one_key_on_most(n):
    rows = _number(np.random.default_rng(1).integers(0, 256, (n, RB), dtype=np.uint8))
    rows[np.random.default_rng(2).random(n) < 0.6, :KB] = 0x77
    return rows


def _all_keys_equal(n):
    rows = _number(np.random.default_rng(3).integers(0, 256, (n, RB), dtype=np.uint8))
    rows[:, :KB] = 0x42
    return rows


def _already_sorted(n):
    rows = np.random.default_rng(4).integers(0, 256, (n, RB), dtype=np.uint8)
    return rows[np.lexsort(rows[:, KB - 1::-1].T)]


def _reverse_sorted(n):
    return _already_sorted(n)[::-1]


@pytest.mark.parametrize("make", [_one_key_on_most, _all_keys_equal,
                                  _already_sorted, _reverse_sorted])
def test_skew_the_sample_cannot_save_is_still_exact(tmp_path, make):
    data = np.ascontiguousarray(make(6000)).tobytes()
    rc, got, err = _sort(tmp_path, data, "--mesh")
    assert rc == 0, err
    assert got == records_reference.sorted_records(data)
    assert "; 0 bytes lost" in err


@pytest.mark.parametrize("make", [_one_key_on_most, _all_keys_equal])
def test_a_splitter_cuts_a_key_by_input_position(tmp_path, make):
    """(key, global row index) splitters: one key on most of the records
    still gives four shards of a quarter each."""
    data = np.ascontiguousarray(make(8000)).tobytes()
    rc, _got, err = _sort(tmp_path, data, "--mesh")
    assert rc == 0
    shards = _shard_lines(err)
    assert max(shards) <= 1.1 * 8000 / DEVICES and min(shards) >= 0.9 * 8000 / DEVICES


@pytest.fixture
def tracer():
    obs.disable()
    yield obs.enable(process="mesh-sort")
    obs.disable()


def test_small_bins_retry_counted_and_exact(tmp_path, monkeypatch, tracer):
    monkeypatch.setattr(MeshRecordSort, "bin_rows", lambda self, rows: 8)
    data = _skewed(5000, seed=9, tmp=tmp_path)
    rc, got, err = _sort(tmp_path, data, "--mesh")
    assert rc == 0, err
    assert got == records_reference.sorted_records(data)
    assert obs.metrics_snapshot()["counters"]["sort.mesh.retries"] >= 1
    events = {e["args"]["id"]: e for e in tracer.to_chrome()["traceEvents"] if e["ph"] == "X"}
    retries = [e for e in events.values() if e["name"] == "sort.mesh.retry"]
    assert retries and retries[0]["args"]["from_bin_rows"] == 8
    assert retries[0]["args"]["to_bin_rows"] >= retries[0]["args"]["worst_bin"] > 8
    redone = [e for e in events.values() if e["name"] == "sort.mesh.exchange"
              and e["args"]["attempt"] >= 1]
    assert redone and all(events[e["args"]["parent"]]["name"] == "sort.mesh.retry"
                          for e in redone)


def test_no_retry_budget_exits_nonzero_and_leaves_no_out(tmp_path, monkeypatch):
    monkeypatch.setattr(MeshRecordSort, "bin_rows", lambda self, rows: 8)
    monkeypatch.setattr(MeshRecordSort, "MAX_RETRIES", 0)
    data = _skewed(5000, seed=9, tmp=tmp_path)
    (tmp_path / "out.bin").write_bytes(records_reference.sorted_records(data))  # an earlier job's
    rc, got, err = _sort(tmp_path, data, "--mesh")
    assert rc != 0
    assert "locust_tpu: error:" in err and "holds 8" in err and "nothing is written" in err
    assert got is None
    sorter = MeshRecordSort(make_mesh(DEVICES), RB, KB)
    with pytest.raises(BinOverflow):
        sorter.sort(sorter.load(RecordSource.from_bytes(data, RB)))


@pytest.mark.parametrize("flags", [("--mesh",), ()])
@pytest.mark.parametrize("left", ["longer", "equal", "shorter"])
def test_an_out_that_is_there_is_written_over_in_place_and_cut_to_size(tmp_path, left, flags):
    """``serde.write_records`` opens OUT without truncating it: the file a
    job before left is the same file after (its pages are written over,
    not freed and allocated anew), holds the reference's bytes and ends
    at the last of them, whatever its size was."""
    data = _skewed(3000, seed=4, tmp=tmp_path)
    size = {"longer": len(data) + 12345, "equal": len(data), "shorter": 777}[left]
    out = tmp_path / "out.bin"
    out.write_bytes(b"\xa5" * size)
    inode = out.stat().st_ino
    rc, got, err = _sort(tmp_path, data, *flags)
    assert rc == 0 and "; 0 bytes lost" in err
    assert got == records_reference.sorted_records(data)
    assert out.stat().st_ino == inode


@pytest.mark.skipif(not hasattr(os, "memfd_create"), reason="no memfd_create here")
@pytest.mark.parametrize("budget", ["enough", "none"])
def test_out_may_be_an_open_memory_file_named_through_proc(tmp_path, monkeypatch, budget):
    """OUT as ``tera-skew.mesh4``'s driver hands it over: an anonymous
    memory file named ``/proc/self/fd/N``.  It is written over in place
    and cut to size like any OUT; a job that could not place every record
    cannot unlink it and leaves it EMPTY instead."""
    data = _skewed(5000, seed=9, tmp=tmp_path)
    (tmp_path / "in.bin").write_bytes(data)
    fd = os.memfd_create("out")
    try:
        os.write(fd, b"\xa5" * (len(data) + 999))  # an earlier job's, longer
        if budget == "none":
            monkeypatch.setattr(MeshRecordSort, "bin_rows", lambda self, rows: 8)
            monkeypatch.setattr(MeshRecordSort, "MAX_RETRIES", 0)
        err = io.StringIO()
        with contextlib.redirect_stderr(err):
            rc = cli_main(["sort", str(tmp_path / "in.bin"), f"/proc/self/fd/{fd}",
                           "--mesh", "--backend", "cpu"])
        got = os.pread(fd, len(data) + 999, 0)
        if budget == "none":
            assert rc != 0 and "nothing is written" in err.getvalue() and got == b""
        else:
            assert rc == 0 and "; 0 bytes lost" in err.getvalue()
            assert got == records_reference.sorted_records(data)
    finally:
        os.close(fd)


def test_a_sink_that_fails_leaves_out_at_the_last_byte_written(tmp_path):
    from locust_tpu.io import serde

    out = tmp_path / "out.bin"
    out.write_bytes(b"\xa5" * 1000)

    def blocks():
        yield np.arange(100, dtype=np.uint8)
        raise RuntimeError("the second block never came")

    with pytest.raises(RuntimeError):
        serde.write_records(str(out), blocks())
    assert out.read_bytes() == bytes(range(100))  # no tail of the job before
    assert serde.write_records(os.devnull, [np.zeros(10, np.uint8)]) == 10


@pytest.mark.parametrize("record_bytes,key_bytes", [(50, 7), (13, 13), (7, 1)])
def test_widths_that_are_no_multiple_of_four(tmp_path, record_bytes, key_bytes):
    rng = np.random.default_rng(record_bytes)
    rows = rng.integers(0, 4, (3001, record_bytes), dtype=np.uint8)  # few values: ties
    data = rows.tobytes()
    rc, got, err = _sort(tmp_path, data, "--mesh", "--record-bytes", str(record_bytes),
                         "--key-bytes", str(key_bytes))
    assert rc == 0, err
    assert got == records_reference.sorted_records(data, record_bytes, key_bytes)


def test_eight_devices_too(tmp_path, monkeypatch):
    monkeypatch.undo()  # every visible device, as the CLI takes them
    data = _skewed(9000, seed=8, tmp=tmp_path)
    rc, got, err = _sort(tmp_path, data, "--mesh")
    assert rc == 0, err
    assert got == records_reference.sorted_records(data)
    assert len(_shard_lines(err)) == 8


# ------------------------------------------------------------------ the plan

def test_plan_lowers_onto_the_mesh_sorter_and_explain_names_it(tmp_path):
    from locust_tpu.config import EngineConfig
    from locust_tpu.engine import RecordSort
    from locust_tpu.plan import records_sort_plan
    from locust_tpu.plan.compile import compile_plan

    data = _skewed(3000, seed=2, tmp=tmp_path)
    want = records_reference.sorted_records(data)
    on_mesh = compile_plan(records_sort_plan(), EngineConfig(), mesh=True)
    assert isinstance(on_mesh._record_sorter(), MeshRecordSort)
    assert "MeshRecordSort over" in on_mesh.explain()
    assert on_mesh.run(RecordSource.from_bytes(data, RB)).output == want
    assert on_mesh.run_corpus(data).output == want
    solo = compile_plan(records_sort_plan(), EngineConfig())
    assert isinstance(solo._record_sorter(), RecordSort)
    assert "engine.RecordSort on one device" in solo.explain()
    assert "MeshRecordSort" not in solo.explain()
    assert solo.run_corpus(data).output == want


def test_the_distributor_still_refuses_sort():
    from locust_tpu.plan import records_sort_plan
    from locust_tpu.plan.distribute import SOLO_ONLY, plan_shape

    assert "sort" in SOLO_ONLY
    assert plan_shape(records_sort_plan()) == (None, "solo_only_kind")


# ------------------------------------------------------- programs and spans

def test_a_second_mesh_job_builds_no_program(tmp_path, tracer):
    data = _skewed(2000, seed=1, tmp=tmp_path)
    assert _sort(tmp_path, data, "--mesh")[0] == 0
    first = obs.metrics_snapshot()["counters"]
    assert (first["engine.programs_built"], first.get("engine.programs_shared", 0)) == (1, 0)
    seen = len(tracer.to_chrome()["traceEvents"])
    rc, got, _ = _sort(tmp_path, data, "--mesh")
    assert rc == 0 and got == records_reference.sorted_records(data)
    second = obs.metrics_snapshot()["counters"]
    assert second["engine.programs_built"] - first["engine.programs_built"] == 0
    assert second["engine.programs_shared"] - first.get("engine.programs_shared", 0) == 1
    assert not [e for e in tracer.to_chrome()["traceEvents"][seen:]
                if e["name"].startswith("engine.program.")]


MESH_SPANS = ("sort.mesh.split", "sort.mesh.exchange", "sort.mesh.shard_sort")
MESH_METRICS = ("sort.mesh.retries", "sort.mesh.bin_rows", "sort.mesh.shard_rows_max",
                "sort.mesh.shard_rows_min", "sort.mesh.bytes_exchanged")


def test_names_are_registered():
    for name in (*MESH_SPANS, "sort.mesh.retry"):
        assert names.NAMES[name] == "span"
    for name in MESH_METRICS:
        assert names.NAMES[name] in ("counter", "gauge")


def test_a_traced_mesh_job_records_its_spans_and_counters(tmp_path):
    data = _skewed(20000, seed=4, tmp=tmp_path)
    trace = tmp_path / "trace.json"
    rc, got, err = _sort(tmp_path, data, "--mesh", "--trace-out", str(trace))
    assert rc == 0 and got == records_reference.sorted_records(data)
    doc = json.loads(trace.read_text())
    events = {e["args"]["id"]: e for e in doc["traceEvents"] if e["ph"] == "X"}
    by_name: dict = {}
    for e in events.values():
        by_name.setdefault(e["name"], []).append(e)

    def parent(e):
        return events[e["args"]["parent"]]["name"]

    for name in MESH_SPANS:  # children of cli.run, through the plan's run
        (span,) = by_name[name]
        assert parent(events[span["args"]["parent"]]) == "cli.run" or parent(span) == "cli.run"
    assert by_name["sort.mesh.split"][0]["args"]["samples"] == DEVICES * 4096
    assert by_name["sort.mesh.split"][0]["args"]["splitters"] == DEVICES - 1
    exchange = by_name["sort.mesh.exchange"][0]["args"]
    assert exchange["attempt"] == 0 and 0 < exchange["worst_bin"] <= exchange["bin_rows"]
    assert "sort.mesh.retry" not in by_name
    shards = _shard_lines(err)
    assert by_name["sort.mesh.shard_sort"][0]["args"]["rows"] == max(shards)
    assert {e["args"]["device"] for e in by_name["sort.h2d"]} == set(range(DEVICES))
    assert all(parent(e) == "cli.load" for e in by_name["sort.h2d"])
    assert sum(e["args"]["bytes"] for e in by_name["sort.read"]) == len(data)
    assert sum(e["args"]["bytes"] for e in by_name["sort.d2h"]) == len(data)
    assert sum(e["args"]["bytes"] for e in by_name["sort.write"]) == len(data)
    assert {e["args"]["what"] for e in by_name["engine.sync"]} >= {
        "h2d", "split", "exchange", "keys", "d2h"}
    metrics = doc["otherData"]["metrics"]
    assert metrics["counters"]["sort.mesh.retries"] == 0
    assert metrics["counters"]["sort.records"] == 20000
    assert metrics["gauges"]["sort.mesh.shard_rows_max"] == max(shards)
    assert metrics["gauges"]["sort.mesh.shard_rows_min"] == min(shards)
    assert metrics["gauges"]["sort.mesh.bin_rows"] == exchange["bin_rows"]
    assert 0 < metrics["counters"]["sort.mesh.bytes_exchanged"] <= len(data)
