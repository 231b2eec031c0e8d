"""The record sort — ``python -m locust_tpu sort IN OUT`` — held to the
plain reference (``locust_tpu/records_reference.py``: the rows
stable-sorted by their key bytes on the host, no jax, nothing of the
package): OUT byte-equal at every size and key pattern, the loud refusals,
the programs built once a process, the spans and counters of a traced job.
"""

import json
import os

import numpy as np
import pytest

from locust_tpu import engine, obs, records_reference
from locust_tpu.cli import main as cli_main
from locust_tpu.core.kv import RecordBatch
from locust_tpu.io.loader import RecordSource

RB, KB = 100, 10


def _records(n: int, seed: int = 0, record_bytes: int = RB) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.integers(0, 256, (n, record_bytes), dtype=np.uint8)


def _number(rows: np.ndarray) -> np.ndarray:
    """Each record's number in its payload, so that equal keys differ."""
    rows[:, -4:] = np.arange(rows.shape[0], dtype=">u4").view(np.uint8).reshape(-1, 4)
    return rows


def _sort(tmp_path, data: bytes, *flags, name: str = "in.bin"):
    """(exit status, OUT's bytes or None, stderr) of one ``sort`` job."""
    src, out = tmp_path / name, tmp_path / (name + ".sorted")
    src.write_bytes(data)
    return _run(str(src), str(out), *flags)


def _run(src: str, out: str, *flags):
    import contextlib
    import io

    err = io.StringIO()
    with contextlib.redirect_stderr(err):
        rc = cli_main(["sort", src, out, "--backend", "cpu", *flags])
    got = open(out, "rb").read() if os.path.exists(out) else None
    return rc, got, err.getvalue()


@pytest.mark.parametrize("n", [1, 2, 4095, 4096, 4097, 20000])
def test_out_is_byte_equal_to_the_reference(tmp_path, n):
    data = _records(n, seed=n).tobytes()
    rc, got, err = _sort(tmp_path, data)
    assert rc == 0, err
    assert got == records_reference.sorted_records(data)
    assert f"{n} records, {n * RB} bytes written" in err and "; 0 bytes lost" in err
    assert "[locust] backend: cpu " in err


def _all_keys_equal(n):
    rows = _number(_records(n, 1))
    rows[:, :KB] = 0x42
    return rows


def _already_sorted(n):
    rows = _records(n, 2)
    return rows[np.lexsort(rows[:, KB - 1::-1].T)]


def _differ_in_the_tenth_byte_only(n):
    rows = _number(_records(n, 3))
    rows[:, :KB - 1] = 0x7F
    return rows


def _zero_and_ff_in_every_position(n):
    # Every key byte 0x00 or 0xFF: unsigned order (0xFF last, not first)
    # in every lane, the last lane's two padding bytes included.
    rows = _number(_records(n, 4))
    rows[:, :KB] = np.where(rows[:, :KB] & 1, 0xFF, 0x00)
    return rows


def _duplicates_by_the_hundred(n):
    rows = _number(_records(n, 5))
    rows[:, :KB] = rows[: n // 100, :KB].repeat(100, axis=0)[
        np.random.default_rng(6).permutation(n // 100 * 100)[:n]
    ]
    return rows


@pytest.mark.parametrize("make", [
    _all_keys_equal, _already_sorted, lambda n: _already_sorted(n)[::-1],
    _differ_in_the_tenth_byte_only, _zero_and_ff_in_every_position,
    _duplicates_by_the_hundred,
], ids=["all_keys_equal", "already_sorted", "reversed", "tenth_byte_only",
        "00_and_FF_everywhere", "duplicates_by_the_hundred"])
def test_key_patterns_keep_unsigned_order_and_input_order_of_ties(tmp_path, make):
    data = np.ascontiguousarray(make(3000)).tobytes()
    rc, got, err = _sort(tmp_path, data)
    assert rc == 0, err
    want = records_reference.sorted_records(data)
    assert got == want


def test_several_blocks_and_a_short_last_one(tmp_path, monkeypatch):
    """A job of many staged blocks (the cell's has 16) at a size a test can
    run: blocks of 1,024 records, the last one 544 of them real."""
    monkeypatch.setattr(engine.MapReduceEngine, "RECORD_BLOCK_BYTES", 1024 * RB)
    data = _records(20000, 7).tobytes()
    rc, got, err = _sort(tmp_path, data)
    assert rc == 0, err
    assert got == records_reference.sorted_records(data)


@pytest.mark.parametrize("record_bytes, key_bytes", [(100, 2), (100, 100), (10, 3), (7, 7), (13, 1)])
def test_other_record_and_key_widths(tmp_path, record_bytes, key_bytes):
    rows = _records(2500, 8, record_bytes)
    rows[:, :key_bytes] &= 0x83  # few values a byte: ties, 0x00 and bytes over 0x7F
    data = rows.tobytes()
    rc, got, err = _sort(tmp_path, data, "--record-bytes", str(record_bytes),
                         "--key-bytes", str(key_bytes))
    assert rc == 0, err
    assert got == records_reference.sorted_records(data, record_bytes, key_bytes)


def test_a_two_byte_key_is_another_answer_than_the_ten_byte_reference(tmp_path):
    """The benchmark's control: ``--key-bytes 2`` ties records by the
    hundred and leaves them in input order."""
    data = _records(20000, 9).tobytes()
    rc, got, _ = _sort(tmp_path, data, "--key-bytes", "2")
    assert rc == 0
    assert got == records_reference.sorted_records(data, RB, 2)
    assert got != records_reference.sorted_records(data)


@pytest.mark.parametrize("data, flags, says", [
    (b"", (), "no records"),
    (b"x" * 250, (), "no whole number of 100-byte records"),
    (b"x" * 99, (), "no whole number of 100-byte records"),
    (b"x" * 200, ("--key-bytes", "101"), "--key-bytes 101 must lie in 1..--record-bytes"),
    (b"x" * 200, ("--key-bytes", "0"), "--key-bytes 0 must lie in"),
], ids=["empty", "250_bytes", "99_bytes", "key_wider_than_record", "no_key"])
def test_a_bad_input_is_a_loud_error_and_writes_no_out(tmp_path, data, flags, says):
    rc, got, err = _sort(tmp_path, data, *flags)
    assert rc == 2 and got is None
    assert "locust_tpu: error:" in err and says in err


def test_a_missing_input_is_an_error(tmp_path):
    rc, got, err = _run(str(tmp_path / "nowhere.bin"), str(tmp_path / "out.bin"))
    assert rc == 2 and got is None and "locust_tpu: error:" in err


def test_the_reference_refuses_what_the_command_refuses():
    for bad in (b"", b"x" * 250):
        with pytest.raises(ValueError):
            records_reference.sorted_records(bad)
    with pytest.raises(ValueError):
        RecordSource.from_bytes(b"x" * 250, RB)


def _counters():
    got = obs.metrics_snapshot()["counters"]
    return got.get("engine.programs_built", 0), got.get("engine.programs_shared", 0)


def test_a_second_job_of_the_process_builds_no_program(tmp_path):
    """``engine._programs_for``: the sort's programs and the engine's are a
    configuration's, built by the process's first job of it."""
    data = _records(3000, 10).tobytes()
    (tmp_path / "in.bin").write_bytes(data)
    tracer = obs.enable(process="sort")
    try:
        assert _run(str(tmp_path / "in.bin"), str(tmp_path / "a.bin"))[0] == 0
        first = _counters()
        assert first[0] >= 1
        mark = len(tracer.to_chrome()["traceEvents"])
        rc, got, _ = _run(str(tmp_path / "in.bin"), str(tmp_path / "b.bin"))
        assert rc == 0 and got == records_reference.sorted_records(data)
        built, shared = _counters()
        assert built == first[0] and shared > first[1]
        later = [e["name"] for e in tracer.to_chrome()["traceEvents"][mark:]]
        assert not [n for n in later if n.startswith("engine.program.")], later
    finally:
        obs.disable()


def test_trace_out_holds_every_span_with_its_args_and_both_counters(tmp_path, monkeypatch):
    monkeypatch.setattr(engine.MapReduceEngine, "RECORD_BLOCK_BYTES", 1024 * RB)
    n = 3000  # three blocks of 1,024
    trace = tmp_path / "t.json"
    rc, got, err = _sort(tmp_path, _records(n, 11).tobytes(), "--trace-out", str(trace))
    assert rc == 0, err
    doc = json.loads(trace.read_text())
    spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    by_name = {}
    for e in spans:
        by_name.setdefault(e["name"], []).append(e["args"])
    for name in ("cli.load", "cli.run", "cli.output", "plan.run", "sort.keys"):
        assert len(by_name[name]) == 1, name
    for name in ("sort.read", "sort.h2d", "sort.permute", "sort.d2h", "sort.write"):
        assert len(by_name[name]) == 3, (name, len(by_name.get(name, ())))
    for name in ("sort.read", "sort.h2d", "sort.d2h", "sort.write"):
        assert all(a["bytes"] > 0 for a in by_name[name]), name
    for name in ("sort.read", "sort.d2h", "sort.write"):
        assert sum(a["bytes"] for a in by_name[name]) == n * RB, name
    assert by_name["sort.keys"][0]["rows"] == 3 * 1024
    assert all(a["rows"] == 1024 for a in by_name["sort.permute"])
    assert sorted(a["what"] for a in by_name["engine.sync"]) == ["d2h"] * 3 + ["h2d", "keys"]
    counters = doc["otherData"]["metrics"]["counters"]
    assert counters["sort.records"] == n and counters["sort.bytes_out"] == n * RB


def test_key_lanes_are_the_key_bytes_big_endian_zero_padded():
    rows = np.zeros((2, 12), np.uint8)
    rows[0, :10] = [1, 2, 3, 4, 5, 6, 7, 8, 9, 10]
    rows[0, 10:] = 0xEE  # payload bytes of the third word: masked off
    rows[1, :10] = 0xFF
    lanes = RecordBatch(np.ascontiguousarray(rows).view(np.uint32)).key_lanes(10)
    assert [int(x) for x in np.asarray(lanes)[:, 0]] == [0x01020304, 0x05060708, 0x090A0000]
    assert [int(x) for x in np.asarray(lanes)[:, 1]] == [0xFFFFFFFF, 0xFFFFFFFF, 0xFFFF0000]
