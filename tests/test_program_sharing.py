"""An engine's programs belong to its configuration: the process builds
them once a key and every later engine of the key takes the same jit
objects (engine._programs_for), so its first job traces, lowers and reads
back nothing.  ``tests/conftest.py`` empties the memo before every test.
"""

import gc
import sys
import threading
import weakref

import jax.numpy as jnp
import numpy as np
import pytest

from locust_tpu import engine, obs
from locust_tpu.config import EngineConfig
from locust_tpu.engine import MapReduceEngine
from locust_tpu.ops.map_stage import wordcount_map

_SMALL = dict(block_lines=8, line_width=32, key_width=8, emits_per_line=4)
LINES = [b"alpha beta alpha", b"beta gamma"] * 8
COUNTS = [(b"alpha", 16), (b"beta", 16), (b"gamma", 8)]
PROGRAMS = ("_map", "_process", "_reduce", "_merge", "_fold_block",
            "_fold_block_fallback", "_fold_segment", "_scan_blocks_into",
            "_scan_blocks", "_scan_blocks_batch")


@pytest.fixture
def tracer():
    obs.disable()
    yield obs.enable(process="sharing")
    obs.disable()


def _program_spans(tracer, since=0):
    return [e for e in tracer.to_chrome()["traceEvents"][since:]
            if e["name"].startswith("engine.program.")]


def _counters():
    got = obs.metrics_snapshot()["counters"]
    return got["engine.programs_built"], got["engine.programs_shared"]


def _table_bytes(result):
    t = result.table
    return tuple(np.asarray(a).tobytes() for a in (t.key_lanes, t.values, t.valid))


@pytest.mark.parametrize("job", ["timed_run", "run"])
def test_second_engine_of_an_equal_config_builds_and_traces_nothing(tracer, job):
    first = MapReduceEngine(EngineConfig(**_SMALL))
    rows = first.rows_from_lines(LINES)
    want = getattr(first, job)(rows)
    assert _program_spans(tracer), "a configuration's first job holds its programs' reload"
    assert _counters() == (1, 0)
    mark = len(tracer.to_chrome()["traceEvents"])
    second = MapReduceEngine(EngineConfig(**_SMALL))  # equal, not the same object
    assert second is not first and _counters() == (1, 1)
    assert all(getattr(second, p) is getattr(first, p) for p in PROGRAMS)
    got = getattr(second, job)(rows)
    assert not _program_spans(tracer, since=mark)
    assert _table_bytes(got) == _table_bytes(want)
    assert got.to_host_pairs() == COUNTS


def _upper_map(lines, cfg):
    """WordCount over the text with its lower-case letters raised."""
    lower = (lines >= ord("a")) & (lines <= ord("z"))
    return wordcount_map(jnp.where(lower, lines - 32, lines), cfg)


@pytest.mark.parametrize("other, want", [
    (dict(cfg=EngineConfig(**dict(_SMALL, emits_per_line=2))),
     [(b"alpha", 8), (b"beta", 16), (b"gamma", 8)]),  # a line's third word dropped
    (dict(cfg=EngineConfig(**_SMALL), combine="max"),
     [(b"alpha", 1), (b"beta", 1), (b"gamma", 1)]),
    (dict(cfg=EngineConfig(**_SMALL), map_fn=_upper_map),
     [(b"ALPHA", 16), (b"BETA", 16), (b"GAMMA", 8)]),
], ids=["config_field", "combine", "map_fn"])
def test_the_key_discriminates(tracer, other, want):
    base = MapReduceEngine(EngineConfig(**_SMALL))
    eng = MapReduceEngine(**other)
    assert _counters() == (2, 0)
    assert not any(getattr(eng, p) is getattr(base, p) for p in PROGRAMS
                   if getattr(eng, p) is not None)
    rows = base.rows_from_lines(LINES)
    assert eng.timed_run(rows).to_host_pairs() == want
    assert eng.run(rows).to_host_pairs() == want
    assert base.timed_run(rows).to_host_pairs() == COUNTS


def test_the_bound_evicts_the_least_recently_used_which_builds_again(tracer):
    bound = MapReduceEngine.PROGRAM_KEYS
    cfgs = [EngineConfig(**dict(_SMALL, emits_per_line=2 + i))
            for i in range(bound + 1)]
    oldest = MapReduceEngine(cfgs[0])
    for cfg in cfgs[1:bound]:
        MapReduceEngine(cfg)
    assert MapReduceEngine(cfgs[0])._map is oldest._map  # used: now the newest
    assert _counters() == (bound, 1)
    MapReduceEngine(cfgs[bound])  # one key too many: cfgs[1] goes
    assert _counters() == (bound + 1, 1)
    assert MapReduceEngine(cfgs[0])._map is oldest._map
    assert MapReduceEngine(cfgs[2])._map is not None
    assert _counters() == (bound + 1, 3)
    MapReduceEngine(cfgs[1])
    assert _counters() == (bound + 2, 3)
    # The evicted record's engines keep working.
    assert oldest.timed_run(oldest.rows_from_lines(LINES)).to_host_pairs()[0][0] == b"alpha"


def test_a_dead_engine_is_not_a_reference_cycle():
    gc.collect()
    gc.disable()
    try:
        eng = MapReduceEngine(EngineConfig(**_SMALL))
        eng.timed_run(eng.rows_from_lines(LINES))
        ref = weakref.ref(eng)
        del eng
        assert ref() is None
    finally:
        gc.enable()


def test_clear_programs_makes_the_next_engine_build(tracer):
    first = MapReduceEngine(EngineConfig(**_SMALL))
    engine.clear_programs()
    second = MapReduceEngine(EngineConfig(**_SMALL))
    assert _counters() == (2, 0)
    assert second._map is not first._map
    rows = first.rows_from_lines(LINES)
    assert first.timed_run(rows).to_host_pairs() == COUNTS  # keeps its own
    assert second.timed_run(rows).to_host_pairs() == COUNTS


def test_threads_constructing_one_key_get_one_record(tracer, monkeypatch):
    built = []
    real = engine._build_programs

    def counting(*args):
        built.append(threading.get_ident())
        return real(*args)

    monkeypatch.setattr(engine, "_build_programs", counting)
    cfg = EngineConfig(**_SMALL)
    start = threading.Barrier(16)
    engines, errors = [], []

    def construct():
        try:
            start.wait(timeout=30)
            engines.append(MapReduceEngine(cfg))
        except Exception as e:  # noqa: BLE001 - read below
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=construct) for _ in range(16)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
            assert not th.is_alive()
    finally:
        sys.setswitchinterval(interval)
    assert not errors and len(engines) == 16
    assert len(built) == 1
    assert len({id(getattr(e, p)) for e in engines for p in ("_map", "_merge")}) == 2
