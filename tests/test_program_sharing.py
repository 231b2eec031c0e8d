"""An engine's programs belong to its configuration: the process builds
them once a key and every later engine of the key takes the same jit
objects (engine._programs_for), so its first job traces, lowers and reads
back nothing.  ``tests/conftest.py`` empties the memo before every test.
"""

import gc
import sys
import threading
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from helpers import py_wordcount
from test_table_growth import zipf_lines

from locust_tpu import engine, obs
from locust_tpu.config import EngineConfig, default_sort_mode
from locust_tpu.core import bytes_ops
from locust_tpu.engine import MapReduceEngine
from locust_tpu.ops.map_stage import wordcount_map
from locust_tpu.parallel import (
    DistributedMapReduce,
    HierarchicalMapReduce,
    make_mesh,
    make_mesh_2d,
    shuffle,
)

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


# ---------------------------------------------------------------- the mesh
#
# DistributedMapReduce's step, grow and stats programs and the hierarchical
# engine's five are records of the same memo: one key a configuration, the
# shard capacity no part of it (shuffle._MeshPrograms).

needs_mesh = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 (virtual) devices")

# Shards start at 256 rows on 4 devices; this text's 1,139 words pass them
# once: one growth step, two step programs and one grow program a job.
_MESH = dict(block_lines=16, line_width=64, key_width=8, emits_per_line=8,
             table_size=256, sort_mode=default_sort_mode("cpu"))
MESH_LINES = zipf_lines(4_000, 1 << 13, seed=4)


def _mesh_engine(n_dev=4, **kw):
    """A mesh engine as the CLI makes one a job: the mesh made anew (jax
    hands back an equal one), the config equal and not the same object."""
    return DistributedMapReduce(make_mesh(n_dev), EngineConfig(**_MESH), **kw)


def _mesh_rows():
    return bytes_ops.strings_to_rows(MESH_LINES, _MESH["line_width"])


def _mesh_job(dmr, job):
    rows = _mesh_rows()
    if job == "run":
        return dmr.run(rows, stats_sync_every=4)
    lpr = dmr.lines_per_round
    return dmr.run_stream(
        (rows[at:at + lpr] for at in range(0, len(rows), lpr)),
        stats_sync_every=4,
    )


def _mesh_programs(dmr, cap):
    return (dmr._step, dmr._step_at(cap), dmr._programs.grower(cap),
            dmr._stats_merge)


@needs_mesh
@pytest.mark.parametrize("job", ["run", "run_stream"])
def test_second_mesh_engine_of_an_equal_config_builds_and_traces_nothing(tracer, job):
    want = py_wordcount(MESH_LINES, 8)
    first = _mesh_engine()
    res = _mesh_job(first, job)
    assert res.table_grows == 1 and dict(res.to_host_pairs()) == want
    assert _program_spans(tracer), "a configuration's first job holds its programs' reload"
    assert _counters() == (1, 0)
    mark = len(tracer.to_chrome()["traceEvents"])
    second = _mesh_engine()
    assert second is not first and second.cfg is not first.cfg
    assert _counters() == (1, 1)
    got = _mesh_job(second, job)
    assert got.table_grows == 1 and got.shard_capacity == res.shard_capacity
    # Every job starts at its default capacity: nothing is carried over
    # but the programs.
    assert second.shard_capacity == first.shard_capacity < got.shard_capacity
    assert not _program_spans(tracer, since=mark)
    assert all(a is b for a, b in zip(
        _mesh_programs(second, got.shard_capacity),
        _mesh_programs(first, got.shard_capacity)))
    assert _table_bytes(got) == _table_bytes(res)
    assert dict(got.to_host_pairs()) == want


@needs_mesh
@pytest.mark.parametrize("other", [
    dict(bin_capacity=16),
    dict(n_dev=8),
    dict(combine="max"),
    dict(map_fn=_upper_map),
    dict(on_overflow="drop"),
], ids=["bin_capacity", "mesh_size", "combine", "map_fn", "on_overflow"])
def test_the_mesh_key_discriminates(tracer, other):
    base = _mesh_engine()
    eng = _mesh_engine(**other)
    assert _counters() == (2, 0)
    assert eng._programs is not base._programs and eng._step is not base._step
    lines = MESH_LINES[:400]
    rows = bytes_ops.strings_to_rows(lines, _MESH["line_width"])
    want = py_wordcount(lines, 8)
    if "map_fn" in other:
        want = {k.upper(): v for k, v in want.items()}
    if "combine" in other:
        want = dict.fromkeys(want, 1)
    assert dict(eng.run(rows).to_host_pairs()) == want
    assert dict(base.run(rows).to_host_pairs()) == py_wordcount(lines, 8)


@needs_mesh
def test_count_shares_though_its_map_wrapper_is_made_anew(tracer):
    """The key holds the RAW (map_fn, combine): normalize_combine wraps
    the map function of "count" afresh at every call."""
    first = _mesh_engine(combine="count")
    second = _mesh_engine(combine="count")
    assert _counters() == (1, 1) and second._step is first._step
    rows = _mesh_rows()[:400]
    assert second.run(rows).to_host_pairs() == first.run(rows).to_host_pairs()


@needs_mesh
def test_a_growing_mesh_job_spends_one_key_and_mesh_records_are_cleared(tracer):
    first = _mesh_engine()
    assert _mesh_job(first, "run").table_grows == 1
    assert len(engine._PROGRAMS) == 1  # two capacities, one record
    assert MapReduceEngine(EngineConfig(**_MESH))._map is not None
    assert len(engine._PROGRAMS) == 2  # the engine class keeps the keys apart
    engine.clear_programs()
    second = _mesh_engine()
    assert _counters() == (3, 0)
    assert second._programs is not first._programs and second._step is not first._step
    want = py_wordcount(MESH_LINES, 8)
    assert dict(_mesh_job(first, "run").to_host_pairs()) == want  # keeps its own
    assert dict(_mesh_job(second, "run").to_host_pairs()) == want


@needs_mesh
def test_a_dead_mesh_engine_is_collected_while_its_record_lives():
    gc.collect()
    gc.disable()
    try:
        eng = _mesh_engine()
        assert _mesh_job(eng, "run").table_grows == 1
        ref, record = weakref.ref(eng), eng._programs
        del eng
        assert ref() is None
        assert _mesh_engine()._programs is record
    finally:
        gc.enable()


@needs_mesh
def test_a_step_put_on_one_mesh_engine_is_not_seen_by_the_next(tracer):
    poisoned = _mesh_engine()
    real = poisoned._step

    def dying_step(*args):
        raise RuntimeError("injected")

    poisoned._step = dying_step
    assert poisoned._step is dying_step
    rows = _mesh_rows()[:400]
    with pytest.raises(RuntimeError, match="injected"):
        poisoned.run(rows)
    healthy = _mesh_engine()
    assert _counters() == (1, 1)
    assert healthy._step is real and healthy._programs.step(healthy.shard_capacity) is real
    assert dict(healthy.run(rows).to_host_pairs()) == py_wordcount(MESH_LINES[:400], 8)
    poisoned._step = real  # a test's way back
    assert dict(poisoned.run(rows).to_host_pairs()) == py_wordcount(MESH_LINES[:400], 8)


@needs_mesh
def test_threads_constructing_one_mesh_key_get_one_record(tracer, monkeypatch):
    built = []
    real = shuffle._build_mesh_step

    def counting(*args):
        built.append(args[-1])
        return real(*args)

    monkeypatch.setattr(shuffle, "_build_mesh_step", counting)
    start = threading.Barrier(16)
    got, errors = [], []

    def construct():
        try:
            start.wait(timeout=30)
            eng = _mesh_engine()
            got.append((eng._programs, eng._step, eng._step_at(512),
                        eng._programs.grower(512)))
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
    assert not errors and len(got) == 16
    assert _counters() == (1, 15)
    assert sorted(built) == [256, 512]  # one step program a capacity
    assert all(len({id(g[i]) for g in got}) == 1 for i in range(4))


@needs_mesh
def test_second_hierarchical_engine_of_an_equal_config_shares_its_programs(tracer):
    def make(**kw):
        return HierarchicalMapReduce(make_mesh_2d(2, 4), EngineConfig(**_MESH), **kw)

    obs.watch_programs()  # this engine records no span of its own
    lines = MESH_LINES[:200]  # its shards hold what they start with
    rows = bytes_ops.strings_to_rows(lines, _MESH["line_width"])
    first = make()
    want = first.run(rows)
    assert not want.truncated
    assert _program_spans(tracer) and _counters() == (1, 0)
    mark = len(tracer.to_chrome()["traceEvents"])
    second = make()
    assert _counters() == (1, 1)
    names = ("_step", "_combine", "_combine_dbg", "_stats_merge", "_replicate_stats")
    assert all(getattr(second, n) is getattr(first, n) for n in names)
    got = second.run(rows)
    assert not _program_spans(tracer, since=mark)
    assert _table_bytes(got) == _table_bytes(want)
    assert dict(got.to_host_pairs()) == py_wordcount(lines, 8)
    second._step = None  # an engine's own attribute: the next one is whole
    assert make()._step is first._step and make(bin_capacity=16)._step is not first._step
    assert _counters() == (2, 2)
