"""The mesh's hash shards grow with what they see, together, and stay exact.

``DistributedMapReduce`` with the default ``shard_capacity`` starts where
it always started; when the worst shard counted more distinct keys than a
shard holds, every shard grows to the capacity that holds them
(``core/kv.rows_to_hold``, the default path's rule) and the rounds folded
since the last table known to be whole are folded again from it
(``_run_rounds``); from the second stats sync on the shards grow AHEAD of
a stretch that, adding what the last one added, would pass them.
Tolerance: none — every table here is equal to the ``py_wordcount``
oracle.  An explicit ``shard_capacity=`` and the hierarchical mesh keep a
fixed capacity and their loud report (tests/test_scale.py too); the
default path's growth is tests/test_table_growth.py.
"""

import logging
import re

import jax
import numpy as np
import pytest

from helpers import py_wordcount
from test_table_growth import distinct_lines, zipf_lines

from locust_tpu import cli, obs
from locust_tpu.config import EngineConfig, default_sort_mode
from locust_tpu.core import bytes_ops
from locust_tpu.core.kv import KVBatch
from locust_tpu.parallel import DistributedMapReduce, make_mesh

# 16 lines x 8 emits a block; shards start at max(n_dev x bin, a fair
# share of table_size x 2) = 256 rows on 4 devices, 128 x 8... on 8.
_SMALL = dict(block_lines=16, line_width=64, key_width=8, emits_per_line=8,
              table_size=256)
MODES = [default_sort_mode("tpu"), default_sort_mode("cpu")]

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 (virtual) devices")


@pytest.fixture(autouse=True)
def _tracing_off():
    obs.disable()
    yield
    obs.disable()


def _engine(n_dev, mode, **kw):
    return DistributedMapReduce(make_mesh(n_dev), EngineConfig(sort_mode=mode, **_SMALL), **kw)


def _rows(dmr, lines):
    return bytes_ops.strings_to_rows(lines, dmr.cfg.line_width)


def _grow_spans(tracer):
    return [e["args"] for e in tracer.to_chrome()["traceEvents"]
            if e.get("ph") == "X" and e["name"] == "mesh.table.grow"]


def _shard_tables(res):
    """The result's table a shard at a time: [{key: count}, ...]."""
    host = jax.device_get(res.table)
    cap = res.shard_capacity
    return [dict(KVBatch(host.key_lanes[lo:lo + cap], host.values[lo:lo + cap],
                         host.valid[lo:lo + cap]).to_host_pairs())
            for lo in range(0, res.table.size, cap)]


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("n_dev", [4, 8])
def test_shards_grow_together_and_the_table_is_exact(n_dev, mode):
    """A Zipf text whose vocabulary passes the starting shards several
    times over: doubling steps from the start, one of them found out
    BETWEEN two syncs (its rounds folded again), the oracle's table."""
    lines = zipf_lines(24_000, 1 << 16, seed=n_dev)
    want = py_wordcount(lines, 8)
    dmr = _engine(n_dev, mode)
    start = dmr.shard_capacity
    assert dmr.grows and len(want) > 2 * n_dev * start
    tracer = obs.enable(process="meshgrow")
    res = dmr.run(_rows(dmr, lines), stats_sync_every=4)
    assert not res.truncated and res.distinct == len(want)
    assert dict(res.to_host_pairs()) == want
    steps = _grow_spans(tracer)
    metrics = obs.metrics_snapshot()
    assert len(steps) == metrics["counters"]["mesh.table_grows"] == res.table_grows >= 2
    assert metrics["gauges"]["mesh.shard_rows"] == res.shard_capacity
    assert res.table.size == n_dev * res.shard_capacity
    assert metrics["counters"]["mesh.rounds"] == -(-len(lines) // dmr.lines_per_round)
    # Geometric from the start, each step from where the last ended.
    assert [s["from_rows"] for s in steps] == [start] + [s["to_rows"] for s in steps[:-1]]
    assert all(s["to_rows"] in [s["from_rows"] << k for k in range(1, 12)] for s in steps)
    assert steps[-1]["to_rows"] == res.shard_capacity
    # A sync that found keys dropped redoes its rounds; one that sees the
    # next stretch would pass the shards grows them first.
    redone = [s["rounds_redone"] for s in steps]
    assert max(redone) > 0 and all(r <= 4 for r in redone)
    # The engine's own capacity is where a run starts, not where one ended.
    assert dmr.shard_capacity == start
    names = {e["name"] for e in tracer.to_chrome()["traceEvents"] if e.get("ph") == "X"}
    assert {"mesh.round", "mesh.sync", "mesh.table.grow", "mesh.gather"} <= names


@pytest.mark.parametrize("mode", MODES)
def test_shards_tie_to_the_whole_and_to_the_cli_report(mode, tmp_path, capsysbinary):
    """Every key lives in exactly one shard, the shards' tables together
    are the oracle's, and a shard's count is the CLI's 'shard d: n keys'."""
    lines = zipf_lines(30_000, 1 << 20, seed=5)
    want = py_wordcount(lines, 8)
    path = tmp_path / "zipf.txt"
    path.write_bytes(b"\n".join(lines) + b"\n")
    argv = ["--block-lines", "64", "--emits-per-line", "8", "--sort-mode", mode]
    assert cli.main([str(path), "--mesh", "--backend", "cpu", *argv]) == 0
    got = capsysbinary.readouterr()
    assert got.out == b"".join(k + b"\t%d\n" % v for k, v in sorted(want.items()))
    err = got.err.decode()
    said = [int(n) for n in re.findall(r"shard \d+: (\d+) keys", err)]
    assert "WARN" not in err and "truncated=False" in err
    assert re.search(r"shards grew 1024 -> \d+ rows in \d+ step", err)

    cfg = EngineConfig(block_lines=64, emits_per_line=8, sort_mode=mode)
    dmr = DistributedMapReduce(make_mesh(), cfg)
    assert dmr.shard_capacity == 1024 < max(said)
    res = dmr.run(bytes_ops.strings_to_rows(lines, cfg.line_width))
    shards = _shard_tables(res)
    assert [len(s) for s in shards] == said and len(shards) == 8 and min(said) > 0
    assert sum(said) == len(want) == res.distinct
    whole = {}
    for s in shards:
        assert not whole.keys() & s.keys()  # a key has one home
        whole.update(s)
    assert whole == want


@pytest.mark.parametrize("mode", MODES)
def test_same_table_with_and_without_growth_ahead_and_from_a_stream(mode):
    """One sync at the job's end finds out late and redoes every round, a
    sync a round grows ahead; ``run`` and ``run_stream`` share the loop."""
    lines = zipf_lines(12_000, 1 << 16, seed=3)
    want = py_wordcount(lines, 8)
    tables = {}
    for name, every in (("late", 10 ** 6), ("ahead", 2)):
        dmr = _engine(4, mode)
        tracer = obs.enable(process=name)
        res = dmr.run(_rows(dmr, lines), stats_sync_every=every)
        steps = _grow_spans(tracer)
        obs.disable()
        assert not res.truncated and res.shard_capacity == 1024 and steps
        tables[name] = (res.to_host_pairs(), [len(s) for s in _shard_tables(res)])
        if name == "late":  # every step found out after the fact
            assert all(s["rounds_redone"] == -(-len(lines) // dmr.lines_per_round)
                       for s in steps)
            # A redone stretch's wait follows its grow span: no time is in
            # both mesh.sync and mesh.table.grow.
            spans = [e for e in tracer.to_chrome()["traceEvents"] if e.get("ph") == "X"]
            grown = {e["args"]["id"] for e in spans if e["name"] == "mesh.table.grow"}
            waits = [e for e in spans if e["name"] == "mesh.sync"
                     and e["args"]["what"] == "regrow"]
            assert len(waits) == len(steps)
            assert not any(e["args"].get("parent") in grown for e in waits)
        else:               # once it has two syncs behind it, ahead of the next
            assert 0 in [s["rounds_redone"] for s in steps]
    dmr = _engine(4, mode)
    rows = _rows(dmr, lines)
    lpr = dmr.lines_per_round
    res = dmr.run_stream(rows[i:i + lpr] for i in range(0, len(rows), lpr))
    tables["stream"] = (res.to_host_pairs(), [len(s) for s in _shard_tables(res)])
    assert dict(tables["late"][0]) == want
    assert tables["late"] == tables["ahead"] == tables["stream"]


@pytest.mark.parametrize("over", [0, 1])
def test_landing_on_the_capacity_and_one_key_over(over):
    """Keys that exactly fill the worst shard grow nothing; one more key
    in that shard is one step for every shard."""
    dmr = _engine(4, MODES[0])
    # Find where the keys land, then cut the key set so that every shard
    # is under its capacity but one, which holds exactly 256 (+ over).
    probe = _engine(4, MODES[0], shard_capacity=4096)
    keys = distinct_lines(1_400)
    shards = sorted(_shard_tables(probe.run(_rows(probe, keys))), key=len)
    assert len(shards[0]) > 256
    drop = {k for s in shards[:-1] for k in sorted(s)[250:]}
    drop |= set(sorted(shards[-1])[256 + over:])
    words = [w for ln in keys for w in ln.split() if w not in drop]
    lines = [b" ".join(words[i:i + 8]) for i in range(0, len(words), 8)] * 2
    obs.enable(process="edge")
    res = dmr.run(_rows(dmr, lines), stats_sync_every=10 ** 6)
    assert not res.truncated and dict(res.to_host_pairs()) == py_wordcount(lines, 8)
    assert max(len(s) for s in _shard_tables(res)) == 256 + over
    assert res.table_grows == over and res.shard_capacity == 256 * (1 + over)
    assert obs.metrics_snapshot()["counters"]["mesh.table_grows"] == over


def test_a_sync_that_no_round_follows_grows_nothing():
    """Growth ahead is for the NEXT stretch: the sync that sees it coming
    only notes it and the round that follows grows, so a job's last sync
    (and one ahead of a snapshot) leaves the shards as they are — no
    doubled table to gather, no step counted that no fold needed."""
    keys = distinct_lines(2_000)
    words = [w for ln in keys for w in ln.split()]
    lines = [b" ".join(words[i:i + 2]) for i in range(0, len(words), 2)]

    def job(n_rounds):
        dmr = _engine(4, MODES[0])
        tracer = obs.enable(process="last")
        res = dmr.run(_rows(dmr, lines[:n_rounds * dmr.lines_per_round]),
                      stats_sync_every=1)
        spans = [e for e in tracer.to_chrome()["traceEvents"] if e.get("ph") == "X"]
        obs.disable()
        return res, spans

    # The first job length at which the shards grow: ahead of its LAST
    # round, on what the sync before it (the shorter job's last) noted.
    for n in range(3, 40):
        res, spans = job(n)
        if res.table_grows:
            break
    [step] = [e for e in spans if e["name"] == "mesh.table.grow"]
    rounds = sorted((e for e in spans if e["name"] == "mesh.round"), key=lambda e: e["ts"])
    assert step["args"]["rounds_redone"] == 0 and step["args"]["worst_shard"] > 256
    assert rounds[-2]["ts"] < step["ts"] < rounds[-1]["ts"]
    assert dict(res.to_host_pairs()) == py_wordcount(lines[:n * 64], 8)
    # One round fewer ends at that very sync: the estimate passed a shard,
    # no shard's count did, nothing grew.
    res, spans = job(n - 1)
    assert max(len(s) for s in _shard_tables(res)) <= 256 < step["args"]["worst_shard"]
    assert res.table_grows == 0 and res.shard_capacity == 256
    assert res.table.size == 4 * 256 and not res.truncated
    assert not [e for e in spans if e["name"] == "mesh.table.grow"]


def test_explicit_capacity_stays_a_fixed_bound_and_says_so(caplog):
    """``shard_capacity=`` is a promise about memory: no growth, the tail
    dropped, the report as loud as before."""
    lines = distinct_lines(3_000)
    dmr = _engine(4, MODES[0], shard_capacity=256)
    assert not dmr.grows
    with caplog.at_level(logging.WARNING, logger="locust_tpu"):
        res = dmr.run(_rows(dmr, lines))
    assert res.truncated and res.table_grows == 0 and res.shard_capacity == 256
    assert res.table.size == 4 * 256
    assert "exceeded its table capacity (256)" in caplog.text
    pairs = res.to_host_pairs()
    assert len(pairs) == 4 * 256 and all(v == 1 for _, v in pairs)


def test_hierarchical_mesh_keeps_its_fixed_shards_and_its_report(caplog):
    from locust_tpu.parallel.hierarchical import HierarchicalMapReduce
    from locust_tpu.parallel.mesh import make_mesh_2d

    lines = distinct_lines(6_000)
    hmr = HierarchicalMapReduce(make_mesh_2d(2), EngineConfig(sort_mode=MODES[0], **_SMALL))
    with caplog.at_level(logging.WARNING, logger="locust_tpu"):
        res = hmr.run(bytes_ops.strings_to_rows(lines, 64))
    assert res.truncated and res.table_grows == 0
    assert res.shard_capacity == hmr.shard_capacity
    assert f"exceeded its table capacity ({hmr.shard_capacity})" in caplog.text


def test_cli_warn_names_what_holds_a_fixed_table(tmp_path, capsys):
    """--slices past its shards still says so, and says who grows."""
    path = tmp_path / "keys.txt"
    path.write_bytes(b"\n".join(distinct_lines(20_000)) + b"\n")
    rc = cli.main([str(path), "--mesh", "--slices", "2", "--backend", "cpu",
                   "--block-lines", "64", "--emits-per-line", "8"])
    err = capsys.readouterr().err
    assert rc == 0 and "truncated=True" in err
    assert "WARN: a shard's table capacity was exceeded" in err
    assert "--slices holds shards of fixed size" in err


def test_a_snapshot_carries_its_capacity_and_a_resume_goes_on_at_it(tmp_path):
    """Crash after the shards grew: the snapshot is of a settled table at
    the grown capacity, and the resumed run starts there, not at 256."""
    lines = distinct_lines(3_000) * 2
    want = py_wordcount(lines, 8)
    dmr = _engine(4, MODES[0])
    rows = _rows(dmr, lines)
    lpr = dmr.lines_per_round
    n_rounds = -(-len(rows) // lpr)

    def blocks(die_at=None):
        for r in range(n_rounds):
            if r == die_at:
                raise RuntimeError("simulated crash")
            yield rows[r * lpr:(r + 1) * lpr]

    ckpt = str(tmp_path / "ckpt")
    kw = dict(fingerprint="corpus", checkpoint_dir=ckpt, checkpoint_every=2)
    with pytest.raises(RuntimeError, match="simulated crash"):
        dmr.run_stream(blocks(die_at=n_rounds // 2), **kw)
    with np.load(f"{ckpt}/state.p0.npz") as z:
        held = z["acc_valid"].shape[0] // 4
        at = int(z["next_round"])
        assert not bool(z["truncated"]) and int(z["acc_valid"].sum()) == int(z["distinct"])
    assert held > dmr.shard_capacity == 256 and 0 < at <= n_rounds // 2
    tracer = obs.enable(process="resume")
    res = _engine(4, MODES[0]).run_stream(blocks(), **kw)
    assert not res.truncated and dict(res.to_host_pairs()) == want
    assert res.shard_capacity >= held
    steps = _grow_spans(tracer)
    assert all(s["from_rows"] >= held for s in steps)
    # Only the rounds after the snapshot were folded.
    assert obs.metrics_snapshot()["counters"]["mesh.rounds"] == n_rounds - at


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("n_dev", [4, 8])
def test_a_traced_mesh_job_says_what_its_round_and_its_gather_are_made_of(n_dev, mode):
    """A mesh engine watches jax's pipeline as the default path's does: its
    step programs' trace, lower and load are engine.program.* spans inside
    the mesh.round or mesh.table.grow that paid for them (so mesh.round's
    self time is the round without the reload); every round holds one
    mesh.h2d, the sharded device_put alone; mesh.gather resolves into the
    three spans of the table's way to the host, as engine.finalize does."""
    lines = zipf_lines(6_000, 1 << 14, seed=n_dev)
    want = py_wordcount(lines, 8)
    tracer = obs.enable(process="meshtail")
    dmr = _engine(n_dev, mode)
    res = dmr.run(_rows(dmr, lines), stats_sync_every=4)
    pairs = res.to_host_pairs()
    assert dict(pairs) == want and pairs == sorted(pairs)
    spans = [e for e in tracer.to_chrome()["traceEvents"] if e.get("ph") == "X"]
    by_id = {e["args"]["id"]: e for e in spans}

    def kids(parent):
        every = [e for e in tracer.to_chrome()["traceEvents"] if e.get("ph") == "X"]
        return sorted((e for e in every
                       if e["args"].get("parent") == parent["args"]["id"]),
                      key=lambda e: e["ts"])

    rounds = [e for e in spans if e["name"] == "mesh.round"]
    assert len(rounds) == -(-len(lines) // dmr.lines_per_round)
    width = dmr.cfg.line_width
    for r in rounds:
        staged = [e for e in kids(r) if e["name"] == "mesh.h2d"]
        assert len(staged) == 1
        assert staged[0]["args"]["bytes"] == dmr.lines_per_round * width
        assert r["ts"] <= staged[0]["ts"]
        assert staged[0]["ts"] + staged[0]["dur"] <= r["ts"] + r["dur"] + 1.0
    assert len([e for e in spans if e["name"] == "mesh.h2d"]) == len(rounds)
    programs = [e for e in spans if e["name"].startswith("engine.program.")]
    steps = [e for e in programs if "local_step" in e["args"]["fun_name"]]
    assert {e["name"].rsplit(".", 1)[1] for e in steps} == {"trace", "lower", "load"}
    homes = {by_id[e["args"]["parent"]]["name"] for e in steps}
    assert "mesh.round" in homes and homes <= {"mesh.round", "mesh.table.grow"}
    traced = [e for e in steps if e["name"] == "engine.program.trace"
              and by_id[e["args"]["parent"]]["name"] == "mesh.round"]
    assert traced and traced[0]["args"]["parent"] == rounds[0]["args"]["id"]
    # The round without its reload: what the program spans cover is not
    # the round's own time.
    own = tracer.self_times()[rounds[0]["args"]["id"]]
    assert own < rounds[0]["dur"] - max(e["dur"] for e in kids(rounds[0])
                                         if e["name"] == "engine.program.load")
    [gather] = [e for e in spans if e["name"] == "mesh.gather"]
    tail = kids(gather)
    assert [e["name"] for e in tail] == [
        "engine.finalize.d2h", "engine.finalize.decode", "engine.finalize.order"]
    assert all(gather["ts"] <= e["ts"] and e["ts"] + e["dur"]
               <= gather["ts"] + gather["dur"] + 1.0 for e in tail)
    assert tail[0]["args"]["rows"] == res.table.size == gather["args"]["rows"]
    assert tail[0]["args"]["bytes"] == res.table.size * (res.table.num_lanes * 4 + 5)
    assert tail[1]["args"]["rows"] == len(want) == tail[2]["args"]["rows"]
    # The CLI's table asks for ROWS: another mesh.gather over the same
    # three children, the order span saying the arrays are what is printed.
    rows = res.to_host_rows()
    assert rows.pairs() == pairs and rows.keys.shape == (len(want), 8)
    gather2 = [e for e in tracer.to_chrome()["traceEvents"]
               if e.get("ph") == "X" and e["name"] == "mesh.gather"][1]
    tail2 = kids(gather2)
    assert [e["name"] for e in tail2] == [e["name"] for e in tail]
    assert [e["args"]["rows"] for e in tail2] == [e["args"]["rows"] for e in tail]
    assert tail2[2]["args"]["fast"] == 1 and "fast" not in tail[2]["args"]
    assert tail2[2]["args"]["merged"] == tail[2]["args"]["merged"] == 0
    # Only a configuration's FIRST job holds them: a second job re-makes
    # no program — on this engine or on a new one of the configuration,
    # which takes the process's (engine._programs_for) — and its rounds
    # hold their staging alone.
    for again in (dmr, _engine(n_dev, mode)):
        mark = len([e for e in tracer.to_chrome()["traceEvents"] if e.get("ph") == "X"])
        res2 = again.run(_rows(again, lines), stats_sync_every=4)
        assert res2.table_grows == res.table_grows
        assert dict(res2.to_host_pairs()) == want
        later = [e for e in tracer.to_chrome()["traceEvents"] if e.get("ph") == "X"][mark:]
        assert not [e for e in later if e["name"].startswith("engine.program.")]
        mine = [e for e in later if e["name"] == "mesh.round"]
        assert len(mine) == len(rounds)
        assert all([k["name"] for k in kids(r)] == ["mesh.h2d"] for r in mine)


def test_a_mesh_engine_made_with_tracing_off_registers_no_listener():
    import jax._src.monitoring as m

    before = (len(m.get_event_time_span_listeners()), len(m.get_event_listeners()))
    _engine(4, MODES[0])
    assert (len(m.get_event_time_span_listeners()),
            len(m.get_event_listeners())) == before
    obs.enable(process="meshwatch")
    _engine(4, MODES[0])
    _engine(4, MODES[0])  # a second engine: the same pair
    assert (len(m.get_event_time_span_listeners()),
            len(m.get_event_listeners())) == (before[0] + 1, before[1] + 1)
    obs.disable()
    assert (len(m.get_event_time_span_listeners()),
            len(m.get_event_listeners())) == before
