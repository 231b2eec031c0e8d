"""What the benchmark reads from the program is still there to be read.

``benchmarks/layer_metrics/*.json`` name spans, program names and jax
events; a refactoring that renames one turns a per-layer metric to
``null`` on the chip, where no test runs.  These cases hold the program to
those names on the CPU: they read ``benchmarks/`` and never write it, and
a metric file added there is a new case here.  Beside them: ``bench.py``'s
one line, and the environment variables the package may read.
"""

import glob
import json
import os
import re

import jax
import jax.numpy as jnp
import pytest

import bench
import chip_smoke
from locust_tpu import engine
from locust_tpu.config import EngineConfig, default_sort_mode
from locust_tpu.core.kv import KVBatch, RecordBatch
from locust_tpu.engine import MapReduceEngine
from locust_tpu.obs import programs
from locust_tpu.obs.names import METRIC_KINDS, NAMES

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
METRIC_FILES = sorted(
    glob.glob(os.path.join(REPO, "benchmarks", "layer_metrics", "*.json"))
)
SPANS = {name for name, kind in NAMES.items() if kind == "span"}
METRICS = {name for name, kind in NAMES.items() if kind in METRIC_KINDS}
JAX_EVENTS = {programs.TRACE, programs.LOWER, programs.COMPILE,
              programs.CACHE_REQUEST, programs.CACHE_HIT}
# Readers that take nothing from the program: the harness's own job
# clock and the device's memory_stats().
HARNESS_ONLY = {"job_percentile", "memory_stats"}


def _toy_engine() -> MapReduceEngine:
    """Toy shapes under the mode the chip runs."""
    return MapReduceEngine(EngineConfig(
        block_lines=8, line_width=32, key_width=8, emits_per_line=4,
        sort_mode=default_sort_mode("tpu")))


def _toy_mesh_step():
    """The mesh's step program at toy shapes, and the shapes it takes."""
    from locust_tpu.parallel import DistributedMapReduce, make_mesh

    cfg = _toy_engine().cfg
    dmr = DistributedMapReduce(make_mesh(4), cfg)
    lines = jax.ShapeDtypeStruct((dmr.lines_per_round, cfg.line_width), jnp.uint8)
    return dmr._step, (lines, jax.eval_shape(dmr.empty_table),
                       jax.eval_shape(dmr.empty_leftover))


def _lowered_text(eng: MapReduceEngine) -> tuple[str, ...]:
    """The programs the cells run lowered, never compiled: the default
    path's four and ``--mesh``'s step."""
    cfg = eng.cfg
    lines = jax.ShapeDtypeStruct((cfg.block_lines, cfg.line_width), jnp.uint8)
    kv, _ = jax.eval_shape(eng._map, lines)
    batch = jax.eval_shape(eng._process, kv)
    table = jax.eval_shape(eng._reduce, batch)
    acc = jax.eval_shape(lambda: KVBatch.empty(eng._table_size, cfg.key_lanes))
    seen = jax.ShapeDtypeStruct((), jnp.int32)
    lowered = (
        eng._map.lower(lines),
        eng._process.lower(kv),
        eng._reduce.lower(batch),
        eng._merge.lower(acc, (table, table), seen),
    )
    step, shapes = _toy_mesh_step()
    lowered += (step.lower(*shapes),)
    lowered += _lowered_record_sort(eng)
    lowered += _toy_mesh_record_sort()[1]
    lowered += (_lowered_pagerank(),)
    lowered += _lowered_index(cfg)
    lowered += _lowered_join(cfg)
    return tuple(low.as_text() for low in lowered)


def _lowered_join(cfg: EngineConfig) -> tuple:
    """The ``join`` command's five programs at toy shapes: a block, a page
    table of two blocks, a visit store of four."""
    from locust_tpu.apps.join import IP_LANES, _build_join_programs

    progs = engine._programs_for(("join", cfg), lambda: _build_join_programs(cfg))
    lanes = cfg.key_width // 4
    lines = jax.ShapeDtypeStruct((cfg.block_lines, cfg.line_width), jnp.uint8)
    table = jax.ShapeDtypeStruct((2 * cfg.block_lines, lanes + 2), jnp.uint32)
    store = jax.ShapeDtypeStruct((4 * cfg.block_lines, lanes + IP_LANES + 2), jnp.uint32)
    counts = jax.ShapeDtypeStruct((3,), jnp.int32)
    fill = jax.ShapeDtypeStruct((), jnp.int32)
    groups, _ = jax.eval_shape(progs.probe, table, store, fill)
    return (
        progs.map_pages.lower(table, counts, lines, fill),
        progs.map_visits.lower(store, counts, lines, jax.ShapeDtypeStruct((2,), jnp.int32)),
        progs.grow.lower(store, rows=8 * cfg.block_lines),
        progs.probe.lower(table, store, fill),
        progs.cut.lower(groups, rows=cfg.block_lines),
    )


def _lowered_index(cfg: EngineConfig) -> tuple:
    """The ``index`` command's five programs at toy shapes: a block, a
    store of four blocks' emits."""
    from locust_tpu.apps.inverted_index import _build_index_programs

    progs = engine._programs_for(("index", cfg), lambda: _build_index_programs(cfg))
    lines = jax.ShapeDtypeStruct((cfg.block_lines, cfg.line_width), jnp.uint8)
    ids = jax.ShapeDtypeStruct((cfg.block_lines,), jnp.int32)
    head, counts = jax.eval_shape(progs.block, lines, ids)
    fill = jax.ShapeDtypeStruct((), jnp.int32)
    store = jax.eval_shape(lambda: KVBatch.empty(4 * cfg.emits_per_block, cfg.key_lanes))
    collected = jax.eval_shape(progs.collect, store, fill)
    return (
        progs.block.lower(lines, ids),
        progs.append.lower(store, counts, head, counts),
        progs.grow.lower(store, rows=8 * cfg.emits_per_block),
        progs.collect.lower(store, fill),
        progs.cut.lower(*collected[:4], rows=cfg.emits_per_block),
    )


def _lowered_pagerank():
    """The ``pagerank`` command's one program at toy shapes, damping
    traced as the plan passes it."""
    from locust_tpu.apps.pagerank import pagerank

    edges = jax.ShapeDtypeStruct((16,), jnp.int32)
    return pagerank.lower(edges, edges, num_nodes=8, num_iters=3, damping=0.85)


def _lowered_record_sort(eng: MapReduceEngine) -> tuple:
    """The ``sort`` command's programs at toy shapes: two blocks of eight
    gensort-width records."""
    sorter = eng.record_sort(100, 10)
    records = jax.eval_shape(lambda: RecordBatch.empty(16, 100))
    at = jax.ShapeDtypeStruct((), jnp.int32)
    block = jax.ShapeDtypeStruct((8 * 25,), jnp.uint32)
    perm = jax.ShapeDtypeStruct((2, 8), jnp.int32)
    progs = sorter.programs
    return (
        progs.empty.lower(rows=16),
        progs.place.lower(records, block, at),
        progs.sort_keys.lower(records, at, block_rows=8),
        progs.permute.lower(records, perm, at),
    )


def _toy_mesh_record_sort():
    """The mesh record sort at toy shapes — four devices, two blocks of
    eight gensort-width records a device, bins of eight — and its
    programs lowered."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from locust_tpu.parallel import make_mesh
    from locust_tpu.parallel.record_sort import MeshRecordSort

    sorter = MeshRecordSort(make_mesh(4), 100, 10)
    on, everywhere = (NamedSharding(sorter.mesh, spec) for spec in (P("data"), P()))

    def shape(dims, dtype, sharding=on):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)

    words, n = shape((4 * 16, 25), jnp.uint32), shape((), jnp.int32, everywhere)
    progs = sorter.programs
    partition = progs.partition.lower(
        words, shape((3, 4), jnp.uint32, everywhere), n, block_rows=8, bin_rows=8)
    return partition, (
        progs.empty.lower(rows=16),
        progs.place.lower(words, shape((4 * 8 * 25,), jnp.uint32), n),
        progs.split.lower(words, n, block_rows=8, samples=8),
        partition,
        progs.sort_shard.lower(shape((4 * 32, 25), jnp.uint32),
                               shape((4 * 32,), jnp.int32), block_rows=8),
        progs.permute.lower(jax.ShapeDtypeStruct((32, 25), jnp.uint32),
                            jax.ShapeDtypeStruct((4, 8), jnp.int32),
                            jax.ShapeDtypeStruct((), jnp.int32)),
    )


@pytest.fixture(scope="module")
def program_names() -> dict[str, set[str]]:
    """Module names of the programs the cells run (the default path's
    four, the mesh's step, the record sort's four, the mesh record
    sort's six, pagerank's one, the index's five and the join's five), as the
    device trace's ``XLA Modules`` line will show them: of a
    configuration's ``first`` engine, which builds them, and of a later
    one, which takes the process's (``shared``, engine._programs_for) —
    the same programs, text for text."""
    engine.clear_programs()  # whatever ran before: the first engine builds
    first, shared = _toy_engine(), _toy_engine()
    assert shared._merge is first._merge
    texts = {"first": _lowered_text(first), "shared": _lowered_text(shared)}
    assert texts["shared"] == texts["first"]
    return {which: {re.match(r"module @(\S+)", text).group(1) for text in found}
            for which, found in texts.items()}


@pytest.fixture(scope="module")
def cli_stderr(tmp_path_factory) -> str:
    """What ``cli.main`` prints beside the table for a ten-line text: on
    the default path and with ``--mesh``."""
    path = tmp_path_factory.mktemp("contract") / "ten.txt"
    path.write_bytes(b"".join(b"line %d of ten, said twice\n" % i for i in range(10)))
    said = []
    for flags in ([], ["--mesh"]):
        table, err, _ = chip_smoke.run_cli([str(path), "--block-lines", "8", *flags])
        assert table.count(b"\n") == 15  # ten numbers and five words
        said.append(err)
    said.append(_mesh_sort_stderr(path.parent))
    _, err, _ = chip_smoke.run_cli(["index", str(path), "--block-lines", "8"])
    said.append(err)
    visits = path.parent / "uservisits.txt"
    visits.write_bytes(b"".join(
        b"10.0.0.%d,http://ten/%d,1999-06-0%d,%d.500000,agent,USA,en-us,word,3\n"
        % (i % 3, i % 4, i % 9 + 1, i) for i in range(10)))
    ranks = path.parent / "rankings.txt"
    ranks.write_bytes(b"".join(b"http://ten/%d,%d,7\n" % (i, i + 1) for i in range(4)))
    table, err, _ = chip_smoke.run_cli(["join", str(ranks), str(visits), "--block-lines", "8"])
    assert table.count(b"\n") == 3  # three sourceIPs
    said.append(err)
    return "\n".join(said)


def _mesh_sort_stderr(tmp) -> str:
    """What ``cli.main`` says of a ``sort IN OUT --mesh`` job of 64 records."""
    import contextlib
    import io

    from locust_tpu.cli import main as cli_main

    src = tmp / "records.bin"
    src.write_bytes(bytes(range(256)) * 25)
    err = io.StringIO()
    with contextlib.redirect_stderr(err):
        assert cli_main(["sort", str(src), str(tmp / "sorted.bin"), "--mesh",
                         "--backend", "cpu"]) == 0
    return err.getvalue()


@pytest.fixture(scope="module")
def mesh_op_names() -> set[str]:
    """The HLO instructions of the mesh's step program, compiled for the
    CPU at toy shapes: a device trace's ``XLA Ops`` line names an event by
    its whole instruction (``%all_to_all.32 = u32[...] all-to-all(...)``
    on a v5e), so a pattern is held to the opcodes the program has."""
    step, shapes = _toy_mesh_step()
    text = step.lower(*shapes).compile().as_text()
    return {ln.strip() for ln in text.splitlines() if " = " in ln}


@pytest.fixture(scope="module")
def mesh_record_op_names() -> set[str]:
    """The same of the mesh record sort's partition program, where its
    all-to-alls are."""
    text = _toy_mesh_record_sort()[0].compile().as_text()
    return {ln.strip() for ln in text.splitlines() if " = " in ln}


def _assert_patterns_match(patterns, names, what):
    for pat in patterns:
        assert any(re.search(pat, n) for n in names), (
            f"{what}: /{pat}/ matches none of {[n[:80] for n in sorted(names)[:40]]}"
        )


def _metric_cases():
    """A case a metric file; one that finds PROGRAMS by name is two, held
    to a configuration's first engine and to one that shares its programs."""
    for path in METRIC_FILES:
        with open(path) as f:
            spec = json.load(f)
        by_program = spec["reader"] in (
            "xla_module", "roofline", "roofline_job", "roofline_pagerank_job",
            "roofline_index_job", "roofline_join_job") or (
            spec["reader"] == "roofline_device_job" and "programs" in spec)
        for which in ("first", "shared") if by_program else (None,):
            name = os.path.basename(path)
            yield pytest.param(path, which, id=f"{name}-{which}" if which else name)


@pytest.mark.parametrize("path, which", _metric_cases())
def test_layer_metric_reads_a_name_the_program_still_has(path, which, request):
    with open(path) as f:
        spec = json.load(f)
    reader = spec["reader"]
    # Built on first use, once a module: a case that reads a span name
    # lowers no program and runs no job.
    fixture = request.getfixturevalue
    if reader in ("obs_span", "span_count", "span_count_of", "device_in_span"):
        assert spec["span"] in SPANS, f"{spec['span']!r} is not a registered span"
        assert spec.get("within", spec["span"]) in SPANS, spec["within"]
        prefix = spec.get("holds_all_work")
        assert prefix is None or any(s.startswith(prefix) for s in SPANS), prefix
    elif reader == "xla_module":
        _assert_patterns_match(spec["patterns"], fixture("program_names")[which], "patterns")
    elif reader == "xla_op":
        # rec_* files read the mesh record sort's exchange, the others
        # the WordCount mesh's step.
        ops = "mesh_record_op_names" if os.path.basename(path).startswith("rec_") else "mesh_op_names"
        _assert_patterns_match(spec["patterns"], fixture(ops), "patterns")
    elif reader == "roofline_device_job":
        if "programs" in spec:
            _assert_patterns_match(spec["programs"], fixture("program_names")[which], "programs")
        else:
            _assert_patterns_match(spec["ops"], fixture("mesh_record_op_names"), "ops")
    elif reader in ("roofline_job", "roofline_pagerank_job", "roofline_index_job",
                    "roofline_join_job"):
        _assert_patterns_match(spec["programs"], fixture("program_names")[which], "programs")
    elif reader == "roofline":
        names = fixture("program_names")[which]
        _assert_patterns_match([spec["unit"]], names, "unit")
        _assert_patterns_match(spec["programs"], names, "programs")
    elif reader == "jax_monitoring":
        events = set(spec["events"]) | set(spec.get("minus", []))
        assert events <= JAX_EVENTS, (
            f"{sorted(events - JAX_EVENTS)}: not an event obs/programs.py knows"
        )
    elif reader == "counter":
        assert spec["name"] in METRICS, f"{spec['name']!r} is not a registered metric"
    elif reader in ("stderr_regex", "stderr_number"):
        err = fixture("cli_stderr")
        assert re.search(spec["pattern"], err), (
            f"/{spec['pattern']}/ matches nothing cli.main printed:\n{err}"
        )
    else:
        assert reader in HARNESS_ONLY, (
            f"reader kind {reader!r} is unknown to this test: say here what it "
            "reads from the program"
        )


def test_the_glob_found_the_metric_files():
    assert METRIC_FILES, "no case above: benchmarks/layer_metrics/ has moved"


# ------------------------------------------------------------- bench.py


def _one_line(capsys) -> dict:
    out = capsys.readouterr().out
    assert out.count("\n") == 1, out
    return json.loads(out)


def test_bench_prints_one_json_line_with_the_contract_keys(capsys):
    assert bench.main() == 0
    row = _one_line(capsys)
    assert {"metric", "value", "unit", "vs_baseline"} <= set(row)
    assert "error" not in row
    assert row["backend"] == "cpu" and row["value"] > 0
    assert row["distinct"] > 0 and row["truncated"] is False


def test_bench_prints_one_line_with_error_for_a_missing_corpus(tmp_path, capsys):
    assert bench.main(str(tmp_path / "no_such_corpus.txt")) != 0
    row = _one_line(capsys)
    assert {"metric", "value", "unit", "vs_baseline", "error"} <= set(row)
    assert row["value"] == 0.0


# ------------------------------------------------- environment variables

# Credentials, the fault harness's switch, a debug switch.  jax's own
# cache variables (config.compile_cache_dir) are not LOCUST_*.
ALLOWED_ENV = {"LOCUST_SECRET", "LOCUST_FAULT_PLAN", "LOCUST_DEBUG_CHECKS"}


def test_the_package_names_no_other_locust_variable():
    found = {}
    for path in glob.glob(os.path.join(REPO, "locust_tpu", "**", "*.py"), recursive=True):
        with open(path) as f:
            for name in set(re.findall(r"LOCUST_[A-Z0-9_]+", f.read())):
                found.setdefault(name, os.path.relpath(path, REPO))
    assert set(found) <= ALLOWED_ENV, {
        k: v for k, v in found.items() if k not in ALLOWED_ENV
    }
