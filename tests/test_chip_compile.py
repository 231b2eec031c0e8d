"""Compile the main path's kernels for a DESCRIBED v5e — no chip attached.

Interpret mode cannot say whether Mosaic accepts a kernel: both kernels the
fused path rests on passed every interpret-mode test and were refused by
the chip's compiler (an i1 vector shifted across lanes; a scoped-VMEM
budget).  The TPU compiler is installed here and compiles for a topology
that is described and not attached, so these cases guard every later PR at
no chip time.  A compile that passes is not a chip run (chip_smoke.py is).

Rules of this file (on-chip-measurement guide §2): the topology is
described INSIDE a module-scoped fixture that skips when it cannot be —
never at import, in a skipif or in parametrize arguments (only one process
may load libtpu; a module that decides at import breaks xdist collection);
every compile runs in this process with the persistent cache off around
it (a described-chip executable cannot be read back without a chip).
Code that asks ``jax.default_backend()`` sees the CPU here, so each case
compiles the kernel or jitted function itself and steers the TPU branch
from the test (``map_impl="einsum"``, ``interpret=False``).

Left out, with the measured reason (described v5e, no chip, PR 22): one
``hasht`` ``fold_into`` of a 4096-line block compiles in 152 s, and any
``hash*`` Process program (one multi-operand ``lax.sort``) in ~200 s —
whole-program compiles are a scratch script's job, not a test's.
"""

import contextlib
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from locust_tpu.config import EngineConfig

# The published widths (CLI defaults; reference EMITS_PER_LINE=20).
WIDTHS = dict(line_width=128, emits_per_line=20, key_width=32)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure to describe = skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    assert topo.devices[0].device_kind == "TPU v5 lite"
    return SingleDeviceSharding(topo.devices[0])


@contextlib.contextmanager
def _persistent_cache_off():
    """A described-chip executable is written to the persistent cache but
    cannot be read back without a chip (the next compile would warn)."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        cc.reset_cache()


@pytest.fixture(autouse=True)
def cache_off():
    with _persistent_cache_off():
        yield


def _block(one_chip, block_lines):
    return jax.ShapeDtypeStruct(
        (block_lines, WIDTHS["line_width"]), jnp.uint8, sharding=one_chip
    )


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("block_lines", [4096, 32768])
def test_tokenize_block_pallas_compiles(one_chip, block_lines):
    from locust_tpu.ops.pallas.tokenize import tokenize_block_pallas

    cfg = EngineConfig(block_lines=block_lines, **WIDTHS)
    compiled = tokenize_block_pallas.lower(
        _block(one_chip, block_lines), cfg, False
    ).compile()
    assert _has_kernel(compiled)


@pytest.mark.parametrize("block_lines", [4096, 32768])
def test_fused_block_preagg_compiles_at_smoke_widths(one_chip, block_lines):
    """Every op of the megakernel (mask shifts, Gram dedupe, hash, probe
    scatters, residual compaction) at both block sizes and the real
    line_width — at emits_per_line=4 / key_width=8, because Mosaic takes
    ~150 s on the fully unrolled published widths (the slow case below)."""
    from locust_tpu.ops.pallas.fused_fold import fused_block_preagg

    cfg = EngineConfig(block_lines=block_lines, line_width=128,
                       emits_per_line=4, key_width=8, sort_mode="fused")
    compiled = fused_block_preagg.lower(
        _block(one_chip, block_lines), cfg, False
    ).compile()
    assert _has_kernel(compiled)


@pytest.mark.slow
@pytest.mark.parametrize("block_lines", [4096, 32768])
def test_fused_block_preagg_compiles_at_published_widths(one_chip, block_lines):
    """The kernel chip_smoke.py runs.  ~150 s a case (Mosaic, 640 unrolled
    (slot, byte) reductions + 270 probe contractions), so outside tier-1;
    this is the case that found the 16 MB scoped-VMEM refusal."""
    from locust_tpu.ops.pallas.fused_fold import fused_block_preagg

    cfg = EngineConfig(block_lines=block_lines, sort_mode="fused", **WIDTHS)
    compiled = fused_block_preagg.lower(
        _block(one_chip, block_lines), cfg, False
    ).compile()
    assert _has_kernel(compiled)


def _eqns(jaxpr, primitive, out):
    """Every equation of one primitive, nested jaxprs included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == primitive:
            out.append(eqn)
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else [v]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _eqns(inner, primitive, out)
    return out


def _fused_jaxpr():
    from locust_tpu.ops.pallas.fused_fold import fused_block_preagg

    cfg = EngineConfig(block_lines=64, line_width=128, emits_per_line=4,
                       key_width=8, sort_mode="fused")
    return jax.make_jaxpr(
        lambda x: fused_block_preagg(x, cfg, False)
    )(jnp.zeros((64, 128), jnp.uint8)).jaxpr


def test_fused_kernel_asks_full_f32_where_operands_exceed_a_byte():
    """What only the chip shows, pinned structurally: Mosaic's default
    precision feeds the MXU bf16 operands — exact for bytes and one-hots,
    wrong for the squared norms and the per-tile counts.  Exactly the
    norm broadcast, the count scatter of each probe round and the
    residual count compaction carry Precision.HIGHEST; on the CPU and in
    the interpreter f32 dots are exact, so dropping one of these passes
    every other test and miscounts on the chip (first chip run, PR 22)."""
    from locust_tpu.config import HASHT_PROBES

    dots = _eqns(_fused_jaxpr(), "dot_general", [])
    exact = [e.params["precision"] for e in dots
             if e.params["precision"] is not None]
    assert len(exact) == 1 + HASHT_PROBES + 1
    assert all(
        p == (jax.lax.Precision.HIGHEST, jax.lax.Precision.HIGHEST)
        for p in exact
    )


def test_fused_kernel_asks_for_the_vmem_the_published_widths_need():
    """The published-width compile is ``-m slow`` (150 s a case), so in
    tier-1 only this guards the repair of Mosaic's refusal there —
    ``Scoped allocation with size 23.78M and limit 16.00M exceeded scoped
    vmem limit``: the kernel's pallas_call must carry a limit above that
    need.  (A change that RAISES the need is caught only by the slow
    cases: run them before touching ops/pallas/fused_fold.py.)"""
    (call,) = _eqns(_fused_jaxpr(), "pallas_call", [])
    params = call.params["compiler_params"]["mosaic_tpu"]
    assert params.vmem_limit_bytes >= 32 << 20


def test_tokenize_block_einsum_branch_compiles(one_chip):
    """The MXU formulation of the map stage — the branch a TPU takes
    (``map_impl="auto"`` asks jax.default_backend(), which is the CPU
    here, so the test names it) and no CPU test ever compiled."""
    from locust_tpu.ops.map_stage import tokenize_block

    cfg = EngineConfig(block_lines=4096, map_impl="einsum", **WIDTHS)
    compiled = jax.jit(tokenize_block, static_argnums=1).lower(
        _block(one_chip, 4096), cfg
    ).compile()
    assert not _has_kernel(compiled)  # pure XLA
    assert "convolution" in compiled.as_text() or "dot" in compiled.as_text()


PAGERANK_EDGES = 5_105_039


def _pagerank_compiled(one_chip, edges):
    from locust_tpu.apps.pagerank import pagerank

    ids = jax.ShapeDtypeStruct((edges,), jnp.int32, sharding=one_chip)
    with _persistent_cache_off():  # a module's fixture runs before a test's
        return pagerank.lower(
            ids, ids, num_nodes=916_428, num_iters=20, damping=0.85
        ).compile()


@pytest.fixture(scope="module")
def pagerank_text_and_stats(one_chip):
    """``pagerank5M.batch``'s one program at its own shape — web-Google's
    5,105,039 edges over 916,428 ids, 20 rounds, damping traced as the
    plan passes it (3.4 s on a described v5e, PR 41; 5 s with the chunks'
    loop, PR 46), compiled once for the cases below."""
    compiled = _pagerank_compiled(one_chip, PAGERANK_EDGES)
    return compiled.as_text(), compiled.memory_analysis()


def test_pagerank_iterate_compiles_at_the_cells_shape(pagerank_text_and_stats, one_chip):
    """A change that breaks the shape, or blows its memory past a chip's,
    shows on the CPU.  The 1 GiB is what refuses the share's rows gathered
    for ALL the edges at once (2.6 GB of temporaries; PERF.md §6, PR 46),
    and four times the edges must cost words an edge more, never rows: the
    rows exist a chunk at a time."""
    from locust_tpu.apps.pagerank import CHUNK, LANES

    text, stats = pagerank_text_and_stats
    assert "while" in text and "scatter" in text  # one scan, the scatter-add inside
    assert stats.argument_size_in_bytes + stats.temp_size_in_bytes < 1 << 30
    more = _pagerank_compiled(one_chip, 4 * PAGERANK_EDGES).memory_analysis()
    grown = more.temp_size_in_bytes - stats.temp_size_in_bytes
    assert grown < 3 * PAGERANK_EDGES * 16 + CHUNK * LANES * 4, grown


def _computations(text):
    """``{name: its lines}`` of every computation of an HLO module's text."""
    found, name = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?(%\S+) \(.*\{$", line)
        if head:
            name = head.group(1)
            found[name] = []
        elif name:
            found[name].append(line)
    return found


def test_pagerank_round_gathers_rows_in_an_inner_loop_on_the_chip(pagerank_text_and_stats):
    """The chip pays a gather by the index: a word an edge cost it 33.8 ms
    a round, a 128-lane row an edge a third of that (PERF.md §6, PRs 42
    and 46).  So the whole program holds ONE gather, it yields a chunk's
    rows and no word an edge, and its fusion stands in the body of the loop
    over the chunks, which stands in the scan's."""
    from locust_tpu.apps.pagerank import CHUNK, LANES

    text, _ = pagerank_text_and_stats
    gathers = re.findall(r"= (\w+\[[\d,]*\])\S* gather\(", text)
    assert gathers == [f"f32[{CHUNK},{LANES}]"], gathers
    comps = _computations(text)

    def holder(pattern):
        """The one computation with a line that matches ``pattern``."""
        held = [n for n, lines in comps.items() if any(re.search(pattern, x) for x in lines)]
        assert len(held) == 1, (pattern, held)
        return held[0]

    rows = rf"= f32\[{CHUNK},{LANES}\]\S* fusion\("
    chunks_loop = holder(rows)
    scan_body = holder(rf" while\(.*body={re.escape(chunks_loop)}[,\s]")
    entry = holder(rf" while\(.*body={re.escape(scan_body)}[,\s]")
    assert f"ENTRY {entry} " in text


INDEX_STORE_ROWS = 10_485_760  # the capacity indexzipf.batch's store ends at


def _index_programs_and_shapes(one_chip):
    """``indexzipf.batch``'s programs and the shapes they take at the
    capacity the cell's pair store ends at."""
    from locust_tpu.apps.inverted_index import _build_index_programs
    from locust_tpu.core.kv import KVBatch

    cfg = EngineConfig(block_lines=4096, map_impl="einsum", **WIDTHS)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def batch(rows):
        return KVBatch(shape((rows, cfg.key_lanes), jnp.uint32),
                       shape((rows,), jnp.int32), shape((rows,), jnp.bool_))

    return (_build_index_programs(cfg), cfg, shape, batch(INDEX_STORE_ROWS),
            batch(cfg.emits_per_block))


def test_index_store_programs_compile_at_the_cells_shape(one_chip):
    """The pair store's own programs — a block's head appended at the fill,
    a growth step — at the cell's capacity: no sort in them, a second or
    two each, and together with the store well inside a chip.  (The cut
    holds sorts from PR 47 on: it compiles with the collect, below.)"""
    progs, cfg, shape, store, head = _index_programs_and_shapes(one_chip)
    counts, n = shape((3,), jnp.int32), INDEX_STORE_ROWS
    for lowered in (
        progs.append.lower(store, counts, head, counts),
        progs.grow.lower(jax.tree.map(
            lambda x: shape((n // 2, *x.shape[1:]), x.dtype), store), rows=n),
    ):
        compiled = lowered.compile()
        stats = compiled.memory_analysis()
        assert " sort(" not in compiled.as_text()
        assert stats.argument_size_in_bytes + stats.temp_size_in_bytes < 2 << 30


def test_index_collect_groups_the_store_and_orders_only_the_entries():
    """The store is only GROUPED — one five-operand sort by (dead, hash64)
    that carries the doc id and the row index, then ONE gather of whole key
    rows — and the byte order is made on the word entries: the only loop
    of sorts (``_order_rows``' stable three-operand pass) runs over the
    cut's ``rows=``, a sixteenth of the store at the cell's size.  No sort
    has more than five operands, because the chip's compiler takes time
    with the square of them (PERF.md section 6, PR 45), and nothing one word
    wide is gathered over the store: the chip pays a gather by the index
    (8.6 ns a word, 2.4 a word of an eight-lane row)."""
    from locust_tpu.apps.inverted_index import RADIX_KEYS, _build_index_programs
    from locust_tpu.core.kv import KVBatch

    cfg = EngineConfig(block_lines=8, line_width=32, key_width=32, emits_per_line=4)
    progs, n, rows = _build_index_programs(cfg), 256, 64
    collect = jax.make_jaxpr(progs.collect)(
        KVBatch.empty(n, cfg.key_lanes), jnp.int32(7))
    cut = jax.make_jaxpr(functools.partial(progs.cut, rows=rows))(*collect.out_avals[:4]).jaxpr
    collect = collect.jaxpr

    def store_sized(eqns):
        return [e.outvars[0].aval.shape for e in eqns if e.outvars[0].aval.shape[0] == n]

    assert sorted(len(e.invars) for e in _eqns(collect, "sort", [])) == [1, 5]
    assert not _eqns(collect, "scan", []) and not _eqns(collect, "while", [])
    assert store_sized(_eqns(collect, "gather", [])) == [(n, cfg.key_lanes)]
    assert max(len(e.invars) for e in _eqns(cut, "sort", [])) <= 3
    assert store_sized(_eqns(cut, "gather", [])) == []
    loops = _eqns(cut, "scan", [])  # a fori_loop of a known length
    assert len(loops) == 1 and loops[0].params["length"] == cfg.key_lanes // RADIX_KEYS
    assert not _eqns(cut, "while", [])
    in_loop = _eqns(loops[0].params["jaxpr"].jaxpr, "sort", [])
    assert len(in_loop) == 1 and len(in_loop[0].invars) == RADIX_KEYS + 1
    assert in_loop[0].params["is_stable"] and in_loop[0].params["num_keys"] == RADIX_KEYS
    assert {v.aval.shape for v in in_loop[0].invars} == {(rows,)}


@pytest.mark.slow
def test_index_block_and_collect_compile_at_the_cells_shape(one_chip):
    """The three programs that hold sorts, at the cell's shapes: the block
    program (tokenise, the five-operand in-block sort, the compaction) in
    about 200 s, the collect (one five-operand sort over the store) in
    about 140 s and the cut (the entries' radix pass and five narrow
    sorts) in about 90 s on the sandbox's CPU — outside tier-1 like every
    whole-program compile.  The collect and the cut with their operands and
    temporaries each stay under a fifth of a chip."""
    progs, cfg, shape, store, _ = _index_programs_and_shapes(one_chip)
    progs.block.lower(shape((cfg.block_lines, cfg.line_width), jnp.uint8),
                      shape((cfg.block_lines,), jnp.int32)).compile()
    n = INDEX_STORE_ROWS
    for lowered in (
        progs.collect.lower(store, shape((), jnp.int32)),
        progs.cut.lower(shape((n, cfg.key_lanes), jnp.uint32), shape((n,), jnp.int32),
                        shape((n,), jnp.int32), shape((), jnp.int32), rows=1 << 20),
    ):
        stats = lowered.compile().memory_analysis()
        assert (stats.argument_size_in_bytes + stats.output_size_in_bytes
                + stats.temp_size_in_bytes) < 3 << 30


# ``joinvisits.batch``: blocks of 4,096 rows of 256 bytes, keys of 128; the
# page table of 30 blocks (120,000 lines) and the visit store at the
# capacity the cell's job ends at (one growth step up from 65,536).
JOIN_PAGE_ROWS, JOIN_STORE_ROWS = 122_880, 131_072


def _join_programs_and_shapes(one_chip):
    from locust_tpu.apps.join import IP_LANES, _build_join_programs

    cfg = EngineConfig(block_lines=4096, line_width=256, key_width=128)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    lanes = cfg.key_width // 4
    return (_build_join_programs(cfg), cfg, shape,
            shape((JOIN_PAGE_ROWS, lanes + 2), jnp.uint32),
            shape((JOIN_STORE_ROWS, lanes + IP_LANES + 2), jnp.uint32))


def test_join_map_programs_compile_at_the_cells_shape(one_chip):
    """The device-side field parser at the cell's block: the delimiter
    scan, the barrel shifters, the date and the numbers by static weights,
    the passed rows' one-operand sort and their row gather — no gather
    along a row's bytes, nothing held in HBM beside the table it writes
    into, a few seconds each to compile.  The store's growth step and the
    result's cut hold no sort."""
    progs, cfg, shape, table, store = _join_programs_and_shapes(one_chip)
    lines, counts = shape((cfg.block_lines, cfg.line_width), jnp.uint8), shape((3,), jnp.int32)
    for lowered, sorts in (
        (progs.map_pages.lower(table, counts, lines, shape((), jnp.int32)), 0),
        (progs.map_visits.lower(store, counts, lines, shape((2,), jnp.int32)), 1),
        (progs.grow.lower(shape((JOIN_STORE_ROWS // 2, store.shape[1]), jnp.uint32),
                          rows=JOIN_STORE_ROWS), 0),
        (progs.cut.lower(shape((JOIN_STORE_ROWS, 9), jnp.uint32), rows=1 << 15), 0),
    ):
        compiled = lowered.compile()
        assert compiled.as_text().count(" sort(") == sorts
        assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


def test_join_probe_sorts_three_operands_and_walks_only_on_a_collision():
    """The probe's one sort over pages and visits has THREE operands (the
    two hash words and the tagged row index, all keys: the chip's compiler
    takes time with the square of them), the regrouping and the order run
    ``_order_rows``' stable three-operand passes, and the only loop of
    unknown length is the collision walk."""
    from locust_tpu.apps.inverted_index import RADIX_KEYS
    from locust_tpu.apps.join import IP_LANES, _build_join_programs

    cfg = EngineConfig(block_lines=8, line_width=64, key_width=32)
    lanes = cfg.key_width // 4
    probe = jax.make_jaxpr(_build_join_programs(cfg).probe)(
        jnp.zeros((16, lanes + 2), jnp.uint32),
        jnp.zeros((32, lanes + IP_LANES + 2), jnp.uint32), jnp.int32(5)).jaxpr
    assert len(_eqns(probe, "while", [])) == 1
    loops = _eqns(probe, "scan", [])
    assert [lp.params["length"] for lp in loops] == [(1 + IP_LANES + 1) // RADIX_KEYS, 2]
    in_loops = []
    for lp in loops:
        (inner,) = _eqns(lp.params["jaxpr"].jaxpr, "sort", [])
        assert len(inner.invars) == RADIX_KEYS + 1 and inner.params["is_stable"]
        in_loops.append(inner)
    top = [e for e in _eqns(probe, "sort", []) if not any(e is i for i in in_loops)]
    assert sorted(len(e.invars) for e in top) == [1, 3]
    assert [e.params["num_keys"] for e in top if len(e.invars) == 3] == [3]


@pytest.mark.slow
def test_join_probe_compiles_at_the_cells_shape(one_chip):
    """The probe at the cell's shapes — 253,952 rows of 32 key lanes through
    one three-operand sort, two key-row gathers and the collision walk, then
    131,072 rows regrouped: about 75 s on the sandbox's CPU, outside tier-1
    like every whole-program compile; with operands and temporaries under
    a tenth of a chip."""
    progs, _, shape, table, store = _join_programs_and_shapes(one_chip)
    stats = progs.probe.lower(table, store, shape((), jnp.int32)).compile().memory_analysis()
    assert (stats.argument_size_in_bytes + stats.output_size_in_bytes
            + stats.temp_size_in_bytes) < 3 << 29


def test_check_kernels_match_chip_smoke():
    """Every kernel chip_smoke.py runs on the chip has a compile case
    above, by name."""
    import chip_smoke

    have = {
        name[len("test_"):].split("_compiles")[0]
        for name in globals()
        if name.startswith("test_") and "_compiles" in name
    }
    assert set(chip_smoke.CHIP_KERNELS) <= have
