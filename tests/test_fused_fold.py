"""sort_mode="fused" — the Pallas map->aggregate megakernel.

The contract is BIT-identity with "hasht": the kernel pre-aggregates each
block in VMEM (ops/pallas/fused_fold.py) and the engine settles
(acc + kernel table + residual) through the UNCHANGED aggregate_exact —
the final table is a pure function of the distinct-key set and the
per-key mod-2^32 totals, so every table, counter, and host pair must
equal the "hasht" fold's byte for byte through every consumer path
(single-device engine, mesh, hierarchical, streaming, checkpoint
resume).  Oracles as everywhere: collections.Counter / helpers
py_wordcount, plus the hasht/hashp2 cross-mode comparison the acceptance
bar names.  All interpret-mode validation here is DIRECT or single-device
— never inside a full CPU mesh program (the check_vma segfault class,
CLAUDE.md; mesh engines run this mode as plain hasht).
"""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from helpers import py_wordcount

from locust_tpu.config import HASHT_FAMILY, SORT_MODES, EngineConfig
from locust_tpu.core import bytes_ops
from locust_tpu.core.kv import KVBatch
from locust_tpu.engine import MapReduceEngine, finalize_host_pairs
from locust_tpu.ops.hash_table import scatter_impl_for
from locust_tpu.ops.map_stage import tokenize_block, wordcount_map
from locust_tpu.ops.pallas.fused_fold import (
    fused_block_preagg,
    fused_engine_eligible,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def corpus_lines(n_lines=700):
    """Reference hamlet when mounted, else the shipped sample corpus."""
    for path in ("/root/reference/hamlet.txt",
                 os.path.join(REPO, "data", "sample_corpus.txt")):
        if os.path.exists(path):
            return open(path, "rb").read().splitlines()[:n_lines]
    pytest.skip("no corpus available")


def _assert_tables_identical(a: KVBatch, b: KVBatch, what=""):
    assert np.array_equal(np.asarray(a.key_lanes), np.asarray(b.key_lanes)), what
    assert np.array_equal(np.asarray(a.values), np.asarray(b.values)), what
    assert np.array_equal(np.asarray(a.valid), np.asarray(b.valid)), what


def _preagg_pairs(tab: KVBatch, resid: KVBatch) -> dict:
    """Union of kernel table + residual rows, duplicate keys re-merged —
    the multiset the settlement fold consumes."""
    return dict(finalize_host_pairs(KVBatch.concat(tab, resid), "sum"))


# --------------------------------------------------------- the primitive


@pytest.mark.parametrize("n_tiles", [1, 3, 4])
def test_preagg_matches_counter_oracle(n_tiles):
    """Kernel table + residual must union to EXACTLY the block's token
    counts, at pow2 and non-pow2 grid sizes (3 tiles = the non-pow2
    case; tiles execute sequentially against the resident table)."""
    cfg = EngineConfig(block_lines=32 * n_tiles, line_width=128,
                       key_width=8, emits_per_line=6, sort_mode="fused")
    rng = np.random.default_rng(n_tiles)
    vocab = [b"w%02d" % i for i in range(40)] + [b"longer-token", b"x"]
    lines = [
        b" ".join(vocab[j] for j in rng.integers(0, len(vocab), 5))
        for _ in range(cfg.block_lines)
    ]
    rows = jnp.asarray(bytes_ops.strings_to_rows(lines, 128))
    tab, resid, ovf, flag = fused_block_preagg(
        rows, cfg, interpret=True, table_slots=1024, resid_rows=32
    )
    assert not bool(flag)
    assert _preagg_pairs(tab, resid) == py_wordcount(lines, 6, 8)
    ref = tokenize_block(rows, cfg)
    assert int(ovf) == int(ref.overflow)  # identical tokenize contract


def test_preagg_table_tile_wraparound():
    """table_slots below the 512-lane tile (t_hi pads up to the f32
    sublane tile): padded slots must decode as invalid, real slots must
    still carry exact counts — the wraparound case of the [t_hi, t_lo]
    layout."""
    cfg = EngineConfig(block_lines=32, line_width=128, key_width=8,
                       emits_per_line=6, sort_mode="fused")
    lines = [b"aa bb cc dd ee", b"aa bb cc", b"ff gg"] * 10 + [b""] * 2
    rows = jnp.asarray(bytes_ops.strings_to_rows(lines, 128))
    tab, resid, _, flag = fused_block_preagg(
        rows, cfg, interpret=True, table_slots=512, resid_rows=32
    )
    assert not bool(flag)
    assert tab.size == 8 * 512  # hi axis padded 1 -> 8 sublanes
    # Padded region (slot ids >= 512 are unaddressable) stays invalid.
    assert not np.asarray(tab.valid)[512:].any()
    assert _preagg_pairs(tab, resid) == py_wordcount(lines, 6, 8)


def test_preagg_residual_carries_stranded_keys_exactly():
    """A tiny kernel table strands keys by probe exhaustion; the
    residual stream must carry every stranded key's tile counts so the
    union stays Counter-exact (nothing lost, the module invariant)."""
    cfg = EngineConfig(block_lines=64, line_width=128, key_width=8,
                       emits_per_line=8, sort_mode="fused")
    rng = np.random.default_rng(7)
    vocab = [b"k%03d" % i for i in range(150)]
    lines = [
        b" ".join(vocab[j] for j in rng.integers(0, 150, 6))
        for _ in range(64)
    ]
    rows = jnp.asarray(bytes_ops.strings_to_rows(lines, 128))
    tab, resid, _, flag = fused_block_preagg(
        rows, cfg, interpret=True, table_slots=64, resid_rows=256
    )
    assert not bool(flag)
    assert int(np.asarray(resid.valid).sum()) > 0  # stranding happened
    assert _preagg_pairs(tab, resid) == py_wordcount(lines, 8, 8)


def test_preagg_residual_overflow_flag_is_sticky():
    """More stranded leaders than the residual buffer holds must raise
    the flag (the engine's signal to re-fold the block stock)."""
    cfg = EngineConfig(block_lines=32, line_width=128, key_width=8,
                       emits_per_line=8, sort_mode="fused")
    rng = np.random.default_rng(11)
    vocab = [b"k%03d" % i for i in range(200)]
    lines = [
        b" ".join(vocab[j] for j in rng.integers(0, 200, 7))
        for _ in range(32)
    ]
    rows = jnp.asarray(bytes_ops.strings_to_rows(lines, 128))
    _, _, _, flag = fused_block_preagg(
        rows, cfg, interpret=True, table_slots=16, resid_rows=8
    )
    assert bool(flag)


def test_preagg_shape_validation():
    cfg = EngineConfig(sort_mode="fused")
    with pytest.raises(ValueError, match="multiple of 32"):
        fused_block_preagg(jnp.zeros((48, 128), jnp.uint8), cfg,
                           interpret=True)
    with pytest.raises(ValueError, match="multiple of 128"):
        fused_block_preagg(jnp.zeros((32, 64), jnp.uint8), cfg,
                           interpret=True)
    with pytest.raises(ValueError, match="power of two"):
        fused_block_preagg(jnp.zeros((32, 128), jnp.uint8), cfg,
                           interpret=True, table_slots=768)


# --------------------------------------------------- engine eligibility


def test_engine_eligibility_gates():
    """The kernel engages only on the wordcount map + sum/count combine
    + aligned shapes; everything else degrades to the hasht-identical
    path — decided statically, logged once, never inside traced code."""
    ok, _ = fused_engine_eligible(
        EngineConfig(block_lines=64, sort_mode="fused"), wordcount_map,
        "sum",
    )
    assert ok
    ok, why = fused_engine_eligible(
        EngineConfig(block_lines=48, sort_mode="fused"), wordcount_map,
        "sum",
    )
    assert not ok and "multiple" in why

    def other_map(lines, cfg):
        return wordcount_map(lines, cfg)

    ok, why = fused_engine_eligible(
        EngineConfig(block_lines=64, sort_mode="fused"), other_map, "sum"
    )
    assert not ok and "tokenizer" in why
    ok, why = fused_engine_eligible(
        EngineConfig(block_lines=64, sort_mode="fused"), wordcount_map,
        "min",
    )
    assert not ok and "kernel spelling" in why
    # Engine on an ineligible shape still runs (hasht-identical path).
    eng = MapReduceEngine(
        EngineConfig(block_lines=48, line_width=64, sort_mode="fused")
    )
    assert not eng._fused_kernel_on
    res = eng.run_lines([b"a b a", b"c"])
    assert dict(res.to_host_pairs()) == {b"a": 2, b"b": 1, b"c": 1}


def test_engine_interpret_cap_falls_back(monkeypatch):
    """Off-TPU, blocks above FUSED_INTERPRET_MAX_LINES must not trace
    the interpret kernel (the per-grid-step re-trace cost class); the
    fold stays hasht-exact."""
    import locust_tpu.config as config_mod

    monkeypatch.setattr(config_mod, "FUSED_INTERPRET_MAX_LINES", 32)
    cfg = EngineConfig(block_lines=64, sort_mode="fused")
    eng = MapReduceEngine(cfg)
    assert not eng._fused_kernel_on
    res = eng.run_lines([b"x y x"] * 8)
    assert dict(res.to_host_pairs()) == {b"x": 16, b"y": 8}


def test_count_combine_engages_kernel():
    """combine="count" lowers to emit-1 + sum — exactly the kernel's
    count plane; the raw wordcount map identity must survive the
    normalize_combine wrapper."""
    cfg = EngineConfig(block_lines=32, line_width=128, key_width=8,
                       emits_per_line=6, sort_mode="fused")
    eng = MapReduceEngine(cfg, combine="count")
    assert eng._fused_kernel_on
    res = eng.run_lines([b"a b a", b"b b"] * 4)
    assert dict(res.to_host_pairs()) == {b"a": 8, b"b": 12}


# ------------------------------------------ engine / ladder parity


def test_engine_fused_bit_identical_to_hasht_and_oracle():
    """Single device: fused equals the Python oracle, produces the
    IDENTICAL device table as hasht (same slot layout — the settlement
    IS hasht's fold over the same key set and totals), and identical
    finalized pairs as hashp2 (the acceptance bar)."""
    lines = corpus_lines(200)
    res = {}
    for mode in ("fused", "hasht", "hashp2"):
        eng = MapReduceEngine(
            EngineConfig(block_lines=64, sort_mode=mode, key_width=16,
                         emits_per_line=8)
        )
        if mode == "fused":
            assert eng._fused_kernel_on
        res[mode] = eng.run_lines(lines)
    want = sorted(py_wordcount(lines, 8, 16).items())
    assert res["fused"].to_host_pairs() == want
    assert res["fused"].to_host_pairs() == res["hashp2"].to_host_pairs()
    _assert_tables_identical(res["fused"].table, res["hasht"].table)
    assert res["fused"].num_segments == res["hasht"].num_segments
    assert res["fused"].overflow_tokens == res["hasht"].overflow_tokens


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_fused_hasht_parity_property(seed):
    """Random corpora: tables, distinct counts and overflow must stay
    BIT-identical between fused and hasht (the settlement-function
    argument, exercised across shapes incl. multi-block folds)."""
    rng = np.random.default_rng(seed)
    vocab = [b"w%d" % i for i in range(120)] + [b"x" * 30, b"hy-phen"]
    lines = [
        bytes(rng.choice([b" ", b", ", b"; "])).join(
            vocab[j] for j in rng.integers(0, len(vocab), rng.integers(0, 9))
        )
        for _ in range(200)
    ]
    cfg_kw = dict(block_lines=64, key_width=8, emits_per_line=6,
                  table_size=4096)
    a = MapReduceEngine(
        EngineConfig(sort_mode="fused", **cfg_kw)
    ).run_lines(lines)
    b = MapReduceEngine(
        EngineConfig(sort_mode="hasht", **cfg_kw)
    ).run_lines(lines)
    _assert_tables_identical(a.table, b.table, f"seed {seed}")
    assert a.num_segments == b.num_segments
    assert a.overflow_tokens == b.overflow_tokens
    assert dict(a.to_host_pairs()) == dict(
        py_wordcount([ln[:128] for ln in lines], 6, 8)
    )


def test_fused_settlement_residual_ladder_parity():
    """Capacity pressure drives the SETTLEMENT off its fast path
    (probe exhaustion -> place_residual): fused and hasht must walk the
    identical ladder to identical slot layouts — the stranded key set
    and the per-key totals are the same, so placement is too."""
    rng = np.random.default_rng(3)
    vocab = [b"key%d" % i for i in range(60)]
    lines = [
        b" ".join(vocab[j] for j in rng.integers(0, 60, 6))
        for _ in range(96)
    ]
    cfg_kw = dict(block_lines=96, key_width=8, emits_per_line=6,
                  table_size=64)
    a = MapReduceEngine(
        EngineConfig(sort_mode="fused", **cfg_kw)
    ).run_lines(lines)
    b = MapReduceEngine(
        EngineConfig(sort_mode="hasht", **cfg_kw)
    ).run_lines(lines)
    _assert_tables_identical(a.table, b.table)
    assert a.num_segments == b.num_segments
    assert a.truncated == b.truncated


def test_fused_truncation_parity_stays_loud():
    """distinct > capacity: both modes must report the same truncation
    and the same (conservative) distinct count."""
    vocab = [b"t%03d" % i for i in range(300)]
    lines = [b" ".join(vocab[i:i + 6]) for i in range(0, 294, 2)]
    cfg_kw = dict(block_lines=64, key_width=8, emits_per_line=6,
                  table_size=128)
    a = MapReduceEngine(
        EngineConfig(sort_mode="fused", **cfg_kw)
    ).run_lines(lines)
    b = MapReduceEngine(
        EngineConfig(sort_mode="hasht", **cfg_kw)
    ).run_lines(lines)
    assert a.truncated and b.truncated
    assert a.num_segments == b.num_segments
    _assert_tables_identical(a.table, b.table)


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 (virtual) devices")
def test_mesh_fused_oracle_exact_no_kernel_inside_mesh():
    """8-device all-to-all shuffle: "fused" runs as plain hasht inside
    mesh programs (the interpret kernel must NEVER trace inside a full
    CPU mesh program — CLAUDE.md segfault class) and stays oracle-exact
    and pair-identical to hasht/hashp2."""
    from locust_tpu.parallel import DistributedMapReduce, make_mesh

    lines = [ln[:64] for ln in corpus_lines(160)]
    got = {}
    for mode in ("fused", "hasht", "hashp2"):
        cfg = EngineConfig(block_lines=32, line_width=64, emits_per_line=12,
                           sort_mode=mode)
        dmr = DistributedMapReduce(make_mesh(), cfg)
        rows = bytes_ops.strings_to_rows(lines, 64)
        got[mode] = dmr.run(rows).to_host_pairs()
    assert got["fused"] == sorted(py_wordcount(lines, 12).items())
    assert got["fused"] == got["hasht"] == got["hashp2"]


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 (virtual) devices")
def test_hierarchical_fused_oracle_exact():
    """[2 slices x 4 devices]: the cross-slice combine dispatches fused
    through the hasht family reduce_into."""
    from locust_tpu.parallel.hierarchical import HierarchicalMapReduce
    from locust_tpu.parallel.mesh import make_mesh_2d

    lines = [ln[:64] for ln in corpus_lines(120)]
    got = {}
    for mode in ("fused", "hashp2"):
        cfg = EngineConfig(block_lines=16, line_width=64, emits_per_line=12,
                           sort_mode=mode)
        dmr = HierarchicalMapReduce(make_mesh_2d(2), cfg)
        rows = bytes_ops.strings_to_rows(lines, 64)
        got[mode] = dmr.run(rows).to_host_pairs()
    assert got["fused"] == sorted(py_wordcount(lines, 12).items())
    assert got["fused"] == got["hashp2"]


def test_stream_fused_oracle_exact_with_donated_fold(tmp_path):
    """Bounded-memory streaming ingest under the fused fold: the donated
    accumulator + staging ring + the kernel must compose exactly."""
    from locust_tpu.io.loader import StreamingCorpus

    lines = corpus_lines(150)
    p = tmp_path / "c.txt"
    p.write_bytes(b"\n".join(lines) + b"\n")
    cfg = EngineConfig(block_lines=64, sort_mode="fused", key_width=8,
                       emits_per_line=8)
    eng = MapReduceEngine(cfg)
    assert eng._fused_kernel_on
    res = eng.run_stream(
        StreamingCorpus(str(p), cfg.line_width, cfg.block_lines)
    )
    assert dict(res.to_host_pairs()) == py_wordcount(lines, 8, 8)


def test_checkpoint_resume_fused_round_trips(tmp_path):
    """Crash mid-run, resume: fused's slot-ordered snapshots restore and
    finish exact — the hasht-mxu bar, on the kernel path."""
    cfg = EngineConfig(block_lines=32, sort_mode="fused", key_width=8,
                       emits_per_line=8)
    lines = [b"to be or not to be", b"that is the question",
             b"the rest is silence"] * 24
    eng = MapReduceEngine(cfg)
    rows = eng.rows_from_lines(lines)
    ckpt = str(tmp_path / "ckpt")

    calls = {"n": 0}
    real_fold = eng._fold_block

    def dying_fold(acc, blk):
        if calls["n"] >= 2:
            raise RuntimeError("injected crash")
        calls["n"] += 1
        return real_fold(acc, blk)

    eng._fold_block = dying_fold
    with pytest.raises(RuntimeError, match="injected crash"):
        eng.run_checkpointed(rows, ckpt, every=1)

    eng2 = MapReduceEngine(cfg)
    res = eng2.run_checkpointed(rows, ckpt, every=1)
    assert dict(res.to_host_pairs()) == py_wordcount(lines, 8, 8)


def test_breaker_failover_uses_stock_fold_and_stays_exact(tmp_path):
    """Mid-job breaker failover with the fused kernel ON: the CPU
    fallback dispatch must run the kernel-free stock fold (at failover
    trace time jax.default_backend() is still the dead primary, so the
    in-fold interpret switch cannot see the migration — re-tracing the
    kernel there would abort a job with a healthy fallback) and finish
    oracle-exact from the last checkpoint."""
    from locust_tpu.backend import CircuitBreaker
    from locust_tpu.utils import faultplan

    cfg = EngineConfig(block_lines=32, line_width=128, key_width=8,
                       emits_per_line=6, sort_mode="fused")
    eng = MapReduceEngine(cfg)
    assert eng._fused_kernel_on
    assert eng._fold_block_fallback is not eng._fold_block
    lines = [b"aaa bbb ccc", b"bbb ccc ddd"] * 64  # 4 blocks
    rows = eng.rows_from_lines(lines)
    want = dict(eng.run(rows).to_host_pairs())

    fallback_calls = {"n": 0}
    real_fallback = eng._fold_block_fallback

    def counting_fallback(acc, blk):
        fallback_calls["n"] += 1
        return real_fallback(acc, blk)

    eng._fold_block_fallback = counting_fallback
    br = CircuitBreaker(threshold=2, cooldown_s=30.0)  # stays open
    p = faultplan.FaultPlan(
        [{"site": "backend.dispatch", "action": "error", "times": 3}],
        seed=7,
    )
    with faultplan.active_plan(p):
        res = eng.run_checkpointed(
            rows, str(tmp_path / "ck"), every=1, breaker=br
        )
    assert dict(res.to_host_pairs()) == want
    assert br.stats()["trips"] == 1
    assert fallback_calls["n"] > 0  # the failover ran the stock fold


def test_debug_checks_accept_fused_tables(monkeypatch):
    """validate_batch(expect_compact=False) extends to the whole hasht
    family — fused tables are slot-ordered, not a layout violation."""
    monkeypatch.setenv("LOCUST_DEBUG_CHECKS", "1")
    eng = MapReduceEngine(
        EngineConfig(block_lines=32, line_width=128, key_width=8,
                     emits_per_line=6, sort_mode="fused")
    )
    res = eng.run_lines([b"a b a", b"c d"])
    assert dict(res.to_host_pairs()) == {b"a": 2, b"b": 1, b"c": 1, b"d": 1}


# --------------------------------------- lowering / shard_map / registry


def test_fused_kernel_lowers_to_tpu_mosaic():
    """The pre-hardware gate: the REAL (interpret=False) kernel must
    lower through the Mosaic pipeline for the TPU target off-hardware —
    this catch already paid for itself in-PR (integer reductions and
    f32->u32 converts have no lowering in this jaxlib's Mosaic; the
    kernel now spells both in f32/int32)."""
    from jax import export as jax_export

    cfg = EngineConfig(block_lines=64, sort_mode="fused", key_width=16,
                       emits_per_line=8)
    f = jax.jit(functools.partial(fused_block_preagg, cfg=cfg,
                                  interpret=False))
    shape = jax.ShapeDtypeStruct((64, cfg.line_width), jnp.uint8)
    exp = jax_export.export(f, platforms=["tpu"])(shape)
    m = exp.mlir_module()
    assert len(m) > 0
    assert "tpu_custom_call" in m  # the Mosaic kernel, not interpret HLO


def test_fused_engine_scan_lowers_for_tpu():
    """The whole fused fold (kernel + settlement ladder inside lax.scan)
    must export for the TPU target — the same gate hasht-mxu gets."""
    from jax import export as jax_export

    cfg = EngineConfig(block_lines=64, sort_mode="fused", key_width=16,
                       emits_per_line=8)
    eng = MapReduceEngine(cfg)
    shape = jax.ShapeDtypeStruct((2, 64, cfg.line_width), jnp.uint8)
    exp = jax_export.export(eng._scan_blocks, platforms=["tpu"])(shape)
    assert len(exp.mlir_module()) > 0


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 (virtual) devices")
def test_fused_kernel_traces_under_shard_map():
    """The shard_map traceability a future TPU mesh integration relies
    on: a direct small interpret-mode kernel call under shard_map(check_vma=False) must
    trace, run per-shard, and pre-aggregate exactly.  (The
    full-mesh-program interpret combination is deliberately NOT
    exercised: it is the CPU-compiler segfault class.)"""
    from jax.sharding import Mesh, PartitionSpec as P

    cfg = EngineConfig(block_lines=32, line_width=128, key_width=8,
                       emits_per_line=4, sort_mode="fused")
    per = [
        [b"s%d a b" % s, b"s%d a" % s] + [b""] * 30
        for s in range(8)
    ]
    rows = np.concatenate(
        [bytes_ops.strings_to_rows(p, 128) for p in per]
    )
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(8), ("d",))

    def body(blk):
        tab, resid, ovf, flag = fused_block_preagg(
            blk, cfg, interpret=True, table_slots=512, resid_rows=16
        )
        return tab.values, tab.key_lanes, tab.valid

    f = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P("d"),), out_specs=(P("d"), P("d"), P("d")),
        check_vma=False,
    ))
    values, lanes, valid = f(jnp.asarray(rows))
    n_slots = values.shape[0] // 8
    for s in range(8):
        tab = KVBatch(
            key_lanes=lanes[s * n_slots:(s + 1) * n_slots],
            values=values[s * n_slots:(s + 1) * n_slots],
            valid=valid[s * n_slots:(s + 1) * n_slots],
        )
        got = dict(finalize_host_pairs(tab, "sum"))
        assert got == py_wordcount(per[s], 4, 8), f"shard {s}"


def test_fused_registered_in_mode_tables():
    """Two-sided registry hygiene: the mode is in SORT_MODES (CLI choices
    + config validation) AND in HASHT_FAMILY (every family site), and
    its XLA settlement keeps the hasht scatter spelling."""
    assert "fused" in SORT_MODES and "fused" in HASHT_FAMILY
    assert scatter_impl_for("fused") == "xla"
    from locust_tpu.config import (
        FUSED_RESIDUAL_ROWS,
        FUSED_TABLE_SLOTS,
        FUSED_TILE_LINES,
        fused_grid,
    )

    t_hi, t_lo = fused_grid()
    assert t_hi * t_lo == FUSED_TABLE_SLOTS
    assert t_lo & (t_lo - 1) == 0  # shift+mask split needs pow2
    assert FUSED_TILE_LINES % 32 == 0
    assert FUSED_RESIDUAL_ROWS & (FUSED_RESIDUAL_ROWS - 1) == 0
    # ONE decider for the physical plane layout: the kernel consumes
    # config.fused_table_layout.
    import locust_tpu.ops.pallas.fused_fold as ff
    from locust_tpu.config import FUSED_SUBLANE, fused_table_layout

    assert ff.fused_table_layout is fused_table_layout
    p_hi, p_lo = fused_table_layout()
    assert p_lo == t_lo and p_hi * p_lo >= FUSED_TABLE_SLOTS
    assert p_hi % FUSED_SUBLANE == 0 or p_hi == FUSED_SUBLANE


# ------------------------------------------------ megakernel v2: stream


def _stream_cfg(**kw):
    kw.setdefault("block_lines", 64)
    kw.setdefault("line_width", 128)
    kw.setdefault("key_width", 8)
    kw.setdefault("emits_per_line", 8)
    kw.setdefault("sort_mode", "fused")
    return EngineConfig(**kw)


def test_fused_stream_seg_blocks_clamps():
    """The segment-size clamp (config.fused_stream_seg_blocks): the
    exactness bound (segment emits < 2^24 for the f32 count planes), the
    off-TPU interpret cap (segment lines <= FUSED_INTERPRET_MAX_LINES —
    the interpreter re-traces per grid step), and the >=1 floor."""
    from locust_tpu.config import (
        FUSED_INTERPRET_MAX_LINES,
        FUSED_STREAM_BLOCKS,
        fused_stream_seg_blocks,
    )

    # Small shapes: the configured default survives intact on TPU.
    assert fused_stream_seg_blocks(512, 64, True) == FUSED_STREAM_BLOCKS
    # Exactness cap: emits_per_block so large only 1 block fits 2^24.
    assert fused_stream_seg_blocks((1 << 24) - 1, 64, True) == 1
    assert fused_stream_seg_blocks(1 << 23, 64, True) == 1
    # Off-TPU interpret cap: block_lines at the interpret max -> seg 1.
    assert fused_stream_seg_blocks(512, FUSED_INTERPRET_MAX_LINES, False) == 1
    # Off-TPU small blocks keep the default (the cap is generous).
    assert fused_stream_seg_blocks(512, 64, False) == FUSED_STREAM_BLOCKS
    # The floor: never 0, whatever the shape.
    assert fused_stream_seg_blocks(1 << 30, 1 << 20, False) == 1


def test_stream_fused_multi_segment_identical_to_hasht():
    """The persistent streaming kernel across FULL and PARTIAL segments
    must be BIT-identical to hasht streaming over the same blocks — the
    v2 acceptance bar.  20 blocks at seg=8 exercises two full segments
    plus a 4-block trailing partial (zero-padded, the _blocks padding
    contract)."""
    lines = corpus_lines(600)
    f_eng = MapReduceEngine(_stream_cfg(block_lines=32))
    h_eng = MapReduceEngine(_stream_cfg(block_lines=32, sort_mode="hasht"))
    assert f_eng._fold_segment is not None  # streaming formulation armed
    bl = f_eng.cfg.block_lines

    def blocks(eng):
        rows = eng.rows_from_lines(lines)
        for i in range(0, rows.shape[0], bl):
            yield rows[i:i + bl]

    f = f_eng.run_stream(blocks(f_eng))
    h = h_eng.run_stream(blocks(h_eng))
    _assert_tables_identical(f.table, h.table, "stream fused vs hasht")
    assert f.num_segments == h.num_segments
    assert f.overflow_tokens == h.overflow_tokens
    assert dict(f.to_host_pairs()) == py_wordcount(lines, 8, 8)
    # Result + stats surface the formulation (no silent anything).
    assert f.fused_kernel == "stream" and not f.fused_demoted
    fs = f.stream["fused"]
    assert fs["formulation"] == "stream" and fs["seg_blocks"] > 1
    assert f.stream["blocks"] > fs["seg_blocks"]  # genuinely multi-segment
    assert f.stream["blocks"] % fs["seg_blocks"] != 0  # partial trailing seg
    assert fs["segments"] == -(-f.stream["blocks"] // fs["seg_blocks"])
    assert h.fused_kernel is None and not h.fused_demoted


def test_stream_fused_crash_resume_byte_identical(tmp_path):
    """Crash mid-stream under the persistent kernel, resume from the
    snapshot: the restored table re-enters the resident kernel (the
    _load_state copy feeds the donated segment fold) and the final
    table is BIT-identical to hasht streaming the whole corpus — even
    though the resume REGROUPS the remaining blocks into fresh segments
    (the fold is a pure function of the line multiset)."""
    from locust_tpu.io.loader import StreamingCorpus

    lines = corpus_lines(600)  # 19 blocks at bl=32: 3 segments at seg=8
    p = tmp_path / "c.txt"
    p.write_bytes(b"\n".join(lines) + b"\n")
    cfg = _stream_cfg(block_lines=32)
    sc = lambda: StreamingCorpus(str(p), cfg.line_width, cfg.block_lines)  # noqa: E731
    want = MapReduceEngine(
        _stream_cfg(block_lines=32, sort_mode="hasht")
    ).run_stream(sc())

    ckpt = str(tmp_path / "ckpt")
    fp = sc().fingerprint()
    eng = MapReduceEngine(cfg)
    assert eng._fold_segment is not None
    real_seg = eng._fold_segment
    calls = {"n": 0}

    def dying_segment(acc, seg_lines):
        if calls["n"] >= 1:
            raise RuntimeError("injected stream crash")
        calls["n"] += 1
        return real_seg(acc, seg_lines)

    # every=3 with seg=8: the mark cadence is segment-granular, so the
    # crash after one dispatched segment leaves a mid-stream snapshot.
    eng._fold_segment = dying_segment
    with pytest.raises(RuntimeError, match="injected stream crash"):
        eng.run_stream(sc(), checkpoint_dir=ckpt, every=3, fingerprint=fp)
    eng._fold_segment = real_seg
    res = eng.run_stream(sc(), checkpoint_dir=ckpt, every=3, fingerprint=fp)
    _assert_tables_identical(res.table, want.table, "crash-resume stream")
    assert res.num_segments == want.num_segments
    assert res.overflow_tokens == want.overflow_tokens
    assert res.fused_kernel == "stream"
    # A further resume on the finished snapshot folds nothing and still
    # reports the restored table (the exhausted-iterator contract).
    res2 = eng.run_stream(iter([]), checkpoint_dir=ckpt, every=3,
                          fingerprint=fp)
    _assert_tables_identical(res2.table, want.table, "no-op resume")


def test_breaker_failover_with_streaming_kernel_active(tmp_path):
    """Breaker trip + mid-job TPU->CPU failover on an engine whose
    PERSISTENT STREAMING formulation is armed: the fallback dispatch
    stays kernel-free (stock fold) and the table stays oracle-exact —
    then the SAME engine's run_stream still takes the segment kernel
    path, unpoisoned by the failover."""
    from locust_tpu.backend import CircuitBreaker
    from locust_tpu.utils import faultplan

    cfg = _stream_cfg(block_lines=32, emits_per_line=6)
    eng = MapReduceEngine(cfg)
    assert eng._fused_kernel_on and eng._fold_segment is not None
    lines = [b"aaa bbb ccc", b"bbb ccc ddd"] * 64  # 4 blocks
    rows = eng.rows_from_lines(lines)
    want = dict(eng.run(rows).to_host_pairs())

    br = CircuitBreaker(threshold=2, cooldown_s=30.0)  # stays open
    p = faultplan.FaultPlan(
        [{"site": "backend.dispatch", "action": "error", "times": 3}],
        seed=11,
    )
    with faultplan.active_plan(p):
        res = eng.run_checkpointed(
            rows, str(tmp_path / "ck"), every=1, breaker=br
        )
    assert dict(res.to_host_pairs()) == want
    assert br.stats()["trips"] == 1
    bl = cfg.block_lines
    streamed = eng.run_stream(
        rows[i:i + bl] for i in range(0, rows.shape[0], bl)
    )
    assert dict(streamed.to_host_pairs()) == want
    assert streamed.fused_kernel == "stream"


# -------------------------------------------- megakernel v2: mesh-native


def test_fused_mesh_eligible_gates_backend_and_capacity(monkeypatch):
    """fused_mesh_eligible: off-TPU is a hard no (the interpret kernel
    never traces inside a CPU mesh program — the check_vma segfault
    class), and on TPU the kernel's table+residual output must fit the
    shard's emit capacity (the local combiner's fixed-size contract)."""
    from locust_tpu.ops.pallas import fused_fold as ff

    cfg = _stream_cfg(block_lines=32, emits_per_line=4)
    ok, why = ff.fused_mesh_eligible(cfg, wordcount_map, "count")
    assert not ok and "TPU-only" in why

    monkeypatch.setattr(ff.jax, "default_backend", lambda: "tpu")
    # emits_per_block (32*4=128) << table planes: capacity refusal.
    ok, why = ff.fused_mesh_eligible(cfg, wordcount_map, "count")
    assert not ok and "emit capacity" in why
    # Enough emit capacity: eligible on (mocked) TPU.
    big = _stream_cfg(block_lines=1024, emits_per_line=9)
    ok, why = ff.fused_mesh_eligible(big, wordcount_map, "count")
    assert ok, why
    # Base ineligibility (non-wordcount spine) propagates unchanged.
    ok, why = ff.fused_mesh_eligible(
        big, lambda lines, cfg: None, "count"
    )
    assert not ok


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 (virtual) devices")
def test_mesh_fused_demotion_is_explicit_not_silent(caplog):
    """The PR 13 silent demotion is gone: a CPU mesh engine under
    sort_mode="fused" logs the reason ONCE at construction and the
    result carries fused_demoted=True / fused_kernel=None — while a
    hasht mesh engine reports neither."""
    import logging

    from locust_tpu.parallel import DistributedMapReduce, make_mesh
    from locust_tpu.parallel.hierarchical import HierarchicalMapReduce
    from locust_tpu.parallel.mesh import make_mesh_2d

    lines = [ln[:64] for ln in corpus_lines(160)]
    rows = bytes_ops.strings_to_rows(lines, 64)
    with caplog.at_level(logging.INFO, logger="locust_tpu"):
        dmr = DistributedMapReduce(
            make_mesh(),
            EngineConfig(block_lines=32, line_width=64, emits_per_line=12,
                         sort_mode="fused"),
        )
    assert dmr.fused_demoted
    assert sum(
        "kernel not engaged" in r.message for r in caplog.records
    ) == 1  # one-time construction log, engine named
    res = dmr.run(rows)
    assert res.fused_demoted and res.fused_kernel is None
    assert res.to_host_pairs() == sorted(py_wordcount(lines, 12).items())

    h = HierarchicalMapReduce(
        make_mesh_2d(2),
        EngineConfig(block_lines=16, line_width=64, emits_per_line=12,
                     sort_mode="fused"),
    )
    assert h.fused_demoted
    hres = h.run(rows)
    assert hres.fused_demoted and hres.fused_kernel is None

    hasht = DistributedMapReduce(
        make_mesh(),
        EngineConfig(block_lines=32, line_width=64, emits_per_line=12,
                     sort_mode="hasht"),
    )
    assert not hasht.fused_demoted
    hr = hasht.run(rows)
    assert not hr.fused_demoted and hr.fused_kernel is None
    assert res.to_host_pairs() == hr.to_host_pairs()
