"""Fused map->aggregate Pallas megakernel: tokenize + hash + table-update
in one VMEM-resident kernel.

The hot path's largest remaining HBM round-trip (ROADMAP item 5) is the
``[lines, emits, key_width]`` token tensor materialized between the map
stage (ops/map_stage.py) and the hash-table fold (ops/hash_table.py) —
the same global-memory staging the reference does between ``kernMap`` and
its Process sort (reference MapReduce/src/main.cu:392-415).  This kernel
DELETES that intermediate rather than accelerating it (the FlashAttention
keep-it-resident argument applied to the map->process boundary): per
line-tile grid step it

  1. tokenizes the ``[FUSED_TILE_LINES, line_width]`` uint8 tile in VMEM,
     reusing the mask / prefix-sum / masked-reduction formulation of
     ops/pallas/tokenize.py byte for byte (same key bytes, same validity,
     same overflow count);
  2. collapses the tile's duplicate keys EXACTLY with a Gram-matrix
     equality (``d2 = |a|^2 + |b|^2 - 2 a.b == 0`` over the key byte
     planes — one [n, K] x [K, n] MXU contraction; every operand is an
     integer < 2^24, so f32 arithmetic is exact and equality is exact);
  3. hashes the surviving tile leaders with the SAME ``hash_pair``
     formulation the hasht family probes by (fmix32 salted folds over
     big-endian uint32 lanes, core/packing.py);
  4. walks the hasht probe sequence ``slot_p = (h1 + p*(h2|1)) % S`` over
     a ``[t_hi, t_lo]``-tiled accumulator table kept RESIDENT in VMEM
     across grid steps (config.fused_grid / FUSED_TABLE_SLOTS): empty-slot
     key writes, full-key verify, and count combine are all spelled as
     one-hot f32 contractions — the PR 4 limb-decomposition MXU trick,
     simplified to a single count plane because wordcount emits are 1 and
     a block's count total stays < 2^24 (the engine guards this bound);
  5. streams tile leaders the probe rounds strand through a bounded
     per-tile residual buffer (one-hot placement by prefix-sum rank);
     residual overflow raises a sticky flag and the ENGINE re-folds the
     whole block through the stock hasht path — exact either way.

Exactness story (the same shape as hash_table.py's):

* A row resolves into a slot ONLY on a full-key byte compare against the
  stored planes, so hash collisions can never merge distinct keys.
* Two distinct keys writing the same empty slot in one round produce a
  byte-plane SUM ("chimera") — the analog of hasht's unspecified
  duplicate-index row write.  Chimera slots match no writer (the sums
  differ from either key, and a plane > 255 can equal no key byte; all
  arithmetic is f32-exact, no bf16 rounding anywhere), so both writers
  keep probing or strand to the residual; a chimera that happens to equal
  a THIRD key's bytes simply becomes that key's slot.
* Everything not in the table comes back out: stranded leaders exit via
  the residual stream, and a residual-buffer overflow flags the block for
  the engine's stock re-fold.  No path can lose a row silently.

Bit-identity with "hasht" (tests/test_fused_fold.py): the engine settles
``concat(acc, kernel_table, residual)`` through the UNCHANGED
``hash_table.aggregate_exact``.  hasht's final table is a pure function
of the distinct-key set (each key's (h1, h2) drives the same probe
sequence regardless of row multiplicity; claim scatter-min, full-lane
verify and the commutative combines are all order- and
multiplicity-independent) plus the per-key mod-2^32 totals — and the
kernel preserves exactly that: same distinct keys, same totals (every
valid emit lands in exactly one leader count; leader counts land in
exactly one table slot or residual row per tile; the settlement re-merges
per-tile duplicates like any other duplicate key rows).  The one
divergence window: the settlement's exactness LADDER counts stranded
ROWS, and this mode strands one pre-aggregated row per key where hasht
strands every raw row — so in the pathological > RESIDUAL_CAP-stranded-
rows regime hasht takes the full-sort rebuild while fused may still take
the (cheaper) residual branch.  Both stay exact (identical host pairs);
only the slot LAYOUT can differ there, and reaching it needs > 4096
probe-exhausted raw rows in a single fold.

Megakernel v2 adds two more FORMULATIONS of this same kernel — not new
kernels (both call :func:`fused_block_preagg` unchanged, so the
bit-identity argument above carries over verbatim):

* **Persistent streaming** (engine.run_stream): the kernel already keeps
  its table planes at a constant index_map — VMEM-resident across ALL
  grid steps — and accepts any tile-multiple line count, so the engine
  feeds it SEGMENTS of ``config.FUSED_STREAM_BLOCKS`` staged blocks per
  launch.  Pallas double-buffers the per-tile line DMA automatically
  (indexed input BlockSpec), the bounded residual drains per tile as
  before, and the acc->settle->acc HBM round-trip plus the table flush
  amortize by the segment length.
  Exactness: the per-SEGMENT emit budget must stay < 2^24 for the f32
  count planes — :func:`config.fused_stream_seg_blocks` clamps the
  segment to that bound (and to the interpret-cost cap off-TPU).
* **Mesh-native** (parallel/shuffle.py, parallel/hierarchical.py): the
  kernel runs per shard UNDER shard_map, replacing map+local-combine in
  the shuffle step body; the per-shard table+residual settle through the
  UNCHANGED per-shard merge + hierarchical combine.  TPU-only
  (:func:`fused_mesh_eligible`): off-TPU the mesh engines demote to
  plain hasht with an explicit one-time log and a ``fused_demoted``
  result field — the interpret kernel NEVER runs inside a CPU mesh
  program (the check_vma segfault class, CLAUDE.md).

Validation off-TPU uses interpret mode strictly under the pinned
direct-test pattern — NEVER inside a full CPU mesh program (the
check_vma segfault class, CLAUDE.md); the mesh engines run this mode as
plain hasht, and ``config.FUSED_INTERPRET_MAX_LINES`` bounds the
interpreter's per-grid-step re-trace on the single-device path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from locust_tpu.config import (
    DELIMITERS,
    FUSED_RESID_PAD,
    FUSED_RESIDUAL_ROWS,
    FUSED_TABLE_SLOTS,
    FUSED_TILE_LINES,
    HASHT_PROBES,
    EngineConfig,
    # The physical [t_hi, t_lo] plane layout is decided ONCE in config.
    fused_table_layout,
)
from locust_tpu.core.kv import KVBatch

# Residual row layout: key bytes [0..K-1], count [K], valid flag [K+1],
# zero padding out to K + RESID_PAD lanes.  Kept narrow deliberately:
# residual rows DO cross HBM.
RESID_PAD = FUSED_RESID_PAD

FUSED_VMEM_LIMIT_BYTES = 64 * 1024 * 1024

# Every contraction in the kernel carries integers < 2^24 in f32, and the
# exactness story needs each one exact.  Mosaic's DEFAULT precision feeds
# the MXU bf16 operands (f32 accumulate): exact for bytes (<= 255) and
# one-hots — every operand of the Gram product, the key-plane scatters and
# the plane gathers (a chimera sum > 255 may round, but never down to a
# byte, so a mismatch stays a mismatch) — and WRONG for the three operands
# that exceed 8 significant bits: the squared norms (< 2^21), and the
# per-tile counts (<= emits * tile) in the count scatter and the residual
# compaction.  Those contractions ask for full f32 (_EXACT).  The
# interpreter and the CPU compute f32 dots exactly, so only the chip shows
# the difference (first chip run, PR 22: the dedupe mis-paired keys and
# the block overflowed its residual).
_EXACT = jax.lax.Precision.HIGHEST



def _fmix32(h):
    """murmur3 finalizer on uint32 — the packing._fmix32 formulation."""
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> 16)
    return h


def _salted_fold_lanes(lanes, salt_prime, pre_mul):
    """packing._salted_fold over a LIST of [N, 1] uint32 lane columns:
    fmix32(sum_j fmix32(lane_j ^ salt_j)), wraparound uint32 adds."""
    acc = None
    for j, lane in enumerate(lanes):
        x = lane if pre_mul is None else lane * jnp.uint32(pre_mul)
        term = _fmix32(x ^ jnp.uint32(((j + 1) * salt_prime) & 0xFFFFFFFF))
        acc = term if acc is None else acc + term
    return _fmix32(acc)


def _fused_kernel(
    x_ref, tab_ref, resid_ref, ovf_ref, flag_ref,
    *, emits, key_w, width, slots, t_hi, t_lo, probes, r_cap,
):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        # The accumulator planes live at a CONSTANT index_map, so Pallas
        # keeps them in VMEM across grid steps; step 0 owns the init.
        tab_ref[:] = jnp.zeros_like(tab_ref)
        ovf_ref[:] = jnp.zeros_like(ovf_ref)
        flag_ref[:] = jnp.zeros_like(flag_ref)

    # ---- 1. tokenize the tile (ops/pallas/tokenize.py formulation) ----
    x = x_ref[:]                                            # [T, W] uint8
    xi = x.astype(jnp.int32)
    is_delim = xi == 0
    for c in DELIMITERS + b"\n\r":
        is_delim = is_delim | (xi == c)
    in_tok = ~is_delim
    # Shift the int32 widening, compare afterwards: Mosaic cannot shift
    # an i1 mask vector across lanes (see ops/pallas/tokenize.py).
    tok_i = in_tok.astype(jnp.int32)
    zeros_col = jnp.zeros((x.shape[0], 1), dtype=jnp.int32)
    prev = jnp.concatenate([zeros_col, tok_i[:, :-1]], axis=1)
    nxt = jnp.concatenate([tok_i[:, 1:], zeros_col], axis=1)
    starts = in_tok & (prev == 0)
    ends = in_tok & (nxt == 0)
    csum = starts.astype(jnp.int32)
    shift = 1
    while shift < width:
        pad = jnp.zeros((csum.shape[0], shift), dtype=jnp.int32)
        csum = csum + jnp.concatenate([pad, csum[:, :-shift]], axis=1)
        shift *= 2
    tid = csum - 1                                          # [T, W]
    pos = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)   # [T, W]
    # EVERY reduction below runs in f32: this jaxlib generation's Mosaic
    # has no integer-reduction lowering, and all reduced values here are
    # integers < 2^24, where f32 sums are exact.  Elementwise integer
    # adds (the Hillis-Steele scans) lower fine and stay int.
    ntok = jnp.sum(starts.astype(jnp.float32), axis=1, keepdims=True)
    # Accumulated scalar (constant-index [1, 1] block — Mosaic requires
    # block dims divisible by the tile or equal to the array's, so a
    # per-tile (1, 1) block over an [n_tiles, 1] array cannot lower).
    ovf_ref[:] = ovf_ref[:] + jnp.sum(
        jnp.maximum(ntok - float(emits), 0.0)
    )[None, None].astype(jnp.int32)

    # Per-(slot, byte) masked VPU reductions -> flat [N = emits*T] rows
    # in emit-major order, one [N, 1] column per key byte (row order is
    # immaterial: the table is a set, and the dedupe below is
    # order-blind).  Column-wise instead of one [N, K] array so no later
    # step needs an unaligned lane slice of a packed key matrix.
    byte_cols = [[] for _ in range(key_w)]                  # [K][E] of [T,1]
    valid_cols = []
    pos_f = pos.astype(jnp.float32)
    xi_f = xi.astype(jnp.float32)
    for e in range(emits):  # static unroll: emits is a config constant
        sel = tid == e
        m_start = (starts & sel).astype(jnp.float32)
        m_end = (ends & sel).astype(jnp.float32)
        s_idx = jnp.sum(
            pos_f * m_start, axis=1, keepdims=True
        ).astype(jnp.int32)                                     # [T, 1]
        e_idx = jnp.sum(
            pos_f * m_end, axis=1, keepdims=True
        ).astype(jnp.int32)                                     # [T, 1]
        has_tok = jnp.sum(m_start, axis=1, keepdims=True) > 0.0  # [T, 1]
        tok_len = jnp.clip(e_idx - s_idx + 1, 0, key_w)
        valid_cols.append(has_tok)
        for k in range(key_w):  # static unroll: key bytes
            hit = (pos == s_idx + k) & has_tok & (k < tok_len)
            byte_cols[k].append(
                jnp.sum(
                    xi_f * hit.astype(jnp.float32), axis=1, keepdims=True
                )
            )
    bcols = [
        jnp.concatenate(byte_cols[k], axis=0) for k in range(key_w)
    ]                                                       # [K] of [N,1] f32
    valid = jnp.concatenate(valid_cols, axis=0)             # [N, 1] bool
    bf = jnp.concatenate(bcols, axis=1)                     # [N, K] f32
    n_rows = bf.shape[0]
    ones_col = jnp.ones((n_rows, 1), dtype=jnp.float32)

    def row_bcast(col, precision=None):
        """[N, 1] -> [N, N] carrying col[m] at (n, m): a rank-1 ones x
        col contraction — the lane-major broadcast WITHOUT an in-kernel
        transpose (Mosaic-safe).  Default precision is exact for
        one-hot/byte magnitudes only; pass _EXACT above 255."""
        return jax.lax.dot_general(
            ones_col, col, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=precision,
        )

    # ---- 2. exact within-tile dedupe via the Gram matrix ----
    gram = jax.lax.dot_general(
        bf, bf, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                       # [N, N] bytes: exact
    norm = jnp.zeros((n_rows, 1), dtype=jnp.float32)
    for c in bcols:
        norm = norm + c * c                                 # [N, 1]
    d2 = norm + row_bcast(norm, _EXACT) - 2.0 * gram        # exact: < 2^24
    eq = (d2 == 0.0) & valid & (row_bcast(valid.astype(jnp.float32)) > 0.0)
    ridx = jax.lax.broadcasted_iota(jnp.int32, (n_rows, n_rows), 0)
    cidx = jax.lax.broadcasted_iota(jnp.int32, (n_rows, n_rows), 1)
    has_prev = jnp.sum(
        (eq & (cidx < ridx)).astype(jnp.float32), axis=1, keepdims=True
    ) > 0.0
    leader = valid & ~has_prev                              # [N, 1]
    cnt = jnp.sum(eq.astype(jnp.float32), axis=1, keepdims=True)  # [N, 1]

    # ---- 3. hash leaders (packing.hash_pair formulation) ----
    lanes = []
    for j in range(key_w // 4):
        # f32 -> int32 -> uint32: the direct f32->u32 convert recurses in
        # this jaxlib generation's Mosaic _convert_helper; the two-step
        # spelling is exact (bytes are 0..255) and lowers everywhere.
        b0 = bcols[4 * j].astype(jnp.int32).astype(jnp.uint32)
        b1 = bcols[4 * j + 1].astype(jnp.int32).astype(jnp.uint32)
        b2 = bcols[4 * j + 2].astype(jnp.int32).astype(jnp.uint32)
        b3 = bcols[4 * j + 3].astype(jnp.int32).astype(jnp.uint32)
        lanes.append((b0 << 24) | (b1 << 16) | (b2 << 8) | b3)
    h1 = _salted_fold_lanes(lanes, 0x9E3779B9, None)        # [N, 1] uint32
    h2 = _salted_fold_lanes(lanes, 0xC2B2AE3D, 0x01000193)
    step = h2 | jnp.uint32(1)
    lo_bits = (t_lo - 1).bit_length() if t_lo > 1 else 0

    iota_lo = jax.lax.broadcasted_iota(jnp.int32, (n_rows, t_lo), 1)
    iota_hi = jax.lax.broadcasted_iota(jnp.int32, (n_rows, t_hi), 1)

    def gather_plane(p, oh_lo, oh_hi):
        """tab plane ``p`` value at each row's slot, via one one-hot
        contraction + a masked hi-reduction — exact (single hot term)."""
        plane = tab_ref[p * t_hi:(p + 1) * t_hi, :]         # [t_hi, t_lo]
        g = jax.lax.dot_general(
            oh_lo, plane, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                   # [N, t_hi]
        return jnp.sum(oh_hi * g, axis=1, keepdims=True)    # [N, 1]

    def scatter_plane(p, oh_lo, oh_hi, w, precision=None):
        """tab plane ``p`` += one-hot scatter of per-row weights ``w``
        (weights above 255 need ``precision=_EXACT``)."""
        delta = jax.lax.dot_general(
            oh_hi * w, oh_lo, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=precision,
        )                                                   # [t_hi, t_lo]
        rows = tab_ref[p * t_hi:(p + 1) * t_hi, :]
        tab_ref[p * t_hi:(p + 1) * t_hi, :] = rows + delta

    # ---- 4. hasht probe sequence over the resident table ----
    unres = leader
    for p in range(probes):  # static unroll: probes is a config constant
        slot = (h1 + jnp.uint32(p) * step) & jnp.uint32(slots - 1)
        s32 = slot.astype(jnp.int32)                        # [N, 1]
        hi = s32 >> lo_bits
        lo = s32 & (t_lo - 1)
        oh_lo = (lo == iota_lo).astype(jnp.float32)         # [N, t_lo]
        oh_hi = (hi == iota_hi).astype(jnp.float32)         # [N, t_hi]
        # Empty = occupied plane reads 0 (plane K = writer count).
        occ = gather_plane(key_w, oh_lo, oh_hi)
        writer = (unres & (occ == 0.0)).astype(jnp.float32)
        for k in range(key_w):
            scatter_plane(k, oh_lo, oh_hi, bcols[k] * writer)
        scatter_plane(key_w, oh_lo, oh_hi, writer)
        # Full-key verify AFTER this round's writes (a clean writer must
        # match its own write).  Empty slots read all-zero planes and a
        # real key's byte 0 is >= 1, so no occupied check is needed.
        match = unres
        for k in range(key_w):
            match = match & (gather_plane(k, oh_lo, oh_hi) == bcols[k])
        scatter_plane(key_w + 1, oh_lo, oh_hi,
                      cnt * match.astype(jnp.float32), _EXACT)
        unres = unres & ~match

    # ---- 5. residual stream: rank-compact stranded leaders ----
    ri = unres.astype(jnp.int32)                            # [N, 1]
    shift = 1
    while shift < n_rows:
        pad = jnp.zeros((shift, 1), dtype=jnp.int32)
        ri = ri + jnp.concatenate([pad, ri[:-shift]], axis=0)
        shift *= 2
    rank = ri - 1                                           # [N, 1]
    n_resid = jnp.sum(unres.astype(jnp.float32))
    iota_r = jax.lax.broadcasted_iota(jnp.int32, (n_rows, r_cap), 1)
    place = ((rank == iota_r) & unres).astype(jnp.float32)  # [N, r_cap]

    def compact(cols, precision=None):
        return jax.lax.dot_general(
            place, cols, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=precision,
        )                                                   # [r_cap, .]

    # One full-width store (no partial lane-dim ref slices): bytes,
    # count, valid flag, zero tail.
    resid_ref[:] = jnp.concatenate(
        [
            compact(bf),
            compact(cnt, _EXACT),
            compact(unres.astype(jnp.float32)),
            jnp.zeros((r_cap, RESID_PAD - 2), dtype=jnp.float32),
        ],
        axis=1,
    )
    flag_ref[:] = jnp.maximum(
        flag_ref[:],
        (n_resid > float(r_cap)).astype(jnp.int32)[None, None],
    )


def fused_engine_eligible(cfg: EngineConfig, map_fn, combine: str):
    """Can the single-device engine run this fold through the megakernel?

    Returns ``(ok, reason)`` — ``reason`` says why not, so the engine can
    log the hasht-identical degrade ONCE at construction (outside any
    traced code; keeps the kernel body R002-clean).  The checks are all
    static:

    * the kernel bakes in the wordcount tokenizer and the sum monoid
      ("count" lowers to emit-1 + sum, which IS the kernel's count
      plane); any other map_fn/combine folds exactly like "hasht";
    * tile/lane alignment: block_lines a multiple of FUSED_TILE_LINES,
      line_width a multiple of 128 (the uint8 VMEM tile);
    * ``emits_per_block < 2^24``: the kernel accumulates counts in f32
      planes, exact only below the float24 integer ceiling;
    * off-TPU, blocks above FUSED_INTERPRET_MAX_LINES stay on the stock
      path — the interpreter re-traces the kernel body per grid step and
      production block sizes cost minutes of XLA CPU compile.
    """
    from locust_tpu.config import FUSED_INTERPRET_MAX_LINES
    from locust_tpu.ops.map_stage import wordcount_map

    if map_fn is not wordcount_map:
        return False, (
            "map_fn is not the wordcount tokenizer (the kernel bakes "
            "tokenize+count in); folding exactly like 'hasht'"
        )
    if combine not in ("sum", "count"):
        return False, (
            f"combine={combine!r} has no kernel spelling (sum/count only); "
            "folding exactly like 'hasht'"
        )
    if cfg.block_lines % FUSED_TILE_LINES != 0:
        return False, (
            f"block_lines={cfg.block_lines} not a multiple of the "
            f"{FUSED_TILE_LINES}-line kernel tile; folding exactly like "
            "'hasht'"
        )
    if cfg.line_width % 128 != 0:
        return False, (
            f"line_width={cfg.line_width} not a multiple of 128 (uint8 "
            "VMEM tile); folding exactly like 'hasht'"
        )
    if cfg.emits_per_block >= 1 << 24:
        return False, (
            f"emits_per_block={cfg.emits_per_block} >= 2^24 breaks the "
            "kernel's f32 count exactness; folding exactly like 'hasht'"
        )
    if (
        jax.default_backend() != "tpu"
        and cfg.block_lines > FUSED_INTERPRET_MAX_LINES
    ):
        return False, (
            f"off-TPU interpret mode capped at "
            f"{FUSED_INTERPRET_MAX_LINES} lines/block "
            f"(block_lines={cfg.block_lines}); folding exactly like 'hasht'"
        )
    return True, ""


def fused_mesh_eligible(cfg: EngineConfig, map_fn, combine: str):
    """Can the MESH engines run their per-shard map+combine through the
    megakernel?  Returns ``(ok, reason)`` like :func:`fused_engine_eligible`.

    Everything static, decided once at engine construction (the mesh
    engines log the demotion there and surface it as ``fused_demoted``
    on DistributedResult — the ISSUE 19 fix for the silent fallback):

    * all single-device checks apply per shard (each shard folds
      ``cfg.block_lines`` lines per round — the same block the kernel
      pre-aggregates);
    * **TPU only**: the interpret-mode kernel inside a full CPU mesh
      program segfaults XLA's CPU compiler (the check_vma class,
      CLAUDE.md) — off-TPU the mesh fold stays plain hasht, period.
      The CPU kernel-under-shard_map path is pinned by a small DIRECT
      test instead (tests/test_fused_fold.py);
    * the pre-aggregated rows (table slots + per-tile residuals) must
      fit the shard's ``emits_per_block`` KV capacity — the shuffle
      step's capacity contract is that the local combiner's output size
      equals the raw emit count, and the kernel's output pads up to it.
    """
    ok, why = fused_engine_eligible(cfg, map_fn, combine)
    if not ok:
        return False, why
    if jax.default_backend() != "tpu":
        return False, (
            "mesh fused mode is TPU-only (the interpret kernel never "
            "runs inside a CPU mesh program — check_vma segfault class); "
            "folding exactly like 'hasht'"
        )
    t_hi, t_lo = fused_table_layout()
    n_tiles = cfg.block_lines // FUSED_TILE_LINES
    preagg_rows = t_hi * t_lo + n_tiles * FUSED_RESIDUAL_ROWS
    if preagg_rows > cfg.emits_per_block:
        return False, (
            f"kernel output ({preagg_rows} table+residual rows) exceeds "
            f"the shard's emit capacity ({cfg.emits_per_block}); folding "
            "exactly like 'hasht'"
        )
    return True, ""


@functools.partial(
    jax.jit,
    static_argnames=(
        "cfg", "interpret", "table_slots", "resid_rows", "probes",
        "tile_lines",
    ),
)
def fused_block_preagg(
    lines: jax.Array,
    cfg: EngineConfig,
    interpret: bool = False,
    table_slots: int | None = None,
    resid_rows: int | None = None,
    probes: int | None = None,
    tile_lines: int | None = None,
):
    """Pre-aggregate one ``[block_lines, line_width]`` uint8 block in VMEM.

    Returns ``(table, residual, overflow, resid_overflow)``:

    * ``table`` — KVBatch over the (sublane-padded) kernel table: each
      valid slot holds one distinct key of the block with its exact
      occurrence count (int32; the engine guards ``block_lines *
      emits_per_line < 2^24`` so the in-kernel f32 counts are exact);
    * ``residual`` — KVBatch of ``n_tiles * resid_rows`` rows: per-tile
      distinct keys the probe rounds stranded, with their tile counts
      (the same key may appear once per tile — the settlement fold
      re-merges duplicate key rows exactly, hash_table.aggregate_exact);
    * ``overflow`` — int32 tokens dropped by the per-line emit cap, the
      tokenize contract (identical formulation to tokenize_block);
    * ``resid_overflow`` — bool: some tile stranded more leaders than the
      residual buffer holds; the caller MUST discard this call's table
      and residual and re-fold the block through the stock path (the
      engine's lax.cond does).  Nothing is lost either way — the flag is
      sticky across grid steps.

    The union of table and residual rows carries exactly the block's
    distinct keys with exact per-key totals — the invariant the
    bit-identity argument in the module docstring rests on.
    """
    num_lines, width = lines.shape
    tile = FUSED_TILE_LINES if tile_lines is None else tile_lines
    slots = FUSED_TABLE_SLOTS if table_slots is None else table_slots
    r_cap = FUSED_RESIDUAL_ROWS if resid_rows is None else resid_rows
    n_probes = HASHT_PROBES if probes is None else probes
    if num_lines % tile != 0:
        raise ValueError(f"block_lines must be a multiple of {tile}")
    if width % 128 != 0:
        raise ValueError(f"line_width must be a multiple of 128, got {width}")
    if slots < 2 or slots & (slots - 1):
        raise ValueError(f"table_slots must be a power of two, got {slots}")
    emits, key_w = cfg.emits_per_line, cfg.key_width
    t_hi, t_lo = fused_table_layout(slots)
    out_slots = t_hi * t_lo                                 # >= slots
    n_tiles = num_lines // tile
    rw = key_w + RESID_PAD

    kernel = functools.partial(
        _fused_kernel, emits=emits, key_w=key_w, width=width,
        slots=slots, t_hi=t_hi, t_lo=t_lo, probes=n_probes, r_cap=r_cap,
    )
    tab, resid, ovf, flag = pl.pallas_call(
        kernel,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((tile, width), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=(
            pl.BlockSpec(((key_w + 2) * t_hi, t_lo), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((r_cap, rw), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
        ),
        out_shape=(
            jax.ShapeDtypeStruct(((key_w + 2) * t_hi, t_lo), jnp.float32),
            jax.ShapeDtypeStruct((n_tiles * r_cap, rw), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        ),
        # The unrolled per-(slot, byte) columns keep ~24 MB of [T, 1]
        # vregs live — over Mosaic's 16 MB default scoped-VMEM budget,
        # well inside a v5e core's 128 MiB.
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=FUSED_VMEM_LIMIT_BYTES
        ),
        interpret=interpret,
    )(lines)

    # Decode the plane-major table into a slot-major KVBatch (slot id =
    # hi * t_lo + lo, the same split the kernel addressed).  Chimera
    # slots (count 0) may hold byte sums > 255; they are invalid and the
    # uint8 wrap below never reaches a consumer.
    planes = tab.reshape(key_w + 2, t_hi, t_lo)
    key_bytes = (
        planes[:key_w].transpose(1, 2, 0).reshape(out_slots, key_w)
        .astype(jnp.uint8)
    )
    counts = planes[key_w + 1].reshape(out_slots).astype(jnp.int32)
    table_kv = KVBatch.from_bytes(key_bytes, counts, counts > 0)

    resid_kv = KVBatch.from_bytes(
        resid[:, :key_w].astype(jnp.uint8),
        resid[:, key_w].astype(jnp.int32),
        resid[:, key_w + 1] > 0.0,
    )
    return table_kv, resid_kv, ovf[0, 0], flag[0, 0] > 0
