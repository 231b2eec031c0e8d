"""Runtime configuration for the Locust-TPU engine.

The reference (wuyan33/Locust) freezes its capacities at compile time via
``#define``s — MAX_LINES_FILE_READ=5800, EMITS_PER_LINE=20, MAX_EMITS,
GRID_SIZE/BLOCK_SIZE (reference MapReduce/src/main.cu:18-27).  On TPU, JIT
specialization replaces compile-time constants, so the same knobs live in a
runtime dataclass: each distinct config traces/compiles once and is cached.

Byte-width caps mirror the reference's fixed-width KV structs
(KeyValuePair.key[100]/value[100], KeyIntValuePair.key[30] —
reference MapReduce/src/KeyValue.h:6-18), rounded up to TPU-friendly
power-of-two widths (lane-sized multiples of 4 for uint32 key packing).
"""

from __future__ import annotations

import dataclasses
import os as _os


# Tokenization delimiter set — byte-for-byte the reference's strtok delimiters
# (reference MapReduce/src/main.cu:138).  This *defines* WordCount semantics
# (hyphens split words, apostrophes split contractions); see SURVEY.md Q11.
DELIMITERS: bytes = b" ,.-;:'()\"\t"

# The single source of truth for Process-stage sort strategies:
# EngineConfig validation, the CLI --sort-mode choices, and
# ops.process_stage.sort_and_compact dispatch all key off this.
SORT_MODES = ("hash", "hashp2", "hashp1", "lex", "hasht", "hasht-mxu", "fused")

# The sort-FREE fold family (ops/hash_table.py): identical probe/exactness
# ladder, differing only in how the value-combine scatter is spelled —
# "hasht" = XLA duplicate-index scatter, "hasht-mxu" = one-hot bf16
# contraction on the MXU (hash_table.mxu_scatter_add), "fused" = hasht
# semantics everywhere PLUS the Pallas map->aggregate megakernel
# (ops/pallas/fused_fold.py) at the single-device line->fold boundary,
# which pre-aggregates each block in VMEM so the [lines, emits, key_width]
# token tensor never round-trips HBM.  Every site that used to test
# ``sort_mode == "hasht"`` must test membership here instead; the three
# modes share slot-ordered (non prefix-compact) table semantics and
# bit-identical tables (tests/test_hasht_mxu.py, tests/test_fused_fold.py).
HASHT_FAMILY = ("hasht", "hasht-mxu", "fused")


def default_sort_mode(backend: str) -> str:
    """Per-backend default Process strategy of the CLI and the daemon.

    TPU: "hashp2" — the mode every cell of BENCHMARK.json runs, so the
    only one with ledger lines (PERF_LEDGER.jsonl; PERF.md section 5);
    no other mode has been measured against it on this machine.  CPU:
    "hasht" (sort-free fold; ``timed_run``'s split stages take it as
    "hashp1", process_stage.sort_and_compact); its speed is not measured
    on this machine.  Anything else: the portable "hash".
    ``EngineConfig.sort_mode`` itself defaults to "hash": three answers
    to one question, ROADMAP Design item 2.
    """
    return {"cpu": "hasht", "tpu": "hashp2"}.get(backend, "hash")

# Newline bytes also terminate tokens: the reference tokenizes line-by-line so
# a '\n' never reaches strtok; our padded line tensors strip newlines at ingest.
PAD_BYTE: int = 0

# Bytes that are token boundaries on DEVICE beyond the strtok set: NUL (row
# padding / embedded NULs) and the newline pair.  The single source for
# every host-side measure that must count tokens the device's way
# (core/bytes_ops.delimiter_mask, io/loader.measure_caps*) — three drifting
# copies of this literal would let --auto-caps under-size emits_per_line.
TOKEN_BOUNDARY_EXTRA: bytes = b"\x00\n\r"
FULL_DELIMITERS: bytes = DELIMITERS + TOKEN_BOUNDARY_EXTRA


def compile_cache_dir(sub: str = ".jax_cache") -> str:
    """Where jax's persistent compilation cache lives — and the ONE place
    that exports it (``JAX_COMPILATION_CACHE_DIR``) for this process and
    the children it starts.

    * ``JAX_COMPILATION_CACHE_DIR`` already set: that directory, as is.
      This code sets no other and never wipes it.
    * otherwise ``<checkout>/<sub>`` (git-ignored): a FIXED path — the
      path is part of jax's cache key, so a directory named after a pid,
      a time or a temp dir never hits.  The tests pass
      ``sub=".jax_cache_cpu"`` so entries compiled for the test host's
      CPU stay apart from what a chip run caches.

    It also lowers jax's floor for WRITING an entry
    (``JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS``, 1 s by default) to 0
    unless the variable is already set: a job drives two sub-second
    helper programs beside its sorts, and under jax's floor every new
    process compiled those anew (PERF.md section 2).

    jax-free (never imports it) and idempotent: every entry point calls
    it before its first ``import jax``, which reads both variables; a jax
    that is already imported gets the same values through its config.
    """
    d = _os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not d:
        d = _os.path.join(
            _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
            sub,
        )
        _os.environ["JAX_COMPILATION_CACHE_DIR"] = d
    floor = _os.environ.setdefault(
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0"
    )
    import sys

    jax = sys.modules.get("jax")
    if jax is not None:  # imported before us: its config already read env
        jax.config.update("jax_compilation_cache_dir", d)
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", float(floor)
        )
    return d


# --- kernel geometry: constants, part of every compiled shape that uses
# them.  Here, jax-free, because ops/hash_table.py, ops/pallas/*.py and
# the engines' eligibility checks all read them.  The asserts hold the
# relations a kernel relies on.


def _pow2(n: int) -> bool:
    return n > 0 and not n & (n - 1)


# Probe rounds of the sort-free hash-table aggregation (sort_mode="hasht",
# ops/hash_table.py) before a row falls back to the exact sort path.
HASHT_PROBES: int = 4

# MXU histogram geometry for the "hasht-mxu" combine scatter
# (ops/hash_table.mxu_scatter_add): the slot id decomposes as
# ``hi * HASHT_MXU_LANES + lo`` and the per-slot sums come out of
# ``[t_hi, n] x [n, t_lo]`` bf16 contractions.  512 lanes is a multiple
# of the 128-wide MXU/VPU tile (65,536 buckets as [128, 512]); not
# measured on this machine.
HASHT_MXU_LANES: int = 512

# Rows per one-hot chunk: the [chunk, t_hi]+[chunk, t_lo] bf16 one-hot
# operands are materialized per chunk (lax.scan over chunks), bounding the
# transient at ~chunk*(t_hi+t_lo)*2 bytes instead of scaling with the
# whole fold's n.  The cap also carries an EXACTNESS bound: per-chunk
# partial sums accumulate in fp32, and 8-bit value limbs stay exact there
# while a slot's per-chunk partial < 2^24, i.e. chunk <= 2^24/255 = 65793.
HASHT_MXU_CHUNK: int = 32768
assert 255 * HASHT_MXU_CHUNK < 1 << 24, "fp32 partial-sum exactness bound"


def hasht_mxu_grid(table_size: int) -> tuple[int, int]:
    """[t_hi, t_lo] histogram grid covering ``table_size`` slots.

    The ONE place the decomposition is decided (ops/hash_table.py runs
    it).  Grid cells at/above table_size are never addressed (slot ids
    are < T) and simply stay zero."""
    t_lo = min(HASHT_MXU_LANES, table_size)
    t_hi = -(-table_size // t_lo)
    return t_hi, t_lo


# --- fused map->aggregate megakernel (ops/pallas/fused_fold.py) ---

# Lines per kernel grid step.  uint8 VMEM tiles are (32, 128), so the tile
# must be a multiple of 32; each step's within-tile dedupe builds a
# [tile*emits_per_line]^2 Gram matrix in VMEM, which is what keeps the
# default small (32 lines x 20 emits = a 640^2 f32 Gram, ~1.6 MB).
FUSED_TILE_LINES: int = 32
assert FUSED_TILE_LINES % 32 == 0, "uint8 sublane tile"

# VMEM-resident kernel table slots (per BLOCK, rebuilt every fold): bounds
# the distinct keys one block can pre-aggregate in VMEM; keys past it
# strand to the residual stream (and a residual overflow falls the whole
# block back to the stock hasht fold — exact either way).  Power of two so
# the in-kernel ``h % slots`` is a bitwise AND.  8192 slots x (key bytes +
# occupied + count) f32 planes ~ 1.2 MB VMEM at key_width 32.
FUSED_TABLE_SLOTS: int = 8192
assert FUSED_TABLE_SLOTS >= 512 and _pow2(FUSED_TABLE_SLOTS)

# Residual rows per grid tile: per-tile distinct keys the probe rounds
# strand (table collision/full) stream out through this bounded buffer;
# more than this per tile sets the kernel's overflow flag and the engine
# re-folds the block through the stock path.  Power of two.
FUSED_RESIDUAL_ROWS: int = 32
assert FUSED_RESIDUAL_ROWS >= 8 and _pow2(FUSED_RESIDUAL_ROWS)

# Residual row padding lanes beyond the key bytes (count + valid flag +
# zero tail): the kernel's residual rows are (key_width + FUSED_RESID_PAD)
# f32 lanes wide, and those rows DO cross HBM.
FUSED_RESID_PAD: int = 8

# Off-TPU the kernel runs in interpret mode (the pinned test vehicle —
# NEVER inside a full CPU mesh program, CLAUDE.md); the interpreter
# re-traces the kernel body per grid step, so production block sizes cost
# minutes of XLA CPU compile.  Blocks with more lines than this take the
# hasht-identical stock path off-TPU with a one-time notice.  On TPU the
# Mosaic kernel always runs.
FUSED_INTERPRET_MAX_LINES: int = 8192


# f32 sublane tile rows: the kernel stores its table as stacked
# [t_hi, t_lo] planes and slices them per plane, so the plane stride
# (t_hi) must stay sublane-aligned for Mosaic; fused_table_layout pads
# small tables up to this.
FUSED_SUBLANE: int = 8


def fused_grid(slots: int | None = None) -> tuple[int, int]:
    """[t_hi, t_lo] LOGICAL decomposition of a ``slots``-slot kernel
    table's slot axis (default FUSED_TABLE_SLOTS; t_hi * t_lo == slots;
    slot = hi * t_lo + lo).

    t_lo is fixed at 512 lanes (NOT HASHT_MXU_LANES: the kernel's hi/lo
    split is shift+mask, so t_lo must stay a power of two).  The ONE
    place the decomposition is decided: :func:`fused_table_layout` (the
    physical plane layout) derives from it, so the two can never drift."""
    s = FUSED_TABLE_SLOTS if slots is None else slots
    t_lo = min(512, s)
    t_hi = s // t_lo
    return t_hi, t_lo


# Blocks folded per PERSISTENT-KERNEL segment (megakernel v2 streaming
# formulation, ops/pallas/fused_fold.py).  run_stream groups this many
# staged blocks into ONE kernel launch whose table planes stay VMEM-
# resident across the whole segment, amortizing the per-block
# acc->settle->acc HBM round-trip by this factor.  Clamped at runtime by
# :func:`fused_stream_seg_blocks` (f32 count-plane exactness + off-TPU
# interpret-cost caps).
FUSED_STREAM_BLOCKS: int = 8


def fused_stream_seg_blocks(
    emits_per_block: int, block_lines: int, on_tpu: bool
) -> int:
    """Blocks per persistent-kernel streaming segment, clamped for
    exactness and interpret cost.

    The kernel counts in f32 planes, exact only below 2**24, and the
    per-segment emit budget is ``seg_blocks * emits_per_block`` — so the
    segment is clamped to keep that product under 2**24 (the same bound
    fused_engine_eligible enforces per block).  Off-TPU the interpreter
    re-traces per grid step, so the segment additionally respects
    FUSED_INTERPRET_MAX_LINES over its total line count."""
    cap = max(1, ((1 << 24) - 1) // max(1, emits_per_block))
    seg = min(FUSED_STREAM_BLOCKS, cap)
    if not on_tpu and block_lines > 0:
        seg = min(seg, max(1, FUSED_INTERPRET_MAX_LINES // block_lines))
    return max(1, seg)


def fused_table_layout(slots: int | None = None) -> tuple[int, int]:
    """[t_hi, t_lo] PHYSICAL plane layout for a ``slots``-slot kernel
    table (default FUSED_TABLE_SLOTS): the :func:`fused_grid`
    decomposition with the hi axis padded up to FUSED_SUBLANE so
    per-plane ref slices stay Mosaic-aligned.  The megakernel allocates
    its VMEM planes from this.  Padded slots are never addressed (slot
    ids < slots) and decode as count-0 = invalid."""
    t_hi, t_lo = fused_grid(slots)
    return max(FUSED_SUBLANE, t_hi), t_lo


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Static shape/capacity configuration of one MapReduce pipeline.

    Frozen + hashable so it can be a ``jax.jit`` static argument.
    """

    # Max bytes per input line (value side). Reference: char value[100]
    # (KeyValue.h:9) → rounded to 128 for TPU lane alignment.
    line_width: int = 128

    # Max bytes per emitted key. Reference: char key[30] (KeyValue.h:15) →
    # rounded to 32 (8 uint32 big-endian lanes).
    key_width: int = 32

    # Max emits (tokens) per line. Reference: EMITS_PER_LINE=20 (main.cu:19).
    emits_per_line: int = 20

    # Lines per processing block. Reference caps the whole file at
    # MAX_LINES_FILE_READ=5800 (main.cu:18); we instead stream fixed-size
    # blocks so there is no global cap (SURVEY.md §5 "long-context").
    block_lines: int = 4096

    # Accumulator table capacity: distinct keys tracked across blocks.
    # Bounds the cross-block merge cost (the merge sorts table_size +
    # emits_per_block rows, not 2 x emits_per_block).  None (default)
    # resolves to min(65536, max(emits_per_block, 4096)) (see
    # resolved_table_size for the floor's rationale).  The default path
    # (engine.timed_run) STARTS here and grows its table when a group of
    # blocks counts more distinct keys than it holds, so it is exact at
    # any vocabulary, and so do the flat mesh's hash shards (their fair
    # share of it is one floor of where they start,
    # parallel/shuffle.DistributedMapReduce); every other path (run,
    # run_fused, run_stream, the hierarchical mesh) holds this capacity
    # for the whole job and reports truncation past it
    # (RunResult.truncated; tests/test_scale.py pins that loud report at
    # the default) — raise it explicitly there.
    table_size: int | None = None

    # Process-stage sort strategy (none of the speeds below is measured on
    # this machine; the only mode with ledger lines is "hashp2", the TPU
    # default — see default_sort_mode).  "hash": sort by a 64-bit key hash
    # — 3 sort operands + one index payload + gather; equal keys still
    # group adjacently (exact-key segment boundaries downstream), device
    # order is hash order (host output re-sorts).  "hashp2": the row rides
    # as sort PAYLOAD operands instead of a post-sort gather, with only 2
    # key operands (validity folded into a 31-bit primary hash, h2
    # tiebreak).  "hashp1": payload carriage behind ONE 32-bit sort
    # operand (31 hash bits + validity bit); collisions only duplicate a
    # table row, re-merged downstream (process_stage._folded_key).
    # "lex": sort full big-endian key lanes — exact lexicographic device
    # order, the reference's KIVComparator semantics (KeyValue.h:20-33).
    # "hasht": the fold-level SORT-FREE hash-table aggregation
    # (ops/hash_table.py) — probe/claim/verify scatters with an exact
    # sort fallback ladder; the CPU default.  "hasht-mxu": the same fold
    # with the value-combine scatter spelled as a one-hot bf16 MXU
    # contraction (hash_table.mxu_scatter_add) instead of XLA's
    # duplicate-index scatter — byte-identical tables.
    # "fused": hasht semantics PLUS the Pallas map->aggregate megakernel
    # (ops/pallas/fused_fold.py) at the single-device line->fold
    # boundary — tokenize + hash + table-update in one VMEM-resident
    # kernel, so the [lines, emits, key_width] token tensor never
    # round-trips HBM; tables stay BIT-identical to "hasht" (the
    # settlement fold is hasht's own aggregate_exact).  Off the
    # wordcount map / off supported shapes / inside mesh programs the
    # mode degrades to "hasht" exactly.
    sort_mode: str = "hash"

    # Use Pallas kernels for the map/reduce hot loops where available;
    # otherwise pure-jnp/XLA lowering.
    use_pallas: bool = False

    # Map-stage key extraction: "einsum" contracts the one-hot start mask
    # against shifted byte planes on the MXU (the gather-as-matmul trick —
    # the TPU winner, where scalar gathers are ~12x slower); "gather" is a
    # plain scatter-starts + take_along_axis (the CPU winner: the einsum
    # does L*W*E*K multiply-adds a CPU has no systolic array to hide —
    # ~36ms vs ~2ms at 700 hamlet lines).  "auto"
    # resolves per backend at trace time: einsum on TPU, gather elsewhere.
    map_impl: str = "auto"

    # --- zero-stall streaming executor knob (docs/DESIGN.md) ----------
    # Move checkpoint snapshots to a bounded background writer
    # (io/snapshot.py): the fold loop only marks a generation (an
    # on-device table copy, async) and the writer thread does the
    # device->host copy + npz write + atomic rename off the critical
    # path, latest-wins when the loop laps it.  False restores the
    # synchronous in-loop save (identical on-disk format either way).
    async_checkpoint: bool = True

    # Structured telemetry opt-in (locust_tpu.obs, docs/OBSERVABILITY.md):
    # True enables the process tracer at engine construction, so API
    # users get spans/metrics without touching the obs module (the CLI's
    # --trace-out sets the same switch and adds the export).  Default
    # False = the zero-overhead no-op path; note the knob is part of the
    # config repr, so flipping it (like any config change) starts
    # checkpointed runs fresh.
    trace: bool = False

    def __post_init__(self):
        if self.key_width <= 0 or self.key_width % 4 != 0:
            raise ValueError("key_width must be a positive multiple of 4 (uint32 lanes)")
        if self.line_width <= 0 or self.emits_per_line <= 0 or self.block_lines <= 0:
            raise ValueError("line_width, emits_per_line, block_lines must be positive")
        if self.table_size is not None and self.table_size <= 0:
            raise ValueError("table_size must be positive")
        if self.sort_mode not in SORT_MODES:
            raise ValueError(
                f"sort_mode must be one of {SORT_MODES}, got {self.sort_mode!r}"
            )
        if self.map_impl not in ("auto", "einsum", "gather"):
            raise ValueError(
                "map_impl must be 'auto', 'einsum', or 'gather', "
                f"got {self.map_impl!r}"
            )

    @property
    def key_lanes(self) -> int:
        """Number of uint32 big-endian lanes a packed key occupies."""
        return self.key_width // 4

    def fingerprint(self) -> str:
        """Stable digest of EVERY config field — the executable-identity
        half of the serve tier's warm-cache key (docs/SERVING.md): two
        configs share a compiled program iff their fingerprints match.
        Built on ``repr`` of the frozen dataclass (field order is the
        class definition, values are literals), the same identity the
        checkpoint fingerprints already ride (``run_stream`` embeds
        ``repr(cfg)``), so "same executable" and "same checkpoint
        lineage" can never disagree about what a config IS.  Memoized:
        the serve scheduler keys every pending job by it on every poll
        tick, and a frozen config's identity never changes."""
        fp = self.__dict__.get("_fingerprint")
        if fp is None:
            import hashlib

            fp = hashlib.sha1(repr(self).encode()).hexdigest()[:12]
            object.__setattr__(self, "_fingerprint", fp)
        return fp

    @property
    def emits_per_block(self) -> int:
        """Emit-table rows per block (analog of MAX_EMITS, main.cu:20)."""
        return self.block_lines * self.emits_per_line

    @property
    def resolved_table_size(self) -> int:
        """Accumulator capacity with the None default resolved.

        ``min(65536, emits_per_block)`` bounds the merge's sort (not
        measured on this machine), and the 4096 FLOOR is a usability
        guard the round-4 batteries earned three times over:
        the table is CORPUS-level state, and a small block size (e.g.
        block_lines=4 -> 32 emits) used to cap the entire vocabulary at
        32 keys — loudly, per contract, but on completely ordinary
        inputs.  The floor costs ~150KB and binds only where
        emits_per_block < 4096, far below any tuned shape."""
        if self.table_size is not None:
            return self.table_size
        return min(1 << 16, max(self.emits_per_block, 4096))


DEFAULT_CONFIG = EngineConfig()
