"""Roofline accounting for the Process-stage sort.

"15x a GTX 1060" says nothing about how much of a TPU the pipeline uses.
This module converts a bench run's configuration + elapsed time into an
analytic estimate of the sort's HBM traffic and the achieved fraction of
the chip's peak memory bandwidth, so the headline number is judged against
the hardware, not against 2016's (reference README.md:66: the baseline GPU
is a GTX 1060).

Model (documented limits, all stated in the emitted row):

* Only the Process stage is modeled — it is ~94% of the reference's GPU
  runtime (reference MapReduce/src/main.cu:414-415 region) and the
  dominant consumer here; map/reduce traffic is ignored, which UNDERSTATES
  true utilization slightly.
* ``lax.sort`` lowers to a bitonic-style network: for n rows that is
  ``k(k+1)/2`` compare-exchange passes with ``k = ceil(log2 n)``, each
  pass streaming every operand byte read+write.  Real XLA schedules fuse
  some stages in VMEM, so the estimate is an UPPER bound on sort traffic;
  utilization = achieved/peak computed from it is correspondingly a lower
  bound on how hard the memory system works per useful byte.
* The radix mode does ``ceil(32/8)=4`` LSD counting passes instead
  (ops/radix_sort.py), each streaming key + rank arrays, plus one final
  payload gather.
* The sort-free hasht family is modeled as probe-round row sweeps
  (``sort_pass_count``); "hasht-mxu" replaces the value-combine sweep
  with the MXU histogram's one-hot operand traffic (reported separately
  as ``est_onehot_bytes`` — the one-hot-bytes-vs-scatter-bytes tradeoff
  the engine A/B decides), sized off ``config.hasht_mxu_grid``.
* The fused fold (engine.fold_block) does ONE sort of
  ``table_size + emits_per_block`` rows per block — the accumulator is
  concatenated with the block's emits so grouping and cross-block merge
  share a single sort.  That is the sort the model counts.

Peak bandwidths are the public per-chip HBM numbers; an unknown device
kind yields ``peak=None`` and no utilization claim (CPU included: DRAM
peak varies too much across hosts to assert one).
"""

from __future__ import annotations

import math

# Public per-chip HBM peaks, GB/s.  Keys match jax Device.device_kind.
PEAK_HBM_GB_S: dict[str, float] = {
    "TPU v5 lite": 819.0,
    "TPU v5e": 819.0,
    "TPU v5": 1228.0,
    "TPU v5p": 2765.0,
    "TPU v4": 1228.0,
    "TPU v6 lite": 1640.0,
    "TPU v6e": 1640.0,
}

# Sort-operand structure per Process-stage mode (ops/process_stage.py):
# (key_operands_u32, payload_operands_u32(key_lanes), gathers_full_row).
# Payload modes carry the row through every pass; gather modes sort a
# small index and pay one scattered read + dense write of the row at the
# end.  Validity rides folded into a key operand where noted in the
# process_stage docstrings; we charge it as part of the listed operands.
_MODE_OPERANDS = {
    "hash": (4, 0, True),      # (invalid, h1, h2, idx), then row gather
    "hashp": (3, None, False),  # 3 hash keys + row payload
    "hashp2": (2, None, False),  # folded hash + h2 tiebreak + row payload
    "hashp1": (1, None, False),  # folded hash only + row payload
    "hasht": (1, None, False),  # scatter rounds modeled via sort_pass_count
    # hasht-mxu: claim/verify row sweeps via sort_pass_count; the value
    # combine's traffic moves to the one-hot term (pipeline_sort_traffic).
    "hasht-mxu": (1, None, False),
    # fused: the settlement fold's hasht sweeps over the PRE-AGGREGATED
    # rows (kernel table + residual, not the raw emits); the kernel's own
    # HBM bytes land in the est_kernel_bytes term (pipeline_sort_traffic).
    "fused": (1, None, False),
    "hash1": (2, 0, True),     # (folded key, idx), then row gather
    "radix": (2, 0, True),     # folded key + rank arrays, then row gather
    "bitonic": (1, None, False),  # folded key + row payload, VMEM tiles
    "lex": (None, 1, False),   # key lanes as keys + value payload
}

_RADIX_PASSES = 4  # ceil(32 key bits / 8-bit digits), ops/radix_sort.py


def _bitonic_tile_bits() -> int:
    """log2 of the bitonic kernel's tile, from the SAME validated value
    the kernel reads (config.BITONIC_TILE_ROWS — jax-free, so this module
    stays importable in analysis contexts) — a hardcoded copy here would
    silently model the wrong pass count when the knob moves."""
    from locust_tpu.config import BITONIC_TILE_ROWS

    return (BITONIC_TILE_ROWS * 128).bit_length() - 1


def _row_u32(key_lanes: int) -> int:
    """uint32 lanes a full KV row occupies: key lanes + value."""
    return key_lanes + 1


def sort_pass_count(n_rows: int, mode: str = "hash") -> int:
    """Data-streaming passes one sort of ``n_rows`` makes over its operands."""
    if n_rows <= 1:
        return 0
    if mode == "radix":
        return _RADIX_PASSES
    if mode == "hasht":
        # Not a sort: ~2 row-sized gather/scatter sweeps per probe round
        # (claim + lanes-verify + value-combine, ops/hash_table.py) — an
        # order-of-magnitude model, like the radix constant above.
        from locust_tpu.config import HASHT_PROBES

        return 2 * HASHT_PROBES
    if mode == "hasht-mxu":
        # Same probe rounds, but the value-combine scatter's row sweep is
        # replaced by the MXU histogram: ~1 row-sized sweep per round
        # remains (claim + lanes-verify), and the combine is priced by
        # the one-hot term in pipeline_sort_traffic instead.
        from locust_tpu.config import HASHT_PROBES

        return HASHT_PROBES
    if mode == "fused":
        # The XLA settlement IS a hasht fold (ops/pallas/fused_fold.py:
        # aggregate_exact over kernel table + residual) — same sweep
        # count, over far fewer rows (pipeline_sort_traffic shrinks
        # rows_per_sort for this mode; the kernel's own bytes are the
        # est_kernel_bytes term).
        from locust_tpu.config import HASHT_PROBES

        return 2 * HASHT_PROBES
    k = math.ceil(math.log2(n_rows))
    if mode == "bitonic":
        # HBM round-trips of the Pallas tiled network = entries in the
        # SAME launch plan the kernel executes (config.bitonic_schedule:
        # each fused local launch and each cross pass streams every
        # operand once) — counting a shared plan instead of a formula
        # keeps the model honest when BITONIC_MAX_FUSED splits launches.
        from locust_tpu.config import bitonic_schedule

        m = min(k, _bitonic_tile_bits())
        return len(bitonic_schedule(k, m))
    return k * (k + 1) // 2


def mode_row_bytes(mode: str, key_lanes: int) -> tuple[int, int]:
    """(bytes carried per row per sort pass, bytes moved once by gather)."""
    key_ops, payload_ops, gathers = _MODE_OPERANDS[mode]
    if key_ops is None:  # lex: every key lane is a sort key
        key_ops = key_lanes + 1  # lanes + validity operand
    if payload_ops is None:  # payload modes carry the whole row
        payload_ops = _row_u32(key_lanes)
    per_pass = 4 * (key_ops + payload_ops)
    gather = 2 * 4 * _row_u32(key_lanes) if gathers else 0  # read + write
    return per_pass, gather


def pipeline_sort_traffic(
    sort_mode: str,
    key_lanes: int,
    emits_per_block: int,
    table_size: int,
    n_blocks: int,
    block_lines: int | None = None,
    line_width: int | None = None,
    fused_variant: str = "batch",
    stream_seg_blocks: int | None = None,
) -> dict:
    """Estimated HBM bytes the fold's sorts move end-to-end.

    One sort per block (engine.fold_block): accumulator + block emits in
    a single ``table_size + emits_per_block``-row sort.

    ``sort_mode="fused"`` (the Pallas megakernel) REQUIRES
    ``block_lines``/``line_width``: its per-block bytes are the kernel's
    own HBM touches (one streaming read of the raw line block, the
    VMEM-resident table's one flush + decode, the bounded residual
    stream — all sized off the SAME config knobs the kernel runs with)
    plus the hasht settlement sweeps over ``table_size + kernel slots +
    residual rows`` — the emit-count term disappears entirely, which is
    the mode's whole thesis.

    ``fused_variant`` selects the megakernel v2 formulation:

    * ``"batch"`` (default) — the v1 per-block model above: every block
      pays the full table flush+decode AND the acc->settle->acc sweeps.
    * ``"stream"`` — engine._run_stream_fused: the table stays
      VMEM-resident across a SEGMENT of ``stream_seg_blocks`` blocks
      (default: the SAME clamp the engine runs with,
      config.fused_stream_seg_blocks on a TPU backend), so the flush +
      settlement are paid once per SEGMENT; line reads and the bounded
      residual stream stay per-tile.  Strictly below the batch figure
      whenever the clamp exceeds one block (test-pinned at the bench
      shape, the PR 13 strictly-below discipline).
    * ``"mesh"`` — the per-shard shard_map formulation: the kernel
      replaces map + the local combiner; the shuffle partition,
      all-to-all and shard merge are unchanged by the mode and are NOT
      modeled (they cancel in any fused-vs-hasht mesh comparison).
      Charged per shard-block: the kernel's bytes plus the
      combine-replacement sweeps over the pre-aggregated rows.
    """
    if sort_mode == "fused":
        if fused_variant not in ("batch", "stream", "mesh"):
            raise ValueError(
                f"fused_variant must be batch/stream/mesh, "
                f"got {fused_variant!r}"
            )
        if block_lines is None or line_width is None:
            raise ValueError(
                "fused roofline needs block_lines and line_width (the "
                "kernel's HBM bytes are sized off the line block, not "
                "the emit count)"
            )
        from locust_tpu.config import (
            FUSED_RESID_PAD,
            FUSED_RESIDUAL_ROWS,
            FUSED_TILE_LINES,
            fused_table_layout,
        )

        # The PHYSICAL (sublane-padded) plane layout the kernel
        # allocates — config.fused_table_layout is the one decider, so
        # the flushed bytes modeled here are the bytes that crossed HBM.
        t_hi, t_lo = fused_table_layout()
        n_tiles = -(-block_lines // FUSED_TILE_LINES)
        key_w = 4 * key_lanes
        resid_rows = n_tiles * FUSED_RESIDUAL_ROWS
        # Per-tile terms (paid for every line tile in every variant):
        # the streaming line read + the bounded residual store+reload.
        line_bytes = block_lines * line_width
        resid_bytes = 2 * resid_rows * (key_w + FUSED_RESID_PAD) * 4
        # Per-LAUNCH terms: the VMEM-resident table's flush + decode.
        flush_bytes = 2 * (key_w + 2) * t_hi * t_lo * 4
        per_pass, gather = mode_row_bytes("hasht", key_lanes)
        out = {
            "sort_mode": sort_mode,
            "n_blocks": n_blocks,
            "fused_grid": [t_hi, t_lo],
            "fused_variant": fused_variant,
        }
        if fused_variant == "stream":
            # The persistent streaming formulation: one launch + one
            # settlement per SEGMENT; flush and acc sweeps amortize by
            # the segment length.  The default segment is the SAME
            # validated clamp the engine runs with (config — modeled
            # for the TPU target, where the interpret cap is inactive).
            if stream_seg_blocks is None:
                from locust_tpu.config import fused_stream_seg_blocks

                stream_seg_blocks = fused_stream_seg_blocks(
                    emits_per_block, block_lines, on_tpu=True
                )
            seg = max(1, int(stream_seg_blocks))
            n_segments = -(-n_blocks // seg)
            seg_resid_rows = seg * resid_rows
            settle_rows = table_size + t_hi * t_lo + seg_resid_rows
            passes = sort_pass_count(settle_rows, "fused")
            per_segment = (
                seg * (line_bytes + resid_bytes)
                + flush_bytes
                + settle_rows * (2 * per_pass * passes + gather)
            )
            out.update(
                rows_per_sort=settle_rows,
                sort_passes=passes,
                stream_seg_blocks=seg,
                n_segments=n_segments,
                est_kernel_bytes=int(
                    n_segments * (seg * (line_bytes + resid_bytes)
                                  + flush_bytes)
                ),
                est_sort_traffic_bytes=int(n_segments * per_segment),
            )
            return out
        kernel_bytes = line_bytes + flush_bytes + resid_bytes
        if fused_variant == "mesh":
            # Per shard-block: kernel bytes + the local-combine-
            # replacement sweeps over the pre-aggregated rows (shuffle /
            # shard merge unchanged by the mode, not modeled).
            preagg_rows = t_hi * t_lo + resid_rows
            passes = sort_pass_count(preagg_rows, "fused")
            per_block = kernel_bytes + preagg_rows * (
                2 * per_pass * passes + gather
            )
        else:  # "batch" — the v1 per-block acc->settle->acc model
            settle_rows = table_size + t_hi * t_lo + resid_rows
            preagg_rows = settle_rows
            passes = sort_pass_count(settle_rows, "fused")
            per_block = kernel_bytes + settle_rows * (
                2 * per_pass * passes + gather
            )
        out.update(
            rows_per_sort=preagg_rows,
            sort_passes=passes,
            est_kernel_bytes=int(n_blocks * kernel_bytes),
            est_sort_traffic_bytes=int(n_blocks * per_block),
        )
        return out
    per_pass, gather = mode_row_bytes(sort_mode, key_lanes)
    n_rows = table_size + emits_per_block
    passes = sort_pass_count(n_rows, sort_mode)
    # Each pass reads and writes every operand byte.
    per_block = n_rows * (2 * per_pass * passes + gather)
    out = {
        "sort_mode": sort_mode,
        "rows_per_sort": n_rows,
        "sort_passes": passes,
        "n_blocks": n_blocks,
    }
    if sort_mode == "hasht-mxu":
        # The one-hot term: per probe round the combine materializes and
        # contracts bf16 one-hot operands (the 5 weight planes ride the
        # hi operand — hash_table.mxu_scatter_add's [n, 5*t_hi] lhs and
        # [n, t_lo] rhs, write + read = x2x2) plus one fp32 partial
        # histogram per chunk.  Grid/chunk read from the SAME validated
        # config values the kernel runs with (config.hasht_mxu_grid) so
        # the modeled bytes can't drift from the contraction's operands.
        from locust_tpu.config import (
            HASHT_MXU_CHUNK,
            HASHT_PROBES,
            hasht_mxu_grid,
        )

        t_hi, t_lo = hasht_mxu_grid(table_size)
        n_chunks = max(1, -(-n_rows // HASHT_MXU_CHUNK))
        onehot = HASHT_PROBES * (
            n_rows * 2 * 2 * (5 * t_hi + t_lo)
            + n_chunks * 4 * 5 * t_hi * t_lo
        )
        per_block += onehot
        out["est_onehot_bytes"] = int(n_blocks * onehot)
        out["mxu_grid"] = [t_hi, t_lo]
    out["est_sort_traffic_bytes"] = int(n_blocks * per_block)
    return out


def summarize(
    sort_mode: str,
    key_lanes: int,
    emits_per_block: int,
    table_size: int,
    n_blocks: int,
    elapsed_s: float,
    device_kind: str | None,
    block_lines: int | None = None,
    line_width: int | None = None,
    fused_variant: str = "batch",
    stream_seg_blocks: int | None = None,
) -> dict:
    """The bench-facing roofline row: traffic model + achieved vs peak."""
    out = pipeline_sort_traffic(
        sort_mode, key_lanes, emits_per_block, table_size, n_blocks,
        block_lines=block_lines, line_width=line_width,
        fused_variant=fused_variant, stream_seg_blocks=stream_seg_blocks,
    )
    gb = out["est_sort_traffic_bytes"] / 1e9
    achieved = gb / elapsed_s if elapsed_s > 0 else 0.0
    out["est_sort_traffic_gb"] = round(gb, 3)
    out["achieved_sort_gb_s"] = round(achieved, 2)
    out["device_kind"] = device_kind
    peak = PEAK_HBM_GB_S.get(device_kind or "")
    out["hbm_peak_gb_s"] = peak
    out["hbm_utilization_pct"] = (
        round(100.0 * achieved / peak, 2) if peak else None
    )
    out["model"] = "bitonic k(k+1)/2 passes, sort-only, see utils/roofline.py"
    return out
