"""Pallas TPU bitonic sort for the Process stage.

The Process-stage sort is where the reference's target is won or lost
(94% of its GPU runtime: reference MapReduce/src/main.cu:414-415 region);
ours runs on stock ``lax.sort``, whose TPU lowering streams every sort
operand through HBM on each of ~k(k+1)/2 compare-exchange passes
(k = ceil(log2 n) ~ 20 at engine shape -> ~210 passes).  A bitonic
network has a locality structure XLA does not exploit: every substage
with compare distance d < tile operates INSIDE an aligned tile, so one
VMEM-resident kernel invocation can run ALL such substages back-to-back,
paying ONE HBM round-trip where the stock sort pays dozens.

Structure (n padded to 2^k, element e lives at [row e//128, lane e%128]):

  * stage s = 1..k, substage t = s..1, distance d = 2^(t-1);
    partner(e) = e ^ d; block direction asc = ((e >> s) & 1) == 0;
    the lower partner keeps the min iff asc (Batcher's network).
  * substages with d <= tile/2 are tile-local -> fused Pallas kernel
    (grid over tiles, key + payload operands pinned in VMEM; lane-dim
    exchanges (d < 128) via jnp.roll along lanes, sublane-dim exchanges
    via a leading-axis reshape swap).
  * substages with d >= tile are a single elementwise pass each — plain
    XLA on a [n/2d, 2, d-elements] view (one fused read+write of the
    array; no Pallas needed, there is no reuse to exploit).

HBM round-trips: 1 + sum_{s=m+1..k} (s - m + 1) where 2^m = tile
(e.g. ~21 at n=2^20, tile=2^15) vs ~210 operand streamings for the
stock network — the "hand-managed VMEM" formulation of the one-pass
rank/cumsum idea that made the pure-XLA radix attempt lose
(ops/radix_sort.py: its per-pass gathers go to HBM; here they stay in
VMEM).  That count assumes unlimited fusion; when BITONIC_MAX_FUSED
caps the substages per launch (the Mosaic compile-size mitigation),
the true count is ``len(config.bitonic_schedule(k, m))`` — the launch
plan this kernel executes.

The engine-facing mode ("bitonic", config.SORT_MODES) sorts the folded
31-bit-hash+validity key (process_stage._folded_key, same collision
story as "hash1") and carries the row as payload (same payload-carriage
win as "hashp").  Correctness is oracle-tested in interpret mode off-TPU;
on the chip, chip_smoke.py's kernels phase holds the compiled kernel to
jax.lax.sort bit for bit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from locust_tpu.config import BITONIC_TILE_ROWS, bitonic_schedule

# Default tile: 2^15 elements = 256 rows x 128 lanes.  Working set per
# operand = 128KB; key + 9 payload operands (key_width 32) = 1.25MB of
# VMEM — comfortable, and m=15 leaves few cross stages.  A constant of
# config.py (with bitonic_schedule, which needs it); where the knee is
# has not been measured on this machine.
TILE_ROWS = BITONIC_TILE_ROWS

_LANES = 128


def _ilog2(n: int) -> int:
    b = n.bit_length() - 1
    if n != (1 << b):
        raise ValueError(f"{n} is not a power of two")
    return b


def _compare_exchange(arrs, pv, keep_min_i):
    """One compare-exchange: arrs[0] is the key; every operand takes its
    partner's value where the key decision says so.  Ties never swap, so
    the two partners always agree.  ``keep_min_i`` is int32 0/1 and the
    lt/gt outcomes are widened to int32 before the select: a select whose
    OPERANDS are bools lowers to ``arith.trunci i8 -> i1``, which v5e
    Mosaic rejects ("Unsupported target bitwidth for truncation",
    measured on-hardware 2026-07-31) — masks may be i1, data may not."""
    key, pkey = arrs[0], pv[0]
    lt = (pkey < key).astype(jnp.int32)
    gt = (pkey > key).astype(jnp.int32)
    take = jnp.where(keep_min_i != 0, lt, gt) != 0
    return [jnp.where(take, p, a) for a, p in zip(arrs, pv)]


def _local_stages_kernel(*refs, stages, tile_rows, n_ops):
    """Run ``stages`` = ((s, t_hi, t_lo), ...) with every substage
    t_hi..t_lo tile-local in VMEM.  refs = n_ops inputs then n_ops
    outputs (aliased)."""
    ins, outs = refs[:n_ops], refs[n_ops:]
    arrs = [r[:] for r in ins]
    base = pl.program_id(0) * tile_rows * _LANES
    row = jax.lax.broadcasted_iota(jnp.int32, (tile_rows, _LANES), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (tile_rows, _LANES), 1)
    gidx = base + row * _LANES + lane

    for s, t_hi, t_lo in stages:
        asc_i = ((gidx >> s) & 1) ^ 1  # int32 1 = ascending block
        for t in range(t_hi, t_lo - 1, -1):
            d = 1 << (t - 1)
            # int32 throughout (no i1==i1 compares, no bool-operand
            # selects — see _compare_exchange for the Mosaic constraint).
            is_lower_i = ((gidx & d) == 0).astype(jnp.int32)
            keep_min_i = 1 - (asc_i ^ is_lower_i)
            if d < _LANES:
                # Lane-dim exchange: partner lane = lane ^ d.  l + d keeps
                # bit d set iff it was clear, so the two rotations cover
                # both partner directions; the wrapped values are never
                # selected.  Rotation is spelled slice+concat rather than
                # jnp.roll: roll's lowering drops the varying-manual-axes
                # type under shard_map(check_vma=True), poisoning every
                # downstream compare (jax issue) —
                # slice/concat propagate vma correctly and lower the same.
                def _rot(a, k):  # left-rotate lanes by k
                    return jnp.concatenate([a[:, k:], a[:, :k]], axis=1)

                down = [_rot(a, d) for a in arrs]
                up = [_rot(a, _LANES - d) for a in arrs]
                pv = [
                    jnp.where((lane & d) == 0, dn, u)
                    for dn, u in zip(down, up)
                ]
            else:
                # Sublane-dim exchange: partner row = row ^ (d/128); an
                # aligned leading-axis reshape turns it into a pair swap.
                dr = d // _LANES
                g = tile_rows // (2 * dr)

                def swap(a, g=g, dr=dr):
                    a4 = a.reshape(g, 2, dr, _LANES)
                    return jnp.concatenate(
                        [a4[:, 1:2], a4[:, 0:1]], axis=1
                    ).reshape(tile_rows, _LANES)

                pv = [swap(a) for a in arrs]
            arrs = _compare_exchange(arrs, pv, keep_min_i)

    for o, a in zip(outs, arrs):
        o[:] = a


def _run_local(arrs, stages, tile_rows, interpret):
    """One pallas_call over all tiles; operands aliased in-place."""
    n_ops = len(arrs)
    rows = arrs[0].shape[0]
    grid = rows // tile_rows
    spec = pl.BlockSpec(
        (tile_rows, _LANES), lambda i: (i, 0), memory_space=pltpu.VMEM
    )
    kernel = functools.partial(
        _local_stages_kernel,
        stages=tuple(stages),
        tile_rows=tile_rows,
        n_ops=n_ops,
    )

    def out_sds(a):
        # Inside shard_map with check_vma=True, pallas outputs must state
        # how they vary across mesh axes; the sort is elementwise over
        # its own shard, so each output varies exactly like its (aliased)
        # input.  Outside shard_map, vma is absent/empty — plain struct.
        typeof = getattr(jax, "typeof", None)  # absent on jax 0.4.x
        vma = getattr(typeof(a), "vma", None) if typeof else None
        if vma is not None:  # frozenset() (replicated) must pass through
            return jax.ShapeDtypeStruct(a.shape, a.dtype, vma=vma)
        return jax.ShapeDtypeStruct(a.shape, a.dtype)

    return list(
        pl.pallas_call(
            kernel,
            grid=(grid,),
            in_specs=[spec] * n_ops,
            out_specs=[spec] * n_ops,
            out_shape=[out_sds(a) for a in arrs],
            input_output_aliases={i: i for i in range(n_ops)},
            interpret=interpret,
        )(*arrs)
    )


def _run_cross(arrs, s, t):
    """One cross-tile substage (d >= tile) as a single fused XLA pass."""
    d = 1 << (t - 1)
    dr = d // _LANES
    g = arrs[0].shape[0] // (2 * dr)
    # Direction is constant over each 2d block (t <= s), so it is a
    # per-block scalar vector, broadcast over the pair.
    block_start = jnp.arange(g, dtype=jnp.int32) * 2 * d
    asc = ((block_start >> s) & 1) == 0
    asc = asc[:, None, None]

    a4 = [a.reshape(g, 2, dr, _LANES) for a in arrs]
    lo = [a[:, 0] for a in a4]
    hi = [a[:, 1] for a in a4]
    key_lo, key_hi = lo[0], hi[0]
    # Lower partner keeps min iff ascending; ties never swap.
    swap = jnp.where(asc, key_hi < key_lo, key_hi > key_lo)
    out = []
    for alo, ahi in zip(lo, hi):
        nlo = jnp.where(swap, ahi, alo)
        nhi = jnp.where(swap, alo, ahi)
        out.append(
            jnp.stack([nlo, nhi], axis=1).reshape(arrs[0].shape)
        )
    return out


def bitonic_sort(
    key: jax.Array,
    payloads: tuple[jax.Array, ...] = (),
    tile_rows: int = TILE_ROWS,
    interpret: bool = False,
    max_fused: int | None = None,
) -> tuple[jax.Array, tuple[jax.Array, ...]]:
    """Ascending sort of a uint32 ``key`` [n]; ``payloads`` ride along.

    n is padded to the next power of two with 0xFFFFFFFF keys (sorted to
    the tail, sliced off).  Not stable (equal keys may reorder) — callers
    sort hash keys whose grouping semantics tolerate that, exactly like
    lax.sort's use in the "hash*" modes.  Arrays smaller than one tile
    shrink the tile to fit (floor 8 rows, the int32 min sublane tile).

    PAD-SENTINEL CAVEAT: rows whose key is exactly 0xFFFFFFFF tie with
    the pad rows, and since ties reorder arbitrarily, the ``[:n]`` slice
    may keep a pad row (zero payloads) in place of a real sentinel-keyed
    row — the sentinel-run PAYLOADS are then not a permutation of the
    inputs.  Callers must either keep keys < 0xFFFFFFFF or not care
    about sentinel-row payloads.  The engine's "bitonic" mode is safe by
    construction: its folded key reserves 0xFFFFFFFF for INVALID rows
    (process_stage._folded_key), whose payloads are dead downstream
    (valid=False) — pinned by a test.  The on-hardware checkers generate
    keys < 0xFFFFFFFF for the same reason.
    """
    n = key.shape[0]
    if key.dtype != jnp.uint32:
        raise TypeError(f"key must be uint32, got {key.dtype}")
    pay = [p.astype(jnp.uint32) for p in payloads]
    pay_dtypes = [p.dtype for p in payloads]

    # Next power of two >= n, floor 1024 (8 sublanes x 128 lanes, the
    # int32 min tile): 2^bit_length(n-1) >= n always holds.
    n_pad = max(1 << 10, 1 << max(n - 1, 1).bit_length())
    pad = n_pad - n
    key_p = jnp.pad(key, (0, pad), constant_values=jnp.uint32(0xFFFFFFFF))
    pay_p = [jnp.pad(p, (0, pad)) for p in pay]

    rows = n_pad // _LANES
    tr = min(tile_rows, rows)
    kbits = _ilog2(n_pad)
    m = _ilog2(tr * _LANES)

    arrs = [key_p.reshape(rows, _LANES)] + [
        p.reshape(rows, _LANES) for p in pay_p
    ]
    # Execute the shared launch plan (config.bitonic_schedule): fused
    # VMEM launches for tile-local substage runs (capped at
    # BITONIC_MAX_FUSED substages each — unlimited fusion once crashed a
    # Mosaic compile, config.py), single XLA passes for cross-tile substages.
    for step in bitonic_schedule(kbits, m, max_fused):
        if step[0] == "local":
            arrs = _run_local(arrs, step[1], tr, interpret)
        else:
            arrs = _run_cross(arrs, step[1], step[2])

    out_key = arrs[0].reshape(-1)[:n]
    out_pay = tuple(
        a.reshape(-1)[:n].astype(dt)
        for a, dt in zip(arrs[1:], pay_dtypes)
    )
    return out_key, out_pay
