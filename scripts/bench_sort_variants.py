"""Micro-bench: sort strategies for the Process stage, on the real device.

Compares (per N rows, 8 key lanes):
  A. lex:    lax.sort with 9 keys (invalid + lanes) + value payload
  B. hash64: lax.sort with 3 keys (invalid, h1, h2) + index payload, gather
             after — using the SHIPPED packing.hash_pair (salted-sum form)
  C. hash64: same 3 keys but rows ride as sort payloads (no gather)

Checksums force full materialization: on remote-TPU links,
block_until_ready alone does not reliably block.

Usage: [N=393216] python scripts/bench_sort_variants.py [--backend auto|cpu|tpu]
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from locust_tpu.config import compile_cache_dir  # noqa: E402 - jax-free

compile_cache_dir()

N = int(os.environ.get("N", 393216))
L = 8


def variant_a(lanes, values, valid):
    import jax
    import jax.numpy as jnp

    invalid = (~valid).astype(jnp.uint32)
    operands = (invalid, *(lanes[:, i] for i in range(L)), values)
    out = jax.lax.sort(operands, num_keys=1 + L)
    return jnp.sum(out[1]) + jnp.sum(out[-1].astype(jnp.uint32))


def variant_b(lanes, values, valid):
    import jax
    import jax.numpy as jnp

    from locust_tpu.core import packing

    invalid = (~valid).astype(jnp.uint32)
    h1, h2 = packing.hash_pair(lanes)
    idx = jnp.arange(N, dtype=jnp.int32)
    _, _, _, sidx = jax.lax.sort((invalid, h1, h2, idx), num_keys=3)
    return jnp.sum(lanes[sidx, 0]) + jnp.sum(values[sidx].astype(jnp.uint32))


def variant_c(lanes, values, valid):
    """hash keys, but rows ride as sort PAYLOADS (no post-sort gather)."""
    import jax
    import jax.numpy as jnp

    from locust_tpu.core import packing

    invalid = (~valid).astype(jnp.uint32)
    h1, h2 = packing.hash_pair(lanes)
    out = jax.lax.sort(
        (invalid, h1, h2, *(lanes[:, i] for i in range(L)), values),
        num_keys=3,
    )
    return jnp.sum(out[3]) + jnp.sum(out[-1].astype(jnp.uint32))


def variant_d(lanes, values, valid):
    """ONE 32-bit sort key: 31-bit hash, validity in the top bit; gather.

    Collisions between distinct keys rise to ~n^2/2^31 per sort, but the
    engine's segment reduce compares full key lanes at boundaries, so a
    collision only duplicates a table row (re-merged on the next fold or
    in the host finalize) — same safety argument as the 64-bit hash mode
    at ~2x the sort-key bandwidth savings.
    """
    import jax
    import jax.numpy as jnp

    from locust_tpu.core import packing

    h1, _ = packing.hash_pair(lanes)
    key = jnp.where(valid, h1 >> 1, jnp.uint32(0xFFFFFFFF))
    idx = jnp.arange(N, dtype=jnp.int32)
    _, sidx = jax.lax.sort((key, idx), num_keys=1)
    return jnp.sum(lanes[sidx, 0]) + jnp.sum(values[sidx].astype(jnp.uint32))


def variant_e(lanes, values, valid):
    """LSD radix sort (pure XLA): 4x8-bit counting passes over the 32-bit
    folded key — an O(n) alternative to lax.sort's comparison network."""
    import jax.numpy as jnp

    from locust_tpu.core import packing
    from locust_tpu.ops.radix_sort import radix_argsort

    h1, _ = packing.hash_pair(lanes)
    key = jnp.where(valid, h1 >> 1, jnp.uint32(0xFFFFFFFF))
    sidx = radix_argsort(key)
    return jnp.sum(lanes[sidx, 0]) + jnp.sum(values[sidx].astype(jnp.uint32))


def variant_f(lanes, values, valid):
    """radix with 64 buckets x 6 passes: 4x less one-hot traffic per pass
    than 8-bit digits at 1.5x the passes — net ~2.7x less bandwidth."""
    import jax.numpy as jnp

    from locust_tpu.core import packing
    from locust_tpu.ops.radix_sort import radix_argsort

    h1, _ = packing.hash_pair(lanes)
    key = jnp.where(valid, h1 >> 1, jnp.uint32(0xFFFFFFFF))
    sidx = radix_argsort(key, bits=6)
    return jnp.sum(lanes[sidx, 0]) + jnp.sum(values[sidx].astype(jnp.uint32))


def variant_g(lanes, values, valid):
    """2 sort keys + payload-carry: validity folded into the top bit of a
    31-bit primary hash (as variant D), full h2 as tiebreaker — one fewer
    key operand than C at the same grouping guarantee (31+32 tiebreak bits;
    the engine's segment reduce compares full lanes at boundaries anyway)."""
    import jax
    import jax.numpy as jnp

    from locust_tpu.core import packing

    h1, h2 = packing.hash_pair(lanes)
    key = jnp.where(valid, h1 >> 1, jnp.uint32(0xFFFFFFFF))
    out = jax.lax.sort(
        (key, h2, *(lanes[:, i] for i in range(L)), values), num_keys=2
    )
    return jnp.sum(out[2]) + jnp.sum(out[-1].astype(jnp.uint32))


def variant_h(lanes, values, valid):
    """Pallas bitonic tiles (ops/pallas/sort.py): variant D's folded
    single key with variant C's payload carriage, tile-local compare
    passes fused in VMEM — the hand-written kernel the engine exposes as
    sort_mode="bitonic"."""
    import jax
    import jax.numpy as jnp

    from locust_tpu.core import packing
    from locust_tpu.ops.pallas.sort import bitonic_sort

    h1, _ = packing.hash_pair(lanes)
    key = jnp.where(valid, h1 >> 1, jnp.uint32(0xFFFFFFFF))
    interpret = jax.default_backend() != "tpu"
    _, pays = bitonic_sort(
        key,
        tuple(lanes[:, i] for i in range(L)) + (values,),
        interpret=interpret,
    )
    return jnp.sum(pays[0]) + jnp.sum(pays[-1].astype(jnp.uint32))


def variant_i(lanes, values, valid):
    """1 sort key + payload-carry: variant D's folded 31-bit key with
    variant C's payload carriage and no tiebreaker — the minimum-traffic
    lax.sort formulation, exposed by the engine as sort_mode="hashp1"
    (one fewer key operand than G; collision story identical to D)."""
    import jax
    import jax.numpy as jnp

    from locust_tpu.core import packing

    h1, _ = packing.hash_pair(lanes)
    key = jnp.where(valid, h1 >> 1, jnp.uint32(0xFFFFFFFF))
    out = jax.lax.sort(
        (key, *(lanes[:, i] for i in range(L)), values), num_keys=1
    )
    return jnp.sum(out[1]) + jnp.sum(out[-1].astype(jnp.uint32))


def variant_j(lanes, values, valid):
    """SORT-FREE aggregation probe: scatter-add into a hash-bucket table.

    The engine's Process+Reduce exists to produce per-key totals; a hash
    table does that in O(n) single-pass traffic instead of O(n log^2 n)
    sort passes — IF the backend's scatter-with-duplicate-indices is not
    serialized.  This variant times the three primitives such an engine
    mode would be built from, at the real shape:

      * scatter-add of values into table_size buckets (duplicate indices),
      * scatter-max claiming a representative key per bucket,
      * per-row gather-back + compare (the collision-verify pass that
        routes mismatched rows to a tiny sort-based fallback).

    It does NOT produce the engine's exact output (collided rows would
    need the fallback pass); it measures whether the primitives leave the
    sort's measured 0.58s/33.6MB far enough behind to justify building
    that mode.  Recorded like every variant; adoption only ever follows
    an engine-level A/B.
    """
    import jax.numpy as jnp

    from locust_tpu.core import packing

    T = 65536  # resolved_table_size at bench shapes
    h1, h2 = packing.hash_pair(lanes)
    folded = jnp.where(valid, h1 >> 1, jnp.uint32(0xFFFFFFFF))
    bucket = (h1 ^ h2) & jnp.uint32(T - 1)
    counts = jnp.zeros(T, jnp.int32).at[bucket].add(
        jnp.where(valid, values, 0), mode="drop"
    )
    claimed = jnp.zeros(T, jnp.uint32).at[bucket].max(
        jnp.where(valid, folded, jnp.uint32(0)), mode="drop"
    )
    mismatch = valid & (claimed[bucket] != folded)
    return (
        jnp.sum(counts.astype(jnp.uint32))
        + jnp.sum(mismatch.astype(jnp.uint32))
    )


def variant_k(lanes, values, valid):
    """MXU histogram probe: scatter-add spelled as a one-hot matmul.

    PRODUCTIZED (round 6) as ``ops/hash_table.mxu_scatter_add`` behind
    engine sort mode "hasht-mxu" — this probe stays as the cheap
    primitive-level A/B against variant J (the exact engine spelling
    adds value limbs + the hit plane for bit-exactness; the engine-level
    verdict is the benchmark's to give).  Decompose
    the bucket id as ``hi * 512 + lo`` and accumulate
    ``counts2d[h, l] = sum_n value_n * onehot_hi[n, h] * onehot_lo[n, l]``
    — ONE ``[128, n] x [n, 512]`` bf16 contraction on the MXU (~47
    GMACs at sweep shape ~ 0.5 ms of v5e MXU time; one-hot traffic
    ~0.9 GB vs the sort's ~14 GB model).  bf16 one-hot entries and
    sub-256 values are exact; f32 accumulation is exact below 2^24 per
    bucket.  Like J this measures the PRIMITIVE — an engine mode still
    needs the representative-key claim/verify ladder for exactness —
    and adoption only ever follows an engine-level A/B.
    """
    import jax.numpy as jnp

    from locust_tpu.core import packing

    T_HI, T_LO = 128, 512  # 65536 buckets as a [128, 512] grid
    h1, h2 = packing.hash_pair(lanes)
    bucket = ((h1 ^ h2) & jnp.uint32(T_HI * T_LO - 1)).astype(jnp.int32)
    hi = bucket >> 9
    lo = bucket & (T_LO - 1)
    w = jnp.where(valid, values, 0).astype(jnp.bfloat16)
    oh_hi = (
        hi[:, None] == jnp.arange(T_HI, dtype=jnp.int32)[None, :]
    ).astype(jnp.bfloat16)
    oh_lo = (
        lo[:, None] == jnp.arange(T_LO, dtype=jnp.int32)[None, :]
    ).astype(jnp.bfloat16)
    counts2d = jnp.einsum(
        "nh,nl->hl",
        oh_hi * w[:, None],
        oh_lo,
        preferred_element_type=jnp.float32,
    )
    return jnp.sum(counts2d).astype(jnp.uint32)


VARIANTS = [
    ("A_lex9", variant_a),
    ("B_hash3_gather", variant_b),
    ("C_hash3_payload", variant_c),
    ("D_hash1_gather", variant_d),
    ("E_radix4x8", variant_e),
    ("F_radix6x6", variant_f),
    ("G_hash2_payload", variant_g),
    ("H_bitonic_pallas", variant_h),
    ("I_hash1_payload", variant_i),
    ("J_scatter_agg", variant_j),
    ("K_mxu_hist", variant_k),
]


def timeit(fn, *args, reps=5):
    import jax

    f = jax.jit(fn)
    t0 = time.perf_counter()
    float(f(*args))
    compile_s = time.perf_counter() - t0
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        float(f(*args))
        best = min(best, time.perf_counter() - t0)
    return compile_s, best * 1e3


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", default="auto", choices=["auto", "cpu", "tpu"])
    args = ap.parse_args()

    from locust_tpu.backend import select_backend

    select_backend(args.backend)
    import jax
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(0)
    lanes = jnp.asarray(
        rng.integers(0, 2**32, size=(N, L), dtype=np.uint64).astype(np.uint32)
    )
    values = jnp.asarray(rng.integers(0, 100, size=(N,), dtype=np.int32))
    valid = jnp.asarray(rng.random(N) < 0.6)

    from locust_tpu.utils import artifacts

    print(f"backend={jax.default_backend()} N={N} L={L}", flush=True)
    results = {}
    # LOCUST_SORT_VARIANTS=B,D,E runs a subset (A_lex9's 9-operand sort
    # takes minutes of XLA compile at bench shapes on TPU; skip it when
    # chip time is short).
    sel = os.environ.get("LOCUST_SORT_VARIANTS")
    if sel is None:
        chosen = list(VARIANTS)
    else:
        # Env ORDER is priority order: budgeted chip time should spend
        # its first compiles on the variants the caller cares about.
        # Unknown letters are a loud error — a mistyped selector must
        # not silently consume a chip call with zero measurements;
        # duplicates dedupe.
        by_letter = {name.split("_")[0]: (name, fn) for name, fn in VARIANTS}
        chosen, bad = [], []
        for s in dict.fromkeys(sel.upper().split(",")):
            (chosen if s in by_letter else bad).append(
                by_letter.get(s, s)
            )
        if bad or not chosen:
            raise SystemExit(
                f"LOCUST_SORT_VARIANTS: unknown variant letter(s) {bad}; "
                f"known: {sorted(by_letter)}"
            )
    force = bool(os.environ.get("LOCUST_ARTIFACT_FORCE"))
    for name, fn in chosen:
        # Error-isolate per variant: an unsupported-lowering failure on one
        # (e.g. a Mosaic rejection of the Pallas variant, measured
        # 2026-07-31: H's compile crash killed B-G's whole window) must
        # not cost the remaining variants' measurements — the error IS the
        # evidence row for that variant.
        try:
            c, ms = timeit(fn, lanes, values, valid)
            results[name] = {"compile_s": round(c, 1), "run_ms": round(ms, 3)}
            print(f"{name}: compile={c:.1f}s run={ms:.2f}ms  N={N}", flush=True)
        except Exception as e:  # noqa: BLE001 — captured as evidence
            results[name] = {"error": f"{type(e).__name__}: {e}"[:300]}
            print(f"{name}: ERROR {type(e).__name__}: {str(e)[:200]}",
                  flush=True)
        # Record after EVERY variant: a window that closes mid-run keeps
        # what it measured (consumers read the latest row of the kind).
        artifacts.record(
            "sort_variants",
            {"n_rows": N, "key_lanes": L, "variants": dict(results),
             "partial": name != chosen[-1][0]},
            force=force,
        )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
