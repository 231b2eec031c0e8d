"""LSD radix argsort in pure XLA: the optimized Process-stage sort attempt.

The reference's Process stage is ``thrust::sort`` — on its GPU, 94% of
total runtime (reference MapReduce/src/main.cu:414-415, README.md:72-80) —
and SURVEY.md §7.3.2 calls sort throughput the make-or-break of the perf
target.  ``jax.lax.sort`` on TPU lowers to a comparison network whose cost
scales ~n·log^2(n) per key operand; for the hash sort mode the keys are
machine integers, where an O(n·passes) radix sort can win.

Design (per 2^bits-bucket stable counting pass, LSD order):

  * digits            d[i]   = (key[i] >> shift) & (B-1)
  * stable rank       r[i]   = |{j < i : d[j] == d[i]}|
  * bucket bases      base[b] = exclusive-sum of the digit histogram
  * scatter           out[base[d[i]] + r[i]] = in[i]

Everything is computed with fixed-shape vectorized ops — no data-dependent
control flow, so the whole sort jits into one XLA program:

  * ranks/histograms come from a chunked one-hot cumulative sum:
    ``[chunks, chunk_len, B]`` one-hot, cumsum along the chunk axis for
    within-chunk ranks, summed for per-chunk histograms, cumsum across
    chunks for chunk offsets.  uint16 accumulators keep the one-hot
    intermediate (the bandwidth cost of the algorithm) at 2·B bytes/row.
  * the scatter is ``jnp.ndarray.at[pos].set`` — one XLA scatter per pass.

Stability makes LSD correct: pass p orders by digit p preserving the order
of passes < p, so after ceil(keybits/bits) passes the keys are fully
sorted and ties keep their original index order (needed by the engine: the
valid-first convention relies on padded rows sorting after real rows with
the same sentinel key).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("bits", "chunk", "key_bits"))
def radix_argsort(
    key: jax.Array,
    bits: int = 8,
    chunk: int = 8192,
    key_bits: int = 32,
) -> jax.Array:
    """Stable ascending argsort of a uint32 key via LSD counting passes.

    Returns an int32 permutation ``sidx`` with ``key[sidx]`` sorted and
    equal keys in original order.  ``bits`` is the digit width (B = 2^bits
    buckets per pass), ``chunk`` the row-block size of the rank cumsum,
    ``key_bits`` how many low bits of the key participate (fewer passes if
    the caller packed its information narrow).
    """
    if key.dtype != jnp.uint32:
        raise TypeError(f"radix_argsort expects uint32 keys, got {key.dtype}")
    n = key.shape[0]
    B = 1 << bits
    if B > 65536 or chunk >= 65536:
        # uint16 rank accumulators: within-chunk counts must fit.
        raise ValueError(f"bits={bits}/chunk={chunk} overflow uint16 ranks")
    n_passes = -(-key_bits // bits)

    # Pad to a chunk multiple with the max key: stability puts pad rows
    # after every real row of the same key, so perm[:n] is exactly the
    # real-row permutation.
    n_pad = -(-n // chunk) * chunk
    kpad = jnp.full((n_pad - n,), jnp.uint32(0xFFFFFFFF))
    k = jnp.concatenate([key, kpad]) if n_pad != n else key
    perm = jnp.arange(n_pad, dtype=jnp.int32)
    C = n_pad // chunk
    crange = jnp.arange(C, dtype=jnp.int32)[:, None]
    buckets = jnp.arange(B, dtype=jnp.int32)

    for p in range(n_passes):
        d = ((k >> (p * bits)) & (B - 1)).astype(jnp.int32).reshape(C, chunk)
        oh = (d[..., None] == buckets).astype(jnp.uint16)        # [C, M, B]
        within = jnp.cumsum(oh, axis=1, dtype=jnp.uint16) - oh   # exclusive
        rank = jnp.take_along_axis(within, d[..., None], axis=-1)[..., 0]
        hist = jnp.sum(oh, axis=1, dtype=jnp.uint32)             # [C, B]
        chunk_base = jnp.cumsum(hist, axis=0, dtype=jnp.uint32) - hist
        total = jnp.sum(hist, axis=0, dtype=jnp.uint32)          # [B]
        digit_base = jnp.cumsum(total, dtype=jnp.uint32) - total
        pos = (
            digit_base[d] + chunk_base[crange, d] + rank.astype(jnp.uint32)
        ).reshape(n_pad).astype(jnp.int32)
        perm = jnp.zeros_like(perm).at[pos].set(perm)
        k = jnp.zeros_like(k).at[pos].set(k)

    return perm[:n]
