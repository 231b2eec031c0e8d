"""Key packing: NUL-padded key bytes <-> big-endian uint32 lanes.

The reference sorts 30-byte keys with a byte-wise comparator loop
(KIVComparator, reference MapReduce/src/KeyValue.h:20-33).  TPUs sort
integers far faster than data-dependent byte loops, and byte-wise
lexicographic order on NUL-padded equal-width keys is *exactly* elementwise
tuple order on big-endian-packed uint32 lanes — so a key_width-byte key
becomes key_width/4 uint32 sort operands and ``jax.lax.sort`` with
``num_keys=key_lanes`` reproduces the comparator's ordering with no
comparator at all.

Ordering note: we compare bytes as *unsigned* (0..255).  The reference
compares ``char`` (signed on its platforms), which differs only for
non-ASCII bytes >= 0x80; documented deliberate divergence (SURVEY.md §7.3).
NUL-padding means a proper prefix sorts before its extensions, matching
strcmp semantics.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def pack_keys(keys: jax.Array) -> jax.Array:
    """uint8 ``[..., K]`` -> big-endian uint32 lanes ``[..., K//4]``."""
    k = keys.shape[-1]
    if k % 4 != 0:
        raise ValueError(f"key width {k} not a multiple of 4")
    r = keys.reshape(*keys.shape[:-1], k // 4, 4).astype(jnp.uint32)
    return (r[..., 0] << 24) | (r[..., 1] << 16) | (r[..., 2] << 8) | r[..., 3]


def unpack_keys(lanes: jax.Array) -> jax.Array:
    """Big-endian uint32 lanes ``[..., L]`` -> uint8 bytes ``[..., 4L]``."""
    parts = jnp.stack(
        [
            (lanes >> 24) & 0xFF,
            (lanes >> 16) & 0xFF,
            (lanes >> 8) & 0xFF,
            lanes & 0xFF,
        ],
        axis=-1,
    ).astype(jnp.uint8)
    return parts.reshape(*lanes.shape[:-1], lanes.shape[-1] * 4)


def lanes_equal(a: jax.Array, b: jax.Array) -> jax.Array:
    """Row-wise key equality over the lane dim: ``my_strcmp(...) == 0``."""
    return jnp.all(a == b, axis=-1)


def _first_diff_lanes(a: jax.Array, b: jax.Array):
    """Broadcast-compare lane tuples; return (any_diff, a_at, b_at) where
    ``*_at`` are the values at the first (most significant) differing lane.

    The shared core of every lexicographic comparator here: big-endian lane
    tuple order == byte order, so the first differing lane decides.
    """
    a, b = jnp.broadcast_arrays(a, b)
    neq = a != b
    first_diff = jnp.argmax(neq, axis=-1)
    a_at = jnp.take_along_axis(a, first_diff[..., None], axis=-1)[..., 0]
    b_at = jnp.take_along_axis(b, first_diff[..., None], axis=-1)[..., 0]
    return jnp.any(neq, axis=-1), a_at, b_at


def lanes_less(a: jax.Array, b: jax.Array) -> jax.Array:
    """Row-wise lexicographic ``a < b`` over big-endian lanes.

    Equivalent to KIVComparator (KeyValue.h:20-33) on the unpacked bytes —
    without its walk-past-NUL out-of-bounds read on equal keys (SURVEY.md Q3).
    """
    any_diff, a_at, b_at = _first_diff_lanes(a, b)
    return jnp.where(any_diff, a_at < b_at, False)


def _fmix32(h: jax.Array) -> jax.Array:
    """murmur3 finalizer: a full-avalanche bijection on uint32."""
    h ^= h >> 16
    h = h * jnp.uint32(0x85EBCA6B)
    h ^= h >> 13
    h = h * jnp.uint32(0xC2B2AE35)
    h ^= h >> 16
    return h


def _salted_fold(lanes: jax.Array, salt_prime: int, pre_mul: int | None) -> jax.Array:
    """fmix32(sum_i fmix32(lane_i ^ salt_i)): one vectorized pass over lanes.

    Deliberately NOT a sequential per-lane fold (h = (h^lane)*prime):
    column-at-a-time reads of a fused producer make XLA recompute the whole
    upstream tokenize chain once per read — measured ~12x the cost of the
    entire map stage on TPU v5e.  The commutative salted-sum form reads the
    ``[N, L]`` lane array in one elementwise pass + one lane-axis reduction;
    position sensitivity comes from per-lane salts, avalanche from fmix32.
    Non-cryptographic, same grade as murmur/xxHash.
    """
    n_lanes = lanes.shape[-1]
    i = jnp.arange(n_lanes, dtype=jnp.uint32)
    salts = (i + 1) * jnp.uint32(salt_prime)
    x = lanes if pre_mul is None else lanes * jnp.uint32(pre_mul)
    per_lane = _fmix32(x ^ salts)  # trailing-dim broadcast: any leading rank
    return _fmix32(jnp.sum(per_lane, axis=-1, dtype=jnp.uint32))


def hash_pair(lanes: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Two independent uint32 mixing hashes of packed key lanes.

    Together they act as a 64-bit grouping hash for the "hash" sort mode
    (ops/process_stage.py): sorting by (h1, h2) groups equal keys adjacently
    with 3 sort operands instead of key_lanes+1.  Distinct keys colliding in
    all 64 bits (~n^2/2^64 per block) could interleave within their hash run;
    downstream segment boundaries compare FULL key lanes, so the failure mode
    is a duplicated table row, which the host-side finalize re-merges.
    """
    h1 = _salted_fold(lanes, 0x9E3779B9, None)
    h2 = _salted_fold(lanes, 0xC2B2AE3D, 0x01000193)
    return h1, h2


def fold_hash(lanes: jax.Array) -> jax.Array:
    """uint32 mixing hash of packed key lanes (for shuffle bucketing).

    Used by the distributed shuffle to hash-partition keys across mesh
    devices (SURVEY.md §2.3 "TPU-native plan" for the shuffle).  Uses a
    salt distinct from both hash_pair streams so shuffle bucketing is
    uncorrelated with sort order.
    """
    return _salted_fold(lanes, 0x85EBCA77, None)
