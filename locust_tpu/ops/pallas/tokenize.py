"""Pallas TPU kernel for the Map stage tokenizer.

The jnp tokenizer (ops/map_stage.py) materializes ``[lines, width, emits]``
one-hot tensors for the slot-index reduction; whether those round-trip HBM
is up to XLA's fusion heuristics.  This kernel pins the whole per-tile
working set in VMEM and never builds a 3-D intermediate: the emit-slot loop
is statically unrolled (emits_per_line is a small config constant, the
reference's EMITS_PER_LINE=20, main.cu:19), and each (slot, byte) output is
a masked VPU reduction over the line.

Replaces the reference's one-CUDA-thread-per-line ``kernMap``
(reference MapReduce/src/main.cu:155-159) whose inner ``my_strtok_r`` loop
is inherently sequential per thread; here every line in the tile advances
in lockstep vector operations.

Grid: one program per tile of ``TILE_LINES`` lines.  uint8 inputs use the
(32, 128) min tile, so TILE_LINES is a multiple of 32 and line_width a
multiple of 128.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from locust_tpu.config import DELIMITERS, EngineConfig

TILE_LINES = 64


def _tokenize_kernel(x_ref, keys_ref, valid_ref, ovf_ref, *, emits, key_w, width):
    x = x_ref[:]  # [T, W] uint8
    xi = x.astype(jnp.int32)

    # Delimiter classification, statically unrolled over the delimiter set
    # (reference delimiters, main.cu:138, + NUL pad + CR/LF).  Compare on
    # the int32 widening: v5e Mosaic rejects i8 vector compares
    # ("Target does not support this comparison", measured on-hardware).
    is_delim = xi == 0
    for c in DELIMITERS + b"\n\r":
        is_delim = is_delim | (xi == c)
    in_tok = ~is_delim

    # Neighbour shifts run on the int32 widening and compare afterwards:
    # Mosaic has no lane shift of an i1 mask vector ("Invalid vector
    # register cast ... tpu.bitcast_vreg (vector<8x128xi1>)").
    tok_i = in_tok.astype(jnp.int32)
    zeros_col = jnp.zeros((x.shape[0], 1), dtype=jnp.int32)
    prev = jnp.concatenate([zeros_col, tok_i[:, :-1]], axis=1)
    nxt = jnp.concatenate([tok_i[:, 1:], zeros_col], axis=1)
    starts = in_tok & (prev == 0)
    ends = in_tok & (nxt == 0)
    # Inclusive prefix sum along the line, as a statically-unrolled
    # Hillis-Steele doubling scan: log2(W) shift-adds.  (jnp.cumsum has no
    # Pallas TPU lowering; this form is plain vector adds.)
    csum = starts.astype(jnp.int32)
    shift = 1
    while shift < width:
        pad = jnp.zeros((csum.shape[0], shift), dtype=jnp.int32)
        csum = csum + jnp.concatenate([pad, csum[:, :-shift]], axis=1)
        shift *= 2
    tid = csum - 1                                          # [T, W]
    pos = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)   # [T, W]

    ntok = jnp.sum(starts.astype(jnp.int32), axis=1, keepdims=True)  # [T, 1]
    ovf_ref[:] = jnp.maximum(ntok - emits, 0)

    for e in range(emits):  # static unroll: emits is a config constant
        sel = tid == e
        m_start = (starts & sel).astype(jnp.int32)
        m_end = (ends & sel).astype(jnp.int32)
        s_idx = jnp.sum(pos * m_start, axis=1, keepdims=True)   # [T, 1]
        e_idx = jnp.sum(pos * m_end, axis=1, keepdims=True)     # [T, 1]
        has_tok = jnp.sum(m_start, axis=1, keepdims=True) > 0   # [T, 1]
        tok_len = jnp.clip(e_idx - s_idx + 1, 0, key_w)
        valid_ref[:, e : e + 1] = has_tok.astype(jnp.int32)
        for k in range(key_w):  # static unroll: key bytes
            # Byte k of slot e = x[l, s_idx + k], as a masked VPU reduction.
            hit = (pos == s_idx + k) & has_tok & (k < tok_len)
            byte = jnp.sum(xi * hit.astype(jnp.int32), axis=1, keepdims=True)
            keys_ref[:, e * key_w + k : e * key_w + k + 1] = byte.astype(
                jnp.uint8
            )


@functools.partial(jax.jit, static_argnames=("cfg", "interpret"))
def tokenize_block_pallas(
    lines: jax.Array, cfg: EngineConfig, interpret: bool = False
):
    """Pallas variant of ops/map_stage.tokenize_block (same contract).

    Returns (keys [L, E, K] uint8, valid [L, E] bool, overflow int32).
    """
    num_lines, width = lines.shape
    if num_lines % TILE_LINES != 0:
        raise ValueError(f"block_lines must be a multiple of {TILE_LINES}")
    if width % 128 != 0:
        # uint8 tiles are (32, 128): a non-multiple width would misalign
        # every VMEM block (module docstring constraint, now enforced).
        raise ValueError(f"line_width must be a multiple of 128, got {width}")
    emits, key_w = cfg.emits_per_line, cfg.key_width
    grid = (num_lines // TILE_LINES,)

    kernel = functools.partial(
        _tokenize_kernel, emits=emits, key_w=key_w, width=width
    )
    keys, valid, ovf = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((TILE_LINES, width), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=(
            pl.BlockSpec((TILE_LINES, emits * key_w), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((TILE_LINES, emits), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((TILE_LINES, 1), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((num_lines, emits * key_w), jnp.uint8),
            jax.ShapeDtypeStruct((num_lines, emits), jnp.int32),
            jax.ShapeDtypeStruct((num_lines, 1), jnp.int32),
        ),
        interpret=interpret,
    )(lines)
    return (
        keys.reshape(num_lines, emits, key_w),
        valid.astype(bool),
        jnp.sum(ovf),
    )
