"""Map stage: data-parallel tokenization into fixed-slot KV emits.

TPU-native replacement for the reference's map kernel (``map()``/``kernMap``,
reference MapReduce/src/main.cu:136-159), which runs one CUDA thread per line
looping ``my_strtok_r`` sequentially and emitting ``(word, 1)`` into fixed
slot ``line*EMITS_PER_LINE + count`` with a cap of EMITS_PER_LINE=20
(main.cu:19,145-147).

Here the whole block tokenizes in one fused pass of vectorized ops:
delimiter masks -> token-start/end masks -> prefix-sum token ids -> a
one-hot reduction that turns "the e-th token of line l starts at byte w"
into a dense ``[lines, emits]`` index table -> key-byte extraction as an
MXU matmul.  No sequential loop, no thread divergence, static shapes.

Key-byte extraction rides the MXU: an element gather
(``keys[l,e,k] = lines[l, start[l,e]+k]``) lowers to a scalar gather that
is ~12x slower than the rest of the stage combined on TPU v5e; instead the
one-hot start mask contracts against ``key_width`` shifted copies of the
line bytes — ``einsum('lwe,lwk->lek', onehot, shifted)`` in bfloat16
(bytes 0..255 and 0/1 indicators are exact in bf16; accumulation in f32).
That is the standard TPU gather-as-matmul trick: the systolic array does
scattered reads as dense FLOPs.

The fixed-slot emit contract is preserved (same capacity semantics as
main.cu:145): each line owns ``emits_per_line`` slots; excess tokens are
dropped and counted (the reference printf-warns and drops, main.cu:141-144).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from locust_tpu.config import EngineConfig
from locust_tpu.core import bytes_ops
from locust_tpu.core.kv import KVBatch


class TokenizeResult(NamedTuple):
    keys: jax.Array      # uint8 [lines, emits_per_line, key_width]
    valid: jax.Array     # bool  [lines, emits_per_line]
    overflow: jax.Array  # int32 [] — tokens dropped beyond the per-line cap


def tokenize_block(lines: jax.Array, cfg: EngineConfig) -> TokenizeResult:
    """Tokenize a ``[block_lines, line_width]`` uint8 block.

    Pure-jnp formulation (the Pallas variant lives in ops/pallas/); XLA fuses
    the mask/compare chain into a couple of VPU passes plus one gather.
    """
    num_lines, width = lines.shape
    emits, key_w = cfg.emits_per_line, cfg.key_width

    in_token = ~bytes_ops.delimiter_mask(lines)            # [L, W]
    starts = bytes_ops.token_starts(in_token)              # [L, W]
    tid = bytes_ops.token_ids(starts)                      # [L, W]

    slot = jnp.arange(emits, dtype=jnp.int32)              # [E]
    ntok = jnp.sum(starts.astype(jnp.int32), axis=-1)      # [L]
    valid = slot[None, :] < jnp.minimum(ntok, emits)[:, None]

    # keys[l,e,k] = lines[l, start[l,e]+k], formulated per backend
    # (cfg.map_impl).
    padded = jnp.pad(lines, ((0, 0), (0, key_w)))
    impl = cfg.map_impl
    if impl == "auto":
        impl = "einsum" if jax.default_backend() == "tpu" else "gather"
    if impl == "einsum":
        # MXU contraction (see module docstring): one-hot "token e of
        # line l starts at byte w" x key_width shifted byte planes.
        start_oh = starts[..., None] & (tid[..., None] == slot)  # [L, W, E]
        shifted = jnp.stack(
            [padded[:, k : k + width] for k in range(key_w)], axis=-1
        )                                                   # [L, W, K] uint8
        gathered = jnp.einsum(
            "lwe,lwk->lek",
            start_oh.astype(jnp.bfloat16),
            shifted.astype(jnp.bfloat16),
            preferred_element_type=jnp.float32,
        ).astype(jnp.uint8)                                 # exact: bytes<256
    else:
        # Plain gather: scatter each token's start column into its emit
        # slot (each live (line, slot) written at most once — token ids
        # are unique per start), then one take_along_axis over the
        # NUL-padded row.  O(L*W + L*E*K) scalar work instead of the
        # einsum's L*W*E*K multiply-adds — the right trade everywhere
        # EXCEPT the MXU.  Non-starts and overflow tokens land in an
        # explicit dump slot (index ``emits``, sliced off) so every write
        # is in-bounds — a mode="drop" OOB write would trip the checkify
        # index guard the debug pipeline runs under.  Invalid slots
        # gather from column 0; `valid` masks them below.
        w_col = jnp.broadcast_to(
            jnp.arange(width, dtype=jnp.int32)[None, :], lines.shape
        )
        slot_of_col = jnp.where(
            starts, jnp.minimum(tid, emits), emits
        )                                                   # [L, W] in [0,E]
        start_idx = (
            jnp.zeros((num_lines, emits + 1), dtype=jnp.int32)
            .at[jnp.arange(num_lines, dtype=jnp.int32)[:, None], slot_of_col]
            .set(w_col)[:, :emits]
        )                                                   # [L, E]
        idx = start_idx[:, :, None] + jnp.arange(key_w, dtype=jnp.int32)
        gathered = jnp.take_along_axis(
            padded, idx.reshape(num_lines, -1), axis=1
        ).reshape(num_lines, emits, key_w)                  # [L, E, K] uint8

    # Token end masking needs no end-index table: a token's bytes run until
    # its first delimiter (NUL pad included in the delimiter set), so the
    # running all-non-delimiter AND over the gathered window IS the key
    # mask.  Tokens longer than key_w truncate, matching the reference's
    # 30-byte key field (KeyValue.h:15).  The prefix-AND runs as log2(K)
    # shifted ANDs rather than a cumprod: XLA lowers cumprod to a serial
    # scan that costs ~2x the whole rest of the tail on CPU (measured
    # 8.9ms vs 5.0ms at [8192, 17, 16]), while K is a tiny static width.
    live = ~bytes_ops.delimiter_mask(gathered)              # [L, E, K]
    shift = 1
    while shift < key_w:
        live = live & jnp.concatenate(
            [jnp.ones_like(live[..., :shift]), live[..., :-shift]], axis=-1
        )
        shift *= 2
    keys = jnp.where(live & valid[..., None], gathered, jnp.uint8(0))

    overflow = jnp.sum(jnp.maximum(ntok - emits, 0))
    return TokenizeResult(keys=keys, valid=valid, overflow=overflow)


def wordcount_map(lines: jax.Array, cfg: EngineConfig) -> tuple[KVBatch, jax.Array]:
    """The WordCount map_fn: emit ``(token, 1)`` per token.

    Returns the flat emit batch ``[block_lines * emits_per_line]`` and the
    overflow counter — the analog of the reference's per-line fixed-slot emit
    table ``dev_map_kvs[MAX_EMITS]`` (main.cu:20,392).

    ``cfg.use_pallas`` selects the hand-written VMEM-resident kernel
    (ops/pallas/tokenize.py); interpret mode engages automatically off-TPU.
    """
    if cfg.use_pallas:
        from locust_tpu.ops.pallas.tokenize import tokenize_block_pallas

        interpret = jax.default_backend() != "tpu"
        keys, valid, overflow = tokenize_block_pallas(lines, cfg, interpret)
    else:
        res = tokenize_block(lines, cfg)
        keys, valid, overflow = res.keys, res.valid, res.overflow
    flat_keys = keys.reshape(-1, cfg.key_width)
    flat_valid = valid.reshape(-1)
    values = jnp.ones(flat_keys.shape[0], dtype=jnp.int32)
    return KVBatch.from_bytes(flat_keys, values, flat_valid), overflow
