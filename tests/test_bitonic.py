"""Direct unit tests for the Pallas bitonic sort kernel (interpret mode).

Engine-level coverage lives in test_pipeline/test_tfidf/test_distributed;
these pin the kernel's own contract: ascending keys, payload permutation,
non-power-of-two padding, multi-tile cross stages, and the documented
pad-sentinel caveat (code-review r4 finding)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from locust_tpu.ops.pallas.sort import bitonic_sort


@pytest.mark.parametrize("n,tile_rows", [(1024, 8), (5000, 8), (8192, 16)])
def test_sorts_and_permutes_payload(n, tile_rows):
    rng = np.random.default_rng(n)
    # Keys < 0xFFFFFFFF: the documented precondition for exact payload
    # permutation (the pad sentinel ties otherwise).
    keys = rng.integers(0, 2**32 - 1, n, dtype=np.uint32)
    idx = np.arange(n, dtype=np.int32)
    sk, (si,) = jax.jit(
        lambda k, i: bitonic_sort(k, (i,), tile_rows=tile_rows, interpret=True)
    )(jnp.asarray(keys), jnp.asarray(idx))
    sk, si = np.asarray(sk), np.asarray(si)
    assert np.array_equal(sk, np.sort(keys))
    assert np.array_equal(keys[si], sk)          # pairing intact
    assert np.array_equal(np.sort(si), idx)      # payload is a permutation


def test_multiple_payload_operands_move_together():
    rng = np.random.default_rng(0)
    n = 2048
    keys = rng.integers(0, 2**32 - 1, n, dtype=np.uint32)
    p1 = np.arange(n, dtype=np.int32)
    p2 = (np.arange(n, dtype=np.int32) * 7 + 3)
    sk, (s1, s2) = bitonic_sort(
        jnp.asarray(keys), (jnp.asarray(p1), jnp.asarray(p2)),
        tile_rows=8, interpret=True,
    )
    s1, s2 = np.asarray(s1), np.asarray(s2)
    assert np.array_equal(s2, s1 * 7 + 3)        # rows moved as units


def test_all_equal_and_tiny_inputs():
    for n in (1, 2, 7):
        keys = np.full(n, 42, np.uint32)
        sk, (si,) = bitonic_sort(
            jnp.asarray(keys), (jnp.asarray(np.arange(n, dtype=np.int32)),),
            tile_rows=8, interpret=True,
        )
        assert np.array_equal(np.asarray(sk), keys)
        assert np.array_equal(np.sort(np.asarray(si)), np.arange(n))


def test_engine_folded_keys_never_hit_the_pad_sentinel():
    """The engine's "bitonic" mode is safe from the documented sentinel
    caveat BY CONSTRUCTION: a valid row's folded key is h1 >> 1 (top bit
    clear, < 0x80000000), so only INVALID rows — whose payloads are dead
    downstream — can carry 0xFFFFFFFF.  Pin the construction."""
    from locust_tpu.core import bytes_ops
    from locust_tpu.core.kv import KVBatch
    from locust_tpu.ops.process_stage import _folded_key

    words = [b"a", b"bb", b"ccc", b"", b"dddd", b""]
    keys = jnp.asarray(bytes_ops.strings_to_rows(words, 8))
    valid = jnp.asarray([bool(w) for w in words])
    batch = KVBatch.from_bytes(keys, jnp.arange(len(words)), valid)
    folded = np.asarray(_folded_key(batch))
    assert (folded[np.asarray(valid)] < 0x80000000).all()
    assert (folded[~np.asarray(valid)] == 0xFFFFFFFF).all()


def test_bad_dtype_rejected():
    with pytest.raises(TypeError, match="uint32"):
        bitonic_sort(jnp.zeros(16, jnp.int32), (), interpret=True)


@pytest.mark.parametrize("max_fused", [1, 3, 16])
def test_max_fused_chunking_sorts_identically(max_fused):
    """BITONIC_MAX_FUSED splits the fused launches (the Mosaic
    compile-size mitigation); every split must sort identically."""
    rng = np.random.default_rng(7)
    n = 5000
    keys = rng.integers(0, 2**32 - 1, n, dtype=np.uint32)
    idx = np.arange(n, dtype=np.int32)
    sk, (si,) = bitonic_sort(
        jnp.asarray(keys), (jnp.asarray(idx),), tile_rows=8,
        interpret=True, max_fused=max_fused,
    )
    sk, si = np.asarray(sk), np.asarray(si)
    assert np.array_equal(sk, np.sort(keys))
    assert np.array_equal(keys[si], sk)


def test_bitonic_schedule_covers_every_substage_once():
    """The shared launch plan (config.bitonic_schedule) must enumerate
    exactly Batcher's network — substages (s, t) for s=1..k, t=s..1, in
    descending-t order within each stage — for ANY fusion cap."""
    from locust_tpu.config import bitonic_schedule

    for kbits, m in ((10, 10), (20, 15), (13, 8)):
        want = [(s, t) for s in range(1, kbits + 1)
                for t in range(s, 0, -1)]
        for mf in (0, 1, 5, 64):
            got = []
            for step in bitonic_schedule(kbits, m, mf):
                if step[0] == "cross":
                    got.append((step[1], step[2]))
                else:
                    for s, t_hi, t_lo in step[1]:
                        got.extend((s, t) for t in range(t_hi, t_lo - 1, -1))
            assert got == want, (kbits, m, mf)
            if mf:
                for step in bitonic_schedule(kbits, m, mf):
                    if step[0] == "local":
                        assert sum(t_hi - t_lo + 1
                                   for _, t_hi, t_lo in step[1]) <= mf


def test_engine_mode_over_the_interpret_cap_serves_the_stock_sort(monkeypatch):
    """Off-TPU, a bitonic-mode sort above BITONIC_INTERPRET_MAX must take
    the equivalent stock formulation (hashp1) with its one-time warning —
    the branch every CPU run at production block sizes takes."""
    from locust_tpu.core.kv import KVBatch
    from locust_tpu.ops import process_stage

    monkeypatch.setattr(process_stage, "BITONIC_INTERPRET_MAX", 1024)
    monkeypatch.setattr(process_stage, "_warned_bitonic_interpret", False)
    rng = np.random.default_rng(0)
    keys = rng.integers(97, 101, size=(4096, 8), dtype=np.uint8)
    batch = KVBatch.from_bytes(
        jnp.asarray(keys), jnp.ones(4096, jnp.int32),
        jnp.asarray(rng.random(4096) < 0.8),
    )
    got = process_stage.sort_and_compact(batch, "bitonic")
    want = process_stage.sort_and_compact(batch, "hashp1")
    assert process_stage._warned_bitonic_interpret is True
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
