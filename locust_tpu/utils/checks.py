"""Runtime invariant checking (the race-detector/sanitizer analog).

The reference ships no sanitizers and one known sync hazard
(``__syncthreads`` after divergent early-return, reference
MapReduce/src/main.cu:162-174, SURVEY.md §5).  XLA removes that bug class;
what remains worth checking are DATA invariants at stage boundaries.  Two
tiers:

  * ``checkify_pipeline`` — wrap a jitted pipeline fn with
    ``jax.experimental.checkify`` so out-of-range/NaN-class errors surface
    as real errors instead of silent garbage.
  * ``validate_batch`` — host-side structural asserts for tests/debugging
    (valid-prefix layout, in-range values, NUL-padded keys).
"""

from __future__ import annotations

import jax
import numpy as np
from jax.experimental import checkify

from locust_tpu.core.kv import KVBatch


def checkify_pipeline(fn, errors=checkify.user_checks | checkify.index_checks):
    """Wrap fn so checkify errors are raised on the host after each call."""
    checked = checkify.checkify(fn, errors=errors)

    def wrapper(*args, **kwargs):
        err, out = checked(*args, **kwargs)
        err.throw()
        return out

    return wrapper


def validate_batch(batch: KVBatch, expect_sorted: bool = False, expect_compact: bool = False) -> None:
    """Host-side invariant asserts; raises AssertionError with specifics."""
    lanes = np.asarray(jax.device_get(batch.key_lanes))
    valid = np.asarray(jax.device_get(batch.valid))
    values = np.asarray(jax.device_get(batch.values))
    assert lanes.ndim == 2 and lanes.dtype == np.uint32, "lanes must be [N, L] uint32"
    assert valid.shape == (lanes.shape[0],) and valid.dtype == bool
    assert values.shape == (lanes.shape[0],)

    if expect_compact:
        # Valid-prefix layout: no valid row after the first invalid one.
        if valid.any():
            last_valid = np.max(np.nonzero(valid)[0])
            assert valid[: last_valid + 1].all(), "valid rows not a prefix"
    # Vectorized throughout: Python per-row loops made
    # LOCUST_DEBUG_CHECKS cost seconds on a 65k-row table; these numpy row
    # ops keep it in the low milliseconds, same assertions.
    if expect_sorted:
        live = lanes[valid]
        if live.shape[0] > 1:
            a, b = live[:-1], live[1:]
            # Row-wise lexicographic a <= b over big-endian lanes: decide at
            # the first differing lane (all-equal rows pass trivially).
            neq = a != b
            any_diff = neq.any(axis=1)
            first = np.argmax(neq, axis=1)
            r = np.arange(a.shape[0])
            ok = ~any_diff | (a[r, first] < b[r, first])
            bad = np.nonzero(~ok)[0]
            assert bad.size == 0, f"rows {bad[0]},{bad[0]+1} out of order"
    # Keys must be NUL-padded: no nonzero byte after the first NUL.  A row
    # passes iff bytes are monotone in "zero-ness": once a NUL appears, all
    # later bytes are NUL == the nonzero mask never rises after falling.
    from locust_tpu.core.packing import unpack_keys
    import jax.numpy as jnp

    kb = np.asarray(jax.device_get(unpack_keys(jnp.asarray(lanes[valid]))))
    if kb.size:
        nonzero = kb != 0
        rises = (~nonzero[:, :-1]) & nonzero[:, 1:]
        bad = np.nonzero(rises.any(axis=1))[0]
        assert bad.size == 0, (
            f"row {bad[0] if bad.size else '?'} has bytes after NUL "
            "(interior NUL key)"
        )
