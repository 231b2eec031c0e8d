"""The hardening utils are WIRED, not decorative.

- checkify_pipeline turns device-side invariant violations into host errors;
- validate_batch runs inside the engine under LOCUST_DEBUG_CHECKS;
- SpanTimer powers the CLI --trace report.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import checkify

from locust_tpu.config import EngineConfig
from locust_tpu.core.kv import KVBatch
from locust_tpu.engine import MapReduceEngine
from locust_tpu.utils import SpanTimer, checkify_pipeline, validate_batch


def test_checkify_pipeline_raises_on_violated_check():
    @jax.jit
    def guarded(x):
        checkify.check(jnp.all(x >= 0), "negative input")
        return x * 2

    wrapped = checkify_pipeline(guarded)
    np.testing.assert_array_equal(wrapped(jnp.arange(4)), jnp.arange(4) * 2)
    with pytest.raises(Exception, match="negative input"):
        wrapped(jnp.asarray([-1, 2]))


def test_checkify_pipeline_guards_engine_stage():
    """Wrap a real pipeline stage: an index-checked gather over emits."""
    from locust_tpu.ops.map_stage import wordcount_map

    cfg = EngineConfig(block_lines=4, line_width=64, emits_per_line=4)

    def stage(lines):
        kv, overflow = wordcount_map(lines, cfg)
        checkify.check(
            jnp.sum(kv.valid.astype(jnp.int32)) >= 0, "emit count underflow"
        )
        return kv.values, overflow

    from locust_tpu.core import bytes_ops

    rows = jnp.asarray(
        bytes_ops.strings_to_rows([b"a b", b"c"], cfg.line_width)
    )
    pad = jnp.zeros((2, cfg.line_width), jnp.uint8)
    vals, _ = checkify_pipeline(jax.jit(stage))(jnp.concatenate([rows, pad]))
    assert vals.shape == (cfg.block_lines * cfg.emits_per_line,)


def test_engine_debug_checks_env(monkeypatch):
    monkeypatch.setenv("LOCUST_DEBUG_CHECKS", "1")
    cfg = EngineConfig(block_lines=4, line_width=64, emits_per_line=4)
    eng = MapReduceEngine(cfg)
    res = eng.run_lines([b"a b a", b"c"])
    assert dict(res.to_host_pairs()) == {b"a": 2, b"b": 1, b"c": 1}


def test_validate_batch_catches_non_prefix_layout():
    batch = KVBatch(
        key_lanes=jnp.zeros((4, 8), jnp.uint32),
        values=jnp.zeros(4, jnp.int32),
        valid=jnp.asarray([True, False, True, False]),
    )
    with pytest.raises(AssertionError, match="prefix"):
        validate_batch(batch, expect_compact=True)


def test_span_timer_accumulates():
    t = SpanTimer()
    with t.span("a"):
        pass
    with t.span("a"):
        pass
    with t.span("b"):
        pass
    assert set(t.spans_ms) == {"a", "b"}
    assert "a" in t.report() and "ms" in t.report()


def test_cli_trace_flag_prints_span_report(tmp_path, capsys):
    from locust_tpu import cli

    f = tmp_path / "in.txt"
    f.write_bytes(b"hello world\nhello\n")
    rc = cli.main([str(f), "--backend", "cpu", "--no-timing", "--trace"])
    assert rc == 0
    err = capsys.readouterr().err
    assert "load" in err and "run" in err and "output" in err
