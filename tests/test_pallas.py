"""Pallas kernel parity tests (interpret mode on CPU — SURVEY.md §5
"our analog is ... interpret-mode Pallas tests")."""

import numpy as np
import jax.numpy as jnp
import pytest

from helpers import strtok_tokens

from locust_tpu.config import EngineConfig
from locust_tpu.core import bytes_ops
from locust_tpu.ops import map_stage
from locust_tpu.ops.pallas.tokenize import TILE_LINES, tokenize_block_pallas


def cfg_for(width=128, emits=8, key_w=16):
    return EngineConfig(
        block_lines=TILE_LINES, line_width=width, emits_per_line=emits,
        key_width=key_w,
    )


LINES = [
    b"to be or not to be",
    b"that is the question",
    b"",
    b"hyphen-split 'quoted' (x), y.z;",
    b"a" * 120,
    b"one two three four five six seven eight nine ten",  # overflows emits=8
]


def _pad(lines, cfg):
    rows = bytes_ops.strings_to_rows(lines + [b""] * (cfg.block_lines - len(lines)),
                                     cfg.line_width)
    return jnp.asarray(rows)


def test_pallas_tokenizer_matches_jnp_reference():
    cfg = cfg_for()
    rows = _pad(LINES, cfg)
    ref = map_stage.tokenize_block(rows, cfg)
    keys, valid, ovf = tokenize_block_pallas(rows, cfg, interpret=True)
    np.testing.assert_array_equal(np.asarray(valid), np.asarray(ref.valid))
    np.testing.assert_array_equal(np.asarray(keys), np.asarray(ref.keys))
    assert int(ovf) == int(ref.overflow)


def test_pallas_tokenizer_exact_tokens():
    cfg = cfg_for()
    rows = _pad(LINES, cfg)
    keys, valid, _ = tokenize_block_pallas(rows, cfg, interpret=True)
    for i, line in enumerate(LINES):
        toks = strtok_tokens(line, max_tokens=cfg.emits_per_line,
                             key_width=cfg.key_width)
        got = bytes_ops.rows_to_strings(np.asarray(keys[i][: len(toks)]))
        assert got == toks, f"line {i}"
        assert int(np.asarray(valid[i]).sum()) == len(toks)


def test_engine_with_pallas_map_matches_oracle():
    from helpers import py_wordcount
    from locust_tpu.engine import MapReduceEngine

    cfg = EngineConfig(
        block_lines=TILE_LINES, line_width=128, emits_per_line=8,
        key_width=16, use_pallas=True,
    )
    eng = MapReduceEngine(cfg)
    res = eng.run_lines(LINES)
    assert dict(res.to_host_pairs()) == dict(
        py_wordcount(LINES, cfg.emits_per_line, cfg.key_width)
    )


def test_pallas_tokenizer_rejects_bad_tile():
    cfg = EngineConfig(block_lines=TILE_LINES + 1, line_width=128,
                       emits_per_line=4, key_width=16)
    rows = jnp.zeros((cfg.block_lines, 128), jnp.uint8)
    with pytest.raises(ValueError, match="multiple"):
        tokenize_block_pallas(rows, cfg, interpret=True)


def test_pallas_tokenizer_rejects_bad_width():
    cfg = EngineConfig(block_lines=TILE_LINES, line_width=96,
                       emits_per_line=4, key_width=16)
    rows = jnp.zeros((cfg.block_lines, 96), jnp.uint8)
    with pytest.raises(ValueError, match="128"):
        tokenize_block_pallas(rows, cfg, interpret=True)
