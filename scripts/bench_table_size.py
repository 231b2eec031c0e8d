"""Measure the throughput cost of the accumulator table size.

The per-block merge sorts ``table_size + emits_per_block`` rows, so table
capacity is a throughput knob as well as a truncation knob
(pick the default from data, not vibes).

Usage: python scripts/bench_table_size.py [--backend auto|cpu|tpu]
Prints one JSON line per (table_size, vocab) cell.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from locust_tpu.config import compile_cache_dir  # noqa: E402 - jax-free

compile_cache_dir()


def corpus_lines(n_vocab: int, total_tokens: int, seed: int = 0) -> list[bytes]:
    """Zipf corpus: vocabulary of n_vocab words, ~total_tokens draws."""
    from locust_tpu.io.corpus import synthetic_corpus

    return synthetic_corpus(total_tokens * 8, n_vocab=n_vocab, seed=seed)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", default="auto", choices=["auto", "cpu", "tpu"])
    ap.add_argument("--block-lines", type=int, default=32768)
    ap.add_argument("--tokens", type=int, default=1_000_000)
    args = ap.parse_args()

    from locust_tpu.backend import select_backend

    select_backend(args.backend)
    import jax

    from locust_tpu.config import EngineConfig
    from locust_tpu.engine import MapReduceEngine

    for n_vocab in (5_000, 100_000):
        lines = corpus_lines(n_vocab, args.tokens)
        nbytes = sum(len(ln) + 1 for ln in lines)
        for tsize in (1 << 16, 1 << 17, 1 << 18):
            cfg = EngineConfig(block_lines=args.block_lines, table_size=tsize)
            eng = MapReduceEngine(cfg)
            blocks = eng.prepare_blocks(eng.rows_from_lines(lines))
            blocks.block_until_ready()
            eng.run_blocks(blocks)  # warmup/compile
            best_ms, res = float("inf"), None
            for _ in range(3):
                r = eng.run_blocks(blocks)
                if r.times.total_ms < best_ms:
                    best_ms, res = r.times.total_ms, r
            print(json.dumps({
                "backend": jax.default_backend(),
                "table_size": tsize,
                "vocab": n_vocab,
                "distinct": res.num_segments,
                "truncated": res.truncated,
                "ms": round(best_ms, 1),
                "mb_s": round(nbytes / 1e6 / (best_ms / 1e3), 2),
            }), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
