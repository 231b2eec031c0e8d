"""North-star-scale streaming run with honest, fold-only RSS accounting.

The largest committed streaming artifact was 64MB and
its peak RSS was dominated by in-process corpus GENERATION.  This script
is the canonical ``stream_scale`` evidence producer:

  1. the Zipf corpus is pre-generated to disk by a SEPARATE process
     (bounded-memory chunked writer, io/corpus.write_corpus), so
     generation cost never pollutes the measurement;
  2. the measuring process then runs the bounded-memory streaming fold
     (auto-capped, prefetching StreamingCorpus -> engine.run_stream) and
     reports its OWN rss before the measure pass, before the fold, and
     the process peak — the fold's working-set delta is the bounded-RSS
     claim, on top of the jax runtime's fixed baseline;
  3. the output table is verified against a bounded-memory host oracle
     (streaming Counter over the same file: vocabulary-bounded, not
     corpus-bounded) -> ``token_oracle_match``.

Usage:
  python scripts/stream_scale.py --mb 512                  # CPU
  python scripts/stream_scale.py --mb 512 --backend tpu    # in a window

Appends a ``stream_scale`` row to artifacts/tpu_runs.jsonl (the artifact
hook records backend/device itself).  Match: reference loadFile slicing
(MapReduce/src/main.cu:40-64) at BASELINE.json north-star scale.
"""

import argparse
import collections
import json
import os
import re
import resource
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

N_VOCAB = 50_000


def _rss_mb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024


def ensure_corpus(path: str, mb: int) -> int:
    """Generate the corpus in a child process (its RSS is not ours)."""
    want = mb * 1_000_000
    if os.path.exists(path) and os.path.getsize(path) >= want:
        return os.path.getsize(path)
    code = (
        "import sys; sys.path.insert(0, %r); "
        "from locust_tpu.io.corpus import write_corpus; "
        "write_corpus(%r, %d, n_vocab=%d)" % (REPO, path, want, N_VOCAB)
    )
    t0 = time.perf_counter()
    subprocess.run(
        [sys.executable, "-c", code],
        check=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO},
    )
    print(
        f"[stream] generated {os.path.getsize(path)/1e6:.0f} MB in child "
        f"process ({time.perf_counter()-t0:.0f}s)",
        file=sys.stderr,
    )
    return os.path.getsize(path)


def host_oracle(path: str, delimiters: bytes):
    """Bounded-memory oracle: total tokens + per-word counts, streamed.

    Memory is vocabulary-bounded (Counter over <= N_VOCAB + noise keys),
    never corpus-bounded.  Uses the device's FULL delimiter set so the
    comparison is exact, and the device's line_width truncation is NOT
    applied — the generator's 10 x 7B-token lines fit 128B rows, so
    truncation never fires on this corpus.
    """
    pat = re.compile(b"[" + re.escape(delimiters) + b"]+")
    counts: collections.Counter = collections.Counter()
    with open(path, "rb") as f:
        for ln in f:
            counts.update(t for t in pat.split(ln) if t)
    return counts


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mb", type=int, default=512)
    ap.add_argument("--path", default=None)
    ap.add_argument("--backend", choices=["auto", "cpu", "tpu"], default="cpu")
    ap.add_argument("--block-lines", type=int, default=32768)
    ap.add_argument("--skip-oracle", action="store_true",
                    help="skip the host verification pass (faster; the "
                         "row then reports token_oracle_match: null)")
    args = ap.parse_args()
    path = args.path or f"/tmp/stream_scale_{args.mb}mb.txt"

    size = ensure_corpus(path, args.mb)

    from locust_tpu.backend import select_backend

    backend = select_backend(args.backend)
    print(f"[stream] backend: {backend}", file=sys.stderr)

    import bench

    from locust_tpu.config import FULL_DELIMITERS, EngineConfig
    from locust_tpu.engine import MapReduceEngine
    from locust_tpu.io.loader import (
        StreamingCorpus,
        measure_caps_stream,
        size_caps,
    )
    from locust_tpu.utils import artifacts

    rss_start = _rss_mb()
    d = EngineConfig()
    t0 = time.perf_counter()
    measure_stream = StreamingCorpus(path, d.line_width, args.block_lines)
    fp = measure_stream.fingerprint()
    max_tok, max_per_line = measure_caps_stream(measure_stream)
    kw, epl = size_caps(max_tok, max_per_line, d.key_width, d.emits_per_line)
    measure_s = time.perf_counter() - t0
    print(
        f"[stream] caps: key_width={kw} emits_per_line={epl} "
        f"({measure_s:.0f}s measure pass)",
        file=sys.stderr,
    )

    # table_size pinned to the default-caps resolution (bench_engine_config
    # policy) so the table is identical to a default-config run.
    eng = MapReduceEngine(
        bench.bench_engine_config(
            args.block_lines, key_width=kw, emits_per_line=epl
        )
    )
    run_src = StreamingCorpus(path, d.line_width, args.block_lines)
    if run_src.fingerprint() != fp:
        print("[stream] corpus changed between passes; abort", file=sys.stderr)
        return 1
    # Warm up compile + XLA runtime arenas BEFORE the RSS baseline: the
    # fold executable and its workspace are one-time allocations shared
    # with any corpus size; the bounded-RSS claim is about growth WITH
    # corpus size, so they belong to the baseline, not the fold delta.
    import numpy as np

    eng.run(np.zeros((1, d.line_width), np.uint8))
    rss_before_fold = _rss_mb()
    t0 = time.perf_counter()
    res = eng.run_stream(run_src)
    wall = time.perf_counter() - t0
    rss_peak = _rss_mb()

    # The fold's expected working set: the staging ring
    # (STREAM_DISPATCH_DEPTH + 1 reusable slots — the in-flight blocks
    # ARE ring slots now) + prefetch-held source blocks + the device
    # table mirrored at sync + host block assembly.
    block_mb = args.block_lines * d.line_width / 1e6
    expected_mb = (
        block_mb * (MapReduceEngine.STREAM_DISPATCH_DEPTH + 1 + 2)
        + eng.cfg.resolved_table_size * (kw + 8) / 1e6
    )

    match = None
    distinct_oracle = None
    if not args.skip_oracle:
        t0 = time.perf_counter()
        oracle = host_oracle(path, FULL_DELIMITERS)
        pairs = dict(res.to_host_pairs())
        match = pairs == dict(oracle)
        distinct_oracle = len(oracle)
        print(
            f"[stream] oracle: {len(oracle)} keys, match={match} "
            f"({time.perf_counter()-t0:.0f}s host pass)",
            file=sys.stderr,
        )

    row = {
        "corpus_mb": round(size / 1e6, 1),
        "wall_s": round(wall, 1),
        "mb_s": round(size / 1e6 / wall, 2),
        "caps": {"key_width": kw, "emits_per_line": epl},
        "block_lines": args.block_lines,
        "distinct": res.num_segments,
        "truncated": res.truncated,
        "rss_start_mb": round(rss_start, 0),
        "rss_before_fold_mb": round(rss_before_fold, 0),
        "peak_rss_mb": round(rss_peak, 0),
        "fold_delta_mb": round(rss_peak - rss_before_fold, 0),
        "expected_working_set_mb": round(expected_mb, 1),
        "stream": res.stream,  # zero-stall executor accounting
        "token_oracle_match": match,
        "note": "corpus pre-generated by a separate process; rss fields "
                "are the measuring process only",
    }
    # TPU rows ride the standard evidence hook; CPU rows persist to their
    # own committed ladder file (artifacts.record is TPU-gated by design).
    if not artifacts.record("stream_scale", row):
        os.makedirs(artifacts.artifacts_dir(), exist_ok=True)
        cpu_path = os.path.join(
            artifacts.artifacts_dir(), "stream_scale_cpu_r4.jsonl"
        )
        with open(cpu_path, "a") as f:
            f.write(json.dumps({"ts": round(time.time(), 1),
                                "kind": "stream_scale", "backend": backend,
                                **row}) + "\n")
    print(json.dumps(row))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
