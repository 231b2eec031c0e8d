"""bench.py — the driver's one-line contract, and nothing else.

Prints exactly ONE JSON line
``{"metric", "value", "unit", "vs_baseline", "backend", "distinct",
"truncated"}``, with ``"error"`` added on any failure: the CLI's default
path, once to warm and once timed, on the shipped sample text
(io/corpus.py generated it) on whatever backend jax picks.  ``value`` is
file bytes over the second run's wall clock; ``vs_baseline`` is against
the reference's ~2.2 MB/s on hamlet.txt (BASELINE.md "Notes").

NOT the benchmark: a 66 kB job is all launch and program reload.  What
the chip has shown is in PERF.md, measured by ``benchmarks/run.py``
(BENCHMARK.json, PERF_LEDGER.jsonl).
"""

import json
import os
import re

CORPUS = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "data", "sample_corpus.txt"
)
BASELINE_MB_S = 2.2


def main(corpus: str = CORPUS) -> int:
    row = {"metric": "wordcount_throughput", "value": 0.0, "unit": "MB/s",
           "vs_baseline": 0.0, "backend": None, "distinct": 0,
           "truncated": False}
    try:
        # The CLI in this process, table and stderr captured (stderr is
        # shown too); raises unless the CLI returned 0.
        from chip_smoke import run_cli
        from locust_tpu.config import compile_cache_dir

        compile_cache_dir()  # before the first `import jax`
        run_cli([corpus])  # warm: compiles, or reads the cache
        table, err, seconds = run_cli([corpus])
        mb_s = os.path.getsize(corpus) / 1e6 / seconds
        backend = re.search(r"\[locust\] backend: (\S+)", err)
        row.update(
            value=round(mb_s, 3),
            vs_baseline=round(mb_s / BASELINE_MB_S, 3),
            backend=backend.group(1) if backend else None,
            distinct=table.count(b"\n"),
            truncated="WARN: table capacity exceeded" in err,
        )
    except (Exception, SystemExit) as e:  # the one line, whatever happened
        row["error"] = f"{type(e).__name__}: {e}"[:500]
    print(json.dumps(row), flush=True)
    return 1 if "error" in row else 0


if __name__ == "__main__":
    raise SystemExit(main())
