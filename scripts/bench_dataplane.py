"""Loopback data-plane microbench CLI (docs/DATAPLANE.md).

Runs the distributor fetch-path comparison (JSON/base64 vs binary
framing, raw vs zlib, window=1 vs window=K) against one in-process
worker on 127.0.0.1 and appends a ``dataplane_bench`` evidence row to
``artifacts/tpu_runs.jsonl`` via the shared ledger writer
(locust_tpu/utils/artifacts.py, ``force=True`` — this is host/socket
evidence, valid on any backend).

Usage:
    python scripts/bench_dataplane.py [--bytes N] [--chunk N] [--window K]
                                      [--repeats R] [--no-record]

Prints the result as one JSON document on stdout.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Pin the CPU BEFORE anything can touch a jax backend (the artifacts
# writer imports jax for row metadata): a pure-socket bench has no
# business taking the chip from the process that needs it.
from locust_tpu.backend import select_backend  # noqa: E402

select_backend("cpu")

from locust_tpu.distributor.microbench import run_microbench  # noqa: E402
from locust_tpu.utils import artifacts  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="bench_dataplane")
    p.add_argument("--bytes", type=int, default=4 << 20,
                   help="approx staged intermediate size (default 4MiB)")
    p.add_argument("--chunk", type=int, default=64 * 1024,
                   help="fetch chunk size (default 64KiB)")
    p.add_argument("--window", type=int, default=4,
                   help="pipelined chunks in flight (default 4)")
    p.add_argument("--repeats", type=int, default=3,
                   help="runs per variant; throughput is the best")
    p.add_argument("--no-record", action="store_true",
                   help="skip the artifacts ledger append")
    args = p.parse_args(argv)

    res = run_microbench(
        target_bytes=args.bytes,
        chunk_bytes=args.chunk,
        window=args.window,
        repeats=args.repeats,
    )
    if not args.no_record:
        # Kind imported from the two-sided registry, never re-spelled
        # (artifacts.BENCH_SUBDICT_KINDS — same discipline as
        # CONFIG_AB_KINDS).
        artifacts.record(
            artifacts.BENCH_SUBDICT_KINDS["dataplane"], res, force=True
        )
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
