#!/usr/bin/env python3
"""Measure a cell's run-to-run spread the way its bound is set from.

    python3 benchmarks/measure_sets.py --workload <cell> --seconds <run_seconds> \
        [--sets 2] [--runs 6] [--seed0 N] [--trace 0] [--out DIR]

Runs ``run.py`` as a child ``sets x runs`` times, one after another (this
parent never touches jax, so each child has the chip to itself), with the
same seeds in every set.  Per metric and set it prints the spread —
(third quartile - first quartile) / median, quartiles as
``statistics.quantiles(values, n=4)`` gives them — then the wider of the
sets' spreads, five times that (the bound the contract asks for, never
under 1%), and how far the second set's median lies from the first's.
Every run's result line is appended to ``DIR/<cell>.t<trace>.jsonl``.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def spread(values: list[float]) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return (q3 - q1) / med if med else float("inf")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--seed0", type=int, default=2147483700)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--out", default="chiprun_out/sets")
    args = ap.parse_args(argv)
    os.makedirs(args.out, exist_ok=True)
    log = os.path.join(args.out, f"{args.workload}.t{args.trace}.jsonl")
    sets: list[list[dict]] = []
    for s in range(args.sets):
        rows = []
        for r in range(args.runs):
            seed = args.seed0 + r
            t0 = time.time()
            p = subprocess.run(
                [sys.executable, os.path.join(HERE, "run.py"), "--workload", args.workload,
                 "--seed", str(seed), "--seconds", str(args.seconds),
                 "--trace", str(args.trace)],
                capture_output=True, text=True)
            wall = time.time() - t0
            lines = p.stdout.strip().splitlines()
            try:
                doc = json.loads(lines[-1])
            except (IndexError, ValueError):
                print(f"set {s} run {r} seed {seed}: rc {p.returncode}, no result line\n"
                      + p.stdout[-1500:] + p.stderr[-1500:], flush=True)
                return 1
            doc.update(set=s, run=r, seed=seed, wall_s=wall, rc=p.returncode)
            with open(os.path.join(args.out, f"{args.workload}.t{args.trace}.log"), "a") as f:
                f.write(f"=== set {s} run {r} seed {seed}\n"
                        + "\n".join(ln for ln in lines if ln.startswith("[bench")) + "\n")
            with open(log, "a") as f:
                f.write(json.dumps(doc) + "\n")
            vals = {k: round(v["value"], 4) for k, v in doc["metrics"].items()}
            print(f"set {s} run {r} seed {seed}: rc {p.returncode} wall {wall:.1f} s "
                  f"correct {doc['correct']} attempted {doc['attempted']} failed "
                  f"{doc['failed']} {vals}", flush=True)
            rows.append(doc)
        sets.append(rows)
    ok = all(d["correct"] and d["rc"] == 0 for rows in sets for d in rows)
    for name in sets[0][0]["metrics"]:
        per_set = [[d["metrics"][name]["value"] for d in rows] for rows in sets]
        spreads = [spread(v) for v in per_set] if args.runs >= 2 else [0.0]
        meds = [statistics.median(v) for v in per_set]
        drift = (meds[-1] - meds[0]) / meds[0] if meds[0] else 0.0
        widest = max(spreads)
        print(f"{args.workload} {name}: medians {[round(m, 4) for m in meds]}, spreads "
              f"{[round(x, 5) for x in spreads]}, widest {widest:.5f}, x5 = "
              f"{max(0.01, 5 * widest):.4f}; second set's median {drift:+.4%} from the first's",
              flush=True)
    print(f"{args.workload}: every run correct: {ok}", flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
