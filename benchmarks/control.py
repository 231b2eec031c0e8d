#!/usr/bin/env python3
"""The control of ``correct``: one guarantee broken, and the check must say so.

    python3 benchmarks/control.py --workload <cell> --seeds 11,12,13 [--rehearse]

For every seed, in ONE process (set-up and compilation are paid once): the
cell's corpus and oracle, one job with the cell's own argv (must keep the
guarantee) and one with the traffic file's ``control_argv`` added — for the
shipped cells ``--emits-per-line 8``, which drops every word past a line's
eighth (must NOT keep it).  Both go through the window's own job function
and check.  Exit 0 only if every sound job was correct and every control
job was not.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run  # noqa: E402
import yardstick  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    cell, config, traffic = run.find_cell(bench, args.workload)
    env = run.set_up(bench, cell, config, traffic, seeds[0], args.rehearse)
    driver = importlib.import_module("drivers." + traffic["driver"])
    rows = []
    try:
        for seed in seeds:
            env.corpus_bytes = yardstick.build_corpus(
                env.corpus_path, config["text"], env.sizes["corpus_lines"], seed)
            env.expect = yardstick.oracle_table(env.corpus_path)
            base = list(env.extra_argv)
            sound = driver.one_job(env, 0, traced=False)
            env.extra_argv = base + list(traffic["control_argv"])
            broken = driver.one_job(env, 1, traced=False)
            env.extra_argv = base
            rows.append({"seed": seed, "sound_correct": sound.verdict is None,
                         "control_correct": broken.verdict is None,
                         "control_verdict": broken.verdict,
                         "sound_verdict": sound.verdict})
            run.say(f"seed {seed}: sound job {sound.seconds:.3f} s -> "
                    f"{sound.verdict or 'equal to the oracle'}; control "
                    f"{' '.join(traffic['control_argv'])} {broken.seconds:.3f} s -> "
                    f"{broken.verdict or 'EQUAL TO THE ORACLE (the check has no teeth)'}")
    finally:
        shutil.rmtree(env.workdir, ignore_errors=True)
    ok = all(r["sound_correct"] and not r["control_correct"] for r in rows)
    print(json.dumps({"workload": args.workload, "platform": env.platform,
                      "control_holds": ok, "seeds": rows}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
