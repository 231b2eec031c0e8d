#!/usr/bin/env python3
"""Self-check of the yardstick's arithmetic, on a CPU sandbox:

    python3 benchmarks/fixtures/selfcheck.py

* ``trace_reduce`` on the recorded chip trace ``ref4463_2jobs.xplane.pb``
  (two jobs of ``ref4463.jobs`` on one TPU v5e, PR 23) must give the values
  in ``ref4463_2jobs.expected.json`` — which were written from that same
  trace and are re-derived here a second way, by plain sums over the lines;
* ``merge`` / ``clip`` / ``label_gaps`` / ``top_ops`` on hand-made intervals;
* ``yardstick.percentile`` (nearest rank) and the delimiter set against the
  program's, where the program is importable;
* the ``roofline`` reader on that trace against a sum by hand, and
  ``yardstick.build_corpus``: seeds change the order of the lines and nothing else.

Prints one line per check and exits non-zero on the first failure.
"""

from __future__ import annotations

import json
import math
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import trace_reduce as tr  # noqa: E402
import yardstick  # noqa: E402

FIXTURE = os.path.join(HERE, "ref4463_2jobs.xplane.pb")
EXPECTED = os.path.join(HERE, "ref4463_2jobs.expected.json")


def check(name: str, ok: bool, detail: str = "") -> None:
    print(f"{'ok  ' if ok else 'FAIL'} {name} {detail}", flush=True)
    if not ok:
        raise SystemExit(1)


def close(a: float, b: float, rel: float = 1e-9) -> bool:
    return math.isclose(a, b, rel_tol=rel, abs_tol=1e-12)


def hand_made() -> None:
    check("merge joins overlapping and touching intervals",
          tr.merge([(5, 7), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 7)])
    check("clip cuts to the window and drops what lies outside",
          tr.clip([(0, 4), (5, 7), (9, 10)], 2, 6) == [(2, 4), (5, 6)])
    # two jobs [0,100] and [200,300]; job 0 has an outer span and an inner one
    jobs = [(0.0, 100.0), (200.0, 300.0)]
    spans = [[("outer", 0.0, 100.0), ("inner", 10.0, 20.0)], []]
    gaps = [(12e0, 14e0), (50.0, 60.0), (120.0, 180.0), (210.0, 220.0)]
    got = dict((k, round(v * 1e9, 6)) for k, v in tr.label_gaps(gaps, jobs, spans))
    check("label_gaps: innermost span, job without spans, between jobs",
          got == {"inner": 2.0, "outer": 10.0, "between jobs": 60.0,
                  "job, outside its spans": 10.0}, str(got))
    dev = {"ops": {"%a = long " + "x" * 200: [2.0, 1], "%a = long " + "x" * 300: [1.0, 1],
                   "%b": [2.5, 3]}}
    top = tr.top_ops(dev, n=2, width=40)
    check("top_ops sums names that are equal once cut, largest first",
          top == [["%a = long " + "x" * 30, 3.0], ["%b", 2.5]], str(top))
    check("percentile is nearest rank",
          yardstick.percentile(list(range(1, 101)), 0.95) == 95
          and yardstick.percentile([3, 1, 2], 0.5) == 2
          and yardstick.percentile([1, 2, 3, 4], 0.9) == 4)


def recorded() -> None:
    with open(EXPECTED) as f:
        want = json.load(f)
    red = tr.reduce_trace(FIXTURE)
    check("the fixture holds one chip's plane", sorted(red["devices"]) == [0])
    check("two job annotations", len(red["jobs"]) == want["jobs"] == 2)
    dev = red["devices"][0]
    check("window_s", close(red["window_s"], want["window_s"]), f"{red['window_s']}")
    check("busy_s (union of op intervals)", close(dev["busy_s"], want["busy_s"]),
          f"{dev['busy_s']}")
    check("idle share = 1 - busy/window, inside (0, 1)",
          close(dev["idle_share"], 1 - dev["busy_s"] / red["window_s"])
          and 0 < dev["idle_share"] < 1, f"{dev['idle_share']}")
    for name, (secs, calls) in want["modules"].items():
        got = dev["modules"].get(name)
        check(f"program {name}: seconds and calls",
              got is not None and close(got[0], secs) and got[1] == calls, str(got))
    # a second way to the same numbers: plain sums over the raw lines
    pd = tr.load(FIXTURE)
    plane = pd.find_plane_with_name("/device:TPU:0")
    lo, hi = red["jobs"][0][0], red["jobs"][-1][1]
    ops = [(e.start_ns, e.start_ns + e.duration_ns)
           for line in plane.lines if line.name == tr.OPS_LINE for e in line.events
           if e.start_ns + e.duration_ns > lo and e.start_ns < hi]
    op_sum = sum(b - a for a, b in ops) / 1e9
    check("the union never passes the sum of the ops, nor the window",
          dev["busy_s"] <= op_sum * (1 + 1e-9) and dev["busy_s"] < red["window_s"],
          f"sum {op_sum}")
    mod_sum = sum(s for s, _ in dev["modules"].values())
    check("the programs' time covers the ops' union (ops run inside programs)",
          mod_sum >= dev["busy_s"] * (1 - 1e-6), f"programs {mod_sum}")
    gaps = sum(b - a for a, b in dev["gaps"]) / 1e9
    check("gaps + busy = window", close(gaps + dev["busy_s"], red["window_s"], 1e-9))
    roofline_and_corpus(red)


def roofline_and_corpus(red) -> None:
    """The roofline reader on the recorded trace, against a sum by hand; the
    corpus builder's promise that seeds change the order and nothing else."""
    import collections
    import tempfile
    import types

    from readers import roofline

    bench = os.path.dirname(HERE)
    with open(os.path.join(bench, "configs", "wc-ref-4463.json")) as f:
        config = json.load(f)
    with open(os.path.join(bench, "layer_metrics", "process_roofline.json")) as f:
        spec = json.load(f)
    with open(os.path.join(bench, "peaks.json")) as f:
        peaks = json.load(f)["TPU v5 lite"]
    env = types.SimpleNamespace(trace=red, sizes=config["sizes"], device={"peaks": peaks})
    # 4 blocks (two jobs of two); per block 3 x 81,920 + 4 x 65,536 rows of 37 bytes
    by_hand = 100 * (4 * (3 * 81920 + 4 * 65536) * 37 / 819e9) / (0.0082636 + 0.014159184)
    got = roofline.read(spec, env)
    check("process_roofline on the recorded trace equals the sum by hand",
          close(got, by_hand, 1e-6) and 0 < got < 100, f"{got} %")
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "c.txt")
        tables, sizes = [], []
        for seed in (5, 3000000019):
            sizes.append(yardstick.build_corpus(path, config["text"], 2 * 3462, seed))
            with open(path, "rb") as f:
                data = f.read()
            tables.append((collections.Counter(data.split(b"\n")), yardstick.oracle_table(path)))
        check("two whole shuffles: every seed the same lines, bytes and table, another order",
              sizes[0] == sizes[1] == 2 * 180181 and tables[0] == tables[1], str(sizes))
        n = yardstick.build_corpus(path, config["text"], 4463, 5)
        with open(path, "rb") as f:
            data = f.read()
        check("a cut shuffle still has exactly the lines asked for",
              data.count(b"\n") == 4463 and len(data) == n)


def delimiters() -> None:
    sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
    try:
        from locust_tpu.config import FULL_DELIMITERS
    except ImportError:
        print("skip delimiters: the program is not importable here")
        return
    check("the oracle's delimiter set equals the program's",
          yardstick.DELIMITERS == FULL_DELIMITERS)


if __name__ == "__main__":
    hand_made()
    delimiters()
    if os.path.exists(FIXTURE):
        recorded()
    else:
        check("the recorded trace is present", False, FIXTURE)
    print("selfcheck: all passed")
