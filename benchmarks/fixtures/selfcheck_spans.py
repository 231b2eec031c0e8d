#!/usr/bin/env python3
"""Self-check of the readers that read the program's own spans, on a CPU
sandbox (beside ``selfcheck.py``, which checks ``trace_reduce``):

    python3 benchmarks/fixtures/selfcheck_spans.py

* ``device_in_span``'s busy-time function and offset fit, and ``span_count``,
  on hand-made intervals;
* ``device_in_span`` and ``span_count`` on the recorded chip trace
  ``ref4463_2jobs_spans.xplane.pb`` (two jobs of ``ref4463.jobs`` on one TPU
  v5e, PR 24: the program's spans are annotations on ``/host:CPU`` of the
  trace) must give the values in ``ref4463_2jobs_spans.expected.json`` —
  written from that same trace, and re-derived here a second way: a sweep
  over the raw op boundaries that shares no code with the reader;
* with the trace's device clock set 1.1 ms early, as one profiler session in
  three of PR 24's had it, the offset fit finds its way back to the same values;
* on that trace the three stage metrics sum to the device time of the
  three programs ``process_dev_ms`` reads, which is what lets them split it.

Prints one line per check and exits non-zero on the first failure.
"""

from __future__ import annotations

import json
import math
import os
import shutil
import sys
import tempfile
import types

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import trace_reduce as tr  # noqa: E402
from readers import device_in_span, span_count, xla_module  # noqa: E402

FIXTURE = os.path.join(HERE, "ref4463_2jobs_spans.xplane.pb")
EXPECTED = os.path.join(HERE, "ref4463_2jobs_spans.expected.json")
STAGE_METRICS = ("sort_dev_ms", "reduce_dev_ms", "merge_dev_ms")


def check(name: str, ok: bool, detail: str = "") -> None:
    print(f"{'ok  ' if ok else 'FAIL'} {name} {detail}", flush=True)
    if not ok:
        raise SystemExit(1)


def close(a: float, b: float, rel: float = 1e-9) -> bool:
    return math.isclose(a, b, rel_tol=rel, abs_tol=1e-12)


def spec_of(metric: str) -> dict:
    with open(os.path.join(BENCH, "layer_metrics", metric + ".json")) as f:
        return json.load(f)


def hand_made() -> None:
    busy = device_in_span.DeviceBusy([(0, 10), (20, 30), (40, 50)])
    check("busy time before t: whole ops, a cut op, nothing",
          [busy.before(t) for t in (-1, 0, 4, 10, 15, 25, 50, 99)] == [0, 0, 4, 10, 10, 15, 30, 30])
    check("busy inside spans: ops cut at the span's edges, what lies outside skipped",
          busy.inside([(5, 25), (45, 100)]) == 5 + 5 + 5
          and busy.inside([(10, 20), (60, 70)]) == 0 and busy.inside([]) == 0
          and device_in_span.DeviceBusy([]).inside([(0, 9)]) == 0)
    check("a device clock moved later moves its ops later against the spans",
          busy.inside([(12, 18)]) == 0 and busy.inside([(12, 18)], shift=5) == 3
          and busy.inside([(12, 18)], shift=-5) == 3)
    # spans that hold all the work once the device clock is moved 1.0-1.2 ms later
    step = device_in_span.STEP_NS
    ops = [(k * 100 * step, (k * 100 + 2) * step) for k in range(50)]
    spans = [(a + 20 * step, b + 24 * step) for a, b in ops]
    early = device_in_span.DeviceBusy(ops)
    fit = device_in_span.fit_offset(early, spans)
    check("fit_offset: the middle of the offsets that put all the work inside the spans",
          early.inside(spans) == 0 and fit == 22 * step
          and early.inside(spans, fit) == 50 * 2 * step, f"{fit} ns")
    jobs = [types.SimpleNamespace(spans=[("engine.sync", 0, 1)] * 11 + [("cli.run", 0, 9)]),
            types.SimpleNamespace(spans=[("engine.sync", 0, 1)] * 13),
            types.SimpleNamespace(spans=[("engine.sync", 0, 1)] * 11),
            types.SimpleNamespace(spans=[])]           # an untraced job: not counted
    env = types.SimpleNamespace(jobs=jobs)
    check("span_count: median over the jobs that recorded the span",
          span_count.read({"span": "engine.sync"}, env) == 11.0
          and span_count.read({"span": "no.such"}, env) is None)


def covered_by_sweep(ops, spans) -> float:
    """ns in which at least one op AND at least one span is open: one sweep
    over all boundaries, no merging and no bisection."""
    edges = ([(a, 0, +1) for a, _ in ops] + [(b, 0, -1) for _, b in ops]
             + [(a, 1, +1) for a, _ in spans] + [(b, 1, -1) for _, b in spans])
    open_ = [0, 0]
    total, last = 0.0, None
    for t, kind, step in sorted(edges, key=lambda e: (e[0], e[2])):
        if last is not None and open_[0] > 0 and open_[1] > 0:
            total += t - last
        open_[kind] += step
        last = t
    return total


def recorded() -> None:
    with open(EXPECTED) as f:
        want = json.load(f)
    red = tr.reduce_trace(FIXTURE)
    red["slice_jobs"] = [None] * len(red["jobs"])
    check("two job annotations, one chip", len(red["jobs"]) == want["jobs"] == 2
          and sorted(red["devices"]) == [0])
    pd = tr.load(FIXTURE)
    lo, hi = red["jobs"][0][0], red["jobs"][-1][1]
    plane = pd.find_plane_with_name("/device:TPU:0")
    raw_ops = [(e.start_ns, e.start_ns + e.duration_ns)
               for line in plane.lines if line.name == tr.OPS_LINE for e in line.events]
    host = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for line in pd.find_plane_with_name(tr.HOST_PLANE).lines for e in line.events]
    with tempfile.TemporaryDirectory() as tmp:
        # the reader looks where run.py's profiler session writes
        run_dir = os.path.join(tmp, "plugins", "profile", "recorded")
        os.makedirs(run_dir)
        shutil.copy(FIXTURE, run_dir)
        said = []
        env = types.SimpleNamespace(trace=red, profile_dir=tmp, say=said.append)
        got = {}
        for metric in STAGE_METRICS:
            spec = spec_of(metric)
            got[metric] = device_in_span.read(spec, env)
            spans = [(a, b) for n, a, b in host if n == spec["span"] and b > lo and a < hi]
            by_hand = covered_by_sweep(raw_ops, spans) / 1e6 / 2
            check(f"{metric} ({spec['span']}, {len(spans)} spans): the expected value, "
                  "and the sweep over raw boundaries",
                  close(got[metric], want[metric]) and close(got[metric], by_hand, 1e-9)
                  and len(spans) == want["spans"][spec["span"]], f"{got[metric]} ms")
        check("a span the trace does not hold reads nothing, not 0; the fit was said once",
              device_in_span.read({"span": "no.such.span", "holds_all_work": "engine.stage."},
                                  env) is None and len(said) == 1, said[0])
    # the same trace with its device clock 1.1 ms early: unfitted the split is
    # wrong by a third, fitted it is the same to the nanosecond
    stage_spans = device_in_span.annotations_with_prefix(pd, "engine.stage.")
    early = device_in_span.DeviceBusy([(a - 1.1e6, b - 1.1e6) for a, b in tr.merge(raw_ops)])
    fit = device_in_span.fit_offset(early, tr.clip(stage_spans, lo, hi))
    cut = {m: tr.clip(tr.annotations(pd, spec_of(m)["span"]), lo, hi) for m in STAGE_METRICS}
    unfitted = {m: early.inside(cut[m]) / 2e6 for m in STAGE_METRICS}
    check("the device clock 1.1 ms early: unfitted the split is wrong, fitted it is the same",
          max(abs(unfitted[m] - want[m]) / want[m] for m in STAGE_METRICS) > 0.3
          and all(close(early.inside(cut[m], fit) / 2e6, want[m], 1e-9) for m in STAGE_METRICS),
          f"unfitted {unfitted}, offset found {fit / 1e3:+.0f} us")
    # what lets the three split process_dev_ms: each program's ops lie inside
    # its stage's span, so the three sum to the programs' device time
    modules = red["devices"][0]["modules"]
    programs = sum(s for s, _ in xla_module.matched(
        modules, spec_of("process_dev_ms")["patterns"])) * 1e3 / 2
    total = sum(got.values())
    check("sort + reduce + merge = the device time of process_dev_ms's programs within 1%",
          abs(total - programs) / programs < 0.01, f"{total} against {programs} ms")
    all_stage = [(a, b) for n, a, b in host if n.startswith("engine.stage.") and b > lo and a < hi]
    busy = red["devices"][0]["busy_s"] * 1e3 / 2
    inside = covered_by_sweep(raw_ops, all_stage) / 1e6 / 2
    check("the stage spans hold the device's work (every stage waits for its own): all "
          "but the microseconds of the helper programs that fill the empty table",
          busy * 0.999 <= inside <= busy * (1 + 1e-9), f"{inside} inside, {busy} busy, ms per job")
    # span_count on jobs rebuilt from the trace's own annotations
    jobs = [types.SimpleNamespace(spans=[(n, s, e) for n, s, e in host if a <= s and e <= b])
            for a, b in red["jobs"]]
    spec = spec_of("syncs_per_job")
    by_hand = [sum(1 for n, s, e in host if n == spec["span"] and a <= s and e <= b)
               for a, b in red["jobs"]]
    got_n = span_count.read(spec, types.SimpleNamespace(jobs=jobs))
    check("syncs_per_job: 5 a block x 2 blocks + the closing sync",
          by_hand == [11, 11] and got_n == want["syncs_per_job"] == 11.0, f"{got_n}")


if __name__ == "__main__":
    hand_made()
    if os.path.exists(FIXTURE):
        recorded()
    else:
        check("the recorded trace is present", False, FIXTURE)
    print("selfcheck_spans: all passed")
