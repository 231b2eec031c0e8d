"""The self-checks of the span-read metrics (PR 24), on the CPU; kept out of
``tests/`` like ``test_controls.py``:

    env JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_span_metrics.py -q

1. ``fixtures/selfcheck_spans.py`` passes: ``device_in_span`` and
   ``span_count`` equal a sum by hand on the recorded chip trace;
2. a traced rehearsal of each cell names its span-read metrics in the
   "metrics read" line — every ``obs_span`` / ``span_count`` metric the cell
   lists in BENCHMARK.json; the ``device_in_span`` ones read nothing on a
   CPU, as the other device readers do.
"""

import json
import os
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    _BENCHMARK = json.load(_f)
SPAN_READERS = ("obs_span", "span_count", "device_in_span")
# the spans PR 24 added to the program; cli.* were read before it
NEW_SPANS = ("engine.h2d", "engine.sync", "engine.finalize", "engine.program.trace",
             "engine.program.lower", "engine.program.load")


def _spec(name):
    path = os.path.join(BENCH, "layer_metrics", name + ".json")
    if not os.path.exists(path):
        path = os.path.join(BENCH, "layer_metrics", name.rpartition(".")[0] + ".json")
    with open(path) as f:
        return json.load(f)


def _span_metrics(cell):
    """{reader: [metric names]} of the cell's metrics that read a span."""
    out = {}
    for m in _BENCHMARK["per_layer"]:
        if cell in m.get("workloads", [cell]):
            spec = _spec(m["name"])
            if spec["reader"] in SPAN_READERS:
                out.setdefault(spec["reader"], []).append((m["name"], spec["span"]))
    return out


def test_selfcheck_spans_passes():
    p = subprocess.run([sys.executable, os.path.join(BENCH, "fixtures", "selfcheck_spans.py")],
                       env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
                       text=True, timeout=300)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-2000:]
    assert "selfcheck_spans: all passed" in p.stdout and "FAIL" not in p.stdout


@pytest.mark.parametrize("cell,new_span_metrics", [("wc100.batch", 4), ("ref4463.jobs", 7)])
def test_traced_rehearsal_names_the_span_read_metrics(cell, new_span_metrics):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    p = subprocess.run([sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
                        "--seed", "2147483659", "--seconds", "3", "--trace", "1", "--rehearse"],
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    assert json.loads(p.stdout.strip().splitlines()[-1])["correct"] is True
    [line] = [ln for ln in p.stdout.splitlines() if "rehearsal: metrics read" in ln]
    read = set(line.rpartition(": ")[2].split(", "))
    by_reader = _span_metrics(cell)
    host_side = by_reader.get("obs_span", []) + by_reader.get("span_count", [])
    assert {name for name, _ in host_side} <= read, (host_side, read)
    assert sum(1 for name, span in host_side if span in NEW_SPANS) == new_span_metrics
    # no chip, no device trace: a device reader reads nothing rather than 0
    assert not {name for name, _ in by_reader.get("device_in_span", [])} & read
