"""The span-read metrics PR 37 added, on the CPU; kept out of ``tests/`` like
``test_span_metrics.py``, whose cases and counts it leaves alone:

    env JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_tail_span_metrics.py -q

A traced rehearsal of each of the three cells these metrics are for names, in
its "metrics read" line, the new entries BENCHMARK.json lists for that cell and
none it lists only for another: the table's way to the host and the output's two
halves in ``wczipf.batch`` and ``wczipf250.mesh4``, the round's staging in the
mesh cell alone, the CLI's set-up in ``ref4463.jobs`` alone.
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

# entry -> the span its metric file reads
NEW = {
    "finalize_d2h_ms.tput": "engine.finalize.d2h",
    "finalize_decode_ms.tput": "engine.finalize.decode",
    "finalize_order_ms.tput": "engine.finalize.order",
    "output_render_ms.tput": "cli.output.render",
    "output_write_ms.tput": "cli.output.write",
    "mesh_h2d_ms.mesh": "mesh.h2d",
    "job_setup_ms.lat": "cli.setup",
}
TAIL = {n for n in NEW if n.startswith(("finalize_", "output_"))}
CELLS = {
    "wczipf.batch": TAIL,
    "wczipf250.mesh4": TAIL | {"mesh_h2d_ms.mesh"},
    "ref4463.jobs": {"job_setup_ms.lat"},
}


def test_every_new_entry_has_its_file_and_its_cells():
    by_name = {m["name"]: m for m in _BENCHMARK["per_layer"]}
    for name, span in NEW.items():
        with open(os.path.join(BENCH, "layer_metrics", name.rpartition(".")[0] + ".json")) as f:
            spec = json.load(f)
        assert (spec["reader"], spec["span"]) == ("obs_span", span)
        meta = by_name[name]
        assert meta["source"] == "program_span" and meta["unit"] == "ms"
        assert set(meta["workloads"]) == {c for c, names in CELLS.items() if name in names}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_traced_rehearsal_names_the_cells_new_metrics_and_no_other_cells(cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    p = subprocess.run([sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
                        "--seed", "2147483693", "--seconds", "2", "--trace", "1", "--rehearse"],
                       env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    assert json.loads(p.stdout.strip().splitlines()[-1])["correct"] is True
    [line] = [ln for ln in p.stdout.splitlines() if "rehearsal: metrics read" in ln]
    read = set(line.rpartition(": ")[2].split(", "))
    assert read & set(NEW) == CELLS[cell], (sorted(read & set(NEW)), sorted(CELLS[cell]))
