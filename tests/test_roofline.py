"""utils/roofline.py — the sort-traffic/bandwidth model behind the bench's
chip-utilization claim."""

import json
import os
import subprocess
import sys

from locust_tpu.utils import roofline

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_sort_pass_count():
    # bitonic: k(k+1)/2 for k = ceil(log2 n)
    assert roofline.sort_pass_count(2) == 1
    assert roofline.sort_pass_count(1024) == 55
    assert roofline.sort_pass_count(1025) == 66  # k=11
    assert roofline.sort_pass_count(1) == 0
    assert roofline.sort_pass_count(720_896, "radix") == 4


def test_mode_row_bytes_ordering():
    """Payload modes carry more per pass but skip the gather; gather modes
    sort narrow operands.  Spot-check the structural relations rather than
    re-deriving every constant."""
    lanes = 4  # key_width 16
    per_pass = {m: roofline.mode_row_bytes(m, lanes) for m in
                ("hash", "hashp", "hashp2", "hashp1", "hash1", "lex")}
    # Each step down the payload-carry ladder drops one key operand.
    assert per_pass["hashp2"][0] == per_pass["hashp"][0] - 4
    assert per_pass["hashp1"][0] == per_pass["hashp2"][0] - 4
    assert per_pass["hashp1"][1] == 0  # no gather
    # hash1 sorts the narrowest operand set of the gather modes.
    assert per_pass["hash1"][0] < per_pass["hash"][0]
    # Gather modes pay the row move once; payload modes don't.
    assert per_pass["hash"][1] > 0 and per_pass["hashp"][1] == 0
    # Payload modes carry the full row every pass.
    assert per_pass["hashp"][0] == 4 * (3 + lanes + 1)


def test_summarize_utilization():
    s = roofline.summarize(
        "hashp", 4, 32768 * 17, 65536, 3, 0.1, "TPU v5 lite"
    )
    assert s["hbm_peak_gb_s"] == 819.0
    assert s["hbm_utilization_pct"] is not None
    assert 0 < s["hbm_utilization_pct"] <= 100 or s["achieved_sort_gb_s"] > 819
    # Traffic scales linearly in block count.
    s2 = roofline.summarize(
        "hashp", 4, 32768 * 17, 65536, 6, 0.1, "TPU v5 lite"
    )
    assert s2["est_sort_traffic_bytes"] == 2 * s["est_sort_traffic_bytes"]

    unknown = roofline.summarize("hashp", 4, 100, 100, 1, 0.1, "cpu")
    assert unknown["hbm_peak_gb_s"] is None
    assert unknown["hbm_utilization_pct"] is None


def test_bench_payload_includes_roofline():
    """The driver JSON line carries the utilization summary (tiny corpus
    keeps this fast; the one-line contract must survive the addition)."""
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        PYTHONPATH=REPO,
        LOCUST_BENCH_BACKEND="cpu",
        LOCUST_BENCH_CPU_BYTES="300000",
        LOCUST_ARTIFACTS_DIR="/tmp/locust_roofline_test_artifacts",
    )
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=env, capture_output=True, text=True, timeout=300,
    )
    lines = [ln for ln in out.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1, out.stdout
    row = json.loads(lines[0])
    assert "roofline" in row
    assert row["roofline"]["hbm_peak_gb_s"] is None  # CPU: no claim
    assert row["roofline"]["achieved_sort_gb_s"] > 0
    assert "[bench] roofline:" in out.stderr
