"""Unit tests for bench.py's orchestrator — the driver-facing retry loop.

The orchestrator retries TPU attempts in child processes and falls back
to a CPU re-run (ROADMAP Speed item 1 replaces it); while it stays, its
control flow is pinned with stubbed
child processes (no real TPU, no real subprocesses).
"""

import json
import os
import sys
import types

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import bench


class FakeProc:
    def __init__(self, stdout="", returncode=0):
        self.stdout = stdout
        self.returncode = returncode


@pytest.fixture
def capture_emit(monkeypatch, capsys):
    monkeypatch.setattr(bench, "TIMEOUT_S", 100.0)
    monkeypatch.setattr(bench, "CPU_RESERVE_S", 30.0)
    monkeypatch.setattr(bench, "MIN_TPU_ATTEMPT_S", 10.0)
    monkeypatch.setattr(bench.time, "sleep", lambda s: None)
    return capsys


def _last_json(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


def test_orchestrator_relays_first_tpu_success(monkeypatch, capture_emit):
    tpu_row = json.dumps(
        {"metric": "wordcount_throughput", "value": 30.0, "unit": "MB/s",
         "vs_baseline": 13.6, "backend": "tpu"}
    )
    calls = []

    def fake_run(cmd, **kw):
        calls.append(kw["env"]["LOCUST_BENCH_BACKEND"])
        return FakeProc(stdout=tpu_row + "\n")

    monkeypatch.setattr(bench.subprocess, "run", fake_run)
    assert bench.orchestrate() == 0
    row = _last_json(capture_emit)
    assert row["backend"] == "tpu" and row["value"] == 30.0
    assert calls == ["tpu"]  # no CPU fallback needed


def test_orchestrator_falls_back_to_cpu_after_failures(monkeypatch, capture_emit):
    cpu_row = json.dumps(
        {"metric": "wordcount_throughput", "value": 1.0, "unit": "MB/s",
         "vs_baseline": 0.45, "backend": "cpu"}
    )
    calls = []

    # Each stubbed child "takes" 80s; the clock is otherwise frozen, so
    # with a 200s budget and 45s reserve the loop fits one TPU attempt
    # and still has reserve left for the CPU fallback.
    t = {"now": 0.0}

    def fake_run(cmd, **kw):
        backend = kw["env"]["LOCUST_BENCH_BACKEND"]
        calls.append(backend)
        t["now"] += 80.0
        if backend == "tpu":
            # Child inherits NO_CPU_RERUN and fails fast with an error row.
            assert kw["env"]["LOCUST_BENCH_NO_CPU_RERUN"] == "1"
            return FakeProc(
                stdout=json.dumps(bench.error_payload("tpu down")) + "\n",
                returncode=1,
            )
        return FakeProc(stdout=cpu_row + "\n")

    monkeypatch.setattr(bench, "TIMEOUT_S", 200.0)
    monkeypatch.setattr(bench, "CPU_RESERVE_S", 45.0)
    monkeypatch.setattr(bench, "MIN_TPU_ATTEMPT_S", 10.0)
    monkeypatch.setattr(bench.subprocess, "run", fake_run)
    monkeypatch.setattr(bench.time, "monotonic", lambda: t["now"])
    assert bench.orchestrate() == 0
    row = _last_json(capture_emit)
    assert row["backend"] == "cpu"
    assert calls[-1] == "cpu" and "tpu" in calls


def test_orchestrator_rejects_cpu_row_from_tpu_child(monkeypatch, capture_emit):
    """A TPU attempt whose child silently landed on CPU must NOT be
    relayed as the TPU result."""
    sneaky = json.dumps(
        {"metric": "wordcount_throughput", "value": 1.0, "unit": "MB/s",
         "vs_baseline": 0.45, "backend": "cpu"}
    )
    calls = []
    t = {"now": 0.0}

    def fake_run(cmd, **kw):
        calls.append(kw["env"]["LOCUST_BENCH_BACKEND"])
        t["now"] += 80.0
        return FakeProc(stdout=sneaky + "\n")

    # Two 80s mislabeled TPU attempts fit the budget; 40s remains for the
    # dedicated CPU fallback after the loop gives up.
    monkeypatch.setattr(bench, "TIMEOUT_S", 200.0)
    monkeypatch.setattr(bench, "CPU_RESERVE_S", 50.0)
    monkeypatch.setattr(bench.subprocess, "run", fake_run)
    monkeypatch.setattr(bench.time, "monotonic", lambda: t["now"])
    assert bench.orchestrate() == 0
    # The final relayed row came from the dedicated CPU fallback child,
    # not from a mislabeled TPU attempt.
    assert calls[-1] == "cpu"


def test_main_routes_inner_and_orchestrator(monkeypatch):
    seen = {}
    monkeypatch.setattr(bench, "orchestrate", lambda: (seen.setdefault("o", True), 0)[1])
    monkeypatch.setenv("LOCUST_BENCH_BACKEND", "auto")
    monkeypatch.delenv("LOCUST_BENCH_INNER", raising=False)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    assert bench.main() == 0
    assert seen.get("o") is True


def test_evidence_tuned_tpu_defaults(tmp_path, monkeypatch, capsys):
    """The latest committed A/B rows steer the TPU defaults (argmax MB/s);
    absent rows leave the static defaults untouched."""
    static = {"block_lines": 32768, "sort_mode": "hash", "use_pallas": False}
    monkeypatch.setenv("LOCUST_ARTIFACTS_DIR", str(tmp_path))
    assert bench._evidence_tuned_tpu_defaults(static) == static

    rows = [
        {"kind": "engine_sort_mode_ab", "backend": "tpu",
         "modes": {"hash": {"mb_s": 30.0}, "hashp": {"mb_s": 41.0},
                   "radix": {"mb_s": 12.0}}},
        {"kind": "block_lines_ab", "backend": "tpu",
         "blocks": {"16384": {"mb_s": 33.0}, "32768": {"mb_s": 39.0},
                    "65536": {"mb_s": 35.0}}},
        # A later losing-row update must supersede the earlier one.
        {"kind": "engine_sort_mode_ab", "backend": "tpu",
         "modes": {"hash": {"mb_s": 35.0}, "hashp2": {"mb_s": 44.0}}},
        # CPU rows of the same kind are ignored.
        {"kind": "engine_sort_mode_ab", "backend": "cpu",
         "modes": {"lex": {"mb_s": 999.0}}},
    ]
    with open(tmp_path / "tpu_runs.jsonl", "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
    tuned = bench._evidence_tuned_tpu_defaults(static)
    # block_lines row swept at "hash" (no sort_mode field => historical
    # default) but the adopted mode is hashp2 -> block size NOT adopted:
    # only jointly-measured pairs are trusted.
    assert tuned == {"block_lines": 32768, "sort_mode": "hashp2",
                     "use_pallas": False}

    # A block row recorded AT the winning mode IS adopted; a Pallas A/B
    # win flips use_pallas (an errored side has no mb_s and loses).
    with open(tmp_path / "tpu_runs.jsonl", "a") as f:
        f.write(json.dumps(
            {"kind": "block_lines_ab", "backend": "tpu",
             "sort_mode": "hashp2",
             "blocks": {"16384": {"mb_s": 45.0}, "32768": {"mb_s": 40.0}}}
        ) + "\n")
        # Measured at a DIFFERENT config -> not adopted (joint rule)...
        f.write(json.dumps(
            {"kind": "engine_pallas_ab", "backend": "tpu",
             "sort_mode": "hash", "block_lines": 32768,
             "pallas": {"False": {"mb_s": 40.0}, "True": {"mb_s": 43.0}}}
        ) + "\n")
    tuned = bench._evidence_tuned_tpu_defaults(static)
    assert tuned["use_pallas"] is False

    # ...but a win measured AT the adopted (sort_mode, block_lines) is.
    with open(tmp_path / "tpu_runs.jsonl", "a") as f:
        f.write(json.dumps(
            {"kind": "engine_pallas_ab", "backend": "tpu",
             "sort_mode": "hashp2", "block_lines": 16384,
             "pallas": {"False": {"mb_s": 40.0}, "True": {"mb_s": 43.0}}}
        ) + "\n")
    tuned = bench._evidence_tuned_tpu_defaults(static)
    assert tuned == {"block_lines": 16384, "sort_mode": "hashp2",
                     "use_pallas": True}

    # Pallas side errored (no mb_s) -> flag stays off even at the
    # matching configuration.
    with open(tmp_path / "tpu_runs.jsonl", "a") as f:
        f.write(json.dumps(
            {"kind": "engine_pallas_ab", "backend": "tpu",
             "sort_mode": "hashp2", "block_lines": 16384,
             "pallas": {"False": {"mb_s": 40.0},
                        "True": {"error": "MosaicError: ..."}}}
        ) + "\n")
    tuned = bench._evidence_tuned_tpu_defaults(static)
    assert tuned["use_pallas"] is False


def test_evidence_tuning_caps_rule(tmp_path, monkeypatch, capsys):
    """A/B rows are trusted only at matching caps: a row swept at the
    sweep corpus's caps must not steer a bench assembling different ones
    (e.g. a LOCUST_BENCH_VOCAB corpus)."""
    static = {"block_lines": 32768, "sort_mode": "hash", "use_pallas": False}
    monkeypatch.setenv("LOCUST_ARTIFACTS_DIR", str(tmp_path))
    with open(tmp_path / "tpu_runs.jsonl", "w") as f:
        f.write(json.dumps(
            {"kind": "engine_sort_mode_ab", "backend": "tpu",
             "caps": {"key_width": 16, "emits_per_line": 17},
             "modes": {"hash": {"mb_s": 30.0}, "hashp": {"mb_s": 44.0}}}
        ) + "\n")
    # Different caps -> not adopted.
    tuned = bench._evidence_tuned_tpu_defaults(
        static, {"key_width": 8, "emits_per_line": 10}
    )
    assert tuned == static
    # Matching caps -> adopted.
    tuned = bench._evidence_tuned_tpu_defaults(
        static, {"key_width": 16, "emits_per_line": 17}
    )
    assert tuned["sort_mode"] == "hashp"
    # A pre-caps row (no field) counts as engine defaults 32/20.
    with open(tmp_path / "tpu_runs.jsonl", "w") as f:
        f.write(json.dumps(
            {"kind": "engine_sort_mode_ab", "backend": "tpu",
             "modes": {"hash": {"mb_s": 30.0}, "hash1": {"mb_s": 44.0}}}
        ) + "\n")
    tuned = bench._evidence_tuned_tpu_defaults(
        static, {"key_width": 32, "emits_per_line": 20}
    )
    assert tuned["sort_mode"] == "hash1"
    tuned = bench._evidence_tuned_tpu_defaults(
        static, {"key_width": 16, "emits_per_line": 17}
    )
    assert tuned == static


def test_evidence_tuning_survives_malformed_rows(tmp_path, monkeypatch, capsys):
    """Evidence must never break a run: a null-mode row (exactly what
    artifacts.record's exception fallback can append) or an unknown sort
    mode falls back to the static defaults instead of crashing the TPU
    child before it even probes."""
    static = {"block_lines": 32768, "sort_mode": "hash"}
    monkeypatch.setenv("LOCUST_ARTIFACTS_DIR", str(tmp_path))
    with open(tmp_path / "tpu_runs.jsonl", "w") as f:
        f.write(json.dumps(
            {"kind": "engine_sort_mode_ab", "backend": "tpu",
             "modes": {"hash": None}}
        ) + "\n")
    assert bench._evidence_tuned_tpu_defaults(static) == static

    with open(tmp_path / "tpu_runs.jsonl", "w") as f:
        f.write(json.dumps(
            {"kind": "engine_sort_mode_ab", "backend": "tpu",
             "modes": {"mode_deleted_in_v9": {"mb_s": 99.0}}}
        ) + "\n")
    assert bench._evidence_tuned_tpu_defaults(static) == static


def test_evidence_tuning_guards_each_kind_independently(
    tmp_path, monkeypatch, capsys
):
    """One malformed row of one kind must not revert knobs validly
    adopted from well-formed rows of OTHER kinds (ADVICE r3: the old
    single try/except discarded sort_mode + block_lines together when the
    pallas row was malformed)."""
    static = {"block_lines": 32768, "sort_mode": "hash", "use_pallas": False}
    monkeypatch.setenv("LOCUST_ARTIFACTS_DIR", str(tmp_path))
    with open(tmp_path / "tpu_runs.jsonl", "w") as f:
        f.write(json.dumps(
            {"kind": "engine_sort_mode_ab", "backend": "tpu",
             "modes": {"hash": {"mb_s": 30.0}, "hashp": {"mb_s": 44.0}}}
        ) + "\n")
        # Null A/B sides in the OTHER kinds (exactly what artifacts.record's
        # exception fallback can append) must leave the hashp adoption alone.
        f.write(json.dumps(
            {"kind": "block_lines_ab", "backend": "tpu", "sort_mode": "hashp",
             "blocks": {"16384": None, "32768": None}}
        ) + "\n")
        f.write(json.dumps(
            {"kind": "engine_pallas_ab", "backend": "tpu",
             "sort_mode": "hashp", "block_lines": 32768, "pallas": None}
        ) + "\n")
    tuned = bench._evidence_tuned_tpu_defaults(static)
    assert tuned == {"block_lines": 32768, "sort_mode": "hashp",
                     "use_pallas": False}


def test_evidence_tuning_rejects_off_shape_corpus(tmp_path, monkeypatch, capsys):
    """Sweeps at other sizes record A/B rows at 8MB /
    64MB into the same ledger kinds; a row measured at a different
    corpus size than the headline bench runs must not steer its config
    (code review, r5).  Legacy rows without corpus_mb still count."""
    static = {"block_lines": 32768, "sort_mode": "hash", "use_pallas": False}
    monkeypatch.setenv("LOCUST_ARTIFACTS_DIR", str(tmp_path))
    caps = {"key_width": 32, "emits_per_line": 20}
    with open(tmp_path / "tpu_runs.jsonl", "w") as f:
        f.write(json.dumps(
            {"kind": "engine_sort_mode_ab", "backend": "tpu",
             "corpus_mb": 8.4,  # second-source shape, not the headline
             "modes": {"hashp": {"mb_s": 70.0}}}
        ) + "\n")
    assert bench._evidence_tuned_tpu_defaults(static, caps) == static
    # Headline-shaped row (33.6MB vs TARGET_BYTES 33.55MB): adopted.
    with open(tmp_path / "tpu_runs.jsonl", "w") as f:
        f.write(json.dumps(
            {"kind": "engine_sort_mode_ab", "backend": "tpu",
             "corpus_mb": 33.6,
             "modes": {"hashp": {"mb_s": 70.0}}}
        ) + "\n")
    assert bench._evidence_tuned_tpu_defaults(static, caps)[
        "sort_mode"] == "hashp"
    # Legacy row, no corpus_mb field: treated as headline-shaped.
    with open(tmp_path / "tpu_runs.jsonl", "w") as f:
        f.write(json.dumps(
            {"kind": "engine_sort_mode_ab", "backend": "tpu",
             "modes": {"hash1": {"mb_s": 70.0}}}
        ) + "\n")
    assert bench._evidence_tuned_tpu_defaults(static, caps)[
        "sort_mode"] == "hash1"


def test_evidence_tuning_reaches_past_off_shape_rows(
    tmp_path, monkeypatch, capsys
):
    """An off-shape (second-source) row landing LAST must not knock the
    kind out: tuning skips back to the newest row passing the joint
    rules (code review, r5)."""
    static = {"block_lines": 32768, "sort_mode": "hash", "use_pallas": False}
    monkeypatch.setenv("LOCUST_ARTIFACTS_DIR", str(tmp_path))
    caps = {"key_width": 32, "emits_per_line": 20}
    with open(tmp_path / "tpu_runs.jsonl", "w") as f:
        # Valid headline-shaped rows first...
        f.write(json.dumps(
            {"kind": "engine_sort_mode_ab", "backend": "tpu",
             "corpus_mb": 33.6, "modes": {"hashp2": {"mb_s": 57.6}}}
        ) + "\n")
        f.write(json.dumps(
            {"kind": "block_lines_ab", "backend": "tpu", "corpus_mb": 33.6,
             "sort_mode": "hashp2",
             "blocks": {"32768": {"mb_s": 55.0}, "65536": {"mb_s": 64.0}}}
        ) + "\n")
        # ...then an 8MB second-source sweep appends off-shape rows LAST.
        f.write(json.dumps(
            {"kind": "engine_sort_mode_ab", "backend": "tpu",
             "corpus_mb": 8.4, "modes": {"hasht": {"mb_s": 70.0}}}
        ) + "\n")
        f.write(json.dumps(
            {"kind": "block_lines_ab", "backend": "tpu", "corpus_mb": 8.4,
             "sort_mode": "hasht", "blocks": {"16384": {"mb_s": 71.0}}}
        ) + "\n")
    tuned = bench._evidence_tuned_tpu_defaults(static, caps)
    assert tuned["sort_mode"] == "hashp2"
    assert tuned["block_lines"] == 65536


def test_evidence_tuning_rejects_lossy_sides(tmp_path, monkeypatch, capsys):
    """A faster-but-lossy A/B side must never steer the headline config:
    nonzero overflow_tokens, or fewer distinct
    keys than the best side of the same row (= dropped tokens or a
    truncated table), disqualify a side; the best LOSSLESS side wins
    instead."""
    static = {"block_lines": 32768, "sort_mode": "hash", "use_pallas": False}
    monkeypatch.setenv("LOCUST_ARTIFACTS_DIR", str(tmp_path))
    with open(tmp_path / "tpu_runs.jsonl", "w") as f:
        # "hashp" is fastest but dropped tokens (overflow); "hash1" is
        # second-fastest but its table lost distinct keys; "hashp2" is
        # the best exact side and must be the one adopted.
        f.write(json.dumps(
            {"kind": "engine_sort_mode_ab", "backend": "tpu",
             "modes": {
                 "hashp": {"mb_s": 60.0, "overflow_tokens": 275802,
                           "distinct": 5608},
                 "hash1": {"mb_s": 55.0, "overflow_tokens": 0,
                           "distinct": 5476},
                 "hashp2": {"mb_s": 50.0, "overflow_tokens": 0,
                            "distinct": 5608},
                 "radix": {"mb_s": 10.0, "distinct": 5608},
             }}
        ) + "\n")
        # A lossy pallas=True side must not flip the flag either.
        f.write(json.dumps(
            {"kind": "engine_pallas_ab", "backend": "tpu",
             "sort_mode": "hashp2", "block_lines": 32768,
             "pallas": {
                 "True": {"mb_s": 70.0, "distinct": 5000},
                 "False": {"mb_s": 50.0, "distinct": 5608},
             }}
        ) + "\n")
    tuned = bench._evidence_tuned_tpu_defaults(static)
    assert tuned["sort_mode"] == "hashp2"
    assert tuned["use_pallas"] is False

    # All sides lossy -> nothing adoptable -> static default survives.
    with open(tmp_path / "tpu_runs.jsonl", "w") as f:
        f.write(json.dumps(
            {"kind": "engine_sort_mode_ab", "backend": "tpu",
             "modes": {"hashp": {"mb_s": 60.0, "overflow_tokens": 7,
                                 "distinct": 5608}}}
        ) + "\n")
    assert bench._evidence_tuned_tpu_defaults(static) == static


def test_error_payload_shape():
    row = bench.error_payload("boom")
    assert set(row) >= {"metric", "value", "unit", "vs_baseline", "error"}
    assert row["value"] == 0.0


def test_bad_config_env_still_emits_one_json_line(tmp_path):
    """A malformed LOCUST_* env var that locust_tpu.config rejects at
    import must surface as the single JSON error line, not a bare
    traceback — config import happens inside main()'s guard (and the
    module-level cache-dir import is its own no-cache-beats-no-JSON
    try).  Real subprocess: the failure mode is import-order-dependent."""
    import subprocess

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env.update(
        PYTHONPATH=repo,
        JAX_PLATFORMS="cpu",
        LOCUST_BENCH_BACKEND="cpu",
        LOCUST_BITONIC_MAX_FUSED="-1",
        LOCUST_ARTIFACTS_DIR=str(tmp_path),
    )
    out = subprocess.run(
        [sys.executable, os.path.join(repo, "bench.py")],
        env=env, capture_output=True, text=True, timeout=120,
    )
    lines = [ln for ln in out.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1, out.stdout + out.stderr
    row = json.loads(lines[0])
    assert "LOCUST_BITONIC_MAX_FUSED" in row["error"]
    assert out.returncode == 1


def test_best_tpu_ab_row_picks_max_and_labels(tmp_path, monkeypatch):
    """The CPU-fallback embed must surface the strongest committed
    engine-level A/B measurement with its kind/setting, skipping errored
    sides (they have no mb_s)."""
    monkeypatch.setenv("LOCUST_ARTIFACTS_DIR", str(tmp_path))
    with open(tmp_path / "tpu_runs.jsonl", "w") as f:
        f.write(json.dumps(
            {"kind": "engine_sort_mode_ab", "backend": "tpu", "ts": 1.0,
             "device": "TPU v5 lite",
             "modes": {"hashp2": {"mb_s": 57.6},
                       "bitonic": {"error": "MosaicError"}}}
        ) + "\n")
        f.write(json.dumps(
            {"kind": "block_lines_ab", "backend": "tpu", "ts": 2.0,
             "device": "TPU v5 lite",
             "blocks": {"65536": {"mb_s": 63.95}, "32768": {"mb_s": 57.4}}}
        ) + "\n")
    row = bench._best_tpu_ab_row()
    assert row["value"] == 63.95
    assert row["kind"] == "block_lines_ab"
    assert row["setting"] == "65536"
    assert row["vs_baseline"] == round(63.95 / bench.BASELINE_MB_S, 2)


def test_best_tpu_ab_row_empty_ledger(tmp_path, monkeypatch):
    monkeypatch.setenv("LOCUST_ARTIFACTS_DIR", str(tmp_path))
    assert bench._best_tpu_ab_row() is None


def test_auto_table_size_rule():
    """Distinct-aware table sizing: power of two >= 2x distinct, floor
    4096, ceiling the default resolution."""
    assert bench._auto_table_size(100, 65536) == 4096
    assert bench._auto_table_size(2048, 65536) == 4096
    assert bench._auto_table_size(2049, 65536) == 8192
    assert bench._auto_table_size(5608, 65536) == 16384
    assert bench._auto_table_size(60000, 65536) == 65536   # ceiling
    assert bench._auto_table_size(500000, 65536) == 65536  # never above


def test_count_distinct_tokens_engine_semantics():
    from locust_tpu.io.loader import count_distinct_tokens

    lines = [b"to be, or not to-be", b"to be, or not to-be", b"that\tis"]
    # strtok semantics: ',' '-' '\t' split; duplicates (incl. whole
    # duplicate lines) count once: to, be, or, not, that, is
    assert count_distinct_tokens(lines) == 6
    assert count_distinct_tokens([]) == 0
    assert count_distinct_tokens([b"", b"  , "]) == 0


def test_evidence_tuning_adopts_table_size_jointly(tmp_path, monkeypatch, capsys):
    """engine_table_ab adoption: only at the adopted (mode, block) pair,
    truncated sides never win, and the pallas joint rule now includes
    the adopted table."""
    static = {"block_lines": 32768, "sort_mode": "hash", "use_pallas": False}
    monkeypatch.setenv("LOCUST_ARTIFACTS_DIR", str(tmp_path))
    with open(tmp_path / "tpu_runs.jsonl", "w") as f:
        f.write(json.dumps(
            {"kind": "engine_sort_mode_ab", "backend": "tpu",
             "modes": {"hasht": {"mb_s": 70.0, "distinct": 5608}}}
        ) + "\n")
        f.write(json.dumps(
            {"kind": "block_lines_ab", "backend": "tpu",
             "sort_mode": "hasht",
             "blocks": {"65536": {"mb_s": 72.0, "distinct": 5608}}}
        ) + "\n")
        f.write(json.dumps(
            {"kind": "engine_table_ab", "backend": "tpu",
             "sort_mode": "hasht", "block_lines": 65536,
             "measured_distinct": 5608,
             "tables": {
                 "65536": {"mb_s": 72.0, "distinct": 5608,
                           "truncated": False},
                 "16384": {"mb_s": 80.0, "distinct": 5608,
                           "truncated": False},
                 "4096": {"mb_s": 95.0, "distinct": 4096,
                          "truncated": True},
             }}
        ) + "\n")
        # Pallas row measured WITHOUT the adopted table -> joint fails.
        f.write(json.dumps(
            {"kind": "engine_pallas_ab", "backend": "tpu",
             "sort_mode": "hasht", "block_lines": 65536,
             "pallas": {"True": {"mb_s": 99.0}, "False": {"mb_s": 70.0}}}
        ) + "\n")
    tuned = bench._evidence_tuned_tpu_defaults(static)
    assert tuned["sort_mode"] == "hasht"
    assert tuned["block_lines"] == 65536
    assert tuned["table_size"] == 16384  # fastest LOSSLESS side
    assert tuned["use_pallas"] is False  # table mismatch blocks the flip

    # A pallas row AT the adopted table flips it.
    with open(tmp_path / "tpu_runs.jsonl", "a") as f:
        f.write(json.dumps(
            {"kind": "engine_pallas_ab", "backend": "tpu",
             "sort_mode": "hasht", "block_lines": 65536,
             "table_size": 16384,
             "pallas": {"True": {"mb_s": 99.0}, "False": {"mb_s": 70.0}}}
        ) + "\n")
    tuned = bench._evidence_tuned_tpu_defaults(static)
    assert tuned["use_pallas"] is True

    # A table row at a DIFFERENT mode/block pair is never adopted.
    with open(tmp_path / "tpu_runs.jsonl", "a") as f:
        f.write(json.dumps(
            {"kind": "engine_table_ab", "backend": "tpu",
             "sort_mode": "hashp2", "block_lines": 32768,
             "tables": {"8192": {"mb_s": 120.0, "distinct": 5608,
                                 "truncated": False}}}
        ) + "\n")
    tuned = bench._evidence_tuned_tpu_defaults(static)
    assert tuned["table_size"] == 16384


def test_evidence_readers_match_config_ab_kinds(tmp_path, monkeypatch):
    """ADVICE r5: bench's per-kind evidence reads are derived from the
    shared artifacts.CONFIG_AB_KINDS tuple, and a drift between the two
    fails loudly instead of leaving the committed headline stale."""
    from locust_tpu.utils import artifacts
    from locust_tpu.utils.artifacts import CONFIG_AB_KINDS

    led = tmp_path / "artifacts"
    led.mkdir()
    monkeypatch.setenv("LOCUST_ARTIFACTS_DIR", str(led))
    defaults = {"sort_mode": "hashp2", "block_lines": 32768}
    # Empty ledger: every kind consulted, defaults returned unchanged.
    assert bench._evidence_tuned_tpu_defaults(defaults) == defaults
    # Drift (a kind added to the shared tuple without a bench reader)
    # must raise, not silently skip the new kind.
    monkeypatch.setattr(
        artifacts, "CONFIG_AB_KINDS", CONFIG_AB_KINDS + ("new_kind_ab",)
    )
    with pytest.raises(RuntimeError, match="drifted"):
        bench._evidence_tuned_tpu_defaults(defaults)


def test_bench_subdict_producers_match_registry(monkeypatch):
    """The guarded sub-bench producers are two-sided against
    artifacts.BENCH_SUBDICT_KINDS (same discipline as CONFIG_AB_KINDS):
    a kind registered without a producer — or vice versa — raises
    instead of silently dropping a sub-dict from the headline line."""
    from locust_tpu.utils import artifacts

    subdicts = bench._bench_subdict_producers()
    assert tuple(subdicts) == tuple(artifacts.BENCH_SUBDICT_KINDS)
    monkeypatch.setattr(
        artifacts,
        "BENCH_SUBDICT_KINDS",
        dict(artifacts.BENCH_SUBDICT_KINDS, new_sub="new_sub_bench"),
    )
    with pytest.raises(RuntimeError, match="drifted"):
        bench._bench_subdict_producers()
