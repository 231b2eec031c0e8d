"""Benchmark: WordCount throughput on one TPU chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

Baseline: the reference's implied end-to-end GTX 1060 throughput —
hamlet.txt (~175KB, 4,463 lines) in ~77.5 ms total GPU stage time
=> ~2.2 MB/s (BASELINE.md "Notes").  vs_baseline = our MB/s / 2.2.

Method: replicate the corpus to a fixed size, stage it on device, run the
fused single-dispatch pipeline (engine.run_blocks: lax.scan over blocks),
report the best of 3 steady-state runs.  Timing starts with the scan
dispatch and ends at a host sync — the same boundary as the reference,
whose published stage times start after its H2D memcpy (main.cu:402-408)
and exclude file load.  The persistent compilation cache makes repeat
invocations cheap.

Resilience (ROADMAP Speed item 1 replaces this orchestration with a
benchmark that fails when it finds no chip):

  * in auto mode the TPU run is attempted in CHILD processes until one
    succeeds or only the CPU reserve remains (``orchestrate``);
  * if a TPU run dies, the bench re-execs itself pinned to CPU and
    relays that result, labeled ``backend: cpu`` (``rerun_on_cpu``);
  * a watchdog hard-kills the process after $LOCUST_BENCH_TIMEOUT
    seconds (default 1200), printing the JSON line with an "error"
    field first — the driver always gets its one line of JSON.
"""

import json
import math
import os
import subprocess
import sys
import threading
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _HERE)
# Persistent compilation cache (config.compile_cache_dir: the ambient
# JAX_COMPILATION_CACHE_DIR if set, else <checkout>/.jax_cache) so
# orchestrator retries and repeat invocations reuse compiled executables.
# Guarded: config.py validates LOCUST_* env vars at import, and an
# exception HERE (before main()'s watchdog exists) would break the
# one-JSON-line contract — on failure, skip the persistent cache and let
# main()'s guarded import surface the error as the JSON error line.
try:
    from locust_tpu.config import compile_cache_dir

    compile_cache_dir()
except Exception:  # noqa: BLE001 - no cache beats no JSON line
    pass

import numpy as np

BASELINE_MB_S = 2.2
TARGET_BYTES = int(os.environ.get("LOCUST_BENCH_BYTES", 32 * 1024 * 1024))
CPU_TARGET_BYTES = int(os.environ.get("LOCUST_BENCH_CPU_BYTES", 8 * 1024 * 1024))
# Per-backend defaults, each overridable by env.  CPU: hash1 remains the
# clear winner after the r4 gather-map dispatch (grid re-tune committed in
# artifacts/bench_block_cpu_r4.jsonl: hash1 ~5.1 MB/s vs hashp2 ~2.2 /
# hashp ~1.9 at 8MB; block size 8k/16k/32k within noise, keep 16384); TPU
# keeps the configuration measured on an earlier v5e set-up until a run
# on the current machine says otherwise.
_BLOCK_LINES_ENV = os.environ.get("LOCUST_BENCH_BLOCK_LINES")
_SORT_MODE_ENV = os.environ.get("LOCUST_BENCH_SORT_MODE")
# emits_per_line cap (reference EMITS_PER_LINE=20, main.cu:19).  A smaller
# cap shrinks the Process-stage sort proportionally and is lossless iff the
# reported overflow_tokens stays 0; the sweep's emits_per_line_ab phase
# provides the on-hardware numbers before any default moves off 20.
_EMITS_ENV = os.environ.get("LOCUST_BENCH_EMITS")
# key_width cap in bytes (reference key[30], KeyValue.h:15; our default 32).
# Lossless whenever the corpus's longest token fits (hamlet: 14B); the
# sweep's key_width_ab phase host-verifies table equality before any
# default moves off 32.
_KEY_WIDTH_ENV = os.environ.get("LOCUST_BENCH_KEY_WIDTH")
# "0"/"1": force the Pallas map kernel off/on, overriding both the static
# default and any evidence-tuned flip (the escape hatch every other tuned
# knob already has via its LOCUST_BENCH_* var).  Empty means auto (like
# the other knobs); anything else is a loud error, not a silent force-off
# (validated at the top of main() so the one-JSON-line contract still
# holds without poisoning scripts that merely import this module).
_PALLAS_ENV = os.environ.get("LOCUST_BENCH_PALLAS") or None
_TABLE_ENV = os.environ.get("LOCUST_BENCH_TABLE_SIZE")
_PER_BACKEND = {
    # TPU sort_mode: an on-hardware variant row at the engine's true
    # Process shape (earlier v5e set-up, 720k rows incl. payload; the
    # rows left the tree with PR 22) had payload-carry (C_hash3_payload 67.4ms)
    # beating the gather form ("hash", B 82.6ms) by 18% at the stage that
    # dominates the pipeline — so the static default follows the
    # measurement.  An engine-level engine_sort_mode_ab row supersedes
    # this once a run records one (_evidence_tuned_tpu_defaults).
    "tpu": {"block_lines": 32768, "sort_mode": "hashp", "use_pallas": False},
    # CPU: the sort-free hash-table fold wins the driver-policy grid
    # decisively (artifacts/bench_block_cpu_r4.jsonl, 2026-07-31:
    # hasht@8192 = 7.94 MB/s vs the round-3 default hash1@16384 = 5.14).
    "cpu": {"block_lines": 8192, "sort_mode": "hasht", "use_pallas": False},
}
TIMEOUT_S = float(os.environ.get("LOCUST_BENCH_TIMEOUT", 1200))
# Wall-clock reserved for the final CPU fallback when the retry loop gives
# up on the TPU (compile+run of the CPU-sized corpus fits comfortably).
CPU_RESERVE_S = float(os.environ.get("LOCUST_BENCH_CPU_RESERVE", 420))
# Smallest budget worth starting a TPU attempt with (probe + compile + runs).
MIN_TPU_ATTEMPT_S = float(os.environ.get("LOCUST_BENCH_MIN_ATTEMPT", 150))


def emit(payload: dict) -> None:
    """The one driver-facing JSON line; everything else goes to stderr."""
    print(json.dumps(payload), flush=True)


def error_payload(msg: str) -> dict:
    return {
        "metric": "wordcount_throughput",
        "value": 0.0,
        "unit": "MB/s",
        "vs_baseline": 0.0,
        "error": msg[:500],
    }


def _tpu_rows(kind: str) -> list[dict]:
    """All committed TPU evidence rows of ``kind``, via the one shared
    hardened ledger reader (locust_tpu.utils.artifacts)."""
    sys.path.insert(0, _HERE)
    from locust_tpu.utils.artifacts import ledger_rows

    return [
        r for r in ledger_rows()
        if r.get("kind") == kind and r.get("backend") == "tpu"
    ]


def _last_tpu_bench_row() -> dict | None:
    """Latest committed TPU bench evidence (artifacts/tpu_runs.jsonl)."""
    rows = _tpu_rows("bench")
    if not rows:
        return None
    best = rows[-1]
    return {
        "value": best.get("value"),
        "unit": best.get("unit"),
        "vs_baseline": best.get("vs_baseline"),
        "device": best.get("device"),
        "ts": best.get("ts"),
    }


def _best_tpu_ab_row() -> dict | None:
    """Best committed engine-level TPU A/B measurement (MB/s + setting).

    The engine A/B rows measure the same corpus at the same timing
    boundary as the headline bench — when no TPU run succeeds at bench
    time, the CPU-fallback JSON embeds this (clearly labeled as an A/B
    row) alongside last_tpu_bench, so the driver's captured line carries
    the strongest on-hardware number, not just the stalest.
    """
    best = None
    for kind, field in (("engine_sort_mode_ab", "modes"),
                        ("block_lines_ab", "blocks")):
        for row in _tpu_rows(kind):
            for name, side in (row.get(field) or {}).items():
                if not (isinstance(side, dict)
                        and isinstance(side.get("mb_s"), (int, float))):
                    continue
                if best is None or side["mb_s"] > best["value"]:
                    best = {
                        "value": side["mb_s"],
                        "unit": "MB/s",
                        "vs_baseline": round(side["mb_s"] / BASELINE_MB_S, 2),
                        "kind": kind,
                        "setting": name,
                        "device": row.get("device"),
                        "ts": row.get("ts"),
                    }
    return best


def _evidence_tuned_tpu_defaults(defaults: dict, caps: dict | None = None) -> dict:
    """Fold committed on-hardware A/B evidence into the TPU defaults.

    A measurement run may have recorded engine_sort_mode_ab /
    block_lines_ab rows since the static defaults were last hand-tuned.
    Use the LATEST row of each kind and take its argmax-MB/s setting, so
    the next bench exploits whatever was last measured without a human
    in the loop (no such rows are in the tree as of PR 22).  Env
    overrides still win (handled by the caller); losing rows keep the
    static default.
    """
    out = dict(defaults)

    def caps_match(row: dict) -> bool:
        """Joint-measurement rule for the capacity axes: the row's
        recorded caps (older rows predate the field = engine defaults)
        must equal the caps this bench run assembles, and the row's
        corpus size must match the size THIS bench runs at — sweeps at
        other sizes (8MB / 64MB) append to the same ledger kinds, and an
        off-shape winner must not steer the 32MB headline config (code review, r5)."""
        if caps is None:
            return True
        row_caps = row.get("caps") or {"key_width": 32, "emits_per_line": 20}
        if (
            int(row_caps.get("key_width", 32)) != caps["key_width"]
            or int(row_caps.get("emits_per_line", 20))
            != caps["emits_per_line"]
        ):
            return False
        row_mb = row.get("corpus_mb")
        if isinstance(row_mb, (int, float)) and row_mb > 0:
            target_mb = TARGET_BYTES / 1e6
            if abs(float(row_mb) - target_mb) > 0.25 * target_mb:
                return False
        return True  # legacy rows without corpus_mb were headline-shaped

    def side_mb(side) -> float:
        """MB/s of one A/B side; a malformed/errored side (null, missing
        mb_s) scores -1 so it can never win over a real measurement."""
        if isinstance(side, dict) and isinstance(side.get("mb_s"), (int, float)):
            return float(side["mb_s"])
        return -1.0

    def lossless_sides(sides: dict) -> dict:
        """Drop A/B sides that measured a semantically DIFFERENT run:
        nonzero overflow_tokens, or
        fewer distinct keys than the best side in the same row — losing
        tokens or truncating the table can only shrink distinct, so the
        within-row maximum is the exact anchor.  A faster-but-lossy side
        (e.g. an emits cap that drops tokens) must never steer the
        headline config; sides without the fields are kept (older rows
        predate them, and mb_s-only sides carry no loss signal).
        Errored/malformed sides are dropped here too so max() below can
        only ever pick a real, lossless measurement."""
        real = {
            k: v
            for k, v in sides.items()
            if isinstance(v, dict)
            and isinstance(v.get("mb_s"), (int, float))
        }
        distincts = [
            int(v["distinct"])
            for v in real.values()
            if isinstance(v.get("distinct"), int)
        ]
        anchor = max(distincts) if distincts else None
        out = {}
        for k, v in real.items():
            if int(v.get("overflow_tokens") or 0) > 0:
                continue
            d = v.get("distinct")
            if anchor is not None and isinstance(d, int) and d < anchor:
                continue
            out[k] = v
        return out

    # Evidence must never break a run (same stance as utils/artifacts.py).
    def newest_matching(rows, extra=None):
        """Newest row passing the joint-measurement rules — NOT just
        rows[-1]: sweeps at other sizes (8MB/64MB) append
        off-shape rows to the same kinds, and an off-shape LAST row must
        skip back to the newest headline-shaped one, not knock the whole
        kind out (code review, r5)."""
        for r in reversed(rows):
            if caps_match(r) and (extra is None or extra(r)):
                return r
        return None

    def adopt_sort_mode(kind: str) -> None:
        ab_row = newest_matching(_tpu_rows(kind))
        if ab_row is None:
            return
        modes = lossless_sides(ab_row.get("modes", {}))
        best = max(modes, key=lambda m: side_mb(modes.get(m)), default=None)
        if best is not None and side_mb(modes.get(best)) > 0.0:
            from locust_tpu.config import SORT_MODES

            if best in SORT_MODES:
                out["sort_mode"] = best
                print(
                    f"[bench] evidence-tuned sort_mode={best} "
                    f"({modes[best].get('mb_s')} MB/s in the last TPU A/B)",
                    file=sys.stderr,
                )

    def adopt_block_lines(kind: str) -> None:
        # Only adopt a block size measured AT the adopted sort mode — the
        # block_lines_ab row records which mode it swept with (older rows
        # predate the field and swept the historical default "hash"), so
        # the joint configuration is always one a window actually ran.
        row = newest_matching(
            _tpu_rows(kind),
            extra=lambda r: r.get("sort_mode", "hash") == out["sort_mode"],
        )
        if row is None:
            return
        blocks = lossless_sides(row.get("blocks") or {})
        best = max(blocks, key=lambda b: side_mb(blocks.get(b)), default=None)
        if best is not None and side_mb(blocks.get(best)) > 0.0:
            out["block_lines"] = int(best)
            print(
                f"[bench] evidence-tuned block_lines={best} "
                f"({blocks[best].get('mb_s')} MB/s in the last TPU A/B)",
                file=sys.stderr,
            )

    def adopt_table_size(kind: str) -> None:
        # table_size: adopt only a size measured AT the adopted
        # (sort_mode, block_lines) — the distinct-aware accumulator
        # sizing (engine_table_ab rows; the fold re-aggregates every
        # table row per block, so right-sizing to the vocabulary wins
        # when the default is mostly padding).  Truncated sides record
        # truncated=True and are additionally dropped by lossless_sides'
        # distinct anchor.
        row = newest_matching(
            _tpu_rows(kind),
            extra=lambda r: (
                r.get("sort_mode", "hash") == out["sort_mode"]
                and int(r.get("block_lines", 32768)) == out["block_lines"]
            ),
        )
        if row is None:
            return
        tables = lossless_sides(row.get("tables") or {})
        tables = {k: v for k, v in tables.items() if not v.get("truncated")}
        best = max(tables, key=lambda t: side_mb(tables.get(t)), default=None)
        if best is not None and side_mb(tables.get(best)) > 0.0:
            out["table_size"] = int(best)
            print(
                f"[bench] evidence-tuned table_size={best} "
                f"({tables[best].get('mb_s')} MB/s in the last TPU A/B)",
                file=sys.stderr,
            )

    def adopt_use_pallas(kind: str) -> None:
        # use_pallas: adopt only a measured engine-level win, and only if
        # the row was swept AT the adopted (sort_mode, block_lines,
        # table_size) — same joint-measurement rule as above.  A side
        # that errored has no "mb_s" key and loses.
        row = newest_matching(
            _tpu_rows(kind),
            extra=lambda r: (
                r.get("sort_mode", "hash") == out["sort_mode"]
                and int(r.get("block_lines", 32768)) == out["block_lines"]
                and r.get("table_size") == out.get("table_size")
            ),
        )
        if row is None:
            return
        sides = lossless_sides(row.get("pallas") or {})
        on = side_mb(sides.get("True"))
        off = side_mb(sides.get("False"))
        if on > off > 0.0:
            out["use_pallas"] = True
            print(
                f"[bench] evidence-tuned use_pallas=True "
                f"({on} vs {off} MB/s in the last TPU A/B)",
                file=sys.stderr,
            )

    # Per-kind readers, ITERATED off the shared artifacts.CONFIG_AB_KINDS
    # tuple (ADVICE r5): the anti-drift guarantee is now two-sided — a
    # kind added to the tuple without a reader here, or a reader added
    # without extending the tuple, fails this identity check loudly
    # (order included: later kinds adopt jointly with earlier winners)
    # instead of leaving the committed headline silently stale.
    adopters = {
        "engine_sort_mode_ab": adopt_sort_mode,
        "block_lines_ab": adopt_block_lines,
        "engine_table_ab": adopt_table_size,
        "engine_pallas_ab": adopt_use_pallas,
    }
    from locust_tpu.utils.artifacts import CONFIG_AB_KINDS

    if tuple(adopters) != tuple(CONFIG_AB_KINDS):
        raise RuntimeError(
            "bench evidence readers drifted from artifacts.CONFIG_AB_KINDS: "
            f"{tuple(adopters)} != {tuple(CONFIG_AB_KINDS)}"
        )

    try:
        for kind in CONFIG_AB_KINDS:
            # One malformed row must not revert knobs validly adopted
            # from OTHER kinds (ADVICE r3): each kind is guarded
            # independently; the outer except stays as a backstop.
            try:
                adopters[kind](kind)
            except Exception as e:  # noqa: BLE001 - skip this kind only
                print(
                    f"[bench] {kind} evidence skipped "
                    f"({type(e).__name__}: {e})",
                    file=sys.stderr,
                )
    except Exception as e:  # noqa: BLE001 - tuning is best-effort
        print(
            f"[bench] evidence tuning skipped ({type(e).__name__}: {e}); "
            "using static defaults",
            file=sys.stderr,
        )
        return dict(defaults)
    return out


def load_corpus(target_bytes: int) -> list[bytes]:
    here = os.path.dirname(os.path.abspath(__file__))
    # Realism knob: replicated hamlet has only ~5.6k
    # distinct words, which stresses neither the 65,536-row table nor skew
    # handling.  LOCUST_BENCH_VOCAB=<n> switches to the Zipf generator at
    # that vocabulary, making the headline number harder to game.
    vocab = int(os.environ.get("LOCUST_BENCH_VOCAB", 0))
    if vocab > 0:
        sys.path.insert(0, here)
        from locust_tpu.io.corpus import synthetic_corpus

        return synthetic_corpus(target_bytes, n_vocab=vocab)
    sample = os.path.join(here, "data", "sample_corpus.txt")
    path = "/root/reference/hamlet.txt"
    if os.path.exists(path):
        base = open(path, "rb").read().splitlines()
    elif os.path.exists(sample):  # the repo's own shipped corpus
        base = open(sample, "rb").read().splitlines()
    else:  # fully synthetic Zipf fallback
        sys.path.insert(0, here)
        from locust_tpu.io.corpus import synthetic_corpus

        return synthetic_corpus(target_bytes, n_vocab=30_000)
    lines, total = [], 0
    while total < target_bytes:
        for ln in base:
            lines.append(ln)
            total += len(ln) + 1
            if total >= target_bytes:
                break
    return lines


def bench_engine_config(block_lines: int, table_size: int | None = None,
                        **overrides):
    """The headline bench's exact EngineConfig policy, in one place so an
    A/B is measured at the configuration the bench actually runs:
    table_size is
    pinned to the DEFAULT-caps resolution (auto-sized emits_per_line must
    not shrink the accumulator, see run_bench) unless the caller passes
    a measured one (the CPU path's distinct-aware sizing)."""
    sys.path.insert(0, _HERE)
    from locust_tpu.config import EngineConfig

    return EngineConfig(
        block_lines=block_lines,
        table_size=(
            table_size
            if table_size is not None
            else EngineConfig(block_lines=block_lines).resolved_table_size
        ),
        **overrides,
    )


def _auto_table_size(distinct: int, default_resolved: int) -> int:
    """Distinct-aware accumulator sizing (CPU path): the default
    min(65536, emits_per_block) table is ~92% empty padding on a
    hamlet-sized vocabulary, and the hasht fold re-aggregates every
    table row per block — measured +14% CPU throughput at a right-sized
    table (artifacts/bench_table_cpu_r5).  Power of two at >= 2x the
    measured distinct (load factor <= 0.5 keeps probe failures in the
    cheap residual branch), floored at 4096, never above the default —
    and since ``distinct`` comes from an exact host count, table >=
    distinct means truncation is impossible."""
    t = 4096
    while t < 2 * distinct:
        t <<= 1
    return min(t, default_resolved)


def bench_auto_caps(lines, label: str = "[bench]") -> tuple[int, int]:
    """Measure + log the corpus's lossless caps at the bench's ceilings
    (the engine defaults).  One implementation for bench and sweep."""
    sys.path.insert(0, _HERE)
    from locust_tpu.config import EngineConfig
    from locust_tpu.io.loader import auto_caps

    d = EngineConfig()
    t0 = time.perf_counter()
    # Measure on the width-truncated view the engine actually sees (the
    # same policy as cli.py --auto-caps): a token spanning the line_width
    # boundary must produce identical caps at both sites, or a sweep
    # row's caps could fail the bench's joint caps_match rule (ADVICE r3).
    kw, epl, max_tok, max_per_line = auto_caps(
        [ln[: d.line_width] for ln in lines], d.key_width, d.emits_per_line
    )
    print(
        f"{label} corpus caps: max_token={max_tok}B max_tokens/line="
        f"{max_per_line} -> key_width={kw} emits_per_line={epl} "
        f"({time.perf_counter()-t0:.1f}s)",
        file=sys.stderr,
    )
    return kw, epl


def _dataplane_stats() -> dict:
    """Distributor data-plane summary for the one-line JSON: the loopback
    fetch microbench (locust_tpu/distributor/microbench.py — wire bytes,
    fetch MB/s, compression ratio; docs/DATAPLANE.md).  Pure host/socket
    work, a couple of seconds, backend-independent.  Guarded: a failure
    here must never cost the headline line (LOCUST_BENCH_DATAPLANE=0
    skips it outright)."""
    if os.environ.get("LOCUST_BENCH_DATAPLANE", "1") == "0":
        return {"skipped": True}
    try:
        from locust_tpu.distributor.microbench import run_microbench

        t0 = time.perf_counter()
        res = run_microbench(target_bytes=2 << 20, repeats=2)
        print(
            f"[bench] dataplane microbench: {res['summary']} "
            f"({time.perf_counter()-t0:.1f}s)",
            file=sys.stderr,
        )
        return dict(res["summary"], corpus_bytes=res["corpus_bytes"])
    except Exception as e:  # noqa: BLE001 - the headline line comes first
        return {"error": f"{type(e).__name__}: {e}"[:200]}


def _stream_stats(eng, rows) -> dict:
    """Zero-stall streaming summary for the one-line JSON (docs/DESIGN.md).

    Folds the bench corpus through ``run_stream`` twice — plain, then
    WITH checkpoints on the async background writer — and reports the
    executor's stall accounting: backpressure stall ms, checkpoint
    mark/flush ms, overlap efficiency, and checkpoint lag (latest-wins
    skips).  The contract under test is that snapshots no longer stall
    the fold loop: ckpt_overhead_pct should sit within a few percent.
    Guarded like the dataplane summary — a failure here must never cost
    the headline line; ``LOCUST_BENCH_STREAM=0`` skips outright.  On TPU
    the streamed volume is capped (``LOCUST_BENCH_STREAM_BYTES``,
    default 8MB there): the per-block path must not spend the chip time
    the one-dispatch headline needs.
    """
    if os.environ.get("LOCUST_BENCH_STREAM", "1") == "0":
        return {"skipped": True}
    try:
        import tempfile

        import jax

        bl, w = eng.cfg.block_lines, eng.cfg.line_width
        cap_default = 8 << 20 if jax.default_backend() == "tpu" else 0
        cap = int(os.environ.get("LOCUST_BENCH_STREAM_BYTES", cap_default))
        n = rows.shape[0] if cap <= 0 else min(rows.shape[0], max(bl, cap // w))
        srows = rows[:n]

        def blocks():
            for i in range(0, srows.shape[0], bl):
                yield srows[i : i + bl]

        t0 = time.perf_counter()
        eng.run_stream((srows[i : i + bl] for i in range(0, 2 * bl, bl)))
        warm_s = time.perf_counter() - t0  # per-block fold compile
        t0 = time.perf_counter()
        plain = eng.run_stream(blocks())
        plain_s = time.perf_counter() - t0
        with tempfile.TemporaryDirectory() as td:
            t0 = time.perf_counter()
            ck = eng.run_stream(
                blocks(),
                checkpoint_dir=os.path.join(td, "ck"),
                every=8,
                fingerprint="bench-stream",
            )
            ck_s = time.perf_counter() - t0
        cks = dict(ck.stream.get("ckpt") or {})
        stall = float(ck.stream["backpressure_stall_ms"])
        mark = float(cks.get("mark_ms") or 0.0)
        total = float(ck.stream["total_ms"]) or 1.0
        out = {
            "streamed_mb": round(srows.nbytes / 1e6, 1),
            "blocks": ck.stream["blocks"],
            "compile_s": round(warm_s, 2),
            "plain_s": round(plain_s, 3),
            "ckpt_s": round(ck_s, 3),
            "ckpt_overhead_pct": round(100 * (ck_s - plain_s) / plain_s, 2),
            "backpressure_stall_ms": round(stall, 1),
            "ckpt_mark_ms": round(mark, 1),
            "ckpt_final_flush_ms": cks.get("final_flush_ms"),
            "ckpt_mode": cks.get("mode"),
            "ckpt_written": cks.get("written"),
            "ckpt_skipped": cks.get("skipped"),
            "ckpt_max_lag": cks.get("max_lag"),
            "overlap_pct": round(100 * (1 - (stall + mark) / total), 2),
            "distinct": ck.num_segments,
            "distinct_matches": ck.num_segments == plain.num_segments,
            "fused": _stream_fused_row(eng.cfg, srows, bl),
        }
        print(
            f"[bench] stream: plain {plain_s:.2f}s vs ckpt {ck_s:.2f}s "
            f"({out['ckpt_overhead_pct']:+.1f}%), stall {stall:.0f}ms, "
            f"mark {mark:.0f}ms, lag {cks.get('max_lag')}, "
            f"distinct {ck.num_segments}",
            file=sys.stderr,
        )
        return out
    except Exception as e:  # noqa: BLE001 - the headline line comes first
        return {"error": f"{type(e).__name__}: {e}"[:200]}


def _stream_fused_row(cfg, srows, bl: int) -> dict:
    """Megakernel v2 streaming row: the persistent streaming kernel
    (``sort_mode="fused"`` through ``run_stream``) vs plain hasht over
    the SAME block stream, identity asserted in-row — the tables must
    be bit-identical, a divergence fails the whole stream sub-dict
    loudly rather than landing a passing row.  Off-TPU the walls are
    honest interpret-mode numbers (the kernel re-traces per grid step
    on CPU) and the row says so (``interpret``); when the engine's gate
    demotes (e.g. bench block_lines past the interpret cap) the row
    records ``demoted=True`` with no speedup claim.  Block count is
    bounded: this row's evidence is identity + formulation, the
    throughput headline belongs to the main bench."""
    import dataclasses

    import jax

    from locust_tpu.engine import MapReduceEngine

    on_tpu = jax.default_backend() == "tpu"
    n_blocks = min(srows.shape[0] // bl or 1, 24 if on_tpu else 4)
    frows = srows[: n_blocks * bl]

    def blocks():
        for i in range(0, frows.shape[0], bl):
            yield frows[i : i + bl]

    f_eng = MapReduceEngine(dataclasses.replace(cfg, sort_mode="fused"))
    h_eng = MapReduceEngine(dataclasses.replace(cfg, sort_mode="hasht"))
    f_eng.run_stream(blocks())  # warm both executables
    h_eng.run_stream(blocks())
    t0 = time.perf_counter()
    f_res = f_eng.run_stream(blocks())
    fused_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    h_res = h_eng.run_stream(blocks())
    hasht_s = time.perf_counter() - t0
    assert f_res.to_host_pairs() == h_res.to_host_pairs(), (
        "fused streaming table diverged from hasht"
    )
    fstats = dict(f_res.stream.get("fused") or {})
    return {
        "formulation": f_res.fused_kernel,
        "demoted": bool(f_res.fused_demoted),
        "interpret": not on_tpu,
        "blocks": n_blocks,
        "seg_blocks": fstats.get("seg_blocks"),
        "segments": fstats.get("segments"),
        "fused_s": round(fused_s, 3),
        "hasht_s": round(hasht_s, 3),
        "speedup": round(hasht_s / fused_s, 2) if fused_s > 0 else None,
        "identical": True,  # asserted above
    }


def _percentile(xs: list, q: float) -> float | None:
    """Nearest-rank percentile of a latency list (None when empty):
    rank ceil(q*n), 1-based.  With fewer than 1/(1-q) samples the
    nearest rank IS the maximum (p99 of the 26-job serve stream = its
    slowest job) — the honest small-n reading, not a bug."""
    if not xs:
        return None
    s = sorted(xs)
    rank = max(1, math.ceil(q * len(s)))
    return round(s[min(len(s) - 1, rank - 1)], 3)


# Modeled per-dispatch device time for the workers dimension.  Sized so
# the overlap signal dominates the host-CPU fold share even on a loaded
# single-core container: with ~120ms the measured 2w speedup wandered
# 1.4-1.8x run to run (the host fold serializes on the one core and
# only the device wait overlaps); at 250ms the ratio stays comfortably
# above the 1.3x acceptance across repeats.
_POOL_DEVICE_MS = 250.0


def _serve_pool_scaling() -> dict:
    """Aggregate qps at 1 vs 2 loopback pool workers over the same
    mixed stream (docs/SERVING.md "Scale-out dispatch").

    Two measurements, both through the FULL serve stack (admission,
    fair scheduler, placement, persistent-connection RPC, demux):

      * ``speedup_2w`` (headline) — each worker models an ACCELERATOR
        the host blocks on while the device folds (``modeled_device_ms``
        of per-dispatch device time; on the real fleet that wait is the
        v5e executing).  This is the regime
        the pool exists for, and the number measures what this layer
        actually adds: dispatch lanes that OVERLAP across workers
        instead of serializing on one engine.
      * ``raw`` — the same stream with zero modeled device time: every
        fold is host CPU.  On a multi-core host this also scales; on a
        single-core container (``cores`` is recorded beside it) the
        work is compute-bound on one core and the honest raw speedup is
        ~1.0x — physics, not a placement failure, which is exactly why
        the raw numbers ride beside the modeled ones instead of being
        quoted as the scaling headline.

    Each measurement runs an untimed warm wave first (every engine pays
    its compile once — steady-state placement is the subject, compile
    economics already have their own counters), then times a wave of
    NEW corpora in the same shape bucket: affinity packs batches onto
    warm workers (affinity-hit rate > 0 on this repeat wave), spill-over
    keeps the queue moving when the affine worker is saturated.
    """
    from locust_tpu.distributor.worker import Worker
    from locust_tpu.io.corpus import synthetic_corpus
    from locust_tpu.serve.client import ServeClient
    from locust_tpu.serve.daemon import ServeConfig, ServeDaemon

    cfg = {"block_lines": 256, "key_width": 16, "emits_per_line": 12}

    class ModeledDeviceWorker(Worker):
        """A pool worker whose dispatch blocks for a fixed device
        execution time before the host-side fold — the one-chip-per-
        worker shape this tier targets, modeled so dispatch
        overlap is measurable on a 1-core CPU container at all."""

        def _serve_batch(self, req):
            time.sleep(_POOL_DEVICE_MS / 1e3)
            return super()._serve_batch(req)

    def corpus(n_lines: int, seed: int) -> bytes:
        lines = synthetic_corpus(
            n_lines * 64, n_vocab=2000, seed=seed, words_per_line=6
        )
        assert len(lines) >= n_lines, (len(lines), n_lines)
        return b"\n".join(lines[:n_lines]) + b"\n"

    def measure(n_workers: int, seed_base: int, worker_cls,
                inflight: int) -> dict:
        ws = [
            worker_cls(secret=b"bench-pool", serve=True)
            for _ in range(n_workers)
        ]
        for w in ws:
            w.serve_in_thread()
        daemon = ServeDaemon(
            secret=b"bench-pool",
            cfg=ServeConfig(
                max_batch=2, dispatch_poll_s=0.02,
                pool_inflight=inflight,
                workers=tuple(f"127.0.0.1:{w.addr[1]}" for w in ws),
            ),
        )
        daemon.serve_in_thread()
        client = ServeClient(daemon.addr, b"bench-pool", timeout=120.0)
        tenants = ("alpha", "beta", "gamma")
        try:
            warm = [corpus(400, seed_base + i) for i in range(8)]
            ids = [
                client.submit(corpus=c, tenant=tenants[i % 3],
                              config=cfg)["job_id"]
                for i, c in enumerate(warm)
            ]
            for j in ids:
                client.wait(j, timeout=600.0, poll_s=0.02)
            work = [corpus(400, seed_base + 100 + i) for i in range(12)]
            t0 = time.perf_counter()
            ids = [
                client.submit(corpus=c, tenant=tenants[i % 3],
                              config=cfg)["job_id"]
                for i, c in enumerate(work)
            ]
            lat = []
            for j in ids:
                res = client.wait(j, timeout=600.0, poll_s=0.02)
                lat.append(float(res["latency_ms"]))
            elapsed = time.perf_counter() - t0
            stats = client.stats()
        finally:
            daemon.close()
            for w in ws:
                w._shutdown.set()
                try:
                    w._sock.close()
                except OSError:
                    pass
        pool = stats.get("pool") or {}
        return {
            "jobs": len(work),
            "elapsed_s": round(elapsed, 3),
            "qps": round(len(work) / elapsed, 2) if elapsed > 0 else None,
            "p50_ms": _percentile(lat, 0.50),
            "p99_ms": _percentile(lat, 0.99),
            "placements": pool.get("placements"),
            "local_fallbacks": pool.get("local_fallbacks"),
            "affinity_hits": pool.get("affinity_hits"),
            "spill_overs": pool.get("spill_overs"),
        }

    def ratio(one: dict, two: dict):
        return (
            round(two["qps"] / one["qps"], 3)
            if one.get("qps") and two.get("qps") else None
        )

    # Device-modeled (headline): pool_inflight sized far above the
    # stream's batch count so placement NEVER refuses — a refusal would
    # spill device-bound work onto the local floor, which in this model
    # has no device behind it and would eat the stream at host speed,
    # turning the comparison incoherent.  Dispatches still serialize
    # per worker on its one persistent connection, which is the model's
    # point: one worker = one device lane.
    one = measure(1, 500, ModeledDeviceWorker, inflight=32)
    two = measure(2, 700, ModeledDeviceWorker, inflight=32)
    raw1 = measure(1, 900, Worker, inflight=1)
    raw2 = measure(2, 1100, Worker, inflight=1)
    out = {
        "cores": os.cpu_count(),
        "modeled_device_ms": _POOL_DEVICE_MS,
        "1": one,
        "2": two,
        "speedup_2w": ratio(one, two),
        "raw": {"1": raw1, "2": raw2, "speedup_2w": ratio(raw1, raw2)},
    }
    print(
        f"[bench] serve workers (device-modeled {_POOL_DEVICE_MS:.0f}ms): "
        f"1w {one['qps']} qps vs 2w {two['qps']} qps "
        f"({out['speedup_2w']}x); raw CPU on {out['cores']} core(s): "
        f"{raw1['qps']} vs {raw2['qps']} "
        f"({out['raw']['speedup_2w']}x); affinity hits "
        f"{one['affinity_hits']}/{two['affinity_hits']}",
        file=sys.stderr,
    )
    return out


def _serve_stats() -> dict:
    """Serve-tier summary for the one-line JSON (docs/SERVING.md).

    Runs an in-process loopback daemon and drives a mixed small/large
    job stream across three tenants: distinct small corpora that share
    one shape bucket (coalesced batching + warm-executable hits),
    two large jobs in a bigger bucket, then repeat submissions of the
    small jobs (result-cache hits).  Reports sustained qps, p50/p99
    submit->done latency, and both cache hit counters — the serving
    analog of the dataplane/stream sub-benches.  Guarded the same way:
    a failure never costs the headline line; ``LOCUST_BENCH_SERVE=0``
    skips outright.  On TPU the completed run also lands a
    ``serve_bench`` evidence row (artifacts.BENCH_SUBDICT_KINDS).
    """
    if os.environ.get("LOCUST_BENCH_SERVE", "1") == "0":
        return {"skipped": True}
    try:
        from locust_tpu.io.corpus import synthetic_corpus
        from locust_tpu.serve.client import ServeClient
        from locust_tpu.serve.daemon import ServeConfig, ServeDaemon

        # Small shapes on purpose: the sub-bench measures the SERVING
        # machinery (queueing, batching, caches), not fold throughput —
        # the headline already owns that.  block_lines=256 keeps every
        # small job in shape bucket 1 and the large jobs in bucket 8,
        # so the whole stream compiles a handful of batched shapes.
        cfg = {"block_lines": 256, "key_width": 16, "emits_per_line": 12}

        def corpus(n_lines: int, seed: int) -> bytes:
            # synthetic_corpus sizes by BYTES; 6 words/line of b"w%06d"
            # is 47 bytes + newline, so ask for a margin above 48/line
            # and assert — silently short jobs would land in a smaller
            # shape bucket and invalidate the bucket-1/bucket-8 split
            # this sub-bench (and its evidence rows) is built on.
            lines = synthetic_corpus(
                n_lines * 64, n_vocab=2000, seed=seed, words_per_line=6
            )
            assert len(lines) >= n_lines, (len(lines), n_lines)
            return b"\n".join(lines[:n_lines]) + b"\n"

        smalls = [corpus(200, s) for s in range(12)]
        larges = [corpus(2000, 100 + s) for s in range(2)]
        daemon = ServeDaemon(
            secret=b"bench-serve",
            cfg=ServeConfig(max_batch=4, warm_dir=None),
        )
        daemon.serve_in_thread()
        client = ServeClient(daemon.addr, b"bench-serve", timeout=120.0)
        tenants = ("alpha", "beta", "gamma")
        try:
            t0 = time.perf_counter()
            ids = []
            for i, c in enumerate(smalls):
                ids.append(client.submit(
                    corpus=c, tenant=tenants[i % 3], config=cfg
                )["job_id"])
            for i, c in enumerate(larges):
                ids.append(client.submit(
                    corpus=c, tenant=tenants[i % 3], config=cfg, weight=2.0
                )["job_id"])
            lat, batch_sizes = [], []

            def drain(job_ids):
                for jid in job_ids:
                    res = client.wait(jid, timeout=600.0, poll_s=0.02)
                    lat.append(float(res["latency_ms"]))
                    st = client.status(jid)
                    if st.get("batch_size"):
                        batch_sizes.append(int(st["batch_size"]))

            # Drain the first wave BEFORE the repeat wave: a repeat can
            # only hit the result cache once its original finished — the
            # wave split makes the "repeat jobs are cache hits" claim
            # real instead of a race with the queue.
            drain(ids)
            repeats = []
            for i, c in enumerate(smalls):
                repeats.append(client.submit(
                    corpus=c, tenant=tenants[(i + 1) % 3], config=cfg
                )["job_id"])
            drain(repeats)
            ids += repeats
            elapsed = time.perf_counter() - t0
            stats = client.stats()
        finally:
            daemon.close()
        exec_c = stats["exec_cache"]
        res_c = stats["result_cache"]
        lookups = exec_c["hits"] + exec_c["misses"]
        out = {
            "jobs": len(ids),
            "small_jobs": len(smalls) * 2,
            "large_jobs": len(larges),
            "elapsed_s": round(elapsed, 3),
            "qps": round(len(ids) / elapsed, 2) if elapsed > 0 else None,
            "p50_ms": _percentile(lat, 0.50),
            "p99_ms": _percentile(lat, 0.99),
            "mean_batch": (
                round(sum(batch_sizes) / len(batch_sizes), 2)
                if batch_sizes else None
            ),
            "exec_cache_hit_rate": (
                round(exec_c["hits"] / lookups, 3) if lookups else None
            ),
            "exec_compiles": exec_c["compiles"],
            "result_cache_hits": res_c["hits"],
            "rejected": stats["queue"]["rejected"],
        }
        # Scale-out dimension (ISSUE 11): aggregate qps vs pool worker
        # count.  Guarded separately — a pool failure must not cost the
        # single-daemon serve numbers above.
        try:
            out["workers"] = _serve_pool_scaling()
        except Exception as e:  # noqa: BLE001 - sub-dimension stays soft
            out["workers"] = {"error": f"{type(e).__name__}: {e}"[:200]}
        print(
            f"[bench] serve: {out['jobs']} jobs in {out['elapsed_s']}s "
            f"({out['qps']} qps), p50 {out['p50_ms']}ms p99 "
            f"{out['p99_ms']}ms, exec hit rate "
            f"{out['exec_cache_hit_rate']}, result hits "
            f"{out['result_cache_hits']}",
            file=sys.stderr,
        )
        from locust_tpu.utils import artifacts

        artifacts.record(
            artifacts.BENCH_SUBDICT_KINDS["serve"], dict(out)
        )
        return out
    except Exception as e:  # noqa: BLE001 - the headline line comes first
        return {"error": f"{type(e).__name__}: {e}"[:200]}


def _recovery_stats() -> dict:
    """Durability-tier summary for the one-line JSON (docs/SERVING.md
    "Durability guarantee"): journal append overhead per admit, and the
    restart-to-first-result MTTR of a crash-recovery replay.

    Plus the HA tier (docs/SERVING.md "High availability"): WAL-shipping
    overhead on the admit path (the shipper ENQUEUE — the only
    synchronous cost async shipping adds — as a share of admit latency,
    acceptance <= 5%; the raw wall delta of shipping+standby work on
    this container's cores is reported beside it honestly) and
    ``takeover_mttr_s`` — a primary/standby pair, jobs acked and
    shipped, the primary abandoned kill -9-style, the standby promoted:
    promote -> first replayed result.

    Measurements against in-process loopback daemons:

      * **append overhead** — the same job stream admitted twice, once
        with the write-ahead journal and once without; the journal's own
        per-append accounting (``JobJournal.stats``) divided by the
        journaled daemon's mean admit (submit ack) latency.  Acceptance:
        <= 5% of admit latency.
      * **MTTR** — jobs acked but never dispatched (the scheduler is
        paused = the mid-batch window), the daemon abandoned WITHOUT its
        graceful close (the in-process kill -9), then a fresh daemon on
        the same journal: restart-to-first-result measures daemon
        construction (replay included) until the first replayed job
        answers, restart-to-all until the last does.

    Guarded like the siblings: a failure never costs the headline line;
    ``LOCUST_BENCH_RECOVERY=0`` skips.  Completed runs land a
    ``recovery_bench`` evidence row (artifacts.BENCH_SUBDICT_KINDS).
    """
    if os.environ.get("LOCUST_BENCH_RECOVERY", "1") == "0":
        return {"skipped": True}
    try:
        import shutil
        import tempfile

        from locust_tpu.io.corpus import synthetic_corpus
        from locust_tpu.serve.client import ServeClient
        from locust_tpu.serve.daemon import ServeConfig, ServeDaemon

        cfg = {"block_lines": 256, "key_width": 16, "emits_per_line": 12}
        # Overhead phase: REALISTIC (MB-scale) inline corpora — admit
        # latency there is dominated by the transfer + b64 + sha the
        # submit already pays, which is what the O(1) WAL record rides
        # on; 10 KB toy corpora would make the constant fsync look huge
        # against an artificially cheap admit.  MTTR phase: small jobs,
        # so the replay recompute measures restart machinery, not fold
        # throughput.
        big = [
            b"\n".join(synthetic_corpus(
                1 << 20, n_vocab=4000, seed=s, words_per_line=8
            )) + b"\n"
            for s in range(4)
        ]
        small = [
            b"\n".join(synthetic_corpus(
                200 * 64, n_vocab=2000, seed=100 + s, words_per_line=6
            )[:200]) + b"\n"
            for s in range(8)
        ]
        tmp = tempfile.mkdtemp(prefix="locust_recovery_")
        try:
            def admit_wall(daemon, corpora) -> float:
                """Mean submit->ack wall time over the job stream, with
                dispatch held so queue depth cannot skew the compare."""
                daemon.scheduler.pause()
                client = ServeClient(daemon.addr, b"bench-rec",
                                     timeout=60.0)
                t0 = time.perf_counter()
                for i, c in enumerate(corpora):
                    client.submit(corpus=c, tenant=f"t{i % 3}", config=cfg,
                                  no_cache=True)
                return (time.perf_counter() - t0) / len(corpora)

            base = ServeDaemon(secret=b"bench-rec", cfg=ServeConfig(
                dispatch_poll_s=0.02))
            base.serve_in_thread()
            try:
                plain_admit_s = admit_wall(base, big)
            finally:
                base.close()
            d1 = ServeDaemon(secret=b"bench-rec", cfg=ServeConfig(
                dispatch_poll_s=0.02,
                journal_dir=os.path.join(tmp, "journal_overhead")))
            d1.serve_in_thread()
            try:
                journal_admit_s = admit_wall(d1, big)
                jstats = d1.journal.stats()
            finally:
                d1.close()
            append_ms = jstats["append_ms_mean"] or 0.0
            # MTTR phase: ack small jobs, never dispatch them (the
            # mid-batch window), then an in-process kill -9 — no drain,
            # no compaction, no close — and a fresh daemon on the same
            # journal.
            jdir = os.path.join(tmp, "journal_mttr")
            dm = ServeDaemon(secret=b"bench-rec", cfg=ServeConfig(
                dispatch_poll_s=0.02, journal_dir=jdir))
            dm.serve_in_thread()
            admit_wall(dm, small)
            ids = list(dm._jobs)  # acked, never dispatched: the window
            dm._shutdown.set()
            dm.scheduler.stop()
            dm._sock.close()
            t0 = time.perf_counter()
            d2 = ServeDaemon(secret=b"bench-rec", cfg=ServeConfig(
                dispatch_poll_s=0.02, journal_dir=jdir))
            d2.serve_in_thread()
            try:
                c2 = ServeClient(d2.addr, b"bench-rec", timeout=60.0)
                first_s = None
                for jid in ids:
                    c2.wait(jid, timeout=600.0, poll_s=0.02)
                    if first_s is None:
                        first_s = time.perf_counter() - t0
                all_s = time.perf_counter() - t0
            finally:
                d2.close()
            # Shipping-overhead phase (docs/SERVING.md "High
            # availability"): the SAME big-corpus admit stream against a
            # journaled primary that is also WAL-shipping to a live
            # standby — shipping is async off the admit path, so the
            # acceptance is <= 5% added admit latency over the
            # journal-only daemon.
            sb1 = ServeDaemon(secret=b"bench-rec", cfg=ServeConfig(
                dispatch_poll_s=0.02,
                journal_dir=os.path.join(tmp, "journal_sb1"),
                standby_of="127.0.0.1:9"))
            sb1.serve_in_thread()
            dp1 = ServeDaemon(secret=b"bench-rec", cfg=ServeConfig(
                dispatch_poll_s=0.02,
                journal_dir=os.path.join(tmp, "journal_ship"),
                ship_to=f"{sb1.addr[0]}:{sb1.addr[1]}",
                ship_heartbeat_s=0.2))
            dp1.serve_in_thread()
            try:
                ship_admit_s = admit_wall(dp1, big)
                ship_enqueue_ms = dp1.shipper.stats()["enqueue_ms_mean"]
            finally:
                dp1.close()
                sb1.close()
            # Takeover phase: small jobs acked on a fresh primary and
            # WAL-shipped to its standby, the primary abandoned WITHOUT
            # close (machine death), the standby promoted —
            # takeover_mttr_s = promote command -> first replayed
            # result, takeover_all = the last one.
            sb2 = ServeDaemon(secret=b"bench-rec", cfg=ServeConfig(
                dispatch_poll_s=0.02,
                journal_dir=os.path.join(tmp, "journal_sb2"),
                standby_of="127.0.0.1:9"))
            sb2.serve_in_thread()
            dp2 = ServeDaemon(secret=b"bench-rec", cfg=ServeConfig(
                dispatch_poll_s=0.02,
                journal_dir=os.path.join(tmp, "journal_takeover"),
                ship_to=f"{sb2.addr[0]}:{sb2.addr[1]}",
                ship_heartbeat_s=0.2))
            dp2.serve_in_thread()
            try:
                admit_wall(dp2, small)  # paused: acked, never dispatched
                tids = list(dp2._jobs)
                deadline = time.monotonic() + 30.0
                while time.monotonic() < deadline:
                    ss = dp2.shipper.stats()
                    rs = sb2.receiver.stats()
                    if ss["acked_seq"] >= ss["shipped_seq"] > 0 \
                            and rs["missing_spills"] == 0:
                        break
                    time.sleep(0.02)
                # The in-process kill -9 (no drain, no compaction).
                dp2._shutdown.set()
                dp2.scheduler.stop()
                dp2._sock.close()
                t0 = time.perf_counter()
                cs = ServeClient(sb2.addr, b"bench-rec", timeout=60.0)
                cs.promote()
                take_first_s = None
                for jid in tids:
                    cs.wait(jid, timeout=600.0, poll_s=0.02)
                    if take_first_s is None:
                        take_first_s = time.perf_counter() - t0
                take_all_s = time.perf_counter() - t0
            finally:
                sb2.close()
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
        out = {
            "overhead_jobs": len(big),
            "corpus_bytes": len(big[0]),
            "admit_ms": round(journal_admit_s * 1e3, 3),
            "admit_ms_no_journal": round(plain_admit_s * 1e3, 3),
            "journal_append_ms": round(append_ms, 4),
            "journal_spill_ms": jstats["spill_ms_mean"],
            # The acceptance ratio (<= 5%): the fsync'd WAL record — the
            # O(1) cost every admit pays forever — as a share of the
            # admit latency the client observes.  The corpus spill is
            # reported beside it: corpus-proportional, dedup'd by sha.
            "append_overhead_pct": round(
                100.0 * append_ms / (journal_admit_s * 1e3), 2
            ) if journal_admit_s > 0 else None,
            "replayed": len(ids),
            "mttr_first_result_s": round(first_s, 3),
            "mttr_all_results_s": round(all_s, 3),
            # HA takeover (docs/SERVING.md "High availability").
            # Shipping is ASYNC: the only cost the admit PATH pays is
            # the shipper enqueue, accounted by the shipper itself —
            # that is the <= 5%-of-admit acceptance number.  The wall
            # delta of the whole admit stream is reported beside it
            # honestly: on this container's single core (the PR 11
            # lesson) the standby's concurrent spill transfer + fsync
            # CPU shows up in wall clock, which measures the machine,
            # not the admit path.
            "ship_admit_ms": round(ship_admit_s * 1e3, 3),
            "ship_enqueue_ms": ship_enqueue_ms,
            "ship_overhead_pct": round(
                100.0 * (ship_enqueue_ms or 0.0)
                / (journal_admit_s * 1e3), 2
            ) if journal_admit_s > 0 else None,
            "ship_wall_overhead_pct": round(
                100.0 * (ship_admit_s - journal_admit_s)
                / journal_admit_s, 2
            ) if journal_admit_s > 0 else None,
            "cores": os.cpu_count(),
            "takeover_replayed": len(tids),
            "takeover_mttr_s": round(take_first_s, 3),
            "takeover_all_results_s": round(take_all_s, 3),
        }
        print(
            f"[bench] recovery: append {out['journal_append_ms']}ms "
            f"({out['append_overhead_pct']}% of {out['admit_ms']}ms "
            f"admit, spill {out['journal_spill_ms']}ms), replay "
            f"{out['replayed']} jobs, first result "
            f"{out['mttr_first_result_s']}s, all {out['mttr_all_results_s']}s; "
            f"ship overhead {out['ship_overhead_pct']}%, takeover "
            f"{out['takeover_replayed']} jobs MTTR "
            f"{out['takeover_mttr_s']}s (all {out['takeover_all_results_s']}s)",
            file=sys.stderr,
        )
        from locust_tpu.utils import artifacts

        artifacts.record(
            artifacts.BENCH_SUBDICT_KINDS["recovery"], dict(out)
        )
        return out
    except Exception as e:  # noqa: BLE001 - the headline line comes first
        return {"error": f"{type(e).__name__}: {e}"[:200]}


def _plan_distributed_scaling() -> dict:
    """The distributed-plan row inside the ``plan`` sub-dict
    (docs/PLAN.md "Distributed execution"): one two-stage tf-idf plan
    through the FULL serve stack — admission, plan-shape recognition,
    corpus spill, per-worker map stages, cross-worker shuffle
    partitions, reduce, finalize — at 1 vs 2 modeled device lanes.

    Same modeling stance as ``_serve_pool_scaling``: each plan stage
    blocks ``_POOL_DEVICE_MS`` of modeled device time (the v5e
    executing).  The "1-device" measurement runs the SAME
    2-worker distributed machinery with every modeled device wait
    serialized through one lock — one chip, two RPC endpoints — so the
    headline ``speedup_2w`` isolates what stage overlap buys without
    charging either side different coordinator overhead.  The raw
    numbers (zero modeled device time, ``solo_s`` = the pre-scale-out
    local-engine path vs ``dist_2w_s``) ride beside it with the core
    count: on a 1-core container host-bound folds cannot overlap and
    the honest raw ratio is ~1x or below — physics plus shuffle
    overhead, not a placement failure.  Identity is asserted IN-ROW:
    every measured run's bytes must equal the solo compiled plan's.

    The v2 surface (ISSUE 20) adds ``join`` and ``pagerank`` rows —
    a deep two-hop join tree and a 4-iteration pagerank through the
    same 1-vs-2-lane lens — and a ``warm_repeat`` row pinning that a
    repeat distributed submit rides the workers' warm plan-node
    executables: per-worker compile counts unchanged across the
    repeat, ``map_warm_hits`` > 0, asserted in-row.
    """
    import threading

    from locust_tpu.config import EngineConfig
    from locust_tpu.distributor.worker import Worker
    from locust_tpu.io.corpus import synthetic_corpus
    from locust_tpu.plan import pagerank_plan, tfidf_plan
    from locust_tpu.plan.compile import compile_plan
    from locust_tpu.plan.nodes import Plan as PlanDoc, node
    from locust_tpu.serve.client import ServeClient
    from locust_tpu.serve.daemon import ServeConfig, ServeDaemon

    cfg_ovr = {"block_lines": 64, "line_width": 64, "key_width": 16,
               "emits_per_line": 8}
    cfg = EngineConfig(**cfg_ovr)
    lines = synthetic_corpus(256 * 64, n_vocab=2000, seed=23,
                             words_per_line=6)
    corpus = b"\n".join(lines[:256]) + b"\n"
    plan = tfidf_plan(2)
    oracle = compile_plan(plan, cfg).run_corpus(corpus).output

    # The v2 surface's workloads (ISSUE 20): a DEEP join tree (two join
    # hops over three wordcount-fold leaves — the 3-stage pipeline
    # shape) and an iterative pagerank.  The join corpus keeps its
    # vocabulary small so the leaf folds provably fit the table (the
    # distributed join refuses truncated leaves).
    jnodes = []
    for i in (1, 2, 3):
        jnodes += [
            node(f"c{i}", "source", "text"),
            node(f"m{i}", "map", "tokenize_count", (f"c{i}",)),
            node(f"s{i}", "shuffle", "by_key", (f"m{i}",)),
            node(f"r{i}", "reduce", "sum", (f"s{i}",)),
        ]
    jnodes += [
        node("j1", "join", "inner", ("r1", "r2"), combine="sum"),
        node("j2", "join", "inner", ("j1", "r3"), combine="mul"),
        node("out", "sink", "table", ("j2",)),
    ]
    join_plan = PlanDoc(tuple(jnodes))
    jlines = synthetic_corpus(192 * 64, n_vocab=300, seed=7,
                              words_per_line=6)
    jcorpus = b"\n".join(jlines[:192]) + b"\n"
    join_oracle = compile_plan(join_plan, cfg).run_corpus(
        jcorpus).output

    pr_plan = pagerank_plan(4)
    edges = b"0 1\n1 2\n2 0\n0 2\n3 1\n2 3\n" * 64
    pr_oracle = compile_plan(pr_plan, cfg).run_corpus(edges).output

    one_device = threading.Lock()

    class TwoLaneWorker(Worker):
        """Two workers, two modeled device lanes: stages overlap."""

        def _plan_stage(self, req):
            time.sleep(_POOL_DEVICE_MS / 1e3)
            return super()._plan_stage(req)

    class OneLaneWorker(Worker):
        """Two workers, ONE modeled device lane: the same distributed
        machinery with every device wait serialized — the 1-chip
        baseline the overlap headline is measured against."""

        def _plan_stage(self, req):
            with one_device:
                time.sleep(_POOL_DEVICE_MS / 1e3)
            return super()._plan_stage(req)

    def measure(worker_cls, wl_plan=None, wl_corpus=None,
                wl_oracle=None, repeat_probe=False):
        """One daemon (+ two workers unless worker_cls is None), one
        untimed warmup submit, one timed submit; byte-identity vs the
        solo compiled plan asserted on EVERY run.  repeat_probe=True
        also returns the warm-repeat evidence: per-worker compile
        counts around the timed (repeat) submit and the pool's
        map_warm_hits — the repeat must land on warm executables."""
        wl_plan = plan if wl_plan is None else wl_plan
        wl_corpus = corpus if wl_corpus is None else wl_corpus
        wl_oracle = oracle if wl_oracle is None else wl_oracle
        ws = []
        daemon = None
        try:
            if worker_cls is not None:
                for _ in range(2):
                    w = worker_cls(secret=b"bench-dplan", serve=True)
                    w.serve_in_thread()
                    ws.append(w)
            daemon = ServeDaemon(secret=b"bench-dplan", cfg=ServeConfig(
                dispatch_poll_s=0.02, shard_min_blocks=1,
                workers=tuple(f"127.0.0.1:{w.addr[1]}" for w in ws),
            ))
            daemon.serve_in_thread()
            client = ServeClient(daemon.addr, b"bench-dplan",
                                 timeout=120.0)

            def run_once() -> str:
                ack = client.submit(corpus=wl_corpus, config=cfg_ovr,
                                    plan=wl_plan.to_doc(),
                                    no_cache=True)
                res = client.wait(ack["job_id"], timeout=600.0,
                                  poll_s=0.02)
                assert res["pairs"][0][0] == wl_oracle, (
                    "distributed plan bytes diverged from the solo "
                    "compiled plan"
                )
                return client.status(ack["job_id"])["placed_on"]

            run_once()  # untimed warmup: compiles + connections
            pre = [w._serve_cache.stats()["compiles"] for w in ws]
            t0 = time.perf_counter()
            placed = run_once()
            wall = time.perf_counter() - t0
            want_pool = "plan:" if ws else "local"
            assert placed.startswith(want_pool), (placed, want_pool)
            if not repeat_probe:
                return wall
            post = [w._serve_cache.stats()["compiles"] for w in ws]
            pl = client.stats()["pool"]["plan"]
            probe = {
                "compiles_warmup": sum(pre),
                "compiles_repeat": sum(post),
                "compiles_unchanged": bool(post == pre),
                "map_warm_hits": int(pl.get("map_warm_hits", 0)),
                "solo_fallbacks": int(
                    pl.get("plan_solo_fallbacks", 0)),
                "identical": True,  # asserted on every run above
            }
            return wall, probe
        finally:
            if daemon is not None:
                daemon.close()
            for w in ws:
                w._shutdown.set()
                try:
                    w._sock.close()
                except OSError:
                    pass

    def lane_pair(wl_plan, wl_corpus, wl_oracle) -> dict:
        """The 1-vs-2-modeled-lane row for one workload."""
        o = measure(OneLaneWorker, wl_plan, wl_corpus, wl_oracle)
        t = measure(TwoLaneWorker, wl_plan, wl_corpus, wl_oracle)
        return {
            "modeled_1dev_s": round(o, 3),
            "modeled_2dev_s": round(t, 3),
            "speedup_2w": round(o / t, 3) if t > 0 else None,
            "identical": True,  # asserted on every run above
        }

    solo_s = measure(None)           # the pre-scale-out local floor
    dist_s = measure(Worker)         # distributed, zero device time
    one_s = measure(OneLaneWorker)   # distributed, 1 modeled lane
    two_s = measure(TwoLaneWorker)   # distributed, 2 modeled lanes
    # The v2 rows: a deep join tree and an iterative pagerank through
    # the same 1-vs-2-lane lens, plus the warm-repeat pin — a repeat
    # distributed submit must ride the workers' warm plan-node
    # executables (compiles unchanged, map_warm_hits > 0).
    join_row = lane_pair(join_plan, jcorpus, join_oracle)
    pr_row = lane_pair(pr_plan, edges, pr_oracle)
    _, warm = measure(Worker, join_plan, jcorpus, join_oracle,
                      repeat_probe=True)
    assert warm["compiles_unchanged"] and warm["map_warm_hits"] > 0, (
        "repeat distributed plan submit recompiled on the workers",
        warm,
    )
    out = {
        "cores": os.cpu_count(),
        "modeled_device_ms": _POOL_DEVICE_MS,
        "modeled_1dev_s": round(one_s, 3),
        "modeled_2dev_s": round(two_s, 3),
        "speedup_2w": round(one_s / two_s, 3) if two_s > 0 else None,
        "raw": {
            "solo_s": round(solo_s, 3),
            "dist_2w_s": round(dist_s, 3),
            "speedup_2w": (
                round(solo_s / dist_s, 3) if dist_s > 0 else None
            ),
        },
        "join": join_row,
        "pagerank": pr_row,
        "warm_repeat": warm,
        "identical": True,  # asserted on every run above
    }
    print(
        f"[bench] plan distributed (device-modeled "
        f"{_POOL_DEVICE_MS:.0f}ms/stage): tfidf 1 lane {one_s:.2f}s vs "
        f"2 lanes {two_s:.2f}s ({out['speedup_2w']}x), join "
        f"{join_row['modeled_1dev_s']}s vs {join_row['modeled_2dev_s']}s "
        f"({join_row['speedup_2w']}x), pagerank "
        f"{pr_row['modeled_1dev_s']}s vs {pr_row['modeled_2dev_s']}s "
        f"({pr_row['speedup_2w']}x); warm repeat: compiles "
        f"{warm['compiles_repeat']} (unchanged), "
        f"{warm['map_warm_hits']} warm map hits; raw CPU on "
        f"{out['cores']} core(s): solo {solo_s:.2f}s vs distributed "
        f"{dist_s:.2f}s ({out['raw']['speedup_2w']}x)",
        file=sys.stderr,
    )
    return out


def _plan_optimizer_rows(cfg, lines, rows) -> dict:
    """The optimizer evidence rows (docs/PLAN.md "Optimizer"), identity
    asserted inside every measurement: ``fused`` (the fuse_fold_kernel
    rewrite vs the naive hasht lowering), ``cse`` (a twin-chain join
    folded once, plus the cross-tenant sub-plan cache hit) and
    ``incremental`` (the grown-corpus delta refold vs a full recompute).
    Off-TPU the fused walls are honest interpret-mode numbers — the
    kernel re-traces per grid step on CPU, so the rewrite's win is a
    TPU claim; ``kernel_engaged``/``backend`` say which world the row
    measured."""
    import dataclasses

    import jax

    from locust_tpu.plan import Plan, node, wordcount_plan
    from locust_tpu.plan.compile import compile_plan
    from locust_tpu.serve.cache import SubPlanCache

    def best_of(fn, n=2):
        best, out = float("inf"), None
        for _ in range(n):
            t0 = time.perf_counter()
            out = fn()
            best = min(best, time.perf_counter() - t0)
        return best, out

    def wall(fn):
        t0 = time.perf_counter()
        out = fn()
        return time.perf_counter() - t0, out

    # --- fused: wordcount under hasht, optimizer on vs off ----------
    hasht = dataclasses.replace(cfg, sort_mode="hasht")
    frows = rows[: 2 * cfg.block_lines]  # bound the interpret cost
    fcp = compile_plan(wordcount_plan(), hasht)
    ncp = compile_plan(wordcount_plan(), hasht, optimize=False)
    fcp.run(frows, render=False)  # warm both executables
    ncp.run(frows, render=False)
    f_s, f_res = best_of(lambda: fcp.run(frows, render=False))
    n_s, n_res = best_of(lambda: ncp.run(frows, render=False))
    assert f_res.value == n_res.value, "fuse_fold_kernel diverged"
    # Megakernel v2: which fused formulation this row actually measured
    # — "batch" (one whole-corpus launch), "stream" (the persistent
    # streaming kernel), or None with demoted=True when the engine's
    # gate turned the kernel off and folded exactly like hasht
    # (mesh-demoted is the distributed engines' spelling of the same).
    f_rr = getattr(f_res, "run_result", None)
    fused = {
        "rewrite_fired": bool(fcp.optimized.fuse_kernel),
        "kernel_engaged": bool(
            fcp._wordcount_engine()._fused_kernel_on
        ),
        "formulation": getattr(f_rr, "fused_kernel", None),
        "demoted": bool(getattr(f_rr, "fused_demoted", False)),
        "backend": jax.default_backend(),
        "lines": int(frows.shape[0]),
        "fused_s": round(f_s, 3),
        "hasht_s": round(n_s, 3),
        "speedup": round(n_s / f_s, 2) if f_s > 0 else None,
        "identical": True,  # asserted above
    }

    # --- cse: twin-chain join folds once + the cross-tenant hit -----
    def chain(tag):
        return [
            node(f"{tag}s", "source", "text"),
            node(f"{tag}m", "map", "tokenize_count", (f"{tag}s",)),
            node(f"{tag}g", "shuffle", "by_key", (f"{tag}m",)),
            node(f"{tag}r", "reduce", "sum", (f"{tag}g",)),
        ]

    twin = Plan(tuple(chain("a") + chain("b") + [
        node("j", "join", "inner", ("ar", "br"), combine="sum"),
        node("o", "sink", "table", ("j",)),
    ]))
    crows = rows[:4096]
    ocp = compile_plan(twin, cfg)
    tcp = compile_plan(twin, cfg, optimize=False)
    ocp.run(crows, render=False)
    tcp.run(crows, render=False)
    o_s, o_res = best_of(lambda: ocp.run(crows, render=False))
    t_s, t_res = best_of(lambda: tcp.run(crows, render=False))
    assert o_res.value == t_res.value, "cse_subplan diverged"
    # Cross-tenant: an alpha-renamed wordcount plan (different plan
    # fingerprint, so the whole-job result cache would MISS) lands on
    # the sub-plan edge the first tenant populated.
    corpus = b"".join(ln + b"\n" for ln in lines[:4096])
    renamed = Plan(tuple(chain("t2_") + [
        node("t2_o", "sink", "table", ("t2_r",)),
    ]))
    sub = SubPlanCache()
    wcp = compile_plan(wordcount_plan(), cfg)
    wcp.run_corpus(corpus, sub_cache=sub)  # tenant 1 warms the edge
    first_s, first = wall(
        lambda: compile_plan(wordcount_plan(), cfg).run_corpus(corpus)
    )
    hit_s, hit = wall(
        lambda: compile_plan(renamed, cfg).run_corpus(
            corpus, sub_cache=sub
        )
    )
    assert hit.output == first.output, "cross-tenant edge diverged"
    assert sub.stats()["hits"] >= 1, "second tenant missed the edge"
    cse = {
        "twin_nodes": len(twin.nodes),
        "optimized_nodes": len(ocp.optimized.plan.nodes),
        "twin_naive_s": round(t_s, 3),
        "twin_cse_s": round(o_s, 3),
        "twin_speedup": round(t_s / o_s, 2) if o_s > 0 else None,
        "cross_tenant_cold_s": round(first_s, 3),
        "cross_tenant_hit_s": round(hit_s, 3),
        "cross_tenant_speedup": (
            round(first_s / hit_s, 2) if hit_s > 0 else None
        ),
        "subcache_hits": sub.stats()["hits"],
        "identical": True,  # asserted above, both measurements
    }

    # --- incremental: grown corpus refolds only the delta -----------
    grown = corpus + b"".join(ln + b"\n" for ln in lines[4096:4160])
    icp = compile_plan(wordcount_plan(), cfg)
    icp.run_corpus(grown)  # warm the executable
    full_s, full = best_of(lambda: icp.run_corpus(grown))
    # Warm the delta-shape jit on a throwaway cache (the measured pass
    # must pay the merge, not a one-time trace of the 64-line block).
    wsub = SubPlanCache()
    icp.run_corpus(corpus, sub_cache=wsub)
    icp.run_corpus(grown, sub_cache=wsub)
    isub = SubPlanCache()
    icp.run_corpus(corpus, sub_cache=isub)  # cache the prefix fold
    # ONE measured call: the first consult does the delta merge (a
    # best-of would measure the exact hit it just stored).
    inc_s, inc = wall(
        lambda: icp.run_corpus(grown, sub_cache=isub)
    )
    st = isub.stats()
    assert inc.output == full.output, "incremental_fold diverged"
    assert st["incremental_hits"] == 1, "delta refold did not engage"
    assert st["last_delta_blocks"] < st["last_total_blocks"], (
        "delta refold touched every block"
    )
    incremental = {
        "prefix_lines": 4096,
        "delta_lines": 64,
        "delta_blocks": st["last_delta_blocks"],
        "total_blocks": st["last_total_blocks"],
        "full_s": round(full_s, 3),
        "incremental_s": round(inc_s, 3),
        "speedup": round(full_s / inc_s, 2) if inc_s > 0 else None,
        "identical": True,  # asserted above
    }
    print(
        f"[bench] plan optimizer: fused {f_s:.2f}s vs hasht {n_s:.2f}s "
        f"(kernel_engaged={fused['kernel_engaged']}, "
        f"backend={fused['backend']}), cse twin {t_s:.2f}s -> "
        f"{o_s:.2f}s + cross-tenant hit {hit_s*1e3:.0f}ms "
        f"(cold {first_s:.2f}s), incremental "
        f"{st['last_delta_blocks']}/{st['last_total_blocks']} blocks "
        f"{inc_s:.2f}s vs full {full_s:.2f}s",
        file=sys.stderr,
    )
    return {"fused": fused, "cse": cse, "incremental": incremental}


def _plan_stats() -> dict:
    """Plan-layer overhead summary for the one-line JSON (docs/PLAN.md):
    the plan-compiled WordCount and tf-idf pipelines against their
    hand-wired drivers over the same corpus, best-of-3 each after a
    shared warmup.  The compiler only NAMES work the engine already does
    (the fused fold IS the same engine call), so the acceptance bound is
    <= +5% — anything past that means the lowering grew a real stage.
    Identity is asserted, not assumed: the plan run's pairs must equal
    the hand-wired run's exactly.  Guarded like the siblings: a failure
    never costs the headline line; ``LOCUST_BENCH_PLAN=0`` skips.
    Completed runs land a ``plan_bench`` evidence row
    (artifacts.BENCH_SUBDICT_KINDS)."""
    if os.environ.get("LOCUST_BENCH_PLAN", "1") == "0":
        return {"skipped": True}
    try:
        import numpy as np

        from locust_tpu.apps.tfidf import build_tfidf
        from locust_tpu.config import EngineConfig
        from locust_tpu.engine import MapReduceEngine
        from locust_tpu.io.corpus import synthetic_corpus
        from locust_tpu.plan import tfidf_plan, wordcount_plan
        from locust_tpu.plan.compile import compile_plan
        from locust_tpu.utils import artifacts

        # block_lines sizes the tf fold's pair capacity too
        # (default_pairs_capacity = 2x emits_per_block): 2048 x 12
        # leaves headroom over this corpus's ~31k distinct (word, doc)
        # pairs — the tf fold RAISES on overflow, it never truncates.
        cfg = EngineConfig(block_lines=2048, key_width=16,
                           emits_per_line=12)
        lines = synthetic_corpus(2 << 20, n_vocab=4000, seed=11)
        eng = MapReduceEngine(cfg)
        rows = eng.rows_from_lines(lines)
        wc = compile_plan(wordcount_plan(), cfg)

        def best_of(fn, n=3):
            best, out = float("inf"), None
            for _ in range(n):
                t0 = time.perf_counter()
                out = fn()
                best = min(best, time.perf_counter() - t0)
            return best, out

        eng.run_fused(rows)  # shared warmup: compile once
        # Both sides fold AND host-finalize: the plan run's value IS the
        # decoded pair table, so the hand-wired side must pay the same
        # to_host_pairs or the comparison charges the plan for work the
        # driver also does at print time.
        hand_s, hand_pairs = best_of(
            lambda: eng.run_fused(rows).to_host_pairs()
        )
        plan_s, plan_res = best_of(
            lambda: wc.run(rows, render=False)
        )
        ident = plan_res.value == hand_pairs

        # tf-idf over a 4k-line slice: the pair table must FIT the
        # default capacity (the fold raises on overflow rather than
        # truncate), and the wall comparison only needs a real fold.
        trows = rows[:4000]
        ids = (np.arange(trows.shape[0]) // 8).astype(np.int32)
        tp = compile_plan(tfidf_plan(8), cfg)
        build_tfidf(trows, ids, cfg)  # warmup
        tf_hand_s, tf_hand = best_of(
            lambda: build_tfidf(trows, ids, cfg), n=2
        )
        tf_plan_s, tf_plan = best_of(
            lambda: tp.run(trows, render=False), n=2
        )
        tf_ident = tf_plan.value == tf_hand
        # Identity is ASSERTED, not just recorded: a lowering drift must
        # surface as this sub-dict's error field, never as a passing
        # bench row with identical:false buried in it.
        assert ident and tf_ident, (
            "plan-compiled output diverged from the hand-wired fold "
            f"(wordcount identical={ident}, tfidf identical={tf_ident})"
        )

        def pct(plan, hand):
            return round(100 * (plan - hand) / hand, 2)

        out = {
            "corpus_mb": round(sum(len(x) + 1 for x in lines) / 1e6, 2),
            "wordcount_hand_s": round(hand_s, 3),
            "wordcount_plan_s": round(plan_s, 3),
            "wordcount_overhead_pct": pct(plan_s, hand_s),
            "tfidf_hand_s": round(tf_hand_s, 3),
            "tfidf_plan_s": round(tf_plan_s, 3),
            "tfidf_overhead_pct": pct(tf_plan_s, tf_hand_s),
            "identical": bool(ident and tf_ident),
            "accept_5pct": bool(
                pct(plan_s, hand_s) <= 5.0
                and pct(tf_plan_s, tf_hand_s) <= 5.0
            ),
            "wordcount_fp": wordcount_plan().fingerprint(),
            "tfidf_fp": tfidf_plan(8).fingerprint(),
            # The scale-out row (ISSUE 16): the same tfidf pipeline
            # through the distributed plan path, identity asserted on
            # every measured run inside the helper.
            "distributed": _plan_distributed_scaling(),
        }
        # Optimizer rows (ISSUE 17): fuse/cse/incremental rewrites,
        # identity asserted inside every measurement.
        out.update(_plan_optimizer_rows(cfg, lines, rows))
        print(
            f"[bench] plan: wordcount {hand_s:.2f}s hand vs "
            f"{plan_s:.2f}s plan ({out['wordcount_overhead_pct']:+.1f}%), "
            f"tfidf {tf_hand_s:.2f}s vs {tf_plan_s:.2f}s "
            f"({out['tfidf_overhead_pct']:+.1f}%), identical={ident and tf_ident}",
            file=sys.stderr,
        )
        artifacts.record(
            artifacts.BENCH_SUBDICT_KINDS["plan"], dict(out)
        )
        return out
    except Exception as e:  # noqa: BLE001 - the headline line comes first
        return {"error": f"{type(e).__name__}: {e}"[:200]}


def _bench_subdict_producers() -> dict:
    """Guarded sub-bench producers, two-sided against the evidence-ledger
    kinds (artifacts.BENCH_SUBDICT_KINDS, same identity discipline as
    CONFIG_AB_KINDS): a sub-dict producer added here without a ledger
    kind — or a kind registered with no producer — fails loudly.  The
    "stream" sub-dict stays outside the table on purpose (its evidence
    lands in dedicated artifacts/stream_*.jsonl files, not ledger rows).
    """
    from locust_tpu.utils.artifacts import BENCH_SUBDICT_KINDS

    subdicts = {
        "dataplane": _dataplane_stats,
        "serve": _serve_stats,
        "recovery": _recovery_stats,
        "plan": _plan_stats,
    }
    if tuple(subdicts) != tuple(BENCH_SUBDICT_KINDS):
        raise RuntimeError(
            "bench sub-dict producers drifted from "
            f"artifacts.BENCH_SUBDICT_KINDS: {tuple(subdicts)} != "
            f"{tuple(BENCH_SUBDICT_KINDS)}"
        )
    return subdicts


def run_bench(backend: str) -> dict:
    import jax

    from locust_tpu.config import EngineConfig
    from locust_tpu.engine import MapReduceEngine

    # Opt-in telemetry (LOCUST_BENCH_OBS=1): spans/metrics from the
    # streaming sub-bench land in an "obs" sub-dict of the one JSON line.
    # Default OFF — the headline number must ride the zero-overhead no-op
    # path (tests/test_obs.py pins it).
    obs_on = os.environ.get("LOCUST_BENCH_OBS") == "1"
    if obs_on:
        from locust_tpu import obs

        obs.enable(process="bench")

    target = TARGET_BYTES if backend == "tpu" else CPU_TARGET_BYTES
    lines = load_corpus(target)
    corpus_bytes = sum(len(ln) + 1 for ln in lines)
    defaults = _PER_BACKEND.get(backend, _PER_BACKEND["cpu"])
    # Lossless capacity auto-sizing (env overrides win).  key_width=16 on
    # hamlet: 1.72x end-to-end on CPU at an identical output table
    # (distinct=5608 both widths).  Caps never exceed the defaults AND
    # bench_engine_config pins table_size to what the DEFAULT
    # emits_per_line would resolve (a smaller cap would otherwise shrink
    # resolved_table_size = min(65536, max(block_lines*emits_per_line, 4096)) and
    # truncate keys the default config keeps), so the result is always
    # byte-identical to a default-config run.
    if _EMITS_ENV and _KEY_WIDTH_ENV:
        d = EngineConfig()
        auto_kw, auto_epl = d.key_width, d.emits_per_line  # both pinned
    else:
        auto_kw, auto_epl = bench_auto_caps(lines)
    eff_kw = int(_KEY_WIDTH_ENV) if _KEY_WIDTH_ENV else auto_kw
    eff_epl = int(_EMITS_ENV) if _EMITS_ENV else auto_epl
    if backend == "tpu":
        # Caps are part of the joint-measurement rule: A/B rows are only
        # trusted if swept at the caps THIS bench run assembles (a
        # LOCUST_BENCH_VOCAB corpus has different auto caps than the
        # sweep's corpus and must not inherit its winners).
        defaults = _evidence_tuned_tpu_defaults(
            defaults, {"key_width": eff_kw, "emits_per_line": eff_epl}
        )
    block_lines = (
        int(_BLOCK_LINES_ENV) if _BLOCK_LINES_ENV else defaults["block_lines"]
    )
    # Distinct-aware table sizing, CPU path only: the TPU config must
    # stay jointly measured with the committed A/B rows (which carry no
    # table_size), while on CPU the hasht fold re-aggregates every table
    # row per block and a right-sized table measured +14% (exact: the
    # distinct count is a host measurement, table >= distinct).
    table_size = None
    if _TABLE_ENV:
        table_size = int(_TABLE_ENV)
    elif backend == "tpu":
        # Evidence-tuned only (engine_table_ab rows measured at the
        # adopted mode+block): the TPU config must stay jointly measured.
        table_size = defaults.get("table_size")
    elif backend == "cpu" and not (_EMITS_ENV and _KEY_WIDTH_ENV):
        from locust_tpu.io.loader import count_distinct_tokens

        d = EngineConfig(block_lines=block_lines)
        distinct_est = count_distinct_tokens(
            [ln[: d.line_width] for ln in lines]
        )
        table_size = _auto_table_size(distinct_est, d.resolved_table_size)
        print(
            f"[bench] distinct-aware table: {distinct_est} distinct -> "
            f"table_size={table_size} (default {d.resolved_table_size})",
            file=sys.stderr,
        )
    cfg = bench_engine_config(
        block_lines,
        table_size=table_size,
        sort_mode=_SORT_MODE_ENV or defaults["sort_mode"],
        emits_per_line=eff_epl,
        key_width=eff_kw,
        use_pallas=(
            _PALLAS_ENV == "1"
            if _PALLAS_ENV is not None
            else defaults.get("use_pallas", False)
        ),
    )
    eng = MapReduceEngine(cfg)
    rows = eng.rows_from_lines(lines)
    print(
        f"[bench] corpus: {corpus_bytes/1e6:.1f} MB, {len(lines)} lines, "
        f"block_lines={block_lines}, sort_mode={cfg.sort_mode}, "
        f"emits_per_line={cfg.emits_per_line}, "
        f"table_size={cfg.resolved_table_size}, "
        f"backend={jax.default_backend()}",
        file=sys.stderr,
    )

    t0 = time.perf_counter()
    blocks = eng.prepare_blocks(rows)
    blocks.block_until_ready()  # device_put is async; time the actual transfer
    print(f"[bench] H2D staging: {time.perf_counter()-t0:.1f}s", file=sys.stderr)
    t0 = time.perf_counter()
    res = eng.run_blocks(blocks)
    print(f"[bench] warmup (compile+run): {time.perf_counter()-t0:.1f}s", file=sys.stderr)

    best = float("inf")
    for _ in range(3):
        res = eng.run_blocks(blocks)
        best = min(best, res.times.total_ms / 1e3)
    mb_s = corpus_bytes / 1e6 / best
    print(
        f"[bench] steady-state: {best*1e3:.1f} ms, {mb_s:.1f} MB/s, "
        f"distinct={res.num_segments}, truncated={res.truncated}",
        file=sys.stderr,
    )
    # Roofline calibration: how hard does the sort —
    # the pipeline's dominant consumer — work the chip's memory system,
    # judged against the device's peak HBM bandwidth rather than against
    # the reference's 2016 GPU.
    from locust_tpu.utils import roofline

    n_blocks = -(-len(lines) // block_lines)
    roof = roofline.summarize(
        cfg.sort_mode,
        cfg.key_lanes,
        cfg.emits_per_block,
        cfg.resolved_table_size,
        n_blocks,
        best,
        jax.devices()[0].device_kind,
        block_lines=cfg.block_lines,
        line_width=cfg.line_width,
    )
    util = roof["hbm_utilization_pct"]
    print(
        f"[bench] roofline: ~{roof['est_sort_traffic_gb']} GB sort traffic "
        f"({roof['n_blocks']} blocks x {roof['sort_passes']} passes @ "
        f"{roof['rows_per_sort']} rows) -> {roof['achieved_sort_gb_s']} GB/s"
        + (
            f" = {util}% of {roof['hbm_peak_gb_s']} GB/s "
            f"{roof['device_kind']} HBM peak"
            if util is not None
            else f" (no peak known for {roof['device_kind']!r})"
        ),
        file=sys.stderr,
    )
    subdicts = _bench_subdict_producers()
    payload = {
        "metric": "wordcount_throughput",
        "value": round(mb_s, 3),
        "unit": "MB/s",
        "vs_baseline": round(mb_s / BASELINE_MB_S, 2),
        "backend": jax.default_backend(),
        "distinct": res.num_segments,
        "truncated": res.truncated,
        "roofline": {
            "achieved_sort_gb_s": roof["achieved_sort_gb_s"],
            "hbm_peak_gb_s": roof["hbm_peak_gb_s"],
            "hbm_utilization_pct": roof["hbm_utilization_pct"],
        },
        "dataplane": subdicts["dataplane"](),
        "stream": _stream_stats(eng, rows),
        "serve": subdicts["serve"](),
        "recovery": subdicts["recovery"](),
        "plan": subdicts["plan"](),
    }
    if obs_on:
        from locust_tpu import obs

        payload["obs"] = obs.summary()
    if payload["backend"] == "cpu":
        # A CPU fallback is NOT the framework's number — point at the
        # committed TPU evidence so the driver-captured line is
        # self-contained even when no TPU run succeeded at bench time:
        # the latest TPU bench row AND the best engine-level A/B row
        # (same corpus/timing boundary, labeled with its kind/setting).
        last = _last_tpu_bench_row()
        if last:
            payload["last_tpu_bench"] = last
        ab = _best_tpu_ab_row()
        if ab:
            payload["last_tpu_ab"] = ab
    # TPU evidence: every TPU bench run leaves a committed-able row in artifacts/tpu_runs.jsonl, independent of
    # whether the driver captures this process's stdout.
    from locust_tpu.utils import artifacts

    artifacts.record(
        "bench",
        {
            **payload,
            "corpus_mb": round(corpus_bytes / 1e6, 1),
            "lines": len(lines),
            "block_lines": block_lines,
            "sort_mode": cfg.sort_mode,
            "emits_per_line": cfg.emits_per_line,
            "key_width": cfg.key_width,
            "overflow_tokens": res.overflow_tokens,
            "best_s": round(best, 4),
            "distinct": res.num_segments,
            "truncated": res.truncated,
            "roofline": roof,
        },
    )
    return payload


def rerun_on_cpu(reason: str, budget_s: float) -> int:
    """Re-exec this bench pinned to CPU and relay its JSON line.

    A fresh process is the only reliable way to drop a half-initialized
    TPU backend; jax cannot deregister one post-init.  Runs within the
    REMAINING watchdog budget (not a fresh one) so total wall time stays
    bounded by $LOCUST_BENCH_TIMEOUT, and guarantees a JSON line even if
    the child dies without printing one.
    """
    print(f"[bench] TPU run failed ({reason}); re-running on CPU", file=sys.stderr)
    if budget_s < 30:
        emit(error_payload(f"TPU run failed ({reason}); no budget left for CPU rerun"))
        return 1
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["LOCUST_BENCH_BACKEND"] = "cpu"
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__)],
            env=env,
            timeout=budget_s,
            stdout=subprocess.PIPE,
            stderr=sys.stderr,
            text=True,
        )
    except subprocess.TimeoutExpired:
        emit(error_payload(f"TPU run failed ({reason}); CPU rerun timed out"))
        return 1
    json_lines = _json_lines(proc.stdout)
    if not json_lines:
        emit(error_payload(
            f"TPU run failed ({reason}); CPU rerun rc={proc.returncode} "
            "printed no JSON"
        ))
        return 1
    print(json_lines[-1], flush=True)
    return proc.returncode


def _json_lines(stdout: str) -> list[str]:
    return [ln for ln in stdout.splitlines() if ln.strip().startswith("{")]


def orchestrate() -> int:
    """Outer retry-until-deadline loop.

    In auto mode the bench repeatedly attempts a TPU run in a CHILD
    process (the parent stays off jax, so each child can take the chip)
    until one succeeds or only the CPU-fallback reserve remains.  Each
    attempt's first compile lands in the persistent cache
    (config.compile_cache_dir), so a repeat attempt on the same machine
    reloads it.  ROADMAP Speed item 1 replaces this loop.
    """
    deadline = time.monotonic() + TIMEOUT_S
    attempt = 0
    while True:
        budget = deadline - time.monotonic() - CPU_RESERVE_S
        if budget < MIN_TPU_ATTEMPT_S:
            break
        attempt += 1
        env = dict(os.environ)
        env["LOCUST_BENCH_INNER"] = "1"
        env["LOCUST_BENCH_BACKEND"] = "tpu"
        env["LOCUST_BENCH_TIMEOUT"] = str(max(120.0, budget))
        # The child must FAIL FAST on a mid-run TPU death, not burn this
        # attempt's whole budget on its own CPU rerun — the orchestrator
        # owns the CPU fallback.
        env["LOCUST_BENCH_NO_CPU_RERUN"] = "1"
        print(
            f"[bench] orchestrator: TPU attempt {attempt} "
            f"(budget {budget:.0f}s)",
            file=sys.stderr,
        )
        try:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__)],
                env=env,
                timeout=budget + 30,
                stdout=subprocess.PIPE,
                stderr=sys.stderr,
                text=True,
            )
        except subprocess.TimeoutExpired:
            continue
        lines = _json_lines(proc.stdout)
        if proc.returncode == 0 and lines:
            try:
                row = json.loads(lines[-1])
            except ValueError:
                row = {}
            if row.get("backend") == "tpu" and "error" not in row:
                print(lines[-1], flush=True)
                return 0
        print(
            f"[bench] orchestrator: attempt {attempt} failed "
            f"(rc={proc.returncode}); will retry",
            file=sys.stderr,
        )
        time.sleep(
            min(30.0, max(0.0, deadline - CPU_RESERVE_S - time.monotonic()))
        )

    remaining = deadline - time.monotonic()
    if remaining < 30:
        emit(error_payload("orchestrator: no budget left for CPU fallback"))
        return 1
    print(
        f"[bench] orchestrator: TPU attempts exhausted; CPU fallback "
        f"({remaining:.0f}s)",
        file=sys.stderr,
    )
    env = dict(os.environ)
    env["LOCUST_BENCH_INNER"] = "1"
    env["LOCUST_BENCH_BACKEND"] = "cpu"
    env["JAX_PLATFORMS"] = "cpu"
    env["LOCUST_BENCH_TIMEOUT"] = str(remaining)
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__)],
            env=env,
            timeout=remaining + 30,
            stdout=subprocess.PIPE,
            stderr=sys.stderr,
            text=True,
        )
    except subprocess.TimeoutExpired:
        emit(error_payload("orchestrator: CPU fallback timed out"))
        return 1
    lines = _json_lines(proc.stdout)
    if not lines:
        emit(error_payload(
            f"orchestrator: CPU fallback rc={proc.returncode} printed no JSON"
        ))
        return 1
    print(lines[-1], flush=True)
    return proc.returncode


def main() -> int:
    # Fail fast on a malformed env override — before the orchestrator can
    # burn its whole TPU retry budget re-discovering the same
    # deterministic typo in every child.  Validated here rather than at
    # import so scripts that `import bench` for its helpers
    # (scripts/stream_scale.py) get a normal namespace, not a
    # bench-contract JSON line and sys.exit on their own stdout.
    if _PALLAS_ENV is not None and _PALLAS_ENV not in ("0", "1"):
        emit(error_payload(
            f"LOCUST_BENCH_PALLAS must be '0' or '1', got {_PALLAS_ENV!r}"
        ))
        return 1
    if (
        os.environ.get("LOCUST_BENCH_BACKEND", "auto") == "auto"
        and not os.environ.get("LOCUST_BENCH_INNER")
        and os.environ.get("JAX_PLATFORMS", "").strip() != "cpu"
    ):
        return orchestrate()
    deadline = time.monotonic() + TIMEOUT_S
    watchdog = threading.Timer(
        TIMEOUT_S,
        lambda: (
            emit(error_payload(f"watchdog: bench exceeded {TIMEOUT_S:.0f}s")),
            os._exit(2),
        ),
    )
    watchdog.daemon = True
    watchdog.start()

    mode = os.environ.get("LOCUST_BENCH_BACKEND", "auto")
    try:
        # Import inside the guard: locust_tpu.config validates LOCUST_*
        # env vars at import and raises ValueError on a malformed one —
        # that must become the JSON error line, not a bare traceback.
        from locust_tpu.backend import select_backend

        backend = select_backend(mode)
    except (RuntimeError, ValueError) as e:
        emit(error_payload(str(e)))
        return 1
    print(f"[bench] selected backend: {backend}", file=sys.stderr)

    try:
        payload = run_bench(backend)
    except Exception as e:  # noqa: BLE001 - the driver needs its JSON line
        if backend == "tpu" and not os.environ.get("LOCUST_BENCH_NO_CPU_RERUN"):
            watchdog.cancel()
            return rerun_on_cpu(
                f"{type(e).__name__}: {e}", deadline - time.monotonic()
            )
        emit(error_payload(f"{type(e).__name__}: {e}"))
        return 1
    emit(payload)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
