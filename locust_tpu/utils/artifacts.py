"""Evidence ledger: self-describing JSONL rows of runs on a TPU.

``record(kind, payload)`` appends one JSON line to
``artifacts/tpu_runs.jsonl`` (repo-root relative, overridable via
``$LOCUST_ARTIFACTS_DIR``) **iff this process is actually on a TPU
backend**; on CPU it is a no-op.  Callers are measurement tools only —
``bench.py`` and the ``scripts/bench_*`` / ``stream_scale`` scripts.
The program's own entry points (the CLI, the serve daemon) never write
here: a user's run must not change a file in the checkout.

Each row self-describes: timestamp, jax version, device kind, plus the
caller's payload.  Append-only JSONL with a same-filesystem atomic write
per line (O_APPEND) — concurrent writers (bench retry loop + a test run)
interleave whole lines, never torn ones.
"""

from __future__ import annotations

import json
import os
import time

_DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "artifacts",
)


def artifacts_dir() -> str:
    return os.environ.get("LOCUST_ARTIFACTS_DIR", _DEFAULT_DIR)


# Ledger kinds whose rows DRIVE bench.py's evidence-tuned configuration
# (bench._evidence_tuned_tpu_defaults reads exactly these).  Shared here
# (jax-free) so whatever writes these rows and bench's tuning can never
# drift: a kind added to one but not the other leaves the headline
# config stale.  emits_per_line_ab / key_width_ab are deliberately absent —
# they are verification phases; bench auto-sizes caps from the corpus.
CONFIG_AB_KINDS = (
    "engine_sort_mode_ab",
    "block_lines_ab",
    "engine_table_ab",
    "engine_pallas_ab",
)

# Bench sub-dict -> evidence-ledger row kind for the guarded non-headline
# benches (two-sided, same discipline as CONFIG_AB_KINDS): bench.py's
# sub-dict producer table must match these KEYS exactly (checked with a
# loud identity error at bench time), and every recorder of one of these
# KINDS imports the string from here instead of re-spelling it — a
# sub-dict added without a ledger kind, or a kind recorded that no bench
# sub-dict reports, fails loudly instead of silently drifting.  The
# "stream" sub-dict is deliberately absent: its evidence lands in
# dedicated per-round files (artifacts/stream_*.jsonl), not ledger rows.
BENCH_SUBDICT_KINDS = {
    "dataplane": "dataplane_bench",
    "serve": "serve_bench",
    "recovery": "recovery_bench",
    "plan": "plan_bench",
}


def ledger_rows(path: str | None = None) -> list[dict]:
    """Parsed rows of the evidence ledger (malformed lines skipped).

    The single ledger reader: bench's evidence tuning decides off this
    file, and it is appended by concurrent processes and merged across
    machines via git — every consumer must treat it as untrusted,
    per-line.  One shared copy so a hardening fix can't miss a caller.

    ``path`` pins an explicit ledger file; default is the live
    ``artifacts_dir()`` ledger.  Callers whose WRITES are pinned to one
    file must pin their reads to the same file or the two silently
    diverge under $LOCUST_ARTIFACTS_DIR.
    """
    rows: list[dict] = []
    try:
        # errors="replace": a torn binary write or merge artifact must
        # cost ONE line (json.loads rejects the U+FFFD), not the whole
        # scan — UnicodeDecodeError from line iteration would otherwise
        # escape the per-line guard and kill the caller.
        with open(
            path or os.path.join(artifacts_dir(), "tpu_runs.jsonl"),
            encoding="utf-8",
            errors="replace",
        ) as f:
            for line in f:
                try:
                    r = json.loads(line)
                except ValueError:
                    continue
                if isinstance(r, dict):
                    rows.append(r)
    except OSError:
        pass
    return rows


def latest_row_ts(
    kind: str, backend: str = "tpu", where=None, path: str | None = None
) -> float:
    """Newest ``ts`` among ledger rows of ``kind``/``backend`` that also
    satisfy the optional ``where`` predicate.  Rows with missing or
    malformed ``ts`` (ledger is multi-writer, git-merged) are skipped,
    never raised on — one bad line must not cost a measurement run."""
    ts = 0.0
    for r in ledger_rows(path):
        if r.get("kind") != kind or r.get("backend") != backend:
            continue
        if where is not None:
            try:
                if not where(r):
                    continue
            except Exception:  # locust: noqa[R017] malformed multi-writer ledger rows are skipped by contract (docstring above); per-row logging would spam every sweep over a git-merged ledger
                continue
        try:
            ts = max(ts, float(r.get("ts") or 0))
        except (TypeError, ValueError):
            continue
    return ts


_CODE_FP: str | None = None


def code_fingerprint() -> str:
    """Hash of the measurement-relevant package code: core/ops/parallel/
    io trees plus engine/config/backend.  Evidence rows are stamped with
    it so session-resume logic can tell "same code, reusable
    measurement" from "the compute path changed mid-session, re-measure"
    — a wall-clock floor alone cannot (a carried stale side would steer
    bench's evidence tuning with numbers from two code versions).
    Measurement IMPLEMENTATIONS outside the package are in the hash too:
    the variant kernels (scripts/bench_sort_variants.py) and bench.py's
    corpus/config policy — editing a measured kernel must invalidate its
    rows.  utils/ stays OUTSIDE: ledger changes do not alter what a
    measurement means, and including them would invalidate same-code
    evidence on every instrumentation commit.  Paths hashed relative to the repo so the
    fingerprint is machine-portable."""
    global _CODE_FP
    if _CODE_FP is None:
        import hashlib

        pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        repo = os.path.dirname(pkg)
        files: list[str] = []
        for d in ("core", "ops", "parallel", "io"):
            for root, _, names in os.walk(os.path.join(pkg, d)):
                files.extend(
                    os.path.join(root, n) for n in names if n.endswith(".py")
                )
        files.extend(
            os.path.join(pkg, n)
            for n in ("engine.py", "config.py", "backend.py")
        )
        files.extend(
            os.path.join(repo, p)
            for p in ("bench.py",
                      os.path.join("scripts", "bench_sort_variants.py"))
        )
        h = hashlib.sha1()
        for p in sorted(files):
            try:
                with open(p, "rb") as f:
                    h.update(os.path.relpath(p, repo).encode())
                    h.update(b"\0")
                    h.update(f.read())
                    h.update(b"\0")
            except OSError:
                continue
        _CODE_FP = h.hexdigest()[:12]
    return _CODE_FP


def on_tpu() -> bool:
    """True iff jax is initialized on a non-CPU backend.

    Never *triggers* backend init: a ledger call must not be the thing
    that takes the chip (locust_tpu.backend.select_backend owns that).
    """
    try:
        import jax
        from jax._src import xla_bridge

        if not xla_bridge.backends_are_initialized():
            return False
        return jax.default_backend() not in ("cpu", "interpreter")
    except Exception:  # locust: noqa[R017] any failure to introspect jax state means "not on TPU" — False IS the answer here, not an error to surface
        return False


def record(kind: str, payload: dict, force: bool = False) -> bool:
    """Append one evidence row if on TPU (or ``force``).  Returns written?"""
    if not force and not on_tpu():
        return False
    try:
        import jax

        row = {
            "ts": round(time.time(), 1),
            "kind": kind,
            "backend": jax.default_backend(),
            "device": str(jax.devices()[0].device_kind)
            if jax.devices()
            else "unknown",
            "jax": jax.__version__,
            "code": code_fingerprint(),
            **payload,
        }
    except Exception as e:  # pragma: no cover - evidence must never break a run
        row = {"ts": round(time.time(), 1), "kind": kind, "error": str(e), **payload}
    try:
        d = artifacts_dir()
        os.makedirs(d, exist_ok=True)
        line = json.dumps(row, default=str) + "\n"
        fd = os.open(
            os.path.join(d, "tpu_runs.jsonl"),
            os.O_WRONLY | os.O_CREAT | os.O_APPEND,
            0o644,
        )
        try:
            os.write(fd, line.encode())
        finally:
            os.close(fd)
        return True
    except OSError:  # pragma: no cover - best-effort by design
        return False
