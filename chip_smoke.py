#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, which holds the chip from start to end and starts no child
that needs it.  It drives WordCount through the program's normal entry
point in-process (``locust_tpu.cli.main([... "--backend", "tpu"])``) on a
seeded 32 MiB corpus, and holds every result to an oracle:

  default (one chip)   wordcount -> stream -> kernels
  --chips 4            the ``--mesh`` WordCount over all four devices, and
                       nothing else

Every number printed is the WALL-CLOCK OF A SMOKE RUN (compilation
included where the line says so) — not a benchmark.

Exit status: 0 and a last stdout line
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``
only if jax initialized a TPU whose kind benchmarks/peaks.json lists, every
phase ran, and every comparison was equal.  Anything else — no
accelerator, a phase raising, a table differing by one byte, a CLI
warning about dropped tokens — exits non-zero WITHOUT that line; no
phase's failure is caught while the run goes on.
"""

from __future__ import annotations

import argparse
import collections
import contextlib
import io
import json
import logging
import os
import re
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from locust_tpu.config import (  # noqa: E402 - jax-free
    FULL_DELIMITERS,
    EngineConfig,
    compile_cache_dir,
    default_sort_mode,
)

# The Pallas kernels the chip's compiler accepts at the published widths
# (tests/test_chip_compile.py compiles each for a described v5e).  A
# literal: a kernel the compiler refuses is REMOVED here with its message
# recorded in ROADMAP.md, never skipped at run time.
CHIP_KERNELS = ("tokenize_block_pallas", "fused_block_preagg")

SAMPLE_CORPUS = os.path.join(HERE, "data", "sample_corpus.txt")
# The one table of chips the repo keeps, keyed by device_kind (read only).
PEAKS = os.path.join(HERE, "benchmarks", "peaks.json")
CORPUS_BYTES = 32 << 20          # ROADMAP's wc-sample-32MB shape
FUSED_CLI_BYTES = 4 << 20        # the `--sort-mode fused` CLI run's prefix
KERNEL_BLOCK_LINES = 32768       # one real block, the kernels phase
# The chip check allows a cold run 1200 s, nearly all of it compilation
# (measured cold on a v5e, PR 22: 871 s for the whole default run, the
# last 128 s of it the `--sort-mode fused` CLI run).  Every phase always
# runs: a host too slow for that fails at the limit, loudly.

_SPLIT = re.compile(b"[" + re.escape(FULL_DELIMITERS) + b"]+")
# Anything the CLI says about lost or re-routed work fails the phase.
_BAD_STDERR = re.compile(
    r"\[locust\] WARN|Exceeded emit limit|exceeded table capacity|"
    r"overflow=[1-9]|truncated=True|not engaged|demot"
)


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


# ------------------------------------------------------------ corpus + oracle


def build_corpus(path: str, nbytes: int, seed: int) -> int:
    """Write ~``nbytes`` of text by drawing whole lines of the tracked
    sample corpus with ``numpy.random.default_rng(seed)``; returns the
    line count.  Every line fits line_width=128 / emits_per_line=20 /
    key_width=32, so the CLI's result is exact."""
    import numpy as np

    with open(SAMPLE_CORPUS, "rb") as f:
        lines = [ln for ln in f.read().split(b"\n") if ln]
    lens = np.array([len(ln) + 1 for ln in lines])
    rng = np.random.default_rng(seed)
    draw = rng.integers(0, len(lines), size=int(nbytes // lens.mean()) + 1)
    keep = int(np.searchsorted(np.cumsum(lens[draw]), nbytes)) + 1
    draw = draw[:keep]
    with open(path, "wb") as f:
        f.write(b"".join(lines[i] + b"\n" for i in draw))
    return len(draw)


def oracle_table(path: str) -> bytes:
    """The ``word<TAB>count`` table the CLI must print, from
    ``collections.Counter`` over the file split on config.FULL_DELIMITERS
    (strtok semantics: delimiters collapse, empties drop), key-sorted."""
    with open(path, "rb") as f:
        counts = collections.Counter(t for t in _SPLIT.split(f.read()) if t)
    return b"".join(
        k + b"\t" + str(v).encode() + b"\n" for k, v in sorted(counts.items())
    )


# ------------------------------------------------------------------ CLI runs


class _Tee(io.TextIOBase):
    """stderr that is both shown and kept (the CLI's report is evidence)."""

    def __init__(self, real):
        self.real, self.kept = real, io.StringIO()

    def write(self, s):
        self.real.write(s)
        return self.kept.write(s)

    def flush(self):
        self.real.flush()


def run_cli(argv: list[str]) -> tuple[bytes, str, float]:
    """``python -m locust_tpu``'s ``main(argv)`` in THIS process.  Returns
    (stdout bytes, stderr text + locust_tpu log records, wall seconds);
    raises unless it returned 0."""
    from locust_tpu.cli import main

    out = io.TextIOWrapper(io.BytesIO(), encoding="utf-8", write_through=True)
    err = _Tee(sys.stderr)
    handler = logging.StreamHandler(err)
    log = logging.getLogger("locust_tpu")
    old_level = log.level
    log.addHandler(handler)
    log.setLevel(logging.INFO)
    t0 = time.perf_counter()
    try:
        with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
            rc = main(argv)
    finally:
        log.removeHandler(handler)
        log.setLevel(old_level)
    wall = time.perf_counter() - t0
    if rc != 0:
        raise RuntimeError(f"locust_tpu.cli.main({argv}) returned {rc}")
    return out.buffer.getvalue(), err.kept.getvalue(), wall


def check_cli(name: str, got: bytes, stderr: str, expect: bytes,
              backend: str) -> None:
    if got != expect:
        raise AssertionError(
            f"{name}: CLI table differs from the Counter oracle "
            f"({len(got)} vs {len(expect)} bytes)"
        )
    bad = _BAD_STDERR.search(stderr)
    if bad:
        raise AssertionError(
            f"{name}: the CLI reported {bad.group(0)!r} — lost or demoted work"
        )
    if f"[locust] backend: {backend} " not in stderr:
        raise AssertionError(f"{name}: CLI device line does not name {backend}")
    import jax

    if jax.default_backend() != backend:
        raise AssertionError(
            f"{name}: jax.default_backend()={jax.default_backend()!r} after "
            f"the run, wanted {backend!r}"
        )


def phase_wordcount(path: str, expect: bytes, backend: str = "tpu",
                    extra: tuple = ()) -> None:
    """The CLI with its own defaults, cold then warm in this process.
    (``extra`` CLI arguments are the CPU tests' way to a small block.)"""
    mode = default_sort_mode(backend)
    for label in ("first (compilation included)", "second (same process)"):
        got, err, wall = run_cli([path, "--backend", backend, *extra])
        check_cli("wordcount", got, err, expect, backend)
        say(f"wordcount sort_mode={mode} {label}: wall {wall:.2f} s, "
            f"{expect.count(10)} distinct, table equal to the oracle")


def phase_stream(path: str, expect: bytes, backend: str = "tpu",
                 extra: tuple = ()) -> None:
    """The bounded-memory path (``--stream``): its own per-block program,
    so on a cold cache this compiles a second multi-minute executable."""
    got, err, wall = run_cli([path, "--stream", "--backend", backend, *extra])
    check_cli("stream", got, err, expect, backend)
    say(f"stream (compiles its own fold program when cold): wall "
        f"{wall:.2f} s, table equal to the oracle")


def phase_mesh(path: str, expect: bytes, backend: str = "tpu",
               n_dev: int = 4, extra: tuple = ()) -> None:
    """``--mesh`` WordCount over every device: n_dev shards, each holding
    keys, nothing truncated or overflowed — and on a TPU every device
    must have held memory (a mesh that lands on the first chip fails)."""
    import jax

    got, err, wall = run_cli([path, "--mesh", "--backend", backend, *extra])
    check_cli("mesh", got, err, expect, backend)
    shards = [int(n) for n in re.findall(r"shard \d+: (\d+) keys", err)]
    if len(shards) != n_dev or min(shards) == 0:
        raise AssertionError(f"mesh: wanted {n_dev} non-empty shards, "
                             f"CLI reported {shards}")
    stats = re.search(r"distinct=\d+ drain_rounds=\d+ emit_overflow=0 "
                      r"shuffle_overflow=0 truncated=False", err)
    if not stats:
        raise AssertionError("mesh: CLI stats line missing or not clean")
    peaks = [
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in jax.devices()[:n_dev]
    ]
    if backend == "tpu" and min(peaks) == 0:
        raise AssertionError(f"mesh: a device never held memory: {peaks}")
    say(f"mesh {n_dev} devices: wall {wall:.2f} s (compilation included), "
        f"keys/shard {shards}, peak_bytes_in_use/device {peaks}, "
        f"{stats.group(0)}, table equal to the oracle")


# ------------------------------------------------------------ kernels phase


def _first_block(path: str, cfg: EngineConfig):
    """The corpus's first ``cfg.block_lines`` lines as padded uint8 rows."""
    from locust_tpu.io import loader

    rows = loader.load_rows(path, cfg.line_width, 0, cfg.block_lines)
    if rows.shape[0] != cfg.block_lines:
        raise AssertionError(f"corpus shorter than one {cfg.block_lines}-line block")
    return rows


def _equal(name: str, got, want) -> None:
    import numpy as np

    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape or got.dtype != want.dtype or not (got == want).all():
        raise AssertionError(f"kernels: {name} differs from its XLA formulation")


def kernel_tokenize(rows, cfg) -> None:
    import jax

    from locust_tpu.ops.map_stage import tokenize_block
    from locust_tpu.ops.pallas.tokenize import tokenize_block_pallas

    keys, valid, ovf = tokenize_block_pallas(rows, cfg, interpret=False)
    ref = jax.jit(tokenize_block, static_argnums=1)(rows, cfg)
    _equal("tokenize keys", keys, ref.keys)
    _equal("tokenize valid", valid, ref.valid)
    _equal("tokenize overflow", ovf, ref.overflow)


def kernel_fused(rows, cfg) -> None:
    """Kernel table + residual settled through the UNCHANGED hasht fold vs
    the hasht fold of the block's raw emits: bit-identical tables.  The
    kernel's rows are padded (invalid) up to the raw emit count so both
    settle through ONE compiled fold."""
    import jax
    import jax.numpy as jnp

    from locust_tpu.core.kv import KVBatch
    from locust_tpu.ops.hash_table import fold_into
    from locust_tpu.ops.map_stage import wordcount_map
    from locust_tpu.ops.pallas.fused_fold import fused_block_preagg

    tsize = cfg.resolved_table_size

    @jax.jit
    def settle(batch: KVBatch):
        acc = KVBatch.empty(tsize, cfg.key_lanes)
        return fold_into(acc, batch, tsize, "sum", "hasht")

    tab, resid, ovf, bad = fused_block_preagg(rows, cfg, interpret=False)
    if bool(bad):
        raise AssertionError("kernels: fused residual buffer overflowed")
    raw, raw_ovf = jax.jit(wordcount_map, static_argnums=1)(rows, cfg)
    pre = KVBatch.concat(tab, resid)
    if pre.size > raw.size:
        raise AssertionError("kernels: block too small for the shared fold")
    pre = KVBatch.concat(pre, KVBatch.empty(raw.size - pre.size, cfg.key_lanes))
    got, got_n = settle(pre)
    want, want_n = settle(raw)
    _equal("fused overflow", ovf, raw_ovf)
    _equal("fused distinct", got_n, want_n)
    _equal("fused table valid", got.valid, want.valid)
    _equal("fused table keys",
           jnp.where(got.valid[:, None], got.key_lanes, 0),
           jnp.where(want.valid[:, None], want.key_lanes, 0))
    _equal("fused table values",
           jnp.where(got.valid, got.values, 0),
           jnp.where(want.valid, want.values, 0))


_KERNEL_CHECKS = {
    "tokenize_block_pallas": kernel_tokenize,
    "fused_block_preagg": kernel_fused,
}


def phase_kernels(path: str, tmpdir: str, backend: str = "tpu") -> None:
    """Every CHIP_KERNELS entry compiled by Mosaic (interpret=False) on one
    real 32768-line block, bit for bit against its XLA formulation; then
    ``--sort-mode fused`` through the CLI on the corpus's first 4 MiB
    against the oracle."""
    import jax.numpy as jnp

    cfg = EngineConfig(block_lines=KERNEL_BLOCK_LINES, line_width=128,
                       emits_per_line=20, key_width=32, sort_mode="hasht")
    rows = jnp.asarray(_first_block(path, cfg))
    for name in CHIP_KERNELS:
        t0 = time.perf_counter()
        _KERNEL_CHECKS[name](rows, cfg)
        say(f"kernels {name} interpret=False, block_lines="
            f"{KERNEL_BLOCK_LINES}: wall {time.perf_counter() - t0:.2f} s "
            "(compilation included), bit-identical to XLA")
    if "fused_block_preagg" not in CHIP_KERNELS:
        say("kernels --sort-mode fused CLI run: left out "
            "(fused_block_preagg is not in CHIP_KERNELS)")
        return
    head = os.path.join(tmpdir, "head.txt")
    with open(path, "rb") as f, open(head, "wb") as g:
        data = f.read(FUSED_CLI_BYTES)
        g.write(data[: data.rfind(b"\n") + 1])
    # --no-timing: the timed stage report runs map/process/reduce as
    # separate programs and never reaches the kernel; the one-dispatch
    # run is where `fused` engages (engine.fold_block).
    got, err, wall = run_cli(
        [head, "--sort-mode", "fused", "--no-timing", "--backend", backend]
    )
    check_cli("kernels fused CLI", got, err, oracle_table(head), backend)
    say(f"kernels --sort-mode fused CLI on {os.path.getsize(head)} bytes: "
        f"wall {wall:.2f} s (compilation included), kernel engaged, table "
        "equal to the oracle")


# ----------------------------------------------------------------------- main


def result_line(dev: dict) -> str:
    """The contract's last line, exactly these keys and nothing more."""
    return json.dumps({
        "ok": True,
        "device": {
            "platform": dev["platform"],
            "kind": dev["kind"],
            "count": dev["count"],
        },
    })


class _CacheCounter:
    """Counts jax's persistent-cache hits/misses (public jax.monitoring
    events) so each phase can say compiled-or-cache-hit."""

    def __init__(self):
        import jax.monitoring

        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def take(self) -> str:
        h, m = self.hits, self.misses
        self.hits = self.misses = 0
        return f"persistent cache: {h} hit(s), {m} compiled"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0, help="corpus seed")
    ap.add_argument("--chips", type=int, choices=[1, 4], default=1,
                    help="4 = ONLY the --mesh phase, on four chips")
    args = ap.parse_args(argv)

    cache = compile_cache_dir()  # before the first `import jax`
    from locust_tpu.backend import device_summary, select_backend

    try:
        select_backend("tpu")
    except RuntimeError as e:
        print(f"chip_smoke: error: {e}", file=sys.stderr)
        return 2
    dev = device_summary()
    with open(PEAKS) as f:
        known = json.load(f)
    if dev["kind"] not in known:
        print(f"chip_smoke: error: device kind {dev['kind']!r} is not in "
              "benchmarks/peaks.json", file=sys.stderr)
        return 2
    if dev["count"] != args.chips:
        print(f"chip_smoke: error: --chips {args.chips} but jax sees "
              f"{dev['count']} device(s)", file=sys.stderr)
        return 2
    say(f"device: {dev}")
    n_cached = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    say(f"compile cache: {cache} ({n_cached} entries at start)")
    from locust_tpu.io import native_ingest

    say("ingest: " + (f"native ({native_ingest.so_path().name})"
                      if native_ingest.available()
                      else "io/loader's Python path (native build failed)"))
    counter = _CacheCounter()
    t_all = time.perf_counter()

    with tempfile.TemporaryDirectory(prefix="locust_smoke_") as td:
        path = os.path.join(td, "corpus.txt")
        t0 = time.perf_counter()
        n_lines = build_corpus(path, CORPUS_BYTES, args.seed)
        expect = oracle_table(path)
        say(f"corpus: {os.path.getsize(path)} bytes, {n_lines} lines, seed "
            f"{args.seed}; oracle {expect.count(10)} distinct "
            f"({time.perf_counter() - t0:.2f} s, set-up)")
        if args.chips == 4:
            phase_mesh(path, expect, "tpu", 4)
            say(f"mesh: {counter.take()}")
        else:
            phase_wordcount(path, expect, "tpu")
            say(f"wordcount: {counter.take()}")
            phase_stream(path, expect, "tpu")
            say(f"stream: {counter.take()}")
            phase_kernels(path, td, "tpu")
            say(f"kernels: {counter.take()}")
    say(f"all phases equal; total wall {time.perf_counter() - t_all:.2f} s")
    print(result_line(dev), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
