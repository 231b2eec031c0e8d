"""Traffic driver ``closed_loop_cli_index``: ``closed_loop_cli`` on a text
whose job is its INVERTED INDEX — ``python -m locust_tpu index FILE
--lines-per-doc K`` — with the job's result, every word's posting list on
stdout, held to an index built the plain way from the same file.

``run.py``'s set-up draws every corpus with ``yardstick.build_corpus``
(shuffles of the configuration's ``text``) and builds its WordCount oracle;
an index configuration keeps that placeholder and names its generator, as
``wc-zipf-100MB`` does:

    "generator": {"module": "zipf_text", ...the keyword arguments of its build()}

This driver, before the first job, writes ``sizes.corpus_lines`` lines with
``module.build(path, lines, seed, **arguments)`` BESIDE the placeholder — in
a memory file, as ``closed_loop_cli_edges`` keeps its edge list and for its
reason (the machines' temporary directory is a 9p mount that now and then
stalls a read for a second; ``closed_loop_cli_edges._edges_file``) — and
keeps ``index_oracle.oracle(path, sizes.lines_per_doc)``: the table the CLI
must print, and the DATA's counts (tokens, distinct pairs, words) that
``readers/roofline_index_job.py`` prices.  The traffic file's ``argv`` is a
template over ``{file}`` (the text) and ``{platform}``.

A job is ``locust_tpu.cli.main(argv)`` in this process, as for every cell:
text file in, ``word<TAB>d1,d2,...<LF>`` lines out on stdout.  After each
job, outside its clock and inside the window, the check
(``yardstick.check_job`` against the oracle's table): stdout byte-equal to
the oracle's rendering — every word once, in byte order, with exactly its
documents ascending; a table that differs is then read back into arrays
(``index_oracle.parse``) to say where; nothing in stderr about dropped,
truncated or demoted work (``yardstick.BAD_STDERR``: the index CLI spells
its three cuts ``emit_overflow=``, ``key_overflow=``, ``line_overflow=``);
the result line there at all (the traffic file's
``check.stderr_must_match``); the CLI's device line naming the platform.

The seed is ``--seed`` in a run of ``run.py``.  ``control.py`` draws a new
placeholder per seed and does not pass the seed on, so there the text is
seeded by the placeholder's CRC-32, as ``closed_loop_cli_generated`` does.
A program that cannot run the configuration fails in set-up: a warm-up job
that does not keep the guarantee ends the run with exit code 4 and no result
line (the program before PR 45 carried a fixed table of 163,840 pairs and
raised past it).
"""

from __future__ import annotations

import gc
import importlib
import os
import time
import zlib

import index_oracle
import yardstick
from drivers import closed_loop_cli, closed_loop_cli_edges
from drivers.closed_loop_cli_generated import _stamp


def generate(env, seed: int) -> None:
    """The configuration's text beside the placeholder, and its index."""
    spec = dict(env.config["generator"])
    module = importlib.import_module(spec.pop("module"))
    env.text_path = closed_loop_cli_edges._edges_file(env)  # a memory file, any content
    t0 = time.perf_counter()
    env.corpus_bytes = module.build(env.text_path, env.sizes["corpus_lines"], seed, **spec)
    t1 = time.perf_counter()
    env.oracle = index_oracle.oracle(env.text_path, env.sizes["lines_per_doc"])
    env.placeholder = _stamp(env.corpus_path)
    env.say(f"generated text ({module.__name__}, seed {seed}) at {env.text_path}: "
            f"{env.corpus_bytes} bytes, {env.oracle.lines} lines in {t1 - t0:.2f} s; oracle "
            f"({env.oracle.documents} documents of {env.sizes['lines_per_doc']} lines: "
            f"{env.oracle.tokens} tokens, {env.oracle.pairs} distinct (word, document) pairs, "
            f"{env.oracle.words} words, a table of {len(env.oracle.table)} bytes) in "
            f"{time.perf_counter() - t1:.2f} s (both set-up)")


def check_job(env, res: yardstick.JobResult) -> str | None:
    """None if the job kept the guarantee, else one line saying what broke."""
    verdict = yardstick.check_job(res, env.oracle.table, env.traffic.get("check", {}),
                                  env.platform)
    if verdict is not None and verdict.startswith("table differs"):
        verdict += ": " + index_oracle.first_difference(res.stdout, env.oracle.table)
    return verdict


def one_job(env, index: int, traced: bool) -> closed_loop_cli.Job:
    import jax.profiler

    if getattr(env, "placeholder", None) != _stamp(env.corpus_path):
        with open(env.corpus_path, "rb") as f:  # control.py drew a new placeholder
            generate(env, zlib.crc32(f.read()))
    extra, span_file = [], None
    if traced:
        span_file = os.path.join(env.workdir, f"spans_{index}.json")
        extra = ["--trace-out", span_file]
    subst = {"file": env.text_path, "platform": env.platform}
    argv = [a.format(**subst) for a in env.traffic["argv"]] + list(env.extra_argv) + extra
    with jax.profiler.TraceAnnotation(closed_loop_cli.ANNOTATION):
        epoch_ns = time.time() * 1e9
        res = yardstick.run_cli(env.cli_main, argv)
    t0 = time.perf_counter()
    verdict = check_job(env, res)
    env.check_s = getattr(env, "check_s", []) + [time.perf_counter() - t0]
    spans = closed_loop_cli._read_spans(span_file) if span_file else []
    gc.collect()
    return closed_loop_cli.Job(res, epoch_ns, verdict, spans, env.corpus_bytes)


def measure(env, seconds: float, traced: bool):
    """``closed_loop_cli.measure`` — the window and the trace slice of every
    closed-loop cell, its code and not a copy — with this driver's job in
    the place of its own for as long as it runs."""
    theirs = closed_loop_cli.one_job
    closed_loop_cli.one_job = one_job
    env.check_s = []
    try:
        return closed_loop_cli.measure(env, seconds, traced)
    finally:
        closed_loop_cli.one_job = theirs
        checks = sorted(env.check_s) or [0.0]
        env.say(f"check seconds between jobs (outside every job's clock, inside the window): "
                f"min {checks[0]:.3f}, median {checks[len(checks) // 2]:.3f}, max {checks[-1]:.3f}")


def warm_up(env):
    """``closed_loop_cli.warm_up`` with this driver's job."""
    generate(env, env.seed)
    jobs = []
    for i in range(int(env.traffic.get("warmup_max_jobs", 3))):
        before = env.monitor.compiles()
        job = one_job(env, -1 - i, traced=False)
        jobs.append(job)
        missed = env.monitor.compiles() - before
        env.say(f"warm-up job {i + 1}: {job.seconds:.3f} s, compiled {missed}, check "
                f"{env.check_s[-1]:.3f} s, verdict {job.verdict or 'equal to the oracle'}")
        if job.verdict is not None:
            env.say(f"the program cannot run configuration {env.cell['config']}: a warm-up "
                    f"job did not keep its guarantee ({job.verdict}); no window, no result line")
            raise SystemExit(4)
        if missed == 0 and i >= int(env.traffic.get("warmup_min_jobs", 1)) - 1:
            break
    gc.freeze()
    return jobs
