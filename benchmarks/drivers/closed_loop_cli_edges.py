"""Traffic driver ``closed_loop_cli_edges``: ``closed_loop_cli`` on an edge
list, with the job's result — every node's rank — read from its stdout and
held to a float64 reference within the configuration's tolerance.

``run.py``'s set-up draws every corpus with ``yardstick.build_corpus``
(shuffles of the configuration's ``text``).  A PageRank configuration
keeps a SMALL placeholder there (``sizes.corpus_lines``) and names its
generator:

    "generator": {"module": "rmat_edges"}

and this driver, before the first job, writes ``sizes.edges`` edges with
``module.build(path, edges, seed)`` BESIDE the placeholder (in a memory
file, ``_edges_file``; ``edges.txt`` of the work directory where the platform
has none) and keeps ``module.oracle`` of them — float64 ranks after the
configuration's ``algorithm.num_iters`` rounds at its ``algorithm.damping``
— in memory.  The traffic file's ``argv`` is a template over ``{file}``
(the edge list) and ``{platform}``.

A job is ``locust_tpu.cli.main(argv)`` in this process, as for every cell:
edge-list file in, ``id<TAB>rank`` lines out on stdout.  After each job,
outside its clock and inside the window, the check (``check_job``): stdout
parsed in numpy — exactly N lines, the ids 0 .. N-1 in order, every rank
finite; every rank within ``tolerance.rank_rel`` (relative) of the
reference's and their sum within ``tolerance.sum_abs`` of 1; nothing in
stderr about dropped, truncated or demoted work
(``yardstick.BAD_STDERR``); the CLI's device line naming the platform.

The ROUNDS are held apart, because the table cannot hold them: on the
configuration's graph the ranks stop moving, as far as a float32 shows,
at round 13 of 20 (``rmat_edges.build_probe`` says why), so a program
that ran 19 rounds, or 14, prints a table within every limit.  Two things
hold them.  ``probe_verdict``: the configuration's ``probe`` — a small
R-MAT graph with chains, one shape for every seed, on which a round short stands 2e-2 off — goes
through the same ``cli.main`` under the job's own argv but the file, and
is held to its own float64 ranks by the same ``compare`` and tolerance;
once for every argv tail (the cell's own in set-up, under the first
warm-up job; ``control_argv`` when ``control.py`` adds it), its verdict
every later job's of that tail.  And ``rounds_verdict``: a TRACED job's
``--trace-out`` file must hold the counter ``pagerank.iterations`` at the
configuration's ``algorithm.num_iters`` — what the program says it ran at
the cell's own size, where a loop that stopped when the ranks stood still
would pass the probe.  An untraced job records no counter (telemetry is
off), so there the probe stands alone.

The seed is ``--seed`` in a run of ``run.py``.  ``control.py`` draws a new
placeholder per seed and does not pass the seed on, so there the edges are
seeded by the placeholder's CRC-32, as ``closed_loop_cli_generated`` does.
A program that cannot run the configuration fails in set-up: a warm-up job
that does not keep the guarantee ends the run with exit code 4 and no
result line.
"""

from __future__ import annotations

import gc
import importlib
import json
import os
import time
import warnings
import zlib

import numpy as np

import yardstick
from drivers import closed_loop_cli
from drivers.closed_loop_cli_generated import _stamp


def _edges_file(env) -> str:
    """Where the edge list lies: an anonymous memory file where the platform
    has one (``os.memfd_create``, kept open on ``env`` for the life of the
    run and handed to the CLI as ``/proc/self/fd/N``, a path like any
    other), else ``edges.txt`` in the work directory.  The chip's machines
    keep their temporary directory on a 9p mount of a sandboxed kernel,
    where a read or a write now and then stalls for a second or more
    (PERF.md section 6, PRs 39 and 41: one job of a window 1.4 s longer in
    two runs of twelve with the file there); a deployment reads its own file
    system, so the cell keeps the machine's out of the job.  The program's
    side is the same ``open`` and ``read`` either way."""
    if getattr(env, "edges_path", None):
        return env.edges_path
    try:
        fd = os.memfd_create("locust_bench_edges")
        path = f"/proc/self/fd/{fd}"
        with open(path, "wb") as f:
            f.write(b"0\t1\n")
        with open(path, "rb") as f:
            if f.read() != b"0\t1\n":
                raise OSError("a memory file reopened by its /proc path is another file")
        env.edges_memfd = fd  # stays open: the path is its only name
    except (AttributeError, OSError) as err:
        path = os.path.join(env.workdir, "edges.txt")
        env.say(f"no memory file for the edge list here ({err!r}): it is a file of the work directory")
    env.edges_path = path
    return path


def generate(env, seed: int) -> None:
    """The configuration's edge list beside the placeholder, and its ranks."""
    module = importlib.import_module(env.config["generator"]["module"])
    algorithm = env.config["algorithm"]
    _edges_file(env)
    t0 = time.perf_counter()
    env.corpus_bytes = module.build(env.edges_path, env.sizes["edges"], seed)
    t1 = time.perf_counter()
    env.expect_ranks = module.oracle(
        module.load(env.edges_path), algorithm["num_iters"], algorithm["damping"])
    probe = env.config["probe"]
    env.probe_path = os.path.join(env.workdir, "probe.txt")
    module.build_probe(env.probe_path, seed, **{k: v for k, v in probe.items() if k != "why"})
    env.probe_ranks = module.oracle(
        module.load(env.probe_path), algorithm["num_iters"], algorithm["damping"])
    env.probe_verdicts = {}
    env.placeholder = _stamp(env.corpus_path)
    env.say(f"generated edges ({module.__name__}, seed {seed}) at {env.edges_path}: {env.corpus_bytes} bytes, "
            f"{env.sizes['edges']} edges over {env.expect_ranks.size} ids in {t1 - t0:.2f} s; "
            f"oracle ({algorithm['num_iters']} rounds, float64) in "
            f"{time.perf_counter() - t1:.2f} s (both set-up)")


def compare(table: bytes, want: np.ndarray, tolerance: dict):
    """``(verdict, worst relative error, sum)`` of a printed rank table
    against the reference's ranks: the verdict None if it holds."""
    n = want.size
    if table.count(b"\n") != n or not table.endswith(b"\n"):
        return f"printed {table.count(b'\n')} lines, the graph has {n} ids", None, None
    with warnings.catch_warnings():
        # numpy stops at text it cannot read and warns: behind the last
        # number the size would not say so.
        warnings.simplefilter("error", DeprecationWarning)
        try:
            values = np.fromstring(table, dtype=np.float64, sep=" ")
        except DeprecationWarning:
            return "the table does not hold numbers alone", None, None
    if values.size != 2 * n:
        return f"the table holds {values.size} numbers, not an id and a rank a line", None, None
    ids, ranks = values[0::2], values[1::2]
    if not np.array_equal(ids, np.arange(n)):
        return "the ids are not 0 .. N-1 in order", None, None
    if not np.isfinite(ranks).all():
        return "a rank is not finite", None, None
    off = np.abs(ranks - want) / want
    worst, total = float(off.max()), float(ranks.sum())
    if worst > tolerance["rank_rel"]:
        at = int(off.argmax())
        return (f"the ranks differ from the reference: node {at} printed {ranks[at]:.9e}, "
                f"the reference has {want[at]:.9e}, relative error {worst:.3e} > "
                f"{tolerance['rank_rel']:.1e}"), worst, total
    if abs(total - 1.0) > tolerance["sum_abs"]:
        return (f"the ranks sum to {total!r}, further than {tolerance['sum_abs']:.1e} "
                f"from 1"), worst, total
    return None, worst, total


def check_job(env, res: yardstick.JobResult) -> str | None:
    """None if the job kept the guarantee, else one line saying what broke."""
    if res.error is not None:
        return f"raised {res.error}"
    if res.rc != 0:
        return f"returned {res.rc}"
    verdict, worst, total = compare(res.stdout, env.expect_ranks, env.config["tolerance"])
    if worst is not None:
        env.compared = getattr(env, "compared", []) + [(worst, total)]
    if verdict is not None:
        return verdict
    bad = yardstick.BAD_STDERR.search(res.stderr)
    if bad:
        return f"the CLI reported {bad.group(0)!r}: lost or demoted work"
    if f"[locust] backend: {env.platform} " not in res.stderr:
        return f"the CLI's device line does not name {env.platform}"
    return None


def probe_verdict(env) -> str | None:
    """None if the program, under the argv tail in force, ranks the probe
    graph within the tolerance; run once a tail, outside every job's clock."""
    tail = tuple(env.extra_argv)
    if tail not in env.probe_verdicts:
        subst = {"file": env.probe_path, "platform": env.platform}
        argv = [a.format(**subst) for a in env.traffic["argv"]] + list(tail)
        t0 = time.perf_counter()
        res = yardstick.run_cli(env.cli_main, argv)
        if res.error is not None or res.rc != 0:
            verdict = f"raised {res.error}" if res.error is not None else f"returned {res.rc}"
        else:
            verdict, worst, _ = compare(res.stdout, env.probe_ranks, env.config["tolerance"])
        env.say(f"probe ({env.probe_ranks.size} ids, argv tail {list(tail)}): "
                f"{time.perf_counter() - t0:.3f} s, "
                f"{verdict or f'within the tolerance (worst relative error {worst:.3e})'}")
        env.probe_verdicts[tail] = verdict and (
            f"on the probe graph, where a round shows: {verdict}")
    return env.probe_verdicts[tail]


def rounds_verdict(env, span_file: str) -> str | None:
    """None if the traced job's counters say it ran the configuration's rounds."""
    want = env.config["algorithm"]["num_iters"]
    try:
        with open(span_file) as f:
            ran = json.load(f)["otherData"]["metrics"]["counters"]["pagerank.iterations"]
    except (OSError, ValueError, KeyError):
        return "the job's trace holds no counter pagerank.iterations"
    if ran != want:
        return f"the job counted {ran} rounds (pagerank.iterations), the configuration has {want}"
    return None


def one_job(env, index: int, traced: bool) -> closed_loop_cli.Job:
    import jax.profiler

    if getattr(env, "placeholder", None) != _stamp(env.corpus_path):
        with open(env.corpus_path, "rb") as f:  # control.py drew a new placeholder
            generate(env, zlib.crc32(f.read()))
    extra, span_file = [], None
    if traced:
        span_file = os.path.join(env.workdir, f"spans_{index}.json")
        extra = ["--trace-out", span_file]
    subst = {"file": env.edges_path, "platform": env.platform}
    argv = [a.format(**subst) for a in env.traffic["argv"]] + list(env.extra_argv) + extra
    with jax.profiler.TraceAnnotation(closed_loop_cli.ANNOTATION):
        epoch_ns = time.time() * 1e9
        res = yardstick.run_cli(env.cli_main, argv)
    t0 = time.perf_counter()
    verdict = (check_job(env, res) or probe_verdict(env)
               or (rounds_verdict(env, span_file) if span_file else None))
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
    env.check_s, env.compared = [], []
    try:
        return closed_loop_cli.measure(env, seconds, traced)
    finally:
        closed_loop_cli.one_job = theirs
        checks = sorted(env.check_s) or [0.0]
        env.say(f"check seconds between jobs (outside every job's clock, inside the window): "
                f"min {checks[0]:.3f}, median {checks[len(checks) // 2]:.3f}, max {checks[-1]:.3f}")
        if env.compared:
            tolerance = env.config["tolerance"]
            env.say(f"compared: worst relative error of a rank over the window's jobs "
                    f"{max(w for w, _ in env.compared):.3e} (limit {tolerance['rank_rel']:.1e}), "
                    f"worst |sum - 1| {max(abs(t - 1.0) for _, t in env.compared):.3e} "
                    f"(limit {tolerance['sum_abs']:.1e})")


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
                f"{env.check_s[-1]:.3f} s, verdict {job.verdict or 'within the tolerance'}")
        if job.verdict is not None:
            env.say(f"the program cannot run configuration {env.cell['config']}: a warm-up "
                    f"job did not keep its guarantee ({job.verdict}); no window, no result line")
            raise SystemExit(4)
        if missed == 0 and i >= int(env.traffic.get("warmup_min_jobs", 1)) - 1:
            break
    gc.freeze()
    return jobs
