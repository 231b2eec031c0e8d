"""Traffic driver ``closed_loop_cli_join``: ``closed_loop_cli`` on TWO
tables whose job is their JOIN — ``python -m locust_tpu join RANKINGS
USERVISITS`` (HiBench ``sql/join``) — with the job's result, one
``sourceIP<TAB>avgPageRank<TAB>totalRevenue`` line a sourceIP on stdout,
held to the query computed the plain way from the same two files.

``run.py``'s set-up draws every corpus with ``yardstick.build_corpus``
(shuffles of the configuration's ``text``) and builds its WordCount oracle;
a join configuration keeps that placeholder small and names its generator:

    "generator": {"module": "visits_tables", ...the keyword arguments of its build()}

This driver, before the first job, writes both tables with
``module.build(rankings, uservisits, seed, **arguments)`` BESIDE the
placeholder — in two memory files, as ``closed_loop_cli_edges`` keeps its
edge list and for its reason (the machines' temporary directory is a 9p
mount that now and then stalls a read for a second;
``closed_loop_cli_edges._edges_file``) — and keeps
``join_oracle.oracle(rankings, uservisits, date_from, date_to)``: the rows
the CLI must print, and the DATA's counts (lines, bytes, passed, matched,
groups, pages visited) that ``readers/roofline_join_job.py`` prices.  The
traffic file's ``argv`` is a template over ``{rankings}``, ``{uservisits}``
and ``{platform}``; a job's bytes are the two files' together.

A job is ``locust_tpu.cli.main(argv)`` in this process, as for every cell.
After each job, outside its clock and inside the window, the check
(``check_job``): exit 0; stdout parsed (``join_oracle.compare``) — the
sourceIPs equal to the oracle's as a SET, each one's two numbers within the
configuration's ``tolerance.relative`` of the oracle's float64, the printed
order non-increasing in the printed total; nothing in stderr about cut,
dropped or demoted work (``yardstick.BAD_STDERR``: the join CLI spells its
cuts ``line_overflow=``, ``key_overflow=`` and, with the rows that do not
parse, a ``[locust] WARN`` line); the result line there at all, with
``malformed=0`` (the traffic file's ``check.stderr_must_match``); the CLI's
device line naming the platform.

The seed is ``--seed`` in a run of ``run.py``.  ``control.py`` draws a new
placeholder per seed and does not pass the seed on, so there the tables are
seeded by the placeholder's CRC-32, as ``closed_loop_cli_generated`` does.
A program that cannot run the configuration fails in set-up: a warm-up job
that does not keep the guarantee ends the run with exit code 4 and no
result line (a program without the ``join`` command takes the word for a
file name and exits 2 in its argument parser).
"""

from __future__ import annotations

import gc
import importlib
import os
import re
import time
import zlib

import join_oracle
import yardstick
from drivers import closed_loop_cli
from drivers.closed_loop_cli_generated import _stamp


def _memory_file(env, name: str) -> str:
    """A path for one table: an anonymous memory file where the platform
    has one (kept open on ``env`` for the life of the run, handed to the
    CLI as ``/proc/self/fd/N``), else a file of the work directory —
    ``closed_loop_cli_edges._edges_file``'s reason, for two files."""
    held = env.__dict__.setdefault("table_paths", {})
    if name in held:
        return held[name]
    try:
        fd = os.memfd_create("locust_bench_" + name)
        path = f"/proc/self/fd/{fd}"
        with open(path, "wb") as f:
            f.write(b"a,1\n")
        with open(path, "rb") as f:
            if f.read() != b"a,1\n":
                raise OSError("a memory file reopened by its /proc path is another file")
        env.__dict__.setdefault("table_memfds", []).append(fd)  # stays open
    except (AttributeError, OSError) as err:
        path = os.path.join(env.workdir, name + ".txt")
        env.say(f"no memory file for {name} here ({err!r}): it is a file of the work directory")
    held[name] = path
    return path


def generate(env, seed: int) -> None:
    """The configuration's two tables beside the placeholder, and the query's rows."""
    spec = dict(env.config["generator"])
    module = importlib.import_module(spec.pop("module"))
    spec.update(pages=env.sizes["pages"], visits=env.sizes["visits"])
    env.rankings_path = _memory_file(env, "rankings")
    env.uservisits_path = _memory_file(env, "uservisits")
    query = env.config["query"]
    t0 = time.perf_counter()
    sizes = module.build(env.rankings_path, env.uservisits_path, seed, **spec)
    t1 = time.perf_counter()
    env.oracle = join_oracle.oracle(env.rankings_path, env.uservisits_path,
                                    query["date_from"], query["date_to"])
    env.corpus_bytes = sum(sizes)
    env.placeholder = _stamp(env.corpus_path)
    counts = env.oracle.counts()
    env.say(f"generated tables ({module.__name__}, seed {seed}): Rankings {sizes[0]} bytes, "
            f"{counts['pages']} lines at {env.rankings_path}; UserVisits {sizes[1]} bytes, "
            f"{counts['visits']} lines at {env.uservisits_path}, in {t1 - t0:.2f} s; oracle "
            f"({query['date_from']} .. {query['date_to']}: {counts['passed']} visits passed, "
            f"{counts['matched']} matched {counts['pages_visited']} pages, {counts['groups']} "
            f"sourceIPs, the largest of {counts['largest_group']} visits, "
            f"{counts['malformed']} malformed) in {time.perf_counter() - t1:.2f} s (both set-up)")


def check_job(env, res: yardstick.JobResult) -> str | None:
    """None if the job kept the guarantee, else one line saying what broke."""
    if res.error is not None:
        return f"raised {res.error}"
    if res.rc != 0:
        return f"returned {res.rc}"
    verdict, worst = join_oracle.compare(res.stdout, env.oracle, env.config["tolerance"])
    if worst is not None:
        env.compared = getattr(env, "compared", []) + [worst]
    if verdict is not None:
        return verdict
    bad = yardstick.BAD_STDERR.search(res.stderr)
    if bad:
        return f"the CLI reported {bad.group(0)!r}: lost or demoted work"
    if f"[locust] backend: {env.platform} " not in res.stderr:
        return f"the CLI's device line does not name {env.platform}"
    for rule in env.traffic.get("check", {}).get("stderr_must_match", []):
        if not re.search(rule, res.stderr):
            return f"stderr lacks /{rule}/"
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
    subst = {"rankings": env.rankings_path, "uservisits": env.uservisits_path,
             "platform": env.platform}
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
    env.check_s, env.compared = [], []
    try:
        return closed_loop_cli.measure(env, seconds, traced)
    finally:
        closed_loop_cli.one_job = theirs
        checks = sorted(env.check_s) or [0.0]
        env.say(f"check seconds between jobs (outside every job's clock, inside the window): "
                f"min {checks[0]:.3f}, median {checks[len(checks) // 2]:.3f}, max {checks[-1]:.3f}")
        if env.compared:
            env.say(f"compared: worst relative error of a printed number over the window's "
                    f"jobs {max(env.compared):.3e} (limit "
                    f"{env.config['tolerance']['relative']:.1e})")


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
