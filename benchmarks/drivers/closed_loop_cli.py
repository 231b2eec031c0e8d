"""Traffic driver ``closed_loop_cli``: one client, one CLI job at a time.

A job is what a CLI user does: ``locust_tpu.cli.main(argv)`` in this
process on the corpus file, stdout captured — file bytes in, rendered
``word<TAB>count`` table out.  The next job starts when the last table is
back (a batch user waits for the result).  Each job is checked against the
oracle after its clock has stopped.

Between jobs, outside every job's clock, the driver runs Python's cycle
collector.  ``cli.main`` builds a new engine per job and the dead engine is
a reference cycle: left to the collector's own schedule, dead engines (and
the programs they keep loaded on the chip) pile up for about eleven jobs, each
job a little slower than the last, until a 0.1 s full collection inside some
job clears them — a sawtooth no CLI user sees, since a CLI process runs one
job (PERF.md, Findings, PR 23).  Collecting between jobs gives every job the
clean heap a fresh process has; ``gc.freeze()`` after warm-up keeps those
collections to the garbage of one job.

The traffic file gives ``argv`` (a template over ``{file}`` and
``{platform}``), ``check`` (yardstick.check_job's rules), ``warmup_max_jobs``
and, for a traced run, ``trace_slice`` = ``{"skip": jobs of the window
before the profiler starts, "jobs": jobs it covers}``.
"""

from __future__ import annotations

import gc
import json
import os
import time

import yardstick

ANNOTATION = "bench.job"


class Job:
    """One job of the window: its clock, its verdict, what it said."""

    def __init__(self, res: yardstick.JobResult, epoch_ns: float, verdict, spans, nbytes):
        self.t_start, self.t_end = res.t_start, res.t_end
        self.seconds = res.seconds
        self.epoch_ns = epoch_ns      # time.time() at the job's start, ns
        self.verdict = verdict        # None = kept the guarantee
        self.stderr = res.stderr
        self.spans = spans            # [(name, start epoch ns, end epoch ns)]
        self.bytes = nbytes


def _argv(env, extra=()) -> list[str]:
    subst = {"file": env.corpus_path, "platform": env.platform}
    return [a.format(**subst) for a in env.traffic["argv"]] + list(env.extra_argv) + list(extra)


def _read_spans(path: str):
    """The program's own spans of one job, from the CLI's ``--trace-out``
    Chrome trace (``ts``/``dur`` in epoch microseconds)."""
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return []
    finally:
        if os.path.exists(path):
            os.unlink(path)
    return [(e["name"], e["ts"] * 1e3, (e["ts"] + e["dur"]) * 1e3)
            for e in doc.get("traceEvents", []) if e.get("ph") == "X"]


def one_job(env, index: int, traced: bool) -> Job:
    import jax.profiler

    extra, span_file = (), None
    if traced:
        span_file = os.path.join(env.workdir, f"spans_{index}.json")
        extra = ("--trace-out", span_file)
    argv = _argv(env, extra)
    with jax.profiler.TraceAnnotation(ANNOTATION):
        epoch_ns = time.time() * 1e9
        res = yardstick.run_cli(env.cli_main, argv)
    verdict = yardstick.check_job(res, env.expect, env.traffic.get("check", {}), env.platform)
    spans = _read_spans(span_file) if span_file else []
    gc.collect()
    return Job(res, epoch_ns, verdict, spans, env.corpus_bytes)


def warm_up(env) -> list[Job]:
    """Jobs until one compiles nothing (every compile request a cache hit), at most
    ``warmup_max_jobs``: every program the window drives is then in memory
    or in the cache."""
    jobs = []
    for i in range(int(env.traffic.get("warmup_max_jobs", 3))):
        before = env.monitor.compiles()
        job = one_job(env, -1 - i, traced=False)
        jobs.append(job)
        missed = env.monitor.compiles() - before
        env.say(f"warm-up job {i + 1}: {job.seconds:.3f} s, compiled {missed}, "
                f"verdict {job.verdict or 'equal to the oracle'}")
        if missed == 0 and i >= int(env.traffic.get("warmup_min_jobs", 1)) - 1:
            break
    gc.freeze()
    return jobs


def measure(env, seconds: float, traced: bool):
    """The measured window: jobs start while the clock is under ``seconds``;
    the window closes when the last of them returns.  Returns
    ``(jobs, slice)`` where ``slice`` is ``(profile directory, first job
    index, job count)`` of the profiled part, or None."""
    import jax.profiler

    sl = env.traffic.get("trace_slice", {"skip": 1, "jobs": 2})
    jobs: list[Job] = []
    profiling, slice_info = False, None
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        i = len(jobs)
        if traced and i == sl["skip"] and slice_info is None:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(env.profile_dir, profiler_options=opts)
            profiling = True
        jobs.append(one_job(env, i, traced))
        if profiling and i + 1 == sl["skip"] + sl["jobs"]:
            jax.profiler.stop_trace()
            profiling = False
            slice_info = (env.profile_dir, sl["skip"], sl["jobs"])
    if profiling:  # the window closed inside the slice: keep what it covered
        jax.profiler.stop_trace()
        slice_info = (env.profile_dir, sl["skip"], len(jobs) - sl["skip"])
    return jobs, slice_info
