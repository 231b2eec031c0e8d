"""Traffic driver ``closed_loop_cli_records``: ``closed_loop_cli`` on a
file of binary records, with the job's result read from the file it wrote.

``run.py``'s set-up draws every corpus with ``yardstick.build_corpus``
(shuffles of the configuration's ``text``).  A record-sort configuration
keeps a SMALL placeholder there (``sizes.corpus_lines``) and names its
generator:

    "generator": {"module": "records"}

and this driver, before the first job, writes ``sizes.records`` records
with ``module.build(path, records, seed)`` BESIDE the placeholder
(``records.bin``) and keeps ``module.oracle`` of them in memory.  The
traffic file's ``argv`` is a template over ``{file}`` (the records),
``{out}`` (where the job writes them sorted) and ``{platform}``.

A job is ``locust_tpu.cli.main(argv)`` in this process, as for every
cell: records file in, sorted records file out.  After each job, outside
its clock and inside the window: OUT byte-equal to the oracle — one pass
over two buffers, OUT read 4 MB at a time on four threads and the oracle
in memory — nothing printed to stdout, nothing in stderr about dropped,
truncated or demoted work (``yardstick.BAD_STDERR``), the CLI's device
line naming the platform.
OUT is removed before every job, so a job that wrote nothing cannot be
read as the one before it.

The seed is ``--seed`` in a run of ``run.py``.  ``control.py`` draws a new
placeholder per seed and does not pass the seed on, so there the records
are seeded by the placeholder's CRC-32, as ``closed_loop_cli_generated``
does.  A program that cannot run the configuration fails in set-up: a
warm-up job that does not keep the guarantee ends the run with exit code
4 and no result line.
"""

from __future__ import annotations

import concurrent.futures
import gc
import importlib
import os
import time
import zlib

import numpy as np

import yardstick
from drivers import closed_loop_cli
from drivers.closed_loop_cli_generated import _stamp


def generate(env, seed: int) -> None:
    """The configuration's records beside the placeholder, and their oracle."""
    module = importlib.import_module(env.config["generator"]["module"])
    env.records_path = os.path.join(env.workdir, "records.bin")
    env.out_path = os.path.join(env.workdir, "sorted.bin")
    t0 = time.perf_counter()
    env.corpus_bytes = module.build(env.records_path, env.sizes["records"], seed)
    t1 = time.perf_counter()
    env.expect_records = module.oracle(module.load(env.records_path)).reshape(-1)
    env.placeholder = _stamp(env.corpus_path)
    env.say(f"generated records ({module.__name__}, seed {seed}): {env.corpus_bytes} bytes, "
            f"{env.sizes['records']} records in {t1 - t0:.2f} s; oracle in "
            f"{time.perf_counter() - t1:.2f} s (both set-up)")


CHECK_THREADS = 4


def _equal(path: str, want: np.ndarray, chunk: int = 4 << 20) -> bool:
    """The file's bytes against ``want``'s in one pass over both, a
    quarter of the file a thread: each reads 4 MB at a time into its own
    buffer and compares (mapping the file would fault every page of a new
    800 MB mapping a job: 1.0 s on a v5e's host, where one thread reading
    takes 0.33 s; numpy and ``readinto`` release the GIL)."""
    def part(lo: int) -> bool:
        hi = min(lo + step, want.size)
        buf = np.empty(chunk, np.uint8)
        with open(path, "rb") as f:
            f.seek(lo)
            for at in range(lo, hi, chunk):
                n = min(chunk, hi - at)
                if f.readinto(memoryview(buf)[:n]) != n or not np.array_equal(
                        buf[:n], want[at:at + n]):
                    return False
        return True

    step = -(-want.size // CHECK_THREADS)
    with concurrent.futures.ThreadPoolExecutor(CHECK_THREADS) as pool:
        return all(pool.map(part, range(0, want.size, step)))


def check_job(env, res: yardstick.JobResult) -> str | None:
    """None if the job kept the guarantee, else one line saying what broke."""
    if res.error is not None:
        return f"raised {res.error}"
    if res.rc != 0:
        return f"returned {res.rc}"
    if res.stdout:
        return f"printed {len(res.stdout)} bytes to stdout"
    if not os.path.exists(env.out_path):
        return "wrote no output file"
    size = os.path.getsize(env.out_path)
    if size != env.expect_records.size:
        return (f"output holds {size} bytes, the oracle "
                f"{env.expect_records.size}: records lost or made up")
    if not _equal(env.out_path, env.expect_records):
        return "the record table differs from the oracle: not in key order, or a record altered"
    bad = yardstick.BAD_STDERR.search(res.stderr)
    if bad:
        return f"the CLI reported {bad.group(0)!r}: lost or demoted work"
    if f"[locust] backend: {env.platform} " not in res.stderr:
        return f"the CLI's device line does not name {env.platform}"
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
    subst = {"file": env.records_path, "out": env.out_path, "platform": env.platform}
    argv = [a.format(**subst) for a in env.traffic["argv"]] + list(env.extra_argv) + extra
    if os.path.exists(env.out_path):
        os.unlink(env.out_path)
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
