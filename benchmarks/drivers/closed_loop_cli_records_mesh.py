"""Traffic driver ``closed_loop_cli_records_mesh``: ``closed_loop_cli_records``
with the traffic file's ``check`` rules read.

The records driver's check knows OUT, the oracle and the CLI's device line,
and reads no rule of the traffic file.  A sort across chips has two more
things to say on stderr, and this driver holds every job to them on top of
that check (its generate, warm-up, window and trace slice are that
driver's, its code and not a copy; the job is that driver's less its
unlink of OUT, below):

* ``stderr_must_match``: patterns every job's stderr has to hold (the
  CLI's ``0 bytes lost`` line);
* ``nonempty_record_shards``: the number of ``shard d: n records`` lines,
  every ``n`` above 0 (``yardstick.check_job``'s ``nonempty_shards`` reads
  the WordCount mesh's ``... keys`` lines, and the records driver never
  calls it).

The OUT of the job before STAYS where it is, and the next job writes over
it in place (``serde.write_records`` opens OUT without truncating it and
cuts it to size at the end), as a nightly sort writes over last night's
output.  And OUT is a MEMORY FILE where the platform has one
(``_memory_out``: ``os.memfd_create``, handed to the CLI as
``/proc/self/fd/N``, a path like any other; tried once in set-up the way
the program will use it, and the work directory's ``sorted.bin`` where that
fails).  Both are about the measuring machine and not the program.  A v5e
host's machines run a sandboxed kernel (``uname -r`` 4.4.0) whose root,
the temporary directory with it, is a 9p mount: every ``write`` there goes
through the sandbox to a file of the host outside it.  Measured on those
machines (PERF.md section 6, PR 39): the records driver's unlink of OUT
before every job, inside the window, took 0.6-0.8 s of every cycle and 4-5
s three times in one run of six; the write of a NEW 3.2 GB OUT 1.7-2.2 s by
the age of the host's memory (the first hand-in: a spread of 5.7-8.0% for a
bound of 4.5%, refused as too noisy); written over in place 0.82-0.94 s,
with one job in seven 0.6-2.8 s longer (single ``write``s of 13 MB
stalling 0.1-0.6 s).  Side by side in one call, 3.2 GB written over in
place again and again for 13 s: in the temporary directory a pass took
0.32-1.67 s with seven ``write``s over 50 ms; in the sandbox's own memory
file system 0.18-0.24 s, 64 passes, no ``write`` over 1 ms.  A deployment
writes to its own file system, not to this one, so the cell keeps the
machine's out of the job: the program's sink is the same ``open`` /
``write`` / ``ftruncate`` either way.

So that a job which wrote nothing, or left any part of OUT as it found it,
still cannot pass for the job before, one byte in every MiB of OUT is first
set to the complement of the oracle's (``_spoil_out``, 3,052 one-byte
writes: some ms); an OUT of any other size than the oracle's is emptied.
``one_job`` is therefore this module's own, the records driver's less its
unlink.  After the generator has written them the records are also flushed
(``os.fsync``), so that no write-back of the input falls into the window;
on that host it found nothing to flush.

``all_devices_held_memory`` is ``run.py``'s own, for any traffic.  A
program whose ``sort`` takes no ``--mesh`` fails in set-up: argparse ends
the warm-up job with ``SystemExit: 2``, the run ends with exit code 4 and
no result line.
"""

from __future__ import annotations

import functools
import gc
import os
import re
import time
import zlib

import yardstick
from drivers import closed_loop_cli
from drivers import closed_loop_cli_records as records_driver
from drivers.closed_loop_cli_generated import _stamp

_SHARD_LINE = re.compile(r"shard \d+: (\d+) records")
_records_check = records_driver.check_job
_records_generate = records_driver.generate


def _memory_out(env) -> str | None:
    """The path of an anonymous memory file for OUT (``memfd_create``, kept
    open on ``env`` for the life of the run and named through ``/proc``), or
    None where the platform has none or cannot reopen it by that path: tried
    once, the way the program will use it (opened without truncating,
    written, cut to size, read back)."""
    if getattr(env, "out_memfd", None) is not None:
        return f"/proc/self/fd/{env.out_memfd}"
    fd = None
    try:
        fd = os.memfd_create("locust_bench_sorted")
        path = f"/proc/self/fd/{fd}"
        with open(os.open(path, os.O_WRONLY | os.O_CREAT, 0o666), "wb") as f:
            f.write(b"locust")
            f.truncate(3)
        with open(path, "rb") as f:
            if f.read() != b"loc" or os.path.getsize(path) != 3:
                raise OSError("a memory file reopened by its /proc path is another file")
        os.truncate(path, 0)
    except (AttributeError, OSError) as err:
        if fd is not None:
            os.close(fd)
        env.say(f"no memory file for OUT here ({err!r}): OUT is a file of the work directory")
        return None
    env.out_memfd = fd
    return path


def generate(env, seed: int) -> None:
    """The records driver's generate, then the records file flushed, and
    OUT moved from the work directory into a memory file."""
    _records_generate(env, seed)
    t0 = time.perf_counter()
    fd = os.open(env.records_path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)
    env.say(f"records flushed to the disk in {time.perf_counter() - t0:.2f} s (set-up): "
            "no write-back of the input inside the window")
    memory = _memory_out(env)
    if memory is not None:
        env.out_path = memory
        env.say(f"OUT is a memory file ({memory}): the work directory's file system "
                "is the measuring machine's, not the program's")


def check_job(env, res) -> str | None:
    """The records driver's verdict, then the traffic file's rules."""
    verdict = _records_check(env, res)
    if verdict is not None:
        return verdict
    rules = env.traffic.get("check", {})
    for rule in rules.get("stderr_must_match", []):
        if not re.search(rule, res.stderr):
            return f"stderr lacks /{rule}/"
    want = rules.get("nonempty_record_shards")
    if want is not None:
        shards = [int(n) for n in _SHARD_LINE.findall(res.stderr)]
        if len(shards) != want or min(shards) == 0:
            return f"wanted {want} non-empty shards, CLI reported {shards}"
    return None


SPOIL_STRIDE = 1 << 20


def _spoil_out(env) -> None:
    """The last OUT left in place for the next job to write over, no
    longer equal to the oracle anywhere: one byte a MiB complemented."""
    out = getattr(env, "out_path", None)  # None before the first generate (control.py)
    if out is None or not os.path.exists(out):
        return
    want = env.expect_records
    if os.path.getsize(out) != want.size:
        os.truncate(out, 0)  # no unlink: OUT may be a memory file, which has no name to remove
        return
    fd = os.open(out, os.O_WRONLY)
    try:
        for at in range(0, want.size, SPOIL_STRIDE):
            os.pwrite(fd, bytes([int(want[at]) ^ 0xFF]), at)
    finally:
        os.close(fd)


_THEIRS = {"check_job": records_driver.check_job, "generate": records_driver.generate,
           "one_job": records_driver.one_job}
_depth = 0


def _with_ours(fn):
    """``fn`` of the records driver with this module's check, generate and
    job in the place of its own for as long as the outermost call runs
    (its ``warm_up`` and ``measure`` find ``one_job`` by that name)."""
    @functools.wraps(fn)
    def run(*args, **kwargs):
        global _depth
        if _depth == 0:
            records_driver.check_job, records_driver.generate = check_job, generate
            records_driver.one_job = one_job
        _depth += 1
        try:
            return fn(*args, **kwargs)
        finally:
            _depth -= 1
            if _depth == 0:
                for name, theirs in _THEIRS.items():
                    setattr(records_driver, name, theirs)
    return run


@_with_ours
def one_job(env, index: int, traced: bool) -> closed_loop_cli.Job:
    """The records driver's ``one_job`` with OUT spoiled where that unlinks it."""
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
    _spoil_out(env)
    with jax.profiler.TraceAnnotation(closed_loop_cli.ANNOTATION):
        epoch_ns = time.time() * 1e9
        res = yardstick.run_cli(env.cli_main, argv)
    t0 = time.perf_counter()
    verdict = check_job(env, res)
    env.check_s = getattr(env, "check_s", []) + [time.perf_counter() - t0]
    spans = closed_loop_cli._read_spans(span_file) if span_file else []
    gc.collect()
    return closed_loop_cli.Job(res, epoch_ns, verdict, spans, env.corpus_bytes)


warm_up = _with_ours(records_driver.warm_up)
measure = _with_ours(records_driver.measure)
