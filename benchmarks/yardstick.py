"""The yardstick: seeded corpus, independent oracle, one CLI job, its check.

Copied from ``chip_smoke.py`` (``build_corpus``, ``oracle_table``,
``run_cli``, ``check_cli``, ``_BAD_STDERR``) and ``bench.py``
(``_percentile``) so that no later PR can move the measure by editing a
file outside ``benchmarks/``.  From the program this module takes one
thing: ``locust_tpu.cli.main`` — the entry point a CLI user runs.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import io
import logging
import math
import os
import re
import time

HERE = os.path.dirname(os.path.abspath(__file__))

# The reference's delimiter set (strtok semantics), written out here: the
# oracle imports nothing of the program.  Equal to
# locust_tpu.config.FULL_DELIMITERS, which fixtures/selfcheck.py asserts.
DELIMITERS = b" ,.-;:'()\"\t\x00\n\r"
_SPLIT = re.compile(b"[" + re.escape(DELIMITERS) + b"]+")

# Anything the CLI says about lost or re-routed work fails the job.
BAD_STDERR = re.compile(
    r"\[locust\] WARN|Exceeded emit limit|exceeded table capacity|"
    r"overflow=[1-9]|truncated=True|not engaged|demot"
)


def build_corpus(path: str, text: str, n_lines: int, seed: int) -> int:
    """Write exactly ``n_lines`` whole lines of the real text ``text`` (a
    file under ``benchmarks/``, blank lines and all); returns the bytes
    written.  The lines are whole shuffles of the text, one after another,
    each drawn with ``numpy.random.default_rng(seed)``, cut at ``n_lines``:
    every seed gives the same lines in another order — the same words, the
    same word and line widths, the same block count and compiled shapes —
    and only the cut of the last shuffle moves the byte and word counts."""
    import numpy as np

    with open(os.path.join(HERE, text), "rb") as f:
        lines = np.array(f.read().split(b"\n")[:-1], dtype=object) + b"\n"
    rng = np.random.default_rng(seed)
    shuffles = -(-n_lines // len(lines))
    order = np.concatenate([rng.permutation(len(lines)) for _ in range(shuffles)])
    data = b"".join(lines[order[:n_lines]])
    with open(path, "wb") as f:
        f.write(data)
    return len(data)


def oracle_table(path: str) -> bytes:
    """The ``word<TAB>count`` table the CLI must print: ``Counter`` over
    the FILE split on the delimiters (collapse, drop empties), key-sorted.
    The newline is a delimiter, so no word spans lines: equal lines are
    counted first and each distinct line split once, which is the same
    table in a tenth of the time on a text that repeats its lines."""
    with open(path, "rb") as f:
        lines = collections.Counter(f.read().split(b"\n"))
    counts: collections.Counter = collections.Counter()
    for line, n in lines.items():
        for t in _SPLIT.split(line):
            if t:
                counts[t] += n
    return b"".join(
        k + b"\t" + str(v).encode() + b"\n" for k, v in sorted(counts.items())
    )


@dataclasses.dataclass(slots=True)
class JobResult:
    rc: int | None
    stdout: bytes
    stderr: str
    t_start: float
    t_end: float
    error: str | None

    @property
    def seconds(self) -> float:
        return self.t_end - self.t_start


def run_cli(main, argv: list[str]) -> JobResult:
    """``main(argv)`` in THIS process, stdout and stderr (and the
    ``locust_tpu`` logger) captured.  The clock runs from the call to the
    return: file bytes in, rendered table out.  Never raises: a job that
    raised is a failed job, and the window goes on."""
    out = io.TextIOWrapper(io.BytesIO(), encoding="utf-8", write_through=True)
    err = io.StringIO()
    handler = logging.StreamHandler(err)
    log = logging.getLogger("locust_tpu")
    old_level = log.level
    log.addHandler(handler)
    log.setLevel(logging.INFO)
    rc, error = None, None
    t0 = time.perf_counter()
    try:
        with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
            rc = main(argv)
    except (Exception, SystemExit) as e:  # a failed job, reported below
        error = f"{type(e).__name__}: {e}"
    t1 = time.perf_counter()
    log.removeHandler(handler)
    log.setLevel(old_level)
    return JobResult(rc, out.buffer.getvalue(), err.getvalue(), t0, t1, error)


def check_job(job: JobResult, expect: bytes, rules: dict, platform: str) -> str | None:
    """None if the job kept the configuration's guarantee — the exact
    table, nothing dropped, truncated or demoted, on ``platform`` — else
    one line saying what broke.  ``rules`` is the traffic file's ``check``."""
    if job.error is not None:
        return f"raised {job.error}"
    if job.rc != 0:
        return f"returned {job.rc}"
    if job.stdout != expect:
        return (f"table differs from the oracle ({len(job.stdout)} vs "
                f"{len(expect)} bytes)")
    bad = BAD_STDERR.search(job.stderr)
    if bad:
        return f"the CLI reported {bad.group(0)!r}: lost or demoted work"
    if f"[locust] backend: {platform} " not in job.stderr:
        return f"the CLI's device line does not name {platform}"
    for rule in rules.get("stderr_must_match", []):
        if not re.search(rule, job.stderr):
            return f"stderr lacks /{rule}/"
    want = rules.get("nonempty_shards")
    if want is not None:
        shards = [int(n) for n in re.findall(r"shard \d+: (\d+) keys", job.stderr)]
        if len(shards) != want or min(shards) == 0:
            return f"wanted {want} non-empty shards, CLI reported {shards}"
    return None


def percentile(values: list[float], q: float) -> float:
    """Nearest-rank percentile, q in (0, 1]: rank ceil(q*n), 1-based
    (bench.py's ``_percentile``, without its rounding)."""
    s = sorted(values)
    if not s:
        raise ValueError("percentile of no samples")
    rank = max(1, math.ceil(q * len(s)))
    return s[min(len(s) - 1, rank - 1)]
