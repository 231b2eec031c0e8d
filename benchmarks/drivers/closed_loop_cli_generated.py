"""Traffic driver ``closed_loop_cli_generated``: ``closed_loop_cli`` on a
corpus that the configuration's own generator writes.

``run.py``'s set-up draws every corpus with ``yardstick.build_corpus``
(shuffles of the configuration's ``text``).  A configuration whose text is
not shuffles of a shipped file names its generator instead:

    "generator": {"module": "zipf_text", ...the keyword arguments of its build()}

and this driver, before the first job, writes that corpus OVER the one
set-up drew — ``module.build(path, sizes.corpus_lines, seed, **arguments)``
— and sets ``env.expect`` (``yardstick.oracle_table`` of the new file) and
``env.corpus_bytes``.  Everything else is ``closed_loop_cli``'s: the job,
its check, the warm-up, the window, the trace slice.

The seed is ``--seed`` in a run of ``run.py``.  ``control.py`` draws a new
placeholder per seed and does not pass the seed on, so there the draw is
seeded by the placeholder's CRC-32: another text for every seed all the same.

A configuration the program cannot run fails HERE, in set-up: if a warm-up
job does not keep the guarantee (a program whose table cannot hold the
vocabulary prints a truncated one), the run ends with exit code 4 and no
result line, instead of a window of jobs that are all wrong.
"""

from __future__ import annotations

import importlib
import os
import time
import zlib

import yardstick
from drivers import closed_loop_cli

measure = closed_loop_cli.measure


def _stamp(path: str):
    st = os.stat(path)
    return st.st_mtime_ns, st.st_size


def generate(env, seed: int) -> None:
    """The configuration's corpus over ``env.corpus_path``, and its oracle."""
    spec = dict(env.config["generator"])
    module = importlib.import_module(spec.pop("module"))
    t0 = time.perf_counter()
    env.corpus_bytes = module.build(
        env.corpus_path, env.sizes["corpus_lines"], seed, **spec)
    env.expect = yardstick.oracle_table(env.corpus_path)
    env.generated = _stamp(env.corpus_path)
    env.say(f"generated corpus ({module.__name__}, seed {seed}): {env.corpus_bytes} bytes, "
            f"{env.sizes['corpus_lines']} lines; oracle {env.expect.count(10)} distinct "
            f"words ({time.perf_counter() - t0:.2f} s of set-up)")


def one_job(env, index: int, traced: bool):
    """``closed_loop_cli.one_job`` on the generated corpus: a file at
    ``env.corpus_path`` that this driver did not write is a placeholder."""
    if getattr(env, "generated", None) != _stamp(env.corpus_path):
        with open(env.corpus_path, "rb") as f:
            generate(env, zlib.crc32(f.read()))
    return closed_loop_cli.one_job(env, index, traced)


def warm_up(env):
    generate(env, env.seed)
    jobs = closed_loop_cli.warm_up(env)
    bad = [j.verdict for j in jobs if j.verdict is not None]
    if bad:
        env.say(f"the program cannot run configuration {env.cell['config']}: a warm-up "
                f"job did not keep its guarantee ({bad[0]}); no window, no result line")
        raise SystemExit(4)
    return jobs
