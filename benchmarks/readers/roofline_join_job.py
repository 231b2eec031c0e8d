"""Reader ``roofline_join_job``: ``roofline_job`` for a join configuration
— the share of the device's peak that a group of programs reached, in %,
with the work priced a JOB from the DATA (``spec["least_bytes"]`` names the
function of ``join_least_bytes.py``, which takes the oracle's counts of
lines, bytes, passed visits, pages and groups — ``env.oracle.counts()``,
kept by ``drivers/closed_loop_cli_join.py`` — and the configuration's
sizes; ``roofline_job.py`` imports ``record_least_bytes`` by name and a PR
that adds a cell edits no file) over the device time a job of
``spec["programs"]`` (patterns over the trace's ``XLA Modules`` line) in the
traced slice.  Returns nothing without a device trace, without an oracle's
counts, or when no program matched (a program without them must not read
as 0)."""

import join_least_bytes
import trace_reduce
from readers import xla_module


def read(spec, env):
    oracle = getattr(env, "oracle", None)
    if env.trace is None or oracle is None:
        return None
    modules = env.trace["devices"][trace_reduce.busiest(env.trace)]["modules"]
    dev_s = sum(secs for secs, _ in xla_module.matched(modules, spec["programs"]))
    if not dev_s:
        return None
    least_s = (len(env.trace["slice_jobs"])
               * getattr(join_least_bytes, spec["least_bytes"])(oracle.counts(), env.sizes)
               / (env.device["peaks"][spec["peak"]] * 1e9))
    return 100.0 * least_s / dev_s
