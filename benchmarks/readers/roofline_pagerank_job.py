"""Reader ``roofline_pagerank_job``: ``roofline_job`` for a PageRank
configuration — the share of the device's peak that a group of programs
reached, in %, with the work priced a JOB from the configuration's sizes
alone (``spec["least_bytes"]`` names the function of
``pagerank_least_bytes.py``; ``roofline_job.py`` imports
``record_least_bytes`` by name and a PR that adds a cell edits no file) over
the device time a job of ``spec["programs"]`` (patterns over the trace's
``XLA Modules`` line) in the traced slice.  Returns nothing without a
device trace or when no program matched (a program without them must not
read as 0)."""

import pagerank_least_bytes
import trace_reduce
from readers import xla_module


def read(spec, env):
    if env.trace is None:
        return None
    modules = env.trace["devices"][trace_reduce.busiest(env.trace)]["modules"]
    dev_s = sum(secs for secs, _ in xla_module.matched(modules, spec["programs"]))
    if not dev_s:
        return None
    least_s = (len(env.trace["slice_jobs"])
               * getattr(pagerank_least_bytes, spec["least_bytes"])(env.sizes)
               / (env.device["peaks"][spec["peak"]] * 1e9))
    return 100.0 * least_s / dev_s
