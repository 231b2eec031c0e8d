"""Reader ``roofline_job``: the share of the device's peak that a group of
programs reached, in %, with the work priced a JOB — the least time the
chip could take for one job's worth of the work (least bytes over peak
bytes/s) over the programs' device time a job in the traced slice.

``readers/roofline.py`` prices a unit per CALL of a program; here the
programs run as often as the implementation likes (a call a block of the
output, today) and the work is what the configuration says a job is:
``spec["least_bytes"]`` names the function of ``record_least_bytes.py``
that prices one job from the configuration's sizes alone, so the share
reads the same work whatever implements it.  ``spec["programs"]`` are the
patterns (the trace's ``XLA Modules`` line) whose device time is the
denominator.  Returns nothing without a device trace or when no program
matched (a program without them must not read as 0)."""

import record_least_bytes
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
               * getattr(record_least_bytes, spec["least_bytes"])(env.sizes)
               / (env.device["peaks"][spec["peak"]] * 1e9))
    return 100.0 * least_s / dev_s
