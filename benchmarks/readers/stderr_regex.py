"""Reader ``stderr_regex``: a number the CLI prints about its own job.

``spec["pattern"]`` has one group that captures a number; it is searched in
each job's stderr.  ``spec["per_job"]`` says what a job's value is:
``max_over_mean`` — the largest match over the mean of all of them (the
skew of a list the CLI prints a line at a time); a metric that wants
another reduction brings it.  The value is the median over the window's
jobs that printed any.  Returns nothing when no job did (a program, or a
path, without that line)."""

import re
import statistics


def read(spec, env):
    if spec["per_job"] != "max_over_mean":
        raise ValueError(f"stderr_regex: unknown per_job {spec['per_job']!r}")
    pattern = re.compile(spec["pattern"])
    per_job = []
    for j in env.jobs:
        found = [float(m) for m in pattern.findall(j.stderr)]
        if found:
            per_job.append(max(found) / statistics.fmean(found))
    return statistics.median(per_job) if per_job else None
