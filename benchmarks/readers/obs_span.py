"""Reader ``obs_span``: the program's own host-clock spans (CLI
``--trace-out``).  Per job, the summed duration of the spans named
``spec["span"]``; then the median over the window's jobs, in ms.  Returns
nothing when no job recorded such a span."""

import statistics


def read(spec, env):
    per_job = []
    for j in env.jobs:
        durs = [e - s for n, s, e in j.spans if n == spec["span"]]
        if durs:
            per_job.append(sum(durs) / 1e6)
    return statistics.median(per_job) if per_job else None
