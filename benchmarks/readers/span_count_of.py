"""Reader ``span_count_of``: how often ``spec["span"]`` happened in the
jobs that ran ``spec["within"]``.  Per job that recorded a ``within``
span, the count of spans named ``span`` — 0 where there was none, which
``span_count`` cannot say (it reads only the jobs that recorded the span
itself, so a job without a retry is left out and a window of them reads
nothing); then the median over those jobs.  Returns nothing when no job
recorded ``within`` (a program, or a path, without it)."""

import statistics


def read(spec, env):
    per_job = [sum(1 for n, _, _ in j.spans if n == spec["span"])
               for j in env.jobs
               if any(n == spec["within"] for n, _, _ in j.spans)]
    return float(statistics.median(per_job)) if per_job else None
