"""Reader ``span_count``: the program's own spans (CLI ``--trace-out``),
counted.  Per job, how many spans are named ``spec["span"]``; then the
median over the window's jobs that recorded any.  Returns nothing when no
job did (a program without that span)."""

import statistics


def read(spec, env):
    per_job = [sum(1 for n, _, _ in j.spans if n == spec["span"]) for j in env.jobs]
    per_job = [c for c in per_job if c]
    return float(statistics.median(per_job)) if per_job else None
