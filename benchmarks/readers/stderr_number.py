"""Reader ``stderr_number``: ONE number the CLI prints about its own job.

``spec["pattern"]`` has one group that captures a number; a job's value is
its first match in the job's stderr (``stderr_regex`` reduces a LIST the CLI
prints a line at a time, and knows no other reduction).  The value is the
median over the window's jobs that printed it.  Returns nothing when no job
did (a program, or a path, without that line)."""

import re
import statistics


def read(spec, env):
    pattern = re.compile(spec["pattern"])
    found = [pattern.search(j.stderr) for j in env.jobs]
    per_job = [float(m.group(1)) for m in found if m]
    return statistics.median(per_job) if per_job else None
