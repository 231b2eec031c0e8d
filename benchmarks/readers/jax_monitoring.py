"""Reader ``jax_monitoring``: events jax itself reports to the listener
run.py registers (``Monitor``).  ``spec["reduce"]``:

* ``count_in_window`` — how many of ``spec["events"]`` came inside the
  window, less how many of ``spec["minus"]``;
* ``per_job_sum_median_ms`` — per job, the summed durations of
  ``spec["events"]`` that came inside the job; the median over jobs, ms."""

import statistics


def read(spec, env):
    names = set(spec["events"])
    if spec["reduce"] == "count_in_window":
        lo, hi = env.window
        minus = set(spec.get("minus", []))
        return float(sum((n in names) - (n in minus)
                         for t, n, _ in env.monitor.events if lo <= t <= hi))
    if spec["reduce"] == "per_job_sum_median_ms":
        lo, hi = env.window
        evs = [(t, d) for t, n, d in env.monitor.events
               if n in names and d is not None and lo <= t <= hi]
        per_job = [sum(d for t, d in evs if j.t_start <= t <= j.t_end) * 1e3
                   for j in env.jobs]
        return statistics.median(per_job)
    raise ValueError(f"jax_monitoring: unknown reduce {spec['reduce']!r}")
