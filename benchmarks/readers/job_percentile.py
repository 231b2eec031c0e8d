"""Reader ``job_percentile``: nearest-rank percentile ``spec["q"]`` of the
submit-to-table time of EVERY job of the window, in ms."""

import yardstick


def read(spec, env):
    return yardstick.percentile([j.seconds * 1e3 for j in env.jobs], spec["q"])
