"""Reader ``window_rate``: corpus bytes of the jobs that completed and kept
the guarantee, over ALL the seconds of the window (first job's start to the
last job's return, the checks between jobs included), in ``spec["per"]``
bytes (1e6 = MB)."""


def read(spec, env):
    done = sum(j.bytes for j in env.jobs if j.verdict is None)
    return done / spec["per"] / env.window_s
