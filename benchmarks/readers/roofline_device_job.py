"""Reader ``roofline_device_job``: the share of ONE device's peak that a
group of programs or operations reached, in %, with the work priced a
DEVICE's share of a job — the least time a chip could take for its share
of one job (``mesh_record_least_bytes.py``, from the configuration's sizes
alone: the same work whatever implements it) over the device time a job
on the BUSIEST device of the traced slice.

``readers/roofline_job.py`` prices a whole job against one device's time,
which on a mesh would read as many times too high as there are chips.
``spec["least_bytes"]`` names the pricing function; ``spec["peak"]`` the
key of ``peaks.json`` and ``spec["peak_unit"]`` what it is in
(``GB_per_s``, or ``Gbit_per_s``: eight to a byte); the denominator is
``spec["programs"]`` (patterns over the trace's ``XLA Modules`` line) or
``spec["ops"]`` (patterns over its ``XLA Ops`` line, as ``xla_op`` reads
them).  Returns nothing without a device trace or when nothing matched (an
operation the compiler made asynchronous runs on another line, which no
reader reads: that must not read as 0)."""

import re

import mesh_record_least_bytes
import trace_reduce
from readers import xla_module

_BYTES_PER_S = {"GB_per_s": 1e9, "Gbit_per_s": 1e9 / 8}


def read(spec, env):
    if env.trace is None:
        return None
    dev = env.trace["devices"][trace_reduce.busiest(env.trace)]
    if "programs" in spec:
        dev_s = sum(s for s, _ in xla_module.matched(dev["modules"], spec["programs"]))
    else:
        pats = [re.compile(p) for p in spec["ops"]]
        dev_s = sum(s for name, (s, _) in dev["ops"].items()
                    if any(p.search(name) for p in pats))
    if not dev_s:
        return None
    sizes = dict(env.sizes, chips=env.cell["chips"])
    least_s = (len(env.trace["slice_jobs"])
               * getattr(mesh_record_least_bytes, spec["least_bytes"])(sizes)
               / (env.device["peaks"][spec["peak"]] * _BYTES_PER_S[spec["peak_unit"]]))
    return 100.0 * least_s / dev_s
