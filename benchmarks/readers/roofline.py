"""Reader ``roofline``: the share of the device's peak that a group of
programs reached, in % — the least time the chip could take for their
work (least bytes over peak bytes/s; these programs move rows and do no
matrix arithmetic worth counting, so HBM bounds them) over their device
time in the traced slice.  The work is priced per UNIT, not per program
name, because the trace gives two of the programs one name: ``spec["unit"]``
is the pattern of the program that runs once per unit (a block),
``spec["least_bytes"]`` the function of least_bytes.py that prices one
unit from the configuration's sizes, ``spec["programs"]`` the patterns
whose device time is the denominator.  Names are those of the trace's
``XLA Modules`` line."""

import re

import least_bytes
import trace_reduce


def read(spec, env):
    if env.trace is None:
        return None
    modules = env.trace["devices"][trace_reduce.busiest(env.trace)]["modules"]
    units = sum(calls for name, (_, calls) in modules.items()
                if re.search(spec["unit"], name))
    dev_s = sum(secs for name, (secs, _) in modules.items()
                if any(re.search(p, name) for p in spec["programs"]))
    if not units or not dev_s:
        return None
    least_s = (units * getattr(least_bytes, spec["least_bytes"])(env.sizes)
               / (env.device["peaks"][spec["peak"]] * 1e9))
    return 100.0 * least_s / dev_s
