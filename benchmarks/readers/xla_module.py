"""Reader ``xla_module``: device ms per job of the compiled programs whose
name (the trace's ``XLA Modules`` line, fingerprint stripped) matches one
of ``spec["patterns"]``, on the busiest device of the traced slice.
Returns nothing when no program matched: a pattern that went stale must
not read as 0."""

import re

import trace_reduce


def matched(table: dict, patterns) -> list:
    pats = [re.compile(p) for p in patterns]
    return [v for name, v in table.items() if any(p.search(name) for p in pats)]


def read(spec, env):
    if env.trace is None:
        return None
    dev = env.trace["devices"][trace_reduce.busiest(env.trace)]
    hits = matched(dev["modules"], spec["patterns"])
    if not hits:
        return None
    return sum(s for s, _ in hits) * 1e3 / len(env.trace["slice_jobs"])
