r"""Reader ``xla_op``: device ms per job of the executed HLO operations that
match one of ``spec["patterns"]``, on the busiest device of the traced
slice.  The trace's ``XLA Ops`` line names an event by its whole
instruction — ``%all_to_all.32 = u32[4,40960,8]{...} all-to-all(u32[...]
%bitcast.328), channel_id=1, ...`` on a v5e — so a pattern should name the
OPCODE (``\sall-to-all\(``), not the instruction's name: the compiler names
instructions after the jax primitive they came from (``%all_to_all.36`` is
a reshape), and a fusion lists its operands by name.  Summed
durations, not a union: the operations of one kind run one after another.
Returns nothing without a device trace or when no operation matched (a
program without that operation, or a pattern that went stale, must not read
as 0)."""

import re

import trace_reduce


def read(spec, env):
    if env.trace is None:
        return None
    pats = [re.compile(p) for p in spec["patterns"]]
    ops = env.trace["devices"][trace_reduce.busiest(env.trace)]["ops"]
    hits = [secs for name, (secs, _) in ops.items() if any(p.search(name) for p in pats)]
    if not hits:
        return None
    return sum(hits) * 1e3 / len(env.trace["slice_jobs"])
