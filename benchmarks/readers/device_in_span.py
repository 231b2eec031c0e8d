"""Reader ``device_in_span``: device ms per job that fell inside one of the
program's spans, on the busiest device of the traced slice.

A traced program writes every span as a ``jax.profiler.TraceAnnotation`` on
``/host:CPU`` of the slice's ``.xplane.pb``, beside the device's ``XLA Ops``
line.  The value is the UNION of the op intervals cut to the annotations
named ``spec["span"]`` (inside the slice), summed, over the slice's jobs.
Sound where the span waits for its own device work before it closes
(``timed_run`` syncs inside each stage span), so that work lies wholly
inside it.

The two planes share a clock only to within about a millisecond: the
profiler fits the device's clock to the host's once a session, and one
session in three of PR 24's sat 1.1 ms early — more than the 0.3 ms
between a stage's launch and its first op, so ops fell into the span
before.  ``spec["holds_all_work"]`` names the spans that between them
hold ALL the device's work (a prefix: ``engine.stage.``); the reader
shifts the device's clock by the offset that puts most device time inside
them (the middle of the plateau of best offsets) before it cuts, says the
offset it took, and reads nothing if even then a hundredth of the device's
work lies outside — the planes then disagree by more than an offset.

Returns nothing without a device trace or when the trace holds no such
annotation (a program that does not annotate)."""

import bisect
import glob
import os

import trace_reduce

REACH_NS = 3_000_000   # offsets tried: -3 ms .. +3 ms
STEP_NS = 50_000


class DeviceBusy:
    """A device's busy time as a function of time, from its sorted,
    disjoint op intervals."""

    def __init__(self, ops):
        self.starts = [a for a, _ in ops]
        self.ends = [b for _, b in ops]
        self.cum, total = [], 0.0
        for a, b in ops:
            total += b - a
            self.cum.append(total)

    def before(self, t: float) -> float:
        """ns of busy time before ``t``."""
        i = bisect.bisect_right(self.starts, t)
        if i == 0:
            return 0.0
        return self.cum[i - 1] - max(0.0, self.ends[i - 1] - t)

    def inside(self, spans, shift: float = 0.0) -> float:
        """ns of busy time inside ``spans`` with the device's clock moved
        ``shift`` ns later (overlapping spans count twice)."""
        return sum(self.before(b - shift) - self.before(a - shift) for a, b in spans)


def fit_offset(busy: DeviceBusy, spans) -> float:
    """The shift of the device's clock, ns, that puts most busy time inside
    ``spans``: the middle of the best offsets tried."""
    tried = range(-REACH_NS, REACH_NS + STEP_NS, STEP_NS)
    inside = [busy.inside(spans, s) for s in tried]
    best = max(inside)
    plateau = [s for s, v in zip(tried, inside) if v >= best * (1 - 1e-6)]
    return float(plateau[len(plateau) // 2])


def device_ops(pd, device: int):
    """The merged ``XLA Ops`` intervals of ``/device:TPU:<device>``."""
    plane = pd.find_plane_with_name(f"/device:TPU:{device}")
    line = next(ln for ln in plane.lines if ln.name == trace_reduce.OPS_LINE)
    return trace_reduce.merge(
        [(e.start_ns, e.start_ns + e.duration_ns) for e in line.events])


def annotations_with_prefix(pd, prefix: str) -> list[tuple[float, float]]:
    """The host's annotations whose name starts with ``prefix``, in time order."""
    host = pd.find_plane_with_name(trace_reduce.HOST_PLANE)
    return sorted((e.start_ns, e.start_ns + e.duration_ns)
                  for line in (host.lines if host is not None else ())
                  for e in line.events if e.name.startswith(prefix))


def read(spec, env):
    if env.trace is None:
        return None
    jobs = env.trace["jobs"]
    lo, hi = jobs[0][0], jobs[-1][1]
    if not hasattr(env, "xplane"):  # one load and one fit for every metric of this reader
        found = sorted(glob.glob(os.path.join(
            env.profile_dir, "plugins", "profile", "*", "*.xplane.pb")))
        pd = trace_reduce.load(found[-1])
        busy = DeviceBusy(device_ops(pd, trace_reduce.busiest(env.trace)))
        holders = trace_reduce.clip(
            annotations_with_prefix(pd, spec["holds_all_work"]), lo, hi)
        shift, held = 0.0, 0.0
        if holders:
            shift = fit_offset(busy, holders)
            held = busy.inside(holders, shift) / busy.inside([(lo, hi)], shift)
            env.say(f"device_in_span: device clock moved {shift / 1e3:+.0f} us to fit the "
                    f"{spec['holds_all_work']}* spans; they then hold {100 * held:.3f}% of the "
                    f"slice's device time ({100 * busy.inside(holders) / busy.inside([(lo, hi)]):.3f}% unmoved)")
        env.xplane = (pd, busy, shift, held)
    pd, busy, shift, held = env.xplane
    spans = trace_reduce.clip(trace_reduce.annotations(pd, spec["span"]), lo, hi)
    if not spans or held < 0.99:
        return None
    return busy.inside(spans, shift) / 1e6 / len(env.trace["slice_jobs"])
