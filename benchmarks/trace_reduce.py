"""From a profiler trace (``*.xplane.pb``) to device metrics.

Reads the trace with ``jax.profiler.ProfileData`` alone (no TensorFlow
proto).  What it knows of a TPU trace, checked by hand on a v5e trace and
by ``fixtures/selfcheck.py`` on the recorded one:

* every chip is a plane ``/device:TPU:<n>``; its line ``XLA Ops`` holds
  one event per executed HLO operation, its line ``XLA Modules`` one per
  executed program, named ``<jit name>(<fingerprint>)``;
* host threads are lines of the plane ``/host:CPU``; a
  ``jax.profiler.TraceAnnotation`` is an event there under its own name;
* all planes share one clock (ns).

Busy time is the UNION of the op intervals, never their sum: ops of one
program can overlap, and a sum can pass the window.
"""

from __future__ import annotations

import bisect
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_FINGERPRINT = re.compile(r"\(\d+\)$")


class NoDevicePlane(ValueError):
    """The trace holds no chip's plane: nothing ran on a TPU."""


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def _line(plane, name: str):
    for line in plane.lines:
        if line.name == name:
            return line
    return None


def _events(line) -> list[tuple[str, float, float]]:
    if line is None:
        return []
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events]


def merge(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Sorted, disjoint union of ``(start, end)`` intervals."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def annotations(pd, name: str) -> list[tuple[float, float]]:
    """The host's ``TraceAnnotation(name)`` spans, in time order."""
    host = pd.find_plane_with_name(HOST_PLANE)
    found = []
    if host is not None:
        for line in host.lines:
            found += [(a, b) for n, a, b in _events(line) if n == name]
    return sorted(found)


def _by_name(events, strip_fingerprint: bool, lo: float, hi: float) -> dict:
    """name -> [seconds, calls] of the events that START in [lo, hi)."""
    out: dict[str, list[float]] = {}
    for name, a, b in events:
        if lo <= a < hi:
            if strip_fingerprint:
                name = _FINGERPRINT.sub("", name)
            tot = out.setdefault(name, [0.0, 0])
            tot[0] += (b - a) / 1e9
            tot[1] += 1
    return out


def reduce_trace(path: str, annotation: str = "bench.job") -> dict:
    """The traced slice runs from the first annotation's start to the last
    one's end.  Per device: busy seconds (union of op intervals inside the
    slice), idle share, seconds and calls per program and per op, and the
    idle gaps ``(start, end)`` of the slice."""
    pd = load(path)
    jobs = annotations(pd, annotation)
    if not jobs:
        raise ValueError(f"{path}: no {annotation!r} annotation on the host plane")
    lo, hi = jobs[0][0], jobs[-1][1]
    devices = {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        ops = _events(_line(plane, OPS_LINE))
        busy = clip(merge([(a, b) for _, a, b in ops]), lo, hi)
        busy_s = sum(b - a for a, b in busy) / 1e9
        edges = [lo] + [t for ab in busy for t in ab] + [hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        devices[int(m.group(1))] = {
            "busy_s": busy_s,
            "idle_share": 1.0 - busy_s / ((hi - lo) / 1e9),
            "modules": _by_name(_events(_line(plane, MODULES_LINE)), True, lo, hi),
            "ops": _by_name(ops, False, lo, hi),
            "gaps": gaps,
        }
    if not devices:
        raise NoDevicePlane(f"{path}: no /device:TPU:<n> plane — nothing ran on a chip")
    return {"window_s": (hi - lo) / 1e9, "jobs": jobs, "devices": devices}


def busiest(reduced: dict) -> int:
    return max(reduced["devices"], key=lambda d: reduced["devices"][d]["busy_s"])


def label_gaps(gaps, jobs, host_spans) -> list[list]:
    """Idle seconds by what the host was doing, largest first.

    ``jobs`` are the annotations ``(start, end)``; ``host_spans[j]`` are
    job j's program spans ``(name, start_ns, end_ns)`` on the trace's
    clock.  A gap is labelled at its midpoint by the innermost (shortest)
    span covering it, ``job, outside its spans`` if none does, and
    ``between jobs`` outside every annotation."""
    starts = [a for a, _ in jobs]
    total: dict[str, float] = {}
    for a, b in gaps:
        mid = (a + b) / 2
        j = bisect.bisect_right(starts, mid) - 1
        if j < 0 or mid > jobs[j][1]:
            label = "between jobs"
        else:
            cover = [(e - s, n) for n, s, e in host_spans[j] if s <= mid <= e]
            label = min(cover)[1] if cover else "job, outside its spans"
        total[label] = total.get(label, 0.0) + (b - a) / 1e9
    return sorted(([k, v] for k, v in total.items()), key=lambda kv: -kv[1])


def top_ops(dev: dict, n: int = 10, width: int = 120) -> list[list]:
    """The ops that took most device time, under the names the trace prints
    (whole HLO instructions) cut to ``width`` characters."""
    tot: dict[str, float] = {}
    for name, (secs, _) in dev["ops"].items():
        tot[name[:width]] = tot.get(name[:width], 0.0) + secs
    return sorted(([k, v] for k, v in tot.items()), key=lambda kv: -kv[1])[:n]
