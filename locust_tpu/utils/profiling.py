"""Profiling + stage tracing.

The reference's tracing is three chrono spans printed with a UB printf
(reference MapReduce/src/main.cu:405-468, SURVEY.md Q7).  TPU equivalent:
``jax.profiler`` traces (viewable in TensorBoard/XProf) plus wall-clock
spans that force ``block_until_ready`` at stage edges, preserving the
three-stage Map/Process/Reduce report format.

A capture is read where it is written: a traced run's ``obs`` spans are
``TraceAnnotation``s on ``/host:CPU`` of the same ``.xplane.pb`` as the
device's ops (docs/OBSERVABILITY.md), and ``jax.profiler.ProfileData``
reads both with nothing but jax.
"""

from __future__ import annotations

import contextlib
import time

import jax


@contextlib.contextmanager
def device_trace(logdir: str):
    """Capture an XLA/TPU profiler trace for everything inside the block."""
    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


class SpanTimer:
    """Named wall-clock spans, syncing the given refs at span EXIT.

    Semantics: a span measures host time from entry until the passed refs
    are device-complete.  Entry does NOT sync — if earlier async device
    work is still in flight, either pass its outputs as ``sync_refs`` of
    the previous span (as engine.timed_run does per stage) or sync
    manually before opening the next span; otherwise the straggler's
    device time is billed to the wrong span.
    """

    def __init__(self):
        self.spans_ms: dict[str, float] = {}

    @contextlib.contextmanager
    def span(self, name: str, *sync_refs):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            for ref in sync_refs:
                jax.block_until_ready(ref)  # locust: noqa[R003] profiler span boundary: the sync IS the measurement
            self.spans_ms[name] = self.spans_ms.get(name, 0.0) + (
                time.perf_counter() - t0
            ) * 1e3

    def report(self) -> str:
        """Spans sorted by descending time with a percent-of-total column
        (stable: ties break on name, so repeated reports are diffable)."""
        if not self.spans_ms:
            return ""
        total = sum(self.spans_ms.values())
        width = max(len(k) for k in self.spans_ms)
        rows = sorted(self.spans_ms.items(), key=lambda kv: (-kv[1], kv[0]))
        return "\n".join(
            f"{k.ljust(width)}  {v:10.3f} ms  "
            f"{(100.0 * v / total if total else 0.0):5.1f}%"
            for k, v in rows
        )
