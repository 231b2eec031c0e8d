"""locust_tpu.obs — unified telemetry: tracing and metrics.

One subsystem replaces the fragmented observability that had accreted
across the repo (SpanTimer wall spans, per-shard stats, stream stall
accounting): a process-wide ``Tracer`` with nested named spans + instant
events, a closed-registry ``Metrics`` surface, Chrome-trace/Perfetto
export, and cross-node span merging over the distributor wire.  See
docs/OBSERVABILITY.md; the name registry is ``obs/names.py`` (analysis
rule R009 keeps it honest in both directions).

The span record is (name, start, end, id, parent, job): ``id`` and
``parent`` (the span open on the same thread) ride in ``args``, a root
span carries the tracer's ``trace_id``, and ``Tracer.self_times()`` is a
span's duration minus what its children cover.  ONE clock: timestamps are
``perf_counter_ns`` anchored once to the epoch, and while jax is imported
every open span is also a ``jax.profiler.TraceAnnotation`` — a profiler
session holds the program's spans on ``/host:CPU`` of the same
``.xplane.pb`` as the device's ops, which is the only device-time join
there is.  ``watch_programs()`` (called where an engine is made) adds what
jax reports of itself: ``engine.program.trace/.lower/.load`` spans and the
``engine.compile_requests``/``engine.cache_hits`` counters
(``obs/programs.py``); ``disable()`` takes its listeners out again.

ZERO-overhead disabled contract (same stance as ``utils.faultplan``):
telemetry is OFF by default, and every module hook below bails before
allocating anything — ``span()`` returns one shared null context
manager, ``event``/``metric_*`` return after a thread-local peek + one
global load.  Enable with ``obs.enable()`` (CLI: ``--trace-out FILE``;
API: ``EngineConfig(trace=True)``); the engine/distributor call sites
stay in the code permanently and cost nothing when disabled — pinned by
tests/test_obs.py's overhead guard.

Scoping: ``scoped(tracer)`` pushes a thread-local override (``None``
masks the global tracer) — how a worker daemon serving a traced map
request records into a request-scoped tracer without cross-talk from,
or double-counting into, a tracer enabled in the same process (loopback
clusters share one process).  jax-free at import: safe before backend
selection, safe in jax-free supervisors.
"""

from __future__ import annotations

import contextlib
import sys
import threading

from locust_tpu.obs.metrics import Metrics
from locust_tpu.obs.names import NAMES  # noqa: F401 - public registry
from locust_tpu.obs.trace import NULL_SPAN, Tracer

_TRACER: Tracer | None = None
_METRICS: Metrics | None = None
_WATCHING = False  # obs.programs' listeners are registered with jax
_TLS = threading.local()


def enable(process: str = "main", trace_id: str | None = None) -> Tracer:
    """Turn the process tracer + metrics on (idempotent: an existing
    tracer is kept so nested enables share one timeline)."""
    global _TRACER, _METRICS
    if _TRACER is None:
        _TRACER = Tracer(trace_id=trace_id, process=process)
        _METRICS = Metrics()
    return _TRACER


def disable() -> None:
    global _TRACER, _METRICS, _WATCHING
    _TRACER = None
    _METRICS = None
    if _WATCHING:
        _WATCHING = False
        from locust_tpu.obs import programs

        programs.uninstall()


def watch_programs() -> None:
    """Start recording what jax reports of its own compile pipeline
    (``obs/programs.py``), once per enabled stretch.  Called where an
    engine is made: a fresh CLI process enables telemetry before it
    imports jax, so that is the first place a tracer and jax are both
    certain.  Disabled, or jax not imported: returns having loaded
    nothing."""
    global _WATCHING
    if _WATCHING or current() is None or "jax" not in sys.modules:
        return
    from locust_tpu.obs import programs

    programs.install()
    _WATCHING = True


def current() -> Tracer | None:
    """The tracer this thread records into: the innermost ``scoped``
    override if any (``None`` = masked off), else the process tracer."""
    stack = getattr(_TLS, "stack", None)
    if stack:
        return stack[-1]
    return _TRACER


@contextlib.contextmanager
def scoped(tracer: Tracer | None):
    """Thread-local tracer override for the block (None masks telemetry
    entirely — a worker handling an untraced request must not leak its
    spans into a tracer enabled in the same loopback process)."""
    stack = getattr(_TLS, "stack", None)
    if stack is None:
        stack = _TLS.stack = []
    stack.append(tracer)
    try:
        yield tracer
    finally:
        stack.pop()


# ------------------------------------------------------------- emit hooks
#
# Call sites stay one line and permanently in the code; each hook's first
# statements bail on "disabled" before allocating.


def span(name: str, *sync_refs, **args):
    t = current()
    if t is None:
        return NULL_SPAN
    return t.span(name, *sync_refs, **args)


def span_at(name: str, start_s: float, end_s: float, **args) -> None:
    """A span that is already over, timed by its reporter on
    ``time.time()`` (``Tracer.span_at``)."""
    t = current()
    if t is None:
        return
    t.span_at(name, start_s, end_s, **args)


def event(name: str, **args) -> None:
    t = current()
    if t is None:
        return
    t.event(name, **args)


def _metrics_here() -> Metrics | None:
    """Metrics are PROCESS-scoped (one snapshot per exported timeline),
    so they record only for threads whose current tracer IS the process
    tracer: a ``scoped(None)`` mask suppresses them like spans, and a
    request-scoped tracer (a worker serving someone else's traced map in
    a shared loopback process) must not count its work into this
    process's totals.  Globals are read ONCE into locals — a concurrent
    ``disable()`` (e.g. the master's exit path with abandoned fetch
    threads still draining) must make hooks no-ops, never AttributeError.
    """
    m, t = _METRICS, _TRACER
    if m is None or current() is not t:
        return None
    return m


def metric_inc(name: str, n: float = 1) -> None:
    m = _metrics_here()
    if m is not None:
        m.inc(name, n)


def metric_set(name: str, value: float) -> None:
    m = _metrics_here()
    if m is not None:
        m.set(name, value)


def metric_observe(name: str, value: float) -> None:
    m = _metrics_here()
    if m is not None:
        m.observe(name, value)


# ----------------------------------------------------------------- readout


def metrics_snapshot() -> dict:
    return _METRICS.snapshot() if _METRICS is not None else {}


def summary() -> dict:
    """Compact enabled-state readout (bench's ``obs`` sub-dict)."""
    if _TRACER is None:
        return {"enabled": False}
    return {
        "enabled": True,
        "trace_id": _TRACER.trace_id,
        **_TRACER.counts(),
        "metrics": metrics_snapshot(),
    }


def export(path: str) -> dict | None:
    """Write the process tracer's merged timeline (+ metrics snapshot)
    as Chrome-trace JSON; returns the document, or None when disabled."""
    if _TRACER is None:
        return None
    return _TRACER.export(path, metrics=metrics_snapshot())
