"""What jax does to a program before it can run — trace, lower, then
compile or read it back from the persistent cache — as spans and counters
of the program's own timeline.

Not call sites: jax reports these itself through ``jax.monitoring``
(jax 0.9 ``_src/dispatch.py`` ``LogElapsedTimeContextManager``: a time
span per event with ``start_time``/``end_time`` on ``time.time()`` and
the traced function's ``fun_name``; ``_src/compiler.py``: one plain event
per persistent-cache request and per hit).  ``install`` registers ONE pair
of listeners and ``uninstall`` takes exactly that pair out again;
``obs.watch_programs`` / ``obs.disable`` call them at most once per enabled
stretch: an entry point that runs many jobs in one process must not pile up
a listener a job.

Imported only once a tracer is on and jax is in the process
(``obs.watch_programs``), so the disabled path never loads it.
"""

from __future__ import annotations

from locust_tpu import obs

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


def _on_time_span(event: str, start_time: float, end_time: float, **kw):
    # One literal name a call, not a table of them: R009 reads the
    # registry's emitters off ``obs.<hook>("literal", ...)`` call sites.
    fun_name = str(kw.get("fun_name", ""))
    if event == TRACE:
        obs.span_at("engine.program.trace", start_time, end_time,
                    fun_name=fun_name)
    elif event == LOWER:
        obs.span_at("engine.program.lower", start_time, end_time,
                    fun_name=fun_name)
    elif event == COMPILE:
        obs.span_at("engine.program.load", start_time, end_time,
                    fun_name=fun_name)


def _on_event(event: str, **_kw):
    if event == CACHE_REQUEST:
        obs.metric_inc("engine.compile_requests")
    elif event == CACHE_HIT:
        obs.metric_inc("engine.cache_hits")


def install() -> None:
    import jax.monitoring as monitoring

    monitoring.register_event_time_span_listener(_on_time_span)
    monitoring.register_event_listener(_on_event)


def uninstall() -> None:
    import jax.monitoring as monitoring

    monitoring.unregister_event_time_span_listener(_on_time_span)
    monitoring.unregister_event_listener(_on_event)
