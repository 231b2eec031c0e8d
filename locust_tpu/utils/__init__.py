"""Aux utilities: fault plans, invariant checks, tracing/profiling.

Lazy re-exports (PEP 562): ``checks``/``profiling`` import jax at module
top, but jax-free callers (the serve thin client, the distributor's
control plane) import ``utils.faultplan`` without pulling jax into the
process — an eager package __init__ would do exactly that transitively.
"""

_EXPORTS = {
    "checkify_pipeline": "locust_tpu.utils.checks",
    "validate_batch": "locust_tpu.utils.checks",
    "SpanTimer": "locust_tpu.utils.profiling",
    "device_trace": "locust_tpu.utils.profiling",
}

__all__ = list(_EXPORTS)


def __getattr__(name: str):
    mod_name = _EXPORTS.get(name)
    if mod_name is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    import importlib

    return getattr(importlib.import_module(mod_name), name)
