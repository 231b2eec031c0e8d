"""Aux utilities: evidence ledger, invariant checks, tracing/profiling.

Lazy re-exports (PEP 562): ``checks``/``profiling`` import jax at module
top, but jax-free callers (bench.py's orchestrator, the serve thin
client) need ``utils.artifacts``'s ledger readers without pulling jax
into the process — an eager package __init__ would do exactly that
transitively.
"""

_EXPORTS = {
    "on_tpu": "locust_tpu.utils.artifacts",
    "record": "locust_tpu.utils.artifacts",
    "ledger_rows": "locust_tpu.utils.artifacts",
    "latest_row_ts": "locust_tpu.utils.artifacts",
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
