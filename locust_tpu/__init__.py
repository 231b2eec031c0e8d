"""Locust-TPU: a TPU-native distributed MapReduce framework.

A brand-new JAX/XLA/Pallas implementation of the capability surface of
wuyan33/Locust (a CUDA + TCP MapReduce engine): fixed-width KV
map -> shuffle -> reduce with device-side string processing, a staged CLI,
and a multi-host distributed mode where the shuffle is an ICI all-to-all
over a ``jax.sharding.Mesh`` and the final combine is a ``psum``.

See SURVEY.md for the structural analysis of the reference this framework
rebuilds, layer by layer.
"""

__version__ = "0.1.0"

# Deliberately light — and jax-free: entrypoints must be able to read
# config (e.g. config.compile_cache_dir for JAX_COMPILATION_CACHE_DIR)
# BEFORE their first `import jax`, since jax snapshots env vars at import.
# The two jax-heavy re-exports resolve lazily (PEP 562).
from locust_tpu.config import (  # noqa: F401
    DEFAULT_CONFIG,
    DELIMITERS,
    SORT_MODES,
    EngineConfig,
)

_LAZY = {
    "KVBatch": ("locust_tpu.core.kv", "KVBatch"),
    "StreamingCorpus": ("locust_tpu.io.loader", "StreamingCorpus"),
}


def __getattr__(name: str):
    if name in _LAZY:
        import importlib

        mod, attr = _LAZY[name]
        return getattr(importlib.import_module(mod), attr)
    raise AttributeError(f"module 'locust_tpu' has no attribute {name!r}")


def __dir__():
    return sorted(list(globals()) + list(_LAZY))
