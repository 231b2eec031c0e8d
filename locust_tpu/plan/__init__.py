"""locust_tpu.plan — composable dataflow plans over the engine.

A typed logical DAG (``nodes.py``) with JSON round-tripping and a
content-addressed fingerprint, canonical workload builders
(``builders.py``), and a compiler (``compile.py``) that lowers validated
plans onto the existing engine/mesh primitives — docs/PLAN.md.

jax-free at import (the serve control plane validates and fingerprints
plans before — or without — a backend); ``compile_plan`` resolves
lazily, and jax enters only when a compiled plan actually runs.
"""

from locust_tpu.plan.builders import (  # noqa: F401
    index_plan,
    join_visits_plan,
    pagerank_plan,
    records_sort_plan,
    tfidf_plan,
    wordcount_plan,
)
from locust_tpu.plan.nodes import (  # noqa: F401
    NODE_KINDS,
    NODE_OPS,
    PLAN_VERSION,
    Node,
    Plan,
    PlanError,
    from_doc,
    from_json,
    node,
)
from locust_tpu.plan.optimize import (  # noqa: F401
    REWRITE_RULES,
    Optimized,
    optimize,
)

_LAZY = ("compile_plan", "CompiledPlan", "PlanResult")


def __getattr__(name: str):
    # PEP 562 lazy re-export (the distributor/__init__ pattern): keeps
    # this package importable without numpy/engine modules loaded.
    if name in _LAZY:
        from locust_tpu.plan import compile as _compile

        return getattr(_compile, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
