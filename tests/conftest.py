"""Test harness config: force an 8-device virtual CPU mesh.

Multi-device collectives are tested without TPU hardware via
``xla_force_host_platform_device_count`` — the standard JAX recipe
(SURVEY.md §4).  Must run before the first ``import jax`` anywhere.
"""

import os
import sys

import pytest

# The tests run on the virtual 8-device CPU mesh whatever the ambient
# platform is (on a machine with a chip, that chip is not the tests').
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# Keep CPU tests deterministic and quiet.
os.environ.setdefault("JAX_ENABLE_X64", "0")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from locust_tpu.config import compile_cache_dir

# Persistent compile cache: repeat suite runs skip most XLA compiles.  An
# ambient JAX_COMPILATION_CACHE_DIR wins; otherwise the tests keep theirs
# in <checkout>/.jax_cache_cpu, apart from what a chip run caches in
# <checkout>/.jax_cache (a chip machine gets a copy of this checkout).
# The floor is set first: compile_cache_dir lowers it to 0 only where the
# environment says nothing, and the suite's thousands of tiny programs
# are cheaper to recompile than to write and read back.
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")
compile_cache_dir(".jax_cache_cpu")


@pytest.fixture(autouse=True)
def _fresh_programs():
    """An engine's programs belong to its configuration and the process
    keeps them (engine._programs_for): no test runs programs traced under
    another test's monkeypatched constants or tracer, and every test's
    first engine of a configuration builds, as a fresh process's does."""
    from locust_tpu import engine

    engine.clear_programs()
