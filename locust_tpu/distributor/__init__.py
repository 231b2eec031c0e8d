"""Distributor package: master/worker data plane + the wire protocol.

Submodules resolve lazily (PEP 562): ``master`` and ``worker`` pull jax
in at import, but the serve tier's thin client only needs ``protocol``
(the jax-free wire layer) — an eager import here would make every
control-plane command (``python -m locust_tpu.serve stats`` against a
remote daemon) pay a jax init — seconds of start-up, and on a machine
with a chip a grab for the device the daemon holds.  ``from locust_tpu.distributor import master`` still works
exactly as before; it just imports when asked.
"""

import importlib

_SUBMODULES = ("master", "protocol", "worker")


def __getattr__(name):
    if name in _SUBMODULES:
        return importlib.import_module(f"locust_tpu.distributor.{name}")
    raise AttributeError(
        f"module {__name__!r} has no attribute {name!r}"
    )


def __dir__():
    return sorted(list(globals()) + list(_SUBMODULES))
