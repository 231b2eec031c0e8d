"""Device mesh construction + multi-host runtime init.

Replaces the reference's distribution substrate — a hand-rolled TCP
command channel (reference Distributor/slave.py:5-20) with data staged
through ``/tmp/out.txt`` files (main.cu:428-441) — with the JAX distributed
runtime: ``jax.distributed.initialize`` for the control plane (coordination
service; no hand-rolled sockets) and a ``jax.sharding.Mesh`` over all
devices for the data plane, where the shuffle rides ICI collectives
(SURVEY.md §5 "Distributed communication backend").

Mesh axes:
  "data"  — line/corpus sharding (the reference's per-node [start, end)
            line ranges, main.cu:47-54) AND the hash-shuffle axis.
A single axis suffices for MapReduce (there is no tensor/pipeline dimension
in this workload class); multi-host pods put hosts x local-chips into one
flat axis so the all-to-all crosses ICI within a slice and DCN across.
"""

from __future__ import annotations

import logging

import jax
import numpy as np

logger = logging.getLogger("locust_tpu")

DATA_AXIS = "data"
SLICE_AXIS = "slice"


def make_mesh(n_devices: int | None = None, axis_name: str = DATA_AXIS) -> jax.sharding.Mesh:
    """1-D mesh over the first ``n_devices`` (default: all) devices."""
    devs = jax.devices()
    n = len(devs) if n_devices is None else n_devices
    if n > len(devs):
        raise ValueError(f"requested {n} devices, have {len(devs)}")
    return jax.sharding.Mesh(np.asarray(devs[:n]), (axis_name,))


def make_mesh_2d(
    n_slices: int,
    devs_per_slice: int | None = None,
    slice_axis: str = SLICE_AXIS,
    data_axis: str = DATA_AXIS,
) -> jax.sharding.Mesh:
    """2-D ``[slice, data]`` mesh for the hierarchical engine.

    The ``data`` (minor) axis should map to devices connected by ICI (a
    TPU slice); the ``slice`` (major) axis to groups connected by DCN
    (multi-slice / multi-pod).  ``jax.devices()`` enumerates devices
    process-major, which on real pods is exactly slice-major order, so a
    plain reshape gives the right locality.
    """
    devs = jax.devices()
    if devs_per_slice is None:
        if len(devs) % n_slices:
            raise ValueError(
                f"{len(devs)} devices do not divide into {n_slices} slices"
            )
        devs_per_slice = len(devs) // n_slices
    need = n_slices * devs_per_slice
    if need > len(devs):
        raise ValueError(f"requested {need} devices, have {len(devs)}")
    grid = np.asarray(devs[:need]).reshape(n_slices, devs_per_slice)
    return jax.sharding.Mesh(grid, (slice_axis, data_axis))


def initialize_multihost(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> None:
    """Join the JAX coordination service (multi-host pods).

    The launcher (locust_tpu/distributor/) passes these per-worker; inside
    managed TPU environments all three are auto-detected and may be None.
    """
    # Multi-process CPU pods (the virtual-pod test rig; real pods are
    # TPU) need a cross-process collectives backend: jax >= 0.4.36
    # defaults the CPU client to collectives "none", which makes ANY
    # multiprocess CPU computation raise "Multiprocess computations
    # aren't implemented on the CPU backend".  Flip to the bundled gloo
    # impl while the backend client does not exist yet (this must run
    # BEFORE first device use; jax.distributed.initialize below is
    # exactly that point).  Only for explicitly-CPU runs — TPU pods
    # keep their native collectives untouched.
    # The flag holder is a jax-private symbol (not a jax.config attribute
    # in jax 0.4.36/37), so reach for it defensively: if a future jax
    # moves it, skip the flip with a warning — the run then degrades to
    # jax's own collectives default instead of crashing at init.
    try:
        from jax._src import xla_bridge as _xla_bridge

        _cpu_coll = getattr(
            _xla_bridge, "CPU_COLLECTIVES_IMPLEMENTATION", None
        )
        if _cpu_coll is None:
            raise AttributeError(
                "jax._src.xla_bridge.CPU_COLLECTIVES_IMPLEMENTATION missing"
            )
        plats = (jax.config.jax_platforms or "").split(",")
        if "cpu" in plats and _cpu_coll.value == "none":
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
    except Exception as e:  # noqa: BLE001 - best-effort compat shim
        logger.warning(
            "cpu collectives default not flipped (%s); multiprocess CPU "
            "runs may fail with 'not implemented'", e,
        )
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )


def scatter_host_array(arr, sharding) -> jax.Array:
    """Place a HOST-REPLICATED array onto a (possibly multi-process)
    sharding: each process serves its addressable shards by slicing.
    ``make_array_from_callback`` is specified for multi-controller use,
    unlike a plain ``device_put`` onto a sharding with non-addressable
    devices (ADVICE r2 low #4).  The one scatter recipe shared by the
    checkpoint resume path, ShardedPageRank's plan staging, and anything
    else that builds global state on host."""
    arr = np.asarray(arr)
    return jax.make_array_from_callback(
        arr.shape, sharding, lambda idx: arr[idx]
    )


def gather_host_array(x: jax.Array) -> np.ndarray:
    """Fetch a (possibly multi-process sharded) array to host numpy.

    Multi-process: every process gathers ALL shards (process_allgather
    over DCN) and holds the identical full array; single-process: a plain
    device_get.  The one fetch recipe shared by result gathers, the CLI's
    shard report, and checkpoint snapshots."""
    if jax.process_count() > 1:  # exercised by tests/test_multiprocess.py
        from jax.experimental import multihost_utils

        return np.asarray(multihost_utils.process_allgather(x, tiled=True))
    return np.asarray(jax.device_get(x))


def shard_rows(rows: np.ndarray, mesh: jax.sharding.Mesh, axis_name: str = DATA_AXIS):
    """Place host rows onto the mesh, sharded along the line dimension.

    ``rows`` is the GLOBAL array and must be identical on every process.
    Single-process: one device_put.  Multi-process (multi-host pods or the
    multi-process CPU test rig): each process contributes the slice covering
    its addressable devices via ``jax.make_array_from_process_local_data`` —
    the JAX-native replacement for the reference's per-node ``[start, end)``
    line-range CLI contract (main.cu:47-54, README.md:18-24).
    """
    sharding = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(axis_name)
    )
    if jax.process_count() == 1:
        return jax.device_put(rows, sharding)
    n = rows.shape[0]
    nproc, pid = jax.process_count(), jax.process_index()
    if n % nproc != 0:
        raise ValueError(
            f"global row count {n} must divide evenly over {nproc} processes"
        )
    per = n // nproc
    local = rows[pid * per : (pid + 1) * per]
    return jax.make_array_from_process_local_data(sharding, local, rows.shape)
