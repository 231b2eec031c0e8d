"""Profiling + stage tracing.

The reference's tracing is three chrono spans printed with a UB printf
(reference MapReduce/src/main.cu:405-468, SURVEY.md Q7).  TPU equivalent:
``jax.profiler`` traces (viewable in TensorBoard/XProf) plus wall-clock
spans that force ``block_until_ready`` at stage edges, preserving the
three-stage Map/Process/Reduce report format.

The xplane helpers below close the loop on the
capture: they reduce a trace's ``*.xplane.pb`` protobuf to per-op device
times so utilization can be computed from MEASURED device seconds
instead of the analytic traffic model (utils/roofline.py) timing itself
with host wall clock.  Parsing uses the xplane proto bundled
with the baked-in tensorflow; failures surface as a dict with an
``error`` key — profiling is evidence collection and must never take
down the run it observes (same stance as utils/artifacts.py).
"""

from __future__ import annotations

import contextlib
import glob
import os
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


# Op-name fragments attributed to the Process-stage sort family: stock
# lax.sort lowers to "sort.N" HLOs; the hand-written Pallas bitonic
# kernel lowers to Mosaic custom-calls ("tpu_custom_call" is the Mosaic
# wrapper name).  Fusions are NOT counted (they hold map/reduce
# elementwise work), so the sort figure is a floor on sort device time.
# The fused megakernel's custom-call is EXCLUDED (family_ms exclude=
# below): it has its own family, and a Mosaic-wrapper name carrying the
# kernel name would otherwise land in both — double-counting the
# kernel's ms in family_join's scatter+sort+kernel pairing, the exact
# inflation the DOT family comment warns about.
SORT_OP_FRAGMENTS = ("sort", "custom-call", "tpu_custom_call", "mosaic")

# The sort-FREE "hasht" fold's Process work is scatters (slot compete /
# write / combine) plus the probe gathers — none named "sort".  Tracked
# as a separate figure so hasht's measured Process device time pairs
# with its scatter-round traffic model (utils/roofline.py).
SCATTER_OP_FRAGMENTS = ("scatter", "gather")

# "hasht-mxu" moves the value combine into one-hot contractions that
# lower to dot HLOs ("dot.N" / dot_general) — time the scatter family
# misses entirely.  Tracked separately so the mode's measured Process
# device time can pair with a traffic model that INCLUDES the one-hot
# bytes (roofline est_onehot_bytes); pairing those bytes with a time
# that excludes the dots would inflate utilization (could exceed 100%).
# NOT "conv": that substring also matches "convert.N" casts.
DOT_OP_FRAGMENTS = ("dot",)

# "fused" runs the map->aggregate Pallas megakernel, whose device time
# lands in ONE custom-call op named after the kernel body
# (ops/pallas/fused_fold._fused_kernel).  Tracked separately for the
# same reason as the dots: the mode's traffic model includes the
# kernel's bytes (roofline est_kernel_bytes), so its measured Process
# time must include the kernel's ms or the utilization pairing
# inflates.  Disjoint from the sort family by the exclude rule in
# family_ms (a Mosaic wrapper op carrying the kernel name counts HERE,
# never twice).
FUSED_KERNEL_OP_FRAGMENTS = ("fused_kernel",)


def family_ms(totals: dict, fragments, exclude=()) -> float:
    """Sum of op durations whose name carries any of ``fragments`` and
    none of ``exclude`` — the one family-attribution rule, module-level
    so its disjointness (sort vs fused-kernel) is directly testable."""
    return round(
        sum(
            ms
            for n, ms in totals.items()
            if any(f in n.lower() for f in fragments)
            and not any(x in n.lower() for x in exclude)
        ),
        3,
    )


def parse_xplane(path: str, top_n: int = 12) -> dict:
    """Reduce one ``*.xplane.pb`` to per-plane op-name duration totals.

    Returns ``{"planes": {name: {total_ms, top_ops, sort_ms}},
    "device_plane": name|None, "device_total_ms": float, "sort_ms":
    float}`` or ``{"error": ...}``.  The device plane prefers
    ``/device:*`` (real TPU) and falls back to the XLA-client line of
    ``/host:CPU`` so the parser is testable off-TPU.  Durations sum per
    op name within a plane; a host plane's parallel client threads can
    overstate busy time, device planes serialize per core.
    """
    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2
    except Exception as e:  # noqa: BLE001 - evidence, never a crash
        return {"error": f"xplane proto unavailable: {type(e).__name__}: {e}"}
    try:
        xs = xplane_pb2.XSpace()
        with open(path, "rb") as f:
            xs.ParseFromString(f.read())
    except Exception as e:  # noqa: BLE001
        return {"error": f"xplane parse failed: {type(e).__name__}: {e}"}

    planes: dict[str, dict] = {}
    for plane in xs.planes:
        md = plane.event_metadata
        totals: dict[str, float] = {}
        for line in plane.lines:
            # Host planes interleave python-tracing lines with the XLA
            # client line; only the latter holds op executions.  Device
            # planes keep every line.
            if plane.name.startswith("/host:") and not line.name.startswith(
                ("tf_XLA", "XLA")
            ):
                continue
            for e in line.events:
                name = md[e.metadata_id].name if e.metadata_id in md else "?"
                totals[name] = totals.get(name, 0.0) + e.duration_ps / 1e9
        if totals:
            top = sorted(totals.items(), key=lambda kv: -kv[1])[:top_n]
            planes[plane.name] = {
                "total_ms": round(sum(totals.values()), 3),
                "top_ops": [[n, round(ms, 3)] for n, ms in top],
                "sort_ms": family_ms(
                    totals, SORT_OP_FRAGMENTS,
                    exclude=FUSED_KERNEL_OP_FRAGMENTS,
                ),
                "scatter_ms": family_ms(totals, SCATTER_OP_FRAGMENTS),
                "dot_ms": family_ms(totals, DOT_OP_FRAGMENTS),
                "kernel_ms": family_ms(totals, FUSED_KERNEL_OP_FRAGMENTS),
            }

    device = next(
        (n for n in planes if n.startswith("/device:")),
        "/host:CPU" if "/host:CPU" in planes else None,
    )
    out = {"planes": planes, "device_plane": device}
    if device is not None:
        out["device_total_ms"] = planes[device]["total_ms"]
        out["sort_ms"] = planes[device]["sort_ms"]
        out["scatter_ms"] = planes[device]["scatter_ms"]
        out["dot_ms"] = planes[device]["dot_ms"]
        out["kernel_ms"] = planes[device]["kernel_ms"]
    return out


def _xplane_paths(out_dir: str) -> list[str]:
    return glob.glob(
        os.path.join(out_dir, "**", "*.xplane.pb"), recursive=True
    )


def newest_xplane(out_dir: str, exclude=()) -> str | None:
    """Newest capture under ``out_dir``, skipping ``exclude`` paths.

    ``exclude`` exists for the stale-capture bug: callers that reuse an
    ``out_dir`` must snapshot the pre-existing ``*.xplane.pb`` paths
    before tracing and pass them here, or an EARLIER run's capture (mtime
    ordering is not creation ordering across filesystems/clock steps)
    can be returned as "the" capture of a trace that produced nothing.
    """
    exclude = set(exclude)
    paths = [p for p in _xplane_paths(out_dir) if p not in exclude]
    return max(paths, key=os.path.getmtime) if paths else None


def profile_device(fn, out_dir: str) -> tuple[object, dict, str | None]:
    """Run ``fn()`` under a profiler trace written to ``out_dir``.

    Returns ``(fn_result, summary, xplane_path)``; a capture or parse
    failure returns ``summary={"error": ...}`` (result ``None`` if the
    trace context itself raised).  Only a capture the trace itself
    produced is ever returned: pre-existing ``*.xplane.pb`` files in a
    reused ``out_dir`` are snapshotted before tracing and excluded, so a
    failed capture reports the failure instead of silently handing back
    last run's profile as this run's evidence.
    """
    os.makedirs(out_dir, exist_ok=True)
    pre_existing = set(_xplane_paths(out_dir))
    try:
        with jax.profiler.trace(out_dir):
            result = fn()
            jax.block_until_ready(result)
    except Exception as e:  # noqa: BLE001 - the run may have succeeded
        # outside the profiler's control; report the capture failure.
        return None, {"error": f"trace failed: {type(e).__name__}: {e}"}, None
    path = newest_xplane(out_dir, exclude=pre_existing)
    if path is None:
        msg = "no xplane.pb produced"
        if pre_existing:
            msg += (
                f" (ignored {len(pre_existing)} stale capture(s) already "
                "in the output dir)"
            )
        return result, {"error": msg}, None
    return result, parse_xplane(path), path

