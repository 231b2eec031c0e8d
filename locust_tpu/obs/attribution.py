"""Automatic device-time attribution: xplane family times joined onto
stage spans.

A measured roofline capture and a per-stage device-time split need one
join that used to live nowhere: the profiler capture (utils/profiling.profile_device), the family
reduction (parse_xplane sort/scatter/dot totals) and the stage spans
lived in three places nobody joined.  This module is the join:

  * ``family_join`` — the ONE copy of the Process-family pairing rule
    (sort modes pair with the sort HLO family; the hasht family adds
    scatters; hasht-mxu adds the one-hot dots — pairing one-hot bytes
    with a dot-free time would inflate utilization past honesty);
  * ``attributed_run`` — run a callable under ``profile_device`` and, if
    a tracer is active, annotate its ``engine.stage.process`` spans with
    the measured device families (an ``obs.device_join`` instant marks
    the join in the timeline).

Caveat (docs/OBSERVABILITY.md): one xplane capture has no per-stage op
correlation, so the families attribute to the PROCESS stage — the stage
whose op families they are by construction (profiling.SORT/SCATTER/
DOT_OP_FRAGMENTS); map/reduce elementwise work hides in fusions and is
deliberately not claimed.
"""

from __future__ import annotations

from locust_tpu import obs
from locust_tpu.utils import profiling

# The stage span the device families attach to (see module docstring).
PROCESS_STAGE_SPAN = "engine.stage.process"


def family_join(summary: dict, sort_mode: str) -> dict:
    """Pair a parsed xplane ``summary`` with ``sort_mode``'s Process-stage
    op families.  Returns the joined fields (all floats may be None when
    the capture carried no device plane)."""
    if summary.get("error"):
        return {"error": summary["error"]}
    from locust_tpu.config import HASHT_FAMILY

    sort_ms = summary.get("sort_ms")
    scatter_ms = summary.get("scatter_ms")
    dot_ms = summary.get("dot_ms")
    kernel_ms = summary.get("kernel_ms")
    family = "sort"
    process_ms = sort_ms
    if sort_mode in HASHT_FAMILY:
        process_ms = (scatter_ms or 0.0) + (sort_ms or 0.0)
        family = "scatter+sort"
        if sort_mode == "hasht-mxu":
            process_ms += dot_ms or 0.0
            family = "scatter+sort+dot"
        elif sort_mode == "fused":
            # The megakernel's device time is ONE custom call
            # (profiling.FUSED_KERNEL_OP_FRAGMENTS) the scatter/sort
            # families never see; the mode's traffic model includes the
            # kernel's bytes (roofline est_kernel_bytes), so its time
            # must pair in too — the hasht-mxu dot-family rule again.
            process_ms += kernel_ms or 0.0
            family = "scatter+sort+kernel"
    return {
        "process_family": family,
        "process_device_ms": (
            round(process_ms, 3) if process_ms is not None else None
        ),
        "sort_device_ms": sort_ms,
        "scatter_device_ms": scatter_ms,
        "dot_device_ms": dot_ms,
        "kernel_device_ms": kernel_ms,
        "device_total_ms": summary.get("device_total_ms"),
        "device_plane": summary.get("device_plane"),
    }


def attributed_run(fn, out_dir: str, sort_mode: str):
    """Run ``fn()`` under a profiler capture and join the parsed device
    families onto the active tracer's Process-stage spans.

    Returns ``(fn_result, summary, xplane_path, join)`` — the first three
    exactly as ``profiling.profile_device`` (evidence collection never
    raises), ``join`` from ``family_join``.  The annotation is a no-op
    when telemetry is disabled or the run emitted no stage spans (e.g. a
    fused ``run_blocks`` capture) — the join dict still carries the
    numbers for the evidence rows either way.
    """
    tracer = obs.current()
    mark = tracer.event_count() if tracer is not None else 0
    result, summary, xplane = profiling.profile_device(fn, out_dir)
    join = family_join(summary, sort_mode)
    if tracer is not None and "error" not in join:
        # Annotate only the spans THIS capture ran (since=mark): a
        # warm-up timed_run earlier in the session must not inherit
        # device times the profiler never measured for it.
        matched = tracer.annotate(PROCESS_STAGE_SPAN, join, since=mark)
        obs.event(
            "obs.device_join",
            stage=PROCESS_STAGE_SPAN,
            spans_annotated=matched,
            process_family=join["process_family"],
            process_device_ms=join["process_device_ms"],
        )
    return result, summary, xplane, join
