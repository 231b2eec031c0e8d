"""utils/profiling.py — the wall-clock stage report.

Reading a capture is tests/test_obs.py's business now: a traced run's
spans are annotations in the profiler's own file.
"""

from locust_tpu.utils import profiling


def test_span_timer_report_percent_and_descending_sort():
    """ISSUE 6 satellite pin: report() sorts by descending time (stable
    on ties by name) and carries a percent-of-total column summing to
    ~100%."""
    t = profiling.SpanTimer()
    t.spans_ms = {"small": 10.0, "big": 70.0, "mid": 20.0}
    lines = t.report().splitlines()
    assert [ln.split()[0] for ln in lines] == ["big", "mid", "small"]
    assert all("%" in ln and "ms" in ln for ln in lines)
    pcts = [float(ln.split()[-1].rstrip("%")) for ln in lines]
    assert pcts == [70.0, 20.0, 10.0]
    assert abs(sum(pcts) - 100.0) < 0.2
    assert profiling.SpanTimer().report() == ""
