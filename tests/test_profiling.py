"""utils/profiling.py — xplane capture + parsing.

The profiler path must work off-TPU (the parser falls back to the
/host:CPU plane's XLA-client line) so a chip call never runs it cold: a
parse bug would otherwise burn the capture the call was spent on.  Oracle here is structural — a real capture of a real sort must
yield a positive sort-family device time.
"""

import jax
import jax.numpy as jnp

from locust_tpu.utils import profiling


def test_profile_device_captures_sort(tmp_path):
    @jax.jit
    def f(x):
        return jax.lax.sort((x, x * 2), num_keys=1)[0]

    x = jnp.arange(1 << 16, dtype=jnp.uint32) % jnp.uint32(977)
    f(x).block_until_ready()  # compile outside the trace
    result, summary, path = profiling.profile_device(
        lambda: f(x), str(tmp_path / "trace")
    )
    assert result is not None
    assert "error" not in summary, summary
    assert path is not None and path.endswith(".xplane.pb")
    assert summary["device_plane"] is not None
    assert summary["device_total_ms"] > 0
    # The traced computation IS a sort; the sort-family extraction must
    # see it.
    assert summary["sort_ms"] > 0
    plane = summary["planes"][summary["device_plane"]]
    assert any("sort" in name.lower() for name, _ in plane["top_ops"])


def test_parse_xplane_missing_file_is_error_dict():
    out = profiling.parse_xplane("/nonexistent/path.xplane.pb")
    assert "error" in out


def test_profile_device_ignores_stale_capture_in_reused_dir(tmp_path):
    """Regression (ISSUE 6 satellite): a pre-existing *.xplane.pb in the
    output dir must never be returned as "the" capture — only a file the
    trace itself produced counts."""
    out_dir = tmp_path / "trace"
    stale_dir = out_dir / "plugins" / "profile" / "old"
    stale_dir.mkdir(parents=True)
    stale = stale_dir / "host.xplane.pb"
    stale.write_bytes(b"not a real capture")

    @jax.jit
    def f(x):
        return jax.lax.sort((x, x + 1), num_keys=1)[0]

    x = jnp.arange(1 << 12, dtype=jnp.uint32) % jnp.uint32(97)
    f(x).block_until_ready()
    result, summary, path = profiling.profile_device(
        lambda: f(x), str(out_dir)
    )
    assert result is not None
    # A real capture happened, and it is NOT the stale file.
    assert path is not None and path != str(stale)
    assert "error" not in summary, summary


def test_profile_device_reports_stale_only_dir_as_error(tmp_path, monkeypatch):
    """When the trace produces nothing and the dir holds only stale
    captures, the result is an ERROR, not last run's profile."""
    out_dir = tmp_path / "trace"
    out_dir.mkdir()
    (out_dir / "old.xplane.pb").write_bytes(b"stale")

    import contextlib

    monkeypatch.setattr(
        jax.profiler, "trace", lambda _d: contextlib.nullcontext()
    )
    result, summary, path = profiling.profile_device(lambda: 1, str(out_dir))
    assert path is None
    assert "error" in summary and "stale" in summary["error"]


def test_newest_xplane_exclude_filter(tmp_path):
    a = tmp_path / "a.xplane.pb"
    b = tmp_path / "b.xplane.pb"
    a.write_bytes(b"a")
    b.write_bytes(b"b")
    import os as _os

    _os.utime(a, (1, 1))  # a is older; b newest
    assert profiling.newest_xplane(str(tmp_path)) == str(b)
    assert profiling.newest_xplane(str(tmp_path), exclude={str(b)}) == str(a)
    assert (
        profiling.newest_xplane(str(tmp_path), exclude={str(a), str(b)})
        is None
    )


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


def test_profile_device_never_raises(tmp_path, monkeypatch):
    """A capture failure must surface as an error dict, not an exception
    (evidence collection cannot take down a window sweep)."""

    def boom(*a, **k):
        raise RuntimeError("profiler unavailable")

    monkeypatch.setattr(jax.profiler, "trace", boom)
    result, summary, path = profiling.profile_device(
        lambda: 1, str(tmp_path / "t")
    )
    assert result is None and path is None
    assert "error" in summary
