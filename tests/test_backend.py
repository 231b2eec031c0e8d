"""Backend selection (locust_tpu/backend.py) and the compile-cache rule
(config.compile_cache_dir).

Selection is three lines of public jax config: these tests pin that it
stays that way — no child process, no marker file, no fallback — and
that the cache directory can be placed from outside and never moves.
"""

import os
import subprocess
import sys
from pathlib import Path

import pytest

from locust_tpu import backend, config

REPO = Path(__file__).resolve().parent.parent


def test_select_cpu_pins_cpu():
    import jax

    assert backend.select_backend("cpu") == "cpu"
    assert backend.select_backend("cpu") == "cpu"  # idempotent
    assert jax.config.jax_platforms == "cpu"
    assert jax.default_backend() == "cpu"


def test_select_tpu_raises_on_a_cpu_host():
    with pytest.raises(RuntimeError, match="'tpu' requested.*'cpu'"):
        backend.select_backend("tpu")


def test_auto_honors_jax_platforms_env():
    # conftest exports JAX_PLATFORMS=cpu: auto takes what jax initializes.
    assert os.environ["JAX_PLATFORMS"] == "cpu"
    assert backend.select_backend("auto") == "cpu"


@pytest.mark.parametrize("mode", ["gpu", "", "TPU"])
def test_invalid_mode(mode):
    with pytest.raises(ValueError):
        backend.select_backend(mode)


@pytest.mark.parametrize("mode", ["auto", "cpu", "tpu"])
def test_selection_starts_no_process_and_writes_no_file(
    mode, tmp_path, monkeypatch
):
    """No probe child, no marker under ~/.cache (or anywhere): the old
    selection did both on every start-up."""
    monkeypatch.setenv("HOME", str(tmp_path))
    monkeypatch.setenv("XDG_CACHE_HOME", str(tmp_path / "xdg"))

    def boom(*a, **k):  # pragma: no cover - must not be reached
        raise AssertionError("select_backend must not start a process")

    monkeypatch.setattr(subprocess, "Popen", boom)
    monkeypatch.setattr(subprocess, "run", boom)
    monkeypatch.setattr(os, "fork", boom)
    try:
        backend.select_backend(mode)
    except RuntimeError:
        assert mode == "tpu"
    assert list(tmp_path.iterdir()) == []


def test_backend_module_is_public_api_only():
    src = (REPO / "locust_tpu" / "backend.py").read_text()
    assert "subprocess" not in src and "_src" not in src


def test_device_summary_shape():
    dev = backend.device_summary()
    assert dev["platform"] == "cpu" and dev["count"] == 8
    assert isinstance(dev["kind"], str) and dev["kind"]


def test_select_backend_cli_prints_device_line(capsys):
    assert backend.select_backend_cli("cpu", prog="mapreduce") == "cpu"
    err = capsys.readouterr().err
    assert "[locust] backend: cpu" in err
    assert "device_kind=" in err and "count=8" in err


def test_select_backend_cli_refusal_is_an_error_line_not_a_fallback(capsys):
    assert backend.select_backend_cli("tpu", prog="mapreduce") is None
    err = capsys.readouterr().err
    assert err.startswith("mapreduce: error:")
    assert "[locust] backend:" not in err


# ----------------------------------------------------- compile-cache rule


@pytest.fixture
def keep_jax_cache_dir():
    """compile_cache_dir() follows through to an already-imported jax;
    put this process's own directory back afterwards."""
    import jax

    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_cache_env_wins_and_is_never_wiped(
    tmp_path, monkeypatch, keep_jax_cache_dir
):
    d = tmp_path / "placed"
    d.mkdir()
    (d / "foreign_entry").write_text("kept")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(d))
    assert config.compile_cache_dir() == str(d)
    assert config.compile_cache_dir(".jax_cache_cpu") == str(d)
    assert (d / "foreign_entry").read_text() == "kept"
    assert sorted(p.name for p in d.iterdir()) == ["foreign_entry"]


def test_cache_write_floor_is_lowered_unless_the_environment_sets_it(
    monkeypatch, keep_jax_cache_dir
):
    """jax writes a program to the persistent cache only if it took a
    second to compile; the program lowers that to 0 where it sets the
    directory (a job's two sub-second helpers then compile once a
    checkout, not once a process), and an ambient value wins."""
    import jax

    floor = "jax_persistent_cache_min_compile_time_secs"
    before = getattr(jax.config, floor)
    try:
        monkeypatch.delenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
                           raising=False)
        config.compile_cache_dir(".jax_cache_cpu")
        assert os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] == "0"
        assert getattr(jax.config, floor) == 0.0
        monkeypatch.setenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "2.5")
        config.compile_cache_dir(".jax_cache_cpu")
        assert os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] == "2.5"
        assert getattr(jax.config, floor) == 2.5
    finally:
        jax.config.update(floor, before)


@pytest.mark.parametrize("sub", [".jax_cache", ".jax_cache_cpu"])
def test_cache_default_is_a_fixed_dir_in_the_checkout(
    sub, monkeypatch, keep_jax_cache_dir
):
    import jax

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = config.compile_cache_dir(sub)
    assert got == str(REPO / sub)
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == got  # children
    assert config.compile_cache_dir("ignored-once-set") == got
    assert "/tmp" not in got and str(os.getpid()) not in got
    # a jax imported before the call follows through its config
    assert jax.config.jax_compilation_cache_dir == got


def test_cache_path_agrees_across_calls_and_a_child_process(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=str(REPO), JAX_PLATFORMS="cpu")
    code = (
        "from locust_tpu.config import compile_cache_dir as c; "
        "import sys; a = c(); assert 'jax' not in sys.modules; print(a)"
    )
    outs = [
        subprocess.run(
            [sys.executable, "-c", code], env=env, cwd=cwd,
            capture_output=True, text=True, check=True,
        ).stdout.strip()
        for cwd in (str(REPO), "/")
    ]
    assert outs[0] == outs[1] == str(REPO / ".jax_cache")


def test_tests_own_cache_is_not_under_tmp():
    """conftest placed this process's cache: the ambient directory if one
    was given, else <checkout>/.jax_cache_cpu — never /tmp/jax_comp_cache_*
    and never ~/.cache/locust_tpu."""
    import jax

    d = jax.config.jax_compilation_cache_dir
    assert d == os.environ["JAX_COMPILATION_CACHE_DIR"]
    assert "jax_comp_cache_" not in d
    assert not d.startswith(os.path.expanduser("~/.cache/locust_tpu"))


@pytest.mark.parametrize(
    "module", ["locust_tpu", "locust_tpu.serve", "locust_tpu.distributor.worker"]
)
def test_entry_points_place_the_cache_before_jax(module, tmp_path):
    """`python -m <entry> --help` must have exported the cache directory
    (the ambient one, untouched) without importing jax first."""
    env = dict(os.environ)
    env.update(
        PYTHONPATH=str(REPO), JAX_PLATFORMS="cpu",
        JAX_COMPILATION_CACHE_DIR=str(tmp_path / "x"),
    )
    code = (
        "import runpy, sys, os\n"
        "sys.argv = [sys.argv[1], '--help']\n"
        "try:\n"
        "    runpy.run_module(sys.argv[0], run_name='__main__')\n"
        "except SystemExit:\n"
        "    pass\n"
        "print('CACHE=' + os.environ['JAX_COMPILATION_CACHE_DIR'])\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code, module], env=env,
        capture_output=True, text=True,
    )
    assert f"CACHE={tmp_path / 'x'}" in proc.stdout, proc.stderr
    assert not (tmp_path / "x").exists() or not any((tmp_path / "x").iterdir())


# ------------------------------------------------------- circuit breaker
#
# The dispatch-time complement of the selection rule above: a device that
# initialized can still fail a later dispatch, so consecutive dispatch
# failures trip a breaker, the run fails over to CPU from its last
# checkpoint, and a half-open probe readmits the TPU.  All clocked by an
# injectable fake so every transition is deterministic.


class _Clock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t


def _breaker(threshold=3, cooldown_s=30.0):
    clk = _Clock()
    return backend.CircuitBreaker(
        threshold=threshold, cooldown_s=cooldown_s, clock=clk
    ), clk


def test_breaker_trips_after_consecutive_failures_only():
    br, _ = _breaker(threshold=3)
    br.record_failure()
    br.record_failure()
    br.record_success()  # success resets the consecutive count
    br.record_failure()
    br.record_failure()
    assert br.state() == "closed" and br.allow()
    br.record_failure()
    assert br.state() == "open" and not br.allow()
    assert br.stats()["trips"] == 1


def test_breaker_half_open_single_probe_then_close():
    br, clk = _breaker(threshold=1, cooldown_s=10.0)
    br.record_failure()
    assert br.state() == "open" and not br.allow()
    clk.t += 10.0
    assert br.allow()          # the one half-open probe
    assert br.state() == "half_open"
    assert not br.allow()      # concurrent callers stay on the fallback
    br.record_success()
    assert br.state() == "closed" and br.allow()


def test_breaker_failed_probe_reopens_for_full_cooldown():
    br, clk = _breaker(threshold=1, cooldown_s=10.0)
    br.record_failure()
    clk.t += 10.0
    assert br.allow()
    br.record_failure()        # probe dies: back to open, new cooldown
    assert br.state() == "open"
    clk.t += 9.9
    assert not br.allow()
    clk.t += 0.2
    assert br.allow()
    br.record_success()
    assert br.state() == "closed"
    assert br.stats()["trips"] == 1  # a failed probe re-opens, not re-trips


def test_breaker_rejects_bad_params():
    with pytest.raises(ValueError):
        backend.CircuitBreaker(threshold=0)
    with pytest.raises(ValueError):
        backend.CircuitBreaker(cooldown_s=0.0)


def test_guarded_dispatch_accounts_success_and_failure():
    br, _ = _breaker(threshold=2)
    assert backend.guarded_dispatch(br, lambda: 41 + 1) == 42
    with pytest.raises(RuntimeError):
        backend.guarded_dispatch(br, _raise_runtime)
    st = br.stats()
    assert st["successes"] == 1 and st["failures"] == 1
    assert st["state"] == "closed"  # one failure, threshold two


def _raise_runtime():
    raise RuntimeError("device died")


def test_cpu_fallback_device_exists_on_cpu_host():
    dev = backend.cpu_fallback_device()
    assert dev is not None and dev.platform == "cpu"
