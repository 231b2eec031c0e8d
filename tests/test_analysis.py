"""locust_tpu.analysis — fixture tests per rule + the repo-wide gate.

Layout: each rule gets at least one FIRING fixture and one SILENT
fixture (the rule catalog's contract, docs/ANALYSIS.md); R004/R005 are
additionally demonstrated by MUTATING copies of the real modules
(faultplan SITES, protocol constants) so registry drift provably fails
the gate.  ``test_repo_gate`` then runs the whole rule set over the
actual tree — that test IS the tier-1 wiring: no new CI infrastructure,
a finding anywhere in locust_tpu/, scripts/ or tests/ fails the suite.

Pure host-side AST work: no jax import, no device, fast.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

from locust_tpu.analysis import run_analysis
from locust_tpu.analysis.baseline import write_baseline
from locust_tpu.analysis.registry import all_rules, get_rules

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _write(root, rel, code):
    path = root / rel
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(code))
    return path


def _run(root, rules, paths=None):
    return run_analysis(
        paths=paths, root=str(root), rules=rules,
        baseline_path=str(root / "no_baseline.json"),
    )


def _ids(result):
    return [(f.rule_id, f.path) for f in result.new]


# ------------------------------------------------------------------- R001


def test_r001_fires_on_unlocked_self_write_in_thread_target(tmp_path):
    _write(tmp_path, "mod.py", """
        import threading

        class Srv:
            def start(self):
                threading.Thread(target=self.worker, daemon=True).start()

            def worker(self):
                self.state = "running"
    """)
    res = _run(tmp_path, ["R001"], ["mod.py"])
    assert len(res.new) == 1
    assert "self.state" in res.new[0].message


def test_r001_fires_on_global_write_via_executor_submit(tmp_path):
    _write(tmp_path, "mod.py", """
        from concurrent.futures import ThreadPoolExecutor

        total = 0

        def task():
            global total
            total += 1

        def run():
            with ThreadPoolExecutor() as ex:
                ex.submit(task)
    """)
    res = _run(tmp_path, ["R001"], ["mod.py"])
    assert len(res.new) == 1
    assert "total" in res.new[0].message


def test_r001_silent_when_write_is_under_lock(tmp_path):
    _write(tmp_path, "mod.py", """
        import threading

        class Srv:
            def __init__(self):
                self._lock = threading.Lock()

            def start(self):
                threading.Thread(target=self.worker).start()

            def worker(self):
                with self._lock:
                    self.state = "running"
    """)
    assert not _run(tmp_path, ["R001"], ["mod.py"]).new


def test_r001_silent_on_entry_fn_own_locals_and_nested_nonlocals(tmp_path):
    # master.py's shape: the entry fn's own locals, mutated via a nested
    # helper's nonlocal, are private to the entry thread — not shared.
    _write(tmp_path, "mod.py", """
        from concurrent.futures import ThreadPoolExecutor

        def one(shard):
            seq = 0

            def launch():
                nonlocal seq
                seq += 1

            launch()
            return seq

        def run(n):
            with ThreadPoolExecutor() as ex:
                return list(ex.map(one, range(n)))
    """)
    assert not _run(tmp_path, ["R001"], ["mod.py"]).new


# ------------------------------------------------------------------- R002


def test_r002_fires_on_print_and_time_in_jitted_fn(tmp_path):
    _write(tmp_path, "mod.py", """
        import time
        import jax

        def step(x):
            print("tracing", x)
            t = time.time()
            return x * t

        step_j = jax.jit(step)
    """)
    res = _run(tmp_path, ["R002"], ["mod.py"])
    messages = " | ".join(f.message for f in res.new)
    assert len(res.new) == 2
    assert "print()" in messages and "time.time" in messages


def test_r002_fires_on_global_write_in_shard_map_body(tmp_path):
    _write(tmp_path, "mod.py", """
        import jax

        calls = 0

        def body(x):
            global calls
            calls += 1
            return x

        step = jax.jit(jax.shard_map(body, None, None, None))
    """)
    res = _run(tmp_path, ["R002"], ["mod.py"])
    assert len(res.new) == 1
    assert "global write" in res.new[0].message


def test_r002_fires_under_functools_partial_jit_decorator(tmp_path):
    # The dominant decorator idiom in this repo (tokenize,
    # pagerank): the tracer name lives in the partial's ARGUMENTS.
    _write(tmp_path, "mod.py", """
        import functools
        import time
        import jax

        @functools.partial(jax.jit, static_argnames=("n",))
        def step(x, n):
            t = time.time()
            return x * n * t
    """)
    res = _run(tmp_path, ["R002"], ["mod.py"])
    assert len(res.new) == 1
    assert "time.time" in res.new[0].message


def test_r002_silent_on_pure_fn_and_sanctioned_debug_print(tmp_path):
    _write(tmp_path, "mod.py", """
        import functools
        import jax

        @functools.partial(jax.jit, static_argnames=("n",))
        def step(x, n):
            jax.debug.print("x = {}", x)
            return x * n
    """)
    assert not _run(tmp_path, ["R002"], ["mod.py"]).new


# ------------------------------------------------------------------- R003


def test_r003_fires_on_sync_in_loop(tmp_path):
    _write(tmp_path, "locust_tpu/hot.py", """
        import jax

        def drain(blocks):
            out = []
            for b in blocks:
                out.append(jax.block_until_ready(b))
            return out
    """)
    res = _run(tmp_path, ["R003"], ["locust_tpu"])
    assert len(res.new) == 1
    assert "block_until_ready" in res.new[0].message


def test_r003_silent_outside_loops_and_outside_library(tmp_path):
    _write(tmp_path, "locust_tpu/ok.py", """
        import jax

        def run(x):
            y = step(x)
            jax.block_until_ready(y)
            return y
    """)
    _write(tmp_path, "scripts/tool.py", """
        import jax

        def drain(blocks):
            for b in blocks:
                jax.block_until_ready(b)
    """)
    assert not _run(tmp_path, ["R003"], ["locust_tpu", "scripts"]).new


# ------------------------------------------------------------------- R004

_FIXTURE_FAULTPLAN = """
    SITES = {
        "rpc.ping": ("delay",),
        "io.write": ("corrupt",),
    }
"""


def _r004_tree(tmp_path, hook_site="rpc.ping", tests_text=None,
               docs_text=None, faultplan=_FIXTURE_FAULTPLAN):
    _write(tmp_path, "locust_tpu/utils/faultplan.py", faultplan)
    _write(tmp_path, "locust_tpu/net.py", f"""
        from locust_tpu.utils import faultplan

        def send(data):
            faultplan.delay({hook_site!r}, cmd="send")
            faultplan.mangle("io.write", data)
            return data
    """)
    _write(tmp_path, "tests/test_faults.py",
           tests_text if tests_text is not None
           else '# exercises "rpc.ping" and "io.write"\n')
    (tmp_path / "docs").mkdir(exist_ok=True)
    (tmp_path / "docs" / "FAULTS.md").write_text(
        docs_text if docs_text is not None
        else "| `rpc.ping` | ... |\n| `io.write` | ... |\n"
    )


def test_r004_silent_when_registry_call_sites_tests_docs_agree(tmp_path):
    _r004_tree(tmp_path)
    assert not _run(tmp_path, ["R004"], ["locust_tpu", "tests"]).new


def test_r004_fires_on_typod_call_site(tmp_path):
    _r004_tree(tmp_path, hook_site="rpc.pnig")
    res = _run(tmp_path, ["R004"], ["locust_tpu", "tests"])
    assert any("rpc.pnig" in f.message and "not in faultplan.SITES"
               in f.message for f in res.new)


def test_r004_fires_on_unexercised_and_undocumented_site(tmp_path):
    _r004_tree(tmp_path, tests_text='# only "rpc.ping" here\n',
               docs_text="| `rpc.ping` |\n")
    res = _run(tmp_path, ["R004"], ["locust_tpu", "tests"])
    msgs = " | ".join(f.message for f in res.new)
    assert "never exercised" in msgs and "undocumented" in msgs
    assert all("io.write" in f.message for f in res.new)


def test_r004_mutating_real_sites_registry_fails_the_gate(tmp_path):
    """The acceptance demo: copy the REAL faultplan + hook modules +
    chaos suite + docs, add one site to SITES — the gate must fail with
    unhooked/untested/undocumented findings for exactly that site."""
    for rel in (
        "locust_tpu/utils/faultplan.py",
        "locust_tpu/distributor/protocol.py",
        "locust_tpu/distributor/worker.py",
        "locust_tpu/distributor/master.py",
        "locust_tpu/parallel/shuffle.py",
        "locust_tpu/io/snapshot.py",  # hooks io.ckpt_write + io.checkpoint
        "locust_tpu/engine.py",       # hooks via finalize_snapshot call
        "locust_tpu/serve/daemon.py",  # hooks serve.admit + serve.dispatch
        "locust_tpu/serve/journal.py",  # hooks serve.journal
        "locust_tpu/serve/pool.py",     # hooks serve.place
        "locust_tpu/serve/replicate.py",  # hooks serve.ship
        "locust_tpu/backend.py",        # hooks backend.dispatch
        "locust_tpu/plan/distribute.py",  # hooks plan.partition (chaos_partition)
        "locust_tpu/ops/pallas/fused_fold.py",  # hot-path kernel: site-free
        "tests/test_faults.py",
        "docs/FAULTS.md",
    ):
        dst = tmp_path / rel
        dst.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(os.path.join(REPO, rel), dst)
    paths = ["locust_tpu", "tests"]
    assert not _run(tmp_path, ["R004"], paths).new  # faithful copy: green

    fp = tmp_path / "locust_tpu/utils/faultplan.py"
    mutated = fp.read_text().replace(
        'SITES = {', 'SITES = {\n    "io.phantom": ("corrupt",),', 1
    )
    assert 'io.phantom' in mutated
    fp.write_text(mutated)
    res = _run(tmp_path, ["R004"], paths)
    assert len(res.new) == 3  # unhooked + untested + undocumented
    assert all("io.phantom" in f.message for f in res.new)


# ------------------------------------------------------------------- R005


def test_r005_fires_on_respelled_max_frame_in_wire_layer(tmp_path):
    shutil.copy(
        os.path.join(REPO, "locust_tpu/distributor/protocol.py"),
        _write(tmp_path, "locust_tpu/distributor/protocol.py", ""),
    )
    _write(tmp_path, "locust_tpu/distributor/evil.py", """
        LIMIT = 64 * 1024 * 1024  # forked spelling of MAX_FRAME
    """)
    res = _run(tmp_path, ["R005"], ["locust_tpu"])
    assert len(res.new) == 1
    assert "MAX_FRAME" in res.new[0].message


def test_r005_fires_on_respelled_magic_bytes_anywhere(tmp_path):
    shutil.copy(
        os.path.join(REPO, "locust_tpu/distributor/protocol.py"),
        _write(tmp_path, "locust_tpu/distributor/protocol.py", ""),
    )
    _write(tmp_path, "scripts/sniff.py", """
        def is_binary(frame: bytes) -> bool:
            return frame.startswith(b"\\x00LB")
    """)
    res = _run(tmp_path, ["R005"], ["locust_tpu", "scripts"])
    assert len(res.new) == 1
    assert "BIN_MAGIC" in res.new[0].message


def test_r005_one_definer_respelling_anothers_magic_fires(tmp_path):
    # The definer exemption is PER-CONSTANT: serde may spell b"LKVB" but
    # not protocol's b"\x00LB" — cross-module skew between the two wire
    # modules is the likeliest fork of all.
    for rel in ("locust_tpu/distributor/protocol.py",
                "locust_tpu/io/serde.py"):
        dst = tmp_path / rel
        dst.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(os.path.join(REPO, rel), dst)
    assert not _run(tmp_path, ["R005"], ["locust_tpu"]).new  # faithful: green
    serde = tmp_path / "locust_tpu/io/serde.py"
    serde.write_text(
        serde.read_text()
        + '\n\ndef _sniff(frame):\n    return frame[:3] == b"\\x00LB"\n'
    )
    res = _run(tmp_path, ["R005"], ["locust_tpu"])
    assert len(res.new) == 1
    assert "BIN_MAGIC" in res.new[0].message


def test_r005_silent_on_imported_constant_and_out_of_layer_sizes(tmp_path):
    shutil.copy(
        os.path.join(REPO, "locust_tpu/distributor/protocol.py"),
        _write(tmp_path, "locust_tpu/distributor/protocol.py", ""),
    )
    _write(tmp_path, "locust_tpu/distributor/good.py", """
        from locust_tpu.distributor import protocol

        def cap(n):
            return min(n, protocol.MAX_FRAME)
    """)
    # 64 MiB as a CORPUS size outside the wire layer: legitimate.
    _write(tmp_path, "scripts/bench_thing.py", """
        TARGET_BYTES = 64 * 1024 * 1024
    """)
    assert not _run(tmp_path, ["R005"], ["locust_tpu", "scripts"]).new


# ------------------------------------------------------------------- R006


def test_r006_fires_on_unpinned_python_spawn(tmp_path):
    _write(tmp_path, "tests/test_x.py", """
        import subprocess
        import sys

        def test_child():
            subprocess.run([sys.executable, "-c", "print(1)"], timeout=5)
    """)
    res = _run(tmp_path, ["R006"], ["tests"])
    assert len(res.new) == 1
    assert "inherited environment" in res.new[0].message


def test_r006_fires_when_env_lacks_the_pins(tmp_path):
    _write(tmp_path, "scripts/go.py", """
        import os
        import subprocess
        import sys

        def launch():
            env = dict(os.environ)
            env["OTHER"] = "1"
            subprocess.run([sys.executable, "x.py"], env=env)
    """)
    res = _run(tmp_path, ["R006"], ["scripts"])
    assert len(res.new) == 1
    assert "JAX_PLATFORMS" in res.new[0].message


def test_r006_silent_on_pinned_env_wrapper_param_and_non_python(tmp_path):
    _write(tmp_path, "tests/test_ok.py", """
        import os
        import subprocess
        import sys

        def test_pinned(repo):
            env = dict(os.environ)
            env.update(JAX_PLATFORMS="cpu", PYTHONPATH=repo)
            subprocess.run([sys.executable, "-c", "pass"], env=env)

        def run_phase(cmd, env):
            # wrapper: callers own the pinning
            subprocess.run([sys.executable, *cmd], env=env)

        def test_git():
            subprocess.run(["git", "status"])
    """)
    assert not _run(tmp_path, ["R006"], ["tests"]).new


# ------------------------------------------------------------------- R007


def test_r007_fires_on_stray_stdout_print_and_double_emit(tmp_path):
    _write(tmp_path, "bench.py", """
        import json

        def main():
            print("starting up")
            print(json.dumps({"metric": "x"}))
            print(json.dumps({"metric": "again"}))
    """)
    res = _run(tmp_path, ["R007"], ["bench.py"])
    msgs = " | ".join(f.message for f in res.new)
    assert "outside the one-JSON-line contract" in msgs
    assert "exactly ONE print(json.dumps" in msgs


def test_r007_fires_on_flushed_literal_noise(tmp_path):
    # flush=True is not a free pass: a relay must print a CAPTURED value
    # (Name/Subscript), not a literal that adds a second stdout line.
    _write(tmp_path, "bench.py", """
        import json

        def main():
            print("sneaky stdout noise", flush=True)
            print(json.dumps({"metric": "x"}), flush=True)
    """)
    res = _run(tmp_path, ["R007"], ["bench.py"])
    assert len(res.new) == 1
    assert "outside the one-JSON-line contract" in res.new[0].message


def test_r007_silent_on_contract_shape(tmp_path):
    _write(tmp_path, "bench.py", """
        import json
        import sys

        def emit(payload):
            print(json.dumps(payload), flush=True)

        def main():
            print("[bench] progress", file=sys.stderr)
            line = '{"metric": 1}'
            print(line, flush=True)  # relay of a child's captured line
    """)
    assert not _run(tmp_path, ["R007"], ["bench.py"]).new


# ------------------------------------------------------------------- R008


def test_r008_tracked_junk_regex():
    from locust_tpu.analysis.rules_hygiene import _TRACKED_JUNK

    assert _TRACKED_JUNK.search("locust_tpu/__pycache__/engine.cpython-310.pyc")
    assert _TRACKED_JUNK.search("a/b/__pycache__/x.pyc")
    assert _TRACKED_JUNK.search("x/.pytest_cache/v/cache")
    assert _TRACKED_JUNK.search("mod.pyc")
    assert not _TRACKED_JUNK.search("locust_tpu/engine.py")
    assert not _TRACKED_JUNK.search("docs/cache_notes.md")


def test_r008_repo_has_no_tracked_artifacts_and_gitignore_covers():
    res = run_analysis(root=REPO, rules=["R008"])
    assert not res.new, [f.format() for f in res.new]


# ------------------------------------------------------------------- R009

_FIXTURE_OBS_NAMES = """
    NAMES = {
        "a.span": "span",
        "b.blocks": "counter",
        "c.fired": "event",
    }
"""


def _r009_tree(tmp_path, emitter=None, names=_FIXTURE_OBS_NAMES):
    _write(tmp_path, "locust_tpu/obs/names.py", names)
    _write(tmp_path, "locust_tpu/eng.py", emitter if emitter is not None else """
        from locust_tpu import obs

        def run():
            with obs.span("a.span", i=0):
                obs.metric_inc("b.blocks")
                obs.event("c.fired", site="x")
    """)


def test_r009_silent_when_registry_and_emitters_agree(tmp_path):
    _r009_tree(tmp_path)
    assert not _run(tmp_path, ["R009"], ["locust_tpu"]).new


def test_r009_fires_on_typod_emission_name(tmp_path):
    _r009_tree(tmp_path, emitter="""
        from locust_tpu import obs

        def run():
            with obs.span("a.spam"):   # typo'd
                obs.metric_inc("b.blocks")
                obs.event("c.fired")
    """)
    res = _run(tmp_path, ["R009"], ["locust_tpu"])
    msgs = " | ".join(f.message for f in res.new)
    assert "a.spam" in msgs and "not in the obs NAMES registry" in msgs
    # ...and the registered-but-now-unemitted 'a.span' fires the other side.
    assert "never emitted" in msgs and "'a.span'" in msgs


def test_r009_fires_on_kind_mismatch_and_unemitted_entry(tmp_path):
    _r009_tree(tmp_path, emitter="""
        from locust_tpu import obs

        def run():
            with obs.span("a.span"):
                obs.metric_observe("b.blocks", 1.0)  # counter as histogram
    """)
    res = _run(tmp_path, ["R009"], ["locust_tpu"])
    msgs = " | ".join(f.message for f in res.new)
    assert "kind drift" in msgs and "b.blocks" in msgs
    assert "never emitted" in msgs and "'c.fired'" in msgs


def test_r009_sees_span_at_as_a_span_emission(tmp_path):
    """A span recorded after the fact (obs.span_at, how jax's own compile
    time spans land) is an emission like any other: it satisfies the
    registry, a typo'd name fires, a non-span kind drifts."""
    _r009_tree(tmp_path, emitter="""
        from locust_tpu import obs

        def run(t0, t1):
            obs.span_at("a.span", t0, t1, fun_name="f")
            obs.metric_inc("b.blocks")
            obs.event("c.fired")
    """)
    assert not _run(tmp_path, ["R009"], ["locust_tpu"]).new
    _r009_tree(tmp_path, emitter="""
        from locust_tpu import obs

        def run(t0, t1):
            obs.span_at("a.spam", t0, t1)
            obs.span_at("b.blocks", t0, t1)
            obs.event("c.fired")
    """)
    msgs = " | ".join(
        f.message for f in _run(tmp_path, ["R009"], ["locust_tpu"]).new
    )
    assert "obs.span_at('a.spam', ...)" in msgs
    assert "kind drift" in msgs and "b.blocks" in msgs
    assert "never emitted" in msgs and "'a.span'" in msgs


def test_r009_ignores_non_obs_span_lookalikes(tmp_path):
    # SpanTimer.span("load") and other objects' .event(...) must never
    # be claimed by the rule — only the obs module-function convention.
    _r009_tree(tmp_path, emitter="""
        from locust_tpu import obs
        from locust_tpu.utils import SpanTimer

        def run(timer: SpanTimer, sock):
            with timer.span("load"):
                pass
            sock.event("connected")
            with obs.span("a.span"):
                obs.metric_inc("b.blocks")
                obs.event("c.fired")
    """)
    assert not _run(tmp_path, ["R009"], ["locust_tpu"]).new


def test_r009_missing_registry_is_one_loud_finding(tmp_path):
    _write(tmp_path, "locust_tpu/eng.py", """
        from locust_tpu import obs

        def run():
            obs.event("c.fired")
    """)
    res = _run(tmp_path, ["R009"], ["locust_tpu"])
    assert len(res.new) == 1
    assert "cannot parse the NAMES registry" in res.new[0].message


def test_r009_real_registry_mutation_fails_the_gate(tmp_path):
    """R004-style acceptance demo on the REAL tree: copy obs/names.py and
    the real emitters, register one phantom name — the gate must fail
    with exactly the never-emitted finding for it."""
    for rel in (
        "locust_tpu/obs/names.py",
        "locust_tpu/engine.py",
        "locust_tpu/io/snapshot.py",
        "locust_tpu/io/loader.py",      # emits sort.read
        "locust_tpu/io/serde.py",       # emits sort.write / sort.bytes_out
        "locust_tpu/utils/faultplan.py",
        "locust_tpu/distributor/master.py",
        "locust_tpu/distributor/worker.py",
        "locust_tpu/cli.py",
        "locust_tpu/cli_apps.py",       # emits pagerank.read, index.read / .render / .write, join.read / .render / .write
        "locust_tpu/apps/join.py",      # emits join.map / .h2d / .probe / .d2h and the join.* counters
        "locust_tpu/apps/inverted_index.py",  # emits index.map / .collect and the index.* counters
        "locust_tpu/obs/programs.py",  # emits engine.program.* via span_at
        "locust_tpu/serve/daemon.py",  # emits the serve.* spans/metrics
        "locust_tpu/serve/journal.py",  # emits serve.journal_ms
        "locust_tpu/serve/pool.py",     # emits serve.place/affinity_hits
        "locust_tpu/serve/replicate.py",  # emits serve.ship/ship_lag
        "locust_tpu/backend.py",        # emits the backend.breaker_* ladder
        "locust_tpu/plan/compile.py",   # emits plan.compile/plan.run
        "locust_tpu/plan/optimize.py",  # emits plan.optimize/plan.rewrites
        "locust_tpu/plan/distribute.py",  # emits plan.partition_bytes
        "locust_tpu/parallel/shuffle.py",  # emits the mesh.* spans/metrics
        "locust_tpu/parallel/record_sort.py",  # emits the sort.mesh.* spans/metrics
        "locust_tpu/ops/pallas/fused_fold.py",  # kernel: must stay name-free
    ):
        dst = tmp_path / rel
        dst.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(os.path.join(REPO, rel), dst)
    assert not _run(tmp_path, ["R009"], ["locust_tpu"]).new  # faithful: green

    np_ = tmp_path / "locust_tpu/obs/names.py"
    mutated = np_.read_text().replace(
        "NAMES = {", 'NAMES = {\n    "obs.phantom": "event",', 1
    )
    assert "obs.phantom" in mutated
    np_.write_text(mutated)
    res = _run(tmp_path, ["R009"], ["locust_tpu"])
    assert len(res.new) == 1
    assert "obs.phantom" in res.new[0].message
    assert "never emitted" in res.new[0].message


# ------------------------------------------- R001/R002 interprocedural

_R001_ENTRY = """
    import threading
    from locust_tpu.state import bump

    class Srv:
        def start(self):
            threading.Thread(target=self.worker, daemon=True).start()

        def worker(self):
            bump()
"""
_R001_HELPER = """
    total = 0

    def bump():
        global total
        total += 1
"""


def test_r001_cross_module_race_the_per_module_engine_missed(tmp_path):
    """The acceptance fixture: the thread entry lives in a.py, the
    unlocked global write in state.py.  Either file ALONE is silent —
    which is exactly what the old single-pass per-module engine saw —
    but the whole program is a finding, attributed to the write."""
    _write(tmp_path, "locust_tpu/a.py", _R001_ENTRY)
    _write(tmp_path, "locust_tpu/state.py", _R001_HELPER)
    # Per-module views (the old engine's blind spot): both silent.
    assert not _run(tmp_path, ["R001"], ["locust_tpu/a.py"]).new
    assert not _run(tmp_path, ["R001"], ["locust_tpu/state.py"]).new
    # Whole program: the race is visible, flagged AT the write.
    res = _run(tmp_path, ["R001"], ["locust_tpu"])
    assert len(res.new) == 1
    f = res.new[0]
    assert f.path == "locust_tpu/state.py"
    assert "total" in f.message and "worker" in f.message


def test_r001_same_module_call_chain_fires(tmp_path):
    _write(tmp_path, "mod.py", """
        import threading

        class Srv:
            def start(self):
                threading.Thread(target=self.loop, daemon=True).start()

            def loop(self):
                self.step()

            def step(self):
                self.count = 1
    """)
    res = _run(tmp_path, ["R001"], ["mod.py"])
    assert len(res.new) == 1
    assert "self.count" in res.new[0].message
    assert "loop -> step" in res.new[0].message


def test_r001_silent_when_lock_held_across_the_call(tmp_path):
    # The "caller holds self._lock" convention (daemon._corpus_put):
    # a call made inside `with <lock>:` covers the whole callee chain.
    _write(tmp_path, "mod.py", """
        import threading

        class Srv:
            def __init__(self):
                self._lock = threading.Lock()

            def start(self):
                threading.Thread(target=self.loop, daemon=True).start()

            def loop(self):
                with self._lock:
                    self.step()

            def step(self):
                self.count = 1
    """)
    assert not _run(tmp_path, ["R001"], ["mod.py"]).new


def test_r002_cross_module_impurity_in_traced_callee(tmp_path):
    _write(tmp_path, "locust_tpu/kernels.py", """
        import jax
        from locust_tpu.helpers import stamp

        def step(x):
            return stamp(x)

        step_j = jax.jit(step)
    """)
    _write(tmp_path, "locust_tpu/helpers.py", """
        import time

        def stamp(x):
            return x * time.time()
    """)
    # Alone, neither module shows the bug (the old engine's limit)...
    assert not _run(tmp_path, ["R002"], ["locust_tpu/kernels.py"]).new
    assert not _run(tmp_path, ["R002"], ["locust_tpu/helpers.py"]).new
    # ...together the traced body is followed into its callee.
    res = _run(tmp_path, ["R002"], ["locust_tpu"])
    assert len(res.new) == 1
    f = res.new[0]
    assert f.path == "locust_tpu/helpers.py"
    assert "time.time" in f.message and "step" in f.message


def test_r002_silent_on_pure_cross_module_callee(tmp_path):
    _write(tmp_path, "locust_tpu/kernels.py", """
        import jax
        from locust_tpu.helpers import double

        def step(x):
            return double(x)

        step_j = jax.jit(step)
    """)
    _write(tmp_path, "locust_tpu/helpers.py", """
        def double(x):
            return x * 2
    """)
    assert not _run(tmp_path, ["R002"], ["locust_tpu"]).new


# ------------------------------------------------------------------- R010

_R010_PRELUDE = """
    import jax
    import jax.numpy as jnp
    import numpy as np

    def fold(acc, blk):
        return acc

    fold_j = jax.jit(fold, donate_argnums=(0,))
"""


def test_r010_fires_on_donated_numpy_alias(tmp_path):
    _write(tmp_path, "locust_tpu/eng.py", _R010_PRELUDE + """
    def run(z, blk):
        acc = jnp.asarray(z["table"])  # zero-copy view of host memory
        acc = fold_j(acc, blk)
        return acc
    """)
    res = _run(tmp_path, ["R010"], ["locust_tpu"])
    assert len(res.new) == 1
    assert "alias" in res.new[0].message
    assert "copy=True" in res.new[0].message


def test_r010_fires_on_alias_through_a_helper_return(tmp_path):
    # The PR 5 incident shape: the alias is BORN in a loader helper and
    # donated by the caller — one call-graph hop apart.
    _write(tmp_path, "locust_tpu/eng.py", _R010_PRELUDE + """
    class Table:
        pass

    def load(z, acc):
        if z is not None:
            acc = Table(jnp.asarray(z["table"]))
        return 0, acc

    def run(z, blk):
        start, acc = load(z, None)
        acc = fold_j(acc, blk)
        return acc
    """)
    res = _run(tmp_path, ["R010"], ["locust_tpu"])
    assert len(res.new) == 1
    assert "alias" in res.new[0].message


def test_r010_fires_on_read_after_donate(tmp_path):
    _write(tmp_path, "locust_tpu/eng.py", _R010_PRELUDE + """
    def run(acc, blk):
        out = fold_j(acc, blk)
        return acc.sum() + out
    """)
    res = _run(tmp_path, ["R010"], ["locust_tpu"])
    assert len(res.new) == 1
    assert "read after being donated" in res.new[0].message


def test_r010_silent_on_copied_restore_and_rebinding_loop(tmp_path):
    # The sanctioned shapes: jnp.array(..., copy=True) owns the memory,
    # and the fold loop rebinds the accumulator every donation.
    _write(tmp_path, "locust_tpu/eng.py", _R010_PRELUDE + """
    def run(z, blocks):
        acc = jnp.array(z["table"], copy=True)
        for blk in blocks:
            acc = fold_j(acc, blk)
        jax.block_until_ready(acc)
        return acc
    """)
    assert not _run(tmp_path, ["R010"], ["locust_tpu"]).new


def test_r010_mutating_real_engine_restore_fails_the_gate(tmp_path):
    """The acceptance demo on the REAL donation site: engine._load_state
    materializes the restored table with jnp.array(..., copy=True)
    exactly because the first resumed fold donates it (the PR 5 heap
    corruption).  Reverting that fix to jnp.asarray must be FLAGGED —
    the old engine (no R010, no cross-function alias tracking) passed
    this exact bug into the tree."""
    dst = tmp_path / "locust_tpu/engine.py"
    dst.parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(os.path.join(REPO, "locust_tpu/engine.py"), dst)
    assert not _run(tmp_path, ["R010"], ["locust_tpu"]).new  # faithful: green

    text = dst.read_text()
    assert 'jnp.array(z["key_lanes"], copy=True)' in text
    dst.write_text(text.replace(
        'jnp.array(z["key_lanes"], copy=True)',
        'jnp.asarray(z["key_lanes"])',
    ))
    res = _run(tmp_path, ["R010"], ["locust_tpu"])
    assert res.new, "reverted copy=True fix must be flagged"
    assert all(f.path == "locust_tpu/engine.py" for f in res.new)
    assert any("alias" in f.message for f in res.new)


# ------------------------------------------------------------------- R011

_FIXTURE_JOBS = """
    ERROR_CODES = (
        "queue_full",
        "bad_spec",
    )

    def structured_error(code, message):
        if code not in ERROR_CODES:
            raise ValueError(f"unknown serve error code {code!r}")
        return {"status": "error", "code": code, "error": message}

    def parse_spec(req):
        if "corpus" not in req:
            raise ValueError("bad_spec\\nsubmit needs a corpus")
        return req
"""


def _r011_tree(tmp_path, daemon=None, jobs=_FIXTURE_JOBS,
               docs_text=None, tests_text=None):
    _write(tmp_path, "locust_tpu/serve/jobs.py", jobs)
    _write(tmp_path, "locust_tpu/serve/daemon.py", daemon if daemon is not None else """
        from locust_tpu.serve.jobs import structured_error

        def handle(req):
            if req is None:
                return structured_error("queue_full", "full")
            return {"status": "ok"}
    """)
    _write(tmp_path, "tests/test_serve.py",
           tests_text if tests_text is not None
           else '# exercises "queue_full" and "bad_spec"\n')
    (tmp_path / "docs").mkdir(exist_ok=True)
    (tmp_path / "docs" / "SERVING.md").write_text(
        docs_text if docs_text is not None
        else "| `queue_full` | ... |\n| `bad_spec` | ... |\n"
    )


def test_r011_silent_when_registry_emitters_docs_tests_agree(tmp_path):
    _r011_tree(tmp_path)
    assert not _run(tmp_path, ["R011"], ["locust_tpu", "tests"]).new


def test_r011_fires_on_unregistered_code_at_emission_site(tmp_path):
    _r011_tree(tmp_path, daemon="""
        from locust_tpu.serve.jobs import structured_error

        def handle(req):
            return structured_error("queue_fulll", "typo'd")
    """)
    res = _run(tmp_path, ["R011"], ["locust_tpu", "tests"])
    msgs = " | ".join(f.message for f in res.new)
    assert "queue_fulll" in msgs and "not in jobs.ERROR_CODES" in msgs
    # ...and the now-unemitted registered code fires the other side.
    assert "never emitted" in msgs


def test_r011_fires_on_valueerror_first_line_convention(tmp_path):
    # parse_spec's ValueError("code\\n...") shape is an emission site too.
    _r011_tree(tmp_path, jobs=_FIXTURE_JOBS.replace(
        '"bad_spec\\nsubmit needs a corpus"',
        '"bad_spce\\nsubmit needs a corpus"',
    ))
    res = _run(tmp_path, ["R011"], ["locust_tpu", "tests"])
    msgs = " | ".join(f.message for f in res.new)
    assert "bad_spce" in msgs and "not in jobs.ERROR_CODES" in msgs


def test_r011_fires_on_undocumented_and_untested_code(tmp_path):
    _r011_tree(tmp_path, docs_text="| `queue_full` |\n",
               tests_text='# only "queue_full" here\n')
    res = _run(tmp_path, ["R011"], ["locust_tpu", "tests"])
    msgs = " | ".join(f.message for f in res.new)
    assert "undocumented" in msgs and "never exercised" in msgs
    assert all("bad_spec" in f.message for f in res.new)


def test_r011_mutating_real_error_codes_fails_the_gate(tmp_path):
    """R004-style acceptance demo on the REAL serve tier: copy the
    registry + every emitting module + docs + suites, register one
    phantom code — the gate must fail with exactly the unemitted/
    undocumented/untested findings for it (the shutting_down /
    result_too_large / unknown_job review incidents, machine-checked)."""
    for rel in (
        "locust_tpu/serve/jobs.py",
        "locust_tpu/serve/daemon.py",
        "locust_tpu/serve/scheduler.py",
        "locust_tpu/serve/cache.py",
        "locust_tpu/serve/batch.py",
        "locust_tpu/serve/client.py",
        "locust_tpu/serve/replicate.py",  # emits stale_epoch
        "tests/test_serve.py",
        "tests/test_faults.py",
        "docs/SERVING.md",
    ):
        dst = tmp_path / rel
        dst.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(os.path.join(REPO, rel), dst)
    paths = ["locust_tpu", "tests"]
    assert not _run(tmp_path, ["R011"], paths).new  # faithful copy: green

    jp = tmp_path / "locust_tpu/serve/jobs.py"
    mutated = jp.read_text().replace(
        "ERROR_CODES = (", 'ERROR_CODES = (\n    "phantom_code",', 1
    )
    assert "phantom_code" in mutated
    jp.write_text(mutated)
    res = _run(tmp_path, ["R011"], paths)
    assert len(res.new) == 3  # unemitted + undocumented + untested
    assert all("phantom_code" in f.message for f in res.new)


# ------------------------------------------------------------------- R012


def test_r012_fires_on_unjoined_thread_and_unmanaged_executor(tmp_path):
    _write(tmp_path, "locust_tpu/svc.py", """
        import threading
        from concurrent.futures import ThreadPoolExecutor

        class Svc:
            def start(self):
                self._t = threading.Thread(target=self.run)
                self._t.start()
                self._pool = ThreadPoolExecutor(max_workers=2)

            def run(self):
                pass
    """)
    res = _run(tmp_path, ["R012"], ["locust_tpu"])
    msgs = " | ".join(f.message for f in res.new)
    assert len(res.new) == 2
    assert "never joined" in msgs and "no .shutdown" in msgs


def test_r012_fires_on_inline_started_non_daemon_thread(tmp_path):
    _write(tmp_path, "locust_tpu/svc.py", """
        import threading

        def kick(fn):
            threading.Thread(target=fn).start()
    """)
    res = _run(tmp_path, ["R012"], ["locust_tpu"])
    assert len(res.new) == 1
    assert "started inline" in res.new[0].message


def test_r012_silent_on_daemon_join_with_and_shutdown(tmp_path):
    _write(tmp_path, "locust_tpu/svc.py", """
        import threading
        from concurrent.futures import ThreadPoolExecutor

        class Svc:
            def start(self):
                self._t = threading.Thread(target=self.run, daemon=True)
                self._t.start()
                self._pool = ThreadPoolExecutor(max_workers=2)

            def close(self):
                self._pool.shutdown(wait=False)
                self._t.join(timeout=5.0)

            def run(self):
                pass

        def work(items):
            with ThreadPoolExecutor() as ex:
                return list(ex.map(str, items))

        def spawn_joined(fn):
            t = threading.Thread(target=fn)
            t.start()
            t.join()
    """)
    assert not _run(tmp_path, ["R012"], ["locust_tpu"]).new


def test_r012_ignores_tests_and_scripts(tmp_path):
    _write(tmp_path, "scripts/tool.py", """
        import threading

        def kick(fn):
            threading.Thread(target=fn).start()
    """)
    assert not _run(tmp_path, ["R012"], ["scripts"]).new


# --------------------------------------------------------- noqa + baseline


def test_noqa_with_reason_suppresses(tmp_path):
    _write(tmp_path, "locust_tpu/hot.py", """
        import jax

        def drain(blocks):
            for b in blocks:
                jax.block_until_ready(b)  # locust: noqa[R003] backpressure: bounded queue depth
    """)
    res = _run(tmp_path, ["R003"], ["locust_tpu"])
    assert not res.new and res.suppressed == 1


def test_noqa_without_reason_does_not_suppress_and_flags_itself(tmp_path):
    _write(tmp_path, "locust_tpu/hot.py", """
        import jax

        def drain(blocks):
            for b in blocks:
                jax.block_until_ready(b)  # locust: noqa[R003]
    """)
    res = _run(tmp_path, ["R003"], ["locust_tpu"])
    ids = sorted(f.rule_id for f in res.new)
    assert ids == ["R000", "R003"]
    assert "no reason" in next(
        f.message for f in res.new if f.rule_id == "R000"
    )


def test_noqa_for_a_different_rule_does_not_suppress(tmp_path):
    _write(tmp_path, "locust_tpu/hot.py", """
        import jax

        def drain(blocks):
            for b in blocks:
                jax.block_until_ready(b)  # locust: noqa[R005] wrong rule id
    """)
    res = _run(tmp_path, ["R003"], ["locust_tpu"])
    assert [f.rule_id for f in res.new] == ["R003"]


def test_baseline_roundtrip_suppresses_then_burns_down(tmp_path):
    src = _write(tmp_path, "locust_tpu/hot.py", """
        import jax

        def drain(blocks):
            for b in blocks:
                jax.block_until_ready(b)
    """)
    baseline = tmp_path / "baseline.json"
    res = run_analysis(paths=["locust_tpu"], root=str(tmp_path),
                       rules=["R003"], baseline_path=str(baseline))
    assert len(res.new) == 1
    write_baseline(str(baseline), res.findings)

    res2 = run_analysis(paths=["locust_tpu"], root=str(tmp_path),
                        rules=["R003"], baseline_path=str(baseline))
    assert not res2.new
    assert len(res2.findings) == 1 and res2.findings[0].baselined

    # Fixing the finding leaves a stale baseline entry, not a failure.
    src.write_text("import jax\n")
    res3 = run_analysis(paths=["locust_tpu"], root=str(tmp_path),
                        rules=["R003"], baseline_path=str(baseline))
    assert not res3.findings


def test_baseline_survives_unrelated_line_drift(tmp_path):
    code = """
        import jax

        def drain(blocks):
            for b in blocks:
                jax.block_until_ready(b)
    """
    src = _write(tmp_path, "locust_tpu/hot.py", code)
    baseline = tmp_path / "baseline.json"
    res = run_analysis(paths=["locust_tpu"], root=str(tmp_path),
                       rules=["R003"], baseline_path=str(baseline))
    write_baseline(str(baseline), res.findings)
    src.write_text("# a new header comment\n" + textwrap.dedent(code))
    res2 = run_analysis(paths=["locust_tpu"], root=str(tmp_path),
                        rules=["R003"], baseline_path=str(baseline))
    assert not res2.new and res2.findings[0].baselined


def test_r000_is_never_baselineable(tmp_path):
    # Even a baseline that CONTAINS an R000 fingerprint (hand-edited or
    # written by an old tool) must not accept it: fix the parse error /
    # write the noqa reason instead.
    _write(tmp_path, "locust_tpu/hot.py", """
        import jax

        def drain(blocks):
            for b in blocks:
                jax.block_until_ready(b)  # locust: noqa[R003]
    """)
    baseline = tmp_path / "baseline.json"
    res = run_analysis(paths=["locust_tpu"], root=str(tmp_path),
                       rules=["R003"], baseline_path=str(baseline))
    assert sorted(f.rule_id for f in res.new) == ["R000", "R003"]
    write_baseline(str(baseline), res.findings)  # includes R000 on purpose
    res2 = run_analysis(paths=["locust_tpu"], root=str(tmp_path),
                        rules=["R003"], baseline_path=str(baseline))
    assert [f.rule_id for f in res2.new] == ["R000"]


def test_config_fallback_parser_handles_multiline_arrays(tmp_path):
    # The py3.10 fallback must read the same config tomllib would: a
    # maintainer wrapping the paths array must not silently revert the
    # gate to DEFAULTS on 3.10 while 3.11 reads the new value.
    from locust_tpu.analysis.config import _parse_section_fallback

    section = _parse_section_fallback(textwrap.dedent("""
        [tool.other]
        paths = ["decoy"]

        [tool.locust-analysis]
        # comment line
        paths = [
          "locust_tpu",
          "extras",
        ]
        baseline = "b.json"

        [tool.after]
        baseline = "decoy.json"
    """))
    assert section == {"paths": ["locust_tpu", "extras"],
                       "baseline": "b.json"}


def test_syntax_error_is_a_finding_not_a_crash(tmp_path):
    _write(tmp_path, "locust_tpu/broken.py", "def f(:\n")
    res = _run(tmp_path, ["R003"], ["locust_tpu"])
    assert [f.rule_id for f in res.new] == ["R000"]
    assert "does not parse" in res.new[0].message


# ------------------------------------------------------------------- R013


def test_r013_fires_on_unbounded_blocking_calls(tmp_path):
    _write(tmp_path, "locust_tpu/serve/svc.py", """
        import socket
        import threading

        def serve(sock_holder):
            conn, _ = sock_holder.sock.accept()   # no settimeout in scope
            return conn

        def wait_all(threads, ev, fut):
            for t in threads:
                t.join()            # unbounded
            ev.wait()               # unbounded
            return fut.result()     # unbounded
    """)
    res = _run(tmp_path, ["R013"], ["locust_tpu"])
    assert len(res.new) == 4
    msgs = " | ".join(f.message for f in res.new)
    assert ".accept()" in msgs and ".join()" in msgs
    assert ".wait()" in msgs and ".result()" in msgs


def test_r013_silent_on_bounded_and_trusted_forms(tmp_path):
    _write(tmp_path, "locust_tpu/distributor/svc.py", """
        import os
        import socket

        def recv_exact(sock, n):
            return sock.recv(n)      # param socket: caller owns deadline

        def serve(self):
            self._sock.settimeout(0.5)
            conn, _ = self._sock.accept()   # settimeout in scope
            return conn

        def bounded(t, ev, fut, timeout):
            t.join(timeout=5.0)
            ev.wait(timeout)
            fut.result(timeout=timeout)
            return os.path.join("a", "b") + ",".join(["x", "y"])
    """)
    assert not _run(tmp_path, ["R013"], ["locust_tpu"]).new


def test_r013_ignores_files_outside_daemon_tiers(tmp_path):
    _write(tmp_path, "locust_tpu/engine2.py", """
        def wait_all(ev):
            ev.wait()
    """)
    _write(tmp_path, "tests/test_x.py", """
        def wait_all(ev):
            ev.wait()
    """)
    assert not _run(tmp_path, ["R013"], ["locust_tpu", "tests"]).new


def test_r013_reason_noqa_suppresses(tmp_path):
    _write(tmp_path, "locust_tpu/serve/svc.py", """
        def drain(ev):
            ev.wait()  # locust: noqa[R013] deliberate forever-wait: owner kills the process
    """)
    res = _run(tmp_path, ["R013"], ["locust_tpu"])
    assert not res.new and res.suppressed == 1


# ------------------------------------------------------------------- R014

_FIXTURE_PLAN_NODES = """
    NODE_KINDS = (
        "source",
        "sink",
    )

    def node(node_id, kind, op, inputs=(), **params):
        return (node_id, kind, op, tuple(inputs), tuple(params.items()))
"""


_FIXTURE_PLAN_DISTRIBUTE = """
    SOLO_ONLY = ()

    def shape(n):
        if n.kind == "source":
            return "dist-source"
        if n.kind == "sink":
            return "dist-sink"
        return None
"""


def _r014_tree(tmp_path, compile_src=None, nodes=_FIXTURE_PLAN_NODES,
               docs_text=None, tests_text=None,
               distribute_src=_FIXTURE_PLAN_DISTRIBUTE):
    _write(tmp_path, "locust_tpu/plan/nodes.py", nodes)
    _write(
        tmp_path, "locust_tpu/plan/compile.py",
        compile_src if compile_src is not None else """
        def lower(n):
            if n.kind == "source":
                return "stage-source"
            if n.kind == "sink":
                return "stage-sink"
            raise ValueError(n.kind)
    """)
    _write(tmp_path, "locust_tpu/plan/distribute.py", distribute_src)
    _write(tmp_path, "tests/test_plan.py",
           tests_text if tests_text is not None
           else '# exercises "source" and "sink"\n')
    (tmp_path / "docs").mkdir(exist_ok=True)
    (tmp_path / "docs" / "PLAN.md").write_text(
        docs_text if docs_text is not None
        else "| `source` | ... |\n| `sink` | ... |\n"
    )


def test_r014_silent_when_registry_compiler_docs_tests_agree(tmp_path):
    _r014_tree(tmp_path)
    assert not _run(tmp_path, ["R014"], ["locust_tpu", "tests"]).new


def test_r014_fires_on_unregistered_kind_at_construction_site(tmp_path):
    # A typo'd kind in a node(...) construction anywhere in locust_tpu/.
    _write(tmp_path, "locust_tpu/builders.py", """
        from locust_tpu.plan.nodes import node

        def broken_plan():
            return [node("a", "sorce", "text")]
    """)
    _r014_tree(tmp_path)
    res = _run(tmp_path, ["R014"], ["locust_tpu", "tests"])
    msgs = " | ".join(f.message for f in res.new)
    assert "sorce" in msgs and "not in" in msgs and "NODE_KINDS" in msgs


def test_r014_fires_on_unregistered_kind_match_in_plan_layer(tmp_path):
    # A matcher arm for an unregistered kind inside locust_tpu/plan/.
    _r014_tree(tmp_path, compile_src="""
        def lower(n):
            if n.kind == "source":
                return "stage-source"
            if n.kind == "sink":
                return "stage-sink"
            if n.kind == "window":
                return "stage-window"
            raise ValueError(n.kind)
    """)
    res = _run(tmp_path, ["R014"], ["locust_tpu", "tests"])
    msgs = " | ".join(f.message for f in res.new)
    assert "window" in msgs and "NODE_KINDS" in msgs


def test_r014_kind_match_outside_plan_layer_not_attributed(tmp_path):
    # Attribution discipline: `.kind` is a common attribute name — a
    # comparison in a NON-plan module (the analyzer's own thread
    # summaries use s.kind == "thread") must not be claimed as a plan
    # kind.  Construction calls stay checked repo-wide.
    _write(tmp_path, "locust_tpu/other.py", """
        def classify(s):
            return s.kind == "thread"
    """)
    _r014_tree(tmp_path)
    assert not _run(tmp_path, ["R014"], ["locust_tpu", "tests"]).new


def test_r014_fires_on_uncompiled_untested_undocumented_kind(tmp_path):
    _r014_tree(
        tmp_path,
        nodes=_FIXTURE_PLAN_NODES.replace(
            '"source",', '"source",\n        "window",'
        ),
    )
    res = _run(tmp_path, ["R014"], ["locust_tpu", "tests"])
    msgs = " | ".join(f.message for f in res.new)
    assert "never lowered" in msgs
    assert "never exercised" in msgs
    assert "undocumented" in msgs
    assert "neither matched" in msgs  # the distribute-coverage side
    assert all("window" in f.message for f in res.new)
    assert len(res.new) == 4


def test_r014_analyzer_suite_quotes_do_not_count_as_coverage(tmp_path):
    """A kind quoted ONLY in tests/test_analysis.py (the rule's own
    fixtures quote phantom kinds to test the RULE) must still fire
    'never exercised' — otherwise a real future kind named after a
    fixture literal would read as covered forever (review finding)."""
    _r014_tree(
        tmp_path,
        nodes=_FIXTURE_PLAN_NODES.replace(
            '"source",', '"source",\n        "window",'
        ),
        compile_src="""
        def lower(n):
            if n.kind == "source":
                return "s"
            if n.kind == "sink":
                return "k"
            if n.kind == "window":
                return "w"
            raise ValueError(n.kind)
    """,
        docs_text="| `source` | `sink` | `window` |\n",
        distribute_src=_FIXTURE_PLAN_DISTRIBUTE.replace(
            '"sink":', '"window" or n.kind == "sink":'
        ),
    )
    _write(tmp_path, "tests/test_analysis.py",
           '# quotes "window" in a rule fixture, not a plan test\n')
    res = _run(tmp_path, ["R014"], ["locust_tpu", "tests"])
    assert len(res.new) == 1
    assert "never exercised" in res.new[0].message
    assert "window" in res.new[0].message


def test_r014_missing_registry_reports_once(tmp_path):
    _r014_tree(tmp_path, nodes="KINDS = ()\n")
    res = _run(tmp_path, ["R014"], ["locust_tpu", "tests"])
    assert len(res.new) == 1
    assert "cannot parse the NODE_KINDS registry" in res.new[0].message


def test_r014_mutating_real_node_kinds_fails_the_gate(tmp_path):
    """R004/R011-style acceptance demo on the REAL plan layer: copy the
    registry + compiler + suite + docs, register one phantom kind — the
    gate must fail with exactly the unlowered/untested/undocumented
    findings for it (the drift ROADMAP item 4's new operators would
    otherwise introduce, machine-checked)."""
    for rel in (
        "locust_tpu/plan/nodes.py",
        "locust_tpu/plan/compile.py",
        "locust_tpu/plan/distribute.py",
        "locust_tpu/plan/builders.py",
        "tests/test_plan.py",
        "docs/PLAN.md",
    ):
        dst = tmp_path / rel
        dst.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(os.path.join(REPO, rel), dst)
    paths = ["locust_tpu", "tests"]
    assert not _run(tmp_path, ["R014"], paths).new  # faithful copy: green

    np_ = tmp_path / "locust_tpu/plan/nodes.py"
    mutated = np_.read_text().replace(
        'NODE_KINDS = (\n    "source",',
        'NODE_KINDS = (\n    "window",\n    "source",', 1,
    )
    assert '"window"' in mutated
    np_.write_text(mutated)
    res = _run(tmp_path, ["R014"], paths)
    # unlowered + untested + undocumented + undistributed
    assert len(res.new) == 4
    assert all("window" in f.message for f in res.new)


def test_r014_solo_only_registry_covers_an_unmatched_kind(tmp_path):
    """The distribute-coverage escape hatch: a kind distribute.py never
    matches is green IF (and only if) it sits in SOLO_ONLY."""
    nodes = _FIXTURE_PLAN_NODES.replace(
        '"source",', '"source",\n        "window",'
    )
    compile_src = """
        def lower(n):
            if n.kind == "source":
                return "s"
            if n.kind == "sink":
                return "k"
            if n.kind == "window":
                return "w"
            raise ValueError(n.kind)
    """
    kw = dict(
        nodes=nodes, compile_src=compile_src,
        docs_text="| `source` | `sink` | `window` |\n",
        tests_text='# exercises "source", "sink" and "window"\n',
    )
    _r014_tree(tmp_path, **kw)
    res = _run(tmp_path, ["R014"], ["locust_tpu", "tests"])
    assert len(res.new) == 1
    assert "neither matched" in res.new[0].message
    assert "window" in res.new[0].message
    _r014_tree(tmp_path, distribute_src=_FIXTURE_PLAN_DISTRIBUTE.replace(
        "SOLO_ONLY = ()", 'SOLO_ONLY = ("window",)'
    ), **kw)
    assert not _run(tmp_path, ["R014"], ["locust_tpu", "tests"]).new


def test_r014_fires_on_stale_and_unknown_solo_only_entries(tmp_path):
    # Stale: "sink" is exempted AND matched in distribute.py.  Unknown:
    # "ghost" is not a NODE_KINDS entry at all.
    _r014_tree(tmp_path, distribute_src=_FIXTURE_PLAN_DISTRIBUTE.replace(
        "SOLO_ONLY = ()", 'SOLO_ONLY = ("sink", "ghost")'
    ))
    res = _run(tmp_path, ["R014"], ["locust_tpu", "tests"])
    msgs = " | ".join(f.message for f in res.new)
    assert len(res.new) == 2
    assert "stale" in msgs and "sink" in msgs
    assert "ghost" in msgs and "not a NODE_KINDS entry" in msgs


def test_r014_missing_solo_only_registry_reports_once(tmp_path):
    _r014_tree(tmp_path, distribute_src="""
        def shape(n):
            if n.kind == "source":
                return "dist-source"
            if n.kind == "sink":
                return "dist-sink"
            return None
    """)
    res = _run(tmp_path, ["R014"], ["locust_tpu", "tests"])
    assert len(res.new) == 1
    assert "cannot parse the SOLO_ONLY registry" in res.new[0].message


# ------------------------------------------------------------------- R015

_FIXTURE_OPTIMIZE = """
    REWRITE_RULES = (
        "fuse_two",
        "drop_noop",
    )

    def record_rewrite(rule):
        if rule not in REWRITE_RULES:
            raise ValueError(rule)

    def fuse(applied):
        record_rewrite("fuse_two")
        applied.append("fuse_two")

    def drop(applied):
        record_rewrite("drop_noop")
        applied.append("drop_noop")
"""


def _r015_tree(tmp_path, optimize_src=None, docs_text=None,
               tests_text=None):
    _write(tmp_path, "locust_tpu/plan/optimize.py",
           optimize_src if optimize_src is not None else _FIXTURE_OPTIMIZE)
    _write(tmp_path, "tests/test_plan_optimize.py",
           tests_text if tests_text is not None
           else '# exercises "fuse_two" and "drop_noop"\n')
    (tmp_path / "docs").mkdir(exist_ok=True)
    (tmp_path / "docs" / "PLAN.md").write_text(
        docs_text if docs_text is not None
        else "| `fuse_two` | ... |\n| `drop_noop` | ... |\n"
    )


def test_r015_silent_when_registry_applied_docs_tests_agree(tmp_path):
    _r015_tree(tmp_path)
    assert not _run(tmp_path, ["R015"], ["locust_tpu", "tests"]).new


def test_r015_fires_on_unregistered_rule_at_firing_site(tmp_path):
    # A typo'd rule id passed to record_rewrite anywhere in locust_tpu/.
    _r015_tree(tmp_path)
    _write(tmp_path, "locust_tpu/plan/compile.py", """
        from locust_tpu.plan.optimize import record_rewrite

        def lower():
            record_rewrite("fuse_twoo")
    """)
    res = _run(tmp_path, ["R015"], ["locust_tpu", "tests"])
    msgs = " | ".join(f.message for f in res.new)
    assert "fuse_twoo" in msgs and "not in REWRITE_RULES" in msgs


def test_r015_fires_on_unapplied_untested_undocumented_rule(tmp_path):
    _r015_tree(
        tmp_path,
        optimize_src=_FIXTURE_OPTIMIZE.replace(
            '"fuse_two",', '"fuse_two",\n        "hoist_sink",'
        ),
    )
    res = _run(tmp_path, ["R015"], ["locust_tpu", "tests"])
    msgs = " | ".join(f.message for f in res.new)
    assert "never applied" in msgs
    assert "never exercised" in msgs
    assert "undocumented" in msgs
    assert all("hoist_sink" in f.message for f in res.new)
    assert len(res.new) == 3


def test_r015_registry_literals_are_not_applied_evidence(tmp_path):
    """The registry tuple's own literals must NOT count as application
    sites — otherwise registering a rule would self-certify it as
    applied and the 'dead contract' arm could never fire."""
    _r015_tree(
        tmp_path,
        optimize_src="""
        REWRITE_RULES = (
            "fuse_two",
        )
    """,
        docs_text="| `fuse_two` |\n",
        tests_text='# quotes "fuse_two"\n',
    )
    res = _run(tmp_path, ["R015"], ["locust_tpu", "tests"])
    assert len(res.new) == 1
    assert "never applied" in res.new[0].message


def test_r015_analyzer_suite_quotes_do_not_count_as_coverage(tmp_path):
    # Same exclusion as R014: phantom ids quoted in the analyzer's own
    # fixtures are rule tests, not rewrite coverage.
    _r015_tree(
        tmp_path,
        optimize_src=_FIXTURE_OPTIMIZE.replace(
            '"fuse_two",', '"fuse_two",\n        "hoist_sink",'
        ).replace(
            'record_rewrite("fuse_two")',
            'record_rewrite("fuse_two")\n        '
            'record_rewrite("hoist_sink")',
        ),
        docs_text="| `fuse_two` | `drop_noop` | `hoist_sink` |\n",
    )
    _write(tmp_path, "tests/test_analysis.py",
           '# quotes "hoist_sink" in a rule fixture, not a plan test\n')
    res = _run(tmp_path, ["R015"], ["locust_tpu", "tests"])
    assert len(res.new) == 1
    assert "never exercised" in res.new[0].message
    assert "hoist_sink" in res.new[0].message


def test_r015_missing_registry_reports_once(tmp_path):
    _r015_tree(tmp_path, optimize_src="RULES = ()\n")
    res = _run(tmp_path, ["R015"], ["locust_tpu", "tests"])
    assert len(res.new) == 1
    assert "cannot parse the REWRITE_RULES registry" in res.new[0].message


def test_r015_mutating_real_rewrite_rules_fails_the_gate(tmp_path):
    """Acceptance demo on the REAL optimizer: copy the registry module +
    suite + docs, register one phantom rule — the gate must fail with
    exactly the unapplied/untested/undocumented findings for it."""
    for rel in (
        "locust_tpu/plan/optimize.py",
        "locust_tpu/plan/nodes.py",
        "tests/test_plan_optimize.py",
        "docs/PLAN.md",
    ):
        dst = tmp_path / rel
        dst.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(os.path.join(REPO, rel), dst)
    paths = ["locust_tpu", "tests"]
    assert not _run(tmp_path, ["R015"], paths).new  # faithful copy: green

    op = tmp_path / "locust_tpu/plan/optimize.py"
    mutated = op.read_text().replace(
        'REWRITE_RULES = (\n    "fuse_fold_kernel",',
        'REWRITE_RULES = (\n    "hoist_sink",\n    "fuse_fold_kernel",', 1,
    )
    assert '"hoist_sink"' in mutated
    op.write_text(mutated)
    res = _run(tmp_path, ["R015"], paths)
    assert len(res.new) == 3  # unapplied + untested + undocumented
    assert all("hoist_sink" in f.message for f in res.new)


# ------------------------------------------- R016/R017/R018 (rpcflow)

# A minimal but REAL-shaped rpc tier at the canonical rel paths the
# default registries point at: a protocol module owning the command
# tuples + the framing leaf, a dispatcher, and a client whose payloads
# ride a helper one module away (the rpcflow fixpoint under test).

_RPC_PROTOCOL = """
    EPOCH_KEY = "_epoch"
    COMMANDS = ("ping", "work")
    SHIP_COMMANDS = ("ship",)

    def send_frame(sock, payload, secret):
        return payload
"""

_RPC_WORKER = """
    def handle(req):
        cmd = req.get("cmd")
        if cmd == "ping":
            return {"status": "ok", "pong": True}
        if cmd == "work":
            blocks = req["blocks"]
            return {"status": "ok", "done": len(blocks)}
        if cmd == "ship":
            rec = req["rec"]
            return {"status": "ok", "applied": bool(rec)}
        return {"status": "error"}
"""

_RPC_CLIENT = """
    from locust_tpu.distributor import protocol

    def rpc(sock, payload, secret):
        protocol.send_frame(sock, payload, secret)
        return {"status": "ok"}

    def do_ping(sock, secret):
        rep = rpc(sock, {"cmd": "ping"}, secret)
        return rep.get("pong")

    def do_work(sock, blocks, secret):
        req = {"cmd": "work", "blocks": blocks}
        rep = rpc(sock, req, secret)
        return rep.get("done")

    def do_ship(sock, rec, epoch, secret):
        req = {"cmd": "ship", "rec": rec}
        req[protocol.EPOCH_KEY] = epoch
        rep = rpc(sock, req, secret)
        return rep.get("status")
"""


def _rpc_tree(tmp_path, client=_RPC_CLIENT, worker=_RPC_WORKER,
              protocol=_RPC_PROTOCOL):
    _write(tmp_path, "locust_tpu/distributor/protocol.py", protocol)
    _write(tmp_path, "locust_tpu/distributor/worker.py", worker)
    _write(tmp_path, "locust_tpu/serve/client.py", client)
    return ["locust_tpu"]


def test_r016_silent_on_agreeing_schemas(tmp_path):
    paths = _rpc_tree(tmp_path)
    assert not _run(tmp_path, ["R016"], paths).new


def test_r016_fires_on_typoed_send_cmd_never_baselineable(tmp_path):
    paths = _rpc_tree(
        tmp_path,
        client=_RPC_CLIENT.replace('{"cmd": "ping"}', '{"cmd": "pingg"}'),
    )
    res = _run(tmp_path, ["R016"], paths)
    assert len(res.new) == 1
    f = res.new[0]
    assert "pingg" in f.message and "registry" in f.message
    assert f.path == "locust_tpu/serve/client.py"
    assert f.baselineable is False


def test_r016_fires_on_registered_cmd_with_no_arm(tmp_path):
    paths = _rpc_tree(
        tmp_path,
        protocol=_RPC_PROTOCOL.replace(
            '("ping", "work")', '("ping", "work", "orphan")'
        ),
        client=_RPC_CLIENT + """
    def do_orphan(sock, secret):
        return rpc(sock, {"cmd": "orphan"}, secret)
""",
    )
    res = _run(tmp_path, ["R016"], paths)
    assert len(res.new) == 1
    assert "orphan" in res.new[0].message
    assert "no" in res.new[0].message and "arm" in res.new[0].message
    assert res.new[0].baselineable is False


def test_r016_fires_on_required_read_no_sender_supplies(tmp_path):
    # do_work stops sending "blocks"; the handler's req["blocks"] now
    # raises KeyError on every request — the finding lands at the ARM.
    paths = _rpc_tree(
        tmp_path,
        client=_RPC_CLIENT.replace(
            '{"cmd": "work", "blocks": blocks}', '{"cmd": "work"}'
        ),
    )
    res = _run(tmp_path, ["R016"], paths)
    assert len(res.new) == 1
    assert "'blocks'" in res.new[0].message
    assert res.new[0].path == "locust_tpu/distributor/worker.py"


def test_r016_fires_on_dead_payload_key(tmp_path):
    paths = _rpc_tree(
        tmp_path,
        client=_RPC_CLIENT.replace(
            '{"cmd": "work", "blocks": blocks}',
            '{"cmd": "work", "blocks": blocks, "junk": 1}',
        ),
    )
    res = _run(tmp_path, ["R016"], paths)
    assert len(res.new) == 1
    assert "dead payload key 'junk'" in res.new[0].message
    assert res.new[0].path == "locust_tpu/serve/client.py"


def test_r016_fires_on_reply_key_no_arm_produces(tmp_path):
    paths = _rpc_tree(
        tmp_path,
        client=_RPC_CLIENT.replace(
            'rep.get("pong")', 'rep.get("pongg")'
        ),
    )
    res = _run(tmp_path, ["R016"], paths)
    assert len(res.new) == 1
    assert "reply key 'pongg'" in res.new[0].message


def test_r016_fires_on_unfenced_ship_plane_send(tmp_path):
    # Drop the epoch from the SHIP_COMMANDS-plane send: fencing drift.
    paths = _rpc_tree(
        tmp_path,
        client=_RPC_CLIENT.replace(
            "        req[protocol.EPOCH_KEY] = epoch\n", ""
        ),
    )
    res = _run(tmp_path, ["R016"], paths)
    assert len(res.new) == 1
    assert "epoch-fenced cmd 'ship'" in res.new[0].message


def test_r016_mutating_real_modules_fails_the_gate(tmp_path):
    """The acceptance demo on the REAL tree: copy serve/ + distributor/,
    green as-is; a typo'd send-site cmd and an unfenced ship-plane send
    each provably fail the gate."""
    for pkg in ("serve", "distributor"):
        shutil.copytree(
            os.path.join(REPO, "locust_tpu", pkg),
            tmp_path / "locust_tpu" / pkg,
            ignore=shutil.ignore_patterns("__pycache__"),
        )
    paths = ["locust_tpu"]
    assert not _run(tmp_path, ["R016"], paths).new  # faithful copy: green

    cp = tmp_path / "locust_tpu/serve/client.py"
    orig = cp.read_text()
    assert '{"cmd": "ping"}' in orig
    cp.write_text(orig.replace('{"cmd": "ping"}', '{"cmd": "pingg"}', 1))
    res = _run(tmp_path, ["R016"], paths)
    assert [f.path for f in res.new] == ["locust_tpu/serve/client.py"]
    assert "pingg" in res.new[0].message
    assert res.new[0].baselineable is False
    cp.write_text(orig)

    rp = tmp_path / "locust_tpu/serve/replicate.py"
    fenced = '"cmd": "ship",\n                ' \
        "protocol.EPOCH_KEY: int(self._epoch_fn()),"
    text = rp.read_text()
    assert fenced in text
    rp.write_text(text.replace(fenced, '"cmd": "ship",', 1))
    res = _run(tmp_path, ["R016"], paths)
    assert [f.path for f in res.new] == ["locust_tpu/serve/replicate.py"]
    assert "epoch-fenced cmd 'ship'" in res.new[0].message


def test_rpcflow_resolves_helper_indirection_on_real_tree():
    """Satellite pin: the facts behind R016 on the actual repo.  The
    pool's serve_batch dispatch builds its payload in a local dict
    assignments before handing it to the ``rpc`` helper, which forwards
    into ``_rpc_one``/``send_frame``; the handler arm lives across the
    module boundary in distributor/worker.py.  rpcflow must resolve the
    whole chain CLOSED — every required handler key provably supplied."""
    from locust_tpu.analysis import rpcflow, rules_rpc
    from locust_tpu.analysis.core import load_files
    from locust_tpu.analysis.summaries import build_program

    files = load_files(["locust_tpu"], REPO)
    program = build_program([f for f in files if f.tree is not None], REPO)
    rp = rpcflow.get(
        program, rules_rpc.DEFAULT_SCOPE, rules_rpc.DEFAULT_REGISTRIES,
        rules_rpc.DEFAULT_SEEDS,
    )

    sites = [
        s for s in rp.sites_by_cmd.get("serve_batch", []) if not s.synthetic
    ]
    assert len(sites) == 1
    s = sites[0]
    assert s.rel == "locust_tpu/serve/pool.py"
    assert not s.payload.open
    # Payload keys resolved through the earlier `payload = {...}` /
    # `payload[...] = ...` assignments, then through the helper hop.
    assert {"bucket", "jobs", "spill_dir"} <= s.payload.all_keys()
    assert "_epoch" in s.payload.all_keys()
    chain = [fn.name for fn in s.fns]
    assert "rpc" in chain and "_rpc_one" in chain  # helper indirection

    arms = rp.arm_index["serve_batch"]
    assert [a.rel for a in arms] == ["locust_tpu/distributor/worker.py"]
    a = arms[0]
    assert not a.open_reads
    # Two-sided closure: what the handler demands, the sender carries.
    assert a.required - rpcflow.WIRE_META_KEYS <= s.payload.all_keys()

    # And the client.py submit path (payload built two assignments
    # early, three-deep helper chain _rpc_ok -> rpc -> _rpc_one).
    sub = [
        s for s in rp.sites_by_cmd.get("submit", [])
        if s.rel == "locust_tpu/serve/client.py"
    ]
    assert len(sub) == 1 and not sub[0].payload.open
    assert {"tenant", "weight"} <= sub[0].payload.keys
    assert "corpus_b64" in sub[0].payload.cond  # If-guarded add
    assert [a.rel for a in rp.arm_index["submit"]] == \
        ["locust_tpu/serve/daemon.py"] * len(rp.arm_index["submit"])


def test_write_baseline_refuses_phantom_cmds(tmp_path):
    """`--write-baseline` must never bury a dead RPC: a phantom-cmd
    finding (baselineable=False) refuses the whole write, exit 2."""
    _rpc_tree(
        tmp_path,
        client=_RPC_CLIENT.replace('{"cmd": "ping"}', '{"cmd": "pingg"}'),
    )
    _write(tmp_path, "pyproject.toml", """
        [tool.locust-analysis]
        paths = ["locust_tpu"]
    """)
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO}
    proc = subprocess.run(
        [sys.executable, "-m", "locust_tpu.analysis", "--root",
         str(tmp_path), "--rule", "R016", "--write-baseline"],
        cwd=str(tmp_path), env=env, capture_output=True, text=True,
        timeout=120,
    )
    assert proc.returncode == 2, proc.stdout + proc.stderr
    assert "refusing" in proc.stderr and "pingg" in proc.stderr
    assert not (tmp_path / "analysis_baseline.json").exists()


def test_r017_fires_on_silent_broad_swallow(tmp_path):
    _write(tmp_path, "locust_tpu/mod.py", """
        def poll(q):
            try:
                q.drain()
            except Exception:
                pass
    """)
    res = _run(tmp_path, ["R017"], ["locust_tpu"])
    assert len(res.new) == 1
    assert "swallows" in res.new[0].message


def test_r017_silent_when_swallow_logs_or_uses_exception(tmp_path):
    _write(tmp_path, "locust_tpu/mod.py", """
        import logging

        logger = logging.getLogger(__name__)

        def poll(q):
            try:
                q.drain()
            except Exception:
                logger.warning("drain failed; retrying", exc_info=True)

        def classify(q):
            try:
                q.drain()
            except Exception as e:
                q.last_error = e
    """)
    assert not _run(tmp_path, ["R017"], ["locust_tpu"]).new


def test_r017_fires_on_unprotected_thread_entry(tmp_path):
    _write(tmp_path, "locust_tpu/mod.py", """
        import threading

        def loop(q):
            while True:
                q.step()

        def start(q):
            threading.Thread(target=loop, args=(q,), daemon=True).start()
    """)
    res = _run(tmp_path, ["R017"], ["locust_tpu"])
    assert len(res.new) == 1
    assert "thread entry 'loop'" in res.new[0].message


def test_r017_silent_when_entry_protected_one_hop_away(tmp_path):
    _write(tmp_path, "locust_tpu/mod.py", """
        import logging
        import threading

        logger = logging.getLogger(__name__)

        def loop(q):
            while True:
                _safe_step(q)

        def _safe_step(q):
            try:
                q.step()
            except Exception:
                logger.warning("step failed; loop stays up", exc_info=True)

        def start(q):
            threading.Thread(target=loop, args=(q,), daemon=True).start()
    """)
    assert not _run(tmp_path, ["R017"], ["locust_tpu"]).new


def test_r018_fires_on_chaos_blind_data_plane_cmd(tmp_path):
    _rpc_tree(
        tmp_path,
        protocol=_RPC_PROTOCOL.replace(
            '("ping", "work")', '("ping", "fetch")'
        ),
        worker="""
    def handle(req):
        cmd = req.get("cmd")
        if cmd == "ping":
            return {"status": "ok", "pong": True}
        if cmd == "fetch":
            return _fetch(req)
        return {"status": "error"}

    def _fetch(req):
        path = req["path"]
        return {"status": "ok", "data": path}
""",
        client="""
    from locust_tpu.distributor import protocol

    def rpc(sock, payload, secret):
        protocol.send_frame(sock, payload, secret)
        return {"status": "ok"}

    def do_fetch(sock, path, secret):
        return rpc(sock, {"cmd": "fetch", "path": path}, secret)

    def do_ship(sock, rec, epoch, secret):
        from locust_tpu.utils import faultplan
        faultplan.fire("serve.ship", rec=rec)
        req = {"cmd": "ship", "rec": rec}
        req[protocol.EPOCH_KEY] = epoch
        return rpc(sock, req, secret)
""",
    )
    res = _run(tmp_path, ["R018"], ["locust_tpu"])
    # fetch (data plane) has no reachable hook; ship's SEND PATH has one
    # (coverage can come from either side of the wire).
    assert len(res.new) == 1
    assert "data-plane cmd 'fetch'" in res.new[0].message
    assert res.new[0].path == "locust_tpu/distributor/worker.py"


def test_r018_silent_when_handler_reaches_a_hook(tmp_path):
    _rpc_tree(
        tmp_path,
        protocol=_RPC_PROTOCOL.replace(
            '("ping", "work")', '("ping", "fetch")'
        ),
        worker="""
    from locust_tpu.utils import faultplan

    def handle(req):
        cmd = req.get("cmd")
        if cmd == "ping":
            return {"status": "ok", "pong": True}
        if cmd == "fetch":
            return _fetch(req)
        if cmd == "ship":
            return _apply_ship(req)
        return {"status": "error"}

    def _fetch(req):
        path = req["path"]
        faultplan.damage_file("dist.fetch", path)
        return {"status": "ok", "data": path}

    def _apply_ship(req):
        rec = req["rec"]
        faultplan.fire("serve.ship", rec=rec)
        return {"status": "ok"}
""",
        client="""
    from locust_tpu.distributor import protocol

    def rpc(sock, payload, secret):
        protocol.send_frame(sock, payload, secret)
        return {"status": "ok"}

    def do_fetch(sock, path, secret):
        return rpc(sock, {"cmd": "fetch", "path": path}, secret)

    def do_ship(sock, rec, epoch, secret):
        req = {"cmd": "ship", "rec": rec}
        req[protocol.EPOCH_KEY] = epoch
        return rpc(sock, req, secret)
""",
    )
    assert not _run(tmp_path, ["R018"], ["locust_tpu"]).new


def test_r018_fires_on_unclassified_cmd(tmp_path):
    _rpc_tree(
        tmp_path,
        protocol=_RPC_PROTOCOL.replace(
            '("ping", "work")', '("ping", "mystery")'
        ),
        worker="""
    def handle(req):
        cmd = req.get("cmd")
        if cmd == "ping":
            return {"status": "ok", "pong": True}
        if cmd == "mystery":
            return {"status": "ok"}
        if cmd == "ship":
            return {"status": "ok"}
        return {"status": "error"}
""",
        client="""
    from locust_tpu.distributor import protocol

    def rpc(sock, payload, secret):
        protocol.send_frame(sock, payload, secret)
        return {"status": "ok"}

    def do_ship(sock, rec, epoch, secret):
        req = {"cmd": "ship", "rec": rec}
        req[protocol.EPOCH_KEY] = epoch
        return rpc(sock, req, secret)

    def chaos_demo():
        from locust_tpu.utils import faultplan
        faultplan.fire("serve.ship", cmd="demo")
""",
    )
    res = _run(tmp_path, ["R018"], ["locust_tpu"])
    findings = [f for f in res.new if "mystery" in f.message]
    assert len(findings) == 1
    assert "no plane classification" in findings[0].message


# ------------------------------------------------------- registry + CLI


def test_registry_is_closed_and_complete():
    assert sorted(all_rules()) == [
        "R001", "R002", "R003", "R004", "R005", "R006", "R007", "R008",
        "R009", "R010", "R011", "R012", "R013", "R014", "R015", "R016",
        "R017", "R018",
    ]
    with pytest.raises(ValueError, match="unknown rule"):
        get_rules(["R042"])


def test_cli_json_gate_green_on_repo(tmp_path):
    """The CLI surface of the tier-1 gate: exit 0, parseable JSON."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO}
    proc = subprocess.run(
        [sys.executable, "-m", "locust_tpu.analysis", "--json"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    report = json.loads(proc.stdout)
    assert report["new"] == 0
    assert report["rules"] == sorted(all_rules())
    # Per-rule wall time: one entry per selected rule, so a perf
    # regression against the <10s self-perf pin is attributable.
    assert sorted(report["rule_ms"]) == report["rules"]
    assert all(
        isinstance(v, (int, float)) and v >= 0
        for v in report["rule_ms"].values()
    )


def test_cli_rule_filter_and_unknown_rule(tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO}
    proc = subprocess.run(
        [sys.executable, "-m", "locust_tpu.analysis", "--rule", "R042"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 2
    assert "unknown rule" in proc.stderr


# ------------------------------------------------- --changed and SARIF

_R003_HOT = """
    import jax

    def drain(blocks):
        for b in blocks:
            jax.block_until_ready(b)
"""


def _git(root, *args):
    proc = subprocess.run(
        ["git", "-C", str(root), "-c", "user.name=t",
         "-c", "user.email=t@t", *args],
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 0, proc.stderr
    return proc.stdout


def test_changed_scope_drops_preexisting_findings(tmp_path):
    from locust_tpu.analysis.core import changed_lines, scope_to_changed

    # A committed pre-existing violation + a fresh uncommitted one.
    _write(tmp_path, "locust_tpu/old.py", _R003_HOT)
    _git(tmp_path, "init", "-q")
    _git(tmp_path, "add", "-A")
    _git(tmp_path, "commit", "-q", "-m", "seed")
    _write(tmp_path, "locust_tpu/hot.py", _R003_HOT)
    _git(tmp_path, "add", "-A")  # --changed diffs vs HEAD: staged counts

    res = _run(tmp_path, ["R003"], ["locust_tpu"])
    assert len(res.new) == 2  # full-repo behavior unchanged
    scoped = scope_to_changed(res, changed_lines(str(tmp_path), "HEAD"))
    assert [f.path for f in scoped.new] == ["locust_tpu/hot.py"]


def test_changed_scope_includes_untracked_files(tmp_path):
    # git diff never lists a not-yet-added file; --changed must still
    # see it whole-file, or a brand-new module is silently unscoped.
    from locust_tpu.analysis.core import changed_lines, scope_to_changed

    _git(tmp_path, "init", "-q")
    _write(tmp_path, "locust_tpu/seed.py", "X = 1\n")
    _git(tmp_path, "add", "-A")
    _git(tmp_path, "commit", "-q", "-m", "seed")
    _write(tmp_path, "locust_tpu/fresh.py", _R003_HOT)  # untracked

    res = _run(tmp_path, ["R003"], ["locust_tpu"])
    scoped = scope_to_changed(res, changed_lines(str(tmp_path), "HEAD"))
    assert [f.path for f in scoped.new] == ["locust_tpu/fresh.py"]


def test_changed_lines_unknown_ref_is_loud(tmp_path):
    from locust_tpu.analysis.core import changed_lines

    _git(tmp_path, "init", "-q")
    with pytest.raises(ValueError):
        changed_lines(str(tmp_path), "no-such-ref")


def test_cli_changed_scopes_exit_code(tmp_path):
    _write(tmp_path, "locust_tpu/old.py", _R003_HOT)
    _write(tmp_path, "pyproject.toml", """
        [tool.locust-analysis]
        paths = ["locust_tpu"]
    """)
    _git(tmp_path, "init", "-q")
    _git(tmp_path, "add", "-A")
    _git(tmp_path, "commit", "-q", "-m", "seed")
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO}
    # Full run fails on the committed violation; --changed (clean tree,
    # empty diff) scopes it away — the fast pre-commit loop.
    full = subprocess.run(
        [sys.executable, "-m", "locust_tpu.analysis", "--root",
         str(tmp_path)],
        cwd=str(tmp_path), env=env, capture_output=True, text=True,
        timeout=120,
    )
    assert full.returncode == 1
    scoped = subprocess.run(
        [sys.executable, "-m", "locust_tpu.analysis", "--root",
         str(tmp_path), "--changed"],
        cwd=str(tmp_path), env=env, capture_output=True, text=True,
        timeout=120,
    )
    assert scoped.returncode == 0, scoped.stdout + scoped.stderr


def test_sarif_schema_shape(tmp_path):
    """Pin the SARIF 2.1.0 surface CI annotators consume."""
    from locust_tpu.analysis.sarif import sarif_report

    _write(tmp_path, "locust_tpu/hot.py", _R003_HOT)
    res = _run(tmp_path, ["R003"], ["locust_tpu"])
    assert len(res.new) == 1
    doc = sarif_report(res, {"R003": "host sync inside a hot loop"})
    assert doc["version"] == "2.1.0"
    assert doc["$schema"].endswith("sarif-schema-2.1.0.json")
    run = doc["runs"][0]
    driver = run["tool"]["driver"]
    assert driver["name"] == "locust-analysis"
    assert [r["id"] for r in driver["rules"]] == ["R003"]
    assert driver["rules"][0]["shortDescription"]["text"]
    result = run["results"][0]
    assert result["ruleId"] == "R003"
    assert result["level"] == "error"
    assert result["message"]["text"]
    loc = result["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"] == "locust_tpu/hot.py"
    assert loc["region"]["startLine"] == res.new[0].line
    assert loc["region"]["startColumn"] == res.new[0].col + 1
    assert (result["partialFingerprints"]["locustFingerprint/v1"]
            == res.new[0].fingerprint)
    assert result["baselineState"] == "new"


def test_sarif_rule_entries_carry_help_uri_and_level(tmp_path):
    """Passing rule CLASSES (the CLI's catalog) decorates each rule
    entry with helpUri (docs/ANALYSIS.md anchor) and a default level;
    bare-title catalogs (the legacy shape above) still work."""
    from locust_tpu.analysis.sarif import sarif_report

    _write(tmp_path, "locust_tpu/hot.py", _R003_HOT)
    res = _run(tmp_path, ["R003"], ["locust_tpu"])
    doc = sarif_report(res, dict(all_rules()))
    rules = doc["runs"][0]["tool"]["driver"]["rules"]
    assert [r["id"] for r in rules] == sorted(all_rules())
    for r in rules:
        assert r["helpUri"].startswith("docs/ANALYSIS.md#")
        assert r["defaultConfiguration"]["level"] == "error"
        assert r["shortDescription"]["text"]


def test_cli_sarif_writes_parseable_log(tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO}
    out = tmp_path / "findings.sarif"
    proc = subprocess.run(
        [sys.executable, "-m", "locust_tpu.analysis", "--rule", "R008",
         "--sarif", str(out)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.loads(out.read_text())
    assert doc["version"] == "2.1.0"
    driver = doc["runs"][0]["tool"]["driver"]
    assert driver["name"] == "locust-analysis"
    # The CLI passes rule classes: every entry carries a helpUri.
    assert all("helpUri" in r for r in driver["rules"])


# ----------------------------------------------------- two-phase engine


def test_full_repo_run_is_fast_and_parses_each_file_once():
    """The analyzer self-perf pin: the two-phase engine must stay cheap
    enough to live inside tier-1 (< 10 s on the CPU container) and keep
    the one-parse-per-file economy — phase 2 runs over summaries, and
    the registry rules reuse phase-1 trees instead of re-reading their
    anchor modules."""
    import time as _time

    from locust_tpu.analysis import core as acore
    from locust_tpu.analysis import rpcflow as arpc

    acore.reset_parse_count()
    arpc.reset_build_count()
    t0 = _time.perf_counter()
    res = run_analysis(root=REPO)
    elapsed = _time.perf_counter() - t0
    assert elapsed < 10.0, f"full-repo analysis took {elapsed:.1f}s"
    assert acore.parse_count() == res.n_files, (
        f"{acore.parse_count()} parses for {res.n_files} files — "
        "a rule is re-parsing instead of reusing phase-1 trees"
    )
    # The message-flow economy rides the same pin: R016 and R018 share
    # ONE RpcProgram build per run (rpcflow.get caches on the Program).
    assert arpc.build_count() == 1, (
        f"{arpc.build_count()} rpcflow builds in one run — R016/R018 "
        "stopped sharing the cached RpcProgram"
    )


# ------------------------------------------------------------ THE GATE


def test_repo_gate_zero_new_findings():
    """Tier-1: the full rule set over the configured tree (pyproject
    [tool.locust-analysis]) must report zero non-baselined findings.
    A new unlocked thread write, impure traced statement, hot-loop sync,
    fault-site typo, re-spelled wire constant, unpinned python spawn or
    stray bench print fails the suite right here."""
    res = run_analysis(root=REPO)
    assert not res.new, "\n" + "\n".join(f.format() for f in res.new)
