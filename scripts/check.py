#!/usr/bin/env python
"""One-shot dev gate: static analysis + its test suite + a traced run.

    env JAX_PLATFORMS=cpu python scripts/check.py [--fast]

Runs (1) the two-phase invariant checker (R001-R018) over the configured
paths (exit 1 on new findings — docs/ANALYSIS.md), with a --changed
pre-gate (findings on diff-touched lines reported first) and a SARIF
emission round-trip archived to the configured artifact path,
(2) tests/test_analysis.py, which includes the
repo-wide gate test, and (3) a small traced engine run whose exported
timeline is validated against locust_tpu/obs/trace.schema.json (the obs
contract, docs/OBSERVABILITY.md) — in a subprocess with a pinned env, so
this process stays jax-free.  ``--fast`` skips (2) and (3).
Exit code is non-zero if any part fails.
"""

from __future__ import annotations

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    fast = "--fast" in argv

    # In-process: the analyzer imports no checked code (and no jax).
    sys.path.insert(0, REPO)
    from locust_tpu.analysis import run_analysis
    from locust_tpu.analysis.core import changed_lines, scope_to_changed

    result = run_analysis(root=REPO)

    # --changed pre-gate: the findings on lines YOU touched, reported
    # FIRST — the thing a dev iterating on a diff actually wants to see
    # before the whole-tree report.  Same run (analysis is always
    # whole-program; the scope only narrows what is reported), so the
    # pre-gate costs nothing.  Skipped without complaint when git can't
    # diff (detached tmp checkouts).
    try:
        scoped = scope_to_changed(result, changed_lines(REPO, "HEAD"))
        if scoped.new:
            print("[check] pre-gate: new finding(s) on changed lines:",
                  file=sys.stderr)
            for f in scoped.new:
                print(f"  {f.format()}", file=sys.stderr)
        else:
            print("[check] pre-gate: changed lines clean", file=sys.stderr)
    except ValueError as e:
        print(f"[check] pre-gate skipped ({e})", file=sys.stderr)

    for f in result.findings:
        print(f.format(), file=sys.stderr)
    print(
        f"[check] analysis: {len(result.new)} new finding(s) over "
        f"{result.n_files} file(s), {result.suppressed} suppressed",
        file=sys.stderr,
    )
    rc = 1 if result.new else 0

    # SARIF emission round-trip + archive: the CI-annotation surface must
    # stay a loadable 2.1.0 log whatever the findings are, and the log is
    # ARCHIVED (config "sarif_artifact", gitignored) so the last gate
    # run's findings are inspectable after the fact (docs/ANALYSIS.md).
    import json

    from locust_tpu.analysis import config as _cfg
    from locust_tpu.analysis.registry import all_rules
    from locust_tpu.analysis.sarif import write_sarif

    sarif_path = os.path.join(
        REPO, _cfg.load_config(REPO)["sarif_artifact"]
    )
    os.makedirs(os.path.dirname(sarif_path), exist_ok=True)
    write_sarif(sarif_path, result, dict(all_rules()))
    with open(sarif_path, encoding="utf-8") as fh:
        doc = json.load(fh)
    drv = doc["runs"][0]["tool"]["driver"]
    if (
        doc.get("version") != "2.1.0"
        or not all("helpUri" in r for r in drv["rules"])
    ):
        print("[check] sarif round-trip: bad version or rule metadata",
              file=sys.stderr)
        rc = rc or 1
    else:
        print(f"[check] sarif archived to {sarif_path}", file=sys.stderr)
    if fast:
        return rc

    # Pinned env (R006 applies to this script too): the analyzer suite
    # runs pytest in a child python; the child imports THIS checkout and
    # stays off the chip its parent may hold.
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO}
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "tests/test_analysis.py", "-q"],
        cwd=REPO, env=env, timeout=600,
    )

    # Traced round-trip: a tiny engine run under the obs tracer, exported
    # and schema-validated — the telemetry contract every --trace-out run
    # rides.  Subprocess (same pinned env) keeps THIS process jax-free.
    trace_rc = subprocess.run(
        [sys.executable, "-c", _TRACE_ROUNDTRIP], cwd=REPO, env=env,
        timeout=300,
    ).returncode

    # Serve-tier smoke (docs/SERVING.md): a loopback daemon serves a
    # submit, a result-cache repeat, and a same-bucket warm dispatch,
    # then shuts down cleanly — the zero-to-serving contract the CLI
    # (`python -m locust_tpu.serve`) rides.  Same pinned env.
    serve_rc = subprocess.run(
        [sys.executable, "-c", _SERVE_SMOKE], cwd=REPO, env=env,
        timeout=300,
    ).returncode

    # Crash-recovery smoke (docs/SERVING.md "Durability guarantee"): a
    # REAL daemon process is SIGKILL'd mid-job, restarted on the same
    # write-ahead journal, and the replayed result must be byte-identical
    # to the one-shot CLI over the same corpus/config.  Same pinned env.
    recovery_rc = subprocess.run(
        [sys.executable, "-c", _RECOVERY_SMOKE], cwd=REPO, env=env,
        timeout=300,
    ).returncode

    # Scale-out pool smoke (docs/SERVING.md "Scale-out dispatch"): a
    # daemon over TWO real worker processes serves a submit exactly,
    # then one worker is SIGKILL'd mid-serve-batch and the retried
    # result must STILL be byte-identical to the one-shot CLI — worker
    # death costs latency, never an answer.  Same pinned env.
    pool_rc = subprocess.run(
        [sys.executable, "-c", _POOL_SMOKE], cwd=REPO, env=env,
        timeout=420,
    ).returncode

    # Plan smoke (docs/PLAN.md): a two-stage tf-idf PLAN submitted to a
    # real daemon must answer byte-identically to the one-shot
    # `python -m locust_tpu tfidf` CLI over the same corpus, and a
    # repeat must be a result-cache hit keyed by the plan fingerprint.
    # The recovery smoke above additionally SIGKILLs a daemon holding a
    # journaled plan job and diffs its replay the same way.
    plan_rc = subprocess.run(
        [sys.executable, "-c", _PLAN_SMOKE], cwd=REPO, env=env,
        timeout=300,
    ).returncode

    # Distributed-plan smoke (docs/PLAN.md "Distributed execution"): the
    # same two-stage tfidf plan across TWO real --serve workers, one
    # SIGKILL'd mid-map-stage (held open by an injected delay), and the
    # answer must STILL be byte-identical to the one-shot tfidf CLI —
    # stage-granular recompute on the survivor, never a wrong answer.
    dplan_rc = subprocess.run(
        [sys.executable, "-c", _DPLAN_SMOKE], cwd=REPO, env=env,
        timeout=420,
    ).returncode

    # Fused-stream smoke (docs/DESIGN.md 1.7, megakernel v2): the persistent
    # STREAMING formulation of the map->aggregate megakernel — a
    # `--stream --sort-mode fused` CLI run over 20 blocks (3 segments,
    # the last partial) must be byte-identical to the one-shot hasht
    # CLI, and the stream stats must show the streaming formulation
    # actually engaged (not a demotion).  Same pinned env.
    fused_stream_rc = subprocess.run(
        [sys.executable, "-c", _FUSED_STREAM_SMOKE], cwd=REPO, env=env,
        timeout=300,
    ).returncode

    # Machine-death failover smoke (docs/SERVING.md "High
    # availability"): a REAL primary+standby pair, the primary
    # SIGKILL'd holding a wordcount AND a journaled plan job, the
    # standby promoted via the CLI — both replays byte-identical to
    # the one-shot CLIs — and the zombie primary's restart fenced with
    # stale_epoch down to a not_primary-answering standby.
    failover_rc = subprocess.run(
        [sys.executable, "-c", _FAILOVER_SMOKE], cwd=REPO, env=env,
        timeout=420,
    ).returncode
    print(
        f"[check] tests: rc={proc.returncode}; analysis rc={rc}; "
        f"trace round-trip rc={trace_rc}; serve smoke rc={serve_rc}; "
        f"recovery smoke rc={recovery_rc}; pool smoke rc={pool_rc}; "
        f"plan smoke rc={plan_rc}; dplan smoke rc={dplan_rc}; "
        f"fused-stream smoke rc={fused_stream_rc}; "
        f"failover smoke rc={failover_rc}",
        file=sys.stderr,
    )
    return (rc or proc.returncode or trace_rc or serve_rc
            or recovery_rc or pool_rc or plan_rc or dplan_rc
            or fused_stream_rc or failover_rc)


_TRACE_ROUNDTRIP = """
import sys, tempfile, os
from locust_tpu.backend import select_backend
select_backend("cpu")
from locust_tpu import obs
from locust_tpu.config import EngineConfig
from locust_tpu.engine import MapReduceEngine
from locust_tpu.obs.schema import validate_trace
obs.enable(process="check")
eng = MapReduceEngine(
    EngineConfig(block_lines=8, line_width=32, key_width=8, emits_per_line=4)
)
eng.timed_run(eng.rows_from_lines([b"a b a", b"b c", b"c a b"]))
with tempfile.TemporaryDirectory() as td:
    path = os.path.join(td, "check.trace.json")
    doc = obs.export(path)
    validate_trace(doc)
    names = {e["name"] for e in doc["traceEvents"]}
need = {"engine.stage.map", "engine.stage.process", "engine.stage.reduce"}
missing = need - names
if missing:
    print(f"[check] trace round-trip missing spans: {missing}",
          file=sys.stderr)
    sys.exit(1)
print(f"[check] trace round-trip ok ({len(names)} span/event names)",
      file=sys.stderr)
"""


_SERVE_SMOKE = """
import sys
from locust_tpu.backend import select_backend
select_backend("cpu")
from locust_tpu.serve import ServeClient, ServeConfig, ServeDaemon
cfgov = {"block_lines": 8, "line_width": 64, "key_width": 16,
         "emits_per_line": 8}
daemon = ServeDaemon(secret=b"check-smoke", cfg=ServeConfig(max_batch=2))
daemon.serve_in_thread()
client = ServeClient(daemon.addr, b"check-smoke", timeout=60.0)
corpus = b"alpha beta gamma\\nbeta gamma delta\\n" * 6
ack = client.submit(corpus=corpus, config=cfgov)
res = client.wait(ack["job_id"], timeout=120.0)
assert dict(res["pairs"]) == {b"alpha": 6, b"beta": 12, b"gamma": 12,
                              b"delta": 6}, res["pairs"]
ack2 = client.submit(corpus=corpus, config=cfgov)
assert ack2["cached"] is True, ack2
ack3 = client.submit(corpus=corpus, config=cfgov, invalidate=True)
res3 = client.wait(ack3["job_id"], timeout=120.0)
assert res3["cache"] == "warm", res3  # same bucket: skipped compilation
assert dict(res3["pairs"]) == dict(res["pairs"])
client.shutdown()
daemon.close()
print("[check] serve smoke ok (result-cache + warm-executable hits)",
      file=sys.stderr)
"""


_RECOVERY_SMOKE = """
import os, signal, subprocess, sys, tempfile

td = tempfile.mkdtemp(prefix="locust_recovery_smoke_")
corpus_path = os.path.join(td, "corpus.txt")
with open(corpus_path, "wb") as f:
    f.write(b"alpha beta gamma\\nbeta gamma delta\\n" * 8)
cfg_flags = ["--block-lines", "8", "--line-width", "64",
             "--key-width", "16", "--emits-per-line", "8"]
env = {**os.environ, "JAX_PLATFORMS": "cpu",
       "PYTHONPATH": os.getcwd(), "LOCUST_SECRET": "recovery-smoke"}

# The oracles: the one-shot CLI over the same corpus + caps, for the
# WordCount job AND the two-stage tf-idf PLAN job (docs/PLAN.md).
one_shot = subprocess.run(
    [sys.executable, "-m", "locust_tpu", corpus_path,
     "--backend", "cpu", "--no-timing"] + cfg_flags,
    env=env, capture_output=True, timeout=240,
)
assert one_shot.returncode == 0, one_shot.stderr[-800:]
tfidf_shot = subprocess.run(
    [sys.executable, "-m", "locust_tpu", "tfidf", corpus_path,
     "--backend", "cpu", "--lines-per-doc", "2"] + cfg_flags,
    env=env, capture_output=True, timeout=240,
)
assert tfidf_shot.returncode == 0, tfidf_shot.stderr[-800:]

def spawn(env=env):
    proc = subprocess.Popen(
        [sys.executable, "-m", "locust_tpu.serve", "--port", "0",
         "--journal-dir", os.path.join(td, "journal")],
        env=env, stderr=subprocess.PIPE, text=True,
    )
    line = proc.stderr.readline()
    assert "listening on" in line, line
    host, _, port = line.rsplit(" ", 1)[1].strip().partition(":")
    return proc, (host, int(port))

from locust_tpu.plan import tfidf_plan
from locust_tpu.serve.client import ServeClient

proc, addr = spawn()
try:
    client = ServeClient(addr, b"recovery-smoke", timeout=30.0)
    cfgov = {"block_lines": 8, "line_width": 64, "key_width": 16,
             "emits_per_line": 8}
    corpus = open(corpus_path, "rb").read()
    job_id = client.submit(corpus=corpus, config=cfgov,
                           no_cache=True)["job_id"]
    # A journaled PLAN job rides the same crash: the WAL admit record
    # carries the whole plan document, so the restart must re-execute
    # the arbitrary pipeline under its original id (docs/PLAN.md).
    plan_id = client.submit(corpus=corpus, config=cfgov,
                            plan=tfidf_plan(2).to_doc(),
                            no_cache=True)["job_id"]
    # SIGKILL right behind the acks: the jobs are queued-or-mid-
    # dispatch, exactly the lost-work window the journal closes.
    proc.send_signal(signal.SIGKILL)
    proc.wait(timeout=10)
finally:
    if proc.poll() is None:
        proc.kill()
proc2, addr2 = spawn()
try:
    c2 = ServeClient(addr2, b"recovery-smoke", timeout=30.0)
    res = c2.wait(job_id, timeout=240.0)
    got = b"".join(
        k + b"\\t" + str(v).encode() + b"\\n"
        for k, v in sorted(res["pairs"])
    )
    assert got == one_shot.stdout, (
        "replayed result != one-shot CLI\\n%r\\n%r"
        % (got[:200], one_shot.stdout[:200])
    )
    pres = c2.wait(plan_id, timeout=240.0)
    assert pres.get("plan") is True, pres.get("plan")
    assert pres["pairs"][0][0] == tfidf_shot.stdout, (
        "replayed plan result != one-shot tfidf CLI\\n%r\\n%r"
        % (pres["pairs"][0][0][:200], tfidf_shot.stdout[:200])
    )
    c2.shutdown()
    proc2.wait(timeout=30)
finally:
    if proc2.poll() is None:
        proc2.kill()
print("[check] recovery smoke ok (SIGKILL mid-job -> wordcount AND "
      "plan replays byte-identical to the one-shot CLI)",
      file=sys.stderr)
"""


_POOL_SMOKE = """
import json, os, signal, subprocess, sys, tempfile, time

td = tempfile.mkdtemp(prefix="locust_pool_smoke_")
corpus_path = os.path.join(td, "corpus.txt")
with open(corpus_path, "wb") as f:
    f.write(b"alpha beta gamma\\nbeta gamma delta\\n" * 8)
cfg_flags = ["--block-lines", "8", "--line-width", "64",
             "--key-width", "16", "--emits-per-line", "8"]
env = {**os.environ, "JAX_PLATFORMS": "cpu",
       "PYTHONPATH": os.getcwd(), "LOCUST_SECRET": "pool-smoke"}

one_shot = subprocess.run(
    [sys.executable, "-m", "locust_tpu", corpus_path,
     "--backend", "cpu", "--no-timing"] + cfg_flags,
    env=env, capture_output=True, timeout=240,
)
assert one_shot.returncode == 0, one_shot.stderr[-800:]

def spawn_worker():
    # Workers hold their SECOND serve_batch 3s (rpc.delay, after: 1) so
    # the SIGKILL below provably lands MID-serve-batch: the first job
    # dispatches clean and warms the worker, the same-bucket repeat is
    # routed back to it by affinity and held inside the dispatch.
    wenv = dict(env, LOCUST_FAULT_PLAN=json.dumps({"seed": 7, "rules": [
        {"site": "rpc.delay", "action": "delay", "delay_s": 3.0,
         "match": {"cmd": "serve_batch"}, "after": 1, "times": 1}]}))
    proc = subprocess.Popen(
        [sys.executable, "-m", "locust_tpu.distributor.worker",
         "--serve", "--port", "0"],
        env=wenv, stderr=subprocess.PIPE, text=True,
    )
    line = proc.stderr.readline()
    assert "listening on" in line, line
    host, _, port = line.rsplit(" ", 1)[1].strip().partition(":")
    return proc, f"{host}:{port}"

w1, a1 = spawn_worker()
w2, a2 = spawn_worker()
daemon = subprocess.Popen(
    [sys.executable, "-m", "locust_tpu.serve", "--port", "0",
     "--workers", f"{a1},{a2}"],
    env=env, stderr=subprocess.PIPE, text=True,
)
try:
    line = daemon.stderr.readline()
    assert "listening on" in line, line
    host, _, port = line.rsplit(" ", 1)[1].strip().partition(":")
    from locust_tpu.serve.client import ServeClient
    client = ServeClient((host, int(port)), b"pool-smoke", timeout=60.0)
    cfgov = {"block_lines": 8, "line_width": 64, "key_width": 16,
             "emits_per_line": 8}
    corpus = open(corpus_path, "rb").read()

    def as_cli(pairs):
        return b"".join(
            k + b"\\t" + str(v).encode() + b"\\n" for k, v in sorted(pairs)
        )

    jid = client.submit(corpus=corpus, config=cfgov,
                        no_cache=True)["job_id"]
    res = client.wait(jid, timeout=240.0)
    assert as_cli(res["pairs"]) == one_shot.stdout, "pool != one-shot CLI"
    placed = client.status(jid)["placed_on"]
    victim = w1 if placed == a1 else w2
    survivor_addr = a2 if placed == a1 else a1

    # Same-SHAPE repeat (same line count -> same bucket): affinity sends
    # it to the warm worker, whose serve_batch is held 3s by the fault
    # rule — SIGKILL it mid-batch.
    corpus2 = corpus.replace(b"alpha", b"omega")
    j2 = client.submit(corpus=corpus2, config=cfgov,
                       no_cache=True)["job_id"]
    time.sleep(0.8)
    victim.send_signal(signal.SIGKILL)
    victim.wait(timeout=10)
    res2 = client.wait(j2, timeout=240.0)
    p2 = os.path.join(td, "corpus2.txt")
    with open(p2, "wb") as f:
        f.write(corpus2)
    oracle2 = subprocess.run(
        [sys.executable, "-m", "locust_tpu", p2,
         "--backend", "cpu", "--no-timing"] + cfg_flags,
        env=env, capture_output=True, timeout=240,
    )
    assert oracle2.returncode == 0, oracle2.stderr[-800:]
    assert as_cli(res2["pairs"]) == oracle2.stdout, (
        "post-worker-death result != one-shot CLI"
    )
    st2 = client.status(j2)
    assert st2["placed_on"] != placed, st2
    client.shutdown()
    daemon.wait(timeout=60)
finally:
    for p in (w1, w2, daemon):
        if p.poll() is None:
            p.kill()
print("[check] pool smoke ok (2 real workers; SIGKILL mid-serve-batch "
      "-> retried result byte-identical to the one-shot CLI)",
      file=sys.stderr)
"""


_PLAN_SMOKE = """
import json, os, subprocess, sys, tempfile

td = tempfile.mkdtemp(prefix="locust_plan_smoke_")
corpus_path = os.path.join(td, "corpus.txt")
with open(corpus_path, "wb") as f:
    f.write(b"alpha beta gamma\\nbeta gamma delta\\nalpha alpha\\n"
            b"epsilon zeta\\n" * 4)
cfg_flags = ["--block-lines", "8", "--line-width", "64",
             "--key-width", "16", "--emits-per-line", "8"]
env = {**os.environ, "JAX_PLATFORMS": "cpu",
       "PYTHONPATH": os.getcwd(), "LOCUST_SECRET": "plan-smoke"}

# The oracle: the one-shot hand-wired tfidf CLI over the same corpus.
one_shot = subprocess.run(
    [sys.executable, "-m", "locust_tpu", "tfidf", corpus_path,
     "--backend", "cpu", "--lines-per-doc", "2"] + cfg_flags,
    env=env, capture_output=True, timeout=240,
)
assert one_shot.returncode == 0, one_shot.stderr[-800:]

# The same pipeline as a PLAN document, submitted through the serve CLI
# (`submit FILE --plan PLAN.json`) against a real daemon.
from locust_tpu.plan import tfidf_plan

plan_path = os.path.join(td, "tfidf_plan.json")
with open(plan_path, "w") as f:
    json.dump(tfidf_plan(2).to_doc(), f)

daemon = subprocess.Popen(
    [sys.executable, "-m", "locust_tpu.serve", "--port", "0"],
    env=env, stderr=subprocess.PIPE, text=True,
)
try:
    line = daemon.stderr.readline()
    assert "listening on" in line, line
    host, _, port = line.rsplit(" ", 1)[1].strip().partition(":")
    submit = [sys.executable, "-m", "locust_tpu.serve", "submit",
              corpus_path, "--plan", plan_path, "--port", port] + cfg_flags
    cold = subprocess.run(submit, env=env, capture_output=True,
                          timeout=240)
    assert cold.returncode == 0, cold.stderr[-800:]
    assert cold.stdout == one_shot.stdout, (
        "plan submit != one-shot tfidf CLI\\n%r\\n%r"
        % (cold.stdout[:200], one_shot.stdout[:200])
    )
    # Repeat: a result-cache hit keyed by the plan fingerprint, still
    # byte-identical.
    warm = subprocess.run(submit, env=env, capture_output=True,
                          timeout=240)
    assert warm.returncode == 0, warm.stderr[-800:]
    assert warm.stdout == one_shot.stdout
    assert b"(cached)" in warm.stderr, warm.stderr[-400:]

    # Cross-tenant sub-plan sharing (docs/PLAN.md "Optimizer"): an
    # alpha-RENAMED tfidf plan — different plan fingerprint, so the
    # whole-job result cache MISSES — over the same corpus lands on the
    # per-edge entry the first tenant populated.
    doc = tfidf_plan(2).to_doc()
    for n in doc["nodes"]:
        n["id"] = "x_" + n["id"]
        n["inputs"] = ["x_" + r for r in n["inputs"]]
    plan2_path = os.path.join(td, "tfidf_plan_renamed.json")
    with open(plan2_path, "w") as f:
        json.dump(doc, f)
    ten2 = subprocess.run(
        [sys.executable, "-m", "locust_tpu.serve", "submit", corpus_path,
         "--plan", plan2_path, "--tenant", "t2", "--port", port]
        + cfg_flags,
        env=env, capture_output=True, timeout=240,
    )
    assert ten2.returncode == 0, ten2.stderr[-800:]
    assert ten2.stdout == one_shot.stdout, (
        "alpha-renamed plan != one-shot tfidf CLI"
    )
    assert b"(cached)" not in ten2.stderr  # not a whole-job cache hit

    # Incremental resubmit: the corpus grows APPEND-ONLY; the daemon
    # verifies the prefix sha server-side, re-folds only the delta
    # blocks, and the result must still be byte-identical to a cold
    # one-shot CLI over the grown corpus.
    with open(corpus_path, "rb") as f:
        base = f.read()
    grown_path = os.path.join(td, "corpus_grown.txt")
    with open(grown_path, "wb") as f:
        f.write(base + b"eta theta\\nalpha eta\\n" * 8)
    cold_grown = subprocess.run(
        [sys.executable, "-m", "locust_tpu", "tfidf", grown_path,
         "--backend", "cpu", "--lines-per-doc", "2"] + cfg_flags,
        env=env, capture_output=True, timeout=240,
    )
    assert cold_grown.returncode == 0, cold_grown.stderr[-800:]
    inc = subprocess.run(
        [sys.executable, "-m", "locust_tpu.serve", "submit", grown_path,
         "--plan", plan_path, "--port", port] + cfg_flags,
        env=env, capture_output=True, timeout=240,
    )
    assert inc.returncode == 0, inc.stderr[-800:]
    assert inc.stdout == cold_grown.stdout, (
        "incremental resubmit != cold one-shot CLI over the grown corpus"
    )
    stats = subprocess.run(
        [sys.executable, "-m", "locust_tpu.serve", "stats",
         "--port", port],
        env=env, capture_output=True, timeout=60,
    )
    assert stats.returncode == 0, stats.stderr[-800:]
    sub = json.loads(stats.stdout)["subplan_cache"]
    assert sub["hits"] >= 1, sub              # renamed tenant hit the edge
    assert sub["incremental_hits"] >= 1, sub  # the delta refold engaged
    assert 0 < sub["last_delta_blocks"] < sub["last_total_blocks"], sub

    subprocess.run(
        [sys.executable, "-m", "locust_tpu.serve", "shutdown",
         "--port", port],
        env=env, capture_output=True, timeout=60,
    )
    daemon.wait(timeout=30)
finally:
    if daemon.poll() is None:
        daemon.kill()
print("[check] plan smoke ok (two-stage tfidf plan byte-identical to "
      "the one-shot CLI, repeat = plan-keyed result-cache hit; "
      "alpha-renamed second tenant = sub-plan edge hit; append-only "
      "regrowth = incremental delta refold, still byte-identical)",
      file=sys.stderr)
"""


_DPLAN_SMOKE = """
import json, os, signal, subprocess, sys, tempfile, time

td = tempfile.mkdtemp(prefix="locust_dplan_smoke_")
corpus_path = os.path.join(td, "corpus.txt")
with open(corpus_path, "wb") as f:
    f.write(b"alpha beta gamma\\nbeta gamma delta\\nalpha alpha\\n"
            b"epsilon zeta\\n" * 8)
cfg_flags = ["--block-lines", "8", "--line-width", "64",
             "--key-width", "16", "--emits-per-line", "8"]
env = {**os.environ, "JAX_PLATFORMS": "cpu",
       "PYTHONPATH": os.getcwd(), "LOCUST_SECRET": "dplan-smoke"}

# The oracle: the one-shot hand-wired tfidf CLI over the same corpus.
one_shot = subprocess.run(
    [sys.executable, "-m", "locust_tpu", "tfidf", corpus_path,
     "--backend", "cpu", "--lines-per-doc", "2"] + cfg_flags,
    env=env, capture_output=True, timeout=240,
)
assert one_shot.returncode == 0, one_shot.stderr[-800:]

from locust_tpu.plan import tfidf_plan

plan_path = os.path.join(td, "tfidf_plan.json")
with open(plan_path, "w") as f:
    json.dump(tfidf_plan(2).to_doc(), f)

def spawn_worker(fault=None):
    wenv = dict(env)
    if fault is not None:
        wenv["LOCUST_FAULT_PLAN"] = json.dumps(fault)
    proc = subprocess.Popen(
        [sys.executable, "-m", "locust_tpu.distributor.worker",
         "--serve", "--port", "0"],
        env=wenv, stderr=subprocess.PIPE, text=True,
    )
    line = proc.stderr.readline()
    assert "listening on" in line, line
    host, _, port = line.rsplit(" ", 1)[1].strip().partition(":")
    return proc, f"{host}:{port}"

# w2 holds its first map stage open 6s: the SIGKILL below provably
# lands MID-stage, and the coordinator must recompute that split on
# the survivor from the durable corpus spill.
w1, a1 = spawn_worker()
w2, a2 = spawn_worker(fault={"seed": 7, "rules": [
    {"site": "plan.stage", "action": "delay", "delay_s": 6.0,
     "match": {"phase": "map"}, "times": 1}]})
daemon = subprocess.Popen(
    [sys.executable, "-m", "locust_tpu.serve", "--port", "0",
     "--workers", f"{a1},{a2}", "--shard-min-blocks", "1"],
    env=env, stderr=subprocess.PIPE, text=True,
)
try:
    line = daemon.stderr.readline()
    assert "listening on" in line, line
    host, _, port = line.rsplit(" ", 1)[1].strip().partition(":")
    submit = subprocess.Popen(
        [sys.executable, "-m", "locust_tpu.serve", "submit",
         corpus_path, "--plan", plan_path, "--port", port] + cfg_flags,
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
    )
    # Kill w2 only once the map wave is provably in flight (w2's split
    # is held open by its fault plan while w1's lands) — a blind sleep
    # races a slow admit and can kill w2 BEFORE placement, demoting the
    # job to solo instead of exercising the mid-stage recompute.
    from locust_tpu.serve.client import ServeClient
    client = ServeClient((host, int(port)), b"dplan-smoke", timeout=60.0)
    deadline = time.time() + 120.0
    while time.time() < deadline:
        try:
            pl = client.stats()["pool"]["plan"]
        except Exception:
            pl = {}
        if pl.get("stages", 0) >= 1:
            break
        time.sleep(0.2)
    else:
        raise AssertionError("map wave never started: %r" % (pl,))
    time.sleep(0.5)
    w2.send_signal(signal.SIGKILL)
    w2.wait(timeout=10)
    out, err = submit.communicate(timeout=240)
    assert submit.returncode == 0, err[-800:]
    assert out == one_shot.stdout, (
        "distributed plan != one-shot tfidf CLI\\n%r\\n%r"
        % (out[:200], one_shot.stdout[:200])
    )
    pl = client.stats()["pool"]["plan"]
    assert pl["stages"] >= 4, pl      # it really ran distributed
    assert pl["recomputes"] >= 1, pl  # and really lost a stage
    client.shutdown()
    daemon.wait(timeout=60)
finally:
    for p in (w1, w2, daemon):
        if p.poll() is None:
            p.kill()
print("[check] dplan smoke ok (tfidf plan across 2 real workers; "
      "SIGKILL mid-map-stage -> survivor recompute, byte-identical "
      "to the one-shot CLI)", file=sys.stderr)

# ---- Plan surface v2 drills: SIGKILL mid-JOIN-stage and mid-pagerank-
# EPOCH.  Oracle = the same plan submitted to a solo (poolless) daemon;
# the distributed answer must be byte-identical even with a worker
# killed while its stage is provably in flight (the fault plan holds
# that stage open, and the kill lands inside the hold).
from locust_tpu.plan import pagerank_plan
from locust_tpu.plan.nodes import Plan, node
from locust_tpu.serve.client import ServeClient

join_doc = Plan((
    node("c1", "source", "text"),
    node("m1", "map", "tokenize_count", ("c1",)),
    node("s1", "shuffle", "by_key", ("m1",)),
    node("r1", "reduce", "sum", ("s1",)),
    node("c2", "source", "text"),
    node("m2", "map", "tokenize_count", ("c2",)),
    node("s2", "shuffle", "by_key", ("m2",)),
    node("r2", "reduce", "sum", ("s2",)),
    node("j1", "join", "inner", ("r1", "r2"), combine="mul"),
    node("out", "sink", "table", ("j1",)),
)).to_doc()
join_path = os.path.join(td, "join_plan.json")
pr_path = os.path.join(td, "pr_plan.json")
edges_path = os.path.join(td, "edges.txt")
with open(join_path, "w") as f:
    json.dump(join_doc, f)
with open(pr_path, "w") as f:
    json.dump(pagerank_plan(4).to_doc(), f)
with open(edges_path, "wb") as f:
    f.write(b"0 1\\n1 2\\n2 0\\n0 2\\n3 1\\n2 3\\n" * 3)

def spawn_daemon(workers=None):
    cmd = [sys.executable, "-m", "locust_tpu.serve", "--port", "0"]
    if workers:
        cmd += ["--workers", ",".join(workers),
                "--shard-min-blocks", "1"]
    proc = subprocess.Popen(cmd, env=env, stderr=subprocess.PIPE,
                            text=True)
    line = proc.stderr.readline()
    assert "listening on" in line, line
    host, _, port = line.rsplit(" ", 1)[1].strip().partition(":")
    return proc, host, port

def submit(port, corpus, plan_path, background=False):
    p = subprocess.Popen(
        [sys.executable, "-m", "locust_tpu.serve", "submit",
         corpus, "--plan", plan_path, "--port", port] + cfg_flags,
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
    )
    if background:
        return p
    out, err = p.communicate(timeout=240)
    assert p.returncode == 0, err[-800:]
    return out

sd, _, sport = spawn_daemon()
try:
    oracle_join = submit(sport, corpus_path, join_path)
    oracle_pr = submit(sport, edges_path, pr_path)
    subprocess.run(
        [sys.executable, "-m", "locust_tpu.serve", "shutdown",
         "--port", sport],
        env=env, capture_output=True, timeout=60,
    )
    sd.wait(timeout=30)
finally:
    if sd.poll() is None:
        sd.kill()

def drill(plan_path, corpus, phase, oracle, kill_after_stages,
          min_stages, match=None):
    wa, aa = spawn_worker()
    wb, ab = spawn_worker(fault={"seed": 7, "rules": [
        {"site": "plan.stage", "action": "delay", "delay_s": 8.0,
         "match": match or {"phase": phase}, "times": 1}]})
    dproc, host, port = spawn_daemon([aa, ab])
    try:
        sub = submit(port, corpus, plan_path, background=True)
        client = ServeClient((host, int(port)), b"dplan-smoke",
                             timeout=60.0)
        deadline = time.time() + 120.0
        while time.time() < deadline:
            try:
                pl = client.stats()["pool"]["plan"]
            except Exception:
                pl = {}
            if pl.get("stages", 0) >= kill_after_stages:
                break
            time.sleep(0.2)
        else:
            raise AssertionError("%s drill never reached %d stages"
                                 % (phase, kill_after_stages))
        time.sleep(0.5)  # the held stage is now in flight on wb
        wb.send_signal(signal.SIGKILL)
        wb.wait(timeout=10)
        out, err = sub.communicate(timeout=240)
        assert sub.returncode == 0, (phase, err[-800:])
        assert out == oracle, (
            "distributed %s plan != solo daemon\\n%r\\n%r"
            % (phase, out[:200], oracle[:200])
        )
        pl = client.stats()["pool"]["plan"]
        assert pl["stages"] >= min_stages, (phase, pl)
        assert pl["recomputes"] >= 1, (phase, pl)
        assert pl["plan_solo_fallbacks"] == 0, (phase, pl)
        client.shutdown()
        dproc.wait(timeout=60)
    finally:
        for p in (wa, wb, dproc):
            if p.poll() is None:
                p.kill()

# Join: the map wave (2 splits) completes, then wb's join stage is held
# open 8s — the SIGKILL lands mid-join-bin and the survivor re-joins
# that bin from the durable leaf partitions.
drill(join_path, corpus_path, "join", oracle_join,
      kill_after_stages=2, min_stages=4)
# Iterate: epoch 1 (2 rank shards) completes and journals, then wb's
# epoch-2 sweep is held open — the SIGKILL lands mid-epoch and the
# survivor recomputes that rank shard from epoch 1's partitions.
drill(pr_path, edges_path, "iterate", oracle_pr,
      kill_after_stages=2, min_stages=6,
      match={"phase": "iterate", "split": 2})
print("[check] dplan smoke ok (join tree + pagerank plans across 2 "
      "real workers; SIGKILL mid-join-stage and mid-pagerank-epoch -> "
      "survivor recompute, byte-identical to the solo daemon)",
      file=sys.stderr)
"""


_FUSED_STREAM_SMOKE = """
import os, subprocess, sys, tempfile

td = tempfile.mkdtemp(prefix="locust_fused_stream_smoke_")
corpus_path = os.path.join(td, "corpus.txt")
with open(corpus_path, "wb") as f:
    f.write((b"alpha beta gamma\\nbeta gamma delta\\nalpha alpha\\n"
             b"epsilon zeta\\n") * 160)   # 640 lines = 20 blocks of 32
cfg_flags = ["--block-lines", "32", "--line-width", "128",
             "--key-width", "16", "--emits-per-line", "8"]
env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": os.getcwd()}

# The oracle: the one-shot hasht CLI over the same corpus + caps.
one_shot = subprocess.run(
    [sys.executable, "-m", "locust_tpu", corpus_path,
     "--backend", "cpu", "--no-timing", "--sort-mode", "hasht"]
    + cfg_flags,
    env=env, capture_output=True, timeout=240,
)
assert one_shot.returncode == 0, one_shot.stderr[-800:]

# The persistent streaming kernel: `--stream --sort-mode fused` folds
# 8-block segment buffers inside one kernel dispatch each (megakernel
# v2, docs/DESIGN.md 1.7) — 20 blocks = 3 segments, the last PARTIAL, so the
# zero-pad path is inside the identity, not just the aligned case.
fused = subprocess.run(
    [sys.executable, "-m", "locust_tpu", corpus_path,
     "--backend", "cpu", "--no-timing", "--stream",
     "--sort-mode", "fused"] + cfg_flags,
    env=env, capture_output=True, timeout=240,
)
assert fused.returncode == 0, fused.stderr[-800:]
assert fused.stdout == one_shot.stdout, (
    "streamed fused run != one-shot hasht CLI\\n%r\\n%r"
    % (fused.stdout[:200], one_shot.stdout[:200])
)
# The run must have taken the streaming FORMULATION, not a demotion:
# run_stream surfaces it in the `[locust] stream:` stats line.
assert b"'formulation': 'stream'" in fused.stderr, fused.stderr[-800:]
print("[check] fused-stream smoke ok (persistent streaming kernel, "
      "3 segments incl. a partial, byte-identical to the one-shot "
      "hasht CLI)", file=sys.stderr)
"""


_FAILOVER_SMOKE = """
import json, os, signal, subprocess, sys, tempfile, time

td = tempfile.mkdtemp(prefix="locust_failover_smoke_")
corpus_path = os.path.join(td, "corpus.txt")
with open(corpus_path, "wb") as f:
    f.write(b"alpha beta gamma\\nbeta gamma delta\\n" * 8)
cfg_flags = ["--block-lines", "8", "--line-width", "64",
             "--key-width", "16", "--emits-per-line", "8"]
env = {**os.environ, "JAX_PLATFORMS": "cpu",
       "PYTHONPATH": os.getcwd(), "LOCUST_SECRET": "failover-smoke"}

# The oracles: the one-shot CLIs for the wordcount job AND the
# two-stage tf-idf PLAN job.
one_shot = subprocess.run(
    [sys.executable, "-m", "locust_tpu", corpus_path,
     "--backend", "cpu", "--no-timing"] + cfg_flags,
    env=env, capture_output=True, timeout=240,
)
assert one_shot.returncode == 0, one_shot.stderr[-800:]
tfidf_shot = subprocess.run(
    [sys.executable, "-m", "locust_tpu", "tfidf", corpus_path,
     "--backend", "cpu", "--lines-per-doc", "2"] + cfg_flags,
    env=env, capture_output=True, timeout=240,
)
assert tfidf_shot.returncode == 0, tfidf_shot.stderr[-800:]

def spawn(extra, env=env):
    proc = subprocess.Popen(
        [sys.executable, "-m", "locust_tpu.serve", "--port", "0"] + extra,
        env=env, stderr=subprocess.PIPE, text=True,
    )
    line = proc.stderr.readline()
    assert "listening on" in line, line
    addr = line.split("listening on ", 1)[1].split(" ")[0].strip()
    host, _, port = addr.partition(":")
    return proc, (host, int(port))

from locust_tpu.plan import tfidf_plan
from locust_tpu.serve.client import ServeClient

SECRET = b"failover-smoke"
sdir, pdir = os.path.join(td, "standby-j"), os.path.join(td, "primary-j")
standby, saddr = spawn(["--journal-dir", sdir,
                        "--standby-of", "127.0.0.1:9"])
primary, paddr = spawn(["--journal-dir", pdir,
                        "--ship-to", f"{saddr[0]}:{saddr[1]}"])
zombie = None
try:
    pc = ServeClient(paddr, SECRET, timeout=30.0)
    sc = ServeClient(saddr, SECRET, timeout=30.0)
    cfgov = {"block_lines": 8, "line_width": 64, "key_width": 16,
             "emits_per_line": 8}
    corpus = open(corpus_path, "rb").read()
    job_id = pc.submit(corpus=corpus, config=cfgov,
                       no_cache=True)["job_id"]
    plan_id = pc.submit(corpus=corpus, config=cfgov,
                        plan=tfidf_plan(2).to_doc(),
                        no_cache=True)["job_id"]
    # Both acks are durable on the primary the instant they return;
    # wait for the async WAL ship to land them on the standby (the
    # operator's replication-lag check), then kill the machine.
    deadline = time.monotonic() + 60.0
    while time.monotonic() < deadline:
        rep = sc.stats()["replication"]["standby"]
        if rep["applied_seq"] >= 2 and rep["missing_spills"] == 0:
            break
        time.sleep(0.1)
    assert rep["applied_seq"] >= 2 and rep["missing_spills"] == 0, rep
    primary.send_signal(signal.SIGKILL)
    primary.wait(timeout=10)

    # Takeover via the CLI surface.
    promote = subprocess.run(
        [sys.executable, "-m", "locust_tpu.serve", "promote",
         "--port", str(saddr[1])],
        env=env, capture_output=True, timeout=60,
    )
    assert promote.returncode == 0, promote.stderr[-400:]

    res = sc.wait(job_id, timeout=240.0)
    got = b"".join(
        k + b"\\t" + str(v).encode() + b"\\n"
        for k, v in sorted(res["pairs"])
    )
    assert got == one_shot.stdout, (
        "failover wordcount != one-shot CLI\\n%r\\n%r"
        % (got[:200], one_shot.stdout[:200])
    )
    pres = sc.wait(plan_id, timeout=240.0)
    assert pres.get("plan") is True, pres.get("plan")
    assert pres["pairs"][0][0] == tfidf_shot.stdout, (
        "failover plan result != one-shot tfidf CLI\\n%r\\n%r"
        % (pres["pairs"][0][0][:200], tfidf_shot.stdout[:200])
    )

    # The zombie: the old primary's machine comes back on its journal,
    # still shipping at the standby — its first ship is rejected with
    # the structured stale_epoch and it demotes itself.
    zombie, zaddr = spawn(["--journal-dir", pdir,
                           "--ship-to", f"{saddr[0]}:{saddr[1]}"])
    zc = ServeClient(zaddr, SECRET, timeout=30.0)
    deadline = time.monotonic() + 60.0
    while time.monotonic() < deadline:
        zrep = zc.stats()["replication"]
        if zrep["role"] == "standby":
            break
        time.sleep(0.1)
    assert zrep["role"] == "standby", zrep
    assert zrep["fenced_by"] is not None, zrep
    raw = zc._rpc_one(zaddr, {"cmd": "submit", "corpus_b64": "YQo="})
    assert raw.get("code") == "not_primary", raw
    assert raw.get("primary") == f"{saddr[0]}:{saddr[1]}", raw

    # Roster transparency: a client still pointed at the OLD primary's
    # address reaches the new one through the redirect.
    rc = ServeClient([f"{zaddr[0]}:{zaddr[1]}"], SECRET, timeout=30.0)
    assert rc.stats()["replication"]["role"] == "standby"  # direct hit
    ack = rc.submit(corpus=corpus, config=cfgov)           # redirected
    rres = rc.wait(ack["job_id"], timeout=240.0)
    rgot = b"".join(
        k + b"\\t" + str(v).encode() + b"\\n"
        for k, v in sorted(rres["pairs"])
    )
    assert rgot == one_shot.stdout

    sc.shutdown()
    standby.wait(timeout=30)
    zc.shutdown()
    zombie.wait(timeout=30)
finally:
    for p in (standby, primary, zombie):
        if p is not None and p.poll() is None:
            p.kill()
print("[check] failover smoke ok (primary SIGKILL'd mid-job -> standby "
      "promoted, wordcount AND plan replays byte-identical to the "
      "one-shot CLI; zombie restart fenced stale_epoch -> not_primary)",
      file=sys.stderr)
"""


if __name__ == "__main__":
    raise SystemExit(main())
