#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process holds the chip(s) from start to end and starts no child that
needs them.  Everything that belongs to one cell, configuration, traffic
mix or metric is a file found by the name BENCHMARK.json gives it
(``configs/``, ``traffic/``, ``drivers/``, ``end_to_end/``,
``layer_metrics/``, ``readers/``): this file holds none of those names.
README.md says how to add each.

Set-up (``setup_s``, everything before the window): require the device the
cell asks for — a TPU whose ``device_kind`` is in peaks.json, as many chips
as the cell's ``chips``, else exit 2 with no result line; compile cache
where ``locust_tpu.config.compile_cache_dir`` puts it; corpus and oracle
from ``--seed``; warm-up jobs until one compiles nothing.  Then the window,
then the contract's last line.

``--rehearse`` is for the CPU sandbox: it pins jax to the CPU (as many
virtual devices as the cell's chips), takes the configuration's
``rehearsal`` sizes, and prints NO metric — a CPU number is never written
under a device metric's name.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import trace_reduce  # noqa: E402
import yardstick  # noqa: E402

# jax counts a persistent-cache "miss" only when it WRITES an entry, which
# it does not for a program that compiled in under a second.  A compile is
# therefore a request that was not a hit.
REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
HIT = "/jax/compilation_cache/cache_hits"


def say(msg: str) -> None:
    print(f"[bench +{time.perf_counter() - T_START:7.3f}s] {msg}", flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find_cell(bench: dict, name: str):
    """(cell, its configuration's file as a dict, its traffic file)."""
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        raise SystemExit(f"run.py: no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return (cell, load_json(ROOT, conf["file"]),
            load_json(HERE, "traffic", cell["traffic"] + ".json"))


def metrics_of(bench: dict, group: str, cell_name: str) -> list[dict]:
    """The metrics of ``end_to_end`` or ``per_layer`` this cell reports."""
    return [m for m in bench[group]
            if "workloads" not in m or cell_name in m["workloads"]]


class Monitor:
    """jax.monitoring listener: every event and duration with the host
    clock at which it came, so a reader can cut them by job or window."""

    def __init__(self):
        import jax.monitoring

        self.events: list[tuple[float, str, float | None]] = []
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        self.events.append((time.perf_counter(), name, None))

    def _duration(self, name, secs, **_):
        self.events.append((time.perf_counter(), name, secs))

    def count(self, name: str, lo: float = 0.0, hi: float = float("inf")) -> int:
        return sum(1 for t, n, _ in self.events if n == name and lo <= t <= hi)

    def compiles(self, lo: float = 0.0, hi: float = float("inf")) -> int:
        return self.count(REQUEST, lo, hi) - self.count(HIT, lo, hi)


def host_state() -> str:
    """The host's clock, as far as a process can see it: the cores' MHz and
    governor, the steal ticks so far (time the hypervisor gave to other
    guests), and five timings of one fixed piece of interpreter work.  Printed
    on either side of the window, so that a run at another speed can be told
    from a program at another speed."""
    mhz = []
    try:
        with open("/proc/cpuinfo") as f:
            mhz = sorted(float(ln.split(":")[1]) for ln in f if ln.startswith("cpu MHz"))
    except (OSError, ValueError):
        pass
    try:
        with open("/sys/devices/system/cpu/cpu0/cpufreq/scaling_governor") as f:
            governor = f.read().strip()
    except OSError:
        governor = "not exposed"
    steal = "not exposed"
    try:
        with open("/proc/stat") as f:
            fields = f.readline().split()
        if len(fields) > 8:
            steal = fields[8]
    except OSError:
        pass
    probe = []
    for _ in range(5):
        t0 = time.perf_counter()
        n = 0
        for i in range(300_000):
            n += i & 7
        probe.append((time.perf_counter() - t0) * 1e3)
    return (f"cpu MHz min/median/max "
            f"{(mhz[0], mhz[len(mhz) // 2], mhz[-1]) if mhz else 'not exposed'}, governor "
            f"{governor}, steal ticks {steal}, probe loop ms {[round(x, 2) for x in sorted(probe)]}")


# What a driver and the readers get: the cell's files, the device, the
# corpus and its oracle, and the program's one entry point.
Env = types.SimpleNamespace


def require_device(cell: dict, rehearse: bool) -> dict:
    """The device as jax reports it, or exit 2 with no result line."""
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    peaks = load_json(HERE, "peaks.json")
    if not rehearse:
        if dev["platform"] != "tpu":
            print(f"run.py: error: jax initialized {dev['platform']!r}, not a "
                  "TPU; there is no CPU fallback", file=sys.stderr)
            raise SystemExit(2)
        if dev["kind"] not in peaks:
            print(f"run.py: error: device kind {dev['kind']!r} is not in "
                  "benchmarks/peaks.json", file=sys.stderr)
            raise SystemExit(2)
    if dev["count"] != cell["chips"]:
        print(f"run.py: error: the cell asks for {cell['chips']} chip(s), jax "
              f"sees {dev['count']}", file=sys.stderr)
        raise SystemExit(2)
    dev["peaks"] = peaks.get(dev["kind"])
    return dev


def set_up(bench: dict, cell: dict, config: dict, traffic: dict, seed: int,
           rehearse: bool, extra_argv=(), keep_floor: bool = False) -> Env:
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={cell['chips']}")
    sys.path.insert(0, ROOT)
    try:
        from locust_tpu.config import compile_cache_dir
    except ImportError as e:
        print(f"run.py: error: the program is not in this checkout: {e}",
              file=sys.stderr)
        raise SystemExit(2)
    cache = compile_cache_dir(".jax_cache_cpu" if rehearse else ".jax_cache")
    import jax

    # jax keeps only programs that took a second to compile in its
    # persistent cache, and the program leaves that floor alone.  A CLI
    # user's job therefore compiles the handful of sub-second programs
    # anew; here they would compile inside every job of the window, which
    # the contract forbids.  So the harness lowers the floor to 0 — a
    # change to the system under test that PERF.md (sections 2 and 5)
    # states, with both readings.  ``--jax-cache-floor`` leaves the floor as the
    # program has it, for that reading.
    if not keep_floor:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    say("persistent-cache floor: "
        f"{jax.config.jax_persistent_cache_min_compile_time_secs} s"
        + (" (the program's own)" if keep_floor else " (lowered by the harness)"))
    dev = require_device(cell, rehearse)
    say("jax is up")
    monitor = Monitor()
    from locust_tpu.cli import main as cli_main

    say(f"cell {cell['name']}: config {cell['config']}, traffic "
        f"{cell['traffic']}, seed {seed}" + (" — REHEARSAL on the CPU" if rehearse else ""))
    say(f"device: platform {dev['platform']}, device_kind {dev['kind']!r}, "
        f"count {dev['count']}")
    n_cached = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    say(f"compile cache: {cache} ({n_cached} entries at start)")

    workdir = tempfile.mkdtemp(prefix="locust_bench_")
    sizes = dict(config["sizes"], **(config.get("rehearsal", {}).get("sizes", {}) if rehearse else {}))
    if rehearse:
        extra_argv = list(config.get("rehearsal", {}).get("argv", [])) + list(extra_argv)
    corpus_path = os.path.join(workdir, "corpus.txt")
    t0 = time.perf_counter()
    nbytes = yardstick.build_corpus(corpus_path, config["text"], sizes["corpus_lines"], seed)
    expect = yardstick.oracle_table(corpus_path)
    say(f"corpus: {nbytes} bytes, {sizes['corpus_lines']} lines of {config['text']}, "
        f"seed {seed}; oracle {expect.count(10)} distinct words "
        f"({time.perf_counter() - t0:.2f} s of set-up)")
    return Env(bench=bench, cell=cell, config=config, sizes=sizes, traffic=traffic,
               seed=seed, rehearse=rehearse, device=dev, platform=dev["platform"],
               monitor=monitor, cli_main=cli_main, workdir=workdir,
               profile_dir=os.path.join(workdir, "profile"),
               corpus_path=corpus_path, corpus_bytes=nbytes, expect=expect,
               extra_argv=list(extra_argv), say=say)


def read_metric(group_dir: str, meta: dict, env: Env):
    # ``load_ms.tput`` and ``load_ms.lat`` are one quantity in cells that
    # report different end-to-end metrics: both read ``load_ms.json``
    # unless a file of the full name says otherwise.
    name = meta["name"]
    if not os.path.exists(os.path.join(HERE, group_dir, name + ".json")):
        name = name.rpartition(".")[0] or name
    spec = load_json(HERE, group_dir, name + ".json")
    reader = importlib.import_module("readers." + spec["reader"])
    return reader.read(spec, env)


def device_peaks() -> list[int] | None:
    """``peak_bytes_in_use`` of every device, or None where the backend
    keeps no memory statistics (the CPU)."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.devices()]
    return None if any(p is None for p in peaks) else peaks


def run_cell(args) -> dict:
    """Set-up, window, metrics: the result line as a dict."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cell, config, traffic = find_cell(bench, args.workload)
    if args.slice_jobs:
        traffic["trace_slice"] = dict(traffic["trace_slice"], jobs=args.slice_jobs)
    env = set_up(bench, cell, config, traffic, args.seed, args.rehearse,
                 keep_floor=args.jax_cache_floor)
    try:
        driver = importlib.import_module("drivers." + traffic["driver"])
        warm = driver.warm_up(env)
        say("host before the window: " + host_state())
        env.ru0 = resource.getrusage(resource.RUSAGE_SELF)
        t_win = time.perf_counter()
        env.setup_s = t_win - T_START
        say(f"set-up {env.setup_s:.3f} s: compile cache "
            f"{env.monitor.count(HIT)} hit(s), {env.monitor.compiles()} compiled")
        traced = bool(args.trace)
        jobs, slice_info = driver.measure(env, args.seconds, traced)
        env.jobs, env.window_s = jobs, jobs[-1].t_end - t_win
        say("host after the window: " + host_state())
        env.window = (t_win, jobs[-1].t_end)
        env.trace = None
        if slice_info is not None:
            try:
                env.trace = reduce_slice(env, slice_info, args.keep_trace)
            except trace_reduce.NoDevicePlane:
                if not env.rehearse:  # a traced run in which no chip ran is no run
                    raise
                say("rehearsal: the trace has no TPU plane; device readers read nothing")
        return result(env, warm, traced)
    finally:
        shutil.rmtree(env.workdir, ignore_errors=True)


def reduce_slice(env: Env, slice_info, keep: str | None) -> dict:
    import glob

    profile_dir, first, count = slice_info
    found = sorted(glob.glob(os.path.join(profile_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise RuntimeError("the profiler wrote no .xplane.pb")
    if keep:
        os.makedirs(keep, exist_ok=True)
        shutil.copy(found[-1], os.path.join(keep, f"{env.cell['name']}.xplane.pb"))
    red = trace_reduce.reduce_trace(found[-1])
    sl_jobs = env.jobs[first:first + count]
    if len(red["jobs"]) != len(sl_jobs):
        raise RuntimeError(f"trace holds {len(red['jobs'])} job annotations, "
                           f"the slice ran {len(sl_jobs)}")
    # The program's spans run on the epoch clock; the annotation of the
    # same job gives that job's offset to the trace's clock.
    red["host_spans"] = [
        [(n, s - j.epoch_ns + a, e - j.epoch_ns + a) for n, s, e in j.spans]
        for j, (a, _) in zip(sl_jobs, red["jobs"])
    ]
    red["slice_jobs"] = sl_jobs
    return red


def result(env: Env, warm, traced: bool) -> dict:
    jobs = env.jobs
    bad = [(i, j.verdict) for i, j in enumerate(jobs) if j.verdict is not None]
    bad += [(f"warm-up {i}", j.verdict) for i, j in enumerate(warm) if j.verdict is not None]
    for i, why in bad[:5]:
        say(f"job {i} FAILED: {why}")
    env.device_peaks = device_peaks()
    notes = []
    if env.traffic.get("check", {}).get("all_devices_held_memory") and not env.rehearse:
        say(f"peak_bytes_in_use per device: {env.device_peaks} (each must be > 0)")
        if not env.device_peaks or min(env.device_peaks) <= 0:
            notes.append("a device never held memory")
    failed = sum(1 for j in jobs if j.verdict is not None)
    correct = bool(jobs) and not bad and not notes
    times = sorted(j.seconds for j in jobs)
    say(f"window {env.window_s:.3f} s: {len(jobs)} job(s) started, {failed} failed; "
        f"samples per job-time metric {len(jobs)}; compiled in the window "
        f"{env.monitor.compiles(*env.window)}")
    say(f"job seconds: min {times[0]:.4f}, median {times[len(times) // 2]:.4f}, "
        f"max {times[-1]:.4f}; in order {[round(j.seconds, 3) for j in jobs]}")
    say(f"job ends, seconds into the window: {[round(j.t_end - env.window[0], 3) for j in jobs]}")
    ru = resource.getrusage(resource.RUSAGE_SELF)
    say(f"host: process CPU {ru.ru_utime - env.ru0.ru_utime:.2f} s user + "
        f"{ru.ru_stime - env.ru0.ru_stime:.2f} s system in the window, context switches "
        f"{ru.ru_nvcsw - env.ru0.ru_nvcsw} voluntary / {ru.ru_nivcsw - env.ru0.ru_nivcsw} "
        f"involuntary, load average {os.getloadavg()[0]:.2f} on {os.cpu_count()} cores")
    say(f"compared: jobs whose table differs from the oracle or that reported "
        f"lost work {len(bad)} (limit 0, exact); {'; '.join(notes) or 'device checks clean'}"
        f" -> correct {str(correct).lower()}")

    group, group_dir = ("per_layer", "layer_metrics") if traced else ("end_to_end", "end_to_end")
    metrics = {}
    for meta in metrics_of(env.bench, group, env.cell["name"]):
        value = read_metric(group_dir, meta, env)
        if value is not None:
            metrics[meta["name"]] = {"value": value, "unit": meta["unit"]}
    out = {"correct": correct, "attempted": len(jobs), "failed": failed}
    if env.rehearse:
        say("rehearsal: metrics read (values withheld, this is a CPU): "
            + ", ".join(sorted(metrics)))
        out["rehearsal"] = True
        return out
    out["metrics"] = metrics
    out["device"] = {"platform": env.device["platform"], "kind": env.device["kind"],
                     "count": env.device["count"],
                     "memory_peak_bytes": max(env.device_peaks) if env.device_peaks else None}
    if env.trace is not None:
        devs = env.trace["devices"]
        out["device"]["busy_s"] = sum(d["busy_s"] for d in devs.values()) / len(devs)
        out["device"]["window_s"] = env.trace["window_s"]
        top = devs[trace_reduce.busiest(env.trace)]
        out["breakdown"] = {
            "device_ops": trace_reduce.top_ops(top),
            "idle_gaps": trace_reduce.label_gaps(
                top["gaps"], env.trace["jobs"], env.trace["host_spans"])[:10],
        }
        for d, v in sorted(devs.items()):
            say(f"traced slice {env.trace['window_s']:.3f} s, device {d}: busy "
                f"{v['busy_s']:.3f} s, idle share {v['idle_share']:.4f}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU sandbox only: tiny sizes, no metric printed")
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="copy the traced slice's .xplane.pb into DIR")
    ap.add_argument("--jax-cache-floor", action="store_true",
                    help="diagnostic: leave jax's persistent-cache floor (1 s) as the "
                         "program has it, so sub-second programs compile in every job")
    ap.add_argument("--slice-jobs", type=int, default=None,
                    help="profile this many jobs, not the traffic file's count "
                         "(for recording a small fixture)")
    args = ap.parse_args(argv)
    out = run_cell(args)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
