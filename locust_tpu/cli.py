"""CLI driver: the ``mapreduce <file> [start] [end] [node] [stage]`` contract.

Preserves the reference's positional CLI (reference MapReduce/src/main.cu:362-387)
and staged execution model:

  stage 0 (or absent)  single mode: map -> process -> reduce, print table
  stage 1              staged map: process this node's [start, end) line
                       slice, write the intermediate TSV, exit
                       ("master will start back up", main.cu:432)
  stage 2              staged reduce: load intermediate TSV(s), reduce,
                       print table

Fixes over the reference, each documented in SURVEY.md Appendix A:
  Q9 — unguarded argv reads -> argparse with the same positional contract.
  Q6 — the reference's reduce stage never re-sorts loaded intermediate data
       (correct only if the missing master pre-sorted globally); our reduce
       stage always sorts, so any concatenation order is correct.
  Q5/Q10 — clean TSV keys; only live entries written.

Timing report mirrors the reference's three chrono spans (main.cu:405-468)
— in milliseconds, not its UB %d-of-duration printf (Q7).
"""

from __future__ import annotations

import argparse
import sys
import time

import numpy as np

from locust_tpu import obs  # jax-free; zero-overhead unless --trace-out

STAGE_SINGLE, STAGE_MAP, STAGE_REDUCE = 0, 1, 2
DEFAULT_INTERMEDIATE = "/tmp/out.txt"  # reference path, main.cu:428


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="mapreduce",
        description="TPU-native MapReduce (WordCount) with staged multi-node mode",
    )
    p.add_argument("filename",
                   help="input text file (stage 0/1); ignored for stage 2.  "
                        "The default path reads it inside the run, a group "
                        "of blocks ahead of the device, and never holds it "
                        "whole in host memory; --no-timing, "
                        "--checkpoint-dir, --auto-caps and --mesh (each "
                        "without --stream) load it whole first: their "
                        "loops index the rows")
    p.add_argument("line_start", nargs="?", type=int, default=-1)
    p.add_argument("line_end", nargs="?", type=int, default=-1)
    p.add_argument("node_num", nargs="?", type=int, default=0)
    p.add_argument("stage", nargs="?", type=int, default=STAGE_SINGLE,
                   choices=[STAGE_SINGLE, STAGE_MAP, STAGE_REDUCE])
    p.add_argument("--intermediate", "-i", action="append", default=None,
                   help="intermediate path(s); default "
                        f"{DEFAULT_INTERMEDIATE} (reference main.cu:428)")
    p.add_argument("--inter-format", choices=["tsv", "bin"], default="tsv",
                   help="stage-1 intermediate format: 'tsv' (reference "
                        "parity, key\\tvalue text) or 'bin' (packed binary "
                        "KV, docs/DATAPLANE.md — what the distributor "
                        "master requests).  Stage 2 sniffs the format per "
                        "file, so mixed inputs reduce fine.")
    p.add_argument("--block-lines", type=int, default=4096)
    p.add_argument("--line-width", type=int, default=128)
    p.add_argument("--key-width", type=int, default=32)
    p.add_argument("--emits-per-line", type=int, default=20)
    p.add_argument("--auto-caps", action="store_true",
                   help="size key_width / emits_per_line to the corpus's "
                        "measured maxima (one host pass; lossless — output "
                        "identical to the configured caps, smaller sorted "
                        "arrays).  With --stream the measuring pass re-reads "
                        "the file in bounded memory.  No effect for stage 2.")
    p.add_argument("--no-timing", action="store_true",
                   help="one dispatch over the whole corpus and no stage "
                        "report (run_fused): the corpus is loaded whole "
                        "and staged whole, and the table is of fixed size")
    p.add_argument("--limit", type=int, default=None,
                   help="print only the first N table rows")
    p.add_argument("--checkpoint-dir", default=None,
                   help="crash-resumable block-granular snapshots: a re-run "
                        "with the same corpus+config resumes at the last "
                        "snapshot (TPU upgrade of the reference's "
                        "/tmp/out.txt restartability, SURVEY.md §5)")
    def positive_int(s: str) -> int:
        v = int(s)
        if v < 1:
            raise argparse.ArgumentTypeError(f"must be >= 1, got {v}")
        return v

    p.add_argument("--checkpoint-every", type=positive_int, default=8,
                   help="blocks between snapshots (with --checkpoint-dir)")
    p.add_argument("--sync-checkpoint", action="store_true",
                   help="write snapshots synchronously inside the fold "
                        "loop instead of on the bounded background writer "
                        "(EngineConfig.async_checkpoint; identical on-disk "
                        "format — async marks a generation and the writer "
                        "copies/serializes off the hot path, latest-wins "
                        "if the loop laps it; docs/DESIGN.md)")
    from locust_tpu.config import SORT_MODES

    p.add_argument("--sort-mode", choices=list(SORT_MODES),
                   default=None,
                   help="Process-stage sort strategy (config.EngineConfig."
                        "sort_mode); default follows the per-backend "
                        "choice (config.default_sort_mode)")
    p.add_argument("--mesh", action="store_true",
                   help="run stage 0/1 on ALL visible devices via the "
                        "all-to-all shuffle engine (DistributedMapReduce) "
                        "instead of the single-device engine; prints "
                        "per-shard stats on stderr")
    p.add_argument("--slices", type=positive_int, default=None,
                   help="with --mesh: use the hierarchical engine on a "
                        "[slices, devices/slice] mesh — per-round shuffle "
                        "stays intra-slice (ICI), slices combine once at "
                        "the end (DCN)")
    p.add_argument("--stream", action="store_true",
                   help="the one-program-a-block fold over the file read "
                        "two blocks ahead, with no stage report and a table "
                        "of fixed size; composes with --mesh, "
                        "--checkpoint-dir and --auto-caps, which without "
                        "it load the corpus whole (the default path holds "
                        "no more of it than a group of blocks either)")
    p.add_argument("--backend", choices=["auto", "cpu", "tpu"], default="auto",
                   help="auto: whatever jax initializes (JAX_PLATFORMS is "
                        "honoured); cpu: pin the CPU; tpu: require a TPU, "
                        "error otherwise — no mode falls back")
    p.add_argument("--coordinator", default=None,
                   help="multi-process pod launch: coordinator address "
                        "host:port (jax.distributed.initialize); every "
                        "process runs the same command with its own "
                        "--process-id.  Requires --mesh; only process 0 "
                        "prints the table.  Inside managed TPU "
                        "environments pass --coordinator alone and the "
                        "process count/id are auto-detected.")
    p.add_argument("--num-processes", type=int, default=None,
                   help="with --coordinator: total process count")
    p.add_argument("--process-id", type=int, default=None,
                   help="with --coordinator: this process's index")
    p.add_argument("--trace", action="store_true",
                   help="print a wall-clock span report (load/run/output) "
                        "on stderr in addition to the stage report")
    p.add_argument("--trace-out", default=None, metavar="FILE",
                   help="structured telemetry (locust_tpu.obs): record "
                        "the run's spans/events/metrics and export a "
                        "Chrome-trace/Perfetto JSON timeline to FILE "
                        "(docs/OBSERVABILITY.md)")
    p.add_argument("--fault-plan", default=None,
                   help="chaos-test fault injection plan: JSON text or a "
                        "path to a JSON file (also $LOCUST_FAULT_PLAN); "
                        "zero overhead when unset — see docs/FAULTS.md")
    p.add_argument("--profile-dir", default=None,
                   help="capture a jax/XLA profiler trace of the run into "
                        "this directory (view with TensorBoard/XProf)")
    return p


def main(argv=None) -> int:
    entered = time.time()  # cli.setup starts here, before a tracer can
    argv = sys.argv[1:] if argv is None else list(argv)
    # Workload-ladder subcommands (PageRank / inverted index / TF-IDF /
    # the record sort, cli_apps.py).  Dispatch on the first argument so
    # the reference's bare positional WordCount contract stays intact; a
    # FILE literally named "pagerank" or "sort" needs ./pagerank, ./sort.
    from locust_tpu.cli_apps import SUBCOMMANDS
    from locust_tpu import cli_apps

    if argv and argv[0] in SUBCOMMANDS:
        return cli_apps.main(argv[0], argv[1:], entered)
    args = build_parser().parse_args(argv)
    args.entered = entered
    if args.trace_out:
        obs.enable(process="cli")
    try:
        return _run(args)
    except OSError as e:
        print(f"mapreduce: error: {e}", file=sys.stderr)
        return 1
    finally:
        if args.trace_out:
            # Telemetry must not take down (or re-color) the run: an
            # unwritable trace path is a warning, never the exit status.
            try:
                obs.export(args.trace_out)
                print(f"[locust] trace written to {args.trace_out}",
                      file=sys.stderr)
            except OSError as e:
                print(f"[locust] trace export to {args.trace_out} "
                      f"failed: {e}", file=sys.stderr)
            obs.disable()


def _run(args) -> int:

    # Fault injection first: the plan must be live before any distributor
    # RPC or checkpoint write it is meant to intercept (docs/FAULTS.md).
    # Pure host-side control-plane hooks; a run with no plan pays one
    # None-check per hook and nothing else.
    from locust_tpu.utils import faultplan

    faultplan.install(args.fault_plan)

    # Pod launch: join the coordination service BEFORE any in-process jax
    # backend init (jax.distributed.initialize is a no-op too late once
    # jax.devices() has run).  The same command line runs on every
    # process with its own --process-id — the JAX-native analog of the
    # reference's per-node [start, end) staged contract (main.cu:47-54).
    multiproc = (
        args.coordinator is not None
        or args.num_processes is not None
        or args.process_id is not None
    )
    if multiproc:
        if not (args.mesh or args.slices):
            print(
                "mapreduce: error: --coordinator/--num-processes/"
                "--process-id require --mesh",
                file=sys.stderr,
            )
            return 2
        from locust_tpu.parallel.mesh import initialize_multihost

        initialize_multihost(
            args.coordinator, args.num_processes, args.process_id
        )

    # Backend resolution MUST precede any other jax backend use: a
    # platform pin cannot move a backend that is already initialized
    # (locust_tpu/backend.py).  Prints the device line to stderr.
    from locust_tpu.backend import select_backend_cli

    if select_backend_cli(args.backend, prog="mapreduce") is None:
        return 1

    if args.slices and not args.mesh:
        args.mesh = True  # --slices implies the mesh engine; never ignore it

    # Import jax lazily so --help works instantly.
    from locust_tpu.config import EngineConfig, default_sort_mode
    from locust_tpu.core.kv import KVBatch
    from locust_tpu.engine import MapReduceEngine
    from locust_tpu.io import loader, serde
    import jax
    import jax.numpy as jnp

    if args.sort_mode is None:
        # select_backend_cli above already initialized the platform, so
        # default_backend() reports exactly what was selected.
        args.sort_mode = default_sort_mode(jax.default_backend())

    cfg = EngineConfig(
        block_lines=args.block_lines,
        line_width=args.line_width,
        key_width=args.key_width,
        emits_per_line=args.emits_per_line,
        sort_mode=args.sort_mode,
        async_checkpoint=not args.sync_checkpoint,
    )

    # --trace / --profile-dir wire the hardening utils (SURVEY.md §5
    # tracing): wall-clock spans + optional XLA profiler capture.
    import contextlib

    from locust_tpu.utils import SpanTimer, device_trace

    timer = SpanTimer()
    prof = (
        device_trace(args.profile_dir)
        if args.profile_dir
        else contextlib.nullcontext()
    )

    # --auto-caps: measure the corpus once and shrink key_width /
    # emits_per_line to their lossless floors (never above the flags);
    # table_size pins to the flag-config resolution so the output table is
    # byte-identical either way (speed not measured on this machine).
    # SpanTimer spans accumulate per name, so this preload bills to the
    # same "load" span the main path uses.
    preloaded_rows = None
    auto_caps_fp = None  # stream identity at measure time (checked at run)
    if args.auto_caps and args.stage in (STAGE_SINGLE, STAGE_MAP):
        import dataclasses

        _setup_over(args)
        with timer.span("load"), obs.span("cli.load"):
            if args.stream:
                # Bounded-memory measuring pass: the file is read twice
                # (measure, then run) but never materialized — the caps
                # win usually dwarfs the extra host read on device-bound
                # streaming runs.  The fingerprint pins the file identity
                # so a corpus mutated between the passes is caught
                # instead of silently under-sizing the caps.
                measure_stream = loader.StreamingCorpus(
                    args.filename, cfg.line_width, cfg.block_lines,
                    args.line_start, args.line_end,
                )
                auto_caps_fp = measure_stream.fingerprint()
                max_tok, max_per_line = loader.measure_caps_stream(
                    measure_stream
                )
            else:
                preloaded_rows = loader.load_rows(
                    args.filename, cfg.line_width,
                    args.line_start, args.line_end,
                )
                # Measured on the width-truncated rows the engine will
                # actually see (full row bytes, NOT NUL-truncated: an
                # embedded NUL is a token boundary to the device
                # tokenizer and post-NUL tokens still count).
                max_tok, max_per_line = loader.measure_caps(
                    [r.tobytes() for r in preloaded_rows]
                )
        kw, epl = loader.size_caps(
            max_tok, max_per_line, cfg.key_width, cfg.emits_per_line
        )
        cfg = dataclasses.replace(
            cfg,
            key_width=kw,
            emits_per_line=epl,
            table_size=cfg.resolved_table_size,
        )
        print(
            f"[locust] auto-caps: max_token={max_tok}B "
            f"max_tokens/line={max_per_line} -> key_width="
            f"{cfg.key_width} emits_per_line={cfg.emits_per_line}",
            file=sys.stderr,
        )

    # The single-device stage-0/1 path builds its engine INSIDE the
    # compiled plan below; only the stage-2 reduce branch needs one
    # directly (for the normalized combine), so nothing is built twice.
    inter = args.intermediate or [DEFAULT_INTERMEDIATE]

    if args.mesh and args.stage in (STAGE_SINGLE, STAGE_MAP):
        rc = _run_mesh(args, cfg, timer, prof, preloaded_rows, auto_caps_fp)
        if args.trace:
            print(timer.report(), file=sys.stderr)
        return rc

    if args.stage in (STAGE_SINGLE, STAGE_MAP):
        # WordCount runs as a compiled PLAN (docs/PLAN.md): the driver
        # constructs the canonical DAG (source -> tokenize -> group ->
        # sum -> table) and the compiler lowers it back onto this same
        # engine — byte-identical output, the reference's staged timing
        # report intact, and checkpoints still land at the fold-stage
        # boundary (plan/compile.py).
        from locust_tpu.plan import wordcount_plan
        from locust_tpu.plan.compile import compile_plan

        wc_plan = compile_plan(wordcount_plan(), cfg)
        lines_read: list[int] = []  # a block's, as the run reads it
        with prof:
            _setup_over(args)
            with timer.span("load"), obs.span("cli.load"):
                if args.stream:
                    rows = None
                    stream = loader.StreamingCorpus(
                        args.filename, cfg.line_width, cfg.block_lines,
                        args.line_start, args.line_end,
                    )
                    if _stale_auto_caps(stream, auto_caps_fp):
                        return 1
                elif (preloaded_rows is None and not args.no_timing
                        and not args.checkpoint_dir):
                    # The default path never holds the corpus: the file is
                    # opened here and read inside the run, a group of
                    # blocks ahead of the device (engine.timed_run).
                    rows = _counted(lines_read, loader.StreamingCorpus(
                        args.filename, cfg.line_width, cfg.block_lines,
                        args.line_start, args.line_end,
                    ))
                else:
                    # --no-timing (run_fused) and --checkpoint-dir
                    # (run_checkpointed) index the rows, and --auto-caps
                    # has loaded them to measure them: the array, whole.
                    rows = (
                        preloaded_rows
                        if preloaded_rows is not None
                        else loader.load_rows(
                            args.filename, cfg.line_width,
                            args.line_start, args.line_end,
                        )
                    )
                    lines_read.append(rows.shape[0])
            with timer.span("run"), obs.span("cli.run"):
                # Each run method syncs internally, so the span is accurate.
                if args.stream:
                    kw = {}
                    if args.checkpoint_dir:
                        kw = dict(
                            checkpoint_dir=args.checkpoint_dir,
                            every=args.checkpoint_every,
                            fingerprint=stream.fingerprint(),
                        )
                    res = wc_plan.run_stream(stream, **kw)
                else:
                    pres = wc_plan.run(
                        rows,
                        timed=not args.no_timing,
                        render=False,
                        # No pairs: the table on stdout is rendered from
                        # rows (below), and the staged map node dumps the
                        # raw table (dump_intermediate).
                        finalize=False,
                        checkpoint_dir=args.checkpoint_dir or None,
                        every=args.checkpoint_every,
                    )
                    res = pres.run_result
                    print(f"[locust] {sum(lines_read)} lines loaded",
                          file=sys.stderr)
                if args.stage != STAGE_MAP:
                    # The table's way to the host belongs to the run
                    # (engine.finalize), as on the mesh.
                    table = res.to_host_rows()
            if args.stream and res.stream is not None:
                # Zero-stall executor accounting: backpressure stall +
                # checkpoint mark/write stats (engine.run_stream).
                print(f"[locust] stream: {res.stream}", file=sys.stderr)
            if not args.no_timing:
                # The reference's per-stage report (README.md:72-88
                # stages), through SpanTimer.report(): stable descending
                # sort + percent-of-total (format pinned by
                # tests/test_profiling.py).
                st = SpanTimer()
                st.spans_ms = {
                    "Map stage": res.times.map_ms,
                    "Process stage": res.times.process_ms,
                    "Reduce stage": res.times.reduce_ms,
                }
                print(st.report(), file=sys.stderr)
            if res.truncated:
                # The default path's table grows and never lands here;
                # --stream / --no-timing hold a table of fixed size.
                print("[locust] WARN: table capacity exceeded; tail keys "
                      "dropped (--stream and --no-timing hold a table of "
                      "fixed size; the default path grows its table, "
                      "--mesh its shards)",
                      file=sys.stderr)
            with timer.span("output"), obs.span("cli.output"):
                if args.stage == STAGE_MAP:
                    out = inter[0]
                    res.dump_intermediate(out, args.inter_format)
                    print(f"[locust] node {args.node_num}: intermediate written to {out}",
                          file=sys.stderr)
                else:
                    _print_table(table, args.limit)
        if args.trace:
            print(timer.report(), file=sys.stderr)
        return 0

    # STAGE_REDUCE: merge intermediate TSVs from map nodes; always re-sort (Q6).
    with prof:
        _setup_over(args)
        with timer.span("load"), obs.span("cli.load"):
            key_rows_list, values_list = [], []
            for path in inter:
                k, v = serde.read_intermediate(path, cfg.key_width)
                key_rows_list.append(k)
                values_list.append(v)
            keys = np.concatenate(key_rows_list) if key_rows_list else np.zeros((0, cfg.key_width), np.uint8)
            values = np.concatenate(values_list) if values_list else np.zeros((0,), np.int32)
        print(f"[locust] node {args.node_num}: {keys.shape[0]} intermediate pairs "
              f"from {len(inter)} file(s)", file=sys.stderr)
        batch = KVBatch.from_bytes(
            jnp.asarray(keys), jnp.asarray(values), jnp.ones(keys.shape[0], bool)
        )
        from locust_tpu.engine import finalize_host_rows
        from locust_tpu.ops import segment_reduce, sort_and_compact

        eng = MapReduceEngine(cfg)  # stage 2 only: the normalized combine
        with timer.span("run"), obs.span("cli.run"):
            table = segment_reduce(sort_and_compact(batch, cfg.sort_mode), eng.combine)
            rows = finalize_host_rows(table, eng.combine)  # device sync
        with timer.span("output"), obs.span("cli.output"):
            _print_table(rows, args.limit)
    if args.trace:
        print(timer.report(), file=sys.stderr)
    return 0


def _setup_over(args) -> None:
    """``cli.setup``, recorded by whichever ``cli.load`` comes first: from
    ``main``'s entry to here — the parser, backend selection, the lazy
    imports, ``EngineConfig``, the plan compiled or the mesh engine made.
    Recorded once it is over (``obs.span_at``): the tracer exists only
    from the parsed arguments on."""
    if args.trace_out and args.entered is not None:
        obs.span_at("cli.setup", args.entered, time.time())
        args.entered = None


def _counted(lines_read: list[int], blocks):
    """``blocks`` as an iterator, each block's line count noted in
    ``lines_read`` as it goes by (on whichever thread reads it)."""
    for blk in blocks:
        lines_read.append(blk.shape[0])
        yield blk


def _stale_auto_caps(stream, auto_caps_fp) -> bool:
    """True (and prints the error) if the corpus changed between the
    --auto-caps measuring pass and the run pass — under-sized caps would
    silently truncate or drop the new content's tokens otherwise."""
    if auto_caps_fp is None or stream.fingerprint() == auto_caps_fp:
        return False
    print(
        "mapreduce: error: corpus changed between the --auto-caps "
        "measuring pass and the run; re-run (or drop --auto-caps for a "
        "file that is being written to)",
        file=sys.stderr,
    )
    return True


def _run_mesh(args, cfg, timer, prof, preloaded_rows=None,
              auto_caps_fp=None) -> int:
    """Stage 0/1 over ALL visible devices: the CLI face of the mesh engine.

    The reference's distributed mode is CLI-driven (main.cu:358-387,
    README.md:12-24) but its shipped entrypoint is single-GPU; here one
    ``--mesh`` flag routes the same positional contract through the
    all-to-all shuffle (parallel/shuffle.py), so a multi-chip host uses
    every chip.
    """
    import jax

    from locust_tpu.io import loader, serde
    from locust_tpu.parallel.mesh import make_mesh
    from locust_tpu.parallel.shuffle import DistributedMapReduce

    inter = args.intermediate or [DEFAULT_INTERMEDIATE]
    if args.slices:
        from locust_tpu.parallel.hierarchical import HierarchicalMapReduce
        from locust_tpu.parallel.mesh import make_mesh_2d

        mesh = make_mesh_2d(args.slices)
        dmr = HierarchicalMapReduce(mesh, cfg)
        print(
            f"[locust] hierarchical mesh: {dmr.n_slices} slice(s) x "
            f"{dmr.devs_per_slice} device(s), {dmr.lines_per_round} "
            f"lines/round, bin_capacity={dmr.bin_capacity}, "
            f"shard_capacity={dmr.shard_capacity}",
            file=sys.stderr,
        )
    else:
        mesh = make_mesh()
        dmr = DistributedMapReduce(mesh, cfg)
        print(
            f"[locust] mesh: {dmr.n_dev} device(s), {dmr.lines_per_round} "
            f"lines/round, bin_capacity={dmr.bin_capacity}, "
            f"shard_capacity={dmr.shard_capacity} (where the shards "
            "start: they grow together with what they see)",
            file=sys.stderr,
        )
    n_dev = dmr.n_dev
    with prof:
        _setup_over(args)
        t0 = time.perf_counter()
        with timer.span("load"), obs.span("cli.load"):
            kw = {}
            if args.checkpoint_dir:
                kw = dict(
                    checkpoint_dir=args.checkpoint_dir,
                    checkpoint_every=args.checkpoint_every,
                )
            if args.stream:
                stream = loader.StreamingCorpus(
                    args.filename, cfg.line_width, dmr.lines_per_round,
                    args.line_start, args.line_end,
                )
                if _stale_auto_caps(stream, auto_caps_fp):
                    return 1
                if args.checkpoint_dir:
                    kw["fingerprint"] = stream.fingerprint()
            else:
                rows = (
                    preloaded_rows
                    if preloaded_rows is not None
                    else loader.load_rows(
                        args.filename, cfg.line_width,
                        args.line_start, args.line_end,
                    )
                )
                print(f"[locust] {rows.shape[0]} lines loaded", file=sys.stderr)
        with timer.span("run"), obs.span("cli.run"):
            res = (
                dmr.run_stream(stream, **kw)
                if args.stream
                else dmr.run(rows, **kw)
            )
            # gathers + syncs: pairs for the staged map node's file, rows
            # for the table on stdout
            table = (res.to_host_pairs() if args.stage == STAGE_MAP
                     else res.to_host_rows())
        run_ms = (time.perf_counter() - t0) * 1e3

        # Per-shard report: one hash shard per shard_capacity rows (the
        # hierarchical table has devs_per_slice shards, the flat one n_dev)
        # — the capacity the run ENDED at: the flat mesh's shards grow.
        # Gather ONLY the valid mask through the multi-process-safe path —
        # a plain device_get of the sharded table touches non-addressable
        # devices on a pod, and the full-table gather would move
        # key_lanes+values over DCN just to be discarded.
        from locust_tpu.parallel.mesh import gather_host_array

        shard_live = gather_host_array(res.table.valid).reshape(
            -1, res.shard_capacity
        ).sum(axis=1)
        for d in range(shard_live.shape[0]):
            print(
                f"[locust] shard {d}: {int(shard_live[d])} keys",
                file=sys.stderr,
            )
        if res.table_grows:
            print(
                f"[locust] shards grew {dmr.shard_capacity} -> "
                f"{res.shard_capacity} rows in {res.table_grows} step(s)",
                file=sys.stderr,
            )
        print(
            f"[locust] distinct={res.distinct} drain_rounds={res.drain_rounds} "
            f"emit_overflow={res.emit_overflow} "
            f"shuffle_overflow={res.shuffle_overflow} "
            f"truncated={res.truncated} total={run_ms:.1f} ms",
            file=sys.stderr,
        )
        if res.truncated:
            # --mesh alone never lands here: its shards grow.  The
            # hierarchical mesh (--slices) holds shards of fixed size.
            print(
                "[locust] WARN: a shard's table capacity was exceeded; "
                "tail keys dropped (--slices holds shards of fixed size; "
                "--mesh without it grows them, as the default path "
                "grows its table)",
                file=sys.stderr,
            )
        with timer.span("output"), obs.span("cli.output"):
            if args.stage == STAGE_MAP:
                out = inter[0]
                serde.write_intermediate(table, out, args.inter_format)
                print(
                    f"[locust] node {args.node_num}: intermediate written "
                    f"to {out}",
                    file=sys.stderr,
                )
            else:
                _print_table(table, args.limit)
    return 0


def _print_table(table, limit=None) -> None:
    """Final ``key<TAB>count`` table on stdout (analog of printKeyIntValues,
    main.cu:126-134 — we print two columns, not its internal three), its
    first ``limit`` rows.  ``table`` is what ``to_host_rows`` /
    ``finalize_host_rows`` gave the default path, ``--stream``, ``--mesh``
    and the reduce stage: key-ordered ``HostRows``, rendered in numpy
    (``bytes_ops.render_rows``), or — where the data cannot be printed
    from arrays — the sorted pair list, joined a row at a time.  On a
    multi-process pod every process holds the gathered table (the gather
    is an allgather); only process 0 prints so the pod's combined stdout
    is one table, not N interleaved copies."""
    import jax

    from locust_tpu.core import bytes_ops
    from locust_tpu.core.kv import HostRows

    if jax.process_count() > 1 and jax.process_index() != 0:
        return
    # One write: a table of 650,000 rows written a row at a time took 7.7 s
    # to a file on the chip's host, most of a job.
    shown = table[:limit]
    fast = isinstance(shown, HostRows)
    with obs.span("cli.output.render", rows=len(shown), fast=int(fast)):
        if fast:
            out = bytes_ops.render_rows(shown.keys, shown.values)
        else:
            out = b"".join(
                k + b"\t" + str(v).encode() + b"\n" for k, v in shown
            )
    with obs.span("cli.output.write", bytes=len(out)):
        sys.stdout.buffer.write(out)
        sys.stdout.flush()


if __name__ == "__main__":
    raise SystemExit(main())
