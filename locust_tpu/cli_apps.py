"""CLI subcommands for the workload ladder beyond WordCount.

The reference's entire capability is CLI-driven (reference
MapReduce/src/main.cu:358-387, README.md:12-24); ours matched that for
WordCount but left PageRank / inverted index / TF-IDF / the record sort
library-only.
Since the plan layer (docs/PLAN.md) these
drivers no longer hand-wire stage chains: each one CONSTRUCTS the
workload's canonical logical plan (locust_tpu/plan/builders.py) and runs
it through the plan compiler, which lowers onto the same apps/engine
primitives — output byte-identical to the pre-plan drivers (pinned by
tests/test_plan.py).  These subcommands wire the existing apps:

  python -m locust_tpu pagerank <edges.txt> [--mesh] [--num-iters N]
  python -m locust_tpu index  <file> [--mesh] [--lines-per-doc K]
  python -m locust_tpu tfidf  <file> [--lines-per-doc K]
  python -m locust_tpu sort   <in> <out> [--mesh] [--record-bytes 100] [--key-bytes 10]
  python -m locust_tpu join   <rankings> <uservisits> [--date-from D] [--date-to D]

Edge-list format: one ``src dst`` pair of integer node ids per line;
lines starting with ``#`` are comments (the web-Google / SNAP convention,
BASELINE.json configs[3]).  A clean file — comments at its head, then
``src<TAB or SPACE>dst<LF>`` and nothing else — is parsed in numpy; any
other goes through the line loop, which names the line at fault.  Ids
are int32: a larger one, or one past ``--num-nodes``, is an error and
never a wrapped index.  ``pagerank`` prints one ``id<TAB>rank`` line for
every node 0 .. N-1 in id order (``--top K``: the K highest, by rank),
the rank with NINE significant digits as ``d.dddddddde-XX`` — what a
float32 needs to come back bit for bit; eight decimals held two or three
digits of a rank of 1e-6 (``plan.compile.rank_row``).  For index/tfidf
the doc id of line i is ``i // lines_per_doc`` — line-sharded documents,
the same convention as the library tests.  ``index`` prints one
``word<TAB>d1,d2,...<LF>`` line a word, the words in byte order, a word's
documents distinct and ascending — PUMA's Inverted-Index.  On one device
it is a COLLECT (``apps.inverted_index.build_index``, PR 45): every
block's distinct (word, doc) pairs appended to a pair store resident on
the device that grows with the job (no capacity to guess, no size one
device holds an error), the store ordered ONCE, the index brought back as
arrays (words, offsets, postings) and printed from them in numpy
(``bytes_ops.render_postings``; ``--limit N``: the first N words).  What
the fixed widths drop or cut is said on stderr in the WordCount CLI's own
words — ``[locust] index: words= pairs= docs= emit_overflow=
key_overflow= line_overflow= truncated=False ...``, every count zero or
not, and a ``[locust] WARN`` line where one is not — so a driver can hold
"nothing dropped"; plain reference ``locust_tpu/index_reference.py``.
``join`` is HiBench's ``sql/join`` (Pavlo et al.'s Join Task), the one
command with TWO inputs: RANKINGS (``pageURL,pageRank,avgDuration``) and
USERVISITS (``sourceIP,destURL,visitDate,adRevenue,...``), both text, one
row a line, fields apart by ``,``.  It prints one
``sourceIP<TAB>avgPageRank<TAB>totalRevenue<LF>`` line for every sourceIP
with a visit inside ``--date-from .. --date-to`` (both ends in) to a ranked
page, ordered by the total descending, ties by the sourceIP's bytes; both
numbers with nine significant digits, as ``pagerank`` prints a rank.  The
field split, the date filter, the join on the URL's BYTES, the regrouping
by sourceIP and the order all run on the device (``apps.join``); the sums
are exact integers (adRevenue in millionths).  Its result line —
``[locust] join: pages= visits= passed= matched= groups= pages_visited=
line_overflow= key_overflow= malformed= truncated=False ...`` — says what
the inner join dropped and what the fixed widths cut, every count zero or
not, with a ``[locust] WARN`` line where one of the last four is not;
plain reference ``locust_tpu/join_reference.py``.  No capacity is a flag.
``sort`` is TeraSort: IN holds fixed-width binary
records (gensort's: 100 bytes, the first 10 the key), OUT gets every one
of them ordered by key as unsigned bytes, equal keys in input order; a
size that is no whole number of records, or an empty IN, is an error and
exit status 2, and no OUT is written.  An OUT that is already there is
written over in place and cut to size at the end (``serde.write_records``).

``--mesh`` selects the sharded engines (ShardedPageRank — rank state
O(nodes/n_dev) per device —, DistributedInvertedIndex — a fixed pair table
a shard, the index a dict printed a row at a time —, and for ``sort``
the mesh record sort: IN's blocks dealt round the devices, every record
through one all-to-all to the device that owns its key range, OUT
written shard after shard, the same bytes, one ``shard d: n records``
line a device on stderr) over all
visible devices; without it the single-device variants run.  Backend
resolution (probe/fallback) is shared with the WordCount path.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

from locust_tpu import obs  # jax-free; zero-overhead unless --trace-out

SUBCOMMANDS = ("pagerank", "index", "tfidf", "sort", "join")


def _add_backend_flag(p: argparse.ArgumentParser,
                      sort_mode: bool = True) -> None:
    p.add_argument(
        "--backend", choices=["auto", "cpu", "tpu"], default="auto",
        help="auto: whatever jax initializes; cpu: pin the CPU; tpu: "
             "require a TPU, error otherwise (no mode falls back)",
    )
    # Ladder/WordCount CLI parity: every subcommand takes the main CLI's
    # observability + sort-strategy flags, so a plan-compiled ladder run
    # is traceable and tunable with zero new plumbing.
    from locust_tpu.config import SORT_MODES

    if sort_mode:  # the record sort has ONE spelling (process_stage.order_by_lanes)
        p.add_argument(
            "--sort-mode", choices=list(SORT_MODES), default=None,
            help="Process-stage sort strategy (config.EngineConfig."
                 "sort_mode); default follows the measured per-backend "
                 "choice (config.default_sort_mode).  pagerank accepts it "
                 "for ladder parity only — its dense pipeline has no sort.",
        )
    p.add_argument(
        "--trace-out", default=None, metavar="FILE",
        help="structured telemetry (locust_tpu.obs): record the run's "
             "spans/events/metrics (plan.compile/plan.run + engine "
             "stages) and export a Chrome-trace JSON timeline to FILE "
             "(docs/OBSERVABILITY.md)",
    )


def build_parser(cmd: str) -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog=f"locust_tpu {cmd}")
    if cmd == "pagerank":
        p.add_argument("edges", help="edge list: 'src dst' per line, # comments; "
                       "prints 'id<TAB>rank' for every node, the rank with "
                       "nine significant digits (d.dddddddde-XX)")
        p.add_argument("--num-iters", type=int, default=20)
        p.add_argument("--damping", type=float, default=0.85)
        p.add_argument("--num-nodes", type=int, default=None,
                       help="default: max node id in the file + 1")
        p.add_argument("--mesh", action="store_true",
                       help="ShardedPageRank over all visible devices "
                            "(rank state sharded O(nodes/n_dev))")
        p.add_argument("--top", type=int, default=None,
                       help="print only the N highest-ranked nodes")
    elif cmd == "sort":
        from locust_tpu.plan.builders import KEY_BYTES, RECORD_BYTES

        p.add_argument("input", metavar="IN",
                       help="file of fixed-width binary records")
        p.add_argument("output", metavar="OUT",
                       help="file the sorted records are written to (one that is there "
                            "is written over in place)")
        p.add_argument("--record-bytes", type=int, default=RECORD_BYTES,
                       help="bytes a record (gensort: 100)")
        p.add_argument("--key-bytes", type=int, default=KEY_BYTES,
                       help="leading bytes of a record that are its key, "
                            "compared as unsigned bytes (gensort: 10)")
        p.add_argument("--mesh", action="store_true",
                       help="sort across all visible devices: the file's "
                            "blocks dealt round them, every record through "
                            "one all-to-all to the device that owns its key "
                            "range (sampled splitters), the shards written "
                            "in turn — the same bytes in OUT")
    elif cmd == "join":
        from locust_tpu.plan.builders import DATE_FROM, DATE_TO

        p.add_argument("rankings", metavar="RANKINGS",
                       help="text, a row a line: pageURL,pageRank,avgDuration")
        p.add_argument("uservisits", metavar="USERVISITS",
                       help="text, a row a line: sourceIP,destURL,visitDate,"
                            "adRevenue,... (visitDate YYYY-MM-DD, adRevenue a "
                            "decimal of at most six places)")
        p.add_argument("--date-from", default=DATE_FROM, metavar="YYYY-MM-DD",
                       help="the first visitDate that passes")
        p.add_argument("--date-to", default=DATE_TO, metavar="YYYY-MM-DD",
                       help="the last visitDate that passes")
        p.add_argument("--block-lines", type=int, default=4096)
        p.add_argument("--line-width", type=int, default=256,
                       help="bytes of a row on the device; a longer line is "
                            "cut and counted (line_overflow=)")
        p.add_argument("--key-width", type=int, default=128,
                       help="bytes of a URL the join compares; a longer one "
                            "is joined by its head and counted (key_overflow=)")
    else:
        p.add_argument("filename", help="input text file")
        p.add_argument("--lines-per-doc", type=int, default=1,
                       help="doc id of line i = i // K (default 1: "
                            "one document per line)")
        p.add_argument("--mesh", action="store_true",
                       help="build across all visible devices "
                            "(DistributedInvertedIndex shuffle)")
        p.add_argument("--limit", type=int, default=None,
                       help="print only the first N table rows")
        p.add_argument("--block-lines", type=int, default=4096)
        p.add_argument("--line-width", type=int, default=128)
        p.add_argument("--key-width", type=int, default=32)
        p.add_argument("--emits-per-line", type=int, default=20)
    _add_backend_flag(p, sort_mode=cmd not in ("sort", "join"))
    return p


def load_edges(path: str) -> tuple[np.ndarray, np.ndarray]:
    """Parse a SNAP-style edge list; loud error on malformed lines.

    Delegates to the ONE byte-level parser
    (``plan.compile.edges_from_bytes``) so the CLI and a pagerank plan
    submitted to the serve daemon can never disagree about the format;
    the file path is prefixed onto any parse error for CLI context."""
    from locust_tpu.plan import PlanError
    from locust_tpu.plan.compile import edges_from_bytes

    with obs.span("pagerank.read") as sp:
        with open(path, "rb") as f:
            data = f.read()
        sp.set(bytes=len(data))
    try:
        return edges_from_bytes(data)
    except PlanError as e:
        raise ValueError(f"{path}: {e}")


def run_pagerank(args) -> int:
    from locust_tpu.plan import pagerank_plan
    from locust_tpu.plan.compile import (
        MAX_NODE_ID, compile_plan, rank_row, render_ranks,
    )

    # The driver constructs the canonical plan and lets the compiler
    # pick the lowering (apps.pagerank single-device vs ShardedPageRank
    # under --mesh) — same value, byte-identical output (docs/PLAN.md).
    rank_plan = compile_plan(
        pagerank_plan(num_iters=args.num_iters, damping=args.damping),
        mesh=args.mesh,
    )
    if args.trace_out:  # main's entry to the first cli.load, once it is over
        obs.span_at("cli.setup", args.entered, time.time())
    with obs.span("cli.load"):
        src, dst = load_edges(args.edges)
        top = int(max(src.max(), dst.max()))
        n = args.num_nodes if args.num_nodes is not None else top + 1
        # Both bounds before anything is put on the device, where an
        # index past the end is clamped or dropped and never an error.
        if top >= n or n > MAX_NODE_ID + 1:
            print(
                f"locust_tpu: error: --num-nodes {n} but max node id is "
                f"{top}: every id must lie under --num-nodes, and "
                f"--num-nodes under {MAX_NODE_ID + 1} (ids are int32)",
                file=sys.stderr,
            )
            return 1
        print(f"[locust] {src.shape[0]} edges loaded, {n} nodes",
              file=sys.stderr)
    with obs.span("cli.run"):
        ranks = rank_plan.run(
            (src, dst), num_nodes=n, render=False
        ).value
    with obs.span("cli.output"):
        whole = args.top is None
        with obs.span("cli.output.render", fast=int(whole)) as sp:
            if whole:
                out = render_ranks(ranks)
            else:
                order = np.argsort(-ranks, kind="stable")[: args.top]
                out = b"".join(rank_row(int(i), ranks[i]) for i in order)
            sp.set(rows=out.count(b"\n"))
        with obs.span("cli.output.write", bytes=len(out)):
            sys.stdout.buffer.write(out)
            sys.stdout.buffer.flush()
    return 0


def _docs_config(args):
    import jax

    from locust_tpu.config import EngineConfig, default_sort_mode

    return EngineConfig(
        block_lines=args.block_lines,
        line_width=args.line_width,
        key_width=args.key_width,
        emits_per_line=args.emits_per_line,
        # Measured per-backend Process default (backend already selected
        # by main's select_backend_cli); apps inherit the same fold wins.
        # --sort-mode overrides it, same as the WordCount CLI.
        sort_mode=args.sort_mode or default_sort_mode(jax.default_backend()),
    )


def _load_docs(args):
    from locust_tpu.io import loader

    cfg = _docs_config(args)
    return cfg, loader.load_rows(args.filename, cfg.line_width)


def _lines_cut(path: str, rows: np.ndarray) -> int:
    """Lines of ``path`` longer than the rows' width, which the loader
    cut.  Only a line that fills its row to the last byte can have been
    longer, and a file whose lines fit has none: the file is read again,
    and its line lengths taken, only where there is such a row."""
    return _lines_longer(path, rows.shape[1], np.flatnonzero(rows[:, -1]))


def _lines_longer(path: str, width: int, full: np.ndarray) -> int:
    """Of the lines ``full`` of ``path`` (the rows filled to their last
    byte), those longer than ``width``."""
    if not full.size:
        return 0
    with open(path, "rb") as f:
        data = np.frombuffer(f.read(), np.uint8)
    ends = np.flatnonzero(data == ord("\n"))
    if data[-1] != ord("\n"):
        ends = np.append(ends, data.size)
    starts = np.concatenate([[0], ends[:-1] + 1])
    length = ends - starts
    # a CR before the LF is not content (the loader strips it)
    length -= (length > 0) & (data[np.maximum(ends - 1, 0)] == ord("\r"))
    return int(np.count_nonzero(length[full] > width))


def _visit_blocks(path: str, cfg, full: list):
    """The UserVisits file's blocks as the job reads them — every pull a
    ``join.read`` span of the thread that pulls —, the rows filled to
    their last byte noted in ``full`` as they go by."""
    from locust_tpu.io import loader

    source = iter(loader.StreamingCorpus(path, cfg.line_width, cfg.block_lines))
    at, end = 0, object()
    while True:
        with obs.span("join.read", table="uservisits") as sp:
            blk = next(source, end)
            if blk is not end:
                sp.set(lines=blk.shape[0])
        if blk is end:
            return
        full.append(np.flatnonzero(blk[:, -1]) + at)
        at += blk.shape[0]
        yield blk


def run_index(args) -> int:
    from locust_tpu.io import loader
    from locust_tpu.plan import index_plan
    from locust_tpu.plan.compile import compile_plan, render_postings

    t0 = time.perf_counter()
    cfg = _docs_config(args)
    # Plan-compiled: the source node derives the line->doc sharding
    # (``i // lines_per_doc``, the module contract above) and the
    # compiler lowers onto apps.inverted_index (build_index, a collect
    # whose result is arrays; DistributedInvertedIndex under --mesh).
    plan = compile_plan(index_plan(args.lines_per_doc), cfg, mesh=args.mesh)
    if args.trace_out:  # main's entry to the first cli.load, once it is over
        obs.span_at("cli.setup", args.entered, time.time())
    with obs.span("cli.load"):
        with obs.span("index.read") as sp:
            rows = loader.load_rows(args.filename, cfg.line_width)
            sp.set(bytes=os.path.getsize(args.filename), lines=rows.shape[0])
        cut_lines = _lines_cut(args.filename, rows)
        print(f"[locust] {rows.shape[0]} lines loaded", file=sys.stderr)
    with obs.span("cli.run"):
        res = plan.run(rows, render=False, finalize=False)
    index = res.value
    if isinstance(index, dict):  # --mesh: the shards' union, a dict
        return _print_rendered("postings", index, args.limit)
    # What the job dropped or cut, in the WordCount CLI's own words
    # (emit_overflow=, truncated=): a driver holds "nothing dropped" by
    # this line, so every count is on it, zero or not.
    print(
        f"[locust] index: words={len(index)} pairs={index.postings.shape[0]} "
        f"docs={-(-rows.shape[0] // args.lines_per_doc)} "
        f"emit_overflow={index.dropped_tokens} key_overflow={index.cut_keys} "
        f"line_overflow={cut_lines} truncated=False "
        f"store_rows={index.store_rows} grows={index.grows} "
        f"total={(time.perf_counter() - t0) * 1e3:.1f} ms",
        file=sys.stderr,
    )
    if index.dropped_tokens or index.cut_keys or cut_lines:
        print(
            "[locust] WARN: the index is NOT the file's: "
            f"{index.dropped_tokens} token(s) past --emits-per-line "
            f"{cfg.emits_per_line} have no posting, {index.cut_keys} key(s) "
            f"past --key-width {cfg.key_width} are indexed by their head, "
            f"{cut_lines} line(s) past --line-width {cfg.line_width} were cut",
            file=sys.stderr,
        )
    with obs.span("cli.output"):
        with obs.span("index.render", words=len(index)) as sp:
            out = render_postings(index, args.limit)
            sp.set(bytes=len(out))
        with obs.span("index.write", bytes=len(out)):
            sys.stdout.buffer.write(out)
            sys.stdout.buffer.flush()
    return 0


def run_join(args) -> int:
    from locust_tpu.apps.join import GROUP_BLOCKS
    from locust_tpu.io import loader
    from locust_tpu.plan.compile import compile_plan, render_revenue

    t0 = time.perf_counter()
    cfg = args.cfg
    # Plan-compiled: two delimited sources with distinct inputs, and the
    # chain map -> join -> shuffle -> reduce -> sort lowered onto apps.join.
    plan = compile_plan(args.plan, cfg)
    if args.trace_out:  # main's entry to the first cli.load, once it is over
        obs.span_at("cli.setup", args.entered, time.time())
    with obs.span("cli.load"):
        # Rankings whole: the page table is sized from its line count.
        # UserVisits is only OPENED here and read inside the run, a group
        # of blocks ahead of the device on a reader thread, never held
        # whole (as the default WordCount path reads its file).
        with obs.span("join.read", table="rankings") as sp:
            pages = loader.load_rows(args.rankings, cfg.line_width)
            sp.set(bytes=os.path.getsize(args.rankings), lines=pages.shape[0])
        cut_lines = _lines_cut(args.rankings, pages)
        os.stat(args.uservisits)  # a missing file is this span's error
        full: list = []
        visits = loader.prefetch_blocks(
            _visit_blocks(args.uservisits, cfg, full), depth=GROUP_BLOCKS)
    with obs.span("cli.run"):
        joined = plan.run({"rankings": pages, "uservisits": visits},
                          render=False).value
    cut_lines += _lines_longer(args.uservisits, cfg.line_width,
                               np.concatenate(full or [np.zeros(0, np.int64)]))
    print(f"[locust] {joined.pages} + {joined.visits} lines loaded",
          file=sys.stderr)
    obs.metric_inc("join.line_overflow", cut_lines)
    # What the inner join dropped and what the fixed widths cut, in the
    # index CLI's own words: a driver holds "nothing cut" by this line, so
    # every count is on it, zero or not.
    print(
        f"[locust] join: pages={joined.pages} visits={joined.visits} "
        f"passed={joined.passed} matched={joined.matched} "
        f"groups={len(joined)} pages_visited={joined.pages_visited} "
        f"line_overflow={cut_lines} key_overflow={joined.cut_keys} "
        f"malformed={joined.malformed} truncated=False "
        f"store_rows={joined.store_rows} grows={joined.grows} "
        f"total={(time.perf_counter() - t0) * 1e3:.1f} ms",
        file=sys.stderr,
    )
    if cut_lines or joined.cut_keys or joined.malformed:
        print(
            "[locust] WARN: the table is NOT the files': "
            f"{cut_lines} line(s) past --line-width {cfg.line_width} were cut, "
            f"{joined.cut_keys} key(s) past --key-width {cfg.key_width} (a "
            f"sourceIP's 16 bytes) are joined by their head, "
            f"{joined.malformed} row(s) whose fields do not parse take no part",
            file=sys.stderr,
        )
    with obs.span("cli.output"):
        with obs.span("join.render", rows=len(joined)) as sp:
            out = render_revenue(joined)
            sp.set(bytes=len(out))
        with obs.span("join.write", bytes=len(out)):
            sys.stdout.buffer.write(out)
            sys.stdout.buffer.flush()
    return 0


def _print_rendered(op: str, value, limit) -> int:
    """Print through the plan sink's ONE row renderer
    (plan.compile.iter_rendered) — the driver's stdout and a plan
    job's rendered result stay byte-identical by construction."""
    from locust_tpu.plan.compile import iter_rendered

    out = sys.stdout.buffer
    for i, row in enumerate(iter_rendered(op, value)):
        if limit is not None and i >= limit:
            break
        out.write(row)
    out.flush()
    return 0


def run_tfidf(args) -> int:
    cfg, rows = _load_docs(args)
    from locust_tpu.plan import tfidf_plan
    from locust_tpu.plan.compile import compile_plan

    scores = compile_plan(
        tfidf_plan(args.lines_per_doc), cfg
    ).run(rows, render=False).value
    return _print_rendered("tfidf", scores, args.limit)


def run_sort(args, source) -> int:
    from locust_tpu.config import EngineConfig
    from locust_tpu.io import serde
    from locust_tpu.plan import records_sort_plan
    from locust_tpu.plan.compile import compile_plan

    # Plan-compiled like the rest of the ladder: source/records ->
    # sort/by_key -> sink/records onto engine.RecordSort.  The driver
    # evaluates the source and the sink itself, as the WordCount CLI
    # loads its rows and prints its table, so ingest, sort and output
    # show as cli.load / cli.run / cli.output.
    sort_plan = compile_plan(
        records_sort_plan(args.record_bytes, args.key_bytes), EngineConfig(),
        mesh=args.mesh,
    )
    if args.trace_out:  # main's entry to the first cli.load, once it is over
        obs.span_at("cli.setup", args.entered, time.time())
    with obs.span("cli.load"):
        staged = sort_plan.load_records(source)
        print(f"[locust] {staged.n_records} records of "
              f"{args.record_bytes} bytes loaded", file=sys.stderr)
    with obs.span("cli.run"):
        from locust_tpu.parallel.record_sort import BinOverflow

        try:
            ordered = sort_plan.run(staged, render=False).value
        except BinOverflow:
            # Not every record found a place (the mesh's retry budget):
            # an OUT left by an earlier job must not pass for this one's.
            if os.path.exists(args.output):
                try:
                    os.unlink(args.output)
                except OSError:  # no name to remove (/proc/self/fd/N): emptied
                    os.truncate(args.output, 0)
            raise
    with obs.span("cli.output"):
        written = serde.write_records(args.output, ordered.host_blocks())
    for d, rows in enumerate(getattr(ordered, "shard_rows", ())):
        # The range partition, as the WordCount mesh reports its hash
        # shards: shard d's keys all precede shard d + 1's.
        print(f"[locust] shard {d}: {rows} records", file=sys.stderr)
    print(f"[locust] sorted by the first {args.key_bytes} bytes: "
          f"{written // args.record_bytes} records, {written} bytes written "
          f"to {args.output}; {source.nbytes - written} bytes lost",
          file=sys.stderr)
    return 0 if written == source.nbytes else 1


def main(cmd: str, argv, entered: float) -> int:
    """``entered``: ``time.time()`` at ``cli.main``'s entry, where the
    job's ``cli.setup`` span starts."""
    args = build_parser(cmd).parse_args(argv)
    args.entered = entered
    # Pure argument validation BEFORE backend resolution: a trivially
    # invalid invocation must not pay a backend init (and take the
    # chip) before its error prints.
    if cmd == "tfidf" and args.mesh:
        print(
            "locust_tpu: error: tfidf has no mesh variant (the tf pair "
            "table is device-bounded; use index --mesh for the "
            "distributed path)",
            file=sys.stderr,
        )
        return 2
    source = None
    if cmd == "sort":
        if not 1 <= args.key_bytes <= args.record_bytes:
            print(f"locust_tpu: error: --key-bytes {args.key_bytes} must lie "
                  f"in 1..--record-bytes ({args.record_bytes})", file=sys.stderr)
            return 2
        from locust_tpu.io.loader import RecordSource

        # The file's size is checked before the backend is taken: a cut
        # or missing record is an error, never a shorter OUT.
        try:
            source = RecordSource.open(args.input, args.record_bytes)
        except (OSError, ValueError) as e:
            print(f"locust_tpu: error: {e}", file=sys.stderr)
            return 2
    elif cmd == "join":
        from locust_tpu.config import EngineConfig
        from locust_tpu.plan import join_visits_plan

        # The widths by EngineConfig's own checks, the dates by the plan's.
        try:
            args.cfg = EngineConfig(block_lines=args.block_lines,
                                    line_width=args.line_width, key_width=args.key_width)
            args.plan = join_visits_plan(args.date_from, args.date_to)
            if args.date_from > args.date_to:
                raise ValueError("--date-from lies after --date-to")
        except ValueError as e:
            print(f"locust_tpu: error: {e}", file=sys.stderr)
            return 2
    elif cmd != "pagerank" and args.lines_per_doc < 1:
        print("locust_tpu: error: --lines-per-doc must be >= 1",
              file=sys.stderr)
        return 2
    if cmd == "pagerank":
        if args.num_nodes is not None and args.num_nodes < 1:
            print("locust_tpu: error: --num-nodes must be >= 1",
                  file=sys.stderr)
            return 2
        if args.top is not None and args.top < 1:
            print("locust_tpu: error: --top must be >= 1", file=sys.stderr)
            return 2
    from locust_tpu.backend import select_backend_cli

    if select_backend_cli(args.backend) is None:
        return 1
    if args.trace_out:
        obs.enable(process="cli")
    try:
        if cmd == "pagerank":
            return run_pagerank(args)
        if cmd == "index":
            return run_index(args)
        if cmd == "sort":
            return run_sort(args, source)
        if cmd == "join":
            return run_join(args)
        return run_tfidf(args)
    except (OSError, ValueError) as e:
        print(f"locust_tpu: error: {e}", file=sys.stderr)
        return 1
    finally:
        if args.trace_out:
            # Same stance as the WordCount CLI: telemetry must not take
            # down (or re-color) the run — an unwritable trace path is a
            # warning, never the exit status.
            try:
                obs.export(args.trace_out)
                print(f"[locust] trace written to {args.trace_out}",
                      file=sys.stderr)
            except OSError as e:
                print(f"[locust] trace export to {args.trace_out} "
                      f"failed: {e}", file=sys.stderr)
            obs.disable()
