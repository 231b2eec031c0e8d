"""The closed telemetry name registry — every span, instant event and
metric the framework can emit, in ONE dict literal.

Closed-registry stance (same as ``faultplan.SITES`` and the analysis rule
table): a typo'd name at an emission site must fail LOUDLY — at runtime
(``Tracer``/``Metrics`` validate against this dict when telemetry is
enabled) and statically (analysis rule R009 checks both directions: every
``obs.span``/``obs.event``/``obs.metric_*`` literal exists here, and
every entry here is emitted somewhere under ``locust_tpu/``).  A name
nobody validates is a timeline nobody can correlate.

Emission convention (what R009 can see): emit through the ``obs`` module
functions with a literal name — ``obs.span("engine.stage.map")``,
``obs.span_at("engine.program.load", t0, t1)`` for a span that is already
over — never a name built at runtime.  Kinds: ``span`` (duration),
``event`` (instant), ``counter``/``gauge``/``histogram`` (metrics).
"""

from __future__ import annotations

NAMES = {
    # --- spans (durations) -------------------------------------------
    "job.run": "span",              # master: one distributor job end-to-end
    "master.map_rpc": "span",       # master: one shard map attempt RPC
    "master.fetch": "span",         # master: one intermediate transfer
    "worker.map": "span",           # worker: one map command (runner incl.)
    "cli.setup": "span",            # CLI: main's entry to the first cli.load — parser, backend, imports, EngineConfig, compile_plan (recorded once over, obs.span_at)
    "cli.load": "span",             # CLI: corpus ingest
    "cli.run": "span",              # CLI: the engine run
    "cli.output": "span",           # CLI: table print / intermediate write
    "cli.output.render": "span",    # CLI: the table's rows made into one buffer — in numpy from ordered rows (fast=1, bytes_ops.render_rows), or joined a row at a time from pairs on the fall-back (fast=0) (args rows, fast)
    "cli.output.write": "span",     # CLI: that buffer written and flushed (arg bytes)
    "engine.stage.map": "span",     # timed_run Map stage (per GROUP of blocks, arg blocks)
    "engine.stage.process": "span", # timed_run Process stage (per group)
    "engine.stage.reduce": "span",  # timed_run Reduce stage (per group)
    "engine.stage.merge": "span",   # timed_run: a group's block tables merged into the table at once (args blocks, tables, merges)
    "engine.table.grow": "span",    # timed_run: table grown + its group merged again
    "engine.h2d": "span",           # one block padded + staged host->device
    "engine.ingest.read": "span",   # one pull from the corpus source: a block read, split and padded (timed_run's reader thread, or inline in the first group)
    "engine.ingest.wait": "span",   # the consumer of a read-ahead queue found it empty and waited for the reader (loader.prefetch_blocks)
    "engine.sync": "span",          # host blocked on the device (arg what)
    "engine.finalize": "span",      # table D2H + decode + the checks or the host sort
    "engine.finalize.d2h": "span",  # ... the device-to-host copy alone; on the mesh the gather of the shards (args bytes, rows)
    "engine.finalize.decode": "span",  # ... live rows masked, lanes to key bytes, numpy argsort, both arrays taken in that order: ends at ordered ROWS (finalize_host_rows, the CLI's table) or goes on to pairs (finalize_host_pairs) (arg rows, live)
    "engine.finalize.order": "span",   # ... for rows three checks over whole arrays (no NUL inside a key, no key twice, no negative value: fast=1), the pairs' way only where one fails (fast=0, reason nul | duplicate | negative); for pairs the duplicate-key check, the merge by hand where it fires, sorted (args rows, merged, fast, reason)
    "engine.program.trace": "span", # jax traced a program (obs/programs.py)
    "engine.program.lower": "span", # ... lowered it to an MLIR module
    "engine.program.load": "span",  # ... compiled it or read it from the cache
    "stream.block": "span",         # run_stream: stage+dispatch of one block
    "mesh.round": "span",           # mesh: one round staged + dispatched (arg lines)
    "mesh.h2d": "span",             # mesh: a round's lines placed on the devices, inside its mesh.round (arg bytes)
    "mesh.sync": "span",            # mesh: host blocked on the devices' stats (arg what: stats | regrow)
    "mesh.table.grow": "span",      # mesh: every shard grown a step, the rounds since the last whole table folded again (args from_rows, to_rows, worst_shard, rounds_redone)
    "mesh.gather": "span",          # mesh: table from its shards to key-ordered host rows (to_host_rows) or sorted pairs (to_host_pairs) (args rows, shards)
    "sort.read": "span",            # record sort: a block of the mapped file found, or its copy where it must be padded (arg bytes)
    "sort.h2d": "span",             # record sort: a staged block handed up and placed (arg bytes; under --mesh also device)
    "sort.keys": "span",            # record sort: the key sort launched and waited for (arg rows)
    "sort.permute": "span",         # record sort: a block's payload gather launched (arg rows; under --mesh also device)
    "sort.d2h": "span",             # record sort: a sorted block brought down (arg bytes)
    "sort.write": "span",           # record sort: a sorted block written to OUT (arg bytes)
    "pagerank.read": "span",        # pagerank CLI: the edge list read whole from its file (arg bytes)
    "pagerank.parse": "span",       # the ONE edge parser (plan.compile.edges_from_bytes, CLI, daemon and workers): fast=1 a clean file read with no Python object an edge, 0 the line loop; native=1 read by the ONE native pass (native_ingest.parse_edges), 0 by numpy or the line loop (args bytes, edges, fast, native)
    "pagerank.h2d": "span",         # pagerank: src and dst put on the device and waited for (arg bytes)
    "pagerank.iterate": "span",     # pagerank: the iterate program dispatched and waited for (its child engine.sync what=iterate); under --mesh ShardedPageRank's whole run (args nodes, edges, iters)
    "pagerank.d2h": "span",         # pagerank: the rank vector brought down (arg bytes)
    "index.read": "span",           # index CLI: the text read whole and padded to rows (loader.load_rows; args bytes, lines)
    "index.h2d": "span",            # index: a block's lines and doc ids handed up (device_put returns at once; arg bytes)
    "index.map": "span",            # index: a GROUP of blocks launched — tokenise, in-block dedup, the survivors appended to the pair store (arg blocks)
    "index.grow": "span",           # index: the pair store grown a step ahead of a group (args from_rows, to_rows, pairs)
    "index.collect": "span",        # index: the store grouped by hash ONCE (jit_index_collect; child engine.sync what=index.entries reads the word entries' count), then the entries ordered by their bytes, the pairs by (rank, doc), deduplicated across blocks and cut into CSR (jit_index_cut; child engine.sync what=index.collect); the two syncs are the job's wait for the device (arg rows)
    "index.d2h": "span",            # index: postings, word keys and offsets brought down (arg bytes)
    "index.render": "span",         # index CLI: the postings' word<TAB>d,d,...<LF> lines made into one buffer from arrays (bytes_ops.render_postings; args words, bytes)
    "index.write": "span",          # index CLI: that buffer written and flushed (arg bytes)
    "join.read": "span",            # join CLI: the Rankings file read whole and padded to rows (loader.load_rows; args table, bytes, lines), then a PULL of a UserVisits block by the reader thread, the file read as the job goes (cli_apps._visit_blocks; args table, lines)
    "join.map": "span",             # join: the Rankings blocks launched, or a GROUP of UserVisits blocks — fields split, the date filter, the rows projected and written into the page table / appended to the visit store, which is grown ahead of the group where it must (args table, blocks, rows)
    "join.h2d": "span",             # join: a block's byte rows handed up (device_put returns at once; arg bytes)
    "join.probe": "span",           # join: the probe program dispatched — pages and passed visits grouped by hash64(URL), matched by a compare of the full key lanes, regrouped by sourceIP, summed as exact integers, ordered by the total — and waited for (child engine.sync what=join.probe reads the counts; args pages, rows)
    "join.d2h": "span",             # join: the ordered groups cut to their ladder size and brought down (arg bytes)
    "join.render": "span",          # join CLI: the sourceIP<TAB>avgPageRank<TAB>totalRevenue<LF> lines made into one buffer from arrays (bytes_ops.render_revenue_rows; args rows, bytes)
    "join.write": "span",           # join CLI: that buffer written and flushed (arg bytes)
    "sort.mesh.split": "span",      # mesh record sort: sample, gather, splitters and their one sync (args samples, splitters)
    "sort.mesh.exchange": "span",   # mesh record sort: bucket, bin, all-to-all, the bin counts read back (args bin_rows, attempt, worst_bin)
    "sort.mesh.retry": "span",      # mesh record sort: parent of an exchange redone with larger bins (args from_bin_rows, to_bin_rows, worst_bin)
    "sort.mesh.shard_sort": "span", # mesh record sort: the shards' key sorts launched and waited for (arg rows of the largest shard)
    "ckpt.write": "span",           # async writer: serialize+publish one gen
    "serve.queue_wait": "span",     # serve: dispatcher waiting on the queue
    "serve.compile_or_hit": "span", # serve: warm-executable cache lookup/build
    "serve.dispatch": "span",       # serve: one coalesced batch dispatch
    "serve.place": "span",          # serve: pool placement decision (pool.py)
    "serve.demux": "span",          # serve: per-job result split + store
    "serve.ship": "span",           # serve: one WAL ship/catch-up RPC (replicate.py)
    "plan.optimize": "span",        # plan: the rewrite pass (optimize.py)
    "plan.compile": "span",         # plan: DAG lowering onto the engine
    "plan.run": "span",             # plan: one compiled-plan execution
    "plan.stage": "span",           # plan: one distributed stage RPC (both sides)
    "plan.shuffle": "span",         # plan: one cross-worker partition transfer
    # --- instant events ----------------------------------------------
    "fault.injected": "event",      # a faultplan rule fired (site, action)
    "ckpt.mark": "event",           # fold loop marked a snapshot generation
    "ckpt.publish": "event",        # finalize_snapshot atomic rename landed
    "ckpt.skip": "event",           # latest-wins replaced a pending mark
    "stream.stall": "event",        # bounded-inflight backpressure sync
    "serve.admit": "event",         # serve: job admitted to the queue
    "serve.reject": "event",        # serve: admission rejected (reason code)
    "serve.retry": "event",         # serve: failed dispatch requeued w/ backoff
    "serve.replay": "event",        # serve: journal replay summary at startup
    "serve.takeover": "event",      # serve: role change (promotion / demotion)
    "backend.breaker_open": "event",       # breaker tripped: primary ineligible
    "backend.breaker_half_open": "event",  # cooldown over: one probe allowed
    "backend.breaker_close": "event",      # probe succeeded: primary restored
    "backend.failover": "event",    # run resumed from checkpoint on fallback
    # --- metrics ------------------------------------------------------
    "job.workers": "gauge",         # cluster size of the running job
    "engine.compile_requests": "counter",  # programs asked of the persistent cache
    "engine.cache_hits": "counter",        # ... and found there (rest compiled)
    "engine.programs_built": "counter",    # engines that built their configuration's programs (engine._programs_for)
    "engine.programs_shared": "counter",   # ... that took the ones the process already held
    "engine.combine_scatters": "gauge",    # scatters over the input rows that the configuration's segment combine issues (reduce_stage.combine_scatters)
    "engine.ingest.blocks_ahead": "counter",   # read-ahead queue: blocks that were there when pulled (loader.prefetch_blocks)
    "engine.ingest.blocks_waited": "counter",  # ... that the consumer waited for; ahead / (ahead + waited) is the hit share
    "engine.table_rows": "gauge",   # timed_run: the table's capacity at the job's end
    "engine.table_grows": "counter",  # timed_run: growth steps the job took
    "engine.merges": "counter",     # timed_run: merge programs launched (one a group + one a group redone)
    "pagerank.edges": "counter",    # pagerank: edges ranked over
    "pagerank.nodes": "counter",    # pagerank: dense node slots (largest id + 1, or --num-nodes)
    "pagerank.iterations": "counter",  # pagerank: rounds run (benchmarks' closed_loop_cli_edges holds a traced job to the configuration's count by it)
    "pagerank.parse.native": "counter",  # pagerank: edge lists parsed by the native pass (0 where the library did not load or the file was not clean)
    "index.pairs": "counter",       # index: distinct (word, doc) pairs = postings out
    "index.words": "counter",       # index: distinct words
    "index.hash_splits": "counter", # index: word entries the collect made minus distinct words — how often a 64-bit hash collision split a word's rows (the cut folds the pieces back; 0 in a sound job)
    "index.docs": "counter",        # index: runs of equal doc ids over the lines (the documents, for ids that follow the lines)
    "index.dropped_tokens": "counter",  # index: tokens past emits_per_line, whose postings are missing
    "index.grows": "counter",       # index: growth steps the pair store took
    "join.pages": "counter",        # join: lines of the Rankings file
    "join.visits": "counter",       # join: lines of the UserVisits file
    "join.passed": "counter",       # join: well-formed visits whose visitDate lies in the window
    "join.matched": "counter",      # join: passed visits whose destURL is a page's (passed - matched were dropped: a key on one side only)
    "join.groups": "counter",       # join: sourceIPs in the table = lines out
    "join.line_overflow": "counter",  # join CLI: lines of either file past --line-width, cut by the loader
    "join.key_overflow": "counter", # join: URLs past --key-width (sourceIPs past 16 bytes), joined by their head
    "join.malformed": "counter",    # join: rows of either file whose fields do not parse, or do not end inside the row; they take no part
    "join.grows": "counter",        # join: growth steps the visit store took
    "sort.records": "counter",      # record sort: records staged on the device
    "sort.bytes_out": "counter",    # record sort: bytes written to OUT
    "sort.mesh.retries": "counter",          # mesh record sort: exchanges redone because a bin overflowed
    "sort.mesh.bin_rows": "gauge",           # mesh record sort: rows a (source, destination) bin held in the exchange that stood
    "sort.mesh.shard_rows_max": "gauge",     # mesh record sort: records of the largest shard
    "sort.mesh.shard_rows_min": "gauge",     # ... and of the smallest
    "sort.mesh.bytes_exchanged": "counter",  # mesh record sort: record bytes that left their device in the all-to-all
    "mesh.rounds": "counter",       # mesh: rounds dispatched (redone ones not counted again)
    "mesh.table_grows": "counter",  # mesh: growth steps the job's shards took
    "mesh.drain_rounds": "counter", # mesh: extra all-to-all rounds the backlog took
    "mesh.shard_rows": "gauge",     # mesh: rows a shard holds at the job's end
    "stream.blocks": "counter",     # blocks folded by run_stream
    "stream.stall_ms": "histogram", # per-sync backpressure stall
    "ckpt.marks": "counter",        # snapshot generations marked
    "fault.injections": "counter",  # faults injected across all sites
    "fetch.bytes": "counter",       # intermediate payload bytes fetched
    "fetch.mb_s": "histogram",      # per-fetch payload throughput
    "serve.jobs": "counter",        # serve: jobs completed by the daemon
    "serve.latency_ms": "histogram",  # serve: per-job submit->done latency
    "serve.exec_cache_hits": "counter",    # warm-executable cache hits
    "serve.exec_cache_misses": "counter",  # ... and compiles/builds paid
    "serve.result_cache_hits": "counter",  # result cache answered a submit
    "serve.affinity_hits": "counter",      # pool placements on the warm worker
    "serve.journal_ms": "histogram",  # per-append journal write latency
    "serve.ship_lag": "gauge",      # replication lag in unacked WAL records
    "backend.breaker_trips": "counter",  # closed->open breaker transitions
    "plan.partition_bytes": "counter",  # published shuffle-partition bytes
    "plan.recomputes": "counter",   # plan stages recomputed after a failure
    "plan.speculated": "counter",   # speculative backup stage attempts
    "plan.rewrites": "counter",     # optimizer rewrites applied (optimize.py)
    "plan.subcache_hits": "counter",    # sub-plan result cache hits
    "plan.subcache_misses": "counter",  # ... and fold recomputes paid
    "plan.solo_fallbacks": "counter",   # plan jobs demoted to the solo engine
    "plan.map_warm_hits": "counter",    # map stages on warm fold-node executables
}

METRIC_KINDS = ("counter", "gauge", "histogram")


def check(name: str, kind: str) -> None:
    """Loud closed-registry validation (enabled-path only)."""
    got = NAMES.get(name)
    if got is None:
        raise ValueError(
            f"telemetry name {name!r} is not in the obs NAMES registry "
            "(locust_tpu/obs/names.py) — register it; a typo'd name "
            "records nothing the timeline can correlate"
        )
    if got != kind:
        raise ValueError(
            f"telemetry name {name!r} is registered as a {got}, "
            f"emitted as a {kind} — kind mismatch"
        )
