"""Lower a validated logical plan onto the existing engine/mesh tiers.

NO new device code lives here (the tentpole's constraint): compilation
pattern-matches subgraphs of the DAG onto the primitives the repo
already trusts —

  * ``map(tokenize_count) → shuffle(by_key) → reduce(sum)`` over a text
    source fuses into the engine's one-sort-per-block fold
    (``MapReduceEngine``; ``DistributedMapReduce`` under ``mesh=True``),
    exactly the WordCount pipeline — so a plan-compiled run IS the
    hand-wired run, byte for byte, and checkpoint placement rides the
    fold-stage boundary (``run_checkpointed``/``run_stream``);
  * ``map(tokenize_pairs) → shuffle → reduce(sum)`` fuses into the
    composite-key tf fold (``apps.tfidf.term_doc_counts``);
  * ``map(tokenize_pairs) → shuffle → reduce(collect_docs)`` fuses into
    the inverted-index fold (``apps.inverted_index``, mesh variant under
    ``mesh=True``);
  * ``map(tfidf_score)`` over a tf table is the host-side rescore fold
    (df/n_docs over a table orders of magnitude smaller than the
    corpus — the ``build_tfidf`` stance);
  * ``iterate(pagerank)`` over an edge source lowers onto
    ``apps.pagerank`` (``ShardedPageRank`` under ``mesh=True``);
  * ``sort(by_key)`` over a records source lowers onto the engine's
    record sort (``engine.RecordSort``): the records whole on the device,
    their key bytes and a row index through one ``lax.sort``, the payload
    permuted by the sorted index — no combiner, every record kept;
  * ``join(inner)`` merges two terminal tables on key — a host fold
    over device-built tables, like every other table-level finalize;
  * ``map(select_visits)`` and ``map(select_pages)`` over two delimited
    sources, ``join(inner)`` of their keyed ROWS, ``shuffle(by_key) →
    reduce(sum_avg)`` and ``sort(by_value)`` fuse into the device join of
    ``apps.join`` (HiBench's sql/join): field split, filter, join on the
    URL's bytes, regrouping by sourceIP and the order all on the device;
  * ``sink`` renders the terminal value to the EXACT bytes the
    hand-wired CLI drivers print (the byte-identity contract the tests
    pin).

A composition outside these signatures is a loud ``PlanError`` at
compile time, never a silently-wrong execution.  jax-free at import
(jax enters inside ``run``) so the serve control plane can compile-check
plans without a backend.
"""

from __future__ import annotations

import collections.abc
import dataclasses

from locust_tpu import obs
from locust_tpu.plan.nodes import Node, Plan, PlanError
from locust_tpu.plan.optimize import (
    incremental_delta,
    optimize as optimize_plan,
    record_rewrite,
)

# Serve-side bound on the pagerank state size: ``num_nodes`` derives
# from the max node id in the CORPUS, so a 12-byte submit naming node
# 2e9 would otherwise allocate multi-GB dense rank/degree vectors inside
# the multi-tenant daemon (overload must reject, never OOM —
# serve/daemon.py).  2^24 nodes ≈ 67 MB per dense float32 vector.  The
# CLI path (``run()``) stays unbounded like the pre-plan driver: a
# single-tenant process may spend its own memory.
SERVE_MAX_PAGERANK_NODES = 1 << 24

# Lowered stage shapes (the compiler's internal vocabulary; every
# NODE_KINDS entry is matched somewhere below — analysis rule R014
# checks this file for exactly that).
_FOLDS = {
    ("tokenize_count", "sum"): "wordcount",
    ("tokenize_pairs", "sum"): "tf",
    ("tokenize_pairs", "collect_docs"): "index",
}


@dataclasses.dataclass
class PlanResult:
    """One executed plan: the workload-shaped ``value`` (pairs list /
    dict / ranks array), the sink-rendered ``output`` bytes (``None``
    when ``render=False``), and the loss/limit accounting the serve
    tier reports."""

    value: object
    output: bytes | None
    distinct: int
    truncated: bool
    overflow_tokens: int
    run_result: object | None = None  # engine RunResult (wordcount fold)


class CompiledPlan:
    """A plan lowered to an executable stage tree.

    Holds the underlying engine lazily and reuses it across ``run``
    calls, so a resident ``CompiledPlan`` (the serve tier's warm-
    executable cache holds these for plan jobs) keeps its jit caches
    warm exactly like a resident ``MapReduceEngine`` does.
    """

    def __init__(self, plan: Plan, cfg=None, mesh: bool = False,
                 optimize: bool = True):
        self.plan = plan  # the ORIGINAL plan: cache/WAL identity
        self.cfg = cfg
        self.mesh = mesh
        self._engine = None  # lazy MapReduceEngine (wordcount fold)
        self._sorter = None  # lazy engine.RecordSort (sort stage)
        # The rewrite pass (plan/optimize.py) runs between validation
        # and lowering; ``self.plan`` stays the original so every
        # fingerprint-keyed identity (warm/result caches, WAL replay,
        # batch keys) is untouched, and the LOWERED plan is the
        # optimizer's output — byte-identical by the rule contracts.
        self.optimized = (
            optimize_plan(plan, cfg=cfg, mesh=mesh) if optimize else None
        )
        self._lowered = (
            self.optimized.plan if self.optimized is not None else plan
        )
        with obs.span("plan.compile", plan=plan.fingerprint()):
            self._by_id = self._lowered.by_id()
            self._sink = self._lowered.sink()
            self._stages: dict[str, tuple] = {}
            self._root = self._lower(self._sink.id)
        if cfg is None and any(
            n.kind == "source" and n.op in ("text", "records", "delimited")
            for n in plan.nodes
        ):
            raise PlanError(
                "a plan with a text, records or delimited source needs an "
                "EngineConfig"
            )
        if mesh and any(s[0] == "visit_join" for s in self._stages.values()):
            raise PlanError(
                "the rows join has no mesh lowering: one device holds both "
                "tables whole"
            )
        if mesh and self._needs_mesh_guard():
            raise PlanError(
                "the tf fold has no mesh lowering (the pair table is "
                "device-bounded; use the index plan for the distributed "
                "path)"
            )

    def _needs_mesh_guard(self) -> bool:
        return any(
            s[0] == "fold" and s[1] == "tf" for s in self._stages.values()
        )

    # ------------------------------------------------------------ lowering

    def _lower(self, nid: str) -> str:
        """Classify node ``nid`` (and its producers) into a stage;
        returns the stage id (== node id).  Memoized so a multi-consumer
        node lowers (and later executes) once."""
        if nid in self._stages:
            return nid
        n = self._by_id[nid]
        if n.kind == "source":
            stage = ("source", n)
        elif n.kind == "reduce":
            shuf = self._by_id[n.inputs[0]]
            if shuf.kind != "shuffle":
                raise PlanError(
                    f"node {n.id!r}: reduce must consume a shuffle node "
                    "(the engine fuses group+combine into one sort)"
                )
            mapper = self._by_id[shuf.inputs[0]]
            if mapper.kind != "map":
                raise PlanError(
                    f"node {shuf.id!r}: shuffle must consume a map node"
                )
            fold = _FOLDS.get((mapper.op, n.op))
            if fold is None:
                raise PlanError(
                    f"node {n.id!r}: no fold lowering for map "
                    f"{mapper.op!r} + reduce {n.op!r}"
                )
            src_id = self._lower(mapper.inputs[0])
            stage = ("fold", fold, src_id)
        elif n.kind == "map" and n.op == "tfidf_score":
            tf_id = self._lower(n.inputs[0])
            tf_stage = self._stages[tf_id]
            if not (tf_stage[0] == "fold" and tf_stage[1] == "tf"):
                raise PlanError(
                    f"node {n.id!r}: tfidf_score must consume the tf fold"
                )
            composed = (
                self.optimized is not None
                and n.id in self.optimized.composed_scores
            )
            stage = ("score", tf_id, composed)
        elif n.kind == "map":
            # tokenize maps only exist fused under a shuffle+reduce; a
            # bare token stream has no materialization (the fixed-slot
            # emit tensor is an engine-internal shape).
            raise PlanError(
                f"node {n.id!r}: map {n.op!r} must feed a "
                "shuffle -> reduce chain"
            )
        elif n.kind == "shuffle":
            raise PlanError(
                f"node {n.id!r}: shuffle must feed a reduce node (the "
                "engine's one-sort fold groups and combines together)"
            )
        elif n.kind == "sort" and n.op == "by_value":
            stage = self._lower_visit_join(n)
        elif n.kind == "sort":
            src_id = self._lower(n.inputs[0])
            src = self._by_id[src_id]
            if not (src.kind == "source" and src.op == "records"):
                raise PlanError(  # pragma: no cover - typing owns this
                    f"node {n.id!r}: sort(by_key) must consume a records source"
                )
            from locust_tpu.plan.builders import KEY_BYTES, RECORD_BYTES

            record_bytes = src.param("record_bytes", RECORD_BYTES)
            key_bytes = n.param("key_bytes", KEY_BYTES)
            if key_bytes > record_bytes:
                raise PlanError(
                    f"node {n.id!r}: key_bytes {key_bytes} is more than the "
                    f"source's record_bytes {record_bytes}"
                )
            stage = ("record_sort", src_id, record_bytes, key_bytes)
        elif n.kind == "join" and self._lowered.node_types()[nid] != "table":
            # Typing lets only shuffle(by_key) consume a rows join, and
            # only the chain _lower_visit_join matches reach a sink.
            raise PlanError(  # pragma: no cover - typing owns this
                f"node {n.id!r}: a join of keyed rows must feed "
                "shuffle -> reduce(sum_avg) -> sort(by_value)"
            )
        elif n.kind == "join":
            left = self._lower(n.inputs[0])
            right = self._lower(n.inputs[1])
            stage = ("join", left, right, n.param("combine", "sum"))
        elif n.kind == "iterate":
            src_id = self._lower(n.inputs[0])
            src = self._by_id[src_id]
            if not (src.kind == "source" and src.op == "edges"):
                raise PlanError(
                    f"node {n.id!r}: iterate(pagerank) must consume an "
                    "edges source"
                )
            stage = ("pagerank", src_id,
                     n.param("num_iters", 20), n.param("damping", 0.85))
        elif n.kind == "sink":
            stage = ("render", n.op, self._lower(n.inputs[0]))
        else:  # pragma: no cover - Plan validation owns kind closure
            raise PlanError(f"node {n.id!r}: unknown kind {n.kind!r}")
        self._stages[nid] = stage
        return nid

    def _lower_visit_join(self, order: Node) -> tuple:
        """``sort(by_value) <- reduce(sum_avg) <- shuffle(by_key) <-
        join(inner) <- (map(select_visits) <- source, map(select_pages) <-
        source)``: the whole chain is ONE lowered stage, ``apps.join`` —
        the typing admits no other producer of any link, so walking the
        inputs is the match."""
        by_id = self._by_id
        reducer = by_id[order.inputs[0]]
        join = by_id[by_id[reducer.inputs[0]].inputs[0]]
        visits, pages = (by_id[i] for i in join.inputs)
        from locust_tpu.plan.builders import DATE_FROM, DATE_TO

        date_from = visits.param("date_from", DATE_FROM)
        date_to = visits.param("date_to", DATE_TO)
        if date_from > date_to:
            raise PlanError(
                f"node {visits.id!r}: date_from {date_from} lies after "
                f"date_to {date_to}"
            )
        return ("visit_join", self._lower(visits.inputs[0]),
                self._lower(pages.inputs[0]), date_from, date_to)

    # ----------------------------------------------------------- execution

    def run(
        self,
        data=None,
        *,
        num_nodes: int | None = None,
        max_nodes: int | None = None,
        timed: bool = False,
        render: bool = True,
        finalize: bool = True,
        checkpoint_dir: str | None = None,
        every: int = 8,
        sub_cache=None,
        corpus_sha: str | None = None,
        corpus_bytes: bytes | None = None,
    ) -> PlanResult:
        """Execute the compiled plan.

        ``data`` feeds the source node(s): a rows array / list of line
        bytes for a text source — or, for the ``timed`` wordcount fold
        alone, an ITERATOR of its host row blocks
        (``iter(loader.StreamingCorpus(...))``), read a group ahead of the
        device and never held whole —, an ``(src, dst)`` edge-array pair for
        an edges source, or a ``{input_name: data}`` dict when sources
        name distinct inputs (``source`` param ``input``; default
        ``"corpus"``).  ``timed`` routes the wordcount fold through
        ``timed_run`` (the reference's stage report); ``checkpoint_dir``
        places crash-resumable snapshots at the fold-stage boundary
        (``run_checkpointed``).  ``render=False`` skips the sink's
        output-bytes rendering (CLI drivers print from ``value``);
        ``finalize=False`` additionally skips the wordcount fold's
        host-pairs decode (``value`` comes back None, ``run_result``
        carries the device table) — for the CLI, which prints its table
        from ordered rows (``RunResult.to_host_rows``) and whose staged
        map node only dumps the raw table: the pair list would be paid
        and discarded.  For the single-device index fold it leaves
        ``value`` the index AS ARRAYS (``apps.inverted_index.Postings``)
        and builds no dict — an ``int`` a posting that the CLI, which
        prints from the arrays, would pay and discard.  Only a plan whose
        sink consumes one of those two folds directly may skip it.
        """
        stage = self._stages[self._stages[self._root][2]]
        if not finalize and not (
            stage[0] == "fold" and stage[1] in ("wordcount", "index")
        ):
            raise PlanError(
                "finalize=False is only meaningful for a sink fed by "
                "the wordcount or the index fold (other stages need the "
                "decoded value)"
            )
        if not finalize and render:
            # There is no decoded value to render — a None would reach
            # _render as a raw TypeError instead of a loud PlanError.
            raise PlanError("finalize=False requires render=False")
        with obs.span("plan.run", plan=self.plan.fingerprint()):
            ctx = _RunCtx(self, data, num_nodes, timed,
                          checkpoint_dir, every, finalize=finalize,
                          max_nodes=max_nodes, sub_cache=sub_cache,
                          corpus_sha=corpus_sha,
                          corpus_bytes=corpus_bytes)
            value = ctx.eval(self._stages[self._root][2])
            render_op = self._stages[self._root][1]
            output = _render(render_op, value) if render else None
            distinct, truncated, overflow = ctx.accounting(
                self._stages[self._root][2], value
            )
            return PlanResult(
                value=value, output=output, distinct=distinct,
                truncated=truncated, overflow_tokens=overflow,
                run_result=ctx.run_result,
            )

    def run_stream(self, blocks, **kw):
        """Bounded-memory passthrough for a pure wordcount-fold plan:
        delegates to ``MapReduceEngine.run_stream`` (same checkpoint/
        resume contract) and returns the raw ``RunResult`` — the
        streaming CLI's existing stall/ckpt accounting rides it
        unchanged."""
        stage = self._stages[self._stages[self._root][2]]
        if not (stage[0] == "fold" and stage[1] == "wordcount"
                and not self.mesh):
            raise PlanError(
                "run_stream supports the single-device wordcount fold "
                "plan only"
            )
        return self._wordcount_engine().run_stream(blocks, **kw)

    def run_corpus(self, corpus: bytes, *, sub_cache=None,
                   corpus_sha: str | None = None) -> PlanResult:
        """The serve tier's entry: raw corpus bytes in, rendered result
        out.  Text sources split lines exactly like the daemon's batch
        stager (``serve/batch.split_lines``); edge sources parse the
        SNAP ``src dst`` format exactly like the CLI
        (``cli_apps.load_edges``).  ONE corpus only: a plan whose
        sources name distinct ``input``s would silently self-join the
        same bytes — loud instead (``parse_spec`` rejects it as
        ``bad_spec`` before admission; this is the dispatch-side
        defense)."""
        named = sorted({
            n.param("input", "corpus")
            for n in self.plan.nodes if n.kind == "source"
        } - {"corpus"})
        if named:
            raise PlanError(
                f"run_corpus feeds ONE corpus; this plan's sources name "
                f"distinct inputs {named} — submit it through run() "
                "with a data dict instead"
            )
        if any(n.kind == "source" and n.op == "edges"
               for n in self.plan.nodes):
            src, dst = edges_from_bytes(corpus)
            return self.run(
                (src, dst), max_nodes=SERVE_MAX_PAGERANK_NODES
            )
        record_src = next(
            (n for n in self.plan.nodes
             if n.kind == "source" and n.op == "records"), None
        )
        if record_src is not None:
            from locust_tpu.io.loader import RecordSource
            from locust_tpu.plan.builders import RECORD_BYTES

            try:
                return self.run(RecordSource.from_bytes(
                    corpus, record_src.param("record_bytes", RECORD_BYTES)
                ))
            except ValueError as e:  # no whole number of records
                raise PlanError(str(e))
        if sub_cache is not None and corpus_sha is None:
            import hashlib

            corpus_sha = hashlib.sha256(corpus).hexdigest()
        return self.run(corpus.splitlines(), sub_cache=sub_cache,
                        corpus_sha=corpus_sha, corpus_bytes=corpus)

    def _record_sorter(self):
        """The sort stage's sorter, one a compiled plan (its programs are
        the process's, ``engine._programs_for``): ``engine.RecordSort`` on
        one device, under ``mesh`` ``parallel.record_sort.MeshRecordSort``
        over every visible device — same ``load`` / ``sort`` /
        ``host_blocks``, same bytes out."""
        if self._sorter is None:
            stage = next(
                s for s in self._stages.values() if s[0] == "record_sort"
            )
            if self.mesh:
                from locust_tpu.parallel.mesh import make_mesh
                from locust_tpu.parallel.record_sort import MeshRecordSort

                self._sorter = MeshRecordSort(make_mesh(), stage[2], stage[3])
            else:
                from locust_tpu.engine import MapReduceEngine

                self._sorter = MapReduceEngine(self.cfg).record_sort(
                    stage[2], stage[3]
                )
        return self._sorter

    def explain(self) -> str:
        """What each stage of the lowered plan runs on, a line a stage in
        the order they were lowered: ``<node>: <stage> -> <lowering>``."""
        import jax

        lines = []
        for nid, stage in self._stages.items():
            kind = stage[0]
            if kind == "record_sort":
                where = (
                    f"parallel.record_sort.MeshRecordSort over "
                    f"{len(jax.devices())} devices (range partition by "
                    "sampled splitters, one all-to-all, a sorted shard a device)"
                    if self.mesh else
                    "engine.RecordSort on one device (the whole data set resident)"
                )
                lines.append(f"{nid}: record_sort({stage[2]}-byte records, "
                             f"{stage[3]}-byte key) -> {where}")
            else:
                what = ", ".join(str(x) for x in stage[1:] if isinstance(x, (str, int)))
                lines.append(f"{nid}: {kind}({what})"
                             + (" [mesh]" if self.mesh and kind == "fold" else ""))
        return "\n".join(lines)

    def load_records(self, source):
        """Evaluate a records plan's SOURCE now: ``source`` (an
        ``io/loader.RecordSource``) read and staged on the device.  What
        it returns is the ``data`` of a later ``run`` — for a driver that
        times ingest apart from the sort (the CLI's ``cli.load`` /
        ``cli.run``); ``run`` takes a ``RecordSource`` as well."""
        return self._record_sorter().load(source)

    def _wordcount_engine(self):
        if self._engine is None:
            from locust_tpu.engine import MapReduceEngine

            cfg = self.cfg
            if self.optimized is not None and self.optimized.fuse_kernel:
                # fuse_fold_kernel (plan/optimize.py): the wordcount
                # fold engages the Pallas megakernel — the engine's own
                # eligibility check stays the runtime authority and
                # degrades to plain hasht byte-identically off
                # supported shapes/backends.
                cfg = dataclasses.replace(cfg, sort_mode="fused")
            self._engine = MapReduceEngine(cfg)
        return self._engine


def compile_plan(plan: Plan, cfg=None, mesh: bool = False,
                 optimize: bool = True) -> CompiledPlan:
    """Lower ``plan`` onto the engine tier; raises ``PlanError`` on any
    composition outside the supported signatures (docs/PLAN.md).
    ``optimize=False`` skips the rewrite pass (plan/optimize.py) — the
    naive 1:1 lowering the optimizer's byte-identity contract is pinned
    against."""
    return CompiledPlan(plan, cfg=cfg, mesh=mesh, optimize=optimize)


class _RunCtx:
    """One plan execution: stage memo + source staging + accounting."""

    def __init__(self, cp: CompiledPlan, data, num_nodes, timed,
                 checkpoint_dir, every, finalize: bool = True,
                 max_nodes: int | None = None, sub_cache=None,
                 corpus_sha: str | None = None,
                 corpus_bytes: bytes | None = None):
        self.cp = cp
        self.data = data
        self.num_nodes = num_nodes
        self.max_nodes = max_nodes
        self.timed = timed
        self.checkpoint_dir = checkpoint_dir
        self.every = every
        self.finalize = finalize
        self.run_result = None
        self.sub_cache = sub_cache        # serve.cache.SubPlanCache
        self.corpus_sha = corpus_sha
        self.corpus_bytes = corpus_bytes
        self._memo: dict[str, object] = {}
        self._acct: dict[str, tuple] = {}  # stage id -> (dist, trunc, ovf)

    def _sub_engaged(self) -> bool:
        """Per-edge sub-result caching engages only on the serve path
        (run_corpus with a cache): plain host pairs in/out, no engine
        side effects — timed/checkpointed/unfinalized runs and mesh
        execution need the engine's own artifacts, so they stay naive."""
        return (
            self.sub_cache is not None
            and self.corpus_sha is not None
            and self.corpus_bytes is not None
            and self.finalize
            and not self.cp.mesh
            and not self.timed
            and not self.checkpoint_dir
        )

    # -------------------------------------------------------------- eval

    def eval(self, sid: str):
        if sid in self._memo:
            return self._memo[sid]
        stage = self.cp._stages[sid]
        kind = stage[0]
        if kind == "source":
            out = self._eval_source(stage[1])
        elif kind == "fold":
            out = self._eval_fold(sid, stage)
        elif kind == "score":
            out = self._eval_score(stage)
        elif kind == "record_sort":
            out = self.cp._record_sorter().sort(self.eval(stage[1]))
            self._acct[sid] = (out.n_records, False, 0)
        elif kind == "join":
            out = self._eval_join(sid, stage)
        elif kind == "visit_join":
            from locust_tpu.apps.join import join_tables

            out = join_tables(
                self.eval(stage[2]), self.eval(stage[1]), self.cp.cfg,
                date_from=stage[3], date_to=stage[4],
            )
            self._acct[sid] = (len(out), False, 0)
        elif kind == "pagerank":
            out = self._eval_pagerank(sid, stage)
        else:  # pragma: no cover - render handled by run()
            raise PlanError(f"unexpected stage {kind!r}")
        self._memo[sid] = out
        return out

    def _source_data(self, n: Node):
        name = n.param("input", "corpus")
        data = self.data
        if isinstance(data, dict):
            if name not in data:
                raise PlanError(
                    f"source {n.id!r}: no input named {name!r} in the "
                    f"run data (have: {sorted(data)})"
                )
            data = data[name]
        if data is None:
            raise PlanError(f"source {n.id!r}: run() got no input data")
        return data

    def _eval_source(self, n: Node):
        import numpy as np

        data = self._source_data(n)
        if n.op == "edges":
            src, dst = data
            return np.asarray(src), np.asarray(dst)
        if n.op == "records":
            from locust_tpu.engine import StagedRecords

            if isinstance(data, StagedRecords):  # CompiledPlan.load_records
                return data
            return self.cp._record_sorter().load(data)
        if n.op == "delimited":
            # io.loader.load_rows' rows, or an iterator of a file's blocks
            # (StreamingCorpus): handed on as they are.
            if isinstance(data, (np.ndarray, collections.abc.Iterator)):
                return data
            from locust_tpu.core import bytes_ops

            return bytes_ops.strings_to_rows(list(data), self.cp.cfg.line_width)
        if isinstance(data, collections.abc.Iterator):
            # The corpus as an iterator of host row blocks
            # (io.loader.StreamingCorpus): handed on as rows are, never
            # held whole.  Only the timed wordcount fold reads it
            # (engine.timed_run, a group ahead of the device); it has no
            # doc ids, and no other fold can index it.
            return data, None
        from locust_tpu.core import bytes_ops

        cfg = self.cp.cfg
        rows = (
            data
            if isinstance(data, np.ndarray)
            else bytes_ops.strings_to_rows(list(data), cfg.line_width)
        )
        k = n.param("lines_per_doc", 1)
        ids = (np.arange(rows.shape[0]) // k).astype(np.int32)
        return rows, ids

    def _eval_fold(self, sid: str, stage):
        """Fold-stage dispatch: sub-plan cache consult (exact hit ->
        skip even the source staging; verified append-only regrowth ->
        delta-only refold + merge) before the full fold.  Every path
        returns EXACTLY what the naive fold returns — cached values are
        the bytes a previous identical fold produced, and the
        incremental merge rides the mergeable-table property with
        bail-to-full guards wherever a full refold could differ
        (truncation, capacity) — docs/PLAN.md "Optimizer"."""
        if not self._sub_engaged():
            return self._eval_fold_full(sid, stage)
        sub = self.sub_cache
        key_fp = self.cp._lowered.node_fingerprint(sid)
        cfg_fp = self.cp.cfg.fingerprint()
        ent = sub.get(key_fp, cfg_fp, self.corpus_sha)
        if ent is not None:
            obs.metric_inc("plan.subcache_hits")
            return self._restore_fold_entry(sid, stage, ent)
        obs.metric_inc("plan.subcache_misses")
        ent = self._incremental_fold(sid, stage, sub, key_fp, cfg_fp)
        if ent is not None:
            return self._restore_fold_entry(sid, stage, ent)
        value = self._eval_fold_full(sid, stage)
        sub.put(key_fp, cfg_fp, self.corpus_sha,
                self._fold_entry(sid, stage, value))
        return value

    def _restore_fold_entry(self, sid: str, stage, ent: dict):
        fold = stage[1]
        self._acct[sid] = (
            int(ent["distinct"]), bool(ent["truncated"]),
            int(ent["overflow"]),
        )
        if fold == "tf":
            src_node = self.cp._stages[stage[2]][1]
            k = src_node.param("lines_per_doc", 1)
            n_lines = int(ent["n_lines"])
            # n_docs exactly as the full path derives it: distinct of
            # arange(n_lines) // k, i.e. ceil(n_lines / k), floor 1.
            self._memo[f"{sid}.n_docs"] = (
                -(-n_lines // k) if n_lines else 1
            )
        value = ent["value"]
        # Shallow copies out of the cache: entry values are shared
        # across runs and must never be mutated by a consumer.
        return list(value) if isinstance(value, list) else dict(value)

    def _fold_entry(self, sid: str, stage, value) -> dict:
        fold = stage[1]
        rows, _ids = self.eval(stage[2])
        dist, trunc, ovf = self._acct[sid]
        return {
            "fold": fold, "value": value,
            "distinct": int(dist), "truncated": bool(trunc),
            "overflow": int(ovf),
            "corpus_len": len(self.corpus_bytes),
            "corpus_sha": self.corpus_sha,
            "n_lines": int(rows.shape[0]),
            "bytes": _fold_value_bytes(fold, value),
        }

    def _incremental_fold(self, sid: str, stage, sub, key_fp, cfg_fp):
        """incremental_fold (plan/optimize.py): look for a cached entry
        over a hash-verified append-only PREFIX of this corpus, refold
        only the delta lines, merge.  Returns the merged entry (also
        stored under the new corpus sha, so future growth chains), or
        None -> full recompute."""
        fold = stage[1]
        if fold not in ("wordcount", "tf"):
            return None  # index postings: exact-hit reuse only
        for cand in sub.prefix_candidates(key_fp, cfg_fp):
            info = incremental_delta(cand, self.corpus_bytes)
            if info is None:
                continue
            merged = self._merge_delta(sid, stage, fold, cand, info)
            if merged is None:
                continue  # guard bailed: the full path owns this run
            sub.put(key_fp, cfg_fp, self.corpus_sha, merged)
            record_rewrite(info["rule"])
            return merged
        return None

    def _merge_delta(self, sid: str, stage, fold: str, ent: dict,
                     info: dict):
        cfg = self.cp.cfg
        rows, ids = self.eval(stage[2])
        n_old = int(info["old_n_lines"])
        n_total = int(rows.shape[0])
        if not 0 <= n_old < n_total:
            return None
        delta_rows = rows[n_old:]
        if fold == "wordcount":
            from locust_tpu.engine import merge_host_pairs

            eng = self.cp._wordcount_engine()
            res = eng.run(delta_rows)
            if res.truncated:
                return None
            pairs = merge_host_pairs(
                ent["value"], res.to_host_pairs(), combine=eng.combine
            )
            if len(pairs) > cfg.resolved_table_size:
                # A full refold would truncate, and only IT knows which
                # keys survive — bail to the naive path.
                return None
            dist = len(pairs)
            ovf = int(ent["overflow"]) + int(res.overflow_tokens)
            value = pairs
        else:  # tf
            from locust_tpu.apps.inverted_index import (
                default_pairs_capacity,
            )
            from locust_tpu.apps.tfidf import term_doc_counts
            from locust_tpu.engine import _wrap_i32

            try:
                tf_delta = term_doc_counts(delta_rows, ids[n_old:], cfg)
            except Exception:  # noqa: BLE001  # locust: noqa[R017] loss condition = documented bail to the naive recompute, which raises the canonical error for the full corpus — nothing is lost silently
                # The delta fold hit a loss condition (overflow /
                # capacity — term_doc_counts raises rather than
                # truncate).  Bail so the NAIVE path recomputes and
                # raises the canonical error for the full corpus.
                return None
            value = dict(ent["value"])
            for key, v in tf_delta.items():
                value[key] = _wrap_i32(int(value.get(key, 0)) + int(v))
            if len(value) > default_pairs_capacity(cfg):
                return None  # a full refold RAISES; let it
            dist, ovf = len(value), 0
        bl = cfg.block_lines
        sub = self.sub_cache
        sub.record_incremental(
            delta_blocks=-(-(n_total - n_old) // bl),
            total_blocks=max(1, -(-n_total // bl)),
        )
        return {
            "fold": fold, "value": value,
            "distinct": int(dist), "truncated": False,
            "overflow": int(ovf),
            "corpus_len": len(self.corpus_bytes),
            "corpus_sha": self.corpus_sha,
            "n_lines": n_total,
            "bytes": _fold_value_bytes(fold, value),
        }

    def _eval_fold_full(self, sid: str, stage):
        fold = stage[1]
        src_node = self.cp._stages[stage[2]][1]
        rows, ids = self.eval(stage[2])
        cfg, mesh = self.cp.cfg, self.cp.mesh
        if ids is None and not (  # the source handed a block iterator on
            fold == "wordcount" and self.timed
            and not mesh and not self.checkpoint_dir
        ):
            raise PlanError(
                f"source {src_node.id!r}: an iterator of row blocks feeds "
                "the timed wordcount fold only (run(..., timed=True), no "
                "mesh, no checkpoints); every other fold indexes its rows "
                "— pass the rows array"
            )
        if fold == "wordcount":
            if mesh:
                from locust_tpu.parallel.mesh import make_mesh
                from locust_tpu.parallel.shuffle import DistributedMapReduce

                opt = self.cp.optimized
                if opt is not None and opt.fuse_kernel:
                    # fuse_fold_kernel fires for mesh jobs too
                    # (megakernel v2): the mesh engine's own
                    # fused_mesh_eligible gate keeps runtime authority —
                    # off-TPU it demotes explicitly (fused_demoted) and
                    # folds exactly like hasht.
                    cfg = dataclasses.replace(cfg, sort_mode="fused")
                res = DistributedMapReduce(make_mesh(), cfg).run(rows)
                pairs = res.to_host_pairs() if self.finalize else None
                self._acct[sid] = (
                    res.distinct, res.truncated, res.emit_overflow
                )
            else:
                eng = self.cp._wordcount_engine()
                if self.checkpoint_dir:
                    res = eng.run_checkpointed(
                        rows, self.checkpoint_dir, every=self.every
                    )
                elif self.timed:
                    res = eng.timed_run(rows)
                else:
                    res = eng.run_fused(rows)
                self.run_result = res
                pairs = res.to_host_pairs() if self.finalize else None
                self._acct[sid] = (
                    res.num_segments, res.truncated, res.overflow_tokens
                )
            return pairs
        if fold == "tf":
            from locust_tpu.apps.tfidf import term_doc_counts

            tf = term_doc_counts(rows, ids, cfg)
            self._acct[sid] = (len(tf), False, 0)
            # The score stage needs n_docs exactly as build_tfidf
            # derives it: distinct ids over the INPUT, not the table
            # (a doc whose lines carry no tokens still counts).
            self._memo[f"{sid}.n_docs"] = (
                len(set(int(d) for d in ids)) or 1
            )
            return tf
        if fold == "index":
            if mesh:
                from locust_tpu.apps.inverted_index import (
                    build_inverted_index_mesh,
                )
                from locust_tpu.parallel.mesh import make_mesh

                index = build_inverted_index_mesh(
                    rows, ids, make_mesh(), cfg
                )
                self._acct[sid] = (len(index), False, 0)
                return index
            from locust_tpu.apps.inverted_index import build_index

            # The collect's result is arrays (CSR); the dict spelling is
            # built from them, and only for a caller that asks for it.
            index = build_index(rows, ids, cfg)
            self._acct[sid] = (len(index), False, index.dropped_tokens)
            return index.to_dict() if self.finalize else index
        raise PlanError(  # pragma: no cover - _FOLDS is closed
            f"unknown fold {fold!r} (source {src_node.id!r})"
        )

    def _eval_score(self, stage):
        from locust_tpu.apps.tfidf import scores_from_tf

        tf_id = stage[1]
        composed = len(stage) > 2 and stage[2]
        if composed and tf_id not in self._memo:
            # compose_score (plan/optimize.py): fold + rescore as ONE
            # stage — the tf table is consumed inline and never
            # retained in the stage memo (the reduce has exactly one
            # consumer, so nothing else can ask for it).
            tf = self._eval_fold(tf_id, self.cp._stages[tf_id])
        else:
            tf = self.eval(tf_id)
        return scores_from_tf(tf, self._memo[f"{tf_id}.n_docs"])

    def _eval_join(self, sid: str, stage):
        _, left_id, right_id, combine = stage
        left = dict(self.eval(left_id))
        right = dict(self.eval(right_id))
        op = {
            "sum": lambda a, b: a + b,
            "mul": lambda a, b: a * b,
            "min": min,
        }[combine]
        pairs = sorted(
            (k, op(v, right[k])) for k, v in left.items() if k in right
        )
        self._acct[sid] = (len(pairs), False, 0)
        return pairs

    def _eval_pagerank(self, sid: str, stage):
        import numpy as np

        _, src_id, num_iters, damping = stage
        src, dst = self.eval(src_id)
        n = (
            self.num_nodes
            if self.num_nodes is not None
            else int(max(int(src.max()), int(dst.max()))) + 1
        )
        if self.max_nodes is not None and n > self.max_nodes:
            # Serve-side bound (SERVE_MAX_PAGERANK_NODES): the node
            # count derives from corpus CONTENT, so a tiny submit
            # naming a huge id must reject, not allocate.
            raise PlanError(
                f"pagerank needs {n} dense node slots, past this "
                f"endpoint's cap ({self.max_nodes}); renumber the "
                "graph or run it through the CLI"
            )
        edges = int(src.shape[0])
        obs.metric_inc("pagerank.edges", edges)
        obs.metric_inc("pagerank.nodes", n)
        obs.metric_inc("pagerank.iterations", num_iters)
        sizes = dict(nodes=n, edges=edges, iters=num_iters)
        if self.cp.mesh:
            from locust_tpu.apps.pagerank import ShardedPageRank
            from locust_tpu.parallel.mesh import make_mesh

            with obs.span("pagerank.iterate", **sizes):
                ranks = ShardedPageRank(
                    make_mesh(), n, damping=damping
                ).run(src, dst, num_iters=num_iters)
        else:
            import jax

            from locust_tpu.apps.pagerank import pagerank

            with obs.span("pagerank.h2d", bytes=8 * edges):
                on_device = jax.block_until_ready(jax.device_put(
                    (np.asarray(src, np.int32), np.asarray(dst, np.int32))
                ))
            with obs.span("pagerank.iterate", **sizes):
                ranks = pagerank(
                    *on_device, num_nodes=n, num_iters=num_iters,
                    damping=damping,
                )
                with obs.span("engine.sync", what="iterate"):
                    ranks.block_until_ready()
            with obs.span("pagerank.d2h", bytes=4 * n):
                ranks = np.asarray(ranks)
        self._acct[sid] = (n, False, 0)
        return ranks

    def accounting(self, sid: str, value) -> tuple:
        got = self._acct.get(sid)
        if got is not None:
            return got
        try:
            return len(value), False, 0
        except TypeError:
            return 0, False, 0


def _fold_value_bytes(fold: str, value) -> int:
    """Byte-size estimate of one cached fold value (the sub-plan
    cache's LRU accounting — the ``pairs_bytes`` stance: an estimate
    that tracks growth, not an exact RSS)."""
    if fold == "wordcount":
        return sum(len(k) + 8 for k, _v in value)
    if fold == "tf":
        return sum(len(w) + 16 for (w, _d) in value)
    return sum(len(w) + 8 * len(docs) for w, docs in value.items())


def rank_row(node: int, rank: float) -> bytes:
    """ONE spelling of a pagerank output row — the ``ranks`` sink and
    the driver's ``--top`` path (which reorders rows) both use it, so
    the formats cannot drift apart.  Nine significant digits,
    ``d.dddddddde-XX``: what a float32 needs to come back bit for bit
    (eight decimals held two or three digits of a rank of 1e-6).
    ``bytes_ops.render_rank_rows`` prints a whole vector's rows from
    arrays, byte-equal to this a row (tests/test_pagerank_cli.py)."""
    return f"{node}\t{float(rank):.8e}\n".encode()


def iter_rendered(op: str, value):
    """Per-row sink rendering, the ONE spelling of each workload's
    output format: ``_render`` joins it for plan results, and the
    hand-wired CLI drivers (``cli_apps``) iterate it directly (honoring
    ``--limit``) — byte-identity holds by construction, not by parallel
    maintenance."""
    if op == "table":
        for k, v in value:  # pairs are already host-finalized + sorted
            yield k + b"\t" + str(v).encode() + b"\n"
    elif op == "tfidf":
        for word, doc in sorted(value):
            yield (
                word + b"\t" + str(doc).encode()
                + b"\t" + f"{value[(word, doc)]:.6f}".encode() + b"\n"
            )
    elif op == "postings":
        for word in sorted(value):
            docs = b",".join(str(d).encode() for d in value[word])
            yield word + b"\t" + docs + b"\n"
    elif op == "ranks":
        for i in range(value.shape[0]):
            yield rank_row(i, value[i])
    elif op == "revenue":
        for ip, avg, total in zip(value.ips, value.averages, value.totals):
            yield (ip.tobytes().rstrip(b"\0")
                   + f"\t{avg:.8e}\t{total:.8e}\n".encode())
    elif op == "records":  # the records themselves, a sorted block a row
        for block in value.host_blocks():
            yield memoryview(block)
    else:  # pragma: no cover - NODE_OPS closes the sink set
        raise PlanError(f"unknown sink op {op!r}")


def _render(op: str, value) -> bytes:
    """Sink rendering: byte-for-byte the hand-wired drivers' stdout —
    the byte-identity contract serve plan results ride."""
    if op == "ranks":
        return render_ranks(value)
    if op == "postings" and not isinstance(value, dict):
        return render_postings(value)
    if op == "revenue":
        return render_revenue(value)
    return b"".join(iter_rendered(op, value))


def render_postings(index, limit: int | None = None) -> bytes:
    """An index AS ARRAYS (``apps.inverted_index.Postings``) as the
    ``postings`` sink spells it, ``word<TAB>d1,d2,...<LF>`` a word in byte
    order (its first ``limit`` words): rendered from the arrays
    (``bytes_ops.render_postings``), a row at a time from the dict only
    for an index that holds what the array layout cannot spell."""
    from locust_tpu.core import bytes_ops

    if limit is not None:
        index = index.head(limit)
    out = bytes_ops.render_postings(index.words, index.offsets, index.postings)
    if out is None:
        out = b"".join(iter_rendered("postings", index.to_dict()))
    return out


def render_revenue(joined) -> bytes:
    """The join's table (``apps.join.Joined``) as the ``revenue`` sink
    spells it, ``sourceIP<TAB>avgPageRank<TAB>totalRevenue<LF>`` a row, both
    numbers with nine significant digits: rendered from the arrays
    (``bytes_ops.render_revenue_rows``), a row at a time only where they
    hold what the fixed-width layout cannot spell."""
    from locust_tpu.core import bytes_ops

    out = bytes_ops.render_revenue_rows(
        joined.ips, joined.averages, joined.totals)
    if out is None:
        out = b"".join(iter_rendered("revenue", joined))
    return out


def render_ranks(ranks) -> bytes:
    """Every node's ``rank_row`` in id order as one buffer: rendered from
    arrays (``bytes_ops.render_rank_rows``), a row at a time only for a
    vector that holds what its fixed-width layout cannot spell."""
    from locust_tpu.core import bytes_ops

    out = bytes_ops.render_rank_rows(ranks)
    if out is None:
        out = b"".join(iter_rendered("ranks", ranks))
    return out


# The largest node id: ids are int32 on the device, where an index past
# the end is clamped or dropped and never an error.
MAX_NODE_ID = (1 << 31) - 1


def edges_from_bytes(corpus: bytes):
    """SNAP-style ``src dst`` edge list from raw bytes, as two int32
    arrays.  The ONE parser (comment/2-field/int/negative-id rules):
    ``cli_apps.load_edges`` delegates here, so a pagerank plan submitted
    to the daemon parses its corpus exactly like the CLI parses a file —
    by construction, not by parallel maintenance.  A clean file (``#``
    lines at its head, then ``src<TAB or SPACE>dst<LF>`` and nothing
    else) is read without a Python object an edge: by ONE native pass
    (``native_ingest.parse_edges``) where the library loads, by numpy
    (``_edges_clean``) where it does not.  Anything else goes through
    the line loop, which words the errors."""
    from locust_tpu.io import native_ingest

    with obs.span("pagerank.parse", bytes=len(corpus)) as sp:
        try:
            parsed = native_ingest.parse_edges(corpus)
            native = parsed is not None
        except OSError:  # no toolchain: numpy reads the clean file
            parsed = _edges_clean(corpus)
            native = False
        fast = parsed is not None
        if native:
            src, dst, top = parsed
        else:
            src, dst = parsed if fast else _edges_by_line(corpus)
            top = max(int(src.max()), int(dst.max()))
        _check_top_id(top)
        sp.set(edges=int(src.shape[0]), fast=int(fast), native=int(native))
        obs.metric_inc("pagerank.parse.native", int(native))
        # int32 already from the native pass: no copy there.
        return (src.astype("int32", copy=False),
                dst.astype("int32", copy=False))


def _check_top_id(top: int) -> None:
    if top > MAX_NODE_ID:
        raise PlanError(
            f"edge list has a node id past int32 ({top} > {MAX_NODE_ID})"
        )


def _edges_clean(corpus: bytes):
    """The edges of a CLEAN file as two int64 arrays, or None.  Clean:
    ``#`` lines at the head only; then digits, each pair of numbers one
    TAB or SPACE apart and each line ended by one LF (the last may lack
    it).  Every byte that is no digit is looked at — ``translate`` keeps
    them, and they must alternate separator, LF — so a sign, a third
    field, a blank line, a CR or a later comment sends the file to the
    line loop; ``numpy.fromstring`` then reads numbers it cannot misread
    (a number past int64 comes back as int64's largest, past int32)."""
    import numpy as np

    head = 0
    while corpus.startswith(b"#", head):
        head = corpus.find(b"\n", head) + 1
        if head == 0:
            return None  # comments only
    body = corpus[head:] if head else corpus
    if not body[:1].isdigit():
        return None
    seps = np.frombuffer(body.translate(None, b"0123456789"), np.uint8)
    within, ends = seps[0::2], seps[1::2]
    if not (((within == 9) | (within == 32)).all() and (ends == 10).all()):
        return None
    values = np.fromstring(body, dtype=np.int64, sep=" ")
    if values.size % 2 or values.size != seps.size + body[-1:].isdigit():
        return None  # a separator that stands alone: a lone field, a blank
    return values[0::2], values[1::2]


def _edges_by_line(corpus: bytes):
    import numpy as np

    src, dst = [], []
    for ln_no, ln in enumerate(corpus.splitlines(), 1):
        ln = ln.strip()
        if not ln or ln.startswith(b"#"):
            continue
        parts = ln.split()
        if len(parts) != 2:
            raise PlanError(
                f"edge list line {ln_no}: expected 'src dst', got "
                f"{ln[:60]!r}"
            )
        try:
            src.append(int(parts[0]))
            dst.append(int(parts[1]))
        except ValueError:
            raise PlanError(
                f"edge list line {ln_no}: non-integer node id {ln[:60]!r}"
            )
    if not src:
        raise PlanError("edge list has no edges")
    if min(min(src), min(dst)) < 0:
        raise PlanError("edge list has a negative node id")
    _check_top_id(max(max(src), max(dst)))  # Python ints: past int64 too
    return np.asarray(src, np.int64), np.asarray(dst, np.int64)
