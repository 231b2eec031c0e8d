"""Process-wide structured tracer: nested named spans + instant events,
exported as Chrome-trace/Perfetto JSON.

The reference's entire observability is three chrono spans printed with a
UB printf (reference MapReduce/src/main.cu:405-468, SURVEY.md Q7); our
repro had outgrown that into fragments (SpanTimer wall spans, xplane
parsing, per-shard stats, stream stall accounting) that never composed
into one timeline.  This module is the one timeline:

  * a span is (name, start, end, id, parent, job): timestamps are
    ``time.perf_counter_ns()`` anchored ONCE to the epoch when the
    tracer is made — monotonic inside a process (``time.time()`` can
    step), still epoch microseconds on the wire (cross-node merge stays
    a clock-offset shift); ``id`` is unique in the timeline, ``parent``
    is the span open on the same thread at entry, and a span with no
    parent (a root) carries the job's identifier, the tracer's
    ``trace_id``, instead.  All three ride in ``args``.  Recorded as
    Chrome ``"ph": "X"`` complete events; instants are ``"ph": "i"``;
  * ONE clock with the device: while ``jax`` is imported, an open span
    also holds a ``jax.profiler.TraceAnnotation`` of its name, so any
    profiler session (the CLI's ``--profile-dir``, a benchmark's) keeps
    the program's spans on ``/host:CPU`` of the same ``.xplane.pb`` as
    the device's ``XLA Ops`` — no anchor, no second file;
  * a span may carry ``sync_refs`` — device arrays blocked on at span
    EXIT, reusing SpanTimer's sync-at-exit semantics (jax imported
    lazily and only then: the tracer itself is jax-free so every
    entrypoint can import it before backend selection);
  * names are validated against the closed registry
    (``locust_tpu.obs.names``) — a typo'd name raises, enabled-path only;
  * ``serialize()``/``ingest()`` move span lists across the distributor
    wire: a worker runs its map under a request-scoped tracer, ships the
    span list back inside the map reply, and the master ``ingest``s it
    shifted by the estimated clock offset into one merged timeline
    (each remote process gets its own Chrome pid + process_name).

Thread-safe: spans/events append under one lock; tids are per-thread
Chrome thread ids.  All methods are cheap relative to what they measure
(device dispatches, RPCs); the ZERO-overhead disabled path lives in
``locust_tpu.obs.__init__`` (module hooks bail before reaching here).
"""

from __future__ import annotations

import itertools
import json
import os
import sys
import threading
import time
import uuid

from locust_tpu.obs import names as _names


class _NullSpan:
    """Shared no-op context manager: the disabled fast path allocates
    nothing (``obs.span`` returns this singleton)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args):
        pass


NULL_SPAN = _NullSpan()


class _Span:
    """One open span; records a complete ("X") event at exit."""

    __slots__ = ("_tracer", "_name", "_sync", "_args", "_t0", "_id",
                 "_parent", "_anno", "_stack")

    def __init__(self, tracer: "Tracer", name: str, sync, args: dict):
        self._tracer = tracer
        self._name = name
        self._sync = sync
        self._args = args

    def __enter__(self):
        stack = self._stack = self._tracer._open_spans()
        self._parent = stack[-1] if stack else None
        self._id = next(self._tracer._ids)
        stack.append(self._id)
        # The same span on the profiler's clock.  jax is looked up, never
        # imported: obs stays jax-free, and a span opened before the
        # entry point imported jax simply has no annotation.
        jax = sys.modules.get("jax")
        profiler = getattr(jax, "profiler", None)
        self._anno = None
        if profiler is not None:
            self._anno = profiler.TraceAnnotation(self._name)
            self._anno.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def set(self, **args):
        """Arguments known only once the span's work has run."""
        self._args.update(args)

    def __exit__(self, *exc):
        if self._sync:
            import jax  # lazy: sync-at-exit is opt-in, tracer stays jax-free

            for ref in self._sync:
                jax.block_until_ready(ref)  # locust: noqa[R003] profiler span boundary: the sync IS the measurement
        t1 = time.perf_counter_ns()
        if self._anno is not None:
            self._anno.__exit__(None, None, None)
        self._stack.pop()  # the entering thread's; ``with`` nests LIFO
        self._tracer._complete(
            self._name, self._tracer._us(self._t0), (t1 - self._t0) / 1e3,
            self._args, self._id, self._parent,
        )
        return False


def self_times(events) -> dict[int, float]:
    """``id -> self microseconds`` of every complete event of a timeline:
    its duration minus the part of it its child spans cover (the union
    of their intervals, cut to the parent — children may overlap one
    another, as jax's nested trace spans do).  Works on a live tracer's
    records and on ``traceEvents`` read back from an exported file."""
    spans = {
        e["args"]["id"]: e for e in events
        if e.get("ph") == "X" and "id" in e.get("args", {})
    }
    children: dict[int, list[tuple[float, float]]] = {}
    for e in spans.values():
        parent = e["args"].get("parent")
        if parent in spans:
            children.setdefault(parent, []).append(
                (e["ts"], e["ts"] + e["dur"])
            )
    out = {}
    for sid, e in spans.items():
        lo, hi = e["ts"], e["ts"] + e["dur"]
        covered, edge = 0.0, lo
        for a, b in sorted(children.get(sid, ())):
            a, b = max(a, edge), min(b, hi)
            if b > a:
                covered += b - a
                edge = b
        out[sid] = e["dur"] - covered
    return out


class Tracer:
    """Structured span/event recorder for ONE process (or one request).

    ``trace_id`` correlates records across nodes: the master stamps it
    into map requests, workers open their request tracer with it, and the
    shipped span lists merge back under the one id.
    """

    def __init__(self, trace_id: str | None = None, process: str = "main"):
        self.trace_id = trace_id or uuid.uuid4().hex[:16]
        self.process = process
        # The one anchor: epoch microseconds at perf_counter reading
        # ``_perf0``.  Every later timestamp is that plus elapsed
        # perf_counter time, so a stepped wall clock cannot fold a span.
        self._perf0 = time.perf_counter_ns()
        self._epoch0_us = time.time() * 1e6
        self._ids = itertools.count(1)
        self._tls = threading.local()
        self._lock = threading.Lock()
        self._events: list[dict] = []
        self._pids: dict[str, int] = {process: 0}
        self._tids: dict[int, int] = {}
        self._meta_process(0, process)

    # ------------------------------------------------------------ recording

    def span(self, name: str, *sync_refs, **args) -> _Span:
        _names.check(name, "span")
        return _Span(self, name, sync_refs, args)

    def event(self, name: str, **args) -> None:
        _names.check(name, "event")
        with self._lock:
            self._events.append(
                {
                    "name": name,
                    "cat": "locust",
                    "ph": "i",
                    "s": "t",
                    "ts": round(self._us(time.perf_counter_ns()), 1),
                    "pid": 0,
                    "tid": self._tid_locked(),
                    "args": args,
                }
            )

    def span_at(self, name: str, start_s: float, end_s: float, **args):
        """Record a span that is already over, from a reporter that timed
        it itself on ``time.time()`` (jax.monitoring's time spans).  It is
        placed by how long AGO it ended, read off both clocks now, so the
        tracer's anchor stays the only one; its parent is the span open
        on this thread, which the reported work ran inside."""
        _names.check(name, "span")
        now_us = self._us(time.perf_counter_ns())
        end_us = now_us - max(0.0, time.time() - end_s) * 1e6
        dur_us = max(0.0, end_s - start_s) * 1e6
        stack = self._open_spans()
        self._complete(name, end_us - dur_us, dur_us, args,
                       next(self._ids), stack[-1] if stack else None)

    def _us(self, perf_ns: int) -> float:
        return self._epoch0_us + (perf_ns - self._perf0) / 1e3

    def _open_spans(self) -> list[int]:
        """Ids of the spans open on THIS thread, outermost first."""
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    def _complete(self, name: str, ts_us: float, dur_us: float, args: dict,
                  span_id: int, parent: int | None):
        link = (
            {"id": span_id, "trace_id": self.trace_id} if parent is None
            else {"id": span_id, "parent": parent}
        )
        with self._lock:
            self._events.append(
                {
                    "name": name,
                    "cat": "locust",
                    "ph": "X",
                    "ts": round(ts_us, 1),
                    "dur": round(dur_us, 1),
                    "pid": 0,
                    "tid": self._tid_locked(),
                    "args": {**args, **link},
                }
            )

    def self_times(self) -> dict[int, float]:
        """``id -> self microseconds`` of this tracer's spans
        (module-level ``self_times`` has the rule)."""
        with self._lock:
            return self_times(list(self._events))

    def _tid_locked(self) -> int:
        ident = threading.get_ident()
        tid = self._tids.get(ident)
        if tid is None:
            tid = self._tids[ident] = len(self._tids)
        return tid

    def _meta_process(self, pid: int, label: str) -> None:
        self._events.append(
            {
                "name": "process_name",
                "ph": "M",
                "pid": pid,
                "tid": 0,
                "args": {"name": label},
            }
        )

    # ------------------------------------------------------- cross-node merge

    def serialize(self) -> list[dict]:
        """The span/event list for the wire (metadata rows excluded — the
        ingesting side assigns its own pid + process_name)."""
        with self._lock:
            return [dict(e) for e in self._events if e.get("ph") != "M"]

    def ingest(
        self, events: list[dict], offset_s: float = 0.0, process: str = "remote"
    ) -> int:
        """Merge a remote tracer's serialized records, shifting their
        wall-clock timestamps by ``-offset_s`` into this tracer's clock
        (``offset_s`` = remote_clock - local_clock at a common instant).
        Each distinct ``process`` label gets its own Chrome pid, and every
        span a fresh id (parents re-pointed).  Returns records merged; malformed entries are skipped, never raised on
        (telemetry must not take down a job)."""
        n = 0
        with self._lock:
            pid = self._pids.get(process)
            if pid is None:
                pid = self._pids[process] = max(self._pids.values()) + 1
                self._meta_process(pid, process)
            merged = []
            for e in events:
                if not isinstance(e, dict) or e.get("ph") not in ("X", "i"):
                    continue
                try:
                    ts = float(e["ts"]) - offset_s * 1e6
                except (KeyError, TypeError, ValueError):
                    continue
                merged.append(dict(e, pid=pid, ts=round(ts, 1)))
            # The remote tracer counted its span ids from 1 as this one
            # does: give each a fresh id here and point parents at those.
            fresh = {
                e["args"]["id"]: next(self._ids) for e in merged
                if isinstance(e.get("args"), dict) and "id" in e["args"]
            }
            for e in merged:
                if isinstance(e.get("args"), dict) and "id" in e["args"]:
                    args = dict(e["args"], id=fresh[e["args"]["id"]])
                    if args.get("parent") in fresh:
                        args["parent"] = fresh[args["parent"]]
                    else:
                        args.pop("parent", None)
                    e["args"] = args
            self._events.extend(merged)
            n = len(merged)
        return n

    # --------------------------------------------------------------- export

    def counts(self) -> dict:
        with self._lock:
            spans = sum(1 for e in self._events if e.get("ph") == "X")
            events = sum(1 for e in self._events if e.get("ph") == "i")
        return {"spans": spans, "events": events}

    def to_chrome(self, metrics: dict | None = None) -> dict:
        """The Chrome-trace JSON object (loadable in chrome://tracing and
        ui.perfetto.dev)."""
        with self._lock:
            events = [dict(e) for e in self._events]
        other = {"trace_id": self.trace_id, "clock": "epoch_us"}
        if metrics is not None:
            other["metrics"] = metrics
        return {"traceEvents": events, "otherData": other}

    def export(self, path: str, metrics: dict | None = None) -> dict:
        doc = self.to_chrome(metrics)
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        tmp = path + f".tmp.{os.getpid()}"
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(doc, f)
        os.replace(tmp, path)
        return doc
