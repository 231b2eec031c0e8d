"""Shared test oracles: strtok-semantics tokenization + WordCount Counter.

Single source of truth for the delimiter-split oracle so the engine's
delimiter set (locust_tpu.config.DELIMITERS) has exactly one mirror here.
"""

import collections
import ctypes
import logging
import re

from locust_tpu.config import DELIMITERS

_SPLIT = re.compile(b"[" + re.escape(DELIMITERS + b"\n\r\x00") + b"]+")


def strtok_tokens(line: bytes, max_tokens=None, key_width=None) -> list[bytes]:
    """Split like the reference's my_strtok_r loop: delimiters collapse,
    empty tokens drop; honor the per-line emit cap and key truncation."""
    toks = [t for t in _SPLIT.split(line) if t]
    if max_tokens is not None:
        toks = toks[:max_tokens]
    if key_width is not None:
        toks = [t[:key_width] for t in toks]
    return toks


def py_wordcount(lines, max_tokens_per_line=None, key_width=32):
    c = collections.Counter()
    for line in lines:
        c.update(strtok_tokens(line, max_tokens_per_line, key_width))
    return c


def serve_abandon(daemon):
    """Simulate kill -9 on an in-process ServeDaemon: stop the threads
    WITHOUT the graceful close() path (no drain, no warm flush, no
    journal compaction) — the crash the write-ahead journal exists for.
    One definition so the durability tests and rehearsals all model the
    same crash.

    The _closed latch must flip BEFORE the socket dies: the accept
    loop's ``finally: close()`` otherwise races the "restarted" daemon
    — the zombie drains the paused jobs as failed and compacts the very
    journal the successor is replaying, two os.replace rewrites cross,
    and the successor's terminal records land on an unlinked inode (a
    real SIGKILL'd process can't run any of that)."""
    daemon._shutdown.set()
    with daemon._lock:
        daemon._closed = True
    daemon.scheduler.stop()
    shipper = daemon.shipper
    if shipper is not None:
        # A dead process ships nothing: drop the replication stream so
        # the standby sees silence (lease expiry) instead of a zombie
        # that keeps heartbeating past its own "death".
        shipper.stop()
    daemon._sock.close()
    # A dead process runs nothing more.  A dispatcher or stage thread that
    # an injected delay holds in flight (30-60 s in the durability tests)
    # would otherwise wake up in a LATER test file of the same worker and
    # run its batch there: jit work, and root spans on a thread of its own
    # in whatever tracer that test enabled.  Every thread the daemon owns
    # ends where it next runs a line of Python: the executors' threads by
    # SystemExit (no ``except Exception`` of a retry ladder absorbs it; a
    # work item hands it to a future nobody reads), the dispatcher by an
    # Exception its loop logs before it sees ``_shutdown`` and returns
    # (a SystemExit would leave ``run`` and reach the thread excepthook).
    executors = (
        getattr(daemon, "_shard_executor", None),
        getattr(daemon.pool, "_executor", None),
    )
    doomed = [(daemon._dispatcher, _Abandoned)]
    for ex in executors:
        doomed += [(t, SystemExit) for t in getattr(ex, "_threads", ())]
    for t, exc in doomed:
        if t.is_alive():
            ctypes.pythonapi.PyThreadState_SetAsyncExc(
                ctypes.c_ulong(t.ident), ctypes.py_object(exc)
            )


class _Abandoned(Exception):
    """Raised in an abandoned daemon's dispatcher (``serve_abandon``)."""


# The dispatcher's loop logs what ends it; that traceback would land in
# the captured stderr of whichever test runs when the delay runs out.
logging.getLogger("locust_tpu").addFilter(
    lambda rec: not (rec.exc_info and rec.exc_info[0] is _Abandoned)
)


def native_ingest_missing(monkeypatch):
    """Make ``native/ingest.cpp``'s library fail to load, as where no
    toolchain is: every ``native_ingest`` entry point raises ``OSError``
    and its callers take their Python paths."""
    from locust_tpu.io import native_ingest

    def no_toolchain():
        raise OSError("native ingest build failed: no g++")

    monkeypatch.setattr(native_ingest, "_load", no_toolchain)
