"""Zero-stall streaming executor: donation, staging ring, async snapshots.

Pins the three invariants the streaming tier's throughput rests on
(docs/DESIGN.md "zero-stall streaming"):

  * **Donated fold state** — the accumulator is donated into every fold
    dispatch and the scan init, so XLA aliases its buffers input->output:
    asserted at the runtime level (the donated input is deleted, the
    output REUSES the same buffer pointer across folds) and at the
    compiled-memo level (``input_output_alias`` in the executable).
  * **Staging ring** — per-block padding/transfer reuses
    ``STREAM_DISPATCH_DEPTH + 1`` pre-allocated host buffers; results are
    the oracle's through ragged blocks, and RSS stays flat in corpus
    size with async checkpoints enabled (subprocess-measured).
  * **Async checkpointing** — snapshots ride a bounded latest-wins
    background writer; on-disk state is equivalent to the synchronous
    writer's and the loop's counters/output are unchanged.  (Chaos
    coverage for the writer's failure modes lives in tests/test_faults.py
    — the io.ckpt_write site.)
"""

import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from helpers import py_wordcount

from locust_tpu.config import EngineConfig
from locust_tpu.core import bytes_ops
from locust_tpu.core.kv import KVBatch
from locust_tpu.engine import MapReduceEngine
from locust_tpu.io.snapshot import AsyncCheckpointWriter, finalize_snapshot

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

LINES = [b"alpha beta gamma", b"beta gamma delta", b"gamma delta epsilon",
         b"zeta eta theta iota", b"epsilon alpha beta"] * 9


def _cfg(**kw):
    kw.setdefault("block_lines", 8)
    kw.setdefault("line_width", 64)
    kw.setdefault("emits_per_line", 8)
    return EngineConfig(**kw)


# ------------------------------------------------------------------ donation


@pytest.mark.parametrize("mode", ["hasht", "hashp2"])
def test_fold_donation_reuses_accumulator_buffers(mode):
    """The per-block fold updates the table IN PLACE: the donated input
    is deleted and every accumulator leaf keeps its buffer pointer
    across folds — no per-block re-allocation of the largest live
    array."""
    eng = MapReduceEngine(_cfg(sort_mode=mode))
    acc = KVBatch.empty(eng._table_size, eng.cfg.key_lanes)
    blk = jnp.zeros((eng.cfg.block_lines, eng.cfg.line_width), jnp.uint8)
    acc2, _, _ = eng._fold_block(acc, blk)
    assert acc.key_lanes.is_deleted(), "donated input must be consumed"
    ptrs = {
        f: getattr(acc2, f).unsafe_buffer_pointer()
        for f in ("key_lanes", "values", "valid")
    }
    acc3, _, _ = eng._fold_block(acc2, blk)
    for f, ptr in ptrs.items():
        assert getattr(acc3, f).unsafe_buffer_pointer() == ptr, (
            f"accumulator leaf {f} was re-allocated instead of reused"
        )


@pytest.mark.parametrize("mode", ["hasht", "hashp2"])
def test_fold_donation_alias_in_compiled_executable(mode):
    """The compiled memo itself carries the input->output alias — the
    donation is a property of the executable, not a runtime accident."""
    eng = MapReduceEngine(_cfg(sort_mode=mode))
    acc = KVBatch.empty(eng._table_size, eng.cfg.key_lanes)
    sds = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), acc
    )
    blk = jax.ShapeDtypeStruct(
        (eng.cfg.block_lines, eng.cfg.line_width), jnp.uint8
    )
    txt = eng._fold_block.lower(sds, blk).compile().as_text()
    assert "input_output_alias" in txt


def test_scan_path_donates_init_accumulator():
    """The one-dispatch lax.scan path donates its init table into the
    scan carry — run_blocks allocates no second table per dispatch."""
    eng = MapReduceEngine(_cfg(sort_mode="hasht"))
    blocks = jnp.zeros(
        (2, eng.cfg.block_lines, eng.cfg.line_width), jnp.uint8
    )
    acc0 = KVBatch.empty(eng._table_size, eng.cfg.key_lanes)
    eng._scan_blocks_into(acc0, blocks)
    assert acc0.key_lanes.is_deleted()


def test_donation_correctness_across_config_paths():
    """One engine, whose every fold donates, produces the identical (and
    the oracle's) table through run / run_fused / run_stream."""
    rows = bytes_ops.strings_to_rows(LINES, 64)
    eng = MapReduceEngine(_cfg(sort_mode="hasht"))
    got = {
        "run": dict(eng.run(rows).to_host_pairs()),
        "fused": dict(eng.run_fused(rows).to_host_pairs()),
        "stream": dict(
            eng.run_stream(
                rows[i : i + 8] for i in range(0, rows.shape[0], 8)
            ).to_host_pairs()
        ),
    }
    assert got["run"] == got["fused"] == got["stream"]
    assert got["run"] == dict(py_wordcount(LINES, eng.cfg.emits_per_line))


# -------------------------------------------------------------- staging ring


def test_normalize_round_chunk_out_buffer():
    from locust_tpu.parallel.shuffle import normalize_round_chunk

    out = np.full((4, 8), 0xFF, np.uint8)  # stale bytes from a prior block
    chunk = np.arange(6, dtype=np.uint8).reshape(2, 3)
    got = normalize_round_chunk(chunk, 4, 8, out=out)
    assert got is out
    assert (got[:2, :3] == chunk).all()
    assert got[2:].sum() == 0 and got[:2, 3:].sum() == 0  # stale bytes cleared
    # exact-shape chunks are still COPIED into the ring slot
    full = np.ones((4, 8), np.uint8)
    got = normalize_round_chunk(full, 4, 8, out=out)
    assert got is out and (got == 1).all()
    # validation still applies with out=
    with pytest.raises(ValueError, match="rows"):
        normalize_round_chunk(np.zeros((5, 8), np.uint8), 4, 8, out=out)
    with pytest.raises(ValueError, match="out buffer"):
        normalize_round_chunk(chunk, 4, 8, out=np.zeros((4, 9), np.uint8))


def test_staging_ring_parity_with_ragged_blocks():
    """Ring staging is exact against the oracle through short final
    blocks and narrower-than-width rows (both pad into a slot that held
    another block's bytes), for more blocks than the ring has slots."""
    rows = bytes_ops.strings_to_rows(LINES, 40)  # narrower than line_width
    eng = MapReduceEngine(_cfg(sort_mode="hasht", block_lines=8, line_width=64))
    n_blocks = -(-rows.shape[0] // 8)
    assert rows.shape[0] % 8 and n_blocks > eng.STREAM_DISPATCH_DEPTH + 1

    # ragged: 8, 8, ..., then a 5-row tail
    res = eng.run_stream(rows[i : i + 8] for i in range(0, rows.shape[0], 8))
    want = dict(py_wordcount(LINES, eng.cfg.emits_per_line))
    assert dict(res.to_host_pairs()) == want
    assert res.num_segments == len(want)
    assert res.stream["blocks"] == n_blocks


# -------------------------------------------------------- async checkpointing


def test_async_and_sync_checkpoints_equivalent_on_disk(tmp_path):
    """Both writers produce the same final state: cursor, counters and
    table content (the on-disk format is shared; only WHERE the write
    runs differs)."""
    rows = bytes_ops.strings_to_rows(LINES, 64)

    def blocks():
        for i in range(0, rows.shape[0], 8):
            yield rows[i : i + 8]

    states = {}
    for name, async_ in (("async", True), ("sync", False)):
        eng = MapReduceEngine(
            _cfg(sort_mode="hasht", async_checkpoint=async_)
        )
        ck = str(tmp_path / name)
        res = eng.run_stream(
            blocks(), checkpoint_dir=ck, every=2, fingerprint="parity-fp"
        )
        assert res.stream["ckpt"]["mode"] == name
        with np.load(os.path.join(ck, "state.npz")) as z:
            states[name] = {
                "next_block": int(z["next_block"]),
                "overflow": int(z["overflow"]),
                "max_distinct": int(z["max_distinct"]),
                "live": int(np.asarray(z["valid"]).sum()),
            }
        states[name]["pairs"] = dict(res.to_host_pairs())
    assert states["async"] == states["sync"]


def test_run_stream_stats_schema(tmp_path):
    rows = bytes_ops.strings_to_rows(LINES, 64)
    eng = MapReduceEngine(_cfg(sort_mode="hasht"))
    res = eng.run_stream(rows[i : i + 8] for i in range(0, rows.shape[0], 8))
    st = res.stream
    assert st["blocks"] == -(-rows.shape[0] // 8)
    assert st["backpressure_stall_ms"] >= 0.0
    assert "ckpt" not in st  # no checkpointing requested
    res2 = eng.run_stream(
        (rows[i : i + 8] for i in range(0, rows.shape[0], 8)),
        checkpoint_dir=str(tmp_path / "ck"), every=4, fingerprint="fp",
    )
    cks = res2.stream["ckpt"]
    assert cks["mode"] == "async" and cks["every"] == 4
    assert cks["written"] >= 1 and cks["submitted"] >= cks["written"]
    assert cks["final_flush_ms"] >= 0.0
    # plain runs never attach stream stats to the fused paths
    assert MapReduceEngine(_cfg()).run_fused(rows).stream is None


def test_async_writer_latest_wins_and_order():
    written = []
    w = AsyncCheckpointWriter(name="t-writer")
    try:
        gate = {"hold": True}

        def slow():
            while gate["hold"]:
                time.sleep(0.01)
            written.append(1)

        w.submit(1, slow)
        # Wait until the worker has actually DEQUEUED generation 1 (busy,
        # nothing pending) — a fixed sleep would flake under CI load.
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            with w._cond:
                if w._busy and w._pending is None:
                    break
            time.sleep(0.005)
        else:
            pytest.fail("worker never dequeued generation 1")
        w.submit(2, lambda: written.append(2))  # pending...
        w.submit(3, lambda: written.append(3))  # ...replaced (latest wins)
        gate["hold"] = False
        w.flush()
        st = w.stats()
        assert written == [1, 3]
        assert st["submitted"] == 3 and st["written"] == 2
        assert st["skipped"] == 1
        # lag at publish: gen 1 landed while gen 3 was already marked
        assert st["max_lag"] == 2
    finally:
        w.close()


def test_async_writer_error_propagates_at_flush():
    w = AsyncCheckpointWriter(name="t-err")
    try:
        def boom():
            raise OSError("disk gone")

        w.submit(1, boom)
        with pytest.raises(OSError, match="disk gone"):
            w.flush()
        # the writer survives a failed write and keeps accepting work
        w.submit(2, lambda: None)
        w.flush()
        assert w.stats()["written"] == 1
    finally:
        w.close()


def test_async_writer_close_semantics():
    w = AsyncCheckpointWriter(name="t-close")
    w.submit(1, lambda: None)
    w.close()
    w.close()  # idempotent
    with pytest.raises(RuntimeError, match="closed"):
        w.submit(2, lambda: None)
    assert w.stats()["written"] == 1


def test_finalize_snapshot_rotation(tmp_path):
    path = str(tmp_path / "state.npz")
    prev = path + ".prev.npz"

    def write(tag: bytes):
        tmp = path + ".tmp.npz"
        with open(tmp, "wb") as f:
            f.write(tag)
        finalize_snapshot(tmp, path, prev_path=prev, generation=1)

    write(b"gen1")
    assert open(path, "rb").read() == b"gen1" and not os.path.exists(prev)
    write(b"gen2")
    assert open(path, "rb").read() == b"gen2"
    assert open(prev, "rb").read() == b"gen1"


# --------------------------------------------------------------- RSS flatness

_RSS_CHILD = r"""
import json, resource, sys
import numpy as np

sys.path.insert(0, __REPO__)
from locust_tpu.config import EngineConfig
from locust_tpu.engine import MapReduceEngine

def rss_mb():
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024

BL, W = 2048, 64
cfg = EngineConfig(block_lines=BL, line_width=W, emits_per_line=8,
                   sort_mode="hasht")
eng = MapReduceEngine(cfg)

lines = [b"k%04d common" % i for i in range(BL)]
base = np.zeros((BL, W), np.uint8)
for i, ln in enumerate(lines):
    base[i, : len(ln)] = np.frombuffer(ln, np.uint8)

def blocks(n):
    for _ in range(n):
        yield base.copy()  # fresh host array per block, like the loader

import os, tempfile
td = tempfile.mkdtemp()
N_SMALL, N_BIG = 24, 320

res = eng.run_stream(blocks(N_SMALL), checkpoint_dir=os.path.join(td, "a"),
                     every=4, fingerprint="rss-a")
assert res.num_segments == BL + 1, res.num_segments
rss_small = rss_mb()
res = eng.run_stream(blocks(N_BIG), checkpoint_dir=os.path.join(td, "b"),
                     every=4, fingerprint="rss-b")
assert res.num_segments == BL + 1, res.num_segments
assert res.stream["ckpt"]["mode"] == "async"
rss_big = rss_mb()
print(json.dumps({
    "rss_small_mb": round(rss_small, 1),
    "rss_big_mb": round(rss_big, 1),
    "delta_mb": round(rss_big - rss_small, 1),
    "big_corpus_mb": round(N_BIG * BL * W / 1e6, 1),
    "ckpt": res.stream["ckpt"],
}))
"""


def test_rss_flat_with_async_checkpoints_tier1():
    """Tier-1 RSS-flatness regression: a 13x-larger streamed corpus with
    async checkpoints enabled must not grow peak RSS by more than a
    fixed margin — staging ring + bounded inflight + latest-wins marks
    keep the working set O(1) in corpus size (the flat-RSS contract)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO  # the child imports THIS checkout (R006)
    r = subprocess.run(
        [sys.executable, "-c", _RSS_CHILD.replace("__REPO__", repr(REPO))],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert r.returncode == 0, f"child failed:\n{r.stderr[-2000:]}"
    row = json.loads(r.stdout.strip().splitlines()[-1])
    # The big run streams ~42MB; a regression that pins staged blocks
    # (or buffers snapshot generations) shows up as tens of MB here.
    assert row["delta_mb"] < 25, f"streaming RSS grew with corpus: {row}"
    assert row["ckpt"]["written"] >= 1
