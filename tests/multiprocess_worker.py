"""Worker entrypoint for the multi-process distributed tests.

Each OS process joins the JAX coordination service, contributes 2 virtual
CPU devices to a 4-device global mesh, and runs the SAME global program;
process 0 writes the gathered result as JSON.  This is the standard JAX
recipe for exercising the multi-host path (coordinator + per-process
``jax.distributed.initialize`` + ``make_array_from_process_local_data``)
without a TPU pod — the real-pod launch differs only in addresses
(SURVEY.md §7.3.5).

Modes (r2 features must run under process_count>1):

  wordcount        DistributedMapReduce end-to-end (the original test)
  checkpoint       crash injected mid-run, then a FRESH engine resumes from
                   the per-process npz snapshots — exercises the multihost
                   ``process_allgather`` snapshot gather and the
                   ``make_array_from_callback`` resume scatter
  invindex         DistributedInvertedIndex across process boundaries
  samplesort       DistributedSampleSort + its multihost result gather
  hierarchical     HierarchicalMapReduce, slice axis across processes
  hier_checkpoint  the checkpoint scenario on the hierarchical engine's
                   2-D [slice, data] sharding

Usage: multiprocess_worker.py <coordinator> <num_procs> <pid> <out_json>
       <mode> [checkpoint_dir]
Env (set by the spawning test, BEFORE jax import):
  JAX_PLATFORMS=cpu, XLA_FLAGS=--xla_force_host_platform_device_count=2
"""

import json
import sys

BASE_LINES = [
    b"the quick brown fox jumps over the dog",
    b"pack my box with five dozen liquor jugs",
    b"the five boxing wizards jump quickly",
    b"sphinx of black quartz judge my vow",
]


def run_wordcount(dmr, cfg, out):
    from locust_tpu.core import bytes_ops

    lines = BASE_LINES * (dmr.lines_per_round // 2)
    rows = bytes_ops.strings_to_rows(lines, cfg.line_width)
    res = dmr.run(rows)
    out["pairs"] = [[k.decode(), v] for k, v in res.to_host_pairs()]
    out["n_lines"] = len(lines)


def _crash_resume(make_engine, cfg, out, checkpoint_dir):
    """Shared crash+resume harness: crash at round 2 of 4, rebuild the
    engine via ``make_engine()``, resume from the per-process snapshots.
    One copy for the flat and hierarchical scenarios, so the protocol
    under test (crash round, cadence, resumed-round accounting) cannot
    drift between them."""
    from locust_tpu.core import bytes_ops

    eng = make_engine()
    lines = BASE_LINES * eng.lines_per_round  # 4 rounds
    rows = bytes_ops.strings_to_rows(lines, cfg.line_width)
    nrounds = -(-rows.shape[0] // eng.lines_per_round)
    assert nrounds >= 4, nrounds

    real_step = eng._step
    calls = {"n": 0}

    def crashing_step(*args):
        if calls["n"] == 2:  # deterministic on every process, pre-dispatch
            raise RuntimeError("injected crash")
        calls["n"] += 1
        return real_step(*args)

    eng._step = crashing_step
    crashed = False
    try:
        eng.run(rows, checkpoint_dir=checkpoint_dir, checkpoint_every=1,
                stats_sync_every=1)
    except RuntimeError as e:
        crashed = "injected crash" in str(e)
    assert crashed, "crash injection did not fire"

    # Fresh engine (same config/mesh) resumes from the snapshots.
    eng2 = make_engine()
    resumed_calls = {"n": 0}
    real2 = eng2._step

    def counting_step(*args):
        resumed_calls["n"] += 1
        return real2(*args)

    eng2._step = counting_step
    res = eng2.run(rows, checkpoint_dir=checkpoint_dir, checkpoint_every=1)
    out["pairs"] = [[k.decode(), v] for k, v in res.to_host_pairs()]
    out["n_lines"] = len(lines)
    out["nrounds"] = nrounds
    out["resumed_rounds"] = resumed_calls["n"]


def run_invindex(mesh, cfg, out):
    import numpy as np

    from locust_tpu.apps.inverted_index import build_inverted_index_mesh

    lines = BASE_LINES * 8
    doc_ids = (np.arange(len(lines), dtype=np.int32) // 2).astype(np.int32)
    index = build_inverted_index_mesh(lines, doc_ids, mesh, cfg)
    out["index"] = {k.decode(): v for k, v in index.items()}
    out["doc_ids"] = doc_ids.tolist()
    out["lines"] = [ln.decode() for ln in lines]


def run_hierarchical(cfg, out):
    """2 slices x 2 devices, slice axis ACROSS processes: exercises the
    slice-varying stats fetch (a plain device_get would touch
    non-addressable devices) and the cross-slice combine over DCN."""
    from locust_tpu.core import bytes_ops
    from locust_tpu.parallel.hierarchical import HierarchicalMapReduce
    from locust_tpu.parallel.mesh import make_mesh_2d

    mesh2 = make_mesh_2d(2, 2)
    h = HierarchicalMapReduce(mesh2, cfg)
    lines = BASE_LINES * (2 * h.lines_per_round // len(BASE_LINES))
    rows = bytes_ops.strings_to_rows(lines, cfg.line_width)
    res = h.run(rows, stats_sync_every=1)  # sync every round: worst case
    out["pairs"] = [[k.decode(), v] for k, v in res.to_host_pairs()]
    out["n_lines"] = len(lines)
    out["distinct"] = res.distinct


def run_hier_checkpoint(cfg, out, checkpoint_dir):
    """The crash+resume scenario on the hierarchical engine: the
    ShardedCheckpoint gather/scatter runs on the 2-D [slice, data]
    sharding with the slice axis spanning process boundaries — the
    hardest layout the snapshot protocol has to survive."""
    from locust_tpu.parallel.hierarchical import HierarchicalMapReduce
    from locust_tpu.parallel.mesh import make_mesh_2d

    _crash_resume(
        lambda: HierarchicalMapReduce(make_mesh_2d(2, 2), cfg),
        cfg, out, checkpoint_dir,
    )


def run_spagerank(mesh, out):
    """ShardedPageRank across process boundaries: the host-replicated
    routing plan scatters via make_array_from_callback and the final rank
    vector gathers via process_allgather — the two multi-controller paths
    a single-process mesh never exercises."""
    import numpy as np

    from locust_tpu.apps.pagerank import ShardedPageRank

    n = 200
    rng = np.random.default_rng(11)  # same seed on every process
    src = rng.integers(0, n, 1200).astype(np.int32)
    dst = rng.integers(0, n, 1200).astype(np.int32)
    ranks = ShardedPageRank(mesh, n).run(src, dst, num_iters=10)
    out["ranks"] = [float(r) for r in ranks]
    out["num_nodes"] = n
    out["edge_seed"] = 11
    out["n_edges"] = 1200


def run_samplesort(mesh, cfg, out):
    import numpy as np

    from locust_tpu.apps.sample_sort import DistributedSort
    from locust_tpu.core import bytes_ops

    rng = np.random.default_rng(7)
    words = [b"w%04d" % n for n in rng.integers(0, 500, size=64)]
    keys = bytes_ops.strings_to_rows(words, cfg.key_width)
    srt = DistributedSort(mesh, cfg, rows_per_device=64)
    res = srt.sort_rows(keys)
    out["sorted"] = [[k.decode(), int(v)] for k, v in res.to_host_sorted()]
    out["input"] = [w.decode() for w in words]


def main() -> int:
    coordinator, num_procs, pid, out_path, mode = (
        sys.argv[1],
        int(sys.argv[2]),
        int(sys.argv[3]),
        sys.argv[4],
        sys.argv[5] if len(sys.argv) > 5 else "wordcount",
    )
    checkpoint_dir = sys.argv[6] if len(sys.argv) > 6 else None

    import jax

    from locust_tpu.config import EngineConfig
    from locust_tpu.parallel import DistributedMapReduce, make_mesh
    from locust_tpu.parallel.mesh import initialize_multihost

    initialize_multihost(coordinator, num_procs, pid)
    assert jax.process_count() == num_procs, jax.process_count()

    cfg = EngineConfig(block_lines=8, line_width=64, emits_per_line=8)
    mesh = make_mesh()  # all devices across all processes
    out = {"n_devices": len(jax.devices())}

    if mode == "wordcount":
        run_wordcount(DistributedMapReduce(mesh, cfg), cfg, out)
    elif mode == "hasht":
        # The sort-free fold under REAL multi-process collectives: the
        # per-shard aggregate_exact ladder (scatters + nested lax.cond)
        # composing with cross-process all_to_all is exactly what the
        # single-process 8-device mesh cannot prove.
        import dataclasses as _dc

        hcfg = _dc.replace(cfg, sort_mode="hasht")
        run_wordcount(DistributedMapReduce(mesh, hcfg), hcfg, out)
    elif mode == "checkpoint":
        _crash_resume(
            lambda: DistributedMapReduce(make_mesh(), cfg),
            cfg, out, checkpoint_dir,
        )
    elif mode == "hasht_checkpoint":
        # Crash+resume with hasht's SLOT-ORDERED accumulator tables: the
        # snapshot/scatter-resume path must round-trip a table whose
        # valid rows are hash-scattered, not prefix-compacted.
        import dataclasses as _dc

        hcfg = _dc.replace(cfg, sort_mode="hasht")
        _crash_resume(
            lambda: DistributedMapReduce(make_mesh(), hcfg),
            hcfg, out, checkpoint_dir,
        )
    elif mode == "invindex":
        run_invindex(mesh, cfg, out)
    elif mode == "samplesort":
        run_samplesort(mesh, cfg, out)
    elif mode == "spagerank":
        run_spagerank(mesh, out)
    elif mode == "hierarchical":
        run_hierarchical(cfg, out)
    elif mode == "hier_checkpoint":
        run_hier_checkpoint(cfg, out, checkpoint_dir)
    else:
        raise SystemExit(f"unknown mode {mode!r}")

    if pid == 0:
        with open(out_path, "w") as f:
            json.dump(out, f)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
