"""PageRank: iterative MapReduce over an edge list (BASELINE.json configs[3]).

MapReduce formulation (the reference engine never shipped a second workload,
but its map/emit/reduce contract extends directly — SURVEY.md §7.1 "API"):
per iteration, map each edge (s -> d) to the emit ``(d, rank[s]/deg[s])``
and reduce by key with sum; then apply damping.

TPU-native formulation: node ids ARE the keys, so the shuffle degenerates to
a dense ``segment_sum`` into a ``[num_nodes]`` vector — no byte keys, no
sort.  What is a function of the NODE is computed over the nodes: a round
multiplies ``ranks * inv_deg`` once (the emit every out-edge of a node
carries) and the edges take it in ONE gather (PERF.md §6, PR 42).  A v5e
pays a gather by the index and not by the byte — a gathered word costs it
6.6 ns, a gathered 128-lane row 2.2 — so the edges gather ROWS of the share
laid out ``[N / 128, 128]`` and select a lane, a fixed-size chunk of edges at
a time (``_gather_chunks`` has the account; PERF.md §6, PR 46): the same
bits as ``share[src]`` on every backend, one spelling everywhere.
Iterations run under ``lax.scan`` (static trip count) or a
``while_loop`` on the L1 residual.  Distributed: edges shard across the
mesh, each device computes a partial dense contribution vector, and the
"shuffle" is a single ``psum`` — the degenerate all-to-all for dense integer
keys.  Dangling mass (deg==0 nodes) redistributes uniformly, the standard
correction.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from locust_tpu.parallel.mesh import DATA_AXIS


LANES = 128
# Edges gathered a step of the round's inner loop.  Chosen on the chip
# (PERF.md section 6, PR 46): at 65,536 the chip's compiler keeps a chunk's
# ``[CHUNK, 128]`` rows out of HBM and the program's temporaries do not grow
# with the edges.
CHUNK = 65_536


def _edge_chunks(src):
    """int32 ids ``[E]`` as the ``[chunks, chunk]`` unsigned ids that
    ``_gather_chunks`` walks: at most ``CHUNK`` a chunk (a graph smaller
    than a chunk is one short chunk), padded with id 0 to a whole number of
    chunks.  A caller that loops pads ONCE, before its loop."""
    edges = src.shape[0]
    chunk = max(1, min(CHUNK, edges))
    return jnp.pad(src.astype(jnp.uint32), (0, -edges % chunk)).reshape(-1, chunk)


def _gather_chunks(share, chunks):
    """``share[ids]`` for float32 ``share`` ``[N]`` and every id of
    ``chunks`` (``_edge_chunks``), flat, the padding's values included —
    spelled as a gather of 128-lane ROWS and a lane select.

    A v5e pays a gather by the index and not by the byte: a word an index
    costs it 6.6 ns, a 128-lane row 2.2 (PERF.md section 6, PRs 42 and 46).
    So the share is laid out as a ``[ceil(N / 128), 128]`` table (padded
    with zeros), an edge takes row ``id >> 7`` whole and keeps lane
    ``id & 127``.  The select is ``where`` + ``sum`` over the lanes: one
    value and 127 zeros sum to that value's bits on every backend (a share
    is never ``-0.0``), where a product with a one-hot would let an ``inf``
    or a ``nan`` of a NEIGHBOUR in the row through, and a dot on the MXU is
    not float32 unless asked.

    The rows of all edges at once would be ``E x 512`` bytes (2.6 GB at 5 M
    edges), so ``lax.map`` walks the edges a chunk at a time and only one
    chunk's rows exist.

    The ids are read as unsigned and the row index clamps, so nothing
    wraps: a negative id or one at or past ``N`` reads the table's zero
    padding or a lane of its last row, not ``share[-1]`` as numpy's
    indexing has it — callers pass valid ids.  A CPU pays some 0.15 us an
    edge for this spelling where ``share[ids]`` costs it 0.004: accepted,
    so that the tests run the code the chip runs.
    """
    nodes = share.shape[0]
    rows = -(-nodes // LANES)
    table = jnp.pad(share, (0, rows * LANES - nodes)).reshape(rows, LANES)
    lane = jnp.arange(LANES, dtype=jnp.uint32)

    def pick(ids):
        picked = jnp.where(lane == (ids & 127)[:, None], table[ids >> 7], 0.0)
        return jnp.sum(picked, axis=1)

    return jax.lax.map(pick, chunks).reshape(-1)


def _gather_share(share, src):
    """``share[src]`` for valid int32 ids ``src`` ``[E]``, gathered as rows
    (``_gather_chunks``): the same bits in the same order."""
    return _gather_chunks(share, _edge_chunks(src))[: src.shape[0]]


def _contributions(chunks, dst, ranks, inv_deg, num_nodes):
    """Dense map+reduce of one iteration: sum_d rank[s]/deg[s], the edges'
    sources as ``_edge_chunks`` lays them out.

    The share is multiplied over the NODES and the edges gather it once, as
    rows: the same two float32 operands an edge as
    ``ranks[src] * inv_deg[src]``, so the same bits
    (tests/test_pagerank_cli.py holds both), in the same edge order into
    the same scatter-add.
    """
    share = _gather_chunks(ranks * inv_deg, chunks)[: dst.shape[0]]
    return jax.ops.segment_sum(share, dst, num_segments=num_nodes)


@functools.partial(jax.jit, static_argnames=("num_nodes", "num_iters"))
def pagerank(
    src: jax.Array,
    dst: jax.Array,
    num_nodes: int,
    num_iters: int = 20,
    damping: float = 0.85,
) -> jax.Array:
    """Single-device PageRank over int32 edge arrays ``[E]``.

    Pass valid edges only (no padding; ids in ``[0, num_nodes)`` — the CLI
    refuses the others before the device): the share is gathered as rows
    (``_gather_chunks``), under which an id out of range reads a zero or a
    lane of the table's last row and not, as numpy's indexing would have it,
    a wrap to ``share[-1]``.  The distributed variant supports masked edge
    padding for equal shard sizes.
    """
    deg = jax.ops.segment_sum(
        jnp.ones_like(src, dtype=jnp.float32), src, num_segments=num_nodes
    )
    inv_deg = jnp.where(deg > 0, 1.0 / jnp.maximum(deg, 1.0), 0.0)
    dangling = deg == 0
    ranks0 = jnp.full((num_nodes,), 1.0 / num_nodes, dtype=jnp.float32)
    chunks = _edge_chunks(src)

    def body(ranks, _):
        contrib = _contributions(chunks, dst, ranks, inv_deg, num_nodes)
        dangling_mass = jnp.sum(jnp.where(dangling, ranks, 0.0))
        ranks_new = (1.0 - damping) / num_nodes + damping * (
            contrib + dangling_mass / num_nodes
        )
        return ranks_new, None

    ranks, _ = jax.lax.scan(body, ranks0, None, length=num_iters)
    return ranks


@functools.partial(jax.jit, static_argnames=("num_nodes",))
def pagerank_prep(src: jax.Array, num_nodes: int):
    """The loop-invariant state of ``pagerank`` as a standalone jit:
    (inv_deg, dangling mask) from the FULL edge source column — spelled
    exactly as the fused kernel above so the distributed epoch sweep
    (plan/distribute.py IterateShape) reproduces its bits."""
    deg = jax.ops.segment_sum(
        jnp.ones_like(src, dtype=jnp.float32), src, num_segments=num_nodes
    )
    inv_deg = jnp.where(deg > 0, 1.0 / jnp.maximum(deg, 1.0), 0.0)
    return inv_deg, deg == 0


@functools.partial(jax.jit, static_argnames=("num_nodes",))
def pagerank_step(
    src: jax.Array,
    dst: jax.Array,
    ranks: jax.Array,
    inv_deg: jax.Array,
    dangling: jax.Array,
    damping,
    num_nodes: int,
) -> jax.Array:
    """ONE ``pagerank`` iteration as a standalone jit, bit-identical to
    the scan body above (both go through ``_contributions``: one row
    gathered an edge, of the share multiplied over the nodes; this one
    lays its ``src`` out in chunks every call, the scan once).
    ``damping`` is a TRACED f32 operand on
    purpose: the fused kernel traces it too, so ``(1-damping)/n``
    computes in f32 on device — marking it static would constant-fold
    that expression in python float64 and change the low bits (pinned
    by tests/test_serve.py's distributed-iterate identity).

    Epoch sharding rides dst-restriction: calling this with the edge
    SUBSET ``dst in [lo, hi)`` (full ranks/inv_deg/dangling vectors)
    yields a vector whose ``[lo:hi)`` slice is bit-identical to the
    full step's — segment_sum contributions land only on in-range dst,
    and the dangling/teleport terms are global scalars either way.
    """
    contrib = _contributions(_edge_chunks(src), dst, ranks, inv_deg, num_nodes)
    dangling_mass = jnp.sum(jnp.where(dangling, ranks, 0.0))
    return (1.0 - damping) / num_nodes + damping * (
        contrib + dangling_mass / num_nodes
    )


class DistributedPageRank:
    """Edge-sharded PageRank on a mesh: local segment_sum + psum combine.

    The mesh/axis contract matches DistributedMapReduce; ranks and degrees
    are replicated (dense [num_nodes] vectors), edges shard along the axis.
    Edge padding: pad with (-1 -> clamped) masked edges via ``edge_mask``.
    """

    def __init__(self, mesh, num_nodes: int, axis_name: str = DATA_AXIS,
                 damping: float = 0.85):
        self.mesh = mesh
        self.num_nodes = num_nodes
        self.axis = axis_name
        self.damping = damping
        n_dev = mesh.shape[axis_name]
        num = num_nodes
        damp = damping

        def step(src, dst, mask, ranks, inv_deg, dangling_vec):
            # Local partial: masked edges contribute 0.
            w = _gather_share(ranks * inv_deg, src) * mask
            partial = jax.ops.segment_sum(w, dst, num_segments=num)
            contrib = jax.lax.psum(partial, axis_name)          # the combine
            local_dangling = jnp.sum(jnp.where(dangling_vec, ranks, 0.0))
            ranks_new = (1.0 - damp) / num + damp * (
                contrib + local_dangling / num
            )
            return ranks_new

        self._step = jax.jit(
            jax.shard_map(
                step,
                mesh=mesh,
                in_specs=(P(axis_name), P(axis_name), P(axis_name), P(), P(), P()),
                out_specs=P(),
            )
        )
        self.n_dev = n_dev

    def run(self, src: np.ndarray, dst: np.ndarray, num_iters: int = 20) -> np.ndarray:
        num = self.num_nodes
        deg = np.bincount(src, minlength=num).astype(np.float32)
        inv_deg = np.where(deg > 0, 1.0 / np.maximum(deg, 1.0), 0.0).astype(
            np.float32
        )
        dangling = deg == 0
        # Pad edge shards to equal length per device.
        e = len(src)
        per = -(-e // self.n_dev)
        pad = per * self.n_dev - e
        src_p = np.concatenate([src, np.zeros(pad, src.dtype)]).astype(np.int32)
        dst_p = np.concatenate([dst, np.zeros(pad, dst.dtype)]).astype(np.int32)
        mask = np.concatenate(
            [np.ones(e, np.float32), np.zeros(pad, np.float32)]
        )
        ranks = np.full((num,), 1.0 / num, dtype=np.float32)
        for _ in range(num_iters):
            ranks = self._step(src_p, dst_p, mask, ranks, inv_deg, dangling)
        return np.asarray(jax.device_get(ranks))


class ShardedPageRank:
    """Node-partitioned PageRank: rank state sharded, not replicated.

    ``DistributedPageRank`` replicates dense ``[num_nodes]`` rank/degree
    vectors on every device, capping graph size at one device's HBM.
    Here device ``d`` owns the
    contiguous node block ``[d*npd, (d+1)*npd)`` and only ever holds

      * its rank/degree block                  O(nodes / n_dev)
      * its edge shard (grouped by src owner)  O(edges / n_dev)
      * fixed-size send/recv buffers           O(n_dev * send_cap)

    The per-iteration exchange is the sparse analog of the shuffle in
    parallel/shuffle.py: contributions pre-aggregate into a STATIC send
    slot per (device, destination-shard, distinct-destination-node) —
    the graph is static, so the entire routing plan (slot ids, receive
    maps) is computed ONCE on the host and the device step is just

      gather local shares (ONE gather, as rows) -> segment_sum into send slots ->
      lax.all_to_all -> segment_sum into the local rank block -> damp,

    with the dangling-mass correction as a scalar psum.  Because slots
    are per *distinct* destination node, capacity is exact (no skew
    overflow, no drop/retry path — unlike hash bins, a destination node
    can appear in a given sender's buffer at most once).
    """

    def __init__(self, mesh, num_nodes: int, axis_name: str = DATA_AXIS,
                 damping: float = 0.85):
        if num_nodes < 1:
            raise ValueError(f"num_nodes must be >= 1, got {num_nodes}")
        self.mesh = mesh
        self.num_nodes = num_nodes
        self.axis = axis_name
        self.damping = damping
        self.n_dev = int(mesh.shape[axis_name])
        self.npd = -(-num_nodes // self.n_dev)  # nodes per device (padded)

    # -------------------------------------------------------- host-side plan

    def _build_plan(self, src: np.ndarray, dst: np.ndarray):
        """Static routing plan: all data-dependent indexing leaves the
        device loop.  Returns dict of per-device arrays (leading axis =
        device, sharded over the mesh in the step).

        Fully vectorized — ONE lexsort over (owner, dest_shard, dst) plus
        run-length boundaries; the per-(device, shard) ``np.unique`` loop
        it replaces was O(n_dev^2) host work, quadratic in devices on a
        real pod.  A dst's slot id is its rank among
        the distinct dsts of its (owner, dest_shard) pair, which after
        the lexsort is a prefix count of run starts — identical to the
        old builder's ``searchsorted(uniq, dst)`` because uniq was
        ascending.  O(E log E) total.
        """
        n_dev, npd = self.n_dev, self.npd
        src = np.asarray(src, np.int64).ravel()
        dst = np.asarray(dst, np.int64).ravel()
        owner = src // npd
        dest = dst // npd
        n_edges = src.shape[0]

        order = np.lexsort((dst, dest, owner))
        src, dst, owner, dest = (
            src[order], dst[order], owner[order], dest[order]
        )
        counts = np.bincount(owner, minlength=n_dev)
        starts = np.concatenate([[0], np.cumsum(counts)])
        e_max = max(1, int(counts.max()))

        if n_edges:
            # Run starts: first edge of each distinct (owner, dest, dst);
            # pair starts: first edge of each (owner, dest) group.
            same_run = (
                (owner[1:] == owner[:-1])
                & (dest[1:] == dest[:-1])
                & (dst[1:] == dst[:-1])
            )
            new_run = np.concatenate([[True], ~same_run])
            pair_change = np.concatenate(
                [[True], (owner[1:] != owner[:-1]) | (dest[1:] != dest[:-1])]
            )
            run_id = np.cumsum(new_run) - 1
            pair_id = np.cumsum(pair_change) - 1
            pair_first_run = run_id[pair_change]          # [n_pairs]
            rank = run_id - pair_first_run[pair_id]       # dst rank in pair
            n_pairs = int(pair_id[-1]) + 1
            nuniq = np.bincount(pair_id[new_run], minlength=n_pairs)
            cap = max(1, int(nuniq.max()))
        else:
            rank = np.zeros(0, np.int64)
            cap = 1
        cap = -(-cap // 8) * 8  # lane-align the all-to-all payload

        src_l = np.zeros((n_dev, e_max), np.int32)        # src local id
        mask = np.zeros((n_dev, e_max), np.float32)
        # Padded (and only padded) edge slots scatter to the dump slot.
        send_seg = np.full((n_dev, e_max), n_dev * cap, np.int32)
        recv_map = np.full((n_dev, n_dev, cap), npd, np.int32)  # npd = dump
        if n_edges:
            col = np.arange(n_edges) - starts[owner]      # slot within device
            src_l[owner, col] = (src - owner * npd).astype(np.int32)
            mask[owner, col] = 1.0
            send_seg[owner, col] = (dest * cap + rank).astype(np.int32)
            # Receiver p's map for sender d: slot -> its local node id,
            # one entry per distinct (owner, dest, dst) run.
            r_owner, r_dest = owner[new_run], dest[new_run]
            recv_map[r_dest, r_owner, rank[new_run]] = (
                dst[new_run] - r_dest * npd
            ).astype(np.int32)

        return dict(
            src_l=src_l, mask=mask, send_seg=send_seg, recv_map=recv_map,
            cap=cap, e_max=e_max,
        )

    # ------------------------------------------------------------------- run

    def run(self, src: np.ndarray, dst: np.ndarray, num_iters: int = 20) -> np.ndarray:
        n_dev, npd, num = self.n_dev, self.npd, self.num_nodes
        axis = self.axis
        damp = self.damping
        plan = self._build_plan(src, dst)
        cap = plan["cap"]

        # Node-block-local static vectors.
        deg = np.bincount(np.asarray(src), minlength=n_dev * npd).astype(np.float32)
        inv_deg = np.where(deg > 0, 1.0 / np.maximum(deg, 1.0), 0.0)
        node_valid = (np.arange(n_dev * npd) < num).astype(np.float32)
        dangling = ((deg == 0) & (node_valid > 0)).astype(np.float32)
        ranks0 = (node_valid / num).astype(np.float32)

        def step(src_l, mask, send_seg, recv_map, ranks_l, inv_deg_l,
                 dangling_l, valid_l):
            # shard_map gives [1, ...] blocks along the device axis; drop it.
            src_l, mask, send_seg = src_l[0], mask[0], send_seg[0]
            recv_map = recv_map[0]
            ranks_l, inv_deg_l = ranks_l[0], inv_deg_l[0]
            dangling_l, valid_l = dangling_l[0], valid_l[0]

            w = _gather_share(ranks_l * inv_deg_l, src_l) * mask
            send = jax.ops.segment_sum(
                w, send_seg, num_segments=n_dev * cap + 1
            )[: n_dev * cap].reshape(n_dev, cap)
            recv = jax.lax.all_to_all(send, axis, 0, 0)
            contrib = jax.ops.segment_sum(
                recv.reshape(-1), recv_map.reshape(-1), num_segments=npd + 1
            )[:npd]
            dangling_mass = jax.lax.psum(
                jnp.sum(ranks_l * dangling_l), axis
            )
            new_ranks = valid_l * (
                (1.0 - damp) / num + damp * (contrib + dangling_mass / num)
            )
            return new_ranks[None]

        spec = P(axis)
        step_j = jax.jit(
            jax.shard_map(
                step,
                mesh=self.mesh,
                in_specs=(spec,) * 8,
                out_specs=spec,
            )
        )

        from locust_tpu.parallel.mesh import scatter_host_array

        sharding = jax.sharding.NamedSharding(self.mesh, spec)

        def put(x):
            # Every process holds the full plan (host-replicated build);
            # the shared multi-controller scatter serves each process's
            # addressable shards by slicing.
            return scatter_host_array(x, sharding)
        src_l = put(plan["src_l"])
        mask = put(plan["mask"])
        send_seg = put(plan["send_seg"])
        recv_map = put(plan["recv_map"])
        inv_deg_l = put(inv_deg.reshape(n_dev, npd))
        dangling_l = put(dangling.reshape(n_dev, npd))
        valid_l = put(node_valid.reshape(n_dev, npd))
        ranks = put(ranks0.reshape(n_dev, npd))
        for _ in range(num_iters):
            ranks = step_j(
                src_l, mask, send_seg, recv_map, ranks, inv_deg_l,
                dangling_l, valid_l,
            )
        from locust_tpu.parallel.mesh import gather_host_array

        return gather_host_array(ranks).reshape(-1)[:num]
