"""R-MAT edge lists: the generator and the oracle of ``pagerank-rmat-5M``.

The benchmark's OWN copies, as ``records.py`` holds the record sort's:
nothing here imports the program or jax, so no later PR can move the
measure by editing ``locust_tpu/``.

``build`` writes a SNAP-style edge list — ``#`` header lines as SNAP
writes them, then one ``FromNodeId<TAB>ToNodeId`` line an edge — of
exactly ``edges`` DISTINCT directed edges with no self-loop, drawn from
``seed`` by the R-MAT recursion (Chakrabarti, Zhan, Faloutsos 2004) with
Graph500's quadrant probabilities a, b, c, d = 0.57, 0.19, 0.19, 0.05:
an edge picks one quadrant of the adjacency matrix a level, 22 levels
deep at the configuration's size, so a few hubs take most edges and
most ids of the recursion take none.  An edge with an end at
``RMAT_IDS`` or past it (no power of two), a self-loop, and an edge
already drawn are drawn again; the edges stand in the order drawn.
``RMAT_IDS`` is chosen so that the nodes an edge NAMES come to
web-Google's 875,713 within 2% at web-Google's 5,105,039 edges
(``configs/pagerank-rmat-5M.json`` has the counts over five seeds).  The
named nodes are then renamed by a random injection into the file's id
space ``0 .. 916,427`` — SNAP's file's own, whose ids are not dense
either — so some 40,000 ids no edge names remain as slots, and the
largest id is always named: N = 916,428 for every seed.

``build_probe`` writes the graph on which a ROUND shows: R-MAT mixes like
a random graph — a round shrinks the distance to PageRank's fixed point
about fourfold, and from round 13 on no rank of the configuration's graph
moves by what a float32 holds — so at the cell's size no printed table
can tell 19 rounds from 20.  A web graph's chains keep PageRank moving:
the probe is a small R-MAT body (``edge_list``) with ``chains`` directed
chains hung on it, each fed by one edge from a random body node,
``chain_nodes`` nodes long (more than the rounds), its last node
dangling.  Mass walks one node down a chain a round, so the chains' far
nodes and, through the dangling mass at their ends, every other rank
move by about 0.85**20 x 0.15 a round still: the 19-round ranks stand
2.4e-2 off the 20-round ones at worst and some 90% of the nodes past
3e-5, where the float32 program reads 3e-7 to 8e-7 (PERF.md section 6,
PR 41: the CPU's readings and the chip's).

``oracle`` is the plain reference: LDBC Graphalytics' PageRank in float64
numpy — N = largest id + 1 slots, multi-edges counted as often as they
stand, dangling mass spread evenly, teleport (1 - d) / N, a fixed number
of rounds from 1 / N.
"""

from __future__ import annotations

import numpy as np

A, B, C = 0.57, 0.19, 0.19          # Graph500's; d = 0.05 is the rest
FULL_EDGES = 5_105_039              # web-Google's edges
FULL_NODES = 875_713                # web-Google's nodes: the ids an edge names
FULL_IDS = 916_428                  # web-Google's id space: its ids run to 916,427
RMAT_IDS = 2_750_000                # the recursion's id space at FULL_EDGES (names ~875,713 nodes)


def _draw(rng, n: int, levels: int) -> tuple[np.ndarray, np.ndarray]:
    """``n`` R-MAT edges over ``2**levels`` ids, a quadrant a level."""
    src = np.zeros(n, np.int64)
    dst = np.zeros(n, np.int64)
    for _ in range(levels):
        u = rng.random(n, dtype=np.float32)
        src = (src << 1) | (u >= A + B)
        dst = (dst << 1) | (((u >= A) & (u < A + B)) | (u >= A + B + C))
    return src, dst


def edge_list(edges: int, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """Exactly ``edges`` distinct R-MAT edges without self-loops, in the
    order drawn, under the file's ids.  Below the configuration's own
    size (a rehearsal, a test) the recursion's id space shrinks with the
    edges and the file's keeps web-Google's share of unnamed ids."""
    rmat_ids = max(16, RMAT_IDS * edges // FULL_EDGES)
    levels = int(rmat_ids - 1).bit_length()
    rng = np.random.default_rng(seed)
    src = np.zeros(0, np.int64)
    dst = np.zeros(0, np.int64)
    while src.size < edges:
        s, d = _draw(rng, max(4096, 3 * (edges - src.size) // 2), levels)
        keep = (s < rmat_ids) & (d < rmat_ids) & (s != d)
        src = np.concatenate([src, s[keep]])
        dst = np.concatenate([dst, d[keep]])
        # The first of every (src, dst) pair, in the order drawn.
        _, first = np.unique(src * rmat_ids + dst, return_index=True)
        first.sort()
        src, dst = src[first], dst[first]
    src, dst = src[:edges], dst[:edges]
    # The nodes an edge names, renamed into the file's id space by a
    # random injection; its largest id is always named, so that N — and
    # with it the compiled shape — is one number for every seed.
    named = np.union1d(src, dst)
    ids = FULL_IDS if edges == FULL_EDGES else named.size * FULL_IDS // FULL_NODES
    if named.size > ids:
        raise ValueError(f"{named.size} nodes named, the id space holds {ids}")
    rename = rng.permutation(ids)
    last = int(np.flatnonzero(rename == ids - 1)[0])
    if last >= named.size:
        rename[0], rename[last] = rename[last], rename[0]
    return (rename[np.searchsorted(named, src)],
            rename[np.searchsorted(named, dst)])


def _decimal_lines(src: np.ndarray, dst: np.ndarray) -> bytes:
    """``src<TAB>dst<LF>`` an edge, rendered in numpy."""
    width = len(str(int(max(src.max(), dst.max()))))
    out = np.zeros((src.size, 2 * width + 2), np.uint8)
    for col0, values in ((0, src), (width + 1, dst)):
        v = values.astype(np.uint32)
        for k in range(width - 1, -1, -1):
            q = v // np.uint32(10)
            digit = (v - q * np.uint32(10) + np.uint32(48)).astype(np.uint8)
            # A leading zero is a NUL, dropped below; the last digit stays.
            out[:, col0 + k] = digit if k == width - 1 else np.where(
                values >= 10 ** (width - 1 - k), digit, 0)
            v = q
    out[:, width] = 9
    out[:, -1] = 10
    flat = out.ravel()
    return flat[flat != 0].tobytes()


def _write(path: str, src: np.ndarray, dst: np.ndarray, name: str, what: str) -> int:
    head = (f"# Directed graph (each unordered pair of nodes is saved once): {name}.txt\n"
            f"# {what}\n"
            f"# Nodes: {np.union1d(src, dst).size} Edges: {src.size}\n"
            f"# FromNodeId\tToNodeId\n").encode()
    body = _decimal_lines(src, dst)
    with open(path, "wb") as f:
        f.write(head)
        f.write(body)
    return len(head) + len(body)


def build(path: str, edges: int, seed: int) -> int:
    """Write the edge list of (edges, seed) to ``path``; returns the bytes
    written.  A function of (edges, seed)."""
    src, dst = edge_list(edges, seed)
    return _write(path, src, dst, f"rmat-seed-{seed}",
                  "R-MAT (a, b, c, d = 0.57, 0.19, 0.19, 0.05), distinct edges, no self-loops")


def build_probe(path: str, seed: int, edges: int, chains: int, chain_nodes: int, ids: int) -> int:
    """Write the probe of ``seed`` to ``path``: ``edges`` R-MAT edges over
    the first ids and ``chains`` chains of ``chain_nodes`` nodes over the
    LAST ``chains * chain_nodes`` of ``ids`` ids, a chain's head fed by one
    body node, its end dangling; the ids between are slots no edge names.
    So N = ``ids`` and the edge count are one number for every seed — one
    compiled shape, as the configuration's own graph has.  Returns the
    bytes written."""
    src, dst = edge_list(edges, seed)
    rng = np.random.default_rng([seed, chains])
    body_ids = int(max(src.max(), dst.max())) + 1
    chain_ids = np.arange(ids - chains * chain_nodes, ids)
    if body_ids > chain_ids[0]:
        raise ValueError(f"the body takes {body_ids} ids, the chains start at {chain_ids[0]}")
    before = chain_ids - 1
    before[::chain_nodes] = rng.integers(0, body_ids, chains)   # what feeds a chain's head
    return _write(path, np.concatenate([src, before]), np.concatenate([dst, chain_ids]),
                  f"rmat-chains-seed-{seed}",
                  f"R-MAT as above, and {chains} chains of {chain_nodes} nodes behind it")


def load(path: str) -> tuple[np.ndarray, np.ndarray]:
    """The file's edges as two int64 arrays: ``#`` lines skipped, every
    other line two numbers."""
    with open(path, "rb") as f:
        data = f.read()
    while data.startswith(b"#"):
        data = data[data.index(b"\n") + 1:]
    values = np.fromstring(data, dtype=np.int64, sep=" ")
    if values.size == 0 or values.size != 2 * data.count(b"\n"):
        raise ValueError(f"{path}: not two numbers a line")
    return values[0::2], values[1::2]


def oracle(edges: tuple[np.ndarray, np.ndarray], num_iters: int = 20,
           damping: float = 0.85) -> np.ndarray:
    """float64 ranks of every id 0 .. largest id, Graphalytics' PR."""
    src, dst = edges
    n = int(max(src.max(), dst.max())) + 1
    out_degree = np.bincount(src, minlength=n).astype(np.float64)
    dangling = out_degree == 0
    share = np.where(dangling, 0.0, 1.0 / np.maximum(out_degree, 1.0))
    ranks = np.full(n, 1.0 / n)
    for _ in range(num_iters):
        received = np.bincount(dst, weights=(ranks * share)[src], minlength=n)
        ranks = (1.0 - damping) / n + damping * (received + ranks[dangling].sum() / n)
    return ranks
