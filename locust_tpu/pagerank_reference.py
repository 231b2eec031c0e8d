"""The plain reference of PageRank: what ``python -m locust_tpu pagerank
EDGES`` must print, computed the straightforward way on the host.

Independent of the code under test: float64 numpy, no jax, nothing of
``locust_tpu`` (it lies beside ``records_reference.py``, outside
``apps/``, whose package imports jax).  The semantics are LDBC
Graphalytics' PR, to the letter of ``apps/pagerank.py``:

* ``num_nodes`` dense slots 0 .. N-1, the ids no edge names among them
  (such a phantom id has no in-edge and no out-edge: it is dangling);
* an edge that stands k times in the list counts k times, in its source's
  out-degree and in what its destination receives;
* every node starts at 1 / N; each of ``num_iters`` rounds gives a node
  ``(1 - d) / N + d * (sum over its in-edges of rank[s] / outdeg[s] +
  dangling / N)``, where ``dangling`` is the summed rank of the nodes
  with no out-edge, spread evenly;
* the result is the vector after the last round (it sums to 1).

The benchmark keeps its own copy (``benchmarks/rmat_edges.oracle``), as
``benchmarks/records.py`` keeps the record sort's.
"""

from __future__ import annotations

import numpy as np


def pagerank(src, dst, num_nodes: int, num_iters: int = 20,
             damping: float = 0.85) -> np.ndarray:
    """float64 ``[num_nodes]`` ranks of the edge list ``src[i] -> dst[i]``."""
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    if src.size and not 0 <= min(src.min(), dst.min()) <= max(
            src.max(), dst.max()) < num_nodes:
        raise ValueError(f"a node id outside 0 .. {num_nodes - 1}")
    out_degree = np.bincount(src, minlength=num_nodes).astype(np.float64)
    dangling = out_degree == 0
    share = np.zeros(num_nodes)
    np.divide(1.0, out_degree, out=share, where=~dangling)
    ranks = np.full(num_nodes, 1.0 / num_nodes)
    for _ in range(num_iters):
        received = np.bincount(dst, weights=(ranks * share)[src],
                               minlength=num_nodes)
        ranks = (1.0 - damping) / num_nodes + damping * (
            received + ranks[dangling].sum() / num_nodes)
    return ranks


def parse_ranks(table: bytes) -> tuple[np.ndarray, np.ndarray]:
    """The CLI's ``id<TAB>rank`` table as (int64 ids, float64 ranks)."""
    values = np.array(table.split(), dtype=np.float64).reshape(-1, 2)
    return values[:, 0].astype(np.int64), values[:, 1]
