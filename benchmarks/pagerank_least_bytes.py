"""The least bytes one JOB of a PageRank configuration has to move through
HBM, from the configuration's sizes alone (``edges``, ``nodes``,
``num_iters``): the same work whatever implements it.  (``least_bytes.py``
prices the WordCount stages a block and ``record_least_bytes.py`` the
record sort a job; a PR that adds a cell edits no file, so PageRank's
prices live here.)"""

WORD = 4  # an int32 id, a float32 rank


def job(sizes) -> int:
    """The degree pass once — ``src`` read, a degree a node written — and
    then a round ``num_iters`` times: ``src`` and ``dst`` read once, ONE
    word gathered an edge (a node's rank already divided by its degree),
    and three node vectors passed once (the ranks read, the degrees'
    reciprocals read, the new ranks written).  The scatter-add's
    read-modify-write of its destination is not priced: a sum over edges
    ordered by destination writes each slot once."""
    edges, nodes = sizes["edges"], sizes["nodes"]
    degree_pass = WORD * (edges + nodes)
    a_round = WORD * (3 * edges + 3 * nodes)
    return degree_pass + sizes["num_iters"] * a_round
