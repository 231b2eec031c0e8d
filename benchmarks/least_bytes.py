"""The least bytes a piece of the program has to move through HBM, from the
configuration's sizes alone: each input row of each program read once, each
output row written once.  A row is what the programs hand one another, a
``KVBatch`` row: ``key_width`` key bytes (uint32 lanes), an int32 count and
a validity byte — 37 bytes at the CLI's ``key_width`` 32.  The hash arrays
a sort makes for itself inside a program are not counted: they are not
least bytes."""


def _row(sizes) -> int:
    return sizes["key_width"] + 4 + 1


def process_block(sizes) -> int:
    """The Process stage's three programs on ONE block of the default
    path: ``sort_and_compact`` (E emit rows in, E out), ``segment_reduce``
    (E in, a T-row table out) and ``merge_tables`` (two T-row tables in,
    one out)."""
    emits = sizes["block_lines"] * sizes["emits_per_line"]
    return (3 * emits + 4 * sizes["table_rows"]) * _row(sizes)
