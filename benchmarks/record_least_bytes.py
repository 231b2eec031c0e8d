"""The least bytes one JOB of a record-sort configuration has to move
through HBM, from the configuration's sizes alone (``least_bytes.py``
prices the WordCount stages a block; a PR that adds a cell edits no file,
so the record sort's prices live here)."""


def permute(sizes) -> int:
    """Permuting the payload by a sorted index: every record read once and
    written once, and the 4-byte index of each read once."""
    return sizes["records"] * (2 * sizes["record_bytes"] + 4)
