"""The least bytes ONE DEVICE's share of a mesh record-sort job has to
move, from the configuration's sizes alone (``records``, ``record_bytes``,
``chips``): the mean share is priced, and the reader divides it by the
BUSIEST device's time, so skew lowers a share and nothing can push it
past 100%.  (``record_least_bytes.py`` prices a whole job on one device;
a PR that adds a cell edits no file, so the mesh's prices live here.)"""


def _share(sizes) -> float:
    return sizes["records"] / sizes["chips"]


def partition(sizes) -> float:
    """HBM: binning whole records — each local record read once and
    written once into its bin, and the 4-byte index of each read once."""
    return _share(sizes) * (2 * sizes["record_bytes"] + 4)


def shard_permute(sizes) -> float:
    """HBM: permuting a shard by its sorted index — every record read
    once and written once, 4 bytes of index each."""
    return _share(sizes) * (2 * sizes["record_bytes"] + 4)


def all_to_all(sizes) -> float:
    """ICI: the record bytes that LEAVE a chip in the range exchange — of
    an even partition, (chips - 1) / chips of its records."""
    return _share(sizes) * sizes["record_bytes"] * (sizes["chips"] - 1) / sizes["chips"]
