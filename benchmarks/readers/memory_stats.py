"""Reader ``memory_stats``: ``peak_bytes_in_use`` after the window, the
largest over the cell's devices, in ``spec["per"]`` bytes.  Returns nothing
where the backend reports no memory statistics."""


def read(spec, env):
    return max(env.device_peaks) / spec["per"] if env.device_peaks else None
