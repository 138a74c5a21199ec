"""Peak device memory of the run, ``peak_bytes_in_use`` read after the
window, in GB (1e9 bytes)."""


def read(run):
    peak = run.memory_peak_bytes
    return None if peak is None else peak / 1e9
