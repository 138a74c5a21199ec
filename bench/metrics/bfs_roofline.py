"""The BFS solves' share of their HBM roofline, in %: the least time the
chip could take for the bytes of the arcs and vertices the searches
traverse (``least_bytes`` of ``bench/algorithms/bfs.py``, at the peak HBM
bandwidth of ``bench/peaks.json``) over the device's busy time in the
traced window."""


def read(run):
    t = run.trace
    if t is None or t.busy_s <= 0:
        return None
    return 100.0 * run.least_bytes / run.peaks["hbm_bytes_per_s"] / t.busy_s
