"""The solves' share of their HBM roofline, in %: the least time the
chip could take for the least bytes the solves must move
(``least_bytes`` of the algorithm, at the peak HBM bandwidth of
``bench/peaks.json``) over the device's busy time in the traced window.
Bytes bound PageRank: it does about one operation per byte."""


def read(run):
    t = run.trace
    if t is None or t.busy_s <= 0:
        return None
    return 100.0 * run.least_bytes / run.peaks["hbm_bytes_per_s"] / t.busy_s
