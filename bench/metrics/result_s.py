"""Time to result: the window's seconds over the solves completed in it
(host clock; every solve ends in ``block_until_ready``)."""


def read(run):
    return run.window_s / run.solves
