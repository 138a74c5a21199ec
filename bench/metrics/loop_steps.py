"""Loop steps per solve (PageRank iterations), averaged over the window."""


def read(run):
    return sum(run.steps) / len(run.steps)
