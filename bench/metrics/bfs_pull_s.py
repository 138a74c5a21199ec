"""Device seconds per BFS of its pull levels in the traced window: the
slab engine's max-pull over the blocked layout (scope ``traversal.pull``,
which holds the ``tocab.*`` phases), over the solves in the window."""


def read(run):
    if run.scopes is None or "traversal.pull" not in run.scopes:
        return None
    return run.scopes["traversal.pull"] / run.solves
