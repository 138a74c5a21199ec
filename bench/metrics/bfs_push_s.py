"""Device seconds per BFS of its push levels in the traced window: the
flat pass that sends each frontier vertex's mark along every arc (scope
``traversal.push``), over the solves in the window."""


def read(run):
    if run.scopes is None or "traversal.push" not in run.scopes:
        return None
    return run.scopes["traversal.push"] / run.solves
