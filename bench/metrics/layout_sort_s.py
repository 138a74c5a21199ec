"""Host seconds of ``build_blocked``'s sort step, over every layout the
cell reads: the ``np.lexsort`` of the arcs by block and compacted id, and
the reorder of the arc arrays.  Read from the program's own
``build_blocked.sort`` spans."""
from bench.scopes import host_seconds


def read(run):
    return host_seconds("build_blocked.sort")
