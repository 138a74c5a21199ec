"""Host seconds of ``build_blocked``'s fill step, over every layout the
cell reads: run and local ids, the padded slabs, ``id_map`` and the
window-side counts.  Read from the program's own ``build_blocked.fill``
spans."""
from bench.scopes import host_seconds


def read(run):
    return host_seconds("build_blocked.fill")
