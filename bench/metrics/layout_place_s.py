"""Host seconds of the copies of the blocked layouts
(``build_blocked.place``) and of the flat graph
(``device_graph.place``) to the device: the program's own spans around
the calls that start the copies."""
from bench.scopes import host_seconds


def read(run):
    return host_seconds("build_blocked.place", "device_graph.place")
