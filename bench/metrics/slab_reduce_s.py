"""Device seconds per loop step of the slab engine's phase 3 in the
traced window: the scatter of the blocks' partials into the vertex array
through ``id_map`` (scope ``tocab.reduce``)."""
from bench.scopes import per_step


def read(run):
    return per_step(run, "tocab.reduce")
