"""Device seconds per loop step of the slab engine's phase 1 in the
traced window: the gather of each slot's source value into the padded slot
slab (scope ``tocab.gather``)."""
from bench.scopes import per_step


def read(run):
    return per_step(run, "tocab.gather")
