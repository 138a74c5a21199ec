"""Device seconds per loop step of the slab engine's phase 2 in the
traced window: each block's scatter of its slots into partials by local
target (scope ``tocab.partials``)."""
from bench.scopes import per_step


def read(run):
    return per_step(run, "tocab.partials")
