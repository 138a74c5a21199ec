"""The benchmark's graph generators and the seed's relabelling."""
import numpy as np
import pytest

from bench.generators import kron
from bench.graph import make_graph, simple_undirected

SCALE = 10
BLOCK = 128


def config(generator: str) -> dict:
    cfg = {"name": f"small-{generator}", "generator": generator,
           "scale": SCALE, "edge_factor": 16, "undirected": True,
           "graph_seed": 0}
    if generator == "kron":
        cfg.update(a=0.57, b=0.19, c=0.19, permute_ids=True)
    return cfg


def arcs_of(g) -> np.ndarray:
    src = np.repeat(np.arange(g.n, dtype=np.int64), g.degree)
    return src * g.n + g.colidx


@pytest.mark.parametrize("generator", ["kron", "urand"])
def test_graph_is_simple_undirected_and_sized(generator):
    g = make_graph(config(generator), seed=2**31 + 5, block=BLOCK)
    assert g.n == 1 << SCALE
    assert g.rowptr.shape == (g.n + 1,) and g.rowptr[-1] == g.arcs
    assert g.colidx.dtype == np.int32
    assert 0 <= g.colidx.min() and g.colidx.max() < g.n
    src = np.repeat(np.arange(g.n), g.degree)
    assert not (src == g.colidx).any(), "self-loop"
    keys = arcs_of(g)
    assert np.unique(keys).size == keys.size, "duplicate arc"
    rev = g.colidx.astype(np.int64) * g.n + src
    assert np.array_equal(np.sort(keys), np.sort(rev)), "arc without reverse"
    # edge factor 16: at most 2 * 16 * n arcs, most of them kept
    nominal = 2 * 16 * g.n
    low = 0.6 if generator == "kron" else 0.95
    assert low * nominal < g.arcs <= nominal


def block_loads(g, block):
    """Per block of source ids: arcs, and distinct targets."""
    src = np.repeat(np.arange(g.n), g.degree) // block
    arcs = np.bincount(src, minlength=g.n // block)
    pairs = np.unique(src * g.n + g.colidx) // g.n
    return arcs, np.bincount(pairs, minlength=g.n // block)


@pytest.mark.parametrize("generator", ["kron", "urand"])
def test_seed_fixes_the_graph_and_relabels_within_blocks(generator):
    cfg = config(generator)
    a = make_graph(cfg, seed=7, block=BLOCK)
    b = make_graph(cfg, seed=7, block=BLOCK)
    assert np.array_equal(a.rowptr, b.rowptr)
    assert np.array_equal(a.colidx, b.colidx)
    c = make_graph(cfg, seed=8, block=BLOCK)
    assert not np.array_equal(np.sort(arcs_of(a)), np.sort(arcs_of(c)))
    # another seed: the same degrees, and in every block (and every
    # multiple of it) the same arcs and distinct targets
    assert np.array_equal(np.sort(a.degree), np.sort(c.degree))
    for block in (BLOCK, 2 * BLOCK):
        for x, y in zip(block_loads(a, block), block_loads(c, block)):
            assert np.array_equal(x, y)


def test_kron_is_skewed_and_urand_is_not():
    kron = make_graph(config("kron"), seed=1, block=BLOCK).degree
    urand = make_graph(config("urand"), seed=1, block=BLOCK).degree
    assert kron.max() > 8 * kron.mean()
    assert urand.max() < 3 * urand.mean()


def test_directed_config_is_refused():
    with pytest.raises(ValueError, match="undirected"):
        make_graph(dict(config("urand"), undirected=False), seed=0, block=BLOCK)



@pytest.mark.parametrize("seed", [0, 2**40 + 3])
def test_run_id_maps_the_drawn_graph_onto_the_run(seed):
    """``run_id`` takes each drawn vertex to its id in the run's graph,
    within its block, and every drawn arc to an arc of the run."""
    cfg = config("kron")
    run = make_graph(cfg, seed=seed, block=BLOCK)
    drawn = simple_undirected(run.n, *kron.draw(cfg, cfg["graph_seed"]))
    ids = run.run_id
    assert drawn.run_id is None and ids.dtype == np.int32
    assert np.array_equal(np.sort(ids), np.arange(run.n))
    assert np.array_equal(ids // BLOCK, np.arange(run.n) // BLOCK)
    src = np.repeat(np.arange(drawn.n), drawn.degree)
    mapped = ids[src].astype(np.int64) * run.n + ids[drawn.colidx]
    assert np.array_equal(np.sort(mapped), np.sort(arcs_of(run)))
