"""The reference copies agree with the program's originals at a small
size."""
import numpy as np
import pytest

from benchmarks.chip.reference import graphs, oracle
from repro.core.graph import BipartiteGraph, powerlaw_bipartite
from repro.core.peeling import bup_oracle, butterfly_supports


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5])
def test_generator_matches_program(seed):
    n_u, n_v, eu, ev = graphs.powerlaw_bipartite(300, 120, 1500, seed=seed)
    g = powerlaw_bipartite(300, 120, 1500, seed=seed)
    assert (n_u, n_v) == (g.n_u, g.n_v)
    assert np.array_equal(eu, g.edges_u) and np.array_equal(ev, g.edges_v)


@pytest.mark.parametrize("side", ["U", "V"])
def test_oracle_matches_program(side):
    edges = graphs.relabeled(graphs.powerlaw_bipartite(200, 90, 1200), 3)
    g = BipartiteGraph.from_edges(*edges)
    gs = g if side == "U" else g.transposed()
    peel = oracle.peel_side(edges, side)
    theta, metrics = bup_oracle(gs)
    assert np.array_equal(peel.theta, theta)
    assert peel.max_support == int(butterfly_supports(gs).max())
    assert peel.wedges_peel == metrics.wedges
    assert peel.wedges_count == metrics.wedges_static


def test_relabeling_keeps_the_tip_numbers():
    base = graphs.powerlaw_bipartite(200, 90, 1200, seed=1)
    a = oracle.bup_peel(base).theta
    b = oracle.bup_peel(graphs.relabeled(base, 2**31 + 1)).theta
    assert sorted(a.tolist()) == sorted(b.tolist())
