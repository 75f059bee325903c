"""Graph generation for the benchmark, independent of the program.

A copy of the program's Chung-Lu power-law generator
(``repro.core.graph.powerlaw_bipartite``), returning plain edge arrays:
deduplicated, sorted by ``(u, v)``, int32 — the same canonical form as
``BipartiteGraph.from_edges``.  ``tests/test_reference.py`` holds the
copy to the original at a small size.

``relabeled`` permutes both vertex sets from the run's seed.  Every seed
then decomposes the same graph in another order: the same sizes, the
same tip numbers up to the labels, the same compiled shapes — so runs
with different seeds measure the same work.
"""
from __future__ import annotations

from typing import Tuple

import numpy as np

Edges = Tuple[int, int, np.ndarray, np.ndarray]


def canonical(n_u: int, n_v: int, eu, ev) -> Edges:
    """Deduplicate and sort an edge list by ``(u, v)``."""
    key = np.unique(np.asarray(eu, np.int64) * n_v + np.asarray(ev, np.int64))
    return (n_u, n_v, (key // n_v).astype(np.int32),
            (key % n_v).astype(np.int32))


def powerlaw_bipartite(n_u: int, n_v: int, m_target: int,
                       alpha_u: float = 2.0, alpha_v: float = 2.0,
                       seed: int = 0) -> Edges:
    """Chung-Lu bipartite graph with power-law expected degrees (a copy
    of the program's generator: same draws, same edges)."""
    rng = np.random.default_rng(seed)
    wu = np.arange(1, n_u + 1, dtype=np.float64) ** (-1.0 / (alpha_u - 1.0))
    wv = np.arange(1, n_v + 1, dtype=np.float64) ** (-1.0 / (alpha_v - 1.0))
    wu *= m_target / wu.sum()
    wv *= m_target / wv.sum()
    pu = wu / wu.sum()
    pv = wv / wv.sum()
    k = int(m_target * 1.3) + 16
    eu = rng.choice(n_u, size=k, p=pu)
    ev = rng.choice(n_v, size=k, p=pv)
    return canonical(n_u, n_v, eu, ev)


def relabeled(graph: Edges, seed: int) -> Edges:
    """The same graph with U and V labels permuted from ``seed``."""
    n_u, n_v, eu, ev = graph
    rng = np.random.default_rng(seed)
    pu = rng.permutation(n_u)
    pv = rng.permutation(n_v)
    return canonical(n_u, n_v, pu[eu], pv[ev])


def make_graph(spec: dict, seed: int) -> Edges:
    """The graph a traffic mix names (``spec`` is its ``graph`` group),
    relabeled from the run's seed."""
    if spec["family"] != "powerlaw":
        raise ValueError(f"unknown graph family {spec['family']!r}")
    base = powerlaw_bipartite(spec["n_u"], spec["n_v"], spec["m_target"],
                              alpha_u=spec["alpha_u"],
                              alpha_v=spec["alpha_v"],
                              seed=spec["structure_seed"])
    return relabeled(base, seed)
