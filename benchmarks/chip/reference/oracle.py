"""The plain reference: sequential bottom-up tip peeling, exact int64.

A copy of the program's ``repro.core.peeling.bup_oracle`` (Alg. 2 of
RECEIPT, arXiv:2010.08695) over plain edge arrays, independent of the
program: it peels one vertex of least support at a time and caps each
neighbour's support at the level being peeled.  Tip numbers are unique,
so any exact engine must return exactly these.

It also counts the wedges the peel traverses, which
``benchmarks/chip/roofline.py`` turns into the work of a decomposition.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import scipy.sparse as sp

from .graphs import Edges

# The engine keeps supports in float32; integers are exact below 2**24
# (the exactness bound the configurations state).
F32_EXACT = 1 << 24


@dataclasses.dataclass
class Peel:
    theta: np.ndarray          # int64 tip number per U vertex
    max_support: int           # largest initial butterfly support
    wedges_count: int          # wedges of the static graph (counting)
    wedges_peel: int           # residual wedges traversed while peeling


def transposed(graph: Edges) -> Edges:
    n_u, n_v, eu, ev = graph
    key = np.unique(ev.astype(np.int64) * n_u + eu.astype(np.int64))
    return (n_v, n_u, (key // n_u).astype(np.int32),
            (key % n_u).astype(np.int32))


def shared_butterflies(graph: Edges) -> np.ndarray:
    """B2[i, j] = C(W[i, j], 2) with W = A A^T and a zero diagonal."""
    n_u, n_v, eu, ev = graph
    a = sp.csr_matrix((np.ones(eu.size, np.int64), (eu, ev)),
                      shape=(n_u, n_v))
    b2 = (a @ a.T).toarray()
    b2 *= b2 - 1
    b2 //= 2
    np.fill_diagonal(b2, 0)
    return b2


def bup_peel(graph: Edges, support_dtype=None) -> Peel:
    """Sequential bottom-up peeling of the U side.

    ``support_dtype`` (the control only) rounds every support to that
    type after each update, as a program keeping its supports in it
    would; None keeps them exact."""
    n_u, n_v, eu, ev = graph
    b2 = shared_butterflies(graph)
    support = b2.sum(axis=1)
    if support_dtype is not None:
        support = support.astype(support_dtype).astype(np.float64)
    max_support = int(support.max()) if support.size else 0
    theta = np.zeros(n_u, support.dtype)
    alive = np.ones(n_u, bool)
    order = np.argsort(eu, kind="stable")
    indptr = np.zeros(n_u + 1, np.int64)
    np.add.at(indptr, eu.astype(np.int64) + 1, 1)
    np.cumsum(indptr, out=indptr)
    nbrs_all = ev[order]
    dv = np.bincount(ev, minlength=n_v).astype(np.int64)
    wedges_count = int((dv * (dv - 1)).sum())
    wedges_peel = 0
    for _ in range(n_u):
        cand = np.where(alive)[0]
        u = cand[np.argmin(support[cand])]
        th = support[u]
        theta[u] = th
        alive[u] = False
        nbrs = nbrs_all[indptr[u]:indptr[u + 1]]
        wedges_peel += int((dv[nbrs] - 1).sum())
        dv[nbrs] -= 1
        upd = (b2[u] > 0) & alive
        support[upd] = np.maximum(th, support[upd] - b2[u][upd])
        if support_dtype is not None:
            support[upd] = support[upd].astype(support_dtype)
    if support_dtype is not None:
        theta = theta.astype(np.int64)
    return Peel(theta=theta, max_support=max_support,
                wedges_count=wedges_count, wedges_peel=wedges_peel)


def peel_side(graph: Edges, side: str, support_dtype=None) -> Peel:
    """Peel the ``side`` ("U" or "V") vertex set."""
    return bup_peel(graph if side == "U" else transposed(graph),
                    support_dtype)
