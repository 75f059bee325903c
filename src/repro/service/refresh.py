"""Incremental-refresh orchestration (DESIGN.md §11).

``refresh_dataset`` is the service's worker for one stale dataset: it
recovers the net insert/delete sets from the base/current graph diff,
maintains the peeled-axis butterfly supports through the delta kernels,
builds the stop ladder from the stored CD bounds, and hands
``Executor.repeel`` the bounded prefix peel — falling back to a full
``Executor.decompose`` when the delta path cannot win (no prior result,
dirty fraction over the threshold, tiled-routed plan, empty endpoint
graphs) or when it fails (any ``ReceiptError``).  The fallback IS the
degradation story: a refresh never errors out of the service, it just
recomputes.

Support maintenance per axis:

* **tip** — pure delta: ``vertex_support_edge_delta`` on the union
  matrix with the insert rows gives per-vertex gains, with the delete
  rows gives losses; ``B_new = B_base + gains - losses``, sequentially
  exact.  ``B_base`` is primed lazily (host recount of the base graph
  on the first delta refresh) and then carried incrementally.
* **wing** — the union supports come from ONE closed-form
  ``edge_support_all`` recount (the edge axis's always-available HUC
  arm): ``edge_support_delta`` self-zeroes a removed slot's own cell,
  so the delta kernel cannot report an inserted edge's own support.
  Deletions then ride the delta kernel — ``B_new = B_union - d_del`` at
  the kept slots, where the accumulated delta is exact.
"""
from __future__ import annotations

from typing import List, Optional

import jax.numpy as jnp
import numpy as np

from ..api.errors import ReceiptError
from ..api.executor import TipDecomposition, WingDecomposition
from ..core.graph import BipartiteGraph
from ..core.peeling import butterfly_supports
from ..kernels import ops as kops
from .state import DatasetState, ServiceConfig, edge_keys

__all__ = ["refresh_dataset", "classify_refresh"]


def _ladder(bounds: Optional[List[float]], floor: float) -> List[float]:
    """Ascending stop candidates strictly above ``floor`` (integer
    levels, so "+0.5" separates), ending in ``inf`` — the rung every
    ladder can always escalate to (a whole-graph level peel from the
    maintained supports: exact, still skips counting + CD)."""
    rungs = sorted({float(b) for b in (bounds or [])
                    if float(b) > floor + 0.5})
    rungs.append(float("inf"))
    return rungs


def _mark_subsets(stats, bounds: Optional[List[float]]) -> None:
    """Refresh evidence: a stored CD subset ``s`` (theta range
    ``[bounds[s], bounds[s+1])``) is re-peeled iff its range starts
    below the stop; everything above is CLEAN and kept verbatim."""
    if bounds and len(bounds) >= 2:
        total = len(bounds) - 1
        repeeled = sum(1 for s in range(total)
                       if bounds[s] < stats.refresh_stop)
    else:
        total, repeeled = 1, 1
    stats.refresh_subsets_total = total
    stats.refresh_subsets_repeeled = repeeled


def _full(ds: DatasetState, executor, *, fallback: bool):
    dec = executor.decompose(ds.graph)
    stats = dec.stats
    if fallback:
        stats.refresh_mode = "full"
    ds.full_recomputes += 1
    bounds = list(stats.bounds) if getattr(stats, "bounds", None) else None
    ds.commit(dec, bounds=bounds, supports=None)
    return stats


def _tip_delta(ds: DatasetState, executor, kI: np.ndarray, kD: np.ndarray):
    base, cur = ds.base_graph, ds.graph
    n_v = base.n_v
    iu, iv = kI // n_v, kI % n_v
    du, dv = kD // n_v, kD % n_v
    if executor.side == "V":
        gb = base.transposed()
        iu, iv, du, dv = iv, iu, dv, du
    else:
        gb = base
    # union matrix = base + inserts, peeled orientation
    a_u = np.zeros((gb.n_u, gb.n_v), np.float32)
    a_u[gb.edges_u, gb.edges_v] = 1.0
    a_u[iu, iv] = 1.0
    if ds.supports is None:
        # primes the maintained vector: exact on the host, independent
        # of the device kernels
        ds.supports = butterfly_supports(gb).astype(np.float64)
    a_dev = jnp.asarray(a_u)
    gains = losses = 0.0
    if kI.size:
        gains = np.asarray(kops.vertex_support_edge_delta(
            a_dev, jnp.asarray(iu, jnp.int32), jnp.asarray(iv, jnp.int32),
            jnp.ones(kI.size, bool)), np.float64)
    if kD.size:
        losses = np.asarray(kops.vertex_support_edge_delta(
            a_dev, jnp.asarray(du, jnp.int32), jnp.asarray(dv, jnp.int32),
            jnp.ones(kD.size, bool)), np.float64)
    sup_new = np.asarray(ds.supports, np.float64) + gains - losses

    numbers_old = np.asarray(ds.result.numbers, np.int64)
    # deletion ceiling is certified by stored numbers; the insert
    # endpoints' stored numbers only SEED the ladder higher (fewer
    # escalations when their level won't have dropped) — correctness
    # comes from the watch set, not the seed
    t_known = float(numbers_old[du].max()) if kD.size else 0.0
    seed = max(t_known,
               float(numbers_old[iu].max()) if kI.size else 0.0)
    stops = _ladder(ds.bounds, seed)
    watch = np.unique(iu)
    numbers_new, stats = executor.repeel(
        cur, sup0=sup_new, numbers_old=numbers_old, stops=stops,
        watch=watch)
    stats.refresh_dirty_edges = int(kI.size + kD.size)
    ceil = t_known
    if watch.size:
        ceil = max(ceil, float(numbers_new[watch].max()))
    stats.refresh_t_hi = ceil
    _mark_subsets(stats, ds.bounds)
    dec = TipDecomposition(graph=cur, side=executor.side,
                           theta=numbers_new, stats=stats, plan=None)
    ds.refreshes += 1
    ds.commit(dec, bounds=ds.bounds, supports=sup_new)
    return stats


def _wing_delta(ds: DatasetState, executor, kI: np.ndarray, kD: np.ndarray):
    base, cur = ds.base_graph, ds.graph
    n_v = base.n_v
    k_base = edge_keys(base)
    k_cur = edge_keys(cur)
    ku = np.sort(np.concatenate([k_base, kI]))
    eu_u = (ku // n_v).astype(np.int32)
    ev_u = (ku % n_v).astype(np.int32)
    a_u = np.zeros((base.n_u, n_v), np.float32)
    a_u[eu_u, ev_u] = 1.0
    a_dev = jnp.asarray(a_u)
    eu_dev, ev_dev = jnp.asarray(eu_u), jnp.asarray(ev_u)
    b_union = np.asarray(kops.edge_support_all(a_dev, eu_dev, ev_dev),
                         np.float64)
    if kD.size:
        del_slots = np.searchsorted(ku, kD).astype(np.int32)
        d_del = np.asarray(kops.edge_support_delta(
            a_dev, eu_dev, ev_dev, jnp.asarray(del_slots),
            jnp.ones(kD.size, bool)), np.float64)
    else:
        d_del = 0.0
    kept = np.isin(ku, k_cur)          # ku and k_cur both sorted: aligned
    sup_new = (b_union - d_del)[kept]

    psi_base = np.asarray(ds.result.numbers, np.int64)
    psi_old = np.zeros(cur.m, np.int64)            # inserts: placeholder —
    in_base = np.isin(k_cur, k_base)               # always peeled via watch
    psi_old[in_base] = psi_base[np.searchsorted(k_base, k_cur[in_base])]
    t_known = (float(psi_base[np.searchsorted(k_base, kD)].max())
               if kD.size else 0.0)
    stops = _ladder(ds.bounds, t_known)
    watch = np.nonzero(np.isin(k_cur, kI))[0]
    numbers_new, stats = executor.repeel(
        cur, sup0=sup_new, numbers_old=psi_old, stops=stops, watch=watch)
    stats.refresh_dirty_edges = int(kI.size + kD.size)
    ceil = t_known
    if watch.size:
        ceil = max(ceil, float(numbers_new[watch].max()))
    stats.refresh_t_hi = ceil
    _mark_subsets(stats, ds.bounds)
    dec = WingDecomposition(graph=cur, side=executor.side,
                            edge_wing=numbers_new, stats=stats, plan=None)
    ds.refreshes += 1
    ds.commit(dec, bounds=ds.bounds, supports=None)
    return stats


def classify_refresh(ds: DatasetState, scfg: ServiceConfig, *,
                     force_full: bool = False) -> str:
    """Route one stale dataset WITHOUT doing device work: ``"noop"``
    (already fresh, or a net no-op mutation sequence), ``"full"``
    (from-scratch decompose — forced, no prior result, or past the
    dirty threshold) or ``"delta"`` (the incremental path).

    The scheduler uses this to batch: every ``"full"``-routed tip
    dataset in a drain cycle — forced fulls AND refreshes that would
    fall back anyway — joins one ``Executor.map`` fleet, and the
    ``"delta"`` routes pack into LPT-ordered repeel fleets.
    """
    if ds.fresh and not force_full:
        return "noop"
    if force_full or ds.result is None or ds.base_graph is None:
        return "full"
    k_base = edge_keys(ds.base_graph)
    k_cur = edge_keys(ds.graph)
    kI = np.setdiff1d(k_cur, k_base)
    kD = np.setdiff1d(k_base, k_cur)
    if not kI.size and not kD.size:
        return "noop"
    dirty = (kI.size + kD.size) / max(ds.base_graph.m, 1)
    if (dirty > scfg.refresh_dirty_threshold
            or ds.base_graph.m == 0 or ds.graph.m == 0):
        return "full"
    return "delta"


def refresh_dataset(ds: DatasetState, executor,
                    scfg: ServiceConfig, *, force_full: bool = False):
    """Bring ``ds.result`` up to ``ds.version``; returns the run's
    ``RunStats`` (or None when the dataset was already fresh).

    Routing (``classify_refresh``): delta refresh when a prior result +
    base graph exist, the net dirty fraction is within
    ``scfg.refresh_dirty_threshold`` and both endpoint graphs are
    non-degenerate; full recompute otherwise (and on ANY
    ``ReceiptError`` from the delta path — e.g. a plan that routed to
    the tiled representation, which the dense refresh loops reject as
    ``PlanInfeasibleError``).
    """
    route = classify_refresh(ds, scfg, force_full=force_full)
    if route == "noop":
        if not ds.fresh and ds.result is not None:
            # net no-op mutation sequence: the stored result IS current
            ds.result_version = ds.version
            ds.base_graph = ds.graph
        return None
    if route == "full":
        # fallback=True marks the runs the DELTA path declined (dirty
        # fraction, degenerate endpoints) — a forced full or a first
        # decompose is not a fallback
        fallback = not (force_full or ds.result is None
                        or ds.base_graph is None)
        return _full(ds, executor, fallback=fallback)
    kI = np.setdiff1d(edge_keys(ds.graph), edge_keys(ds.base_graph))
    kD = np.setdiff1d(edge_keys(ds.base_graph), edge_keys(ds.graph))
    try:
        if ds.workload == "wing":
            return _wing_delta(ds, executor, kI, kD)
        return _tip_delta(ds, executor, kI, kD)
    except ReceiptError as exc:
        ds.last_error = exc
        return _full(ds, executor, fallback=True)
