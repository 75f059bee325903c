"""Subset-scoped prefix re-peel: the serving layer's incremental-refresh
engine entry points (DESIGN.md §11).

After an edge-mutation batch, a decomposition does NOT have to be redone
from scratch.  Order the batch deletions-first (only the endpoint states
matter) and apply the witness-containment argument per phase: every
butterfly a mutation destroys or creates contains the mutated edge's
peeled-axis element (the edge's U endpoint on the vertex axis, the edge
itself on the edge axis), so any witness subgraph certifying a CHANGED
tip/wing number contains that element.  Hence

* **deletions** only change numbers at levels <= the mutated element's
  STORED number (deletion is monotone-decreasing, and the destroyed
  witness pins the old level to the element's old number) — a ceiling
  known before any device work;
* **insertions** only change numbers at levels <= the mutated element's
  NEW number — not known up front, but certified DURING the re-peel:
  if the element itself peels below the stop level, its exact new
  number is in hand and the ceiling is proven; if it survives, its new
  number is >= the stop, so the stop escalates to the next stored CD
  bound and the SAME device state keeps peeling (no work repeated).

Consequences, given the previous run's CD bounds (Alg. 3's theta-range
partition, ``RunStats.bounds``):

* every subset whose lower bound exceeds the certified ceiling is
  CLEAN — its members keep their stored numbers bit-for-bit;
* an exact refresh is one LEVEL PEEL from the delta-maintained supports
  (``kernels.ops.vertex_support_edge_delta`` / ``edge_support_delta``),
  stopped at the first bound that clears the ceiling: peeled elements
  get their exact new number (the ParButterfly min-peel argument, same
  as ``Executor.map``'s whole-graph schedule with ``lo = 0``),
  survivors keep the stored one.

The loops below are the bounded variant of ``batched_level_loop``:
single-graph, a ``hi_stop`` cut in the loop condition, and on the
vertex axis the same two update forms — the peel level gathered into a
fixed ``peel_width`` buffer, or the mask form when the level overflows
it (a mask-form sweep contracts every row against every row, so a
refresh of thousands of small levels must not pay it per level) — the sweep pieces (``level_threshold`` / ``select_peel`` /
``apply_delta`` / ``record_theta`` / ``peel_cost``) are the shared ones,
not copies.  ``hi_stop`` rides the carry as a traced scalar so neither
different mutation batches nor stop escalations retrace.

Degree-sort relabeling is deliberately SKIPPED here: the maintained
support vector and the stored numbers live in canonical vertex order,
the refresh sweeps are mask-form (no staircase to concentrate), and a
per-refresh relabel would cost a host permutation per mutation batch.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...kernels import ops as kops
from ...kernels.butterfly_sparse import (batched_row_extents,
                                         gathered_tile_extents)
from ..graph import BipartiteGraph
from .peel_loop import (
    _INF,
    ReceiptConfig,
    RunStats,
    apply_delta,
    bucket,
    level_threshold,
    peel_cost,
    record_theta,
    select_peel,
)
from .wing import build_edge_state

__all__ = ["repeel_tip_prefix", "repeel_wing_prefix", "synthesize_bounds"]

# f32-finite stand-in for an unbounded stop (supports are integers far
# below this; padded-row supports are +inf and stay unpeelable)
_STOP_MAX = float(np.float32(3.0e38))


def synthesize_bounds(numbers, num_partitions: int):
    """Coarse ascending CD-style bound ladder from COMPUTED peel numbers.

    ``Executor.map`` runs the whole-graph level schedule (``lo = 0``) and
    never builds Alg. 3's theta-range partition, so mapped results used
    to carry no bounds and their first refresh had to peel one ``[inf]``
    rung.  The exact numbers in hand are strictly better information
    than CD's bounds ever were: quantize them into ``num_partitions``
    equi-mass rungs and the result is a valid stop ladder — each rung
    ``b`` certifies the same clean-prefix property as a CD bound (every
    element with ``numbers >= b`` keeps its stored value when the
    certified refresh ceiling lands below ``b``).

    Invariants honored (the ones ``verify_*_decomposition`` checks and
    ``_drain`` escalation relies on): strictly increasing, integral
    rungs, ``bounds[0] == 0`` and ``bounds[-1] > numbers.max()``.
    """
    th = np.asarray(numbers, np.float64).reshape(-1)
    t_max = float(th.max()) if th.size else 0.0
    interior = np.empty(0, np.float64)
    if th.size and int(num_partitions) > 1:
        qs = np.linspace(0.0, 1.0, int(num_partitions) + 1)[1:-1]
        interior = np.round(np.quantile(th, qs))
    rungs = np.unique(np.concatenate(
        [[0.0], interior, [t_max + 1.0]]))
    return [float(b) for b in rungs]


@functools.partial(jax.jit, static_argnames=("backend", "blocks",
                                             "peel_width", "max_sweeps"))
def _tip_prefix_loop(a, ids, row_ext, kmax, support, alive, dv, theta, rho,
                     wedges, hi_stop, *, backend, blocks, peel_width,
                     max_sweeps):
    """Level-peel every row whose tip number lands below ``hi_stop``.

    One ``lax.while_loop``; each sweep peels the whole current-minimum
    support level (necessarily < ``hi_stop`` while the loop runs) and
    applies the butterfly-update delta with the Alg. 2 monotonicity
    clamp.  Exits when every survivor's support >= ``hi_stop`` (their
    numbers are >= the stop and stay stored) or on the ``max_sweeps``
    valve; the host re-enters on either (cap re-entry / stop
    escalation) by feeding the state straight back.  A level of at most
    ``peel_width`` rows is gathered and contracted against the whole
    matrix; a larger one takes the mask form (``B = A``).
    """
    f32 = jnp.float32
    sparse = backend in kops.SPARSE_BACKENDS

    def mask_update(peel, n_peel):
        delta = kops.butterfly_update(
            a, a, peel.astype(a.dtype), ids, ids,
            backend=backend, blocks=blocks, kmax_a=kmax, kmax_b=kmax)
        return delta, peel.astype(f32) @ a.astype(f32)

    def gathered_update(peel, n_peel):
        rows = jnp.nonzero(peel, size=peel_width, fill_value=0)[0]
        rows = rows.astype(jnp.int32)
        valid = jnp.arange(peel_width) < n_peel
        a_peel = a[rows] * valid[:, None].astype(a.dtype)
        kb = (gathered_tile_extents(row_ext, rows, valid, blocks[1])
              if sparse else None)
        delta = kops.butterfly_update(
            a, a_peel, valid.astype(a.dtype), ids, rows,
            backend=backend, blocks=blocks, kmax_a=kmax, kmax_b=kb)
        return delta, valid.astype(f32) @ a_peel.astype(f32)

    def cond_fn(st):
        support, alive = st[0], st[1]
        sweeps = st[6]
        return (jnp.any(alive & (support < hi_stop))
                & (sweeps < max_sweeps))

    def body_fn(st):
        support, alive, dv, theta, rho, wedges, sweeps = st
        hi, cap = level_threshold(support, alive, 0.0)
        peel = select_peel(support, alive, hi)
        n_peel = jnp.sum(peel)
        delta, colsum = jax.lax.cond(n_peel > peel_width, mask_update,
                                     gathered_update, peel, n_peel)
        wedges = wedges + peel_cost(colsum, dv)
        support2, alive2 = apply_delta(support, alive, peel, delta, cap)
        theta2 = record_theta(theta, peel, cap)
        return (support2, alive2, dv - colsum, theta2,
                rho + jnp.int32(1), wedges, sweeps + jnp.int32(1))

    state0 = (support, alive, dv, theta, rho, wedges, jnp.int32(0))
    return jax.lax.while_loop(cond_fn, body_fn, state0)


@functools.partial(jax.jit, static_argnames=("backend", "blocks",
                                             "max_sweeps"))
def _wing_prefix_loop(a, eu, ev, support, alive, dv, theta, rho, wedges,
                      hi_stop, *, backend, blocks, max_sweeps):
    """Edge-axis twin of ``_tip_prefix_loop``: peel level, scatter the
    peeled slots out of the carried biadjacency, recount every survivor
    closed-form (batched-exact — no double-delete bookkeeping), clamp
    at the sweep cap, stop at ``hi_stop``."""
    f32 = jnp.float32

    def cond_fn(st):
        support, alive = st[1], st[2]
        sweeps = st[7]
        return (jnp.any(alive & (support < hi_stop))
                & (sweeps < max_sweeps))

    def body_fn(st):
        a_cur, support, alive, dv, theta, rho, wedges, sweeps = st
        hi, cap = level_threshold(support, alive, 0.0)
        peel = select_peel(support, alive, hi)
        n_peel = jnp.sum(peel)
        peel_mat = jnp.zeros_like(a_cur).at[eu, ev].add(
            peel.astype(a_cur.dtype))
        a2 = a_cur * (1.0 - jnp.minimum(peel_mat, 1.0))
        colsum = jnp.zeros_like(dv).at[ev].add(peel.astype(f32))
        theta2 = record_theta(theta, peel, cap)
        alive2 = alive & ~peel
        s2 = kops.edge_support_all(a2, eu, ev, backend=backend,
                                   blocks=blocks)
        support2 = jnp.where(alive2, jnp.maximum(s2, cap), _INF)
        return (a2, support2, alive2, dv - colsum, theta2,
                rho + jnp.int32(1), wedges + n_peel.astype(f32),
                sweeps + jnp.int32(1))

    state0 = (a, support, alive, dv, theta, rho, wedges, jnp.int32(0))
    return jax.lax.while_loop(cond_fn, body_fn, state0)


def _drain(run_one, stops: Sequence[float], watch: np.ndarray,
           alive0: np.ndarray, stats: RunStats):
    """Shared escalation driver: drain the prefix loop at each candidate
    stop until every watched element is peeled (or the ladder is
    exhausted), carrying the device state across stops and cap exits.

    ``run_one(stop)`` runs one device-loop invocation at ``stop`` from
    the CURRENT carried state and returns the fetched
    ``(alive, theta, rho, support)`` host views.  Returns
    ``(alive_h, th_acc, stop_used)``.
    """
    watch = np.asarray(watch, np.int64).reshape(-1)
    th_acc = np.zeros(alive0.shape, np.float64)
    prev_alive = alive0
    alive_h = alive0
    si = 0
    while True:
        stop = float(stops[si])
        alive_h, th_h, rho_h, sup_h = run_one(min(stop, _STOP_MAX))
        stats.device_loop_calls += 1
        stats.host_round_trips += 1
        newly_dead = prev_alive & ~alive_h
        th_acc = np.where(newly_dead, th_h, th_acc)
        prev_alive = alive_h
        if (alive_h & (sup_h < stop)).any() and rho_h > 0:
            continue                     # max_sweeps cap exit: re-enter
        if si + 1 < len(stops) and alive_h[watch].any():
            si += 1                      # a watched element survived: its
            continue                     # new number is >= stop — escalate
        stats.refresh_stop = stop
        return alive_h, th_acc, stop


def repeel_tip_prefix(
    g: BipartiteGraph, sup0: np.ndarray, theta_old: np.ndarray,
    stops: Sequence[float], watch: np.ndarray,
    cfg: Optional[ReceiptConfig] = None,
    stats: Optional[RunStats] = None, *, plan=None,
) -> Tuple[np.ndarray, float]:
    """Exact tip refresh of ``g`` (the POST-mutation graph, peeled side
    already on U): level-peel from the maintained supports ``sup0``,
    stop at the first level of the ascending ladder ``stops`` that
    clears the mutation ceiling, keep ``theta_old`` for survivors.

    ``sup0`` must be the exact whole-graph butterfly supports of ``g``
    (delta-maintained or recounted) and ``theta_old`` the pre-mutation
    tip numbers — both in canonical vertex order.  ``stops[0]`` must
    already exceed the DELETION ceiling (max stored theta of deleted
    edges' U endpoints); ``watch`` holds the INSERTED edges' U
    endpoints, whose new numbers certify the insertion ceiling (module
    docstring) — while any of them survives, the stop escalates to the
    next rung (``inf`` as the last rung degenerates to a full
    whole-graph level peel: still exact, still skips counting + CD).

    Returns ``(theta_new int64[n_u], stop_used)`` — bit-identical to a
    from-scratch decomposition of ``g``.
    """
    cfg = cfg or ReceiptConfig()
    stats = stats or RunStats()
    backend = kops.resolve_backend(cfg.backend)
    blocks = cfg.kernel_blocks
    bi, bj, bk = blocks
    n_u = g.n_u

    # wedge-incapable V columns carry no butterflies; compact them away
    # exactly like the map-path ingest
    sub, _ = g.induced_on_u(np.arange(n_u), min_degree_v=2)
    row_align = 8 if backend == "xla" else max(bi, bj)
    col_align = 8 if backend == "xla" else bk
    rows_pad = bucket(max(n_u, 1), row_align)
    cols_pad = bucket(max(sub.n_v, 1), col_align)
    if plan is not None:
        rows_pad = plan.quantize_dim("refresh_rows", rows_pad)
        cols_pad = plan.quantize_dim("refresh_cols", cols_pad)

    a = np.zeros((rows_pad, cols_pad), np.float32)
    a[sub.edges_u, sub.edges_v] = 1.0
    alive0 = np.arange(rows_pad) < n_u
    sup_pad = np.full(rows_pad, np.inf, np.float64)
    sup_pad[:n_u] = np.asarray(sup0, np.float64)[:n_u]
    a_dev = jnp.asarray(a)
    ids = jnp.arange(rows_pad, dtype=jnp.int32)
    if backend in kops.SPARSE_BACKENDS:
        rext = batched_row_extents(a[None], bk)[0]
        kmax = jnp.asarray(
            rext.reshape(-1, bi).max(axis=1).astype(np.int32))
        row_ext = jnp.asarray(rext)
    else:
        kmax = row_ext = None
    # levels are small once the lowest ones are gone: one row tile of
    # buffer (or the configured width) keeps a sweep O(width * rows)
    peel_width = min(bucket(cfg.peel_width or bj, bj), rows_pad)
    carry = dict(
        support=jnp.where(jnp.asarray(alive0),
                          jnp.asarray(sup_pad, jnp.float32), _INF),
        alive=jnp.asarray(alive0),
        dv=jnp.asarray(a.sum(axis=0)),
        theta=jnp.zeros(rows_pad, jnp.float32),
        rho=jnp.int32(0), wedges=jnp.float32(0.0),
    )

    def run_one(stop):
        out = _tip_prefix_loop(
            a_dev, ids, row_ext, kmax, carry["support"], carry["alive"],
            carry["dv"], carry["theta"], carry["rho"], carry["wedges"],
            jnp.float32(stop), backend=backend, blocks=blocks,
            peel_width=peel_width, max_sweeps=cfg.max_sweeps)
        (carry["support"], carry["alive"], carry["dv"], carry["theta"],
         carry["rho"], carry["wedges"], _sw) = out
        alive_h, th_h, rho_h, sup_h = jax.device_get(
            (carry["alive"], carry["theta"], carry["rho"],
             carry["support"]))
        return (np.asarray(alive_h), np.asarray(th_h, np.float64),
                int(rho_h), np.asarray(sup_h, np.float64))

    alive_h, th_acc, stop_used = _drain(run_one, stops, watch, alive0,
                                        stats)
    stats.rho_fd += int(jax.device_get(carry["rho"]))
    stats.wedges_fd += int(jax.device_get(carry["wedges"]))
    theta_new = np.where(alive_h[:n_u],
                         np.asarray(theta_old, np.int64)[:n_u],
                         np.round(th_acc[:n_u]).astype(np.int64))
    return theta_new.astype(np.int64), stop_used


def repeel_wing_prefix(
    g: BipartiteGraph, sup0: np.ndarray, psi_old: np.ndarray,
    stops: Sequence[float], watch: np.ndarray,
    cfg: Optional[ReceiptConfig] = None,
    stats: Optional[RunStats] = None, *, plan=None,
) -> Tuple[np.ndarray, float]:
    """Edge-axis twin of ``repeel_tip_prefix``: exact wing refresh of
    ``g`` from maintained per-edge supports ``sup0`` (canonical edge
    order of ``g``), escalating through ``stops`` until every watched
    slot (the INSERTED edges) is peeled, with ``psi_old`` kept for
    survivors.  ``stops[0]`` must exceed the deletion ceiling (max
    stored psi of the deleted edges).  Inserted edges carry any
    placeholder in ``psi_old`` — the escalation guarantees they are
    peeled, never served from the placeholder.

    Returns ``(psi_new int64[m], stop_used)`` — bit-identical to
    from-scratch.
    """
    cfg = cfg or ReceiptConfig()
    stats = stats or RunStats()
    backend = kops.resolve_backend(cfg.backend)
    blocks = cfg.kernel_blocks
    state = build_edge_state(g, cfg, plan=plan)
    m, m_pad = state["m"], state["m_pad"]

    sup_pad = np.full(m_pad, np.inf, np.float64)
    sup_pad[:m] = np.asarray(sup0, np.float64)[:m]
    alive0 = np.asarray(state["alive0"])
    eu, ev = state["eu"], state["ev"]
    carry = dict(
        a=state["a"],
        support=jnp.where(jnp.asarray(alive0),
                          jnp.asarray(sup_pad, jnp.float32), _INF),
        alive=jnp.asarray(alive0),
        dv=state["dv0"],
        theta=jnp.zeros(m_pad, jnp.float32),
        rho=jnp.int32(0), wedges=jnp.float32(0.0),
    )

    def run_one(stop):
        out = _wing_prefix_loop(
            carry["a"], eu, ev, carry["support"], carry["alive"],
            carry["dv"], carry["theta"], carry["rho"], carry["wedges"],
            jnp.float32(stop),
            backend=backend, blocks=blocks, max_sweeps=cfg.max_sweeps)
        (carry["a"], carry["support"], carry["alive"], carry["dv"],
         carry["theta"], carry["rho"], carry["wedges"], _sw) = out
        alive_h, th_h, rho_h, sup_h = jax.device_get(
            (carry["alive"], carry["theta"], carry["rho"],
             carry["support"]))
        return (np.asarray(alive_h), np.asarray(th_h, np.float64),
                int(rho_h), np.asarray(sup_h, np.float64))

    alive_h, th_acc, stop_used = _drain(run_one, stops, watch, alive0,
                                        stats)
    stats.rho_fd += int(jax.device_get(carry["rho"]))
    stats.wedges_fd += int(jax.device_get(carry["wedges"]))
    psi_new = np.where(alive_h[:m],
                       np.asarray(psi_old, np.int64)[:m],
                       np.round(th_acc[:m]).astype(np.int64))
    return psi_new.astype(np.int64), stop_used
