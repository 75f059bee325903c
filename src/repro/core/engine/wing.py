"""Wing (bitruss) decomposition on the shared peel engine (DESIGN.md §10).

ROADMAP item 2 executed: edge peeling rides the SAME machinery as vertex
tip decomposition.  The support vector is reinterpreted as per-EDGE-SLOT
butterfly supports, the geometry dict ``{"a", "eu", "ev"}`` (the carried
residual biadjacency plus the static edge endpoints) replaces the
loop-invariant matrix, and everything else — CD range-peel
(``device_peel_loop(axis="edge")`` per subset or the single-dispatch
``device_wing_graph_loop``), batched level-FD
(``batched_level_loop(axis="edge")``), plan shape quantization and the
executable cache — is the tip path's code, not a copy of it.

Phase structure mirrors ``tip_decompose`` exactly:

* **CD** partitions the EDGE set into subsets with non-overlapping
  wing-number ranges by range-peeling at adaptive bounds.  Range
  determination uses the equal-edge-count findHi (unit mass per edge —
  the Lakhotia et al. follow-up's partitioning objective for edge
  peeling): host-side on the per-subset support snapshot
  (``cd_dispatch="subset"``) or on device through the same
  ``kernels.ops.find_hi_device`` reduction with ``w = 1``
  (``cd_dispatch="graph"``, the whole CD phase in ONE dispatch with O(1)
  blocking round trips per graph).
* **FD** peels each subset independently and BATCHED: one (S, R, C)
  residual stack — subset s's matrix holds every edge of subsets >= s,
  because a peeled edge's support delta can involve higher-subset edges
  (the edge-axis form of Theorem 1's range containment) — with only
  subset-s slots alive, supports recounted in-stack and floored at
  ``bounds[s]``, then ONE ``batched_level_loop(axis="edge")`` dispatch
  drains all subsets level-synchronously.  Every sweep is batched-exact
  (closed-form recount of all survivors), so the double-delete conflict
  of simultaneous edge peeling never arises.

Exactness: wing numbers are canonical — any exact peel schedule produces
THE psi vector — so every (dispatch, backend, side) combination here is
differentially pinned bit-identical to the sequential host oracle
``core/wing.wing_bup_oracle`` (tests/test_wing.py).

Degree-sort relabeling is a vertex-axis tile-density optimization and is
deliberately SKIPPED on this axis: edge slots must stay aligned with the
construction-order ``g.edges_u``/``g.edges_v`` so psi maps back without a
permutation, and the edge kernels are plain matmul contractions with no
staircase to concentrate.  ``side="V"`` transposes the graph (butterflies
are side-symmetric, so psi is transpose-invariant) and maps the result
back through the canonical edge-order permutation.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...api.errors import KernelBackendError
from ...api.faults import fault_point
from ...kernels import ops as kops
from ...utils.spans import fetch, span
from ..graph import BipartiteGraph
from .peel_loop import (
    _INF,
    ReceiptConfig,
    RunStats,
    _sweep_once,
    batched_level_loop,
    bucket,
    device_peel_loop,
    select_peel,
)

__all__ = [
    "wing_decompose_engine",
    "receipt_wing_cd",
    "receipt_wing_fd",
    "device_wing_graph_loop",
    "wing_graph_state0",
    "build_edge_state",
]


def build_edge_state(g: BipartiteGraph, cfg: ReceiptConfig, *, plan=None):
    """Bucket-padded edge-axis geometry + initial peel state (the edge
    analogue of ``DeviceGraph``).

    Edge slot j < m corresponds to ``(g.edges_u[j], g.edges_v[j])`` —
    construction (canonical) order, never permuted, so psi comes back
    aligned.  Padding slots alias cell (0, 0) with ``alive=False``:
    every scatter they touch adds zero (the peel mask is False there)
    and every gather they make is masked off by ``a[eu, ev]`` inside
    ``kernels.ops.edge_support_all``.

    ``c_rcnt`` is the HUC break-even estimate in PEELED-EDGE units: the
    closed-form recount costs ~C_pad matvec-equivalents (the AᵀA
    contraction), each incrementally peeled edge ~3, so recount wins
    once a sweep peels more than ~C_pad/3 edges.  A bad estimate only
    shifts which exact branch runs (exactness never depends on it).

    ``plan`` quantizes the three padded dims through the shape-floor
    ladder so same-signature graphs land on already-traced dispatch
    shapes (the executable-cache contract, DESIGN.md §6).
    """
    bi, bj, bk = cfg.kernel_blocks
    rows_pad = bucket(max(g.n_u, 1), max(bi, bj))
    cols_pad = bucket(max(g.n_v, 1), bk)
    m_pad = bucket(max(g.m, 1), bj)
    if plan is not None:
        rows_pad = plan.quantize_dim("wing_rows", rows_pad)
        cols_pad = plan.quantize_dim("wing_cols", cols_pad)
        m_pad = plan.quantize_dim("wing_edges", m_pad)

    a = np.zeros((rows_pad, cols_pad), np.float32)
    a[g.edges_u, g.edges_v] = 1.0
    eu = np.zeros(m_pad, np.int32)
    ev = np.zeros(m_pad, np.int32)
    eu[: g.m] = g.edges_u
    ev[: g.m] = g.edges_v
    alive = np.zeros(m_pad, bool)
    alive[: g.m] = True

    if cfg.peel_width is not None:
        peel_width = min(bucket(cfg.peel_width, bj), m_pad)
    else:
        peel_width = min(bucket(max(bj, m_pad // 8), bj), m_pad)

    return dict(
        m=g.m, m_pad=m_pad, rows_pad=rows_pad, cols_pad=cols_pad,
        a=jnp.asarray(a, cfg.dtype),
        eu=jnp.asarray(eu), ev=jnp.asarray(ev),
        eu_np=np.asarray(g.edges_u), ev_np=np.asarray(g.edges_v),
        alive0=alive,
        dv0=jnp.asarray(a.sum(axis=0)),
        c_rcnt=max(float(cols_pad) / 3.0, 1.0),
        peel_width=peel_width,
    )


# ---------------------------------------------------------------------- #
# wing CD, subset dispatch (one device loop per subset, host findHi)
# ---------------------------------------------------------------------- #
def receipt_wing_cd(
    g: BipartiteGraph, cfg: ReceiptConfig, stats: RunStats, *, plan=None,
) -> Tuple[np.ndarray, np.ndarray, dict]:
    """Partition the edge set into subsets with non-overlapping
    wing-number ranges (the paper's Alg. 3 re-aimed at edges).

    Equal-edge-count range determination on the host support snapshot
    (one snapshot per subset — the same sync the tip path pays, O(P)
    round trips per graph): the next bound is the support value at the
    ``remaining/(P-i)``-th smallest alive support, so subsets carry
    near-equal edge counts.  Each subset's range is drained by the
    shared ``device_peel_loop(axis="edge")``; the edge axis has no
    overflow exit (oversized sweeps recount in-body), so the only
    re-entry is the ``max_sweeps`` cap.

    Returns (subset_id[m], bounds[S+1], edge_state).
    """
    backend = cfg.backend or kops.default_backend()
    blocks = cfg.kernel_blocks
    p_total = cfg.num_partitions

    es = build_edge_state(g, cfg, plan=plan)
    m = es["m"]
    subset_id = np.full(m, -1, np.int64)
    bounds = [0.0]

    with span("cd.count") as sp:
        fault_point("kernel_launch", KernelBackendError,
                    dispatch="wing_subset", backend=backend, phase="count")
        support = kops.edge_support_all(es["a"], es["eu"], es["ev"],
                                        backend=backend, blocks=blocks)
        alive = jnp.asarray(es["alive0"])
        support = jnp.where(alive, support, _INF)
        sup_np = np.asarray(fetch(stats, support, "cd.count"), np.float64)
    stats.time_count = sp.seconds
    geom = {"a": es["a"], "eu": es["eu"], "ev": es["ev"]}
    dv = es["dv0"]
    theta0 = jnp.zeros(es["m_pad"], jnp.float32)
    alive_np = np.asarray(es["alive0"])

    peel_width = es["peel_width"]
    width_hint = plan.cd_peel_width_hint() if plan is not None else None
    if width_hint is not None and cfg.peel_width is None:
        peel_width = min(es["m_pad"],
                         max(peel_width, bucket(width_hint, blocks[1])))
    lo = 0.0
    i = 0
    while alive_np.any():
        catch = i >= p_total - 1
        if catch:
            hi = float(np.max(np.where(alive_np, sup_np, -np.inf))) + 1.0
        else:
            vals = np.sort(sup_np[alive_np])
            tgt = max(len(vals) // (p_total - i), 1)
            hi = float(vals[min(tgt - 1, len(vals) - 1)]) + 1.0
        with span("cd.subset", i=i):
            sweeps = 0
            while True:
                fault_point("kernel_launch", KernelBackendError,
                            dispatch="wing_subset", subset=i, backend=backend)
                (geom, support, alive, dv, _th, peeled, d_rho, d_wedges,
                 d_hucs, d_elided, _d_cov, _d_sweeps, _ovf) = device_peel_loop(
                    geom, None, None, None, support, alive, dv, theta0,
                    hi, lo, es["c_rcnt"], 0,
                    backend=backend, blocks=blocks, use_huc=cfg.use_huc,
                    peel_width=peel_width, max_sweeps=cfg.max_sweeps,
                    minmode=False, axis="edge",
                )
                stats.device_loop_calls += 1
                (peeled_np, alive_np, sup_f32, d_rho, d_wedges, d_hucs,
                 d_elided) = fetch(
                    stats, (peeled, alive, support, d_rho, d_wedges, d_hucs,
                            d_elided), "cd.subset")
                sup_np = np.asarray(sup_f32, np.float64)
                stats.rho_cd += int(d_rho)
                stats.wedges_cd += int(d_wedges)
                stats.huc_recounts += int(d_hucs)
                stats.elided_sweeps += int(d_elided)
                sweeps += int(d_rho)
                subset_id[np.where(peeled_np[:m])[0]] = i
                if not (alive_np & (sup_np < hi)).any():
                    break
                if int(d_rho) == 0:
                    raise RuntimeError(
                        "wing CD device loop made no progress on a non-empty "
                        "range (max_sweeps misconfigured?)")
        stats.sweeps_per_subset.append(sweeps)
        bounds.append(hi)
        lo = hi
        i += 1
        if catch:
            break

    stats.num_subsets = i
    stats.bounds = [float(b) for b in bounds]
    if plan is not None:
        plan.note_cd_peel_width(peel_width)
    assert (subset_id >= 0).all(), "wing CD left unassigned edges"
    return subset_id, np.asarray(bounds), es


# ---------------------------------------------------------------------- #
# wing CD, graph dispatch (the whole CD phase in ONE device dispatch)
# ---------------------------------------------------------------------- #
@functools.partial(
    jax.jit,
    static_argnames=("backend", "blocks", "use_huc", "peel_width",
                     "max_iters", "p_total"),
)
def device_wing_graph_loop(state, *, backend, blocks, use_huc, peel_width,
                           max_iters, p_total):
    """Every wing-CD subset under one ``lax.while_loop`` — the edge-axis
    twin of ``device_cd_graph_loop`` (DESIGN.md §2.3 applied to §10).

    The boundary branch closes subset ``i`` (records ``bounds[i+1]`` and
    the per-subset sweep count) and opens ``i+1`` with the DEVICE findHi
    reduction at UNIT mass per edge (``kernels.ops.find_hi_device`` with
    ``w = 1`` — the equal-edge-count objective; f32 prefix sums are
    exact below 2^24 edges).  The sweep branch is one shared
    ``_sweep_once(axis="edge")`` sweep; newly peeled edges are stamped
    with the open subset in ``subset_of``.  No DGM step: edge peeling
    already rewrites the carried biadjacency every sweep, so the
    residual graph is permanently compact — the whole reason the
    geometry rides in the loop state.

    The host blocks ONCE per invocation; re-entry happens only on a
    ``max_iters`` cap-exit (the edge axis cannot overflow — oversized
    peel sets recount in-body), so round trips per graph are O(1) by
    construction — the bound ``bench_gate.py`` pins.
    """
    f32 = jnp.float32
    i32 = jnp.int32

    def boundary(st):
        i = st["i"]
        closing = i >= 0
        idx = jnp.maximum(i, 0)
        bounds = st["bounds"].at[idx + 1].set(
            jnp.where(closing, st["hi"], st["bounds"][idx + 1]))
        rho_sub = st["rho_sub"].at[idx].set(
            jnp.where(closing, st["rho"] - st["rho_start"],
                      st["rho_sub"][idx]))
        lo = jnp.where(closing, st["hi"], st["lo"])
        done = ~jnp.any(st["alive"])
        i2 = jnp.where(done, i, i + 1)
        catch = i2 >= p_total - 1
        n_alive = jnp.sum(st["alive"]).astype(f32)
        tgt = jnp.where(
            catch, jnp.inf,
            jnp.maximum(
                n_alive / jnp.maximum(p_total - i2, 1).astype(f32), 1.0))
        ones = jnp.ones_like(st["support"], f32)
        hi = kops.find_hi_device(st["support"], st["alive"], ones, tgt)
        return dict(
            st, bounds=bounds, rho_sub=rho_sub, lo=lo, done=done, i=i2,
            hi=hi, rho_start=st["rho"], iters=st["iters"] + 1,
        )

    def sweep(st):
        (geom, support, alive, dv, _th, peeled, rho, wedges, hucs, elided,
         covered, ovf) = _sweep_once(
            {"a": st["a"], "eu": st["eu"], "ev": st["ev"]},
            None, None, None, st["c_rcnt"], st["hi"], st["lo"],
            st["support"], st["alive"], st["dv"], f32(0.0), st["peeled"],
            st["rho"], st["wedges"], st["hucs"], st["elided"],
            st["covered"], st["ovf"],
            backend=backend, blocks=blocks, use_huc=use_huc,
            peel_width=peel_width, minmode=False, axis="edge",
        )
        newly = peeled & ~st["peeled"]
        return dict(
            st, a=geom["a"], support=support, alive=alive, dv=dv,
            peeled=peeled, rho=rho, wedges=wedges, hucs=hucs,
            elided=elided, covered=covered, ovf=ovf,
            subset_of=jnp.where(newly, st["i"], st["subset_of"]),
            iters=st["iters"] + 1,
        )

    def cond_fn(st):
        return ~st["done"] & (st["iters"] < max_iters)

    def body_fn(st):
        drained = ~jnp.any(select_peel(st["support"], st["alive"],
                                       st["hi"]))
        return jax.lax.cond(drained, boundary, sweep, st)

    return jax.lax.while_loop(cond_fn, body_fn, state)


def wing_graph_state0(es: dict, support, alive, p_total: int):
    """Initial carried state of ``device_wing_graph_loop``.  ``hi = -inf``
    makes the first iteration take the boundary branch (subset 0 opens
    on device); the driver re-enters a cap-exit by feeding the fetched
    state back with a fresh ``iters`` budget."""
    i32 = jnp.int32
    f32 = jnp.float32
    m_pad = es["m_pad"]
    return dict(
        a=es["a"], eu=es["eu"], ev=es["ev"], dv=es["dv0"],
        c_rcnt=f32(es["c_rcnt"]),
        support=support, alive=alive,
        subset_of=jnp.full(m_pad, -1, i32),
        peeled=jnp.zeros(m_pad, bool),
        bounds=jnp.zeros(p_total + 1, f32),
        rho_sub=jnp.zeros(max(p_total, 1), i32),
        i=i32(-1), hi=f32(-jnp.inf), lo=f32(0.0),
        rho=i32(0), wedges=f32(0.0), hucs=i32(0), elided=i32(0),
        covered=f32(0.0), rho_start=i32(0),
        iters=i32(0), ovf=jnp.bool_(False), done=jnp.bool_(False),
    )


def _receipt_wing_cd_graph(
    g: BipartiteGraph, cfg: ReceiptConfig, stats: RunStats, *, plan=None,
) -> Tuple[np.ndarray, np.ndarray, dict]:
    """Whole-graph wing CD: O(1) blocking round trips per graph."""
    backend = cfg.backend or kops.default_backend()
    blocks = cfg.kernel_blocks
    p_total = cfg.num_partitions

    es = build_edge_state(g, cfg, plan=plan)
    m = es["m"]
    with span("cd.count") as sp:
        fault_point("kernel_launch", KernelBackendError,
                    dispatch="wing_graph", backend=backend, phase="count")
        support = kops.edge_support_all(es["a"], es["eu"], es["ev"],
                                        backend=backend, blocks=blocks)
        alive = jnp.asarray(es["alive0"])
        support = jnp.where(alive, support, _INF)
    # async dispatch: no blocking sync between counting and the CD loop
    stats.time_count = sp.seconds

    peel_width = es["peel_width"]
    width_hint = plan.cd_peel_width_hint() if plan is not None else None
    if width_hint is not None and cfg.peel_width is None:
        peel_width = min(es["m_pad"],
                         max(peel_width, bucket(width_hint, blocks[1])))
    state = wing_graph_state0(es, support, alive, p_total)
    while True:
        fault_point("kernel_launch", KernelBackendError,
                    dispatch="wing_graph", backend=backend)
        state = device_wing_graph_loop(
            state, backend=backend, blocks=blocks, use_huc=cfg.use_huc,
            peel_width=peel_width, max_iters=cfg.max_sweeps,
            p_total=p_total,
        )
        stats.device_loop_calls += 1
        st = fetch(stats, state, "cd.loop")       # THE blocking transfer
        if bool(st["done"]):
            break
        state = dict(state, iters=jnp.int32(0))   # max_sweeps cap-exit

    num_subsets = int(st["i"]) + 1
    subset_id = np.asarray(st["subset_of"][:m], np.int64)
    bounds = [0.0] + [float(b)
                      for b in np.asarray(st["bounds"])[1: num_subsets + 1]]
    stats.rho_cd += int(st["rho"])
    stats.wedges_cd += int(st["wedges"])
    stats.huc_recounts += int(st["hucs"])
    stats.elided_sweeps += int(st["elided"])
    stats.sweeps_per_subset.extend(
        int(x) for x in np.asarray(st["rho_sub"])[:num_subsets])
    stats.num_subsets = num_subsets
    stats.bounds = [float(b) for b in bounds]
    if plan is not None:
        plan.note_cd_peel_width(peel_width)
    assert (subset_id >= 0).all(), "wing CD left unassigned edges"
    return subset_id, np.asarray(bounds), es


# ---------------------------------------------------------------------- #
# wing FD (one batched level-peel dispatch over the subset stack)
# ---------------------------------------------------------------------- #
def receipt_wing_fd(
    g: BipartiteGraph, subset_id: np.ndarray, bounds: np.ndarray,
    cfg: ReceiptConfig, stats: RunStats, es: dict, *, plan=None,
) -> np.ndarray:
    """Exact wing numbers by batched independent peeling of the subset
    residual stack.

    Subset s's stack member holds EVERY edge of subsets >= s (a peeled
    edge's butterflies can involve higher-subset edges — the edge-axis
    range-containment argument), with only subset-s slots alive and
    supports recounted in-stack, floored at ``bounds[s]``.  All members
    share the graph's padded shape and the global ``eu``/``ev`` slot
    map, so the whole FD phase is ONE ``batched_level_loop(axis="edge")``
    dispatch + one blocking fetch (a ``max_sweeps`` cap-exit re-enters
    with the carried 9-tuple).  Every sweep is batched-exact (closed-form
    recount), so simultaneous deletes never race.
    """
    backend = cfg.backend or kops.default_backend()
    blocks = cfg.kernel_blocks
    m = es["m"]
    m_pad = es["m_pad"]
    psi = np.zeros(m, np.float64)
    sids = [s for s in range(int(subset_id.max()) + 1 if m else 0)
            if (subset_id == s).any()]
    for s in sids:
        stats.subset_sizes.append(int((subset_id == s).sum()))
    n_g = len(sids)
    if n_g == 0:
        return psi
    n_gp = plan.quantize_dim("wing_fd_groups", n_g) if plan is not None \
        else n_g

    slot_of = np.full(int(subset_id.max()) + 1, -1, np.int64)
    a = np.zeros((n_gp, es["rows_pad"], es["cols_pad"]), np.float32)
    alive = np.zeros((n_gp, m_pad), bool)
    los = np.zeros(n_gp, np.float64)
    eu_np, ev_np = es["eu_np"], es["ev_np"]
    for k, s in enumerate(sids):
        slot_of[s] = k
        resid = subset_id >= s
        a[k, eu_np[resid], ev_np[resid]] = 1.0
        alive[k, np.where(subset_id == s)[0]] = True
        los[k] = float(bounds[s])

    fault_point("kernel_launch", KernelBackendError,
                dispatch="wing_fd", backend=backend,
                group_shape=(n_gp, m_pad))
    a_dev = jnp.asarray(a, cfg.dtype)
    alive_dev = jnp.asarray(alive)
    dv_dev = jnp.asarray(a.sum(axis=1), jnp.float32)
    lo_dev = jnp.asarray(los, jnp.float32)
    sup0 = kops.edge_support_all(a_dev, es["eu"], es["ev"],
                                 backend=backend, blocks=blocks)
    sup0 = jnp.where(alive_dev,
                     jnp.maximum(sup0, lo_dev[:, None]), _INF)
    rext = jnp.zeros((n_gp, m_pad), jnp.int32)   # unused on the edge axis

    out = batched_level_loop(
        a_dev, rext, sup0, alive_dev, dv_dev, lo_dev, es["eu"], es["ev"],
        backend=backend, blocks=blocks, peel_width=1,
        max_sweeps=cfg.max_sweeps, update_mode="kernel", axis="edge",
    )
    stats.device_loop_calls += 1
    stats.fd_groups = 1
    th_acc = np.zeros((n_gp, m_pad), np.float64)
    prev_alive = alive
    max_level_seen = 0
    while True:
        a_c, sup, alv, dv_c, th, rho, wedges, max_lev, _sw = out
        th_h, alive_h, rho_h, wedges_h, max_lev_h = fetch(
            stats, (th, alv, rho, wedges, max_lev), "fd.drain")
        d_rho = int(np.asarray(rho_h).sum())
        stats.rho_fd += d_rho
        stats.wedges_fd += int(np.asarray(wedges_h, np.float64).sum())
        max_level_seen = max(max_level_seen,
                             int(np.asarray(max_lev_h).max()))
        newly_dead = prev_alive & ~alive_h
        th_acc = np.where(newly_dead, np.asarray(th_h, np.float64), th_acc)
        if not alive_h.any() or d_rho == 0:
            break
        prev_alive = alive_h
        out = batched_level_loop(
            a_c, rext, sup, alv, dv_c, lo_dev, es["eu"], es["ev"],
            backend=backend, blocks=blocks, peel_width=1,
            max_sweeps=cfg.max_sweeps, update_mode="kernel", axis="edge",
        )
        stats.device_loop_calls += 1
    stats.fd_max_levels.append(max_level_seen)
    stats.fd_peel_widths.append(m_pad)

    psi = th_acc[slot_of[subset_id], np.arange(m)]
    return psi


# ---------------------------------------------------------------------- #
# top-level driver (the wing twin of engine.tip_decompose)
# ---------------------------------------------------------------------- #
def wing_decompose_engine(
    g: BipartiteGraph, cfg: Optional[ReceiptConfig] = None,
    *, side: str = "U", plan=None,
) -> Tuple[np.ndarray, RunStats]:
    """Full engine-path wing decomposition of ``g``.

    Returns (psi int64[m], RunStats) with ``psi[j]`` the wing (bitruss)
    number of edge ``(g.edges_u[j], g.edges_v[j])`` — bit-identical to
    ``core/wing.wing_bup_oracle`` on every dispatch/backend combination
    (the differential contract, tests/test_wing.py).

    ``side="V"`` peels the transposed graph (psi is transpose-invariant:
    butterflies are side-symmetric) and maps back through the canonical
    edge-order permutation — ``BipartiteGraph.from_edges`` sorts edges
    by (u, v), so transposing REORDERS them and the identity is
    ``psi[lexsort((edges_u, edges_v))] = psi_transposed``.
    """
    cfg = cfg or ReceiptConfig()
    if side == "V":
        psi_t, stats = wing_decompose_engine(
            g.transposed(), cfg, side="U", plan=plan)
        psi = np.zeros(g.m, np.int64)
        psi[np.lexsort((g.edges_u, g.edges_v))] = psi_t
        return psi, stats
    if side != "U":
        raise ValueError(f"side must be 'U' or 'V', got {side!r}")
    stats = RunStats()
    if g.m == 0:
        return np.zeros(0, np.int64), stats
    with span("cd", dispatch=cfg.cd_dispatch) as sp:
        if cfg.cd_dispatch == "graph":
            subset_id, bounds, es = _receipt_wing_cd_graph(g, cfg, stats,
                                                           plan=plan)
        else:
            subset_id, bounds, es = receipt_wing_cd(g, cfg, stats,
                                                    plan=plan)
    stats.time_cd = sp.seconds - stats.time_count
    with span("fd") as sp:
        psi_f = receipt_wing_fd(g, subset_id, bounds, cfg, stats, es,
                                plan=plan)
    stats.time_fd = sp.seconds
    return np.round(psi_f).astype(np.int64), stats
