"""CD — coarse-grained decomposition (the paper's Alg. 3).

Partitions U into subsets with non-overlapping tip-number ranges by
running the unified peel core (`engine/peel_loop.py`) in **range-peel**
mode.  Two dispatch granularities (``cfg.cd_dispatch``, DESIGN.md
§2.0/§2.3):

* ``"subset"`` — one device-resident ``while_loop`` per subset.
  Host-side pieces: adaptive range determination (findHi on the
  per-subset support snapshot), DGM re-induction at subset boundaries,
  checkpointing, and the overflow replay through ``host_sweep``.
* ``"graph"`` — the ENTIRE CD phase is one device dispatch
  (``device_cd_graph_loop``): subset boundaries, the findHi wedge-mass
  reduction (``kernels.ops.find_hi_device``), the FD init-vector
  snapshot and the subset-id stamping all run inside one
  ``lax.while_loop``; the host blocks O(1) times per GRAPH instead of
  O(subsets) — the dispatch-layer analogue of the paper's 1100x sync
  reduction.  DGM and checkpointing are subset-dispatch features (both
  need the host at subset boundaries).
"""
from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp
import numpy as np

from ...api.errors import KernelBackendError, PeelOverflowError
from ...api.faults import fault_point
from ...kernels import ops as kops
from ...utils.spans import fetch, span
from ..graph import BipartiteGraph
from .peel_loop import (
    _INF,
    DeviceGraph,
    ReceiptConfig,
    RunStats,
    bucket,
    cd_graph_state0,
    device_cd_graph_loop,
    device_peel_loop,
    host_sweep,
    residual_dv,
    support_all,
)

__all__ = ["receipt_cd", "cd_checkpoint_state", "find_hi_np"]

# Bounded retry-with-widening (DESIGN.md §7): each overflow replay
# doubles the peel buffer, and the buffer is clamped at the padded row
# count, so a healthy run replays at most O(log rows_pad) times; the
# bound exists to turn a buggy no-progress loop into a structured
# PeelOverflowError instead of a hang.
_MAX_OVERFLOW_REPLAYS = 64


def find_hi_np(support: np.ndarray, w: np.ndarray, alive: np.ndarray,
               tgt: float) -> float:
    """Adaptive range upper bound (Alg. 3 findHi) on the host snapshot.

    Sort alive supports ascending, prefix-sum their wedge counts, pick the
    smallest support whose cumulative wedge count reaches the target.
    Falls back to max support + 1 (catch-all) when the target exceeds the
    remaining wedge mass.  Runs on the per-subset host support snapshot
    (which Alg. 3 needs anyway for the FD init vector), so it costs no
    extra device round trip.
    """
    sup = np.where(alive, support, np.inf)
    order = np.argsort(sup, kind="stable")
    ws = np.where(alive, w, 0.0)[order]
    cum = np.cumsum(ws)
    hit = cum >= tgt
    if hit.size and hit[-1]:
        hi = sup[order][int(np.argmax(hit))]
    else:
        hi = float(np.max(np.where(alive, support, -np.inf)))
    return float(hi) + 1.0


def cd_checkpoint_state(subset_id, init_support, bounds, members, support_np,
                        rem_wedges, scale, lo, i):
    """CD loop state as a plain pytree — checkpointable through
    train/checkpoint.py like any train state (fault tolerance for the
    peeling engine itself; restart is exact because CD is deterministic
    given this state)."""
    return {
        "subset_id": np.asarray(subset_id),
        "init_support": np.asarray(init_support),
        "bounds": np.asarray(bounds, np.float64),
        "members": np.asarray(members),
        "support": np.asarray(support_np, np.float64),
        "rem_wedges": np.float64(rem_wedges),
        "scale": np.float64(scale),
        "lo": np.float64(lo),
        "i": np.int64(i),
    }


def receipt_cd(
    g: BipartiteGraph, cfg: ReceiptConfig, stats: RunStats,
    *, checkpoint_cb=None, resume_state=None, plan=None,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Partition U into subsets with non-overlapping tip-number ranges.

    Returns (subset_id[n_u], init_support[n_u], bounds[P+1], theta_hint)
    where subset_id[u] in [0, P), init_support is the FD support
    initialization vector (Alg. 3 line 7) and bounds[i] = theta(i+1) lower
    bounds, bounds[-1] > theta_max.

    With ``cfg.device_loop`` (default) each subset's sweep loop runs
    device-resident (see ``device_peel_loop``); the host syncs ONCE per
    subset to snapshot supports (needed for the FD init vector and findHi
    anyway).  ``device_loop=False`` preserves the blocking host-driven
    engine for apples-to-apples round-trip benchmarks.

    checkpoint_cb(state): called with a cd_checkpoint_state pytree at
    every subset boundary.  resume_state: continue an interrupted run
    from such a state (tests/test_receipt.py::test_cd_checkpoint_restart).

    ``cfg.cd_dispatch="graph"`` routes to the whole-graph single-dispatch
    driver (``_receipt_cd_graph``); checkpointing needs the host at
    subset boundaries and therefore ``cd_dispatch="subset"``.

    ``plan``: an ``repro.api.ExecutionPlan`` (or any object with its
    peel-width hint surface).  A plan carrying a MEASURED peel width from
    an earlier same-signature run pins the gather buffer to it — the
    width (a jit-static argument) stops depending on this graph's data,
    so the executable cache hits instead of retracing, and the graph
    dispatch skips its pre-dispatch sizing snapshot entirely (one fewer
    blocking round trip).  The driver records the width it ended up with
    back into the plan.  ``plan=None`` (every legacy call site) keeps
    the self-sizing behavior bit-identical to PR 4.
    """
    if cfg.max_sweeps < 1:
        raise ValueError(
            f"max_sweeps must be >= 1 (got {cfg.max_sweeps}): the valve "
            "bounds one device-loop invocation; a sub-1 cap can make no "
            "progress and would break Theorem 1's range containment")
    if cfg.cd_dispatch not in ("subset", "graph"):
        raise ValueError(f"unknown cd_dispatch {cfg.cd_dispatch!r}")
    if cfg.cd_dispatch == "graph":
        if not cfg.device_loop:
            raise ValueError(
                "cd_dispatch='graph' runs the whole CD phase on device "
                "and requires device_loop=True")
        if checkpoint_cb is not None or resume_state is not None:
            raise ValueError(
                "CD checkpointing captures subset-boundary state on the "
                "host; use cd_dispatch='subset'")
    with span("cd", dispatch=cfg.cd_dispatch) as sp:
        if cfg.cd_dispatch == "graph":
            out = _receipt_cd_graph(g, cfg, stats, plan=plan)
        else:
            out = _receipt_cd_subset(g, cfg, stats, plan=plan,
                                     checkpoint_cb=checkpoint_cb,
                                     resume_state=resume_state)
    stats.time_cd = sp.seconds - stats.time_count
    return out


def _receipt_cd_subset(g: BipartiteGraph, cfg: ReceiptConfig,
                       stats: RunStats, *, plan, checkpoint_cb,
                       resume_state):
    """Subset-dispatch CD: one device loop (and one blocking fetch) per
    subset, findHi and DGM on the host (``receipt_cd``'s contract)."""
    backend = cfg.backend or kops.default_backend()
    blocks = cfg.kernel_blocks
    n_u = g.n_u
    p_total = cfg.num_partitions

    if resume_state is not None:
        st = resume_state
        subset_id = np.asarray(st["subset_id"]).copy()
        init_support = np.asarray(st["init_support"]).copy()
        bounds = [float(b) for b in st["bounds"]]
        members = np.asarray(st["members"])
        dg = DeviceGraph(g, members, cfg, plan=plan, stats=stats)
        stats.wedges_pvbcnt = g.counting_wedge_bound()
        alive = jnp.zeros(dg.rows_pad, bool).at[: dg.n_rows].set(True)
        support = jnp.full(dg.rows_pad, _INF, cfg.dtype)
        support = support.at[: dg.n_rows].set(
            jnp.asarray(st["support"][: dg.n_rows], cfg.dtype)
        )
        dv = dg.dv0
        sup_np, alive_np = fetch(stats, (support, alive), "cd.resume")
        sup_np = np.asarray(sup_np, np.float64)
        rem_wedges = float(st["rem_wedges"])
        scale = float(st["scale"])
        lo = float(st["lo"])
        i = int(st["i"])
    else:
        subset_id = np.full(n_u, -1, np.int64)
        init_support = np.zeros(n_u, np.float64)
        bounds = [0.0]

        dg = DeviceGraph(g, np.arange(n_u), cfg, plan=plan, stats=stats)
        stats.wedges_pvbcnt = g.counting_wedge_bound()

        # --- initial per-vertex counting (pvBcnt) ---------------------- #
        with span("cd.count") as sp:
            sparse = backend in kops.SPARSE_BACKENDS
            alive = jnp.zeros(dg.rows_pad, bool).at[: dg.n_rows].set(True)
            fault_point("kernel_launch", KernelBackendError,
                        dispatch="subset", backend=backend, phase="count")
            support = support_all(dg.a, alive, dg.ids,
                                  dg.kmax if sparse else None,
                                  backend=backend, blocks=blocks)
            support = jnp.where(alive, support, _INF)
            dv = dg.dv0
            sup_np, alive_np = fetch(stats, (support, alive), "cd.count")
            sup_np = np.asarray(sup_np, np.float64)
        stats.time_count = sp.seconds

        rem_wedges = dg.total_wedges
        scale = 1.0
        lo = 0.0
        i = 0

    peel_width = dg.initial_peel_width()
    width_hint = plan.cd_peel_width_hint() if plan is not None else None
    if width_hint is not None and cfg.peel_width is None:
        # measured width from an earlier same-signature run: pin the
        # buffer (a jit-static arg) so the trace cache hits; the overflow
        # replay keeps an undersized hint exact
        peel_width = min(dg.rows_pad,
                         max(peel_width, bucket(width_hint, blocks[1])))
    width_max = peel_width
    while alive_np.any():
        with span("cd.subset", i=i):
            if checkpoint_cb is not None:
                live = np.where(alive_np)[0]
                checkpoint_cb(cd_checkpoint_state(
                    subset_id, init_support, bounds, dg.members[live],
                    sup_np[live], rem_wedges, scale, lo, i,
                ))
            # final catch-all subset (paper: "puts all of them in U_{P+1}")
            catch_all = i >= p_total - 1
            tgt = (np.inf if catch_all
                   else max(rem_wedges / (p_total - i) * scale, 1.0))

            # support snapshot -> FD init vector (Alg. 3 lines 6-7)
            live_rows = np.where(alive_np)[0]
            init_support[dg.members[live_rows]] = sup_np[live_rows]

            if catch_all:
                hi = float(np.max(np.where(alive_np, sup_np, -np.inf)))
                hi += 1.0
            else:
                hi = find_hi_np(sup_np, dg.w_np, alive_np, tgt)

            sweeps = 0
            covered_wedges = 0.0
            if cfg.device_loop:
                # -------- device-resident sweep loop (O(1) syncs) ------ #
                # the subset's FIRST sweep peels the whole initial range; its
                # size is already known from the host snapshot, so size the
                # peel buffer to fit it and overflow only on larger cascades
                # (an explicit cfg.peel_width — or a plan's measured width,
                # which must stay data-independent to keep the trace cache
                # hitting — pins the initial width instead)
                if cfg.peel_width is None and width_hint is None:
                    n_first = int((alive_np & (sup_np < hi)).sum())
                    peel_width = max(peel_width, min(
                        dg.rows_pad,
                        bucket(max(n_first, blocks[1]), blocks[1]),
                    ))
                if fault_point("peel_buffer", dispatch="subset", subset=i,
                               backend=backend):
                    # injected sizing fault: undersize the buffer to the
                    # smallest width the backend accepts (one row on xla,
                    # one block tile on the kernel routes) so the overflow
                    # replay path is forced on any larger sweep (degrade-
                    # style point — results stay exact through the replay +
                    # retry-with-widening)
                    peel_width = 1 if backend == "xla" else blocks[1]
                replays = 0
                while True:
                    fault_point("kernel_launch", KernelBackendError,
                                dispatch="subset", subset=i,
                                backend=backend)
                    (support, alive, dv, _th, peeled, d_rho, d_wedges,
                     d_hucs, d_elided, d_covered, _d_sweeps,
                     ovf) = device_peel_loop(
                        dg.a, dg.ids, dg.row_ext, dg.kmax, support, alive,
                        dv, jnp.zeros(dg.rows_pad, jnp.float32), hi, lo,
                        dg.c_rcnt, 0,
                        backend=backend, blocks=blocks, use_huc=cfg.use_huc,
                        peel_width=peel_width, max_sweeps=cfg.max_sweeps,
                        minmode=False,
                    )
                    stats.device_loop_calls += 1
                    (peeled_np, alive_np, sup_f32, d_rho, d_wedges, d_hucs,
                     d_elided, d_covered, ovf_h) = fetch(
                        stats, (peeled, alive, support, d_rho, d_wedges,
                                d_hucs, d_elided, d_covered, ovf),
                        "cd.subset")
                    sup_np = np.asarray(sup_f32, np.float64)
                    stats.rho_cd += int(d_rho)
                    stats.wedges_cd += int(d_wedges)
                    stats.huc_recounts += int(d_hucs)
                    stats.elided_sweeps += int(d_elided)
                    sweeps += int(d_rho)
                    covered_wedges += float(d_covered)
                    subset_id[dg.members[np.where(peeled_np)[0]]] = i
                    if bool(ovf_h):
                        # peel buffer overflow: replay this one sweep on
                        # the host at the precise bucket, re-enter with a
                        # wider buffer (bounded retry-with-widening,
                        # DESIGN.md §7)
                        replays += 1
                        if replays > _MAX_OVERFLOW_REPLAYS:
                            raise PeelOverflowError(
                                f"peel-buffer overflow replay made no "
                                f"progress after {_MAX_OVERFLOW_REPLAYS} "
                                f"widenings (width={peel_width}, "
                                f"rows_pad={dg.rows_pad})",
                                dispatch="subset", subset=i, backend=backend,
                                peel_width=peel_width, rows_pad=dg.rows_pad)
                        stats.overflow_fallbacks += 1
                        support, alive, info = host_sweep(
                            dg, cfg, stats, support, alive, hi, lo, backend,
                            blocks)
                        if info is not None:
                            covered_wedges += info["c_peel"]
                            sweeps += 1
                            rows = info["peel_np"].nonzero()[0]
                            subset_id[dg.members[rows]] = i
                        dv = residual_dv(dg.a, alive)
                        sup_np, alive_np = fetch(stats, (support, alive),
                                                 "cd.replay")
                        sup_np = np.asarray(sup_np, np.float64)
                        peel_width = min(dg.rows_pad, peel_width * 2)
                        continue
                    # max_sweeps valve: caps ONE invocation, never the
                    # subset — a cap-exit with range left re-enters
                    # (Theorem 1 needs [lo, hi) fully drained before the
                    # bound is recorded)
                    if not (alive_np & (sup_np < hi)).any():
                        break
                    if int(d_rho) == 0:
                        raise RuntimeError(
                            "CD device loop made no progress on a non-empty "
                            "range (max_sweeps misconfigured?)")
            else:
                # -------- pre-PR engine: blocking host-driven sweeps --- #
                # (no valve here: the host regains control at every sweep,
                # and each sweep peels >= 1 row, so the loop terminates in
                # <= n_rows sweeps — draining fully preserves Theorem 1)
                while True:
                    support, alive, info = host_sweep(
                        dg, cfg, stats, support, alive, hi, lo, backend,
                        blocks)
                    if info is None:
                        break
                    sweeps += 1
                    covered_wedges += info["c_peel"]
                    subset_id[dg.members[info["peel_np"].nonzero()[0]]] = i
                sup_np, alive_np = fetch(stats, (support, alive), "cd.host")
                sup_np = np.asarray(sup_np, np.float64)

        stats.sweeps_per_subset.append(sweeps)
        bounds.append(hi)
        rem_wedges = max(rem_wedges - covered_wedges, 0.0)
        if covered_wedges > 0 and not catch_all:
            scale = min(1.0, tgt / covered_wedges)
        lo = hi
        i += 1
        if catch_all:
            break

        # --- DGM: re-induce the residual graph into smaller buckets ---- #
        n_alive = int(alive_np.sum())
        if n_alive == 0:
            break
        if cfg.use_dgm and n_alive < cfg.dgm_row_threshold * dg.rows_pad:
            fault_point("dgm_boundary", KernelBackendError,
                        dispatch="subset", subset=i, backend=backend)
            live = np.where(alive_np)[0]
            new_members = dg.members[live]
            sup_keep = sup_np[live]
            width_max = max(width_max, peel_width)
            dg = DeviceGraph(g, new_members, cfg, plan=plan, stats=stats)
            stats.dgm_compactions += 1
            alive = jnp.zeros(dg.rows_pad, bool).at[: dg.n_rows].set(True)
            support = jnp.full(dg.rows_pad, _INF, cfg.dtype)
            support = support.at[: dg.n_rows].set(
                jnp.asarray(sup_keep, cfg.dtype)
            )
            dv = dg.dv0
            alive_np = np.zeros(dg.rows_pad, bool)
            alive_np[: dg.n_rows] = True
            sup_np = np.full(dg.rows_pad, np.inf)
            sup_np[: dg.n_rows] = sup_keep
            rem_wedges = dg.total_wedges
            peel_width = min(peel_width, dg.initial_peel_width())

    stats.num_subsets = i
    stats.bounds = [float(b) for b in bounds]
    if plan is not None:
        plan.note_cd_peel_width(max(width_max, peel_width))
    # every vertex must be assigned
    assert (subset_id >= 0).all(), "CD left unassigned vertices"
    return subset_id, init_support, np.asarray(bounds), None


class _GraphStateView:
    """``host_sweep`` adapter over the device-carried residual graph.

    The whole-graph loop's overflow replay must run against the CARRIED
    biadjacency — after an on-device DGM boundary the columns are
    permuted (live-V prefix) and dead rows/columns zeroed, so ``dg.a``
    (the construction-time matrix) would compute wrong colsums/extents.
    This view exposes the ``DeviceGraph`` attribute surface ``host_sweep``
    consumes, sourced from the fetched loop state instead.
    """

    def __init__(self, dg: DeviceGraph, state, c_rcnt: float):
        self.a = state["a"]
        self.ids = dg.ids
        self.row_ext = state["row_ext"]
        self.kmax = state["kmax"]
        self.c_rcnt = c_rcnt
        self.rows_pad = dg.rows_pad


def _receipt_cd_graph(
    g: BipartiteGraph, cfg: ReceiptConfig, stats: RunStats, *, plan=None,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Whole-graph CD: every subset under ONE device dispatch.

    The host's entire involvement per graph is: build the device graph,
    launch the initial counting + ``device_cd_graph_loop``, and fetch the
    final state in ONE blocking transfer — subset boundaries, findHi, the
    FD init snapshot, subset-id stamping AND Dynamic Graph Maintenance
    (on-device column compaction + HUC-bound re-estimation + staircase
    re-tightening, gated by ``cfg.use_dgm``) all happen inside the loop
    (DESIGN.md §2.3).  Re-entry happens only on a peel-buffer overflow
    (host replays that one sweep at the precise bucket — against the
    carried, column-permuted matrix via ``_GraphStateView`` — folds its
    effect into the carried state, doubles the buffer) or a
    ``max_sweeps`` cap-exit (state fed straight back with a fresh
    iteration budget), so ``RunStats.host_round_trips`` is O(1) per
    graph instead of O(subsets).

    Bounds may differ from the subset driver (fresh residual wedge
    counts at every boundary, f32 findHi prefix sums, per-boundary
    instead of threshold-gated DGM cadence) but tip numbers cannot
    (Theorem 1 holds for any subset bounds).
    """
    backend = cfg.backend or kops.default_backend()
    blocks = cfg.kernel_blocks
    sparse = backend in kops.SPARSE_BACKENDS
    n_u = g.n_u
    p_total = cfg.num_partitions

    subset_id = np.full(n_u, -1, np.int64)
    init_support = np.zeros(n_u, np.float64)
    dg = DeviceGraph(g, np.arange(n_u), cfg, plan=plan, stats=stats)
    stats.wedges_pvbcnt = g.counting_wedge_bound()

    with span("cd.count") as sp:
        alive = jnp.zeros(dg.rows_pad, bool).at[: dg.n_rows].set(True)
        fault_point("kernel_launch", KernelBackendError,
                    dispatch="graph", backend=backend, phase="count")
        support = support_all(dg.a, alive, dg.ids,
                              dg.kmax if sparse else None,
                              backend=backend, blocks=blocks)
        support = jnp.where(alive, support, _INF)
    # async dispatch: no blocking sync between counting and the CD loop
    stats.time_count = sp.seconds

    peel_width = dg.initial_peel_width()
    width_hint = plan.cd_peel_width_hint() if plan is not None else None
    if width_hint is not None and cfg.peel_width is None:
        # measured width from an earlier same-signature run: the sizing
        # snapshot below becomes unnecessary, so a cache-hit graph runs
        # the whole CD phase with ONE blocking round trip (the final
        # state fetch); an undersized hint still replays exactly through
        # the overflow path
        peel_width = min(dg.rows_pad,
                         max(peel_width, bucket(width_hint, blocks[1])))
    elif cfg.peel_width is None and dg.n_rows and p_total > 1:
        # size the buffer to subset 0's first sweep, known from ONE host
        # snapshot (the only pre-dispatch sync; still O(1) per graph).
        # Later subsets' first sweeps are range-bounded, and any sweep
        # that peels EVERY survivor — the catch-all opener in particular
        # — takes the bufferless elide branch.  With p_total == 1 the
        # single catch-all sweep elides, so no sizing is needed at all.
        sup_np, alive_np = fetch(stats, (support, alive), "cd.size")
        sup_np = np.asarray(sup_np, np.float64)
        tgt0 = max(dg.total_wedges / p_total, 1.0)
        hi0 = find_hi_np(sup_np, dg.w_np, alive_np, tgt0)
        n_first = int((alive_np & (sup_np < hi0)).sum())
        peel_width = max(peel_width, min(
            dg.rows_pad, bucket(max(n_first, blocks[1]), blocks[1])))
    if fault_point("peel_buffer", dispatch="graph", backend=backend):
        # injected sizing fault: undersize the buffer to the smallest
        # width the backend accepts (one row on xla, one block tile on
        # the kernel routes) so the overflow replay is forced on any
        # larger sweep (exact through the host replay +
        # retry-with-widening)
        peel_width = 1 if backend == "xla" else blocks[1]
    state = cd_graph_state0(dg, support, alive, p_total)
    replays = 0
    while True:
        fault_point("kernel_launch", KernelBackendError,
                    dispatch="graph", backend=backend)
        state = device_cd_graph_loop(
            dg.ids, state,
            backend=backend, blocks=blocks, use_huc=cfg.use_huc,
            use_dgm=cfg.use_dgm, peel_width=peel_width,
            max_iters=cfg.max_sweeps, p_total=p_total,
        )
        stats.device_loop_calls += 1
        st = fetch(stats, state, "cd.loop")       # THE blocking transfer
        if bool(st["done"]):
            break
        state = dict(state, iters=jnp.int32(0))   # fresh invocation budget
        if int(st["dgm"]):
            fault_point("dgm_boundary", KernelBackendError,
                        dispatch="graph", backend=backend,
                        compactions=int(st["dgm"]))
        if not bool(st["ovf"]):
            continue                              # max_sweeps cap-exit
        replays += 1
        if replays > _MAX_OVERFLOW_REPLAYS:
            raise PeelOverflowError(
                f"peel-buffer overflow replay made no progress after "
                f"{_MAX_OVERFLOW_REPLAYS} widenings (width={peel_width}, "
                f"rows_pad={dg.rows_pad})",
                dispatch="graph", backend=backend,
                peel_width=peel_width, rows_pad=dg.rows_pad)
        # peel-buffer overflow: replay this ONE sweep on the host at the
        # precise bucket — against the CARRIED residual graph (column-
        # permuted/compacted by the on-device DGM boundaries, so dg.a
        # would be stale), fold its effect into the carried state (the
        # replay's stats go through a scratch RunStats so the final
        # device counters are added exactly once; its round trips are
        # added here), re-enter wider
        stats.overflow_fallbacks += 1
        tmp = RunStats()
        i_cur = int(st["i"])
        gv = _GraphStateView(dg, state, float(st["c_rcnt"]))
        support2, alive2, info = host_sweep(
            gv, cfg, tmp, state["support"], state["alive"],
            float(st["hi"]), float(st["lo"]), backend, blocks)
        stats.host_round_trips += tmp.host_round_trips
        state["support"] = support2
        state["alive"] = alive2
        state["dv"] = residual_dv(state["a"], alive2)
        state["ovf"] = jnp.bool_(False)
        if info is not None:
            peel_dev = jnp.asarray(info["peel_np"])
            state["peeled"] = state["peeled"] | peel_dev
            state["subset_of"] = jnp.where(
                peel_dev, jnp.int32(i_cur), state["subset_of"])
            state["rho"] = state["rho"] + 1
            state["covered"] = state["covered"] + jnp.float32(info["c_peel"])
            state["wedges"] = state["wedges"] + jnp.float32(tmp.wedges_cd)
            state["hucs"] = state["hucs"] + jnp.int32(tmp.huc_recounts)
            state["elided"] = state["elided"] + jnp.int32(tmp.elided_sweeps)
        peel_width = min(dg.rows_pad, peel_width * 2)

    num_subsets = int(st["i"]) + 1
    subset_id[dg.members] = np.asarray(st["subset_of"][: dg.n_rows],
                                       np.int64)
    init_support[dg.members] = np.asarray(st["init_sup"][: dg.n_rows],
                                          np.float64)
    bounds = [0.0] + [float(b)
                      for b in np.asarray(st["bounds"])[1: num_subsets + 1]]
    stats.rho_cd += int(st["rho"])
    stats.wedges_cd += int(st["wedges"])
    stats.huc_recounts += int(st["hucs"])
    stats.elided_sweeps += int(st["elided"])
    stats.dgm_device_compactions += int(st["dgm"])
    stats.sweeps_per_subset.extend(
        int(x) for x in np.asarray(st["rho_sub"])[:num_subsets])
    stats.num_subsets = num_subsets
    stats.bounds = [float(b) for b in bounds]
    if plan is not None:
        plan.note_cd_peel_width(peel_width)
    assert (subset_id >= 0).all(), "CD left unassigned vertices"
    return subset_id, init_support, np.asarray(bounds), None
