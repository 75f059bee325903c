"""FD — fine-grained decomposition (the paper's Alg. 4) on the unified core.

Each CD subset's induced subgraph is peeled independently.  Subsets are
grouped into equal-padded-shape stacks (`core/scheduler.py` — the LPT /
workload-aware scheduling analogue) and each stack is peeled by the
unified peel core's **batched level-peel** loop
(`engine/peel_loop.batched_level_loop`): every device sweep removes the
whole current-minimum support level of every still-live subset in the
stack — the ParButterfly / PBNG peel granularity, vmapped over the shape
group and dispatched through the grouped butterfly kernels.

Runtime structure (DESIGN.md §2.2):

* **iterated host pre-peel** (``pre_peel_tasks``): up to
  ``cfg.fd_prepeel_levels`` peel levels of every subset are resolved
  from the host support snapshot, between CD's last sync and the first
  FD launch (nothing is queued on the device meanwhile) — each level's
  theta is assigned host-side and its delta folded in exactly (pairwise
  shared-wedge subtraction, computed from the level's wedges by a sparse
  product; exact for simultaneous level peels), so the device stacks
  hold the SURVIVORS of all hoisted levels (the catch-all subset
  typically shrinks severalfold); the last hoisted level's delta
  reaches the survivors through one grouped butterfly kernel call;
* **one device dispatch + one blocking ``fetch`` per shape group**
  (theta, per-subset sweep counts rho and dynamic wedge counters all ride
  back in the same transfer); a ``max_sweeps`` cap-exit re-enters with
  the carried state (the valve bounds one invocation, never the
  schedule — DESIGN.md §2.0);
* **double-buffered group dispatch**: the host induces and stacks the
  NEXT group's subgraphs while the device peels the current group (JAX
  async dispatch);
* ``RunStats.rho_fd`` counts actual level sweeps, ``RunStats.wedges_fd``
  the dynamically traversed wedges (sum of per-sweep C_peel) — both were
  previously static placeholders.

Tuning knobs (both on ``ReceiptConfig``, defaults chosen by cost model —
DESIGN.md §2.2 "Knobs"):

* ``fd_update_mode`` — ``"auto"`` precomputes the (G, M, M) B2 stack
  when ``G*M*M <= fd_b2_cells`` (strictly fewer flops whenever it fits:
  M²C once vs MC per sweep) and streams through the grouped butterfly
  kernel otherwise (O(M) working set, the scale path).  ``"b2"`` /
  ``"kernel"`` pin either side; both produce bit-identical deltas.
* ``peel_width`` — the per-sweep gather buffer; ``None`` sizes it to
  the ``mm/8`` bucket (post-first-level cascades are small and sweeps
  are memory-bound).  An oversized level falls back ON DEVICE to the
  mask-form kernel — never to the host.

**Mesh execution** (DESIGN.md §4): ``receipt_fd(mesh=...)`` routes the
same pipeline through ``_run_level_groups_mesh`` — per shape group, the
survivor/first-level stacks are LPT-assigned to ``mesh.size`` shards
(`core/distributed.shard_level_group`, with load carryover across
groups), placed on the shards (an ``fd.shard`` span) and peeled under
``shard_map`` with zero collectives
(`core/distributed.distributed_fd_level_peel`); per-shard loads and
stack bytes are reconciled into ``RunStats.fd_shard_rho`` /
``fd_shard_wedges`` / ``fd_shard_bytes`` and tip numbers are
bit-identical to the local path.
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np
from scipy.sparse import csr_matrix

from ...api.errors import KernelBackendError
from ...api.faults import fault_point
from ...kernels import ops as kops
from ...kernels.butterfly_sparse import batched_row_extents
from ...utils.spans import fetch, span
from ..graph import BipartiteGraph, pad_to_multiple
from ..scheduler import pack_by_shape
from .peel_loop import (
    ReceiptConfig,
    RunStats,
    batched_level_loop,
    bucket,
)

__all__ = ["receipt_fd", "build_fd_tasks", "build_level_stack"]


# ---------------------------------------------------------------------- #
# task construction + scheduling
# ---------------------------------------------------------------------- #
def build_fd_tasks(g: BipartiteGraph, subset_id: np.ndarray,
                   bounds: np.ndarray, stats: RunStats) -> List[Dict]:
    """Induce each subset's subgraph (the paper's "only traverse its
    wedges" saving) and record per-subset size/wedge-bound stats."""
    n_sub = int(subset_id.max()) + 1 if subset_id.size else 0
    tasks = []
    with span("fd.tasks", subsets=n_sub):
        for i in range(n_sub):
            members = np.where(subset_id == i)[0]
            stats.subset_sizes.append(len(members))
            if len(members) == 0:
                stats.subset_wedges_fd.append(0)
                continue
            sub, _ = g.induced_on_u(members)
            wsub = int(sub.wedge_counts_u().sum())
            stats.subset_wedges_fd.append(wsub)
            tasks.append(
                dict(
                    members=members,
                    sub=sub,
                    lo=float(bounds[i]),
                    wedges=wsub,
                )
            )
    return tasks


def _aligns(cfg: ReceiptConfig, backend: str):
    """Row/col padding multiples: kernel blocks for the pallas-family
    backends, the legacy 8 for the pure-jnp oracle."""
    bi, bj, bk = cfg.kernel_blocks
    if backend == "xla":
        return 8, 8, 8
    return max(bi, bj), bk, bj


def _level_delta(a, surv_mask: np.ndarray, l_mask: np.ndarray):
    """Exact support loss of the survivors when level ``L`` peels:
    ``delta[u] = sum_{x in L} C(|N(u) & N(x)|, 2)`` for every ``u`` in
    ``surv_mask`` (in row order), int64.

    ``a`` is the subset's biadjacency as an int64 ``scipy.sparse`` CSR
    matrix.  The sparse product ``a[surv] @ a[L].T`` walks the level's
    wedges and nothing else: for each survivor ``u``, each ``v`` in
    ``N(u)`` and each peeled ``x`` in ``N(v)`` it adds one to the
    ``(u, x)`` count, so its work is the number of such pairs,
    ``sum_v |N(v) & surv| * |N(v) & L|``, not the ``n * n_v * |L|`` of a
    dense product.  A butterfly holds exactly two peeled-side vertices,
    so the pairwise sum is exact for a simultaneous level peel.
    """
    w = (a[surv_mask] @ a[l_mask].T).tocsr()
    w.data = w.data * (w.data - 1) // 2
    return np.asarray(w.sum(axis=1), np.int64).ravel()


def pre_peel_tasks(tasks: List[Dict], init_support: np.ndarray,
                   theta: np.ndarray, stats: RunStats,
                   levels: int = 1) -> List[Dict]:
    """Host-side pre-peel of up to ``levels`` support levels (the CD
    first-sweep-sizing insight applied to FD): a subset's first peel
    level is fully determined by the host support snapshot — cap =
    max(min support, lo), level = everyone at or below cap — so its
    theta (= cap, exact by the simultaneous-peel argument) is assigned
    here, its wedge cost is accounted here, and the DEVICE stack is
    built from the survivors only.  On catch-all subsets the first
    level is the bulk of the subset, so survivor compaction shrinks the
    padded stack (and the B2/kernel contraction that dominates FD) by a
    large factor.

    ``levels > 1`` (``ReceiptConfig.fd_prepeel_levels``) keeps peeling
    on the host: levels 2, 3, ... are derived by folding each earlier
    level's exact delta (``_level_delta``, from the level's wedges) into
    the survivor supports, which then floor at the level cap.  This runs
    between CD's last sync and the first FD launch, with nothing queued
    on the device, so its cost is the pairs the deltas traverse
    (``RunStats.fd_prepeel_pairs``).  Theta is IDENTICAL for every
    ``levels >= 1`` (tip numbers are canonical across exact schedules;
    regression-tested).  The LAST hoisted level is handed to the device
    contract unchanged: ``l1``/``cap1``/``sup_surv`` describe that
    level, whose delta the launcher applies through one grouped
    butterfly kernel call — earlier levels' deltas are already folded
    into ``sup_surv`` host-side.

    Mutates ``theta`` / ``stats`` (rho_fd += 1 and the level's dynamic
    C_peel per hoisted level, the delta's pairs) and returns the
    survivor task list.
    """
    levels = max(int(levels), 1)
    out = []
    for t in tasks:
        mems, sub, lo = t["members"], t["sub"], t["lo"]
        sup = np.asarray(init_support[mems], np.float64).copy()
        n = len(mems)
        alive = np.ones(n, bool)
        # column degrees of the still-alive rows (wedge accounting)
        dv_cur = np.bincount(sub.edges_v, minlength=sub.n_v)
        a = None                        # sparse rows, built lazily (only
        #                               # needed once a 2nd level peels)
        done = False
        for lvl in range(levels):
            cap_l = (max(float(sup[alive].min()), lo) if alive.any()
                     else lo)
            l_mask = alive & (sup <= cap_l)
            theta[mems[l_mask]] = cap_l
            # dynamic wedge cost of this sweep: colsum_L . max(dv - 1, 0)
            peel_e = l_mask[sub.edges_u]
            colsum = np.bincount(sub.edges_v[peel_e], minlength=sub.n_v)
            stats.wedges_fd += int(
                (colsum * np.maximum(dv_cur - 1, 0)).sum())
            stats.rho_fd += 1
            surv_mask = alive & ~l_mask
            if not surv_mask.any():
                done = True             # subset fully drained on host
                break
            if lvl == levels - 1:
                # last hoisted level: the device applies its delta (one
                # grouped kernel call) — hand over the standard contract
                out.append(dict(
                    t, surv=np.where(surv_mask)[0],
                    l1=np.where(l_mask)[0], cap1=cap_l,
                    sup_surv=sup[surv_mask],
                ))
                done = True
                break
            # fold this level's delta host-side and keep hoisting
            if a is None:
                a = csr_matrix(
                    (np.ones(sub.m, np.int64), (sub.edges_u, sub.edges_v)),
                    shape=(n, sub.n_v))
            delta = _level_delta(a, surv_mask, l_mask)
            dv_cur = dv_cur - colsum
            stats.fd_prepeel_pairs += int((colsum * dv_cur).sum())
            sup[surv_mask] = np.maximum(sup[surv_mask] - delta, cap_l)
            alive = surv_mask
        if not done and alive.any():
            # `levels` exhausted with survivors and no handover recorded
            # (cannot happen: the last iteration either drains or hands
            # over) — defensive: hand over a zero-width last level
            out.append(dict(
                t, surv=np.where(alive)[0], l1=np.zeros(0, np.int64),
                cap1=lo, sup_surv=sup[alive],
            ))
    return out


def _level_pad(n: int, align: int) -> int:
    """Level-stack padding: power-of-two-ish buckets.  Coarser buckets
    merge more survivor subgraphs into one stack, and stack merging is
    what amortizes the per-sweep loop overhead (sweeps are memory-bound
    reads of W gathered rows, so the padded-flop penalty of pow2 buckets
    stays secondary to running fewer, fatter level loops)."""
    return bucket(n, align)


def _probe_peel_width(group: List[Dict]) -> int:
    """First-sweep level-size probe (PR 5 satellite; replaces the static
    ``mm/8`` heuristic, closing the ROADMAP deferred item).

    The gather buffer only needs to fit the peel LEVELS the loop will
    see, and the host support snapshot already measures their shape: the
    survivor supports' value multiplicities are exactly the level sizes
    the first device sweeps peel.  Sweeps further in can merge levels
    (deltas push rows onto the subset's range floor), so the probe takes
    the largest single level AND the bottom-two cumulative mass per
    task; anything larger at runtime falls back to the mask-form kernel
    ON DEVICE (never the host), and the loop's measured ``max_level``
    refines the plan for the next same-signature run.
    """
    probe = 1
    for t in group:
        sup = np.asarray(t["sup_surv"])
        if sup.size == 0:
            continue
        _, counts = np.unique(sup, return_counts=True)
        probe = max(probe, int(counts.max()), int(counts[:2].sum()))
    return probe


def build_level_stack(group: List[Dict], cfg: ReceiptConfig,
                      backend: str, plan=None) -> Dict:
    """Assemble one shape group into the batched level-peel stacks
    (host-side work; overlapped with the previous group's device sweep
    by the double-buffered driver).

    Two stacks per group: the SURVIVOR stack ``a`` (G, mm, cc) the level
    loop peels, and the first-level stack ``a_l1`` (G, w1, cc) whose
    delta the launcher applies through one grouped butterfly kernel call
    before entering the loop.  Group tasks must carry the
    ``pre_peel_tasks`` fields (surv / l1 / cap1 / sup_surv).

    ``plan`` (an ``repro.api.ExecutionPlan``) quantizes every stack
    dimension — rows ``mm``, cols ``cc``, first-level width ``w1`` and
    the GROUP count — up to the nearest shape an earlier same-signature
    run compiled (dead padding rows/groups are no-ops in the level
    loop), and supplies the measured gather-buffer width for the
    resulting shape.  That makes the whole FD dispatch sequence
    shape-stable across graphs of the same signature: the jit cache hits
    instead of retracing per graph.  ``plan=None`` keeps the self-sized
    behavior.
    """
    row_align, col_align, w_align = _aligns(cfg, backend)
    sparse = backend in kops.SPARSE_BACKENDS
    n_real = len(group)
    mm = _level_pad(max(len(t["surv"]) for t in group), row_align)
    cc = _level_pad(max(max(t["sub"].n_v, 1) for t in group), col_align)
    w1 = pad_to_multiple(max(len(t["l1"]) for t in group), w_align)
    n_g = n_real
    if plan is not None:
        mm = plan.quantize_dim("fd_rows", mm)
        cc = plan.quantize_dim("fd_cols", cc)
        w1 = plan.quantize_dim("fd_l1", w1)
        n_g = plan.quantize_dim("fd_groups", n_real)

    a = np.zeros((n_g, mm, cc), np.float32)
    a_l1 = np.zeros((n_g, w1, cc), np.float32)
    sup0 = np.full((n_g, mm), np.inf, np.float64)
    nmem = np.zeros(n_g, np.int32)
    n_l1 = np.zeros(n_g, np.int32)
    los = np.zeros(n_g, np.float64)
    cap1 = np.zeros(n_g, np.float64)
    for k, t in enumerate(group):
        surv, l1 = t["surv"], t["l1"]
        nmem[k] = len(surv)
        n_l1[k] = len(l1)
        los[k] = t["lo"]
        cap1[k] = t["cap1"]
        sup0[k, : len(surv)] = t["sup_surv"]
        s = t["sub"]
        # scatter edges of survivor rows (compacted) and first-level rows
        surv_pos = np.full(s.n_u, -1, np.int64)
        surv_pos[surv] = np.arange(len(surv))
        l1_pos = np.full(s.n_u, -1, np.int64)
        l1_pos[l1] = np.arange(len(l1))
        es = surv_pos[s.edges_u] >= 0
        a[k, surv_pos[s.edges_u[es]], s.edges_v[es]] = 1.0
        ep = l1_pos[s.edges_u] >= 0
        a_l1[k, l1_pos[s.edges_u[ep]], s.edges_v[ep]] = 1.0

    # support-update cost model (the HUC argument applied to FD): pay the
    # (M, M) wedge contraction once when the B2 stack fits the budget,
    # stream sweeps through the grouped butterfly kernel when it cannot
    if cfg.fd_update_mode == "auto":
        update_mode = ("b2" if n_g * mm * mm <= cfg.fd_b2_cells
                       else "kernel")
    else:
        update_mode = cfg.fd_update_mode

    if cfg.peel_width is not None:
        peel_width = min(bucket(cfg.peel_width, w_align), mm)
    else:
        # measured-width policy (PR 5 satellite): a plan carrying the
        # max level an earlier same-signature run actually peeled at
        # this stack shape pins the buffer to it; otherwise the
        # first-sweep level-size probe sizes it from the host support
        # snapshot.  Gathered sweeps only touch W rows of A/B2 (sweeps
        # are memory-bound, not flop-bound), and an oversized level hits
        # the on-device mask-form fallback, never the host.
        hint = plan.fd_width_hint((mm, cc)) if plan is not None else None
        probe = hint if hint is not None else _probe_peel_width(group)
        peel_width = min(bucket(max(probe, w_align), w_align), mm)

    dv0 = a.sum(axis=1)
    alive0 = np.arange(mm)[None, :] < nmem[:, None]
    bk = cfg.kernel_blocks[2]
    row_ext = (batched_row_extents(a, bk)
               if sparse else np.zeros((n_g, mm), np.int32))
    row_ext_l1 = (batched_row_extents(a_l1, bk)
                  if sparse else np.zeros((n_g, w1), np.int32))
    return dict(
        group=group, a=a, a_l1=a_l1, sup0=sup0, nmem=nmem, n_l1=n_l1,
        los=los, cap1=cap1, dv0=dv0, alive0=alive0, row_ext=row_ext,
        row_ext_l1=row_ext_l1, mm=mm, cc=cc, w1=w1,
        peel_width=peel_width, update_mode=update_mode,
        padded_cells=n_g * (mm + w1) * cc, bytes=a.nbytes + a_l1.nbytes,
        used_cells=int(sum(len(t["members"]) * max(t["sub"].n_v, 1)
                           for t in group)),
    )


def _note_group_run(built: Dict, max_level_seen: int, stats: RunStats,
                    plan) -> None:
    """Fold one drained group's measured level shape into RunStats and
    the plan (the feedback half of the measured-width loop)."""
    stats.fd_peel_widths.append(int(built["peel_width"]))
    stats.fd_max_levels.append(int(max_level_seen))
    if max_level_seen > built["peel_width"]:
        stats.fd_mask_fallbacks += 1
    if plan is not None:
        plan.note_fd_level((built["mm"], built["cc"]), int(max_level_seen),
                           int(built["peel_width"]))


# ---------------------------------------------------------------------- #
# FD driver
# ---------------------------------------------------------------------- #
def receipt_fd(
    g: BipartiteGraph,
    subset_id: np.ndarray,
    init_support: np.ndarray,
    bounds: np.ndarray,
    cfg: ReceiptConfig,
    stats: RunStats,
    *,
    mesh=None,
    plan=None,
) -> np.ndarray:
    """Exact tip numbers by independent peeling of induced subgraphs.

    ``mesh``: a ``jax.sharding.Mesh`` runs each shape group's level loop
    under ``shard_map`` with subsets LPT-assigned to devices
    (``_run_level_groups_mesh``); tip numbers are identical to the
    single-device path and per-shard loads are reconciled into
    ``stats.fd_shard_rho`` / ``fd_shard_wedges`` (DESIGN.md §4).
    """
    if cfg.max_sweeps < 1:
        raise ValueError(
            f"max_sweeps must be >= 1 (got {cfg.max_sweeps}): the valve "
            "bounds one loop invocation; a sub-1 cap makes no progress")
    with span("fd") as sp:
        theta = np.zeros(g.n_u, np.float64)
        backend = cfg.backend or kops.default_backend()

        tasks = build_fd_tasks(g, subset_id, bounds, stats)
        if mesh is not None:
            theta = _run_level_groups_mesh(tasks, init_support, cfg, stats,
                                           theta, mesh, plan=plan)
        else:
            theta = _run_level_groups(tasks, init_support, cfg, backend,
                                      stats, theta, plan=plan)
    stats.time_fd = sp.seconds
    return theta


def _prepeel_groups(tasks, init_support, theta, stats, cfg, row_align,
                    col_align) -> List[List[Dict]]:
    """Host pre-peel of every task, then the survivors packed into
    equal-padded-shape groups (one ``fd.prepeel`` span)."""
    with span("fd.prepeel", levels=cfg.fd_prepeel_levels) as sp:
        pairs0 = stats.fd_prepeel_pairs
        tasks = pre_peel_tasks(tasks, init_support, theta, stats,
                               levels=cfg.fd_prepeel_levels)
        sp.set_metadata(pairs=stats.fd_prepeel_pairs - pairs0)
        groups = pack_by_shape(
            tasks,
            size_of=lambda t: (len(t["surv"]), max(t["sub"].n_v, 1)),
            weight_of=lambda t: t["wedges"],
            bucket=lambda n: _level_pad(n, row_align),
            bucket_cols=lambda n: _level_pad(n, col_align),
        )
    stats.fd_groups = len(groups)
    return groups


def _stack(k: int, group: List[Dict], cfg: ReceiptConfig, backend: str,
           plan) -> Dict:
    """``build_level_stack`` for group ``k`` under an ``fd.stack`` span."""
    with span("fd.stack", group=k) as sp:
        built = build_level_stack(group, cfg, backend, plan=plan)
        sp.set_metadata(bytes=built["bytes"])
    return built


def _run_level_groups(tasks, init_support, cfg, backend, stats, theta,
                      plan=None):
    """Pre-peel first levels on the host, group the SURVIVOR subgraphs by
    padded shape, and dispatch each group through the batched level-peel
    loop — double-buffering host stack assembly against device compute."""
    blocks = cfg.kernel_blocks
    row_align, col_align, _ = _aligns(cfg, backend)
    sparse = backend in kops.SPARSE_BACKENDS

    groups = _prepeel_groups(tasks, init_support, theta, stats, cfg,
                             row_align, col_align)
    padded = used = 0
    pending = None           # (built, device outputs) one group in flight

    def launch(built):
        with span("fd.launch", bytes=built["bytes"]):
            g_n, mm, w1 = built["a"].shape[0], built["mm"], built["w1"]
            fault_point("kernel_launch", KernelBackendError,
                        dispatch="fd_level", backend=backend,
                        group_shape=(g_n, mm))
            a_dev = jnp.asarray(built["a"], cfg.dtype)
            sup_dev = jnp.asarray(built["sup0"], cfg.dtype)
            alive_dev = jnp.asarray(built["alive0"])
            dv_dev = jnp.asarray(built["dv0"], jnp.float32)
            lo_dev = jnp.asarray(built["los"], jnp.float32)
            rext_dev = jnp.asarray(built["row_ext"])
            # first-level delta: ONE grouped kernel call sized to survivors
            # (output side) x first level (gathered side)
            a_l1 = jnp.asarray(built["a_l1"], cfg.dtype)
            valid1 = (jnp.arange(w1)[None, :]
                      < jnp.asarray(built["n_l1"])[:, None])
            ids_s = jnp.broadcast_to(
                jnp.arange(mm, dtype=jnp.int32)[None, :], (g_n, mm))
            ids_l1 = jnp.broadcast_to(
                mm + jnp.arange(w1, dtype=jnp.int32)[None, :], (g_n, w1))
            if sparse:
                bi, bj, _bk = blocks
                kma = rext_dev.reshape(g_n, -1, bi).max(axis=2).astype(
                    jnp.int32)
                kmb = jnp.asarray(built["row_ext_l1"]).reshape(
                    g_n, -1, bj).max(axis=2).astype(jnp.int32)
            else:
                kma = kmb = None
            delta1 = kops.butterfly_update_batched(
                a_dev, a_l1, valid1, ids_s, ids_l1,
                backend=backend, blocks=blocks, kmax_a=kma, kmax_b=kmb,
            )
            cap1 = jnp.asarray(built["cap1"], cfg.dtype)
            sup1 = jnp.maximum(sup_dev - delta1, cap1[:, None])
            out = batched_level_loop(
                a_dev, rext_dev, sup1, alive_dev, dv_dev, lo_dev,
                backend=backend, blocks=blocks,
                peel_width=built["peel_width"], max_sweeps=cfg.max_sweeps,
                update_mode=built["update_mode"],
            )
            stats.device_loop_calls += 1
            built["_loop_args"] = (a_dev, rext_dev, lo_dev)
        return out

    def drain(built, out):
        # one blocking sync per group in the common case; a loop that
        # exits via the max_sweeps safety valve with survivors left is
        # re-entered (the valve caps ONE invocation, not the schedule —
        # same contract as the CD and ParB drivers)
        th_acc = None
        prev_alive = built["alive0"]
        max_level_seen = 0
        while True:
            sup, alive, dv, th, rho, wedges, max_lev, _sweeps = out
            th_h, alive_h, rho_h, wedges_h, max_lev_h = fetch(
                stats, (th, alive, rho, wedges, max_lev), "fd.drain")
            d_rho = int(np.asarray(rho_h).sum())
            stats.rho_fd += d_rho
            stats.wedges_fd += int(np.asarray(wedges_h, np.float64).sum())
            max_level_seen = max(max_level_seen,
                                 int(np.asarray(max_lev_h).max()))
            newly_dead = prev_alive & ~alive_h
            th_h = np.asarray(th_h, np.float64)
            th_acc = (np.where(newly_dead, th_h, th_acc)
                      if th_acc is not None
                      else np.where(newly_dead, th_h, 0.0))
            if not alive_h.any() or d_rho == 0:
                break
            prev_alive = alive_h
            a_dev, rext_dev, lo_dev = built["_loop_args"]
            out = batched_level_loop(
                a_dev, rext_dev, sup, alive, dv, lo_dev,
                backend=backend, blocks=blocks,
                peel_width=built["peel_width"], max_sweeps=cfg.max_sweeps,
                update_mode=built["update_mode"],
            )
            stats.device_loop_calls += 1
        _note_group_run(built, max_level_seen, stats, plan)
        for k, t in enumerate(built["group"]):
            theta[t["members"][t["surv"]]] = th_acc[k, : built["nmem"][k]]

    for k, group in enumerate(groups):
        built = _stack(k, group, cfg, backend, plan)
        padded += built["padded_cells"]
        used += built["used_cells"]
        out = launch(built)                     # async dispatch
        if pending is not None:
            drain(*pending)
        pending = (built, out)                  # fetch AFTER next build
    if pending is not None:
        drain(*pending)

    stats.fd_padding_waste = 1.0 - used / padded if padded else 0.0
    return theta


def _run_level_groups_mesh(tasks, init_support, cfg, stats, theta, mesh,
                           plan=None):
    """End-to-end mesh-sharded FD (DESIGN.md §4): the same pipeline as
    ``_run_level_groups`` — host first-level pre-peel, shape-group
    packing, double-buffered group dispatch, ONE blocking sync per group
    — with each group's level loop running under ``shard_map``
    (`core/distributed.distributed_fd_level_peel`): subsets LPT-assigned
    to mesh devices (`core/distributed.shard_level_group`), zero
    collectives, every shard's while_loop exiting as soon as its local
    subsets drain.  Per group, an ``fd.shard`` span covers the LPT layout
    and the sharded upload of the survivor and first-level stacks, and
    ``fd.launch`` only the dispatch.  Per-shard sweep/wedge loads and
    uploaded stack bytes accumulate into ``stats.fd_shard_rho`` /
    ``fd_shard_wedges`` / ``fd_shard_bytes`` — the reconciled
    multi-shard report of the run.

    The shard_map local body computes with the pure-jnp oracle backend
    ("xla"), so tip numbers are bit-identical to the single-device path
    (integer regime, DESIGN.md §8)."""
    from ..distributed import (
        distributed_fd_level_peel,
        fd_stack_sharding,
        shard_level_group,
    )

    backend = "xla"                   # shard_map local compute path
    row_align, col_align, _ = _aligns(cfg, backend)
    n_shards = mesh.size

    groups = _prepeel_groups(tasks, init_support, theta, stats, cfg,
                             row_align, col_align)
    stats.fd_shards = n_shards
    shard_rho = np.zeros(n_shards, np.int64)
    shard_wedges = np.zeros(n_shards, np.float64)
    lpt_loads = np.zeros(n_shards, np.float64)   # cross-group carryover
    shard_bytes = np.zeros(n_shards, np.int64)   # stack bytes per shard
    stack_sharding = fd_stack_sharding(mesh)

    padded = used = 0
    pending = None           # (built, sharded, slots, out) one in flight

    def place(built):
        # LPT layout and the sharded upload of the group's two stacks;
        # cap-exit re-entries reuse the device-resident survivor stack
        nonlocal lpt_loads, shard_bytes
        with span("fd.shard", shards=n_shards) as sp:
            sharded, slots = shard_level_group(built, n_shards,
                                               init_loads=lpt_loads)
            lpt_loads = lpt_loads + sharded["shard_load"]
            placed = np.zeros(n_shards, np.int64)
            for key in ("a", "a_l1"):
                arr = jax.device_put(sharded[key], stack_sharding)
                for piece in arr.addressable_shards:
                    placed[piece.index[0].start // sharded["per_shard"]] += (
                        piece.data.nbytes)
                sharded[key] = arr
            shard_bytes += placed
            sp.set_metadata(bytes=int(placed.sum()),
                            max_shard_bytes=int(placed.max()))
        return sharded, slots

    def launch(built, sharded):
        with span("fd.launch", bytes=built["bytes"]):
            out = distributed_fd_level_peel(
                mesh, sharded["a"], sharded["sup"], sharded["alive"],
                sharded["dv"], sharded["lo"],
                a_l1=sharded["a_l1"], n_l1=sharded["n_l1"],
                cap1=sharded["cap1"],
                update_mode=built["update_mode"],
                peel_width=built["peel_width"],
                max_sweeps=cfg.max_sweeps, full_state=True,
            )
        stats.device_loop_calls += 1
        return out

    def drain(built, sharded, slots, out):
        # one blocking sync per group in the common case; a max_sweeps
        # cap-exit with survivors left re-enters with the carried state
        # (same contract as the local driver and the CD drivers)
        nonlocal shard_rho, shard_wedges
        per_shard = sharded["per_shard"]
        th_acc = None
        prev_alive = sharded["alive"]
        while True:
            sup, alive, dv, th, rho, wedges = out
            th_h, alive_h, rho_h, wedges_h = fetch(
                stats, (th, alive, rho, wedges), "fd.drain")
            d_rho = int(np.asarray(rho_h).sum())
            stats.rho_fd += d_rho
            stats.wedges_fd += int(np.asarray(wedges_h, np.float64).sum())
            shard_rho += np.asarray(rho_h, np.int64).reshape(
                n_shards, per_shard).sum(axis=1)
            shard_wedges += np.asarray(wedges_h, np.float64).reshape(
                n_shards, per_shard).sum(axis=1)
            newly_dead = prev_alive & ~np.asarray(alive_h)
            th_h = np.asarray(th_h, np.float64)
            th_acc = (np.where(newly_dead, th_h, th_acc)
                      if th_acc is not None
                      else np.where(newly_dead, th_h, 0.0))
            if not np.asarray(alive_h).any() or d_rho == 0:
                break
            prev_alive = np.asarray(alive_h)
            # the first-level delta is already applied: re-enter bare
            out = distributed_fd_level_peel(
                mesh, sharded["a"], sup, alive, dv, sharded["lo"],
                update_mode=built["update_mode"],
                peel_width=built["peel_width"],
                max_sweeps=cfg.max_sweeps, full_state=True,
            )
            stats.device_loop_calls += 1
        for s, t_idx in enumerate(slots):
            if t_idx < 0:
                continue
            t = built["group"][t_idx]
            nm = int(built["nmem"][t_idx])
            theta[t["members"][t["surv"]]] = th_acc[s, :nm]

    for k, group in enumerate(groups):
        # plan hints apply (shape quantization + measured widths); the
        # measured-level feedback itself is recorded on the local path
        # only — the sharded loop keeps its 6-field state contract
        built = _stack(k, group, cfg, backend, plan)
        sharded, slots = place(built)
        out = launch(built, sharded)            # async dispatch
        padded += sharded["a"].size + sharded["a_l1"].size
        used += built["used_cells"]
        if pending is not None:
            drain(*pending)
        pending = (built, sharded, slots, out)  # fetch AFTER next build
    if pending is not None:
        drain(*pending)

    stats.fd_padding_waste = 1.0 - used / padded if padded else 0.0
    stats.fd_shard_rho = [int(x) for x in shard_rho]
    stats.fd_shard_wedges = [float(x) for x in shard_wedges]
    stats.fd_shard_bytes = [int(x) for x in shard_bytes]
    return theta
