"""Tiled-sparse whole-graph tip decomposition (DESIGN.md section 9).

``receipt_tiled`` is the engine behind ``representation="tiled"``: the
path a graph takes when its padded dense biadjacency would not fit the
memory budget (or the Planner's cost model measures the tiled kernels
as cheaper).  It runs the whole-graph EXACT schedule — simultaneous
level peel from the initial per-vertex butterfly counts with ``lo = 0``
— over the nonzero-tile list (`core.graph.TiledGraph` +
`kernels.butterfly_tiled`), never materializing a ``(rows_pad,
cols_pad)`` matrix on host or device.

Why this is the SAME decomposition the dense CD+FD pipeline computes:
tip numbers are canonical — any exact peel schedule yields bit-identical
theta.  Whole-graph level peel with ``lo = 0`` is the ParButterfly
schedule, already used by ``Executor.map`` and proved exact in
DESIGN.md section 2.2:

* a butterfly contains exactly TWO U vertices, so when a peel set S is
  removed the support subtraction ``delta[x] = sum_{y in S, y != x}
  C(W[x, y], 2)`` charges each butterfly {x, y} to exactly one peeled
  partner — no double subtraction, with the adjacency held STATIC
  during the sweep;
* ``W[x, y] = |N(x) /\\ N(y)|`` depends only on rows x and y, so the
  between-sweep regather (zeroing peeled rows and columns whose
  residual degree dropped below 2 — ``regather_tiles``) never changes
  an alive pair's wedge count (the DGM exactness argument).

The sweep loop is one jitted ``lax.while_loop`` whose body reuses the
shared schedule pieces from ``peel_loop`` (``level_threshold`` /
``select_peel`` / ``record_theta`` / ``apply_delta`` / ``peel_cost``)
with the tiled update kernel supplying the delta.  The host driver runs
the loop in SEGMENTS of ``cfg.tiled_compact_every`` sweeps (further
bounded by the ``cfg.max_sweeps`` valve): after each segment it
scatters the newly-assigned theta out and, once the alive-row fraction
drops to ``cfg.tiled_compact_ratio``, REBUILDS the slot list from the
survivors — shapes are static inside a dispatch, so without the rebuild
every sweep would pay O(initial n_slots) forever.  Carried supports are
the loop's clamped values (``apply_delta`` caps at the running level),
so recompaction preserves the monotone-level schedule exactly.

Shape discipline: rows/cols pad to the tile block, then bucket
(power-of-two-ish); with a plan attached the bucketed dims and the slot
count quantize through ``plan.quantize_dim`` ("tiled_rows" /
"tiled_cols" / "tiled_slots") so repeat runs of same-regime graphs hit
the executable cache — ``TiledGraph.from_graph(pad_slots_to=...)``
appends provably-inert zero filler slots to reach the quantized count.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...kernels import butterfly_tiled as ktiled
from ...kernels import ops as kops
from ...utils.spans import fetch, span
from ..graph import BipartiteGraph, TiledGraph
from .peel_loop import (
    ReceiptConfig,
    RunStats,
    apply_delta,
    bucket,
    level_threshold,
    peel_cost,
    record_theta,
    select_peel,
)

__all__ = ["receipt_tiled", "tiled_blocks", "build_tiled"]


def tiled_blocks(cfg: ReceiptConfig) -> Tuple[int, int]:
    """(block_rows, block_k) of the tiled layout for a config.

    The pallas kernel's B-side gather mirrors row bands against column
    bands of the SAME slot list, so the row block must cover both the
    bi and bj roles of the dense kernels: ``max(bi, bj)``.  The xla
    streaming oracle has no MXU tile constraint — 8 keeps its per-band
    working set (and the host tile list) small.
    """
    backend = kops.resolve_backend(cfg.backend)
    bi, bj, bk = (int(b) for b in cfg.kernel_blocks)
    if backend == "xla":
        return 8, 8
    return max(bi, bj), bk


def build_tiled(g: BipartiteGraph, cfg: ReceiptConfig,
                plan=None) -> TiledGraph:
    """Build the engine's ``TiledGraph`` with plan-quantized padding."""
    br, bc = tiled_blocks(cfg)
    rows_pad = bucket(max(g.n_u, 1), br)
    cols_pad = bucket(max(g.n_v, 1), bc)
    if plan is not None:
        rows_pad = plan.quantize_dim("tiled_rows", rows_pad)
        cols_pad = plan.quantize_dim("tiled_cols", cols_pad)
    tg = TiledGraph.from_graph(g, block_rows=br, block_k=bc,
                               rows_pad=rows_pad, cols_pad=cols_pad)
    if plan is not None:
        slots = plan.quantize_dim("tiled_slots", bucket(tg.n_slots, 8))
        if slots > tg.n_slots:
            tg = TiledGraph.from_graph(
                g, block_rows=br, block_k=bc, rows_pad=rows_pad,
                cols_pad=cols_pad, pad_slots_to=slots)
    return tg


@functools.partial(
    jax.jit,
    static_argnames=("backend", "max_sweeps", "regather_every",
                     "n_col_tiles"))
def _tiled_peel_loop(td, slot_live, srow, scol, sptr, pos, support, alive,
                     theta, dv, *, backend, max_sweeps, regather_every,
                     n_col_tiles):
    """One device invocation of the tiled level-peel loop.

    Carry: (td, slot_live, support, alive, theta, dv, wedges, sweeps).
    Exits when no row is alive or the ``max_sweeps`` valve trips; the
    host driver inspects ``alive`` and re-enters on a valve exit.
    """
    f32 = jnp.float32

    def cond(carry):
        _td, _sl, _sup, al, _th, _dv, _wed, sweeps = carry
        return jnp.logical_and(jnp.any(al), sweeps < max_sweeps)

    def body(carry):
        td, sl, sup, al, th, dvv, wed, sweeps = carry
        hi, cap = level_threshold(sup, al, 0.0)
        peel = select_peel(sup, al, hi)
        peelf = peel.astype(f32)
        delta = kops.butterfly_update_tiled(
            td, srow, scol, sptr, pos, sl, peelf, backend=backend)
        # dynamic wedge charge of this peel set: column sums of the
        # peeled rows against the residual degrees (peel_cost identity)
        csum = ktiled.masked_colsum_tiled(td, srow, scol, pos, peelf)
        wed = wed + peel_cost(csum, dvv)
        th = record_theta(th, peel, cap)
        # Alg. 2 line 13: cap survivor supports at the CURRENT level so
        # the peel level is monotone — a survivor whose butterflies all
        # sat on this peel set still has tip number >= cap (it outlived
        # the cap-level peel), and next sweep's min is then >= cap.
        sup, al = apply_delta(sup, al, peel, delta, cap)
        dvv = dvv - csum
        alf = al.astype(f32)
        colf = (dvv >= 2.0).astype(f32)
        if regather_every == 1:
            td, sl = ktiled.regather_tiles(td, srow, scol, alf, colf)
        else:
            td, sl = jax.lax.cond(
                sweeps % regather_every == regather_every - 1,
                lambda t, s: ktiled.regather_tiles(t, srow, scol, alf,
                                                   colf),
                lambda t, s: (t, s),
                td, sl)
        return td, sl, sup, al, th, dvv, wed, sweeps + 1

    wed0 = jnp.zeros((), f32)
    carry = (td, slot_live, support, alive, theta, dv, wed0,
             jnp.int32(0))
    return jax.lax.while_loop(cond, body, carry)


def receipt_tiled(
    g_work: BipartiteGraph,
    cfg: ReceiptConfig,
    stats: RunStats,
    plan=None,
) -> np.ndarray:
    """Whole-graph tiled tip decomposition of the U side of ``g_work``.

    Returns theta float64[n_u] in ``g_work`` labels (the ``tip_decompose``
    driver handles side transposition and degree-sort unmapping, exactly
    as for the dense CD+FD pipeline).
    """
    with span("tiled") as sp:
        backend = kops.resolve_backend(cfg.backend)
        n_u = g_work.n_u
        stats.wedges_pvbcnt = g_work.counting_wedge_bound()
        stats.num_subsets = 1
        theta_out = np.zeros(n_u, np.float64)
        cur_ids = np.arange(n_u, dtype=np.int64)
        # host DGM pre-compaction: degree-<2 columns complete no wedge
        sub, _v_map = g_work.induced_on_u(cur_ids, min_degree_v=2)
        stats.dgm_compactions += 1
        seg_sweeps = max(1, min(cfg.max_sweeps, cfg.tiled_compact_every))
        support_carry = None   # None until the first device count

        while True:
            # (re)build the slot list for the current survivor graph.  The
            # peel state carries over: support values are the loop's CLAMPED
            # supports (capped at the running level by apply_delta, exactly
            # the oracle's Alg. 2 line 13), so they must be carried, never
            # recounted — a recount could fall below the running level and
            # break cap monotonicity.
            tg = build_tiled(sub, cfg, plan=plan)
            td = jnp.asarray(tg.tile_data)
            srow = jnp.asarray(tg.srow)
            scol = jnp.asarray(tg.scol)
            sptr = jnp.asarray(tg.sptr)
            pos = jnp.asarray(tg.pos)
            sl = ktiled.slot_liveness(td)
            rows_pad = tg.rows_pad
            n_cur = sub.n_u

            alive = jnp.arange(rows_pad) < n_cur
            dv = ktiled.colsum_tiled(td, scol, tg.n_col_tiles)
            if support_carry is None:
                with span("tiled.count") as sp_count:
                    support = kops.butterfly_update_tiled(
                        td, srow, scol, sptr, pos, sl,
                        alive.astype(jnp.float32), backend=backend)
                stats.time_count += sp_count.seconds
            else:
                sup_host = np.zeros(rows_pad, np.float32)
                sup_host[:n_cur] = support_carry
                support = jnp.asarray(sup_host)
            theta = jnp.zeros(rows_pad, jnp.float32)
            prev_alive = np.ones(n_cur, dtype=bool)

            done = False
            while True:
                (td, sl, support, alive, theta, dv, wed,
                 sweeps) = _tiled_peel_loop(
                    td, sl, srow, scol, sptr, pos, support, alive, theta,
                    dv, backend=backend, max_sweeps=seg_sweeps,
                    regather_every=cfg.tiled_regather_every,
                    n_col_tiles=tg.n_col_tiles)
                stats.device_loop_calls += 1
                # the support snapshot rides along for a host recompaction
                n_sweeps, wed_h, alive_host, theta_host, sup_h = fetch(
                    stats, (sweeps, wed, alive, theta, support),
                    "tiled.segment")
                n_sweeps = int(n_sweeps)
                stats.rho_fd += n_sweeps
                stats.wedges_fd += int(round(float(wed_h)))
                stats.dgm_device_compactions += (
                    n_sweeps // cfg.tiled_regather_every)
                alive_host = np.asarray(alive_host)[:n_cur]
                theta_host = np.asarray(theta_host)[:n_cur]
                died = prev_alive & ~alive_host
                theta_out[cur_ids[died]] = theta_host[died]
                prev_alive = alive_host
                n_alive = int(alive_host.sum())
                if n_alive == 0:
                    done = True
                    break
                if (cfg.tiled_compact_ratio > 0.0
                        and n_alive <= cfg.tiled_compact_ratio * n_cur):
                    # host recompaction: rebuild the slot list from the
                    # survivors so per-sweep cost tracks the residual graph
                    # (static shapes keep dead slots in every dispatch
                    # until this rebuild — the host half of the tiled DGM)
                    keep = np.where(alive_host)[0]
                    support_carry = np.asarray(sup_h)[:n_cur][keep]
                    cur_ids = cur_ids[keep]
                    sub, _v_map = sub.induced_on_u(keep, min_degree_v=2)
                    stats.dgm_compactions += 1
                    break
            if done:
                break
        stats.sweeps_per_subset.append(stats.rho_fd)
        stats.subset_sizes.append(n_u)
    stats.time_fd += sp.seconds - stats.time_count
    return theta_out
