"""Baselines on the unified peel core (apples-to-apples comparators).

PARBUTTERFLY-style batch peeling shares the engine with RECEIPT: same
kernels, same device-resident ``while_loop`` core (`engine/peel_loop`),
only the schedule differs — **min-peel** (``minmode=True``) instead of
CD's range-peel.  The only independent variable left for Table 3 is the
number of synchronization rounds, which is the paper's argument.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np

from ...kernels import ops as kops
from ...utils.spans import fetch, span
from ..graph import BipartiteGraph
from .peel_loop import (
    _INF,
    DeviceGraph,
    ReceiptConfig,
    RunStats,
    bucket,
    device_peel_loop,
    host_sweep,
    residual_dv,
    support_all,
)

__all__ = ["parb_tip_decompose"]


def parb_tip_decompose(
    g: BipartiteGraph, cfg: Optional[ReceiptConfig] = None
) -> Tuple[np.ndarray, RunStats]:
    """PARBUTTERFLY-style batch peeling on the dense engine.

    Identical kernels/dispatch machinery to RECEIPT, but each sweep peels
    only the CURRENT MINIMUM support set (the ParB schedule).  This is the
    apples-to-apples wall-clock baseline for Table 3: the only difference
    from RECEIPT is the number of synchronization rounds.  The same
    device-resident while_loop engine drives it (``minmode=True``: the
    min-support threshold is recomputed ON DEVICE each sweep, and theta is
    recorded in the loop state), including terminal-sweep elision;
    ``cfg.device_loop=False`` preserves the blocking host schedule.
    """
    cfg = cfg or ReceiptConfig()
    stats = RunStats()
    backend = cfg.backend or kops.default_backend()
    blocks = cfg.kernel_blocks
    sparse = backend in kops.SPARSE_BACKENDS

    dg = DeviceGraph(g, np.arange(g.n_u), cfg, stats=stats)
    stats.wedges_pvbcnt = g.counting_wedge_bound()
    alive = jnp.zeros(dg.rows_pad, bool).at[: dg.n_rows].set(True)
    support = support_all(dg.a, alive, dg.ids,
                          dg.kmax if sparse else None,
                          backend=backend, blocks=blocks)
    support = jnp.where(alive, support, _INF)
    dv = dg.dv0

    theta = np.zeros(g.n_u, np.int64)
    with span("parb") as sp:
        if cfg.device_loop:
            theta_dev = jnp.zeros(dg.rows_pad, jnp.float32)
            # min-support sets are small (ParB's whole problem is that
            # there are MANY of them): start at one kernel tile and let
            # the overflow path double on demand
            peel_width = min(dg.rows_pad, bucket(
                cfg.peel_width if cfg.peel_width is not None else blocks[1],
                blocks[1],
            ))
            while True:
                (support, alive, dv, theta_dev, peeled, d_rho, d_wedges, _h,
                 d_elided, _c, _s, ovf) = device_peel_loop(
                    dg.a, dg.ids, dg.row_ext, dg.kmax, support, alive, dv,
                    theta_dev, 0.0, 0.0, 0.0,
                    backend=backend, blocks=blocks, use_huc=False,
                    peel_width=peel_width, max_sweeps=cfg.max_sweeps,
                    minmode=True,
                )
                stats.device_loop_calls += 1
                (peeled_np, alive_np, th_np, d_rho, d_wedges, d_elided,
                 ovf_h) = fetch(
                    stats, (peeled, alive, theta_dev, d_rho, d_wedges,
                            d_elided, ovf), "parb.loop")
                stats.rho_cd += int(d_rho)
                stats.wedges_cd += int(d_wedges)
                stats.elided_sweeps += int(d_elided)
                sel = peeled_np[: dg.n_rows].nonzero()[0]
                theta[dg.members[sel]] = np.round(
                    th_np[: dg.n_rows][sel]).astype(np.int64)
                if not bool(ovf_h):
                    if not alive_np.any():
                        break
                    # max_sweeps cap-exit with survivors left (the host
                    # schedule has no cap): re-enter — the loop reseeds its
                    # sweep counter.  d_rho == 0 means no progress is
                    # possible (max_sweeps <= 0): bail instead of spinning.
                    if int(d_rho) == 0:
                        break
                    continue
                # overflow: replay the min-sweep on the host, widen, re-enter
                stats.overflow_fallbacks += 1
                sup_np = np.asarray(fetch(stats, support, "parb.replay"),
                                    np.float64)
                mn = float(np.min(np.where(alive_np, sup_np, np.inf)))
                support, alive, info = host_sweep(
                    dg, cfg, stats, support, alive, mn + 1.0, mn, backend,
                    blocks, allow_huc=False)
                if info is not None:
                    sel = info["peel_np"][: dg.n_rows].nonzero()[0]
                    theta[dg.members[sel]] = int(mn)
                dv = residual_dv(dg.a, alive)
                peel_width = min(dg.rows_pad, peel_width * 2)
        else:
            while True:
                n_alive = int(fetch(stats, jnp.sum(alive), "parb.alive"))
                if n_alive == 0:
                    break
                mn = float(fetch(
                    stats, jnp.min(jnp.where(alive, support, _INF)),
                    "parb.min"))
                support, alive, info = host_sweep(
                    dg, cfg, stats, support, alive, mn + 1.0, mn, backend,
                    blocks, allow_huc=False)
                if info is None:
                    break
                sel = info["peel_np"][: dg.n_rows].nonzero()[0]
                theta[dg.members[sel]] = int(mn)
    stats.time_cd = sp.seconds
    return theta, stats
