"""RECEIPT peel engine package (DESIGN.md sections 2 and 2.2).

One parameterized device-resident sweep core (`peel_loop.py`) drives
every schedule in the repo:

* `cd.py`        — RECEIPT CD (Alg. 3), range-peel mode
* `fd.py`        — RECEIPT FD (Alg. 4), batched level-peel mode
* `baselines.py` — the ParButterfly min-peel baseline
* `wing.py`      — wing / bitruss decomposition on the EDGE axis
  (``DELTA_RULES["edge"]``, DESIGN.md §10): the same CD range-peel and
  batched level-FD loops over per-edge butterfly supports

``tip_decompose`` below is the top-level driver (CD then FD, with the
degree-sort relabeling and the side="V" transpose).  `core/receipt.py`
remains as a compatibility facade re-exporting this package's public
API, so existing imports keep working.
"""
from __future__ import annotations

import contextlib
from typing import Optional, Tuple

import jax
import numpy as np

from ...utils.spans import span
from ..graph import BipartiteGraph
from .baselines import parb_tip_decompose
from .cd import cd_checkpoint_state, find_hi_np, receipt_cd
from .fd import build_fd_tasks, build_level_stack, receipt_fd
from .peel_loop import (
    DeviceGraph,
    ReceiptConfig,
    RunStats,
    batched_level_loop,
    bucket,
    device_cd_graph_loop,
    device_peel_loop,
    host_sweep,
)
from .refresh import (repeel_tip_prefix, repeel_wing_prefix,
                      synthesize_bounds)
from .tiled import receipt_tiled
from .wing import (
    device_wing_graph_loop,
    receipt_wing_cd,
    receipt_wing_fd,
    wing_decompose_engine,
)

__all__ = [
    "ReceiptConfig",
    "RunStats",
    "tip_decompose",
    "cd_device",
    "wing_decompose_engine",
    "receipt_cd",
    "receipt_fd",
    "receipt_wing_cd",
    "receipt_wing_fd",
    "receipt_tiled",
    "repeel_tip_prefix",
    "synthesize_bounds",
    "repeel_wing_prefix",
    "device_wing_graph_loop",
    "parb_tip_decompose",
    "cd_checkpoint_state",
    "find_hi_np",
    "build_fd_tasks",
    "build_level_stack",
    "DeviceGraph",
    "device_peel_loop",
    "device_cd_graph_loop",
    "batched_level_loop",
    "host_sweep",
    "bucket",
]


def cd_device(mesh):
    """The device CD runs on under ``mesh``: the mesh's first device,
    so that CD never lands on a device outside the mesh (JAX's default
    device need not be one of its devices)."""
    return mesh.devices.flat[0]


def tip_decompose(
    g: BipartiteGraph, cfg: Optional[ReceiptConfig] = None,
    *, side: str = "U", mesh=None, plan=None,
) -> Tuple[np.ndarray, RunStats]:
    """Full RECEIPT tip decomposition of one side of ``g``.

    side="V" peels the other vertex set (the paper decomposes both sides
    of every dataset — *U/*V rows of Table 3); implemented by transposing
    the bipartite graph, which is exact by symmetry.

    ``mesh``: a ``jax.sharding.Mesh`` routes the FD phase through the
    sharded level-peel driver (`core/distributed.py` — subsets
    LPT-assigned to devices, zero collectives, per-shard stats
    reconciled into the returned RunStats).  CD runs single-device
    either way, under a mesh on ``cd_device(mesh)`` (its multi-device
    twin ``distributed_cd_fused_loop`` is a separate entry point: CD is
    one global range loop, not an embarrassingly parallel stack).  Tip
    numbers are identical with and without a mesh (DESIGN.md §4).

    ``plan``: an ``repro.api.ExecutionPlan`` — supplies measured peel
    widths and shape quantization from earlier same-signature runs and
    receives this run's measurements (DESIGN.md §6).  ``plan=None``
    (every pre-PR-5 call site) self-sizes exactly as before.

    Returns (theta int64[n_side], RunStats).
    """
    cfg = cfg or ReceiptConfig()
    if side == "V":
        g = g.transposed()
    elif side != "U":
        raise ValueError(f"side must be 'U' or 'V', got {side!r}")
    stats = RunStats()
    if cfg.degree_sort:
        # relabel for tile density; map results back at the end
        with span("relabel"):
            du = g.degrees_u()
            perm_u = np.argsort(-du, kind="stable")
            dv = g.degrees_v()
            perm_v = np.argsort(-dv, kind="stable")
            inv_u = np.empty_like(perm_u)
            inv_u[perm_u] = np.arange(g.n_u)
            inv_v = np.empty_like(perm_v)
            inv_v[perm_v] = np.arange(g.n_v)
            g_work = BipartiteGraph.from_edges(
                g.n_u, g.n_v, inv_u[g.edges_u], inv_v[g.edges_v]
            )
    else:
        perm_u = np.arange(g.n_u)
        g_work = g

    if cfg.representation == "tiled":
        # blocked-sparse whole-graph level peel: same theta (tip numbers
        # are canonical across exact schedules), never materializes the
        # dense biadjacency — the route above the dense memory ceiling
        theta_work = receipt_tiled(g_work, cfg, stats, plan=plan)
    else:
        with (jax.default_device(cd_device(mesh)) if mesh is not None
              else contextlib.nullcontext()):
            subset_id, init_support, bounds, _ = receipt_cd(
                g_work, cfg, stats, plan=plan)
        theta_work = receipt_fd(g_work, subset_id, init_support, bounds, cfg,
                                stats, mesh=mesh, plan=plan)

    theta = np.zeros(g.n_u, np.int64)
    theta[perm_u] = np.round(theta_work).astype(np.int64)
    return theta, stats
