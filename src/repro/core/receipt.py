"""RECEIPT — compatibility facade over `core/engine/` and `repro.api`.

The engine that used to live here in one 1000-line module was split into
the `core/engine/` package (PR 2), and the public surface moved to the
`repro.api` plan/compile/execute service layer (PR 5).  Every name this
module historically exported keeps working and produces BIT-IDENTICAL
tip numbers (tests/test_api_compat.py pins it):

* ``tip_decompose`` is now a thin wrapper over ``repro.api.decompose``
  — each call plans and executes on a fresh Executor, so legacy callers
  see byte-for-byte the pre-PR-5 engine behavior (hold an
  ``repro.api.Executor`` to get the cross-graph executable cache);
* ``receipt_cd`` / ``receipt_fd`` / ``parb_tip_decompose`` remain the
  phase-level engine entry points the service layer itself drives;
* ``ReceiptConfig`` remains the engine-layer kwarg config —
  ``repro.api.EngineConfig`` is its frozen, serializable, strictly
  validated replacement for new code.

New code should import from ``repro.api`` (drivers) and
``repro.core.engine`` (engine internals).
"""
from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

from .engine import (
    DeviceGraph,
    ReceiptConfig,
    RunStats,
    batched_level_loop,
    bucket,
    cd_checkpoint_state,
    device_cd_graph_loop,
    device_peel_loop,
    find_hi_np,
    host_sweep,
    parb_tip_decompose,
    receipt_cd,
    receipt_fd,
)
from .graph import BipartiteGraph


def tip_decompose(
    g: BipartiteGraph, cfg: Optional[ReceiptConfig] = None,
    *, side: str = "U", mesh=None,
) -> Tuple[np.ndarray, RunStats]:
    """Full RECEIPT tip decomposition — legacy signature, routed through
    the `repro.api` service layer (planning included; a fresh Executor
    per call keeps behavior bit-identical to the pre-PR-5 engine).

    Returns (theta int64[n_side], RunStats) exactly as before; see
    ``repro.core.engine.tip_decompose`` for the knob/mesh semantics and
    ``repro.api`` for the plan/compile/execute surface superseding this.
    """
    from .. import api

    td = api.decompose(g, cfg, side=side, mesh=mesh)
    return td.theta, td.stats
from .engine.peel_loop import (  # noqa: F401 (compat)
    apply_delta,
    residual_dv,
    support_all,
    support_delta,
    sweep_info,
)

# pre-split private aliases (kept so downstream forks / notebooks that
# reached into the module keep working)
_DeviceGraph = DeviceGraph
_cd_device_loop = device_peel_loop
_host_sweep = host_sweep
_bucket = bucket
_find_hi_np = find_hi_np
_support_all = support_all
_support_delta = support_delta
_sweep_info = sweep_info
_residual_dv = residual_dv
_apply_delta = apply_delta

__all__ = [
    "ReceiptConfig",
    "RunStats",
    "tip_decompose",
    "receipt_cd",
    "receipt_fd",
    "parb_tip_decompose",
    "cd_checkpoint_state",
    "DeviceGraph",
    "device_peel_loop",
    "device_cd_graph_loop",
    "batched_level_loop",
    "host_sweep",
    "bucket",
    "find_hi_np",
]
