"""The unified device-resident peel core (DESIGN.md section 2).

ONE parameterized sweep engine drives every peel schedule in the repo:

* **CD range-peel** (Alg. 3): peel everything with support < ``hi`` until
  the range drains; support updates cap at ``lo`` = theta(i).
  ``device_peel_loop(minmode=False)`` — used by `engine/cd.py`'s
  per-subset dispatch (``cd_dispatch="subset"``).
* **Whole-graph CD** (Alg. 3, single dispatch): ALL subsets of a graph
  under one ``lax.while_loop`` — the boundary branch closes/opens subsets
  on device (findHi via ``kernels.ops.find_hi_device``, DESIGN.md §2.3),
  the sweep branch is the same shared body.  ``device_cd_graph_loop`` —
  used by `engine/cd.py` when ``cd_dispatch="graph"``.
* **ParB min-peel** (baseline): each sweep peels the current
  minimum-support set; threshold recomputed on device per sweep.
  ``device_peel_loop(minmode=True, lo=0)`` — used by `engine/baselines.py`.
* **FD level-peel** (Alg. 4, ParButterfly/PBNG granularity): peel the
  entire current-minimum support *level* per sweep, batched over a vmap
  stack of independent induced subgraphs.  ``batched_level_loop`` — used
  by `engine/fd.py`, both single-device (per shape group) and under
  ``shard_map`` (`core/distributed.py` — ``receipt_fd(mesh=...)``).
  Level-peel is min-peel with a per-subset floor:
  the threshold is ``cap = max(min support, lo_subset)`` so every level
  below the subset's theta lower bound collapses into one sweep (exact:
  all such vertices have tip number exactly ``cap``, and survivors floor
  at ``cap`` either way — the ParB simultaneous-peel argument).

The single-graph sweep body itself lives in ``_sweep_once``; the two CD
loops and the ParB loop are thin ``lax.while_loop`` shells around it.

The sweep-body LOGIC is shared, not duplicated: ``level_threshold``,
``select_peel``, ``apply_delta``, ``record_theta`` and ``peel_cost``
operate on the LAST axis with arbitrary leading batch dims, so the
single-graph loop (shape ``(M,)`` state) and the batched loop (shape
``(G, M)`` state) run the same code.  What legitimately differs is
control flow: the single-graph loop branches per sweep with ``lax.cond``
(HUC peel-vs-recount, terminal-sweep elision, peel-buffer overflow —
scalar predicates), while the batched loop replaces data-dependent
branching with masking (per-group predicates cannot drive ``lax.cond``)
and needs neither HUC nor overflow: a level that exceeds the gather
buffer falls back to the mask-form kernel *on device* (a scalar
any-group cond), never to the host.

Support updates route through the Pallas butterfly kernels: the
single-graph loop through ``kernels.ops.butterfly_update`` and the
batched loop through the grouped entry point
``kernels.ops.butterfly_update_batched`` (leading batch dim over stacked
subsets, staircase extents per group member for the sparse backends).

`DeviceGraph` (the bucketed residual-graph container) and ``host_sweep``
(one blocking host-driven sweep: the peel-buffer overflow replay)
complete the module.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...kernels import ops as kops
from ...kernels.ref import EXACT
from ...kernels.butterfly_sparse import (
    batched_gathered_tile_extents,
    gathered_tile_extents,
)
from ...utils.spans import fetch, span
from ..graph import BipartiteGraph

__all__ = [
    "ReceiptConfig",
    "RunStats",
    "bucket",
    "DELTA_RULES",
    "DeviceGraph",
    "device_peel_loop",
    "device_cd_graph_loop",
    "cd_graph_state0",
    "batched_level_loop",
    "host_sweep",
    "support_all",
    "support_delta",
    "sweep_info",
    "residual_dv",
    "apply_delta",
    "level_threshold",
    "select_peel",
    "record_theta",
    "peel_cost",
]

_INF = jnp.inf


# ---------------------------------------------------------------------- #
# config / stats
# ---------------------------------------------------------------------- #
@dataclasses.dataclass
class ReceiptConfig:
    num_partitions: int = 8                  # P
    backend: Optional[str] = None            # kernel backend (None = auto)
    kernel_blocks: Tuple[int, int, int] = (128, 128, 512)
    use_huc: bool = True
    use_dgm: bool = True                     # DGM: re-induction per subset
    #   boundary (cd_dispatch="subset", gated by dgm_row_threshold): the
    #   host induces the edge list, the device scatters the dense matrix;
    #   or on-device column compaction + c_rcnt re-estimation + staircase
    #   re-tightening at EVERY boundary (cd_dispatch="graph", §2.3)
    degree_sort: bool = True                 # Wang et al. relabel (tile density)
    dgm_row_threshold: float = 0.7           # re-induce when alive < thresh*rows
    cd_dispatch: str = "subset"              # "subset": one device loop per
    #   CD subset, findHi on the host snapshot (DGM + checkpointing live
    #   here); "graph": the WHOLE CD phase is one dispatch — findHi runs
    #   on device (kernels.ops.find_hi_device) and the host blocks O(1)
    #   times per graph (DESIGN.md §2.3)
    dtype: Any = jnp.float32
    max_sweeps: int = 100_000                # valve: bounds ONE device-loop
    #   invocation (never the schedule — drivers re-enter on cap-exit,
    #   so Theorem 1's range containment survives any cap >= 1)
    peel_width: Optional[int] = None         # device peel buffer (None = auto;
    #   CD sizes it to the first sweep of each subset from the host
    #   snapshot, FD to mm/8 — both bucketed, doubled on overflow)
    fd_update_mode: str = "auto"             # level-peel support updates:
    #   "auto"   cost model: precompute the (G, M, M) B2 stack when it fits
    #            fd_b2_cells, else the grouped butterfly kernel (the HUC
    #            argument applied to FD: pay the wedge contraction ONCE
    #            when memory permits, stream it through the kernel when not)
    #   "b2"     always precompute; "kernel" always stream (scale path)
    fd_b2_cells: int = 1 << 24               # B2-stack budget: total cells
    #                                        # (G * M * M) materialized per
    #                                        # group stack
    representation: str = "dense"            # biadjacency layout the engine
    #   runs on: "dense" (the padded (rows, cols) matrix through CD + FD)
    #   or "tiled" (nonzero-block slot list through the whole-graph
    #   level-peel engine, core/engine/tiled.py — the only path when the
    #   dense matrix cannot be materialized).  "auto" is an API-layer
    #   value: the Planner's cost model resolves it before dispatch;
    #   the engine floor treats it as "dense".
    tiled_regather_every: int = 1            # sweeps between tile-list
    #   regathers (the tiled DGM cadence; 1 = every sweep — the regather
    #   is O(n_slots) tile passes, negligible next to the update kernel)
    tiled_compact_every: int = 64            # device sweeps per tiled
    #   segment: the host driver re-enters after this many sweeps and
    #   considers a host recompaction (tile-list shapes are static
    #   inside one dispatch, so per-sweep cost stays O(n_slots) until
    #   the slot list is REBUILT from survivors)
    tiled_compact_ratio: float = 0.5         # alive-row fraction at or
    #   below which the tiled host driver rebuilds the tile list from
    #   the surviving rows (the tiled analogue of dgm_row_threshold;
    #   <= 0 disables host recompaction)
    fd_prepeel_levels: int = 4               # max support levels the FD
    #   host pre-peel hoists per task (level 1, 2, ... on the host
    #   support snapshot, before the first FD launch); 1 reproduces the
    #   original single-level hoist.  Any value yields identical theta —
    #   the hoisted levels are the same exact level-peel sweeps the
    #   device loop would run (regression-tested).

    def __post_init__(self):
        """Validate every knob AT CONSTRUCTION (PR 5 satellite): the
        pre-PR behavior deferred checks to whichever driver happened to
        read a knob first (``cd_dispatch`` only in ``receipt_cd``,
        ``backend`` nowhere — a typo'd backend silently routed to the
        compiled pallas kernel).
        ``repro.api.EngineConfig`` layers stricter cross-knob rules on
        top; this is the floor every config object must clear.
        """
        if self.num_partitions < 1:
            raise ValueError(
                f"num_partitions must be >= 1 (got {self.num_partitions})")
        kops.resolve_backend(self.backend)   # raises on unknown names
        blocks = tuple(self.kernel_blocks)
        if len(blocks) != 3 or any(int(b) < 1 for b in blocks):
            raise ValueError(
                f"kernel_blocks must be three positive tile sizes "
                f"(bi, bj, bk), got {self.kernel_blocks!r}")
        if self.backend in kops.SPARSE_BACKENDS and blocks[0] != blocks[1]:
            raise ValueError(
                f"sparse backends require square row tiles (bi == bj), "
                f"got kernel_blocks={self.kernel_blocks!r}")
        if self.cd_dispatch not in ("subset", "graph"):
            raise ValueError(
                f"unknown cd_dispatch {self.cd_dispatch!r}: expected "
                "'subset' or 'graph'")
        if self.fd_update_mode not in ("auto", "b2", "kernel"):
            raise ValueError(
                f"unknown fd_update_mode {self.fd_update_mode!r}: "
                "expected 'auto', 'b2' or 'kernel'")
        if self.max_sweeps < 1:
            raise ValueError(
                f"max_sweeps must be >= 1 (got {self.max_sweeps}): the "
                "valve bounds one device-loop invocation; a sub-1 cap "
                "can make no progress")
        if self.peel_width is not None and self.peel_width < 1:
            raise ValueError(
                f"peel_width must be >= 1 or None (got {self.peel_width})")
        if not (0.0 < self.dgm_row_threshold <= 1.0):
            raise ValueError(
                f"dgm_row_threshold must lie in (0, 1] (got "
                f"{self.dgm_row_threshold}): it is the alive-row fraction "
                "below which the subset dispatch re-induces")
        if self.fd_b2_cells < 1:
            raise ValueError(
                f"fd_b2_cells must be >= 1 (got {self.fd_b2_cells})")
        if self.representation not in ("dense", "tiled", "auto"):
            raise ValueError(
                f"unknown representation {self.representation!r}: expected "
                "'dense', 'tiled' or 'auto'")
        if self.tiled_regather_every < 1:
            raise ValueError(
                f"tiled_regather_every must be >= 1 "
                f"(got {self.tiled_regather_every})")
        if self.tiled_compact_every < 1:
            raise ValueError(
                f"tiled_compact_every must be >= 1 "
                f"(got {self.tiled_compact_every})")
        if self.tiled_compact_ratio > 1.0:
            raise ValueError(
                f"tiled_compact_ratio must be <= 1 (got "
                f"{self.tiled_compact_ratio}): it is an alive-row "
                "fraction (<= 0 disables host recompaction)")
        if self.fd_prepeel_levels < 1:
            raise ValueError(
                f"fd_prepeel_levels must be >= 1 (got "
                f"{self.fd_prepeel_levels}): the FD pre-peel always "
                "hoists at least the first support level")


@dataclasses.dataclass
class RunStats:
    """The paper's evaluation counters (Table 3 / Figs 5-9).

    ``rho_fd`` counts FD peel sweeps: level-peel sweeps summed over
    subsets.  ``wedges_fd`` is the number of wedges DYNAMICALLY
    traversed by the FD level-peel loop (sum of per-sweep C_peel).
    ``subset_wedges_fd`` always records the static per-subset bound —
    it is the scheduler's workload proxy, known before peeling.
    """

    rho_cd: int = 0                 # CD sync rounds (peel sweeps)
    rho_fd: int = 0                 # FD peel sweeps (see class docstring)
    sweeps_per_subset: List[int] = dataclasses.field(default_factory=list)
    wedges_pvbcnt: int = 0          # counting bound sum_E min(du, dv)
    wedges_cd: int = 0              # wedges traversed peeling in CD
    wedges_fd: int = 0              # wedges traversed in FD (see docstring)
    huc_recounts: int = 0
    dgm_compactions: int = 0        # DGM re-inductions (subset dispatch)
    dgm_device_compactions: int = 0  # on-device DGM column compactions at
    #                               # subset boundaries (graph dispatch)
    cd_graph_upload_bytes: int = 0  # host-to-device bytes of the
    #                               # DeviceGraph builds (edge lists and
    #                               # O(rows + cols) metadata)
    elided_sweeps: int = 0          # terminal-sweep elision (beyond-paper)
    num_subsets: int = 0
    bounds: List[int] = dataclasses.field(default_factory=list)
    subset_sizes: List[int] = dataclasses.field(default_factory=list)
    subset_wedges_fd: List[int] = dataclasses.field(default_factory=list)
    host_round_trips: int = 0       # blocking device->host transfers
    device_loop_calls: int = 0      # lax.while_loop invocations
    overflow_fallbacks: int = 0     # peel buffer overflows -> host sweeps
    fd_groups: int = 0              # FD shape groups dispatched
    fd_padding_waste: float = 0.0   # 1 - used/(padded) cells of FD stacks
    fd_peel_widths: List[int] = dataclasses.field(default_factory=list)
    #                               # per-group gather-buffer widths used
    fd_max_levels: List[int] = dataclasses.field(default_factory=list)
    #                               # per-group measured largest peel level
    #                               # (the width probe fed back into plans)
    fd_mask_fallbacks: int = 0      # groups whose largest level exceeded
    #                               # the gather buffer (on-device mask-form
    #                               # fallback fired; exact either way)
    fd_prepeel_pairs: int = 0       # (u, x) pairs the FD host pre-peel's
    #                               # level deltas traversed (its work)
    fd_shards: int = 0              # mesh devices driving FD (0 = local)
    fd_shard_rho: List[int] = dataclasses.field(default_factory=list)
    #                               # per-shard level sweeps (mesh FD)
    fd_shard_wedges: List[float] = dataclasses.field(default_factory=list)
    #                               # per-shard dynamic wedge load (mesh
    #                               # FD; the LPT balance evidence)
    fd_shard_bytes: List[int] = dataclasses.field(default_factory=list)
    #                               # per-shard stack bytes uploaded (mesh
    #                               # FD; summed over the shape groups)
    time_count: float = 0.0         # wall seconds of the receipt.cd.count
    time_cd: float = 0.0            # span, of receipt.cd less counting,
    time_fd: float = 0.0            # and of receipt.fd (utils/spans.py)
    # hardened-runtime evidence (DESIGN.md §7): which backend actually
    # produced the result, the degradation path that led there, and what
    # the self-verification pass checked
    backend_used: str = ""          # resolved backend the run completed on
    backend_fallbacks: List[str] = dataclasses.field(default_factory=list)
    #                               # backends that FAILED before this run
    #                               # succeeded (the walked fallback chain)
    quarantined: bool = False       # run started on a quarantined-signature
    #                               # fallback backend (skipped the primary)
    straggler: bool = False         # Executor.map flagged this graph's
    #                               # chunk as a straggler (EWMA threshold)
    verified: bool = False          # decompose(verify=True) ran + passed
    verify_checks: int = 0          # invariant checks the verifier executed
    # serving-layer incremental refresh evidence (DESIGN.md §11): how a
    # dataset's numbers were brought up to date after edge mutations
    refresh_mode: str = ""          # "" (not a refresh) | "delta" | "full"
    refresh_t_hi: float = 0.0       # change-ceiling bound of the mutation
    #                               # batch (max mutated-endpoint support
    #                               # in the union graph)
    refresh_stop: float = 0.0       # the CD bound the prefix re-peel
    #                               # stopped at (inf = whole range)
    refresh_subsets_repeeled: int = 0   # old CD subsets below the stop
    refresh_subsets_total: int = 0      # old CD subset count
    refresh_dirty_edges: int = 0    # inserted + deleted edges absorbed

    @property
    def wedges_total(self) -> int:
        return self.wedges_pvbcnt + self.wedges_cd + self.wedges_fd


# ---------------------------------------------------------------------- #
# shape bucketing
# ---------------------------------------------------------------------- #
def bucket(n: int, block: int) -> int:
    """Power-of-two-ish bucket >= n, multiple of ``block`` (bounds the
    number of distinct jit shapes to O(log n))."""
    b = block
    while b < n:
        b *= 2
    return b


# ---------------------------------------------------------------------- #
# jitted device primitives (cached per bucketed shape)
# ---------------------------------------------------------------------- #
# The TPU lays a 2-D float32 matrix out in (8, 128) tiles, tile after
# tile.  A plain 2-D scatter is linearized row-major and then copied into
# the tiles, two matrices at once; a scatter in the tiles' own order
# leaves the matrix in place (a bfloat16 one too; tests/test_tpu_compile.py).
_TILE = (8, 128)


def _tile(shape) -> Tuple[int, int]:
    """The scatter's tile for ``shape``: ``_TILE`` where it divides the
    matrix, else whole rows (the order is then plain row-major)."""
    rows, cols = shape
    return (_TILE[0] if rows % _TILE[0] == 0 else 1,
            _TILE[1] if cols % _TILE[1] == 0 else cols)


@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def _scatter_tiles(eu, ev, n_real, shape, dtype):
    """Dense 0/1 matrix of ``shape``: the first ``n_real`` (u, v) pairs are
    its ones, in tile order (``scatter_dense``); the rest write 0."""
    rows, cols = shape
    tr, tc = _tile(shape)
    valid = (jnp.arange(eu.shape[0]) < n_real).astype(dtype)
    a = jnp.zeros((rows // tr, cols // tc, tr, tc), dtype).at[
        eu // tr, ev // tc, eu % tr, ev % tc].max(valid,
                                                  indices_are_sorted=True)
    return a.transpose(0, 2, 1, 3).reshape(shape)


def scatter_dense(eu: np.ndarray, ev: np.ndarray, n_pad: int, shape,
                  dtype):
    """Dense 0/1 biadjacency of ``shape`` on the device from the
    (u, v)-sorted, unique edge list ``eu``/``ev``.

    The host orders the edges tile by tile, pads them to ``n_pad`` with
    copies of the matrix's last cell (in bounds, last in tile order, and
    written 0) and uploads them as int32; the device scatters them into
    one matrix.  Returns the matrix and the uploaded edge arrays.
    """
    rows, cols = shape
    tr, tc = _tile(shape)
    # stable: within a tile the edges keep their (u, v) order
    order = np.argsort((eu.astype(np.int64) // tr) * (cols // tc)
                       + ev // tc, kind="stable")
    pad = n_pad - len(eu)
    edges = tuple(
        jnp.asarray(np.append(x[order], np.full(pad, n - 1)).astype(np.int32))
        for x, n in ((eu, rows), (ev, cols)))
    return _scatter_tiles(*edges, len(eu), shape, jnp.dtype(dtype)), edges


@functools.partial(jax.jit, static_argnames=("backend", "blocks"))
def support_all(a, alive, ids, kmax, *, backend, blocks):
    """HUC recount / initial count: support of every row w.r.t. alive rows."""
    return kops.butterfly_update(
        a, a, alive.astype(a.dtype), ids, ids, backend=backend, blocks=blocks,
        kmax_a=kmax, kmax_b=kmax,
    )


@functools.partial(jax.jit, static_argnames=("backend", "blocks"))
def support_delta(a, a_peel, valid, ids, ids_peel, kmax_a, kmax_b, *,
                  backend, blocks):
    """CD peel update: delta[u'] = sum_{u in S} C(W[u, u'], 2)."""
    return kops.butterfly_update(
        a, a_peel, valid.astype(a.dtype), ids, ids_peel,
        backend=backend, blocks=blocks, kmax_a=kmax_a, kmax_b=kmax_b,
    )


@jax.jit
def sweep_info(a, support, alive, hi):
    """Host-path sweep selection (pre-PR engine): recomputes the residual
    V-degrees and per-row wedge counts with two dense contractions.

    Returns (peel_mask, n_peel, c_peel) where c_peel is the dynamic wedge
    cost  sum_{u in S} sum_{v in N_u} (d_v - 1)  of peeling S in the
    residual graph (HUC's C_peel).
    """
    peel = alive & (support < hi)
    dv = a.T @ alive.astype(a.dtype)                 # residual V degrees
    wcur = jnp.matmul(a, jnp.maximum(dv - 1.0, 0.0),  # per-row residual
                      precision=EXACT)               # wedges (§8)
    c_peel = jnp.sum(jnp.where(peel, wcur, 0.0))
    return peel, jnp.sum(peel), c_peel


@jax.jit
def residual_dv(a, alive):
    """Residual V degrees (used to re-seed the incremental vector after a
    host-path fallback sweep or a checkpoint resume)."""
    return a.T @ alive.astype(a.dtype)


# ---------------------------------------------------------------------- #
# shared sweep-body pieces (last-axis semantics; leading dims broadcast,
# so the SAME code runs shape-(M,) single-graph and shape-(G, M) batched)
# ---------------------------------------------------------------------- #
def level_threshold(support, alive, lo):
    """Min-peel threshold: cap = max(min alive support, lo), hi = cap + 1.

    ``lo = 0`` gives the ParB schedule (supports are non-negative);
    a per-subset ``lo`` gives the FD level-peel schedule (sub-``lo``
    levels collapse into one exact sweep).  Dead batch members yield
    cap = inf, which makes every downstream piece a no-op.
    """
    mn = jnp.min(jnp.where(alive, support, _INF), axis=-1)
    cap = jnp.maximum(mn, lo)
    return cap + 1.0, cap


def select_peel(support, alive, hi):
    """Peel set of one sweep: alive rows with support below ``hi``."""
    return alive & (support < jnp.expand_dims(hi, -1))


@jax.jit
def apply_delta(support, alive, peel, delta, lo):
    """Alg. 2 update with the Alg. 3 range cap: cap at theta(i) = lo."""
    alive_after = alive & ~peel
    cap = jnp.expand_dims(jnp.asarray(lo), -1)
    sup = jnp.where(alive_after, jnp.maximum(support - delta, cap), support)
    return sup, alive_after


def record_theta(theta, peel, cap):
    """Min-peel theta recording: every peeled row gets the sweep's cap."""
    return jnp.where(peel, jnp.expand_dims(cap, -1), theta)


def peel_cost(colsum, dv):
    """Dynamic wedge cost of a peel set from its column sums:
    C_peel = colsum_S . max(dv - 1, 0)  (no per-row wedge vector needed)."""
    return jnp.sum(colsum * jnp.maximum(dv - 1.0, 0.0), axis=-1)


# ---------------------------------------------------------------------- #
# the shared device sweep body (one peel sweep of every single-graph loop)
# ---------------------------------------------------------------------- #
def _sweep_once(a, ids, row_ext, kmax, c_rcnt, hi_cur, cap, support, alive,
                dv, theta, peeled, rho, wedges, hucs, elided, covered, ovf,
                *, backend, blocks, use_huc, peel_width, minmode,
                axis="vertex"):
    """One peel sweep of the device-resident engines (DESIGN.md §2.0).

    The sweep body shared by ``device_peel_loop`` (per-subset CD range-peel
    / ParB min-peel) and ``device_cd_graph_loop`` (whole-graph CD): peel
    selection at ``hi_cur``, terminal-sweep elision, the fixed-width
    gather with its overflow flag, the HUC peel-vs-recount ``lax.cond``
    and the incremental residual-degree / wedge-counter updates.  Callers
    guard that the peel set is non-empty.  Returns the updated
    (support, alive, dv, theta, peeled, rho, wedges, hucs, elided,
    covered, ovf); ``rho`` advances exactly when a sweep was applied
    (the overflow exit leaves every field untouched, so the host can
    replay the sweep at the precise bucket).

    ``axis`` plugs in the delta rule (``DELTA_RULES``, DESIGN.md §10):
    ``"vertex"`` is the body documented above; ``"edge"`` reinterprets
    the support vector as PER-EDGE butterfly supports — ``a`` becomes
    the geometry dict ``{"a", "eu", "ev"}`` (the carried residual
    biadjacency plus the static edge-slot endpoints), the return tuple
    is geometry-prefixed (peeling mutates the matrix), the HUC
    alternative is the closed-form recount (always available — an
    oversized peel set routes there instead of overflowing to the
    host), and the peel path is the sequentially-composed masked-matvec
    / rank-1 update (``kernels.ops.edge_support_delta``).
    """
    if axis != "vertex":
        return DELTA_RULES[axis].sweep(
            a, ids, row_ext, kmax, c_rcnt, hi_cur, cap, support, alive,
            dv, theta, peeled, rho, wedges, hucs, elided, covered, ovf,
            backend=backend, blocks=blocks, use_huc=use_huc,
            peel_width=peel_width, minmode=minmode)
    sparse = backend in kops.SPARSE_BACKENDS
    i32 = jnp.int32
    f32 = jnp.float32
    peel = select_peel(support, alive, hi_cur)
    n_peel = jnp.sum(peel)
    is_elide = jnp.sum(alive) == n_peel

    def br_elide(support, alive, dv, theta):
        # terminal-sweep elision (beyond-paper, DESIGN.md): a sweep
        # that peels EVERY survivor needs no update kernel — and no
        # peel buffer either (checked BEFORE overflow): the full
        # peel set's column sums are dv itself, so
        # C_peel = dv . max(dv-1, 0) with no gather at all
        c_peel = peel_cost(dv, dv)
        theta2 = record_theta(theta, peel, cap) if minmode else theta
        return (support, alive & ~peel, jnp.zeros_like(dv), theta2,
                peeled | peel, rho + 1, wedges, hucs, elided + 1,
                covered + c_peel, ovf)

    def on_overflow(support, alive, dv, theta):
        return (support, alive, dv, theta, peeled, rho, wedges, hucs,
                elided, covered, jnp.bool_(True))

    def do_sweep(support, alive, dv, theta):
        rows = jnp.nonzero(peel, size=peel_width, fill_value=0)[0]
        rows = rows.astype(jnp.int32)
        valid = jnp.arange(peel_width) < n_peel
        a_peel = a[rows] * valid[:, None].astype(a.dtype)
        # incremental residual degrees: peeled rows' column sums
        colsum = valid.astype(f32) @ a_peel.astype(f32)
        c_peel = peel_cost(colsum, dv)

        def br_peel(sup, alv):
            if sparse:
                kb = gathered_tile_extents(row_ext, rows, valid,
                                           blocks[1])
            else:
                kb = None
            delta = support_delta(
                a, a_peel, valid, ids, rows, kmax if sparse else None,
                kb, backend=backend, blocks=blocks,
            )
            s2, alv2 = apply_delta(sup, alv, peel, delta, cap)
            return jnp.where(alv2, s2, _INF), alv2

        if use_huc:
            use_rec = c_peel > c_rcnt

            def br_recount(sup, alv):
                alv2 = alv & ~peel
                s2 = support_all(
                    a, alv2, ids, kmax if sparse else None,
                    backend=backend, blocks=blocks,
                )
                return jnp.where(alv2, jnp.maximum(s2, cap), _INF), alv2

            support2, alive2 = jax.lax.cond(
                use_rec, br_recount, br_peel, support, alive
            )
        else:
            use_rec = jnp.bool_(False)
            support2, alive2 = br_peel(support, alive)

        wedges2 = wedges + jnp.where(use_rec, c_rcnt, c_peel)
        theta2 = record_theta(theta, peel, cap) if minmode else theta
        return (
            support2, alive2, dv - colsum, theta2, peeled | peel,
            rho + 1, wedges2, hucs + use_rec.astype(i32),
            elided, covered + c_peel, ovf,
        )

    def non_elide(support, alive, dv, theta):
        return jax.lax.cond(
            n_peel > peel_width, on_overflow, do_sweep,
            support, alive, dv, theta,
        )

    return jax.lax.cond(
        is_elide, br_elide, non_elide, support, alive, dv, theta,
    )


def _sweep_once_edge(geom, ids, row_ext, kmax, c_rcnt, hi_cur, cap, support,
                     alive, dv, theta, peeled, rho, wedges, hucs, elided,
                     covered, ovf, *, backend, blocks, use_huc, peel_width,
                     minmode):
    """The edge-axis sweep body (wing / bitruss peeling, DESIGN.md §10).

    State semantics: ``support``/``alive``/``theta``/``peeled`` are per
    EDGE SLOT (padding slots dead, support +inf), ``dv`` stays the
    residual V-degree vector (maintained by scattering the peeled edges'
    column hits), and ``geom = {"a", "eu", "ev"}`` carries the residual
    biadjacency — peeling REWRITES it, so the updated geometry leads the
    return tuple.  ``ids``/``row_ext``/``kmax`` are accepted for body
    parity with the vertex rule and ignored (the edge delta entry points
    are pure-jnp contractions on every backend).

    Support updates, the paper's double-delete conflict dissolved twice
    over (both exact, pinned against each other by the differential
    suite):

    * **recount** — zero the peeled edges (a full-mask scatter: NO
      gather buffer, so an oversized peel set routes here instead of
      overflowing to the host — the edge axis has no overflow exit and
      keeps the O(1) round-trip bound by construction) and re-derive
      every survivor from the closed form ``kernels.ops.
      edge_support_all``.  With ``use_huc=False`` this is the only path.
    * **peel** — ``kernels.ops.edge_support_delta``: the masked-matvec /
      rank-1 per-edge deltas composed SEQUENTIALLY over the gathered
      peel set, so each edge updates against its predecessors' residual.

    ``use_huc=True`` picks between them per sweep with the HUC cost
    comparison: ``c_peel`` = edges peeled (each costs one matvec pair)
    against the caller's recount estimate ``c_rcnt`` in the same units.

    Returns ``(geom, support, alive, dv, theta, peeled, rho, wedges,
    hucs, elided, covered, ovf)``; ``ovf`` is carried untouched (never
    raised).
    """
    i32 = jnp.int32
    f32 = jnp.float32
    a, eu, ev = geom["a"], geom["eu"], geom["ev"]
    peel = select_peel(support, alive, hi_cur)
    n_peel = jnp.sum(peel)
    is_elide = jnp.sum(alive) == n_peel

    # the post-sweep geometry: a full-mask scatter zeroes every peeled
    # edge (padding slots all alias cell (0, 0) with peel=False, so the
    # min-clamp keeps them inert)
    peel_mat = jnp.zeros_like(a).at[eu, ev].add(peel.astype(a.dtype))
    a2 = a * (1.0 - jnp.minimum(peel_mat, 1.0))
    geom2 = dict(geom, a=a2)
    colsum = jnp.zeros_like(dv).at[ev].add(peel.astype(f32))
    c_peel = n_peel.astype(f32)

    def br_elide(support, alive, theta):
        theta2 = record_theta(theta, peel, cap) if minmode else theta
        return (geom2, support, alive & ~peel, dv - colsum, theta2,
                peeled | peel, rho + 1, wedges, hucs, elided + 1,
                covered + c_peel, ovf)

    def do_sweep(support, alive, theta):
        rows = jnp.nonzero(peel, size=peel_width, fill_value=0)[0]
        rows = rows.astype(i32)
        valid = jnp.arange(peel_width) < n_peel
        if use_huc:
            use_rec = (n_peel > peel_width) | (c_peel > c_rcnt)
        else:
            use_rec = jnp.bool_(True)

        def br_recount(sup, alv):
            alv2 = alv & ~peel
            s2 = kops.edge_support_all(
                a2, eu, ev, backend=backend, blocks=blocks)
            return jnp.where(alv2, jnp.maximum(s2, cap), _INF), alv2

        def br_peel(sup, alv):
            delta = kops.edge_support_delta(
                a, eu, ev, rows, valid, backend=backend, blocks=blocks)
            s2, alv2 = apply_delta(sup, alv, peel, delta, cap)
            return jnp.where(alv2, s2, _INF), alv2

        support2, alive2 = jax.lax.cond(
            use_rec, br_recount, br_peel, support, alive)
        theta2 = record_theta(theta, peel, cap) if minmode else theta
        return (geom2, support2, alive2, dv - colsum, theta2,
                peeled | peel, rho + 1,
                wedges + jnp.where(use_rec, c_rcnt, c_peel),
                # hucs counts HUC *decisions*: with use_huc=False the
                # always-recount path is policy, not a decision
                hucs + (use_rec.astype(i32) if use_huc else i32(0)),
                elided, covered + c_peel, ovf)

    return jax.lax.cond(is_elide, br_elide, do_sweep, support, alive, theta)


@dataclasses.dataclass(frozen=True)
class DeltaRule:
    """One peel axis of the shared engine (the ``DELTA_RULES`` plug
    point, DESIGN.md §10): which sweep body ``_sweep_once`` dispatches
    to, and whether a sweep rewrites the carried geometry (edge peeling
    deletes matrix entries; vertex peeling only masks rows, so the
    biadjacency is loop-invariant and stays OUT of the carried state)."""

    axis: str
    mutable_geom: bool
    sweep: Any


DELTA_RULES = {
    "vertex": DeltaRule(axis="vertex", mutable_geom=False,
                        sweep=_sweep_once),
    "edge": DeltaRule(axis="edge", mutable_geom=True,
                      sweep=_sweep_once_edge),
}


# ---------------------------------------------------------------------- #
# single-graph device-resident sweep loop (CD range-peel / ParB min-peel)
# ---------------------------------------------------------------------- #
@functools.partial(
    jax.jit,
    static_argnames=("backend", "blocks", "use_huc", "peel_width",
                     "max_sweeps", "minmode", "axis"),
)
def device_peel_loop(a, ids, row_ext, kmax, support, alive, dv, theta,
                     hi, lo, c_rcnt, sweeps0=0, *, backend, blocks, use_huc,
                     peel_width, max_sweeps, minmode, axis="vertex"):
    """Run an entire peel-sweep loop on device (``jax.lax.while_loop``).

    Two schedules share the body (``_sweep_once``, which the whole-graph
    CD loop ``device_cd_graph_loop`` also reuses — DESIGN.md §2.0/§2.3):

    * ``minmode=False`` (RECEIPT CD, Alg. 3): peel everything with
      support < ``hi`` until the range drains; support updates cap at
      ``lo`` = theta(i).
    * ``minmode=True``  (ParB baseline & FD single-subset fallback):
      each sweep peels the current minimum-support level; ``hi``/``cap``
      are recomputed per sweep as ``level_threshold(support, alive, lo)``
      and ``theta`` records the peel value.  ``lo = 0`` reproduces ParB
      exactly; a positive ``lo`` gives FD level-peel semantics.

    The peel set is gathered into a fixed (``peel_width``, n_v) buffer.
    A sweep whose peel set exceeds the buffer sets the overflow flag and
    exits WITHOUT applying the sweep; the host replays it at the precise
    bucket and re-enters with a doubled buffer.  Residual V-degrees ``dv``
    are maintained incrementally (peeled rows' column sums are subtracted)
    so no sweep recomputes a dense ``a.T @ alive`` contraction.

    Returns the full carried state; the caller fetches it in ONE blocking
    transfer: (support, alive, dv, theta, peeled, rho, wedges, hucs,
    elided, covered, sweeps, overflow).  ``sweeps`` counts from the traced
    ``sweeps0``, and the ``max_sweeps`` safety valve bounds ONE invocation,
    never the schedule: every driver (CD, ParB, FD) re-enters on a
    cap-exit with peelable rows left, so the valve only bounds how long
    the host goes without regaining control (DESIGN.md §2.0).

    Counter exactness: wedge counters accumulate in f32 and are exact
    while every partial sum stays below 2^24 (DESIGN.md section 8).

    ``axis="edge"`` (DESIGN.md §10) runs the SAME loop over the edge
    delta rule: ``a`` is the geometry dict ``{"a", "eu", "ev"}`` and the
    carried state is geometry-prefixed (peeling rewrites the residual
    biadjacency), so the return tuple gains one leading element:
    (geom, support, alive, dv, theta, peeled, rho, wedges, hucs, elided,
    covered, sweeps, overflow).  The overflow flag can never be raised
    on this axis (an oversized peel set routes to the closed-form
    recount inside the sweep body), so the O(1) round-trip bound holds
    by construction.
    """
    i32 = jnp.int32
    f32 = jnp.float32
    hi = jnp.asarray(hi, f32)
    lo = jnp.asarray(lo, f32)
    c_rcnt = jnp.asarray(c_rcnt, f32)

    def hi_cap(support, alive):
        if minmode:
            return level_threshold(support, alive, lo)
        return hi, lo

    if axis == "edge":

        def cond_fn_e(st):
            support, alive = st[1], st[2]
            sweeps, ovf = st[11], st[12]
            hi_cur, _ = hi_cap(support, alive)
            return (
                jnp.any(select_peel(support, alive, hi_cur))
                & (sweeps < max_sweeps)
                & ~ovf
            )

        def body_fn_e(st):
            (geom, support, alive, dv, theta, peeled, rho, wedges, hucs,
             elided, covered, sweeps, ovf) = st
            hi_cur, cap = hi_cap(support, alive)
            (geom, support, alive, dv, theta, peeled, rho2, wedges, hucs,
             elided, covered, ovf) = _sweep_once(
                geom, ids, row_ext, kmax, c_rcnt, hi_cur, cap, support,
                alive, dv, theta, peeled, rho, wedges, hucs, elided,
                covered, ovf, backend=backend, blocks=blocks,
                use_huc=(use_huc and not minmode),
                peel_width=peel_width, minmode=minmode, axis="edge",
            )
            return (geom, support, alive, dv, theta, peeled, rho2, wedges,
                    hucs, elided, covered, sweeps + (rho2 - rho), ovf)

        state0_e = (
            a, support, alive, dv, theta, jnp.zeros_like(alive),
            i32(0), f32(0), i32(0), i32(0), f32(0),
            jnp.asarray(sweeps0, i32), jnp.bool_(False),
        )
        return jax.lax.while_loop(cond_fn_e, body_fn_e, state0_e)

    def cond_fn(st):
        support, alive = st[0], st[1]
        sweeps, ovf = st[10], st[11]
        hi_cur, _ = hi_cap(support, alive)
        return (
            jnp.any(select_peel(support, alive, hi_cur))
            & (sweeps < max_sweeps)
            & ~ovf
        )

    def body_fn(st):
        (support, alive, dv, theta, peeled, rho, wedges, hucs, elided,
         covered, sweeps, ovf) = st
        hi_cur, cap = hi_cap(support, alive)
        (support, alive, dv, theta, peeled, rho2, wedges, hucs, elided,
         covered, ovf) = _sweep_once(
            a, ids, row_ext, kmax, c_rcnt, hi_cur, cap, support, alive,
            dv, theta, peeled, rho, wedges, hucs, elided, covered, ovf,
            backend=backend, blocks=blocks,
            use_huc=(use_huc and not minmode),
            peel_width=peel_width, minmode=minmode,
        )
        return (support, alive, dv, theta, peeled, rho2, wedges, hucs,
                elided, covered, sweeps + (rho2 - rho), ovf)

    state0 = (
        support, alive, dv, theta, jnp.zeros_like(alive),
        i32(0), f32(0), i32(0), i32(0), f32(0),
        jnp.asarray(sweeps0, i32), jnp.bool_(False),
    )
    return jax.lax.while_loop(cond_fn, body_fn, state0)


# ---------------------------------------------------------------------- #
# whole-graph CD loop (ALL subsets under one dispatch, findHi on device)
# ---------------------------------------------------------------------- #
@functools.partial(
    jax.jit,
    static_argnames=("backend", "blocks", "use_huc", "use_dgm",
                     "peel_width", "max_iters", "p_total"),
)
def device_cd_graph_loop(ids, state, *, backend, blocks, use_huc, use_dgm,
                         peel_width, max_iters, p_total):
    """Run the ENTIRE CD phase — every subset — in one device dispatch.

    One ``lax.while_loop`` alternates two body branches (DESIGN.md §2.3):

    * **sweep** (range not drained): one ``_sweep_once`` peel sweep at the
      carried (``hi``, ``lo``) — identical semantics to the per-subset
      ``device_peel_loop``, including HUC, terminal-sweep elision and the
      overflow exit.  Newly peeled rows are stamped with the open subset
      index in ``subset_of``.
    * **subset boundary** (range drained): close subset ``i`` (record
      ``bounds[i+1] = hi``, per-subset sweep count, the adaptive target
      ``scale``), run the ON-DEVICE Dynamic Graph Maintenance step (below,
      ``use_dgm``), then open subset ``i+1`` entirely on device: snapshot
      ``init_sup`` (the FD init vector, Alg. 3 line 7), recompute the
      residual per-row wedge counts ``w = A·max(dv-1, 0)`` (so range
      determination always sees the FRESH residual graph), and pick the
      next ``hi`` with the device findHi reduction
      (``kernels.ops.find_hi_device``).  ``done`` is raised when no rows
      survive — the loop's only exit besides the overflow flag and the
      ``max_iters`` valve (which bounds one invocation; the driver
      re-enters).

    **On-device DGM** (the residual-graph compaction the paper's §5.2
    runs on the host between subsets, here with static shapes and zero
    host syncs): dead rows are zeroed out of the carried biadjacency,
    live-V columns (residual degree >= 2 — anything less cannot form a
    wedge) are gathered into a dense prefix by a stable argsort
    permutation (preserving the construction-time degree-sort order
    within the live prefix), the carried ``dv`` permutes along, the HUC
    recount bound ``c_rcnt`` is RE-ESTIMATED from the compacted residual
    degrees (``sum_E min(du, dv)`` — Chiba-Nishizeki on the residual
    graph, not the whole-graph value), and the block-sparse staircase
    extents (``row_ext``/``kmax``) are re-tightened on device
    (``kernels.ops.tighten_extents_device``, clamped by the freshly
    counted live columns) so the stripe-skip path keeps winning as the
    graph dies.  The permutation is support-invariant: a column kept by
    compaction is shared only between live rows, a dropped column
    (residual degree < 2) can never contribute to a wedge between a
    survivor and a peeled row — so supports, bounds and tip numbers are
    bit-identical with DGM on or off (the equivalence suite pins this).

    ``state`` is a dict pytree (see ``cd_graph_state0``) carrying the
    (possibly column-permuted) biadjacency and its staircase/HUC
    metadata alongside the peel state, so the driver can re-enter after
    an overflow replay or a cap-exit by feeding the fetched state
    straight back.  The host blocks exactly ONCE per invocation — O(1)
    round trips per GRAPH instead of O(subsets), the dispatch-layer
    analogue of the paper's 1100x sync reduction.

    Remaining trade-off vs the per-subset driver: the matrix SHAPE stays
    at the seed bucket (compaction permutes and masks, it cannot shrink
    the dispatch shape), and findHi prefix-sums in f32 (DESIGN.md §8) —
    both may shift subset BOUNDS, never tip numbers (Theorem 1 holds for
    any bounds).
    """
    f32 = jnp.float32
    i32 = jnp.int32

    def boundary(st):
        # ---- close subset i (no-op on the very first entry, i = -1) --- #
        i = st["i"]
        closing = i >= 0
        idx = jnp.maximum(i, 0)
        bounds = st["bounds"].at[idx + 1].set(
            jnp.where(closing, st["hi"], st["bounds"][idx + 1]))
        rho_sub = st["rho_sub"].at[idx].set(
            jnp.where(closing, st["rho"] - st["rho_start"],
                      st["rho_sub"][idx]))
        was_catch = i >= p_total - 1
        scale = jnp.where(
            closing & (st["covered"] > 0) & ~was_catch,
            jnp.minimum(1.0, st["tgt"] / st["covered"]), st["scale"])
        lo = jnp.where(closing, st["hi"], st["lo"])
        done = ~jnp.any(st["alive"])
        # ---- on-device DGM: compact the residual graph ---------------- #
        if use_dgm:
            a0 = st["a"] * st["alive"][:, None].astype(st["a"].dtype)
            live_col = st["dv"] >= 2.0
            # stable sort/prefix permutation (find_hi_device idiom): live
            # columns form a dense prefix, degree-sort order preserved
            perm = jnp.argsort(~live_col)
            a2 = (jnp.take(a0, perm, axis=1)
                  * live_col[perm][None, :].astype(a0.dtype))
            dv = jnp.where(live_col, st["dv"], 0.0)[perm]
            n_live = jnp.sum(live_col).astype(i32)
            row_ext, kmax = kops.tighten_extents_device(
                a2, n_live, block_rows=blocks[0], block_k=blocks[2])
            # HUC recount bound re-estimated on the compacted residual
            # graph: sum_E min(du, dv) — no longer the whole-graph value
            du = jnp.sum(a2, axis=1)
            c_rcnt = jnp.sum(a2 * jnp.minimum(du[:, None], dv[None, :]))
            dgm = st["dgm"] + closing.astype(i32)
        else:
            a2, dv = st["a"], st["dv"]
            row_ext, kmax, c_rcnt = st["row_ext"], st["kmax"], st["c_rcnt"]
            dgm = st["dgm"]
        # ---- open subset i+1 (all garbage-safe when done) ------------- #
        i2 = jnp.where(done, i, i + 1)
        init_sup = jnp.where(st["alive"], st["support"], st["init_sup"])
        # fresh residual wedge counts: the range proxy the subset driver
        # only refreshes at DGM compactions, here free at every boundary
        w = jnp.matmul(a2, jnp.maximum(dv - 1.0, 0.0), precision=EXACT)
        rem = jnp.sum(jnp.where(st["alive"], w, 0.0))
        catch = i2 >= p_total - 1
        tgt = jnp.where(
            catch, jnp.inf,
            jnp.maximum(
                rem / jnp.maximum(p_total - i2, 1).astype(f32) * scale,
                1.0))
        hi = kops.find_hi_device(st["support"], st["alive"], w, tgt)
        return dict(
            st, a=a2, dv=dv, row_ext=row_ext, kmax=kmax, c_rcnt=c_rcnt,
            dgm=dgm,
            bounds=bounds, rho_sub=rho_sub, scale=scale, lo=lo,
            done=done, i=i2, init_sup=init_sup, tgt=tgt, hi=hi,
            covered=f32(0.0), rho_start=st["rho"],
            iters=st["iters"] + 1,
        )

    def sweep(st):
        (support, alive, dv, _theta, peeled, rho, wedges, hucs, elided,
         covered, ovf) = _sweep_once(
            st["a"], ids, st["row_ext"], st["kmax"], st["c_rcnt"],
            st["hi"], st["lo"], st["support"], st["alive"], st["dv"],
            f32(0.0), st["peeled"], st["rho"], st["wedges"], st["hucs"],
            st["elided"], st["covered"], st["ovf"],
            backend=backend, blocks=blocks, use_huc=use_huc,
            peel_width=peel_width, minmode=False,
        )
        newly = peeled & ~st["peeled"]
        return dict(
            st, support=support, alive=alive, dv=dv, peeled=peeled,
            rho=rho, wedges=wedges, hucs=hucs, elided=elided,
            covered=covered, ovf=ovf,
            subset_of=jnp.where(newly, st["i"], st["subset_of"]),
            iters=st["iters"] + 1,
        )

    def cond_fn(st):
        return ~st["done"] & ~st["ovf"] & (st["iters"] < max_iters)

    def body_fn(st):
        drained = ~jnp.any(select_peel(st["support"], st["alive"],
                                       st["hi"]))
        return jax.lax.cond(drained, boundary, sweep, st)

    return jax.lax.while_loop(cond_fn, body_fn, state)


def cd_graph_state0(dg: "DeviceGraph", support, alive, p_total: int):
    """Initial carried state of ``device_cd_graph_loop``.

    ``hi = -inf`` makes the first body iteration take the boundary branch,
    which opens subset 0 on device (no host-side findHi at all).  The
    driver re-enters with the FETCHED state after an overflow replay or a
    cap-exit, resetting only ``iters`` (the per-invocation valve budget).

    The residual graph itself rides in the state — biadjacency ``a``,
    residual V-degrees ``dv``, staircase extents ``row_ext``/``kmax``
    and the HUC bound ``c_rcnt`` — because the on-device DGM step
    rewrites all of them at subset boundaries (the live-column count it
    clamps the extents with is recomputed there, not carried); ``dgm``
    counts the compactions for RunStats.
    """
    i32 = jnp.int32
    f32 = jnp.float32
    rows_pad = dg.rows_pad
    return dict(
        a=dg.a, dv=dg.dv0,
        row_ext=dg.row_ext, kmax=dg.kmax,
        c_rcnt=f32(dg.c_rcnt), dgm=i32(0),
        support=support, alive=alive,
        subset_of=jnp.full(rows_pad, -1, i32),
        init_sup=jnp.zeros(rows_pad, f32),
        peeled=jnp.zeros(rows_pad, bool),
        bounds=jnp.zeros(p_total + 1, f32),
        rho_sub=jnp.zeros(max(p_total, 1), i32),
        i=i32(-1), hi=f32(-jnp.inf), lo=f32(0.0),
        scale=f32(1.0), tgt=f32(0.0),
        covered=f32(0.0), rho_start=i32(0),
        rho=i32(0), wedges=f32(0.0), hucs=i32(0), elided=i32(0),
        iters=i32(0), ovf=jnp.bool_(False), done=jnp.bool_(False),
    )


# ---------------------------------------------------------------------- #
# batched level-peel loop (FD: a stack of independent subsets)
# ---------------------------------------------------------------------- #
@functools.partial(
    jax.jit,
    static_argnames=("backend", "blocks", "peel_width", "max_sweeps",
                     "update_mode", "axis"),
)
def batched_level_loop(a, row_ext, support, alive, dv, lo, eu=None, ev=None,
                       *, backend, blocks, peel_width, max_sweeps,
                       update_mode="kernel", axis="vertex"):
    """Peel a stack of G independent subsets by whole support levels.

    One ``lax.while_loop`` carries the whole stack; each sweep peels, in
    EVERY still-live group, the entire current-minimum support level
    (``level_threshold`` with the group's theta lower bound ``lo[g]``).
    This is the ParButterfly/PBNG peel granularity inside a subset,
    batched over the scheduler's shape group — G subsets x L levels
    collapse into max_g(L_g) device sweeps and ONE host sync.

    a:       (G, M, C)  stacked induced biadjacencies (0/1)
    row_ext: (G, M)     int32 per-row staircase extents (sparse backends;
                        pass zeros otherwise — it is ignored)
    support: (G, M)     FD-initialized supports (+inf on padding rows)
    alive:   (G, M)     bool (False on padding rows)
    dv:      (G, C)     residual V-degrees of each induced subgraph
    lo:      (G,)       per-subset theta lower bounds (CD range floors)

    The peel level is gathered into a fixed (G, ``peel_width``, C) buffer
    and dispatched through the grouped butterfly kernel
    (``butterfly_update_batched``; per-group staircase extents on the
    sparse backends).  A sweep where ANY group's level exceeds the buffer
    falls back — on device, via a scalar ``lax.cond`` — to the mask-form
    kernel (B = A, s = peel mask): same output, no gather, no host
    involvement.  ``peel_width >= M`` selects the mask form statically.

    ``update_mode`` selects how a sweep's support delta is produced:

    * ``"kernel"`` — stream every sweep through the grouped butterfly
      kernel (wedge contraction recomputed per sweep; O(M) working set
      per group member — the ONLY option when the (M, M) pairwise
      butterfly matrix cannot be materialized);
    * ``"b2"``     — contract the whole (G, M, M) shared-butterfly stack
      ONCE before the loop and reduce gathered B2 rows per sweep.  Total
      work M^2 C + sum_l W_l M versus the kernel route's
      M C sum_l W_l >= M^2 C: strictly fewer flops whenever the stack
      fits.  The driver's ``fd_update_mode="auto"`` cost model picks per
      group (the HUC update-vs-recount argument applied to FD).

    Both modes produce bit-identical deltas (integer regime, DESIGN.md
    section 8); the equivalence suite pins them against each other.

    Returns (support, alive, dv, theta, rho, wedges, max_level, sweeps):
    ``theta`` (G, M) holds the tip numbers of peeled rows; ``rho`` (G,)
    counts sweeps in which group g actually peeled (the FD analogue of
    the paper's synchronization counter); ``wedges`` (G,) accumulates the
    dynamic wedge cost C_peel per group (f32-exact below 2^24, DESIGN.md
    section 8); ``max_level`` (G,) records the LARGEST peel level each
    group saw — the measured-width probe the driver feeds back into the
    plan so repeat runs of the same shape signature size the gather
    buffer from data instead of a heuristic (PR 5 satellite; a value
    above ``peel_width`` also tells the host the mask-form fallback
    fired).  Groups finish independently; a finished group is a no-op
    for the remaining sweeps (empty peel set).

    ``axis="edge"`` (DESIGN.md §10, wing FD): ``support``/``alive``/
    ``theta`` become per-EDGE-SLOT vectors of width E, ``eu``/``ev``
    (G, E) int32 carry each slot's endpoints into the shared stacked
    biadjacency, and every sweep is BATCHED-EXACT: peel the level with a
    full-mask scatter, then re-derive every survivor from the
    closed-form recount (``kernels.ops.edge_support_all``) — no gather
    buffer, no update-mode cost model (``peel_width``/``update_mode``
    are accepted and ignored), and the double-delete conflict never
    arises because nothing is incrementally composed.  The residual
    matrix is REWRITTEN by peeling, so the edge axis returns a 9-tuple
    with the carried biadjacency in front: (a, support, alive, dv,
    theta, rho, wedges, max_level, sweeps) — the driver re-enters on a
    cap-exit by feeding it straight back.  ``wedges`` counts peeled
    edges (each sweep's recount work proxy).
    """
    sparse = backend in kops.SPARSE_BACKENDS
    f32 = jnp.float32

    if axis == "edge":
        g_n = a.shape[0]
        gidx = jnp.arange(g_n)[:, None]
        lo = jnp.asarray(lo, f32)

        def cond_fn_e(st):
            alive, sweeps = st[2], st[8]
            return jnp.any(alive) & (sweeps < max_sweeps)

        def body_fn_e(st):
            (a_cur, support, alive, dv, theta, rho, wedges, max_level,
             sweeps) = st
            hi, cap = level_threshold(support, alive, lo)   # (G,), (G,)
            act = jnp.any(alive, axis=-1)                   # (G,)
            peel = select_peel(support, alive, hi)          # (G, E)
            n_peel = jnp.sum(peel, axis=-1)
            peel_mat = jnp.zeros_like(a_cur).at[gidx, eu, ev].add(
                peel.astype(a_cur.dtype))
            a2 = a_cur * (1.0 - jnp.minimum(peel_mat, 1.0))
            colsum = jnp.zeros_like(dv).at[gidx, ev].add(peel.astype(f32))
            theta2 = record_theta(theta, peel, cap)
            alive2 = alive & ~peel
            s2 = kops.edge_support_all(
                a2, eu, ev, backend=backend, blocks=blocks)
            support2 = jnp.where(
                alive2, jnp.maximum(s2, cap[:, None]), _INF)
            return (
                a2, support2, alive2, dv - colsum, theta2,
                rho + act.astype(jnp.int32),
                wedges + jnp.where(act, n_peel.astype(f32), 0.0),
                jnp.maximum(max_level, n_peel.astype(jnp.int32)),
                sweeps + 1,
            )

        theta0_e = jnp.zeros(support.shape, f32)
        state0_e = (
            a, support, alive, dv, theta0_e,
            jnp.zeros(g_n, jnp.int32), jnp.zeros(g_n, f32),
            jnp.zeros(g_n, jnp.int32), jnp.int32(0),
        )
        return jax.lax.while_loop(cond_fn_e, body_fn_e, state0_e)

    g_n, mm, cc = a.shape
    lo = jnp.asarray(lo, f32)
    ids = jnp.broadcast_to(
        jnp.arange(mm, dtype=jnp.int32)[None, :], (g_n, mm)
    )
    if sparse:
        kmax_a = row_ext.reshape(g_n, -1, blocks[0]).max(axis=2)
        kmax_a = kmax_a.astype(jnp.int32)
    else:
        kmax_a = None

    if update_mode == "b2":
        # one wedge contraction for the whole run; sweeps reduce its
        # rows.  On the Pallas backends the contraction + C(w, 2) + eye
        # mask fuse into the staircase-skipping b2_stack kernel; the xla
        # route (and any block-misaligned stack) keeps the einsum —
        # bit-identical either way (integer regime).
        aligned = (mm % blocks[0] == 0 and mm % blocks[1] == 0
                   and cc % blocks[2] == 0)
        b2 = kops.b2_stack(a.astype(f32),
                           backend=backend if aligned else "xla",
                           blocks=blocks)
    elif update_mode != "kernel":
        raise ValueError(f"unknown update_mode {update_mode!r}")

    def full_mask_update(peel):
        """Full-width update: B = A, s = peel mask (no gather)."""
        if update_mode == "b2":
            delta = jnp.einsum("gm,gmn->gn", peel.astype(f32), b2,
                               precision=EXACT)
        else:
            delta = kops.butterfly_update_batched(
                a, a, peel.astype(a.dtype), ids, ids,
                backend=backend, blocks=blocks, kmax_a=kmax_a, kmax_b=kmax_a,
            )
        colsum = jnp.einsum("gm,gmc->gc", peel.astype(f32), a.astype(f32))
        return delta, colsum

    def gathered_update(peel, n_peel):
        """Gathered update: peel level compacted to the fixed
        (G, peel_width, ...) buffer (stable argsort puts peel rows
        first), then either the grouped butterfly kernel (wedge
        contraction against the gathered rows) or a reduction of the
        precomputed B2 rows."""
        order = jnp.argsort(~peel, axis=-1)
        rows = order[:, :peel_width].astype(jnp.int32)
        valid = jnp.arange(peel_width)[None, :] < n_peel[:, None]
        a_peel = (
            jnp.take_along_axis(a, rows[:, :, None], axis=1)
            * valid[:, :, None].astype(a.dtype)
        )
        if update_mode == "b2":
            b2_rows = jnp.take_along_axis(b2, rows[:, :, None], axis=1)
            delta = jnp.einsum("gw,gwm->gm", valid.astype(f32), b2_rows,
                               precision=EXACT)
        else:
            if sparse:
                kb = batched_gathered_tile_extents(row_ext, rows, valid,
                                                   blocks[1])
            else:
                kb = None
            delta = kops.butterfly_update_batched(
                a, a_peel, valid, ids, rows,
                backend=backend, blocks=blocks, kmax_a=kmax_a, kmax_b=kb,
            )
        colsum = jnp.einsum(
            "gw,gwc->gc", valid.astype(f32), a_peel.astype(f32)
        )
        return delta, colsum

    def cond_fn(st):
        alive, sweeps = st[1], st[7]
        return jnp.any(alive) & (sweeps < max_sweeps)

    def body_fn(st):
        support, alive, dv, theta, rho, wedges, max_level, sweeps = st
        hi, cap = level_threshold(support, alive, lo)     # (G,), (G,)
        act = jnp.any(alive, axis=-1)                     # (G,)
        peel = select_peel(support, alive, hi)            # (G, M)
        n_peel = jnp.sum(peel, axis=-1)

        if peel_width >= mm:
            delta, colsum = full_mask_update(peel)
        else:
            delta, colsum = jax.lax.cond(
                jnp.any(n_peel > peel_width),
                lambda _: full_mask_update(peel),
                lambda _: gathered_update(peel, n_peel),
                operand=None,
            )

        c_peel = peel_cost(colsum, dv)                    # (G,)
        theta = record_theta(theta, peel, cap)
        support2, alive2 = apply_delta(support, alive, peel, delta, cap)
        support2 = jnp.where(alive2, support2, _INF)
        return (
            support2, alive2, dv - colsum, theta,
            rho + act.astype(jnp.int32),
            wedges + jnp.where(act, c_peel, 0.0),
            jnp.maximum(max_level, n_peel.astype(jnp.int32)),
            sweeps + 1,
        )

    theta0 = jnp.zeros((g_n, mm), f32)
    state0 = (
        support, alive, dv, theta0,
        jnp.zeros(g_n, jnp.int32), jnp.zeros(g_n, f32),
        jnp.zeros(g_n, jnp.int32), jnp.int32(0),
    )
    return jax.lax.while_loop(cond_fn, body_fn, state0)


# ---------------------------------------------------------------------- #
# device-graph container (bucketed, compacted view of the residual graph)
# ---------------------------------------------------------------------- #
class DeviceGraph:
    """Bucket-padded dense residual graph on device.

    rows 0..n_rows-1 are live U vertices (original ids in ``members``);
    cols are the compacted V vertices with residual degree >= 2.  Alongside
    the biadjacency it carries everything the device-resident sweep loop
    needs resident: the initial residual V-degree vector (``dv0``), the
    static per-row wedge counts (device ``w`` + host ``w_np`` for findHi),
    and the block-sparse staircase metadata (``kmax`` row-tile column
    extents + ``row_ext`` per-row extents) recomputed at every DGM
    compaction — exactly where compaction makes the staircase steepest.

    The host induces and derives the metadata from the edge list, O(m);
    the dense matrix is scattered on the device from the uploaded edges
    (``scatter_dense``), so a build moves O(m + rows + cols) bytes to
    the device and never O(rows × cols).  ``upload_bytes`` is what it
    moved; ``stats.cd_graph_upload_bytes`` sums it over the builds.
    """

    def __init__(self, g: BipartiteGraph, members: np.ndarray,
                 cfg: ReceiptConfig, plan=None,
                 stats: Optional[RunStats] = None):
        with span("cd.graph") as sp:
            self.cfg = cfg
            bi, bj, bk = cfg.kernel_blocks
            # induce on the live rows, dropping V columns that cannot form a
            # wedge (residual degree < 2) — the DGM column compaction
            sub, _ = g.induced_on_u(members, min_degree_v=2)
            dvk = sub.degrees_v()
            eu, ev = sub.edges_u, sub.edges_v      # sorted by (u, v), unique

            self.members = np.asarray(members)
            self.n_rows = len(members)
            self.n_cols = max(int(sub.n_v), 1)
            self.rows_pad = bucket(self.n_rows, max(bi, bj))
            self.cols_pad = bucket(self.n_cols, bk)
            n_edges = bucket(len(eu), 1024)
            if plan is not None:
                # DGM re-induction shapes quantize through the plan's
                # geometric shape floors, so subset re-induction lands on a
                # dispatch size an earlier same-signature run already traced
                # (the executable cache stays warm instead of retracing per
                # residual-graph size)
                self.rows_pad = plan.quantize_dim("dgm_rows", self.rows_pad)
                self.cols_pad = plan.quantize_dim("dgm_cols", self.cols_pad)
                n_edges = plan.quantize_dim("dgm_edges", n_edges)

            self.a, edges = scatter_dense(
                eu, ev, n_edges, (self.rows_pad, self.cols_pad), cfg.dtype)
            self.ids = jnp.arange(self.rows_pad, dtype=jnp.int32)
            # residual V degrees at construction (everything alive)
            dv_pad = np.zeros(self.cols_pad, np.float32)
            dv_pad[: len(dvk)] = dvk
            self.dv0 = jnp.asarray(dv_pad)
            # static per-row wedge counts in this residual graph (range proxy)
            w = np.zeros(self.rows_pad, np.float64)
            np.add.at(w, eu, (dvk[ev] - 1).astype(np.float64))
            self.w_np = w
            self.w = jnp.asarray(w, dtype=cfg.dtype)
            # total residual wedges = sum of per-row counts (everything alive)
            self.total_wedges = float(w.sum())
            # Chiba-Nishizeki recount bound of this residual graph (HUC C_rcnt)
            du = np.bincount(eu, minlength=self.rows_pad)
            self.c_rcnt = float(np.minimum(du[eu], dvk[ev]).sum())
            # block-sparse staircase metadata (scalar-prefetched by the
            # pallas_sparse backend; cheap enough to keep fresh always)
            backend = cfg.backend or kops.default_backend()
            if backend in kops.SPARSE_BACKENDS and bi != bj:
                raise ValueError("sparse backends require square row tiles")
            # a row's extent is one past the k-stripe of its last edge: the
            # edges are (u, v)-sorted, so that is the row's final edge
            rext = np.zeros(self.rows_pad, np.int32)
            last = np.flatnonzero(np.diff(eu, append=self.rows_pad))
            rext[eu[last]] = ev[last] // bk + 1
            self.row_ext = jnp.asarray(rext)
            # tile extents = per-tile max of the row extents
            self.kmax = jnp.asarray(rext.reshape(-1, bi).max(axis=1))
            self.upload_bytes = sum(x.nbytes for x in (
                *edges, self.dv0, self.w, self.row_ext, self.kmax))
            if stats is not None:
                stats.cd_graph_upload_bytes += self.upload_bytes
            sp.set_metadata(rows=self.rows_pad, cols=self.cols_pad,
                            edges=n_edges, upload_bytes=self.upload_bytes)

    def initial_peel_width(self) -> int:
        """Auto-sized device peel buffer: a quarter of the padded rows
        (bucketed), never below one kernel row tile.  Doubled by the
        driver on overflow."""
        cfg = self.cfg
        if cfg.peel_width is not None:
            w = bucket(cfg.peel_width, cfg.kernel_blocks[1])
        else:
            w = bucket(max(cfg.kernel_blocks[1], self.rows_pad // 4),
                       cfg.kernel_blocks[1])
        return min(w, self.rows_pad)


# ---------------------------------------------------------------------- #
# host-driven sweep (the peel-buffer overflow replay)
# ---------------------------------------------------------------------- #
def host_sweep(dg, cfg: ReceiptConfig, stats: RunStats,
               support, alive, hi: float, lo: float, backend, blocks,
               *, allow_huc: bool = True):
    """One blocking host-driven sweep: select, decide, dispatch, fetch.

    ``dg`` is a ``DeviceGraph`` or any object with the same
    ``a``/``ids``/``row_ext``/``kmax``/``c_rcnt``/``rows_pad`` surface —
    the whole-graph overflow replay passes a view over the loop-carried
    (column-permuted) residual graph instead (`engine/cd._GraphStateView`).

    Returns (support, alive, info) where info is None when nothing was
    peelable, else a dict with keys ``peel_np`` (host peel mask),
    ``n_peel`` and ``c_peel``.  Every blocking transfer is a ``fetch``
    counted in ``stats.host_round_trips`` — this is the per-sweep cost
    the device-resident loop removes.
    """
    sparse = backend in kops.SPARSE_BACKENDS
    peel, n_peel, c_peel = sweep_info(dg.a, support, alive, hi)
    n_peel = int(fetch(stats, n_peel, "sweep.select"))
    if n_peel == 0:
        return support, alive, None
    c_peel = float(fetch(stats, c_peel, "sweep.cost"))
    stats.rho_cd += 1

    n_alive_after = int(fetch(stats, jnp.sum(alive), "sweep.alive")) - n_peel
    if n_alive_after == 0:
        # terminal-sweep elision (beyond-paper, DESIGN.md): when a sweep
        # peels every remaining vertex there is no survivor to update, so
        # the update kernel is skipped entirely.  On hub-dominated graphs
        # this removes the single most expensive sweep (the paper would
        # traverse all its wedges).
        alive = alive & ~peel
        stats.elided_sweeps += 1
    elif allow_huc and cfg.use_huc and c_peel > dg.c_rcnt:
        # HUC: recount survivors instead of propagating peel updates
        alive = alive & ~peel
        support = support_all(
            dg.a, alive, dg.ids, dg.kmax if sparse else None,
            backend=backend, blocks=blocks,
        )
        support = jnp.where(alive, jnp.maximum(support, lo), _INF)
        stats.huc_recounts += 1
        stats.wedges_cd += int(dg.c_rcnt)
    else:
        # gather the peel rows into a bucketed matrix
        peel_rows = jnp.nonzero(peel, size=dg.rows_pad, fill_value=0)[0]
        n_peel_pad = bucket(n_peel, blocks[1])
        rows = peel_rows[:n_peel_pad].astype(jnp.int32)
        valid = jnp.arange(n_peel_pad) < n_peel
        a_peel = dg.a[rows] * valid[:, None].astype(dg.a.dtype)
        kb = (gathered_tile_extents(dg.row_ext, rows, valid, blocks[1])
              if sparse else None)
        delta = support_delta(
            dg.a, a_peel, valid, dg.ids, rows,
            dg.kmax if sparse else None, kb,
            backend=backend, blocks=blocks,
        )
        support, alive = apply_delta(support, alive, peel, delta, lo)
        support = jnp.where(alive, support, _INF)
        stats.wedges_cd += int(c_peel)

    peel_np = np.asarray(fetch(stats, peel, "sweep.mask"))
    return support, alive, dict(peel_np=peel_np, n_peel=n_peel, c_peel=c_peel)
