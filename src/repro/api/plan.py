"""Planning stage: ``Planner.plan(graph, config) -> ExecutionPlan``.

RECEIPT's whole point is that peeling has statically schedulable
structure — subset wedge budgets, padded shape groups, LPT shards,
kernel routes — but until PR 5 that structure was derived inside the
engine and thrown away.  The plan surfaces it BEFORE execution:

* what will run — CD dispatch mode and partition budget, FD mode and
  update policy, the resolved kernel backend and its route label;
* at what shapes — the bucketed device-matrix shape (``rows_pad`` x
  ``cols_pad``; the jit cache key's shape component), the initial CD
  peel-buffer width, and a wedge-equipartition ESTIMATE of the FD shape
  groups and their padding waste (the exact groups depend on the CD
  result; estimates are labeled as such and refined by execution);
* at what cost — a padded-bytes device-memory estimate;
* where — the mesh shard count when an executor holds a mesh.

``ExecutionPlan.signature`` is the executable-cache key (DESIGN.md §6):
two graphs with the same bucketed shape and the same config share every
traced executable, so the Executor reuses their compilations and their
MEASURED sizing (peel-buffer widths, stack shape floors) — the
``measured`` slot is the mutable feedback channel the engine writes
back through (`engine/cd.py` / `engine/fd.py` ``plan=`` kwarg).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ..core.engine.peel_loop import ReceiptConfig, bucket
from ..core.graph import BipartiteGraph
from ..kernels import butterfly_tiled as ktiled
from ..kernels import ops as kops
from .config import EngineConfig
from .errors import PlanInfeasibleError

__all__ = ["ExecutionPlan", "PlanMeasurements", "Planner"]

# ---------------------------------------------------------------------- #
# dense -> tiled routing crossover (representation="auto")
#
# The tiled wedge kernel visits ``n_row_tiles * n_slots`` tile pairs
# where the dense kernel's grid is ``n_row_tiles * n_col_tiles *
# n_row_tiles`` — so the work ratio is the TILE-GRID OCCUPANCY
# ``n_slots / (n_row_tiles * n_col_tiles)``.  Both constants are
# MEASURED, not guessed: benchmarks/bench_receipt.py's "representations"
# section times dense vs tiled across the paper-regime graphs
# (benchmarks/datasets.py) plus a sparse power-law ladder and records
# the observed winners in BENCH_receipt.json; bench_gate.py asserts
# these constants bracket the measurement.  At occupancy ~1 the tiled
# form is pure overhead (same tile pairs + gather indirection); the
# measured warm walls on the ladder (xla backend) put the crossover
# between sp_mid (occupancy 0.033, 2^24 dense cells, dense wins at
# 1.08x) and sp_large (occupancy 0.025, 2^25 cells, tiled wins at
# 0.58x), so routing fires at occupancy <= 0.03 AND >= 2^24 padded
# dense cells — below that cell count the dense matmul's constant
# factor wins at any sparsity we measured.  Memory admission overrides
# the speed crossover: when the dense matrix cannot fit the budget,
# tiled is chosen regardless.
# ---------------------------------------------------------------------- #
TILED_OCCUPANCY_CROSSOVER = 0.03
TILED_MIN_DENSE_CELLS = 1 << 24


@dataclasses.dataclass
class PlanMeasurements:
    """Execution feedback attached to a plan (and folded into the
    executor's cache entry for the plan's signature).

    ``cd_peel_width`` — the CD gather-buffer width the run ended with
    (first-sweep sizing + overflow doublings); reused by the next
    same-signature run so the width stops depending on that graph's
    data (the jit-static argument stabilizes -> no retrace) and the
    graph dispatch skips its sizing snapshot.

    ``fd_level_widths`` — per stacked-shape ``(mm, cc)``: the largest
    peel level the batched loop measured (`batched_level_loop`'s
    ``max_level``), replacing the first-sweep probe on repeat runs.

    ``shape_floors`` — per stack dimension: sorted shape values earlier
    runs compiled; ``quantize_dim`` pads new stacks up to the nearest
    one so the FD dispatch sequence is shape-stable across graphs.
    """

    cd_peel_width: Optional[int] = None
    fd_level_widths: Dict[Tuple[int, int], int] = dataclasses.field(
        default_factory=dict)
    shape_floors: Dict[str, List[int]] = dataclasses.field(
        default_factory=dict)
    observed_dims: Dict[str, set] = dataclasses.field(default_factory=dict)
    runs: int = 0


@dataclasses.dataclass
class ExecutionPlan:
    """What a decomposition WILL do, inspectable before it runs.

    Static fields describe the ingested graph and the derived dispatch
    structure; ``est_*`` fields are pre-execution estimates (labeled —
    the exact FD groups depend on the CD result); ``measured`` carries
    execution feedback (see ``PlanMeasurements``).
    """

    signature: Tuple                 # executable-cache key (hashable)
    side: str
    n_u: int                         # peeled side (post side-selection)
    n_v: int
    m: int
    backend: str                     # resolved (never None)
    kernel_route: str                # human-readable route label
    kernel_blocks: Tuple[int, int, int]
    cd_dispatch: str
    num_partitions: int
    rows_pad: int                    # bucketed device-matrix shape —
    cols_pad: int                    # the shape half of the signature
    cd_peel_width0: int              # initial CD gather-buffer width
    cd_host_syncs_bound: int         # O(1) bound for the graph dispatch,
    #                                # O(P) for the subset dispatch
    fd_update_policy: str            # "auto" | "b2" | "kernel"
    est_fd_groups: List[Dict[str, int]]   # wedge-equipartition ESTIMATE
    est_fd_padding_waste: float
    mesh_shards: int                 # 0 = single device
    degree_sort: bool
    padded_bytes: int                # device-memory estimate
    workload: str = "tip"            # "tip" (vertex axis) | "wing"
    #                                # (edge axis, DESIGN.md §10) — part
    #                                # of the signature, so executables
    #                                # never cross workloads
    m_pad: int = 0                   # bucketed edge-slot count (the
    #                                # support-vector width of wing
    #                                # plans; 0 on the vertex axis)
    representation: str = "dense"    # resolved biadjacency layout:
    #                                # "dense" | "tiled" (never "auto" —
    #                                # the Planner's cost model resolves
    #                                # the knob; part of the signature)
    cost_model: Dict[str, Any] = dataclasses.field(default_factory=dict)
    #                                # the routing decision's inputs:
    #                                # dense/tiled byte+work estimates,
    #                                # tile occupancy, crossover constants
    memory_budget_bytes: Optional[int] = None   # admission-control budget
    degraded_from_partitions: Optional[int] = None
    #                                # set when admission control downshifted
    #                                # the plan to smaller FD groups: the
    #                                # config's ORIGINAL partition count
    #                                # (num_partitions holds the admitted one)
    measured: PlanMeasurements = dataclasses.field(
        default_factory=PlanMeasurements)

    # ------------------------------------------------------------------ #
    # engine feedback surface (consumed by engine/cd.py and engine/fd.py)
    # ------------------------------------------------------------------ #
    def cd_peel_width_hint(self) -> Optional[int]:
        return self.measured.cd_peel_width

    def note_cd_peel_width(self, width: int) -> None:
        cur = self.measured.cd_peel_width or 0
        self.measured.cd_peel_width = max(cur, int(width))

    def fd_width_hint(self, shape: Tuple[int, int]) -> Optional[int]:
        return self.measured.fd_level_widths.get(tuple(shape))

    def note_fd_level(self, shape: Tuple[int, int], level: int,
                      width_used: int) -> None:
        """Record the gather width to reuse at this stack shape: the
        width this run TRACED when it sufficed (so the next run reuses
        the compiled program bit-for-bit), the measured level when the
        mask-form fallback fired (so the next run's buffer grows to
        what the data actually needed)."""
        shape = tuple(shape)
        level, width_used = int(level), int(width_used)
        want = width_used if level <= width_used else level
        cur = self.measured.fd_level_widths.get(shape, 1)
        self.measured.fd_level_widths[shape] = max(cur, want, 1)

    def quantize_dim(self, name: str, value: int) -> int:
        """Pad a stack dimension up to the nearest shape an earlier
        same-signature run compiled (shape floors are seeded from the
        executor cache; within a cold run they are empty, so behavior is
        identical to the self-sized engine).  The value actually used is
        recorded so the executor can fold it back into the cache."""
        floors = self.measured.shape_floors.get(name, ())
        fits = [v for v in floors if v >= value]
        out = min(fits) if fits else int(value)
        self.measured.observed_dims.setdefault(name, set()).add(out)
        return out

    # ------------------------------------------------------------------ #
    # inspection
    # ------------------------------------------------------------------ #
    def to_dict(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        d["signature"] = list(map(str, self.signature))
        d["measured"] = {
            "cd_peel_width": self.measured.cd_peel_width,
            "fd_level_widths": {f"{k[0]}x{k[1]}": v for k, v in
                                self.measured.fd_level_widths.items()},
            "runs": self.measured.runs,
        }
        return d

    def describe(self) -> str:
        """Terse human-readable plan summary."""
        est = ", ".join(
            f"{g['count']}x({g['rows']}x{g['cols']})"
            for g in self.est_fd_groups) or "none"
        admit = ""
        if self.degraded_from_partitions is not None:
            admit = (f", admission-degraded from "
                     f"P={self.degraded_from_partitions} under "
                     f"{(self.memory_budget_bytes or 0) / 2**20:.1f} MiB")
        occ = self.cost_model.get("tile_occupancy")
        rep = self.representation
        if rep == "tiled" and occ is not None:
            rep += f" (occupancy {occ:.2f})"
        return (
            f"ExecutionPlan[{self.side}]: |U|={self.n_u} |V|={self.n_v} "
            f"m={self.m}\n"
            f"  representation: {rep}\n"
            f"  device matrix : {self.rows_pad} x {self.cols_pad} "
            f"(~{self.padded_bytes / 2**20:.1f} MiB padded{admit})\n"
            f"  kernel route  : {self.kernel_route}, blocks="
            f"{self.kernel_blocks}\n"
            f"  CD            : dispatch={self.cd_dispatch!r}, "
            f"P={self.num_partitions}, peel_width0={self.cd_peel_width0}, "
            f"host syncs <= {self.cd_host_syncs_bound}\n"
            f"  FD            : update={self.fd_update_policy!r}, "
            f"est groups: {est} "
            f"(est padding waste {self.est_fd_padding_waste:.0%})\n"
            f"  mesh shards   : {self.mesh_shards or 'single-device'}\n"
            f"  measured      : cd_peel_width="
            f"{self.measured.cd_peel_width}, "
            f"{len(self.measured.fd_level_widths)} FD width(s), "
            f"runs={self.measured.runs}"
        )


class Planner:
    """Derives an ``ExecutionPlan`` from (graph, config) — pure host
    preprocessing, no device work, no jax tracing.

    Accepts an ``EngineConfig`` (the strict service surface) or a legacy
    ``ReceiptConfig`` + ``side`` (the compat wrappers' currency — kept
    permissive so A/B configurations the service layer rejects still
    plan and run).
    """

    def __init__(self, config=None, *, side: Optional[str] = None):
        if config is None:
            config = EngineConfig() if side is None else EngineConfig(
                side=side)
        if isinstance(config, EngineConfig):
            if side is not None and side != config.side:
                config = dataclasses.replace(config, side=side)
            self.config = config
            self.rcfg = config.to_receipt_config()
            self.side = config.side
            self.workload = config.workload
            self.memory_budget = config.memory_budget_bytes
        elif isinstance(config, ReceiptConfig):
            self.config = None          # legacy currency: no strict view
            self.rcfg = config
            self.side = side or "U"
            self.workload = "tip"       # workload is a service-layer knob
            self.memory_budget = None   # admission control is a service-
            #                           # layer feature (EngineConfig knob)
        else:
            raise ValueError(
                f"Planner expects an EngineConfig or ReceiptConfig, got "
                f"{type(config).__name__}")
        if self.side not in ("U", "V"):
            raise ValueError(f"side must be 'U' or 'V', got {self.side!r}")

    def describe(self) -> str:
        """Resolved-configuration rendering for the service's config
        endpoint: the ``EngineConfig.describe()`` knob set plus the
        planner-level state (admission budget, legacy-config mode)."""
        if self.config is not None:
            body = self.config.describe()
        else:
            body = (f"ReceiptConfig (legacy engine currency, "
                    f"{self.workload} workload, side={self.side}; no "
                    "admission control)")
        budget = self.memory_budget
        tail = ("  admission budget: "
                + (f"{budget / 2**20:.1f} MiB" if budget else "unlimited"))
        return body + "\n" + tail

    # ------------------------------------------------------------------ #
    def plan(self, graph: BipartiteGraph, *, mesh=None) -> ExecutionPlan:
        if not isinstance(graph, BipartiteGraph):
            raise ValueError(
                f"Planner.plan expects a BipartiteGraph (got "
                f"{type(graph).__name__}); ingest edge lists with "
                "BipartiteGraph.from_edges or dense 0/1 matrices with "
                "BipartiteGraph.from_dense")
        graph.validate()
        cfg = self.rcfg
        g = graph.transposed() if self.side == "V" else graph
        backend = kops.resolve_backend(cfg.backend)
        bi, bj, bk = cfg.kernel_blocks
        mesh_shards = int(mesh.size) if mesh is not None else 0
        if self.workload == "wing":
            return self._plan_wing(g, cfg, backend, mesh_shards)

        # --- ingestion-derived shapes (the DeviceGraph bucket math) ---- #
        dv = g.degrees_v()
        n_cols = max(int((dv >= 2).sum()), 1)   # wedge-capable V columns
        rows_pad = bucket(max(g.n_u, 1), max(bi, bj))
        cols_pad = bucket(n_cols, bk)
        if cfg.peel_width is not None:
            width0 = min(bucket(cfg.peel_width, bj), rows_pad)
        else:
            width0 = min(bucket(max(bj, rows_pad // 4), bj), rows_pad)

        # --- FD shape-group estimate (wedge-mass equipartition) -------- #
        est_groups, est_waste = self._estimate_fd_groups(g, cfg, backend)

        # --- memory estimate ------------------------------------------- #
        itemsize = 4                                    # f32 regime
        fixed_bytes = itemsize * (
            rows_pad * cols_pad                         # CD biadjacency
            + width0 * cols_pad                         # CD peel buffer
        )
        stack_cells = sum(g_["count"] * g_["rows"] * g_["cols"]
                          for g_ in est_groups)
        padded_bytes = fixed_bytes + itemsize * stack_cells

        # --- representation routing (DESIGN.md §9) --------------------- #
        # "auto" resolves against the measured occupancy/size crossover
        # (module constants above), with memory admission overriding the
        # speed heuristic: a dense matrix that cannot fit the budget
        # routes tiled regardless of density.  The mesh FD driver is
        # dense-only, so a sharded executor always plans dense.
        req_rep = getattr(cfg, "representation", "dense")
        tiled_est = self._estimate_tiled(g, cfg, backend)
        dense_cells = rows_pad * cols_pad
        budget = self.memory_budget
        if req_rep == "tiled":
            representation = "tiled"
        elif req_rep == "auto" and mesh_shards == 0 and (
                (budget is not None and fixed_bytes > budget)
                or (tiled_est["tile_occupancy"] <= TILED_OCCUPANCY_CROSSOVER
                    and dense_cells >= TILED_MIN_DENSE_CELLS
                    and tiled_est["tiled_kernel_fits"])):
            representation = "tiled"
        else:
            representation = "dense"
        cost_model = {
            "requested": req_rep,
            "dense_bytes": padded_bytes,
            "dense_fixed_bytes": fixed_bytes,
            "dense_cells": dense_cells,
            "tiled_bytes": tiled_est["tiled_bytes"],
            "n_tiles": tiled_est["n_tiles"],
            "tile_occupancy": tiled_est["tile_occupancy"],
            "tile_blocks": tiled_est["tile_blocks"],
            "tiled_kernel_smem_bytes": tiled_est["tiled_kernel_smem_bytes"],
            "tiled_kernel_vmem_bytes": tiled_est["tiled_kernel_vmem_bytes"],
            "occupancy_crossover": TILED_OCCUPANCY_CROSSOVER,
            "min_dense_cells": TILED_MIN_DENSE_CELLS,
        }

        # --- admission control (DESIGN.md §7) -------------------------- #
        # Over-budget plans DEGRADE before they reject: re-partitioning
        # resizes the FD stacks (subset sizes trade against per-group
        # padding, so the estimate is NOT monotone in P — both directions
        # are probed, nearest the requested count first), trading
        # dispatch count for peak memory.  Only when the fixed CD
        # footprint alone overflows, or no probed partitioning fits, is
        # the plan infeasible — and a representation="auto" plan takes
        # the tiled route instead of rejecting when the tile list fits.
        admitted_p = cfg.num_partitions
        degraded_from = None
        if representation == "tiled":
            padded_bytes = tiled_est["tiled_bytes"]
            est_groups, est_waste = [], 0.0
            if budget is not None and padded_bytes > budget:
                raise PlanInfeasibleError(
                    f"the tiled representation still needs {padded_bytes} "
                    f"bytes ({tiled_est['n_tiles']} nonzero "
                    f"{tiled_est['tile_blocks'][0]}x"
                    f"{tiled_est['tile_blocks'][1]} tiles), over the "
                    f"memory_budget_bytes={budget} admission budget — "
                    "raise the budget or shrink the graph/blocks",
                    dispatch=cfg.cd_dispatch, backend=backend,
                    padded_bytes=padded_bytes, budget=budget)
            if not tiled_est["tiled_kernel_fits"]:
                raise PlanInfeasibleError(
                    f"the compiled tiled kernel's tables need "
                    f"{tiled_est['tiled_kernel_smem_bytes']} bytes of SMEM "
                    f"(limit {ktiled.SMEM_BYTES}) and "
                    f"{tiled_est['tiled_kernel_vmem_bytes']} bytes of VMEM "
                    f"(limit {ktiled.VMEM_BYTES}) for "
                    f"{tiled_est['tiled_rows_pad']} rows and "
                    f"{tiled_est['n_tiles']} tiles — backend='xla' runs "
                    "the streaming tiled form with no on-chip tables",
                    dispatch=cfg.cd_dispatch, backend=backend,
                    padded_bytes=padded_bytes, budget=budget)
        elif budget is not None and padded_bytes > budget:
            if fixed_bytes > budget:
                raise PlanInfeasibleError(
                    f"the CD device matrix alone needs {fixed_bytes} "
                    f"padded bytes ({rows_pad} x {cols_pad} biadjacency + "
                    f"{width0}-row peel buffer), over the "
                    f"memory_budget_bytes={budget} admission budget — no "
                    "FD downshift can help; raise the budget, shrink the "
                    "graph/blocks, or route representation='tiled'",
                    dispatch=cfg.cd_dispatch, backend=backend,
                    padded_bytes=padded_bytes, budget=budget)
            cands: List[int] = []
            lo_p = hi_p = cfg.num_partitions
            for _ in range(8):                      # bounded probe, near
                lo_p = max(lo_p // 2, 1)            # to far in both
                hi_p *= 2                           # directions
                for q in (lo_p, hi_p):
                    if q != cfg.num_partitions and q not in cands:
                        cands.append(q)
            best = (padded_bytes, admitted_p, est_groups, est_waste)
            found = False
            for p_try in cands:
                groups_try, waste_try = self._estimate_fd_groups(
                    g, cfg, backend, num_partitions=p_try)
                cells = sum(g_["count"] * g_["rows"] * g_["cols"]
                            for g_ in groups_try)
                bytes_try = fixed_bytes + itemsize * cells
                if bytes_try < best[0]:
                    best = (bytes_try, p_try, groups_try, waste_try)
                if bytes_try <= budget:
                    best = (bytes_try, p_try, groups_try, waste_try)
                    found = True
                    break                           # first fit = nearest
            padded_bytes, admitted_p, est_groups, est_waste = best
            if not found and padded_bytes > budget:
                if (req_rep == "auto" and mesh_shards == 0
                        and tiled_est["tiled_bytes"] <= budget
                        and tiled_est["tiled_kernel_fits"]):
                    # no dense partitioning fits — the tile list does
                    representation = "tiled"
                    padded_bytes = tiled_est["tiled_bytes"]
                    admitted_p = cfg.num_partitions
                    est_groups, est_waste = [], 0.0
                else:
                    raise PlanInfeasibleError(
                        f"plan needs {padded_bytes} padded bytes, over the "
                        f"memory_budget_bytes={budget} admission budget even "
                        f"at the best probed partitioning ({admitted_p} "
                        f"partitions; requested {cfg.num_partitions})",
                        dispatch=cfg.cd_dispatch, backend=backend,
                        padded_bytes=padded_bytes, budget=budget)
            if representation == "dense" and admitted_p != cfg.num_partitions:
                degraded_from = cfg.num_partitions

        cfg_items = tuple(sorted(
            (f.name, _freeze(getattr(cfg, f.name)))
            for f in dataclasses.fields(cfg)))
        signature = (rows_pad, cols_pad, self.side, backend, mesh_shards,
                     admitted_p, representation, cfg_items, self.workload)
        return ExecutionPlan(
            signature=signature, workload=self.workload,
            side=self.side, n_u=g.n_u, n_v=g.n_v, m=g.m,
            backend=backend, kernel_route=kops.route_label(backend),
            kernel_blocks=tuple(cfg.kernel_blocks),
            cd_dispatch=cfg.cd_dispatch,
            num_partitions=admitted_p,
            rows_pad=rows_pad, cols_pad=cols_pad,
            cd_peel_width0=width0,
            cd_host_syncs_bound=(2 if cfg.cd_dispatch == "graph"
                                 else admitted_p + 1),
            fd_update_policy=cfg.fd_update_mode,
            est_fd_groups=est_groups, est_fd_padding_waste=est_waste,
            mesh_shards=mesh_shards,
            degree_sort=cfg.degree_sort,
            padded_bytes=padded_bytes,
            representation=representation,
            cost_model=cost_model,
            memory_budget_bytes=budget if budget is not None else None,
            degraded_from_partitions=degraded_from,
        )

    # ------------------------------------------------------------------ #
    def _plan_wing(self, g: BipartiteGraph, cfg: ReceiptConfig,
                   backend: str, mesh_shards: int) -> ExecutionPlan:
        """Edge-axis (wing / bitruss) plan (DESIGN.md §10).

        Shapes mirror ``engine.wing.build_edge_state`` exactly: the
        biadjacency keeps the FULL ``n_v`` column count (the edge axis
        peels matrix entries, so wedge-incapable columns still anchor
        live edges and cannot be compacted away as the vertex planner
        does), and the support vector lives on ``m_pad`` edge slots.
        The FD phase is ONE stack of P slices of the same biadjacency
        shape (subset s's member holds every edge of subsets >= s), so
        the group estimate is exact up to empty subsets.  Admission
        control downshifts the partition count — each partition is one
        ``rows_pad x cols_pad`` stack member — before rejecting.
        """
        bi, bj, bk = cfg.kernel_blocks
        rows_pad = bucket(max(g.n_u, 1), max(bi, bj))
        cols_pad = bucket(max(g.n_v, 1), bk)
        m_pad = bucket(max(g.m, 1), bj)
        if cfg.peel_width is not None:
            width0 = min(bucket(cfg.peel_width, bj), m_pad)
        else:
            width0 = min(bucket(max(bj, m_pad // 8), bj), m_pad)

        itemsize = 4                                    # f32 regime
        cell_bytes = itemsize * rows_pad * cols_pad     # one stack member
        # CD matrix + FD stack (P members) + ~6 m_pad-length edge vectors
        # (support / alive / theta / eu / ev / peel mask)
        fixed_bytes = cell_bytes + itemsize * 6 * m_pad
        budget = self.memory_budget
        admitted_p = max(cfg.num_partitions, 1)
        degraded_from = None
        padded_bytes = fixed_bytes + cell_bytes * admitted_p
        if budget is not None and padded_bytes > budget:
            if fixed_bytes + cell_bytes > budget:
                raise PlanInfeasibleError(
                    f"the wing device matrix alone needs "
                    f"{fixed_bytes + cell_bytes} padded bytes "
                    f"({rows_pad} x {cols_pad} biadjacency, {m_pad} edge "
                    f"slots, one FD stack member), over the "
                    f"memory_budget_bytes={budget} admission budget — no "
                    "partition downshift can help; raise the budget or "
                    "shrink the graph/blocks",
                    dispatch=cfg.cd_dispatch, backend=backend,
                    padded_bytes=fixed_bytes + cell_bytes, budget=budget)
            p_fit = int((budget - fixed_bytes) // cell_bytes)
            degraded_from = cfg.num_partitions
            admitted_p = max(p_fit, 1)
            padded_bytes = fixed_bytes + cell_bytes * admitted_p
        est_groups = [dict(rows=rows_pad, cols=cols_pad, count=admitted_p)]
        est_waste = (1.0 - g.m / float(admitted_p * rows_pad * cols_pad)
                     if g.m else 0.0)
        cost_model = {
            "requested": getattr(cfg, "representation", "dense"),
            "dense_bytes": padded_bytes,
            "dense_fixed_bytes": fixed_bytes,
            "dense_cells": rows_pad * cols_pad,
            "edge_slots": m_pad,
        }
        cfg_items = tuple(sorted(
            (f.name, _freeze(getattr(cfg, f.name)))
            for f in dataclasses.fields(cfg)))
        signature = (rows_pad, cols_pad, self.side, backend, mesh_shards,
                     admitted_p, "dense", cfg_items, self.workload)
        return ExecutionPlan(
            signature=signature, workload="wing", m_pad=m_pad,
            side=self.side, n_u=g.n_u, n_v=g.n_v, m=g.m,
            backend=backend, kernel_route=kops.route_label(backend),
            kernel_blocks=tuple(cfg.kernel_blocks),
            cd_dispatch=cfg.cd_dispatch,
            num_partitions=admitted_p,
            rows_pad=rows_pad, cols_pad=cols_pad,
            cd_peel_width0=width0,
            cd_host_syncs_bound=(2 if cfg.cd_dispatch == "graph"
                                 else admitted_p + 1),
            fd_update_policy="kernel",
            est_fd_groups=est_groups, est_fd_padding_waste=est_waste,
            mesh_shards=mesh_shards,
            degree_sort=False,          # edge axis never relabels (it
            #                           # would permute canonical edge ids)
            padded_bytes=padded_bytes,
            representation="dense",
            cost_model=cost_model,
            memory_budget_bytes=budget if budget is not None else None,
            degraded_from_partitions=degraded_from,
        )

    # ------------------------------------------------------------------ #
    def _estimate_tiled(self, g: BipartiteGraph, cfg: ReceiptConfig,
                        backend: str) -> Dict[str, Any]:
        """Host-side estimate of the tiled representation's footprint.

        Mirrors what ``engine.tiled.receipt_tiled`` will actually build:
        the DGM pre-compaction (degree-<2 V columns drop out) followed
        by the degree-sort relabeling (which concentrates nonzeros into
        leading tiles), then counts occupied ``block_rows x block_k``
        tiles.  Pure numpy over the edge list — O(m log m), no device
        work.  ``tiled_bytes`` budgets the tile payloads ~3x (the peel
        loop's regather/peel-masked copies) plus the reverse map.
        """
        from ..core.engine.tiled import tiled_blocks

        br, bc = tiled_blocks(cfg)
        eu, ev = g.edges_u, g.edges_v
        if len(ev):
            dv = np.bincount(ev, minlength=g.n_v)
            keep = dv[ev] >= 2
            eu, ev = eu[keep], ev[keep]
        n_cols = max(int(np.unique(ev).size), 1) if len(ev) else 1
        if cfg.degree_sort and len(eu):
            du2 = np.bincount(eu, minlength=g.n_u)
            dv2 = np.bincount(ev, minlength=g.n_v)
            inv_u = np.empty(g.n_u, np.int64)
            inv_u[np.argsort(-du2, kind="stable")] = np.arange(g.n_u)
            inv_v = np.empty(g.n_v, np.int64)
            inv_v[np.argsort(-dv2, kind="stable")] = np.arange(g.n_v)
            eu, ev = inv_u[eu], inv_v[ev]
        rows_pad_t = bucket(max(g.n_u, 1), br)
        cols_pad_t = bucket(n_cols, bc)
        n_rt = rows_pad_t // br
        n_ct = cols_pad_t // bc
        if len(eu):
            occupied = np.unique(eu.astype(np.int64) // br * n_ct
                                 + ev.astype(np.int64) // bc)
            empty_bands = n_rt - np.unique(occupied // n_ct).size
            n_tiles = int(occupied.size) + int(empty_bands)
        else:
            n_tiles = n_rt                      # one filler slot per band
        tiled_bytes = 4 * (3 * n_tiles * br * bc + n_rt * n_ct
                           + 4 * rows_pad_t)
        kernel_shape = (n_rt, n_ct, bucket(n_tiles, 8), br, bc)
        smem, vmem = ktiled.tiled_kernel_bytes(*kernel_shape)
        return {
            "tiled_bytes": int(tiled_bytes),
            "tiled_kernel_smem_bytes": smem,
            "tiled_kernel_vmem_bytes": vmem,
            # only the compiled kernel keeps its tables on the chip
            "tiled_kernel_fits": (backend not in ("pallas", "pallas_sparse")
                                  or ktiled.tiled_kernel_fits(*kernel_shape)),
            "n_tiles": n_tiles,
            "tile_occupancy": n_tiles / float(n_rt * n_ct),
            "tile_blocks": (br, bc),
            "tiled_rows_pad": rows_pad_t,
            "tiled_cols_pad": cols_pad_t,
        }

    # ------------------------------------------------------------------ #
    def _estimate_fd_groups(self, g: BipartiteGraph, cfg: ReceiptConfig,
                            backend: str,
                            num_partitions: Optional[int] = None):
        """Wedge-equipartition ESTIMATE of the FD shape groups.

        CD partitions residual wedge mass roughly evenly over P subsets,
        and vertices peel roughly in wedge-count order — so sorting U by
        static wedge count and cutting the cumulative mass at W/P
        boundaries predicts the subset MEMBER COUNTS, which bucket into
        predicted stack shapes.  This is a planning estimate (the real
        groups depend on supports, HUC and the pre-peel); the bench
        shows it lands within a bucket or two, which is all a capacity
        estimate needs.
        """
        from ..core.engine.fd import _aligns, _level_pad

        row_align, col_align, _ = _aligns(cfg, backend)
        w = np.sort(g.wedge_counts_u().astype(np.float64))
        total = float(w.sum())
        p = max(num_partitions if num_partitions is not None
                else cfg.num_partitions, 1)
        if g.n_u == 0 or total <= 0:
            return [], 0.0
        cum = np.cumsum(w)
        cuts = np.searchsorted(cum, total / p * np.arange(1, p + 1))
        sizes = np.diff(np.concatenate([[0], np.minimum(cuts + 1, g.n_u)]))
        sizes = sizes[sizes > 0]
        cc = _level_pad(max(int((g.degrees_v() >= 2).sum()), 1), col_align)
        shapes: Dict[Tuple[int, int], int] = {}
        used = 0
        for s in sizes:
            mm = _level_pad(int(s), row_align)
            shapes[(mm, cc)] = shapes.get((mm, cc), 0) + 1
            used += int(s) * cc
        groups = [dict(rows=k[0], cols=k[1], count=v)
                  for k, v in sorted(shapes.items(), reverse=True)]
        padded = sum(g_["count"] * g_["rows"] * g_["cols"] for g_ in groups)
        waste = 1.0 - used / padded if padded else 0.0
        return groups, waste


def _freeze(v):
    """Hashable view of a config field value (for the signature)."""
    if isinstance(v, (list, tuple)):
        return tuple(v)
    if isinstance(v, type):
        return v.__name__
    return v
