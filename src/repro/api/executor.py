"""Execution stage: ``Executor`` (executable cache + multi-graph map)
and the ``TipDecomposition`` result object.

**The executable cache** (DESIGN.md §6).  Every device program in the
engine is a module-level jit keyed on shapes and static arguments — but
two of those static arguments used to depend on each graph's DATA (the
CD peel-buffer width sized from the first-sweep snapshot, the FD stack
shapes and gather widths sized per run), so decomposing a fleet of
same-shaped graphs retraced the pipeline per graph.  The Executor keys
a cache entry on ``ExecutionPlan.signature`` (bucketed matrix shape +
full config) and feeds each run the PREVIOUS runs' measured sizing:
peel widths pin to measured values, FD stack dims quantize up to
previously compiled shapes.  Result: repeated graphs of the same
bucketed shape run entirely out of the jit cache — zero retraces — and
the graph-dispatch CD drops its sizing snapshot (one fewer blocking
round trip per graph).

**``Executor.map``** extends the FD shape-group machinery ACROSS
graphs: a fleet of small bipartite graphs (the recsys
millions-of-cohorts scenario, ``examples/recsys_tip_filtering.py``) is
bucketed by padded shape (`core/scheduler.pack_by_shape`), LPT-chunked
under a stack-cell budget (`core/scheduler.lpt_assign`), and each chunk
is decomposed by ONE batched counting kernel + ONE
`batched_level_loop` dispatch + ONE blocking fetch.  A whole-graph tip
decomposition IS a level-peel from the initial supports with ``lo = 0``
(the ParButterfly simultaneous-peel argument: every minimum-support
vertex's tip number equals that support), so the batched path is exact
— bit-identical to per-graph ``tip_decompose`` — while issuing a
handful of dispatches instead of a full pipeline per graph.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
import warnings
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..core.engine import tip_decompose as _engine_tip_decompose
from ..core.engine import wing_decompose_engine as _engine_wing_decompose
from ..core.engine.peel_loop import (
    ReceiptConfig,
    RunStats,
    batched_level_loop,
    bucket,
)
from ..core.graph import BipartiteGraph
from ..core.scheduler import lpt_assign, pack_by_shape
from ..kernels import ops as kops
from ..kernels.butterfly_sparse import batched_row_extents
from ..train.fault_tolerance import StragglerMonitor
from ..utils.spans import span
from . import faults
from .errors import (
    FleetPartialFailure,
    GraphValidationError,
    KernelBackendError,
    PlanInfeasibleError,
    ReceiptError,
    VerificationError,
)
from .plan import ExecutionPlan, Planner

__all__ = ["Executor", "Decomposition", "TipDecomposition",
           "WingDecomposition", "decompose", "verify_tip_decomposition",
           "verify_wing_decomposition"]

# device-program failures the fallback chain recovers from: the taxonomy's
# KernelBackendError (incl. injected faults) plus whatever the XLA runtime
# raises for a failed executable
_KERNEL_FAILURES: Tuple = (KernelBackendError, jax.errors.JaxRuntimeError)

def _warn_degraded(backend: str, exc: BaseException, where) -> None:
    """Every backend degradation is loud: the run stays exact on the
    next stop of the chain, but it no longer measures ``backend``."""
    nxt = kops.fallback_backend(backend)
    warnings.warn(
        f"kernel backend {backend!r} failed ({type(exc).__name__}: {exc}); "
        + (f"degrading to {nxt!r}" if nxt else "no fallback left")
        + f" [{where}]", RuntimeWarning, stacklevel=3)


# failures of a plan's PRIMARY backend before its signature is quarantined
# onto the fallback backend (subsequent runs skip the primary entirely)
_QUARANTINE_AFTER = 2


# --------------------------------------------------------------------- #
# result objects
# --------------------------------------------------------------------- #
class Decomposition:
    """Shared protocol of the two decomposition results (DESIGN.md §11).

    The serving layer handles tip and wing datasets through ONE
    interface: ``numbers`` (the per-element level array — theta per
    peeled-side vertex, psi per edge), ``max_level()``, ``subgraph_at(k)``
    and ``to_dict()``.  The workload-specific spellings
    (``theta``/``max_theta`` on tip, ``edge_wing``/``max_psi`` on wing)
    remain as thin deprecated aliases; new code should use the protocol
    names.

    Subclasses set ``workload`` and ``axis`` and provide ``numbers`` and
    ``subgraph_at`` (the return shapes differ per axis — vertex
    subgraphs carry member/column id maps, edge subgraphs carry the
    surviving edge indices).
    """

    workload: str = ""
    axis: str = ""                   # "vertex" | "edge"

    @property
    def numbers(self) -> np.ndarray:
        """Per-element decomposition levels (int64, canonical order)."""
        raise NotImplementedError

    def max_level(self) -> int:
        """The densest level present (0 for an empty peel axis)."""
        nums = self.numbers
        return int(nums.max()) if nums.size else 0

    def subgraph_at(self, k: float):
        raise NotImplementedError

    def to_dict(self) -> Dict:
        """JSON-able summary: workload, sizes, levels — the service's
        query-response payload shape."""
        g = self.graph                               # type: ignore[attr-defined]
        return {
            "workload": self.workload,
            "axis": self.axis,
            "side": self.side,                       # type: ignore[attr-defined]
            "n_u": int(g.n_u),
            "n_v": int(g.n_v),
            "m": int(g.m),
            "numbers": [int(x) for x in np.asarray(self.numbers)],
            "max_level": self.max_level(),
        }


@dataclasses.dataclass
class TipDecomposition(Decomposition):
    """Result of one tip decomposition: tip numbers + run evidence +
    hierarchy queries.

    ``theta[i]`` is the tip number of vertex ``i`` of the PEELED side
    (``side``); the k-tip hierarchy is nested, so ``subgraph_at(k)``
    induces the maximal subgraph whose peeled-side vertices all sit in
    butterfly density >= k (the paper's k-tip, §2).
    """

    graph: BipartiteGraph            # the ingested (un-transposed) graph
    side: str
    theta: np.ndarray                # int64[n_side]
    stats: RunStats
    plan: Optional[ExecutionPlan] = None

    workload = "tip"
    axis = "vertex"

    @property
    def numbers(self) -> np.ndarray:
        """Protocol view of ``theta`` (``Decomposition.numbers``)."""
        return self.theta

    @property
    def n(self) -> int:
        return int(self.theta.size)

    def vertex_tip(self, v: int) -> int:
        """Tip number of one peeled-side vertex.

        Deprecated alias — prefer ``numbers[v]`` via the shared
        ``Decomposition`` protocol.
        """
        if not 0 <= v < self.theta.size:
            raise IndexError(
                f"vertex {v} out of range for side {self.side!r} "
                f"(n={self.theta.size})")
        return int(self.theta[v])

    def max_theta(self) -> int:
        """Deprecated alias of ``max_level()``."""
        return self.max_level()

    def subgraph_at(self, theta_min: float):
        """The theta_min-tip: the subgraph induced on peeled-side
        vertices with tip number >= ``theta_min`` (plus every V column
        they still touch).

        Returns ``(subgraph, members, v_ids)``: the induced
        ``BipartiteGraph`` (U side compacted to ``members`` order), the
        original peeled-side vertex ids, and the original other-side ids
        of the compacted columns.
        """
        g = self.graph.transposed() if self.side == "V" else self.graph
        members = np.where(self.theta >= theta_min)[0]
        sub, v_ids = g.induced_on_u(members)
        return sub, members, v_ids


@dataclasses.dataclass
class WingDecomposition(Decomposition):
    """Result of one wing (bitruss) decomposition: per-EDGE wing numbers
    + run evidence + hierarchy queries (DESIGN.md §10).

    ``edge_wing[e]`` is the wing number psi of edge ``e`` in the graph's
    CANONICAL edge order (``graph.edges_u[e], graph.edges_v[e]``) —
    regardless of ``side`` (wing numbers are side-symmetric; the
    ``side="V"`` run transposes internally and maps psi back through the
    edge-order permutation).  The k-wing hierarchy is nested, so
    ``subgraph_at(k)`` induces the maximal subgraph whose EDGES all sit
    in butterfly density >= k (the bitruss literature's k-wing / k-tip
    edge analogue, paper §2).
    """

    graph: BipartiteGraph            # the ingested (un-transposed) graph
    side: str
    edge_wing: np.ndarray            # int64[m], canonical edge order
    stats: RunStats
    plan: Optional[ExecutionPlan] = None

    workload = "wing"
    axis = "edge"

    @property
    def numbers(self) -> np.ndarray:
        """Protocol view of ``edge_wing`` (``Decomposition.numbers``)."""
        return self.edge_wing

    @property
    def m(self) -> int:
        return int(self.edge_wing.size)

    def edge_psi(self, e: int) -> int:
        """Wing number of one edge (canonical edge order).

        Deprecated alias — prefer ``numbers[e]`` via the shared
        ``Decomposition`` protocol.
        """
        if not 0 <= e < self.edge_wing.size:
            raise IndexError(
                f"edge {e} out of range (m={self.edge_wing.size})")
        return int(self.edge_wing[e])

    def max_psi(self) -> int:
        """Deprecated alias of ``max_level()``."""
        return self.max_level()

    def subgraph_at(self, psi_min: float):
        """The psi_min-wing: the subgraph of edges with wing number >=
        ``psi_min`` (vertex sets kept at original ids — edges, not
        vertices, are the peeled axis).

        Returns ``(subgraph, edge_ids)``: the induced ``BipartiteGraph``
        and the surviving edges' canonical indices into
        ``graph.edges_u``/``graph.edges_v``.
        """
        keep = np.where(self.edge_wing >= psi_min)[0]
        sub = BipartiteGraph.from_edges(
            self.graph.n_u, self.graph.n_v,
            self.graph.edges_u[keep], self.graph.edges_v[keep])
        return sub, keep


# --------------------------------------------------------------------- #
# executable cache
# --------------------------------------------------------------------- #
@dataclasses.dataclass
class _CacheEntry:
    runs: int = 0
    cd_peel_width: Optional[int] = None
    fd_level_widths: Dict[Tuple[int, int], int] = dataclasses.field(
        default_factory=dict)
    shape_floors: Dict[str, List[int]] = dataclasses.field(
        default_factory=dict)
    # hardened runtime (DESIGN.md §7): per-signature failure bookkeeping.
    # After _QUARANTINE_AFTER primary-backend failures the signature is
    # quarantined — subsequent runs start directly on degraded_backend.
    failures: int = 0
    degraded_backend: Optional[str] = None


class Executor:
    """Holds compiled-pipeline reuse state for one configuration.

    ``decompose(graph)`` plans (or takes a plan), seeds it from the
    cache entry of its shape signature, runs the engine, and folds the
    run's measurements back.  ``map(graphs)`` batches a fleet of small
    graphs through shared dispatches (module docstring).  The same
    Executor can serve any mix of graphs — entries are per signature.
    """

    def __init__(self, config=None, *, side: Optional[str] = None,
                 mesh=None, map_stack_cells: int = 1 << 26,
                 guardrails: bool = True):
        self._planner = Planner(config, side=side)
        self.mesh = mesh
        self.map_stack_cells = int(map_stack_cells)
        self._entries: Dict[Tuple, _CacheEntry] = {}
        self._hits = 0
        self._misses = 0
        self.last_map_report: Optional[Dict] = None
        # hardened runtime (DESIGN.md §7).  guardrails=False strips the
        # degradation machinery from the hot path (no input validation,
        # no fault-point consults, no fallback wrapping, no straggler
        # timing) — the comparator the bench gate measures overhead
        # against; production executors keep the default.
        self.guardrails = bool(guardrails)
        api_cfg = self._planner.config
        spec = api_cfg.fault_spec if api_cfg is not None else None
        self._injector = faults.FaultInjector(spec) if spec else None
        self._stragglers = StragglerMonitor()
        self._fallback_runs = 0
        self._runs = 0                  # decompose/repeel calls: the ``run``
        #                               # of their receipt.* spans
        self._admitted_partitions = self.config.num_partitions
        self._plan_representation = "dense"

    # ------------------------------------------------------------------ #
    @property
    def config(self) -> ReceiptConfig:
        """The engine-layer config this executor runs (legacy currency)."""
        return self._planner.rcfg

    @property
    def side(self) -> str:
        return self._planner.side

    @property
    def workload(self) -> str:
        return self._planner.workload

    @property
    def cache_stats(self) -> Dict[str, int]:
        return dict(entries=len(self._entries), hits=self._hits,
                    misses=self._misses,
                    quarantined=sum(1 for e in self._entries.values()
                                    if e.degraded_backend is not None),
                    fallback_runs=self._fallback_runs)

    @property
    def fault_report(self) -> List[Dict]:
        """Per-rule hit/fire accounting of this executor's injector
        (empty when ``EngineConfig.fault_spec`` is unset)."""
        return self._injector.report() if self._injector else []

    def plan(self, graph: BipartiteGraph) -> ExecutionPlan:
        with span("plan"):
            return self._planner.plan(graph, mesh=self.mesh)

    def _fault_scope(self):
        """Activate this executor's injector (env-armed faults apply
        regardless through ``faults.active_injector``)."""
        if self.guardrails and self._injector is not None:
            return faults.inject(self._injector)
        if not self.guardrails:
            return faults.suppressed()
        return contextlib.nullcontext()

    # ------------------------------------------------------------------ #
    # single-graph plan/compile/execute
    # ------------------------------------------------------------------ #
    def decompose(self, graph: BipartiteGraph,
                  plan: Optional[ExecutionPlan] = None, *,
                  verify: bool = False
                  ) -> Union[TipDecomposition, "WingDecomposition"]:
        """Full RECEIPT decomposition of one graph through the cache.

        ``workload="tip"`` returns a ``TipDecomposition`` (theta per
        peeled-side vertex); ``workload="wing"`` returns a
        ``WingDecomposition`` (psi per edge) — same cache, same fallback
        chain, same plan feedback (DESIGN.md §10).

        ``verify=True`` re-derives the paper's invariants from the result
        (residual butterfly supports at each subset boundary,
        theta/psi containment and bound monotonicity —
        ``verify_tip_decomposition`` / ``verify_wing_decomposition``) and
        records the check count in ``RunStats``; a violation raises
        ``VerificationError``.
        """
        if self.workload == "wing" and self.mesh is not None:
            raise ValueError(
                "workload='wing' runs single-device; the sharded FD "
                "driver is a vertex-axis path (ROADMAP deferred item). "
                "Build the executor without a mesh.")
        self._runs += 1
        with span("decompose", run=self._runs):
            if plan is None:
                plan = self.plan(graph)
            entry = self._seed(plan)
            theta, stats = self._execute(graph, plan, entry)
            self._absorb(plan, entry)
            if self.workload == "wing":
                if verify:
                    stats.verify_checks = verify_wing_decomposition(
                        graph, theta, bounds=stats.bounds,
                        plan_signature=plan.signature)
                    stats.verified = True
                return WingDecomposition(graph=graph, side=self.side,
                                         edge_wing=theta, stats=stats,
                                         plan=plan)
            if verify:
                stats.verify_checks = verify_tip_decomposition(
                    graph, self.side, theta, bounds=stats.bounds,
                    plan_signature=plan.signature)
                stats.verified = True
            return TipDecomposition(graph=graph, side=self.side,
                                    theta=theta, stats=stats, plan=plan)

    # ------------------------------------------------------------------ #
    # incremental re-peel (serving layer, DESIGN.md §11)
    # ------------------------------------------------------------------ #
    def repeel(self, graph: BipartiteGraph, *, sup0: np.ndarray,
               numbers_old: np.ndarray, stops: Sequence[float],
               watch: np.ndarray,
               plan: Optional[ExecutionPlan] = None) -> Tuple[np.ndarray,
                                                              RunStats]:
        """Exact incremental refresh: prefix re-peel of the POST-mutation
        ``graph`` from delta-maintained supports, stopping at the first
        CD bound that clears the mutation ceiling
        (``core.engine.refresh`` module docstring).

        ``sup0``/``numbers_old`` are the maintained whole-graph supports
        and the pre-mutation levels on the PEELED axis in canonical
        order (per-vertex for tip — ``side="V"`` transposes internally,
        exactly like ``decompose`` — per-edge for wing); ``stops`` is
        the ascending stop-level ladder (first rung already above the
        deletion ceiling); ``watch`` the inserted elements whose new
        levels certify the insertion ceiling.

        Runs SINGLE-backend (the plan's choice, no fallback walk): the
        service layer's degradation story for a failed refresh is a full
        ``decompose`` recompute, not a slower exact replay of the same
        delta.  Plans routed to the tiled representation are rejected —
        the refresh loops are dense-geometry.

        Returns ``(numbers_new int64, stats)`` with the refresh evidence
        fields (``stats.refresh_stop`` etc.) populated by the engine;
        bit-identical to ``decompose(graph).numbers``.
        """
        from ..core.engine import repeel_tip_prefix, repeel_wing_prefix

        self._runs += 1
        with span("repeel", run=self._runs):
            if plan is None:
                plan = self.plan(graph)
            if plan.representation == "tiled":
                raise PlanInfeasibleError(
                    "incremental re-peel runs on the dense geometry; this "
                    "plan routed to the tiled representation — refresh by "
                    "full recompute instead", plan_signature=plan.signature,
                    dispatch="repeel")
            entry = self._seed(plan)
            rcfg = self._run_cfg(plan.backend)
            if self.workload == "tip" and self.side == "V":
                graph = graph.transposed()
            stats = RunStats()
            stats.refresh_mode = "delta"
            with self._fault_scope():
                if self.workload == "wing":
                    numbers, _stop = repeel_wing_prefix(
                        graph, sup0, numbers_old, stops, watch, rcfg, stats,
                        plan=plan)
                else:
                    numbers, _stop = repeel_tip_prefix(
                        graph, sup0, numbers_old, stops, watch, rcfg, stats,
                        plan=plan)
            stats.backend_used = plan.backend
            self._absorb(plan, entry)
        return numbers, stats

    def _run_cfg(self, backend: str) -> ReceiptConfig:
        """Engine config for one (possibly degraded) execution attempt."""
        rcfg = self.config
        kw = {}
        if kops.resolve_backend(rcfg.backend) != backend:
            kw["backend"] = backend
        if self._planner.memory_budget is not None:
            # admission control may have downshifted the partition count;
            # the plan's value is authoritative (plan.num_partitions)
            kw["num_partitions"] = self._admitted_partitions
        if rcfg.representation != self._plan_representation:
            # the Planner's cost model resolved "auto" (or admission
            # control rerouted); the plan's representation is authoritative
            kw["representation"] = self._plan_representation
        return dataclasses.replace(rcfg, **kw) if kw else rcfg

    def _execute(self, graph: BipartiteGraph, plan: ExecutionPlan,
                 entry: _CacheEntry):
        """Run the engine, walking the backend fallback chain on kernel
        failure (DESIGN.md §7): ``pallas -> interpret -> xla`` (each stop
        exact), quarantining the plan signature after repeated primary
        failures so later same-signature runs skip the broken backend."""
        self._admitted_partitions = plan.num_partitions
        self._plan_representation = plan.representation
        if not self.guardrails:
            with self._fault_scope():
                theta, stats = self._engine_run(
                    graph, self._run_cfg(plan.backend), plan)
            stats.backend_used = plan.backend
            return theta, stats
        primary = plan.backend
        start = entry.degraded_backend or primary
        chain = kops.fallback_chain(start)
        failed: List[str] = []
        last: Optional[Exception] = None
        with self._fault_scope():
            for b in chain:
                try:
                    theta, stats = self._engine_run(
                        graph, self._run_cfg(b), plan)
                except _KERNEL_FAILURES as e:
                    failed.append(b)
                    last = e
                    _warn_degraded(b, e, plan.signature)
                    if b == primary:
                        entry.failures += 1
                        nxt = kops.fallback_backend(b)
                        if (entry.failures >= _QUARANTINE_AFTER
                                and entry.degraded_backend is None
                                and nxt is not None):
                            entry.degraded_backend = nxt
                    continue
                stats.backend_used = b
                stats.backend_fallbacks = list(failed)
                stats.quarantined = entry.degraded_backend is not None
                if failed:
                    self._fallback_runs += 1
                return theta, stats
        raise KernelBackendError(
            f"every backend in the fallback chain failed: "
            f"{' -> '.join(chain)} (last: {type(last).__name__}: {last})",
            plan_signature=plan.signature, dispatch=plan.cd_dispatch,
            backend=chain[-1])

    def _engine_run(self, graph: BipartiteGraph, cfg: ReceiptConfig,
                    plan: ExecutionPlan):
        """One engine invocation of the plan's workload (the fallback
        chain retries this per backend)."""
        with span("engine", backend=kops.resolve_backend(cfg.backend)):
            if self.workload == "wing":
                return _engine_wing_decompose(graph, cfg, side=self.side,
                                              plan=plan)
            return _engine_tip_decompose(graph, cfg, side=self.side,
                                         mesh=self.mesh, plan=plan)

    def _seed(self, plan: ExecutionPlan) -> _CacheEntry:
        entry = self._entries.get(plan.signature)
        if entry is None:
            self._misses += 1
            entry = _CacheEntry()
            self._entries[plan.signature] = entry
        else:
            self._hits += 1
            plan.measured.cd_peel_width = entry.cd_peel_width
            plan.measured.fd_level_widths = dict(entry.fd_level_widths)
            plan.measured.shape_floors = {
                k: list(v) for k, v in entry.shape_floors.items()}
        plan.measured.runs = entry.runs
        return entry

    def _absorb(self, plan: ExecutionPlan, entry: _CacheEntry) -> None:
        m = plan.measured
        if m.cd_peel_width is not None:
            entry.cd_peel_width = max(entry.cd_peel_width or 0,
                                      m.cd_peel_width)
        for shape, width in m.fd_level_widths.items():
            entry.fd_level_widths[shape] = max(
                entry.fd_level_widths.get(shape, 1), width)
        for name, seen in m.observed_dims.items():
            merged = set(entry.shape_floors.get(name, ())) | seen
            entry.shape_floors[name] = sorted(merged)
        entry.runs += 1
        m.runs = entry.runs

    # ------------------------------------------------------------------ #
    # multi-graph batched decomposition
    # ------------------------------------------------------------------ #
    def map(self, graphs: Sequence[BipartiteGraph], *,
            strict: bool = False
            ) -> List[Union[TipDecomposition, ReceiptError]]:
        """Decompose a fleet of small graphs in a handful of batched
        dispatches (module docstring).  Exact: bit-identical tip numbers
        to per-graph ``decompose``/``tip_decompose``.

        Per shape bucket (rows x wedge-capable cols, pow2-ish), graphs
        are LPT-chunked under ``map_stack_cells`` and each chunk costs
        one batched counting kernel, one batched level loop (re-entered
        only on a ``max_sweeps`` cap-exit) and ONE blocking fetch.
        ``last_map_report`` records the dispatch accounting the bench
        and the acceptance tests compare against the sequential path.

        **Fleet isolation** (DESIGN.md §7): one bad member does not sink
        the fleet.  The returned list has one slot PER INPUT GRAPH — a
        ``TipDecomposition`` for every healthy member, the member's own
        ``ReceiptError`` for every failed one.  A chunk whose batched
        dispatch fails is retried down the backend fallback chain, and
        on the terminal backend each member is re-run alone so only the
        genuinely bad graph carries an error.  ``strict=True`` restores
        raise-on-any-failure as a ``FleetPartialFailure`` aggregating
        the per-graph errors.
        """
        cfg = self.config
        if self.workload != "tip":
            # structured (PR 6 taxonomy): the plan — not the input — is
            # infeasible; PlanInfeasibleError IS a ValueError, so
            # pre-taxonomy `except ValueError` handlers keep working
            raise PlanInfeasibleError(
                "Executor.map batches VERTEX-axis (tip) decompositions; "
                f"workload={self.workload!r} is not mappable — use "
                "Executor.decompose per graph (the wing FD stack already "
                "batches its subsets)", dispatch="map")
        if self.mesh is not None:
            raise ValueError(
                "Executor.map runs single-device; sharding map chunks "
                "over a mesh is not implemented (ROADMAP deferred item). "
                "Use Executor.decompose(graph) for mesh execution, or "
                "build the executor without a mesh.")
        t0 = time.perf_counter()
        backend = kops.resolve_backend(cfg.backend)
        blocks = cfg.kernel_blocks
        results: List[Optional[TipDecomposition]] = [None] * len(graphs)
        errors: Dict[int, ReceiptError] = {}
        report = dict(n_graphs=len(graphs), groups=0, chunks=0,
                      counting_dispatches=0, device_loop_calls=0,
                      host_round_trips=0, cache_hits=0, cache_misses=0,
                      backend=backend, wall_s=0.0,
                      chunk_failures=0, chunk_retries=0, isolated_graphs=0,
                      errors={}, stragglers=[])
        with self._fault_scope():
            tasks = []
            for i, g in enumerate(graphs):
                try:
                    tasks.append(self._map_task(i, g))
                except ReceiptError as e:
                    errors[i] = e

            groups = pack_by_shape(
                tasks,
                size_of=lambda t: (t["rows_pad"], t["cols_pad"]),
                weight_of=lambda t: t["wedges"],
                bucket=lambda n: n,    # tasks carry pre-bucketed shapes
            )
            report["groups"] = len(groups)
            for group in groups:
                mm, cc = group[0]["rows_pad"], group[0]["cols_pad"]
                # LPT-chunk the group under the stack-cell budget:
                # balanced chunks (by wedge mass), each one batched
                # dispatch.  The fit count rounds DOWN to a power of two
                # so the padded group dim (bucket(g, 1) in _map_chunk)
                # never exceeds the budget the caller sized to device
                # memory.
                per_graph = mm * cc
                n_fit = max(int(self.map_stack_cells // max(per_graph, 1)),
                            1)
                n_fit = 1 << (n_fit.bit_length() - 1)
                n_chunks = max(-(-len(group) // n_fit), 1)
                chunks = lpt_assign([t["wedges"] for t in group], n_chunks)
                for chunk_idx in chunks:
                    # LPT balances wedge mass, not counts — slice any
                    # chunk that still exceeds the fit count so the
                    # padded stack never overruns the budget
                    for lo_i in range(0, len(chunk_idx), n_fit):
                        part = chunk_idx[lo_i:lo_i + n_fit]
                        self._map_chunk_guarded(
                            [group[i] for i in part], mm, cc, backend,
                            blocks, results, report, errors)
        # straggler flagging: per-chunk wall clocks EWMA'd in the shared
        # StragglerMonitor; members of flagged chunks carry the mark
        strag = set(self._stragglers.stragglers())
        if strag:
            report["stragglers"] = sorted(
                s for s in strag if isinstance(s, tuple) and s[0] == "map")
            for r in results:
                if (r is not None
                        and getattr(r.stats, "chunk_sig", None) in strag):
                    r.stats.straggler = True
        report["errors"] = {
            i: f"{type(e).__name__}: {e}" for i, e in sorted(errors.items())}
        report["wall_s"] = time.perf_counter() - t0
        self.last_map_report = report
        if errors and strict:
            raise FleetPartialFailure(
                "Executor.map(strict=True)", errors=errors,
                n_ok=sum(1 for r in results if r is not None),
                backend=backend)
        out: List[Union[TipDecomposition, ReceiptError]] = list(results)
        for i, e in errors.items():
            out[i] = e
        return out

    # ------------------------------------------------------------------ #
    def _map_task(self, idx: int, graph: BipartiteGraph) -> Dict:
        """Ingest one graph of the fleet: side selection, degree-sort
        relabeling (tile density, exactly as `engine.tip_decompose`),
        wedge-capable column compaction, bucketed shape."""
        cfg = self.config
        if not isinstance(graph, BipartiteGraph):
            raise GraphValidationError(
                f"Executor.map expects BipartiteGraphs, got "
                f"{type(graph).__name__}", graph_index=idx)
        if self.guardrails:
            try:
                graph.validate()
            except GraphValidationError as e:
                raise GraphValidationError(
                    e.message, graph_index=idx, **e.context) from None
        g = graph.transposed() if self.side == "V" else graph
        if cfg.degree_sort:
            perm_u = np.argsort(-g.degrees_u(), kind="stable")
            perm_v = np.argsort(-g.degrees_v(), kind="stable")
            inv_u = np.empty_like(perm_u)
            inv_u[perm_u] = np.arange(g.n_u)
            inv_v = np.empty_like(perm_v)
            inv_v[perm_v] = np.arange(g.n_v)
            g_work = BipartiteGraph.from_edges(
                g.n_u, g.n_v, inv_u[g.edges_u], inv_v[g.edges_v])
        else:
            perm_u = np.arange(g.n_u)
            g_work = g
        # drop V columns that cannot center a wedge (the DGM compaction)
        sub, _ = g_work.induced_on_u(np.arange(g_work.n_u), min_degree_v=2)
        bi, bj, bk = cfg.kernel_blocks
        backend = kops.resolve_backend(cfg.backend)
        row_align = 8 if backend == "xla" else max(bi, bj)
        col_align = 8 if backend == "xla" else bk
        return dict(
            idx=idx, graph=graph, n_u=g.n_u, perm_u=perm_u, sub=sub,
            rows_pad=bucket(max(g.n_u, 1), row_align),
            cols_pad=bucket(max(sub.n_v, 1), col_align),
            wedges=float(sub.wedge_counts_u().sum()),
        )

    def _map_chunk_guarded(self, chunk: List[Dict], mm: int, cc: int,
                           backend: str, blocks, results: List,
                           report: Dict, errors: Dict[int, ReceiptError]
                           ) -> None:
        """Fleet isolation around one chunk dispatch (DESIGN.md §7).

        The batched dispatch is retried down the backend fallback chain
        (whole chunk — the cheap case: a backend bug / injected launch
        fault affects every member equally).  If the TERMINAL backend
        still fails, members are re-run one at a time so the error is
        pinned to the graph(s) that actually caused it; healthy members
        of a failing chunk keep their (bit-identical) results.
        """
        if not self.guardrails:
            self._map_chunk(chunk, mm, cc, backend, blocks, results,
                            report)
            return
        chain = kops.fallback_chain(backend)
        for j, b in enumerate(chain):
            terminal = j == len(chain) - 1
            try:
                self._map_chunk(chunk, mm, cc, b, blocks, results, report)
                if j:
                    report["chunk_retries"] += 1
                    self._fallback_runs += 1
                return
            except _KERNEL_FAILURES as e:
                report["chunk_failures"] += 1
                _warn_degraded(b, e, ("map", mm, cc))
                if not terminal:
                    continue
                if len(chunk) == 1:
                    raise          # single member: the per-graph handler
                #                  # below owns the error slot
                # terminal backend, multi-member chunk: isolate per graph
                for t in chunk:
                    try:
                        self._map_chunk([t], mm, cc, b, blocks, results,
                                        report)
                        report["isolated_graphs"] += 1
                    except _KERNEL_FAILURES as e:
                        errors[t["idx"]] = (
                            e if isinstance(e, ReceiptError) else
                            KernelBackendError(
                                f"map chunk member failed on terminal "
                                f"backend: {type(e).__name__}: {e}",
                                backend=b, graph_index=t["idx"]))
                return
            except ReceiptError as e:
                # non-kernel failure (overflow bound, injected map_chunk
                # fault on the fetch): not a backend problem, isolate
                # straight away
                report["chunk_failures"] += 1
                if len(chunk) == 1:
                    errors[chunk[0]["idx"]] = e
                    return
                for t in chunk:
                    try:
                        self._map_chunk([t], mm, cc, b, blocks, results,
                                        report)
                        report["isolated_graphs"] += 1
                    except (ReceiptError,) + _KERNEL_FAILURES as pe:
                        errors[t["idx"]] = (
                            pe if isinstance(pe, ReceiptError) else
                            KernelBackendError(
                                f"map chunk member failed: "
                                f"{type(pe).__name__}: {pe}",
                                backend=b, graph_index=t["idx"]))
                return

    def _map_chunk(self, chunk: List[Dict], mm: int, cc: int, backend: str,
                   blocks, results: List, report: Dict) -> None:
        """Decompose one stacked chunk: batched counting + batched level
        peel + one fetch."""
        t_chunk = time.perf_counter()
        faults.fault_point(
            "map_chunk", KernelBackendError, chunk=report["chunks"],
            backend=backend, n_graphs=len(chunk))
        cfg = self.config
        sparse = backend in kops.SPARSE_BACKENDS
        g_real = len(chunk)
        g_pad = bucket(g_real, 1)               # pow2 group dim: stable
        #                                       # stack shapes across calls
        sig = ("map", g_pad, mm, cc, backend, tuple(blocks),
               cfg.fd_update_mode, cfg.max_sweeps)
        if sig in self._entries:
            self._hits += 1
            report["cache_hits"] += 1
        else:
            self._misses += 1
            report["cache_misses"] += 1
            self._entries[sig] = _CacheEntry()
        self._entries[sig].runs += 1

        a = np.zeros((g_pad, mm, cc), np.float32)
        nmem = np.zeros(g_pad, np.int32)
        for k, t in enumerate(chunk):
            s = t["sub"]
            a[k, s.edges_u, s.edges_v] = 1.0
            nmem[k] = t["n_u"]
        alive0 = np.arange(mm)[None, :] < nmem[:, None]
        dv0 = a.sum(axis=1)

        a_dev = jnp.asarray(a)
        alive_dev = jnp.asarray(alive0)
        ids = jnp.broadcast_to(
            jnp.arange(mm, dtype=jnp.int32)[None, :], (g_pad, mm))
        if sparse:
            rext = batched_row_extents(a, blocks[2])
            kma = rext.reshape(g_pad, -1, blocks[0]).max(axis=2)
            kma = jnp.asarray(kma.astype(np.int32))
            rext_dev = jnp.asarray(rext)
        else:
            kma = None
            rext_dev = jnp.zeros((g_pad, mm), jnp.int32)
        # batched per-vertex counting: one kernel call for the chunk
        sup0 = kops.butterfly_update_batched(
            a_dev, a_dev, alive_dev.astype(a_dev.dtype), ids, ids,
            backend=backend, blocks=blocks, kmax_a=kma, kmax_b=kma)
        report["counting_dispatches"] += 1
        sup0 = jnp.where(alive_dev, sup0, jnp.inf)
        if cfg.fd_update_mode == "auto":
            update_mode = ("b2" if g_pad * mm * mm <= cfg.fd_b2_cells
                           else "kernel")
        else:
            update_mode = cfg.fd_update_mode
        lo = jnp.zeros(g_pad, jnp.float32)

        # whole-graph level peel (lo=0 == the exact ParB schedule);
        # peel_width=mm selects the mask form statically — small-graph
        # stacks are flop-cheap, so no gather machinery is needed
        out = batched_level_loop(
            a_dev, rext_dev, sup0, alive_dev, jnp.asarray(dv0), lo,
            backend=backend, blocks=blocks, peel_width=mm,
            max_sweeps=cfg.max_sweeps, update_mode=update_mode)
        report["device_loop_calls"] += 1
        # drain with cap-exit re-entry (theta/rho/wedges accumulate per
        # invocation, exactly like the FD group drain)
        th_acc = np.zeros((g_pad, mm), np.float64)
        rho_acc = np.zeros(g_pad, np.int64)
        wedges_acc = np.zeros(g_pad, np.float64)
        prev_alive = alive0
        while True:
            sup, alive, dv, th, rho, wedges, _maxlev, _sweeps = out
            th_h, alive_h, rho_h, wedges_h = jax.device_get(
                (th, alive, rho, wedges))
            report["host_round_trips"] += 1
            alive_h = np.asarray(alive_h)
            newly_dead = prev_alive & ~alive_h
            th_acc = np.where(newly_dead, np.asarray(th_h, np.float64),
                              th_acc)
            rho_acc += np.asarray(rho_h, np.int64)
            wedges_acc += np.asarray(wedges_h, np.float64)
            if not alive_h.any() or int(np.asarray(rho_h).sum()) == 0:
                break
            prev_alive = alive_h
            out = batched_level_loop(                  # cap-exit re-entry
                a_dev, rext_dev, sup, alive, dv, lo,
                backend=backend, blocks=blocks, peel_width=mm,
                max_sweeps=cfg.max_sweeps, update_mode=update_mode)
            report["device_loop_calls"] += 1
        report["chunks"] += 1
        chunk_id = ("map", mm, cc, report["chunks"])
        if self.guardrails:
            self._stragglers.record(chunk_id,
                                    time.perf_counter() - t_chunk)

        from ..core.engine.refresh import synthesize_bounds

        for k, t in enumerate(chunk):
            theta = np.zeros(t["n_u"], np.int64)
            theta[t["perm_u"]] = np.round(th_acc[k, : t["n_u"]]).astype(
                np.int64)
            stats = RunStats()
            stats.rho_fd = int(rho_acc[k])
            stats.wedges_fd = int(wedges_acc[k])
            stats.wedges_pvbcnt = t["graph"].counting_wedge_bound()
            stats.backend_used = backend
            stats.chunk_sig = chunk_id     # straggler flagging key (map)
            # the whole-graph level schedule never built CD's theta-range
            # partition, but the exact theta in hand quantizes into an
            # equi-mass stop ladder — so a mapped result's first refresh
            # re-peels a bounded prefix instead of one [inf] rung
            stats.bounds = synthesize_bounds(theta, cfg.num_partitions)
            results[t["idx"]] = TipDecomposition(
                graph=t["graph"], side=self.side, theta=theta, stats=stats)


# --------------------------------------------------------------------- #
# verify mode: recompute the paper's invariants from the result
# --------------------------------------------------------------------- #
def _butterfly_supports_host(g: BipartiteGraph,
                             members: np.ndarray) -> np.ndarray:
    """Butterfly supports of ``members`` in their induced subgraph,
    recomputed on the host with an INDEPENDENT formulation (float64
    dense wedge matrix ``W = A @ A.T``, ``B[u] = sum_{u'!=u}
    C(W[u,u'], 2)``) so verify mode shares no code with the kernels it
    checks."""
    pos = np.full(g.n_u, -1, np.int64)
    pos[members] = np.arange(members.size)
    keep = pos[g.edges_u] >= 0
    a = np.zeros((members.size, g.n_v), np.float64)
    a[pos[g.edges_u[keep]], g.edges_v[keep]] = 1.0
    w = a @ a.T
    cw = w * (w - 1.0) / 2.0
    np.fill_diagonal(cw, 0.0)
    return cw.sum(axis=1)


def verify_tip_decomposition(graph: BipartiteGraph, side: str,
                             theta: np.ndarray, *,
                             bounds: Optional[Sequence[float]] = None,
                             max_boundaries: int = 8,
                             plan_signature=None) -> int:
    """Check a claimed tip decomposition against RECEIPT's invariants;
    returns the number of checks performed, raises ``VerificationError``
    on the first violation.

    Checks (DESIGN.md §7):

    1. shape/domain: ``theta`` covers the peeled side, no negatives;
    2. support bound: ``theta[u] <= B0[u]`` (a vertex's tip number never
       exceeds its initial butterfly support — peeling only lowers it);
    3. bound monotonicity: the CD subset bounds are non-decreasing and
       ``theta.max() < bounds[-1]`` (Alg. 3's termination guarantee);
    4. theta containment at each boundary ``b``: the member set
       ``{u : theta[u] >= b}`` must be a b-tip — every member's support
       INDUCED ON THE SET is >= b.  By maximality of the b-tip this
       catches any upward-corrupted theta: a vertex that does not belong
       drags its induced support below b.

    Supports are recomputed host-side by an independent dense float64
    formulation (``_butterfly_supports_host``) — no kernel code shared
    with the path under test.
    """
    g = graph.transposed() if side == "V" else graph
    th = np.asarray(theta)
    checks = 0

    def _fail(msg, **ctx):
        raise VerificationError(msg, plan_signature=plan_signature, **ctx)

    if th.shape != (g.n_u,):
        _fail(f"theta shape {th.shape} != peeled side ({g.n_u},)")
    checks += 1
    if th.size == 0:
        return checks
    if np.any(th < 0):
        _fail(f"negative tip numbers at "
              f"{np.where(th < 0)[0][:4].tolist()}")
    checks += 1

    sup0 = _butterfly_supports_host(g, np.arange(g.n_u))
    bad = np.where(th > sup0 + 0.5)[0]
    if bad.size:
        u = int(bad[0])
        _fail(f"theta exceeds initial butterfly support: theta[{u}]="
              f"{int(th[u])} > B0[{u}]={sup0[u]:.0f} "
              f"({bad.size} violation(s))")
    checks += 1

    if bounds:
        bs = [float(b) for b in bounds]
        if any(b2 < b1 for b1, b2 in zip(bs, bs[1:])):
            _fail(f"CD subset bounds not monotone: {bs}")
        checks += 1
        if float(th.max()) >= bs[-1]:
            _fail(f"theta.max()={int(th.max())} >= terminal bound "
                  f"{bs[-1]} (bounds[-1] must exceed theta_max)")
        checks += 1
        levels = sorted({b for b in bs if 0.0 < b < np.inf})
    else:
        # no CD bounds recorded (Executor.map results): probe up to
        # max_boundaries distinct positive theta levels instead
        uniq = np.unique(th[th > 0]).astype(np.float64)
        if uniq.size > max_boundaries:
            pick = np.linspace(0, uniq.size - 1, max_boundaries)
            uniq = uniq[np.round(pick).astype(int)]
        levels = [float(b) for b in uniq]

    for b in levels:
        members = np.where(th >= b)[0]
        if members.size == 0:
            continue
        sup = _butterfly_supports_host(g, members)
        low = np.where(sup < b - 0.5)[0]
        if low.size:
            u = int(members[low[0]])
            _fail(f"theta containment violated at boundary {b:.0f}: "
                  f"vertex {u} (theta={int(th[u])}) has induced support "
                  f"{sup[low[0]]:.0f} < {b:.0f}", boundary=b)
        checks += 1
    return checks


def _edge_supports_host(g: BipartiteGraph, keep: np.ndarray) -> np.ndarray:
    """Butterfly supports of the ``keep`` edges in the subgraph they
    induce, recomputed on the host with an INDEPENDENT route (float64
    wedge matrix ``W = A @ A.T``; the support of edge (u, v) is
    ``sum_{u'!=u} A[u', v] * (W[u, u'] - 1)``, i.e. ``(W @ A)[u, v] -
    du[u] - dv[v] + 1``) — no code shared with the kernels it checks."""
    eu, ev = g.edges_u[keep], g.edges_v[keep]
    a = np.zeros((g.n_u, g.n_v), np.float64)
    a[eu, ev] = 1.0
    s = (a @ a.T) @ a
    du = a.sum(axis=1)
    dvv = a.sum(axis=0)
    return s[eu, ev] - du[eu] - dvv[ev] + 1.0


def verify_wing_decomposition(graph: BipartiteGraph, psi: np.ndarray, *,
                              bounds: Optional[Sequence[float]] = None,
                              max_boundaries: int = 8,
                              plan_signature=None) -> int:
    """Check a claimed wing decomposition against RECEIPT's invariants
    (the edge-axis analogue of ``verify_tip_decomposition``); returns
    the number of checks performed, raises ``VerificationError`` on the
    first violation.

    Checks (DESIGN.md §10):

    1. shape/domain: ``psi`` covers the canonical edge list, no
       negatives;
    2. support bound: ``psi[e] <= B0[e]`` (an edge's wing number never
       exceeds its initial butterfly support);
    3. bound monotonicity: CD subset bounds non-decreasing and
       ``psi.max() < bounds[-1]``;
    4. psi containment at each boundary ``b``: the edge set
       ``{e : psi[e] >= b}`` must be a b-wing — every kept edge's
       support INDUCED ON THE SET is >= b.

    ``psi`` is side-agnostic (wing numbers are side-symmetric), so no
    ``side`` parameter: supports are recomputed on the graph's canonical
    edge order directly.
    """
    g = graph
    ps = np.asarray(psi)
    checks = 0

    def _fail(msg, **ctx):
        raise VerificationError(msg, plan_signature=plan_signature, **ctx)

    if ps.shape != (g.m,):
        _fail(f"psi shape {ps.shape} != canonical edge list ({g.m},)")
    checks += 1
    if ps.size == 0:
        return checks
    if np.any(ps < 0):
        _fail(f"negative wing numbers at "
              f"{np.where(ps < 0)[0][:4].tolist()}")
    checks += 1

    sup0 = _edge_supports_host(g, np.arange(g.m))
    bad = np.where(ps > sup0 + 0.5)[0]
    if bad.size:
        e = int(bad[0])
        _fail(f"psi exceeds initial butterfly support: psi[{e}]="
              f"{int(ps[e])} > B0[{e}]={sup0[e]:.0f} "
              f"({bad.size} violation(s))")
    checks += 1

    if bounds:
        bs = [float(b) for b in bounds]
        if any(b2 < b1 for b1, b2 in zip(bs, bs[1:])):
            _fail(f"CD subset bounds not monotone: {bs}")
        checks += 1
        if float(ps.max()) >= bs[-1]:
            _fail(f"psi.max()={int(ps.max())} >= terminal bound "
                  f"{bs[-1]} (bounds[-1] must exceed psi_max)")
        checks += 1
        levels = sorted({b for b in bs if 0.0 < b < np.inf})
    else:
        uniq = np.unique(ps[ps > 0]).astype(np.float64)
        if uniq.size > max_boundaries:
            pick = np.linspace(0, uniq.size - 1, max_boundaries)
            uniq = uniq[np.round(pick).astype(int)]
        levels = [float(b) for b in uniq]

    for b in levels:
        keep = np.where(ps >= b)[0]
        if keep.size == 0:
            continue
        sup = _edge_supports_host(g, keep)
        low = np.where(sup < b - 0.5)[0]
        if low.size:
            e = int(keep[low[0]])
            _fail(f"psi containment violated at boundary {b:.0f}: edge "
                  f"{e} ({int(g.edges_u[e])},{int(g.edges_v[e])}) "
                  f"(psi={int(ps[e])}) has induced support "
                  f"{sup[low[0]]:.0f} < {b:.0f}", boundary=b)
        checks += 1
    return checks


# --------------------------------------------------------------------- #
# one-shot convenience (the compat wrappers' entry point)
# --------------------------------------------------------------------- #
def decompose(graph: BipartiteGraph, config=None, *,
              side: Optional[str] = None, mesh=None,
              plan: Optional[ExecutionPlan] = None,
              verify: bool = False
              ) -> Union[TipDecomposition, WingDecomposition]:
    """Plan + execute one decomposition on a fresh Executor.

    ``config`` may be an ``EngineConfig``, a legacy ``ReceiptConfig``
    (the compat wrappers' currency) or None.  A fresh Executor means no
    cross-call measured-sizing reuse — byte-for-byte the legacy engine
    behavior; hold an ``Executor`` to get the executable cache.
    ``EngineConfig(workload="wing")`` returns a ``WingDecomposition``.
    """
    return Executor(config, side=side, mesh=mesh).decompose(
        graph, plan=plan, verify=verify)
