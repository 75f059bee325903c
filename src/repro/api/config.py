"""`EngineConfig` — the frozen, serializable service-layer configuration.

The engine's `ReceiptConfig` grew into a 20-knob kwarg sprawl whose
validation was scattered across whichever driver read a knob first.
`EngineConfig` is the planning/execution layer's replacement (DESIGN.md
§6): a FROZEN dataclass validated completely at construction, with a
strict ``to_dict``/``from_dict`` round trip so service configs survive
JSON/YAML storage without silently dropping or inventing knobs.

Two validation tiers:

* the engine floor (shared with ``ReceiptConfig.__post_init__``):
  value-range and enum checks every config object must clear;
* the service layer's stricter cross-knob rules — combinations that run
  but silently diverge from the benchmarked configuration
  (``cd_dispatch="graph"`` with ``use_dgm=False`` pays the stale
  whole-graph HUC bound the bench gates against) are rejected here with
  an actionable message.  ``ReceiptConfig`` keeps permitting them for
  A/B experiments (the dgm-off equivalence tests rely on that).

``dtype`` is a STRING here (serializability); only ``"float32"`` is
accepted — the engine's bit-exactness contract is the f32 integer
regime (DESIGN.md §8), and a wider policy would silently break it.
"""
from __future__ import annotations

import dataclasses
import difflib
from typing import Any, Dict, Optional, Tuple

import jax.numpy as jnp

from ..core.engine.peel_loop import ReceiptConfig

__all__ = ["EngineConfig"]

_DTYPES = ("float32",)


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Frozen service-layer configuration (see module docstring).

    Field semantics match ``ReceiptConfig`` (DESIGN.md §2.2 "Knobs")
    plus ``side``: which vertex set to peel (``"V"`` transposes the
    graph — exact by symmetry, the paper's Table 3 *V rows).
    """

    side: str = "U"
    workload: str = "tip"
    #   which decomposition runs (DESIGN.md §10): "tip" peels vertices
    #   (theta per U/V vertex), "wing" peels EDGES (psi per edge) on the
    #   same engine through DELTA_RULES["edge"].  API-layer only — the
    #   Executor selects the engine driver; the engine's ReceiptConfig
    #   is workload-agnostic.
    num_partitions: int = 8
    backend: Optional[str] = None
    kernel_blocks: Tuple[int, int, int] = (128, 128, 512)
    use_huc: bool = True
    use_dgm: bool = True
    degree_sort: bool = True
    dgm_row_threshold: float = 0.7
    cd_dispatch: str = "subset"
    dtype: str = "float32"
    max_sweeps: int = 100_000
    peel_width: Optional[int] = None
    fd_update_mode: str = "auto"
    fd_b2_cells: int = 1 << 24
    representation: str = "auto"
    #   biadjacency layout: "dense" (padded matrix through CD + FD),
    #   "tiled" (nonzero-block slot list through the whole-graph
    #   level-peel engine), or "auto" — the Planner's cost model picks
    #   per graph (DESIGN.md §9: dense below the measured density/size
    #   crossover, tiled above it or whenever the dense matrix would
    #   blow the memory budget).  The engine default is "dense";
    #   the service layer defaults to routing.
    tiled_regather_every: int = 1
    fd_prepeel_levels: int = 4
    #   max support levels the FD host pre-peel hoists per task,
    #   before the first FD launch (DESIGN.md §2.2); theta is
    #   identical for every value >= 1 (regression-tested).
    # hardened-runtime knobs (DESIGN.md §7) — service-layer only, never
    # forwarded to the engine's ReceiptConfig:
    #   memory_budget_bytes  Planner admission control: plans whose
    #                        padded-bytes estimate exceeds this degrade
    #                        to smaller FD groups (more partitions) or
    #                        raise PlanInfeasibleError.  None = no limit.
    #   fault_spec           arm the deterministic fault-injection
    #                        harness (repro.api.faults grammar).
    memory_budget_bytes: Optional[int] = None
    fault_spec: Optional[str] = None

    def __post_init__(self):
        # normalize sequence-typed fields (from_dict hands us lists)
        object.__setattr__(self, "kernel_blocks",
                           tuple(int(b) for b in self.kernel_blocks))
        if self.side not in ("U", "V"):
            raise ValueError(
                f"side must be 'U' or 'V' (got {self.side!r}): tip "
                "decomposition peels one vertex set; 'V' transposes")
        if self.workload not in ("tip", "wing"):
            raise ValueError(
                f"workload must be 'tip' or 'wing' (got "
                f"{self.workload!r}): 'tip' peels vertices, 'wing' peels "
                "edges on the same engine (DESIGN.md §10)")
        if self.workload == "wing" and self.representation == "tiled":
            raise ValueError(
                "workload='wing' runs on the dense edge-axis geometry; "
                "the tiled representation is a vertex-axis path "
                "(use representation='dense' or 'auto')")
        if self.dtype not in _DTYPES:
            raise ValueError(
                f"dtype must be one of {_DTYPES} (got {self.dtype!r}): "
                "the engine's exactness contract is the f32 integer "
                "regime (DESIGN.md §8)")
        if self.memory_budget_bytes is not None:
            if int(self.memory_budget_bytes) <= 0:
                raise ValueError(
                    f"memory_budget_bytes must be a positive byte count "
                    f"(got {self.memory_budget_bytes}); use None for no "
                    "admission-control budget")
            object.__setattr__(self, "memory_budget_bytes",
                               int(self.memory_budget_bytes))
        if self.fault_spec is not None:
            # parse eagerly so a typo'd site name fails at construction
            # (the did-you-mean error), not mid-fleet
            from .faults import FaultSpec

            FaultSpec.parse(self.fault_spec)
        # the engine floor: enum/range checks shared with ReceiptConfig
        # (constructing one runs its __post_init__)
        self.to_receipt_config()
        # stricter service-layer cross-knob rules
        if self.cd_dispatch == "graph" and not self.use_dgm:
            raise ValueError(
                "cd_dispatch='graph' with use_dgm=False pays the stale "
                "whole-graph HUC recount bound for the entire run — the "
                "configuration silently diverges from the benchmarked "
                "wedge economics (BENCH_receipt.json "
                "derived.cd_graph_wedge_ratio).  Enable use_dgm, or use "
                "cd_dispatch='subset'; for A/B experiments construct a "
                "raw ReceiptConfig instead.")

    # ------------------------------------------------------------------ #
    # conversions
    # ------------------------------------------------------------------ #
    # service-layer-only fields the engine's ReceiptConfig never sees
    _API_ONLY = ("side", "workload", "dtype", "memory_budget_bytes",
                 "fault_spec")

    def to_receipt_config(self) -> ReceiptConfig:
        """The engine-layer view of this config (drops the service-layer
        fields, maps the dtype string to the jnp dtype)."""
        kw = {f.name: getattr(self, f.name)
              for f in dataclasses.fields(self)
              if f.name not in self._API_ONLY}
        return ReceiptConfig(dtype=jnp.dtype(self.dtype).type, **kw)

    @staticmethod
    def from_receipt(cfg: ReceiptConfig, side: str = "U") -> "EngineConfig":
        """Lift a legacy ``ReceiptConfig`` into the service layer.

        Raises where the service layer is stricter (see class docstring);
        the compat wrappers therefore bypass this and hand the raw
        ``ReceiptConfig`` to the Planner/Executor directly.
        """
        known = {f.name for f in dataclasses.fields(EngineConfig)}
        kw = {f.name: getattr(cfg, f.name)
              for f in dataclasses.fields(cfg)
              if f.name != "dtype" and f.name in known}
        return EngineConfig(side=side, dtype=jnp.dtype(cfg.dtype).name, **kw)

    # ------------------------------------------------------------------ #
    # rendering
    # ------------------------------------------------------------------ #
    def describe(self) -> str:
        """Human-readable rendering of the RESOLVED knob set.

        Unlike ``to_dict`` (which round-trips exactly what was given),
        this renders what the engine will actually run: the backend
        after auto-resolution, non-default knobs flagged, and the
        service-layer fields grouped separately — the service's config
        endpoint and ``--describe`` CLI both print this.
        """
        from ..kernels import ops as kops

        resolved = kops.resolve_backend(self.backend)
        lines = [f"EngineConfig ({self.workload} workload, side={self.side})"]
        defaults = {f.name: f.default for f in dataclasses.fields(self)}
        backend_note = (f"{self.backend!r} -> {resolved}"
                        if self.backend != resolved else repr(resolved))
        lines.append(f"  backend:          {backend_note}")
        shown = {"side", "workload", "backend"}
        listed = ("num_partitions", "kernel_blocks", "representation",
                  "cd_dispatch", "fd_update_mode", "degree_sort", "use_huc",
                  "use_dgm", "dtype")
        for name in listed:
            val = getattr(self, name)
            flag = "" if val == defaults.get(name) else "   [non-default]"
            lines.append(f"  {name + ':':<17} {val!r}{flag}")
        shown.update(listed)
        extras = [f.name for f in dataclasses.fields(self)
                  if f.name not in shown
                  and getattr(self, f.name) != defaults.get(f.name)]
        for name in extras:
            lines.append(f"  {name + ':':<17} {getattr(self, name)!r}"
                         "   [non-default]")
        if self.memory_budget_bytes is None and "memory_budget_bytes" \
                not in extras:
            lines.append("  memory budget:    unlimited")
        return "\n".join(lines)

    # ------------------------------------------------------------------ #
    # strict serialization round trip
    # ------------------------------------------------------------------ #
    def to_dict(self) -> Dict[str, Any]:
        """JSON-able dict; ``from_dict`` round-trips it exactly."""
        d = dataclasses.asdict(self)
        d["kernel_blocks"] = list(self.kernel_blocks)
        return d

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "EngineConfig":
        """Strict deserialization: unknown keys are REJECTED (with a
        did-you-mean hint), never dropped — a typo'd service config must
        fail loudly, not silently run defaults."""
        if not isinstance(d, dict):
            raise ValueError(
                f"EngineConfig.from_dict expects a dict, got "
                f"{type(d).__name__}")
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = sorted(set(d) - known)
        if unknown:
            hints = []
            for k in unknown:
                close = difflib.get_close_matches(k, known, n=1)
                hints.append(f"{k!r}" + (f" (did you mean {close[0]!r}?)"
                                         if close else ""))
            raise ValueError(
                f"EngineConfig.from_dict: unknown key(s) "
                f"{', '.join(hints)}; known keys: {', '.join(sorted(known))}")
        return cls(**d)
