"""RECEIPT engine benchmark: CD dispatch modes and the ParB baseline.

Runs RECEIPT and the ParB baseline on the synthetic power-law interaction
graphs (src/repro/data/synthetic.py — the KONECT-shaped workload of the
paper's Table 3) on the fused ``lax.while_loop`` engine, and writes
``BENCH_receipt.json`` with, per graph and engine:

  * wall clock (cold = includes jit, warm = steady-state best-of-3),
  * blocking host round trips (RunStats.host_round_trips) — the
    dispatch-layer analogue of the paper's synchronization counter rho,
  * rho_cd / rho_fd / wedge counters / HUC / DGM / elision counters,
  * FD runtime shape: shape-group count, stack padding waste,
  * derived ratios (graph- vs subset-dispatch CD round trips and wedges).

Engines: ``receipt_device`` (fused per-subset CD loop + FD level-peel,
the default stack), ``receipt_graph`` (whole-graph single-dispatch CD —
cd_dispatch="graph"), ``parb_device`` (the ParB min-peel schedule on the
same engine, the sync-count comparator).  A separate CD-phase-only
measurement records the tentpole metrics: O(1) blocking host round trips
per GRAPH for the single-dispatch driver vs >= 1 per subset
(``cd_phase_round_trips`` / ``derived.cd_rt_graph_total``), and — with
the ISSUE 4 on-device DGM — the graph dispatch's traversed-wedge count
within 10% of the per-subset host-DGM driver's
(``derived.cd_graph_wedge_ratio``).

The ``wing`` section (PR 8, DESIGN.md §10) benches the EDGE-axis
decomposition on the same engine: per seeded graph, the host
``wing_bup_oracle`` wall vs both engine dispatch modes, blocking host
round trips (graph dispatch: O(1) per graph, no overflow surcharge),
the HUC recount fraction and exact psi checksums (gated bit-for-bit by
``scripts/bench_gate.py``).

The ``service`` section (PR 9, DESIGN.md §11) benches the serving
layer: incremental refresh vs warm full recompute on a <=5%-dirty
mutation ladder (the refresh must take the delta re-peel, stay
bit-exact and win on wall), plus warm-query p50/p99 latency with a
zero-dispatch cache-hit requirement.

The ``service_async`` section (PR 10, DESIGN.md §12) benches the
background scheduler: stale-read p50 with the flush worker on vs the
same-process inline drain wall (reads must not pay the refresh wall),
bit-exactness of the asynchronously refreshed result, and the
CacheGovernor eviction smoke (evict under a tiny budget, recompute
exactly).

Usage:  PYTHONPATH=src python benchmarks/bench_receipt.py [--quick] [--out F]
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, "src")


def _load_gate_constants():
    """Shared gate constants from scripts/bench_gate.py (loaded by file
    path — scripts/ is not a package, and prepending it to sys.path
    could shadow repro modules)."""
    import importlib.util

    path = Path(__file__).resolve().parent.parent / "scripts" / "bench_gate.py"
    spec = importlib.util.spec_from_file_location("bench_gate", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return (mod.OVF_RT_SURCHARGE, mod.WEDGE_RATIO_TOL,
            mod.MAP_DISPATCH_MIN_REDUCTION, mod.MAP_HIT_RATE_MIN,
            mod.TILED_WALL_MAX_RATIO, mod.WING_RT_BOUND,
            mod.SERVICE_REFRESH_WALL_MAX_RATIO,
            mod.SERVICE_WARM_QUERY_MAX_DISPATCHES,
            mod.SERVICE_ASYNC_STALE_MAX_RATIO)


(OVF_RT_SURCHARGE, WEDGE_RATIO_TOL,
 MAP_DISPATCH_MIN_REDUCTION, MAP_HIT_RATE_MIN,
 TILED_WALL_MAX_RATIO, WING_RT_BOUND,
 SERVICE_REFRESH_WALL_MAX_RATIO,
 SERVICE_WARM_QUERY_MAX_DISPATCHES,
 SERVICE_ASYNC_STALE_MAX_RATIO) = _load_gate_constants()

from datasets import DATASETS
from repro.core.graph import powerlaw_bipartite
from repro.core.peeling import bup_oracle
from repro.core.receipt import (
    ReceiptConfig,
    parb_tip_decompose,
    tip_decompose,
)
from repro.data.synthetic import interaction_graph

GRAPHS = [
    # (name, builder) — interaction graphs (KONECT-shaped power law) plus
    # the paper-regime dataset matrix (benchmarks/datasets.py, Table 2):
    # every entry gets the full engine suite AND the name-matched
    # deterministic-counter gates in scripts/bench_gate.py
    ("pl_small", lambda: interaction_graph(512, 256, 4_000, seed=7)),
    ("pl_medium", lambda: interaction_graph(1_024, 512, 8_000, seed=7)),
    ("pl_large", lambda: interaction_graph(2_048, 1_024, 16_000, seed=7)),
    ("itu_like", DATASETS["itu_like"]),
    ("tru_like", DATASETS["tru_like"]),
    ("dev_like", DATASETS["dev_like"]),
    ("orv_like", DATASETS["orv_like"]),
]

# dense-vs-tiled representation matrix: the regime graphs (tile
# occupancy near 1 — dense territory) plus genuinely sparse graphs
# above the Planner's min-size floor (occupancy << 1 — tiled territory).
# The measured crossover between the two cohorts is what the Planner's
# routing constants (repro/api/plan.py TILED_OCCUPANCY_CROSSOVER /
# TILED_MIN_DENSE_CELLS) must bracket; bench_gate.py enforces it.
REPRESENTATION_GRAPHS = [
    ("itu_like", DATASETS["itu_like"]),
    ("tru_like", DATASETS["tru_like"]),
    ("dev_like", DATASETS["dev_like"]),
    ("orv_like", DATASETS["orv_like"]),
    ("sp_quick", lambda: powerlaw_bipartite(1_024, 1_024, 6_000,
                                            alpha_u=2.0, alpha_v=2.0,
                                            seed=11)),
    # the sparse ladder that brackets the wall crossover: sp_mid is the
    # densest cell count where dense still wins (barely), sp_large is
    # where the tiled engine's O(n_slots) sweeps beat the dense matmul
    ("sp_mid", lambda: powerlaw_bipartite(4_096, 4_096, 24_000,
                                          alpha_u=2.0, alpha_v=2.0,
                                          seed=14)),
    ("sp_large", lambda: powerlaw_bipartite(8_192, 8_192, 32_000,
                                            alpha_u=2.0, alpha_v=2.0,
                                            seed=15)),
]
REPRESENTATION_QUICK = ("itu_like", "dev_like", "sp_quick")


def _stats_dict(stats) -> dict:
    return {
        "rho_cd": stats.rho_cd,
        "rho_fd": stats.rho_fd,
        "host_round_trips": stats.host_round_trips,
        "device_loop_calls": stats.device_loop_calls,
        "overflow_fallbacks": stats.overflow_fallbacks,
        "wedges_pvbcnt": stats.wedges_pvbcnt,
        "wedges_cd": stats.wedges_cd,
        "wedges_fd": stats.wedges_fd,
        "huc_recounts": stats.huc_recounts,
        "dgm_compactions": stats.dgm_compactions,
        "dgm_device_compactions": stats.dgm_device_compactions,
        "elided_sweeps": stats.elided_sweeps,
        "num_subsets": stats.num_subsets,
        "fd_groups": stats.fd_groups,
        "fd_padding_waste": stats.fd_padding_waste,
        "time_count_s": stats.time_count,
        "time_cd_s": stats.time_cd,
        "time_fd_s": stats.time_fd,
    }


def _run_engine(fn, *args, **kw):
    t0 = time.perf_counter()
    fn(*args, **kw)                      # cold: includes compilation
    cold = time.perf_counter() - t0
    warm = float("inf")
    fd_warm = float("inf")
    for _ in range(3):                   # warm: jit caches hot, best-of-3
        t0 = time.perf_counter()
        out, stats = fn(*args, **kw)
        warm = min(warm, time.perf_counter() - t0)
        fd_warm = min(fd_warm, stats.time_fd)
    return out, stats, cold, warm, fd_warm


def bench_graph(name: str, builder, *, partitions: int, check: bool) -> dict:
    g = builder()
    rec = {"name": name, "n_u": g.n_u, "n_v": g.n_v, "m": g.m,
           "num_partitions": partitions, "engines": {}}

    theta_ref = None
    if check:
        theta_ref, _ = bup_oracle(g)

    for label, runner, kw in (
        ("receipt_device", tip_decompose, dict()),
        ("receipt_graph", tip_decompose, dict(cd_dispatch="graph")),
        ("parb_device", parb_tip_decompose, dict()),
    ):
        cfg = ReceiptConfig(num_partitions=partitions, backend="xla", **kw)
        theta, stats, cold, warm, fd_warm = _run_engine(runner, g, cfg)
        if theta_ref is not None:
            assert (np.asarray(theta) == theta_ref).all(), (
                f"{name}/{label}: theta mismatch vs BUP oracle")
        rec["engines"][label] = {
            "wall_cold_s": cold, "wall_warm_s": warm,
            "time_fd_warm_s": fd_warm, **_stats_dict(stats),
        }
        print(f"  {label:15s} cold={cold:7.2f}s warm={warm:6.2f}s "
              f"fd={fd_warm*1e3:6.1f}ms RT={stats.host_round_trips:6d} "
              f"rho={stats.rho_cd:5d} rho_fd={stats.rho_fd:5d} "
              f"ovf={stats.overflow_fallbacks}", flush=True)

    # CD-phase-only round trips (the single-dispatch tentpole metric;
    # measured via receipt_cd so FD's per-group syncs don't blur it)
    from repro.core.receipt import RunStats, receipt_cd

    cd_rt = {}
    for disp in ("subset", "graph"):
        cfg = ReceiptConfig(num_partitions=partitions, backend="xla",
                            cd_dispatch=disp)
        s = RunStats()
        receipt_cd(g, cfg, s)
        cd_rt[disp] = {
            "host_round_trips": s.host_round_trips,
            "overflow_fallbacks": s.overflow_fallbacks,
            "num_subsets": s.num_subsets,
            "device_loop_calls": s.device_loop_calls,
            "wedges_cd": s.wedges_cd,
            "rho_cd": s.rho_cd,
            "huc_recounts": s.huc_recounts,
            "dgm_compactions": s.dgm_compactions,
            "dgm_device_compactions": s.dgm_device_compactions,
        }
    rec["cd_phase_round_trips"] = cd_rt
    print(f"  CD-only RTs: subset={cd_rt['subset']['host_round_trips']} "
          f"graph={cd_rt['graph']['host_round_trips']} "
          f"(ovf={cd_rt['graph']['overflow_fallbacks']}, "
          f"{cd_rt['graph']['num_subsets']} subsets, "
          f"{cd_rt['graph']['dgm_device_compactions']} device DGM)",
          flush=True)

    ed = rec["engines"]["receipt_device"]
    eg = rec["engines"]["receipt_graph"]
    n_sub = max(ed["num_subsets"], 1)
    rec["derived"] = {
        # whole-graph single-dispatch CD: O(1) RTs per graph
        "cd_rt_graph_total": cd_rt["graph"]["host_round_trips"],
        "cd_rt_subset_total": cd_rt["subset"]["host_round_trips"],
        "cd_graph_rt_reduction":
            cd_rt["subset"]["host_round_trips"]
            / max(cd_rt["graph"]["host_round_trips"], 1),
        "cd_graph_wall_warm_s": eg["wall_warm_s"],
        # on-device DGM: the graph dispatch's traversed wedges vs the
        # per-subset host-DGM driver's (the ISSUE 4 tentpole metric —
        # close to 1.0 now that c_rcnt is re-estimated per boundary)
        "cd_graph_wedges": cd_rt["graph"]["wedges_cd"],
        "cd_subset_wedges": cd_rt["subset"]["wedges_cd"],
        "cd_graph_wedge_ratio":
            cd_rt["graph"]["wedges_cd"]
            / max(cd_rt["subset"]["wedges_cd"], 1),
        "cd_graph_dgm_device": cd_rt["graph"]["dgm_device_compactions"],
        "cd_rt_per_subset_device": ed["host_round_trips"] / n_sub,
        "fd_group_count": ed["fd_groups"],
        "fd_padding_waste": ed["fd_padding_waste"],
        "fd_rho_level": ed["rho_fd"],
    }
    d = rec["derived"]
    print(f"  -> {d['cd_rt_per_subset_device']:.1f} RTs per subset; "
          f"single-dispatch CD: {d['cd_rt_subset_total']} -> "
          f"{d['cd_rt_graph_total']} per graph, "
          f"wedge ratio {d['cd_graph_wedge_ratio']:.3f} vs subset DGM",
          flush=True)
    print(f"  -> FD: {d['fd_group_count']} groups, "
          f"{d['fd_padding_waste']*100:.0f}% padding waste, "
          f"rho_fd {d['fd_rho_level']}", flush=True)
    return rec


def bench_representations(*, quick: bool, check: bool) -> dict:
    """Dense vs tiled representation matrix (ISSUE 7 tentpole).

    For each graph: the full dense CD+FD pipeline and the tiled
    whole-graph level-peel engine, both on the xla backend (CPU CI), with
    traversed-wedge counters and warm walls; plus the Planner's routing
    verdict for representation="auto" and its cost-model inputs.  The
    measured dense/tiled crossover (highest tiled-winning occupancy vs
    lowest dense-winning) is recorded so bench_gate.py can assert the
    Planner's routing constants bracket what was actually measured —
    the constants are provenanced here, never guessed.
    """
    from repro.api import EngineConfig, Planner
    from repro.api.plan import (
        TILED_MIN_DENSE_CELLS,
        TILED_OCCUPANCY_CROSSOVER,
    )

    names = REPRESENTATION_QUICK if quick else None
    records = []
    for name, builder in REPRESENTATION_GRAPHS:
        if names is not None and name not in names:
            continue
        g = builder()
        plan = Planner(EngineConfig(backend="xla")).plan(g)
        theta_ref = None
        if check and g.n_u * g.n_v <= 1 << 22:
            # the host oracle is O(n_u^2); on the big sparse-ladder
            # graphs the dense<->tiled bit-identity check below is the
            # (still exact) stand-in — the dense pipeline is itself
            # oracle-checked on every regime graph
            theta_ref, _ = bup_oracle(g)
        entry = {"name": name, "n_u": g.n_u, "n_v": g.n_v, "m": g.m,
                 "tile_occupancy": plan.cost_model["tile_occupancy"],
                 "dense_cells": plan.cost_model["dense_cells"],
                 # representation footprints (roofline_report --tiled)
                 "dense_bytes": plan.cost_model["dense_fixed_bytes"],
                 "tiled_bytes": plan.cost_model["tiled_bytes"],
                 "n_tiles": plan.cost_model["n_tiles"],
                 "routed": plan.representation}
        thetas = {}
        for label, rep in (("dense", "dense"), ("tiled", "tiled")):
            cfg = ReceiptConfig(backend="xla", representation=rep)
            theta, stats, cold, warm, _ = _run_engine(tip_decompose, g, cfg)
            thetas[label] = np.asarray(theta)
            if theta_ref is not None:
                assert (np.asarray(theta) == theta_ref).all(), (
                    f"{name}/{label}: theta mismatch vs BUP oracle")
            entry[label] = {
                "wall_cold_s": cold, "wall_warm_s": warm,
                "wedges_traversed": stats.wedges_cd + stats.wedges_fd,
                "rho": stats.rho_cd + stats.rho_fd,
            }
        if check:
            assert (thetas["dense"] == thetas["tiled"]).all(), (
                f"{name}: dense and tiled theta diverged")
        entry["wedge_ratio"] = (
            entry["tiled"]["wedges_traversed"]
            / max(entry["dense"]["wedges_traversed"], 1))
        entry["wall_ratio_warm"] = (
            entry["tiled"]["wall_warm_s"]
            / max(entry["dense"]["wall_warm_s"], 1e-9))
        records.append(entry)
        print(f"  {name:10s} occ={entry['tile_occupancy']:.3f} "
              f"routed={entry['routed']:5s} "
              f"wedges tiled/dense={entry['wedge_ratio']:.3f} "
              f"wall tiled/dense={entry['wall_ratio_warm']:.2f}", flush=True)

    tiled_wins = [r["tile_occupancy"] for r in records
                  if r["wall_ratio_warm"] <= 1.0]
    dense_wins = [r["tile_occupancy"] for r in records
                  if r["wall_ratio_warm"] > 1.0]
    rec = {
        "graphs": records,
        "occupancy_crossover": TILED_OCCUPANCY_CROSSOVER,
        "min_dense_cells": TILED_MIN_DENSE_CELLS,
        "measured": {
            # the wall-clock crossover bracket this run observed (None
            # when a side is empty, e.g. the quick subset)
            "max_tiled_win_occupancy": max(tiled_wins) if tiled_wins
            else None,
            "min_dense_win_occupancy": min(dense_wins) if dense_wins
            else None,
        },
    }
    print(f"[bench_receipt] representations: tiled wins up to occupancy "
          f"{rec['measured']['max_tiled_win_occupancy']}, dense wins from "
          f"{rec['measured']['min_dense_win_occupancy']} "
          f"(routing constant {TILED_OCCUPANCY_CROSSOVER})", flush=True)
    return rec


WING_GRAPHS = [
    # seeded graphs sized for the O(m * butterflies) host oracle, so the
    # engine-vs-oracle wall comparison is measured, not extrapolated
    ("wing_pl_small", lambda: powerlaw_bipartite(160, 96, 1_000,
                                                 alpha_u=2.0, alpha_v=2.0,
                                                 seed=21)),
    ("wing_itu_mini", lambda: interaction_graph(192, 128, 1_400, seed=23)),
]
WING_QUICK = ("wing_pl_small",)


def bench_wing(*, quick: bool, check: bool, partitions: int = 8) -> dict:
    """Edge-axis (wing / bitruss) decomposition on the shared peel engine
    (PR 8, DESIGN.md §10) vs the sequential host oracle.

    Per seeded graph: the host oracle wall (``wing_bup_oracle``, one peel
    round per support level) and both engine dispatch modes, with the
    counters the gate pins — blocking host round trips (the graph
    dispatch must stay O(1): the full-mask edge peel has no overflow
    path), the recount fraction (which HUC arm the edge axis actually
    takes — the paper's argument that recount matters MORE for edge
    peeling, made measurable) and exact psi checksums (deterministic
    graphs, so ``bench_gate.py`` gates them bit-for-bit)."""
    from repro.core.engine import wing_decompose_engine
    from repro.core.wing import wing_bup_oracle

    records = []
    for name, builder in WING_GRAPHS:
        if quick and name not in WING_QUICK:
            continue
        g = builder()
        t0 = time.perf_counter()
        psi_ref, oracle_rounds = wing_bup_oracle(g)
        oracle_wall = time.perf_counter() - t0
        entry = {"name": name, "n_u": g.n_u, "n_v": g.n_v, "m": g.m,
                 "oracle_wall_s": oracle_wall,
                 "oracle_rounds": oracle_rounds,
                 "max_psi": int(psi_ref.max(initial=0)),
                 "psi_checksum": int(psi_ref.sum()),
                 "engines": {}}
        for disp in ("subset", "graph"):
            cfg = ReceiptConfig(num_partitions=partitions, backend="xla",
                                cd_dispatch=disp)
            psi, stats, cold, warm, _ = _run_engine(
                wing_decompose_engine, g, cfg)
            if check:
                assert (np.asarray(psi) == psi_ref).all(), (
                    f"{name}/{disp}: psi mismatch vs wing BUP oracle")
            sweeps = stats.rho_cd + stats.rho_fd
            entry["engines"][disp] = {
                "wall_cold_s": cold, "wall_warm_s": warm,
                "host_round_trips": stats.host_round_trips,
                "rho": sweeps,
                "huc_recounts": stats.huc_recounts,
                "recount_fraction": stats.huc_recounts / max(sweeps, 1),
                "oracle_speedup_warm": oracle_wall / max(warm, 1e-9),
            }
            e = entry["engines"][disp]
            print(f"  wing/{disp:6s} cold={cold:6.2f}s warm={warm:5.2f}s "
                  f"RT={e['host_round_trips']:3d} rho={e['rho']:4d} "
                  f"recount={e['recount_fraction']:.2f} "
                  f"oracle x{e['oracle_speedup_warm']:.1f} "
                  f"(oracle {oracle_wall:.2f}s, {oracle_rounds} rounds)",
                  flush=True)
        records.append(entry)
    return {"graphs": records, "rt_bound": WING_RT_BOUND}


def bench_executor_map(*, n_graphs: int = 12, check: bool = True) -> dict:
    """Multi-graph batched decomposition (PR 5): ``Executor.map`` over a
    fleet of small cohort graphs vs a sequential per-graph
    ``tip_decompose`` loop.  Reported: wall (cold = first map call incl.
    tracing, warm = second fleet of the same shapes — pure cache hits),
    device-dispatch counts (deterministic; gated by bench_gate.py) and
    the warm cache hit rate."""
    from repro.api import Executor
    from repro.core.receipt import ReceiptConfig, tip_decompose

    cfg = ReceiptConfig(num_partitions=4, backend="xla")
    mk = lambda seed0: [interaction_graph(160, 96, 1100, seed=seed0 + s)
                        for s in range(n_graphs)]
    graphs = mk(100)

    # sequential per-graph pipeline (the pre-PR-5 serving shape)
    t0 = time.perf_counter()
    seq = [tip_decompose(g, cfg) for g in graphs]
    seq_cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    seq = [tip_decompose(g, cfg) for g in graphs]
    seq_warm = time.perf_counter() - t0
    seq_dispatches = sum(s.device_loop_calls + s.host_round_trips
                         for _, s in seq)

    ex = Executor(cfg)
    t0 = time.perf_counter()
    tds = ex.map(graphs)
    map_cold = time.perf_counter() - t0
    rep_cold = dict(ex.last_map_report)
    if check:
        for (t_seq, _), td in zip(seq, tds):
            assert (np.asarray(t_seq) == td.theta).all(), (
                "Executor.map theta mismatch vs per-graph tip_decompose")
    # warm: a SECOND fleet of the same bucketed shapes — executables and
    # measured sizing come entirely out of the cache
    fleet2 = mk(500)
    t0 = time.perf_counter()
    ex.map(fleet2)
    map_warm = time.perf_counter() - t0
    rep_warm = dict(ex.last_map_report)
    hits = rep_warm["cache_hits"]
    hit_rate = hits / max(hits + rep_warm["cache_misses"], 1)

    # guardrail overhead (PR 6): the hardened warm path (input
    # validation, fault-point consults, fallback wrapping, straggler
    # timing) vs the bare guardrails=False path, measured in the SAME
    # process on the SAME warm fleet (min of repeats) so the gate's
    # ratio is not at the mercy of cross-run CI noise
    ex_bare = Executor(cfg, guardrails=False)
    ex_bare.map(fleet2)                      # warm the bare executor
    guarded_w, bare_w = [], []
    for _ in range(3):
        t0 = time.perf_counter()
        ex.map(fleet2)
        guarded_w.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        ex_bare.map(fleet2)
        bare_w.append(time.perf_counter() - t0)
    guarded_wall, bare_wall = min(guarded_w), min(bare_w)
    guardrail_overhead = guarded_wall / max(bare_wall, 1e-9) - 1.0
    map_dispatches = (rep_cold["device_loop_calls"]
                      + rep_cold["counting_dispatches"]
                      + rep_cold["host_round_trips"])
    rec = {
        "n_graphs": n_graphs,
        "groups": rep_cold["groups"],
        "chunks": rep_cold["chunks"],
        "seq_wall_cold_s": seq_cold,
        "seq_wall_warm_s": seq_warm,
        "map_wall_cold_s": map_cold,
        "map_wall_warm_s": map_warm,
        "map_wall_speedup_warm": seq_warm / max(map_warm, 1e-9),
        "seq_dispatches": seq_dispatches,
        "map_dispatches": map_dispatches,
        "dispatch_reduction": seq_dispatches / max(map_dispatches, 1),
        "warm_cache_hit_rate": hit_rate,
        "guarded_wall_warm_s": guarded_wall,
        "bare_wall_warm_s": bare_wall,
        "guardrail_overhead": guardrail_overhead,
    }
    print(f"[bench_receipt] executor_map: {n_graphs} graphs, "
          f"{rec['chunks']} chunk(s): dispatches {seq_dispatches} -> "
          f"{map_dispatches} ({rec['dispatch_reduction']:.1f}x fewer), "
          f"wall warm {seq_warm:.2f}s -> {map_warm:.2f}s "
          f"({rec['map_wall_speedup_warm']:.1f}x), warm hit rate "
          f"{hit_rate:.0%}, guardrail overhead "
          f"{guardrail_overhead:+.1%}", flush=True)
    return rec


def _service_mutations(g, count, rng):
    """``count`` inserts absent from ``g`` + ``count`` present deletes,
    both biased to LOW-degree endpoints (the regime where the adaptive
    stop ladder stays low and partial re-peels actually happen — the
    serving layer's target traffic: cold users/items churn, the dense
    core is stable)."""
    du = np.bincount(g.edges_u, minlength=g.n_u)
    dv = np.bincount(g.edges_v, minlength=g.n_v)
    u_pool = np.argsort(du)[: max(8, g.n_u // 4)]
    v_pool = np.argsort(dv)[: max(8, g.n_v // 4)]
    have = set((g.edges_u.astype(np.int64) * g.n_v
                + g.edges_v).tolist())
    ins = []
    while len(ins) < count:
        u = int(rng.choice(u_pool))
        v = int(rng.choice(v_pool))
        k = u * g.n_v + v
        if k not in have:
            have.add(k)
            ins.append((u, v))
    score = du[g.edges_u] + dv[g.edges_v]
    drop = np.argsort(score)[:count]
    return np.array(ins, np.int64), drop


def bench_service(*, quick: bool, check: bool, partitions: int = 8) -> dict:
    """Serving layer (PR 9, DESIGN.md §11): incremental refresh vs full
    recompute on a dirty-fraction ladder, plus warm-query latency.

    Per rung: re-ingest the seed graph, run the full decompose (primes
    the CD-bound stop ladder), one warm-up mutation round (compiles the
    prefix-peel loops at these shapes), then a MEASURED round — wall of
    ``flush()`` draining the coalesced refresh vs a warm from-scratch
    ``Executor.decompose`` of the same mutated graph in the same
    process.  The refresh must take the delta path, stay bit-exact and
    beat the full wall (gated here and by scripts/bench_gate.py).  The
    warm-query loop then times repeat reads of the fresh dataset: p50 /
    p99 latency and the number of flush-dispatching misses (must be
    <= SERVICE_WARM_QUERY_MAX_DISPATCHES — fresh reads are pure cache
    hits, zero device work)."""
    from repro.api import EngineConfig
    from repro.service import DecompositionService, ServiceConfig

    n_u, n_v, m = (128, 96, 1100) if quick else (256, 160, 2600)
    fracs = (0.02,) if quick else (0.01, 0.02, 0.05)
    g0 = interaction_graph(n_u, n_v, m, seed=31)
    cfg = EngineConfig(num_partitions=partitions, backend="xla")
    # threshold above the ladder's top rung so every rung exercises the
    # delta path (the threshold fallback has its own test coverage)
    svc = DecompositionService(cfg, ServiceConfig(
        refresh_dirty_threshold=0.12))
    ex = svc._executor("tip")
    rng = np.random.default_rng(5)
    name = "bench"

    ladder = []
    for frac in fracs:
        k = max(1, int(round(frac * g0.m / 2)))
        svc.ingest(name, g0, workload="tip", replace=True)
        svc.flush(name)                 # full run: primes the CD bounds
        for measured in (False, True):  # warm-up round, then measured
            g = svc._datasets[name].graph
            ins, drop = _service_mutations(g, k, rng)
            svc.insert_edges(name, ins[:, 0], ins[:, 1])
            svc.delete_edges(name, g.edges_u[drop], g.edges_v[drop])
            t0 = time.perf_counter()
            svc.flush(name)
            refresh_wall = time.perf_counter() - t0
        ds = svc._datasets[name]
        stats = ds.result.stats
        full_wall = float("inf")
        for _ in range(2):              # warm from-scratch comparator
            t0 = time.perf_counter()
            ref = ex.decompose(ds.graph)
            full_wall = min(full_wall, time.perf_counter() - t0)
        exact = bool((np.asarray(ds.result.numbers)
                      == np.asarray(ref.numbers)).all())
        if check:
            assert exact, (f"service refresh diverged from from-scratch "
                           f"decompose at dirty={frac}")
        stop = stats.refresh_stop
        rung = {
            "dirty_frac": frac,
            "dirty_edges": stats.refresh_dirty_edges,
            "mode": stats.refresh_mode,
            "stop": None if stop == float("inf") else stop,
            "subsets_repeeled": stats.refresh_subsets_repeeled,
            "subsets_total": stats.refresh_subsets_total,
            "refresh_dispatches": (stats.device_loop_calls
                                   + stats.host_round_trips),
            "refresh_wall_s": refresh_wall,
            "full_wall_s": full_wall,
            "refresh_speedup": full_wall / max(refresh_wall, 1e-9),
            "exact": exact,
        }
        ladder.append(rung)
        print(f"  dirty={frac:4.0%} ({rung['dirty_edges']:3d} edges) "
              f"mode={rung['mode']:5s} subsets="
              f"{rung['subsets_repeeled']}/{rung['subsets_total']} "
              f"refresh={refresh_wall:.3f}s full={full_wall:.3f}s "
              f"({rung['refresh_speedup']:.1f}x) exact={exact}",
              flush=True)

    # warm-query loop on the (fresh) dataset: every read is a cache hit
    before = svc.report()["datasets"][name]
    n_queries = 200
    lat = []
    for _ in range(n_queries):
        t0 = time.perf_counter()
        svc.query(name)
        lat.append(time.perf_counter() - t0)
    after = svc.report()["datasets"][name]
    hits = after["query_hits"] - before["query_hits"]
    warm_query = {
        "queries": n_queries,
        "hits": hits,
        # a non-hit read drains the queue: at most one dispatch batch
        "dispatching_misses": n_queries - hits,
        "p50_ms": float(np.percentile(lat, 50) * 1e3),
        "p99_ms": float(np.percentile(lat, 99) * 1e3),
    }
    print(f"[bench_receipt] service: warm query p50="
          f"{warm_query['p50_ms']:.3f}ms p99={warm_query['p99_ms']:.3f}ms "
          f"hits={hits}/{n_queries}", flush=True)
    return {"workload": "tip", "n_u": n_u, "n_v": n_v, "m": g0.m,
            "num_partitions": partitions, "ladder": ladder,
            "warm_query": warm_query}


def bench_service_async(*, quick: bool, check: bool,
                        partitions: int = 8) -> dict:
    """Background scheduler (PR 10, DESIGN.md §12): stale-read latency
    with the flush worker on vs the same-process inline drain wall,
    async refresh exactness, and the CacheGovernor eviction smoke.

    The comparator is PR 9's inline mode measured first in the same
    process (ingest → full → warm-up round → measured mutation round
    whose ``flush()`` wall is the drain the worker absorbs).  The async
    service then runs the same traffic with the worker on: each
    measured read lands right after a mutation batch and must return
    non-blocking — a counted stale read or a cache hit, never an
    inline drain on the query thread — while the worker refreshes in
    the background; after ``wait_until_idle`` the read must observe the
    new version, bit-exact against a from-scratch decompose."""
    from repro.api import EngineConfig, Executor
    from repro.service import DecompositionService, ServiceConfig

    n_u, n_v, m = (128, 96, 1100) if quick else (256, 160, 2600)
    rounds = 4 if quick else 8
    g0 = interaction_graph(n_u, n_v, m, seed=37)
    cfg = EngineConfig(num_partitions=partitions, backend="xla")
    name = "bench"
    k = max(1, int(round(0.02 * m / 2)))

    # inline comparator (PR 9 semantics): the drain wall a stale read
    # used to pay, measured warm in this process
    inline = DecompositionService(cfg, ServiceConfig(
        refresh_dirty_threshold=0.12))
    inline.ingest(name, g0, workload="tip")
    inline.flush(name)
    rng = np.random.default_rng(6)
    inline_wall = float("inf")
    for _ in range(2):                  # warm-up round, then measured
        g = inline._datasets[name].graph
        ins, drop = _service_mutations(g, k, rng)
        inline.insert_edges(name, ins[:, 0], ins[:, 1])
        inline.delete_edges(name, g.edges_u[drop], g.edges_v[drop])
        t0 = time.perf_counter()
        inline.flush(name)
        inline_wall = time.perf_counter() - t0

    # async service: same traffic, worker on
    svc = DecompositionService(cfg, ServiceConfig(
        refresh_dirty_threshold=0.12, background=True,
        worker_poll_s=0.005))
    svc.ingest(name, g0, workload="tip")
    svc.query(name, wait=True, timeout=600)
    before = svc.report()["datasets"][name]
    rng = np.random.default_rng(6)      # same mutation stream
    lat = []
    for _ in range(rounds):
        g = svc._datasets[name].graph
        ins, drop = _service_mutations(g, k, rng)
        svc.insert_edges(name, ins[:, 0], ins[:, 1])
        svc.delete_edges(name, g.edges_u[drop], g.edges_v[drop])
        t0 = time.perf_counter()
        svc.query(name, with_info=True)     # must not pay the drain
        lat.append(time.perf_counter() - t0)
        assert svc.wait_until_idle(timeout=600), \
            "background worker failed to drain between rounds"
    after = svc.report()["datasets"][name]
    stale = after["stale_reads"] - before["stale_reads"]
    hits = after["query_hits"] - before["query_hits"]
    dec, info = svc.query(name, with_info=True)
    ref = Executor(cfg).decompose(svc._datasets[name].graph)
    async_exact = bool((np.asarray(dec.numbers)
                        == np.asarray(ref.numbers)).all())
    if check:
        assert async_exact, ("background-refreshed numbers diverged "
                             "from from-scratch decompose")
    worker = svc.report()["worker"]
    svc.close()
    stale_read = {
        "rounds": rounds,
        "stale_reads": stale,
        "hits": hits,
        # reads that were neither a hit nor a counted stale read paid
        # a drain/wait on the query thread — the wall the worker must
        # absorb (gated to zero by scripts/bench_gate.py)
        "blocking_reads": rounds - stale - hits,
        "p50_s": float(np.percentile(lat, 50)),
        "p99_s": float(np.percentile(lat, 99)),
    }

    # eviction smoke: tiny budget forces LRU eviction; the evicted
    # dataset must recompute bit-exactly on demand
    ev = DecompositionService(cfg, ServiceConfig(cache_budget_bytes=64))
    g1 = interaction_graph(64, 48, 480, seed=38)
    ev.ingest("a", g1)
    ev.ingest("b", interaction_graph(56, 44, 420, seed=39))
    ev.query("a")
    ev.query("b")                       # evicts a (budget < any result)
    evictions = ev.cache_report()["evicted_total"]
    dec_a = ev.query("a")               # recompute on demand
    ref_a = Executor(cfg).decompose(ev._datasets["a"].graph)
    ev_exact = bool((np.asarray(dec_a.numbers)
                     == np.asarray(ref_a.numbers)).all())
    if check:
        assert evictions >= 1, "eviction smoke evicted nothing"
        assert ev_exact, "post-eviction recompute diverged"

    print(f"[bench_receipt] service_async: stale read p50="
          f"{stale_read['p50_s'] * 1e3:.3f}ms vs inline drain "
          f"{inline_wall * 1e3:.1f}ms ({stale}/{rounds} stale, "
          f"{hits} hits, {stale_read['blocking_reads']} blocking), "
          f"worker cycles={worker['cycles']}, evictions={evictions} "
          f"exact={async_exact and ev_exact}", flush=True)
    return {
        "workload": "tip", "n_u": n_u, "n_v": n_v, "m": g0.m,
        "num_partitions": partitions,
        "inline_drain_wall_s": inline_wall,
        "stale_read": stale_read,
        "async_exact": async_exact,
        "fresh_after_idle": bool(info["fresh"]),
        "worker": {"cycles": worker["cycles"],
                   "crashes": worker["crashes"]},
        "eviction": {"evictions": evictions, "exact": ev_exact},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_receipt.json")
    ap.add_argument("--quick", action="store_true",
                    help="smallest graph only (CI smoke)")
    ap.add_argument("--partitions", type=int, default=16)
    ap.add_argument("--no-check", action="store_true",
                    help="skip the BUP oracle verification")
    args = ap.parse_args(argv)

    graphs = GRAPHS[:1] if args.quick else GRAPHS
    results = []
    for name, builder in graphs:
        print(f"[bench_receipt] {name}", flush=True)
        results.append(bench_graph(
            name, builder, partitions=args.partitions,
            check=not args.no_check,
        ))

    print("[bench_receipt] representations (dense vs tiled)", flush=True)
    representations = bench_representations(
        quick=args.quick, check=not args.no_check)

    print("[bench_receipt] wing (edge-axis decomposition, DESIGN.md §10)",
          flush=True)
    wing = bench_wing(quick=args.quick, check=not args.no_check)

    exec_map = bench_executor_map(
        n_graphs=8 if args.quick else 12, check=not args.no_check)

    print("[bench_receipt] service (incremental refresh, DESIGN.md §11)",
          flush=True)
    service = bench_service(quick=args.quick, check=not args.no_check)

    print("[bench_receipt] service_async (background scheduler, "
          "DESIGN.md §12)", flush=True)
    service_async = bench_service_async(
        quick=args.quick, check=not args.no_check)

    payload = {
        "benchmark": "receipt_peel_engine",
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "backend": "xla (CPU)",
        "graphs": results,
        "representations": representations,
        "wing": wing,
        "executor_map": exec_map,
        "service": service,
        "service_async": service_async,
    }
    Path(args.out).write_text(json.dumps(payload, indent=2))
    print(f"[bench_receipt] wrote {args.out}")

    largest_cd = results[-1]["cd_phase_round_trips"]["graph"]
    # single-dispatch CD: O(1) RTs per graph (2 + a bounded overflow
    # surcharge), independent of the subset count
    ok = (largest_cd["host_round_trips"]
          <= 2 + OVF_RT_SURCHARGE * largest_cd["overflow_fallbacks"]
          # multi-graph batched decomposition: deterministic dispatch
          # counts and a fully-cached warm fleet (the PR 5 acceptance)
          and exec_map["dispatch_reduction"] >= MAP_DISPATCH_MIN_REDUCTION
          and exec_map["warm_cache_hit_rate"] >= MAP_HIT_RATE_MIN)
    # on-device DGM: every benched graph must keep the O(1)-RT claim AND
    # land its traversed-wedge count within WEDGE_RATIO_TOL of the
    # per-subset host-DGM driver's (the ISSUE 4 acceptance gate)
    for r in results:
        cd = r["cd_phase_round_trips"]["graph"]
        rt_ok = (cd["host_round_trips"]
                 <= 2 + OVF_RT_SURCHARGE * cd["overflow_fallbacks"])
        wedge_ok = r["derived"]["cd_graph_wedge_ratio"] <= WEDGE_RATIO_TOL
        if not (rt_ok and wedge_ok):
            print(f"[bench_receipt] {r['name']}: graph-dispatch gate "
                  f"FAILED (rt_ok={rt_ok}, wedge_ratio="
                  f"{r['derived']['cd_graph_wedge_ratio']:.3f})")
        ok = ok and rt_ok and wedge_ok
    # edge axis (PR 8 acceptance): the graph-dispatch wing driver keeps
    # O(1) blocking round trips per graph — no overflow surcharge, the
    # full-mask edge peel has no overflow path (psi exactness is already
    # asserted against the wing oracle inside bench_wing)
    for r in wing["graphs"]:
        w_rt = r["engines"]["graph"]["host_round_trips"]
        if w_rt > WING_RT_BOUND:
            print(f"[bench_receipt] {r['name']}: wing graph-dispatch gate "
                  f"FAILED (host_round_trips={w_rt} > {WING_RT_BOUND})")
        ok = ok and w_rt <= WING_RT_BOUND
    # tiled representation (ISSUE 7 acceptance): on every graph the cost
    # model routes tiled, the tiled engine must traverse no more wedges
    # than the dense pipeline and keep warm wall within the gate ratio
    for r in representations["graphs"]:
        if r["routed"] != "tiled":
            continue
        t_ok = (r["wedge_ratio"] <= 1.0
                and r["wall_ratio_warm"] <= TILED_WALL_MAX_RATIO)
        if not t_ok:
            print(f"[bench_receipt] {r['name']}: tiled-representation "
                  f"gate FAILED (wedge_ratio={r['wedge_ratio']:.3f}, "
                  f"wall_ratio={r['wall_ratio_warm']:.2f})")
        ok = ok and t_ok
    # serving layer (PR 9 acceptance): every ladder rung stays on the
    # delta path, exact, and beats the same-process full-recompute wall;
    # the warm-query loop serves from the cached decomposition
    for r in service["ladder"]:
        s_ok = (r["mode"] == "delta" and r["exact"]
                and r["refresh_wall_s"]
                <= r["full_wall_s"] * SERVICE_REFRESH_WALL_MAX_RATIO)
        if not s_ok:
            print(f"[bench_receipt] service dirty={r['dirty_frac']}: "
                  f"gate FAILED (mode={r['mode']}, exact={r['exact']}, "
                  f"refresh={r['refresh_wall_s']:.3f}s vs "
                  f"full={r['full_wall_s']:.3f}s)")
        ok = ok and s_ok
    if (service["warm_query"]["dispatching_misses"]
            > SERVICE_WARM_QUERY_MAX_DISPATCHES):
        print(f"[bench_receipt] service: warm-query gate FAILED "
              f"({service['warm_query']['dispatching_misses']} "
              f"dispatching misses)")
        ok = False
    # background scheduler (PR 10 acceptance): every measured read
    # serves non-blocking, stale-read p50 stays far under the inline
    # drain wall, the async refresh is exact, eviction recomputes
    sa_sr = service_async["stale_read"]
    sa_ok = (sa_sr["blocking_reads"] == 0
             and sa_sr["p50_s"] <= service_async["inline_drain_wall_s"]
             * SERVICE_ASYNC_STALE_MAX_RATIO
             and service_async["async_exact"]
             and service_async["fresh_after_idle"]
             and service_async["eviction"]["evictions"] >= 1
             and service_async["eviction"]["exact"])
    if not sa_ok:
        print(f"[bench_receipt] service_async: gate FAILED "
              f"(blocking={sa_sr['blocking_reads']}, "
              f"p50={sa_sr['p50_s'] * 1e3:.3f}ms vs inline "
              f"{service_async['inline_drain_wall_s'] * 1e3:.1f}ms, "
              f"exact={service_async['async_exact']}, "
              f"eviction={service_async['eviction']})")
    ok = ok and sa_ok
    print(f"[bench_receipt] largest graph: "
          f"{largest_cd['host_round_trips']} CD host round trips "
          f"(graph dispatch) -> {'OK' if ok else 'BELOW TARGET'}")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
