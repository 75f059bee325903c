"""Static tip decomposition: one graph, decomposed back to back.

Set-up builds the mix's graph from the seed, hands it to
``Executor.decompose`` (Planner -> Executor -> engine -> kernels) under
the configuration's ``EngineConfig``, and decomposes it until a
decomposition builds no new program.  The window then decomposes the
same graph back to back and ends at the first completion at or after
``--seconds``; ``decompose_s`` is the window's length over the
decompositions it completed.

``correct`` holds every tip number of every decomposition in the window
to the reference peel of the same graph, and each run to the route,
backend and fallback guarantees the mix and configuration state.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List

import numpy as np

from benchmarks.chip import trace
from benchmarks.chip.reference import graphs, oracle

MAX_WARMUP = 4


@dataclasses.dataclass
class Static:
    edges: tuple
    graph: object
    executor: object
    side: str
    route: str


def _summary(dec) -> Dict:
    s = dec.stats
    return {"theta": np.asarray(dec.theta, np.int64),
            "representation": dec.plan.representation,
            "backend": s.backend_used,
            "fallbacks": len(s.backend_fallbacks),
            "host_round_trips": s.host_round_trips,
            "rho_cd": s.rho_cd, "rho_fd": s.rho_fd}


def setup(run) -> Static:
    from repro.api import EngineConfig, Executor
    from repro.core.graph import BipartiteGraph

    mix, cfg = run.cell.traffic, run.cell.config
    edges = graphs.make_graph(mix["graph"], run.seed)
    g = BipartiteGraph.from_edges(*edges)
    engine = EngineConfig.from_dict(cfg["engine"])
    ex = Executor(engine)
    walls = []
    for i in range(MAX_WARMUP):
        built = run.programs_built()
        t0 = time.perf_counter()
        ex.decompose(g)
        walls.append(time.perf_counter() - t0)
        if i >= 1 and run.programs_built() == built:
            break
    run.log("warm-up", decompositions=len(walls), walls_s=walls,
            n_u=g.n_u, n_v=g.n_v, m=g.m)
    return Static(edges=edges, graph=g, executor=ex, side=engine.side,
                  route=mix["route"])


def window(state: Static, seconds: float) -> Dict:
    done, walls = [], []
    t0 = time.perf_counter()
    while True:
        t1 = time.perf_counter()
        with trace.span("decompose"):
            dec = state.executor.decompose(state.graph)
        walls.append(time.perf_counter() - t1)
        done.append(_summary(dec))
        elapsed = time.perf_counter() - t0
        if elapsed >= seconds:
            return {"seconds": elapsed, "decompositions": done,
                    "walls_s": walls,
                    "fallback_runs":
                        state.executor.cache_stats["fallback_runs"]}


def end_to_end(win: Dict, state: Static) -> Dict[str, float]:
    n = len(win["decompositions"])
    return {"decompose_s": win["seconds"] / n, "attempted": n}


def layer_context(state: Static, win: Dict) -> Dict:
    """What the per-layer readers read, besides the trace."""
    return {"decompositions": [{k: v for k, v in d.items() if k != "theta"}
                               for d in win["decompositions"]]}


def release(state: Static) -> None:
    state.executor = None


def check(state: Static, win: Dict, run):
    """(name, value, limit) of every number compared, each to be at most
    its limit; and how many decompositions failed."""
    t0 = time.perf_counter()
    ref = oracle.peel_side(state.edges, state.side)
    dec = win["decompositions"]
    wrong = [int(np.sum(d["theta"] != ref.theta)) for d in dec]
    run.log("reference", seconds=time.perf_counter() - t0,
            max_support=ref.max_support, wedges_count=ref.wedges_count,
            wedges_peel=ref.wedges_peel)
    run.work = {"wedges_count": ref.wedges_count,
                "wedges_peel": ref.wedges_peel,
                "n_u": len(ref.theta), "m": int(state.edges[2].size)}
    checks = [
        ("theta_mismatch", sum(wrong), 0),
        ("max_support", ref.max_support, oracle.F32_EXACT - 1),
        ("route_mismatch",
         sum(d["representation"] != state.route for d in dec), 0),
        ("non_pallas_runs",
         sum(d["backend"] not in run.backends for d in dec), 0),
        ("fallbacks",
         sum(d["fallbacks"] for d in dec) + win["fallback_runs"], 0),
    ]
    return checks, sum(w > 0 for w in wrong)
