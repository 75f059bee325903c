"""Static tip decomposition on a mesh: one graph, decomposed back to back
by an executor that shards FD over the run's chips.

Set-up builds the mix's graph from the seed and an ``Executor`` holding
a mesh of the configuration's shape over the run's devices
(``repro.launch.mesh.make_mesh``): CD runs on the mesh's first chip, and
each FD shape group's independent subsets are LPT-assigned to the chips
and peeled there with zero collectives.  It decomposes until a
decomposition builds no new program.  The window, ``decompose_s`` and
the reference comparison are those of ``kinds/static.py``.

``correct`` adds ``shard_mismatch`` to ``static``'s numbers: the
decompositions that broke the configuration's layout guarantee: a plan
for another shard count, FD on another number of shards, a shard that
peeled no level, or CD on a backend other than the stated one.
"""
from __future__ import annotations

import time
from typing import Dict, List

from benchmarks.chip.kinds import static
from benchmarks.chip.reference import graphs

end_to_end = static.end_to_end
release = static.release


class _Recording:
    """The executor, keeping each decomposition's shard report."""

    def __init__(self, executor):
        self.executor = executor
        self.shards: List[Dict] = []

    @property
    def cache_stats(self):
        return self.executor.cache_stats

    def decompose(self, graph):
        dec = self.executor.decompose(graph)
        s = dec.stats
        self.shards.append({
            "mesh_shards": dec.plan.mesh_shards,
            "fd_shards": s.fd_shards,
            "fd_shard_rho": list(s.fd_shard_rho),
            "fd_shard_wedges": list(s.fd_shard_wedges),
            # absent from programs older than the counter
            "fd_shard_bytes": getattr(s, "fd_shard_bytes", None),
        })
        return dec


def setup(run) -> static.Static:
    from repro.api import EngineConfig, Executor
    from repro.core.graph import BipartiteGraph
    from repro.launch.mesh import make_mesh

    mix, cfg = run.cell.traffic, run.cell.config
    edges = graphs.make_graph(mix["graph"], run.seed)
    g = BipartiteGraph.from_edges(*edges)
    engine = EngineConfig.from_dict(cfg["engine"])
    mesh = make_mesh(tuple(cfg["mesh"]["shape"]), tuple(cfg["mesh"]["axes"]),
                     devices=run.devices)
    ex = _Recording(Executor(engine, mesh=mesh))
    walls = []
    for i in range(static.MAX_WARMUP):
        built = run.programs_built()
        t0 = time.perf_counter()
        ex.decompose(g)
        walls.append(time.perf_counter() - t0)
        if i >= 1 and run.programs_built() == built:
            break
    run.log("warm-up", decompositions=len(walls), walls_s=walls,
            n_u=g.n_u, n_v=g.n_v, m=g.m, mesh=dict(mesh.shape),
            shards=ex.shards[-1])
    return static.Static(edges=edges, graph=g, executor=ex, side=engine.side,
                         route=mix["route"])


def window(state: static.Static, seconds: float) -> Dict:
    state.executor.shards.clear()
    win = static.window(state, seconds)
    win["shards"] = list(state.executor.shards)
    return win


def layer_context(state: static.Static, win: Dict) -> Dict:
    """``static``'s, with each decomposition's shard report."""
    ctx = static.layer_context(state, win)
    for d, sh in zip(ctx["decompositions"], win["shards"]):
        d.update(sh)
    return ctx


def _broken(shards: Dict, summary: Dict, want: int, backends) -> bool:
    return (shards["mesh_shards"] != want or shards["fd_shards"] != want
            or min(shards["fd_shard_rho"], default=0) <= 0
            or summary["backend"] not in backends)


def check(state: static.Static, win: Dict, run):
    """``static``'s numbers and ``shard_mismatch``, each to be at most
    its limit; and how many decompositions failed."""
    checks, failed = static.check(state, win, run)
    want = int(run.cell.config["guarantees"]["fd_shards"])
    broken = sum(_broken(sh, d, want, run.backends)
                 for sh, d in zip(win["shards"], win["decompositions"]))
    run.log("shards", fd_shard_rho=win["shards"][-1]["fd_shard_rho"],
            fd_shard_wedges=win["shards"][-1]["fd_shard_wedges"],
            fd_shard_bytes=win["shards"][-1]["fd_shard_bytes"])
    checks.append(("shard_mismatch", broken, 0))
    return checks, failed
