#!/usr/bin/env python3
"""On-chip smoke run of the tip-decomposition engine and its service.

Drives the main path once on a TPU through the entry points a user calls
(``Planner`` -> ``Executor`` -> ``DecompositionService``) and holds every
result to the exact host oracles, bit for bit:

    python chip_smoke.py                # one chip, every phase below
    python chip_smoke.py --chips 4      # the 4-chip mesh path only

Phases (one process, one chip):

1. device   — refuse anything but a TPU (non-zero exit, no ok line);
2. graph    — ``powerlaw_bipartite(16384, 8192, 131072, seed)``, ~110k
              edges after dedup; its exact butterfly supports are
              checked on the host against the engine's f32 integer
              bound (2^24, DESIGN.md §8) before any device work;
3. tip      — dense representation, sides U and V, CD dispatch
              ``subset`` and ``graph``, cold and warm: theta ==
              ``bup_oracle``;
4. tiled    — the same graph through the tiled representation: theta
              == the dense theta;
5. wing     — a 192 x 128 graph (small enough for the sequential wing
              oracle): psi == ``wing_bup_oracle``.  The edge axis has no
              Pallas kernel: this phase checks its jnp products at
              HIGHEST precision on the chip;
6. fleet    — ``Executor.map`` over 32 seeded 512 x 256 graphs;
7. service  — ingest, three rounds of edge inserts/deletes, queries,
              and a draining shutdown: the served result ==
              ``bup_oracle`` of the mutated graph == a fresh
              decomposition of it.

Every phase must run on a Pallas backend with no fallback.  The last line
of stdout is ``{"ok": true, "device": {...}}``; any failed check raises
and exits non-zero before it.  Walls printed here are those of a smoke
run (cold includes compilation), not benchmark numbers.

Scale cut: the paper's datasets have millions of vertices and butterfly
supports far past 2^24; exact supports past that bound are ROADMAP Queue
2 item 1.  This graph is sized to fill the dense path (a 16384 x 8192
f32 biadjacency is 512 MiB on the device) while its largest support
stays under the bound.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent

GRAPH = dict(n_u=16384, n_v=8192, m_target=131072)
WING_GRAPH = dict(n_u=192, n_v=128, m_target=1600)
FLEET = dict(count=32, n_u=512, n_v=256, m_target=2048)
SERVICE_ROUNDS = 3
SERVICE_EDGE_FRACTION = 0.005       # inserted and deleted per round
F32_EXACT = 1 << 24                 # engine supports are exact below this
PALLAS_BACKENDS = ("pallas", "pallas_sparse")


class SmokeError(RuntimeError):
    """A phase produced a wrong or unverifiable result."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeError(msg)


def log(phase: str, **fields) -> None:
    body = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[smoke] {phase}: {body}", flush=True)


def peak_bytes(device) -> int:
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def check_backend(where: str, plan, stats, executor) -> None:
    """The run used the plan's Pallas backend and degraded nowhere."""
    check(plan.backend in PALLAS_BACKENDS,
          f"{where}: plan.backend={plan.backend!r} is not a Pallas backend")
    check(stats.backend_used == plan.backend,
          f"{where}: ran on {stats.backend_used!r}, planned "
          f"{plan.backend!r}")
    check(not stats.backend_fallbacks,
          f"{where}: backend fallbacks {stats.backend_fallbacks}")
    check(executor.cache_stats["fallback_runs"] == 0,
          f"{where}: executor recorded fallback runs "
          f"{executor.cache_stats}")


# --------------------------------------------------------------------- #
# phases
# --------------------------------------------------------------------- #
def phase_graph(seed: int):
    from repro.core.graph import powerlaw_bipartite
    from repro.core.peeling import bup_oracle, butterfly_supports

    t0 = time.perf_counter()
    g = powerlaw_bipartite(GRAPH["n_u"], GRAPH["n_v"], GRAPH["m_target"],
                           seed=seed)
    max_sup = {}
    for side, gs in (("U", g), ("V", g.transposed())):
        sup = butterfly_supports(gs)
        max_sup[side] = int(sup.max()) if sup.size else 0
        check(max_sup[side] < F32_EXACT,
              f"side {side}: largest butterfly support {max_sup[side]} is "
              f"not below 2^24; the engine's f32 supports would not be "
              f"exact — refusing the graph")
    t_check = time.perf_counter() - t0
    t1 = time.perf_counter()
    oracle = {"U": bup_oracle(g)[0], "V": bup_oracle(g.transposed())[0]}
    log("graph", n_u=g.n_u, n_v=g.n_v, m=g.m, seed=seed,
        max_support_U=max_sup["U"], max_support_V=max_sup["V"],
        bound=F32_EXACT, host_check_s=t_check,
        host_oracle_s=time.perf_counter() - t1)
    return g, oracle


def _timed_decompose(executor, g):
    t0 = time.perf_counter()
    dec = executor.decompose(g)
    return dec, time.perf_counter() - t0


def phase_tip(g, oracle, device) -> None:
    from repro.api import EngineConfig, Executor

    for side in ("U", "V"):
        for dispatch in ("subset", "graph"):
            where = f"tip side={side} dispatch={dispatch}"
            ex = Executor(EngineConfig(side=side, cd_dispatch=dispatch,
                                       representation="dense"))
            walls = []
            for _ in ("cold", "warm"):
                dec, wall = _timed_decompose(ex, g)
                walls.append(wall)
                check(dec.plan.representation == "dense",
                      f"{where}: planned {dec.plan.representation!r}")
                check_backend(where, dec.plan, dec.stats, ex)
                check(np.array_equal(dec.theta, oracle[side]),
                      f"{where}: theta differs from bup_oracle at "
                      f"{int(np.sum(dec.theta != oracle[side]))} vertices")
            s = dec.stats
            log("tip", side=side, dispatch=dispatch, backend=s.backend_used,
                cold_wall_s=walls[0], warm_wall_s=walls[1],
                rho_cd=s.rho_cd, rho_fd=s.rho_fd,
                round_trips=s.host_round_trips,
                device_loop_calls=s.device_loop_calls,
                peak_bytes_in_use=peak_bytes(device), exact=True)


def phase_tiled(g, oracle, device) -> None:
    from repro.api import EngineConfig, Executor

    for side in ("U", "V"):
        where = f"tiled side={side}"
        ex = Executor(EngineConfig(side=side, representation="tiled"))
        dec, wall = _timed_decompose(ex, g)
        check(dec.plan.representation == "tiled",
              f"{where}: planned {dec.plan.representation!r}")
        check_backend(where, dec.plan, dec.stats, ex)
        check(np.array_equal(dec.theta, oracle[side]),
              f"{where}: theta differs from the dense theta at "
              f"{int(np.sum(dec.theta != oracle[side]))} vertices")
        s = dec.stats
        log("tiled", side=side, backend=s.backend_used, cold_wall_s=wall,
            sweeps=s.rho_fd, round_trips=s.host_round_trips,
            recompactions=s.dgm_compactions,
            n_tiles=dec.plan.cost_model["n_tiles"],
            peak_bytes_in_use=peak_bytes(device), exact=True)


def phase_wing(seed: int, device) -> None:
    from repro.api import EngineConfig, Executor
    from repro.core.graph import powerlaw_bipartite
    from repro.core.wing import wing_bup_oracle

    g = powerlaw_bipartite(WING_GRAPH["n_u"], WING_GRAPH["n_v"],
                           WING_GRAPH["m_target"], seed=seed)
    t0 = time.perf_counter()
    psi_ref, _ = wing_bup_oracle(g)
    t_oracle = time.perf_counter() - t0
    ex = Executor(EngineConfig(workload="wing"))
    dec, wall = _timed_decompose(ex, g)
    # the plan's Pallas backend is only a label here: the edge axis runs
    # jnp products on every backend (ops.edge_support_all/_delta)
    check_backend("wing", dec.plan, dec.stats, ex)
    check(np.array_equal(dec.edge_wing, psi_ref),
          f"wing: psi differs from wing_bup_oracle at "
          f"{int(np.sum(dec.edge_wing != psi_ref))} edges")
    log("wing", n_u=g.n_u, n_v=g.n_v, m=g.m, backend=dec.stats.backend_used,
        cold_wall_s=wall, host_oracle_s=t_oracle,
        rho_cd=dec.stats.rho_cd, rho_fd=dec.stats.rho_fd,
        max_psi=int(psi_ref.max()) if psi_ref.size else 0,
        peak_bytes_in_use=peak_bytes(device), exact=True)


def phase_fleet(seed: int, device) -> None:
    from repro.api import EngineConfig, Executor, TipDecomposition
    from repro.core.graph import powerlaw_bipartite
    from repro.core.peeling import bup_oracle

    graphs = [powerlaw_bipartite(FLEET["n_u"], FLEET["n_v"],
                                 FLEET["m_target"], seed=seed * 1000 + i)
              for i in range(FLEET["count"])]
    ex = Executor(EngineConfig())
    t0 = time.perf_counter()
    results = ex.map(graphs, strict=True)
    wall = time.perf_counter() - t0
    rep = ex.last_map_report
    check(rep["backend"] in PALLAS_BACKENDS,
          f"fleet: map ran on {rep['backend']!r}")
    check(rep["chunk_failures"] == 0 and rep["chunk_retries"] == 0,
          f"fleet: chunk failures/retries in {rep}")
    check(ex.cache_stats["fallback_runs"] == 0,
          f"fleet: fallback runs {ex.cache_stats}")
    for i, (g, res) in enumerate(zip(graphs, results)):
        check(isinstance(res, TipDecomposition),
              f"fleet: graph {i} returned {res!r}")
        check(res.stats.backend_used == rep["backend"],
              f"fleet: graph {i} ran on {res.stats.backend_used!r}")
        check(np.array_equal(res.theta, bup_oracle(g)[0]),
              f"fleet: graph {i} theta differs from bup_oracle")
    log("fleet", graphs=len(graphs), backend=rep["backend"], wall_s=wall,
        dispatches=rep["counting_dispatches"] + rep["device_loop_calls"],
        chunks=rep["chunks"], round_trips=rep["host_round_trips"],
        peak_bytes_in_use=peak_bytes(device), exact=True)


def _mutations(g, count: int, rng):
    """``count`` absent edges to insert and ``count`` present ones to
    delete."""
    have = set((g.edges_u.astype(np.int64) * g.n_v + g.edges_v).tolist())
    ins = []
    while len(ins) < count:
        k = int(rng.integers(g.n_u)) * g.n_v + int(rng.integers(g.n_v))
        if k not in have:
            have.add(k)
            ins.append(k)
    ins = np.asarray(ins, np.int64)
    drop = rng.choice(g.m, count, replace=False)
    return (ins // g.n_v, ins % g.n_v, g.edges_u[drop], g.edges_v[drop])


def phase_service(g, seed: int, device) -> None:
    from repro.api import EngineConfig, Executor
    from repro.core.peeling import bup_oracle
    from repro.service import DecompositionService, ServiceConfig

    cfg = EngineConfig(representation="dense")
    svc = DecompositionService(cfg, ServiceConfig(background=True,
                                                  wait_timeout_s=900.0))
    rng = np.random.default_rng(seed)
    count = max(1, int(round(SERVICE_EDGE_FRACTION * g.m)))
    t0 = time.perf_counter()
    try:
        svc.ingest("smoke", g)
        cur = svc.query("smoke", wait=True)
        t_ingest = time.perf_counter() - t0
        check(cur.stats.backend_used in PALLAS_BACKENDS,
              f"service: ingest ran on {cur.stats.backend_used!r}")
        refresh_modes = []
        for _ in range(SERVICE_ROUNDS):
            iu, iv, du, dv = _mutations(cur.graph, count, rng)
            svc.insert_edges("smoke", iu, iv)
            svc.delete_edges("smoke", du, dv)
            # point queries while the worker refreshes (stale or fresh)
            svc.tip_number("smoke", int(iu[0]))
            svc.max_level("smoke")
            dec = svc.query("smoke", wait=True)
            check(dec is not cur, "service: refresh produced no new result")
            check(dec.stats.backend_used in PALLAS_BACKENDS,
                  f"service: refresh ran on {dec.stats.backend_used!r}")
            refresh_modes.append(dec.stats.refresh_mode or "full")
            cur = dec
        t_serve = time.perf_counter() - t0
        drained = svc.stop_worker(drain=True, timeout=900.0)
        report = svc.report()
    finally:
        svc.close()
    check(drained, "service: draining shutdown timed out")
    check(svc.worker is None or not svc.worker.alive,
          "service: worker still alive after close()")
    check(report["executors"]["tip"]["fallback_runs"] == 0,
          f"service: executor fallback runs {report['executors']}")
    final, mutated = cur, cur.graph
    t1 = time.perf_counter()
    theta_ref = bup_oracle(mutated)[0]
    t_oracle = time.perf_counter() - t1
    check(np.array_equal(final.theta, theta_ref),
          f"service: served theta differs from bup_oracle of the mutated "
          f"graph at {int(np.sum(final.theta != theta_ref))} vertices")
    ref_ex = Executor(cfg)
    ref = ref_ex.decompose(mutated)
    check_backend("service reference", ref.plan, ref.stats, ref_ex)
    check(np.array_equal(ref.theta, theta_ref),
          f"service: a fresh decomposition of the mutated graph differs "
          f"from bup_oracle at {int(np.sum(ref.theta != theta_ref))} "
          f"vertices")
    log("service", m_final=mutated.m, edges_per_round=count,
        rounds=SERVICE_ROUNDS, refresh_modes=",".join(refresh_modes),
        ingest_wall_s=t_ingest, total_wall_s=t_serve,
        host_oracle_s=t_oracle,
        worker_cycles=(report["worker"] or {}).get("cycles"),
        max_level=final.max_level(),
        peak_bytes_in_use=peak_bytes(device), exact=True)


def phase_mesh(g, oracle, devices) -> None:
    """The 4-chip path: FD subsets LPT-sharded over a 4-device mesh,
    compared with the one-chip run and the oracle."""
    from repro.api import EngineConfig, Executor
    from repro.launch.mesh import make_mesh

    cfg = EngineConfig(representation="dense")
    one_ex = Executor(cfg)
    one, one_wall = _timed_decompose(one_ex, g)
    check_backend("mesh baseline", one.plan, one.stats, one_ex)
    check(np.array_equal(one.theta, oracle["U"]),
          "mesh baseline: one-chip theta differs from bup_oracle")
    mesh = make_mesh((len(devices),), ("data",), devices=devices)
    ex = Executor(cfg, mesh=mesh)
    dec, wall = _timed_decompose(ex, g)
    s = dec.stats
    check(dec.plan.mesh_shards == len(devices),
          f"mesh: plan has {dec.plan.mesh_shards} shards")
    check_backend("mesh", dec.plan, s, ex)
    check(s.fd_shards == len(devices), f"mesh: fd_shards={s.fd_shards}")
    check(np.array_equal(dec.theta, one.theta),
          "mesh: theta differs from the one-chip theta")
    check(np.array_equal(dec.theta, oracle["U"]),
          "mesh: theta differs from bup_oracle")
    peaks = [peak_bytes(d) for d in devices]
    check(all(r > 0 for r in s.fd_shard_rho),
          f"mesh: a shard peeled nothing (fd_shard_rho={s.fd_shard_rho})")
    check(all(p > 0 for p in peaks),
          f"mesh: a device never held data (peak bytes {peaks})")
    log("mesh", devices=len(devices), fd_shards=s.fd_shards,
        fd_shard_rho=list(s.fd_shard_rho),
        fd_shard_wedges=[float(w) for w in s.fd_shard_wedges],
        one_chip_wall_s=one_wall, mesh_cold_wall_s=wall,
        peak_bytes_in_use=peaks, exact=True)


# --------------------------------------------------------------------- #
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of every generated graph and mutation")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the mesh path and its comparison")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform!r}); "
              "this smoke run needs the chip", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2

    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    dev = devices[0]
    log("device", platform=dev.platform, kind=repr(dev.device_kind),
        count=len(devices), compile_cache=cache.directory)
    print("[smoke] walls below are those of a smoke run (cold includes "
          "compilation), not benchmark numbers", flush=True)
    t0 = time.perf_counter()
    g, oracle = phase_graph(args.seed)
    if args.chips == 4:
        phase_mesh(g, oracle, devices[:4])
    else:
        phase_tip(g, oracle, dev)
        phase_tiled(g, oracle, dev)
        phase_wing(args.seed, dev)
        phase_fleet(args.seed, dev)
        phase_service(g, args.seed, dev)
    log("done", total_wall_s=time.perf_counter() - t0,
        compile_cache_hits=cache.hits, compile_cache_writes=cache.writes)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
