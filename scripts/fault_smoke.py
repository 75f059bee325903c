#!/usr/bin/env python
"""Fault-injection smoke (CI matrix job): results under an armed
``RECEIPT_FAULT`` must be BIT-IDENTICAL to an uninjected baseline.

The job arms one fault config through the environment (the process-wide
injector in ``repro.api.faults``), then runs the decompose surface both
ways in one process:

1. baseline — inside ``faults.suppressed()``, so the env injector is
   masked and the pipeline runs clean;
2. ambient — the same graphs again with the env injector live, letting
   the armed fault fire into the hardened runtime's degradation paths
   (backend fallback, overflow replay, fleet isolation).

Exact equality of every tip-number vector is the acceptance: graceful
degradation must never change results, only cost.  The script fails if
any theta drifts, if a healthy fleet member is lost, or if the armed
spec never fired (a fault config that exercises nothing is a dead
matrix entry).

Run from the repo root::

    RECEIPT_FAULT="kernel_launch:backend=interpret@1" JAX_PLATFORMS=cpu \
        PYTHONPATH=src python scripts/fault_smoke.py
"""
from __future__ import annotations

import os
import sys

import numpy as np

from repro.api import EngineConfig, Executor, faults
from repro.api.errors import ReceiptError
from repro.core.graph import BipartiteGraph

BLOCKS = (8, 8, 8)


def _er(nu, nv, ne, seed):
    rng = np.random.default_rng(seed)
    return BipartiteGraph.from_edges(
        nu, nv, rng.integers(0, nu, ne), rng.integers(0, nv, ne))


def _single_cfg():
    # interpret primary so kernel_launch faults have a fallback stop;
    # subset dispatch + DGM on so the dgm_boundary site is reached
    return EngineConfig(backend="interpret", num_partitions=3,
                        kernel_blocks=BLOCKS, cd_dispatch="subset",
                        use_dgm=True)


def _fleet_cfg():
    return EngineConfig(backend="interpret", num_partitions=3,
                        kernel_blocks=BLOCKS)


def main() -> int:
    spec = os.environ.get(faults.ENV_VAR, "")
    print(f"[fault_smoke] {faults.ENV_VAR}={spec!r}")
    graph = _er(40, 30, 200, seed=1)
    fleet = [_er(16, 12, 60, seed=s) for s in range(6)]

    # 1) clean baseline, env faults masked
    with faults.suppressed():
        base_theta = Executor(_single_cfg()).decompose(graph).theta
        base_fleet = [td.theta for td in Executor(_fleet_cfg()).map(fleet)]

    # 2) ambient run, env injector live
    td = Executor(_single_cfg()).decompose(graph)
    ex = Executor(_fleet_cfg())
    res = ex.map(fleet)

    failures = []
    if not np.array_equal(td.theta, base_theta):
        failures.append("single-graph theta drifted under injection")
    if td.stats.backend_fallbacks:
        print(f"[fault_smoke] decompose degraded: "
              f"{td.stats.backend_fallbacks} -> {td.stats.backend_used}")
    if td.stats.overflow_fallbacks:
        print(f"[fault_smoke] decompose replayed "
              f"{td.stats.overflow_fallbacks} overflow sweep(s)")
    for i, (r, want) in enumerate(zip(res, base_fleet)):
        if isinstance(r, ReceiptError):
            failures.append(f"healthy fleet member {i} lost: {r!r}")
        elif not np.array_equal(r.theta, want):
            failures.append(f"fleet member {i} theta drifted")
    rep = ex.last_map_report
    print(f"[fault_smoke] map: chunk_failures={rep['chunk_failures']} "
          f"chunk_retries={rep['chunk_retries']} "
          f"isolated_graphs={rep['isolated_graphs']}")

    fired = 0
    if spec:
        report = faults.active_injector().report()
        for r in report:
            print(f"[fault_smoke] rule {r['rule']}: hits={r['hits']} "
                  f"fired={r['fired']}")
        fired = sum(r["fired"] for r in report)
        if fired == 0:
            failures.append(
                f"armed spec {spec!r} never fired — dead matrix entry")

    for f in failures:
        print(f"[fault_smoke] FAIL: {f}", file=sys.stderr)
    if failures:
        return 1
    print(f"[fault_smoke] ok: exact under injection "
          f"({fired} firing(s))" if spec else
          "[fault_smoke] ok: clean run (no fault armed)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
