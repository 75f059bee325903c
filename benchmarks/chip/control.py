#!/usr/bin/env python3
"""Read a cell's compared numbers over many seeds in one process, under
a control: the program, or the reference in its place, one precision
below what the configuration states.

    python3 benchmarks/chip/control.py --workload static-dense \\
        --seeds 11,12,13 --seconds 5 --control bfloat16-reference

``default`` runs the program with its exact float32 products
(``repro.kernels.ref.EXACT``, HIGHEST) set to one bfloat16 pass before
any engine module binds them.  ``bfloat16-reference`` puts the reference
peel in the program's place, with its supports rounded to bfloat16, the
nearest type below the float32 supports the configuration states: every
tip number that ``Executor.decompose`` returns is replaced by it.  Each
seed prints one JSON line with the numbers compared and their limits.
The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))


def reference_in_program_place() -> None:
    """Make the executor return the bfloat16-support reference peel of
    the graph it was given, in place of its own tip numbers."""
    import ml_dtypes
    import numpy as np

    from benchmarks.chip.reference import oracle
    from repro.api import executor

    def lowered(graph, side):
        edges = (graph.n_u, graph.n_v, graph.edges_u, graph.edges_v)
        return oracle.peel_side(edges, side, ml_dtypes.bfloat16).theta

    decompose = executor.Executor.decompose
    last = {}                       # the last graph peeled, and its peel

    def decompose_lowered(self, graph, *args, **kw):
        dec = decompose(self, graph, *args, **kw)
        if last.get("graph") is not graph:
            last.update(graph=graph, theta=lowered(graph, self.side))
        dec.theta = np.asarray(last["theta"])
        return dec

    executor.Executor.decompose = decompose_lowered


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one run each")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", required=True,
                    choices=("default", "bfloat16-reference"))
    args = ap.parse_args(argv)

    import jax

    from repro.kernels import ref

    if args.control == "default":
        ref.EXACT = jax.lax.Precision.DEFAULT
    from benchmarks.chip import roofline, spec
    from benchmarks.chip.run import ROOT as BENCH_ROOT, Run, execute
    from repro.launch.compile_cache import enable_compile_cache

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("control.py: no TPU", file=sys.stderr)
        return 2
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if args.control == "bfloat16-reference":
        reference_in_program_place()
    from repro.core.engine import peel_loop

    print(f"control.py: engine products at {peel_loop.EXACT}",
          file=sys.stderr)
    cell = spec.load_cell(args.workload, BENCH_ROOT)
    kind = spec.kind_module(cell.traffic["kind"])
    for seed in (int(s) for s in args.seeds.split(",")):
        run = Run(cell, seed, args.seconds, devices[:cell.chips])
        run.peaks = roofline.peaks(devices[0].device_kind)
        run.listen()
        out = execute(run, kind, traced=False)
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "control": args.control, "correct": out["correct"],
            "metrics": out["metrics"],
            "checks": {n: [v, lim] for n, v, lim in out["checks"]}}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
