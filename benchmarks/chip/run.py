#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

The cell (an entry of ``BENCHMARK.json``'s ``workloads``) names a
configuration and a traffic mix; the mix's ``kind`` names the module in
``kinds/`` that sets the system up, drives the window and checks the
outputs against the plain reference (``reference/``).  The run:

1. refuses anything but a TPU, or fewer chips than the cell asks for
   (non-zero exit, no result line);
2. keeps JAX's compile cache at the program's fixed path
   (``repro.launch.compile_cache``) and every program in it;
3. sets up and warms every shape the cell uses (``setup_s`` counts from
   the start of the process to the first timed operation);
4. measures for ``--seconds`` — with ``--trace 1`` under the profiler,
   reducing the trace (``trace.py``) to the cell's per-layer metrics
   (``metrics/``);
5. reads the peak device memory, frees the program's state, runs the
   reference and compares;
6. prints each compared number beside its limit as the last lines on
   stderr, and the result as the last line on stdout.
"""
from __future__ import annotations

import json
import os
import pathlib
import sys
import time


def _process_age() -> float:
    """Seconds since this process started (Linux ``/proc``)."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return max(0.0, uptime - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


_AGE_AT_IMPORT = _process_age()
_T_IMPORT = time.perf_counter()

ROOT = pathlib.Path(__file__).resolve().parents[2]
# the harness imports as ``benchmarks.chip.*`` from the checkout's root
# (never its own directory: ``trace.py`` would shadow the stdlib module)
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))

import argparse  # noqa: E402
import shutil  # noqa: E402

from benchmarks.chip import roofline, spec, trace  # noqa: E402

PALLAS = ("pallas", "pallas_sparse")
TRACE_DIR = ROOT / ".bench_trace"
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


def since_start() -> float:
    return _AGE_AT_IMPORT + time.perf_counter() - _T_IMPORT


class Run:
    """One run of one cell: its arguments, devices and what it built."""

    def __init__(self, cell, seed: int, seconds: float, devices,
                 backends=PALLAS):
        self.cell = cell
        self.seed = seed
        self.seconds = seconds
        self.devices = devices
        self.backends = backends
        self.work = None             # the reference's count of the work
        self.peaks = None            # peaks.json entry of the device
        self.compiled = 0
        self.loaded = 0

    def programs_built(self) -> int:
        """Programs compiled or loaded from the persistent cache so far."""
        return self.compiled + self.loaded

    def listen(self) -> None:
        import jax

        def on_duration(event, _secs, **_kw):
            if event == BACKEND_COMPILE:
                self.compiled += 1

        def on_event(event, **_kw):
            if event == CACHE_HIT:
                self.loaded += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    @staticmethod
    def log(what: str, **fields) -> None:
        print(f"[bench] {what}: " + json.dumps(fields, default=str),
              file=sys.stderr, flush=True)


def peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks)) if peaks else 0


def measure(run, kind, state, traced: bool):
    """The window; with ``traced``, under the profiler, and the trace's
    reduction."""
    if not traced:
        return kind.window(state, run.seconds), None
    log_dir = TRACE_DIR / run.cell.name
    shutil.rmtree(log_dir, ignore_errors=True)
    with trace.capture(str(log_dir)):
        win = kind.window(state, run.seconds)
    return win, trace.reduce(trace.find_xplane(str(log_dir)))


def execute(run, kind, traced: bool) -> dict:
    """Set up, measure, check; the result's fields (no ``device``)."""
    state = kind.setup(run)
    setup_s = since_start()
    built = run.programs_built()
    cpu0 = time.process_time()
    win, reduced = measure(run, kind, state, traced)
    # CPU seconds of all the process's threads over the window
    cpu_s = time.process_time() - cpu0
    built_in_window = run.programs_built() - built
    run.log("window", programs_built=built_in_window,
            compiled=run.compiled, loaded_from_cache=run.loaded,
            walls_s=win.get("walls_s"), process_cpu_s=cpu_s)
    memory = peak_bytes(run.devices)
    e2e = kind.end_to_end(win, state)
    run.log("end-to-end", **e2e)
    layer_ctx = kind.layer_context(state, win)
    kind.release(state)
    checks, failed = kind.check(state, win, run)
    correct = all(v <= lim for _n, v, lim in checks)
    attempted = e2e.pop("attempted")
    if traced:
        ctx = dict(layer_ctx, trace=reduced, work=run.work,
                   peaks=run.peaks)
        metrics = spec.read_metrics(run.cell.per_layer, ctx)
    else:
        values = dict(e2e, setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in run.cell.end_to_end
                   if values.get(m["name"]) is not None}
    out = {"correct": correct,
           "attempted": attempted,
           "failed": failed,
           "metrics": metrics, "memory": memory, "reduced": reduced,
           "checks": checks}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = spec.load_cell(args.workload, ROOT)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"run.py: no TPU (JAX found {devices[0].platform!r}); the "
              "benchmark runs only on the chip", file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"run.py: {args.workload} needs {cell.chips} chips, JAX "
              f"found {len(devices)}", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    # every program goes to the cache, so only a cell's first run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    run = Run(cell, args.seed, args.seconds, devices[:cell.chips])
    run.peaks = roofline.peaks(devices[0].device_kind)
    run.listen()
    kind = spec.kind_module(cell.traffic["kind"])
    out = execute(run, kind, bool(args.trace))
    Run.log("compile cache", directory=cache.directory, hits=cache.hits,
            writes=cache.writes)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": out["memory"]}
    result = {"correct": out["correct"], "attempted": out["attempted"],
              "failed": out["failed"], "metrics": out["metrics"],
              "device": device}
    reduced = out["reduced"]
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = {n: {"value": v, "limit": lim}
                        for n, v, lim in out["checks"]}
    for n, v, lim in out["checks"]:
        print(f"check {n}: {v} (limit {lim})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
