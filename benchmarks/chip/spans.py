"""The program's own host spans (``receipt.*``) against the device's idle time.

The engine opens ``jax.profiler.TraceAnnotation("receipt.<phase>")`` spans
(``repro.utils.spans``) at each layer and phase boundary, and a
``receipt.sync`` span around every blocking device-to-host transfer.  They
land on the ``/host:CPU`` plane of the ``--trace 1`` run's ``.xplane.pb``,
on the same clock as the device's ``XLA Modules`` line.  This module reads
that file again and puts each stretch of device-idle time down to the spans
open on the host across it:

* device-idle intervals: inside the window (``trace.WINDOW``), the
  complement of the union of each device's ``XLA Modules`` intervals,
  clipped as ``trace.reduce`` clips them for ``busy_s``; averaged over the
  devices that ran anything;
* per span name: how many overlap the window, their host time inside it,
  their self time (less the ``receipt.*`` spans nested in them) and the
  device-idle time under the union of their intervals;
* unattributed: device-idle time under none of ``PHASES``, the engine's
  top-level host phases, which never overlap one another.

The per-layer readers (``metrics/plan_ms.static.py`` and the ``idle_*``
readers) find the trace ``run.py`` wrote under ``.bench_trace/`` by its
window length, and return None where it holds no ``receipt.*`` span.

    python -m benchmarks.chip.spans <file.xplane.pb>

prints the table per span name, then per ``receipt.sync`` phase.
"""
from __future__ import annotations

import collections
import glob
import os
import pathlib
import sys
import warnings
from typing import Dict, List, NamedTuple, Optional, Tuple

from benchmarks.chip.trace import (CLOCK_SKEW_NS, WINDOW, _clip,
                                   _self_times, _union)

PREFIX = "receipt."
PHASES = tuple(PREFIX + p for p in ("plan", "relabel", "cd", "fd", "tiled"))
ROOT = pathlib.Path(__file__).resolve().parents[2]
TRACE_DIR = ROOT / ".bench_trace"       # where run.py traces a cell
MATCH_S = 1e-3                          # window lengths this close match

Interval = Tuple[float, float]


class Span(NamedTuple):
    start: float
    end: float
    name: str
    thread: int
    phase: str                          # a sync's ``phase``, else ""


class Parsed(NamedTuple):
    window: Interval
    devices: List[List[Interval]]       # XLA Modules intervals per device
    spans: List[Span]


_PARSED: Dict[str, Parsed] = {}
_SUMMARIES: Dict[str, Optional[dict]] = {}


def parse(path: str) -> Parsed:
    """The window, the device program intervals and the ``receipt.*``
    spans of one ``.xplane.pb`` (parsed once per process)."""
    if path in _PARSED:
        return _PARSED[path]
    from jax.profiler import ProfileData

    window, devices, spans = None, [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            devices.append([(e.start_ns, e.end_ns)
                            for line in plane.lines
                            if line.name == "XLA Modules"
                            for e in line.events])
        elif plane.name == "/host:CPU":
            for thread, line in enumerate(plane.lines):
                for e in line.events:
                    if e.name == WINDOW:
                        window = (e.start_ns, e.end_ns)
                    elif e.name.startswith(PREFIX):
                        spans.append(Span(e.start_ns, e.end_ns, e.name,
                                          thread, _phase(e)))
    if window is None:
        raise ValueError(f"{path}: no {WINDOW!r} span on the host")
    _PARSED[path] = Parsed(window, devices, spans)
    return _PARSED[path]


def _phase(event) -> str:
    if not event.name.endswith(".sync"):
        return ""
    with warnings.catch_warnings():     # jaxlib's stats type warns once
        warnings.simplefilter("ignore", DeprecationWarning)
        return str(dict(event.stats).get("phase", ""))


def _measure(ivs: List[Interval]) -> float:
    return sum(e - s for s, e in ivs)


def _intersect(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _complement(ivs: List[Interval], lo: float, hi: float) -> List[Interval]:
    """[lo, hi] less a sorted list of disjoint intervals."""
    out, cur = [], lo
    for s, e in ivs:
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


def attribute(window: Interval, devices: List[List[Interval]],
              spans: List[Span]) -> Optional[dict]:
    """Device-idle time under each span name (see the module docstring);
    None where no device ran a program in the window.  Times in s."""
    w0, w1 = window
    idle = []
    for mods in devices:
        busy = _union(_clip(mods, w0 - CLOCK_SKEW_NS, w1))
        if busy:
            idle.append(_complement(busy, w0, w1))
    if not idle:
        return None
    n_dev = len(idle)

    def idle_under(ivs: List[Interval]) -> float:
        cover = _union(ivs)
        return sum(_measure(_intersect(d, cover)) for d in idle) / n_dev

    inside = [sp._replace(start=max(sp.start, w0), end=min(sp.end, w1))
              for sp in spans if sp.end > w0 and sp.start < w1]
    by_name: Dict[str, List[Span]] = collections.defaultdict(list)
    by_thread: Dict[int, List] = collections.defaultdict(list)
    for sp in inside:
        by_name[sp.name].append(sp)
        by_thread[sp.thread].append((sp.start, sp.end, sp.name))
    self_ns: Dict[str, float] = collections.Counter()
    for events in by_thread.values():
        self_ns.update(_self_times(events))
    names = {
        name: {"count": len(group),
               "host_s": sum(sp.end - sp.start for sp in group) / 1e9,
               "self_s": self_ns[name] / 1e9,
               "idle_s": idle_under([(sp.start, sp.end)
                                     for sp in group]) / 1e9}
        for name, group in by_name.items()}
    idle_s = sum(_measure(d) for d in idle) / n_dev / 1e9
    phased = [(sp.start, sp.end) for sp in inside if sp.name in PHASES]
    return {"window_s": (w1 - w0) / 1e9, "idle_s": idle_s,
            "devices": n_dev, "names": names,
            "unattributed_s": idle_s - idle_under(phased) / 1e9}


def find(window_s: float) -> Optional[str]:
    """The newest ``.xplane.pb`` under ``TRACE_DIR`` whose window lasts
    ``window_s`` to within ``MATCH_S``: the run's own trace, never
    another run's."""
    files = glob.glob(os.path.join(str(TRACE_DIR), "**", "*.xplane.pb"),
                      recursive=True)
    for path in sorted(files, key=os.path.getmtime, reverse=True):
        try:
            w0, w1 = parse(path).window
        except ValueError:              # not a benchmark window's trace
            continue
        if abs((w1 - w0) / 1e9 - window_s) <= MATCH_S:
            return path
    return None


def summary(path: str) -> Optional[dict]:
    """``attribute`` of one file; None where it has no ``receipt.*``
    span (a program without spans) or no device program."""
    if path not in _SUMMARIES:
        p = parse(path)
        _SUMMARIES[path] = (attribute(*p) if p.spans else None)
    return _SUMMARIES[path]


def for_run(ctx) -> Optional[Tuple[dict, int]]:
    """The summary of the traced run a reader's ``ctx`` describes, with
    its number of decompositions; None where there is nothing to read."""
    reduced, runs = ctx.get("trace"), ctx.get("decompositions")
    if not reduced or not runs:
        return None
    path = find(reduced["window_s"])
    found = summary(path) if path else None
    return (found, len(runs)) if found else None


def per_decomposition_ms(ctx, name: str, field: str) -> Optional[float]:
    """``field`` (``host_s`` or ``idle_s``) of span ``receipt.<name>``
    per decomposition, in ms."""
    got = for_run(ctx)
    if got is None or PREFIX + name not in got[0]["names"]:
        return None
    found, n = got
    return found["names"][PREFIX + name][field] / n * 1e3


def _table(found: dict) -> List[str]:
    rows = [f"{'span':<28}{'count':>7}{'host ms':>12}{'self ms':>12}"
            f"{'idle ms':>12}"]
    for name, v in sorted(found["names"].items(),
                          key=lambda kv: -kv[1]["idle_s"]):
        rows.append(f"{name:<28}{v['count']:>7}{v['host_s'] * 1e3:>12.1f}"
                    f"{v['self_s'] * 1e3:>12.1f}{v['idle_s'] * 1e3:>12.1f}")
    return rows


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    if len(args) != 1:
        print("usage: python -m benchmarks.chip.spans <file.xplane.pb>",
              file=sys.stderr)
        return 2
    p = parse(args[0])
    found = attribute(*p)
    if found is None:
        print("no device program in the window", file=sys.stderr)
        return 1
    print(f"window {found['window_s']:.6f} s on {found['devices']} "
          f"device(s); device idle {found['idle_s']:.6f} s, "
          f"under none of {', '.join(PHASES)}: "
          f"{found['unattributed_s']:.6f} s")
    print("\n".join(_table(found)))
    syncs = [sp._replace(name=f"sync:{sp.phase}") for sp in p.spans
             if sp.name == PREFIX + "sync"]
    if syncs:
        print()
        print("\n".join(_table(attribute(p.window, p.devices, syncs))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
