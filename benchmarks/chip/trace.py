"""From a profiler trace to device metrics.

``capture`` traces a window with JAX's profiler; ``reduce`` reads the
``.xplane.pb`` it wrote with ``jax.profiler.ProfileData`` and returns:

* ``window_s``  — the traced window: the host span ``WINDOW`` that the
                  benchmark puts around the timed work;
* ``busy_s``    — per device, the union of the intervals in which a
                  program ran (the ``XLA Modules`` line), inside the
                  window; averaged over the devices that ran anything;
* ``ops``       — per device op name (the HLO instruction name without
                  its ``.N`` suffix), the summed device time in seconds
                  and the count, from the ``XLA Ops`` line;
* ``device_ops`` — the ops with the most device time once the time of
                  ops nested in them is taken out (a ``while`` loop's
                  own time, not its body's);
* ``custom_calls`` — the names of the custom calls that ran;
* ``idle_gaps`` — the longest gaps between programs inside the window,
                  each named by the innermost benchmark span (``bench.``)
                  open on the host at its midpoint.

A Pallas kernel is a ``custom-call`` op named after the function that
built it (``butterfly_support_pallas``, ``b2_stack_pallas_sparse``, ...).
Timestamps on the device and host planes share the profile's clock.
"""
from __future__ import annotations

import collections
import contextlib
import glob
import os
import re
from typing import Dict, List, Tuple

WINDOW = "bench.window"
# The device timeline runs up to about a millisecond ahead of the host's
# in the recorded traces (a program can appear to start before the host
# span that dispatched it); device intervals are clipped to the window
# widened by this much at its start.
CLOCK_SKEW_NS = 5e6
_SUFFIX = re.compile(r"\.\d+$")


@contextlib.contextmanager
def capture(log_dir: str):
    """Trace the body; the body runs inside the ``WINDOW`` span."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(WINDOW):
            yield
    finally:
        jax.profiler.stop_trace()


def span(name: str):
    """A host span the idle-gap attribution can name (``bench.<name>``)."""
    import jax

    return jax.profiler.TraceAnnotation(f"bench.{name}")


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def op_name(event_name: str) -> str:
    """``%butterfly_support_pallas.5 = f32[...] custom-call(...)`` ->
    ``butterfly_support_pallas``."""
    head = event_name.split(" = ", 1)[0].lstrip("%")
    return _SUFFIX.sub("", head)


def is_custom_call(event_name: str) -> bool:
    return " custom-call(" in event_name


def kernel_seconds(reduced: Dict) -> float:
    """Device seconds of the Pallas kernels in a reduced trace: the
    custom calls whose op name carries ``pallas``."""
    return sum(op["s"] for name, op in reduced["ops"].items()
               if "pallas" in name and name in reduced["custom_calls"])


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _self_times(events) -> Dict[str, float]:
    """Per op name, the time not covered by ops nested inside it (ns)."""
    out: Dict[str, float] = collections.defaultdict(float)
    stack: List[list] = []              # [end, name, child_ns]
    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            _end, nm, child = stack.pop()
            out[nm] -= child
        if stack:
            stack[-1][2] += e - s
        out[name] += e - s
        stack.append([e, name, 0.0])
    while stack:
        _end, nm, child = stack.pop()
        out[nm] -= child
    return out


def reduce(path: str, top: int = 10) -> Dict:
    """Reduce one ``.xplane.pb`` (see the module docstring)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    window = None
    host_spans = []                      # (start, end, name) of bench.*
    devices = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            mods, ops = [], []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    mods = [(e.start_ns, e.end_ns) for e in line.events]
                elif line.name == "XLA Ops":
                    ops = [(e.start_ns, e.end_ns, e.name)
                           for e in line.events]
            devices[plane.name] = (mods, ops)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW:
                        window = (e.start_ns, e.end_ns)
                    elif e.name.startswith("bench."):
                        host_spans.append((e.start_ns, e.end_ns, e.name))
    if window is None:
        raise ValueError(f"{path}: no {WINDOW!r} span on the host")
    lo, hi = window[0] - CLOCK_SKEW_NS, window[1]
    busy, ops_ns, ops_n, self_ns, gaps = [], collections.Counter(), \
        collections.Counter(), collections.Counter(), []
    for mods, ops in devices.values():
        ivs = _union(_clip(mods, lo, hi))
        if not ivs:
            continue
        busy.append(sum(e - s for s, e in ivs))
        for (s0, e0), (s1, _e1) in zip(ivs, ivs[1:]):
            gaps.append((s1 - e0, e0, s1))
        inside = [(max(s, lo), min(e, hi), op_name(n)) for s, e, n in ops
                  if e > lo and s < hi]
        for s, e, n in inside:
            ops_ns[n] += e - s
            ops_n[n] += 1
        self_ns.update(_self_times(inside))
    n_dev = max(len(busy), 1)
    gaps.sort(reverse=True)
    named_gaps = []
    for length, s, e in gaps[:top]:
        mid = (s + e) / 2
        open_spans = [(se - ss, nm) for ss, se, nm in host_spans
                      if ss <= mid <= se]
        label = min(open_spans)[1] if open_spans else "host:no-bench-span"
        named_gaps.append([label, length / 1e9])
    return {
        "window_s": (window[1] - window[0]) / 1e9,
        "busy_s": sum(busy) / n_dev / 1e9,
        "devices": len(busy),
        "ops": {n: {"s": ops_ns[n] / 1e9 / n_dev, "count": ops_n[n]}
                for n in ops_ns},
        "custom_calls": sorted({op_name(n) for _m, ops in devices.values()
                                for _s, _e, n in ops if is_custom_call(n)}),
        "device_ops": [[n, t / 1e9 / n_dev]
                       for n, t in self_ns.most_common(top)],
        "idle_gaps": named_gaps,
    }
