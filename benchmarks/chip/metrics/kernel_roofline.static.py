"""Least time for a decomposition's work over the Pallas kernels' device time per decomposition, in %.

The work and the least time come from ``benchmarks/chip/roofline.py``
(counted from the graph by the reference peel); the kernels are those
of ``kernel_ms.static``.
"""
from benchmarks.chip import roofline, trace


def read(ctx):
    reduced, runs, work = (ctx.get("trace"), ctx.get("decompositions"),
                           ctx.get("work"))
    if not reduced or not runs or not work or not ctx.get("peaks"):
        return None
    seconds = trace.kernel_seconds(reduced)
    if seconds <= 0:
        return None
    least = roofline.least_time(
        roofline.decomposition_work(**work), ctx["peaks"])
    return least["seconds"] / (seconds / len(runs)) * 100.0
