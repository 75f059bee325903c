"""Device time of the Pallas kernels per decomposition, in ms (``trace.kernel_seconds``)."""
from benchmarks.chip import trace


def read(ctx):
    reduced, runs = ctx.get("trace"), ctx.get("decompositions")
    if not reduced or not runs:
        return None
    seconds = trace.kernel_seconds(reduced)
    return seconds / len(runs) * 1e3 if seconds > 0 else None
