"""Device-idle time inside the ``receipt.cd`` spans (coarse-grained decomposition, on one chip of the mesh) per decomposition, in ms, averaged over the chips that ran a program (``spans.py``)."""
from benchmarks.chip import spans


def read(ctx):
    return spans.per_decomposition_ms(ctx, "cd", "idle_s")
