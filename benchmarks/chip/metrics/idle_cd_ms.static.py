"""Device-idle time inside the ``receipt.cd`` spans (coarse-grained decomposition) per decomposition, in ms (``spans.py``)."""
from benchmarks.chip import spans


def read(ctx):
    return spans.per_decomposition_ms(ctx, "cd", "idle_s")
