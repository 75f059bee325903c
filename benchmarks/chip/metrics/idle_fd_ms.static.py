"""Device-idle time inside the ``receipt.fd`` spans (fine-grained decomposition) per decomposition, in ms (``spans.py``)."""
from benchmarks.chip import spans


def read(ctx):
    return spans.per_decomposition_ms(ctx, "fd", "idle_s")
