"""Device-idle time inside the ``receipt.fd`` spans (fine-grained decomposition, sharded over the mesh) per decomposition, in ms, averaged over the chips that ran a program (``spans.py``)."""
from benchmarks.chip import spans


def read(ctx):
    return spans.per_decomposition_ms(ctx, "fd", "idle_s")
