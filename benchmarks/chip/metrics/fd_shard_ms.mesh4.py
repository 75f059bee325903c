"""Host time in the ``receipt.fd.shard`` spans (each FD group's LPT layout and sharded stack upload) per decomposition, in ms (``spans.py``)."""
from benchmarks.chip import spans


def read(ctx):
    return spans.per_decomposition_ms(ctx, "fd.shard", "host_s")
