"""Host time in the Planner (``receipt.plan`` spans) per decomposition, in ms (``spans.py``)."""
from benchmarks.chip import spans


def read(ctx):
    return spans.per_decomposition_ms(ctx, "plan", "host_s")
