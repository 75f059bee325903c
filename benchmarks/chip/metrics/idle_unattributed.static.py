"""Share of the window's device-idle time inside none of the engine's top-level host phases (``spans.PHASES``), in %."""
from benchmarks.chip import spans


def read(ctx):
    got = spans.for_run(ctx)
    if got is None or got[0]["idle_s"] <= 0:
        return None
    found, _n = got
    return found["unattributed_s"] / found["idle_s"] * 100.0
