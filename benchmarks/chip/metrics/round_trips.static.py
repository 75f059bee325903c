"""Blocking device-to-host transfers per decomposition (``RunStats.host_round_trips``), mean over the traced window."""


def read(ctx):
    runs = ctx.get("decompositions")
    if not runs:
        return None
    return sum(r["host_round_trips"] for r in runs) / len(runs)
