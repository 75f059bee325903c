"""Peel sweeps per decomposition: ``rho_cd + rho_fd`` (the tiled engine counts its sweeps in ``rho_fd``), mean over the traced window."""


def read(ctx):
    runs = ctx.get("decompositions")
    if not runs:
        return None
    return sum(r["rho_cd"] + r["rho_fd"] for r in runs) / len(runs)
