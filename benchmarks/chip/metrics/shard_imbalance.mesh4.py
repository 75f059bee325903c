"""FD's load imbalance over the mesh: per decomposition the largest shard's dynamic wedge load (``RunStats.fd_shard_wedges``) over the shards' mean, averaged over the traced window; 1 is an even spread."""


def read(ctx):
    runs = ctx.get("decompositions")
    if not runs:
        return None
    ratios = []
    for r in runs:
        wedges = r.get("fd_shard_wedges")
        if not wedges or sum(wedges) <= 0:
            return None
        ratios.append(max(wedges) / (sum(wedges) / len(wedges)))
    return sum(ratios) / len(ratios)
