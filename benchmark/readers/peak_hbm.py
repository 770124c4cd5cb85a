"""Peak device memory on the fullest chip (in use plus reserved scratch),
`memory_stats()` of the process that holds the chips, after the window."""

from .. import cluster


def read(ctx, args):
    peak = cluster.peak_bytes(ctx["memory"])
    return peak / 2 ** 30 if peak else None
