"""MiB of latent cache rows one decode step read in one layer: the engine's
cumulative `rows_read` (the live tokens of every step's batch,
`debug_stats()["latent"]`) over the window's decode steps, times
`row_bytes`, the bytes of a row's real values.  None where the program
counts none."""


def read(ctx, args):
    before = (ctx.get("stats_before") or {}).get("latent") or {}
    after = (ctx.get("stats_after") or {}).get("latent") or {}
    if not after.get("enabled") or not before.get("enabled"):
        return None
    steps = after["steps"] - before["steps"]
    if steps <= 0:
        return None
    rows = after["rows_read"] - before["rows_read"]
    return rows / steps * after["row_bytes"] / 2 ** 20
