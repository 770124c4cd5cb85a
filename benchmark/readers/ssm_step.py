"""MiB of recurrent state one decode step read and wrote for its LIVE
sequences: the engine's cumulative `rows_stepped` (the live sequences of
every step, `debug_stats()["mamba"]`) over the window's decode steps, times
`row_bytes`, one sequence's state over all the Mamba layers as the program
holds it, times 2 (read and written).  None where the program counts none.
What the step's program moves for the slots that are NOT live is not in
it: the counter counts what a step must, the trace what it did."""


def read(ctx, args):
    before = (ctx.get("stats_before") or {}).get("mamba") or {}
    after = (ctx.get("stats_after") or {}).get("mamba") or {}
    if not after.get("enabled") or not before.get("enabled"):
        return None
    steps = after["steps"] - before["steps"]
    if steps <= 0:
        return None
    rows = after["rows_stepped"] - before["rows_stepped"]
    return rows / steps * after["row_bytes"] * 2 / 2 ** 20
