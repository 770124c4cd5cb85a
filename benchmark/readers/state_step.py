"""MiB of recurrent state one decode step read and wrote: the engine's
cumulative `rows_stepped` (the live sequences of every step,
`debug_stats()["retention"]`) over the window's decode steps, times
`row_bytes`, one sequence's state over all layers as the program holds it,
times 2 (read and written).  None where the program counts none.  Beside
`decode_batch_mean` it says what the program's FORM of the state costs: the
same live sequences move more or fewer bytes when phi's block or the state's
type changes, which `decode_roofline_pct.state` (the published width) is
blind to by design."""


def read(ctx, args):
    before = (ctx.get("stats_before") or {}).get("retention") or {}
    after = (ctx.get("stats_after") or {}).get("retention") or {}
    if not after.get("enabled") or not before.get("enabled"):
        return None
    steps = after["steps"] - before["steps"]
    if steps <= 0:
        return None
    rows = after["rows_stepped"] - before["rows_stepped"]
    return rows / steps * after["row_bytes"] * 2 / 2 ** 20
