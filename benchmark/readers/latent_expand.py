"""Key rows the window's prefills up-projected to per-head keys and values,
as a share of the key rows they attended: the engine's cumulative
`rows_expanded` and `rows_attended` (`debug_stats()["latent"]`) at the
window's two ends.  An expanded prefill up-projects every row it attends,
cached and new; an absorbed one none.  None where the program counts
none."""


def read(ctx, args):
    before = (ctx.get("stats_before") or {}).get("latent") or {}
    after = (ctx.get("stats_after") or {}).get("latent") or {}
    if not after.get("enabled") or not before.get("enabled"):
        return None
    attended = after["rows_attended"] - before["rows_attended"]
    if attended <= 0:
        return None
    return 100.0 * (after["rows_expanded"] - before["rows_expanded"]) \
        / attended
