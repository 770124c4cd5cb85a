"""Share of the rows the window's prefills ran through the recurrent scans
that held no token: 100 x (1 - `prefill_rows` / `prefill_rows_run`) of
`debug_stats()["mamba"]` at the window's two ends.  A prompt is padded to
a power-of-two bucket and a scan runs every row it is given (the bucket's,
or from 2,048 rows the 512-row blocks that hold a real row).  None where
the program counts none, or no prefill ran."""


def read(ctx, args):
    before = (ctx.get("stats_before") or {}).get("mamba") or {}
    after = (ctx.get("stats_after") or {}).get("mamba") or {}
    if not after.get("enabled") or not before.get("enabled"):
        return None
    ran = after["prefill_rows_run"] - before["prefill_rows_run"]
    if ran <= 0:
        return None
    return 100.0 * (1.0 - (after["prefill_rows"]
                           - before["prefill_rows"]) / ran)
