"""Time to first token of the requests due in the window, from when each
was due.  `args.band` = [lo, hi]: mean of the values between those
percentiles; `args.pct`: that percentile (nearest rank)."""

from .. import client, stats


def read(ctx, args):
    values = client.ttfts_ms(ctx)
    if "band" in args:
        return stats.band_mean(values, *args["band"])
    return stats.percentile(values, args["pct"])
