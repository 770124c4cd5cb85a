"""Time to the whole reply (due -> last token) of the requests due in the
window.  `args.band` = [lo, hi]: mean of the values between those
percentiles; `args.pct`: that percentile (nearest rank)."""

from .. import client, stats


def read(ctx, args):
    if "records" not in ctx:
        return None
    values = client.replies_ms(ctx)
    if "band" in args:
        return stats.band_mean(values, *args["band"])
    return stats.percentile(values, args["pct"])
