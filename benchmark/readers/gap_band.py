"""Mean of the gaps between consecutive output tokens that lie between two
percentiles (`args.band`) of all gaps of the requests due in the window."""

from .. import client, stats


def read(ctx, args):
    return stats.band_mean(client.gaps_ms(ctx), *args["band"])
