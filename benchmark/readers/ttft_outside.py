"""Serve library's share of the time to first token: the client's median
time from SEND to first token, minus the median of the engine's own
`request:admit` spans (enqueue in the replica -> first token fanned out)
that started in the window.  What is left is router, RPC and streaming."""

from .. import client, stats


def read(ctx, args):
    admits = [s["dur_us"] / 1e3 for s in ctx.get("spans", ())
              if s["name"] == "request:admit"]
    if not admits:
        return None
    return stats.median(client.ttfts_ms(ctx, since="sent")) \
        - stats.median(admits)
