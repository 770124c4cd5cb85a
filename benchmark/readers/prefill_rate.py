"""Milliseconds of prefill per 1,000 prompt tokens actually computed.  The
engine's `prefill` spans only cover the dispatch (JAX returns before the
device is done); the wave's `sample_sync` span that follows is where the
host waits for them.  So: the time of the window's `prefill` and
`sample_sync` spans together, over the tokens the prefills computed (their
`tokens` less the `cached_tokens` the prefix cache supplied)."""


def read(ctx, args):
    spans = ctx.get("spans", ())
    fills = [s for s in spans if s["name"] == "prefill" and s.get("args")]
    computed = sum(s["args"]["tokens"] - s["args"].get("cached_tokens", 0)
                   for s in fills)
    if not computed:
        return None
    busy_us = sum(s["dur_us"] for s in fills) + sum(
        s["dur_us"] for s in spans if s["name"] == "sample_sync")
    return busy_us / 1e3 / (computed / 1e3)
