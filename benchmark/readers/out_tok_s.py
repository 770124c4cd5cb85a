"""Output tokens that arrived inside the window, over the window."""

from .. import client


def read(ctx, args):
    if "records" not in ctx:
        return None
    t0, t1 = ctx["window"]
    return client.tokens_in_window(ctx) / (t1 - t0)
