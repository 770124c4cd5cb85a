"""Mean number of sequences in a decode tick: the `batch` argument of the
engine's `decode` spans in the window."""


def read(ctx, args):
    sizes = [s["args"]["batch"] for s in ctx.get("spans", ())
             if s["name"] == "decode" and s.get("args")]
    return sum(sizes) / len(sizes) if sizes else None
