"""Share of the traced stretch the core spent in collective operations
(all-reduce, all-gather, reduce-scatter, all-to-all, collective-permute;
for asynchronous ones their `-start` and `-done` halves): time on the
core's own operation line, so time no compute hides."""


def read(ctx, args):
    tr = ctx.get("trace") or {}
    if not tr.get("window_s"):
        return None
    return 100.0 * tr["collective_s"] / tr["window_s"]
