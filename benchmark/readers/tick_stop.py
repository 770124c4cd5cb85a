"""Milliseconds a running stream stands still when somebody is admitted:
the nanoseconds the replica spent in `args.phases` (the leaves of an
admission: `admit`, `prefill`, `sample_sync`, `chunk`) between
`stats_before` and `stats_after`, over the ticks between them that admitted
a request or advanced a chunked prefill (`debug_stats()["tick"]`
`admitting`).  `admit` also holds its few microseconds in the ticks that
admitted nobody.  None where the program does not count those ticks."""


def read(ctx, args):
    before = (ctx.get("stats_before") or {}).get("tick")
    after = (ctx.get("stats_after") or {}).get("tick")
    if not before or not after or "admitting" not in after:
        return None
    ticks = after["admitting"] - before.get("admitting", 0)
    if ticks <= 0:
        return None
    ns = sum(after["ns"][p] - before["ns"].get(p, 0) for p in args["phases"])
    return ns / 1e6 / ticks
