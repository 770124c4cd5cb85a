"""Median duration (ms) of the flight recorder's `request` spans named
`args.name` that began in the window.  None where the program records no
such span."""

from .. import stats


def read(ctx, args):
    return stats.median([s["dur_us"] / 1e3 for s in ctx.get("spans", ())
                         if s["name"] == args["name"]])
