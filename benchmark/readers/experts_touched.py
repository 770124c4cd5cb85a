"""Distinct held experts a decode step touched, mean per routed layer and
step over the window: the engine's cumulative `touched` counters
(`debug_stats()["routed"]`, one per routed layer) at the window's two ends,
over the decode steps between.  None where the program counts none."""


def read(ctx, args):
    before = (ctx.get("stats_before") or {}).get("routed") or {}
    after = (ctx.get("stats_after") or {}).get("routed") or {}
    if not after.get("enabled") or not before.get("enabled"):
        return None
    steps = after["steps"] - before["steps"]
    if steps <= 0:
        return None
    touched = sum(after["touched"]) - sum(before["touched"])
    return touched / len(after["touched"]) / steps
