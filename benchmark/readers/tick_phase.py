"""Host milliseconds a tick spent in some of its phases, mean over the
window: the replica's cumulative per-phase nanoseconds
(`EngineReplica.debug_stats()["tick"]`, taken at the window's two ends, so
exact and with no ring to overflow) summed over `args.phases` (a list of
phase names, or "all"), less `args.minus`, over the ticks begun in the
window.  None where the program counts no phases."""


def read(ctx, args):
    before = (ctx.get("stats_before") or {}).get("tick")
    after = (ctx.get("stats_after") or {}).get("tick")
    if not before or not after or after["n"] <= before["n"]:
        return None
    phases = args["phases"]
    if phases == "all":
        phases = list(after["ns"])
    spent = {p: after["ns"][p] - before["ns"].get(p, 0) for p in after["ns"]}
    ns = sum(spent[p] for p in phases) \
        - sum(spent[p] for p in args.get("minus", ()))
    return ns / 1e6 / (after["n"] - before["n"])
