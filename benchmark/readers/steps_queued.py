"""Share of the window's decode steps that left for the device while the
step before them was still unread: the engine's cumulative `steps_queued`
over `steps` (`debug_stats()["decode"]`) at the window's two ends.  Such a
step's tick has the device's step for its period: the read-back's late
return, the emit loop, the replica's loop and the next dispatch all run
under a busy chip.  None where the program has no such counter (a program
that reads every step before it sends the next), or no step ran."""


def read(ctx, args):
    before = (ctx.get("stats_before") or {}).get("decode") or {}
    after = (ctx.get("stats_after") or {}).get("decode") or {}
    if not all(k in s for s in (before, after)
               for k in ("steps_queued", "steps")):
        return None
    steps = after["steps"] - before["steps"]
    if steps <= 0:
        return None
    return 100.0 * (after["steps_queued"] - before["steps_queued"]) / steps
