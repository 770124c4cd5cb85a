"""The replica's own account of when the chip had nothing to run, over the
window: `debug_stats()["tick"]["empty_ns"]` (`ray_tpu/llm/tick_phases.py`:
of each tick phase's nanoseconds, those in which everything the engine had
sent the device had been read back and nothing sent since), taken at the
window's two ends as `tick_phase` takes `ns`: exact, untraced, the window
the end-to-end metrics come from.  A LOWER bound of the device's idle time
(a program that ended before it was read is not seen); the trace's
`device_idle_pct.*` is the upper one.

`args.phases` (a list of phase names, or "all") less `args.minus`, over
`args.per`:

  "window"     the time between the two snapshots' own stamps `t`, x 100: %
  "tick"       the ticks begun in the window: ms a tick
  "admitting"  those of them that admitted somebody: ms an admission

None where the program keeps no such account (the parent's), or the
window holds nothing of `per`."""

PER = {"tick": "n", "admitting": "admitting", "window": "t"}


def read(ctx, args):
    before = (ctx.get("stats_before") or {}).get("tick") or {}
    after = (ctx.get("stats_after") or {}).get("tick") or {}
    if "empty_ns" not in before or "empty_ns" not in after:
        return None
    key = PER[args["per"]]
    over = after[key] - before[key]
    if over <= 0:
        return None
    grew = {p: ns - before["empty_ns"].get(p, 0)
            for p, ns in after["empty_ns"].items()}
    phases = list(grew) if args["phases"] == "all" else args["phases"]
    ns = sum(grew[p] for p in phases) \
        - sum(grew[p] for p in args.get("minus", ()))
    return 100.0 * ns / over if key == "t" else ns / 1e6 / over
