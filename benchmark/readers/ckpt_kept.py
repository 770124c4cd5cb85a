"""Share of the checkpoint boundaries the window's admitted prompts passed
that were kept: the engine's `boundaries_kept` over `boundaries_passed`
(`debug_stats()["retention"]`) at the window's two ends.  None where the
program counts none, or no prompt passed a boundary."""


def read(ctx, args):
    before = (ctx.get("stats_before") or {}).get("retention") or {}
    after = (ctx.get("stats_after") or {}).get("retention") or {}
    if not after.get("enabled") or not before.get("enabled"):
        return None
    passed = after["boundaries_passed"] - before["boundaries_passed"]
    if passed <= 0:
        return None
    return 100.0 * (after["boundaries_kept"]
                    - before["boundaries_kept"]) / passed
