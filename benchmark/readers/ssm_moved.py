"""MiB of recurrent state one decode step's PROGRAM read and wrote: the
engine's cumulative `rows_moved` (`debug_stats()["mamba"]`: the slots whose
state a step's program moves, the live ones where its step kernel runs,
every slot of the engine where it does not) over the window's decode steps,
times `row_bytes` (one sequence's state over all the Mamba layers as the
program holds it), times 2 (read and written).  Beside `ssm_step_mib`, what
a step MUST move: equal where no slot is moved for nothing.  None where the
program counts no such rows (a program before the counter, or one with no
Mamba layers)."""


def read(ctx, args):
    before = (ctx.get("stats_before") or {}).get("mamba") or {}
    after = (ctx.get("stats_after") or {}).get("mamba") or {}
    if "rows_moved" not in after or "rows_moved" not in before:
        return None
    steps = after["steps"] - before["steps"]
    if steps <= 0:
        return None
    rows = after["rows_moved"] - before["rows_moved"]
    return rows / steps * after["row_bytes"] * 2 / 2 ** 20
