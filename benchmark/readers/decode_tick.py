"""Device time of one decode step, median, from the trace: the runs of the
engine's most-run program (its steps all trace as `jit__lambda(<id>)`;
the one it runs every tick is the decode step)."""

from .. import stats, trace


def read(ctx, args):
    _, runs = trace.most_run_program(ctx.get("trace") or {}, "jit__lambda")
    return stats.median(runs)
