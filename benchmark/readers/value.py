"""A value the harness took itself: `args.key` of the run's context
(`setup_s`, `ready_s`: host clock from process start)."""


def read(ctx, args):
    return ctx.get(args["key"])
