"""Tokens of the global batch x steps finished in the window, over the
window and the chips.  The window closes with the step that crosses
`--seconds`, so every step counted is whole and so is the time."""


def read(ctx, args):
    if "step_ends" not in ctx:
        return None
    t0, t1 = ctx["window"]
    return ctx["tokens_per_step"] * len(ctx["losses"]) / (t1 - t0) \
        / ctx["chips"]
