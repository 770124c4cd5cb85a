"""Share of the traced stretch in which no operation ran on the device
(averaged over the chips): 1 - busy / window."""


def read(ctx, args):
    tr = ctx.get("trace") or {}
    if not tr.get("window_s"):
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
