"""Median wait in the engine's admission queue: from a request's enqueue
(start of its `request:admit` span) to the start of its `prefill` span,
matched by the engine's request id."""

from .. import stats


def read(ctx, args):
    enq, pre = {}, {}
    for s in ctx.get("spans", ()):
        if s["name"] == "request:admit":
            enq[bytes(s["task_id"])] = s["start_us"]
        elif s["name"] == "prefill":
            pre.setdefault(bytes(s["task_id"]), s["start_us"])
    waits = [(pre[k] - enq[k]) / 1e3 for k in enq if k in pre]
    return stats.median(waits)
