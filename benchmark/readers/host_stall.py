"""Seconds in which the driver process — which only waits — was not run,
from process start to the close of the window (`freeze.py`).  Nearly all
of it is the stop while the TPU client starts, which is set-up."""


def read(ctx, args):
    stalls = ctx.get("stalls")
    return stalls["setup_s"] + stalls["window_s"] if stalls else None
