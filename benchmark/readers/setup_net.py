"""Set-up less the seconds the driver process — which only waits — was
observed not running (`freeze.py`: every watcher tick that woke over 0.1 s
late, between process start and the window's opening).  What is left is the
program's and the harness's own set-up; the difference to `setup_s` is the
machine's."""


def read(ctx, args):
    stalls = ctx.get("stalls")
    if not stalls or ctx.get("setup_s") is None:
        return None
    return ctx["setup_s"] - stalls["late_setup_s"]
