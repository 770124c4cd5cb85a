"""Share of the window's prefills whose attention went through the blocked
kernel (`ops/prefill_attention.py`) and built no scores: the engine's
cumulative `kernel_calls` over `kernel_calls + xla_calls`
(`debug_stats()["prefill"]`) at the window's two ends.  None where the
program counts neither, or no prefill ran."""


def read(ctx, args):
    before = (ctx.get("stats_before") or {}).get("prefill") or {}
    after = (ctx.get("stats_after") or {}).get("prefill") or {}
    if not all(k in s for s in (before, after)
               for k in ("kernel_calls", "xla_calls")):
        return None
    kernel = after["kernel_calls"] - before["kernel_calls"]
    calls = kernel + after["xla_calls"] - before["xla_calls"]
    if calls <= 0:
        return None
    return 100.0 * kernel / calls
