"""Decode step's share of its roofline: the bytes one step must read (all
matmul weights once, plus the keys and values the batch holds — the
family's `decode_step_bytes`, with the live tokens averaged over the traced
stretch from the client's records) over the chip's published HBM
bandwidth, as a share of the step's measured device time.  Bandwidth is
the bound: decode does 2 FLOP per weight byte per sequence."""

from .. import client, peaks, stats, trace


def read(ctx, args):
    tr = ctx.get("trace") or {}
    _, runs = trace.most_run_program(tr, "jit__lambda")
    if not runs:
        return None
    live = client.live_kv_tokens(ctx, tr["t0"], tr["t1"])
    least_s = ctx["family"].decode_step_bytes(ctx["config"], live) \
        / peaks.peak(ctx["device"]["device_kind"], "hbm_bytes_per_s")
    return 100.0 * least_s / (stats.median(runs) / 1e3)
