"""Decode step's share of its roofline, for a family whose cache is pages of
keys and values AND a recurrent state a sequence: the bytes the family's
three-argument `decode_step_bytes` gives (every matrix once, the table once
as the head, the live tokens' keys and values, the live sequences' state
read and written) over the chip's published HBM bandwidth, as a share of
the step's measured device time.  Live tokens and sequences come from the
client's records over the traced stretch (the trace's own `window_s` from
its start).  Nothing where the program counts
no Mamba layers (`debug_stats()["mamba"]`)."""

from .. import client, peaks, stats, trace
from .decode_roofline_hybrid import _live_seqs


def read(ctx, args):
    tr = ctx.get("trace") or {}
    _, runs = trace.most_run_program(tr, "jit__lambda")
    counter = (ctx.get("stats_after") or {}).get("mamba") or {}
    if not runs or not counter.get("enabled"):
        return None
    # (The traced stretch itself: `t1` is taken after the profiler has
    # written its file, which at this depth takes a minute in which the
    # load has long stopped.)
    t0, t1 = tr["t0"], min(tr["t1"], tr["t0"] + tr["window_s"])
    live = client.live_kv_tokens(ctx, t0, t1)
    seqs = _live_seqs(ctx, t0, t1)
    least_s = ctx["family"].decode_step_bytes(ctx["config"], live, seqs) \
        / peaks.peak(ctx["device"]["device_kind"], "hbm_bytes_per_s")
    return 100.0 * least_s / (stats.median(runs) / 1e3)
