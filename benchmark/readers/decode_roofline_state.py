"""Decode step's share of its roofline, for a family whose whole cache is
recurrent state: the bytes the family's three-argument `decode_step_bytes`
gives (every matrix once, the head, and the live sequences' state read and
written in its published width) over the chip's published HBM bandwidth, as
a share of the step's measured device time.  Live tokens and sequences come
from the client's records over the traced stretch.  Nothing where the
program reports no retention layers (`debug_stats()["retention"]`)."""

from .. import client, peaks, stats, trace
from .decode_roofline_hybrid import _live_seqs


def read(ctx, args):
    tr = ctx.get("trace") or {}
    _, runs = trace.most_run_program(tr, "jit__lambda")
    counter = (ctx.get("stats_after") or {}).get("retention") or {}
    if not runs or not counter.get("enabled"):
        return None
    live = client.live_kv_tokens(ctx, tr["t0"], tr["t1"])
    seqs = _live_seqs(ctx, tr["t0"], tr["t1"])
    least_s = ctx["family"].decode_step_bytes(ctx["config"], live, seqs) \
        / peaks.peak(ctx["device"]["device_kind"], "hbm_bytes_per_s")
    return 100.0 * least_s / (stats.median(runs) / 1e3)
