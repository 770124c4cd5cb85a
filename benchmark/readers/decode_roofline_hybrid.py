"""Decode step's share of its roofline, for a family whose step reads only
the routed experts its batch touched and moves recurrent state: the bytes
the family's four-argument `decode_step_bytes` gives (weights outside the
routed experts, touched experts x an expert's bytes, live keys and values,
live sequences' state read and written) over the chip's published HBM
bandwidth, as a share of the step's measured device time.  The touched
experts are the program's own count (`debug_stats()["routed"]`, summed over
the routed layers, a mean per decode step of the window); live tokens and
sequences come from the client's records over the traced stretch.  Nothing
where the program has no such counter: all experts or a mean are never
assumed."""

from .. import client, peaks, stats, trace
from . import experts_touched


def read(ctx, args):
    tr = ctx.get("trace") or {}
    _, runs = trace.most_run_program(tr, "jit__lambda")
    per_layer = experts_touched.read(ctx, args)
    if not runs or per_layer is None:
        return None
    layers = len(ctx["stats_after"]["routed"]["touched"])
    live = client.live_kv_tokens(ctx, tr["t0"], tr["t1"])
    seqs = _live_seqs(ctx, tr["t0"], tr["t1"])
    least_s = ctx["family"].decode_step_bytes(
        ctx["config"], live, per_layer * layers, seqs) \
        / peaks.peak(ctx["device"]["device_kind"], "hbm_bytes_per_s")
    return 100.0 * least_s / (stats.median(runs) / 1e3)


def _live_seqs(ctx, t0: float, t1: float, samples: int = 200) -> float:
    """Mean over [t0, t1] of the sequences the decoding batch holds (between
    a request's first token and its end), as `client.live_kv_tokens` counts
    their tokens."""
    total = 0
    for i in range(samples):
        t = t0 + (t1 - t0) * (i + 0.5) / samples
        total += sum(1 for r in ctx["records"] if r["token_times"]
                     and r["token_times"][0] <= t < r.get("end", t1 + 1))
    return total / samples
