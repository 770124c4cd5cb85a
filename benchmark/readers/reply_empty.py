"""Milliseconds of a request's own time in which the chip had nothing to
run, over the requests an end-to-end band averaged (`request_part.
band_records`: the same ranks `stats.band_mean` keeps): the sum over the
tick's phases of `timing["rest_empty"]` for a reply (`args.of` = "reply":
first token -> end) or of `timing["first_empty"]` for a time to first token
("ttft": enqueue -> first token), which `ray_tpu/llm/serving.py` takes from
the replica's `empty_ns` at the request's own three snapshots.  It is the
most any host-side change could give that stretch: host work the chip did
not wait for is in `reply_host_ms.tok` and not here.  The mean over those
of the band that carry the account; None where none does (a program that
sends no `timing`, or the parent's, whose `timing` has no such key)."""

from . import request_part


def read(ctx, args):
    if "records" not in ctx:
        return None
    key = "rest_empty" if args["of"] == "reply" else "first_empty"
    timings = [(rec["finish"] or {}).get("timing") or {} for _, rec in
               request_part.band_records(ctx, args["of"], args["band"])]
    parts = [sum(t[key].values()) / 1e6 for t in timings if key in t]
    return sum(parts) / len(parts) if parts else None
