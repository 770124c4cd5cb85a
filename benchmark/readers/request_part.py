"""One part of the replica's own account of a request's time, over the
requests an end-to-end band averaged.

`args.of` = "reply" (due -> last token, `client.replies_ms`) or "ttft" (due
-> first token, `client.ttfts_ms`); `args.band` = [lo, hi].  The requests due
in the window are ranked by that client value exactly as `stats.band_mean`
ranks them (a failed request counts the window), ranks ceil(n*lo/100) up to
ceil(n*hi/100) are kept, and the mean of `args.part` is taken over those of
them whose terminal dict carries `timing` (`ray_tpu/llm/serving.py`: ns by
tick phase from enqueue to the first token, `first`, and from there to the
end, `rest`):

  [leaf, ...]  those leaves, of `rest` for a reply and of `first` for a
               time to first token, in ms
  "first"      entry into the replica -> first token put on the stream
               (`lock_wait_ns + first_ns`), ms
  "lock_wait"  entry -> enqueued under the replica's lock, ms
  "stops"      ticks between first token and end that admitted somebody
  "outside"    the client's value less the replica's whole account of the
               same stretch (`lock_wait_ns + total_ns`, or `+ first_ns`):
               router, RPC and streaming, request by request, ms

For a reply, "first" + every leaf + "outside" is the client's value; for a
time to first token, "lock_wait" + every leaf + "outside" is.  None where no
request of the band carries a `timing` (a program that sends none)."""

import math

from .. import client


def band_records(ctx, of, band):
    """(client value in ms, record) of the requests `stats.band_mean` keeps."""
    due = client.due_in_window(ctx)
    values = client.replies_ms(ctx) if of == "reply" else client.ttfts_ms(ctx)
    ranked = sorted(zip(values, range(len(due))))
    lo = math.ceil(len(due) * band[0] / 100.0)
    hi = math.ceil(len(due) * band[1] / 100.0)
    return [(value, due[i]) for value, i in ranked[lo:hi]]


def part_of(value, timing, of, part):
    stretch, upto = ("rest", "total_ns") if of == "reply" \
        else ("first", "first_ns")
    if part == "first":
        return (timing["lock_wait_ns"] + timing["first_ns"]) / 1e6
    if part == "lock_wait":
        return timing["lock_wait_ns"] / 1e6
    if part == "stops":
        return timing["stops"]
    if part == "outside":
        return value - (timing["lock_wait_ns"] + timing[upto]) / 1e6
    return sum(timing[stretch][leaf] for leaf in part) / 1e6


def read(ctx, args):
    if "records" not in ctx:
        return None
    parts = [part_of(value, rec["finish"]["timing"], args["of"], args["part"])
             for value, rec in band_records(ctx, args["of"], args["band"])
             if (rec["finish"] or {}).get("timing")]
    return sum(parts) / len(parts) if parts else None
