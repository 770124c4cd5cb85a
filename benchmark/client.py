"""What the client saw, from the per-request records of a serving run
(benchmark/serve_cell.py): which requests count, their times to first
token, their gaps.  All times are the client's clock, in seconds (wall)."""

from __future__ import annotations

from typing import Any, Dict, List

from . import stats


def due_in_window(ctx: Dict[str, Any]) -> List[dict]:
    t0, t1 = ctx["window"]
    return [r for r in ctx["records"] if t0 <= r["due"] < t1]


def is_failed(rec: dict) -> bool:
    """Refused, broken or unanswered: an error, a stream that ended without
    its finish record (unless the harness cut it), or no first token."""
    if rec["error"] is not None or not rec["token_times"]:
        return True
    return rec["finish"] is None and not rec["cut"]


def ttfts_ms(ctx: Dict[str, Any], since: str = "due") -> List[float]:
    """Time to first token of every request due in the window, from when it
    was due (or `sent`); a failed request counts as the window's length."""
    whole = (ctx["window"][1] - ctx["window"][0]) * 1e3
    return [whole if is_failed(r)
            else (r["token_times"][0] - r[since]) * 1e3
            for r in due_in_window(ctx)]


def replies_ms(ctx: Dict[str, Any]) -> List[float]:
    """Time to the whole reply of every request due in the window: from
    when it was due to its last token.  A failed request, and one whose
    reply was not whole when the harness stopped (it was then at least
    `drain_s` old), counts as the window's length: both sort above every
    whole reply, so they weigh on a band only through the ranks."""
    whole = (ctx["window"][1] - ctx["window"][0]) * 1e3
    return [whole if is_failed(r) or r["finish"] is None
            else (r["token_times"][-1] - r["due"]) * 1e3
            for r in due_in_window(ctx)]


def gaps_ms(ctx: Dict[str, Any]) -> List[float]:
    """Every gap between consecutive output tokens of the requests due in
    the window, as far as they arrived before the harness stopped.  No gap
    is filtered: a stream's first two tokens arrive a fraction of a
    millisecond apart today (admission emits one, the same tick's decode
    the next), and those gaps are in here."""
    out: List[float] = []
    for r in due_in_window(ctx):
        out.extend(stats.gaps_ms([t for t in r["token_times"]
                                  if t <= ctx["cut"]]))
    return out


def tokens_in_window(ctx: Dict[str, Any]) -> int:
    t0, t1 = ctx["window"]
    return sum(1 for r in ctx["records"] for t in r["token_times"]
               if t0 <= t < t1)


def wrong_streams(ctx: Dict[str, Any]) -> List[dict]:
    """Streams that ended, but not as asked: forced lengths mean every one
    ends with finish_reason `length` after exactly the tokens asked."""
    return [r for r in ctx["records"] if r["finish"] is not None and (
        r["finish"].get("finish_reason") != "length"
        or r["finish"].get("n_tokens") != r["asked"]
        or len(r["token_times"]) != r["asked"])]


def live_kv_tokens(ctx: Dict[str, Any], t0: float, t1: float,
                   samples: int = 200) -> float:
    """Mean over [t0, t1] of the tokens whose keys and values the decoding
    batch holds: for each request between its first token and its end, its
    prompt plus the tokens it has produced."""
    total = 0.0
    for i in range(samples):
        t = t0 + (t1 - t0) * (i + 0.5) / samples
        for r in ctx["records"]:
            tt = r["token_times"]
            if tt and tt[0] <= t < r.get("end", t1 + 1):
                total += r["prompt_len"] + sum(1 for x in tt if x <= t)
    return total / samples
