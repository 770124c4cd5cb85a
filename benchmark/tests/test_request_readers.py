"""The readers of a request's own account of its time (`readers/
request_part.py`, `readers/tick_stop.py`) on made-up runs (CPU, no runtime):

- the parts of the replica's `timing` and what lies outside it add up to the
  client's value, over the very requests the end-to-end band averaged;
- a failed request keeps its rank and leaves the mean; no `timing`, no value;
- the eleven metrics that read them: their leaves part the tick's fourteen,
  each names a reader that exists and cells that report what it moves.

`tests/test_llm_tick_spans.py` runs these in tier-1 beside the program's own.
"""

from __future__ import annotations

import json
import os
import random

import pytest

from benchmark import client, stats
from benchmark.readers import request_part, tick_stop
from benchmark.run import HERE, ROOT, load_cell, read_metrics

__all__ = ["test_parts_add_up_to_the_bands_own_mean",
           "test_a_failed_request_keeps_its_rank_and_leaves_the_mean",
           "test_no_timing_no_value", "test_tick_stop_on_two_snapshots",
           "test_the_metrics_leaves_part_the_tick",
           "test_each_new_metric_has_its_reader_and_its_cells"]

# llm/tick_phases.py LEAVES, spelt out: the yardstick shares no code with
# the program
LEAVES = ("idle", "turn", "expire", "hop", "admit", "prefill", "sample_sync",
          "chunk", "prep", "dispatch", "wait", "emit", "ahead", "fan_out")
REPLY = ("reply_first_ms.tok", "reply_wait_ms.tok", "reply_stop_ms.tok",
         "reply_host_ms.tok", "reply_outside_ms.tok")
TTFT = ("ttft_admit_host_ms", "ttft_prefill_ms", "ttft_step_ms",
        "ttft_outside_ms.req")
NEW = REPLY + TTFT + ("reply_stops.tok", "admit_stop_ms.itl")
BAND = {"reply": [40, 60], "ttft": [80, 95]}


def made_up_ctx(n=50, failed=(), bare=()):
    """`n` requests due half a second apart in a window of 45 s, each with a
    `timing` drawn at random and client stamps that lie a random stretch
    outside it; `failed` end in an error, `bare` carry no `timing`."""
    rng = random.Random(7)
    records = []
    for i in range(n):
        first = {p: rng.randrange(0, 30_000_000) for p in LEAVES}
        rest = {p: rng.randrange(0, 90_000_000) for p in LEAVES}
        timing = {"request_id": i, "lock_wait_ns": rng.randrange(10_000_000),
                  "first_ns": sum(first.values()),
                  "total_ns": sum(first.values()) + sum(rest.values()),
                  "first": first, "rest": rest, "ticks": 62,
                  "stops": rng.randrange(12), "prompt_tokens": 3000,
                  "cached_tokens": 0, "recomputed": 0}
        due = 100.0 + 0.5 * i
        inside = timing["lock_wait_ns"] / 1e9
        t_first = due + inside + timing["first_ns"] / 1e9 + rng.random() / 100
        t_last = due + inside + timing["total_ns"] / 1e9 + rng.random() / 100
        finish = {"finish_reason": "length", "n_tokens": 3}
        if i not in bare:
            finish["timing"] = timing
        records.append({"due": due, "sent": due, "cut": False, "error": None,
                        "token_times": [t_first, (t_first + t_last) / 2,
                                        t_last], "finish": finish})
        if i in failed:
            records[-1].update(error="OverloadedError()", finish=None)
    return {"records": records, "window": [100.0, 145.0], "cut": 150.0}


def client_values(ctx, of):
    return client.replies_ms(ctx) if of == "reply" else client.ttfts_ms(ctx)


def read_part(ctx, of, part):
    return request_part.read(ctx, {"of": of, "band": BAND[of], "part": part})


@pytest.mark.parametrize("of, whole", [("reply", "first"),
                                       ("ttft", "lock_wait")])
def test_parts_add_up_to_the_bands_own_mean(of, whole):
    ctx = made_up_ctx()
    parts = [read_part(ctx, of, [leaf]) for leaf in LEAVES]
    assert all(p is not None and p >= 0 for p in parts)
    total = read_part(ctx, of, whole) + sum(parts) \
        + read_part(ctx, of, "outside")
    assert total == pytest.approx(
        stats.band_mean(client_values(ctx, of), *BAND[of]), rel=1e-12)
    assert 0 < read_part(ctx, of, "outside") < 10.0
    assert 0 <= read_part(ctx, of, "stops") <= 11


def test_a_failed_request_keeps_its_rank_and_leaves_the_mean():
    """Six of fifty fail and count 45 s each: ranks 44-49.  The band 80-95
    is ranks 40-47: four failed, and the four slowest of the 44 sound ones,
    which alone are averaged."""
    ctx = made_up_ctx(failed=(3, 11, 19, 27, 35, 43))
    sound = sorted((v, r) for v, r in zip(client_values(ctx, "ttft"),
                                          ctx["records"]) if v < 45_000.0)
    assert len(sound) == 44
    want = [r["finish"]["timing"]["first"]["prefill"] / 1e6
            for _, r in sound[40:]]
    assert read_part(ctx, "ttft", ["prefill"]) == pytest.approx(
        sum(want) / 4, rel=1e-12)
    kept = request_part.band_records(ctx, "ttft", BAND["ttft"])
    assert [v for v, _ in kept[4:]] == [45_000.0] * 4
    # the end-to-end band over the same ranks carries the failed ones
    assert stats.band_mean(client_values(ctx, "ttft"), 80, 95) > 22_500.0


def test_no_timing_no_value():
    """The parent's program sends no `timing`: every part reads None, and a
    band in which only some requests carry one averages those."""
    ctx = made_up_ctx(bare=range(50))
    for of in BAND:
        for part in ("first", "lock_wait", "stops", "outside", ["wait"]):
            assert read_part(ctx, of, part) is None
    assert request_part.read({}, {"of": "reply", "band": [40, 60],
                                  "part": "first"}) is None
    some = made_up_ctx(bare=range(0, 50, 2))
    assert read_part(some, "reply", ["wait"]) > 0


def test_tick_stop_on_two_snapshots():
    phases = ["admit", "prefill", "sample_sync", "chunk"]
    before = {"n": 100, "admitting": 10, "t": 5,
              "ns": dict.fromkeys(LEAVES, 1_000_000)}
    after = {"n": 3100, "admitting": 260, "t": 9, "ns": dict(
        before["ns"], admit=251_000_000, prefill=501_000_000,
        sample_sync=5_001_000_000, chunk=1_000_000, wait=30_000_000_000)}
    ctx = {"stats_before": {"tick": before}, "stats_after": {"tick": after}}
    args = {"phases": phases}
    assert tick_stop.read(ctx, args) == pytest.approx(5750.0 / 250)
    # a program that does not count the admitting ticks (the parent's), a
    # window without an admission, a run with no stats
    old = {k: {"tick": {"n": v["tick"]["n"], "ns": v["tick"]["ns"]}}
           for k, v in ctx.items()}
    assert tick_stop.read(old, args) is None
    assert tick_stop.read(dict(ctx, stats_after=ctx["stats_before"]),
                          args) is None
    assert tick_stop.read({}, args) is None


def metric_file(name):
    with open(os.path.join(HERE, "metrics", f"{name}.json")) as f:
        return json.load(f)


def test_the_metrics_leaves_part_the_tick():
    """The five parts of a reply add up to `reply_mid_ms` through the metric
    files themselves; the four of a first token and the residual PERF.md
    names (the lock's wait and the loop's leaves) to `ttft_tail_ms`."""
    ctx = made_up_ctx()
    cell = load_cell("serve_doc_reask")
    e2e = read_metrics(cell["end_to_end"], ctx)
    layer = read_metrics([m for m in cell["per_layer"]
                          if m["name"] in REPLY + TTFT], ctx)
    assert set(layer) == set(REPLY + TTFT)
    assert sum(layer[m]["value"] for m in REPLY) == pytest.approx(
        e2e["reply_mid_ms"]["value"], rel=1e-12)
    leaves = [leaf for m in REPLY for leaf in metric_file(m)["args"]["part"]
              if isinstance(metric_file(m)["args"]["part"], list)]
    assert sorted(leaves) == sorted(LEAVES)
    named = [leaf for m in TTFT[:3] for leaf in metric_file(m)["args"]["part"]]
    loop = sorted(set(LEAVES) - set(named))
    assert loop == ["expire", "fan_out", "hop", "idle", "turn"]
    residual = read_part(ctx, "ttft", "lock_wait") \
        + read_part(ctx, "ttft", loop)
    assert sum(layer[m]["value"] for m in TTFT) + residual == pytest.approx(
        e2e["ttft_tail_ms"]["value"], rel=1e-12)
    for m in REPLY + TTFT:
        args = metric_file(m)["args"]
        assert args["band"] == metric_file(
            "reply_mid_ms" if args["of"] == "reply"
            else "ttft_tail_ms")["args"]["band"]


@pytest.mark.parametrize("name", NEW)
def test_each_new_metric_has_its_reader_and_its_cells(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry, = [m for m in bench["per_layer"] if m["name"] == name]
    how = metric_file(name)
    assert how["name"] == name and how["reader"] in ("request_part",
                                                     "tick_stop")
    moved, = [m for m in bench["end_to_end"] if m["name"] == entry["moves"]]
    assert entry["workloads"] and set(entry["workloads"]) <= set(
        moved["workloads"])
    for cell in entry["workloads"]:
        assert name in {m["name"] for m in load_cell(cell)["per_layer"]}
