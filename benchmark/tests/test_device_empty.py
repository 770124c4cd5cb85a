"""The readers of the replica's account of when the chip had nothing to run
(`readers/device_empty.py`, `readers/reply_empty.py`) on made-up runs (CPU,
no runtime): known answers from hand-made snapshots and records, `idle`
left out of a share of starvation, nothing (and no raise) on a program that
keeps no such account, and the seven metric files that name them.

`tests/test_llm_device_empty.py` runs these in tier-1 beside the program's
own.
"""

from __future__ import annotations

import copy
import json
import os

import pytest

from benchmark.readers import device_empty, reply_empty, request_part
from benchmark.run import ROOT, load_cell, read_metrics
from benchmark.tests.test_request_readers import (LEAVES, made_up_ctx,
                                                  metric_file)

__all__ = ["test_device_empty_on_two_snapshots",
           "test_device_empty_reads_nothing_where_nothing_is_kept",
           "test_reply_empty_over_the_bands_own_requests",
           "test_reply_empty_reads_nothing_without_the_account",
           "test_each_empty_metric_has_its_reader_and_its_cells",
           "test_the_empty_metrics_part_the_account"]

STOP = ("admit", "prefill", "sample_sync", "chunk")
DOC = ("device_empty_pct.tok", "empty_admit_ms.tok", "empty_step_ms.tok")
CHAT = ("device_empty_pct.itl", "empty_admit_ms.itl", "empty_step_ms.itl")
ALL = DOC + CHAT + ("reply_empty_ms.tok",)

ARGS = {"pct": {"phases": "all", "minus": ["idle"], "per": "window"},
        "admit": {"phases": list(STOP), "per": "admitting"},
        "step": {"phases": "all", "minus": ["idle", *STOP], "per": "tick"}}


def snapshots():
    """A window of 40 s and 4,000 ticks, 200 of which admitted: the chip
    known empty 1 s in `idle`, 0.6 s in `admit`, 0.2 s in `sample_sync`,
    0.9 s in `emit`, 0.3 s in `prep`, 0.4 s in `dispatch`: 2.4 s of 40."""
    before = {"n": 100, "admitting": 10, "t": 5_000_000_000,
              "ns": dict.fromkeys(LEAVES, 7_000_000),
              "empty_ns": dict.fromkeys(LEAVES, 1_000_000),
              "sent": 900, "seen": 899}
    after = {"n": 4100, "admitting": 210, "t": 45_000_000_000,
             "ns": {p: 7_000_000 + 40_000_000_000 // len(LEAVES)
                    for p in LEAVES},
             "empty_ns": dict(before["empty_ns"], idle=1_001_000_000,
                              admit=601_000_000, sample_sync=201_000_000,
                              emit=901_000_000, prep=301_000_000,
                              dispatch=401_000_000),
             "sent": 9000, "seen": 9000}
    return {"stats_before": {"tick": before}, "stats_after": {"tick": after}}


@pytest.mark.parametrize("which, want", [("pct", 100 * 2.4 / 40),
                                         ("admit", 800.0 / 200),
                                         ("step", 1600.0 / 4000)])
def test_device_empty_on_two_snapshots(which, want):
    ctx = snapshots()
    assert device_empty.read(ctx, ARGS[which]) == pytest.approx(want)
    # `idle` is nothing to run, not starvation: counted only when asked for
    whole = device_empty.read(ctx, {"phases": "all", "per": "window"})
    assert whole == pytest.approx(100 * 3.4 / 40)
    assert device_empty.read(ctx, {"phases": ["idle"], "per": "window"}) \
        == pytest.approx(whole - 100 * 2.4 / 40)
    # `ns` is not read: the host's time moves nothing here
    moved = copy.deepcopy(ctx)
    moved["stats_after"]["tick"]["ns"]["emit"] += 10**10
    assert device_empty.read(moved, ARGS[which]) == pytest.approx(want)


@pytest.mark.parametrize("which", sorted(ARGS))
def test_device_empty_reads_nothing_where_nothing_is_kept(which):
    """The parent's program (no `empty_ns` beside `ns`), one end of the
    window without it, no stats, and a window in which nothing of `per`
    happened: None, and no raise."""
    ctx = snapshots()
    old = {k: {"tick": {key: v["tick"][key]
                        for key in ("n", "admitting", "t", "ns")}}
           for k, v in ctx.items()}
    args = ARGS[which]
    assert device_empty.read(old, args) is None
    assert device_empty.read(dict(ctx, stats_before=old["stats_before"]),
                             args) is None
    for bare in ({}, {"stats_before": None, "stats_after": None},
                 {"stats_before": {}, "stats_after": {"tick": None}}):
        assert device_empty.read(bare, args) is None
    assert device_empty.read(dict(ctx, stats_after=ctx["stats_before"]),
                             args) is None


def with_empty(ctx, every=1):
    """`first_empty` / `rest_empty` for every `every`-th request that has a
    `timing`: a tenth of each leaf's time, `hop` and `chunk` left out as a
    leaf that read 0 is."""
    for i, rec in enumerate(ctx["records"]):
        timing = (rec["finish"] or {}).get("timing")
        if timing and i % every == 0:
            for stretch in ("first", "rest"):
                timing[stretch + "_empty"] = {
                    p: v // 10 for p, v in timing[stretch].items()
                    if p not in ("hop", "chunk")}
    return ctx


@pytest.mark.parametrize("of, band, stretch", [("reply", [40, 60], "rest"),
                                               ("ttft", [80, 95], "first")])
def test_reply_empty_over_the_bands_own_requests(of, band, stretch):
    ctx = with_empty(made_up_ctx(failed=(3, 11), bare=(5, 17, 29)))
    args = {"of": of, "band": band}
    kept = [rec["finish"]["timing"] for _, rec in
            request_part.band_records(ctx, of, band)
            if (rec["finish"] or {}).get("timing")]
    assert 5 <= len(kept) <= 10
    want = [sum(v // 10 for p, v in t[stretch].items()
                if p not in ("hop", "chunk")) / 1e6 for t in kept]
    got = reply_empty.read(ctx, args)
    assert got == pytest.approx(sum(want) / len(want), rel=1e-12)
    # at most the same requests' whole stretch, which `request_part` reads
    assert 0 < got < request_part.read(
        ctx, dict(args, part=list(LEAVES))) / 10 + 1e-9
    # a reply whose chip never waited says so with an empty dict: it counts
    for t in kept[:2]:
        t[stretch + "_empty"] = {}
    assert reply_empty.read(ctx, args) == pytest.approx(
        sum(want[2:]) / len(want), rel=1e-12)


def test_reply_empty_reads_nothing_without_the_account():
    """The parent's `timing` has no such key, an older program sends no
    `timing` at all, a run has no records; and where only some requests of
    the band carry the account, those are averaged."""
    args = {"of": "reply", "band": [40, 60]}
    assert reply_empty.read(made_up_ctx(), args) is None
    assert reply_empty.read(with_empty(made_up_ctx(bare=range(50))),
                            args) is None
    assert reply_empty.read({}, args) is None
    some = with_empty(made_up_ctx(), every=2)
    kept = [rec["finish"]["timing"] for _, rec in
            request_part.band_records(some, "reply", [40, 60])]
    have = [t for t in kept if "rest_empty" in t]
    assert 0 < len(have) < len(kept)
    assert reply_empty.read(some, args) == pytest.approx(sum(
        sum(t["rest_empty"].values()) for t in have) / 1e6 / len(have))


@pytest.mark.parametrize("name", ALL)
def test_each_empty_metric_has_its_reader_and_its_cells(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry, = [m for m in bench["per_layer"] if m["name"] == name]
    how = metric_file(name)
    assert how["name"] == name and how["reader"] == (
        "reply_empty" if name.startswith("reply") else "device_empty")
    assert (entry["source"], entry["better"]) == ("program_counter", "lower")
    assert entry["layer"] == ("device" if name.startswith("device")
                              else "admission, batching, cache")
    assert entry["moves"] == ("itl_tail_ms" if name.endswith(".itl")
                              else "reply_mid_ms")
    # the same cells as the trace's idle share, which it stands beside
    idle, = [m for m in bench["per_layer"] if m["name"]
             == "device_idle_pct" + name[name.rindex("."):]]
    assert entry["workloads"] == idle["workloads"]
    for cell in entry["workloads"]:
        assert name in {m["name"] for m in load_cell(cell)["per_layer"]}
    assert bench["per_layer"][-7:] == [
        m for m in bench["per_layer"] if m["name"] in ALL]


def test_the_empty_metrics_part_the_account():
    """Through the metric files themselves: the admission's and the step's
    leaves part every leaf but `idle`, so the two per-tick numbers rebuild
    the window's share; the reply's band is `reply_mid_ms`'s."""
    ctx = dict(with_empty(made_up_ctx()), **snapshots())
    for names, cell in ((DOC, "serve_doc_reask_moe"), (CHAT, "serve_chat")):
        got = read_metrics([m for m in load_cell(cell)["per_layer"]
                            if m["name"] in ALL], ctx)
        assert set(got) == set(names) | (
            {"reply_empty_ms.tok"} if names is DOC else set())
        pct, admit, step = (got[n]["value"] for n in names)
        assert (pct, admit, step) == pytest.approx((6.0, 4.0, 0.4))
        assert (admit * 200 + step * 4000) / 1e3 / 40 * 100 == \
            pytest.approx(pct)
        a, s = (metric_file(n)["args"] for n in names[1:])
        assert sorted(a["phases"]) == sorted(STOP) and s["phases"] == "all"
        assert sorted(s["minus"]) == sorted(STOP + ("idle",))
        assert metric_file(names[0])["args"]["minus"] == ["idle"]
    assert metric_file("reply_empty_ms.tok")["args"] == {
        "of": "reply", "band": metric_file("reply_mid_ms")["args"]["band"]}
