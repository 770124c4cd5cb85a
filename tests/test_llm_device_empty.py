"""The engine's own account of when the chip had nothing to run
(llm/tick_phases.py: `sent`, `seen`, `empty_ns`, the annotation
`ray_tpu/device:empty`): booked with the stamps the leaves already take,
never more than a leaf's own time, nothing while a step is queued behind
the one that is read, from a read-back's return to the next send where
nothing is; in `debug_stats()["tick"]` beside `ns` and in every reply's
`timing`.  Tiny engine on the CPU: the RULE is tested here, what the chip
does under it is PERF.md's.  The benchmark's readers of the account have
their cases in `benchmark/tests/test_device_empty.py`, which run here too.
"""

import asyncio
import random
import sys
import threading
import time

import jax
import numpy as np
import pytest

from benchmark.tests.test_device_empty import *            # noqa: F401,F403
from ray_tpu.llm import EngineReplica, LLMEngine, SamplingParams
from ray_tpu.llm import engine as engine_mod
from ray_tpu.llm.serving import _timing
from ray_tpu.llm.tick_phases import LEAVES, TickPhases
from ray_tpu.models import PRESETS

pytestmark = pytest.mark.serving

LATE = 0.03             # seconds a slowed read-back takes
LOOP = ("turn", "expire", "hop", "admit", "fan_out")


def _engine(**kw):
    return LLMEngine(PRESETS["tiny"], max_batch=4, max_len=64, page_size=8,
                     seed=0, **kw)


def _grew(before, after, key="empty_ns"):
    return {p: after[key][p] - before[key][p] for p in LEAVES}


class _SlowReads:
    """numpy as the engine sees it, with a read-back of a device array that
    takes `LATE` seconds, as a chip's would; `reads` counts them."""

    def __init__(self):
        self.reads = 0

    def __getattr__(self, name):
        return getattr(np, name)

    def asarray(self, x, *args, **kwargs):
        if isinstance(x, jax.Array):
            self.reads += 1
            time.sleep(LATE)
        return np.asarray(x, *args, **kwargs)


# ---------------------------------------------------- the rule, alone ----

def test_an_interval_runs_from_a_read_of_everything_to_the_next_send():
    ph = TickPhases()
    ph.to("prep")
    first = ph.sent()
    ph.to("wait")
    second = ph.sent()                  # a step queued behind the first
    ph.seen(first)                      # ... so reading the first opens
    ph.to("emit")                       # nothing
    s = ph.snapshot()
    assert (s["sent"], s["seen"]) == (2, 1) and not any(
        s["empty_ns"].values())
    ph.to("wait")
    ph.seen(second)
    time.sleep(0.002)
    ph.to("emit")
    time.sleep(0.002)
    ph.to("prep")
    time.sleep(0.002)
    ph.sent()
    time.sleep(0.002)
    ph.to("wait")
    s = ph.snapshot()
    assert (s["sent"], s["seen"]) == (3, 2)
    for leaf in ("wait", "emit", "prep"):
        assert 2_000_000 <= s["empty_ns"][leaf] <= s["ns"][leaf], leaf
    # `prep` ran 2 ms more after the send; the leaves after it have nothing
    assert s["ns"]["prep"] - s["empty_ns"]["prep"] >= 2_000_000
    assert sum(s["empty_ns"].values()) == sum(
        s["empty_ns"][leaf] for leaf in ("wait", "emit", "prep"))
    # an older program's read moves nothing back, and opens nothing
    ph.seen(first)
    assert ph.snapshot()["seen"] == 2
    # the open interval is counted up to the snapshot's own stamp
    ph.seen(3)
    time.sleep(0.002)
    a = ph.snapshot()
    time.sleep(0.002)
    b = ph.snapshot()
    assert b["empty_ns"]["wait"] - a["empty_ns"]["wait"] == b["t"] - a["t"] \
        == b["ns"]["wait"] - a["ns"]["wait"]
    assert set(a) == {"n", "admitting", "t", "ns", "empty_ns", "sent",
                      "seen"} and set(a["ns"]) == set(a["empty_ns"])


def test_no_leaf_open_books_nothing():
    """A bare engine between two `step()` calls is in no leaf: the device's
    empty time there is nobody's, as its host time is."""
    ph = TickPhases()
    ph.to("wait")
    ph.seen(ph.sent())
    ph.to(None)
    time.sleep(0.002)
    ph.to("admit")
    ph.sent()
    ph.to(None)
    s = ph.snapshot()
    assert sum(s["empty_ns"].values()) <= sum(s["ns"].values()) < 2_000_000


def test_two_snapshots_differ_consistently_under_a_concurrent_driver():
    """One thread drives leaves, sends and reads as fast as it can; another
    takes snapshots: between any two of them every leaf's empty time grew by
    no more than its time, the leaves' times by exactly the clock's, and
    nothing was seen that was not sent."""
    ph = TickPhases()
    ph.to("turn")
    stop = threading.Event()
    rng = random.Random(3)

    def drive():
        out = []
        while not stop.is_set():
            ph.to(rng.choice(LEAVES))
            roll = rng.random()
            if roll < 0.4:
                out.append(ph.sent())
            elif roll < 0.8 and out:
                ph.seen(out.pop(0) if rng.random() < 0.5 else out.pop())
                if rng.random() < 0.5:
                    out.clear()
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    driver = threading.Thread(target=drive)
    driver.start()
    try:
        snaps, until = [ph.snapshot()], time.time() + 1.0
        while time.time() < until:
            snaps.append(ph.snapshot())
    finally:
        stop.set()
        driver.join(10)
        sys.setswitchinterval(old)
    assert not driver.is_alive() and len(snaps) > 100
    assert snaps[-1]["sent"] > 100 and sum(snaps[-1]["empty_ns"].values()) > 0
    for a, b in zip(snaps, snaps[1:]):
        ns, empty = _grew(a, b, "ns"), _grew(a, b)
        assert sum(ns.values()) == b["t"] - a["t"]
        for leaf in LEAVES:
            assert 0 <= empty[leaf] <= ns[leaf], (leaf, empty, ns)
        assert a["seen"] <= a["sent"] <= b["sent"] and a["seen"] <= b["seen"]


# ------------------------------------------------- a bare engine's step ----

def test_an_admission_starves_the_chip_up_to_its_ticks_send(monkeypatch):
    """No step is out when the first request is admitted: the device is
    known empty from the return of `sample_sync`'s read, through the rest
    of `admit`, `emit` and `prep`, until the decode step's send inside
    `dispatch`; the hand-over of the first token, later in `dispatch`, and
    the blocked `wait` book nothing.  In lockstep every later tick starves
    it from its read-back to its send."""
    slow = _SlowReads()
    monkeypatch.setattr(engine_mod, "np", slow)
    eng = _engine()
    eng.hand_first = lambda events: time.sleep(LATE)
    ph = eng.phases
    eng.add_request([5, 6, 7, 8], SamplingParams(max_tokens=6))
    assert ph.snapshot()["sent"] == 0
    eng.step()
    s = ph.snapshot()
    assert slow.reads == 2 and s["seen"] == s["sent"] > 2
    # nothing was known before the first read returned
    assert s["empty_ns"]["prefill"] == 0
    for leaf in ("sample_sync", "admit", "emit", "prep", "dispatch", "wait"):
        assert 0 < s["empty_ns"][leaf] <= s["ns"][leaf], leaf
    # ... and a blocked read books nothing before it returns
    for leaf in ("sample_sync", "wait", "dispatch"):
        assert s["ns"][leaf] - s["empty_ns"][leaf] >= LATE * 1e9, leaf
    assert {p for p, v in s["empty_ns"].items() if v} == {
        "sample_sync", "admit", "emit", "prep", "dispatch", "wait"}
    while eng.has_unfinished():
        eng.step()
    e = ph.snapshot()
    grew = _grew(s, e)
    assert all(grew[p] > 0 for p in ("admit", "emit", "prep", "dispatch",
                                     "wait"))
    assert grew["sample_sync"] == grew["prefill"] == grew["ahead"] == 0
    reads = slow.reads - 2
    assert reads == 4 and e["ns"]["wait"] - s["ns"]["wait"] \
        - grew["wait"] >= reads * LATE * 1e9


def test_steps_queued_behind_each_other_add_nothing(monkeypatch):
    """With an owner who lets every step leave behind the one before it,
    each read-back finds another step out: from the second call to the one
    that foresees the reply's end, the chip is never known empty."""
    monkeypatch.setattr(engine_mod, "np", _SlowReads())
    eng = _engine()
    eng.hold_ahead = lambda: False
    ph = eng.phases
    eng.add_request([5, 6, 7, 8], SamplingParams(max_tokens=12))
    eng.step()                  # admits, reads its step, sends one ahead
    a = ph.snapshot()
    assert a["sent"] == a["seen"] + 1 and a["empty_ns"]["emit"] > 0
    for _ in range(7):
        eng.step()
    b = ph.snapshot()
    # (the first of the seven read was sent at the end of the first call)
    assert eng.decode_stats()["steps_queued"] == 6
    assert b["sent"] - a["sent"] == b["seen"] - a["seen"] == 7
    assert not any(_grew(a, b).values())
    assert b["ns"]["wait"] - a["ns"]["wait"] >= 7 * LATE * 1e9
    while eng.has_unfinished():
        eng.step()
    # the last steps are read with nothing behind them
    c = ph.snapshot()
    assert c["seen"] == c["sent"] and _grew(b, c)["emit"] > 0


@pytest.mark.parametrize("outside", ["prefill_only", "sample_first",
                                     "trace_logits", "generate"])
def test_engine_work_outside_a_tick_keeps_seen_behind_sent(outside):
    eng = _engine(prefix_cache=True)
    ph = eng.phases
    prompt = list(range(3, 23))
    if outside == "prefill_only":
        eng.prefill_only(prompt)
        eng.prefill_only(prompt + [4, 5])       # a suffix over cached pages
    elif outside == "sample_first":
        logits, _, _ = eng._run_prefill(prompt)
        before = ph.snapshot()
        assert before["seen"] < before["sent"]  # sent, and nobody has read
        eng.sample_first(logits)
    elif outside == "trace_logits":
        eng.trace_logits(prompt, [7, 8])
    else:
        eng.generate([prompt], SamplingParams(max_tokens=4))
    s = ph.snapshot()
    # every path but the check's ends with the sampler's read; the check
    # reads what it likes and claims nothing
    assert s["sent"] > 0 and s["seen"] == (
        0 if outside == "trace_logits" else s["sent"])
    # in no leaf: nothing booked, whatever the device did
    if outside != "generate":
        assert not any(s["ns"].values()) and not any(s["empty_ns"].values())


# ------------------------------------------------------- in a replica ----

def _reply(order):
    """One reply of 40 tokens through a replica whose read-backs take
    `LATE` seconds; snapshots when it has 4 and 36 tokens.  `order`:
    "queued", a step leaves behind the unread one as a replica's does;
    "ahead", only at the end of the call that read the one before it."""
    async def main():
        er = EngineReplica("tiny", max_batch=4, max_len=64, page_size=8,
                           max_tokens=40)
        if order == "ahead":
            er.engine._next_batch_if_queued = dict
        snaps, n, end = [er._phases.snapshot()], 0, None
        async for item in er.stream_generate([4, 5, 6, 7]):
            if isinstance(item, dict):
                end = item
            else:
                n += 1
                if n in (4, 36):
                    snaps.append(er._phases.snapshot())
        await asyncio.sleep(0.05)
        snaps.append((await er.debug_stats())["tick"])
        return snaps, end, er.engine.decode_stats()
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(engine_mod, "np", _SlowReads())
        return asyncio.run(main())


@pytest.fixture(scope="module", params=["queued", "ahead"])
def reply(request):
    snaps, end, decode = _reply(request.param)
    return {"snaps": snaps, "end": end, "decode": decode,
            "order": request.param}


def test_a_step_out_keeps_the_loops_leaves_at_zero(reply):
    """Between a reply's 4th and 36th token every tick finds a step out:
    `turn`, `expire`, `hop`, `admit` and `fan_out` run under a busy chip.
    Queued, so does everything; sent ahead only at the end of a call, the
    chip waits from the read-back through `emit` to that send (`ahead`)."""
    _, a, b, _ = reply["snaps"]
    ns, empty = _grew(a, b, "ns"), _grew(a, b)
    assert b["n"] - a["n"] == 32
    for leaf in LOOP:
        assert ns[leaf] > 0 and empty[leaf] == 0, leaf
    assert ns["wait"] >= 30 * LATE * 1e9 and empty["prep"] == 0
    if reply["order"] == "queued":
        assert not any(empty.values())
        assert reply["decode"]["steps_queued"] >= 32
    else:
        assert reply["decode"]["steps_queued"] == 0
        for leaf in ("wait", "emit", "ahead"):
            assert 0 < empty[leaf] <= ns[leaf], leaf
        assert ns["wait"] - empty["wait"] >= 30 * LATE * 1e9
        assert set(p for p, v in empty.items() if v) == {"wait", "emit",
                                                         "ahead"}


def test_every_snapshot_keeps_empty_inside_its_leaf(reply):
    snaps = reply["snaps"]
    assert snaps[0]["sent"] == 0 and not any(snaps[0]["empty_ns"].values())
    for a, b in zip(snaps, snaps[1:]):
        ns, empty = _grew(a, b, "ns"), _grew(a, b)
        assert all(0 <= empty[p] <= ns[p] for p in LEAVES), (empty, ns)
        assert a["seen"] <= a["sent"] <= b["sent"]
    # the reply is over and read: the loop idles beside an empty chip
    last = snaps[-1]
    assert last["seen"] == last["sent"] and _grew(snaps[-2], last)["idle"] > 0


def test_timing_carries_the_replys_own_empty_time(reply):
    t = reply["end"]["timing"]
    for stretch in ("first", "rest"):
        empty = t[stretch + "_empty"]
        assert set(empty) <= set(LEAVES)
        assert all(type(v) is int and 0 < v <= t[stretch][p]
                   for p, v in empty.items()), (stretch, empty)
    # the prompt was admitted with no step out: the chip waited from the
    # sampler's read to the tick's send, and not before
    assert {"admit", "emit", "prep", "dispatch"} <= set(t["first_empty"])
    assert "prefill" not in t["first_empty"]
    # of the rest, the last ticks alone are read with nothing behind them
    # (queued), or every tick from its read-back to its send (ahead); the
    # reply ends in its last tick's `fan_out`, before the loop turns
    assert not set(t["rest_empty"]) & {"turn", "expire", "admit"}
    ticks_empty = sum(t["rest_empty"].values())
    if reply["order"] == "queued":
        assert ticks_empty < 5 * LATE * 1e9
    else:
        assert t["rest_empty"]["emit"] > 0 and t["rest_empty"]["ahead"] > 0


def test_timing_is_the_snapshots_difference():
    class Req:
        req_id, prompt, prefix_len, recomputed = 9, [1, 2, 3], 0, 0
    rng = random.Random(5)

    def snap(before=None):
        base = before or {"n": 0, "admitting": 0, "t": 0,
                          "ns": dict.fromkeys(LEAVES, 0),
                          "empty_ns": dict.fromkeys(LEAVES, 0)}
        ns = {p: v + rng.randrange(1, 9_000_000)
              for p, v in base["ns"].items()}
        empty = {p: v + (rng.randrange(0, ns[p] - base["ns"][p])
                         if p not in ("hop", "chunk") else 0)
                 for p, v in base["empty_ns"].items()}
        return {"n": base["n"] + 3, "admitting": base["admitting"] + 1,
                "t": base["t"] + sum(ns.values()) - sum(base["ns"].values()),
                "ns": ns, "empty_ns": empty}
    s0 = snap()
    s1 = snap(s0)
    s2 = snap(s1)
    t = _timing(Req, s0["t"] - 5, s0, s1, s2)
    for stretch, (a, b) in (("first", (s0, s1)), ("rest", (s1, s2))):
        want = {p: b["empty_ns"][p] - a["empty_ns"][p] for p in LEAVES}
        assert t[stretch + "_empty"] == {p: v for p, v in want.items() if v}
        assert "hop" not in t[stretch + "_empty"]
        assert all(v <= t[stretch][p]
                   for p, v in t[stretch + "_empty"].items())
        assert t[stretch] == {p: b["ns"][p] - a["ns"][p] for p in LEAVES}
