"""The serving tick measured from inside (llm/tick_phases.py): the phases
EngineReplica's decode loop and LLMEngine.step() stamp tile every tick,
as flight-recorder spans and as cumulative counters taken at the same
stamps; a request's spans share its id, a tick's spans its number; the
spans the benchmark's readers already depend on keep their names,
arguments and extents; every reply carries its own account of its time
(`timing`), parted exactly by those counters.  Tiny engine on the CPU.
The benchmark's readers of that account have their own cases in
`benchmark/tests/test_request_readers.py`, which run here too.
"""

import asyncio
import importlib
import os
import subprocess
import sys
import time
from collections import defaultdict

import pytest

from benchmark.tests.test_request_readers import *         # noqa: F401,F403
from benchmark.tests.test_steps_queued import *            # noqa: F401,F403
from ray_tpu._private import flight_recorder
from ray_tpu.llm import EngineReplica
from ray_tpu.llm.tick_phases import LEAVES, STOP, _SPAN

pytestmark = pytest.mark.serving

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# Recorded span -> the leaf it is a piece of; `step:admit` and
# `step:chunk` hold their leaves (`admit`, `chunk`) as self time.
LEAF_OF = {**{span: leaf for leaf, span in _SPAN.items()},
           "prefill": "prefill", "sample_sync": "sample_sync"}
PARENTS = {"tick": ("tick:expire", "tick:hop", "step:admit", "step:chunk",
                    "step:emit", "decode", "step:ahead", "tick:fan_out"),
           "step:admit": ("prefill", "sample_sync"),
           "step:chunk": ("prefill", "sample_sync"),
           "decode": ("decode:prep", "decode:dispatch", "decode:wait")}


class _Raw(flight_recorder.FlightRecorder):
    """A recorder the runtime's telemetry flush cannot drain, which also
    keeps every record's own nanosecond stamps."""

    def __init__(self, **kw):
        super().__init__(capacity=1 << 16, **kw)
        self.raw = []

    def _push(self, rec):
        self.raw.append(rec)
        super()._push(rec)

    def drain(self, node_id=b"", worker_id=b""):
        return []

    def rows(self):
        return flight_recorder.FlightRecorder.drain(self)


LONG = [11, 12, 13]         # wave 3's request that decodes through two
INSIDE = [[12, 13, 14], list(range(50, 70))]    # ... later admissions


def _serve(rec, *, prefill_chunk=None):
    """Three waves of requests through one replica, idle in between;
    returns the replica's stats before, after the first wave and at the
    end, and what each request streamed with the wall times of its send
    and first token.  The third wave is one long reply and two requests
    sent while it decodes: each when the one before has its first token."""
    old = flight_recorder._recorder
    flight_recorder._recorder = rec
    base = list(range(1, 13))

    async def main():
        er = EngineReplica("tiny", max_batch=4, max_len=64, page_size=8,
                           max_tokens=5, prefill_chunk=prefill_chunk)
        stats = [await er.debug_stats()]
        records = []

        async def one(prompt, opts=None, then=()):
            r = {"due": time.time(), "sent": time.time(), "token_times": [],
                 "finish": None, "error": None, "cut": False,
                 "prompt": prompt}
            records.append(r)
            after = None
            async for item in er.stream_generate(prompt, opts):
                if isinstance(item, dict):
                    r["finish"] = item
                else:
                    r["token_times"].append(time.time())
                    if then and after is None:
                        after = asyncio.ensure_future(
                            one(then[0], then=then[1:]))
            if after is not None:
                await after

        # buckets: 8 (three prompts), 16, 32; then a prefix-cache hit whose
        # suffix is the first run of its bucket
        wave1 = [[7, 8, 9], [3, 4, 5, 6], [5, 6, 7, 8, 9], base,
                 list(range(20, 45)), [1, 2, 3]]
        wave2 = [base[:8] + [40, 41, 42], [9, 9, 9]]
        for wave in (wave1, wave2):
            await asyncio.gather(*[one(p) for p in wave])
            await asyncio.sleep(0.05)           # the loop goes idle
            stats.append(await er.debug_stats())
        await one(LONG, {"max_tokens": 40}, then=INSIDE)
        await asyncio.sleep(0.05)
        stats[-1] = await er.debug_stats()
        return stats, records

    try:
        stats, records = asyncio.run(main())
    finally:
        flight_recorder._recorder = old
    return {"stats": stats, "records": records}


@pytest.fixture(scope="module", params=["plain", "chunked"])
def run(request):
    rec = _Raw()
    out = _serve(rec, prefill_chunk=8 if request.param == "chunked"
                 else None)
    out["raw"] = [r for r in rec.raw if r[2] == "request"]
    out["rows"] = [r for r in rec.rows() if r["cat"] == "request"]
    out["kind"] = request.param
    return out


def _spans(run, *names):
    """(t0, t1, name, id, args) of the recorded spans with these names."""
    return sorted((t0, t1, name, rid, args or {})
                  for t0, t1, _, name, rid, args in run["raw"]
                  if name in names)


def _leaf_pieces(run):
    """Every leaf piece as (t0, t1, leaf): the recorded leaf spans, plus
    what `step:admit` / `step:chunk` leave between their children."""
    pieces = [(t0, t1, LEAF_OF[name])
              for t0, t1, name, _, _ in _spans(run, *LEAF_OF)]
    for parent, leaf in (("step:admit", "admit"), ("step:chunk", "chunk")):
        for p0, p1, _, _, _ in _spans(run, parent):
            at = p0
            for c0, c1, _, _, _ in _spans(run, *PARENTS[parent]):
                if p0 <= c0 and c1 <= p1:
                    pieces.append((at, c0, leaf))
                    at = c1
            pieces.append((at, p1, leaf))
    return sorted(p for p in pieces if p[1] > p[0] or p[2] not in
                  ("admit", "chunk"))


# ------------------------------------------------------------- tiling ----

def test_leaves_tile_the_run_without_gap_or_overlap(run):
    pieces = _leaf_pieces(run)
    assert {p[2] for p in pieces} >= set(LEAVES) - {"chunk"}
    assert ("chunk" in {p[2] for p in pieces}) == (run["kind"] == "chunked")
    for (_, end, a), (start, _, b) in zip(pieces, pieces[1:]):
        assert start == end, f"{a} ends at {end}, {b} starts at {start}"


def test_leaf_durations_add_up_to_tick_plus_turn(run):
    ticks = {a["n"]: t1 - t0 for t0, t1, _, _, a in _spans(run, "tick")}
    turns = defaultdict(int)
    for t0, t1, _, _, a in _spans(run, "tick:turn"):
        turns[a["n"]] += t1 - t0
    whole = sum(ticks.values()) + sum(turns.values())
    leaves = sum(t1 - t0 for t0, t1, leaf in _leaf_pieces(run)
                 if leaf != "idle")
    assert len(ticks) >= 5 and abs(leaves - whole) <= 0.02 * whole
    assert set(turns) - set(ticks) <= {max(ticks) + 1}
    assert _spans(run, "tick:idle"), "the loop never went idle"


@pytest.mark.parametrize("parent", sorted(PARENTS))
def test_children_lie_inside_their_parents(run, parent):
    """Every child span lies inside exactly one span of its parent, and
    shares its tick number."""
    parents = _spans(run, parent)
    if parent == "step:chunk" and run["kind"] == "plain":
        assert not parents
        return
    assert parents
    # `prefill` and `sample_sync` lie in `step:admit` or in `step:chunk`
    holders = _spans(run, "step:admit", "step:chunk") \
        if parent.startswith("step:") else parents
    for c0, c1, name, _, args in _spans(run, *PARENTS[parent]):
        inside = [a for p0, p1, _, _, a in holders if p0 <= c0 and c1 <= p1]
        assert len(inside) == 1, (name, c0, c1)
        if "n" in args:
            assert args["n"] == inside[0]["n"], (name, args, inside)
    if parent == "decode":          # its children tile it exactly
        for p0, p1, _, _, a in parents:
            kids = [(c0, c1) for c0, c1, _, _, ca in _spans(
                run, *PARENTS["decode"]) if ca["n"] == a["n"]]
            assert kids[0][0] == p0 and kids[-1][1] == p1 and all(
                x[1] == y[0] for x, y in zip(kids, kids[1:]))


# ----------------------------------------------------------- counters ----

def test_counters_are_monotone_and_equal_the_spans(run):
    first, mid, last = (s["tick"] for s in run["stats"])
    assert first["n"] == 0 and set(last["ns"]) == set(LEAVES)
    assert 0 < mid["n"] < last["n"] == len(_spans(run, "tick"))
    for leaf in LEAVES:
        assert first["ns"][leaf] <= mid["ns"][leaf] <= last["ns"][leaf]
    spent = defaultdict(int)
    for t0, t1, leaf in _leaf_pieces(run):
        spent[leaf] += t1 - t0
    # the stats were read while the loop was idle: the open `idle` is in
    # the counters and not yet a span; every other leaf agrees to the ns
    for leaf in set(LEAVES) - {"idle"}:
        assert last["ns"][leaf] - first["ns"][leaf] == spent[leaf], leaf
    assert last["ns"]["idle"] >= spent["idle"] > 0


def test_recorder_off_counts_all_the_same():
    rec = _Raw(enabled=False)
    out = _serve(rec)
    assert rec.raw == [] and rec.rows() == []
    tick = out["stats"][-1]["tick"]
    assert tick["n"] >= 5
    assert all(tick["ns"][leaf] > 0 for leaf in set(LEAVES) - {"chunk"})


# ----------------------------------------------------- ids and numbers ----

def test_a_requests_spans_share_its_id_and_prefill_names_its_tick(run):
    by_id = defaultdict(dict)
    for t0, t1, name, rid, args in _spans(
            run, "request:lock_wait", "request:admit", "prefill"):
        assert len(rid) == 8, name
        by_id[rid].setdefault(name, []).append((t0, t1, args))
    assert len(by_id) == len(run["records"]) == 11
    fan_outs = {a["n"]: t1
                for _, t1, _, _, a in _spans(run, "tick:fan_out")}
    holders = _spans(run, "step:admit", "step:chunk")
    for rid, spans in by_id.items():
        (w0, w1, wa), = spans["request:lock_wait"]
        (a0, a1, aa), = spans["request:admit"]
        assert set(wa) == {"queued"} and set(aa) == {"queued", "decoding"}
        # enqueue follows the lock's wait at once; the span ends where the
        # first token is put on its stream: in the tick that sampled it, from
        # the sampling on (before that tick's decode step is read:
        # `test_a_first_token_leaves_before_its_ticks_decode_step_is_read`)
        assert w1 <= a0 <= w1 + 1_000_000
        last = max(spans["prefill"])
        n = last[2]["n"]
        assert all(p[2]["n"] <= n for p in spans["prefill"])
        holder, = [h for h in holders if h[4]["n"] == n
                   and h[0] <= last[0] and last[1] <= h[1]]
        assert holder[1] <= a1 <= fan_outs[n]


def test_new_program_marks_each_buckets_first_prefill(run):
    seen, flagged = set(), []
    for _, _, _, _, a in _spans(run, "prefill"):
        done = a["tokens"] - a["cached_tokens"]
        bucket = 8
        while bucket < done:
            bucket *= 2
        key = (a["cached_tokens"] > 0, bucket)
        assert a["new_program"] == (key not in seen), (a, seen)
        flagged.append(a["new_program"])
        seen.add(key)
    # chunks of 8 only ever meet the bucket of 8, whole or as a suffix
    assert sum(flagged) == len(seen) >= (2 if run["kind"] == "chunked"
                                         else 4) and 0 in flagged


# ------------------------------------------ a request's own account ----

def _wave(run, wave):
    return [run["records"][:6], run["records"][6:8],
            run["records"][8:]][wave]


def _admitting_ticks(run):
    """Tick number -> the start of its `step:admit`, for the ticks that
    gave a request a slot or advanced a chunked prefill."""
    chunked = {a["n"] for _, _, _, _, a in _spans(run, "step:chunk")}
    return {a["n"]: t0 for t0, _, _, _, a in _spans(run, "step:admit")
            if a["admitted"] > 0 or a["n"] in chunked}


def _check_timing(rec, n_tokens, shipped=False):
    """The terminal dict as it was, and in it a `timing` whose leaves part
    its two stretches to the nanosecond."""
    end = rec["finish"]
    assert end["finish_reason"] == "length" and end["n_tokens"] == n_tokens
    assert set(end) == {"finish_reason", "n_tokens", "timing"}
    t = end["timing"]
    assert set(t) == {
        "request_id", "lock_wait_ns", "first_ns", "total_ns", "first",
        "rest", "first_empty", "rest_empty", "ticks", "stops",
        "prompt_tokens", "cached_tokens", "recomputed"}
    assert tuple(t["first"]) == tuple(t["rest"]) == LEAVES
    ints = [t["lock_wait_ns"], t["first_ns"], t["total_ns"], t["ticks"],
            t["stops"], *t["first"].values(), *t["rest"].values()]
    assert all(type(x) is int and x >= 0 for x in ints), t
    assert sum(t["first"].values()) == t["first_ns"] > 0
    assert sum(t["rest"].values()) == t["total_ns"] - t["first_ns"]
    assert t["prompt_tokens"] == len(rec["prompt"])
    # a tick a token, but for the first two: the tick that admits a request
    # also runs its first decode step (unless a step was out, for the
    # others, before it came); the first token leaves before that step is
    # read, so the step's `wait` lies in `rest`
    assert t["ticks"] in (n_tokens - 2, n_tokens - 1)
    assert t["rest"]["wait"] > 0
    assert t["first"]["prefill"] > 0
    # a shipped prefill is installed (still the leaf `prefill`) and brings
    # its first token with it
    assert (t["first"]["sample_sync"] == 0) == shipped
    return t


@pytest.mark.parametrize("wave", [0, 1, 2])
def test_timing_parts_each_reply_exactly(run, wave):
    for rec in _wave(run, wave):
        t = _check_timing(rec, 40 if rec["prompt"] == LONG else 5)
        # the server's account lies inside what the client's clock saw
        seen = rec["token_times"][-1] - rec["sent"]
        assert t["lock_wait_ns"] + t["total_ns"] <= (seen + 0.05) * 1e9
    hit = _wave(run, 1)[0]["finish"]["timing"]
    assert hit["cached_tokens"] == 8 and hit["recomputed"] == 0
    assert len({r["finish"]["timing"]["request_id"]
                for r in run["records"]}) == 11


def test_request_reply_shares_the_id_and_the_stamps(run):
    spans = {name: {rid: (t0, t1, args) for t0, t1, _, rid, args
                    in _spans(run, name)}
             for name in ("request:lock_wait", "request:admit",
                          "request:reply")}
    assert all(len(by_id) == 11 for by_id in spans.values())
    for rec in run["records"]:
        t = rec["finish"]["timing"]
        rid = t["request_id"].to_bytes(8, "little")
        w0, w1, _ = spans["request:lock_wait"][rid]
        a0, a1, _ = spans["request:admit"][rid]
        r0, r1, args = spans["request:reply"][rid]
        # S0 closes the lock's wait and opens both other spans; S1 closes
        # `request:admit`; S2 closes the reply
        assert w1 == a0 == r0 and w1 - w0 == t["lock_wait_ns"]
        assert a1 - a0 == t["first_ns"] and r1 - r0 == t["total_ns"]
        assert args == {
            "first_us": t["first_ns"] // 1000,
            "wait_us": t["rest"]["wait"] // 1000,
            "stop_us": sum(t["rest"][p] for p in STOP) // 1000,
            "ticks": t["ticks"], "stops": t["stops"]}


def test_stops_are_the_admitting_ticks_inside_the_reply(run):
    admitting = _admitting_ticks(run)
    s1 = {rid: t1 for _, t1, _, rid, _ in _spans(run, "request:admit")}
    s2 = {rid: t1 for _, t1, _, rid, _ in _spans(run, "request:reply")}
    for rec in run["records"]:
        t = rec["finish"]["timing"]
        rid = t["request_id"].to_bytes(8, "little")
        inside = [n for n, at in admitting.items() if s1[rid] < at <= s2[rid]]
        assert t["stops"] == len(inside), (rec["prompt"], inside)
        if not inside:
            assert sum(t["rest"][p] for p in STOP) < t["rest"]["wait"]
    long = next(r for r in run["records"] if r["prompt"] == LONG)
    # two admissions inside it; chunked, the 20-token prompt takes three
    # ticks, the first of them the one that gave it its slot
    assert long["finish"]["timing"]["stops"] == (
        4 if run["kind"] == "chunked" else 2)
    assert long["finish"]["timing"]["rest"]["prefill"] > 0


def test_admitting_counts_the_ticks_that_admitted(run):
    first, mid, last = (s["tick"] for s in run["stats"])
    assert first["admitting"] == 0 < mid["admitting"] < last["admitting"]
    assert last["admitting"] == len(_admitting_ticks(run)) < last["n"]
    chunks = {a["n"] for _, _, _, _, a in _spans(run, "step:chunk")}
    plain = {a["n"] for _, _, _, _, a in _spans(run, "step:admit")
             if a["admitted"] > 0}
    assert bool(chunks - plain) == (run["kind"] == "chunked")
    # a snapshot says up to when it counted: its leaves sum to the time
    # since the replica's loop began
    assert last["t"] - mid["t"] == sum(last["ns"].values()) \
        - sum(mid["ns"].values())


METHODS = ("stream_generate", "generate", "collect_stream", "collect")


@pytest.fixture(scope="module", params=["recorder_on", "recorder_off"])
def ends(request):
    """One reply through each public way to a whole reply, then a stream
    its consumer abandons and a request whose deadline passes mid-decode;
    the replica serves on after both."""
    from ray_tpu._private import deadlines
    from ray_tpu.exceptions import DeadlineExceededError
    rec = _Raw(enabled=request.param == "recorder_on")
    old = flight_recorder._recorder
    flight_recorder._recorder = rec
    prompt = [4, 5, 6, 7]

    async def main():
        er = EngineReplica("tiny", max_batch=4, max_len=512, page_size=8,
                           max_tokens=5)
        out = {}

        async def streamed(gen):
            items = [x async for x in gen]
            return dict(items[-1], tokens=items[:-1])

        async def handed_off():
            blob, first = await er.prefill(prompt)
            return await er.admit_external(
                {"blob": blob, "first": first, "prompt": prompt})

        out["stream_generate"] = await streamed(er.stream_generate(prompt))
        out["generate"] = await er.generate(prompt)
        out["collect_stream"] = await streamed(
            er.collect_stream(await handed_off()))
        out["collect"] = await er.collect(await handed_off())

        gen = er.stream_generate([9, 8, 7], {"max_tokens": 400})
        out["abandoned"] = [await gen.__anext__() for _ in range(2)]
        await gen.aclose()
        token = deadlines.set_current(time.time() + 0.15)
        try:
            with pytest.raises(DeadlineExceededError, match="mid-decode"):
                await er.generate([6, 6, 6], {"max_tokens": 480})
        finally:
            deadlines.reset(token)
        out["after"] = await er.generate(prompt)
        out["stats"] = await er.debug_stats()
        return out

    try:
        out = asyncio.run(main())
    finally:
        flight_recorder._recorder = old
    out.update(prompt=prompt, raw=rec.raw, on=rec.enabled)
    return out


@pytest.mark.parametrize("method", METHODS + ("after",))
def test_every_whole_reply_carries_its_timing(ends, method):
    """... in the terminal dict of a stream and beside the tokens of a
    collected reply, whether or not the recorder is on."""
    reply = dict(ends[method])
    assert len(reply.pop("tokens")) == 5
    t = _check_timing({"finish": reply, "prompt": ends["prompt"]}, 5,
                      shipped=method.startswith("collect"))
    assert t["stops"] == 0 and t["recomputed"] == 0


def test_no_timing_and_no_span_for_a_reply_that_did_not_finish(ends):
    st = ends["stats"]
    assert len(ends["abandoned"]) == 2
    assert (st["cancelled"], st["expired"], st["completed"]) == (1, 1, 5)
    # both gave their pages back (cached pages count as free)
    assert st["active"] == 0 and st["kv_pages_free"] == st["kv_pages_total"]
    replies = [r for r in ends["raw"] if r[3] == "request:reply"]
    assert len(replies) == (5 if ends["on"] else 0)
    assert bool(ends["raw"]) == ends["on"]
    if ends["on"]:
        admits = {r[4] for r in ends["raw"] if r[3] == "request:admit"}
        assert len(admits) == 7 and {r[4] for r in replies} < admits


# ------------------------------------- a first token leaves when sampled ----

class _Late:
    """A decode step's result whose read-back takes 30 ms, as a chip's
    step would: `np.asarray` on it is the tick's `decode:wait`."""

    def __init__(self, x):
        self.x = x

    def __array__(self, *args, **kwargs):
        import numpy as np
        time.sleep(0.03)
        return np.asarray(self.x)


@pytest.fixture(scope="module")
def early():
    """One reply, and a second sent when the first has three tokens, through
    a replica whose decode steps take 30 ms to read back."""
    rec = _Raw()
    old = flight_recorder._recorder
    flight_recorder._recorder = rec

    async def main():
        er = EngineReplica("tiny", max_batch=4, max_len=64, page_size=8,
                           max_tokens=6)
        step = er.engine._decode_jit

        def late(*args):
            *state, nxt = step(*args)
            return (*state, _Late(nxt))
        er.engine._decode_jit = late
        records = []

        async def one(prompt, then=None):
            r = {"prompt": prompt, "tokens": [], "finish": None}
            records.append(r)
            after = None
            async for item in er.stream_generate(prompt):
                if isinstance(item, dict):
                    r["finish"] = item
                else:
                    r["tokens"].append(item)
                    if then and len(r["tokens"]) == 3:
                        after = asyncio.ensure_future(one(then))
            if after is not None:
                await after
        await one([4, 5, 6, 7], then=[9, 8, 7])
        return records, await er.debug_stats()

    try:
        records, stats = asyncio.run(main())
    finally:
        flight_recorder._recorder = old
    return {"records": records, "stats": stats,
            "raw": [r for r in rec.raw if r[2] == "request"]}


def test_a_first_token_leaves_before_its_ticks_decode_step_is_read(early):
    """`request:admit` ends, and the first token is on its stream, while the
    tick that sampled it still waits for its decode step: after that tick's
    `sample_sync` and a step's read-back before its `decode:wait` ends."""
    waits = {a["n"]: (t0, t1)
             for t0, t1, _, _, a in _spans(early, "decode:wait")}
    ticks = _spans(early, "tick")
    samples = [t1 for _, t1, _, _, _ in _spans(early, "sample_sync")]
    admits = _spans(early, "request:admit")
    assert len(admits) == 2
    for _, a1, _, _, _ in admits:
        (t0, _, _, _, args), = [t for t in ticks if t[0] <= a1 <= t[1]]
        sampled, = [s for s in samples if t0 <= s <= a1]
        w0, w1 = waits[args["n"]]
        assert w1 - w0 >= 25_000_000
        assert sampled <= a1 < w1 - 20_000_000, (a1 - sampled, w1 - a1)


def test_an_early_first_token_is_handed_over_once_and_timing_adds_up(early):
    from ray_tpu.llm import LLMEngine, SamplingParams
    from ray_tpu.models import PRESETS
    ref = LLMEngine(PRESETS["tiny"], max_batch=4, max_len=64, page_size=8,
                    seed=0)
    for rec in early["records"]:
        assert rec["tokens"] == ref.generate(
            [rec["prompt"]], SamplingParams(max_tokens=6))[0]
        t = _check_timing(rec, 6)
        # its first token left before the admitting tick's step was read:
        # nearly all of that step's 30 ms lies in the rest of the reply
        assert t["first"]["wait"] < 10_000_000
        assert t["rest"]["wait"] >= (t["ticks"] + 1) * 25_000_000
    assert early["stats"]["decode"]["steps_queued"] > 0


# ------------------------------------------- what the readers depend on ----

def test_old_spans_keep_names_arguments_and_extents(run):
    rows = {name: [r for r in run["rows"] if r["name"] == name]
            for name in ("request:admit", "prefill", "sample_sync",
                         "decode")}
    assert all(rows.values())
    for r in rows["prefill"]:
        assert {"tokens", "cached_tokens", "active"} <= set(r["args"])
        assert len(bytes(r["task_id"])) == 8
    assert all(set(r["args"]) == {"batch"} for r in rows["sample_sync"])
    assert all(set(r["args"]) == {"batch", "n", "pages", "synced", "queued"}
               for r in rows["decode"])
    # `synced` = the slot rows the host wrote into the step's resident state
    # before it: some steps carry an admission or a retirement, most none
    synced = [r["args"]["synced"] for r in rows["decode"]]
    assert 0 < sum(s > 0 for s in synced) < len(synced) / 2
    # `decode` still runs from where the key was split to after the read-back
    # (now: from its first child's start to its last child's end), and a
    # `sample_sync` still follows the wave's last prefill in its tick
    for t0, t1, _, _, a in _spans(run, "decode"):
        kids = [s for s in _spans(run, *PARENTS["decode"])
                if s[4]["n"] == a["n"]]
        assert [k[2] for k in kids] == ["decode:prep", "decode:dispatch",
                                        "decode:wait"]
        assert (kids[0][0], kids[-1][1]) == (t0, t1)
    for s0, _, _, _, _ in _spans(run, "sample_sync"):
        before = [p for p in _spans(run, "prefill") if p[1] <= s0]
        assert before and s0 - before[-1][1] < 5_000_000


@pytest.mark.parametrize("reader", ["queue_wait", "decode_batch",
                                    "prefill_rate", "ttft_outside",
                                    "span_median", "tick_phase",
                                    "device_empty", "reply_empty"])
def test_benchmark_readers_read_these_spans_and_counters(run, reader):
    sys.path.insert(0, ROOT)
    try:
        read = importlib.import_module(f"benchmark.readers.{reader}").read
    finally:
        sys.path.remove(ROOT)
    ctx = {"spans": run["rows"], "records": run["records"],
           "window": [run["records"][0]["due"] - 1.0, time.time()],
           "stats_before": run["stats"][0], "stats_after": run["stats"][-1]}
    args = {"span_median": {"name": "request:lock_wait"},
            "tick_phase": {"phases": "all",
                           "minus": ["idle", "wait", "sample_sync"]},
            "device_empty": {"phases": "all", "minus": ["idle"],
                             "per": "tick"},
            "reply_empty": {"of": "reply", "band": [40, 60]}
            }.get(reader, {})
    value = read(ctx, args)
    assert value is not None and value >= 0
    if reader == "decode_batch":
        assert 1 <= value <= 4
    if reader == "tick_phase":
        tick = run["stats"][-1]["tick"]
        host = sum(tick["ns"].values()) - sum(
            tick["ns"][p] for p in ("idle", "wait", "sample_sync"))
        assert value == pytest.approx(host / 1e6 / tick["n"])
        assert read(ctx, {"phases": ["prep"]}) == pytest.approx(
            tick["ns"]["prep"] / 1e6 / tick["n"])
        # a program that counts no phases (the parent commit): nothing
        old = {k: {} for k in ("stats_before", "stats_after")}
        assert read(old, args) is None and read({}, args) is None
    if reader == "device_empty":
        tick = run["stats"][-1]["tick"]
        busy = sum(tick["empty_ns"].values()) - tick["empty_ns"]["idle"]
        assert 0 < value == pytest.approx(busy / 1e6 / tick["n"])
        # at most the host's time in the same leaves, which `tick_phase`
        # reads; and of the whole run, `idle` included, a share
        assert value <= importlib.import_module(
            "benchmark.readers.tick_phase").read(ctx, {
                "phases": "all", "minus": ["idle"]})
        assert 0 < read(ctx, {"phases": "all", "per": "window"}) <= 100
    if reader == "reply_empty":
        kept = [r["finish"]["timing"] for r in sorted(
            run["records"], key=lambda r: r["token_times"][-1] - r["due"])
            [5:7]]
        assert value == pytest.approx(sum(
            sum(t["rest_empty"].values()) for t in kept) / 2e6)
    if reader == "span_median":
        assert read({"spans": []}, args) is None
        assert read(ctx, {"name": "no:such"}) is None


# ------------------------------------------------------------ hygiene ----

def test_flight_recorder_does_not_import_jax():
    code = ("import sys; import ray_tpu._private.flight_recorder; "
            "import ray_tpu._private.diagnosis; "
            "sys.exit(1 if 'jax' in sys.modules else 0)")
    assert subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          timeout=120).returncode == 0


def test_benchmark_selftest_unit():
    """BENCHMARK.json and the files it names hold to the harness's rules
    (every metric its reader, every `moves` reported in its cells), the
    traffic repeats under two seeds, the trace reduction on its fixtures."""
    p = subprocess.run([sys.executable, "-m", "benchmark.selftest", "unit"],
                       cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    assert "selftest: ok" in p.stdout
