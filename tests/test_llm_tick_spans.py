"""The serving tick measured from inside (llm/tick_phases.py): the phases
EngineReplica's decode loop and LLMEngine.step() stamp tile every tick,
as flight-recorder spans and as cumulative counters taken at the same
stamps; a request's spans share its id, a tick's spans its number; the
spans the benchmark's readers already depend on keep their names,
arguments and extents.  Tiny engine on the CPU.
"""

import asyncio
import importlib
import os
import subprocess
import sys
import time
from collections import defaultdict

import pytest

from ray_tpu._private import flight_recorder
from ray_tpu.llm import EngineReplica
from ray_tpu.llm.tick_phases import LEAVES, _SPAN

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


def _serve(rec, *, prefill_chunk=None):
    """Two waves of requests through one replica, idle in between; returns
    the replica's stats before, between and after, and what each request
    streamed with the wall times of its send and first token."""
    old = flight_recorder._recorder
    flight_recorder._recorder = rec
    base = list(range(1, 13))

    async def main():
        er = EngineReplica("tiny", max_batch=4, max_len=64, page_size=8,
                           max_tokens=5, prefill_chunk=prefill_chunk)
        stats = [await er.debug_stats()]
        records = []

        async def one(prompt):
            r = {"due": time.time(), "sent": time.time(), "token_times": [],
                 "finish": None, "error": None, "cut": False}
            records.append(r)
            async for item in er.stream_generate(prompt):
                if isinstance(item, dict):
                    r["finish"] = item
                else:
                    r["token_times"].append(time.time())

        # buckets: 8 (three prompts), 16, 32; then a prefix-cache hit whose
        # suffix is the first run of its bucket
        wave1 = [[7, 8, 9], [3, 4, 5, 6], [5, 6, 7, 8, 9], base,
                 list(range(20, 45)), [1, 2, 3]]
        wave2 = [base[:8] + [40, 41, 42], [9, 9, 9]]
        for wave in (wave1, wave2):
            await asyncio.gather(*[one(p) for p in wave])
            await asyncio.sleep(0.05)           # the loop goes idle
            stats.append(await er.debug_stats())
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
    assert len(by_id) == len(run["records"]) == 8
    fan_outs = {a["n"]: (t0, t1)
                for t0, t1, _, _, a in _spans(run, "tick:fan_out")}
    holders = _spans(run, "step:admit", "step:chunk")
    for rid, spans in by_id.items():
        (w0, w1, wa), = spans["request:lock_wait"]
        (a0, a1, aa), = spans["request:admit"]
        assert set(wa) == {"queued"} and set(aa) == {"queued", "decoding"}
        # enqueue follows the lock's wait at once; the span ends where the
        # tick that sampled the first token fans it out
        assert w1 <= a0 <= w1 + 1_000_000
        last = max(spans["prefill"])
        n = last[2]["n"]
        assert all(p[2]["n"] <= n for p in spans["prefill"])
        assert [h for h in holders if h[4]["n"] == n
                and h[0] <= last[0] and last[1] <= h[1]]
        assert fan_outs[n][0] <= a1 <= fan_outs[n][1]


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
    assert all(set(r["args"]) == {"batch", "n", "pages", "synced"}
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
                                    "span_median", "tick_phase"])
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
                           "minus": ["idle", "wait", "sample_sync"]}
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
