"""Diagnosis plane, unit level: introspection/flamegraph primitives,
loopmon staleness (the gauge must report a wedged loop, never drop it),
watchdog + anomaly funnel, task-hang tracking, capture bundles, the
timeline anomaly overlay, and the metrics-catalog lint.

Reference model: `ray stack` / dashboard reporter profiling
(dashboard/modules/reporter/profile_manager.py) — here exercised
without a cluster; tests/test_diagnosis_cluster.py covers the fan-out.
"""

import asyncio
import json
import os
import pathlib
import re
import threading
import time

import pytest

from ray_tpu._private import diagnosis, flight_recorder, loopmon
from ray_tpu._private.timeline import chrome_trace_events


# ---------------------------------------------------------------------------
# introspection primitives
# ---------------------------------------------------------------------------

def test_dump_stacks_covers_every_thread():
    evt = threading.Event()

    def parked_marker_thread():
        evt.wait(10)

    t = threading.Thread(target=parked_marker_thread,
                         name="diag-parked", daemon=True)
    t.start()
    try:
        time.sleep(0.1)
        out = diagnosis.dump_stacks()
        assert out["pid"] == os.getpid()
        assert set(out["stacks"]) == set(out["folded"])
        label = next(l for l in out["stacks"] if l.startswith("diag-parked"))
        assert "parked_marker_thread" in out["stacks"][label]
        # folded form is root->leaf basename:line:func
        assert out["folded"][label].split(";")[-1].split(":")[2] == "wait"
    finally:
        evt.set()


def test_dump_thread_stack_from_sibling():
    evt = threading.Event()

    def wedged_marker_function():
        evt.wait(10)

    t = threading.Thread(target=wedged_marker_function, daemon=True)
    t.start()
    try:
        time.sleep(0.1)
        text = diagnosis.dump_thread_stack(t.ident)
        assert "wedged_marker_function" in text
    finally:
        evt.set()
    assert diagnosis.dump_thread_stack(None) == ""
    assert diagnosis.dump_thread_stack(1) == ""   # no such thread


def test_cpu_profile_catches_busy_thread():
    stop = threading.Event()

    def spin_marker_function():
        x = 0
        while not stop.is_set():
            x += 1
        return x

    t = threading.Thread(target=spin_marker_function, daemon=True)
    t.start()
    try:
        prof = asyncio.run(diagnosis.cpu_profile(0.4, 0.01))
    finally:
        stop.set()
    assert prof["samples"] >= 10
    text = " ".join(s["stack"] for s in prof["stacks"])
    assert "spin_marker_function" in text


def test_merge_and_speedscope_render():
    proc = {"pid": 1,
            "stacks": {"MainThread-1": "..."},
            "folded": {"MainThread-1": "a.py:1:f;b.py:2:g"}}
    tree = {"kind": "stacks",
            "gcs": proc,
            "nodes": {"aa" * 16: {"agent": proc,
                                  "workers": {"bb" * 16: proc,
                                              "cc" * 16: {"error": "died"}}},
                      "dd" * 16: {"error": "unreachable"}}}
    folded = diagnosis.merge_cluster_profile(tree)
    roots = {s.split(";")[0] for s in folded}
    assert roots == {"gcs", f"node-{'aa' * 4}/agent",
                     f"node-{'aa' * 4}/worker-{'bb' * 4}"}
    assert all(w == 1 for w in folded.values())

    text = diagnosis.folded_text(folded)
    assert text.endswith("\n") and " 1" in text.splitlines()[0]

    ss = diagnosis.speedscope_json(folded, name="t")
    assert ss["$schema"].endswith("file-format-schema.json")
    prof = ss["profiles"][ss["activeProfileIndex"]]
    assert prof["type"] == "sampled"
    assert len(prof["samples"]) == len(prof["weights"]) == len(folded)
    nframes = len(ss["shared"]["frames"])
    assert all(0 <= i < nframes for s in prof["samples"] for i in s)
    assert prof["endValue"] == sum(prof["weights"])
    json.dumps(ss)   # JSON-serializable end to end

    # cpu_profile trees weight by sample count.
    ctree = {"kind": "cpu_profile",
             "gcs": {"pid": 1, "samples": 9,
                     "stacks": [{"stack": "a.py:1:f", "count": 9}]}}
    cfolded = diagnosis.merge_cluster_profile(ctree)
    assert cfolded == {"gcs;a.py:1:f": 9}


# ---------------------------------------------------------------------------
# loopmon staleness (satellite: stale entries REPORT, never vanish)
# ---------------------------------------------------------------------------

def _loop_in_thread(label):
    loop = asyncio.new_event_loop()
    t = threading.Thread(target=loop.run_forever, daemon=True)
    t.start()
    asyncio.run_coroutine_threadsafe(asyncio.sleep(0), loop).result(5)
    loop.call_soon_threadsafe(loopmon.install, label)
    return loop, t


def test_loopmon_blocked_loop_reports_stale_not_dropped():
    """A wedged loop's entry must stay in the snapshot with a growing
    stale age — dropping it silently is exactly how a hang hides."""
    loop, t = _loop_in_thread("tst_block")
    try:
        deadline = time.monotonic() + 5
        while "tst_block" not in loopmon.snapshot_full():
            assert time.monotonic() < deadline, "probe never installed"
            time.sleep(0.05)
        # Wedge: a synchronous sleep on the loop thread stops the probe.
        loop.call_soon_threadsafe(time.sleep, 3.0)
        time.sleep(1.5)
        snap = loopmon.snapshot()            # legacy ratio view
        full = loopmon.snapshot_full()
        assert "tst_block" in snap, "stale label dropped from snapshot()"
        info = full["tst_block"]
        assert info["stale_s"] > 1.0         # probe period is ~0.5s
        assert info["alive"] is True         # wedged, not stopped
        assert info["thread_ident"] == t.ident
        # ... which is exactly what the gauge row exports.
        det = diagnosis.loop_wedge_detector(threshold_s=1.0)
        hits = [h for h in det() if h["loop"] == "tst_block"]
        assert hits and hits[0]["kind"] == "loop_wedged"
        assert "time.sleep" in hits[0]["stack"] \
            or "_run_once" in hits[0]["stack"] or hits[0]["stack"]
        # flap suppression: immediate re-poll does not re-emit
        assert not [h for h in det() if h["loop"] == "tst_block"]
    finally:
        loop.call_soon_threadsafe(loop.stop)
        t.join(10)
        loop.close()
        time.sleep(0.7)          # let a probe tick observe the closure
        loopmon.snapshot()


def test_loop_wedge_detector_ignores_stopped_loops():
    """Stale + thread dead = the loop STOPPED (shutdown), not wedged."""
    loop, t = _loop_in_thread("tst_stopped")
    try:
        deadline = time.monotonic() + 5
        while "tst_stopped" not in loopmon.snapshot_full():
            assert time.monotonic() < deadline
            time.sleep(0.05)
    finally:
        loop.call_soon_threadsafe(loop.stop)
        t.join(10)
    # Thread is gone but the loop was not closed: entry may linger.
    time.sleep(1.2)
    full = loopmon.snapshot_full()
    if "tst_stopped" in full:
        assert full["tst_stopped"]["alive"] is False
        det = diagnosis.loop_wedge_detector(threshold_s=0.5)
        assert not [h for h in det() if h["loop"] == "tst_stopped"]
    loop.close()
    time.sleep(0.7)
    loopmon.snapshot()


# ---------------------------------------------------------------------------
# watchdog + anomaly funnel
# ---------------------------------------------------------------------------

def test_record_anomaly_counter_recorder_and_notify():
    fresh = flight_recorder.FlightRecorder()
    old = flight_recorder._recorder
    flight_recorder._recorder = fresh
    notes = []
    try:
        info = diagnosis.record_anomaly(
            "task_hung", daemon="worker", node_id="ab12",
            notify=notes.append, task_id="00ff", running_s=9.5,
            stack="x" * 20000)
        rows = fresh.drain()
    finally:
        flight_recorder._recorder = old
    assert info["kind"] == "task_hung" and info["ts"] > 0
    assert notes == [info]
    anomaly_rows = [r for r in rows if r.get("cat") == "anomaly"]
    assert len(anomaly_rows) == 1
    row = anomaly_rows[0]
    assert row["name"] == "anomaly:task_hung" and row["event"] == "SPAN"
    assert row["args"]["running_s"] == 9.5
    assert len(row["args"]["stack"]) <= 8000    # capped for the ring

    from ray_tpu.util.metrics import registry_snapshot
    rows = [m for m in registry_snapshot()
            if m["name"] == "ray_tpu_anomaly_total"
            and m["labels"].get("kind") == "task_hung"
            and m["labels"].get("node_id") == "ab12"]
    assert rows and rows[0]["value"] >= 1


def test_watchdog_polls_detectors_and_survives_bad_ones():
    fired = []

    def bad_detector():
        raise RuntimeError("detector bug")

    def good_detector():
        return [{"kind": "synthetic", "x": 1}]

    w = diagnosis.Watchdog(daemon_name="t", node_id="n1",
                           detectors=[bad_detector, good_detector],
                           notify=fired.append, poll_s=0.05)
    got = w.poll_once()
    assert len(got) == 1 and got[0]["kind"] == "synthetic"
    assert got[0]["daemon"] == "t" and got[0]["x"] == 1
    assert fired and w.fired[-1]["kind"] == "synthetic"
    w.start()
    time.sleep(0.3)
    w.stop()
    w.join(5)
    assert not w.is_alive()
    assert len(w.fired) <= 64


class _StaleLoop:
    """A loopmon entry 10 s stale on a live thread (this one), and a
    private recorder to read the anomalies from."""

    def __enter__(self):
        loopmon._RATIOS["tst_stale"] = (0.0, time.monotonic() - 10.0,
                                        threading.get_ident())
        self.old = flight_recorder._recorder
        self.rec = flight_recorder._recorder = \
            flight_recorder.FlightRecorder()
        return self

    def __exit__(self, *exc):
        loopmon._RATIOS.pop("tst_stale", None)
        flight_recorder._recorder = self.old

    def anomalies(self):
        return [r["name"] for r in self.rec.drain() if r["cat"] == "anomaly"]


def _run_watchdog(monkeypatch, oversleep_s):
    """One poll of a real Watchdog thread body over a stale loop, with
    the wedge threshold at 0.4 s; its wake comes `oversleep_s` late."""
    from ray_tpu._private.config import get_config
    monkeypatch.setitem(get_config()._values, "diagnosis_loop_wedge_s", 0.4)
    notified = []
    w = diagnosis.Watchdog(
        daemon_name="t", node_id="n1", notify=notified.append, poll_s=0.05,
        detectors=[diagnosis.loop_wedge_detector()])
    waits = []

    def wait(timeout):
        waits.append(timeout)
        if len(waits) > 1:
            return True                     # stop after one poll
        time.sleep(timeout + oversleep_s)
        return False

    w._stop_evt.wait = wait
    w.run()
    return w, notified


def test_watchdog_that_woke_late_reports_process_stalled_not_wedged(
        monkeypatch):
    """The whole process stood still (a TPU client starting): every
    loop's stamp is stale, none is wedged.  No detector runs on that
    poll, nothing is forwarded (so no capture bundle), and one
    `process_stalled` instant carries the length."""
    with _StaleLoop() as env:
        w, notified = _run_watchdog(monkeypatch, oversleep_s=0.5)
        assert w.fired == [] and notified == []
        assert env.anomalies() == ["anomaly:process_stalled"]
        # the loops get one wedge threshold (0.4 s here) to stamp afresh
        assert w.note_wake(0.0) is True
        time.sleep(0.45)
        assert w.note_wake(0.0) is False    # then the detectors run again
        assert [a["kind"] for a in w.poll_once()] == ["loop_wedged"]


def test_punctual_watchdog_still_fires_loop_wedged(monkeypatch):
    with _StaleLoop() as env:
        w, notified = _run_watchdog(monkeypatch, oversleep_s=0.0)
        assert [a["kind"] for a in w.fired] == ["loop_wedged"]
        assert notified and notified[0]["loop"] == "tst_stale"
        assert env.anomalies() == ["anomaly:loop_wedged"]


# ---------------------------------------------------------------------------
# task-hang tracking
# ---------------------------------------------------------------------------

def test_task_hang_tracker_thresholds_and_fire_once():
    tr = diagnosis.TaskHangTracker(multiple=10.0, min_s=0.05,
                                   default_s=0.1,
                                   thread_lookup=lambda tid: None)
    # No history -> default threshold.
    assert tr.threshold_for("f") == 0.1
    tid = b"\x01" * 16
    tr.note(tid, "f", "RUNNING")
    st = tr.stats()
    assert st["running"] == 1 and st["tasks_started"] == 1
    assert st["oldest_running_age_s"] is not None
    time.sleep(0.15)
    hits = tr.detector()()
    assert len(hits) == 1 and hits[0]["kind"] == "task_hung"
    assert hits[0]["task_id"] == tid.hex() and hits[0]["name"] == "f"
    assert hits[0]["running_s"] >= hits[0]["threshold_s"]
    # Flagged once: the same hung task never re-fires...
    assert tr.detector()() == []
    # ...and a terminal event clears both tracking and the flag.
    tr.note(tid, "f", "FAILED")
    assert tr.stats()["running"] == 0
    # FAILED does not poison the EMA (only FINISHED updates it).
    assert tr.threshold_for("f") == 0.1


def test_task_hang_tracker_ema_adapts_asymmetrically():
    tr = diagnosis.TaskHangTracker(multiple=2.0, min_s=0.0, default_s=99.0)

    def run(name, dur):
        tid = os.urandom(16)
        tr.note(tid, name, "RUNNING")
        t0, ent = tr._running[tid]
        tr._running[tid] = (t0 - dur, ent)     # backdate instead of sleep
        tr.note(tid, name, "FINISHED")

    run("g", 1.0)
    assert tr.threshold_for("g") == pytest.approx(2.0, rel=0.1)
    run("g", 3.0)          # jumps up fast: 0.5/0.5 blend
    up = tr.threshold_for("g")
    assert up > 3.5
    for _ in range(10):    # decays down slowly: 0.95/0.05 blend
        run("g", 0.1)
    down = tr.threshold_for("g")
    assert 0.2 < down < up


# ---------------------------------------------------------------------------
# capture bundles
# ---------------------------------------------------------------------------

def test_capture_manager_rate_limit_bundle_layout_and_prune(tmp_path):
    root = str(tmp_path)
    mgr = diagnosis.CaptureManager(root, min_interval_s=60.0,
                                   max_bundles=2)
    assert mgr.should_capture("loop_wedged")
    # Flaps inside the window are counted, not captured.
    assert not mgr.should_capture("loop_wedged")
    assert not mgr.should_capture("loop_wedged")
    assert mgr.suppressed["loop_wedged"] == 2
    assert mgr.should_capture("task_hung")      # per-kind limits
    assert mgr.should_capture("loop_wedged", force=True)

    path = mgr.write_bundle(
        "loop_wedged",
        {"stacks": {"a": b"\x01\x02"}, "nodes": [{"node_id": b"\xaa"}]},
        manifest_extra={"kind": "loop_wedged", "loop": "main"})
    assert os.path.basename(path).startswith("diag-loop_wedged-")
    man = json.load(open(os.path.join(path, "manifest.json")))
    assert man["anomaly_kind"] == "loop_wedged"
    assert man["files"] == ["nodes.json", "stacks.json"]
    assert man["suppressed_since_last"] == 2
    assert man["anomaly"]["loop"] == "main"
    stacks = json.load(open(os.path.join(path, "stacks.json")))
    assert stacks == {"a": "0102"}              # bytes -> hex, JSON-safe

    # Same-second bundles get a dedup suffix, and pruning keeps newest.
    p2 = mgr.write_bundle("loop_wedged", {})
    p3 = mgr.write_bundle("loop_wedged", {})
    assert len({path, p2, p3}) == 3
    left = sorted(d for d in os.listdir(root) if d.startswith("diag-"))
    assert len(left) == 2 and os.path.basename(path) not in left


# ---------------------------------------------------------------------------
# timeline overlay
# ---------------------------------------------------------------------------

def test_timeline_renders_anomalies_as_global_instants():
    rows = [{"task_id": b"", "name": "anomaly:loop_wedged",
             "event": "SPAN", "cat": "anomaly", "ts": 100.0,
             "start_us": 100_000_000, "dur_us": 0,
             "worker_id": b"", "node_id": b"\xab\xcd", "job_id": b"",
             "args": {"loop": "main", "stale_s": 6.1}},
            {"task_id": b"\x01" * 16, "name": "pull", "event": "SPAN",
             "cat": "transfer", "ts": 99.0, "start_us": 99_000_000,
             "dur_us": 10, "worker_id": b"", "node_id": b"\xab\xcd",
             "job_id": b""}]
    evs = chrome_trace_events(rows)
    marks = [e for e in evs if e["cat"] == "anomaly"]
    assert len(marks) == 1
    m = marks[0]
    assert m["ph"] == "i" and m["s"] == "g"     # full-height global mark
    assert m["name"] == "anomaly:loop_wedged"
    assert m["args"]["loop"] == "main"
    # ordinary plane spans still render as complete events
    assert any(e["ph"] == "X" and e["cat"] == "transfer" for e in evs)


# ---------------------------------------------------------------------------
# metrics-catalog lint (satellite: every exported series is documented)
# ---------------------------------------------------------------------------

# The io_stats counter family is emitted from an f-string
# (`ray_tpu_io_{k}_total`); expanded here and cross-checked against the
# live snapshot so a new io stat fails the lint until documented.
_IO_KEYS = {"tx_syscalls", "tx_frames", "tx_writev", "tx_bytes",
            "rx_native_bytes", "rx_takeovers", "connections"}


def _exported_series():
    """Every ray_tpu_* series name the runtime can export, collected
    from the definition sites: Counter/Gauge/Histogram constructors,
    daemon `row(...)` helpers, literal `"name": ...` metric rows, and
    the dashboard's derived CLUSTER_SERIES."""
    import ray_tpu
    from ray_tpu._private import rpc
    from ray_tpu.dashboard.grafana import CLUSTER_SERIES
    src_root = pathlib.Path(ray_tpu.__file__).parent
    pat = re.compile(
        r'(?:Counter\(|Gauge\(|Histogram\(|row\(|"name":)\s*f?'
        r'"(ray_tpu_[a-z0-9_{}]+)"', re.S)
    names = set()
    for py in src_root.rglob("*.py"):
        if py.name == "soak.py":    # synthetic soak-harness gauges
            continue
        for m in pat.finditer(py.read_text()):
            names.add(m.group(1))
    assert "ray_tpu_anomaly_total" in names          # collector sanity
    assert "ray_tpu_io_{k}_total" in names
    names.discard("ray_tpu_io_{k}_total")
    assert set(rpc.io_stats_snapshot()) <= _IO_KEYS, \
        "new io stat: add it to _IO_KEYS and the observability.md catalog"
    names.update(f"ray_tpu_io_{k}_total" for k in _IO_KEYS)
    names.update(CLUSTER_SERIES)
    return names


def test_every_exported_metric_is_in_the_catalog():
    doc = pathlib.Path(__file__).resolve().parents[1] \
        / "docs" / "observability.md"
    text = doc.read_text()
    missing = sorted(n for n in _exported_series() if n not in text)
    assert not missing, (
        f"series exported but absent from docs/observability.md "
        f"metrics catalog: {missing}")
