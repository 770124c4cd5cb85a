"""Black-box diagnosis plane: live introspection + hung-work watchdogs.

The flight recorder answers "what happened"; this module answers "what
is stuck RIGHT NOW and why" (reference: `ray stack` + the dashboard
reporter's py-spy/memray profiling, dashboard/modules/reporter/
profile_manager.py; design spirit: Ren et al., Google-Wide Profiling,
IEEE Micro 2010 — always-on sampling — and Dean & Barroso, The Tail at
Scale, CACM 2013 — capture the anomaly at the moment it happens).

Three layers, all composed from primitives that already exist:

* **Introspection helpers** — `dump_stacks()` / `cpu_profile()` are the
  shared implementations behind the worker's `stacks`/`cpu_profile`
  RPCs, the daemons' equivalents (`profile_handlers(tag)` registered on
  the existing GCS/agent conns), and the GCS `cluster_profile` fan-out.
  Results carry both human-readable tracebacks and collapsed
  ("folded") stacks so any subtree of the cluster merges into one
  flamegraph: `merge_cluster_profile()` → `folded_text()` /
  `speedscope_json()`.

* **Watchdog** — one daemon *thread* per process (a thread, not an
  asyncio task: it must keep running when the event loop it watches is
  wedged) polling cheap detectors: wedged loops (loopmon entry stale
  while its thread is alive → dump that thread via
  `sys._current_frames`), tasks RUNNING past a multiple of their
  function's historical p95 (`TaskHangTracker`, fed by the existing
  task-event stream), leases granted-but-never-RUNNING (agent-side),
  serving requests admitted-but-token-silent (serving-side).  A poll
  that itself woke late by more than half the wedge threshold means the
  PROCESS was not run: it records one `process_stalled` anomaly, with
  no notify and so no bundle, and no detector runs for one wedge
  threshold after the thaw.  Every
  firing goes through `record_anomaly()`: a typed `anomaly` recorder
  event + a `ray_tpu_anomaly_total{kind,...}` counter + an optional
  notify callback that forwards the anomaly to the GCS.

* **CaptureManager** — rate-limited per anomaly kind; the GCS uses it
  to write `diag-<kind>-<ts>/` black-box bundles (stacks, CPU profile,
  metrics, node views, recorder drain, manifest).
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import threading
import time
import traceback
from collections import defaultdict
from typing import Any, Callable, Dict, List, Optional

from . import flight_recorder as frec
from . import loopmon
from .config import get_config

# Cap on stack text attached to anomaly events/recorder args (the ring
# and the telemetry blobs are bounded; a deep recursion dump is not).
_STACK_CAP = 8000


# ---------------------------------------------------------------------------
# stack / profile introspection helpers (shared by worker + daemons)
# ---------------------------------------------------------------------------

def _frame_folded(frame) -> str:
    """Collapse one Python frame chain into `root;...;leaf` folded form
    (same `<basename>:<line>:<func>` frame naming as the sampling
    profiler so stacks and profiles merge into the same flamegraphs)."""
    parts: List[str] = []
    while frame is not None:
        co = frame.f_code
        parts.append(f"{os.path.basename(co.co_filename)}:"
                     f"{frame.f_lineno}:{co.co_name}")
        frame = frame.f_back
    return ";".join(reversed(parts))


def dump_stacks() -> dict:
    """Every thread of THIS process: formatted traceback + folded stack.

    Wire shape: ``{"pid", "stacks": {label: text}, "folded":
    {label: "root;...;leaf"}}`` with label ``<thread-name>-<ident>``."""
    frames = sys._current_frames()
    names = {t.ident: t.name for t in threading.enumerate()}
    stacks: Dict[str, str] = {}
    folded: Dict[str, str] = {}
    for tid, frame in frames.items():
        label = f"{names.get(tid, '?')}-{tid}"
        stacks[label] = "".join(traceback.format_stack(frame))
        folded[label] = _frame_folded(frame)
    return {"pid": os.getpid(), "stacks": stacks, "folded": folded}


def dump_thread_stack(ident: Optional[int]) -> str:
    """One thread's current stack, dumped from a SIBLING thread — the
    wedged-loop detector's view into a frozen event loop."""
    if ident is None:
        return ""
    frame = sys._current_frames().get(ident)
    if frame is None:
        return ""
    return "".join(traceback.format_stack(frame))[-_STACK_CAP:]


async def cpu_profile(duration_s: float = 2.0,
                      interval_s: float = 0.01) -> dict:
    """Sampling CPU profile of THIS process (all threads), collapsed
    stacks with sample counts — py-spy-shaped, no native deps.

    Wire shape: ``{"pid", "samples", "stacks": [{"stack", "count"}]}``
    where each ``stack`` is already folded root→leaf."""
    import asyncio
    duration_s = min(float(duration_s), 60.0)
    interval_s = max(float(interval_s), 0.001)
    counts: Dict[str, int] = defaultdict(int)
    samples = 0
    deadline = time.monotonic() + duration_s
    while time.monotonic() < deadline:
        for _tid, frame in sys._current_frames().items():
            counts[_frame_folded(frame)] += 1
        samples += 1
        await asyncio.sleep(interval_s)
    top = sorted(counts.items(), key=lambda kv: -kv[1])[:50]
    return {"pid": os.getpid(), "samples": samples,
            "stacks": [{"stack": s, "count": c} for s, c in top]}


def profile_handlers(tag: str) -> Dict[str, Callable]:
    """RPC handlers serving this process's own stacks/CPU profile —
    registered on the existing daemon conns (GCS server, agent server)
    so `cluster_profile` covers daemons, not just workers."""
    async def h_stacks(conn, p):
        out = dump_stacks()
        out["daemon"] = tag
        return out

    async def h_cpu_profile(conn, p):
        out = await cpu_profile(p.get("duration_s", 2.0),
                                p.get("interval_s", 0.01))
        out["daemon"] = tag
        return out

    return {"stacks": h_stacks, "cpu_profile": h_cpu_profile}


# ---------------------------------------------------------------------------
# flamegraph rendering: folded merge -> folded text / speedscope JSON
# ---------------------------------------------------------------------------

def _proc_folded(result: dict, kind: str, prefix: str,
                 out: Dict[str, int]) -> None:
    if not isinstance(result, dict) or result.get("error"):
        return
    if kind == "stacks":
        for label, folded in (result.get("folded") or {}).items():
            if folded:
                out[f"{prefix};{label};{folded}"] += 1
    else:
        for row in result.get("stacks") or []:
            if row.get("stack"):
                out[f"{prefix};{row['stack']}"] += int(row.get("count", 1))


def merge_cluster_profile(merged: dict) -> Dict[str, int]:
    """Flatten a `cluster_profile` result tree into one folded mapping
    ``"proc;frame;...;leaf" -> weight`` (weight = 1 per thread for
    stacks, sample count for cpu_profile).  Process roots are
    ``gcs``, ``node-<hex8>/agent``, ``node-<hex8>/worker-<hex8>``."""
    kind = merged.get("kind", "stacks")
    out: Dict[str, int] = defaultdict(int)
    if merged.get("gcs"):
        _proc_folded(merged["gcs"], kind, "gcs", out)
    for node_hex, node in (merged.get("nodes") or {}).items():
        if not isinstance(node, dict):
            continue
        root = f"node-{node_hex[:8]}"
        if node.get("agent"):
            _proc_folded(node["agent"], kind, f"{root}/agent", out)
        for wid, wres in (node.get("workers") or {}).items():
            _proc_folded(wres, kind, f"{root}/worker-{wid[:8]}", out)
    return dict(out)


def folded_text(folded: Dict[str, int]) -> str:
    """Brendan-Gregg collapsed-stack text: one `stack count` per line
    (feedable to flamegraph.pl / speedscope / inferno)."""
    lines = [f"{stack} {count}"
             for stack, count in sorted(folded.items(),
                                        key=lambda kv: (-kv[1], kv[0]))]
    return "\n".join(lines) + ("\n" if lines else "")


def speedscope_json(folded: Dict[str, int], name: str = "ray_tpu") -> dict:
    """Render a folded mapping as a speedscope sampled profile
    (https://www.speedscope.app/file-format-schema.json)."""
    frame_index: Dict[str, int] = {}
    frames: List[dict] = []
    samples: List[List[int]] = []
    weights: List[int] = []
    for stack, count in sorted(folded.items()):
        idxs = []
        for fr in stack.split(";"):
            if fr not in frame_index:
                frame_index[fr] = len(frames)
                frames.append({"name": fr})
            idxs.append(frame_index[fr])
        samples.append(idxs)
        weights.append(int(count))
    total = sum(weights)
    return {
        "$schema": "https://www.speedscope.app/file-format-schema.json",
        "shared": {"frames": frames},
        "profiles": [{
            "type": "sampled",
            "name": name,
            "unit": "none",
            "startValue": 0,
            "endValue": total,
            "samples": samples,
            "weights": weights,
        }],
        "activeProfileIndex": 0,
        "exporter": "ray_tpu-diagnosis",
        "name": name,
    }


# ---------------------------------------------------------------------------
# anomaly emission
# ---------------------------------------------------------------------------

_counter_lock = threading.Lock()
_anomaly_counter = None


def _counter():
    global _anomaly_counter
    with _counter_lock:
        if _anomaly_counter is None:
            from ..util.metrics import Counter
            _anomaly_counter = Counter(
                "ray_tpu_anomaly_total",
                "hung-work detector firings by kind",
                tag_keys=("kind", "daemon", "node_id"))
        return _anomaly_counter


def record_anomaly(kind: str, *, daemon: str, node_id: str = "",
                   notify: Optional[Callable[[dict], None]] = None,
                   **details) -> dict:
    """One detector firing: typed recorder event + node-labeled counter
    + optional forward to the GCS (best-effort, thread-safe via the
    caller-provided callback).  Returns the anomaly dict."""
    info = {"kind": kind, "daemon": daemon, "node_id": node_id,
            "ts": time.time(), **details}
    try:
        _counter().inc(1, tags={"kind": kind, "daemon": daemon,
                                "node_id": node_id})
    except Exception:
        pass
    try:
        # Detail keys that shadow instant()'s own parameters (a task_hung
        # detail carries the task's function NAME) get a trailing "_" so
        # they ride as event args instead of raising TypeError.
        args = {(f"{k}_" if k in ("cat", "name", "id") else k):
                (v[-_STACK_CAP:] if isinstance(v, str) else v)
                for k, v in details.items()}
        frec.recorder().instant("anomaly", f"anomaly:{kind}", **args)
    except Exception:
        pass
    if notify is not None:
        try:
            notify(info)
        except Exception:
            pass
    return info


class Watchdog(threading.Thread):
    """Per-daemon hung-work watchdog.

    A plain daemon THREAD (never an asyncio task — its whole job is to
    keep observing when the event loop is wedged) that polls a list of
    detectors.  A detector is a callable returning a list of anomaly
    dicts (``{"kind": ..., **details}``); each is routed through
    `record_anomaly` with this daemon's identity and notify callback."""

    def __init__(self, *, daemon_name: str, node_id: str = "",
                 detectors: List[Callable[[], List[dict]]],
                 notify: Optional[Callable[[dict], None]] = None,
                 poll_s: float = 0.5):
        super().__init__(name=f"diag-watchdog-{daemon_name}", daemon=True)
        self.daemon_name = daemon_name
        self.node_id = node_id
        self.detectors = list(detectors)
        self.notify = notify
        self.poll_s = max(0.05, float(poll_s))
        self._stop_evt = threading.Event()
        self._quiet_until = 0.0         # monotonic; see note_wake
        self.fired: List[dict] = []

    def stop(self) -> None:
        self._stop_evt.set()

    def poll_once(self) -> List[dict]:
        out = []
        for det in self.detectors:
            try:
                anomalies = det() or []
            except Exception:
                continue
            for a in anomalies:
                kind = a.pop("kind", "unknown")
                info = record_anomaly(kind, daemon=self.daemon_name,
                                      node_id=self.node_id,
                                      notify=self.notify, **a)
                out.append(info)
        self.fired.extend(out)
        del self.fired[:-64]
        return out

    def run(self) -> None:
        while True:
            due = time.monotonic() + self.poll_s
            if self._stop_evt.wait(self.poll_s):
                return
            if not self.note_wake(time.monotonic() - due):
                self.poll_once()

    def note_wake(self, late_s: float) -> bool:
        """A wake `late_s` after it was due; returns whether this poll is
        skipped.  Late by more than half the wedge threshold, this thread
        itself was not run — the whole process stood still (a TPU client
        starting stops every process on the machine for seconds), so
        every loop's stamp is stale and none is wedged.  It records ONE
        `process_stalled` instant with the length (no notify, so no
        capture bundle), and no detector runs until a whole wedge
        threshold has passed since the thaw: a loop that is still stale
        then has been given that long to stamp afresh (an agent's loop
        was still working off its backlog 0.8 s after an 8 s stop)."""
        now = time.monotonic()
        wedge_s = get_config().diagnosis_loop_wedge_s
        if late_s > wedge_s / 2.0:
            self._quiet_until = now + wedge_s
            record_anomaly("process_stalled", daemon=self.daemon_name,
                           node_id=self.node_id, stalled_s=round(late_s, 3))
        return now < self._quiet_until


# ---------------------------------------------------------------------------
# detectors
# ---------------------------------------------------------------------------

def loop_wedge_detector(threshold_s: Optional[float] = None
                        ) -> Callable[[], List[dict]]:
    """Wedged event loops: loopmon entry stale >= threshold while the
    loop's thread is still alive (stale+dead = stopped, not wedged).
    Re-emits at most once per threshold while the wedge persists, so a
    long wedge counts as repeated flaps without per-poll spam."""
    last_emit: Dict[str, float] = {}

    def check() -> List[dict]:
        thr = threshold_s
        if thr is None:
            thr = get_config().diagnosis_loop_wedge_s
        out = []
        now = time.monotonic()
        for label, info in loopmon.snapshot_full().items():
            if info["stale_s"] < thr or not info.get("alive"):
                last_emit.pop(label, None)
                continue
            if now - last_emit.get(label, -1e9) < thr:
                continue
            last_emit[label] = now
            out.append({"kind": "loop_wedged", "loop": label,
                        "stale_s": round(info["stale_s"], 3),
                        "stack": dump_thread_stack(info["thread_ident"])})
        return out

    return check


class TaskHangTracker:
    """Per-function execution-time EMA + hung-RUNNING detection.

    Fed from the existing task-event stream (core_worker's
    `record_task_event` — one extra method call on a path that already
    buffers an event): RUNNING starts tracking, a terminal event stops
    it and folds the duration into the function's p95 estimate
    (asymmetric EMA: jumps up fast, decays down slowly — the
    conservative direction for a hang threshold)."""

    _TERMINAL = ("FINISHED", "FAILED", "CANCELLED")

    def __init__(self, *, multiple: float = 20.0, min_s: float = 10.0,
                 default_s: float = 120.0,
                 thread_lookup: Optional[Callable[[bytes],
                                                  Optional[int]]] = None):
        self.multiple = multiple
        self.min_s = min_s
        self.default_s = default_s
        self.thread_lookup = thread_lookup
        self._lock = threading.Lock()
        self._running: Dict[bytes, tuple] = {}   # task_id -> (t0, name)
        self._p95: Dict[str, float] = {}
        self._flagged: set = set()
        self._tasks_started = 0
        self._last_started: Optional[float] = None

    def note(self, task_id: bytes, name: str, event: str) -> None:
        if event == "RUNNING":
            with self._lock:
                self._running[task_id] = (time.monotonic(), name)
                self._tasks_started += 1
                self._last_started = time.monotonic()
        elif event in self._TERMINAL:
            with self._lock:
                ent = self._running.pop(task_id, None)
                self._flagged.discard(task_id)
                if ent is None or event != "FINISHED":
                    return
                dur = time.monotonic() - ent[0]
                prev = self._p95.get(name)
                if prev is None:
                    self._p95[name] = dur
                elif dur > prev:
                    self._p95[name] = 0.5 * prev + 0.5 * dur
                else:
                    self._p95[name] = 0.95 * prev + 0.05 * dur
                if len(self._p95) > 512:
                    self._p95.pop(next(iter(self._p95)))

    def threshold_for(self, name: str) -> float:
        p95 = self._p95.get(name)
        if p95 is None:
            return self.default_s
        return max(self.multiple * p95, self.min_s)

    def stats(self) -> dict:
        """Cheap executor-activity summary (the agent's lease-stall
        detector probes this over the existing worker conn)."""
        with self._lock:
            now = time.monotonic()
            return {
                "running": len(self._running),
                "tasks_started": self._tasks_started,
                "last_task_started_age_s":
                    (now - self._last_started
                     if self._last_started is not None else None),
                "oldest_running_age_s":
                    (now - min(t0 for t0, _ in self._running.values())
                     if self._running else None),
            }

    def detector(self) -> Callable[[], List[dict]]:
        def check() -> List[dict]:
            now = time.monotonic()
            out = []
            with self._lock:
                items = list(self._running.items())
            for task_id, (t0, name) in items:
                age = now - t0
                if age < self.threshold_for(name):
                    continue
                with self._lock:
                    if (task_id in self._flagged
                            or task_id not in self._running):
                        continue
                    self._flagged.add(task_id)
                stack = ""
                if self.thread_lookup is not None:
                    stack = dump_thread_stack(self.thread_lookup(task_id))
                out.append({"kind": "task_hung", "task_id": task_id.hex(),
                            "name": name, "running_s": round(age, 3),
                            "threshold_s": round(self.threshold_for(name), 3),
                            "stack": stack})
            return out
        return check


_task_tracker: Optional[TaskHangTracker] = None


def init_task_tracker(**kw) -> TaskHangTracker:
    global _task_tracker
    _task_tracker = TaskHangTracker(**kw)
    return _task_tracker


def task_tracker() -> Optional[TaskHangTracker]:
    return _task_tracker


# ---------------------------------------------------------------------------
# black-box capture bundles
# ---------------------------------------------------------------------------

def _jsonable(obj):
    if isinstance(obj, dict):
        return {(k.hex() if isinstance(k, bytes) else str(k)): _jsonable(v)
                for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_jsonable(v) for v in obj]
    if isinstance(obj, bytes):
        return obj.hex()
    if isinstance(obj, (str, int, float, bool)) or obj is None:
        return obj
    return str(obj)


class CaptureManager:
    """Writes `diag-<kind>-<ts>/` bundle dirs, rate-limited per anomaly
    kind so a flapping detector keeps counting without DoSing the
    cluster with bundle I/O."""

    def __init__(self, root: str, *, min_interval_s: float = 60.0,
                 max_bundles: int = 20):
        self.root = root
        self.min_interval_s = min_interval_s
        self.max_bundles = max_bundles
        self._lock = threading.Lock()
        self._last: Dict[str, float] = {}
        self.suppressed: Dict[str, int] = defaultdict(int)

    def should_capture(self, kind: str, *, force: bool = False) -> bool:
        """Check + stamp the rate limit for `kind`.  A suppressed flap
        is counted (`suppressed`), never silently dropped."""
        with self._lock:
            now = time.monotonic()
            if not force and now - self._last.get(kind, -1e9) \
                    < self.min_interval_s:
                self.suppressed[kind] += 1
                return False
            self._last[kind] = now
            return True

    def write_bundle(self, kind: str, parts: Dict[str, Any],
                     manifest_extra: Optional[dict] = None) -> str:
        """One timestamped bundle dir: each part as <name>.json plus a
        manifest.json describing what was captured and why."""
        ts = time.strftime("%Y%m%d_%H%M%S")
        base = f"diag-{kind}-{ts}"
        path = os.path.join(self.root, base)
        n = 1
        while os.path.exists(path):
            path = os.path.join(self.root, f"{base}_{n}")
            n += 1
        os.makedirs(path, exist_ok=True)
        files = []
        for name, content in parts.items():
            fname = f"{name}.json"
            with open(os.path.join(path, fname), "w") as f:
                json.dump(_jsonable(content), f, indent=1)
            files.append(fname)
        manifest = {"anomaly_kind": kind, "captured_at": time.time(),
                    "files": sorted(files),
                    "suppressed_since_last": self.suppressed.get(kind, 0),
                    "anomaly": _jsonable(manifest_extra or {})}
        with open(os.path.join(path, "manifest.json"), "w") as f:
            json.dump(manifest, f, indent=1)
        self._prune()
        return path

    def _prune(self) -> None:
        try:
            bundles = sorted(
                d for d in os.listdir(self.root) if d.startswith("diag-"))
        except OSError:
            return
        for stale in bundles[:-self.max_bundles]:
            shutil.rmtree(os.path.join(self.root, stale),
                          ignore_errors=True)
