"""Cluster flight recorder: a low-overhead in-process event ring.

Reference surface: Ray's dedicated observability substrate —
src/ray/util/event.h (bounded in-memory event buffers per process),
core_worker/task_event_buffer.h (buffered status/profile events flushed
on an interval), src/ray/stats/ (per-process metric registry exported
via per-node agents).  TPU-native design collapses those into one
primitive: every daemon and worker owns a preallocated ring of
(mono-ns, category, name, payload) records; recording is one list-slot
store + index bump under a plain lock (any thread, no allocation beyond
the record tuple); flushes ride the process's EXISTING periodic push —
the core worker's telemetry loop and the agent's heartbeat tick — as
rows of the same GCS task-event sink the timeline already renders.  No
new per-event RPCs, ever.

Categories ("plane" granularity, gated via config
`flight_recorder_categories`, default all-on):

    lease      lease lifecycle on the agent (queued -> granted ->
               prefetch), extending the existing PREFETCH task event
    transfer   object-plane timelines: pull start/commit, chunk-wave
               stream, hedge fired, swarm source set
    request    LLM serving lifecycle (llm/serving.py + llm/engine.py).
               Per request, sharing the engine's request id:
               request:lock_wait (entry -> replica lock held and
               enqueued), request:admit (enqueue -> first token fanned
               out), prefill (w/ cached_tokens; `n` = the tick that
               admitted it, `new_program` = 1 when the call compiled;
               a model with recurrent layers: `recomputed` = tokens run
               again behind a state checkpoint, `checkpoints` = rows
               this prefill kept),
               request:reply (enqueue -> the end of a request that
               finished put on its stream; `first_us` to its first token
               and, of the rest, `wait_us` blocked on decode steps and
               `stop_us` stood still for other callers' admissions, over
               `ticks` ticks of which `stops` admitted: the span form of
               the `timing` its terminal item carries),
               request:cancelled, request:kv_broken, sp:gather.
               Per tick, sharing the tick number `n`
               (llm/tick_phases.py; the same boundaries feed
               EngineReplica.debug_stats()["tick"]): tick (lock held ->
               fan-out done) and its pieces tick:expire, tick:hop,
               step:admit (children prefill, sample_sync), step:chunk,
               step:emit, decode (w/ batch, and `experts` = held
               experts the routed layers touched; children decode:prep,
               decode:dispatch, decode:wait), step:ahead (the next
               decode step sent off before `step()` returns),
               tick:fan_out; between ticks tick:turn and tick:idle
    anomaly    diagnosis-plane detector firings (_private/diagnosis.py):
               loop_wedged, task_hung, lease_stalled, serving_silent,
               process_stalled (a watchdog that itself woke late) —
               rendered as global instant marks on the timeline

Overflow drops the OLDEST record and counts it (`dropped`) — the
counter is exported as a metric and stamped into every flush, so a
truncated view is never mistaken for a complete one (same contract the
task-event sink satellite adds GCS-side).  `flight_recorder_sample_n`
keeps high-rate categories cheap: record 1 of every N `instant()`s per
category (spans are never sampled away — their rate is bounded by the
operations they wrap).

Timestamps are MONOTONIC ns at record time; `drain()` converts to this
process's wall clock (clocks.wall(), so injected chaos skew shifts them
like every other stamp) — cross-node alignment happens read-side from
the GCS-estimated per-node offsets (see clocks.py / timeline.py).
"""

from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Dict, List, Optional

from . import clocks


class FlightRecorder:
    def __init__(self, capacity: int = 4096,
                 categories: Optional[set] = None,
                 sample_n: int = 1,
                 enabled: bool = True):
        self.capacity = max(16, int(capacity))
        # Preallocated ring: slot stores are O(1) and the steady-state
        # allocation per event is just its record tuple.
        self._ring: list = [None] * self.capacity
        self._head = 0          # next write slot
        self._count = 0         # live records (<= capacity)
        self._lock = threading.Lock()
        self._categories = categories            # None = all
        self._sample_n = max(1, int(sample_n))
        self._sample_ctr: Dict[str, int] = {}
        self.enabled = enabled
        self.recorded = 0       # accepted records (monotonic)
        self.dropped = 0        # overwritten-before-flush records
        self.sampled_out = 0    # instants skipped by sampling

    # ------------------------------------------------------------ record --
    def active(self, cat: str) -> bool:
        return self.enabled and (self._categories is None
                                 or cat in self._categories)

    def _push(self, rec: tuple) -> None:
        with self._lock:
            if self._count == self.capacity:
                self.dropped += 1       # overwriting the oldest
            else:
                self._count += 1
            self._ring[self._head] = rec
            self._head = (self._head + 1) % self.capacity
            self.recorded += 1

    def instant(self, cat: str, name: str, id: bytes = b"",
                **args) -> None:
        """Point event.  Subject to per-category 1-in-N sampling."""
        if not self.active(cat):
            return
        if self._sample_n > 1:
            c = self._sample_ctr.get(cat, 0)
            self._sample_ctr[cat] = c + 1
            if c % self._sample_n:
                self.sampled_out += 1
                return
        t = clocks.mono_ns()
        self._push((t, t, cat, name, id, args or None))

    def begin(self) -> int:
        """Start stamp for a span; pass to end()."""
        return clocks.mono_ns()

    def end(self, cat: str, name: str, t0_ns: int, id: bytes = b"",
            **args) -> None:
        """Complete a span started at begin().  Spans are never sampled
        away — their rate is bounded by the operation they wrap."""
        if not self.active(cat):
            return
        self._push((t0_ns, clocks.mono_ns(), cat, name, id, args or None))

    def span_at(self, cat: str, name: str, t0_ns: int, t1_ns: int,
                id: bytes = b"", **args) -> None:
        """A span whose two stamps the caller already took (mono-ns):
        spans that share a boundary share the stamp, so they tile."""
        if not self.active(cat):
            return
        self._push((t0_ns, t1_ns, cat, name, id, args or None))

    @contextmanager
    def span(self, cat: str, name: str, id: bytes = b"", **args):
        if not self.active(cat):
            yield
            return
        t0 = clocks.mono_ns()
        try:
            yield
        finally:
            self._push((t0, clocks.mono_ns(), cat, name, id,
                        args or None))

    # ------------------------------------------------------------- flush --
    def drain(self, node_id: bytes = b"",
              worker_id: bytes = b"") -> List[dict]:
        """Swap the ring out and convert records to task-event-sink rows
        (event='SPAN', cat=<plane>) ready to ride an existing batched
        notify.  Mono-ns stamps convert to THIS process's wall clock at
        drain time (one anchor per drain; monotonic spacing preserved
        exactly)."""
        with self._lock:
            if not self._count:
                return []
            if self._count == self.capacity:
                recs = (self._ring[self._head:]
                        + self._ring[:self._head])
            else:
                start = (self._head - self._count) % self.capacity
                if start + self._count <= self.capacity:
                    recs = self._ring[start:start + self._count]
                else:
                    recs = (self._ring[start:]
                            + self._ring[:self._head])
            self._ring = [None] * self.capacity
            self._head = 0
            self._count = 0
        anchor_mono = clocks.mono_ns()
        anchor_wall = clocks.wall()
        out: List[dict] = []
        for t0, t1, cat, name, rid, args in recs:
            start_s = anchor_wall - (anchor_mono - t0) / 1e9
            rec = {
                "task_id": rid or b"",
                "name": name,
                "event": "SPAN",
                "cat": cat,
                "ts": start_s,
                "start_us": int(start_s * 1e6),
                "dur_us": max(0, (t1 - t0) // 1000),
                "worker_id": worker_id,
                "node_id": node_id,
                "job_id": b"",
            }
            if args:
                rec["args"] = args
            out.append(rec)
        return out

    def note_lost(self, n: int) -> None:
        """Count rows that were drained but never delivered (flush
        notify failed and the retry buffer overflowed): they fold into
        `dropped` so the exported counter and every flush's drop stamp
        keep the no-silent-caps contract even for flush-path loss."""
        if n > 0:
            with self._lock:
                self.dropped += n

    def stats(self) -> Dict[str, int]:
        with self._lock:
            pending = self._count
        return {"recorded": self.recorded, "dropped": self.dropped,
                "sampled_out": self.sampled_out, "pending": pending}


_recorder: Optional[FlightRecorder] = None
_rec_lock = threading.Lock()


def recorder() -> FlightRecorder:
    """The per-process recorder, built from config on first use."""
    global _recorder
    if _recorder is None:
        with _rec_lock:
            if _recorder is None:
                _recorder = _from_config()
    return _recorder


def _from_config() -> FlightRecorder:
    try:
        from .config import get_config
        cfg = get_config()
        cats_s = cfg.flight_recorder_categories
        cats = (set(c.strip() for c in cats_s.split(",") if c.strip())
                if cats_s else None)
        return FlightRecorder(
            capacity=cfg.flight_recorder_capacity,
            categories=cats,
            sample_n=cfg.flight_recorder_sample_n,
            enabled=cfg.flight_recorder_enabled)
    except Exception:
        # The recorder must never take a daemon down with it.
        return FlightRecorder()


def reset() -> None:
    """Drop the singleton so the next recorder() re-reads config
    (tests; also correct after fork — each process records its own)."""
    global _recorder
    with _rec_lock:
        _recorder = None


def export_rows(labels: Dict[str, str]) -> List[dict]:
    """The unified-export rows EVERY process ships on its telemetry
    tick — RPC io_stats rollup, copy-audit totals, recorder counters —
    in the util.metrics snapshot row shape.  One definition so the
    agent and core-worker exports cannot silently diverge; callers
    append their daemon-specific gauges."""
    import time
    from . import rpc
    now = time.time()
    rec = recorder().stats()

    def row(name, value, help_="", lab=None):
        return {"name": name, "type": "counter", "help": help_,
                "ts": now, "labels": lab or labels,
                "value": float(value)}

    out = [
        row("ray_tpu_flight_recorder_recorded_total", rec["recorded"]),
        row("ray_tpu_flight_recorder_dropped_total", rec["dropped"],
            help_="flight-recorder records dropped (ring overwrite or "
                  "lost flush)"),
    ]
    for k, v in rpc.io_stats_snapshot().items():
        out.append(row(f"ray_tpu_io_{k}_total", v,
                       help_="process-wide RPC transport counters"))
    for tag, v in rpc.copy_audit_snapshot().items():
        out.append(row("ray_tpu_copied_bytes_total", v,
                       lab={**labels, "tag": tag},
                       help_="deliberate transfer-path copies "
                             "(copy audit)"))
    return out
