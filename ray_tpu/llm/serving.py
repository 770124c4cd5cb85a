"""Production LLM serving: continuous batching, token streaming,
KV-prefix cache, and queue-driven autoscaling.

The subsystem composes pieces earlier layers already ship — the paged-KV
engine (engine.py), Serve's controller/router/replica, streaming
generators (`num_returns="streaming"` riding raw out-of-band frames),
and the flight recorder — into the one path a real deployment needs:

  client ── proxy (SSE/chunked) ── router (pow-2, death retry)
         ── EngineReplica actor ── LLMEngine (paged KV + prefix cache)

Design anchors: Orca's iteration-level scheduling (Yu et al., OSDI'22)
— admission and retirement happen per decode tick, so a late arrival
joins the running batch instead of waiting behind it — and vLLM's
PagedAttention block sharing (Kwon et al., SOSP'23) for the page-level
prefix cache the engine implements.

:class:`EngineReplica` is the Serve deployment callable.  One asyncio
decode loop owns the engine; every request is a per-request stream fed
from the loop's tick events:

  - **Continuous batching** — ``stream_generate`` enqueues into the
    engine's admission queue and returns immediately; the decode loop
    admits per tick against page-pool occupancy and retires per tick.
  - **Token streaming** — each emitted token lands in the request's
    queue and flows engine → router → client as ``ObjectRefGenerator``
    items; per-stream backpressure is the streaming layer's delayed-ack
    window; a client disconnect cancels the request typed and its pages
    return to the pool mid-decode.
  - **Deadlines** — the ambient task deadline (``.options(timeout_s=)``)
    is captured at enqueue; queued requests whose budget expires are
    failed typed (`DeadlineExceededError`) without ever occupying a
    slot, and admitted ones are cancelled mid-decode.
  - **Load shedding** — admission sheds with a typed
    :class:`~ray_tpu.exceptions.OverloadedError` (+ ``retry_after_s``)
    once the queue exceeds ``max_queue`` or the deadline-aware bound
    (estimated queue wait > remaining budget).
  - **Autoscaling** — ``__serve_load__`` exports queue depth × page-pool
    occupancy; the Serve controller scales replica counts on it,
    including scale-to-zero (see serve/_private/controller.py).

Observability: every phase is stamped into the flight recorder under
the ``request`` category and rides the existing telemetry flush to the
GCS sink.  Per request: ``request:lock_wait`` (entry → the replica's
lock held and the request enqueued), ``request:admit`` (enqueue →
admitted, with queue depth and the count of requests already decoding),
``prefill`` (with ``cached_tokens`` for prefix-cache hits and the tick
``n`` that admitted it), and for a request that finished
``request:reply`` (enqueue → its end put on its stream, with ``first_us``
to its first token and, of the rest, ``wait_us`` blocked on decode steps
and ``stop_us`` stood still for other callers' admissions, over ``ticks``
ticks of which ``stops`` admitted).  Per tick: the phases of
``tick_phases.py`` —
``tick`` and, tiling it and the stretch to the next one, ``tick:turn``,
``tick:expire``, ``tick:hop``, ``step:admit``, ``decode`` (with batch
size and ``synced``, the slot rows written to the device before it;
``decode:prep`` / ``:dispatch`` / ``:wait``), ``sample_sync`` (the
batched device→host sample pull), ``step:emit``, ``step:ahead`` (a
later decode step sent off before the call that will read it, at the end of
``step()`` or behind a step still unread, while no caller waits for the
lock), ``tick:fan_out`` — whose cumulative nanoseconds
``debug_stats()["tick"]`` also serves (``ns``), and beside them
``empty_ns``: of each leaf's time, the part in which the device was KNOWN
EMPTY, everything the engine had sent it read back and nothing sent since
(``sent``, ``seen``; a lower bound of the device's idle time, also on any
profile's host plane as the annotation ``ray_tpu/device:empty``:
``tick_phases.py``).  A tick's first tokens are fanned out from inside it,
before its decode step is read (``_hand_first``).

A request's own account of its time: the replica snapshots those counters
when a request is enqueued (S0), when its first token is put on its stream
(S1) and when its end is (S2).  Every instant of a replica lies in one leaf
phase, so the differences part the two stretches exactly, and the stream's
terminal dict carries them as ``timing``, recorder on or off:
``request_id``, ``lock_wait_ns`` (entry → S0), ``first_ns`` (S0 → S1),
``total_ns`` (S0 → S2), ``first`` and ``rest`` (ns by leaf, which sum to
``first_ns`` and ``total_ns - first_ns``), ``first_empty`` and
``rest_empty`` (of those, the ns in which the device was known empty, by
leaf; a leaf that reads 0 is left out), ``ticks`` and ``stops`` (ticks
begun, and those of them that admitted, between S1 and S2),
``prompt_tokens``, ``cached_tokens``, ``recomputed``.  A request that is
cancelled, expires, is shed or fails has none.

`run_open_loop` is the arrival-rate-driven (never closed-loop) load
harness: it offers requests on a fixed schedule regardless of
completions and reports p50/p99 TTFT, inter-token latency, and
tokens/s/replica.  ``perf --check`` gates on its numbers.
"""

from __future__ import annotations

import asyncio
import gc
import logging
import threading
import time
from typing import Any, AsyncIterator, Dict, List, Optional, Sequence

import numpy as np

from .._private import clocks, deadlines, diagnosis, flight_recorder
from .._private.compile_cache import compile_cache_stats
from .._private.config import get_config
from ..exceptions import (DeadlineExceededError, OverloadedError,
                          StreamBrokenError)
from .engine import LLMEngine, SamplingParams
from .tick_phases import LEAVES, STOP

logger = logging.getLogger("ray_tpu.llm.serving")

__all__ = ["EngineReplica", "run_open_loop"]


class _StreamEnd:
    """Terminal stream item: generation finished.  `timing` is the
    request's own account of its time (module docstring)."""

    __slots__ = ("finish_reason", "n_tokens", "timing")

    def __init__(self, finish_reason: str, n_tokens: int,
                 timing: Dict[str, Any]):
        self.finish_reason = finish_reason
        self.n_tokens = n_tokens
        self.timing = timing

    def as_dict(self) -> Dict[str, Any]:
        """The terminal dict every stream and collected reply ends with."""
        return {"finish_reason": self.finish_reason,
                "n_tokens": self.n_tokens, "timing": self.timing}


def _timing(req, t_in: int, s0: dict, s1: dict, s2: dict) -> Dict[str, Any]:
    """A finished request's record, from the `TickPhases` snapshots at its
    enqueue (S0), first token (S1) and end (S2) and the stamp `t_in` of
    its entry: two snapshots differ by exactly the time between them."""
    def empty(a: dict, b: dict) -> Dict[str, int]:
        return {p: ns for p in LEAVES
                if (ns := b["empty_ns"][p] - a["empty_ns"][p])}
    return {"request_id": req.req_id,
            "lock_wait_ns": s0["t"] - t_in,
            "first_ns": s1["t"] - s0["t"],
            "total_ns": s2["t"] - s0["t"],
            "first": {p: s1["ns"][p] - s0["ns"][p] for p in LEAVES},
            "rest": {p: s2["ns"][p] - s1["ns"][p] for p in LEAVES},
            "first_empty": empty(s0, s1),
            "rest_empty": empty(s1, s2),
            "ticks": s2["n"] - s1["n"],
            "stops": s2["admitting"] - s1["admitting"],
            "prompt_tokens": len(req.prompt),
            "cached_tokens": req.prefix_len,
            "recomputed": req.recomputed}


# The collector's full passes in this process, timed: every thread stands
# still for one (`EngineReplica.debug_stats()["gc"]`).  `passes`: the last
# of them, (wall clock at the end, ms).
_GC: Dict[str, Any] = {"full": 0, "t0": 0, "passes": []}


def _on_gc(phase: str, info: Dict[str, int]) -> None:
    if info["generation"] != 2:
        return
    if phase == "start":
        _GC["t0"] = clocks.mono_ns()
        return
    _GC["full"] += 1
    _GC["passes"] = _GC["passes"][-15:] + [
        (time.time(), (clocks.mono_ns() - _GC["t0"]) / 1e6)]


class _EngineLock(asyncio.Lock):
    """The replica's lock, which also knows how many callers wait for it
    (`waiting`; read from the engine's thread, a plain int)."""

    def __init__(self):
        super().__init__()
        self.waiting = 0

    async def acquire(self):
        self.waiting += 1
        try:
            return await super().acquire()
        finally:
            self.waiting -= 1


class EngineReplica:
    """One continuous-batching engine behind Serve.

    Deploy with ``serve_patterns.build_llm_app`` (autoscaled) or
    ``build_dp_deployment``; or use directly as a
    ``ray_tpu.remote(EngineReplica)`` actor (the P/D chaos tests do).
    All public methods are async — they run on the replica's event loop
    while the device work happens on executor threads, so admissions,
    stream acks and health pings keep flowing mid-decode."""

    def __init__(self, preset: str = "tiny", *, max_batch: int = 4,
                 max_len: int = 128, page_size: int = 16,
                 kv_pages: Optional[int] = None,
                 ckpt_rows: Optional[int] = None, prefix_cache: bool = True,
                 max_queue: int = 64, max_tokens: int = 16,
                 temperature: float = 0.0, eos_id: Optional[int] = None,
                 seed: int = 0, mesh=None, sp_degree: Optional[int] = None,
                 sp_strategy: str = "ring",
                 prefill_chunk: Optional[int] = None,
                 kv_gather_window: int = 4, paged_span: int = 64):
        import concurrent.futures

        from .._private.compile_cache import enable_compile_cache
        from ..models import PRESETS
        from ..tpu.accelerator import (TPUAcceleratorManager,
                                       require_tpu_backend)
        enable_compile_cache()
        if TPUAcceleratorManager.leased_chip_ids():
            # Deployed on real chips: the engine below takes whatever
            # jax.devices() gives, so make sure that is the TPU.
            require_tpu_backend("EngineReplica")
        cfg = PRESETS[preset] if isinstance(preset, str) else preset
        # Cross-host KV gather plumbing: part handles are object-plane
        # refs into OTHER replicas' arenas (published through the
        # replica directory); the blocking fetch and the async prefetch
        # both resolve via ray_tpu.get — a swarm-plane bulk pull when
        # the holder is remote.  The prefetch pool is what overlaps the
        # gather with decode compute (the engine kicks it before the
        # attention loop touches the parts).
        self._fetch_pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=2, thread_name_prefix="kv-gather")
        self.engine = LLMEngine(cfg, max_batch=max_batch, max_len=max_len,
                                seed=seed, mesh=mesh, page_size=page_size,
                                kv_pages=kv_pages, ckpt_rows=ckpt_rows,
                                prefix_cache=prefix_cache,
                                sp_degree=sp_degree,
                                sp_strategy=sp_strategy,
                                prefill_chunk=prefill_chunk,
                                kv_gather_window=kv_gather_window,
                                kv_fetch=self._kv_fetch,
                                kv_prefetch=self._kv_prefetch)
        self.paged_span = int(paged_span)
        self.defaults = SamplingParams(max_tokens=max_tokens,
                                       temperature=temperature,
                                       eos_id=eos_id)
        self.max_queue = int(max_queue)
        self._lock = _EngineLock()         # serializes ALL engine access
        # A caller waiting for the lock is about to hand the engine work:
        # the tick does not send its next decode step off ahead of it.
        self.engine.hold_ahead = lambda: self._lock.waiting > 0
        # A tick's first tokens leave for their streams before the tick's
        # decode step is read (`_hand_first`).
        self.engine.hand_first = self._hand_first
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._wake = asyncio.Event()
        self._loop_task: Optional[asyncio.Task] = None
        # req_id -> consumer queue / metadata for in-flight streams.
        self._waiters: Dict[int, asyncio.Queue] = {}
        self._meta: Dict[int, Dict[str, Any]] = {}
        # EMA of request wall time: the shed path's queue-wait estimate.
        self._req_s_ema = 0.25
        self._ticks = 0
        self._phases = self.engine.phases
        self._enqueued = 0              # since the last tick began
        self._max_active = 0
        self._shed = 0
        self._cancelled = 0
        self._expired = 0
        self._completed = 0
        self._tokens_out = 0
        self._kv_broken = 0
        self._gauges = None
        self._last_gauge_flush = 0.0
        self._compiles = -1             # compiles seen (`_keep_compiled`)
        if _on_gc not in gc.callbacks:
            gc.callbacks.append(_on_gc)
        self._silence_task: Optional[asyncio.Task] = None

    # ------------------------------------------------------------ helpers --
    def _kv_fetch(self, handle):
        """Blocking KV-part resolve (engine gather window, executor
        thread): by-value dicts pass through; refs pull from the holding
        arena — remote pulls ride the swarm plane via the owner's
        replica directory location hints."""
        if isinstance(handle, dict):
            return handle
        import ray_tpu
        return ray_tpu.get(handle, timeout=60.0)

    def _kv_prefetch(self, handle):
        """Async KV-part warm (returns a Future with .result()): runs on
        the gather pool so the pull overlaps decode compute."""
        import concurrent.futures
        if isinstance(handle, dict):
            f: concurrent.futures.Future = concurrent.futures.Future()
            f.set_result(handle)
            return f
        import ray_tpu
        return self._fetch_pool.submit(ray_tpu.get, handle, timeout=60.0)

    def _keep_compiled(self) -> None:
        """After a tick in which a program was compiled: collect once, then
        take everything alive out of the collector's sight (`gc.freeze`).
        What tracing leaves in JAX's caches is 20-70 thousand objects a
        program, kept for the replica's life; a dozen prefill buckets and
        the decode step make a heap of most of a million, and each full
        pass of the collector walked all of it with every thread stopped:
        350-440 ms once or twice a minute, in whichever phase of a tick it
        fell, under every running reply (PERF.md §6, PR 54).  Frozen, a
        full pass walks what serving itself allocated since."""
        n = compile_cache_stats()["requests"]
        if n != self._compiles:
            self._compiles = n
            gc.collect()
            gc.freeze()

    def _flush_gauges(self) -> None:
        """Node-labeled KV/cache/gather gauges into the unified metrics
        export (the core worker's telemetry flush ships
        util.metrics.registry_snapshot()); throttled to ~1 Hz so the
        decode tick never pays metric overhead."""
        now = time.monotonic()
        if now - self._last_gauge_flush < 1.0:
            return
        self._last_gauge_flush = now
        try:
            if self._gauges is None:
                import ray_tpu
                from ..util.metrics import Gauge
                try:
                    nid = ray_tpu.get_runtime_context().node_id
                    node = nid.hex() if isinstance(nid, bytes) else str(nid)
                except Exception:
                    node = "driver"
                tags = {"node_id": node}
                self._gauges = {
                    "occ": Gauge("ray_tpu_llm_kv_page_occupancy",
                                 "KV page-pool occupancy (0..1)",
                                 ("node_id",)).set_default_tags(tags),
                    "hit": Gauge("ray_tpu_llm_prefix_cache_hit_rate",
                                 "prefix-cache hit rate (0..1)",
                                 ("node_id",)).set_default_tags(tags),
                    "gbytes": Gauge("ray_tpu_llm_kv_gather_bytes",
                                    "remote KV part bytes gathered",
                                    ("node_id",)).set_default_tags(tags),
                    "gwait": Gauge("ray_tpu_llm_kv_gather_wait_s",
                                   "blocking remote-KV gather wait (s)",
                                   ("node_id",)).set_default_tags(tags),
                    "demo": Gauge("ray_tpu_kv_demoted_pages",
                                  "prefix-cache pages demoted to the "
                                  "host/NVMe offload tier (cumulative)",
                                  ("node_id",)).set_default_tags(tags),
                }
            e = self.engine
            self._gauges["occ"].set(e.kv_page_occupancy())
            cs = e.prefix_cache_stats()
            if cs.get("enabled"):
                total = cs["hits"] + cs["misses"]
                self._gauges["hit"].set(cs["hits"] / total if total else 0.0)
                self._gauges["demo"].set(cs.get("demoted_pages", 0))
            gs = e.kv_gather_stats()
            self._gauges["gbytes"].set(gs["bytes"])
            self._gauges["gwait"].set(gs["wait_s"])
        except Exception:       # metrics must never sink the decode loop
            pass

    def _params(self, opts: Optional[dict]) -> SamplingParams:
        o = opts or {}
        d = self.defaults
        return SamplingParams(
            max_tokens=int(o.get("max_tokens", d.max_tokens)),
            temperature=float(o.get("temperature", d.temperature)),
            eos_id=o.get("eos_id", d.eos_id))

    def __serve_load__(self) -> float:
        """Autoscaling metric: queue depth × page-pool occupancy.  A deep
        queue against a full pool reads as heavy load; the same queue
        against a mostly-free pool (admission imminent) reads lighter;
        idle reads exactly 0 so scale-to-zero can trigger."""
        e = self.engine
        occ = e.kv_page_occupancy()
        return e.queue_depth * (1.0 + occ) + e.active_requests * max(occ,
                                                                     0.25)

    def _maybe_shed(self, deadline: Optional[float]) -> None:
        qd = self.engine.queue_depth
        est_wait = (qd / max(1, self.engine.max_batch)) * self._req_s_ema
        if qd >= self.max_queue:
            self._shed += 1
            raise OverloadedError(
                f"admission queue full ({qd} >= {self.max_queue})",
                retry_after_s=max(0.05, est_wait))
        if deadline is None:
            return
        now = time.time()
        if now > deadline:
            # Budget already spent (e.g. parked behind a compiling
            # tick): that's an expiry, not an overload — retrying the
            # same request would not help.
            self._expired += 1
            raise DeadlineExceededError(
                "deadline exceeded before serving admission queue")
        if now + est_wait > deadline:
            # Deadline-aware bound: admitting would burn decode capacity
            # on a result the caller has already written off.
            self._shed += 1
            raise OverloadedError(
                f"estimated queue wait {est_wait:.2f}s exceeds the "
                f"request's remaining deadline budget",
                retry_after_s=max(0.05, est_wait))

    def _ensure_loop(self) -> None:
        if self._loop_task is None or self._loop_task.done():
            # From here on every instant lies in a leaf: the loop's first
            # turn starts now, not when the new task first runs.
            self._phases.to("turn")
            self._loop_task = asyncio.ensure_future(self._decode_loop())
        cfg = get_config()
        if cfg.diagnosis_enabled and (self._silence_task is None
                                      or self._silence_task.done()):
            self._silence_task = asyncio.ensure_future(
                self._silence_watch(cfg.diagnosis_serving_silence_s))

    async def _silence_watch(self, silence_s: float) -> None:
        """Diagnosis-plane detector: a request that was ADMITTED (holds a
        decode slot) but has emitted no token for `silence_s` is a silent
        hang — the engine thread is wedged or the stream consumer stopped
        being fed.  Flagged once per request (`serving_silent` anomaly);
        the decode loop keeps running, this only observes."""
        poll = max(0.5, silence_s / 4.0)
        while True:
            await asyncio.sleep(poll)
            now = time.monotonic()
            for rid, meta in list(self._meta.items()):
                if (not meta.get("admitted") or meta.get("finished")
                        or meta.get("_silent")):
                    continue
                last = meta["t_last_tok"]   # set at the first token too
                if now - last > silence_s:
                    meta["_silent"] = True
                    diagnosis.record_anomaly(
                        "serving_silent", daemon="serving",
                        request_id=int(rid), silent_s=now - last,
                        active=self.engine.active_requests)

    # --------------------------------------------------------- decode loop --
    async def _decode_loop(self):
        """The continuous-batching tick: admit per tick, ONE compiled
        decode step for every active slot, retire per tick, fan tokens
        out to their streams.  Engine compute runs on an executor thread
        so this loop (and the whole worker runtime) stays responsive."""
        loop = self._loop = asyncio.get_running_loop()
        ph = self._phases
        while True:
            try:
                async with self._lock:
                    enqueued, self._enqueued = self._enqueued, 0
                    ph.tick_begin(enqueued, self.engine.active_requests,
                                  self.engine.queue_depth)
                    self._expire_overdue()
                    if self.engine.has_unfinished():
                        ph.to("hop")
                        done = await loop.run_in_executor(
                            None, self.engine.step)
                        ph.to("fan_out")
                        self._ticks += 1
                        self._max_active = max(self._max_active,
                                               self.engine.active_requests
                                               + len(done))
                        self._fan_out(self.engine.take_tick_events(), done)
                        self._flush_gauges()
                        self._keep_compiled()
                    ph.tick_end()
                if not self.engine.has_unfinished():
                    self._wake.clear()
                    ph.to("idle", enqueued=0)
                    await self._wake.wait()
                    ph.to("turn")
                else:
                    # One loop turn between ticks: lets freshly arrived
                    # requests enqueue (the lock is FIFO-fair) so they are
                    # admitted on the NEXT tick — iteration-level
                    # scheduling, not batch-level.
                    await asyncio.sleep(0)
            except asyncio.CancelledError:
                raise
            except Exception:
                logger.exception("decode loop tick failed")
                await asyncio.sleep(0.2)

    def _expire_overdue(self) -> None:
        """Fail queued requests whose deadline passed (typed, without
        ever occupying a slot) and cancel admitted ones mid-decode."""
        now = time.time()
        for rid, meta in list(self._meta.items()):
            dl = meta.get("deadline")
            if dl is None or now <= dl or meta.get("finished"):
                continue
            self._expired += 1
            self.engine.cancel_request(rid)
            q = self._waiters.get(rid)
            if q is not None:
                q.put_nowait(DeadlineExceededError(
                    "deadline exceeded in serving admission queue"
                    if not meta.get("admitted")
                    else "deadline exceeded mid-decode"))
            meta["finished"] = True

    def _hand_first(self, events) -> None:
        """The engine's hook (`LLMEngine.hand_first`), called on the
        ENGINE's thread inside `step()` with the tick's events so far, its
        first tokens, once the tick's decode step has been dispatched and
        before it is read: they go to their streams now, on the loop's
        thread, which is idle while it awaits `step()`, and not a decode
        step later.  The engine has taken them out of the tick's events,
        so the `_fan_out` at the end of the call hands each token once."""
        self._loop.call_soon_threadsafe(self._fan_out, events, ())

    def _fan_out(self, events, done_reqs) -> None:
        """Tokens onto their streams, and the ends of the requests that
        retired.  A request's first token takes its snapshot S1 here, on
        the loop's thread: at the end of a tick, or inside it while the
        engine's thread is in `dispatch` or `wait` (`_hand_first`)."""
        rec = flight_recorder.recorder()
        done_by_id = {r.req_id: r for r in done_reqs}
        for rid, tok, fin in events:
            meta = self._meta.get(rid)
            if meta is None:
                continue
            if not meta.get("admitted"):
                meta["admitted"] = True
                meta["s1"] = s1 = self._phases.snapshot()
                rec.span_at("request", "request:admit", meta["s0"]["t"],
                            s1["t"], id=rid.to_bytes(8, "little"),
                            queued=self.engine.queue_depth,
                            decoding=max(0, self.engine.active_requests - 1
                                         + len(done_by_id)))
            meta["t_last_tok"] = time.monotonic()
            q = self._waiters.get(rid)
            if q is not None:
                q.put_nowait(int(tok))
        for rid, req in done_by_id.items():
            meta = self._meta.get(rid)
            if meta is not None and not meta.get("finished"):
                meta["finished"] = True
                q = self._waiters.get(rid)
                if req.finish_reason == "error" and req.error is not None:
                    # Mid-decode loss of a KV-holding host: the engine
                    # retired the request typed (KVGatherError, pages
                    # already back in the pool) and never emitted a
                    # wrong token.  Surface the SAME mid-stream contract
                    # as a replica death: StreamBrokenError carrying
                    # tokens_emitted, cause chained for diagnosis.
                    self._kv_broken += 1
                    rec.instant("request", "request:kv_broken",
                                id=rid.to_bytes(8, "little"),
                                tokens=len(req.out))
                    if q is not None:
                        err = StreamBrokenError(
                            f"remote KV lost mid-decode: {req.error}",
                            tokens_emitted=len(req.out))
                        err.__cause__ = req.error
                        q.put_nowait(err)
                    continue
                self._completed += 1
                self._tokens_out += len(req.out)
                s2 = self._phases.snapshot()
                timing = _timing(req, meta["t_in"], meta["s0"], meta["s1"],
                                 s2)
                rest = timing["rest"]
                rec.span_at("request", "request:reply", meta["s0"]["t"],
                            s2["t"], id=rid.to_bytes(8, "little"),
                            first_us=timing["first_ns"] // 1000,
                            wait_us=rest["wait"] // 1000,
                            stop_us=sum(rest[p] for p in STOP) // 1000,
                            ticks=timing["ticks"], stops=timing["stops"])
                # SERVICE time (first token -> finish), not enqueue ->
                # finish: folding queue wait into the EMA would make
                # the shed estimate grow quadratically with depth.
                dur = (timing["total_ns"] - timing["first_ns"]) / 1e9
                self._req_s_ema += 0.2 * (dur - self._req_s_ema)
                if q is not None:
                    q.put_nowait(_StreamEnd(req.finish_reason,
                                            len(req.out), timing))

    # ------------------------------------------------------------ streams --
    def _track(self, rid: int, deadline: Optional[float], t_in: int
               ) -> asyncio.Queue:
        """Under the lock, right after the engine took request `rid`, which
        entered at the stamp `t_in`: its stream's queue and metadata, and
        the decode loop woken.  The snapshot `s0` is the request's
        enqueue: its stamp closes `request:lock_wait` and opens
        `request:admit` and `request:reply`."""
        q: asyncio.Queue = asyncio.Queue()
        self._waiters[rid] = q
        self._enqueued += 1
        self._ensure_loop()
        s0 = self._phases.snapshot()
        # The wait for the tick that held the lock: the part of a
        # client's time to first token that neither the engine's
        # `request:admit` nor the serve library owns.
        flight_recorder.recorder().span_at(
            "request", "request:lock_wait", t_in, s0["t"],
            id=rid.to_bytes(8, "little"), queued=self.engine.queue_depth)
        self._meta[rid] = {"deadline": deadline, "t_in": t_in, "s0": s0,
                           "s1": None, "admitted": False, "finished": False}
        self._wake.set()
        return q

    async def _enqueue(self, prompt_tokens: Optional[Sequence[int]],
                       opts: Optional[dict], *,
                       external: Optional[tuple] = None,
                       cache_prompt: Optional[Sequence[int]] = None):
        """Hand the engine a request (a prompt, or a shipped KV blob and
        its first token) under the replica's lock: (request id, its
        stream's queue).  Typed failures (shed, deadline, engine
        rejection) raise."""
        params = self._params(opts)
        deadline = deadlines.get()
        t_in = clocks.mono_ns()
        async with self._lock:
            # Shed check INSIDE the lock: concurrent arrivals during a
            # decode tick must each see the true queue depth, not a
            # pre-tick snapshot (they would all pass a stale bound).
            self._maybe_shed(deadline)
            if external is not None:
                blob, first = external
                rid = self.engine.add_external_request(
                    blob, first, params, prompt_tokens=cache_prompt)
            else:
                rid = self.engine.add_request(list(prompt_tokens), params)
            return rid, self._track(rid, deadline, t_in)

    async def _items(self, rid: int, q: asyncio.Queue) -> AsyncIterator[Any]:
        """An enqueued request's stream: int tokens, then the terminal
        dict (`_StreamEnd.as_dict`); a typed failure put on the queue
        raises.  Releases the request however it ends."""
        try:
            while True:
                item = await q.get()
                if isinstance(item, BaseException):
                    raise item
                if isinstance(item, _StreamEnd):
                    yield item.as_dict()
                    return
                yield item
        finally:
            await self._release(rid)

    @staticmethod
    async def _whole(items: AsyncIterator[Any]) -> Dict[str, Any]:
        """A stream drained: {"tokens": [...]} and its terminal dict's
        keys (`finish_reason`, `n_tokens`, `timing`)."""
        out: List[int] = []
        end: Dict[str, Any] = {"finish_reason": ""}
        async for item in items:
            if isinstance(item, dict):
                end = item
            else:
                out.append(item)
        return {"tokens": out, **end}

    async def _release(self, rid: int) -> None:
        meta = self._meta.pop(rid, None)
        self._waiters.pop(rid, None)
        if meta is not None and not meta.get("finished"):
            # Consumer went away mid-generation (client disconnect /
            # typed cancellation): retire now, pages return mid-decode.
            self._cancelled += 1
            flight_recorder.recorder().instant(
                "request", "request:cancelled",
                id=rid.to_bytes(8, "little"))
            async with self._lock:
                self.engine.cancel_request(rid)

    async def stream_generate(self, prompt_tokens: Sequence[int],
                              opts: Optional[dict] = None
                              ) -> AsyncIterator[Any]:
        """Async generator: int tokens as they decode, then one terminal
        dict ``{"finish_reason": ..., "n_tokens": ..., "timing": {...}}``
        (`timing`: where the replica's time went while it held the
        request, module docstring).  This is the method the serve router
        dispatches with ``num_returns="streaming"``; each yielded item
        becomes its own object the client can consume while decode
        continues."""
        it = self._items(*await self._enqueue(prompt_tokens, opts))
        try:
            async for item in it:
                yield item
        finally:
            # async-for does not close the inner generator on early exit;
            # close it NOW so an abandoned stream cancels its request (and
            # frees its pages) deterministically, not at a later GC.
            await it.aclose()

    async def generate(self, prompt_tokens: Sequence[int],
                       opts: Optional[dict] = None) -> Dict[str, Any]:
        """Non-streaming completion over the same continuous-batching
        machinery: ``{"tokens": [...], "finish_reason": ..., "n_tokens":
        ..., "timing": {...}}``."""
        return await self._whole(self._items(
            *await self._enqueue(prompt_tokens, opts)))

    async def __call__(self, prompt_tokens: Sequence[int],
                       opts: Optional[dict] = None) -> List[int]:
        """DP-pattern compatibility surface: plain token list."""
        return (await self.generate(prompt_tokens, opts))["tokens"]

    # -------------------------------------------------- P/D disaggregation --
    async def prefill(self, prompt_tokens: Sequence[int],
                      opts: Optional[dict] = None):
        """Prefill half: (kv_blob, first_token) for a decode replica.
        Prefix-cache hits skip the shared span's compute.  LEGACY
        transport: the blob travels BY VALUE (prefill → caller → decode
        = two object-plane transfers, one through the caller's process).
        Production paths use :meth:`prefill_handoff`."""
        params = self._params(opts)
        if deadlines.expired():
            raise DeadlineExceededError(
                "deadline exceeded before prefill started")
        loop = asyncio.get_running_loop()
        async with self._lock:
            return await loop.run_in_executor(
                None, lambda: self.engine.prefill_only(
                    list(prompt_tokens), params))

    async def prefill_handoff(self, req: dict) -> dict:
        """Prefill half returning a HANDOFF instead of the blob: the KV
        pages are put into THIS replica's arena (this worker is the
        owner; the node's agent pins the primary) and only the 20-byte
        ref travels onward.  The decode side resolves the ref itself, so
        the pages move prefill-arena → decode-arena directly via the
        owner's replica directory (PR-5 location hints stamp the pull's
        from_addrs) — the proxy/ingress process never touches the bytes.

        ``req = {"prompt": [...], "opts": {...}}`` (single argument so
        the method binds into a compiled DAG); returns
        ``{"ref", "first", "opts", "prompt"}``."""
        import ray_tpu
        prompt = list(req["prompt"])
        opts = req.get("opts") or {}
        params = self._params(opts)
        if deadlines.expired():
            raise DeadlineExceededError(
                "deadline exceeded before prefill started")
        loop = asyncio.get_running_loop()
        async with self._lock:
            blob, first = await loop.run_in_executor(
                None, lambda: self.engine.prefill_only(prompt, params))
        return {"ref": ray_tpu.put(blob), "first": first, "opts": opts,
                "prompt": prompt}

    async def prefill_handoff_channel(self, req: dict) -> dict:
        """Prefill half for COMPILED pipelines: the KV blob rides the
        compiled channel itself — written once into this node's arena by
        the ring's spill path, shipped arena-to-arena by the agent
        bridge when the decode replica lives on another node, reclaimed
        by last-reader delete.  No ownership bookkeeping at all (an
        owned ObjectRef pickled through a raw channel would escape-pin
        the blob forever — by-value transport is the leak-free form
        here; the serve path uses :meth:`prefill_handoff`'s ref +
        replica-directory pull instead, where task-spec capture pins it
        transiently)."""
        import ray_tpu  # noqa: F401 — parity of env with prefill_handoff
        prompt = list(req["prompt"])
        opts = req.get("opts") or {}
        params = self._params(opts)
        if deadlines.expired():
            raise DeadlineExceededError(
                "deadline exceeded before prefill started")
        loop = asyncio.get_running_loop()
        async with self._lock:
            blob, first = await loop.run_in_executor(
                None, lambda: self.engine.prefill_only(prompt, params))
        return {"blob": blob, "first": first, "opts": opts,
                "prompt": prompt}

    async def _resolve_handoff(self, handoff: dict):
        ref = handoff.get("ref")
        if ref is not None:
            # Arena-to-arena pull: the get resolves against the OWNER
            # (the prefill replica worker), whose directory stamps every
            # holder into from_addrs — no proxy hop, no GCS lookup.
            return await ref
        return handoff["blob"]

    async def admit_external(self, handoff: dict) -> int:
        """Compiled-DAG decode stage: resolve the KV handoff and admit it
        into the continuous batch, returning the request id WITHOUT
        waiting for completion — the DAG step stays cheap (admission
        only) so consecutive requests pipeline through the prefill stage
        while this replica decodes.  Tokens are collected with
        :meth:`collect` / :meth:`collect_stream`."""
        blob = await self._resolve_handoff(handoff)
        rid, _ = await self._enqueue(
            None, handoff.get("opts"), external=(blob, handoff["first"]),
            cache_prompt=handoff.get("prompt"))
        return rid

    async def collect(self, rid: int) -> Dict[str, Any]:
        """Drain an admitted request's stream to completion:
        ``{"tokens": [...], "finish_reason": ..., "n_tokens": ...,
        "timing": {...}}``."""
        return await self._whole(self.collect_stream(rid))

    async def collect_stream(self, rid: int):
        """Async generator over an admitted request: int tokens, then one
        terminal ``{"finish_reason", "n_tokens", "timing"}`` dict.
        Dispatch with ``num_returns="streaming"`` for live token streaming
        — the steady-state per-token path is engine tick → waiter queue →
        worker→owner stream frames: no GCS work per token."""
        q = self._waiters.get(rid)
        if q is None:
            from ..exceptions import RayError
            raise RayError(f"unknown or already-collected request {rid}")
        it = self._items(rid, q)
        try:
            async for item in it:
                yield item
        finally:
            await it.aclose()           # as `stream_generate`

    async def decode_handoff(self, handoff: dict) -> Dict[str, Any]:
        """Decode half over a handoff (direct arena pull): admit through
        the SAME deadline-aware queue as local requests, decode to
        completion."""
        rid = await self.admit_external(handoff)
        return await self.collect(rid)

    async def decode(self, kv_blob: dict, first_token: int,
                     opts: Optional[dict] = None,
                     prompt_tokens: Optional[Sequence[int]] = None
                     ) -> Dict[str, Any]:
        """Decode half: admit a shipped KV blob through the SAME
        admission queue as local requests (deadline-aware, shed-bounded)
        and decode to completion."""
        return await self._whole(self._items(*await self._enqueue(
            None, opts, external=(kv_blob, first_token),
            cache_prompt=prompt_tokens)))

    # ------------------------------------------ cross-host paged KV (SP) ---
    async def prefill_paged_chunk(self, req: dict) -> dict:
        """ONE sequence-parallel prefill shard's unit of work: compute a
        chunk's KV stripe against the already-published context parts
        (pulled through the gather window — cross-host when a part lives
        in a peer shard's arena), publish the stripe into THIS replica's
        arena, and return only its 20-byte ref.  ``req = {"chunk",
        "pos0", "parts", "span", "is_last", "opts"}``; the returned part
        dict drops straight into the next shard's ``parts`` list and
        into the decode handoff.  The LAST chunk also samples the
        prompt's first output token (its queries end at the prompt's
        real last token).  serve_patterns.LongContextApp round-robins
        these across N shard replicas so no single node's arena (or
        pool) ever holds the whole context."""
        import ray_tpu
        chunk = list(req["chunk"])
        pos0 = int(req["pos0"])
        span = int(req.get("span") or self.paged_span)
        parts = list(req.get("parts") or [])
        is_last = bool(req.get("is_last"))
        if deadlines.expired():
            raise DeadlineExceededError(
                "deadline exceeded before prefill chunk started")
        loop = asyncio.get_running_loop()
        first = None
        async with self._lock:
            part, logits = await loop.run_in_executor(
                None, lambda: self.engine.prefill_paged_chunk(
                    chunk, pos0, parts, span=span, is_last=is_last))
            if is_last and logits is not None:
                # Inside the lock: sampling advances the engine RNG and
                # blocks on a device->host pull — both must not race the
                # decode loop's ticks (the one-FIFO-lock invariant).
                params = self._params(req.get("opts"))
                first = await loop.run_in_executor(
                    None, lambda: self.engine.sample_first(logits, params))
        out = {"span": (pos0, pos0 + len(chunk)),
               "handle": ray_tpu.put(part)}
        if first is not None:
            out["first"] = int(first)
        return out

    async def prefill_paged_handoff(self, req: dict) -> dict:
        """Whole-prompt streamed chunked prefill on this one replica —
        the single-shard form of the paged path: every stripe is
        published into this replica's arena and the handoff carries only
        refs, so the decode side pulls arena-to-arena and the proxy
        never touches KV bytes.  ``req = {"prompt", "opts", "span"?}``;
        returns ``{"parts", "len", "first", "opts"}`` for
        :meth:`decode_paged` / :meth:`admit_paged`."""
        import ray_tpu
        prompt = list(req["prompt"])
        opts = req.get("opts") or {}
        span = int(req.get("span") or self.paged_span)
        params = self._params(opts)
        if deadlines.expired():
            raise DeadlineExceededError(
                "deadline exceeded before prefill started")
        loop = asyncio.get_running_loop()
        async with self._lock:
            handoff = await loop.run_in_executor(
                None, lambda: self.engine.prefill_paged(
                    prompt, params, span=span,
                    publish=lambda part: ray_tpu.put(part)))
        handoff["opts"] = opts
        return handoff

    async def admit_paged(self, handoff: dict) -> int:
        """Admit a paged handoff (context KV in external parts — local
        or REMOTE arenas) into the continuous batch through the SAME
        deadline-aware, shed-bounded queue as every other request;
        returns the request id for :meth:`collect` /
        :meth:`collect_stream`.  Only the decode tail occupies this
        node's pool pages."""
        params = self._params(handoff.get("opts"))
        deadline = deadlines.get()
        t_in = clocks.mono_ns()
        async with self._lock:
            self._maybe_shed(deadline)
            rid = self.engine.add_paged_request(
                handoff["parts"], handoff["len"], handoff["first"],
                params, prompt_tokens=handoff.get("prompt"))
            self._track(rid, deadline, t_in)
        return rid

    async def decode_paged(self, handoff: dict) -> Dict[str, Any]:
        """Decode a paged handoff to completion.  A KV part whose host
        is lost mid-decode raises :class:`StreamBrokenError` (carrying
        ``tokens_emitted``) out of this call — never a wrong token."""
        rid = await self.admit_paged(handoff)
        return await self.collect(rid)

    # ------------------------------------------------------------- introspect
    async def debug_stats(self) -> Dict[str, Any]:
        """Counters of this replica and its engine.  `decode` is
        `LLMEngine.decode_stats()`: the pages the decode steps read, and how
        often the host had to write slot rows into the step's
        device-resident state (`state_syncs` of `steps`, `state_rows`);
        `state` and `routed` are `LLMEngine.state_stats()` (recurrent-state
        checkpoints) and `routed_stats()` (experts the decode steps
        touched) and `latent` `latent_stats()` (cache rows the decode steps
        read, key rows the prefills attended and up-projected) and
        `retention` `retention_stats()` (sequences whose state the decode
        steps moved, prefills by form, checkpoint boundaries passed and
        kept) and `mamba` `mamba_stats()` (sequences whose SSM state the
        decode steps moved, the rows the prefills' scans ran beside the
        real ones) and `sparse` `sparse_stats()` (cached rows the decode
        steps' attention layers saw and selected, index rows read),
        `{"enabled": False}` for a model without such layers; `gc`: the
        collector's full passes in this process, the last sixteen as (wall
        clock, ms), and the objects frozen out of its sight
        (`_keep_compiled`, whose own pass after a compile is among them)."""
        e = self.engine
        return {"ticks": self._ticks, "max_active": self._max_active,
                "shed": self._shed, "cancelled": self._cancelled,
                "expired": self._expired, "completed": self._completed,
                "tokens_out": self._tokens_out,
                "kv_broken": self._kv_broken,
                "queue_depth": e.queue_depth,
                "active": e.active_requests,
                "kv_pages_free": e.kv_pages_free(),
                "kv_pages_total": e.kv_pages_total,
                "load": self.__serve_load__(),
                "prefix_cache": e.prefix_cache_stats(),
                "kv_gather": e.kv_gather_stats(),
                "decode": e.decode_stats(),
                "prefill": e.prefill_stats(),
                "state": e.state_stats(),
                "routed": e.routed_stats(),
                "latent": e.latent_stats(),
                "retention": e.retention_stats(),
                "mamba": e.mamba_stats(),
                "sparse": e.sparse_stats(),
                "gc": {"full": _GC["full"], "passes": list(_GC["passes"]),
                       "frozen": gc.get_freeze_count()},
                "tick": self._phases.snapshot()}

    async def pid(self) -> int:
        import os
        return os.getpid()

    async def device_info(self) -> Dict[str, Any]:
        """Platform, device kind and count, peak device memory, leased
        chip ids and compile-cache counts of this replica's process."""
        from ..tpu.accelerator import device_report
        return device_report()


# ---------------------------------------------------------------------------
# Open-loop load harness
# ---------------------------------------------------------------------------

def _pctl(xs: List[float], p: float) -> float:
    return float(np.percentile(np.asarray(xs), p)) if xs else 0.0


def run_open_loop(submit, *, rate_hz: float, duration_s: float,
                  prompt_fn, num_replicas: int = 1,
                  request_timeout_s: float = 120.0) -> Dict[str, Any]:
    """Arrival-rate-driven load harness — OPEN loop, never closed: the
    next request is offered on schedule whether or not earlier ones
    completed, so queueing delay shows up in the latency numbers instead
    of silently throttling the offered load (the classic closed-loop
    measurement bug).

    ``submit(prompt) -> iterable`` must yield stream items (int tokens,
    then a terminal dict with ``finish_reason``); for Serve use
    ``lambda p: handle.options(stream=True).remote(p, opts)``.

    Returns a report with p50/p99 TTFT (ms), p50/p99 inter-token latency
    (ms), tokens/s (total and per replica), max concurrent in-flight
    requests, and shed/error counts."""
    n = max(1, int(rate_hz * duration_s))
    lock = threading.Lock()
    state = {"active": 0, "max_active": 0}
    results: List[Dict[str, Any]] = []
    threads: List[threading.Thread] = []
    t_start = time.perf_counter()

    def _one(i: int):
        rec: Dict[str, Any] = {"ok": False, "shed": False, "error": None,
                               "broken": False}
        with lock:
            state["active"] += 1
            state["max_active"] = max(state["max_active"], state["active"])
        t_sub = time.perf_counter()
        try:
            first = prev = None
            gaps: List[float] = []
            ntok = 0
            for item in submit(prompt_fn(i)):
                now = time.perf_counter()
                if isinstance(item, dict):
                    rec["finish_reason"] = item.get("finish_reason")
                    break
                ntok += 1
                if first is None:
                    first = now
                if prev is not None:
                    gaps.append(now - prev)
                prev = now
            rec.update(ok=True, ttft_s=(first - t_sub) if first else None,
                       total_s=time.perf_counter() - t_sub, gaps=gaps,
                       tokens=ntok)
        except OverloadedError as e:
            rec["shed"] = True
            rec["retry_after_s"] = e.retry_after_s
        except StreamBrokenError as e:
            rec["broken"] = True
            rec["tokens_emitted"] = e.tokens_emitted
        except Exception as e:  # noqa: BLE001 — the harness reports, never dies
            rec["error"] = repr(e)
        finally:
            with lock:
                state["active"] -= 1
            with lock:
                results.append(rec)

    for i in range(n):
        target = t_start + i / rate_hz
        delay = target - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        th = threading.Thread(target=_one, args=(i,), daemon=True)
        th.start()
        threads.append(th)
    deadline = time.perf_counter() + request_timeout_s
    for th in threads:
        th.join(max(0.0, deadline - time.perf_counter()))
    wall = time.perf_counter() - t_start

    done = [r for r in results if r.get("ok")]
    ttfts = [r["ttft_s"] * 1e3 for r in done if r.get("ttft_s") is not None]
    gaps = [g * 1e3 for r in done for g in r.get("gaps", ())]
    tokens = sum(r.get("tokens", 0) for r in done)
    return {
        "offered": n,
        "completed": len(done),
        "shed": sum(1 for r in results if r.get("shed")),
        "broken": sum(1 for r in results if r.get("broken")),
        "errors": [r["error"] for r in results if r.get("error")],
        "unfinished": n - len(results),
        "max_inflight": state["max_active"],
        "ttft_p50_ms": _pctl(ttfts, 50),
        "ttft_p99_ms": _pctl(ttfts, 99),
        "total_p50_ms": _pctl([r["total_s"] * 1e3 for r in done], 50),
        "itl_p50_ms": _pctl(gaps, 50),
        "itl_p99_ms": _pctl(gaps, 99),
        "tokens_total": tokens,
        "duration_s": wall,
        "tokens_per_s": tokens / wall if wall > 0 else 0.0,
        "tokens_per_s_per_replica":
            tokens / wall / max(1, num_replicas) if wall > 0 else 0.0,
    }
