"""Per-process core runtime: task submission, objects, actors.

Equivalent of the reference's CoreWorker (reference:
src/ray/core_worker/core_worker.h:167) linked into every driver and worker:

- task submission with per-scheduling-key lease caching and pipelining
  (reference: task_submission/normal_task_submitter.cc:70 — leases are reused
  for tasks with the same scheduling key; here we additionally pipeline a
  small number of pushes per leased worker to hide RPC latency)
- dependency resolution: pending/small args are awaited and inlined into the
  spec; large args travel by reference (reference: dependency_resolver.cc)
- in-process memory store for small results + shared-memory store for large
  ones (reference: memory_store/ + plasma_store_provider.h)
- ownership: the submitting process owns task returns and puts, serves their
  values to borrowers over its RPC server, and frees primary copies when
  reference counts drop to zero (reference: reference_count.cc)
- actor task submission over direct worker connections with per-handle
  sequence numbers (reference: actor_task_submitter.cc,
  sequential_actor_submit_queue.cc)

The driver runs the asyncio loop on a daemon thread and the public sync API
bridges via run_coroutine_threadsafe; workers run the loop in the foreground
(worker_main.py) and execute user code on executor threads.
"""

from __future__ import annotations

import asyncio
import concurrent.futures
import logging
import os
import threading
import time
import traceback
import weakref
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Optional, Tuple

from .. import exceptions as exc
from ..object_ref import ObjectRef
from . import clocks, deadlines, protocol, rpc
from . import flight_recorder as frec
from .config import get_config
from .ids import (ActorID, JobID, ObjectID, TaskID, WorkerID,
                  fast_actor_task_id)
from .memory_store import MemoryStore
from .reference_counter import ReferenceCounter
from .serialization import get_context
from .shm_store import ShmStore, StoreFullError
from .streaming import ObjectRefGenerator, StreamState, item_object_id

logger = logging.getLogger("ray_tpu.core_worker")

# Set (by worker_main._run_sync) on executor threads while USER task code
# runs: a get()/wait() that is about to block on such a thread notifies
# the node agent so the lease's CPU is released for queued work
# (reference: NotifyDirectCallTaskBlocked, core_worker.cc — deadlock
# avoidance for tasks that block on results of tasks they submitted).
task_exec_tls = threading.local()


def _release_read_pin(store: ShmStore, oid: bytes) -> None:
    """weakref.finalize target for _pinned views: runs from GC on any
    thread, possibly at interpreter exit after the store detached — both
    must be harmless (the C refcount ops are atomic; a freed object's
    release is a no-op in the store)."""
    try:
        if store._h is not None:
            store.release(oid)
    except Exception:
        pass

# Floor of the ADAPTIVE in-flight window per leased worker.  A granted
# lease still RUNS one task at a time (the worker's task lock serializes
# execution, matching reference semantics); pipelined pushes hide the
# push/complete round trip so tiny-task throughput isn't bounded by
# per-task RTT.  The window starts here and grows toward
# max_tasks_in_flight_per_worker while observed latency stays low
# (_note_task_latency), shrinking back on backpressure or lease loss —
# so long tasks never pile onto one worker while other nodes idle, the
# queue drains back through _pump when a lease dies, and queued-at-worker
# tasks remain cancellable (_cancel_requested check before execution).
PIPELINE_DEPTH = 3


class _PendingTask:
    __slots__ = ("spec", "ref_args", "borrowed_args")

    def __init__(self, spec: dict, ref_args: List[bytes],
                 borrowed_args: Optional[List[tuple]] = None):
        self.spec = spec
        self.ref_args = ref_args  # owned object ids pinned while in flight
        # (oid, owner_addr) pairs of borrowed refs nested in arg values:
        # escape-pinned at the remote owner until the reply lands.
        self.borrowed_args = borrowed_args or []


class _Lease:
    __slots__ = ("lease_id", "worker_addr", "worker_id", "conn", "inflight",
                 "agent_conn", "idle_since", "epoch")

    def __init__(self, lease_id, worker_addr, worker_id, conn, agent_conn,
                 epoch=0):
        self.lease_id = lease_id
        self.worker_addr = worker_addr
        self.worker_id = worker_id
        self.conn = conn
        self.agent_conn = agent_conn
        self.inflight = 0
        self.idle_since = time.monotonic()
        # Cluster epoch the grant was minted under (GCS HA fencing):
        # idle leases from an older epoch are dropped on epoch bump.
        self.epoch = epoch


class _KeyState:
    __slots__ = ("queue", "leases", "pending_lease_requests", "resources",
                 "strategy", "runtime_env", "last_demand_report",
                 "lease_backoff_until", "pump_scheduled", "avg_task_s",
                 "prefix", "prefix_blob", "window")

    def __init__(self, resources, strategy, runtime_env=None):
        self.queue: deque[_PendingTask] = deque()
        self.leases: List[_Lease] = []
        self.pending_lease_requests = 0
        self.resources = resources
        self.strategy = strategy
        self.runtime_env = runtime_env
        self.last_demand_report = 0.0
        self.lease_backoff_until = 0.0
        self.pump_scheduled = False
        # EMA of push->complete latency; drives the adaptive window.
        self.avg_task_s: Optional[float] = None
        # Stable spec prefix shared by every task of this key (and its
        # one-time msgpack encoding) — see protocol.spec_prefix_of.
        # Seeded from RemoteFunction._submit_cache when available, else
        # built from the first pushed spec.
        self.prefix: Optional[dict] = None
        self.prefix_blob: Optional[bytes] = None
        # Adaptive in-flight window per lease (PIPELINE_DEPTH ..
        # max_tasks_in_flight_per_worker): grows on low RTT, shrinks on
        # transport backpressure / lease loss.
        self.window = PIPELINE_DEPTH


class _ActorState:
    __slots__ = ("actor_id", "address", "conn", "seq", "dead", "death_cause",
                 "resolving", "submit_queue", "draining", "drain_scheduled",
                 "out_of_order", "prefix", "prefix_blob")

    def __init__(self, actor_id: bytes):
        # Stable spec prefix for this handle's calls (the actor-method
        # equivalent of RemoteFunction's submit cache): method/seq/args
        # travel as per-call deltas.
        self.prefix: Optional[dict] = None
        self.prefix_blob: Optional[bytes] = None
        self.actor_id = actor_id
        self.address = None
        self.conn: Optional[rpc.Connection] = None
        self.seq = 0
        self.dead = False
        self.death_cause = ""
        self.resolving: Optional[asyncio.Future] = None
        # Per-actor submission pipeline: oversized-arg plasma puts complete
        # in order before the push is scheduled, so a later small-arg call
        # cannot overtake an earlier large-arg one.
        self.submit_queue: deque = deque()
        self.draining = False
        self.drain_scheduled = False
        # allow_out_of_order_execution actors use the out-of-order submit
        # queue: dep resolution per call, no head-of-line blocking
        # (reference: out_of_order_actor_submit_queue.cc vs
        # sequential_actor_submit_queue.cc).
        self.out_of_order = False


class CoreWorker:
    def __init__(self, *, mode: str, gcs_address, agent_address,
                 store_path: str, node_id: bytes, session_dir: str,
                 job_id: Optional[bytes] = None,
                 worker_id: Optional[bytes] = None):
        self.mode = mode
        self.gcs_address = tuple(gcs_address)
        self.agent_address = tuple(agent_address)
        self.node_id = node_id
        self.session_dir = session_dir
        # Cluster epoch (GCS HA fencing, docs/control_plane.md §8):
        # learned from grants/rejections, stamped into every lease
        # request so a fenced-off owner is told to refresh instead of
        # silently acting on a pre-failover view.
        self.cluster_epoch = protocol.EPOCH_NONE
        self.stale_epoch_rejections = 0
        self.worker_id = worker_id or WorkerID.from_random().binary()
        self.job_id = job_id
        self.store = ShmStore.attach(store_path)
        self.memory_store = MemoryStore()
        # ref id -> device array (RDT equivalent; experimental/).
        self.device_objects: Dict[bytes, Any] = {}
        self.reference_counter = ReferenceCounter(self._on_ref_zero)
        self.current_task_id: bytes = b""
        # Owner task for puts made outside any executing task (threads the
        # user starts inside actors); minted lazily once job_id is known.
        self._process_task_id_cache: Optional[bytes] = None
        self.current_actor_id: Optional[bytes] = None  # set in actor workers
        self._put_counter = 0
        self._keys: Dict[bytes, _KeyState] = {}
        self._actors: Dict[bytes, _ActorState] = {}
        self._worker_conns: Dict[tuple, rpc.Connection] = {}
        self._owner_conns: Dict[tuple, rpc.Connection] = {}
        self._fn_cache: Dict[bytes, Any] = {}
        self._pg_cache: Dict[bytes, dict] = {}
        self._packaged_envs: Dict[str, dict] = {}
        self._pg_rr: Dict[bytes, int] = {}
        self.current_placement_group: Optional[dict] = None
        self._inflight_replies: Dict[bytes, asyncio.Future] = {}
        self._recovering: Dict[bytes, asyncio.Future] = {}
        self._cancelled: set = set()               # task ids cancelled
        # Task ids whose end-to-end deadline fired owner-side: their
        # return refs already resolved to DeadlineExceededError, so a
        # late reply (or the cancel path's TaskCancelledError) must not
        # overwrite that typed outcome — only bookkeeping runs.
        self._deadline_expired: set = set()
        # task_id -> armed call_later handle; cancelled when the task
        # resolves so a deadline can never fire on a task that already
        # completed (and whose freed return entries it would resurrect).
        self._deadline_timers: Dict[bytes, Any] = {}
        # task_id -> asyncio.Task finishing a deferred submission (fn
        # export / dep resolution); _cancel interrupts these directly.
        self._resolving: Dict[bytes, asyncio.Task] = {}
        # task_id -> StreamState for in-flight streaming generators we own.
        self._streams: Dict[bytes, StreamState] = {}
        self._inflight_tasks: Dict[bytes, _Lease] = {}        # normal tasks
        self._inflight_actor_tasks: Dict[bytes, _ActorState] = {}
        # task_id -> completion record for batched pushes: ("n", key,
        # state, lease, task, t_push) for normal tasks, ("a", astate,
        # conn, task, t_push) for actor calls (the conn the batch was
        # pushed on — astate.conn may already point at a reconnect by
        # the time the old conn's loss cleanup runs).  Resolved by
        # complete_batch frames (_f_complete_batch) or by
        # connection-loss cleanup.
        self._pending_replies: Dict[bytes, tuple] = {}
        # actor_id -> future of an in-flight background registration this
        # process initiated; _actor_conn awaits it instead of polling GCS.
        self._registering: Dict[bytes, asyncio.Future] = {}
        # Task status/profile events, flushed to the GCS sink periodically
        # (reference: core_worker/task_event_buffer.h:297 AddTaskEvent /
        # FlushEvents). Bounded: drops oldest under pressure — counted,
        # reported with every flush (no silent caps).
        self._task_events: deque = deque(maxlen=10000)
        self._task_events_dropped = 0
        # Flight-recorder rows whose flush notify failed, kept for the
        # next telemetry tick (bounded at ring capacity; overflow folds
        # into the recorder's drop counter — no silent loss).
        self._frec_retry: List[dict] = []
        self._seq_lock = threading.Lock()   # seq/put-id minting, any thread
        # Cross-thread submission mailbox: caller threads append closures
        # and schedule ONE loop wakeup per burst instead of one
        # call_soon_threadsafe (self-pipe write + epoll wake) per call —
        # the dominant submit-side syscall cost under task fan-out.
        self._mailbox: deque = deque()
        self._mailbox_scheduled = False
        # channel -> [callback] for GCS pubsub fan-in (see subscribe()).
        self._pubsub_handlers: Dict[str, list] = {}
        self._gcs_subscribed: set = set()   # channels subscribed at GCS
        self.loop: Optional[asyncio.AbstractEventLoop] = None
        self._loop_thread: Optional[threading.Thread] = None
        self.gcs: Optional[rpc.Connection] = None
        self.agent: Optional[rpc.Connection] = None
        self.address: Optional[tuple] = None
        self._server: Optional[rpc.RpcServer] = None
        self.executor = ThreadPoolExecutor(
            max_workers=64, thread_name_prefix="ray_tpu_exec")
        self._shutdown = False
        # Hung-task tracker (diagnosis plane); armed by the worker main
        # when diagnosis_enabled — record_task_event feeds it.
        self._diag_tracker = None
        cfg = get_config()
        self._inline_limit = cfg.max_direct_call_object_size
        self._max_inflight = max(PIPELINE_DEPTH,
                                 cfg.max_tasks_in_flight_per_worker)
        self._ack_timeout = cfg.submit_batch_ack_timeout_s
        ctx = get_context()
        ctx.ref_factory = self._ref_factory
        ctx.ref_hook = self._ref_serialized_hook

    # ------------------------------------------------------------ lifecycle --
    def start_driver(self):
        """Start the loop on a daemon thread and connect (driver mode)."""
        ready = threading.Event()

        def _run():
            self.loop = asyncio.new_event_loop()
            asyncio.set_event_loop(self.loop)
            rpc.enable_eager_tasks(self.loop)
            self.loop.run_until_complete(self._connect())
            ready.set()
            self.loop.run_forever()

        self._loop_thread = threading.Thread(target=_run, daemon=True,
                                             name="ray_tpu_io")
        self._loop_thread.start()
        if not ready.wait(30):
            raise TimeoutError("driver core worker failed to start")
        if self.job_id is None:
            self.job_id = JobID.from_int(
                self._run(self.gcs.call("next_job_id", {}))).binary()
        self.current_task_id = TaskID.for_driver(JobID(self.job_id)).binary()
        self._run(self.gcs.call("register_job", {
            "job_id": self.job_id, "driver_addr": list(self.address)}))

    async def start_in_loop(self):
        """Connect using the already-running loop (worker mode)."""
        self.loop = asyncio.get_running_loop()
        rpc.enable_eager_tasks(self.loop)
        await self._connect()

    async def _connect(self):
        # Chaos wiring: the rpc_chaos config ('Method=N:req%:resp%',
        # reference: rpc_chaos.cc RAY_testing_rpc_failure) applies to
        # every process whose config carries it — set
        # RAY_TPU_rpc_chaos in the environment to inject cluster-wide.
        # Unconditional: an empty spec CLEARS injection, so a chaos-free
        # init() after a chaos session in the same process doesn't
        # inherit the old rules through the module global.
        cfg = get_config()
        rpc.enable_chaos(cfg.rpc_chaos)
        rpc.enable_link_chaos(cfg.link_chaos)
        # Wire hot path: resolve the framer mode ONCE per process from
        # config (a per-node _system_config reaches workers through the
        # agent-forwarded env) so every connection this process opens
        # agrees — mixed modes are a per-NODE property, never per-conn.
        rpc.enable_native_framer(cfg.rpc_native_framer)
        # Gray-failure defense: unary control calls get a default bound
        # so a half-open connection can never hang this process forever
        # (explicit timeout=0 at a call site opts out).
        rpc.set_default_call_timeout(cfg.control_call_timeout_s)
        self._server = rpc.RpcServer(self._handlers(), name=f"cw-{self.mode}")
        self.address = await self._server.start_tcp("127.0.0.1", 0)
        # Reconnecting: calls issued across a GCS restart re-dial and
        # retry once (mutations are id-keyed upserts, so replays are
        # idempotent).
        self.gcs = rpc.ReconnectingConnection(
            self.gcs_address, name="cw->gcs",
            handlers={"pubsub": self.h_pubsub},
            on_reconnect=self._resubscribe,
            # GCS failover re-homing: every dial re-reads the session's
            # advertised-address file, so a promoted standby (new port)
            # is found through the same jittered reconnect backoff.
            resolver=lambda: protocol.resolve_gcs_address(
                self.session_dir, fallback=self.gcs_address))
        await self.gcs.ensure()
        self.agent = await rpc.connect(self.agent_address, name="cw->agent")
        self._spawn(self._telemetry_flush_loop())

    def _handlers(self):
        return {
            "get_object": self.h_get_object,
            "free_notify": self.h_free_notify,
            "borrow_add": self.h_borrow_add,
            "borrow_release": self.h_borrow_release,
            "escape_pin": self.h_escape_pin,
            "escape_release": self.h_escape_release,
            "recover_object": self.h_recover_object,
            "object_locations": self.h_object_locations,
            "object_location_add": self.h_object_location_add,
            "object_location_remove": self.h_object_location_remove,
            "device_fetch": self.h_device_fetch,
            "device_free": self.h_device_free,
            "stream_item": self.h_stream_item,
            "stream_end": self.h_stream_end,
            "ping": lambda conn, p: "pong",
        }

    # Streaming generators (reference: _raylet.pyx:939 streaming-generator
    # execution; see _private/streaming.py for the wire design).
    async def h_stream_item(self, conn, p):
        """An executing generator yielded item `index`; store it under its
        deterministic id and ack — the ack is delayed while the consumer
        lags more than the configured backpressure, which stalls the
        producer's in-flight window (reference: generator_waiter.cc
        consumed-offset watermark)."""
        tid, idx, entry = p["task_id"], p["index"], p["entry"]
        st = self._streams.get(tid)
        if st is None or st.released:
            return {"dropped": True}   # consumer released the generator
        if p.get("attempt", 0) != st.expected_attempt:
            return True                # straggler from a dead attempt
        if idx < st.consumed:
            # Retry re-delivery of an item the consumer already took: its
            # ObjectRef (if still held) keeps the original value; if it was
            # dropped, the object is freed — re-storing would resurrect an
            # untracked entry that never gets released.
            return True
        oid = item_object_id(tid, idx)
        first = st.item_arrived(idx)
        if first:
            self.reference_counter.add_owned(oid)
            # Held by the stream until the consumer takes the item (or the
            # generator is released) — there is no ObjectRef yet.
            self.reference_counter.add_escape_pin(oid)
            nested = [(bytes(noid),
                       None if tuple(nowner) == self.address
                       else tuple(nowner))
                      for noid, nowner in entry.get("nested", [])]
            for noid, nowner in nested:
                if nowner is None:
                    # Our own refs nested in the item: we take the pin
                    # here, synchronously before the ack (same
                    # reply-carried-pin protocol as _handle_reply).
                    self.reference_counter.add_escape_pin(noid)
            if nested:
                self._record_contained(oid, nested, take_pins=False)
        # Duplicates (unconsumed re-delivery after a retry) refresh the
        # stored entry — the plasma copy moved to the new attempt's node —
        # but never re-take ownership or nested pins.
        if "inline" in entry:
            self.memory_store.put_inline(oid, entry["inline"])
        else:
            self.memory_store.put_plasma_location(
                oid, entry["plasma"], size=entry.get("size"))
        while (st.bp and st.unconsumed() >= st.bp and st.total is None
               and not st.released):
            ev = st.consume_event
            await ev.wait()
        return True

    async def h_stream_end(self, conn, p):
        st = self._streams.get(p["task_id"])
        if st is not None and p.get("attempt", 0) == st.expected_attempt:
            st.finish(p["count"], bool(p.get("errored")))
        return True

    async def stream_next_async(self, task_id: bytes):
        st = self._streams.get(task_id)
        if st is None:
            return None
        idx = await st.next_index()
        if idx is None:
            return None
        oid = item_object_id(task_id, idx)
        ref = ObjectRef(oid, self.address, worker=self)
        # The ObjectRef's local ref now keeps the item alive; drop the
        # stream's pin (ordered: pin released only after add_local_ref).
        self.reference_counter.release_escape_pin(oid)
        return ref

    def stream_next(self, task_id: bytes):
        return self._run(self.stream_next_async(task_id))

    def stream_errored(self, task_id: bytes) -> bool:
        st = self._streams.get(task_id)
        return st is not None and st.errored

    def register_stream(self, task_id: bytes, backpressure: int = 0,
                        expected_attempt: int = 0):
        self._streams[task_id] = StreamState(task_id, backpressure,
                                             expected_attempt)

    def release_stream(self, task_id: bytes):
        """Drop a generator the consumer abandoned: free unconsumed items
        and let parked producer acks return (the producer sees `dropped`
        on its next item and stops).  Safe from any thread (__del__)."""
        st = self._streams.get(task_id)
        if st is None or st.released:
            return
        loop = self.loop
        if loop is None or loop.is_closed():
            return

        def _release():
            stt = self._streams.pop(task_id, None)
            if stt is None or stt.released:
                return
            stt.released = True
            stt.consume_event.set()
            stt.event.set()
            for idx in stt.seen:
                if idx >= stt.consumed:
                    oid = item_object_id(task_id, idx)
                    self.reference_counter.release_escape_pin(oid)
                    self.memory_store.delete(oid)

        loop.call_soon_threadsafe(_release)

    def _stream_reset_for_retry(self, spec):
        if spec.get("streaming"):
            st = self._streams.get(spec["task_id"])
            if st is not None:
                st.reset()   # the new attempt regenerates every item
                # Only messages stamped with the retried attempt (the
                # decremented retries_left) finalize the stream now.
                st.expected_attempt = spec["retries_left"]

    def _stream_on_task_failed(self, spec):
        """Task-level failure (error reply, crash out of retries, cancel):
        finalize so iteration drains arrived items then raises the
        completion ref's stored exception."""
        st = self._streams.get(spec["task_id"])
        if st is not None and st.total is None:
            st.finish(st.produced, errored=True)

    # Device-resident objects (RDT equivalent — see experimental/
    # device_objects.py; reference: gpu_object_manager).  Transfers are
    # CHUNKED: one msgpack frame per chunk keeps multi-GB arrays under
    # the RPC frame cap (like the agent's object plane, h_pull_object).
    _DEVICE_CHUNK = 64 * 1024 * 1024

    async def h_device_fetch(self, conn, p):
        entry = self.device_objects.get(p["object_id"])
        if entry is None:
            return None
        offset = p.get("offset", 0)
        import numpy as np

        def _stage():
            # Device->host readback + copy off the event loop: a multi-GB
            # transfer must not stall the owner's RPC handling.
            arr = np.asarray(entry)
            flat = arr.reshape(-1).view(np.uint8)
            total = flat.nbytes
            chunk = bytes(flat[offset:offset + self._DEVICE_CHUNK])
            # Copy audit: count the staged bytes actually shipped (per
            # chunk, so the cumulative series equals bytes transferred).
            from . import device_plane
            device_plane.record_d2h(len(chunk))
            return {"data": chunk, "total": total, "offset": offset,
                    "dtype": str(arr.dtype), "shape": list(arr.shape)}

        return await asyncio.get_running_loop().run_in_executor(
            self.executor, _stage)

    async def h_device_free(self, conn, p):
        self.device_objects.pop(p["object_id"], None)
        return True

    # Owner-side borrower-ledger service (reference: reference counting RPCs
    # folded into CoreWorkerService).
    async def h_borrow_add(self, conn, p):
        # Same staleness/resurrection rules as the reply path: never
        # recreate a freed ref record, honor release tombstones by epoch.
        self.reference_counter.add_borrower_from_reply(
            p["object_id"], p["worker_id"], epoch=p.get("epoch", 0))
        return True

    async def h_borrow_release(self, conn, p):
        self.reference_counter.remove_borrower(
            p["object_id"], p["worker_id"], epoch=p.get("epoch", 0))
        return True

    async def h_escape_pin(self, conn, p):
        self.reference_counter.add_escape_pin(p["object_id"])
        return True

    async def h_escape_release(self, conn, p):
        self.reference_counter.release_escape_pin(p["object_id"])
        return True

    async def h_recover_object(self, conn, p):
        """A borrower lost the primary copy: reconstruct it for them."""
        return await self._recover_object(p["object_id"])

    def shutdown(self):
        if self._shutdown:
            return
        self._shutdown = True
        if self.loop and self._loop_thread:
            def _drain_and_stop():
                # Cancel background tasks and WAIT (bounded) for them to
                # unwind before stopping, so teardown is quiet (no 'Task
                # was destroyed but it is pending') — a task awaiting a
                # nested future needs several loop iterations to finish
                # cancelling, not one.
                async def _finish():
                    cur = asyncio.current_task()
                    tasks = [t for t in asyncio.all_tasks() if t is not cur]
                    for t in tasks:
                        t.cancel()
                    if tasks:
                        await asyncio.wait(tasks, timeout=1.0)
                    self.loop.stop()
                rpc.spawn(_finish())
            self.loop.call_soon_threadsafe(_drain_and_stop)
            self._loop_thread.join(timeout=5)
        self.executor.shutdown(wait=False)
        self.store.close()

    def gcs_call(self, method: str, payload: dict, timeout: float = 60):
        """Synchronous GCS RPC for API modules (placement groups, state)."""
        return self._run(self.gcs.call(method, payload, timeout=timeout))

    def _spawn(self, coro) -> asyncio.Task:
        """ensure_future with a strong reference held until completion."""
        return rpc.spawn(coro)

    # -------------------------------------------------------------- pubsub --
    async def h_pubsub(self, conn, p):
        for cb in list(self._pubsub_handlers.get(p["channel"], [])):
            try:
                cb(p["message"])
            except Exception:
                logger.exception("pubsub callback failed (%s)", p["channel"])
        return True

    async def _resubscribe(self, conn):
        # Snapshot: a concurrent first-time subscribe() may add channels
        # while we await (set-changed-during-iteration otherwise).
        for channel in list(self._gcs_subscribed):
            await conn.call("subscribe", {"channel": channel})

    def subscribe(self, channel: str, callback) -> None:
        """Register callback(message) for a GCS pubsub channel (reference:
        GcsSubscriber). Thread-safe; callbacks run on the event loop.
        Subscriptions survive GCS reconnects (re-registered in
        _resubscribe)."""
        def _do():
            self._pubsub_handlers.setdefault(channel, []).append(callback)
            if channel not in self._gcs_subscribed:
                # Once per (connection, channel): the GCS appends the conn
                # to the channel's subscriber list unconditionally, so a
                # re-subscribe would duplicate every notify.
                self._gcs_subscribed.add(channel)
                self._spawn(self.gcs.call("subscribe",
                                          {"channel": channel}))
        if self._on_loop_thread():
            _do()
        else:
            self.loop.call_soon_threadsafe(_do)

    def unsubscribe(self, channel: str, callback) -> None:
        """Remove a callback registered with subscribe() (thread-safe).
        The GCS-side channel subscription persists (harmless: messages
        with no local handlers are dropped)."""
        def _do():
            lst = self._pubsub_handlers.get(channel)
            if lst and callback in lst:
                lst.remove(callback)
            if lst is not None and not lst:
                del self._pubsub_handlers[channel]
        if self._on_loop_thread():
            _do()
        else:
            self.loop.call_soon_threadsafe(_do)

    def publish(self, channel: str, message) -> None:
        """Fire-and-forget publish (thread-safe)."""
        def _do():
            self._spawn(self.gcs.call("publish", {
                "channel": channel, "message": message}))
        if self._on_loop_thread():
            _do()
        else:
            self.loop.call_soon_threadsafe(_do)

    def _post_to_loop(self, fn) -> None:
        """Run `fn` on the event loop, coalescing a burst of cross-thread
        posts into one loop wakeup.  deque.append is GIL-atomic; the
        flag race (append landing as the drain exits) is closed by the
        drain's re-check."""
        self._mailbox.append(fn)
        if not self._mailbox_scheduled:
            self._mailbox_scheduled = True
            self.loop.call_soon_threadsafe(self._drain_mailbox)

    def _drain_mailbox(self) -> None:
        mb = self._mailbox
        while mb:
            try:
                fn = mb.popleft()
            except IndexError:
                break   # raced another drain
            try:
                fn()
            except Exception:
                logger.exception("mailbox callback failed")
        self._mailbox_scheduled = False
        if mb:
            # An append raced the flag reset: make sure it runs.
            self._mailbox_scheduled = True
            self.loop.call_soon(self._drain_mailbox)

    async def _agent_list_objects(self, agent_addr: tuple,
                                  limit: int = 10_000):
        conn = await rpc.connect(agent_addr, name="cw->agent-state",
                                 retries=2)
        try:
            return await conn.call("list_objects", {"limit": limit},
                                   timeout=20)
        finally:
            await conn.close()

    # ---------------------------------------------------------- telemetry ---
    def record_task_event(self, task_id: bytes, name: str, event: str,
                          **extra):
        """Buffer one task status/profile event; any thread. Stored as a
        tuple — the flush loop expands to the wire dict, so the per-call
        hot path pays one append instead of a 7-key dict build.

        Stamps clocks.wall() (skew-injectable) so cross-node alignment
        applies to these events like every other telemetry source.  The
        deque's silent oldest-drop is counted: the total rides every
        flush to the GCS sink, which surfaces it through the state API
        instead of presenting a truncated stream as complete."""
        if len(self._task_events) == self._task_events.maxlen:
            self._task_events_dropped += 1
        self._task_events.append(
            (task_id, name, event, clocks.wall(), extra or None))
        if self._diag_tracker is not None:
            self._diag_tracker.note(task_id, name, event)

    async def _telemetry_flush_loop(self):
        """Periodic push of buffered task events + metric deltas to the
        GCS sinks (reference: TaskEventBuffer::FlushEvents +
        metrics_agent)."""
        from ..util import metrics as _metrics
        interval = get_config().task_event_flush_interval_s
        export_metrics = get_config().metrics_export_enabled
        while not self._shutdown:
            await asyncio.sleep(interval)
            recorder_rows = self._frec_retry + frec.recorder().drain(
                node_id=self.node_id or b"",
                worker_id=self.worker_id or b"")
            self._frec_retry = []
            if self._task_events or recorder_rows:
                raw = []
                while self._task_events:
                    raw.append(self._task_events.popleft())
                wid, nid, jid = self.worker_id, self.node_id, \
                    self.job_id or b""
                batch = []
                for task_id, name, event, ts, extra in raw:
                    rec = {"task_id": task_id, "name": name, "event": event,
                           "ts": ts, "worker_id": wid, "node_id": nid,
                           "job_id": jid}
                    if extra:
                        rec.update(extra)
                    batch.append(rec)
                # Flight-recorder rows ride the SAME batched notify —
                # the no-new-per-event-RPCs discipline.
                batch.extend(recorder_rows)
                try:
                    # Pre-packed blob: the GCS stores it opaquely (no
                    # per-event msgpack decode on its loop) and expands
                    # lazily at query time — under actor-call fan-out the
                    # event stream is ~3 events/call and GCS-side decode
                    # was a measurable share of the core's CPU.
                    self.gcs.notify("task_events", {
                        "blob": rpc._pack(batch), "n": len(batch),
                        "src": wid,
                        "dropped": (self._task_events_dropped
                                    + frec.recorder().dropped)})
                except Exception:
                    # Transient GCS outage: put the batch back for the
                    # next interval (deque maxlen bounds memory), and
                    # keep the recorder rows too — BOTH bounded, with
                    # overflow COUNTED (no silent loss): extendleft on
                    # a full deque evicts from the opposite (newest)
                    # end, so count what the re-queue itself sheds.
                    overflow = (len(self._task_events) + len(raw)
                                - (self._task_events.maxlen or 0))
                    if overflow > 0:
                        self._task_events_dropped += min(overflow,
                                                         len(raw))
                    self._task_events.extendleft(reversed(raw))
                    cap = frec.recorder().capacity
                    keep = recorder_rows[-cap:]
                    frec.recorder().note_lost(
                        len(recorder_rows) - len(keep))
                    self._frec_retry = keep
            snap = _metrics.registry_snapshot()
            if export_metrics:
                snap = snap + self._runtime_metrics()
            if snap:
                try:
                    self.gcs.notify("report_metrics", {
                        "worker_id": self.worker_id,
                        "node_id": self.node_id,
                        "metrics": snap})
                except Exception:
                    pass

    def _runtime_metrics(self) -> List[dict]:
        """This process's runtime series for the unified export: RPC
        io_stats, copy-audit totals, adaptive submit-window sizes, event
        drop counters.  Same row shape as util.metrics snapshots;
        node_id is stamped at the source (user metrics keep their own
        label sets) and the GCS sums counters across reporters."""
        now = time.time()
        lab = {"proc": "driver" if self.mode == "driver" else "worker",
               "node_id": (self.node_id or b"").hex()}

        def row(name, value, typ="counter", help_="", labels=None):
            return {"name": name, "type": typ, "help": help_, "ts": now,
                    "labels": labels or lab, "value": float(value)}

        out = [
            row("ray_tpu_task_events_buffer_dropped_total",
                self._task_events_dropped,
                help_="task events dropped by this process's bounded "
                      "buffer before flush"),
        ]
        # Common per-process rows (io_stats, copy audit, recorder
        # counters): shared with the agent's export so the two cannot
        # diverge.
        out.extend(frec.export_rows(lab))
        # Adaptive submit windows (control-plane pipelining depth, one
        # per scheduling key): the widest current window is the useful
        # scalar — it shows whether the pipeline opened up or is pinned
        # at the floor by backpressure.  pid label: gauges resolve
        # most-recent-wins per series, so processes sharing a label set
        # would flap among unrelated windows; distinct series per
        # submitter (bounded by the GCS's stale-reporter sweep).
        windows = [k.window for k in self._keys.values()]
        if windows:
            wlab = {**lab, "pid": str(os.getpid())}
            out.append(row("ray_tpu_submit_window_max", max(windows),
                           "gauge", labels=wlab))
            out.append(row("ray_tpu_submit_window_mean",
                           sum(windows) / len(windows), "gauge",
                           labels=wlab))
        return out

    def _run(self, coro, timeout=None):
        """Run a coroutine from a sync caller thread."""
        if self.loop is None:
            raise RuntimeError("core worker not started")
        if threading.current_thread() is self._loop_thread or (
                self._loop_thread is None
                and threading.current_thread().name == "MainThread"
                and self.mode == "worker"):
            raise RuntimeError(
                "sync API called from the event-loop thread; use `await` "
                "inside async actors")
        fut = asyncio.run_coroutine_threadsafe(coro, self.loop)
        return fut.result(timeout)

    # ------------------------------------------------------- ref plumbing ---
    def _ref_factory(self, object_id: bytes, owner_addr):
        ref = ObjectRef(object_id, owner_addr, worker=self)
        if owner_addr and tuple(owner_addr) != self.address:
            # Deserializing someone else's ref makes this process a borrower
            # (reference: reference_count.cc borrower registration; here an
            # eager borrow_add to the owner, released on local GC).
            epoch = self.reference_counter.mark_borrowed(object_id,
                                                         tuple(owner_addr))
            if epoch is not None:
                self._notify_owner(tuple(owner_addr), "borrow_add", object_id,
                                   epoch=epoch)
        return ref

    def _ref_serialized_hook(self, ref: ObjectRef):
        ctx = get_context()
        owner = ref.owner_address
        remote = None if (owner is None or tuple(owner) == self.address) \
            else tuple(owner)
        captured = ctx.capture
        if captured is not None:
            # Containment capture: the surrounding put/arg/return records
            # the pin against the container's lifetime.
            captured.append((ref.binary(), remote))
        elif remote is None:
            # Out-of-band pickle of an owned ref: permanent escape pin.
            self.reference_counter.add_escape_pin(ref.binary())
        else:
            self._notify_owner(remote, "escape_pin", ref.binary())

    def _note_device_resident(self, oid: bytes, owner) -> None:
        """A get() on this worker just re-uploaded the object's arrays
        onto OUR accelerators: register this node in the owner's
        DEVICE-TIER replica directory so locality scheduling scores
        future consumers of the ref toward this slice (above any peer
        whose copy is host-arena bytes)."""
        from . import device_plane
        n, _b = device_plane.take_rebuilt_notice()
        if not n:
            return
        if owner is None or tuple(owner) == self.address:
            self.memory_store.add_location(
                oid, self.agent_address, device=True)
        else:
            self._notify_owner(tuple(owner), "object_location_add", oid,
                               addr=list(self.agent_address), dev=True)

    def _notify_owner(self, owner: tuple, method: str, object_id: bytes,
                      **extra):
        """Fire-and-forget refcount message to an object's owner; safe from
        any thread (GC runs __del__ wherever it likes)."""
        if self.loop is None or self._shutdown:
            return

        async def _go():
            try:
                conn = await self._peer_owner(owner)
                conn.notify(method, {"object_id": object_id,
                                     "worker_id": self.worker_id, **extra})
            except Exception:
                pass

        try:
            asyncio.run_coroutine_threadsafe(_go(), self.loop)
        except RuntimeError:
            pass

    def _on_ref_zero(self, object_id: bytes, owner_addr=None,
                     borrow_epoch: int = 0):
        if owner_addr is not None:
            # Borrowed ref fully dropped: release our borrow with the owner.
            self.memory_store.delete(object_id)
            self._notify_owner(tuple(owner_addr), "borrow_release", object_id,
                               epoch=borrow_epoch)
            return
        # Owned object freed: cascade containment pins, then free the
        # primary copy.
        self._release_nested(self.reference_counter.pop_contained(object_id))
        entry = self.memory_store.get(object_id)
        self.memory_store.delete(object_id)
        if entry is not None and entry.plasma_node is not None:
            node = tuple(entry.plasma_node)
            secondaries = tuple(entry.secondaries or ())
            if self.loop and not self._shutdown:
                asyncio.run_coroutine_threadsafe(
                    self._free_plasma(node, object_id, secondaries),
                    self.loop)

    async def _free_plasma(self, agent_addr, object_id: bytes,
                           secondaries=()):
        # Replica copies free in parallel with the primary: secondaries
        # are unpinned caches (the holder may have evicted already —
        # free is idempotent), but an explicit free bounds how long
        # freed bytes linger cluster-wide.
        for sec in secondaries:
            if tuple(sec) != tuple(agent_addr):
                rpc.spawn(self._free_secondary(tuple(sec), object_id))
        try:
            conn = self.agent if agent_addr == self.agent_address else \
                await self._peer_owner(agent_addr)
            await conn.call("free_objects", {"object_ids": [object_id]})
            return
        except rpc.RpcError:
            pass
        # The recorded primary node is unreachable (e.g. it finished a
        # graceful drain and this owner never re-read the object, so
        # plasma_node was never repointed): follow the drain's relocation
        # record so the adopted pinned copy — and the KV record itself,
        # cleared by the adoptive agent's free — can't leak.
        try:
            moved = await self._migrated_location(object_id)
            if moved is not None and tuple(moved) != tuple(agent_addr):
                conn = await self._peer_owner(tuple(moved))
                await conn.call("free_objects", {"object_ids": [object_id]})
        except rpc.RpcError:
            pass

    async def _free_secondary(self, addr: tuple, object_id: bytes) -> None:
        try:
            conn = self.agent if addr == self.agent_address else \
                await self._peer_owner(addr)
            await conn.call("free_objects", {"object_ids": [object_id]})
        except (rpc.RpcError, asyncio.TimeoutError):
            pass    # evictable cache: the holder's sweep also cleans up

    async def _peer_owner(self, addr) -> rpc.Connection:
        addr = tuple(addr)
        conn = self._owner_conns.get(addr)
        if conn is None or conn.closed:
            conn = await rpc.connect(addr, name="cw->peer", retries=3)
            self._owner_conns[addr] = conn
        return conn

    # ------------------------------------------------------------- put/get --
    def put(self, value: Any) -> ObjectRef:
        """Serialize ONCE on the calling thread (also keeps multi-GB
        pickling off the event loop); small ref-free values then complete
        entirely here — a freshly minted id can have no waiters, plasma
        isn't touched, and the serialization capture is thread-local.

        Large values ALSO complete on the calling thread: the pickle-5
        parts are written straight into the shm arena in one native iov
        memcpy (GIL released), so the event loop never carries the copy
        and a put costs exactly one memory pass (reference: plasma's
        create/write-in-place/seal discipline — the Cython put path
        likewise copies on the caller).  Only the arena-full fallback
        (spill backpressure) routes through the loop."""
        from . import device_plane
        ctx = get_context()
        ctx.capture = captured = []
        device_plane.take_staged_notice()       # drain stale counts
        try:
            parts = ctx.serialize(value)
        finally:
            ctx.capture = None
        # A value containing device arrays registers this node in the
        # ref's DEVICE-TIER directory: the arrays stay resident in this
        # process, so consumers scheduled here skip the re-upload.
        staged_dev = device_plane.take_staged_notice()
        size = ctx.total_size(parts)
        cfg = get_config()
        if not captured and size <= self._inline_limit \
                and cfg.put_small_object_in_memory_store:
            oid = self._next_put_id()
            self.reference_counter.add_owned(oid)
            self.memory_store.put_inline(oid, protocol.concat_parts(parts))
            if staged_dev:
                self.memory_store.add_location(
                    oid, self.agent_address, device=True)
            return ObjectRef(oid, self.address, worker=self)
        if size > self._inline_limit and not self._on_loop_thread():
            # Zero-copy sync plasma path (containment bookkeeping is
            # thread-safe; _record_contained pins before the store).
            # Loop-thread callers fall through to _run, which raises the
            # same "use await / put_async" guard as before — with no
            # ownership state recorded and no multi-GB memcpy blocking
            # the event loop.
            oid = self._next_put_id()
            self.reference_counter.add_owned(oid)
            self._record_contained(oid, captured)
            if self._put_store_sync(oid, parts):
                self.memory_store.put_plasma_location(
                    oid, list(self.agent_address), size=size)
                if staged_dev:
                    self.memory_store.add_location(
                        oid, self.agent_address, device=True)
                return ObjectRef(oid, self.address, worker=self)
            # Arena full: loop-side backpressure/spill.  _run blocks this
            # thread until stored, so the caller may mutate its buffers
            # (which `parts` still views) only after the copy completes.
            ref = self._run(self._put_plasma_prepinned(oid, parts))
        else:
            ref = self._run(
                self._put_serialized_async(parts, captured, size))
        if staged_dev:
            self.memory_store.add_location(
                ref.binary(), self.agent_address, device=True)
        return ref

    def _put_store_sync(self, oid: bytes, parts) -> bool:
        """One native create+iov-copy+seal into shm on the CALLING thread,
        keeping the writer pin; the pin-transfer notify is posted to the
        loop (mailbox order guarantees it precedes any later free of the
        same id).  False when the arena is full — caller takes the
        backpressure path."""
        try:
            self.store.put(oid, parts, keep_pin=True)
        except StoreFullError:
            return False
        if self._on_loop_thread():
            self._send_pin_transfer(oid)
        else:
            self._post_to_loop(lambda: self._send_pin_transfer(oid))
        return True

    async def _put_plasma_prepinned(self, oid: bytes, parts) -> ObjectRef:
        """Finish a sync put whose fast path hit a full arena (ownership
        already recorded)."""
        await self._put_plasma(oid, parts)
        return ObjectRef(oid, self.address, worker=self)

    async def _put_serialized_async(self, parts, captured, size
                                    ) -> ObjectRef:
        oid = self._next_put_id()
        self.reference_counter.add_owned(oid)
        self._record_contained(oid, captured)
        cfg = get_config()
        if size <= self._inline_limit and cfg.put_small_object_in_memory_store:
            self.memory_store.put_inline(oid, protocol.concat_parts(parts))
        else:
            await self._put_plasma(oid, parts)
        return ObjectRef(oid, self.address, worker=self)

    async def put_async(self, value: Any) -> ObjectRef:
        ctx = get_context()
        ctx.capture = captured = []
        try:
            parts = ctx.serialize(value)
        finally:
            ctx.capture = None
        return await self._put_serialized_async(
            parts, captured, ctx.total_size(parts))

    def _next_put_id(self) -> bytes:
        # Minted from the driver thread (submit_actor_task) and the loop
        # thread (put/_store_big_puts) alike: always under the lock.
        with self._seq_lock:
            self._put_counter += 1
            idx = self._put_counter
        task = self.current_task_id
        if not task:
            # put() outside any executing task (e.g. a user thread inside
            # an actor, like a Tune trial's trainable thread): owned by a
            # per-process pseudo-task so ids stay well-formed.
            if self._process_task_id_cache is None:
                self._process_task_id_cache = TaskID.for_normal_task(
                    JobID(self.job_id or b"\x00\x00\x00\x00")).binary()
            task = self._process_task_id_cache
        return ObjectID.for_put(TaskID(task), idx).binary()


    def _record_contained(self, container_id: bytes, captured,
                          take_pins: bool = True):
        """Pin refs nested inside a value until the container is freed.
        take_pins=True when THIS process just serialized the value (we take
        the pins: sync for our own objects — race-free — and an ordered
        escape_pin notify for remote owners, which lands before any
        borrow_release we might later send on the same connection).
        take_pins=False when the pins were already taken by the serializing
        worker and the reply merely transfers release responsibility."""
        if not captured:
            return
        self.reference_counter.add_contained(container_id, captured)
        if take_pins:
            for noid, nowner in captured:
                if nowner is None:
                    self.reference_counter.add_escape_pin(noid)
                else:
                    self._notify_owner(nowner, "escape_pin", noid)

    # Loop-offload threshold for the arena memcpy inside
    # store_with_backpressure (below it the executor hop costs more).
    _OFFLOAD_COPY_MIN = 4 * 1024 * 1024

    async def _put_plasma(self, oid: bytes, parts):
        size = get_context().total_size(parts)
        await self.store_with_backpressure(oid, parts)
        self.memory_store.put_plasma_location(
            oid, list(self.agent_address), size=size)

    async def store_with_backpressure(self, oid: bytes, parts,
                                      owner_addr=None):
        """Create-queue backpressure (reference: plasma create_request_queue):
        on ENOMEM, ask the agent to spill pinned primaries and retry; an
        object that can never fit the arena spills straight to disk. Shared
        by puts and large task returns.

        `owner_addr` names the object's OWNER for the agent's pin records
        (drain migration tells the owner's replica directory where the
        primary moved): task returns are owned by the CALLER, so the
        executing worker passes the caller's address; puts default to
        this process.

        Pin transfer: the shm put keeps the writer's refcount and hands it
        to the agent with a one-way pin_transfer notify — the object is
        never evictable between seal and the agent's pin bookkeeping (the
        old blocking pin_object round trip had exactly that window, and
        cost a full RPC latency per large put)."""
        size = get_context().total_size(parts)
        cfg = get_config()
        deadline = time.monotonic() + cfg.create_backpressure_timeout_s
        stored = False
        refusal = None        # typed refusal from the admission queue

        def _try_store() -> bool:
            try:
                self.store.put(oid, parts, keep_pin=True)
                return True
            except StoreFullError:
                return False

        loop = asyncio.get_running_loop()
        try:
            oversized = size >= self.store.stats()["capacity"] // 2
        except Exception:
            oversized = False
        while True:
            # Multi-MB copies run on an executor thread so this (worker /
            # driver) loop keeps serving RPC during the memcpy; small ones
            # stay inline — the thread hop costs more than the copy.
            if size >= self._OFFLOAD_COPY_MIN and self.executor is not None:
                ok = await loop.run_in_executor(self.executor, _try_store)
            else:
                ok = _try_store()
            if ok:
                stored = True
                self._send_pin_transfer(oid, owner_addr)
                break
            if oversized:
                break  # can never (usefully) fit: straight to the disk tier
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                break
            # Admission queue (the CreateRequestQueue analogue): the
            # agent reserves headroom — parking us FIFO while its
            # eviction/spill sweeps make room — or refuses TYPED with a
            # retry_after_s hint.  The reservation is atomic under the
            # queue, so a racing put can't steal the freed headroom and
            # the sweep won't reclaim our in-progress region.
            try:
                res = await self.agent.call(
                    "reserve_create",
                    {"object_id": oid, "nbytes": size,
                     "timeout_s": remaining},
                    timeout=remaining + 30.0)
            except rpc.RpcError:
                res = None     # reconnect blip: legacy spill-and-retry
            if isinstance(res, dict) and not res.get("ok"):
                refusal = res
                break          # deadline/queue-full: try the disk tier
            if res is None:
                try:
                    freed = (await self.agent.call(
                        "ensure_space", {"nbytes": size}))["freed"]
                except rpc.RpcError:
                    freed = 0
                if freed == 0:
                    if time.monotonic() >= deadline:
                        break
                    await asyncio.sleep(0.05)
        if not stored:
            # Worker and agent share the host: write the spill file here
            # (off-loop) and just register it — no copy crosses the RPC.
            retry_after = float((refusal or {}).get("retry_after_s", 1.0))
            try:
                path = await self.agent.call("spill_path",
                                             {"object_id": oid})

                def _write():
                    with open(path, "wb") as f:
                        for p in parts:
                            f.write(p)

                await asyncio.get_running_loop().run_in_executor(
                    self.executor, _write)
                registered = await self.agent.call(
                    "spill_register",
                    {"object_id": oid,
                     "owner_addr": list(owner_addr or self.address)},
                    timeout=60)
            except (rpc.RpcError, OSError) as e:
                # NEVER a raw arena/IO exception out of a put: the typed
                # error carries the backoff hint and keeps accounting
                # intact (no reservation, no pin, no partial region).
                raise exc.ObjectStoreFullError(
                    f"object of size {size} does not fit and the spill "
                    f"tier failed ({e})", retry_after_s=retry_after) from e
            if not registered:
                raise exc.ObjectStoreFullError(
                    f"object of size {size} does not fit and could not "
                    f"spill", retry_after_s=retry_after)
            # Disk-spilled primaries carry no shm refcount; the agent still
            # records the owner pin so free_objects accounting matches.
            self._send_pin_transfer(oid, owner_addr)

    def _send_pin_transfer(self, oid: bytes, owner_addr=None) -> None:
        """Hand the writer-held pin to the agent. Normally a one-way notify
        on the agent connection (ordered ahead of any later free). If the
        connection is down the notify raises synchronously — release our
        pin and let the reconnect path re-pin with a blocking pin_object.
        An asynchronous loss (frame written, agent died before processing)
        is node death: the arena dies with the agent, so a leaked refcount
        in it is moot (workers watching the agent connection exit too)."""
        try:
            self.agent.notify("pin_transfer", {
                "object_id": oid,
                "owner_addr": list(owner_addr or self.address)})
        except rpc.RpcError:
            self.store.release(oid)
            rpc.spawn(self._pin_after_reconnect(oid, owner_addr))

    async def _pin_after_reconnect(self, oid: bytes,
                                   owner_addr=None) -> None:
        try:
            await self.agent.call("pin_object", {
                "object_id": oid,
                "owner_addr": list(owner_addr or self.address)})
        except rpc.RpcError:
            pass

    def get(self, refs, timeout: Optional[float] = None):
        single = isinstance(refs, ObjectRef)
        if single:
            refs = [refs]
        bad = [r for r in refs if not isinstance(r, ObjectRef)]
        if bad:
            raise TypeError(
                f"get() accepts ObjectRef or a list of ObjectRefs; got "
                f"{type(bad[0]).__name__}")
        release = self._maybe_release_cpu(refs)
        deadline = None if timeout is None else time.monotonic() + timeout
        try:
            if single:
                hit, value = self._try_get_sync(refs[0], timeout)
                if hit:
                    return value
                # Fallback continues on the SAME deadline — the sync wait
                # above already consumed part of the caller's budget.
                if deadline is not None:
                    timeout = max(0.0, deadline - time.monotonic())
            values = self._run(self._get_many(refs, timeout))
        finally:
            if release:
                self._notify_agent_blocked(False)
        return values[0] if single else values

    def _try_get_sync(self, ref: ObjectRef, timeout) -> Tuple[bool, Any]:
        """Loop-free get of ONE owned inline object: park the calling
        thread on a concurrent future the memory store resolves directly
        from its _wake — no call_soon_threadsafe wake, no Task, no gather
        (reference: the Cython get blocks on a C++ future; this is the
        Python-plane equivalent of that zero-loop hop).  Returns
        (False, None) to fall back for anything needing loop IO (plasma
        reads, borrowed refs, recovery)."""
        if self._on_loop_thread():
            return False, None           # must not block the loop
        owner = ref.owner_address
        if owner is not None and tuple(owner) != self.address:
            return False, None           # borrowed: owner RPC path
        ms = self.memory_store
        oid = ref.binary()
        entry = ms.get(oid)
        if entry is None:
            fut = ms.add_sync_waiter(oid)
            if fut is not None:
                try:
                    fut.result(timeout)
                except concurrent.futures.TimeoutError:
                    ms.discard_sync_waiter(oid, fut)
                    raise exc.GetTimeoutError(
                        f"timed out getting {oid.hex()}") from None
            entry = ms.get(oid)
        if entry is None or entry.data is None:
            return False, None           # plasma-resident: loop IO path
        from . import device_plane
        device_plane.take_rebuilt_notice()      # drain stale counts
        value = get_context().deserialize(memoryview(entry.data))
        if isinstance(value, exc.RayError):
            raise value
        self._note_device_resident(oid, None)   # owner-local fast path
        return True, value

    def _maybe_release_cpu(self, refs) -> bool:
        """In-task blocking get/wait on an executor thread: tell the agent
        to free this lease's CPU while we wait (reference:
        NotifyDirectCallTaskBlocked).  Only when some ref isn't already
        local — an all-hit get never round-trips the agent."""
        if not getattr(task_exec_tls, "active", False):
            return False
        if all(self.memory_store.contains(r.binary()) for r in refs):
            return False
        return self._notify_agent_blocked(True)

    def _notify_agent_blocked(self, blocked: bool) -> bool:
        agent = getattr(self, "agent", None)
        if agent is None or agent.closed:
            return False
        method = "worker_blocked" if blocked else "worker_unblocked"
        try:
            asyncio.run_coroutine_threadsafe(
                agent.call(method, {"worker_id": self.worker_id}), self.loop)
        except RuntimeError:          # loop shutting down
            return False
        return True

    async def get_async(self, ref: ObjectRef, timeout=None):
        return (await self._get_many([ref], timeout))[0]

    def get_future(self, ref: ObjectRef):
        return asyncio.run_coroutine_threadsafe(
            self._get_many([ref], None), self.loop)

    async def _get_many(self, refs: List[ObjectRef], timeout):
        deadline = None if timeout is None else time.monotonic() + timeout
        # A get() inside a deadline-carrying task is bounded by the
        # task's REMAINING budget even with no explicit timeout — the
        # fetch must not outlive the promise its caller made.
        amb = deadlines.remaining()
        if amb is not None:
            amb_deadline = time.monotonic() + amb
            if deadline is None or amb_deadline < deadline:
                try:
                    return await self._get_many_at(refs, amb_deadline)
                except exc.GetTimeoutError as e:
                    raise exc.DeadlineExceededError(
                        f"get() exceeded the task's end-to-end deadline: "
                        f"{e}") from None
        return await self._get_many_at(refs, deadline)

    async def _get_many_at(self, refs: List[ObjectRef], deadline):
        if len(refs) < 4:
            return await asyncio.gather(
                *[self._get_one(r, deadline) for r in refs])
        # Batched fast path: every OWNED object is tracked in the memory
        # store (inline puts, plasma puts via _put_plasma, task returns via
        # _handle_reply), so one wait_for_many future covers all pending
        # owned refs — instead of a Task+Event per ref, which dominates
        # caller-side CPU under fan-out (reference: memory_store.cc GetAsync
        # registers N callbacks on one request context for the same reason).
        self_addr = self.address
        mstore = self.memory_store
        ctx = get_context()
        objects = mstore._objects
        pending = None
        owned = [r.owner_address is None or tuple(r.owner_address) == self_addr
                 for r in refs]
        for r, own in zip(refs, owned):
            if own:
                entry = objects.get(r.binary())
                if entry is None:
                    if pending is None:
                        pending = []
                    pending.append(r.binary())
                elif entry.is_exception:
                    # Raise an already-stored error before waiting on
                    # anything else (gather's first-error semantics).
                    raise ctx.deserialize(memoryview(entry.data))
        if pending:
            remaining = None if deadline is None else max(
                0.0, deadline - time.monotonic())
            if not await mstore.wait_for_many(pending, remaining):
                raise exc.GetTimeoutError(
                    f"timed out getting {len(pending)} pending objects")
            # wait_for_many also completes EARLY when any waited-on entry
            # lands as an error: surface it now rather than decoding/IO-ing
            # the rest first (stragglers would otherwise block below).
            for oid in pending:
                entry = objects.get(oid)
                if entry is not None and entry.is_exception:
                    raise ctx.deserialize(memoryview(entry.data))
        # Inline entries decode in place; anything needing IO (plasma reads,
        # borrowed refs, recovery) keeps the concurrent per-ref path.
        out = [None] * len(refs)
        io_idx = None
        for i, r in enumerate(refs):
            entry = objects.get(r.binary()) if owned[i] else None
            if entry is not None and entry.data is not None:
                value = ctx.deserialize(memoryview(entry.data))
                if isinstance(value, exc.RayError):
                    raise value
                out[i] = value
            else:
                if io_idx is None:
                    io_idx = []
                io_idx.append(i)
        if io_idx:
            vals = await asyncio.gather(
                *[self._get_one(refs[i], deadline) for i in io_idx])
            for i, v in zip(io_idx, vals):
                out[i] = v
        return out

    async def _get_one(self, ref: ObjectRef, deadline):
        from . import device_plane
        data = await self._fetch_serialized(ref, deadline)
        device_plane.take_rebuilt_notice()      # drain stale counts
        value = get_context().deserialize(data)
        if isinstance(value, exc.RayError):
            raise value
        self._note_device_resident(ref.binary(), ref.owner_address)
        return value

    def _pinned(self, oid: bytes, view: memoryview) -> memoryview:
        """Tie a store.get read pin's lifetime to the VALUE built over it.

        Deserialize is zero-copy: the user's arrays alias the arena
        mapping, so the pin must outlive them — but it must not outlive
        them FOREVER.  Re-exporting the view through a numpy base whose
        collection releases the pin makes every downstream slice (pickle5
        buffers, ndarray views) keep the base alive; when the last one
        dies, the pin returns and the bytes become evictable/spillable
        again.  Without this, each driver get and worker arg read leaked
        one pin per object for the life of the process — under sustained
        arena oversubscription the resident set only ever grew, and every
        later put aged out its full admission deadline before reaching
        the disk tier."""
        import numpy as np
        base = np.frombuffer(view, np.uint8)
        weakref.finalize(base, _release_read_pin, self.store, oid)
        return memoryview(base).toreadonly()

    async def _fetch_serialized(self, ref: ObjectRef, deadline) -> memoryview:
        oid = ref.binary()
        owner = ref.owner_address or self.address
        recoveries = 0
        while True:
            # 1. Local memory store (owned objects / cached results).
            entry = self.memory_store.get(oid)
            if entry is not None:
                if entry.data is not None:
                    return memoryview(entry.data)
                try:
                    return await self._read_plasma(
                        oid, entry.plasma_node, deadline,
                        locations=self._ordered_locations(entry),
                        owner_addr=list(self.address))
                except exc.ObjectLostError:
                    # Primary copy gone (node death / eviction): owners
                    # re-execute the creating task from lineage (reference:
                    # object_recovery_manager.h:41). Bounded by the caller's
                    # get() deadline.
                    if tuple(owner) == self.address and recoveries < 3:
                        remaining = None if deadline is None else \
                            deadline - time.monotonic()
                        if remaining is not None and remaining <= 0:
                            raise exc.GetTimeoutError(
                                f"timed out getting {oid.hex()}") from None
                        try:
                            ok = await asyncio.wait_for(
                                self._recover_object(oid), remaining)
                        except asyncio.TimeoutError:
                            raise exc.GetTimeoutError(
                                f"timed out recovering {oid.hex()}") from None
                        if ok:
                            recoveries += 1
                            continue
                    raise
            # 2. Local shared memory.
            view = self.store.get(oid, timeout_ms=0)
            if view is not None:
                # Zero-copy; the read pin releases when the deserialized
                # value is collected (_pinned).
                return self._pinned(oid, view)
            # 3. Owner-mediated resolution.
            if tuple(owner) == self.address:
                timeout = None if deadline is None else max(
                    0.0, deadline - time.monotonic())
                entry = await self.memory_store.wait_for(oid, timeout)
                if entry is None:
                    raise exc.GetTimeoutError(f"timed out getting {oid.hex()}")
                continue
            conn = await self._peer_owner(owner)
            timeout_ms = -1 if deadline is None else int(
                max(0.0, deadline - time.monotonic()) * 1000)
            try:
                # timeout=0 opts out of the control-call default: an
                # unbounded ray.get() long-polls the owner by design
                # (the 30s-slice wait lives server-side).
                res = await conn.call(
                    "get_object", {"object_id": oid, "timeout_ms": timeout_ms},
                    timeout=0 if deadline is None else
                    max(0.1, deadline - time.monotonic()))
            except asyncio.TimeoutError:
                raise exc.GetTimeoutError(f"timed out getting {oid.hex()}")
            except rpc.ConnectionLost:
                raise exc.OwnerDiedError(
                    f"owner {owner} of {oid.hex()} is unreachable")
            if res is None:
                raise exc.GetTimeoutError(f"timed out getting {oid.hex()}")
            if "inline" in res:
                return memoryview(res["inline"])
            try:
                return await self._read_plasma(
                    oid, res["plasma"], deadline,
                    locations=res.get("locations"),
                    owner_addr=list(owner))
            except exc.ObjectLostError:
                # Borrowers can't reconstruct; ask the owner to. Bounded by
                # the caller's get() deadline.
                if recoveries < 3:
                    recoveries += 1
                    remaining = 130.0 if deadline is None else \
                        min(130.0, deadline - time.monotonic())
                    if remaining <= 0:
                        raise exc.GetTimeoutError(
                            f"timed out getting {oid.hex()}") from None
                    try:
                        if await conn.call("recover_object",
                                           {"object_id": oid},
                                           timeout=remaining):
                            continue
                    except (rpc.RpcError, asyncio.TimeoutError):
                        pass
                raise

    async def _migrated_location(self, oid: bytes):
        """Where a graceful drain republished this object's primary copy,
        per the GCS KV record the draining agent left (ns 'migrated'), or
        None.  Lets owners repoint instead of re-executing lineage — and
        covers put objects, which have no lineage at all."""
        try:
            v = await self.gcs.call(
                "kv_get", {"ns": "migrated", "key": oid.hex()}, timeout=10)
        except (rpc.RpcError, asyncio.TimeoutError):
            return None
        if not v:
            return None
        import json
        try:
            host, port = json.loads(
                v.decode() if isinstance(v, (bytes, bytearray)) else v)
            return (host, int(port))
        except (ValueError, TypeError):
            return None

    async def _recover_object(self, oid: bytes) -> bool:
        """Restore a lost object: probe the recorded primary, follow a
        drain-migrated copy, restore from the durable spill tier, and only
        then re-execute the creating task from lineage (reference:
        task_manager.h:227 ResubmitTask + object_recovery_manager.cc).
        Deduped across concurrent losses of the same id; actor task returns
        carry no lineage and are never replayed (side effects)."""
        existing = self._recovering.get(oid)
        if existing is not None:
            return await asyncio.shield(existing)
        fut = asyncio.get_running_loop().create_future()
        self._recovering[oid] = fut
        ok = False
        try:
            ok = await self._recover_object_inner(oid)
            return ok
        finally:
            if not fut.done():
                fut.set_result(ok)
            self._recovering.pop(oid, None)

    async def _recover_object_inner(self, oid: bytes) -> bool:
        # Probe first: a transient pull failure must not trigger a
        # destructive re-execution (tasks may have side effects and a
        # failed rerun would overwrite healthy sibling returns).  The
        # probe walks the LOCATION SET — primary first, then every
        # registered secondary: a dead primary with a live replica is a
        # repoint (promote the survivor), never a reconstruction.
        entry = self.memory_store.get(oid)
        if entry is not None and entry.plasma_node is not None:
            if await self._primary_alive(oid, tuple(entry.plasma_node)):
                return True
            for sec in list(entry.secondaries or ()):
                if not await self._primary_alive(oid, tuple(sec)):
                    self.memory_store.remove_location(oid, sec)
                    continue
                # A secondary survives the primary's loss: promote it.
                # adopt_primary pins the (already-present) copy so the
                # new primary can't be LRU-evicted from under us; if the
                # pin fails (copy evicted mid-probe) keep probing.
                try:
                    conn = await self._peer_owner(tuple(sec))
                    if await conn.call("adopt_primary", {
                            "object_id": oid,
                            "from_addrs": [list(sec)],
                            "owner_addr": list(self.address),
                            "priority": 0}, timeout=30):
                        entry.plasma_node = list(sec)
                        self.memory_store.remove_location(oid, sec)
                        return True
                except (rpc.RpcError, asyncio.TimeoutError):
                    pass
                self.memory_store.remove_location(oid, sec)
            # Storage-tier fast path: a registered disk holder still has
            # the bytes in its spill file even though its arena copy is
            # gone.  Ask that agent to restore direct-to-arena
            # (read_file_into), pin the restored copy, and repoint the
            # primary there — no lineage re-execution.
            for dsk in list(entry.disk_nodes or ()):
                try:
                    conn = await self._peer_owner(tuple(dsk))
                    if await conn.call("restore_object",
                                       {"object_id": oid}, timeout=60):
                        await conn.call("pin_object", {
                            "object_id": oid,
                            "owner_addr": list(self.address)}, timeout=30)
                        entry.plasma_node = list(dsk)
                        self.memory_store.remove_location(oid, dsk,
                                                          disk=True)
                        return True
                except (rpc.RpcError, asyncio.TimeoutError):
                    pass
                self.memory_store.remove_location(oid, dsk, disk=True)
        # Drain-migration fast path: a gracefully drained node republished
        # its sole primaries to a peer before exiting — repoint the
        # owner's location record and read from the new holder; no
        # reconstruction, no side effects.
        moved = await self._migrated_location(oid)
        if moved is not None and await self._primary_alive(oid, moved):
            if entry is not None:
                entry.plasma_node = list(moved)
            return True
        # Cloud-spill fast path: if a durable external copy was
        # registered (object_spill_external_uri), the LOCAL agent can
        # restore it — no destructive lineage re-execution, and it
        # works even when the spiller node is dead (reference:
        # spilled-object URLs usable cluster-wide,
        # external_storage.py).
        try:
            if await self.agent.call("restore_object",
                                     {"object_id": oid}, timeout=60):
                # The local agent is the new primary: re-pin there
                # and repoint the owner's location record.
                await self.agent.call("pin_object", {
                    "object_id": oid,
                    "owner_addr": list(self.address)})
                if entry is not None:
                    entry.plasma_node = self.agent_address
                return True
        except (rpc.RpcError, asyncio.TimeoutError):
            pass
        spec = self.reference_counter.get_lineage(oid)
        if spec is None:
            return False
        # Resubmission can only succeed if its by-reference args are
        # still resolvable (live somewhere, or themselves recoverable).
        for e in spec["args"]:
            if "ref" not in e:
                continue
            aid = bytes(e["ref"][0])
            aowner = tuple(e["ref"][1])
            if aowner == self.address and \
                    not self.memory_store.contains(aid) and \
                    not self.store.contains(aid) and \
                    self.reference_counter.get_lineage(aid) is None:
                return False
        self.memory_store.delete(oid)  # only the lost return
        respec = dict(spec)
        # Ensure at least one attempt; negative stays negative (infinite).
        rl = respec.get("retries_left", 0)
        respec["retries_left"] = rl if rl < 0 else max(rl, 1)
        key = protocol.scheduling_key(respec["fn_id"], respec["resources"],
                                      respec.get("scheduling_strategy"),
                                      respec.get("runtime_env"))
        state = self._keys.get(key)
        if state is None:
            state = self._keys[key] = _KeyState(
                respec["resources"], respec.get("scheduling_strategy"),
                respec.get("runtime_env"))
        state.queue.append(_PendingTask(respec, []))
        self._pump(key, state)
        entry = await self.memory_store.wait_for(oid, 120)
        return entry is not None

    async def _primary_alive(self, oid: bytes, agent_addr: tuple) -> bool:
        """Short-timeout probe of the agent recorded as holding the primary."""
        if agent_addr == self.agent_address:
            if self.store.contains(oid):
                return True
            try:
                return bool(await self.agent.call(
                    "object_info", {"object_id": oid}, timeout=5))
            except (rpc.RpcError, asyncio.TimeoutError):
                return False
        try:
            conn = await self._peer_owner(agent_addr)
            return bool(await conn.call("object_info", {"object_id": oid},
                                        timeout=5))
        except (rpc.RpcError, asyncio.TimeoutError):
            return False

    async def _read_plasma(self, oid: bytes, agent_addr, deadline,
                           locations=None, owner_addr=None) -> memoryview:
        """`locations` is the owner's full replica set (primary first;
        falls back to just `agent_addr`): the local agent stripes/hedges
        the pull across every holder and — given `owner_addr` — registers
        itself as a fresh secondary so the NEXT puller has one more
        source (receiver-becomes-source broadcast)."""
        view = self.store.get(oid, timeout_ms=0)
        if view is not None:
            return self._pinned(oid, view)
        if tuple(agent_addr) == self.agent_address:
            # Spilled primaries restore on demand (reference: raylet
            # RestoreSpilledObject on the get path).  Bounded retry: a
            # restore can succeed (or report already-in-store) and the
            # object be EVICTED again before this process maps it — under
            # memory pressure with concurrent restores the window is
            # real, and without the retry the tail wait below can never
            # bring the object back (nothing re-restores it).
            for _ in range(4):
                if await self.agent.call("restore_object",
                                         {"object_id": oid}, timeout=120):
                    view = self.store.get(oid, timeout_ms=0)
                    if view is not None:
                        return self._pinned(oid, view)
                    continue
                break
            # Restore failed — or succeeded 4x with the copy evicted (and
            # re-spilled) before this process mapped it.  Either way the
            # spill file is the durable copy: read it directly.
            spilled = await self._read_spilled(self.agent, oid)
            if spilled is not None:
                return spilled
            timeout_ms = 5_000 if deadline is None else int(
                min(5.0, max(0.0, deadline - time.monotonic())) * 1000)
            view = self.store.get(oid, timeout_ms=timeout_ms)
            if view is None:
                raise exc.ObjectLostError(f"{oid.hex()} not in local store")
            return self._pinned(oid, view)
        # Wall-clock deadline for the pull: the tighter of the caller's
        # get() bound (monotonic) and the ambient task deadline — carried
        # in the RPC frame and inside the payload so the agent bounds its
        # chunk fetches by the REMAINING budget.
        ambient_dl = deadlines.get()
        wall_dl = ambient_dl
        caller_dl = None
        if deadline is not None:
            caller_dl = time.time() + max(0.0, deadline - time.monotonic())
            wall_dl = caller_dl if wall_dl is None \
                else min(wall_dl, caller_dl)

        def _pull_deadline_exc(msg: str) -> Exception:
            # When the caller's get(timeout=) bound is the binding
            # constraint (no tighter ambient task deadline), an expiry
            # is the documented caller-local outcome — GetTimeoutError,
            # on which poll loops legitimately continue — never the
            # end-to-end DeadlineExceededError contract.
            if caller_dl is not None and (ambient_dl is None
                                          or caller_dl <= ambient_dl):
                return exc.GetTimeoutError(
                    f"timed out pulling {oid.hex()}")
            return exc.DeadlineExceededError(msg)

        from_addrs = [list(a) for a in (locations or [])] \
            or [list(agent_addr)]
        ok = False
        for pull_attempt in range(2):
            try:
                ok = await self.agent.call("pull_object", {
                    "object_id": oid, "from_addrs": from_addrs,
                    "owner_addr": owner_addr,
                    "priority": 0, "deadline": wall_dl}, timeout=120,
                    deadline=wall_dl)
                break
            except exc.DeadlineExceededError as e:
                # Local deadline= bound on the call expired (blackholed
                # agent link) before any remote reply.
                raise _pull_deadline_exc(str(e)) from None
            except rpc.RemoteError as e:
                # The agent distinguishes "object gone at every source"
                # (ok=False -> ObjectLostError, recovery may engage) from
                # a TRANSIENT mid-stream failure (ObjectTransferError —
                # drops/timeouts on a live source).  Match the FIRST line
                # only: rpc dispatch formats remote errors as
                # "TypeName: message\n<traceback>", so a traceback that
                # merely mentions the type can't misclassify.  A
                # transient failure gets ONE in-place retry; failing
                # twice escalates to the lost path below — recovery
                # probes the primary first (_recover_object), so a
                # source that is alive but flaky is never destructively
                # re-executed, while a source that can never serve the
                # bytes (e.g. truncated spill file) does reach
                # reconstruction instead of erroring forever.
                first = str(e).split("\n", 1)[0]
                if first.startswith("DeadlineExceededError"):
                    # The pull's budget ran out at the agent: surface the
                    # typed deadline outcome — NOT ObjectLostError, which
                    # would trigger destructive lineage re-execution for
                    # an object that may be perfectly healthy.
                    raise _pull_deadline_exc(first) from None
                if first.startswith("ObjectTransferError") \
                        and pull_attempt == 0:
                    continue
                ok = False
                break
            except (rpc.RpcError, asyncio.TimeoutError):
                ok = False  # source unreachable == primary copy lost
                break
        if not ok:
            raise exc.ObjectLostError(f"failed to pull {oid.hex()}")
        if not self.store.contains(oid):
            # Pull landed on disk (arena pressure): restore, or read the
            # local spill file directly when it can never fit the arena.
            if not await self.agent.call("restore_object", {"object_id": oid},
                                         timeout=120):
                spilled = await self._read_spilled(self.agent, oid)
                if spilled is not None:
                    return spilled
        view = self.store.get(oid, timeout_ms=5000)
        if view is None:
            raise exc.ObjectLostError(f"{oid.hex()} pulled but not sealed")
        return self._pinned(oid, view)

    async def _read_spilled(self, agent_conn, oid: bytes):
        """Chunked read of a spilled object that cannot re-enter the arena
        (reference: spilled_object_reader.h — readers stream straight from
        the spill file).  Chunks arrive as raw out-of-band frames scattered
        directly into the destination buffer (no msgpack pass, no
        intermediate bytes), with a window of requests in flight to
        pipeline the agent's file reads under the wire."""
        info = await agent_conn.call("object_info",
                                     {"object_id": oid, "timeout_ms": 0})
        if info is None or not info.get("spilled"):
            return None
        size = info["size"]
        cfg = get_config()
        chunk = cfg.object_transfer_chunk_bytes
        out = bytearray(size)
        dest = memoryview(out)

        class _ChunkFailed(Exception):
            """Raised (not returned) so gather_windowed cancels the rest
            of the window — a failed first chunk of a multi-GB object
            must not let the remaining gigabytes transfer anyway."""

        async def fetch(pos: int) -> None:
            n = min(chunk, size - pos)
            res = await agent_conn.call_raw(
                "fetch_chunk",
                {"object_id": oid, "offset": pos, "length": n,
                 "raw": True},
                sink=dest[pos:pos + n], timeout=60)
            if isinstance(res, int) and res == n:
                return
            if isinstance(res, (bytes, bytearray)) and len(res) == n:
                dest[pos:pos + n] = res        # legacy peer
                return
            raise _ChunkFailed(pos)

        try:
            await rpc.gather_windowed(
                fetch, range(0, size, chunk),
                cfg.object_transfer_max_inflight_chunks)
        except _ChunkFailed:
            return None           # absent / gone marker / short read
        return dest

    # Owner-side service: borrowers resolve objects through us.
    async def h_get_object(self, conn, p):
        oid = p["object_id"]
        timeout_ms = p.get("timeout_ms", 0)
        timeout = None if timeout_ms < 0 else timeout_ms / 1000.0
        entry = await self.memory_store.wait_for(oid, timeout)
        if entry is None:
            return None
        if entry.data is not None:
            data = entry.data
            # Entries may hold bytes-like views (raw-frame landings);
            # normalize at the msgpack boundary only.
            return {"inline": data if isinstance(data, bytes)
                    else bytes(data)}
        # Full replica set rides along (primary first, suspects last) so
        # the borrower's pull stripes/hedges across every holder.
        return {"plasma": list(entry.plasma_node),
                "locations": self._ordered_locations(entry),
                "size": entry.size}

    async def h_free_notify(self, conn, p):
        for oid in p["object_ids"]:
            self.memory_store.delete(oid)
        return True

    # ------------------------------------------------ replica directory --
    # Owner-side location directory (reference: the ownership table tracks
    # every location of an object, Ownership NSDI'21 §4; here the owner IS
    # the directory).  Agents that complete a pull (or adopt a primary off
    # a draining node) register here; agents that evict/drop their copy
    # deregister; pullers query for the freshest holder set so a 1→N
    # broadcast stripes across every replica instead of serializing on the
    # primary's NIC.

    async def h_object_locations(self, conn, p):
        """Current holder set of an owned plasma object — primary first —
        plus its size.  None when the object is unknown/inline (inline
        objects travel through get_object, not the pull path).

        `add_addr` registers the CALLER as a (mid-pull) secondary in the
        same round trip, atomically on this owner's loop: N agents
        starting a broadcast pull concurrently each register-and-query,
        so all but the very first see their siblings and the stripe set
        forms immediately — the race that would otherwise leave every
        puller convoying on the primary."""
        oid = p["object_id"]
        entry = self.memory_store.get(oid)
        if entry is None or entry.plasma_node is None:
            return None
        if p.get("add_addr"):
            from .config import get_config
            self.memory_store.add_location(
                oid, tuple(p["add_addr"]),
                max_secondaries=get_config()
                .replica_directory_max_secondaries)
        # Exclude the caller from its own view (it can't pull from
        # itself) but keep everyone else, including other mid-pull
        # registrants.
        me = tuple(p["add_addr"]) if p.get("add_addr") else None
        return {"locations": [list(a) for a in self._ordered_locations(
                    entry) if tuple(a) != me],
                "size": entry.size}

    async def h_object_location_add(self, conn, p):
        """An agent holds (or is mid-pull of) a copy: record it.  With
        primary=True the primary record repoints — the drain path's
        adopt_primary uses this so owners learn the new pinned home
        without waiting for a recovery probe.  dev=True registers a
        DEVICE-TIER holder instead (a getter re-uploaded the object's
        arrays onto its accelerators): a locality-scheduling signal,
        never a pull source.  disk=True registers a STORAGE-TIER holder
        (the node spilled its copy to NVMe/external): a real restore
        source ranked below arena holders."""
        from .config import get_config
        return self.memory_store.add_location(
            p["object_id"], tuple(p["addr"]),
            primary=bool(p.get("primary")),
            device=bool(p.get("dev")),
            disk=bool(p.get("disk")),
            max_secondaries=get_config().replica_directory_max_secondaries)

    async def h_object_location_remove(self, conn, p):
        """A holder evicted/aborted its copy (or is draining): the
        directory entry must not outlive the bytes.  disk=True retracts
        only the storage-tier marking (the holder restored its spill
        file back into the arena — any arena record stands)."""
        self.memory_store.remove_location(p["object_id"], tuple(p["addr"]),
                                          disk=bool(p.get("disk")))
        return True

    def _ordered_locations(self, entry_or_oid) -> list:
        """Holder set of an owned object as wire addresses, primary first,
        gray-suspect/draining holders LAST (PR 4's health scores: a
        suspect node still serves, but the swarm prefers healthy
        sources).  Uses the cached node view only — never a GCS round
        trip on the read path."""
        entry = entry_or_oid if not isinstance(entry_or_oid, bytes) \
            else self.memory_store.get(entry_or_oid)
        if entry is None or entry.plasma_node is None:
            return []
        locs = entry.locations()
        if len(locs) > 1:
            locs = locs[:1] + self._suspects_last(locs[1:])
        return [list(a) for a in locs]

    def _suspects_last(self, addrs: list) -> list:
        """Stable-sort addresses: healthy targetable nodes first, then
        gray-suspect, then draining/unknown — per the (possibly stale)
        cached GCS view; on no view, order unchanged."""
        cached = getattr(self, "_nodes_cache", None)
        if not cached:
            return list(addrs)
        from . import scheduling_policy as policy
        rank = {}
        for n in cached[1]:
            rank[tuple(n["address"])] = (
                2 if not policy.targetable(n)
                else 1 if policy.suspicion_of(n) >= policy.SUSPECT_THRESHOLD
                else 0)
        return sorted(addrs, key=lambda a: rank.get(tuple(a), 0))

    # ----------------------------------------------------------------- wait --
    def wait(self, refs, num_returns=1, timeout=None, fetch_local=True):
        # timeout=0 polls return immediately — never worth the agent
        # round trip (and repeated release/reacquire churn).
        release = timeout != 0 and self._maybe_release_cpu(refs)
        try:
            return self._run(self._wait(refs, num_returns, timeout))
        finally:
            if release:
                self._notify_agent_blocked(False)

    async def _wait(self, refs, num_returns, timeout):
        """Event-driven wait (reference: raylet WaitManager — no polling):
        owned refs complete when their memory-store entry lands; borrowed
        refs long-poll the owner's get_object service once."""
        waiters = {self._spawn(self._wait_one(ref)): i
                   for i, ref in enumerate(refs)}
        pending_tasks = set(waiters)
        ready_idx: set = set()
        deadline = None if timeout is None else time.monotonic() + timeout
        try:
            while pending_tasks and len(ready_idx) < num_returns:
                t = None if deadline is None else max(
                    0.0, deadline - time.monotonic())
                done, pending_tasks = await asyncio.wait(
                    pending_tasks, timeout=t,
                    return_when=asyncio.FIRST_COMPLETED)
                for d in done:
                    if not d.cancelled() and d.exception() is None and \
                            d.result():
                        ready_idx.add(waiters[d])
                if deadline is not None and time.monotonic() >= deadline:
                    break
        finally:
            for p in pending_tasks:
                p.cancel()
        ready = [r for i, r in enumerate(refs) if i in ready_idx]
        pending = [r for i, r in enumerate(refs) if i not in ready_idx]
        return ready, pending

    async def _wait_one(self, ref: ObjectRef) -> bool:
        oid = ref.binary()
        if self.memory_store.contains(oid) or self.store.contains(oid):
            return True
        owner = ref.owner_address or self.address
        if tuple(owner) == self.address:
            await self.memory_store.wait_for(oid, None)
            return True
        # Chunked long-poll (30s slices): bounds owner-side waiter lifetime
        # when this waiter is abandoned, and a transient owner outage is
        # retried instead of resolving the ref as never-ready.
        while True:
            try:
                conn = await self._peer_owner(owner)
                res = await conn.call("get_object",
                                      {"object_id": oid, "timeout_ms": 30_000},
                                      timeout=35)
                if res is not None:
                    return True
            except (rpc.RpcError, asyncio.TimeoutError):
                await asyncio.sleep(0.5)

    # ------------------------------------------------------- normal tasks ----
    def package_runtime_env_cached(self, runtime_env):
        """Driver-side packaging (working_dir/py_modules -> uploaded
        URIs), memoized by content so repeated submissions don't re-zip."""
        if not runtime_env:
            return runtime_env
        import json as _json
        from .runtime_env import package_runtime_env
        key = _json.dumps(runtime_env, sort_keys=True, default=str)
        cached = self._packaged_envs.get(key)
        if cached is None:
            if self._on_loop_thread() and (
                    runtime_env.get("working_dir")
                    or runtime_env.get("py_modules")):
                raise RuntimeError(
                    "working_dir/py_modules packaging uploads to the GCS "
                    "and cannot run on the event loop; package this "
                    "runtime_env from a sync context first")
            cached = self._packaged_envs[key] = package_runtime_env(
                self, runtime_env)
        return cached

    @staticmethod
    def _parse_streaming(num_returns, generator_backpressure):
        """num_returns="streaming" (alias "dynamic") -> (1, streaming-spec)
        (reference: remote_function.py:404 num_returns handling)."""
        if not isinstance(num_returns, str):
            return num_returns, None
        if num_returns not in ("streaming", "dynamic"):
            raise ValueError(
                f"num_returns must be an int, 'streaming' or 'dynamic', "
                f"got {num_returns!r}")
        return 1, {"bp": int(generator_backpressure or 0)}

    def submit_task(self, *, fn, fn_id: Optional[bytes], args, kwargs,
                    num_returns, resources: Dict[str, float],
                    max_retries: int, scheduling_strategy=None,
                    runtime_env=None, name="",
                    fn_blob: Optional[bytes] = None,
                    generator_backpressure: int = 0,
                    sched_key: Optional[bytes] = None,
                    spec_prefix: Optional[tuple] = None,
                    timeout_s: Optional[float] = None) -> List[ObjectRef]:
        """Submit a normal task. NEVER blocks on dependencies: refs are
        minted and returned immediately; pending ObjectRef args resolve on
        the io loop and the task joins the lease queue when they're ready
        (reference: normal_task_submitter.cc + dependency_resolver.cc —
        submission is asynchronous end to end). Sync-safe from any thread,
        including the event loop.

        spec_prefix: optional (prefix_dict, prefix_blob) computed once by
        the RemoteFunction submit cache — per-call spec construction then
        copies the template instead of rebuilding all stable fields, and
        the blob rides every submit_batch frame un-re-encoded."""
        num_returns, streaming = self._parse_streaming(
            num_returns, generator_backpressure)
        # End-to-end deadline: an explicit timeout_s starts the clock
        # here; otherwise a submit made INSIDE a deadline-carrying task
        # inherits that task's remaining budget (the composition rule —
        # nested work never outlives its parent's promise).
        # `is not None`, not truthiness: timeout_s=0 is an already-
        # exhausted budget (e.g. max(0, remaining)) and must expire
        # typed immediately, not silently run unbounded.
        deadline = (time.time() + timeout_s) if timeout_s is not None \
            else deadlines.get()
        if sched_key is None:
            # Caller didn't pre-package: do it here (memoized; raises on
            # the loop thread only for not-yet-cached working_dir uploads).
            runtime_env = self.package_runtime_env_cached(runtime_env)
        refs = self._try_submit_fast(
            fn_id=fn_id, args=args, kwargs=kwargs, num_returns=num_returns,
            resources=resources, max_retries=max_retries,
            scheduling_strategy=scheduling_strategy,
            runtime_env=runtime_env, name=name, streaming=streaming,
            sched_key=sched_key, spec_prefix=spec_prefix,
            deadline=deadline)
        if refs is not None:
            return refs
        return self._submit_task_deferred(
            fn=fn, fn_id=fn_id, args=args, kwargs=kwargs,
            num_returns=num_returns, resources=resources,
            max_retries=max_retries, scheduling_strategy=scheduling_strategy,
            runtime_env=runtime_env, name=name, fn_blob=fn_blob,
            streaming=streaming, sched_key=sched_key,
            spec_prefix=spec_prefix, deadline=deadline)

    def _try_submit_fast(self, *, fn_id, args, kwargs, num_returns,
                         resources, max_retries, scheduling_strategy,
                         runtime_env, name, streaming=None,
                         sched_key=None, spec_prefix=None,
                         deadline=None) -> Optional[List[ObjectRef]]:
        """Submission hot path (reference: the Cython submit_task releases
        the GIL and never blocks on the raylet, _raylet.pyx:3432).  When
        the function is already exported and every arg inlines, the spec
        is built entirely on the calling thread and handed to the io loop
        with call_soon_threadsafe — no cross-thread round trip, so
        .remote() costs ~50us instead of ~0.5ms."""
        if fn_id is None or fn_id not in self._fn_cache:
            return None
        ctx = get_context()
        entries = []
        items = [("", a) for a in args] + list(kwargs.items())
        for kw, a in items:
            if isinstance(a, ObjectRef):
                return None          # dependency resolution needs the loop
            # Best-effort size probe before pickling: buffers/arrays/
            # strings that can't inline would otherwise be serialized
            # here AND again by _build_arg_entries_sync on the slow path.
            # (Large
            # containers without a cheap size still pay double pickling.)
            approx = (len(a) if isinstance(a, (bytes, bytearray, str))
                      else getattr(a, "nbytes", 0))
            if not isinstance(approx, (int, float)):
                # Objects with dynamic __getattr__ (e.g. an ActorHandle
                # answers ANY attribute with an ActorMethod) return
                # non-numeric "nbytes" — treat as size-unknown.
                approx = 0
            if approx > self._inline_limit:
                return None
            ctx.capture = captured = []
            try:
                parts = ctx.serialize(a)
            finally:
                ctx.capture = None
            if captured:
                return None          # nested refs need slow-path pinning
            if ctx.total_size(parts) > self._inline_limit:
                return None          # plasma put needs the loop
            entry = {"v": protocol.concat_parts(parts)}
            if kw:
                entry["kw"] = kw
            entries.append(entry)
        task_id = TaskID.for_normal_task(JobID(self.job_id)).binary()
        if spec_prefix is not None:
            # Pre-encoded submit cache hit: the stable fields were built
            # (and msgpack-encoded) once by the RemoteFunction — per call
            # only the delta fields are written.
            spec = dict(spec_prefix[0])
            spec["task_id"] = task_id
            spec["args"] = entries
            spec["retries_left"] = max_retries
            if streaming is not None:
                spec["streaming"] = streaming
            if deadline is not None:
                spec["deadline"] = deadline
            tr = protocol._trace_inject()
            if tr is not None:
                spec["trace"] = tr
        else:
            spec = protocol.make_task_spec(
                task_id=task_id, job_id=self.job_id, fn_id=fn_id,
                args=entries, nreturns=num_returns,
                owner_addr=list(self.address), resources=resources,
                retries_left=max_retries,
                scheduling_strategy=scheduling_strategy,
                runtime_env=runtime_env, name=name, streaming=streaming,
                deadline=deadline)
        refs = []
        for i in range(num_returns):
            oid = task_id + (i + 1).to_bytes(4, "little")
            self.reference_counter.add_owned(oid, lineage=spec)
            refs.append(ObjectRef(oid, self.address, worker=self))
        if streaming is not None:
            self.register_stream(task_id, streaming["bp"],
                                 expected_attempt=max_retries)
            refs = [ObjectRefGenerator(self, task_id, refs[0])]
        key = sched_key if sched_key is not None else \
            protocol.scheduling_key(fn_id, resources, scheduling_strategy,
                                    runtime_env)

        self.record_task_event(task_id, spec["name"], "SUBMITTED")

        def _enqueue():
            state = self._keys.get(key)
            if state is None:
                state = self._keys[key] = _KeyState(resources,
                                                    scheduling_strategy,
                                                    runtime_env)
                if spec_prefix is not None:
                    state.prefix, state.prefix_blob = spec_prefix
            state.queue.append(_PendingTask(spec, []))
            self._arm_task_deadline(spec)
            # Deferred pump: a burst of submissions landing in this loop
            # tick pumps ONCE, so tasks group into per-lease submit_batch
            # frames instead of one frame each.
            self._schedule_pump(key, state)

        self._post_to_loop(_enqueue)
        return refs

    def _deferred_pump(self, key: bytes, state):
        state.pump_scheduled = False
        self._pump(key, state)

    def _note_task_latency(self, state: _KeyState, dt: float) -> None:
        state.avg_task_s = dt if state.avg_task_s is None \
            else 0.8 * state.avg_task_s + 0.2 * dt
        # Adaptive window (reference: normal_task_submitter.cc
        # max_tasks_in_flight_per_worker): deepen while the pipeline keeps
        # push->complete latency low, back off once tasks are slow enough
        # that queuing them here (where lease growth / spillback can still
        # spread them) beats parking them behind one worker.
        if state.avg_task_s < 0.05:
            if state.window < self._max_inflight:
                state.window = min(self._max_inflight, state.window * 2)
        elif state.avg_task_s > 0.25 and state.window > PIPELINE_DEPTH:
            state.window = max(PIPELINE_DEPTH, state.window // 2)

    def _schedule_pump(self, key: bytes, state):
        """Pump at the END of the current loop tick: a burst of replies
        landing together then dispatches the next wave as per-lease
        multi-call frames instead of one single-task frame per reply."""
        if not state.pump_scheduled:
            state.pump_scheduled = True
            self.loop.call_soon(self._deferred_pump, key, state)

    def _submit_task_deferred(self, *, fn, fn_id, args, kwargs, num_returns,
                              resources, max_retries, scheduling_strategy,
                              runtime_env, name, fn_blob, streaming,
                              sched_key, spec_prefix=None,
                              deadline=None) -> List[ObjectRef]:
        """Slow-path submission (ref args / oversized args / unexported
        fn) without blocking the caller: args serialize on the CALLING
        thread (post-call mutation is safe, matching the fast path and
        actor submission), refs return immediately, and a loop coroutine
        exports the function, stores oversized args, awaits pending deps,
        then enqueues + pumps (reference: dependency_resolver.cc — the
        task enters the lease queue only once its deps exist)."""
        ctx = get_context()
        if fn_id is None or fn_id not in self._fn_cache:
            if fn_blob is None:
                fn_blob = ctx.dumps_code(fn)
                fn_id = protocol.function_id(fn_blob)
        export = (fn, fn_id, fn_blob) if fn_id not in self._fn_cache \
            else None
        entries, ref_args, borrowed_args, big_puts = \
            self._build_arg_entries_sync(args, kwargs)
        task_id = TaskID.for_normal_task(JobID(self.job_id)).binary()
        spec = protocol.make_task_spec(
            task_id=task_id, job_id=self.job_id, fn_id=fn_id,
            args=entries, nreturns=num_returns,
            owner_addr=list(self.address),
            resources=resources, retries_left=max_retries,
            scheduling_strategy=scheduling_strategy, runtime_env=runtime_env,
            name=name or getattr(fn, "__name__", ""), streaming=streaming,
            deadline=deadline)
        refs = []
        for i in range(num_returns):
            oid = task_id + (i + 1).to_bytes(4, "little")
            self.reference_counter.add_owned(oid, lineage=spec)
            refs.append(ObjectRef(oid, self.address, worker=self))
        if streaming is not None:
            self.register_stream(task_id, streaming["bp"],
                                 expected_attempt=max_retries)
            refs = [ObjectRefGenerator(self, task_id, refs[0])]
        key = sched_key if sched_key is not None else \
            protocol.scheduling_key(fn_id, resources, scheduling_strategy,
                                    runtime_env)
        task = _PendingTask(spec, ref_args, borrowed_args)
        self.record_task_event(task_id, spec["name"], "SUBMITTED")

        async def _finish():
            try:
                if export is not None:
                    try:
                        await self._export_function(
                            export[0], fn_id=export[1], blob=export[2])
                    except Exception as e:
                        self._store_task_exception(spec, exc.RayError(
                            f"function export failed: {e}"))
                        self._release_task_pins(task)
                        return
                if big_puts or any("ref" in e for e in spec["args"]):
                    if not await self._resolve_task_args(spec, task,
                                                         big_puts):
                        return
            except asyncio.CancelledError:
                # ray_tpu.cancel() while deps were resolving (_cancel
                # cancels this coroutine): resolve the returns NOW instead
                # of whenever the dep lands.
                self._store_task_exception(spec, exc.TaskCancelledError(
                    f"{spec['name']} cancelled"))
                self._release_task_pins(task)
                return
            finally:
                self._resolving.pop(task_id, None)
            if task_id in self._cancelled:
                # Cancelled while deps were resolving: never enqueue.
                self._cancelled.discard(task_id)
                self._store_task_exception(spec, exc.TaskCancelledError(
                    f"{spec['name']} cancelled"))
                self._release_task_pins(task)
                return
            state = self._keys.get(key)
            if state is None:
                state = self._keys[key] = _KeyState(
                    resources, scheduling_strategy, runtime_env)
                if spec_prefix is not None:
                    state.prefix, state.prefix_blob = spec_prefix
            state.queue.append(task)
            self._schedule_pump(key, state)

        def _start():
            self._arm_task_deadline(spec)
            # Eager task execution can run _finish to completion INSIDE
            # this _spawn call (everything already resolved, no suspension
            # point) — its finally-pop would then precede this assignment
            # and a stale done-task entry would shadow the real pushed
            # task from _cancel forever. Register only live coroutines.
            t = self._spawn(_finish())
            if not t.done():
                self._resolving[task_id] = t

        if self._on_loop_thread():
            _start()
        else:
            self._post_to_loop(_start)
        return refs

    async def _export_function(self, fn, fn_id=None, blob=None) -> bytes:
        if blob is None:
            ctx = get_context()
            blob = ctx.dumps_code(fn)
            fn_id = protocol.function_id(blob)
        if fn_id not in self._fn_cache:
            await self.gcs.call("kv_put", {
                "ns": "fn", "key": fn_id.hex(), "value": blob,
                "overwrite": False})
            self._fn_cache[fn_id] = fn
        return fn_id

    def _pump(self, key: bytes, state: _KeyState):
        """Dispatch queued tasks onto leased workers; grow leases on demand
        (reference: normal_task_submitter.cc lease pool + pipelining)."""
        # Breadth-first: one task per lease per wave, so a burst of long
        # tasks spreads across all workers before any lease pipelines a
        # second push.  While more leases are still in flight, hold at
        # depth 1 — pipelining is only for hiding RTT once the cluster
        # has granted all the concurrency it's going to.  When observed
        # task latency is SHORT (EMA < 50ms), deepen to the adaptive
        # window (grown by _note_task_latency toward
        # max_tasks_in_flight_per_worker) so each worker receives a chunk
        # worth amortizing (one submit_batch frame, one executor hop per
        # chunk) instead of trickling a few tasks per completion round
        # trip — binding a burst of sub-50ms tasks to the granted leases
        # costs at most a few hundred ms even if the pool later grows.
        # Long/unknown tasks never deep-pipeline: they must stay queued
        # here so lease growth (and spillback to other nodes) can still
        # spread them.
        if state.avg_task_s is not None and state.avg_task_s < 0.05:
            # Short tasks deepen even while lease requests are parked at a
            # saturated agent: a parked request may not resolve for
            # seconds.
            depth_cap = max(PIPELINE_DEPTH,
                            min(state.window, len(state.queue)
                                // max(1, len(state.leases))))
        elif state.pending_lease_requests > 0:
            depth_cap = 1
        else:
            depth_cap = PIPELINE_DEPTH
        assign: Dict[int, tuple] = {}
        for depth in range(depth_cap):
            if not state.queue:
                break
            for lease in state.leases:
                if not state.queue:
                    break
                if lease.conn.closed or lease.inflight > depth:
                    continue
                task = state.queue.popleft()
                lease.inflight += 1
                assign.setdefault(id(lease), (lease, []))[1].append(task)
        for lease, tasks in assign.values():
            # One submit_batch frame per lease per pump wave: identical
            # per-task semantics to separate pushes (the worker executes
            # them serially off its task lock either way), amortized
            # framing, and completions return as coalesced complete_batch
            # frames.
            self._spawn(self._push_batch(key, state, lease, tasks))
        if time.monotonic() < state.lease_backoff_until:
            return          # saturated: the denied-retry loop re-pumps
        max_leases = get_config().max_leases_per_scheduling_key
        want = min(len(state.queue), max_leases - len(state.leases)
                   - state.pending_lease_requests)
        for _ in range(max(0, want)):
            state.pending_lease_requests += 1
            self._spawn(self._request_lease(key, state))

    def _report_demand(self, key: bytes, state: _KeyState):
        """Tell the GCS this scheduling key has unschedulable tasks so the
        autoscaler can launch capacity (rate-limited per key; reference:
        backlog size in lease requests feeding autoscaler demand)."""
        now = time.monotonic()
        last = getattr(state, "last_demand_report", 0.0)
        if now - last < 2.0:
            return
        state.last_demand_report = now
        shapes = [{"resources": state.resources,
                   "count": max(1, len(state.queue))}]
        self._spawn(self.gcs.call("report_demand", {
            "reporter": self.worker_id + key,
            "shapes": shapes}))

    async def _request_lease(self, key: bytes, state: _KeyState,
                             agent_conn: Optional[rpc.Connection] = None,
                             hops: int = 0):
        strat = state.strategy or {}
        is_pg = strat.get("type") == "placement_group"
        if agent_conn is None and strat.get("type") in (
                "spread", "node_affinity", "node_label"):
            # Submitter-side raylet choice for non-default strategies
            # (reference: lease_policy.cc picks the target raylet before
            # the lease request leaves the worker).
            routed, verdict = await self._route_lease_agent(
                strat, state.resources)
            if verdict == "retry":
                # Transient (stale view / unreachable-but-listed node):
                # keep the tasks queued and try again — the refreshed
                # GCS view either finds the node or declares it dead.
                state.pending_lease_requests -= 1
                if state.queue:
                    await asyncio.sleep(0.5)
                    self._pump(key, state)
                return
            if verdict == "infeasible":
                state.pending_lease_requests -= 1
                self._fail_queued_tasks(state, exc.RayError(
                    f"scheduling strategy {strat.get('type')} has no "
                    "satisfiable node (hard constraint)"))
                return
            agent_conn = routed
        loc_map = self._lease_locality_map(state) \
            if agent_conn is None and not strat.get("type") else None
        if loc_map:
            # Default-strategy tasks with large by-ref args: route the
            # lease to the node already holding the bytes (reference:
            # lease_policy.cc locality-aware raylet choice driven by the
            # owner's location table).  Locality only ever picks among
            # feasible, targetable, trusted nodes — a miss falls through
            # to the local agent exactly as before.
            routed = await self._locality_lease_agent(state, loc_map)
            if routed is not None:
                agent_conn = routed
        if agent_conn is None and is_pg:
            # Route the lease to the agent hosting the target bundle — the
            # local agent may not hold it at all (reference: lease_policy.cc
            # picks the raylet by bundle locality).
            status, agent_conn = await self._pg_agent_conn(strat)
            if status == "removed":
                state.pending_lease_requests -= 1
                self._fail_queued_tasks(
                    state, exc.RayError(
                        "placement group was removed; task can never be "
                        "scheduled"))
                return
            if agent_conn is None:       # PG still pending / node down
                state.pending_lease_requests -= 1
                if state.queue:
                    await asyncio.sleep(0.2)
                    self._pump(key, state)
                return
        agent_conn = agent_conn or self.agent
        try:
            res = await agent_conn.call("request_lease", {
                "resources": state.resources,
                "runtime_env": state.runtime_env,
                "placement_group": ({"pg_id": strat["pg_id"],
                                     "bundle_index":
                                     strat.get("bundle_index", 0)}
                                    if is_pg else None),
                # Large by-ref args of the queued tasks this lease will
                # serve: the granting agent starts pulling missing ones
                # IMMEDIATELY (fetch overlaps worker dispatch/queueing)
                # and its spillback choice scores bytes-already-local.
                "prefetch": self._lease_prefetch_entries(state),
                # Fencing token: an agent that has seen a NEWER cluster
                # epoch (GCS failover) rejects this typed so we refresh
                # and resubmit instead of acting on a stale grant.
                protocol.EPOCH_KEY: self.cluster_epoch,
            }, timeout=130)
        except (rpc.RpcError, asyncio.TimeoutError):
            state.pending_lease_requests -= 1
            if state.queue:
                await asyncio.sleep(0.2)
                self._pump(key, state)
            return
        if not res.get("granted"):
            if res.get("reject") == protocol.REJECT_STALE_EPOCH:
                # Fenced: this owner's epoch predates a GCS failover the
                # agent already lives in.  Adopt the agent's epoch and
                # resubmit through the normal pump — the queued tasks were
                # never granted, so the retry is exactly-once by
                # construction (reference: Raft clients retry with the
                # new term; StaleEpochError is the user-facing type when
                # a caller surfaces this instead of retrying).
                self.stale_epoch_rejections += 1
                self._learn_epoch(res.get(protocol.EPOCH_KEY))
                state.pending_lease_requests -= 1
                if state.queue:
                    self._pump(key, state)
                return
            reason = res.get("reason") or ""
            if "runtime env setup failed" in reason \
                    or protocol.LEASE_REFUSED in reason:
                # A broken env spec (bad package, dead find_links) or a
                # shape the node refuses outright (a fraction of a real
                # chip) can never succeed by retrying — surface it on the
                # tasks (reference: RuntimeEnvSetupError fails the task).
                state.pending_lease_requests -= 1
                self._fail_queued_tasks(state, exc.RayError(reason))
                return
            if is_pg and "bundle" in (res.get("reason") or ""):
                # Bundle gone or exhausted at the routed node: drop the
                # cached table so the next attempt re-resolves (and notices
                # PG removal).
                self._pg_cache.pop(strat["pg_id"], None)
            spill = res.get("spillback")
            if strat.get("type") == "node_affinity" and \
                    not strat.get("soft"):
                spill = None   # hard affinity never follows spillback
            if strat.get("type") == "node_label" and strat.get("hard"):
                spill = None   # hard label selector likewise
            if spill and hops < 4:
                try:
                    peer = await self._peer_owner(tuple(spill))
                    await self._request_lease(key, state, peer, hops + 1)
                    return
                except rpc.ConnectionLost:
                    pass
            state.pending_lease_requests -= 1
            if state.queue:
                retry_s = res.get("retry_after_ms", 100) / 1000
                # Report demand on saturation as well as infeasibility: a
                # cluster where the shape *fits* but every node is busy still
                # needs the autoscaler to see the queued backlog (reference
                # scales on lease backlog, not only infeasible shapes).
                self._report_demand(key, state)
                # Stop hot-looping new lease requests while the cluster is
                # saturated; held leases pipeline in the meantime.
                state.lease_backoff_until = time.monotonic() + retry_s
                await asyncio.sleep(retry_s)
                state.lease_backoff_until = 0.0
                self._pump(key, state)
            return
        state.pending_lease_requests -= 1
        if state.last_demand_report:
            # Demand satisfied: retract the report instead of letting it
            # age out over the TTL (stale shapes over-provision).
            state.last_demand_report = 0.0
            self._spawn(self.gcs.call("report_demand", {
                "reporter": self.worker_id + key, "shapes": []}))
        if not state.queue and not any(ls.inflight for ls in state.leases):
            # Stale grant: the work this request was made for already
            # drained (typical when several requests parked at a saturated
            # agent). Hand the lease straight back — cycling it through
            # the idle reaper would hold the slot ~0.75s, serializing
            # OTHER clients' parked requests behind it (reference:
            # normal_task_submitter.cc cancels unneeded lease requests).
            self._spawn(agent_conn.call(
                "return_lease", {"lease_id": res["lease_id"]}))
            return
        worker_addr = tuple(res["worker_addr"])
        grant_epoch = res.get(protocol.EPOCH_KEY)
        if isinstance(grant_epoch, int):
            self._learn_epoch(grant_epoch)
        conn = await self._worker_conn(worker_addr)
        lease = _Lease(res["lease_id"], worker_addr, res["worker_id"], conn,
                       agent_conn,
                       epoch=(grant_epoch if isinstance(grant_epoch, int)
                              else self.cluster_epoch))
        state.leases.append(lease)
        self._pump(key, state)
        self._spawn(self._lease_reaper(key, state, lease))

    def _learn_epoch(self, epoch):
        """Adopt a higher cluster epoch (GCS failover observed).  Cached
        idle leases minted under the old epoch are handed back — their
        grants are formally fenced, and the replacement request returns
        a fresh same-worker lease stamped with the new epoch.  Leases
        with work in flight finish it first (the executing worker and
        its agent are both still alive; only the grant token aged)."""
        if not isinstance(epoch, int) or epoch <= self.cluster_epoch:
            return
        prev = self.cluster_epoch
        self.cluster_epoch = epoch
        if prev == protocol.EPOCH_NONE:
            return
        logger.warning("cluster epoch bumped %d -> %d (GCS failover)",
                       prev, epoch)
        for key, state in self._keys.items():
            stale = [ls for ls in state.leases
                     if ls.epoch < epoch and not ls.inflight]
            for ls in stale:
                state.leases.remove(ls)
                self._spawn(ls.agent_conn.call(
                    "return_lease", {"lease_id": ls.lease_id,
                                     protocol.EPOCH_KEY: epoch}))
            if stale and state.queue:
                self._pump(key, state)

    async def _cluster_nodes(self, force: bool = False):
        """GCS node view, cached briefly (strategy routing must not add
        a GCS round trip per lease request).  Refreshes are DELTA
        queries (`get_nodes {"since": epoch}`): the GCS ships only the
        views whose scheduling-relevant state changed since our last
        poll, so N polling clients cost the GCS O(changes) per tick
        instead of O(nodes) full-view builds each.  A plain-list reply
        (pre-delta GCS) keeps working unchanged."""
        now = time.monotonic()
        cached = getattr(self, "_nodes_cache", None)
        if not force and cached is not None and now - cached[0] < 2.0:
            return cached[1]
        by_id = getattr(self, "_nodes_by_id", None)
        since = getattr(self, "_nodes_epoch", None)
        res = await self.gcs.call(
            "get_nodes",
            {"since": since if by_id and since is not None else -1})
        if isinstance(res, list):
            by_id = {n["node_id"]: n for n in res}
            self._nodes_epoch = None
        else:
            if by_id is None or since is None:
                by_id = {}
            for v in res["changed"]:
                by_id[v["node_id"]] = v
            if res.get("total") is not None and res["total"] != len(by_id):
                # Node-table reset under us (GCS restarted without its
                # journal): ghosts in our merge would never be sent as
                # dead — bootstrap the view from scratch.
                res = await self.gcs.call("get_nodes", {"since": -1})
                by_id = {v["node_id"]: v for v in res["changed"]}
            self._nodes_epoch = res["epoch"]
        self._nodes_by_id = by_id
        nodes = list(by_id.values())
        self._nodes_cache = (now, nodes)
        return nodes

    def _lease_locality_map(self, state) -> Optional[dict]:
        """Bytes-already-local map for the task at the head of this
        scheduling key's queue (the one the requested lease will run
        first), or None when locality scheduling is disabled / has
        nothing to say."""
        cfg = get_config()
        if not (cfg.object_locality_scheduling_enabled
                and cfg.replica_directory_enabled and state.queue):
            return None
        from . import scheduling_policy as policy
        loc = policy.arg_locality(state.queue[0].spec.get("args"))
        if not loc or max(loc.values()) < cfg.object_locality_min_bytes:
            return None
        return loc

    async def _locality_lease_agent(self, state, loc_map):
        """Agent connection for the targetable+trusted+feasible node
        holding the most hinted arg bytes; None keeps the local agent.
        Feasibility, draining state and gray-suspicion all rank ABOVE
        locality — a byte-holding node that fails any of them is simply
        not a candidate."""
        from . import scheduling_policy as policy
        try:
            nodes = [n for n in await self._cluster_nodes()
                     if policy.targetable(n)]
        except (rpc.RpcError, asyncio.TimeoutError):
            return None
        cands = [(tuple(n["address"]), tuple(n["address"]),
                  n["resources_total"], n["resources_available"])
                 for n in policy.prefer_trusted(nodes)]
        best = policy.pick_by_locality(
            cands, state.resources, loc_map,
            min_bytes=get_config().object_locality_min_bytes)
        if best is None or best == self.agent_address:
            return None
        try:
            return await self._peer_owner(best)
        except (rpc.ConnectionLost, rpc.RpcError, OSError):
            return None     # stale view: the local agent still works

    def _lease_prefetch_entries(self, state, limit: int = 8):
        """[oid, locations, owner_addr, size, task_id] for the large
        by-ref args of the first few queued tasks — the granting agent's
        prefetch work list (missing ones start pulling on grant)."""
        cfg = get_config()
        if not (cfg.arg_prefetch_enabled and cfg.replica_directory_enabled):
            return None
        out, seen = [], set()
        for task in list(state.queue)[:4]:
            for e in task.spec.get("args") or ():
                if "ref" not in e or \
                        int(e.get("sz") or 0) < cfg.arg_prefetch_min_bytes:
                    continue
                oid = bytes(e["ref"][0])
                locs = e["ref"][2]
                if oid in seen or not locs:
                    continue
                seen.add(oid)
                out.append([oid, locs, list(e["ref"][1]),
                            int(e["sz"]), task.spec["task_id"]])
                if len(out) >= limit:
                    return out
        return out or None

    async def _route_lease_agent(self, strat: dict, resources):
        """Pick the agent to lease from for spread / node_affinity /
        node_label tasks (reference: lease_policy.cc +
        scheduling/policy/{spread,node_affinity,node_label}*).

        Returns (conn, verdict): verdict 'ok' with a connection,
        'retry' for transient state (stale node view, GCS hiccup, a
        listed-alive node refusing connections — death-lag), or
        'infeasible' when a HARD constraint is unsatisfiable per the
        authoritative GCS view (target dead/absent, no label match)."""
        hard = ((strat.get("type") == "node_affinity"
                 and not strat.get("soft"))
                or (strat.get("type") == "node_label"
                    and strat.get("hard")))
        from . import scheduling_policy as policy
        try:
            all_nodes = [n for n in await self._cluster_nodes()
                         if policy.targetable(n)]
            # Gray-failure deprioritization (ranking only: node_affinity
            # resolves against the UNFILTERED view below — an explicitly
            # targeted suspect node is deprioritized elsewhere, never
            # hidden from its own affinity match).
            nodes = all_nodes if hard else policy.prefer_trusted(all_nodes)
        except (rpc.RpcError, asyncio.TimeoutError):
            # Never silently violate a hard constraint on a GCS blip.
            return (None, "retry") if hard else (self.agent, "ok")
        conn, verdict = await self._route_on_view(strat, resources, nodes,
                                                  hard, all_nodes)
        if verdict == "infeasible":
            # The cached view can be up to 2s stale — a node that just
            # registered must not get its hard-pinned tasks wrongly
            # failed.  Re-evaluate against a FRESH view before declaring
            # the constraint unsatisfiable.
            try:
                all_nodes = [n for n in
                             await self._cluster_nodes(force=True)
                             if policy.targetable(n)]
            except (rpc.RpcError, asyncio.TimeoutError):
                return None, "retry"
            conn, verdict = await self._route_on_view(
                strat, resources, all_nodes, hard, all_nodes)
        return conn, verdict

    async def _route_on_view(self, strat: dict, resources, nodes, hard,
                             all_nodes=None):
        from . import scheduling_policy as policy
        typ = strat.get("type")

        async def _connect(addr):
            try:
                return await self._peer_owner(tuple(addr))
            except (rpc.ConnectionLost, rpc.RpcError, OSError):
                # Listed alive but unreachable: either restarting or the
                # health check hasn't marked it dead yet — let the caller
                # retry; the refreshed view converges either way.
                self._nodes_cache = None
                return None

        if typ == "node_affinity":
            target = bytes(strat["node_id"])
            # Resolve against the unfiltered targetable view: a soft
            # affinity to a gray-suspect node is an explicit locality
            # preference, not a placement the scheduler chose — hiding
            # it behind prefer_trusted would hard-exclude the target.
            node = next((n for n in (all_nodes or nodes)
                         if bytes(n["node_id"]) == target), None)
            if node is None:
                # Authoritative: the target is dead/absent in the view.
                return (self.agent, "ok") if strat.get("soft") \
                    else (None, "infeasible")
            conn = await _connect(node["address"])
            if conn is not None:
                return conn, "ok"
            return (self.agent, "ok") if strat.get("soft") \
                else (None, "retry")
        if typ == "node_label":
            # Like node_affinity above: label MATCHING sees the
            # unfiltered view — suspicion deprioritizes (ranks suspects
            # last, below), never hard-excludes, so a label whose only
            # match is gray-suspect still resolves instead of silently
            # dropping the preference.
            pool = all_nodes if all_nodes is not None else nodes
            ordered = policy.label_filter(
                [(tuple(n["address"]), n.get("labels") or {})
                 for n in pool],
                strat.get("hard") or None, strat.get("soft") or None)
            if not ordered:
                return (None, "infeasible") if hard else (self.agent, "ok")
            by_addr = {tuple(n["address"]): n for n in pool}
            # Feasible matches first, trusted before suspect within,
            # then any match (its agent backpressures; spillback is
            # suppressed for hard).
            for addr in sorted(ordered, key=lambda a: (
                    not policy.feasible(
                        by_addr[a]["resources_available"], resources),
                    policy.suspicion_of(by_addr[a])
                    >= policy.SUSPECT_THRESHOLD)):
                conn = await _connect(addr)
                if conn is not None:
                    return conn, "ok"
            return (None, "retry") if hard else (self.agent, "ok")
        if typ == "spread":
            feas = [n for n in nodes
                    if policy.feasible(n["resources_available"],
                                       resources)] or nodes
            self._spread_rr = getattr(self, "_spread_rr", -1) + 1
            for i in range(len(feas)):
                node = feas[(self._spread_rr + i) % len(feas)]
                conn = await _connect(node["address"])
                if conn is not None:
                    return conn, "ok"
            return self.agent, "ok"
        return self.agent, "ok"

    async def _pg_agent_conn(self, strat: dict):
        """Resolve the agent hosting a PG-targeted lease's bundle.

        Returns (status, conn): ("ok", conn) | ("pending", None) |
        ("removed", None).  Bundle locations are immutable once placed, so
        the table is cached until a denial invalidates it; bundle_index -1
        round-robins across the PG's nodes."""
        pg_id = strat["pg_id"]
        table = self._pg_cache.get(pg_id)
        if table is None:
            table = await self.gcs.call("get_placement_group",
                                        {"pg_id": pg_id})
            if table is None or table.get("state") == "REMOVED":
                return "removed", None
            if table.get("state") != "CREATED":
                return "pending", None
            self._pg_cache[pg_id] = table
        bundles = table["bundles"]
        idx = strat.get("bundle_index", 0)
        if idx >= len(bundles):
            return "removed", None     # invalid index: task can never run
        if idx < 0:
            n = self._pg_rr.get(pg_id, 0)
            self._pg_rr[pg_id] = n + 1
            idx = n % len(bundles)
        addr = tuple(bundles[idx]["node_addr"])
        if addr == self.agent_address:
            return "ok", self.agent
        try:
            return "ok", await self._peer_owner(addr)
        except rpc.ConnectionLost:
            return "pending", None

    def _fail_queued_tasks(self, state: _KeyState, error: Exception):
        """Resolve every queued task's return refs to an error."""
        while state.queue:
            task = state.queue.popleft()
            self._store_task_exception(task.spec, error)
            self._release_task_pins(task)

    async def _worker_conn(self, addr: tuple) -> rpc.Connection:
        conn = self._worker_conns.get(addr)
        if conn is None or conn.closed:
            conn = await rpc.connect(addr, name="cw->worker", retries=3,
                                     on_close=self._on_peer_conn_close)
            # Batched completions ride back on this same connection.
            conn.fast_handlers["complete_batch"] = self._f_complete_batch
            self._worker_conns[addr] = conn
        return conn

    async def _lease_reaper(self, key, state, lease: _Lease):
        # 100ms grace keeps the lease across back-to-back sync submission
        # loops (gap ~0) but hands the worker back quickly when this key's
        # queue drains — under saturation other clients' lease requests
        # are parked at the agent behind this slot (reference:
        # normal_task_submitter.cc returns the worker when the scheduling
        # key's queue empties; the raylet's idle pool, not a held lease,
        # provides reuse).
        while True:
            await asyncio.sleep(0.05)
            if lease not in state.leases:
                # Already handed back elsewhere (e.g. fenced as stale on
                # an epoch bump) — nothing left to reap.
                return
            if lease.conn.closed:
                state.leases.remove(lease)
                return
            if lease.inflight == 0 and not state.queue:
                if time.monotonic() - lease.idle_since > 0.1:
                    if lease in state.leases:
                        state.leases.remove(lease)
                    try:
                        await lease.agent_conn.call(
                            "return_lease", {"lease_id": lease.lease_id})
                    except rpc.RpcError:
                        pass
                    return

    async def _push_batch(self, key, state, lease: _Lease, tasks):
        """Push queued tasks to one leased worker as a single submit_batch
        frame: one pre-encoded spec prefix + per-task deltas (see
        docs/control_plane.md).  The worker acks enqueue immediately and
        ships results back as coalesced complete_batch frames, applied by
        _f_complete_batch.  Per-task semantics (cancel checks,
        retry/requeue on worker death, OOM triage) match the per-call
        pushes this replaces; the worker executes the batch serially off
        its task lock exactly as it would pipelined singles.

        A lost ack (chaos drop / wedged worker) resends the
        still-unfinished tasks after submit_batch_ack_timeout_s — the
        worker dedups by task id, so a dropped RESPONSE is harmless and a
        dropped REQUEST simply re-enqueues."""
        ready = []
        for task in tasks:
            spec = task.spec
            tid = spec["task_id"]
            if tid in self._cancelled:
                lease.inflight -= 1
                self._store_task_exception(
                    spec, exc.TaskCancelledError(f"{spec['name']} cancelled"))
                self._release_task_pins(task)
                self._cancelled.discard(tid)
                continue
            ready.append(task)
        if not ready:
            self._pump(key, state)
            return
        if lease.conn.closed:
            await self._lease_lost(key, state, lease, ready)
            return
        # Note: a transport write-buffer pause is NOT treated as a shrink
        # signal — the pause already throttles emission, and on a
        # saturated host it fires constantly; halving on it collapses the
        # pipeline exactly when deep batching pays most.  The window
        # shrinks on the real backpressure signals instead: rising
        # push->complete latency (_note_task_latency) and lease loss
        # (_lease_lost).
        if state.prefix is None:
            state.prefix = protocol.spec_prefix_of(ready[0].spec)
            state.prefix_blob = protocol.encode_prefix(state.prefix)
        t_push = time.monotonic()
        for task in ready:
            tid = task.spec["task_id"]
            self._inflight_tasks[tid] = lease
            self._pending_replies[tid] = ("n", key, state, lease, task,
                                          t_push)
        outcome, info = await self._submit_batch_with_ack(
            lease.conn, state.prefix, state.prefix_blob, ready,
            actor=False, abort_label=str(lease.worker_addr))
        if outcome == "remote_error":
            # Dispatch-level failure: fail the tasks, keep the lease
            # accounted.
            e, pending = info
            for task in pending:
                tid = task.spec["task_id"]
                if self._pending_replies.pop(tid, None) is None:
                    continue
                self._inflight_tasks.pop(tid, None)
                lease.inflight -= 1
                self._store_task_exception(task.spec, exc.RayError(
                    f"task push failed: {e}"))
                self._release_task_pins(task)
            self._schedule_pump(key, state)
        elif outcome == "conn_lost":
            # The conn's on_close cleanup usually runs first and sweeps
            # these entries; handle whatever it hasn't claimed.
            leftovers = [t for t in ready
                         if self._pending_replies.pop(t.spec["task_id"],
                                                      None) is not None]
            if leftovers:
                await self._lease_lost(key, state, lease, leftovers)

    async def _submit_batch_with_ack(self, conn, prefix, prefix_blob,
                                     pending, *, actor: bool,
                                     abort_label: str):
        """Send one submit_batch frame and drive the lost-ack resend loop
        (shared by the normal-task and actor arms — the protocol must
        never diverge between them).

        Returns ("ok", _) once acked or nothing is left pending,
        ("remote_error", (err, still_pending)) on a dispatch-level
        RemoteError, ("conn_lost", _) when the connection died mid-call
        (the caller sweeps leftovers).  Tasks whose completions land
        during the retry loop drop out of the resend via the
        _pending_replies membership filter; the receiver dedups re-sent
        ids.  Acks lost 4x in a row mean the worker (or the wire) is
        wedged: recycle the connection — its on_close cleanup funnels the
        in-flight tasks through the normal worker-death semantics."""
        for _attempt in range(4):
            if self._shutdown:
                # Don't resend (or resume bookkeeping) against a runtime
                # that is tearing down.
                return "ok", None
            payload = {"pr": prefix_blob,
                       "t": [protocol.spec_delta(prefix, t.spec)
                             for t in pending]}
            if actor:
                payload["a"] = True
            try:
                await conn.call("submit_batch", payload,
                                timeout=self._ack_timeout)
                return "ok", None   # completions arrive via complete_batch
            except asyncio.TimeoutError:
                pending = [t for t in pending
                           if t.spec["task_id"] in self._pending_replies]
                if not pending:
                    return "ok", None
            except rpc.RemoteError as e:
                return "remote_error", (e, pending)
            except rpc.ConnectionLost:
                return "conn_lost", None
        logger.warning("submit_batch acks lost to %s; recycling "
                       "connection", abort_label)
        conn.abort()
        return "ok", None

    def _f_complete_batch(self, conn, p):
        """Fast handler on direct worker/actor connections: a peer shipped
        a coalesced batch of push results.  Applies every reply's refcount
        + memory-store updates in one pass and schedules a single deferred
        pump per affected scheduling key (the group wakeup) — no per-reply
        RPC future, callback, or asyncio Task."""
        now = time.monotonic()
        for tid, reply in p["t"]:
            tid = bytes(tid)
            rec = self._pending_replies.pop(tid, None)
            if rec is None:
                continue    # already resolved by connection-loss cleanup
            if rec[0] == "n":
                _, key, state, lease, task, t_push = rec
                self._inflight_tasks.pop(tid, None)
                lease.inflight -= 1
                lease.idle_since = now
                self._note_task_latency(state, now - t_push)
                try:
                    self._handle_reply(task.spec, task, reply)
                except Exception:
                    logger.exception("completion handling failed for %s",
                                     task.spec.get("name"))
                # Pump even when reply handling blew up: inflight was
                # already decremented, and a skipped pump would leave the
                # key's queue idle until some unrelated event wakes it.
                self._schedule_pump(key, state)
            else:
                _, astate, _conn, task, _t_push = rec
                self._inflight_actor_tasks.pop(tid, None)
                try:
                    self._handle_reply(task.spec, task, reply)
                except Exception:
                    logger.exception("completion handling failed for %s",
                                     task.spec.get("method"))
        return True

    def _on_peer_conn_close(self, conn):
        """A direct worker/actor connection died: every task whose
        completion was pending on it gets the per-task retry/cancel/fail
        treatment (same semantics as a lost per-call push reply)."""
        if self._shutdown or not self._pending_replies:
            return
        self._spawn(self._conn_lost_cleanup(conn))

    async def _conn_lost_cleanup(self, conn):
        by_lease: Dict[int, tuple] = {}
        by_actor: Dict[int, tuple] = {}
        for tid, rec in list(self._pending_replies.items()):
            if rec[0] == "n":
                _, key, state, lease, task, _t = rec
                if lease.conn is conn:
                    self._pending_replies.pop(tid, None)
                    by_lease.setdefault(
                        id(lease), (key, state, lease, []))[3].append(task)
            else:
                _, astate, pushed_conn, task, _t = rec
                if pushed_conn is conn:
                    self._pending_replies.pop(tid, None)
                    by_actor.setdefault(
                        id(astate), (astate, []))[1].append(task)
        for key, state, lease, tasks in by_lease.values():
            await self._lease_lost(key, state, lease, tasks)
        for astate, tasks in by_actor.values():
            # Only clear the actor's conn if it still points at the DEAD
            # connection — a concurrent retry may already have
            # reconnected, and clobbering the healthy conn would split
            # subsequent calls across two connections (breaking the
            # sequential actor's arrival ordering).
            if astate.conn is conn:
                astate.conn = None
            await self._actor_tasks_lost(astate, tasks)

    async def _lease_lost(self, key, state, lease: _Lease, tasks):
        """The leased worker's connection died with these tasks in flight:
        requeue retryable ones, fail the rest (with one OOM triage against
        the agent for the whole burst)."""
        if lease in state.leases:
            state.leases.remove(lease)
        if state.window > PIPELINE_DEPTH:
            # A died worker is the strongest backpressure signal there is.
            state.window = max(PIPELINE_DEPTH, state.window // 2)
        fate = None
        need_fate = any(
            t.spec["retries_left"] == 0
            and t.spec["task_id"] not in self._cancelled for t in tasks)
        if need_fate:
            try:
                fate = await lease.agent_conn.call(
                    "worker_fate", {"worker_id": lease.worker_id}, timeout=5)
            except (rpc.RpcError, asyncio.TimeoutError):
                pass
        for task in tasks:
            spec = task.spec
            tid = spec["task_id"]
            self._inflight_tasks.pop(tid, None)
            lease.inflight -= 1
            if tid in self._cancelled:
                self._store_task_exception(
                    spec, exc.TaskCancelledError(f"{spec['name']} cancelled"))
                self._release_task_pins(task)
                self._cancelled.discard(tid)
            elif spec["retries_left"] != 0:
                # Negative = retry forever (max_retries=-1, reference
                # semantics); only positive budgets are consumed.
                if spec["retries_left"] > 0:
                    spec["retries_left"] -= 1
                self._stream_reset_for_retry(spec)
                state.queue.append(task)
            else:
                if fate and fate.get("oom_killed"):
                    err = exc.OutOfMemoryError(fate.get("reason") or (
                        f"worker at {lease.worker_addr} was OOM-killed "
                        f"running {spec['name']}"))
                else:
                    err = exc.WorkerCrashedError(
                        f"worker at {lease.worker_addr} died running "
                        f"{spec['name']}")
                self._store_task_failure(spec, err)
                self._release_task_pins(task)
        self._pump(key, state)

    _REPLY_EVENT = {"ok": "FINISHED", "cancelled": "CANCELLED"}

    def _absorb_reply_refs(self, task_id: bytes, reply, *, discard: bool):
        """Absorb a successful reply's reference bookkeeping — shared by
        the normal ok path and the deadline-expired straggler path.
        Neither may skip it: the worker registered borrows and
        escape-pinned nested refs during serialization, so dropping the
        records would free objects the worker still holds, or leak pins
        forever.  Borrow registration must precede the caller's
        _release_task_pins so a stored arg ref keeps its object pinned
        across the handoff.  With discard=True the value can never be
        read (its returns were already resolved to an error), so every
        nested set is released after the escape-pin grace instead of
        being recorded as contained."""
        # In-band borrow registration (see worker_main: reply["borrows"]).
        for oid, epoch in reply.get("borrows", []):
            self.reference_counter.add_borrower_from_reply(
                bytes(oid), bytes(reply["borrower_id"]), epoch=epoch)
        for i, entry in enumerate(reply["returns"]):
            # ObjectID.for_task_return without the class round-trips:
            # ids are plain concatenation (ids.py:166).
            oid = task_id + (i + 1).to_bytes(4, "little")
            # Refs nested inside this return value: the worker already
            # escape-pinned each at its owner during serialization; we
            # record containment so freeing the return releases them
            # (reference: task replies carry borrowed-ref metadata).
            nested = [(bytes(noid),
                       None if tuple(nowner) == self.address
                       else tuple(nowner))
                      for noid, nowner in entry.get("nested", [])]
            # Nested refs WE own arrive unpinned by protocol (the worker
            # defers to us to avoid the notify-vs-reply socket race);
            # take their escape pins now, strictly before the submitted
            # arg pins are released by the caller.
            for noid, nowner in nested:
                if nowner is None:
                    self.reference_counter.add_escape_pin(noid)
            if nested and (discard
                           or not self.reference_counter.is_tracked(oid)):
                # Value discarded, or container already freed (caller
                # dropped the return ref mid-flight): release the
                # worker-taken pins instead of recording them forever.
                # Delayed so in-flight escape_pin notifies land first.
                self.loop.call_later(
                    1.0, lambda n=nested: self._release_nested(n))
            elif nested:
                self._record_contained(oid, nested, take_pins=False)

    def _handle_reply(self, spec, task: Optional[_PendingTask], reply):
        task_id = spec["task_id"]
        self._disarm_task_deadline(task_id)
        if task_id in self._deadline_expired:
            # The owner-side deadline already resolved the returns with
            # DeadlineExceededError; this straggler reply (or the chased
            # cancel's ack) only settles bookkeeping — storing its value
            # now would un-error refs the user may have already observed.
            # The bookkeeping is NOT skippable though: a successful
            # straggler registered borrows and escape-pinned nested refs
            # during serialization; dropping those records would free
            # objects the worker still holds, or leak pins forever.
            if reply.get("status") == "ok":
                self._absorb_reply_refs(task_id, reply, discard=True)
            self._deadline_expired.discard(task_id)
            self._release_task_pins(task)
            self._cancelled.discard(task_id)
            self.record_task_event(
                task_id, spec.get("name") or spec.get("method", ""),
                "FAILED")
            return
        self.record_task_event(
            task_id, spec.get("name") or spec.get("method", ""),
            self._REPLY_EVENT.get(reply.get("status"), "FAILED"))
        if reply.get("status") == "ok":
            self._absorb_reply_refs(task_id, reply, discard=False)
            for i, entry in enumerate(reply["returns"]):
                oid = task_id + (i + 1).to_bytes(4, "little")
                if "inline" in entry:
                    self.memory_store.put_inline(oid, entry["inline"])
                else:
                    self.memory_store.put_plasma_location(
                        oid, entry["plasma"], size=entry.get("size"))
        elif reply.get("status") == "cancelled":
            self._store_task_exception(
                spec, exc.TaskCancelledError(f"{spec['name']} cancelled"))
        else:
            err = get_context().loads_code(reply["error"])
            if isinstance(err, (exc.DeadlineExceededError,
                                exc.OverloadedError,
                                exc.StreamBrokenError,
                                exc.KVGatherError)):
                # Worker-side expiry (refused-before-execution, or a
                # nested hop's budget ran out inside user code),
                # serving load-shed, and mid-stream KV-plane breaks
                # all surface TYPED — wrapped in RayTaskError they
                # would slip past the `except DeadlineExceededError` /
                # `except OverloadedError` / `except
                # StreamBrokenError` contracts the docs promise.
                self._store_task_exception(spec, err)
            else:
                wrapped = exc.RayTaskError(
                    f"task {spec['name']} failed", cause=err,
                    remote_traceback=reply.get("traceback", ""))
                self._store_task_exception(spec, wrapped)
        self._release_task_pins(task)
        self._cancelled.discard(task_id)

    def _store_task_failure(self, spec, error: Exception):
        self._store_task_exception(spec, error)

    def _release_nested(self, nested):
        for noid, nowner in nested:
            if nowner is None:
                self.reference_counter.release_escape_pin(noid)
            else:
                self._notify_owner(nowner, "escape_release", noid)

    def _release_task_pins(self, task: Optional[_PendingTask]):
        if task is None:
            return
        for oid in task.ref_args:
            self.reference_counter.remove_submitted(oid)
        task.ref_args = []
        for noid, nowner in task.borrowed_args:
            self._notify_owner(nowner, "escape_release", noid)
        task.borrowed_args = []

    def _store_task_exception(self, spec, error):
        # Terminal for every failure path (retry exhaustion, cancel,
        # recovery): the armed deadline must not fire afterwards.
        self._disarm_task_deadline(spec["task_id"])
        if spec["task_id"] in self._deadline_expired \
                and not isinstance(error, exc.DeadlineExceededError):
            # The deadline watchdog already resolved the returns with the
            # typed DeadlineExceededError; the cancel it kicked off (or a
            # racing failure path) must not downgrade that to a generic
            # TaskCancelledError/WorkerCrashedError.
            return
        data = protocol.concat_parts(get_context().serialize(error))
        for i in range(spec["nreturns"]):
            oid = ObjectID.for_task_return(
                TaskID(spec["task_id"]), i + 1).binary()
            self.memory_store.put_inline(oid, data, is_exception=True)
        if spec.get("streaming"):
            self._stream_on_task_failed(spec)

    # ------------------------------------------------- deadline watchdog ----
    def _arm_task_deadline(self, spec) -> None:
        """Loop-thread only: schedule the owner-side deadline for a spec
        submitted with .options(timeout_s=...).  The watchdog — not any
        per-hop RPC timeout — is what guarantees the user-visible bound:
        even a fully blackholed worker/agent cannot hold the returns
        hostage past the budget (they resolve to DeadlineExceededError
        and a best-effort cancel chases the in-flight attempt)."""
        dl = spec.get("deadline")
        if not dl:
            return
        self._deadline_timers[spec["task_id"]] = self.loop.call_later(
            max(0.0, dl - time.time()), self._on_task_deadline, spec)

    def _disarm_task_deadline(self, task_id: bytes) -> None:
        """The task resolved (value, error, or cancellation): its armed
        deadline must never fire — a late firing would write error
        entries for return ids whose real entries may already be freed,
        resurrecting them forever."""
        h = self._deadline_timers.pop(task_id, None)
        if h is not None:
            h.cancel()

    def _on_task_deadline(self, spec) -> None:
        tid = spec["task_id"]
        self._deadline_timers.pop(tid, None)
        oid0 = tid + (1).to_bytes(4, "little")
        if self._shutdown or self.memory_store.contains(oid0):
            return                      # resolved (or errored) in time
        name = spec.get("name") or spec.get("method", "")
        err = exc.DeadlineExceededError(
            f"task {name} exceeded its end-to-end deadline "
            f"(submitted with timeout_s; deadline passed "
            f"{time.time() - spec['deadline']:.2f}s ago)")
        self._deadline_expired.add(tid)
        # EVERY return id is consulted, not just the first: a caller
        # that dropped r0 of a multi-return task but still holds r1
        # must see r1 resolve to the typed error — else its get() hangs
        # forever, the exact outcome timeout_s exists to prevent.
        if any(self.reference_counter.is_tracked(
                    tid + (i + 1).to_bytes(4, "little"))
               for i in range(spec.get("nreturns", 1))):
            self._store_task_exception(spec, err)
        # else: the caller already dropped every return ref — storing
        # error entries nobody can observe (or free) would leak them;
        # the chase below still stops the wasted attempt.
        # Bounded: entries clear when the straggler reply/cancel lands
        # (_handle_reply) or via the sweep below once no reply can
        # arrive any more.  The chase's _cancelled entry gets the same
        # sweep — under a permanent blackhole no reply ever arrives to
        # discard it.
        self.loop.call_later(300.0, self._sweep_expired_marker, tid)
        self._spawn(self._chase_expired_task(tid))

    def _sweep_expired_marker(self, tid: bytes) -> None:
        """Cleanup for a deadline-expired task's markers.  While the
        attempt is still in flight on a live conn the marker must
        SURVIVE: discarding it early would let a >300s-late straggler
        reply (gray link, not a dead worker) take the normal ok path
        and store its value — un-erroring returns the user already
        observed as DeadlineExceededError.  Conn loss clears the
        in-flight records, so the next sweep collects; memory stays
        bounded by the in-flight set itself."""
        live = (tid in self._inflight_tasks
                or tid in self._inflight_actor_tasks
                or tid in self._resolving
                # Still queued (lease-starved task / actor call behind a
                # long predecessor): the dispatch-time _cancelled reap
                # needs the marker when the attempt finally surfaces.
                or any(t.spec["task_id"] == tid
                       for state in self._keys.values()
                       for t in state.queue)
                or any(spec["task_id"] == tid
                       for astate in self._actors.values()
                       for spec, _t, _b in astate.submit_queue))
        if live:
            self.loop.call_later(300.0, self._sweep_expired_marker, tid)
            return
        self._deadline_expired.discard(tid)
        self._cancelled.discard(tid)

    async def _chase_expired_task(self, tid: bytes) -> None:
        """Best-effort cancel of the expired attempt so a merely-slow
        (not dead) worker stops burning time on a result nobody will
        read.  (Not routed through _cancel(): the returns already
        resolved, which _cancel treats as nothing-to-do.)  Queued-but-
        undispatched attempts are reaped by the _cancelled check at
        dispatch; failures here are irrelevant."""
        self._cancelled.add(tid)
        fin = self._resolving.pop(tid, None)
        if fin is not None and not fin.done():
            fin.cancel()                # still resolving deps: never runs
            return
        # Queued at the owner but not yet dispatched (lease starvation):
        # reap NOW — the returns already resolved, so the attempt must
        # neither burn a worker later nor outlive the marker sweep and
        # store a straggler value over the typed error.
        for state in self._keys.values():
            for t in list(state.queue):
                if t.spec["task_id"] == tid:
                    state.queue.remove(t)
                    self._release_task_pins(t)
                    self._cancelled.discard(tid)
                    return
        try:
            lease = self._inflight_tasks.get(tid)
            if lease is not None and not lease.conn.closed:
                await lease.conn.call(
                    "cancel_task", {"task_id": tid, "force": False},
                    timeout=10)
                return
            astate = self._inflight_actor_tasks.get(tid)
            if astate is not None and astate.conn \
                    and not astate.conn.closed:
                # interrupt_running=False: an actor method (sync OR
                # async) already executing finishes its work and the
                # straggler result is discarded (documented contract) —
                # interrupting mid-method could leave actor state
                # half-mutated.  Queued/unstarted attempts are still
                # reaped.
                await astate.conn.call(
                    "cancel_task", {"task_id": tid, "force": False,
                                    "interrupt_running": False},
                    timeout=10)
        except (rpc.RpcError, asyncio.TimeoutError):
            pass

    # -------------------------------------------------------------- cancel ---
    def cancel(self, ref: ObjectRef, force: bool = False):
        return self._run(self._cancel(ref.binary(), force))

    async def _cancel(self, oid: bytes, force: bool) -> bool:
        """Cancel the task that creates `oid` (reference: core_worker.h
        CancelTask / CancelRemoteTask, core_worker.proto:531). Queued tasks
        resolve immediately to TaskCancelledError; running async actor
        tasks get their coroutine cancelled; running sync tasks get an
        async-exc (or force=True worker kill)."""
        if ObjectID(oid).is_put():
            raise TypeError(
                "cancel() expects a task return ref, not a put() ref "
                "(reference: ray.cancel only cancels tasks)")
        if self.memory_store.contains(oid):
            return False   # already resolved: nothing to cancel
        task_id = ObjectID(oid).task_id().binary()
        astate = self._inflight_actor_tasks.get(task_id)
        if force and astate is not None:
            raise ValueError(
                "force=True is not supported for actor tasks (it would kill "
                "the whole actor); use ray_tpu.kill(actor) instead")
        self._cancelled.add(task_id)
        # Still resolving dependencies: cancel the deferred-submission
        # coroutine; its CancelledError path stores TaskCancelledError.
        # A done entry means the task moved on (enqueued/pushed) — fall
        # through to the queue/in-flight paths below.
        fin = self._resolving.pop(task_id, None)
        if fin is not None and not fin.done():
            self._cancelled.discard(task_id)
            fin.cancel()
            return True
        # Still queued at the owner: drop it before it ever dispatches.
        for state in self._keys.values():
            for t in list(state.queue):
                if t.spec["task_id"] == task_id:
                    state.queue.remove(t)
                    self._store_task_exception(
                        t.spec,
                        exc.TaskCancelledError(f"{t.spec['name']} cancelled"))
                    self._release_task_pins(t)
                    self._cancelled.discard(task_id)
                    return True
        # In flight on a leased worker.
        lease = self._inflight_tasks.get(task_id)
        if lease is not None and not lease.conn.closed:
            try:
                return bool(await lease.conn.call(
                    "cancel_task", {"task_id": task_id, "force": force},
                    timeout=10))
            except (rpc.RpcError, asyncio.TimeoutError):
                return True  # worker died mid-cancel: resolves as cancelled
        # In flight on an actor.
        if astate is not None and astate.conn and not astate.conn.closed:
            try:
                return bool(await astate.conn.call(
                    "cancel_task", {"task_id": task_id, "force": False},
                    timeout=10))
            except (rpc.RpcError, asyncio.TimeoutError):
                return True
        # Not visible yet (actor resolving, push racing): the _cancelled
        # mark is honored at dispatch by _push_batch/_push_actor_task.
        return True

    # ------------------------------------------------------------- actors ----
    def _on_loop_thread(self) -> bool:
        try:
            return asyncio.get_running_loop() is self.loop
        except RuntimeError:
            return False

    def create_actor(self, *, cls, actor_id: bytes, args, kwargs, resources,
                     name=None, get_if_exists=False, max_restarts=0,
                     max_concurrency=1, runtime_env=None,
                     scheduling_strategy=None, class_name="",
                     concurrency_groups=None) -> dict:
        # Class + args serialize on the CALLING thread (post-call mutation
        # of init args is safe; matches submit_actor_task's guarantee).
        if not self._on_loop_thread():
            runtime_env = self.package_runtime_env_cached(runtime_env)
        elif runtime_env and (runtime_env.get("working_dir")
                              or runtime_env.get("py_modules")):
            raise RuntimeError(
                "working_dir/py_modules packaging uploads to the GCS and "
                "cannot run on the event loop; create this actor from a "
                "sync context (or pre-package the runtime_env)")
        ctx = get_context()
        blob = ctx.dumps_code(cls)
        arg_entries, ref_args, borrowed_args, big_puts = \
            self._build_arg_entries_sync(args, kwargs)
        coro = self._create_actor(
            blob=blob, actor_id=actor_id, arg_entries=arg_entries,
            ref_args=ref_args, borrowed_args=borrowed_args,
            big_puts=big_puts,
            resources=resources, name=name, get_if_exists=get_if_exists,
            max_restarts=max_restarts, max_concurrency=max_concurrency,
            concurrency_groups=concurrency_groups,
            runtime_env=runtime_env, scheduling_strategy=scheduling_strategy,
            class_name=class_name)
        if self._on_loop_thread():
            # Called from an async actor method (e.g. a controller creating
            # replicas): registration proceeds in the background and the
            # client-minted id is returned immediately. get_if_exists needs
            # the existing actor's id synchronously, which would block the
            # loop — disallowed here.
            if get_if_exists:
                raise RuntimeError(
                    "get_if_exists=True cannot be used from an async actor "
                    "method; create the actor from a sync method")
            fut = self._spawn(coro)
            self._registering[actor_id] = fut

            def _done(f, aid=actor_id):
                self._registering.pop(aid, None)
                if not f.cancelled() and f.exception():
                    logger.error(
                        "background actor registration for %s failed: %s",
                        class_name, f.exception())
            fut.add_done_callback(_done)
            return {"actor_id": actor_id, "class_name": class_name}
        return self._run(coro)

    async def _create_actor(self, *, blob, actor_id, arg_entries, ref_args,
                            borrowed_args, big_puts, resources,
                            name, get_if_exists, max_restarts, max_concurrency,
                            runtime_env, scheduling_strategy, class_name,
                            concurrency_groups=None):
        cls_id = protocol.function_id(blob)
        try:
            await self._store_big_puts(arg_entries, big_puts)
            await self.gcs.call("kv_put", {"ns": "actor_cls",
                                           "key": cls_id.hex(),
                                           "value": blob, "overwrite": False})
            return await self._register_actor_spec({
                "actor_id": actor_id,
                "job_id": self.job_id,
                "class_id": cls_id,
                "class_name": class_name,
                "args": arg_entries,
                "resources": resources,
                "name": name,
                "get_if_exists": get_if_exists,
                "max_restarts": max_restarts,
                "max_concurrency": max_concurrency,
                "concurrency_groups": concurrency_groups or {},
                "runtime_env": runtime_env,
                "scheduling_strategy": scheduling_strategy,
                "owner_addr": list(self.address),
            })
        finally:
            # Init-arg pins live until registration settles (the actor's
            # __init__ runs before register_actor returns).
            for oid in ref_args:
                self.reference_counter.remove_submitted(oid)
            for noid, nowner in borrowed_args:
                self._notify_owner(nowner, "escape_release", noid)

    async def _register_actor_spec(self, spec):
        # Epoch-stamped mutation: a fenced ex-primary (or a primary that
        # failed over past us) rejects this typed instead of recording a
        # placement nobody will honor.  A lagging-but-legitimate owner
        # (we just hadn't heard about the failover yet) refreshes its
        # epoch and resubmits ONCE — registration is an id-keyed upsert,
        # so the retry is exactly-once.
        res = await self._gcs_mutate("register_actor", {"spec": spec},
                                     timeout=180)
        return res["actor"]

    async def _gcs_mutate(self, method, payload, timeout=None):
        """Issue an epoch-stamped GCS mutation; on a stale-epoch
        rejection, learn the current epoch and retry once.  Raises
        StaleEpochError if the refreshed epoch is STILL refused — that
        means this owner is genuinely fenced off, not merely behind."""
        payload = dict(payload)
        for attempt in range(2):
            payload[protocol.EPOCH_KEY] = self.cluster_epoch
            try:
                return await self.gcs.call(method, payload, timeout=timeout)
            except rpc.RpcError as e:
                if "stale_epoch" not in str(e) or attempt:
                    if "stale_epoch" in str(e):
                        self.stale_epoch_rejections += 1
                        raise exc.StaleEpochError(
                            f"GCS refused {method}: {e}",
                            stale_epoch=self.cluster_epoch) from e
                    raise
                self.stale_epoch_rejections += 1
                try:
                    info = await self.gcs.call("get_cluster_info", {})
                    self._learn_epoch(info.get(protocol.EPOCH_KEY))
                except rpc.RpcError:
                    pass

    def _build_arg_entries_sync(self, args, kwargs):
        """Serialize args on the CALLING thread (so post-call mutation is
        safe) without touching the event loop: ObjectRefs pass by
        reference, small values inline, oversized values are assigned a
        put id whose plasma store happens later on the loop (big_puts).
        Owned refs get submitted pins here; borrowed nested refs get
        escape pins at their owners. Returns (entries, ref_args,
        borrowed_args, big_puts)."""
        ctx = get_context()
        entries: List[dict] = []
        ref_args: List[bytes] = []
        borrowed_args: List[tuple] = []
        big_puts: List[tuple] = []   # (oid, parts) — stored by the coroutine
        items = [("", a) for a in args] + list(kwargs.items())
        for kw, a in items:
            if isinstance(a, ObjectRef):
                oid = a.binary()
                owner = list(a.owner_address or self.address)
                hint, sz, dev, dsk = None, None, None, None
                if tuple(owner) == self.address:
                    entry_ms = self.memory_store.get(oid)
                    if entry_ms is not None:
                        if entry_ms.plasma_node:
                            # Full replica set (primary first, suspects
                            # last): the scheduler scores bytes-already-
                            # local against EVERY holder and the
                            # executing node's prefetch stripes across
                            # them.
                            hint = self._ordered_locations(entry_ms)
                            sz = entry_ms.size
                        if entry_ms.device_nodes:
                            # Device-tier holders ride a SEPARATE hint
                            # key: arg_locality scores them local-or-
                            # better, but they never join the pull
                            # sources in ref[2] (device bytes aren't in
                            # any arena).
                            dev = [list(x) for x in entry_ms.device_nodes]
                            if sz is None:
                                sz = entry_ms.size or (
                                    len(entry_ms.data)
                                    if entry_ms.data is not None else None)
                        if entry_ms.disk_nodes:
                            # Storage-tier holders (spilled copy on local
                            # NVMe): arg_locality scores them between
                            # arena-local and remote — restoring from the
                            # spill file beats a network pull.
                            dsk = [list(x) for x in entry_ms.disk_nodes]
                # Pin EVERY by-ref arg while in flight — for borrowed refs
                # the submitted pin keeps the local borrow registered (and
                # thus the owner's borrower entry) until the reply.
                ref_args.append(oid)
                self.reference_counter.add_submitted(oid)
                entry = {"ref": [oid, owner, hint]}
                if sz:
                    entry["sz"] = sz
                if dev:
                    entry["dev"] = dev
                if dsk:
                    entry["dsk"] = dsk
            else:
                ctx.capture = captured = []
                try:
                    parts = ctx.serialize(a)
                finally:
                    ctx.capture = None
                size = ctx.total_size(parts)
                for noid, nowner in captured:
                    if nowner is None:
                        ref_args.append(noid)
                        self.reference_counter.add_submitted(noid)
                    else:
                        self._notify_owner(nowner, "escape_pin", noid)
                        borrowed_args.append((noid, nowner))
                if size <= self._inline_limit:
                    entry = {"v": protocol.concat_parts(parts)}
                else:
                    poid = self._next_put_id()
                    self.reference_counter.add_owned(poid)
                    self.reference_counter.add_submitted(poid)
                    ref_args.append(poid)
                    if not self._on_loop_thread() and \
                            self._put_store_sync(poid, parts):
                        # Zero-copy: one sync memcpy into shm right here —
                        # post-call arg mutation is safe (the copy already
                        # happened) and no bytes() flatten survives.
                        self.memory_store.put_plasma_location(
                            poid, list(self.agent_address), size=size)
                        entry = {"ref": [poid, list(self.address),
                                         [list(self.agent_address)]],
                                 "sz": size}
                    else:
                        # Arena full (or submitting from the loop thread,
                        # which must not carry the memcpy): the store
                        # happens later on the loop — so the parts must
                        # be detached from the caller's mutable buffers.
                        big_puts.append(
                            (poid, [bytes(p) for p in parts]))
                        entry = {"ref": [poid, list(self.address), None]}
            if kw:
                entry["kw"] = kw
            entries.append(entry)
        return entries, ref_args, borrowed_args, big_puts

    async def _store_big_puts(self, spec_args, big_puts):
        """Plasma-store oversized sync-serialized args and stamp their
        location hints into the spec entries."""
        for poid, parts in big_puts:
            await self._put_plasma(poid, parts)
            entry_ms = self.memory_store.get(poid)
            for e in spec_args:
                if "ref" in e and bytes(e["ref"][0]) == poid:
                    e["ref"][2] = [list(self.agent_address)]
                    if entry_ms is not None and entry_ms.size:
                        e["sz"] = entry_ms.size

    def submit_actor_task(self, *, actor_id: bytes, method: str, args, kwargs,
                          num_returns, max_task_retries: int = 0,
                          generator_backpressure: int = 0,
                          out_of_order: bool = False,
                          timeout_s: Optional[float] = None
                          ) -> List[ObjectRef]:
        """Sync-safe from ANY thread, including the event loop (async actor
        methods submitting to other actors — e.g. a Serve controller
        pinging replicas). Args are serialized synchronously on the calling
        thread (so post-call mutation of them is safe, matching reference
        semantics); only plasma puts for oversized values and the push
        itself run as a scheduled coroutine."""
        if self.loop is None:
            raise RuntimeError("core worker not started")
        num_returns, streaming = self._parse_streaming(
            num_returns, generator_backpressure)
        state = self._actors.get(actor_id)
        if state is None:
            state = self._actors.setdefault(actor_id, _ActorState(actor_id))
        if out_of_order:
            state.out_of_order = True
        task_id = fast_actor_task_id(actor_id)
        if not args and not kwargs:
            # No-arg fast branch (ping/poll-style calls dominate fan-out
            # load; skips the arg-entry walk entirely).
            entries, ref_args, borrowed_args, big_puts = [], [], [], []
        else:
            entries, ref_args, borrowed_args, big_puts = \
                self._build_arg_entries_sync(args, kwargs)
        with self._seq_lock:
            state.seq += 1
            seq = state.seq
        # `is not None`, not truthiness: timeout_s=0 is an already-
        # exhausted budget (e.g. max(0, remaining)) and must expire
        # typed immediately, not silently run unbounded.
        deadline = (time.time() + timeout_s) if timeout_s is not None \
            else deadlines.get()
        spec = protocol.make_task_spec(
            task_id=task_id, job_id=self.job_id, fn_id=b"", args=entries,
            nreturns=num_returns, owner_addr=list(self.address), resources={},
            retries_left=max_task_retries,
            actor_id=actor_id, method=method, seq=seq, name=method,
            streaming=streaming, deadline=deadline)
        refs = []
        for i in range(num_returns):
            oid = task_id + (i + 1).to_bytes(4, "little")
            self.reference_counter.add_owned(oid)
            refs.append(ObjectRef(oid, self.address, worker=self))
        if streaming is not None:
            self.register_stream(task_id, streaming["bp"],
                                 expected_attempt=max_task_retries)
            refs = [ObjectRefGenerator(self, task_id, refs[0])]
        task = _PendingTask(spec, ref_args, borrowed_args)
        self.record_task_event(task_id, method, "SUBMITTED")

        def _go():
            state.submit_queue.append((spec, task, big_puts))
            self._arm_task_deadline(spec)
            self._schedule_actor_drain(state)

        if self._on_loop_thread():
            _go()
        else:
            self._post_to_loop(_go)
        return refs

    def _schedule_actor_drain(self, state: _ActorState):
        """Defer the queue drain to the END of the current loop tick so a
        submission burst (e.g. 200 .remote() calls landing as consecutive
        callbacks) accumulates and leaves as a handful of multi-call frames
        instead of 200 singles. An eager drain-per-submission would always
        see a 1-element queue."""
        if state.drain_scheduled or state.draining:
            return
        state.drain_scheduled = True

        def _kick():
            state.drain_scheduled = False
            if not state.draining and state.submit_queue:
                self._spawn(self._drain_actor_queue(state))

        self.loop.call_soon(_kick)

    async def submit_actor_task_async(self, *, actor_id, method, args, kwargs,
                                      num_returns, max_task_retries: int = 0,
                                      generator_backpressure: int = 0
                                      ) -> List[ObjectRef]:
        return self.submit_actor_task(
            actor_id=actor_id, method=method, args=args, kwargs=kwargs,
            num_returns=num_returns, max_task_retries=max_task_retries,
            generator_backpressure=generator_backpressure)

    _ACTOR_PUSH_BATCH = 256

    async def _drain_actor_queue(self, state):
        """Drains the per-actor queue in submission order: awaiting the
        plasma puts happens inside the drain, and each push is scheduled
        (not awaited) so concurrent calls still pipeline to async actors.

        Specs that are push-ready without awaiting anything (no plasma
        puts, no ref args — the fan-out hot path) accumulate and go out as
        ONE multi-call frame (rpc.call_many): each sub-call still
        dispatches and replies independently on the worker, so semantics
        match per-call pushes, but framing costs amortize (~4x fewer
        cycles/call under load; reference: actor_task_submitter.cc sends
        per-task gRPC but amortizes in C++ — batching is the Python-plane
        equivalent)."""
        if state.draining:
            return
        state.draining = True
        batch: list = []

        def _flush():
            if not batch:
                return
            items, batch[:] = list(batch), []
            self._spawn(self._push_actor_batch(state, items))

        try:
            while state.submit_queue:
                spec, task, big_puts = state.submit_queue.popleft()
                if not big_puts and not any(
                        "ref" in e for e in spec["args"]):
                    batch.append((spec, task))
                    if len(batch) >= self._ACTOR_PUSH_BATCH:
                        _flush()
                    continue
                # Slow path (plasma puts / ref-arg resolution may suspend):
                # flush what's accumulated first so ready pushes aren't
                # gated behind this item's awaits.
                _flush()
                if state.out_of_order:
                    # Out-of-order submit queue (reference:
                    # out_of_order_actor_submit_queue.cc, opted into via
                    # allow_out_of_order_execution): this call resolves
                    # its deps OFF the drain, so later calls whose deps
                    # are already ready are not head-of-line blocked
                    # behind it.  Only meaningful for actors that execute
                    # concurrently anyway (async / max_concurrency>1).
                    self._spawn(
                        self._resolve_and_push_actor_task(state, spec,
                                                          task, big_puts))
                    continue
                if not await self._resolve_task_args(spec, task,
                                                           big_puts):
                    continue
                self._spawn(
                    self._push_actor_task(state, spec, task))
            _flush()
        finally:
            state.draining = False
            # Submissions that raced the final drain iteration (appended
            # after the while-check) restart the drain.
            if state.submit_queue:
                self._schedule_actor_drain(state)

    async def _resolve_task_args(self, spec, task, big_puts) -> bool:
        """Submitter-side dependency resolution for owned ref args, shared
        by normal-task and actor-task submission (reference:
        dependency_resolver.cc — the task is not pushed until its deps
        exist): pending results are awaited, small values inlined, plasma
        locations stamped.  Keeps the callee's execution slot free while
        deps materialize and removes the callee-side fetch timeout from
        the path.  Returns False (task failed) on a put/resolve error."""
        try:
            await self._store_big_puts(spec["args"], big_puts)
            for e in spec["args"]:
                if "ref" not in e:
                    continue
                roid = bytes(e["ref"][0])
                if tuple(e["ref"][1]) != self.address:
                    continue   # borrowed: callee resolves via owner
                if e["ref"][2] is not None:
                    continue   # already has a plasma location
                entry = await self.memory_store.wait_for(roid)
                if entry.data is not None:
                    val = {"v": entry.data}
                    if "kw" in e:
                        val["kw"] = e["kw"]
                    e.clear()
                    e.update(val)
                elif entry.plasma_node is not None:
                    e["ref"][2] = self._ordered_locations(entry)
                    if entry.size:
                        e["sz"] = entry.size
        except Exception as e:  # put/resolve failed: fail this task
            self._store_task_exception(spec, exc.RayError(
                f"failed to resolve task arg: {e}"))
            self._release_task_pins(task)
            return False
        return True

    async def _resolve_and_push_actor_task(self, state, spec, task,
                                           big_puts):
        """Out-of-order path: resolve deps independently, push when
        ready."""
        if await self._resolve_task_args(spec, task, big_puts):
            await self._push_actor_task(state, spec, task)

    async def _actor_conn(self, state: _ActorState) -> rpc.Connection:
        if state.conn is not None and not state.conn.closed:
            return state.conn
        if state.resolving is not None:
            await state.resolving
            if state.conn is not None and not state.conn.closed:
                return state.conn
        state.resolving = asyncio.get_running_loop().create_future()
        try:
            reg = self._registering.get(state.actor_id)
            if reg is not None:
                # This process kicked off the registration (loop-thread
                # create_actor): wait for it instead of a bounded GCS poll
                # — oversized init args can take arbitrarily long to store.
                try:
                    await asyncio.shield(reg)
                except Exception as e:
                    raise exc.ActorDiedError(
                        f"actor registration failed: {e}") from None
            for attempt in range(60):
                info = await self.gcs.call(
                    "get_actor", {"actor_id": state.actor_id,
                                  "wait_alive": True}, timeout=60)
                if info is None:
                    # The handle may have been minted before its background
                    # registration reached the GCS (loop-thread create_actor
                    # returns immediately); give registration a grace window
                    # before declaring the actor dead.
                    if attempt < 59:
                        await asyncio.sleep(0.25)
                        continue
                    raise exc.ActorDiedError("actor was never registered")
                if info["state"] == protocol.ACTOR_DEAD:
                    state.dead = True
                    state.death_cause = info.get("death_cause") or "dead"
                    raise exc.ActorDiedError(state.death_cause)
                if info["state"] == protocol.ACTOR_ALIVE and info["address"]:
                    try:
                        conn = await rpc.connect(
                            tuple(info["address"]), name="cw->actor",
                            retries=3, on_close=self._on_peer_conn_close)
                        conn.fast_handlers["complete_batch"] = \
                            self._f_complete_batch
                        state.conn = conn
                        state.address = tuple(info["address"])
                        return state.conn
                    except rpc.ConnectionLost:
                        pass
                await asyncio.sleep(0.25)
            raise exc.ActorDiedError("timed out resolving actor address")
        finally:
            fut, state.resolving = state.resolving, None
            fut.set_result(None)

    def _sweep_cancelled_actor(self, tasks):
        """Resolve any cancelled calls in `tasks`; returns the rest."""
        still = []
        for task in tasks:
            tid = task.spec["task_id"]
            if tid in self._cancelled:
                self._store_task_exception(task.spec, exc.TaskCancelledError(
                    f"{task.spec['method']} cancelled"))
                self._release_task_pins(task)
                self._cancelled.discard(tid)
            else:
                still.append(task)
        return still

    async def _push_actor_batch(self, state: _ActorState, items):
        """Push a burst of ready actor calls as one submit_batch frame
        (pre-encoded prefix + per-call deltas); results return as
        coalesced complete_batch frames on the same connection.

        Same per-call semantics as _push_actor_task (cancel checks, retry
        across restarts per retries_left, death-cause reporting — see
        _actor_tasks_lost) — only the framing and completion plumbing are
        shared.  The worker enqueues the batch in frame order onto the
        same serial queue per-call pushes use, so a sequential actor
        executes calls in submission order across batch boundaries."""
        if len(items) == 1:
            await self._push_actor_task(state, items[0][0], items[0][1])
            return
        tasks = [t for _s, t in items]
        while True:
            tasks = self._sweep_cancelled_actor(tasks)
            if not tasks:
                return
            try:
                conn = await self._actor_conn(state)
            except exc.ActorDiedError as e:
                for task in tasks:
                    self._store_task_exception(task.spec, e)
                    self._release_task_pins(task)
                return
            # Cancels may have landed while the connection resolved (an
            # actor restart can block _actor_conn for minutes); honor them
            # before the push, as the single-task path does.
            tasks = self._sweep_cancelled_actor(tasks)
            if not tasks:
                return
            if not conn.closed:
                break
            state.conn = None
        if state.prefix is None:
            state.prefix = protocol.spec_prefix_of(tasks[0].spec)
            state.prefix_blob = protocol.encode_prefix(state.prefix)
        t_push = time.monotonic()
        for task in tasks:
            tid = task.spec["task_id"]
            self._inflight_actor_tasks[tid] = state
            self._pending_replies[tid] = ("a", state, conn, task, t_push)
        outcome, info = await self._submit_batch_with_ack(
            conn, state.prefix, state.prefix_blob, tasks,
            actor=True, abort_label=f"actor {state.actor_id.hex()[:8]}")
        if outcome == "remote_error":
            e, pending = info
            for task in pending:
                tid = task.spec["task_id"]
                if self._pending_replies.pop(tid, None) is None:
                    continue
                self._inflight_actor_tasks.pop(tid, None)
                self._store_task_exception(task.spec, exc.RayError(
                    f"actor push failed: {e}"))
                self._release_task_pins(task)
        elif outcome == "conn_lost":
            if state.conn is conn:
                state.conn = None
            leftovers = [t for t in tasks
                         if self._pending_replies.pop(t.spec["task_id"],
                                                      None) is not None]
            if leftovers:
                await self._actor_tasks_lost(state, leftovers)

    async def _actor_tasks_lost(self, state: _ActorState, tasks):
        """The actor's connection died with these calls awaiting
        completion: honor cancels, retry per retries_left across the
        restart (re-entering through the reconnect-aware single-call
        path), and fail the rest with the GCS-recorded death cause (one
        lookup for the whole burst)."""
        death_cause = None
        for task in tasks:
            spec = task.spec
            tid = spec["task_id"]
            self._inflight_actor_tasks.pop(tid, None)
            if tid in self._cancelled:
                self._store_task_exception(spec, exc.TaskCancelledError(
                    f"{spec['method']} cancelled"))
                self._release_task_pins(task)
                self._cancelled.discard(tid)
            elif spec["retries_left"] != 0:
                # Negative = infinite (max_task_retries=-1).
                if spec["retries_left"] > 0:
                    spec["retries_left"] -= 1
                self._stream_reset_for_retry(spec)
                self._spawn(self._push_actor_task(state, spec, task))
            else:
                if death_cause is None:
                    death_cause = await self._actor_death_cause(
                        state.actor_id)
                self._store_task_exception(spec, exc.ActorDiedError(
                    f"actor {state.actor_id.hex()[:8]} died during "
                    f"{spec['method']}"
                    + (f": {death_cause}" if death_cause else "")))
                self._release_task_pins(task)

    async def _push_actor_task(self, state: _ActorState, spec, task):
        """Push with reconnect-after-restart: a ConnectionLost mid-call
        retries against the actor's next incarnation while retries_left
        lasts (reference: actor_task_submitter.cc queueing across restarts
        per max_task_retries); _actor_conn blocks through RESTARTING and
        raises once the GCS declares the actor DEAD."""
        task_id = spec["task_id"]
        while True:
            if task_id in self._cancelled:
                self._store_task_exception(
                    spec, exc.TaskCancelledError(f"{spec['method']} cancelled"))
                self._release_task_pins(task)
                self._cancelled.discard(task_id)
                return
            try:
                conn = await self._actor_conn(state)
            except exc.ActorDiedError as e:
                self._store_task_exception(spec, e)
                self._release_task_pins(task)
                return
            if task_id in self._cancelled:
                continue  # loop top resolves it as cancelled
            self._inflight_actor_tasks[task_id] = state
            try:
                # timeout=0: this per-call push's reply IS the method's
                # completion — a long-running actor method must not be
                # guillotined by the unary-call default.
                reply = await conn.call("push_actor_task", spec, timeout=0)
            except rpc.ConnectionLost:
                state.conn = None
                if task_id in self._cancelled:
                    self._store_task_exception(
                        spec, exc.TaskCancelledError(
                            f"{spec['method']} cancelled"))
                    self._release_task_pins(task)
                    self._cancelled.discard(task_id)
                    return
                if spec["retries_left"] != 0:
                    # Negative = infinite (max_task_retries=-1).
                    if spec["retries_left"] > 0:
                        spec["retries_left"] -= 1
                    self._stream_reset_for_retry(spec)
                    continue
                cause = await self._actor_death_cause(state.actor_id)
                self._store_task_exception(spec, exc.ActorDiedError(
                    f"actor {state.actor_id.hex()[:8]} died during "
                    f"{spec['method']}"
                    + (f": {cause}" if cause else "")))
                self._release_task_pins(task)
                return
            finally:
                self._inflight_actor_tasks.pop(task_id, None)
            self._handle_reply(spec, task, reply)
            return

    async def _actor_death_cause(self, actor_id: bytes) -> str:
        """Fetch the GCS-recorded death cause (e.g. the OOM monitor's
        reason) for a crashed actor.  The agent's reaper reports the death
        within its 0.5 s poll, so give the record a short grace window."""
        for i in range(8):
            try:
                info = await self.gcs.call(
                    "get_actor", {"actor_id": actor_id,
                                  "wait_alive": False}, timeout=5)
            except (rpc.RpcError, asyncio.TimeoutError):
                return ""
            if info and info.get("death_cause"):
                return info["death_cause"]
            if info and info["state"] == protocol.ACTOR_ALIVE and i >= 3:
                # Still ALIVE well past the reaper's report window: the
                # actor restarted rather than died terminally.  (Early
                # ALIVE reads just mean the death report hasn't landed.)
                return ""
            await asyncio.sleep(0.4)
        return ""

    def kill_actor(self, actor_id: bytes, no_restart=True):
        if self._on_loop_thread():
            self.kill_actor_nowait(actor_id)
        else:
            self._run(self.gcs.call("kill_actor", {"actor_id": actor_id}))
        st = self._actors.get(actor_id)
        if st:
            st.dead = True

    def kill_actor_nowait(self, actor_id: bytes):
        """Fire-and-forget termination used by handle GC — safe to call from
        __del__ on any thread, including the loop thread."""
        if self._shutdown or self.loop is None or not self.loop.is_running():
            return
        def _go():
            if self.gcs and not self.gcs.closed:
                try:
                    self.gcs.notify("kill_actor", {"actor_id": actor_id})
                except rpc.RpcError:
                    pass
        self.loop.call_soon_threadsafe(_go)

    def get_actor_info(self, *, actor_id=None, name=None):
        return self._run(self.gcs.call(
            "get_actor", {"actor_id": actor_id, "name": name,
                          "wait_alive": False}))

    async def get_actor_info_async(self, *, actor_id=None, name=None):
        """Loop-thread-safe variant for async actor methods (e.g. a Serve
        handle resolving its controller from inside a deployment)."""
        return await self.gcs.call(
            "get_actor", {"actor_id": actor_id, "name": name,
                          "wait_alive": False})
