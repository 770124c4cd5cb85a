"""Per-node agent: worker pool, lease scheduler, object-store host.

Equivalent of the reference's raylet (reference: src/ray/raylet/
node_manager.h:133, worker_pool.cc, local_lease_manager.cc) hosting the
shared-memory object store in-process (reference: main.cc:689
ObjectStoreRunner). Responsibilities:

- owns the node's /dev/shm arena lifecycle (create on start, unlink on exit)
- spawns and pools worker processes; leases them to submitters
  (reference: WorkerPool::PopWorker worker_pool.h:55, lease protocol in
  node_manager.proto:441 RequestWorkerLease)
- tracks node resources; placement-group bundle prepare/commit
  (reference: placement_group_resource_manager.cc, 2-phase commit)
- serves cross-node object pulls out of the local store and fetches remote
  objects into it (reference: object_manager/pull_manager.cc + push path)
- registers with the GCS and reports its resource view periodically
  (reference: ray_syncer)
"""

from __future__ import annotations

import argparse
import asyncio
import json
import logging
import os
import signal
import subprocess
import sys
import threading
import time
from typing import Dict, List, Optional, Sequence, Set, Tuple

from . import clocks, diagnosis, loopmon, protocol, rpc
from . import flight_recorder as frec
from .config import Config, get_config, set_config
from .ids import NodeID, WorkerID
from .shm_store import (ObjectExistsError, ShmStore, SpillTruncatedError,
                        StoreFullError)
from .. import exceptions as exc

logger = logging.getLogger("ray_tpu.agent")

IDLE_WORKER_KEEP = 8          # pooled idle workers kept hot per node
LEASE_IDLE_TIMEOUT_S = 2.0


def _needs_tpu(resources) -> bool:
    return any(k == "TPU" or k.startswith("TPU-") for k, v in
               (resources or {}).items() if v > 0)


def _write_file(path: str, data) -> None:
    with open(path, "wb") as f:
        f.write(data)


def _intervals_add(ivs: list, start: int, end: int) -> None:
    """Merge [start, end) into a sorted list of disjoint committed-byte
    intervals (in place).  Pulls commit chunks out of order, so the list
    stays short (≤ inflight window) in steady state."""
    import bisect
    i = bisect.bisect_left(ivs, (start, start))
    if i > 0 and ivs[i - 1][1] >= start:
        i -= 1
    j = i
    while j < len(ivs) and ivs[j][0] <= end:
        start = min(start, ivs[j][0])
        end = max(end, ivs[j][1])
        j += 1
    ivs[i:j] = [(start, end)]


def _intervals_cover(ivs: list, start: int, end: int) -> bool:
    """Whether [start, end) is fully inside one committed interval."""
    if start >= end:
        return True
    import bisect
    i = bisect.bisect_right(ivs, (start, float("inf"))) - 1
    return i >= 0 and ivs[i][0] <= start and ivs[i][1] >= end


def _read_file(path: str) -> bytes:
    with open(path, "rb") as f:
        return f.read()


class _ForkedProc:
    """Popen-shaped handle for a zygote-forked worker. The zygote (not
    the agent) is the child's parent and reaps it on SIGCHLD, so death is
    observed via /proc rather than waitpid; signals go by pid."""

    @staticmethod
    def _stat_fields(pid: int):
        """(state, starttime) from /proc/<pid>/stat; None if gone. comm may
        itself contain ')', so split on the LAST one."""
        try:
            with open(f"/proc/{pid}/stat", "rb") as f:
                rest = f.read().rsplit(b")", 1)[1].split()
            return rest[0], rest[19]   # fields 3 and 22 (1-indexed)
        except (OSError, IndexError):
            return None

    def __init__(self, pid: int):
        self.pid = pid
        self.returncode: int | None = None
        st = self._stat_fields(pid)
        # starttime pins identity: a recycled pid after death+reap must
        # not make a dead worker look alive (or get SIGTERMed by proxy).
        self._starttime = st[1] if st else None

    def poll(self):
        if self.returncode is not None:
            return self.returncode
        st = self._stat_fields(self.pid)
        if st is not None and st[0] != b"Z" and st[1] == self._starttime:
            return None
        self.returncode = 0
        return self.returncode

    def send_signal(self, sig):
        st = self._stat_fields(self.pid)
        if st is None or st[1] != self._starttime:
            return              # pid recycled: never signal a stranger
        try:
            os.kill(self.pid, sig)
        except ProcessLookupError:
            pass

    def terminate(self):
        self.send_signal(signal.SIGTERM)

    def kill(self):
        self.send_signal(signal.SIGKILL)

    def wait(self, timeout=None):
        deadline = None if timeout is None else time.monotonic() + timeout
        while self.poll() is None:
            if deadline is not None and time.monotonic() > deadline:
                raise subprocess.TimeoutExpired("zygote-forked-worker",
                                                timeout)
            time.sleep(0.02)
        return self.returncode


class WorkerHandle:
    def __init__(self, worker_id: bytes, proc: subprocess.Popen):
        self.worker_id = worker_id
        self.proc = proc
        self.address = None           # set at registration
        self.conn: Optional[rpc.Connection] = None   # agent→worker
        self.registered = asyncio.Event()
        self.lease_id: Optional[bytes] = None
        self.lease_resources: Dict[str, float] = {}
        self.lease_bundle: Optional[Tuple[bytes, int]] = None  # PG bundle key
        self.needs_tpu = False        # never pooled: may hold a chip open
        self.chip_ids: Tuple[int, ...] = ()   # real chips this process owns
        self.is_actor = False
        self.has_env = False          # runtime-env workers never pool
        self.lease_owner_conn = None  # server conn that requested the lease
        self.actor_id: Optional[bytes] = None
        self.last_idle = time.monotonic()
        self.spawned_at = time.monotonic()
        # Diagnosis plane: grant stamp for the lease-stall detector
        # (granted-but-never-RUNNING); flagged-once latch per grant.
        self.lease_granted_at: Optional[float] = None
        self.lease_stall_flagged = False
        # Cluster epoch this lease was granted under (GCS HA fencing).
        self.granted_epoch = 0
        # Blocked-get CPU release (reference: NodeManager::
        # HandleNotifyDirectCallTaskBlocked, node_manager.cc — a worker
        # blocked in ray.get releases its CPU so queued work can run).
        self.blocked_depth = 0        # concurrent blocked gets in this worker
        self.blocked_cpus = 0.0       # CPU amount currently released


class NodeAgent:
    # Transfer counters are bumped from I/O shard threads too
    # (shard-local chunk serving): exact under the lock, which is
    # ns-scale against a multi-MiB chunk serve.  Class-level so
    # skeletal test instances share it.
    _served_lock = threading.Lock()

    def __init__(self, *, gcs_address, session_dir: str, node_id: bytes,
                 resources: Dict[str, float], labels: Dict[str, str],
                 store_capacity: int, host: str = "127.0.0.1",
                 tpu_chips: Sequence[int] = ()):
        self.gcs_address = tuple(gcs_address)
        self.session_dir = session_dir
        # Cluster epoch (GCS HA fencing token, docs/control_plane.md §8):
        # learned from registration + every heartbeat reply, monotonic.
        # Stamped into every lease grant; a lease request presenting an
        # OLDER epoch is rejected typed (REJECT_STALE_EPOCH) so owners
        # refresh and resubmit through the normal retry path.
        self.cluster_epoch = protocol.EPOCH_NONE
        from .runtime_env import UriCache
        self.uri_cache = UriCache(
            os.path.join(session_dir, "runtime_resources"))
        self.node_id = node_id
        self.host = host
        self.labels = labels
        self.resources_total = dict(resources)
        self.resources_available = dict(resources)
        # Real chips on this host (empty when TPU counts were injected on
        # a host without chips): a TPU worker is confined to the ids its
        # lease takes from _free_chips and returns them when it EXITS.
        self._host_chips: Tuple[int, ...] = tuple(sorted(tpu_chips))
        if self._host_chips and \
                resources.get("TPU", 0) > len(self._host_chips):
            raise ValueError(
                f"TPU={resources['TPU']} but this host exposes "
                f"{len(self._host_chips)} chips")
        self._free_chips: List[int] = list(self._host_chips)
        self.store_path = os.path.join(
            "/dev/shm", f"raytpu_{node_id.hex()[:12]}")
        self.store = ShmStore.create(self.store_path, store_capacity)
        self.workers: Dict[bytes, WorkerHandle] = {}
        self.idle_workers: List[WorkerHandle] = []      # CPU pool
        self.leases: Dict[bytes, WorkerHandle] = {}
        self.bundles: Dict[Tuple[bytes, int], Dict[str, float]] = {}
        self.pinned: Dict[bytes, int] = {}   # object_id -> pin count (owner pins)
        # Spill manager state (reference: raylet LocalObjectManager,
        # local_object_manager.h:43 — spills pinned primaries to disk under
        # memory pressure, restores on demand).
        cfg = get_config()
        self.spilled: Dict[bytes, Tuple[str, int]] = {}  # oid -> (path, size)
        self._spilling: Set[bytes] = set()               # writes in flight
        self._disk_cached: Dict[bytes, int] = {}         # non-primary copies
        self._spill_dir = cfg.object_spill_dir or os.path.join(
            session_dir, "spill", node_id.hex()[:12])
        self._spill_threshold = cfg.object_spill_threshold
        # Durable external tier (reference: _private/external_storage.py):
        # spills also upload here + register in the GCS KV, so any node
        # can restore a dead node's spilled objects.
        self._ext = None
        self._ext_uris: Dict[bytes, str] = {}
        if cfg.object_spill_external_uri:
            from .external_storage import storage_from_uri
            self._ext = storage_from_uri(cfg.object_spill_external_uri)
        self._pull_inflight: Dict[bytes, asyncio.Future] = {}
        self._pull_waiters: List[Tuple[int, int, asyncio.Future]] = []  # heap
        self._pull_active = 0
        self._pull_seq = 0
        # --- Tiered-memory admission (the CreateRequestQueue analogue;
        # reference: plasma create_request_queue.h + SURVEY N15/N16's
        # unified object manager).  Creates that cannot reserve arena
        # headroom park in a bounded FIFO; _create_queue_loop retries the
        # HEAD as eviction/spill frees room (FIFO = no small-object
        # starvation of a big create), expiring entries typed.
        # _reserved is the admission ledger: oid -> (nbytes, expiry) for
        # creates granted headroom but not yet sealed — counted as
        # in-use by every sweep/admission decision, so a racing put can
        # never be granted the same headroom and a pressure sweep never
        # treats an unsealed in-progress region as reclaimable.
        from collections import deque as _cq
        self._create_queue: _cq = _cq()
        self._create_queue_depth_max = int(cfg.create_queue_depth)
        self._create_event = asyncio.Event()
        self._reserved: Dict[bytes, Tuple[int, float]] = {}
        self._pinned_floor = int(cfg.eviction_pinned_bytes_floor)
        # Spill/restore byte counters (observability catalog rows).
        self._spilled_bytes_total = 0
        self._restored_bytes_total = 0
        # Memory-pressure chaos (config mem_chaos): squeezes the
        # EFFECTIVE arena budget the admission/spill policy sees.
        # Consulted lazily via _capacity_scale() — no extra thread.
        self._mem_chaos = None
        if cfg.mem_chaos:
            from .chaos import MemChaos
            self._mem_chaos = MemChaos(cfg.mem_chaos)
        self._shed_threshold = float(cfg.lease_shed_pressure_threshold)
        self._leases_shed = 0
        # Replica-plane state (see docs/data_plane.md "replica directory"):
        # oid -> owner addr for SECONDARY copies this node registered with
        # an owner (pulled replicas; deregistered on eviction/free/drain so
        # directory entries can't outlive the bytes) ...
        self._replica_owner: Dict[bytes, tuple] = {}
        # ... oid -> owner addr for pinned PRIMARIES (pin_transfer/
        # pin_object stamp it; drain migration forwards it so the adoptive
        # node can repoint the owner's directory) ...
        self._pinned_owner: Dict[bytes, tuple] = {}
        # ... and in-progress arena pulls serving their already-committed
        # chunks to peers (receiver-becomes-source, Cornet-style):
        # oid -> {"size", "buf" (create_buffer view or None for
        # disk-destined pulls), "done" (committed [start, end) intervals)}.
        self._partial: Dict[bytes, dict] = {}
        # Transfer counters (heartbeat -> GCS node view -> `ray_tpu list
        # nodes` / dashboard transfer column).
        self._bytes_served = 0
        self._bytes_pulled = 0
        self._last_pull_sources = 0   # observability: swarm width of the
        #                               most recent pull on this node
        self._chunk_bytes = cfg.object_transfer_chunk_bytes
        self._max_pulls = cfg.max_concurrent_pulls
        self._max_inflight_chunks = cfg.object_transfer_max_inflight_chunks
        self._chunk_timeout = cfg.object_transfer_chunk_timeout_s
        # Per-peer link health: addr -> {"lat": deque[s], "rtt": EMA s,
        # "rate": EMA B/s, "fail": count, "ts": monotonic of last sample}.
        # Fed by every fetch_chunk/object_info round trip; drives the
        # hedge delay (p95 of recent latencies) and rides heartbeats to
        # the GCS as gray-failure evidence about OTHER nodes.
        self._peer_stats: Dict[tuple, dict] = {}
        # Hedge budget (The Tail at Scale): hedged fetches are capped at
        # a fraction of total fetches plus a small burst, so hedging
        # can't amplify load on a cluster that is slow because it is
        # OVERLOADED rather than gray.
        self._hedge_enabled = cfg.pull_hedge_enabled
        self._hedge_delay_ms = cfg.pull_hedge_delay_ms
        self._hedge_budget_frac = cfg.pull_hedge_budget_fraction
        self._hedge_total = 0
        self._hedge_used = 0
        # Flight-recorder rows whose flush notify failed, kept for the
        # next heartbeat tick (bounded at ring capacity; overflow folds
        # into the recorder's drop counter — no silent loss).
        self._frec_retry: List[dict] = []
        # Drop-accounting reporter key: PROCESS-stable, deliberately not
        # node_id — a fresh-id rejoin (_rejoin_with_fresh_id) would
        # otherwise re-report the same cumulative drop count under a new
        # key and the GCS would double-count it.
        self._telemetry_src = os.urandom(8)
        # Parked lease requests: (params, conn, reply_future, deadline),
        # FIFO-granted by _parked_lease_loop as resources free (reference:
        # ClusterLeaseManager's lease queue).
        from collections import deque as _dq
        self._parked_leases: _dq = _dq()
        self._park_event = asyncio.Event()
        # Daemon I/O sharding (config daemon_io_shards): accepted
        # connections live on shard event-loop threads.  Shard-local
        # handlers are the pure arena/io ones — `ping` and the sealed-
        # object `fetch_chunk` fast path (the shm store is cross-process
        # shared memory, so cross-thread reads are its normal operating
        # mode); every state-touching branch FAST_FALLBACKs into the
        # batched main-loop hop.
        self._io_shards = rpc.make_io_shard_pool("agent")
        # Compiled-DAG channel plane: rings created here on behalf of
        # remote compilers + bridge threads pumping cross-node edges
        # (see _private/dag_channels.py and docs/dag.md).
        from .dag_channels import DagChannelManager
        self._dag_chans = DagChannelManager(self.store)
        self._server = rpc.RpcServer(
            self._handlers(), name="agent",
            on_client_close=self._on_client_close,
            io_shards=self._io_shards,
            shard_handlers={
                # The SAME callable as the main handlers dict: the
                # t1/t2 stamp semantics feed clock-offset estimation
                # and must never diverge between modes.
                "ping": self._h_ping,
                "fetch_chunk": self._sh_fetch_chunk,
                # CHAOS (diagnosis_chaos_enabled only): wedge the loop
                # that serves this conn on purpose — the loop-wedge
                # detector's fault-injection hook.  Registered on the
                # shard plane so a sharded agent stalls a SHARD thread.
                **({"debug_stall_loop": self._sh_debug_stall}
                   if cfg.diagnosis_chaos_enabled else {}),
            })
        self.gcs: Optional[rpc.Connection] = None
        self._spawn_lock = asyncio.Lock()
        self._peer_conns: Dict[tuple, rpc.Connection] = {}
        self._tasks: List[asyncio.Task] = []
        self._shutdown = False
        # Graceful drain state (reference: raylet drain / autoscaler
        # DrainNode): reason string while draining — new leases/actors/
        # bundles are refused (with spillback), in-flight leases finish,
        # pinned primaries migrate to a peer before the node exits.
        # _drain_deadline bounds the state itself: well past it, a drain
        # whose orchestrator vanished (GCS crash mid-drain) is abandoned
        # rather than leaving a permanent zombie (see _report_loop).
        self._draining: Optional[str] = None
        self._drain_deadline: float = 0.0
        # Primaries this node adopted from a draining peer (oid set): a
        # later owner free must also clear the cluster-wide "migrated"
        # KV record the drain left behind.
        self._adopted: Set[bytes] = set()
        # oid -> destination agent address for primaries migrated OFF this
        # node while it drains: frees arriving here before teardown are
        # forwarded so the adopted copy (and its pin) can't leak.
        self._migrated_away: Dict[bytes, tuple] = {}
        # worker_id -> {"reason", "ts"}: deaths caused by the OOM monitor,
        # queried by owners via h_worker_fate for typed errors.
        self._oom_kills: Dict[bytes, dict] = {}
        # Optional kernel-level worker isolation (reference: cgroup2
        # system/application split; config `cgroup_enabled`).
        self._worker_cgroup = None
        if cfg.cgroup_enabled:
            from .cgroup import WorkerCgroup
            mem = cfg.cgroup_memory_max_bytes or None
            grp = WorkerCgroup(f"ray_tpu_{self.node_id.hex()[:8]}",
                               memory_max=mem)
            self._worker_cgroup = grp if grp.active else None

    def _handlers(self):
        return {
            "register_worker": self.h_register_worker,
            "request_lease": self.h_request_lease,
            "return_lease": self.h_return_lease,
            "create_actor_worker": self.h_create_actor_worker,
            "actor_worker_died": self.h_actor_worker_died,
            "prepare_bundle": self.h_prepare_bundle,
            "reserve_bundles": self.h_reserve_bundles,
            "commit_bundle": self.h_commit_bundle,
            "return_bundle": self.h_return_bundle,
            "drain": self.h_drain,
            "adopt_primary": self.h_adopt_primary,
            "pin_object": self.h_pin_object,
            "pin_transfer": self.h_pin_transfer,
            "unpin_object": self.h_unpin_object,
            "free_objects": self.h_free_objects,
            "fetch_from_store": self.h_fetch_from_store,
            "object_info": self.h_object_info,
            "fetch_chunk": self.h_fetch_chunk,
            "pull_object": self.h_pull_object,
            "ensure_space": self.h_ensure_space,
            "reserve_create": self.h_reserve_create,
            "spill_path": self.h_spill_path,
            "spill_register": self.h_spill_register,
            "restore_object": self.h_restore_object,
            "node_info": self.h_node_info,
            "store_stats": self.h_store_stats,
            "list_objects": self.h_list_objects,
            # Timestamped ping: the GCS health probe doubles as the
            # clock-alignment probe (NTP t1/t2 server stamps; clocks.wall
            # so injected chaos skew is visible to the estimator exactly
            # like a genuinely off host clock).  Value is ignored by
            # plain liveness callers.  Shared with shard_handlers —
            # sharded and single-loop mode must stamp identically.
            "ping": self._h_ping,
            "worker_fate": self.h_worker_fate,
            "worker_blocked": self.h_worker_blocked,
            "worker_unblocked": self.h_worker_unblocked,
            "profile_worker": self.h_profile_worker,
            "node_profile": self.h_node_profile,
            # Agent's OWN stacks/cpu_profile (diagnosis plane): the
            # cluster_profile fan-out reaches daemons through these.
            **diagnosis.profile_handlers("agent"),
            **({"debug_stall_loop": self._sh_debug_stall}
               if get_config().diagnosis_chaos_enabled else {}),
            "list_logs": self.h_list_logs,
            "read_log": self.h_read_log,
            "shutdown": self.h_shutdown,
            **self._dag_chans.handlers(),
        }

    @staticmethod
    def _h_ping(conn, p):
        return {"pong": True, "t1": clocks.wall(), "t2": clocks.wall()}

    def _sh_debug_stall(self, conn, p):
        """CHAOS (diagnosis_chaos_enabled): block THIS handler's loop
        thread with a synchronous sleep — a real wedge, not a
        simulation: the loopmon probe stops ticking, the stale gauge
        grows, and the watchdog must catch it from its sibling thread."""
        time.sleep(min(float(p.get("seconds", 2.0)), 30.0))
        return True

    # ------------------------------------------------------------ lifecycle --
    async def start(self) -> tuple:
        addr = await self._server.start_tcp(self.host, 0)
        self.address = addr
        # Busy-fraction probe for the main loop (I/O shards install
        # their own under shard<i>): exported per node so single-core
        # daemon saturation is a gauge, not an inference.
        loopmon.install("main")
        cfg = get_config()
        if cfg.diagnosis_enabled:
            self._loop = asyncio.get_running_loop()
            self._watchdog = diagnosis.Watchdog(
                daemon_name="agent", node_id=self.node_id.hex(),
                detectors=[diagnosis.loop_wedge_detector()],
                notify=self._anomaly_from_thread,
                poll_s=cfg.diagnosis_poll_ms / 1000.0)
            self._watchdog.start()

        self.gcs = rpc.ReconnectingConnection(
            self.gcs_address, name="agent->gcs",
            handlers={"pubsub": self._on_pubsub},
            on_reconnect=self._register_gcs,
            # Every reconnect attempt re-reads the advertised address:
            # after a GCS failover the promoted standby serves on a new
            # port, and re-homing rides this same jittered dial loop.
            resolver=lambda: protocol.resolve_gcs_address(
                self.session_dir, fallback=self.gcs_address))
        await self.gcs.ensure()
        self._tasks.append(asyncio.ensure_future(self._report_loop()))
        self._tasks.append(asyncio.ensure_future(self._parked_lease_loop()))
        self._tasks.append(asyncio.ensure_future(self._create_queue_loop()))
        self._tasks.append(asyncio.ensure_future(self._reap_loop()))
        if get_config().worker_fork_server:
            # Warm the fork-server immediately: its one-time heavy import
            # runs while the node finishes bootstrapping.
            self._ensure_zygote()
        self._tasks.append(asyncio.ensure_future(self._prestart_workers()))
        self._tasks.append(asyncio.ensure_future(self._memory_monitor_loop()))
        logger.info("agent %s on %s, store %s",
                    self.node_id.hex()[:8], addr, self.store_path)
        return addr

    async def _prestart_workers(self):
        """Warm the idle pool so first leases skip process startup
        (reference: worker_pool.cc PrestartWorkers)."""
        n = int(get_config().worker_prestart_count)
        for _ in range(max(0, n)):
            if self._shutdown:
                return
            try:
                wh = await self._pop_worker(None)
            except rpc.RpcError:
                return
            wh.last_idle = time.monotonic()
            self.idle_workers.append(wh)

    async def _register_gcs(self, conn):
        """Registration, run on every (re)connect: a restarted GCS replays
        its journal with nodes marked not-alive; re-registering brings
        this node back (reference: raylet re-registration after
        RayletNotifyGCSRestart, core_worker.proto:467).  Reads self.node_id
        at call time so a fresh-id rejoin reuses it unchanged."""
        reply = await conn.call("register_node", {
            "node_id": self.node_id,
            "address": list(self.address),
            "resources": self.resources_total,
            "labels": self.labels,
            "store_path": self.store_path,
            "session_dir": self.session_dir,
            # The agent never reads the cluster view from the reply;
            # skipping it keeps a mass (re-)registration wave O(N) on
            # the GCS instead of O(N^2) view-building.
            "view": False,
        })
        if isinstance(reply, dict):
            self._learn_epoch(reply.get(protocol.EPOCH_KEY))

    def _learn_epoch(self, epoch):
        """Adopt a (monotonically higher) cluster epoch from a GCS reply.
        A bump mid-flight means a standby took over; grants this agent
        mints from now on carry the new epoch, and requests still
        presenting the old one get the typed stale-epoch rejection."""
        if not isinstance(epoch, int) or epoch <= self.cluster_epoch:
            return
        if self.cluster_epoch != protocol.EPOCH_NONE:
            logger.warning(
                "cluster epoch bumped %d -> %d (GCS failover observed); "
                "new lease grants are fenced to the new epoch",
                self.cluster_epoch, epoch)
        self.cluster_epoch = epoch

    async def _rejoin_with_fresh_id(self):
        """The GCS rejected our heartbeat: this node was marked dead while
        the agent was actually alive (health-check false positive — e.g. a
        GC pause outlived the failure budget).  Death is permanent for
        consumers (actors restarted elsewhere, primaries written off), so
        zombieing on the old id helps nobody: rejoin as a FRESH node id —
        same agent process, same store, new identity (reference: a
        restarted raylet likewise registers a new node id)."""
        from .ids import NodeID
        old = self.node_id
        self.node_id = NodeID.from_random().binary()
        logger.warning(
            "GCS rejected heartbeats for node %s (marked dead); "
            "re-registering as fresh node %s",
            old.hex()[:8], self.node_id.hex()[:8])
        # The GCS buried (or restarted elsewhere) every actor of the dead
        # identity, so it will never send their processes a kill: left
        # alone they are orphans, and one that holds a chip holds it — and
        # the TPU capacity of this node — for good.  No death report: the
        # actor id may already name a live incarnation elsewhere.
        for wh in self.workers.values():
            if wh.is_actor:
                wh.is_actor, wh.actor_id = False, None
                wh.proc.terminate()
        await self._register_gcs(self.gcs)

    async def _report_loop(self):
        cfg = get_config()
        period = cfg.resource_report_period_ms / 1000.0
        # Phase desync (like rpc._backoff_delay's jitter): seed this
        # agent's tick with a pid-derived phase offset so N agents
        # started (or healed) together spread their heartbeat+telemetry
        # bursts across the period instead of stampeding the GCS on the
        # same tick — at fleet size the synchronized burst is visible as
        # p99 spikes on everything the GCS serves.
        await asyncio.sleep(period * rpc._jitter_rng.random())
        while not self._shutdown:
            await asyncio.sleep(period)
            try:
                self._sweep_replica_registrations()
                if self.gcs and not self.gcs.closed:
                    ok = await self.gcs.call("report_resources", {
                        "node_id": self.node_id,
                        "available": self.resources_available,
                        # Gray-failure evidence about peers: per-peer
                        # RTT/rate EMAs from this node's transfer paths
                        # (the GCS folds them into suspicion scores).
                        "peer_stats": self._peer_stats_snapshot(),
                        # Data-plane counters for the node views
                        # (`ray_tpu list nodes` / dashboard transfer
                        # column).
                        "transfer": {"bytes_served": self._bytes_served,
                                     "bytes_pulled": self._bytes_pulled},
                        # Runtime gauges for the node view (CLI summary /
                        # dashboard node table); the metrics flush below
                        # exports the same numbers as node-labeled
                        # series.
                        "runtime": self._runtime_stats(),
                    })
                    # Flight-recorder + metrics flush rides THIS tick —
                    # the batching discipline: no new per-event RPCs,
                    # one notify per heartbeat when there is anything
                    # to ship.
                    self._flush_telemetry()
                    if isinstance(ok, dict):
                        self._learn_epoch(ok.get(protocol.EPOCH_KEY))
                    if ok is False and not self._shutdown \
                            and self._draining is None:
                        # Rejected = we're listed dead.  (Never during a
                        # drain: its mark-dead is intentional and the
                        # shutdown notify is on the way.)
                        await self._rejoin_with_fresh_id()
                    elif self._draining is not None and not self._shutdown \
                            and time.monotonic() > \
                            self._drain_deadline + 30.0:
                        # Well past the drain deadline with no teardown:
                        # the orchestrator is gone (GCS crash mid-drain).
                        if ok is False:
                            # The drain DID conclude (we're dead at the
                            # GCS) but the shutdown notify was lost — exit
                            # as it would have made us.
                            logger.warning(
                                "drain concluded but teardown notify lost; "
                                "exiting")
                            await self.h_shutdown(None, {"graceful": True})
                        else:
                            # Still alive at the GCS: the drain was
                            # abandoned — return to service instead of
                            # zombieing (refusing leases forever).
                            logger.warning(
                                "drain (%s) abandoned past deadline; "
                                "returning node to service", self._draining)
                            self._draining = None
                            self._drain_deadline = 0.0
                            self._kick_parked()
            except Exception:
                # One slow/failed report (GCS busy, reconnecting, ...) must
                # never kill the loop: a dead report loop freezes this
                # node's resource view at the GCS and starves scheduling.
                pass

    # ----------------------------------------------------- telemetry -------
    def _runtime_stats(self) -> Dict[str, float]:
        """Small gauge set riding the heartbeat into the node view."""
        try:
            st = self.store.stats()
        except Exception:
            st = {}
        lm = loopmon.snapshot()
        shard_busy = [v for k, v in lm.items() if k.startswith("shard")]
        # Heartbeat tick doubles as the arena source of the shared
        # pressure signal (memory_monitor.pressure_signal): lease
        # shedding and KV demotion drain the same number the create
        # queue backpressures on.
        pressure = self._arena_pressure(st)
        try:
            from .memory_monitor import pressure_signal
            pressure_signal().report("arena", pressure)
            if self._mem_chaos is not None:
                self._mem_chaos.report_pressure()
        except Exception:
            pass
        return {
            "lease_queue_depth": float(len(self._parked_leases)),
            "active_leases": float(len(self.leases)),
            "num_workers": float(len(self.workers)),
            "arena_used_bytes": float(st.get("bytes_in_use", 0)),
            "arena_capacity_bytes": float(st.get("capacity", 0)),
            "arena_pressure": pressure,
            "create_queue_depth": float(len(self._create_queue)),
            # Loop saturation for `ray_tpu summary`'s busy column:
            # main-loop busy fraction / max across I/O shards.
            "loop_busy": float(lm.get("main", 0.0)),
            "loop_busy_shard_max": float(max(shard_busy)
                                         if shard_busy else 0.0),
            "io_shards": float(len(self._io_shards)
                               if self._io_shards else 0),
        }

    def _flush_telemetry(self) -> None:
        """Ship buffered flight-recorder rows + this daemon's metric
        snapshot to the GCS sinks.  Fire-and-forget notifies on the
        existing GCS connection, sent at heartbeat rate — the recorder
        ring absorbs bursts between ticks and counts what it sheds.
        A failed notify keeps the drained rows for the next tick
        (bounded; overflow is COUNTED via note_lost, never silent)."""
        if self.gcs is None or self.gcs.closed:
            return
        rec = frec.recorder()
        rows = self._frec_retry + rec.drain(node_id=self.node_id)
        self._frec_retry = []
        if rows:
            try:
                self.gcs.notify("task_events", {
                    "blob": rpc._pack(rows), "n": len(rows),
                    "src": self._telemetry_src, "dropped": rec.dropped})
            except rpc.RpcError:
                keep = rows[-rec.capacity:]
                rec.note_lost(len(rows) - len(keep))
                self._frec_retry = keep
        if not get_config().metrics_export_enabled:
            return
        try:
            self.gcs.notify("report_metrics", {
                "worker_id": self.node_id,
                "node_id": self.node_id,
                "metrics": self._metrics_snapshot()})
        except rpc.RpcError:
            pass

    def _metrics_snapshot(self) -> List[dict]:
        """This daemon's registry + runtime gauges as metric rows
        (unified export: the same shape util.metrics snapshots use, so
        the GCS merges user and runtime series through one sink)."""
        from ..util import metrics as _metrics
        now = time.time()
        # node_id is stamped at the SOURCE (not injected at the GCS) so
        # runtime series are per-node while user metrics keep whatever
        # label set their authors chose (a silently injected label
        # would change user series identity).
        lab = {"daemon": "agent", "node_id": self.node_id.hex()}

        def row(name, value, typ="gauge", help_="", labels=None):
            return {"name": name, "type": typ, "help": help_, "ts": now,
                    "labels": labels or lab, "value": float(value)}

        rt = self._runtime_stats()
        out = [
            row("ray_tpu_arena_used_bytes", rt["arena_used_bytes"],
                help_="shm arena bytes in use"),
            row("ray_tpu_arena_capacity_bytes",
                rt["arena_capacity_bytes"]),
            row("ray_tpu_lease_queue_depth", rt["lease_queue_depth"],
                help_="parked (queued) lease requests"),
            row("ray_tpu_active_leases", rt["active_leases"]),
            row("ray_tpu_node_workers", rt["num_workers"]),
            row("ray_tpu_transfer_served_bytes_total",
                self._bytes_served, "counter"),
            row("ray_tpu_transfer_pulled_bytes_total",
                self._bytes_pulled, "counter"),
            row("ray_tpu_arena_pressure", rt["arena_pressure"],
                help_="arena occupancy incl. unsealed create "
                      "reservations, over the EFFECTIVE capacity "
                      "(mem_chaos squeezes shrink the denominator)"),
            row("ray_tpu_create_queue_depth", rt["create_queue_depth"],
                help_="creates parked in the FIFO admission queue "
                      "waiting for eviction/spill headroom"),
            row("ray_tpu_spilled_bytes_total",
                self._spilled_bytes_total, "counter",
                help_="bytes written to the NVMe/external spill tier"),
            row("ray_tpu_restored_bytes_total",
                self._restored_bytes_total, "counter",
                help_="bytes restored from spill files into the arena"),
        ]
        # Per-loop busy fractions: main + every I/O shard, node-labeled
        # (the gcs exports its own under daemon="gcs").  Stale entries
        # stay in the export with their probe age alongside — a wedged
        # loop must ALARM in the gauges, not vanish from them.
        for label, info in loopmon.snapshot_full().items():
            out.append(row("ray_tpu_daemon_loop_busy_ratio", info["ratio"],
                           labels={**lab, "loop": label},
                           help_="CPU-seconds per wall-second burned by "
                                 "the thread running this event loop"))
            out.append(row("ray_tpu_daemon_loop_stale_seconds",
                           info["stale_s"],
                           labels={**lab, "loop": label},
                           help_="age of this loop's last busy probe "
                                 "tick; grows past the ~0.5s period "
                                 "when the loop stops servicing "
                                 "callbacks (wedged or stopped)"))
        sst = self._server.shard_stats()
        if sst["shards"]:
            out.append(row("ray_tpu_daemon_io_shard_hops_total",
                           sst["hops"], "counter"))
            out.append(row("ray_tpu_daemon_io_shard_requests_total",
                           sst["submitted"], "counter"))
        # Common per-process rows (io_stats, copy audit, recorder
        # counters): shared with the core worker's export so the two
        # cannot diverge.
        out.extend(frec.export_rows(lab))
        # User/util metrics registered inside this process ride along.
        out.extend(_metrics.registry_snapshot())
        return out

    async def _reap_loop(self):
        """Detect dead worker processes, release their leases, tell GCS about
        dead actors (reference: worker failure path, gcs_service.proto:388
        ReportWorkerFailure)."""
        while not self._shutdown:
            await asyncio.sleep(0.5)
            for wid, wh in list(self.workers.items()):
                if wh.proc.poll() is not None:
                    try:
                        await self._on_worker_death(wh)
                    except Exception:
                        logger.exception(
                            "worker death handling failed; lease state may "
                            "need the next reap pass")
            # Sweep leases whose owner connection is closed: the
            # disconnect callback covers the common case, but a grant
            # that registers in the same loop tick the teardown runs (or
            # any future ordering hole) must not leak CPUs forever —
            # the raylet likewise returns leases on client disconnect
            # unconditionally (reference: node_manager.cc disconnect
            # path).
            for lease_id, wh in list(self.leases.items()):
                conn = wh.lease_owner_conn
                if conn is not None and conn.closed:
                    logger.warning(
                        "sweeping lease %s from disconnected client",
                        lease_id.hex()[:8])
                    try:
                        self._reclaim_lease(lease_id, wh)
                    except Exception:
                        # The reap loop must survive everything: a dead
                        # loop means no death detection node-wide.
                        logger.exception("lease sweep failed for %s",
                                         lease_id.hex()[:8])
            try:
                await self._check_lease_stalls()
            except Exception:
                logger.exception("lease-stall detector pass failed")

    async def _check_lease_stalls(self):
        """Diagnosis-plane detector: a lease granted long ago whose
        worker has started ZERO tasks since the grant (and runs none
        now) — the owner wedged before pushing, or the push vanished.
        Probed via the worker's exec_stats (AGES, not timestamps:
        monotonic clocks don't compare across processes); flagged once
        per grant."""
        cfg = get_config()
        if not cfg.diagnosis_enabled:
            return
        stall_s = cfg.diagnosis_lease_stall_s
        now = time.monotonic()
        for lease_id, wh in list(self.leases.items()):
            if (wh.lease_granted_at is None or wh.lease_stall_flagged
                    or wh.lease_id is None):
                continue
            age = now - wh.lease_granted_at
            if age < stall_s:
                continue
            if wh.conn is None or wh.conn.closed \
                    or wh.proc.poll() is not None:
                continue        # dead-worker path handles these
            try:
                st = await wh.conn.call("exec_stats", {}, timeout=5)
            except rpc.RpcError:
                continue
            if wh.lease_id is None or wh.lease_stall_flagged:
                continue        # released/flagged while we awaited
            started_age = st.get("last_task_started_age_s")
            never_ran = started_age is None or started_age > age
            if st.get("running") or not never_ran:
                continue
            wh.lease_stall_flagged = True
            diagnosis.record_anomaly(
                "lease_stalled", daemon="agent",
                node_id=self.node_id.hex(), notify=self._send_anomaly,
                lease_id=lease_id.hex(), worker_id=wh.worker_id.hex(),
                lease_age_s=round(age, 3))

    async def _memory_monitor_loop(self):
        """Kill-by-policy when node memory crosses the threshold
        (reference: raylet MemoryMonitor + GroupByOwnerIdWorkerKillingPolicy,
        node_manager.cc:229-230)."""
        from .config import get_config
        from .memory_monitor import (GroupByOwnerPolicy, kill_worker,
                                     node_memory_usage, pressure_signal)
        cfg = get_config()
        period = cfg.memory_monitor_refresh_ms / 1000.0
        threshold = cfg.memory_usage_threshold
        if period <= 0 or threshold >= 1.0:
            return
        policy = GroupByOwnerPolicy()
        sig = pressure_signal()
        while not self._shutdown:
            await asyncio.sleep(period)
            try:
                used, total = node_memory_usage()
                frac = used / max(total, 1)
                # Node RAM feeds the SAME pressure signal the create
                # queue and lease shedding drain — but only past the OOM
                # threshold: ordinary host occupancy (a busy dev box)
                # must not flip the cluster into shed mode.
                if frac > threshold:
                    sig.report("node", frac)
                else:
                    sig.clear("node")
                if frac <= threshold:
                    continue
                victim = policy.pick(list(self.workers.values()))
                if victim is None:
                    continue
                reason = (
                    f"node memory usage {frac:.1%} above threshold "
                    f"{threshold:.1%}; killed worker pid={victim.proc.pid} "
                    f"(group-by-owner policy)")
                logger.warning("OOM monitor: %s", reason)
                # Prune stale fate records (owners query within seconds of
                # the crash; 10 min is a generous triage window).
                cutoff = time.monotonic() - 600.0
                for wid in [w for w, i in self._oom_kills.items()
                            if i["ts"] < cutoff]:
                    del self._oom_kills[wid]
                self._oom_kills[victim.worker_id] = {
                    "reason": reason, "ts": time.monotonic()}
                kill_worker(victim, reason)
                # Let the kill land + the reaper release resources before
                # re-evaluating, so one spike doesn't massacre the pool.
                await asyncio.sleep(max(period, 1.0))
            except Exception:
                logger.exception("memory monitor pass failed")

    async def h_worker_fate(self, conn, p):
        """Owner-side crash triage: was this worker OOM-killed?
        (reference: the raylet annotates worker death with
        OOM-kill details so owners raise OutOfMemoryError)."""
        info = self._oom_kills.get(p["worker_id"])
        return {"oom_killed": info is not None,
                "reason": (info or {}).get("reason", "")}

    async def _on_worker_death(self, wh: WorkerHandle):
        self.workers.pop(wh.worker_id, None)
        if wh in self.idle_workers:
            self.idle_workers.remove(wh)
        self._give_back_chips(wh.chip_ids)
        wh.chip_ids = ()
        if wh.lease_id is not None:
            self._release_resources(self._settle_lease_release(wh),
                                    wh.lease_bundle)
            self.leases.pop(wh.lease_id, None)
        logger.warning("worker %s (pid %s) died", wh.worker_id.hex()[:8],
                       wh.proc.pid)
        if wh.is_actor and wh.actor_id and self.gcs and not self.gcs.closed:
            # Report actor death so the GCS can restart-or-bury (reference:
            # ReportWorkerFailure → GcsActorManager::OnWorkerDead).
            oom = self._oom_kills.get(wh.worker_id)
            try:
                await self.gcs.call("actor_failed", {
                    "actor_id": wh.actor_id,
                    "reason": (oom["reason"] if oom else
                               f"worker process {wh.proc.pid} exited with "
                               f"code {wh.proc.returncode}")})
            except (rpc.RpcError, asyncio.TimeoutError):
                pass

    def _on_pubsub(self, conn, p):
        pass  # agents currently only publish

    async def close(self):
        self._shutdown = True
        self._dag_chans.stop_all()
        for t in self._tasks:
            t.cancel()
        z = getattr(self, "_zygote", None)
        if z is not None and z.poll() is None:
            try:
                z.stdin.close()          # zygote exits on EOF
                z.terminate()
            except OSError:
                pass
        for wh in list(self.workers.values()):
            try:
                wh.proc.terminate()
            except ProcessLookupError:
                pass
        await self._server.close()
        if self._io_shards is not None:
            # After the server: bridged connection closes need the
            # shard loops alive to run.
            self._io_shards.close()
        self.store.close()
        if self._worker_cgroup is not None:
            # rmdir on a cgroup with live members returns EBUSY: give the
            # terminated workers a moment to exit before removing.
            for _ in range(30):
                if all(wh.proc.poll() is not None
                       for wh in self.workers.values()):
                    break
                await asyncio.sleep(0.1)
            for wh in self.workers.values():
                if wh.proc.poll() is None:
                    wh.proc.kill()
                    try:
                        wh.proc.wait(timeout=2)
                    except Exception:
                        pass
            self._worker_cgroup.close()
        try:
            os.unlink(self.store_path)
        except FileNotFoundError:
            pass

    # ------------------------------------------------------------- workers --
    def _worker_env(self, env_extra: Dict[str, str] | None,
                    needs_tpu: bool,
                    chip_ids: Sequence[int] = ()) -> Dict[str, str]:
        """A worker's base environment, with its JAX platform pinned
        whatever the agent itself inherited: a worker holding real chips
        sees those chips only and fails if it cannot open them; a worker
        without chips is pinned to the CPU, because an unpinned JAX
        auto-detects the TPU and takes it from the worker that leased it
        (one process per chip).  Only a TPU lease on a host WITHOUT real
        chips (injected counts) is left alone."""
        from .node import child_env
        env = child_env(env_extra)
        if chip_ids:
            from ..tpu.accelerator import TPUAcceleratorManager
            env.update(TPUAcceleratorManager.worker_env(chip_ids,
                                                        self._host_chips))
        elif self._host_chips or not needs_tpu:
            env["JAX_PLATFORMS"] = "cpu"
        return env

    def _ensure_zygote(self) -> Optional[subprocess.Popen]:
        z = getattr(self, "_zygote", None)
        if z is not None and z.poll() is None:
            return z
        log_dir = os.path.join(self.session_dir, "logs")
        os.makedirs(log_dir, exist_ok=True)
        errf = open(os.path.join(log_dir, "zygote.err"), "ab")
        try:
            self._zygote = subprocess.Popen(
                [sys.executable, "-m", "ray_tpu._private.zygote"],
                # A default CPU worker's env, so forked children need no
                # import-time env fixups.
                env=self._worker_env(None, needs_tpu=False),
                stdin=subprocess.PIPE,
                stdout=subprocess.PIPE, stderr=errf,
                cwd=os.getcwd(), start_new_session=True)
        except OSError:
            self._zygote = None
        return self._zygote

    def _zygote_fork(self, req: dict) -> int:
        """Blocking fork request (call via run_in_executor under
        _spawn_lock — the pipe protocol is strictly serial)."""
        z = self._zygote
        z.stdin.write(json.dumps(req).encode() + b"\n")
        z.stdin.flush()
        line = z.stdout.readline()
        if not line:
            raise rpc.RpcError("worker fork-server died")
        return json.loads(line)["pid"]

    async def _spawn_worker(self, env_extra: Dict[str, str] | None = None,
                            needs_tpu: bool = False,
                            cwd: str | None = None,
                            chip_ids: Tuple[int, ...] = ()) -> WorkerHandle:
        """`chip_ids` were taken from _free_chips by the caller; the new
        handle owns them until its process exits (_on_worker_death)."""
        worker_id = WorkerID.from_random().binary()
        env = self._worker_env(env_extra, needs_tpu, chip_ids)
        chaos_spec = get_config().rpc_chaos
        if chaos_spec:
            # Chaos must reach worker processes too (their config builds
            # from env; _system_config stops at the daemons' argv).
            env.setdefault("RAY_TPU_rpc_chaos", chaos_spec)
        link_spec = get_config().link_chaos
        if link_spec:
            # Slow-NODE mode: a node whose agent is link-degraded
            # degrades its workers the same way (the whole host shares
            # the gray NIC).
            env.setdefault("RAY_TPU_link_chaos", link_spec)
        skew = get_config().clock_skew_s
        if skew:
            # Skewed-NODE mode: processes on one host share the system
            # clock, so an injected skew must reach the workers too or
            # the node's own telemetry would disagree with itself.
            env.setdefault("RAY_TPU_clock_skew_s", str(skew))
        # The task-hung watchdog runs IN the worker: its thresholds must
        # reach worker processes too (their config builds from env;
        # _system_config stops at the daemons' argv).
        dcfg = get_config()
        for _k in ("diagnosis_enabled", "diagnosis_poll_ms",
                   "diagnosis_task_hang_multiple",
                   "diagnosis_task_hang_min_s",
                   "diagnosis_task_hang_default_s",
                   "diagnosis_serving_silence_s"):
            env.setdefault(f"RAY_TPU_{_k}", str(getattr(dcfg, _k)))
        env["RAY_TPU_WORKER_ID"] = worker_id.hex()
        env["RAY_TPU_AGENT_ADDR"] = json.dumps(list(self.address))
        env["RAY_TPU_GCS_ADDR"] = json.dumps(list(self.gcs_address))
        env["RAY_TPU_NODE_ID"] = self.node_id.hex()
        env["RAY_TPU_STORE_PATH"] = self.store_path
        env["RAY_TPU_SESSION_DIR"] = self.session_dir
        log_dir = os.path.join(self.session_dir, "logs")
        os.makedirs(log_dir, exist_ok=True)
        out_path = os.path.join(log_dir, f"worker-{worker_id.hex()[:12]}.out")
        err_path = os.path.join(log_dir, f"worker-{worker_id.hex()[:12]}.err")
        proc = None
        if (not needs_tpu and env_extra is None and cwd is None
                and get_config().worker_fork_server):
            # Default-env CPU worker: fork from the warm zygote (~100ms)
            # instead of exec+reimport (~seconds on small hosts).
            z = self._ensure_zygote()
            if z is not None:
                req = {"env": {k: env[k] for k in env
                               if k.startswith(("RAY_TPU_", "JAX_"))},
                       "cwd": os.getcwd(),
                       "stdout": out_path, "stderr": err_path}
                loop = asyncio.get_running_loop()
                try:
                    async with self._spawn_lock:
                        pid = await loop.run_in_executor(
                            None, self._zygote_fork, req)
                    proc = _ForkedProc(pid)
                except (rpc.RpcError, OSError, ValueError):
                    proc = None          # zygote broken: exec fallback
        if proc is None:
            out = open(out_path, "ab")
            err = open(err_path, "ab")
            # Interpreter override (conda runtime env) / container launch
            # (container runtime env) — both set by UriCache.setup.
            py = env.pop("RAY_TPU_WORKER_PYTHON", None) or sys.executable
            cmd = [py, "-m", "ray_tpu._private.worker_main"]
            container = env.pop("RAY_TPU_WORKER_CONTAINER", None)
            if container:
                cmd = self._container_cmd(json.loads(container), env,
                                          cwd or os.getcwd(),
                                          set(env_extra or ()))
            proc = subprocess.Popen(
                cmd, env=env, stdout=out, stderr=err,
                cwd=cwd or os.getcwd(), start_new_session=True)
        if self._worker_cgroup is not None:
            self._worker_cgroup.add(proc.pid)
        wh = WorkerHandle(worker_id, proc)
        wh.needs_tpu = needs_tpu
        wh.chip_ids = tuple(chip_ids)
        wh.has_env = bool(env_extra) or cwd is not None
        self.workers[worker_id] = wh
        return wh

    def _container_cmd(self, spec: dict, env: Dict[str, str],
                       cwd: str, extra_keys: set = frozenset()) -> list:
        """Worker launch line for a container runtime env (reference:
        runtime_env/container.py — podman run with the session mounted).
        Host IPC + host network keep the shm object store and the TCP
        control plane working unchanged inside the container; the ray_tpu
        package and session dir are bind-mounted so the image only needs
        a python. The runtime binary is injectable ('runtime' in the
        spec), which is also how tests exercise this path without a
        container engine."""
        runtime = spec["runtime"]
        cmd = [runtime, "run", "--rm", "--ipc=host", "--network=host",
               "-w", cwd]
        mounts = {self.session_dir, spec.get("pkg_root") or "", cwd}
        for m in sorted(m for m in mounts if m):
            cmd += ["-v", f"{m}:{m}"]
        for k, v in sorted(env.items()):
            # Runtime plumbing + the user's own runtime_env env_vars
            # (extra_keys) — a dropped user var would fail silently
            # inside the container.
            if (k.startswith(("RAY_TPU_", "JAX_")) or k == "PYTHONPATH"
                    or k in extra_keys):
                cmd += ["-e", f"{k}={v}"]
        cmd += spec.get("run_options", [])
        cmd += [spec["image"], "python", "-m",
                "ray_tpu._private.worker_main"]
        return cmd

    # --- log access (reference: dashboard state head log streaming;
    # `ray logs` lists/reads node log files via the node's agent) -------
    def _log_dir(self) -> str:
        return os.path.join(self.session_dir, "logs")

    async def h_list_logs(self, conn, p):
        """Log filenames on this node, with sizes (reference: state API
        list_logs — per-node file listing, optionally glob-filtered)."""
        import fnmatch
        pat = (p or {}).get("glob") or "*"
        log_dir = self._log_dir()
        out = []
        try:
            for name in sorted(os.listdir(log_dir)):
                if fnmatch.fnmatch(name, pat):
                    try:
                        size = os.path.getsize(os.path.join(log_dir, name))
                    except OSError:
                        continue
                    out.append({"name": name, "size": size})
        except FileNotFoundError:
            pass
        return out

    async def h_read_log(self, conn, p):
        """Tail of one log file (reference: state API get_log).  `lines`
        caps the tail; reads are bounded to 4 MiB so a runaway log can't
        blow the RPC frame."""
        name = os.path.basename(p["name"])    # no path traversal
        path = os.path.join(self._log_dir(), name)
        lines = int(p.get("lines", 1000))
        if lines <= 0:
            return ""                 # [-0:] would be the WHOLE file
        try:
            size = os.path.getsize(path)
            with open(path, "rb") as f:
                cap = 4 << 20
                if size > cap:
                    f.seek(size - cap)
                data = f.read(cap)
        except OSError:
            return None
        text = data.decode("utf-8", "replace")
        return "\n".join(text.splitlines()[-lines:])

    async def h_register_worker(self, conn, p):
        wh = self.workers.get(p["worker_id"])
        if wh is None:
            raise rpc.RpcError("unknown worker")
        wh.address = tuple(p["address"])
        wh.conn = conn
        # Do NOT override conn.on_close here: the server installed its
        # close chain (connection-set cleanup + _on_client_close lease
        # reclaim).  A worker's registration conn is the same conn it
        # later requests leases over — clobbering the chain made every
        # lease held by a killed worker leak its CPUs permanently.
        wh.registered.set()
        return {"node_id": self.node_id}

    def _take_chips(self, resources: Dict[str, float]) -> Tuple[int, ...]:
        """Chip ids for a lease of `resources`: () unless it asks for TPU
        on a host with real chips.  Raises when the request cannot be a
        whole number of chips, or when the chips are still held by a
        worker that has not finished exiting."""
        n = resources.get("TPU", 0)
        if not n or not self._host_chips:
            return ()
        if n != int(n):
            raise rpc.RpcError(
                f"{protocol.LEASE_REFUSED}: TPU={n} on a host with real chips — a "
                "chip belongs to one process; ask for whole chips")
        n = int(n)
        if n > len(self._free_chips):
            raise rpc.RpcError(
                f"{n} chips wanted, {len(self._free_chips)} free: the "
                "rest are held by workers that have not exited yet")
        ids, self._free_chips = (tuple(self._free_chips[:n]),
                                 self._free_chips[n:])
        return ids

    def _give_back_chips(self, ids: Sequence[int]) -> None:
        self._free_chips = sorted([*self._free_chips, *ids])

    async def _pop_worker(self, env_extra=None,
                          cwd: str | None = None,
                          resources: Optional[Dict[str, float]] = None
                          ) -> WorkerHandle:
        """Reuse an idle pooled worker or spawn one (reference:
        WorkerPool::PopWorker, worker_pool.h:55; reuse keyed by runtime env —
        round 1 pools only default-env workers).  TPU workers are never
        pooled: a process that opened a chip holds it until it exits, so
        each TPU lease gets a fresh process confined to the lease's
        chips."""
        resources = resources or {}
        needs_tpu = _needs_tpu(resources)
        if not env_extra and cwd is None and not needs_tpu:
            pool = self.idle_workers
            while pool:
                wh = pool.pop()
                if wh.proc.poll() is None and wh.conn and not wh.conn.closed:
                    return wh
        chips = self._take_chips(resources)
        try:
            wh = await self._spawn_worker(env_extra, needs_tpu=needs_tpu,
                                          cwd=cwd, chip_ids=chips)
        except BaseException:
            self._give_back_chips(chips)
            raise
        cfg = get_config()
        deadline = time.monotonic() + cfg.worker_register_timeout_s
        while True:
            try:
                await asyncio.wait_for(wh.registered.wait(), 0.5)
                return wh
            except asyncio.TimeoutError:
                # A worker killed between spawn and registration (crash,
                # chaos SIGKILL) must fail the grant NOW — waiting out the
                # full registration timeout stalls the lease request (and
                # its parked successors) for a minute.
                if wh.proc.poll() is not None:
                    raise rpc.RpcError(
                        f"worker died during startup (exit "
                        f"{wh.proc.returncode})")
                if time.monotonic() >= deadline:
                    wh.proc.kill()
                    raise rpc.RpcError("worker failed to register in time")

    @staticmethod
    def _try_acquire_from(avail: Dict[str, float],
                          resources: Dict[str, float]) -> bool:
        if not all(avail.get(k, 0.0) >= v - 1e-9 for k, v in resources.items()
                   if v > 0):
            return False
        for k, v in resources.items():
            avail[k] = avail.get(k, 0.0) - v
        return True

    def _try_acquire(self, resources: Dict[str, float]) -> bool:
        return self._try_acquire_from(self.resources_available, resources)

    def _release_resources(self, resources: Dict[str, float],
                           bundle_key: Optional[Tuple[bytes, int]] = None):
        """Return lease resources to their pool: the PG bundle they came
        from (if it still exists — a removed bundle already gave the node
        pool its total back), else the node pool."""
        if bundle_key is not None:
            bundle = self.bundles.get(bundle_key)
            if bundle is not None:
                for k, v in resources.items():
                    bundle["available"][k] = bundle["available"].get(k, 0.0) + v
                return
            # Bundle was removed while the lease ran: its unused part went
            # back to the node pool at return_bundle; the lease's share
            # comes back here.
        for k, v in resources.items():
            self.resources_available[k] = self.resources_available.get(k, 0.0) + v
        self._kick_parked()

    # -------------------------------------------------------------- leasing --
    async def h_request_lease(self, conn, p):
        """Grant a worker lease, reply spillback with a better node, or —
        when this node is saturated with a feasible shape and nowhere to
        spill — PARK the request and reply when resources free up
        (reference: NodeManager::HandleRequestWorkerLease
        node_manager.cc:1776; the raylet's ClusterLeaseManager queues
        leases and the RPC replies on grant, it never tells a feasible
        client to poll)."""
        rec = frec.recorder()
        t0 = rec.begin()
        req_epoch = p.get(protocol.EPOCH_KEY)
        if isinstance(req_epoch, int):
            if req_epoch > self.cluster_epoch:
                # The requester heard about a failover before this agent's
                # next heartbeat did — adopt its epoch rather than reject
                # a perfectly current owner.
                self._learn_epoch(req_epoch)
            elif (req_epoch != protocol.EPOCH_NONE
                  and req_epoch < self.cluster_epoch):
                # Fencing: the owner is still living in a pre-failover
                # epoch.  A typed rejection (not a plain refusal) tells it
                # to refresh its epoch and resubmit — retried work stays
                # exactly-once because nothing was granted here.
                return {"granted": False,
                        "reject": protocol.REJECT_STALE_EPOCH,
                        protocol.EPOCH_KEY: self.cluster_epoch}
        if not (self._parked_leases and not p.get("placement_group")):
            # Fast path only while nobody is parked: a fresh request must
            # not jump the FIFO, or a stream of small shapes starves a
            # parked large one forever (the drain loop grants in order).
            res = await self._try_grant_lease(conn, p)
            if res is not None:
                if isinstance(res, dict) and res.get("granted"):
                    rec.end("lease", "lease:grant", t0,
                            id=res.get("lease_id") or b"")
                return res
        # Queued: the span covers queued -> granted (or refused), with
        # the queue depth at park time — the lease-lifecycle leg of the
        # flight recorder (prefetch/push/RUNNING continue it).
        depth = len(self._parked_leases)
        fut = asyncio.get_running_loop().create_future()
        deadline = time.monotonic() + float(p.get("max_park_s", 60.0))
        self._parked_leases.append((p, conn, fut, deadline))
        self._kick_parked()
        res = await fut
        if isinstance(res, dict) and res.get("granted"):
            rec.end("lease", "lease:queued", t0,
                    id=res.get("lease_id") or b"", depth=depth)
        return res

    async def _try_grant_lease(self, conn, p):
        """One grant attempt. Returns a reply dict, or None when the
        request should park (feasible here, saturated, no spillback)."""
        resources = p.get("resources", {})
        if self._draining is not None:
            # Draining nodes accept no new work; point the submitter at a
            # live peer so its lease pump re-routes instead of spinning
            # (reference: raylet lease rejection while draining).
            spill = None
            if not p.get("placement_group"):
                spill = await self._find_spillback(resources,
                                                   p.get("prefetch"))
            return {"granted": False,
                    "reason": f"node draining ({self._draining})",
                    "spillback": spill, "retry_after_ms": 200}
        if not p.get("placement_group"):
            # Memory-pressure lease shedding: when the node's shared
            # pressure signal (arena occupancy / node RAM past the OOM
            # threshold / KV pool / chaos squeeze) is high, prefer a
            # feasible peer over piling more working set onto a node
            # already evicting.  Only when a spillback target EXISTS —
            # a sole node always grants (degrading to refusal would
            # deadlock single-node clusters, and the create queue
            # already backpressures the data plane).
            try:
                from .memory_monitor import pressure_signal
                level = pressure_signal().level()
            except Exception:
                level = 0.0
            if level >= self._shed_threshold:
                spill = await self._find_spillback(resources,
                                                   p.get("prefetch"))
                if spill is not None:
                    self._leases_shed += 1
                    return {"granted": False, "spillback": spill,
                            "reason": "memory pressure shed"}
        pg = p.get("placement_group")
        bundle_key = None
        if pg:
            # Leases inside a PG draw from the bundle's reservation, not the
            # node pool (reference: bundle resources become `CPU_group_*`
            # resources the lease consumes instead of the node's).
            bundle_key = self._find_bundle(pg["pg_id"],
                                           pg.get("bundle_index", 0),
                                           resources)
            if bundle_key is None:
                return {"granted": False,
                        "reason": "bundle not on this node or exhausted",
                        "retry_after_ms": 100}
            acquired = self._try_acquire_from(
                self.bundles[bundle_key]["available"], resources)
        else:
            acquired = self._try_acquire(resources)
        if not acquired:
            if pg:
                # Bundle exhausted: generic spillback would point off-PG;
                # the client retries (rotating bundles for index -1).
                return {"granted": False, "reason": "bundle exhausted",
                        "retry_after_ms": 100}
            spill = await self._find_spillback(resources,
                                               p.get("prefetch"))
            if spill is not None:
                return {"granted": False, "spillback": spill}
            if all(self.resources_total.get(k, 0.0) >= v - 1e-9
                   for k, v in resources.items() if v > 0):
                return None          # feasible but busy: park
            return {"granted": False, "reason": "infeasible",
                    "retry_after_ms": 100}
        # Runtime-env materialization NEVER blocks the grant RPC: a pip
        # install can take minutes while the client's lease timeout is
        # ~130s — a blocked handler whose client gave up would still
        # grant, leaking the lease (reference: the raylet delegates to
        # the runtime-env agent and retries the lease).
        status, payload = self.uri_cache.poll_setup(
            self.gcs, p.get("runtime_env"))
        if status == "pending":
            self._release_resources(resources, bundle_key)
            return {"granted": False,
                    "reason": "runtime env setup in progress",
                    "retry_after_ms": 1000}
        if status == "failed":
            self._release_resources(resources, bundle_key)
            return {"granted": False,
                    "reason": f"runtime env setup failed: {payload}",
                    "retry_after_ms": 200}
        env_extra, cwd = payload
        env_extra = dict(env_extra)
        try:
            if p.get("env"):
                env_extra.update(p["env"])
            wh = await self._pop_worker(env_extra or None, cwd=cwd,
                                        resources=resources)
        except Exception as e:
            # A spawn failure must release the acquired resources.
            self._release_resources(resources, bundle_key)
            return {"granted": False, "reason": str(e), "retry_after_ms": 200}
        if conn.closed:
            # The requester died while this grant was in flight (worker
            # spawn can take seconds) — its disconnect cleanup already
            # ran, so a grant recorded now would leak these resources
            # forever.  Hand everything back instead; the reply goes
            # nowhere anyway.
            self._release_resources(resources, bundle_key)
            self._recycle_worker(wh)
            return {"granted": False, "reason": "client disconnected"}
        lease_id = os.urandom(16)
        wh.lease_id = lease_id
        wh.lease_resources = resources
        wh.lease_bundle = bundle_key
        wh.lease_owner_conn = conn
        wh.lease_granted_at = time.monotonic()
        wh.lease_stall_flagged = False
        wh.granted_epoch = self.cluster_epoch
        self.leases[lease_id] = wh
        if p.get("prefetch"):
            # Arg prefetch: start pulling the lease's missing large
            # by-ref args NOW, so the fetch overlaps the submitter's
            # push round-trip and the worker's dispatch/queueing
            # (reference: the raylet pulls task-arg bundles during
            # lease setup).  Fire-and-forget — the executing task's own
            # resolve joins the in-flight pull (or finds the object
            # landed) via the pull dedup table.
            rpc.spawn(self._prefetch_lease_args(p["prefetch"]))
        return {"granted": True, "lease_id": lease_id,
                "worker_addr": list(wh.address),
                "worker_id": wh.worker_id,
                protocol.EPOCH_KEY: self.cluster_epoch}

    async def _prefetch_lease_args(self, entries) -> None:
        cfg = get_config()
        if not cfg.arg_prefetch_enabled:
            return
        for ent in entries:
            try:
                oid, locs, owner, size, task_id = ent
                oid = bytes(oid)
            except (TypeError, ValueError):
                continue
            if self.store.contains(oid) or oid in self.spilled or \
                    oid in self._pull_inflight:
                continue
            # Visible in the task timeline BEFORE the worker picks the
            # task up: the acceptance signal that fetch overlapped
            # dispatch.
            self._note_task_event(bytes(task_id), "PREFETCH")
            rpc.spawn(self._prefetch_one(oid, locs, owner))

    async def _prefetch_one(self, oid: bytes, locs, owner) -> None:
        try:
            with frec.recorder().span("lease", "prefetch", id=oid):
                await self.h_pull_object(None, {
                    "object_id": oid,
                    "from_addrs": [list(a) for a in locs or ()],
                    "owner_addr": list(owner) if owner else None,
                    "priority": 2})
        except Exception:
            # Best-effort: the task's own arg resolution retries and,
            # failing that, the owner-mediated fetch path decides.
            pass

    def _note_task_event(self, task_id: bytes, event: str) -> None:
        if self.gcs is None or self.gcs.closed:
            return
        try:
            self.gcs.notify("task_events", {"events": [{
                "task_id": task_id, "name": "", "event": event,
                "ts": clocks.wall(), "worker_id": b"",
                "node_id": self.node_id, "job_id": b""}]})
        except rpc.RpcError:
            pass

    def _kick_parked(self):
        """Resources were released somewhere: let the drain loop retry."""
        if self._parked_leases:
            self._park_event.set()

    async def _parked_lease_loop(self):
        """Single drainer (serialization avoids double-granting the head):
        grants parked lease requests FIFO as resources free up. Strict
        FIFO per node matches the reference's queue and avoids starving
        large shapes behind a stream of small ones."""
        while not self._shutdown:
            try:
                await asyncio.wait_for(self._park_event.wait(), timeout=1.0)
            except asyncio.TimeoutError:
                pass               # periodic pass: expire deadlines
            self._park_event.clear()
            q = self._parked_leases
            while q:
                p, conn, fut, deadline = q[0]
                if fut.done() or conn.closed:
                    q.popleft()
                    continue
                if time.monotonic() > deadline:
                    q.popleft()
                    if not fut.done():
                        fut.set_result({"granted": False,
                                        "reason": "saturated",
                                        "retry_after_ms": 100})
                    continue
                try:
                    res = await self._try_grant_lease(conn, p)
                except Exception as e:  # noqa: BLE001 — reply, don't die
                    res = {"granted": False, "reason": str(e),
                           "retry_after_ms": 200}
                if res is None:
                    break          # still saturated: wait for the next kick
                # The head may have been popped by _on_client_close while
                # _try_grant_lease awaited; only pop if it's still us.
                if q and q[0][2] is fut:
                    q.popleft()
                if not fut.done():
                    fut.set_result(res)
                elif res.get("granted"):
                    # Nobody is listening for this grant anymore.
                    wh = self.leases.get(res["lease_id"])
                    if wh is not None:
                        self._reclaim_lease(res["lease_id"], wh)

    def _find_bundle(self, pg_id: bytes, bundle_index: int,
                     resources: Dict[str, float]
                     ) -> Optional[Tuple[bytes, int]]:
        """Resolve a bundle key on this node; -1 = any bundle with room."""
        if bundle_index >= 0:
            key = (pg_id, bundle_index)
            return key if key in self.bundles else None
        for key, bundle in self.bundles.items():
            if key[0] != pg_id:
                continue
            avail = bundle["available"]
            if all(avail.get(k, 0.0) >= v - 1e-9
                   for k, v in resources.items() if v > 0):
                return key
        return None

    async def _find_spillback(self, resources,
                              prefetch=None) -> Optional[list]:
        """Pick a better node from the GCS resource view (stands in for
        the reference's in-raylet cluster view synced by ray_syncer),
        scored by the hybrid top-k policy
        (reference: hybrid_scheduling_policy.h:50). The view is cached
        ~500ms — under saturation every lease request lands here, and the
        reference's syncer view is likewise eventually consistent.

        `prefetch` (the lease request's arg work list) doubles as a
        locality hint: within the same trusted+feasible tier, spill
        toward the node already holding the task's bytes — locality is
        a tiebreak below feasibility and trust, never above."""
        from . import scheduling_policy as policy
        now = time.monotonic()
        if now - getattr(self, "_nodes_cache_ts", 0.0) > 0.5:
            try:
                self._nodes_cache = await self.gcs.call("get_nodes", {})
                self._nodes_cache_ts = time.monotonic()
            except rpc.RpcError:
                return None
        nodes = self._nodes_cache
        loc_map = {}
        if prefetch and get_config().object_locality_scheduling_enabled:
            for ent in prefetch:
                try:
                    _oid, locs, _owner, size, _tid = ent
                except (TypeError, ValueError):
                    continue
                for a in locs or ():
                    key = tuple(a)
                    loc_map[key] = loc_map.get(key, 0) + int(size or 0)
        # Gray-suspect nodes are spilled to only when nothing healthy
        # FITS — try the trusted subset first, then fall back to every
        # live node (mirroring the GCS scheduler: a suspect node is a
        # last resort, never a hard exclusion — if only it has room the
        # lease must spill there, not park forever).
        live = [n for n in nodes
                if policy.targetable(n)
                and bytes(n["node_id"]) != self.node_id]
        trusted = policy.prefer_trusted(live)
        for group in ([trusted, live] if len(trusted) < len(live)
                      else [live]):
            if loc_map:
                best = policy.pick_by_locality(
                    [(tuple(n["address"]), tuple(n["address"]),
                      n["resources_total"], n["resources_available"])
                     for n in group],
                    resources, loc_map,
                    min_bytes=get_config().object_locality_min_bytes)
                if best:
                    return list(best)
            cands = [(tuple(n["address"]), n["resources_total"],
                      n["resources_available"]) for n in group]
            best = policy.hybrid_pick(cands, resources)
            if best:
                return list(best)
        return None

    async def h_profile_worker(self, conn, p):
        """Forward a live-profiling request to workers on this node
        (reference: the reporter agent launching py-spy/memray against
        worker pids, dashboard/modules/reporter/profile_manager.py).
        kind: 'stacks' | 'cpu_profile'; worker_id None = every live
        registered worker."""
        kind = p.get("kind", "stacks")
        if kind not in ("stacks", "cpu_profile"):
            raise rpc.RpcError(f"unknown profile kind {kind!r}")
        payload = {"duration_s": p.get("duration_s", 5.0)}
        targets = []
        want = p.get("worker_id")
        for wid, wh in self.workers.items():
            if want is not None and wid != want:
                continue
            if wh.conn is None or wh.conn.closed or wh.proc.poll() is not None:
                continue
            targets.append((wid, wh))
        out = {}
        results = await asyncio.gather(
            *[wh.conn.call(kind, payload,
                           timeout=float(p.get("duration_s", 5.0)) + 30)
              for _, wh in targets],
            return_exceptions=True)
        for (wid, _), res in zip(targets, results):
            out[wid.hex()] = (
                {"error": str(res)} if isinstance(res, BaseException)
                else res)
        return out

    async def h_node_profile(self, conn, p):
        """Whole-node live profile for the GCS cluster_profile fan-out:
        the agent's own stacks/CPU profile + every live worker's,
        sampled CONCURRENTLY so the node is one coherent time window.
        A worker dying mid-profile yields a typed per-worker error
        entry, never a failed fan-out."""
        kind = p.get("kind", "stacks")
        if kind not in ("stacks", "cpu_profile"):
            raise rpc.RpcError(f"unknown profile kind {kind!r}")
        pid = p.get("pid")
        payload = {"duration_s": p.get("duration_s", 2.0),
                   "interval_s": p.get("interval_s", 0.01)}

        async def _self_profile():
            try:
                if kind == "stacks":
                    r = diagnosis.dump_stacks()
                else:
                    r = await diagnosis.cpu_profile(payload["duration_s"],
                                                    payload["interval_s"])
                r["daemon"] = "agent"
                return r
            except Exception as e:  # noqa: BLE001 — typed entry, not a crash
                return {"error": str(e)}

        async def _one_worker(wid, wh):
            try:
                return wid, await wh.conn.call(
                    kind, payload,
                    timeout=float(payload["duration_s"]) + 30)
            except Exception as e:  # noqa: BLE001
                return wid, {"error": str(e)}

        targets = []
        for wid, wh in self.workers.items():
            if wh.conn is None or wh.conn.closed \
                    or wh.proc.poll() is not None:
                continue
            if pid is not None and wh.proc.pid != int(pid):
                continue
            targets.append((wid, wh))
        include_agent = pid is None or int(pid) == os.getpid()
        coros = [_one_worker(wid, wh) for wid, wh in targets]
        if include_agent:
            coros.append(_self_profile())
        results = await asyncio.gather(*coros)
        out = {"node_id": self.node_id.hex(), "workers": {}}
        if include_agent:
            out["agent"] = results.pop()
        for wid, res in results:
            out["workers"][wid.hex()] = res
        return out

    # ------------------------------------------------------- diagnosis ---
    def _send_anomaly(self, info: dict) -> None:
        """Best-effort forward to the GCS anomaly sink (triggers the
        black-box capture); the counter + recorder event were already
        emitted process-locally by record_anomaly."""
        if self.gcs is None or self.gcs.closed:
            return
        try:
            self.gcs.notify("report_anomaly", info)
        except rpc.RpcError:
            pass

    def _anomaly_from_thread(self, info: dict) -> None:
        loop = getattr(self, "_loop", None)
        if loop is None:
            return
        try:
            loop.call_soon_threadsafe(self._send_anomaly, info)
        except RuntimeError:
            pass

    def _recycle_worker(self, wh: WorkerHandle):
        """Return a no-longer-leased worker to the idle pool, or terminate
        it.  Runtime-env workers are never pooled: their env_vars /
        PYTHONPATH / cwd would leak into default-env tasks.  TPU workers
        are never pooled either (see _pop_worker)."""
        wh.last_idle = time.monotonic()
        if (wh.proc.poll() is None and not wh.is_actor and not wh.has_env
                and not wh.needs_tpu
                and len(self.idle_workers) < IDLE_WORKER_KEEP):
            self.idle_workers.append(wh)
        elif not wh.is_actor:
            wh.proc.terminate()

    def _on_client_close(self, conn):
        """A lease client (driver/worker) disconnected: reclaim every
        lease it still holds — a driver exiting mid-lease must not leak
        node resources (reference: raylet lease cleanup on disconnect)."""
        for item in self._parked_leases:
            # Resolve parked requests from this client so their handler
            # coroutines don't wait forever; the drain loop reaps entries.
            if item[1] is conn and not item[2].done():
                item[2].set_result({"granted": False,
                                    "reason": "client disconnected"})
        for lease_id, wh in list(self.leases.items()):
            if wh.lease_owner_conn is conn:
                self._reclaim_lease(lease_id, wh)

    def _reclaim_lease(self, lease_id: bytes, wh: WorkerHandle):
        """Forcibly return a lease whose owner is gone.  Settles blocked-get
        CPU accounting (a blocked worker's CPU was already handed back by
        h_worker_blocked — returning the full grant would double-credit
        the pool).

        A TPU worker's lease is credited back only once its process has
        EXITED: until then it may hold the chip open, and a worker started
        on the returned capacity would find the device busy.  The lease
        stays charged to the handle; _on_worker_death settles it."""
        self.leases.pop(lease_id, None)
        wh.lease_owner_conn = None
        if wh.needs_tpu and wh.proc.poll() is None:
            wh.proc.terminate()
            rpc.spawn(self._settle_at_exit(wh))
            return
        self._release_resources(self._settle_lease_release(wh),
                                wh.lease_bundle)
        wh.lease_id = None
        wh.lease_resources = {}
        wh.lease_bundle = None
        self._recycle_worker(wh)

    async def _settle_at_exit(self, wh: WorkerHandle,
                              grace_s: float = 10.0) -> None:
        """Wait for a terminated worker to exit (SIGKILL after `grace_s`)
        and settle it at once instead of at the reap loop's next pass."""
        deadline = time.monotonic() + grace_s
        while wh.proc.poll() is None:
            if time.monotonic() > deadline:
                wh.proc.kill()
                deadline = float("inf")
            await asyncio.sleep(0.02)
        if wh.worker_id in self.workers:
            await self._on_worker_death(wh)

    async def h_return_lease(self, conn, p):
        # Returns are accepted under ANY epoch: refusing a release from a
        # pre-failover owner would leak the worker forever, and handing
        # resources back is safe regardless of who asks.  (Grants are the
        # fenced direction — see h_request_lease.)
        e = p.get(protocol.EPOCH_KEY)
        if isinstance(e, int):
            self._learn_epoch(e)
        wh = self.leases.get(p["lease_id"])
        if wh is None:
            return False
        self._reclaim_lease(p["lease_id"], wh)
        return True

    def _settle_lease_release(self, wh: WorkerHandle) -> Dict[str, float]:
        """Resources a finishing/dying lease should hand back: the lease's
        grant minus any CPU already released by a blocked get still
        outstanding (a worker can die mid-get; its CPU must not be
        returned twice)."""
        res = wh.lease_resources
        if wh.blocked_depth > 0 and wh.blocked_cpus:
            res = dict(res)
            res["CPU"] = res.get("CPU", 0.0) - wh.blocked_cpus
        wh.blocked_depth = 0
        wh.blocked_cpus = 0.0
        return res

    async def h_worker_blocked(self, conn, p):
        """A leased worker blocked inside ray_tpu.get: release its CPU so
        queued/parked work (often the very task it waits on) can run here
        (reference: NotifyDirectCallTaskBlocked — the raylet releases CPU
        but never accelerators; a TPU worker blocked in get keeps its
        chip)."""
        wh = self.workers.get(p["worker_id"])
        if wh is None or wh.lease_id is None:
            return False
        wh.blocked_depth += 1
        if wh.blocked_depth == 1:
            cpus = wh.lease_resources.get("CPU", 0.0)
            if cpus > 0:
                wh.blocked_cpus = cpus
                self._release_resources({"CPU": cpus}, wh.lease_bundle)
        return True

    async def h_worker_unblocked(self, conn, p):
        """The blocked get returned: take the CPU back. The pool may go
        NEGATIVE here (other work was granted the freed CPU meanwhile) —
        that's deliberate oversubscription-then-backpressure, matching the
        reference: no new grants until the pool recovers, but the resumed
        task is never made to wait for its own CPU (deadlock)."""
        wh = self.workers.get(p["worker_id"])
        if wh is None or wh.blocked_depth <= 0:
            return False
        wh.blocked_depth -= 1
        if wh.blocked_depth == 0 and wh.blocked_cpus:
            cpus, wh.blocked_cpus = wh.blocked_cpus, 0.0
            pool = None
            if wh.lease_bundle is not None:
                bundle = self.bundles.get(wh.lease_bundle)
                if bundle is not None:
                    pool = bundle["available"]
            if pool is None:
                pool = self.resources_available
            pool["CPU"] = pool.get("CPU", 0.0) - cpus
        return True

    # --------------------------------------------------------------- actors --
    async def h_create_actor_worker(self, conn, p):
        """Lease a dedicated worker and instantiate the actor in it
        (reference: GcsActorScheduler leasing from raylet + PushTask of the
        creation task)."""
        if self._draining is not None:
            raise rpc.RpcError(f"node draining ({self._draining})")
        # Idempotence across GCS restarts: if this actor already has a
        # live worker here (the previous create's reply was lost with the
        # GCS), return it instead of leasing a second process.
        for wh in self.leases.values():
            if (wh.is_actor and wh.actor_id == p["actor_id"]
                    and wh.conn and not wh.conn.closed):
                return {"worker_addr": list(wh.address),
                        "worker_id": wh.worker_id}
        resources = p.get("resources", {})
        strategy = p.get("scheduling_strategy") or {}
        bundle_key = None
        if strategy.get("type") == "placement_group":
            bundle_key = self._find_bundle(
                strategy["pg_id"], strategy.get("bundle_index", 0), resources)
            if bundle_key is None:
                raise rpc.RpcError("PG bundle not on this node or exhausted")
            acquired = self._try_acquire_from(
                self.bundles[bundle_key]["available"], resources)
        else:
            acquired = self._try_acquire(resources)
        if not acquired:
            raise rpc.RpcError("insufficient resources for actor")
        # Same non-blocking env contract as h_request_lease: the GCS
        # scheduler retries while a pip install runs in the background.
        status, payload = self.uri_cache.poll_setup(
            self.gcs, p.get("runtime_env"))
        if status != "ready":
            self._release_resources(resources, bundle_key)
            raise rpc.RpcError(
                "runtime env setup in progress" if status == "pending"
                else f"runtime env setup failed: {payload}")
        env_extra, cwd = payload
        try:
            wh = await self._pop_worker(dict(env_extra) or None,
                                        cwd=cwd, resources=resources)
        except Exception:
            self._release_resources(resources, bundle_key)
            raise
        wh.is_actor = True
        wh.actor_id = p["actor_id"]
        wh.lease_id = os.urandom(16)
        wh.lease_resources = resources
        wh.lease_bundle = bundle_key
        self.leases[wh.lease_id] = wh
        try:
            await wh.conn.call("actor_init", p, timeout=115)
        except (rpc.RpcError, asyncio.TimeoutError) as e:
            # The lease stays charged to the handle: _on_worker_death
            # releases it once the process — and any chip it opened — is
            # gone.  The ACTOR fields are cleared, or the death watcher
            # races this raise with a generic actor_failed("exited with
            # code 0") that masks the real __init__ error (e.g. an
            # unimportable actor class) at the caller.
            self.leases.pop(wh.lease_id, None)
            wh.is_actor = False
            wh.actor_id = None
            wh.proc.terminate()
            if isinstance(e, rpc.RemoteError):
                # The constructor itself raised: no retry cures that.
                raise rpc.RpcError(f"{protocol.ACTOR_INIT_RAISED}: {e}")
            raise rpc.RpcError(f"actor __init__ failed: {e}")
        return {"worker_addr": list(wh.address), "worker_id": wh.worker_id}

    async def h_actor_worker_died(self, conn, p):
        await self.gcs.call("actor_failed", p)
        return True

    # ------------------------------------------------------ graceful drain --
    async def h_drain(self, conn, p):
        """Agent half of the two-phase node drain (GCS h_drain_node):
        stop granting leases (parked requests resolve with spillback),
        migrate pinned primary objects to a live peer, then wait — bounded
        by the deadline — for in-flight non-actor leases to finish.  Actor
        workers keep serving until the final teardown: the GCS restarts
        their actors elsewhere concurrently, and clients fail over on
        connection loss."""
        reason = p.get("reason") or "manual"
        deadline = time.monotonic() + float(p.get("deadline_s", 30.0))
        if self._draining is None:
            self._draining = reason
            logger.warning("node %s draining (%s)",
                           self.node_id.hex()[:8], reason)
            self._kick_parked()
            # Swarm-source handoff: withdraw every secondary-replica
            # registration NOW, so new pulls stop routing here.  The
            # copies keep serving in-flight chunk requests until
            # teardown; mid-stream pulls fail over to the remaining
            # holders when this node finally goes away.
            for oid in list(self._replica_owner):
                self._drop_replica_registration(oid)
        self._drain_deadline = max(self._drain_deadline, deadline)
        migrated = await self._migrate_primaries(deadline)
        while time.monotonic() < deadline:
            if not any(not wh.is_actor for wh in self.leases.values()):
                break
            await asyncio.sleep(0.1)
        # Second pass: leases that finished during the wait may have
        # pinned fresh task returns; push those off-node too.
        migrated += await self._migrate_primaries(deadline)
        busy = sum(1 for wh in self.leases.values() if not wh.is_actor)
        return {"migrated": migrated, "busy_leases": busy}

    async def _migrate_primaries(self, deadline: float) -> int:
        """Republish this node's pinned primary copies to a live peer and
        record each move in the GCS KV (ns 'migrated') so owners repoint
        instead of running destructive lineage re-execution.  Spilled
        primaries migrate the same way — the peer pulls them straight out
        of the spill file via the chunked transfer path."""
        oids = [oid for oid in list(self.pinned)
                if oid not in self._migrated_away]
        if not oids:
            return 0
        try:
            nodes = await self.gcs.call("get_nodes", {})
        except (rpc.RpcError, asyncio.TimeoutError):
            return 0
        from . import scheduling_policy as policy
        peers = [n for n in nodes
                 if policy.targetable(n)
                 and bytes(n["node_id"]) != self.node_id]
        if not peers:
            logger.warning(
                "drain: no live peer for %d pinned primaries; owners fall "
                "back to external restore or lineage re-execution",
                len(oids))
            return 0
        migrated = 0
        for i, oid in enumerate(oids):
            if time.monotonic() >= deadline:
                break
            for attempt in range(len(peers)):
                n = peers[(i + attempt) % len(peers)]
                addr = tuple(n["address"])
                conns = await self._pull_peers([addr])
                if not conns:
                    continue
                timeout = max(1.0, min(60.0, deadline - time.monotonic()))
                owner = self._pinned_owner.get(oid)
                try:
                    ok = await conns[0].call("adopt_primary", {
                        "object_id": oid,
                        "from_addrs": [list(self.address)],
                        # The adoptive node repoints the owner's replica
                        # directory directly (primary=True add) — owners
                        # learn the new home without waiting for a
                        # recovery probe or the migrated-KV fallback.
                        "owner_addr": list(owner) if owner else None,
                        "priority": 0}, timeout=timeout)
                except (rpc.RpcError, asyncio.TimeoutError):
                    continue
                if not ok:
                    continue
                # Record the destination BEFORE the KV write: even if the
                # write fails (owners then fall back to lineage), a later
                # migration pass must not re-adopt at a different peer and
                # orphan this pinned copy, and frees must still forward.
                self._migrated_away[oid] = addr
                try:
                    await self.gcs.call("kv_put", {
                        "ns": "migrated", "key": oid.hex(),
                        "value": json.dumps(list(addr)).encode(),
                        "overwrite": True})
                except (rpc.RpcError, asyncio.TimeoutError):
                    break    # copy exists but owners can't find it; move on
                migrated += 1
                break
        return migrated

    async def h_adopt_primary(self, conn, p):
        """Become the primary holder of an object migrating off a draining
        node: pull the bytes (shm, or disk when the arena is full), take
        one owner pin so they can't be evicted before the owner repoints,
        and remember the adoption so a later free also clears the
        cluster-wide 'migrated' KV record."""
        oid = p["object_id"]
        if not await self.h_pull_object(conn, p):
            return False
        self._disk_cached.pop(oid, None)   # a primary now, not a cache
        if not await self.h_pin_object(conn, {"object_id": oid}):
            return False
        self._adopted.add(oid)
        owner = p.get("owner_addr")
        if owner:
            # Promote in the owner's replica directory: this node is the
            # primary now (any stale secondary record of us collapses
            # into it), so subsequent pulls/frees route straight here.
            self._pinned_owner[oid] = tuple(owner)
            self._replica_owner.pop(oid, None)
            rpc.spawn(self._notify_owner_location(oid, tuple(owner),
                                                  add=True, primary=True))
        return True

    async def _forward_free(self, addr: tuple, oid: bytes) -> None:
        try:
            conns = await self._pull_peers([tuple(addr)])
            if conns:
                await conns[0].call("free_objects", {"object_ids": [oid]})
        except (rpc.RpcError, asyncio.TimeoutError):
            pass

    # ------------------------------------------------------ placement groups --
    def _reserve_one(self, pg_id: bytes, bundle_index: int,
                     resources: Dict[str, float]) -> Optional[bool]:
        """Acquire + record ONE PG bundle reservation. Returns True on a
        fresh reservation, None when already present (idempotent retry),
        False when resources don't fit."""
        key = (pg_id, bundle_index)
        if key in self.bundles:
            return None
        if self._draining is not None:
            return False        # no new reservations on a departing node
        if not self._try_acquire(resources):
            return False
        self.bundles[key] = {"total": dict(resources),
                             "available": dict(resources)}
        return True

    async def h_prepare_bundle(self, conn, p):
        return self._reserve_one(p["pg_id"], p["bundle_index"],
                                 p["resources"]) is not False

    async def h_reserve_bundles(self, conn, p):
        """Single-node PG fast path: prepare+commit every bundle in ONE
        RPC.  The two-phase protocol exists for cross-node atomicity
        (reference: node_manager.proto:471-476); with all bundles on one
        node there is no second participant, so the round trips collapse.
        All-or-nothing: a failed acquire rolls back this call's own
        reservations (bundles already present from a retried call are
        kept)."""
        acquired = []
        for b in p["bundles"]:
            got = self._reserve_one(p["pg_id"], b["bundle_index"],
                                    b["resources"])
            if got is False:
                for k in acquired:
                    self._release_resources(self.bundles.pop(k)["total"])
                return False
            if got:
                acquired.append((p["pg_id"], b["bundle_index"]))
        return True

    async def h_commit_bundle(self, conn, p):
        return (p["pg_id"], p["bundle_index"]) in self.bundles

    async def h_return_bundle(self, conn, p):
        bundle = self.bundles.pop((p["pg_id"], p["bundle_index"]), None)
        if bundle:
            # Only the unused part returns now; resources still held by
            # running leases come back to the node pool as each lease
            # returns (see _release_resources fallthrough) — never
            # double-counted against physical chips.
            self._release_resources(bundle["available"])
        return True

    # -------------------------------------------------------------- objects --
    async def h_pin_object(self, conn, p):
        """Owner-requested pin of a primary copy (reference: raylet
        PinObjectIDs keeping plasma objects alive for their owner)."""
        oid = p["object_id"]
        if p.get("owner_addr"):
            # Who to tell when a drain migrates this primary elsewhere.
            self._pinned_owner[oid] = tuple(p["owner_addr"])
        if oid in self.spilled:
            self.pinned[oid] = self.pinned.get(oid, 0) + 1
            return True
        if self.store.get(oid, timeout_ms=0) is None:
            return False
        self.pinned[oid] = self.pinned.get(oid, 0) + 1
        await self._maybe_spill_to_threshold()
        return True

    async def h_pin_transfer(self, conn, p):
        """Adopt a writer-held pin (one-way notify from the put/return hot
        path). The writer stored with keep_pin, so one shm refcount is
        already in place — this is pure bookkeeping: record it as an owner
        pin so unpin/free release it, exactly as if h_pin_object had taken
        it. Spilled-to-disk primaries carry no shm refcount but use the
        same pinned accounting (h_unpin_object/h_free_objects check
        self.spilled before touching the store)."""
        oid = p["object_id"]
        if p.get("owner_addr"):
            self._pinned_owner[oid] = tuple(p["owner_addr"])
        self.pinned[oid] = self.pinned.get(oid, 0) + 1
        # The create this pin finalizes is sealed: its admission
        # reservation (if any) collapses into the store's real
        # accounting.
        self._release_reservation(oid)
        if oid in self.spilled:
            # Spilled before (or during) the ownership handoff — e.g. a
            # worker's direct put-to-disk whose owner we only learn now:
            # register the storage-tier directory location.
            owner = self._pinned_owner.get(oid)
            if owner is not None:
                rpc.spawn(self._notify_owner_location(
                    oid, owner, add=True, disk=True))
        await self._maybe_spill_to_threshold()
        return True

    async def h_unpin_object(self, conn, p):
        oid = p["object_id"]
        n = self.pinned.get(oid, 0)
        if n <= 1:
            self.pinned.pop(oid, None)
            self._pinned_owner.pop(oid, None)
        else:
            self.pinned[oid] = n - 1
        if n >= 1 and oid not in self.spilled:
            self.store.release(oid)
        return True

    async def h_free_objects(self, conn, p):
        for oid in p["object_ids"]:
            self._release_reservation(oid)
            for _ in range(self.pinned.pop(oid, 0)):
                if oid not in self.spilled:
                    self.store.release(oid)
            spill = self.spilled.pop(oid, None)
            self._disk_cached.pop(oid, None)
            self._pinned_owner.pop(oid, None)
            # Deregister with the owner: for owner-initiated frees the
            # directory entry is already gone (the remove is a no-op),
            # but a direct free (tools/bench) must not leave the owner
            # pointing at bytes we just dropped.
            self._drop_replica_registration(oid)
            if spill is not None:
                try:
                    os.unlink(spill[0])
                except FileNotFoundError:
                    pass
            self._ext_delete(oid)
            self.store.delete(oid)
            if oid in self._adopted:
                # Adopted-from-drain primary freed: clear the cluster-wide
                # migration record so nothing repoints to freed bytes.
                self._adopted.discard(oid)
                if self.gcs is not None:
                    rpc.spawn(self.gcs.call(
                        "kv_del", {"ns": "migrated", "key": oid.hex(),
                                   "prefix": False}))
            dest = self._migrated_away.pop(oid, None)
            if dest is not None:
                # Freed on the draining source after migration: forward so
                # the adopted copy (and its pin) can't leak at the peer.
                rpc.spawn(self._forward_free(dest, oid))
        return True

    def _ext_delete(self, oid: bytes) -> None:
        """Best-effort removal of an object's durable external copy + its
        GCS registration (freed objects must not accumulate in the cloud
        tier)."""
        if self._ext is None:
            return
        uri = self._ext_uris.pop(oid, None)
        if uri is None:
            return
        try:
            self._ext.delete(uri)
        except Exception:
            logger.exception("external spill delete failed for %s",
                             oid.hex())
        if self.gcs is not None:
            rpc.spawn(self.gcs.call(
                "kv_del", {"ns": "spill_ext", "key": oid.hex(),
                           "prefix": False}))

    # --- spilling (reference: local_object_manager.h:43 + plasma
    # create_request_queue backpressure) ------------------------------------
    def _spill_path(self, oid: bytes) -> str:
        os.makedirs(self._spill_dir, exist_ok=True)
        return os.path.join(self._spill_dir, oid.hex())

    async def _spill_one(self, oid: bytes) -> int:
        """Move one pinned primary to disk. Returns bytes freed (0 = not
        spillable right now: unsealed, or a reader outside our pins).
        The file write runs off-loop; the delete is atomic against readers
        (release_n_and_delete_if) so a worker that pins mid-write keeps a
        valid object and the spill aborts."""
        if oid in self.spilled or oid in self._spilling:
            return 0
        npins = self.pinned.get(oid, 0)
        view = self.store.get(oid, timeout_ms=0)
        if view is None:
            return 0
        if self.store.refcount(oid) > npins + 1:  # fast-path skip: reader active
            view.release()
            self.store.release(oid)
            return 0
        self._spilling.add(oid)
        size = len(view)
        try:
            path = self._spill_path(oid)
        except OSError:
            # Spill dir unusable (unwritable/clobbered): the object is
            # simply not spillable right now — the sweep must degrade,
            # not crash the admission loops that drive it.
            self._spilling.discard(oid)
            view.release()
            self.store.release(oid)
            return 0
        try:
            loop = asyncio.get_running_loop()
            await loop.run_in_executor(None, _write_file, path, view)
        except OSError:
            view.release()
            self.store.release(oid)
            return 0
        finally:
            self._spilling.discard(oid)
            view.release()
        if self.pinned.get(oid, 0) != npins:
            # The pin count moved while the write ran off-loop (a second
            # put's pin_transfer, a fresh owner pin, or an unpin): the
            # snapshot release_n_and_delete_if would commit is STALE —
            # releasing n+1 here would either strip a pin someone still
            # counts on or leave the arena copy undeletable with broken
            # accounting.  Abort this sweep's attempt; the object is
            # still resident and a later sweep re-snapshots.
            self.store.release(oid)
            try:
                os.unlink(path)
            except FileNotFoundError:
                pass
            return 0
        if not self.store.release_n_and_delete_if(oid, npins + 1):
            # A reader pinned the object mid-write: abort the spill.
            try:
                os.unlink(path)
            except FileNotFoundError:
                pass
            return 0
        self.spilled[oid] = (path, size)
        self._spilled_bytes_total += size
        # The spilled primary becomes a STORAGE-TIER directory location
        # at its owner: pulls/recovery resolve through it (this agent
        # serves the file via fetch_chunk / restores direct-to-arena),
        # and locality scoring discounts it to DISK_TIER_WEIGHT.
        owner = self._pinned_owner.get(oid)
        if owner is not None:
            rpc.spawn(self._notify_owner_location(oid, owner, add=True,
                                                  disk=True))
        if self._ext is not None:
            # Synchronous: the object is not durably spilled until the
            # external copy exists (the reference's cloud spill IS the
            # spill write, not a background mirror).
            await self._ext_upload(oid, path)
        return size

    async def _free_space(self, need: int) -> int:
        """Make `need` bytes of arena headroom, cheapest eviction first.

        Ordering (the tiered-memory eviction policy, test-pinned):
        1. DROP local secondaries — replicas this node pulled from an
           owner elsewhere.  The swarm can re-fetch them from the
           primary at any time, so deleting one costs a future pull,
           never durability, and no disk write.
        2. SPILL sole pinned primaries oldest-first — each costs an
           NVMe write and makes THIS node the only restore source — but
           never below `eviction_pinned_bytes_floor` of arena-resident
           pinned bytes (a hot working set stays mapped even under
           admission pressure).
        Unpinned sealed objects are already LRU-evicted by the store
        itself on allocation pressure."""
        freed = 0
        try:
            objs = {o: (sz, rc) for o, sz, rc in self.store.list_objects()}
        except Exception:
            objs = {}
        for oid in list(self._replica_owner.keys()):
            if freed >= need:
                return freed
            info = objs.get(oid)
            if info is None or oid in self.pinned or oid in self.spilled:
                continue
            size, rc = info
            if rc != 0:
                continue            # a reader holds it right now
            try:
                self.store.delete(oid)
            except Exception:
                continue
            self._drop_replica_registration(oid)
            freed += size
        if freed >= need:
            return freed
        floor = self._pinned_floor
        resident = 0
        if floor > 0:
            resident = sum(objs.get(o, (0, 0))[0] for o in self.pinned
                           if o not in self.spilled)
        for oid in list(self.pinned.keys()):
            if freed >= need:
                break
            if floor > 0 and \
                    resident - objs.get(oid, (0, 0))[0] < floor:
                continue
            got = await self._spill_one(oid)
            freed += got
            if floor > 0:
                resident -= got
        return freed

    def _capacity_scale(self) -> float:
        """mem_chaos hook: fraction of real capacity the admission/spill
        policy may use right now (1.0 = no squeeze)."""
        return (self._mem_chaos.arena_frac()
                if self._mem_chaos is not None else 1.0)

    def _reserved_bytes(self) -> int:
        """Unexpired admission reservations (granted creates not yet
        sealed) — counted as in-use by every policy decision.  Expiry
        (writer crashed between reserve and seal) is swept here."""
        if not self._reserved:
            return 0
        now = time.monotonic()
        for o in [o for o, (_, exp) in self._reserved.items()
                  if exp < now]:
            del self._reserved[o]
        return sum(n for n, _ in self._reserved.values())

    def _arena_pressure(self, st=None) -> float:
        """Occupancy-with-reservations over EFFECTIVE capacity in
        [0, 1] — the arena's contribution to the shared pressure
        signal."""
        if st is None:
            try:
                st = self.store.stats()
            except Exception:
                return 0.0
        cap = max(1, int(st.get("capacity", 1) * self._capacity_scale()))
        used = st.get("bytes_in_use", 0) + self._reserved_bytes()
        return min(1.0, used / cap)

    async def _maybe_spill_to_threshold(self):
        st = self.store.stats()
        cap = int(st["capacity"] * self._capacity_scale())
        target = int(cap * self._spill_threshold)
        usage = st["bytes_in_use"] + self._reserved_bytes()
        if usage > target:
            await self._free_space(usage - target)

    async def h_ensure_space(self, conn, p):
        """Create-queue backpressure: a writer that got ENOMEM asks us to
        spill; it retries its create afterwards."""
        return {"freed": await self._free_space(int(p["nbytes"]))}

    # --- create admission (the CreateRequestQueue analogue; reference:
    # plasma create_request_queue.h — creates QUEUE for headroom instead
    # of failing, and fail TYPED past their deadline) --------------------
    def _retry_after_s(self) -> float:
        """Backoff hint for a refused create: scales with queue depth so
        a deeper backlog spreads retries wider."""
        return min(5.0, 0.1 * (1 + len(self._create_queue)))

    def _admit_now(self, oid: bytes, nbytes: int) -> bool:
        """Reserve `nbytes` of headroom for `oid` if it fits RIGHT NOW
        under effective capacity minus in-use minus prior reservations.
        The reservation makes admission atomic: a racing create cannot
        be granted the same headroom, and pressure sweeps count it as
        in-use so they never target the headroom an unsealed in-progress
        region is about to occupy."""
        try:
            st = self.store.stats()
        except Exception:
            return False
        cap = int(st["capacity"] * self._capacity_scale())
        headroom = cap - st["bytes_in_use"] - self._reserved_bytes()
        if nbytes > headroom:
            return False
        self._reserved[oid] = (nbytes, time.monotonic() + 60.0)
        return True

    def _release_reservation(self, oid: bytes) -> None:
        if self._reserved.pop(oid, None) is not None and self._reserved:
            self._create_event.set()

    async def h_reserve_create(self, conn, p):
        """Admission control for a put/return seal: reserve arena
        headroom, parking FIFO (bounded) while eviction/spill makes
        room.  Reply {"ok": True} = reserved, go store; {"ok": False,
        "retry_after_s": ...} = refused typed — the caller surfaces
        ObjectStoreFullError(retry_after_s), NEVER a raw arena error."""
        oid = p["object_id"]
        nbytes = int(p["nbytes"])
        deadline = time.monotonic() + float(
            p.get("timeout_s") or get_config().create_backpressure_timeout_s)
        # Fast path only when nothing is parked: FIFO order is the
        # anti-starvation guarantee (a stream of small puts must not
        # starve the big create at the head of the queue).
        if not self._create_queue and self._admit_now(oid, nbytes):
            return {"ok": True}
        if not self._create_queue:
            await self._free_space(nbytes)
            if self._admit_now(oid, nbytes):
                return {"ok": True}
        if len(self._create_queue) >= self._create_queue_depth_max:
            return {"ok": False, "reason": "queue_full",
                    "retry_after_s": self._retry_after_s()}
        fut = asyncio.get_running_loop().create_future()
        self._create_queue.append((oid, nbytes, deadline, fut))
        self._create_event.set()
        return await fut

    async def _create_queue_loop(self):
        """FIFO drainer for parked creates: retries the HEAD as
        eviction/spill/frees make headroom, expires entries typed at
        their deadline."""
        while not self._shutdown:
            if not self._create_queue:
                self._create_event.clear()
                await self._create_event.wait()
                continue
            oid, nbytes, deadline, fut = self._create_queue[0]
            if fut.done():
                self._create_queue.popleft()
                continue
            if time.monotonic() >= deadline:
                self._create_queue.popleft()
                fut.set_result({"ok": False, "reason": "deadline",
                                "retry_after_s": self._retry_after_s()})
                continue
            if self._admit_now(oid, nbytes):
                self._create_queue.popleft()
                fut.set_result({"ok": True})
                continue
            try:
                await self._free_space(nbytes)
            except Exception:
                logger.exception("create-queue eviction pass failed")
            if self._admit_now(oid, nbytes):
                self._create_queue.popleft()
                fut.set_result({"ok": True})
                continue
            # No headroom yet: wait for a free/unpin/chaos-restore tick.
            await asyncio.sleep(0.05)

    async def h_spill_path(self, conn, p):
        """Hand a worker the path for a direct put-to-disk (objects that can
        never fit the arena). The worker writes the file itself — same host,
        shared filesystem — so no copy crosses the RPC."""
        return self._spill_path(p["object_id"])

    async def h_spill_register(self, conn, p):
        oid = p["object_id"]
        path = self._spill_path(oid)
        if not os.path.exists(path):
            return False
        size = os.path.getsize(path)
        self.spilled[oid] = (path, size)
        self._spilled_bytes_total += size
        self._release_reservation(oid)
        owner = (tuple(p["owner_addr"]) if p.get("owner_addr")
                 else self._pinned_owner.get(oid))
        if owner is not None:
            self._pinned_owner.setdefault(oid, owner)
            rpc.spawn(self._notify_owner_location(oid, owner, add=True,
                                                  disk=True))
        if self._ext is not None:
            await self._ext_upload(oid, path)
        return True

    async def _ext_upload(self, oid: bytes, path: str) -> None:
        """Push a freshly-spilled object to the durable tier and register
        its URI in the GCS KV (any node can then restore it)."""
        loop = asyncio.get_running_loop()
        try:
            data = await loop.run_in_executor(None, _read_file, path)
            uri = await loop.run_in_executor(
                None, self._ext.spill, oid.hex(), data)
        except Exception:
            logger.exception("external spill upload failed for %s",
                             oid.hex())
            return
        if oid not in self.spilled:
            # Freed (or restored-and-freed) while uploading.
            try:
                self._ext.delete(uri)
            except Exception:
                pass
            return
        self._ext_uris[oid] = uri
        if self.gcs is not None:
            try:
                await self.gcs.call("kv_put", {
                    "ns": "spill_ext", "key": oid.hex(),
                    "value": uri.encode(), "overwrite": True})
            except rpc.RpcError:
                logger.warning("could not register external spill of %s",
                               oid.hex())

    def _reacquire_pins(self, oid: bytes) -> bool:
        """Re-take this agent's owner pins on a just-restored object.
        False (with any partial pins dropped) if the object vanished
        mid-way — callers must then treat the restore as failed rather
        than deleting the durable copy of an evicted object."""
        need = self.pinned.get(oid, 0)
        for i in range(need):
            if self.store.get(oid, timeout_ms=0) is None:
                for _ in range(i):
                    self.store.release(oid)
                return False
        return True

    def _put_restored(self, oid: bytes, data: bytes) -> bool:
        """Insert restored bytes into shm + re-acquire this agent's pins.
        The writer pin is held across the re-pin so there is no
        zero-refcount window in which the fresh copy could be evicted."""
        held = False
        try:
            self.store.put(oid, [data], keep_pin=True)
            held = True
        except ObjectExistsError:
            pass
        except Exception:
            return False
        ok = self._reacquire_pins(oid)
        if held:
            self.store.release(oid)
        return ok

    async def _restore_from_external(self, oid: bytes) -> bool:
        """Pull a durable copy registered by ANY node (possibly dead) out
        of the external tier (reference: spilled-object URLs resolvable
        cluster-wide via external_storage.py)."""
        if self._ext is None:
            return False
        uri = self._ext_uris.get(oid)
        if uri is None and self.gcs is not None:
            try:
                v = await self.gcs.call(
                    "kv_get", {"ns": "spill_ext", "key": oid.hex()})
            except rpc.RpcError:
                return False
            if v is None:
                return False
            uri = v.decode() if isinstance(v, (bytes, bytearray)) else v
        if uri is None:
            return False
        loop = asyncio.get_running_loop()
        try:
            data = await loop.run_in_executor(None, self._ext.restore, uri)
        except Exception:
            # A transiently unreachable tier (NFS blip, backend IOError)
            # must read as "not restorable" so callers fall back to
            # lineage — not as an RPC error surfacing in a user get().
            logger.exception("external restore failed for %s", oid.hex())
            return False
        if data is None:
            return False
        # This agent now co-owns the durable copy: record its URI so a
        # later free from HERE also reclaims the cloud object + KV key
        # (the spiller node may be dead — cross-node restores must not
        # leak the external tier).
        self._ext_uris[oid] = uri
        for _ in range(3):
            if self._put_restored(oid, data):
                return True
            if await self._free_space(len(data)) == 0:
                break
        # Arena too contended to admit the object (live reader views make
        # primaries unspillable): re-materialize the local spill file so
        # readers can stream from it via the normal spilled-object path
        # (reference: spilled_object_reader.h).  Only this fallback pays
        # the disk write — the common uncontended restore stays in shm.
        path = self._spill_path(oid)
        try:
            await loop.run_in_executor(None, _write_file, path, data)
        except OSError:
            logger.exception("spill re-materialization failed for %s",
                             oid.hex())
            return False
        self.spilled[oid] = (path, len(data))
        return False  # callers fall back to streaming the spill file

    async def _restore_object(self, oid: bytes) -> bool:
        """Bring a spilled object back into shm (reference: raylet
        RestoreSpilledObject). Re-acquires the agent's pins; deletes the
        disk copy on success.  Falls back to the external tier when the
        local spill file is missing (e.g. restored on a different node
        than the spiller after a node death)."""
        spill = self.spilled.get(oid)
        if spill is None:
            if self.store.contains(oid):
                return True
            return await self._restore_from_external(oid)
        path, size = spill
        loop = asyncio.get_running_loop()
        # Zero-copy restore: the spill file is read DIRECTLY into the
        # object's freshly-allocated arena view (readinto — one pass from
        # the page cache, no intermediate Python bytes and no second
        # memcpy), off-loop so a multi-GB restore doesn't stall the agent.
        # keep_pin=True holds the writer pin across the executor->loop hop
        # so the fresh copy can't be evicted before the re-pin below.
        held = False
        for _ in range(3):
            try:
                await loop.run_in_executor(
                    None, lambda: self.store.read_file_into(
                        oid, path, size, keep_pin=True))
                held = True
                break
            except ObjectExistsError:
                break
            except FileNotFoundError:
                self.spilled.pop(oid, None)
                return await self._restore_from_external(oid)
            except StoreFullError:
                if await self._free_space(size) == 0:
                    return False
            except SpillTruncatedError:
                # The on-disk copy itself is damaged: freeing arena space
                # can't help — the durable external copy is the only way
                # back, and the broken file must be forgotten.
                logger.exception("spill file corrupt for %s", oid.hex())
                self.spilled.pop(oid, None)
                return await self._restore_from_external(oid)
            except OSError:
                # Transient I/O (EMFILE under fd churn, EIO blips): the
                # spill file is still the durable copy — KEEP the entry
                # and retry; dropping it would orphan valid bytes and
                # misreport the object as gone to remote pullers.
                logger.warning("transient I/O restoring %s; retrying",
                               oid.hex(), exc_info=True)
        else:
            return False
        ok = self._reacquire_pins(oid)
        if held:
            self.store.release(oid)
        if not ok:
            # Evicted out from under us (pre-existing copy raced an
            # eviction): keep the spill file — it is the durable copy.
            return False
        self.spilled.pop(oid, None)
        self._disk_cached.pop(oid, None)
        self._restored_bytes_total += size
        # Back in the arena: retract the storage-tier directory marking
        # (disk=True removes ONLY the tier record — this node's
        # primary/secondary entry stands, now at full arena weight).
        owner = self._pinned_owner.get(oid)
        if owner is not None:
            rpc.spawn(self._notify_owner_location(oid, owner, add=False,
                                                  disk=True))
        try:
            os.unlink(path)
        except FileNotFoundError:
            pass
        return True

    async def h_restore_object(self, conn, p):
        return await self._restore_object(p["object_id"])

    # --- transfer (reference: object_manager.cc chunked push/pull) ----------
    async def h_fetch_from_store(self, conn, p):
        """Whole-object fetch (small objects / compat path)."""
        oid = p["object_id"]
        if oid in self.spilled:
            path, _ = self.spilled[oid]
            try:
                with open(path, "rb") as f:
                    return f.read()
            except FileNotFoundError:
                return None
        view = self.store.get(oid, timeout_ms=p.get("timeout_ms", 0))
        if view is None:
            return None
        try:
            return bytes(view)
        finally:
            view.release()
            self.store.release(oid)

    def _note_served(self, n: int) -> None:
        # Shard threads and the main loop both serve chunks; += on an
        # attribute is a read-modify-write that drops counts under races.
        with self._served_lock:
            self._bytes_served += n

    def _sh_fetch_chunk(self, conn, p):
        """SHARD-LOCAL fetch_chunk fast path (see _handlers wiring): a
        SEALED shm object is served straight off the connection's I/O
        shard — store lookup, arena subview pin, and the raw writev all
        stay on the shard thread, so N peers pulling N objects spread
        across cores instead of serializing on the agent's main loop.
        Anything stateful — spilled objects, mid-pull partial serves,
        gone-handling (directory retraction) — returns FAST_FALLBACK and
        takes the exact h_fetch_chunk path on the main loop."""
        # Bind every field BEFORE pinning (store.get): a malformed
        # request erroring after the pin would leak it permanently.
        oid, off, length = p["object_id"], p["offset"], p["length"]
        raw = p.get("raw", False)
        if oid in self.spilled:
            return rpc.FAST_FALLBACK
        view = self.store.get(oid, timeout_ms=0)
        if view is None:
            return rpc.FAST_FALLBACK
        self._note_served(min(length, max(0, len(view) - off)))
        if raw:
            piece = view[off:off + length]

            def _unpin(v=view, oid=oid):
                v.release()
                self.store.release(oid)

            return rpc.RawPayload([piece], release=_unpin)
        try:
            piece = bytes(view[off:off + length])
            rpc.note_copied_bytes("serve_legacy_chunk", len(piece))
            return piece
        finally:
            view.release()
            self.store.release(oid)

    async def h_object_info(self, conn, p):
        """Size + presence probe that precedes a chunked pull."""
        oid = p["object_id"]
        if oid in self.spilled:
            return {"size": self.spilled[oid][1], "spilled": True}
        part = self._partial.get(oid)
        if part is not None and part["size"] is not None:
            # Mid-pull here: peers may stripe committed chunks off us
            # (uncommitted ones answer "later" and fail over).  A
            # size-less marker (pull still probing) stays silent — we
            # know nothing a prober doesn't.
            return {"size": part["size"], "spilled": False,
                    "partial": True}
        view = self.store.get(oid, timeout_ms=p.get("timeout_ms", 0))
        if view is None:
            return None
        try:
            return {"size": len(view), "spilled": False}
        finally:
            view.release()
            self.store.release(oid)

    async def h_fetch_chunk(self, conn, p):
        """Serve one chunk of an object's bytes, from shm or the spill file.

        With p["raw"] the chunk leaves as a raw out-of-band frame: a shm
        chunk is handed to the transport as a pinned arena subview (zero
        user-space copies on this side; the pin drops once the transport
        has taken the bytes), and absence becomes the TYPED {"gone": True}
        marker so pullers can tell "source no longer holds it" from a
        dropped/failed fetch.  Legacy (non-raw) callers keep the old
        bytes-or-None contract."""
        oid, off, length = p["object_id"], p["offset"], p["length"]
        raw = p.get("raw", False)
        if oid in self.spilled:
            path, _ = self.spilled[oid]

            def _read_spill_chunk():
                try:
                    fd = os.open(path, os.O_RDONLY)
                except FileNotFoundError:
                    # A concurrent restore sealed the object back into
                    # shm and unlinked the file between our spilled-map
                    # read and the open: the caller falls through to the
                    # store lookup — answering "gone" would misroute the
                    # puller into lineage re-execution for an object
                    # this node still holds.
                    return None
                try:
                    return os.pread(fd, length, off)
                finally:
                    os.close(fd)

            # Off-loop: a cold-cache 8 MiB pread times the whole inflight
            # window would otherwise stall every RPC this agent serves.
            data = await asyncio.get_running_loop().run_in_executor(
                None, _read_spill_chunk)
            if data is not None:
                self._note_served(len(data))
                return rpc.RawPayload([data]) if raw else data
        view = self.store.get(oid, timeout_ms=0)
        if view is None:
            # Receiver-becomes-source: an arena pull of this object is in
            # flight here — serve the chunk if its bytes are already
            # committed (a COPY, never a subview: the unsealed buffer's
            # lifetime belongs to the pull, which may abort), else tell
            # the peer to come back ("later" — it retries its remaining
            # sources; the primary always has the bytes).
            part = self._partial.get(oid)
            if part is not None:
                if part["buf"] is not None and part["size"] is not None:
                    end = min(off + length, part["size"])
                    if _intervals_cover(part["done"], off, end):
                        piece = bytes(part["buf"][off:end])
                        rpc.note_copied_bytes("serve_partial_chunk",
                                              len(piece))
                        self._note_served(len(piece))
                        return rpc.RawPayload([piece]) if raw else piece
                return {"later": True} if raw else None
            # No copy at all: if the directory still lists us, retract
            # the registration (the copy was LRU-evicted) so pullers
            # stop being routed here.
            self._drop_replica_registration(oid)
            return {"gone": True} if raw else None
        self._note_served(min(length, max(0, len(view) - off)))
        if raw:
            piece = view[off:off + length]

            def _unpin(v=view, oid=oid):
                v.release()
                self.store.release(oid)

            return rpc.RawPayload([piece], release=_unpin)
        try:
            piece = bytes(view[off:off + length])
            rpc.note_copied_bytes("serve_legacy_chunk", len(piece))
            return piece
        finally:
            view.release()
            self.store.release(oid)

    async def _pull_slot(self, priority: int):
        """Priority-ordered admission to the pull pool (reference:
        pull_manager.cc bundle priorities: get > wait > task args)."""
        if self._pull_active < self._max_pulls:
            self._pull_active += 1
            return
        import heapq
        fut = asyncio.get_running_loop().create_future()
        self._pull_seq += 1
        heapq.heappush(self._pull_waiters, (priority, self._pull_seq, fut))
        await fut

    def _pull_done(self):
        import heapq
        if self._pull_waiters:
            _, _, fut = heapq.heappop(self._pull_waiters)
            if not fut.done():
                fut.set_result(None)
                return
        self._pull_active -= 1

    async def h_pull_object(self, conn, p):
        """Fetch a remote object into the local store — chunked, pipelined,
        deduped against concurrent pulls of the same id, admission-
        controlled by priority (reference: pull_manager.cc, 806 LoC of
        priority logic).  `from_addrs` lists candidate source nodes in
        preference order (legacy single `from_addr` accepted); a chunk
        that fails mid-stream on one source fails over to the next
        instead of aborting the pull.  Returns True on success, False
        when every source reports the object gone; a TRANSIENT
        mid-stream failure raises ObjectTransferError (typed — never a
        truncated buffer, never a false \"lost\")."""
        oid = p["object_id"]
        if self.store.contains(oid) or oid in self.spilled:
            return True
        addrs = [tuple(a) for a in (p.get("from_addrs") or [])]
        if not addrs and p.get("from_addr"):
            addrs = [tuple(p["from_addr"])]
        addrs = [a for a in addrs if a != tuple(self.address)]
        owner = tuple(p["owner_addr"]) if p.get("owner_addr") else None
        if not addrs and owner is None:
            return False
        # End-to-end budget: explicit payload field, or the deadline the
        # RPC frame itself carried (rpc dispatch exposes it) — pulls
        # triggered inside a deadline-carrying call inherit the
        # REMAINING budget with zero caller changes.
        deadline = p.get("deadline") or rpc.current_handler_deadline()
        # Join a concurrent pull of the same object, but keep each
        # caller's OWN budget: a deadline-less joiner must not inherit
        # DeadlineExceededError when the running pull's (shorter) budget
        # expires — the object is healthy, so re-pull with our budget —
        # and a deadline-carrying joiner is bounded by wait_for even
        # when the running pull has no deadline at all.
        while True:
            entry = self._pull_inflight.get(oid)
            if entry is None:
                break
            fut, running_deadline = entry
            if deadline is not None:
                # Our own budget is checked OUTSIDE the try: the except
                # below routes the running pull's expiry to a re-pull,
                # and our own expiry must never take that branch (it
                # would loop without awaiting — a synchronous spin).
                left = deadline - time.time()
                if left <= -rpc.DEADLINE_SKEW_SLACK_S:
                    raise exc.DeadlineExceededError(
                        f"pull of {oid.hex()} exceeded its deadline "
                        f"while joining an in-flight pull")
            try:
                if deadline is None:
                    return await asyncio.shield(fut)
                return await asyncio.wait_for(asyncio.shield(fut),
                                              max(0.05, left))
            except asyncio.TimeoutError:
                raise exc.DeadlineExceededError(
                    f"pull of {oid.hex()} exceeded its deadline "
                    f"while joining an in-flight pull") from None
            except exc.DeadlineExceededError:
                if deadline is None or (
                        running_deadline is not None
                        and deadline > running_deadline + 1e-6):
                    continue  # its budget, not ours — pull ourselves
                raise
        fut = asyncio.get_running_loop().create_future()
        self._pull_inflight[oid] = (fut, deadline)
        rec = frec.recorder()
        t0 = rec.begin()
        try:
            ok = await self._do_pull(oid, addrs,
                                     p.get("priority", 0),
                                     p.get("timeout_ms", 10000),
                                     deadline=deadline, owner=owner)
            fut.set_result(ok)
            # Object-transfer timeline: one span per pull, start -> commit
            # (chunk-wave/hedge events nest inside it, keyed by the same
            # object id).
            rec.end("transfer", "pull", t0, id=oid, ok=bool(ok),
                    sources=self._last_pull_sources)
            return ok
        except Exception as e:
            fut.set_exception(e)
            # Mark retrieved: with no concurrent deduped waiter the future
            # is dropped, and an unconsumed exception (now routine —
            # transient failures raise ObjectTransferError by design)
            # would spam 'Future exception was never retrieved' at GC.
            fut.exception()
            raise
        finally:
            self._pull_inflight.pop(oid, None)

    class _ObjectGone(Exception):
        """Internal: every source reported the object absent."""

    async def _stream_chunks(self, peers, oid: bytes, size: int,
                             make_sink, commit=None,
                             deadline: float | None = None,
                             on_chunk=None) -> None:
        """Shared pipelined chunk engine for arena- and disk-destined
        pulls (and any future push path).  Keeps up to
        `object_transfer_max_inflight_chunks` fetch_chunk requests in
        flight so the source's shm/spill reads overlap the wire; each
        chunk lands via a raw out-of-band frame scattered straight into
        make_sink(pos, n) — no msgpack pass, no intermediate bytes.
        `commit(pos, data)` (optional coroutine) runs after a chunk fully
        lands — disk-destined pulls stage each chunk in memory and flush
        it off-loop there, so no blocking write ever runs on the agent
        loop; without commit the sink itself is the final destination
        (arena view).  `on_chunk(pos, n)` (optional, sync) fires once a
        chunk is final — the pull path publishes committed ranges there
        so this agent can serve them to swarm peers mid-pull.

        Swarm striping: with >=2 sources, chunk i's source order is the
        peer list ROTATED by i (round-robin) — N concurrent pullers of
        one object spread their chunk load across every holder instead
        of serializing on the first (Cornet-style broadcast; the owner
        already ordered the list primary-first, suspects last, and
        `_order_peers` folded in local link evidence).  A source that
        answers "later" (mid-pull peer that hasn't committed that chunk
        yet) is skipped for this attempt — the rotation always ends at
        a complete copy.

        Tail defense: with >=2 sources and budget left in the hedge
        bucket, the first attempt of each chunk RACES a backup source
        started after the primary's observed p95 latency — first
        responder wins, the straggler is cancelled (its late bytes are
        discarded by call_raw's sink defusal, so the two writers can
        never interleave into the destination).  An end-to-end
        `deadline` (absolute wall clock) caps every attempt's timeout by
        the remaining budget and raises DeadlineExceededError when it
        runs out.

        Failure discipline: a failed chunk retries on each source in turn
        (two passes).  Raises _ObjectGone when every source consistently
        answers \"gone\", ObjectTransferError when transient failures
        (drops, timeouts, short reads) exhaust the retry budget — callers
        abort the destination, so a partial pull can never be mistaken
        for complete data."""
        if size == 0:
            return

        def budget_timeout() -> float:
            if deadline is None:
                return self._chunk_timeout
            rem = deadline - time.time()
            # Slack: the deadline may have been stamped by a remote
            # owner's clock.  Within the skew window attempts continue
            # on a short floor; retry exhaustion past the deadline still
            # classifies as a deadline failure below.
            if rem <= -rpc.DEADLINE_SKEW_SLACK_S:
                raise exc.DeadlineExceededError(
                    f"pull of {oid.hex()} exceeded its deadline")
            return min(self._chunk_timeout, max(rem, 0.25))

        async def try_peer(peer, pos: int, n: int, sink_obj,
                           eff_timeout: float):
            """One fetch attempt -> ('ok'|'gone'|'dead'|'transient', err).
            'dead' == source unreachable: its copy is lost for our
            purposes (must route to ObjectLost -> lineage recovery, not
            to a retryable transient that never reconstructs)."""
            if peer is None or peer.closed:
                return "dead", None
            t0 = time.monotonic()
            try:
                res = await peer.call_raw(
                    "fetch_chunk",
                    {"object_id": oid, "offset": pos,
                     "length": n, "raw": True},
                    sink=sink_obj, timeout=eff_timeout)
            except rpc.ConnectionLost as e:
                self._note_peer_failure(peer)
                return "dead", e
            except (rpc.RpcError, asyncio.TimeoutError) as e:
                self._note_peer_failure(peer)
                return "transient", e
            if isinstance(res, int) and res == n:
                self._note_peer_latency(peer, time.monotonic() - t0, n,
                                        chunk=True)
                return "ok", None
            if isinstance(res, (bytes, bytearray)):
                # Legacy peer: msgpack bytes body.
                if len(res) == n:
                    sink_obj[0:n] = res
                    rpc.note_copied_bytes("pull_legacy_chunk", n)
                    self._note_peer_latency(peer, time.monotonic() - t0,
                                            n, chunk=True)
                    return "ok", None
                return "transient", ValueError(f"short chunk {len(res)}/{n}")
            if isinstance(res, dict) and res.get("later"):
                # Mid-pull peer hasn't committed this chunk yet: not a
                # failure (no health penalty), just not a source for
                # THIS chunk right now.
                return "later", None
            if res is None or (isinstance(res, dict) and res.get("gone")):
                return "gone", None
            return "transient", ValueError(
                f"unexpected fetch_chunk reply {type(res)}")

        async def settle(task):
            """Cancel-and-await a straggler attempt: call_raw's finally
            defuses its reception, so once this returns its late bytes
            can only be discarded — never scattered into a buffer the
            winner already filled.  Our OWN cancellation (this whole
            fetch aborted by a sibling chunk's failure) is re-raised —
            but only after the straggler is done — rather than
            swallowed: a worker that survives cancel would keep
            scattering remote bytes into arena regions the aborted
            pull has already released for reuse."""
            if not task.done():
                task.cancel()
            external = None
            while True:
                try:
                    await task
                except asyncio.CancelledError:
                    if not task.done():
                        # Injected into US mid-await (the straggler is
                        # still running, so it can't be the source).
                        # Remember it and keep waiting the straggler
                        # out — its cancel is already requested.
                        external = asyncio.CancelledError()
                        continue
                except Exception:  # noqa: BLE001
                    pass
                break
            if external is not None:
                raise external

        async def hedged(pos: int, n: int, ordered) -> bool:
            """Primary-vs-delayed-backup race; True = chunk landed.
            `ordered` is this chunk's striped source order — its head is
            the chunk's assigned source, the backup comes from the
            rest."""
            primary = ordered[0]
            backup = next((p for p in ordered[1:]
                           if p is not None and not p.closed), None)
            if backup is None or primary is None or primary.closed:
                return False
            eff = budget_timeout()
            sink1 = make_sink(pos, n)
            t1 = rpc.spawn(try_peer(primary, pos, n, sink1, eff))
            t2 = None
            # try/finally: budget expiry mid-race, or this whole fetch
            # being cancelled by a sibling chunk's failure, must never
            # leave an un-settled attempt scattering into the real sink
            # after the pull aborts and the arena region is reused.
            try:
                delay = min(self._hedge_delay_s(primary), eff)
                done, _ = await asyncio.wait({t1}, timeout=delay)
                if t1 in done:
                    st, _err = t1.result()
                    if st == "ok":
                        if commit is not None:
                            await commit(pos, sink1)
                        return True
                    return False   # sequential pass classifies/retries
                if not self._hedge_allow():
                    st, _err = await t1
                    if st == "ok":
                        if commit is not None:
                            await commit(pos, sink1)
                        return True
                    return False
                staging = memoryview(bytearray(n))
                frec.recorder().instant("transfer", "hedge_fired",
                                        id=oid, offset=pos)
                t2 = rpc.spawn(try_peer(backup, pos, n, staging,
                                        budget_timeout()))
                winner = None
                pending = {t1, t2}
                while pending and winner is None:
                    done, pending = await asyncio.wait(
                        pending, return_when=asyncio.FIRST_COMPLETED)
                    for t in done:
                        if t.result()[0] == "ok":
                            winner = t
                            break
                # Settled BEFORE touching sink1 below (not only in the
                # finally): the loser must be done writing first.
                await settle(t1)
                await settle(t2)
                if winner is None:
                    return False
                if winner is t2:
                    # Backup won into its private staging buffer; the
                    # primary is fully settled, so the real sink is ours.
                    rpc.note_copied_bytes("pull_hedge_staging", n)
                    if commit is None:
                        sink1[0:n] = staging
                    else:
                        await commit(pos, staging)
                    return True
                if commit is not None:
                    await commit(pos, sink1)
                return True
            finally:
                # Settle BOTH stragglers even if our own cancellation
                # lands mid-settle; propagate it only once neither can
                # write another byte.
                external = None
                for t in (t1, t2):
                    if t is None:
                        continue
                    try:
                        await settle(t)
                    except asyncio.CancelledError as e:
                        external = e
                if external is not None:
                    raise external

        # Per-node stripe phase: with N pullers and N holders, chunk i's
        # PRIMARY-assigned owner is unique per puller (k = (i + phase)
        # mod n), so in the cold concurrent phase the origin serves each
        # chunk ~once instead of N times — the swarm then exchanges the
        # rest peer-to-peer (Cornet partitions the chunk space the same
        # way before receivers gossip).
        phase = int.from_bytes(getattr(self, "node_id", b"")[:2] or b"\0",
                               "little") if len(peers) > 1 else 0

        async def fetch(pos: int) -> None:
            n = min(self._chunk_bytes, size - pos)
            # Round-robin stripe: chunk i's preferred source rotates
            # through the holder set, so concurrent pulls of one object
            # form a swarm instead of a convoy on the first source.
            k = (pos // self._chunk_bytes + phase) % len(peers)
            ordered = peers[k:] + peers[:k]
            self._hedge_total += 1
            if self._hedge_enabled and len(peers) >= 2:
                if await hedged(pos, n, ordered):
                    if on_chunk is not None:
                        on_chunk(pos, n)
                    return
            last_err = None
            gone = dead = transient = 0
            for _round in range(2):
                gone = dead = transient = 0
                for peer in ordered:
                    sink_obj = make_sink(pos, n)
                    st, err = await try_peer(peer, pos, n, sink_obj,
                                             budget_timeout())
                    if st == "ok":
                        if commit is not None:
                            await commit(pos, sink_obj)
                        if on_chunk is not None:
                            on_chunk(pos, n)
                        return
                    if st == "gone":
                        gone += 1
                    elif st == "dead":
                        dead += 1
                        last_err = err or last_err
                    else:
                        # "later" (mid-pull peer) counts with transient:
                        # that source still EXISTS, so an all-gone
                        # verdict (-> ObjectLost -> lineage) stays off
                        # the table while any swarm member remains.
                        transient += 1
                        if st == "later" and _round == 0:
                            # Give the mid-pull source a beat to commit
                            # before falling back — without it the cold
                            # phase of a broadcast degenerates to
                            # everyone re-converging on the origin.
                            await asyncio.sleep(0.02)
                        elif st != "later":
                            last_err = err or last_err
                if (gone or dead) and not transient:
                    # Unanimous and unambiguous: no second pass.
                    break
            if transient == 0:
                # Every source is gone or dead — the object is not
                # obtainable by retrying this pull.
                raise NodeAgent._ObjectGone(oid)
            if deadline is not None and time.time() > deadline:
                # Retries exhausted AND the budget ran out: the typed
                # deadline outcome, not a retryable transient — the
                # owner already wrote this pull off.
                raise exc.DeadlineExceededError(
                    f"pull of {oid.hex()} exceeded its deadline "
                    f"(chunk {pos}..{pos + n} unfetched after retries: "
                    f"{last_err!r})")
            raise exc.ObjectTransferError(
                f"chunk {pos}..{pos + n} of {oid.hex()} failed on all "
                f"{len(peers)} source(s) after retries: {last_err!r}")

        await rpc.gather_windowed(
            fetch, range(0, size, self._chunk_bytes),
            self._max_inflight_chunks)

    async def _pull_peers(self, addrs) -> list:
        """Resolve source addresses to live (cached) connections.  Each
        connection is tagged with its address (_peer_addr) so transfer
        paths can record per-peer latency/rate stats."""
        peers = []
        for addr in addrs:
            peer = self._peer_conns.get(addr)
            if peer is None or peer.closed:
                try:
                    peer = await rpc.connect(addr, name="agent->agent",
                                             retries=2)
                except rpc.ConnectionLost:
                    self._note_peer_failure(addr)
                    continue
                peer._peer_addr = addr
                self._peer_conns[addr] = peer
            peers.append(peer)
        return peers

    # ---------------------------------------------- replica directory -----
    async def _owner_conn(self, owner: tuple) -> rpc.Connection:
        """Connection to an object OWNER (a worker/driver process, not an
        agent) — shares the peer connection cache; owners and agents
        speak the same RPC layer."""
        conn = self._peer_conns.get(owner)
        if conn is None or conn.closed:
            conn = await rpc.connect(owner, name="agent->owner", retries=2)
            conn._peer_addr = owner
            self._peer_conns[owner] = conn
        return conn

    async def _merge_owner_locations(self, oid: bytes, addrs: list,
                                     owner: tuple,
                                     register: bool = False) -> list:
        """Union of the caller's from_addrs and the owner directory's
        CURRENT holder set (self excluded, caller's order preserved —
        the owner already ranks suspects last).  With register=True the
        same round trip records THIS node as a mid-pull secondary,
        atomically on the owner's loop — concurrent broadcast pullers
        discover each other through exactly this.  Best-effort: an
        unreachable owner just means no extra sources."""
        try:
            conn = await self._owner_conn(owner)
            res = await conn.call(
                "object_locations",
                {"object_id": oid,
                 "add_addr": list(self.address) if register else None},
                timeout=5)
        except (rpc.RpcError, asyncio.TimeoutError, OSError):
            return addrs
        if not res:
            return addrs
        merged = [tuple(a) for a in addrs]
        me = tuple(self.address)
        for a in res.get("locations") or ():
            t = tuple(a)
            if t != me and t not in merged:
                merged.append(t)
        return merged

    def _order_peers(self, peers: list) -> list:
        """Stable reorder of pull sources by LOCAL link evidence: peers
        with fresh failures sink to the back (the owner's ordering
        already put GCS-scored gray suspects last; this folds in what
        this node saw first-hand, e.g. a half-open link the GCS can't
        see from its vantage).  Freshness is judged on the FAILURE
        timestamp — successes clear the counter — so a long-healed peer
        is never punished for ancient blips."""
        def suspect(conn) -> int:
            st = self._peer_stats.get(getattr(conn, "_peer_addr", None))
            if not st:
                return 0
            return 1 if (st["fail"] >= 2
                         and time.monotonic()
                         - st.get("fail_ts", 0.0) < 60.0) else 0
        return sorted(peers, key=suspect)

    def _drop_replica_registration(self, oid: bytes) -> None:
        """Withdraw a secondary registration (eviction/abort/drain):
        directory entries must never outlive the bytes they point at."""
        owner = self._replica_owner.pop(oid, None)
        if owner is not None:
            rpc.spawn(self._notify_owner_location(oid, owner, add=False))

    async def _notify_owner_location(self, oid: bytes, owner: tuple,
                                     add: bool,
                                     primary: bool = False,
                                     disk: bool = False) -> None:
        try:
            conn = await self._owner_conn(tuple(owner))
            await conn.call(
                "object_location_add" if add else "object_location_remove",
                {"object_id": oid, "addr": list(self.address),
                 "primary": primary, "disk": disk}, timeout=10)
        except Exception:
            # Best-effort: a stale directory entry only costs a puller
            # one failed probe (it fails over); a dead owner means the
            # object is unreachable anyway.
            pass

    def _sweep_replica_registrations(self) -> None:
        """Deregister secondaries whose local copy silently vanished
        (shm LRU eviction happens inside the store, below this agent's
        sight) — rides the heartbeat tick; the fetch-chunk "gone" path
        catches the in-between window lazily."""
        for oid in list(self._replica_owner):
            if oid in self._partial or oid in self.spilled or \
                    self.store.contains(oid):
                continue
            self._drop_replica_registration(oid)

    # ---------------------------------------------- peer link health ------
    def _peer_stat(self, addr: tuple) -> dict:
        st = self._peer_stats.get(addr)
        if st is None:
            from collections import deque as _dq
            st = self._peer_stats[addr] = {
                "lat": _dq(maxlen=64), "rtt": None, "rate": None,
                "fail": 0, "fail_ts": 0.0, "ts": time.monotonic()}
        return st

    def _note_peer_latency(self, peer, dt: float, nbytes: int = 0, *,
                           chunk: bool = False) -> None:
        """Record a per-peer link observation.  chunk=True samples are
        bulk-transfer wall times: they feed the hedge-delay p95 deque
        and the transfer-rate EMA but NOT the rtt EMA — a chunk's
        duration is dominated by bandwidth and pipeline queuing, and
        folding it into 'rtt' would let the GCS's gray scorer (which
        compares against ~ms ping baselines) defame any node that
        merely serves bulk traffic.  Only round-trip-shaped samples
        (timed pings, _sample_peer_rtt) update 'rtt'."""
        addr = getattr(peer, "_peer_addr", None) if not isinstance(
            peer, tuple) else peer
        if addr is None:
            return
        st = self._peer_stat(addr)
        if chunk:
            st["lat"].append(dt)
            # A served chunk proves the link works NOW: clear the local
            # failure evidence so _order_peers judges the present, not
            # a healed blip.
            st["fail"] = 0
            if nbytes and dt > 0:
                rate = nbytes / dt
                st["rate"] = rate if st["rate"] is None \
                    else 0.8 * st["rate"] + 0.2 * rate
        else:
            st["rtt"] = dt if st["rtt"] is None \
                else 0.8 * st["rtt"] + 0.2 * dt
        st["ts"] = time.monotonic()

    async def _sample_peer_rtt(self, peer) -> None:
        """One timed ping — the only evidence allowed into the 'rtt'
        EMA.  Sampled once per probed peer per pull: cheap relative to
        any pull, and a delayed/congested link inflates it exactly when
        the gray scorer should hear about it."""
        if peer is None or peer.closed:
            return
        t0 = time.monotonic()
        try:
            await peer.call("ping", {}, timeout=5)
        except Exception:
            self._note_peer_failure(peer)
            # A lost ping is worst-case RTT evidence, not silence —
            # without this the lossiest link suppresses the very
            # samples that would indict it.
            self._note_peer_latency(peer, 5.0)
            return
        self._note_peer_latency(peer, time.monotonic() - t0)

    def _note_peer_failure(self, peer) -> None:
        addr = getattr(peer, "_peer_addr", None) if not isinstance(
            peer, tuple) else peer
        if addr is None:
            return
        st = self._peer_stat(addr)
        st["fail"] += 1
        st["fail_ts"] = st["ts"] = time.monotonic()

    def _peer_stats_snapshot(self) -> Dict[str, dict]:
        """Heartbeat payload: fresh (<60s) per-peer link observations,
        keyed 'host:port' (msgpack-safe), for the GCS's gray-failure
        scorer."""
        now = time.monotonic()
        # Evict long-dead entries (restarted peers bind fresh ports, so
        # addresses churn forever) — the 15 min horizon still preserves
        # hedge-delay p95 history across ordinary idle gaps.
        for addr in [a for a, st in self._peer_stats.items()
                     if now - st["ts"] > 900.0]:
            del self._peer_stats[addr]
        out = {}
        for addr, st in self._peer_stats.items():
            if now - st["ts"] > 60.0:
                continue
            # "fail" stays local (debugging): failed pings already fold
            # into the rtt EMA as worst-case samples, so shipping the
            # raw lifetime counter would be dead heartbeat payload.
            out[f"{addr[0]}:{addr[1]}"] = {
                "rtt": st["rtt"], "rate": st["rate"],
                "age_s": round(now - st["ts"], 3)}
        return out

    def _hedge_delay_s(self, peer) -> float:
        """How long to let the primary source run before racing a
        backup: its observed p95 chunk latency (x1.5 slack), the
        config override, or a 200ms cold-start default."""
        if self._hedge_delay_ms > 0:
            return self._hedge_delay_ms / 1000.0
        addr = getattr(peer, "_peer_addr", None)
        st = self._peer_stats.get(addr) if addr is not None else None
        if st and len(st["lat"]) >= 8:
            lat = sorted(st["lat"])
            p95 = lat[int(0.95 * (len(lat) - 1))]
            return min(p95 * 1.5 + 0.01, self._chunk_timeout / 2)
        return 0.2

    def _hedge_allow(self) -> bool:
        # Windowed budget (tail-at-scale hedge budgets are windowed for
        # this reason): halving both counters keeps the spend fraction
        # but caps how much credit a long healthy period can bank —
        # without the decay, a million quiet fetches would bankroll a
        # ~100k-hedge burst exactly when the cluster is already slow,
        # doubling load on it.
        if self._hedge_total >= 2048:
            self._hedge_total //= 2
            self._hedge_used //= 2
        if self._hedge_used <= (self._hedge_budget_frac
                                * self._hedge_total + 4):
            self._hedge_used += 1
            return True
        return False

    async def _do_pull(self, oid: bytes, addrs: list, priority: int,
                       timeout_ms: int,
                       deadline: float | None = None,
                       owner=None) -> bool:
        use_dir = owner is not None and \
            get_config().replica_directory_enabled
        ok = False
        if use_dir:
            # Announce this pull FIRST: the size-less partial marker
            # makes peers probing us answer "later" (not "gone"), and
            # the register-and-query round trip below both records us in
            # the owner's directory and returns the freshest holder set
            # — secondaries that registered since the caller stamped its
            # from_addrs, including peers MID-PULL right now.  That is
            # what turns N concurrent pulls of one object into a chunk
            # swarm instead of N convoys on the primary.
            self._partial.setdefault(
                oid, {"size": None, "buf": None, "done": []})
            self._replica_owner[oid] = tuple(owner)
            addrs = await self._merge_owner_locations(oid, addrs, owner,
                                                      register=True)
            # Swarm source set resolved (directory register-and-query):
            # the width here vs the caller's hint is the broadcast's
            # fan-in signature in the timeline.
            frec.recorder().instant("transfer", "swarm_sources", id=oid,
                                    sources=len(addrs))
        try:
            ok = await self._pull_into_node(oid, addrs, priority,
                                            timeout_ms, deadline, owner)
            return ok
        finally:
            if not ok and use_dir:
                # Withdraw the registration before the marker: directory
                # entries must not outlive what they point at.
                self._drop_replica_registration(oid)
            if not ok:
                self._partial.pop(oid, None)

    async def _pull_into_node(self, oid: bytes, addrs: list, priority: int,
                              timeout_ms: int, deadline, owner) -> bool:
        peers = await self._pull_peers(addrs)
        self._last_pull_sources = len(peers)
        if not peers:
            return False
        peers = self._order_peers(peers)
        await self._pull_slot(priority)
        try:
            if deadline is not None and \
                    deadline - time.time() <= -rpc.DEADLINE_SKEW_SLACK_S:
                raise exc.DeadlineExceededError(
                    f"pull of {oid.hex()} exceeded its deadline before "
                    f"the first probe")
            info = None
            for peer in peers:
                probe_timeout = 60 if deadline is None else \
                    max(0.1, min(60, deadline - time.time()))
                t0 = time.monotonic()
                try:
                    info = await peer.call(
                        "object_info",
                        {"object_id": oid, "timeout_ms": timeout_ms},
                        timeout=probe_timeout)
                except (rpc.RpcError, asyncio.TimeoutError):
                    self._note_peer_failure(peer)
                    continue
                # NOT a latency sample: object_info is a long-poll that
                # legitimately parks server-side up to timeout_ms while
                # the object is being created — time a dedicated ping
                # instead (the only evidence the rtt EMA accepts).
                rpc.spawn(self._sample_peer_rtt(peer))
                if info is not None:
                    break
            if info is None:
                return False
            size = info["size"]
            buf = None
            for attempt in range(3):
                try:
                    buf = self.store.create_buffer(oid, size)
                    break
                except ObjectExistsError:
                    return True
                except Exception:
                    if await self._free_space(size) == 0 and attempt:
                        break
            if buf is None:
                # No room even after spilling: land the pull on disk.
                return await self._pull_to_disk(peers, oid, size,
                                                deadline=deadline,
                                                owner=owner)
            # Receiver-becomes-source: publish committed ranges so peers
            # pulling the same object can stripe them off us mid-pull
            # (the directory registration happened at pull start).
            part = {"size": size, "buf": buf, "done": []}
            self._partial[oid] = part

            def on_chunk(pos, n, _done=part["done"], _sz=size):
                _intervals_add(_done, pos, min(pos + n, _sz))
                self._bytes_pulled += n

            ok = False
            try:
                # Chunk-wave span: strictly inside this pull's
                # start/commit span (the cross-node nesting property the
                # alignment test asserts).
                with frec.recorder().span("transfer", "chunks", id=oid,
                                          bytes=size,
                                          sources=len(peers)):
                    await self._stream_chunks(
                        peers, oid, size,
                        make_sink=lambda pos, n: buf[pos:pos + n],
                        deadline=deadline, on_chunk=on_chunk)
                ok = True
            except NodeAgent._ObjectGone:
                return False
            finally:
                if not ok:
                    # The partial marker drops BEFORE the buffer's
                    # memory can be reused: a peer's fetch_chunk must
                    # never copy out of an aborted arena region.
                    self._partial.pop(oid, None)
                buf.release()
                if not ok:
                    # Covers gone, transfer errors and cancellation: never
                    # leave a permanently-unsealed object wedging this id
                    # — and never seal a partially-filled buffer (the
                    # caller withdraws the directory registration too).
                    self.store.abort(oid)
            self.store.seal(oid)
            self.store.release(oid)
            frec.recorder().instant("transfer", "commit", id=oid,
                                    bytes=size)
            # Sealed into the store before the partial record drops:
            # a peer's fetch_chunk always finds one of the two.
            self._partial.pop(oid, None)
            return True
        finally:
            self._pull_done()

    @staticmethod
    def _pwrite_chunk(path: str, data, pos: int) -> None:
        """Positional chunk write with its own fd: runs on an executor
        thread, and a stray write from a cancelled pull can never hit a
        recycled fd number (no shared-fd lifetime).  Loops on short
        writes — a silently partial chunk would later pread back as a
        zero-filled hole in a 'complete' spilled object."""
        view = memoryview(data)
        fd = os.open(path, os.O_WRONLY)
        try:
            off = 0
            while off < view.nbytes:
                n = os.pwrite(fd, view[off:], pos + off)
                if n <= 0:
                    raise IOError(
                        f"pwrite stalled at {off}/{view.nbytes} "
                        f"bytes of chunk @{pos} in {path}")
                off += n
        finally:
            os.close(fd)

    async def _pull_to_disk(self, peers, oid: bytes, size: int,
                            deadline: float | None = None,
                            owner=None) -> bool:
        path = self._spill_path(oid)
        # Create/truncate up front; chunk commits reopen positionally.
        os.close(os.open(path, os.O_WRONLY | os.O_CREAT | os.O_TRUNC, 0o644))
        loop = asyncio.get_running_loop()

        async def commit(pos, data):
            # Chunks stage in memory (window x chunk bytes, bounded) and
            # flush off-loop — a dirty-page writeback stall must not
            # freeze the agent's event loop.
            await loop.run_in_executor(
                None, self._pwrite_chunk, path, data, pos)

        # Disk-destined pulls don't serve partial chunks (the staged
        # buffers are transient), but the marker still answers peers
        # "later" instead of "gone" — a swarm member under memory
        # pressure must not push siblings toward lineage recovery.
        self._partial[oid] = {"size": size, "buf": None, "done": []}

        def on_chunk(pos, n):
            self._bytes_pulled += n

        ok = False
        try:
            try:
                await self._stream_chunks(
                    peers, oid, size,
                    make_sink=lambda pos, n: memoryview(bytearray(n)),
                    commit=commit, deadline=deadline, on_chunk=on_chunk)
                ok = True
            except NodeAgent._ObjectGone:
                return False
        finally:
            if not ok:
                self._partial.pop(oid, None)
                try:
                    os.unlink(path)
                except FileNotFoundError:
                    pass
        if not ok:
            return False
        self.spilled[oid] = (path, size)
        self._partial.pop(oid, None)
        # Non-primary disk copies are a bounded cache, LRU-evicted — the
        # owner's free only reaches the primary node (reference analogue:
        # remote copies are evictable, only primaries are pinned).
        self._disk_cached[oid] = size
        cap = self.store.stats()["capacity"]
        while sum(self._disk_cached.values()) > cap and len(self._disk_cached) > 1:
            old, osz = next(iter(self._disk_cached.items()))
            if old == oid:
                break
            self._disk_cached.pop(old)
            # Directory invalidation precedes the unlink: a puller
            # routed here between the two sees "gone" and fails over.
            self._drop_replica_registration(old)
            sp = self.spilled.pop(old, None)
            if sp is not None:
                try:
                    os.unlink(sp[0])
                except FileNotFoundError:
                    pass
        return True

    async def h_node_info(self, conn, p):
        return {
            "node_id": self.node_id,
            "address": list(self.address),
            "resources_total": self.resources_total,
            "resources_available": self.resources_available,
            "store_path": self.store_path,
            "num_workers": len(self.workers),
            "transfer": {"bytes_served": self._bytes_served,
                         "bytes_pulled": self._bytes_pulled},
        }

    async def h_store_stats(self, conn, p):
        st = self.store.stats()
        # Replica-plane observability: how wide the last pull's source
        # set was (tests assert a production pull sees >=2 once a
        # secondary exists) and cumulative transfer volume.
        st["last_pull_sources"] = self._last_pull_sources
        st["bytes_served"] = self._bytes_served
        st["bytes_pulled"] = self._bytes_pulled
        st["replica_registrations"] = len(self._replica_owner)
        return st

    async def h_list_objects(self, conn, p):
        """Full store index for the state API (reference: raylet
        GetObjectsInfo, node_manager.proto:521)."""
        return self.store.list_objects((p or {}).get("limit", 10_000))

    async def h_shutdown(self, conn, p):
        if (p or {}).get("graceful"):
            # Drain teardown: SIGTERM workers (their actor/lease conns
            # close, so clients fail over to restarted incarnations),
            # unlink the shm arena, then exit — same bounded discipline
            # as the SIGTERM path in _amain.
            async def _bye():
                try:
                    await asyncio.wait_for(self.close(), timeout=10)
                except Exception:
                    try:
                        os.unlink(self.store_path)
                    except OSError:
                        pass
                os._exit(0)

            rpc.spawn(_bye())
            return True
        asyncio.get_running_loop().call_later(0.05, lambda: os._exit(0))
        return True


async def _amain(args):
    rpc.enable_eager_tasks()
    set_config(Config(json.loads(args.system_config) if args.system_config else None))
    chaos_spec = get_config().rpc_chaos
    if chaos_spec:
        rpc.enable_chaos(chaos_spec)
    rpc.enable_link_chaos(get_config().link_chaos)
    rpc.enable_native_framer(get_config().rpc_native_framer)
    rpc.set_default_call_timeout(get_config().control_call_timeout_s)
    agent = NodeAgent(
        gcs_address=json.loads(args.gcs_address),
        session_dir=args.session_dir,
        node_id=bytes.fromhex(args.node_id),
        resources=json.loads(args.resources),
        labels=json.loads(args.labels),
        store_capacity=args.store_capacity,
        tpu_chips=json.loads(args.tpu_chips),
    )
    addr = await agent.start()
    if args.ready_file:
        tmp = args.ready_file + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"address": list(addr), "store_path": agent.store_path}, f)
        os.replace(tmp, args.ready_file)
    loop = asyncio.get_running_loop()
    stop = asyncio.Event()
    loop.add_signal_handler(signal.SIGTERM, stop.set)
    await stop.wait()
    # Bounded graceful drain, then hard exit: a SIGTERM'd agent must not
    # outlive its deadline because some peer keeps a connection open
    # (reference: raylet's graceful-shutdown deadline before _exit).
    try:
        await asyncio.wait_for(agent.close(), timeout=10)
    except Exception:
        # The arena unlink is close()'s last step — never skip it, or
        # repeated agent restarts leak /dev/shm until the tmpfs fills.
        try:
            os.unlink(agent.store_path)
        except OSError:
            pass
    from .node import dump_profile
    dump_profile()
    os._exit(0)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--gcs-address", required=True)
    parser.add_argument("--session-dir", required=True)
    parser.add_argument("--node-id", required=True)
    parser.add_argument("--resources", default="{}")
    parser.add_argument("--labels", default="{}")
    parser.add_argument("--tpu-chips", default="[]")
    parser.add_argument("--store-capacity", type=int, default=1 << 30)
    parser.add_argument("--system-config", default="")
    parser.add_argument("--ready-file", default="")
    parser.add_argument("--log-level", default="INFO")
    args = parser.parse_args()
    logging.basicConfig(level=args.log_level)
    from .node import install_daemon_profiler
    install_daemon_profiler("agent")
    from .auth import require_process_token
    require_process_token("agent", args.session_dir)
    try:
        asyncio.run(_amain(args))
    except KeyboardInterrupt:
        pass


if __name__ == "__main__":
    main()
