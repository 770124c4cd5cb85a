"""Global Control Service: cluster membership, actor directory, KV, pubsub.

Control-plane equivalent of the reference's GCS server (reference:
src/ray/gcs/gcs_server.h:98 and the services in gcs_service.proto — JobInfo,
ActorInfo, NodeInfo, KV, PlacementGroup, WorkerInfo). One asyncio process on
the head node. Tables live in memory with an optional JSON-lines append log
for restart replay (the reference's Redis-backed store_client fills this role;
a file journal gives the same GCS-restart fault-tolerance story on one host).

Actor scheduling follows the reference's GcsActorScheduler: pick a node from
the live resource view, ask that node's agent to lease a worker and
instantiate the actor, publish lifecycle events on the actor channel.
"""

from __future__ import annotations

import argparse
import asyncio
import bisect
import json
import logging
import os
import struct
import time
from typing import Any, Dict, List, Optional

from . import clocks, diagnosis, loopmon, protocol, rpc
from . import scheduling_policy as policy
from .config import get_config

logger = logging.getLogger("ray_tpu.gcs")

_JLEN = struct.Struct("<I")

# KV namespaces excluded from the journal: high-churn ephemeral rendezvous
# state that is worthless after a restart.
_EPHEMERAL_NS = {"collective"}


class Journal:
    """Length-prefixed msgpack append log of GCS table mutations — the
    single-host stand-in for the reference's Redis-backed store_client
    (reference: gcs/store_client/redis_store_client.h; replay on restart
    per gcs_init_data.cc)."""

    def __init__(self, path: str):
        self.path = path
        self._f = open(path, "ab")
        try:
            self.size = os.path.getsize(path)
        except OSError:
            self.size = 0

    def append(self, kind: str, payload) -> None:
        data = rpc._pack([kind, payload])
        self._f.write(_JLEN.pack(len(data)) + data)
        self._f.flush()
        self.size += 4 + len(data)

    def sync(self) -> None:
        self._f.flush()
        os.fsync(self._f.fileno())

    def close(self) -> None:
        self._f.close()

    @staticmethod
    def read(path: str):
        out = []
        try:
            with open(path, "rb") as f:
                while True:
                    hdr = f.read(4)
                    if len(hdr) < 4:
                        break
                    (n,) = _JLEN.unpack(hdr)
                    body = f.read(n)
                    if len(body) < n:
                        break    # torn tail write from a crash; ignore
                    out.append(rpc._unpack(body))
        except FileNotFoundError:
            pass
        return out


class JournalTailer:
    """Follow-mode reader of a live (possibly compacting) journal — the
    warm standby's replication stream.  Shared-path equivalent of a
    `journal_tail` streaming RPC: the primary's append+flush discipline
    makes every complete record visible to a same-host reader, and the
    length-prefix framing makes a half-flushed tail detectable (we
    simply retry it next poll, the same torn-tail tolerance
    Journal.read has).

    Compaction safety: the primary compacts by writing snapshot+suffix
    to a NEW file and atomically replacing the journal path.  A tailer
    mid-tail detects the replacement by inode change (or the file
    shrinking under its offset), reopens, and reports reset=True so the
    caller rebuilds its replica from the new file's start."""

    def __init__(self, path: str):
        self.path = path
        self._f = None
        self._ino = None
        self.offset = 0

    def _open(self) -> bool:
        try:
            f = open(self.path, "rb")
        except FileNotFoundError:
            return False
        if self._f is not None:
            self._f.close()
        self._f = f
        self._ino = os.fstat(f.fileno()).st_ino
        self.offset = 0
        return True

    def lag_bytes(self) -> int:
        """Bytes the primary has journaled that we have not yet applied."""
        try:
            return max(0, os.path.getsize(self.path) - self.offset)
        except OSError:
            return 0

    def poll(self):
        """-> (records, reset).  `records` are the complete records
        appended since the last poll; reset=True means the journal was
        replaced (compaction) and `records` restart from the NEW file's
        beginning — the caller must drop its replica tables first."""
        reset = False
        try:
            st = os.stat(self.path)
        except FileNotFoundError:
            return [], False
        if self._f is None:
            if not self._open():
                return [], False
        elif st.st_ino != self._ino or st.st_size < self.offset:
            if not self._open():
                return [], False
            reset = True
        out = []
        while True:
            self._f.seek(self.offset)
            hdr = self._f.read(4)
            if len(hdr) < 4:
                break
            (n,) = _JLEN.unpack(hdr)
            body = self._f.read(n)
            if len(body) < n:
                break               # torn tail: complete next poll
            try:
                out.append(rpc._unpack(body))
            except Exception:
                break               # half-flushed record: retry next poll
            self.offset += 4 + n
        return out, reset

    def close(self) -> None:
        if self._f is not None:
            self._f.close()
            self._f = None


class NodeInfo:
    def __init__(self, node_id: bytes, address, resources: Dict[str, float],
                 labels: Dict[str, str], store_path: str, session_dir: str):
        self.node_id = node_id
        self.address = tuple(address)
        self.resources_total = dict(resources)
        self.resources_available = dict(resources)
        self.labels = dict(labels)
        self.store_path = store_path
        self.session_dir = session_dir
        self.alive = True
        # Delta node views: the GCS's _view_epoch value at this node's
        # last SCHEDULING-RELEVANT change (registration, death/drain
        # transitions, resources_available movement, suspicion crossing
        # the trust threshold).  `get_nodes {"since": e}` returns only
        # views newer than e — heartbeats that change nothing no longer
        # make every polling client re-ship the full cluster view.
        self.view_version = 0
        self.last_heartbeat = time.monotonic()
        self.conn: Optional[rpc.Connection] = None  # GCS→agent client
        # {"reason", "deadline"} while the two-phase drain runs (NODE_DRAINING)
        self.draining: Optional[dict] = None
        self.drain_task: Optional[asyncio.Task] = None
        # Why this node was (last) drained — survives into DEAD so
        # observers can distinguish a gray-failure evacuation from a
        # planned preemption after the fact.
        self.drain_reason: Optional[str] = None
        # Gray-failure scoring state: RTT EMA from the GCS's own probe
        # pings, per-reporter peer observations about THIS node
        # (reporter node_id -> (rtt_s, monotonic ts)), and the resulting
        # suspicion score in [0, 1] (EMA'd; see _update_suspicion).
        self.rtt_ema: Optional[float] = None
        self.rtt_ts: float = 0.0        # monotonic of last probe sample
        # Clock alignment (NTP-style, fed by the same probes): smoothed
        # estimate of this node's wall clock MINUS the GCS's, min-RTT
        # filtered (see clocks.OffsetEstimator).  Stamped into node
        # views so timeline rendering can correct cross-node order, and
        # exported as the per-node skew gauge.
        self.clock = clocks.OffsetEstimator()
        # Runtime gauges off the agent's heartbeat (lease queue depth,
        # arena occupancy, ...): the CLI summary / dashboard node table
        # read them from the node view.
        self.runtime: Dict[str, float] = {}
        self.peer_rtts: Dict[bytes, tuple] = {}
        # reporter node_id -> (bytes_per_s, ts): peers' observed chunk
        # transfer rates FROM this node — the only signal that catches a
        # bandwidth-degraded (throttled/half-duplex-sick) link whose
        # small-frame ping RTT still looks healthy.
        self.peer_rates: Dict[bytes, tuple] = {}
        self.suspicion = 0.0
        self.suspect_since: Optional[float] = None
        # Data-plane transfer counters from the agent's heartbeat
        # (bytes_served / bytes_pulled): `ray_tpu list nodes` and the
        # dashboard's transfer column read them off the node view.
        # bulk_rate (B/s since the previous heartbeat) additionally
        # guards the gray auto-drain: a node mid-broadcast is BUSY, not
        # gray — its probe RTT inflates for exactly the duration of the
        # transfer (see _maybe_gray_drain).
        self.transfer: Dict[str, int] = {}
        self.bulk_rate: float = 0.0
        self._transfer_prev: int = 0
        self._transfer_prev_ts: float = 0.0
        # The agent's inbound connection (the one that called
        # register_node): its close is an immediate death signal for
        # cleanly crashed agents (see GcsServer._on_client_close).
        self.client_conn: Optional[rpc.Connection] = None

    @property
    def schedulable(self) -> bool:
        """May receive NEW work: alive and not draining."""
        return self.alive and self.draining is None

    def view(self) -> dict:
        return {
            "node_id": self.node_id,
            "address": list(self.address),
            "resources_total": self.resources_total,
            "resources_available": self.resources_available,
            "labels": self.labels,
            "store_path": self.store_path,
            # Attaching drivers adopt the node's session dir — it is
            # where resolve_gcs_address() finds the CURRENT advertised
            # GCS address, so a driver that joined pre-failover can
            # re-home instead of dialing the dead primary forever.
            "session_dir": self.session_dir,
            "alive": self.alive,
            "state": (protocol.NODE_DEAD if not self.alive
                      else protocol.NODE_DRAINING if self.draining
                      else protocol.NODE_ALIVE),
            "draining": ({"reason": self.draining["reason"]}
                         if self.alive and self.draining else None),
            "drain_reason": self.drain_reason,
            # Gray-failure observability: suspicion in [0,1] and the
            # last probe RTT EMA — surfaced in `ray_tpu list nodes`,
            # the dashboard node table, and consumed by every
            # placement path's prefer_trusted filter.
            "suspicion": round(self.suspicion, 3),
            # Authoritative deprioritization threshold, carried with the
            # score so consumers (dashboard) never hardcode a drifting
            # copy of scheduling_policy.SUSPECT_THRESHOLD.
            "suspect_threshold": policy.SUSPECT_THRESHOLD,
            "rtt_ms": (None if self.rtt_ema is None
                       else round(self.rtt_ema * 1000.0, 2)),
            "transfer": self.transfer,
            # Clock alignment: this node's wall clock minus the GCS's
            # (seconds; None until the first successful timestamped
            # probe), plus the asymmetry error bound — consumers
            # comparing cross-node stamps tighter than the bound are
            # reading noise.
            "clock_offset_s": (None if self.clock.offset is None
                               else round(self.clock.offset, 6)),
            "clock_err_bound_s": (
                None if self.clock.error_bound() is None
                else round(self.clock.error_bound(), 6)),
            "runtime": self.runtime,
        }


class ActorInfo:
    def __init__(self, actor_id: bytes, spec: dict):
        self.actor_id = actor_id
        self.spec = spec                     # creation spec (class key, args..)
        self.name = spec.get("name") or None
        self.state = protocol.ACTOR_PENDING
        self.address = None                  # worker RPC address when ALIVE
        self.node_id: Optional[bytes] = None
        self.restarts = 0
        self.max_restarts = spec.get("max_restarts", 0)
        self.death_cause: Optional[str] = None

    def view(self) -> dict:
        return {
            "actor_id": self.actor_id,
            "name": self.name,
            "state": self.state,
            "address": self.address,
            "node_id": self.node_id,
            "restarts": self.restarts,
            "max_restarts": self.max_restarts,
            "death_cause": self.death_cause,
            "class_name": self.spec.get("class_name", ""),
        }


def _h_ping(conn, p):
    # Liveness ping; served shard-local under daemon_io_shards.
    return "pong"


class GcsServer:
    def __init__(self, host: str = "127.0.0.1", port: int = 0,
                 journal_path: Optional[str] = None,
                 ha_dir: Optional[str] = None):
        self.host = host
        self.port = port
        self.journal_path = journal_path
        self.journal: Optional[Journal] = None
        # High availability (docs/control_plane.md §8): `ha_dir` is the
        # shared directory holding the advertised-address file and the
        # primary lease; None (the default, and every in-process test's
        # default) disables the lease machinery entirely.  The cluster
        # epoch is the fencing token: journaled, bumped exactly once per
        # failover by the promoted standby, stamped into registration
        # and heartbeat replies (and by agents into lease grants).
        self.ha_dir = ha_dir
        self.epoch = 1
        self._journal_epoch = 1     # epoch as last journaled/replayed
        self._replayed = False      # standby pre-replays before start()
        self._fenced = False
        self.fenced_event = asyncio.Event()
        self._failover_count = 0
        self._lease_task: Optional[asyncio.Task] = None
        self._last_snapshot_size = 0
        self.kv: Dict[str, Dict[bytes, bytes]] = {}
        self.nodes: Dict[bytes, NodeInfo] = {}
        self.actors: Dict[bytes, ActorInfo] = {}
        self.named_actors: Dict[str, bytes] = {}
        # Kills that arrived before the (background) registration did:
        # register_actor consumes these and buries the actor immediately.
        # Bounded: actor_id -> arrival time, pruned by TTL on insert.
        self._pending_kills: Dict[bytes, float] = {}
        self.jobs: Dict[bytes, dict] = {}
        self.placement_groups: Dict[bytes, dict] = {}
        self._pg_rr: Dict[bytes, int] = {}   # any-bundle rotation counters
        self._job_counter = 0
        self._subscribers: Dict[str, List[rpc.Connection]] = {}
        # Task-event sink (reference: gcs_task_manager.cc — bounded ring).
        from collections import deque as _deque
        from .config import get_config as _gc
        self.task_events: _deque = _deque(maxlen=_gc().gcs_task_events_max)
        # Opaque pre-packed event batches (count, blob): workers pack the
        # batch once, we store it without decoding (queries expand
        # lazily).  _te_blob_total tracks the event count for eviction.
        self._te_blobs: _deque = _deque()
        self._te_blob_total = 0
        self._te_blob_max = _gc().gcs_task_events_max
        # No silent caps: every event this sink evicts (ring overflow,
        # blob-budget eviction, undecodable blob) is counted, and
        # reporters' own buffer drops (the worker-side 10k deque)
        # accumulate per reporter — queries and /metrics surface the
        # totals so a truncated view is never presented as complete.
        self.task_events_dropped = 0
        self._reporter_drops: Dict[bytes, int] = {}
        # (name, labels_tuple) -> {"type", "value"/"sum"/"buckets", ...}
        self.metrics: Dict[tuple, dict] = {}
        self._metrics_reports = 0   # report count; drives reporter GC
        # Resource demand reported by core workers whose lease requests
        # came back infeasible (reference: autoscaler.proto resource
        # demand in GcsAutoscalerStateManager).  reporter -> shapes+ts.
        self.demand: Dict[bytes, dict] = {}
        # Created/removed wakeups for PG waiters (not journaled).
        self._pg_events: Dict[bytes, asyncio.Event] = {}
        # Bumped on every node registration; pending-actor scheduling resets
        # its deadline when this moves (new capacity may fit the actor).
        self._node_epoch = 0
        # Delta node views (see NodeInfo.view_version): monotonically
        # bumped on every scheduling-relevant view change.
        self._view_epoch = 0
        # alive-address -> NodeInfo index for heartbeat peer-stats
        # folding: rebuilt only when membership changes — building it
        # per heartbeat was O(N) x N heartbeats/tick = O(N^2) per tick
        # at fleet size.
        self._addr_index: Optional[Dict[str, NodeInfo]] = None
        self._closing = False
        # Daemon I/O sharding (config daemon_io_shards): accepted
        # connections live on shard event-loop threads; only `ping`
        # (pure I/O, no table access) is served shard-local — every
        # other handler mutates the tables and hops to this loop.
        self._io_shards = rpc.make_io_shard_pool("gcs")
        self._server = rpc.RpcServer(
            self._handlers(), name="gcs",
            on_client_close=self._on_client_close,
            io_shards=self._io_shards,
            # Same callable as the handlers dict: sharded and
            # single-loop mode must answer ping identically.
            shard_handlers={"ping": _h_ping})
        self._health_task: Optional[asyncio.Task] = None
        # Diagnosis plane: anomaly sink (detector firings reported by
        # every daemon/worker + the GCS's own watchdog), GCS-origin
        # anomaly counts for _self_metrics, and the black-box capture
        # manager (armed in start(); rate-limited per kind).
        self._anomalies: _deque = _deque(maxlen=256)
        self._anomaly_counts: Dict[str, int] = {}
        self._capture_mgr = None
        self._watchdog = None
        self._loop: Optional[asyncio.AbstractEventLoop] = None

    def _handlers(self):
        return {
            "kv_put": self.h_kv_put, "kv_get": self.h_kv_get,
            "kv_del": self.h_kv_del, "kv_keys": self.h_kv_keys,
            "kv_exists": self.h_kv_exists,
            "register_node": self.h_register_node,
            "get_nodes": self.h_get_nodes,
            "report_resources": self.h_report_resources,
            "drain_node": self.h_drain_node,
            "next_job_id": self.h_next_job_id,
            "register_job": self.h_register_job,
            "get_jobs": self.h_get_jobs,
            "register_actor": self.h_register_actor,
            "get_actor": self.h_get_actor,
            "list_actors": self.h_list_actors,
            "kill_actor": self.h_kill_actor,
            "actor_failed": self.h_actor_failed,
            "subscribe": self.h_subscribe,
            "publish": self.h_publish,
            "create_placement_group": self.h_create_placement_group,
            "remove_placement_group": self.h_remove_placement_group,
            "get_placement_group": self.h_get_placement_group,
            "list_placement_groups": self.h_list_placement_groups,
            "task_events": self.h_task_events,
            "get_task_events": self.h_get_task_events,
            "report_metrics": self.h_report_metrics,
            "get_metrics": self.h_get_metrics,
            "ping": _h_ping,
            "get_cluster_info": self.h_get_cluster_info,
            "report_demand": self.h_report_demand,
            "get_demand": self.h_get_demand,
            # Diagnosis plane: cluster-wide live introspection + anomaly
            # sink + black-box capture (docs/observability.md §5).
            "cluster_profile": self.h_cluster_profile,
            "report_anomaly": self.h_report_anomaly,
            "get_anomalies": self.h_get_anomalies,
            "capture": self.h_capture,
            # The GCS's OWN stacks/cpu_profile, same handler names every
            # other process serves (diagnosis.profile_handlers).
            **diagnosis.profile_handlers("gcs"),
        }

    def _mark_view_dirty(self, node: NodeInfo) -> None:
        """Record a scheduling-relevant change to `node`'s view so
        delta-polling clients (`get_nodes {"since": e}`) pick it up."""
        self._view_epoch += 1
        node.view_version = self._view_epoch

    def _alive_by_addr(self) -> Dict[str, NodeInfo]:
        idx = self._addr_index
        if idx is None:
            idx = self._addr_index = {
                f"{n.address[0]}:{n.address[1]}": n
                for n in self.nodes.values() if n.alive}
        return idx

    # ----------------------------------------------------------- telemetry --
    async def h_task_events(self, conn, p):
        # Reporter-side drop accounting: senders stamp their cumulative
        # buffer-overflow count ("dropped") and an id ("src"); the sink
        # keeps the latest per reporter so totals don't double-count.
        # Bounded against reporter churn by evicting the longest-silent
        # reporter (move-to-end on re-report): a bounded undercount of
        # long-dead reporters' drops, never unbounded memory.
        if p.get("src") is not None and p.get("dropped") is not None:
            d = self._reporter_drops
            d.pop(p["src"], None)
            d[p["src"]] = int(p["dropped"])
            while len(d) > 8192:
                d.pop(next(iter(d)))
        blob = p.get("blob")
        if blob is not None:
            # Opaque batch: one bin decode on the RPC frame instead of
            # thousands of per-event map decodes on the GCS loop.
            n = p.get("n", 0)
            self._te_blobs.append((n, blob))
            self._te_blob_total += n
            # Bound COMBINED retention (expanded ring + queued blobs) to
            # gcs_task_events_max — each side capped independently would
            # allow ~2x the documented limit after a query expands blobs.
            budget = max(self._te_blob_max - len(self.task_events), 0)
            while (self._te_blob_total > budget
                   and len(self._te_blobs) > 1):
                dn, _ = self._te_blobs.popleft()
                self._te_blob_total -= dn
                self.task_events_dropped += dn
            return True
        events = p["events"]
        overflow = (len(self.task_events) + len(events)
                    - (self.task_events.maxlen or 0))
        if overflow > 0:
            # deque(maxlen) evicts silently on extend; count it.
            self.task_events_dropped += min(overflow, len(events))
        self.task_events.extend(events)
        return True

    def _expanded_task_events(self):
        if self._te_blobs:
            # Expand accumulated blobs into the row ring (query-time cost;
            # queries are dashboard/state-API rate, not hot-path rate).
            blobs, self._te_blobs = list(self._te_blobs), type(self._te_blobs)()
            self._te_blob_total = 0
            for _n, blob in blobs:
                try:
                    rows = rpc._unpack(blob)
                except Exception:
                    # One corrupt blob (sender died mid-notify) must not
                    # fail the query or discard the healthy blobs.
                    logger.warning("dropping undecodable task-event blob "
                                   "(%d events)", _n)
                    self.task_events_dropped += _n
                    continue
                overflow = (len(self.task_events) + len(rows)
                            - (self.task_events.maxlen or 0))
                if overflow > 0:
                    self.task_events_dropped += min(overflow, len(rows))
                self.task_events.extend(rows)
        return self.task_events

    def _events_dropped_total(self) -> int:
        """Sink-side evictions plus every reporter's own buffer drops."""
        return self.task_events_dropped + sum(
            self._reporter_drops.values())

    async def h_get_task_events(self, conn, p):
        out = list(self._expanded_task_events())
        total = len(out)
        if p.get("job_id"):
            out = [e for e in out if e.get("job_id") == p["job_id"]]
        if p.get("task_id"):
            out = [e for e in out if e.get("task_id") == p["task_id"]]
        limit = p.get("limit", 10_000)
        clipped = max(0, len(out) - limit)
        out = out[-limit:]
        if p.get("with_meta"):
            # No silent caps: callers that ask get told how much of the
            # stream they are NOT seeing — events evicted before they
            # could be retained (sink ring + reporter buffers) and rows
            # clipped by this query's own limit.
            return {"events": out,
                    "dropped": self._events_dropped_total(),
                    "clipped": clipped,
                    "total_retained": total}
        return out

    async def h_report_metrics(self, conn, p):
        """Merge a per-process metric snapshot (reference: per-node
        metrics agents pushing to the head aggregator). Counters arrive as
        monotonic per-process totals keyed by worker, so aggregation sums
        the latest value per worker."""
        wid = p["worker_id"]
        # Recency is judged by GCS RECEIPT time (monotonic), never the
        # reporter's own wall stamp: a skewed host — the very condition
        # the clock-alignment feature exists for — must not have its
        # live metrics judged stale (or its dead ones judged fresh).
        recv = time.monotonic()
        for m in p["metrics"]:
            key = (m["name"], tuple(sorted(m.get("labels", {}).items())))
            entry = self.metrics.setdefault(key, {
                "name": m["name"], "labels": m.get("labels", {}),
                "type": m["type"], "help": m.get("help", ""),
                "per_worker": {}})
            entry["type"] = m["type"]
            entry["per_worker"][wid] = (m["value"], recv)
        # Periodic reporter eviction: worker processes churn (every dead
        # worker leaves its final snapshot behind), and with the unified
        # export EVERY process reports — without a sweep the per_worker
        # maps grow for the cluster's lifetime.  Stale gauge reporters
        # stop winning most-recent anyway; dropping their counter
        # contribution after 15min idle trades a bounded undercount for
        # bounded memory (the reference evicts dead-worker views the
        # same way).
        self._metrics_reports += 1
        if self._metrics_reports % 512 == 0:
            horizon = time.monotonic() - 900.0
            for entry in self.metrics.values():
                pw = entry["per_worker"]
                for w in [w for w, (_v, ts) in pw.items()
                          if ts < horizon]:
                    del pw[w]
            self.metrics = {k: e for k, e in self.metrics.items()
                            if e["per_worker"]}
        return True

    async def h_get_metrics(self, conn, p):
        out = []
        for entry in self.metrics.values():
            vals = list(entry["per_worker"].values())   # [(value, ts)]
            if entry["type"] == "gauge":
                # Most recently RECEIVED value wins (receipt monotonic,
                # skew-immune), not dict order.
                value = max(vals, key=lambda v: v[1])[0] if vals else 0.0
            elif entry["type"] == "histogram":
                value = {"count": sum(v[0]["count"] for v in vals),
                         "sum": sum(v[0]["sum"] for v in vals)}
                sets = [v[0] for v in vals
                        if v[0].get("buckets") and v[0].get("boundaries")]
                if sets and all(s["boundaries"] == sets[0]["boundaries"]
                                for s in sets):
                    value["boundaries"] = sets[0]["boundaries"]
                    value["buckets"] = [
                        sum(s["buckets"][i] for s in sets)
                        for i in range(len(sets[0]["buckets"]))]
            else:
                value = sum(v[0] for v in vals)
            out.append({"name": entry["name"], "labels": entry["labels"],
                        "type": entry["type"], "help": entry["help"],
                        "value": value})
        out.extend(self._self_metrics())
        return out

    def _self_metrics(self) -> List[dict]:
        """The GCS's own contribution to the unified export: per-node
        health/clock gauges derived from its tables, and the task-event
        sink's drop counter (the no-silent-caps satellite)."""
        out: List[dict] = [{
            "name": "ray_tpu_gcs_task_events_dropped_total",
            "labels": {}, "type": "counter",
            "help": "task events evicted by the GCS sink or dropped in "
                    "reporter buffers before reaching it",
            "value": float(self._events_dropped_total())}]
        # GCS HA: the fencing epoch and failover count, plus — when a
        # warm standby is tailing our journal — its replication lag
        # (read from the progress file the standby refreshes each poll).
        out.append({
            "name": "ray_tpu_gcs_epoch", "labels": {}, "type": "gauge",
            "help": "cluster epoch (fencing token): bumped exactly once "
                    "per GCS failover, stamped into every grant",
            "value": float(self.epoch)})
        out.append({
            "name": "ray_tpu_gcs_failover_total", "labels": {},
            "type": "counter",
            "help": "GCS failovers this instance participated in "
                    "(takeovers it performed or fencings it suffered)",
            "value": float(self._failover_count)})
        if self.ha_dir:
            sb = self._read_json(
                os.path.join(self.ha_dir, protocol.GCS_STANDBY_FILE))
            if sb and sb.get("ts"):
                out.append({
                    "name": "ray_tpu_gcs_standby_lag_bytes",
                    "labels": {}, "type": "gauge",
                    "help": "journal bytes the warm standby has not yet "
                            "applied to its hot replica tables",
                    "value": float(sb.get("lag_bytes") or 0)})
                out.append({
                    "name": "ray_tpu_gcs_standby_age_seconds",
                    "labels": {}, "type": "gauge",
                    "help": "seconds since the warm standby last "
                            "reported tail progress; grows without "
                            "bound when no standby is running",
                    "value": max(0.0, time.time() - float(sb["ts"]))})
        # Per-loop busy fractions (loopmon): single-core saturation of
        # the GCS main loop — or of any I/O shard — is a gauge, not an
        # inference from host CPU.  Stale entries stay visible with
        # their probe age: a wedged loop alarms instead of vanishing.
        for label, info in loopmon.snapshot_full().items():
            out.append({
                "name": "ray_tpu_daemon_loop_busy_ratio",
                "labels": {"daemon": "gcs", "loop": label},
                "type": "gauge",
                "help": "CPU-seconds per wall-second burned by the "
                        "thread running this event loop (1.0 = one "
                        "core saturated)",
                "value": info["ratio"]})
            out.append({
                "name": "ray_tpu_daemon_loop_stale_seconds",
                "labels": {"daemon": "gcs", "loop": label},
                "type": "gauge",
                "help": "age of this loop's last busy probe tick; "
                        "grows past the ~0.5s period when the loop "
                        "stops servicing callbacks",
                "value": info["stale_s"]})
        # GCS-origin detector firings (its own watchdog); every other
        # process exports its ray_tpu_anomaly_total through its own
        # registry snapshot, so totals never double-count.
        for kind, count in self._anomaly_counts.items():
            out.append({
                "name": "ray_tpu_anomaly_total",
                "labels": {"daemon": "gcs", "kind": kind, "node_id": ""},
                "type": "counter",
                "help": "hung-work detector firings by kind",
                "value": float(count)})
        st = self._server.shard_stats()
        if st["shards"]:
            out.append({
                "name": "ray_tpu_daemon_io_shard_hops_total",
                "labels": {"daemon": "gcs"}, "type": "counter",
                "help": "batched shard->main-loop crossings",
                "value": float(st["hops"])})
            out.append({
                "name": "ray_tpu_daemon_io_shard_requests_total",
                "labels": {"daemon": "gcs"}, "type": "counter",
                "help": "requests forwarded to the main loop by I/O "
                        "shards (requests/hops = wave batching factor)",
                "value": float(st["submitted"])})
        for node in self.nodes.values():
            if not node.alive:
                continue
            lab = {"node_id": node.node_id.hex()}
            if node.clock.offset is not None:
                out.append({
                    "name": "ray_tpu_node_clock_offset_seconds",
                    "labels": lab, "type": "gauge",
                    "help": "estimated node wall clock minus GCS wall "
                            "clock (NTP-style, min-RTT filtered)",
                    "value": node.clock.offset})
            if node.rtt_ema is not None:
                out.append({
                    "name": "ray_tpu_node_probe_rtt_seconds",
                    "labels": lab, "type": "gauge",
                    "help": "GCS health-probe RTT EMA",
                    "value": node.rtt_ema})
            out.append({
                "name": "ray_tpu_node_suspicion",
                "labels": lab, "type": "gauge",
                "help": "gray-failure suspicion score in [0, 1]",
                "value": node.suspicion})
        return out

    # ------------------------------------------------------- diagnosis --
    # (docs/observability.md §5: cluster-wide live introspection, the
    # anomaly sink, and anomaly-triggered black-box capture bundles.)

    async def h_cluster_profile(self, conn, p):
        """Cluster-wide stacks/CPU profile: fans out through every
        agent's node_profile (agent + its workers, concurrently) plus
        the GCS's own process, and stamps each node's clock offset so
        renderers can align cross-node samples.  Selectors: node_id
        (hex prefix), pid, job_id (hex prefix -> the nodes that job's
        tasks touched)."""
        kind = p.get("kind", "stacks")
        if kind not in ("stacks", "cpu_profile"):
            raise rpc.RpcError(f"unknown profile kind {kind!r}")
        return await self._cluster_profile(kind, p)

    async def _cluster_profile(self, kind: str, p: dict) -> dict:
        duration = float(p.get("duration_s", 2.0))
        interval = p.get("interval_s", 0.01)
        sel_node = p.get("node_id")
        sel_pid = p.get("pid")
        node_filter = None
        if p.get("job_id"):
            # Job selection is node-granular: workers are pooled across
            # jobs, so profile every node the job's task events touched.
            node_filter = {
                e["node_id"].hex()
                for e in self._expanded_task_events()
                if e.get("node_id")
                and e.get("job_id")
                and e["job_id"].hex().startswith(p["job_id"])}
        targets = []
        for node in self.nodes.values():
            if not node.alive or node.conn is None or node.conn.closed:
                continue
            hexid = node.node_id.hex()
            if sel_node and not hexid.startswith(str(sel_node)):
                continue
            if node_filter is not None and hexid not in node_filter:
                continue
            targets.append(node)
        payload = {"kind": kind, "duration_s": duration,
                   "interval_s": interval}
        if sel_pid is not None:
            payload["pid"] = int(sel_pid)

        async def _gcs_self():
            try:
                if kind == "stacks":
                    r = diagnosis.dump_stacks()
                else:
                    r = await diagnosis.cpu_profile(duration, interval)
                r["daemon"] = "gcs"
                return r
            except Exception as e:  # noqa: BLE001 — typed, not fatal
                return {"error": str(e)}

        async def _one_node(node):
            try:
                return node, await node.conn.call(
                    "node_profile", payload, timeout=duration + 30)
            except Exception as e:  # noqa: BLE001 — per-node error entry
                return node, {"error": str(e)}

        # The GCS isn't a node: include it unless a selector narrows
        # the sweep.  Everything samples CONCURRENTLY — one coherent
        # cluster-wide time window.
        include_gcs = not (sel_node or sel_pid or node_filter is not None)
        coros = [_one_node(n) for n in targets]
        if include_gcs:
            gcs_task = asyncio.ensure_future(_gcs_self())
        results = await asyncio.gather(*coros)
        out = {"kind": kind, "duration_s": duration,
               "ts": clocks.wall(), "nodes": {}}
        if include_gcs:
            out["gcs"] = await gcs_task
        for node, res in results:
            res = dict(res) if isinstance(res, dict) else {"error": str(res)}
            res["clock_offset_s"] = node.clock.offset
            res["clock_err_bound_s"] = node.clock.error_bound()
            out["nodes"][node.node_id.hex()] = res
        return out

    async def h_report_anomaly(self, conn, p):
        self._ingest_anomaly(dict(p))
        return True

    async def h_get_anomalies(self, conn, p):
        out = list(self._anomalies)
        if p.get("kind"):
            out = [a for a in out if a.get("kind") == p["kind"]]
        return out[-int(p.get("limit", 256)):]

    async def h_capture(self, conn, p):
        """Manual black-box capture (`ray_tpu capture`): same bundle as
        an anomaly trigger, force bypasses the per-kind rate limit."""
        kind = p.get("kind", "manual")
        path = await self._capture_bundle(
            kind, {"kind": kind, "daemon": "manual", "trigger": "rpc"},
            force=bool(p.get("force", True)))
        return {"captured": path is not None, "path": path,
                "suppressed": dict(self._capture_mgr.suppressed)
                if self._capture_mgr else {}}

    def _ingest_anomaly(self, info: dict) -> None:
        """Anomaly sink: every detector firing cluster-wide lands here
        (workers/agents via report_anomaly notifies, the GCS's own
        watchdog via the thread-safe callback).  Counted, published,
        overlaid on the timeline, and — rate-limited — captured."""
        info.setdefault("ts", time.time())
        self._anomalies.append(info)
        kind = info.get("kind", "unknown")
        if info.get("daemon") == "gcs":
            # Reporters export their own ray_tpu_anomaly_total through
            # their registry snapshots; the GCS has no registry export,
            # so its firings are counted here (see _self_metrics) —
            # counting reported ones too would double them.
            self._anomaly_counts[kind] = \
                self._anomaly_counts.get(kind, 0) + 1
            # ... and its recorder ring is never drained, so feed the
            # timeline sink directly (reported anomalies arrive as
            # recorder instants in the normal telemetry drains).
            wall = clocks.wall()
            self.task_events.append({
                "task_id": b"", "name": f"anomaly:{kind}",
                "event": "SPAN", "cat": "anomaly", "ts": wall,
                "start_us": int(wall * 1e6), "dur_us": 0,
                "worker_id": b"", "node_id": b"", "job_id": b"",
                "args": {k: v for k, v in info.items()
                         if k not in ("stack",) and
                         isinstance(v, (str, int, float, bool))}})
        self._publish("anomaly", {
            k: v for k, v in info.items() if k != "stack"})
        if get_config().anomaly_capture_enabled \
                and self._capture_mgr is not None:
            rpc.spawn(self._capture_bundle(kind, info))

    def _anomaly_from_thread(self, info: dict) -> None:
        loop = self._loop
        if loop is None:
            return
        try:
            loop.call_soon_threadsafe(self._ingest_anomaly, info)
        except RuntimeError:
            pass

    async def _capture_bundle(self, kind: str, info: dict,
                              force: bool = False) -> Optional[str]:
        """One `diag-<kind>-<ts>/` bundle: stacks + short CPU profile of
        the implicated nodes (all nodes if the anomaly names none),
        merged metrics, node views (suspicion/clock state included),
        the task-event/recorder ring, recent anomalies, and a manifest.
        Returns the bundle path, or None when rate-limited."""
        mgr = self._capture_mgr
        if mgr is None or not mgr.should_capture(kind, force=force):
            return None
        cfg = get_config()
        sel = {}
        if info.get("node_id"):
            sel["node_id"] = info["node_id"]
        try:
            stacks = await self._cluster_profile("stacks", dict(sel))
            prof = await self._cluster_profile(
                "cpu_profile",
                {**sel, "duration_s": cfg.diagnosis_capture_profile_s})
        except Exception as e:  # noqa: BLE001 — partial bundle > none
            stacks = prof = {"error": str(e)}
        parts = {
            "stacks": stacks,
            "cpu_profile": prof,
            "metrics": await self.h_get_metrics(None, {}),
            "nodes": [n.view() for n in self.nodes.values()],
            "recorder": list(self._expanded_task_events())[-2000:],
            "anomalies": list(self._anomalies),
        }
        try:
            path = mgr.write_bundle(kind, parts, manifest_extra=info)
        except OSError as e:
            logger.warning("diagnosis bundle write failed: %s", e)
            return None
        logger.warning("diagnosis: captured black-box bundle %s "
                       "(anomaly kind=%s)", path, kind)
        self._publish("anomaly", {"kind": kind, "capture_path": path})
        return path

    async def start(self):
        if self.journal_path and not self._replayed:
            self._replay(Journal.read(self.journal_path))
            self._replayed = True
        if self.journal_path:
            self.journal = Journal(self.journal_path)
            if self.epoch != self._journal_epoch:
                # Promoted standby: the epoch bump hits the journal
                # BEFORE the first request is served — a crash right
                # after this line still replays into the new epoch.
                self.journal.append("epoch", self.epoch)
                self.journal.sync()
                self._journal_epoch = self.epoch
        if self.ha_dir:
            os.makedirs(self.ha_dir, exist_ok=True)
            self._claim_lease()
        addr = await self._server.start_tcp(self.host, self.port)
        self.address = addr
        if self.ha_dir:
            # Advertise AFTER the socket listens: a client that re-reads
            # the address file must never be pointed at a closed port.
            self._write_json_atomic(
                os.path.join(self.ha_dir, protocol.GCS_ADDRESS_FILE),
                {"address": list(addr),
                 protocol.EPOCH_KEY: self.epoch,
                 "pid": os.getpid()})
            self._lease_task = asyncio.ensure_future(self._lease_loop())
        # Busy-fraction probe for the main loop (shards install their
        # own): saturation of the state-mutating loop becomes a gauge.
        loopmon.install("main")
        cfg = get_config()
        if cfg.diagnosis_enabled:
            self._loop = asyncio.get_running_loop()
            if cfg.anomaly_capture_enabled:
                root = cfg.diagnosis_capture_dir
                if not root:
                    import tempfile
                    base = (os.path.dirname(self.journal_path)
                            if self.journal_path else
                            os.path.join(tempfile.gettempdir(), "ray_tpu"))
                    root = os.path.join(base, "diagnosis")
                try:
                    os.makedirs(root, exist_ok=True)
                    self._capture_mgr = diagnosis.CaptureManager(
                        root,
                        min_interval_s=cfg.diagnosis_capture_min_interval_s,
                        max_bundles=cfg.diagnosis_capture_max_bundles)
                except OSError as e:
                    logger.warning("diagnosis capture disabled: %s", e)
            self._watchdog = diagnosis.Watchdog(
                daemon_name="gcs",
                detectors=[diagnosis.loop_wedge_detector()],
                notify=self._anomaly_from_thread,
                poll_s=cfg.diagnosis_poll_ms / 1000.0)
            self._watchdog.start()
        self._health_task = asyncio.ensure_future(self._health_loop())
        # Re-kick interrupted placement/scheduling loops (their coroutines
        # died with the previous process; agents re-register shortly).
        for pg in self.placement_groups.values():
            if pg["state"] == "PENDING":
                rpc.spawn(self._place_pg(pg))
        for actor in self.actors.values():
            if actor.state in (protocol.ACTOR_PENDING,
                               protocol.ACTOR_RESTARTING):
                rpc.spawn(self._reschedule_replayed(actor))
        logger.info("GCS listening on %s%s", addr,
                    " (journal replayed)" if self.journal else "")
        return addr

    def _log(self, kind: str, payload) -> None:
        if self.journal is not None:
            self.journal.append(kind, payload)
            limit = get_config().journal_snapshot_every_bytes
            # Compact at the threshold, but only once the log has also
            # doubled past the LAST snapshot: when live state alone
            # exceeds the threshold, an absolute trigger would rewrite
            # the full snapshot on every append.
            if limit and self.journal.size > max(
                    limit, 2 * self._last_snapshot_size):
                try:
                    self._compact_journal()
                except OSError as e:
                    logger.warning("journal compaction failed: %s", e)

    def _compact_journal(self) -> None:
        """Snapshot + truncate: serialize the journaled tables as the
        minimal record sequence into a fresh file and atomically replace
        the journal — replay afterwards is snapshot + suffix.  The
        replace is what a mid-tail standby detects by inode change."""
        old_size = self.journal.size
        tmp = self.journal_path + ".compact"
        try:
            os.unlink(tmp)          # a crashed attempt must not append
        except FileNotFoundError:
            pass
        snap = Journal(tmp)
        snap.append("snapshot", self._snapshot_records())
        snap.sync()
        snap.close()
        self.journal.close()
        os.replace(tmp, self.journal_path)
        self.journal = Journal(self.journal_path)
        self._last_snapshot_size = self.journal.size
        logger.info("journal compacted: %d -> %d bytes",
                    old_size, self.journal.size)

    def _snapshot_records(self) -> list:
        """Current journaled state as the record sequence that rebuilds
        it — `_replay` is the single decoder for both live journals and
        snapshots, so the two can never drift apart."""
        recs: list = [["epoch", self.epoch],
                      ["job_counter", self._job_counter]]
        for ns, d in self.kv.items():
            if ns in _EPHEMERAL_NS:
                continue
            for k, v in d.items():
                recs.append(["kv_put", {"ns": ns, "key": k, "value": v}])
        for job in self.jobs.values():
            recs.append(["job", job])
        for node in self.nodes.values():
            recs.append(["node", {
                "node_id": node.node_id, "address": list(node.address),
                "resources": node.resources_total, "labels": node.labels,
                "store_path": node.store_path,
                "session_dir": node.session_dir}])
        for actor in self.actors.values():
            recs.append(["actor_spec", {"actor_id": actor.actor_id,
                                        "spec": actor.spec}])
            recs.append(["actor_view", actor.view()])
        for pg in self.placement_groups.values():
            recs.append(["pg", pg])
        return recs

    def _reset_tables(self) -> None:
        """Drop every journaled table (snapshot replay, standby reset
        after a compaction landed mid-tail)."""
        self.kv = {}
        self.nodes = {}
        self.actors = {}
        self.named_actors = {}
        self.jobs = {}
        self.placement_groups = {}
        self._job_counter = 0
        self._addr_index = None

    def _log_actor(self, actor: ActorInfo, with_spec: bool = False) -> None:
        # Spec is immutable — journaled once at registration; transitions
        # journal only the (small) view.
        if with_spec:
            self._log("actor_spec", {"actor_id": actor.actor_id,
                                     "spec": actor.spec})
        self._log("actor_view", actor.view())

    def _replay(self, records) -> None:
        """Rebuild tables from the journal (reference: gcs_init_data.cc).
        Nodes replay as not-alive — live agents re-register over their
        reconnecting GCS connections within a heartbeat."""
        for kind, p in records:
            if kind == "kv_put":
                self.kv.setdefault(p["ns"], {})[p["key"]] = p["value"]
            elif kind == "kv_del":
                ns = self.kv.get(p["ns"], {})
                if p.get("prefix"):
                    for k in [k for k in ns if k.startswith(p["key"])]:
                        del ns[k]
                else:
                    ns.pop(p["key"], None)
            elif kind == "job_counter":
                self._job_counter = max(self._job_counter, p)
            elif kind == "job":
                self.jobs[p["job_id"]] = p
            elif kind == "node":
                node = NodeInfo(p["node_id"], p["address"], p["resources"],
                                p.get("labels", {}), p.get("store_path", ""),
                                p.get("session_dir", ""))
                node.alive = False
                self.nodes[node.node_id] = node
            elif kind == "actor_spec":
                if p["actor_id"] not in self.actors:
                    self.actors[p["actor_id"]] = ActorInfo(p["actor_id"],
                                                           p["spec"])
            elif kind == "actor_view":
                actor = self.actors.get(p["actor_id"])
                if actor is None:
                    continue    # spec record lost with a torn tail
                v = p
                actor.state = v["state"]
                actor.address = v["address"]
                actor.node_id = v["node_id"]
                actor.restarts = v["restarts"]
                actor.max_restarts = v["max_restarts"]  # kill() zeroes it
                actor.death_cause = v["death_cause"]
                if actor.name:
                    if actor.state != protocol.ACTOR_DEAD:
                        self.named_actors[actor.name] = actor.actor_id
                    elif self.named_actors.get(actor.name) == actor.actor_id:
                        del self.named_actors[actor.name]
            elif kind == "pg":
                self.placement_groups[p["pg_id"]] = p
            elif kind == "pg_del":
                self.placement_groups.pop(p, None)
            elif kind == "epoch":
                self.epoch = max(self.epoch, int(p))
                self._journal_epoch = self.epoch
            elif kind == "snapshot":
                # Compaction record: the tables reset and rebuild from
                # the embedded record sequence (then the journal suffix
                # after this record replays on top as usual).
                self._reset_tables()
                self._replay(p)

    async def _reschedule_replayed(self, actor: ActorInfo):
        ok = await self._schedule_actor(actor)
        if not ok:
            actor.state = protocol.ACTOR_DEAD
            actor.death_cause = ("scheduling failed after GCS restart: "
                                 "no feasible node")
            self._log_actor(actor)

    async def close(self):
        self._closing = True
        if self._health_task:
            self._health_task.cancel()
        if self._lease_task:
            self._lease_task.cancel()
        await self._server.close()
        if self._io_shards is not None:
            # After the server: bridged connection closes need the
            # shard loops alive to run.
            self._io_shards.close()

    # ------------------------------------------------- HA lease / fencing --
    # (docs/control_plane.md §8.)  The primary holds a disk lease under
    # ha_dir, renewed every ttl/3 — but ONLY while it can see fresh
    # heartbeats from a majority of its alive agents.  A primary
    # partitioned from the cluster therefore stops renewing and yields;
    # a standby partitioned from a HEALTHY primary never sees the lease
    # go stale (renewal rides the agents' votes, not the standby's view
    # of the primary), so it cannot steal the cluster: the split-brain
    # guard.  A fenced ex-primary (a higher epoch appears in the lease
    # file while it was frozen) refuses every write and exits.

    @staticmethod
    def _read_json(path: str) -> Optional[dict]:
        try:
            with open(path) as f:
                return json.load(f)
        except (OSError, ValueError):
            return None

    @staticmethod
    def _write_json_atomic(path: str, obj: dict) -> None:
        # pid-suffixed tmp: the promoted standby and a not-yet-fenced
        # ex-primary must never truncate each other's half-written tmp.
        tmp = f"{path}.tmp{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(obj, f)
        os.replace(tmp, path)

    @staticmethod
    def _pid_alive(pid: int) -> bool:
        try:
            os.kill(pid, 0)
        except ProcessLookupError:
            return False
        except PermissionError:
            return True
        return True

    def _lease_path(self) -> str:
        return os.path.join(self.ha_dir, protocol.GCS_LEASE_FILE)

    def _claim_lease(self) -> None:
        """Take (or re-take) the primary lease at startup.  Refuses to
        start against a live holder: a fresh lease owned by a running
        pid means another primary is serving — starting anyway would be
        manufacturing the very split brain the lease exists to prevent."""
        ttl = float(get_config().gcs_lease_ttl_s)
        cur = self._read_json(self._lease_path())
        if cur:
            if int(cur.get("epoch", 0)) > self.epoch:
                raise RuntimeError(
                    f"GCS lease already held at epoch {cur.get('epoch')} "
                    f"> ours {self.epoch}: a newer primary exists")
            pid = int(cur.get("owner_pid") or 0)
            age = time.time() - float(cur.get("renewed", 0.0))
            if pid and pid != os.getpid() and self._pid_alive(pid) \
                    and age <= float(cur.get("ttl_s", ttl)):
                raise RuntimeError(
                    f"GCS lease held by live pid {pid} "
                    f"(age {age:.1f}s <= ttl): refusing to double-serve")
        self._renew_lease(ttl)

    def _renew_lease(self, ttl: float) -> None:
        self._write_json_atomic(self._lease_path(), {
            "epoch": self.epoch,
            "renewed": time.time(),
            "ttl_s": ttl,
            "owner_pid": os.getpid(),
            "address": list(getattr(self, "address",
                                    (self.host, self.port)))})

    def _heartbeat_majority_ok(self, fresh_window: float) -> bool:
        """Lease renewal votes: a majority of ALIVE agents must have
        heartbeated within the freshness window.  No agents (bootstrap,
        benches) trivially passes — there is no cluster to lose."""
        alive = [n for n in self.nodes.values() if n.alive]
        if not alive:
            return True
        now = time.monotonic()
        fresh = sum(1 for n in alive
                    if now - n.last_heartbeat <= fresh_window)
        return fresh * 2 > len(alive)

    async def _lease_loop(self):
        cfg = get_config()
        ttl = float(cfg.gcs_lease_ttl_s)
        fresh_window = float(cfg.gcs_lease_heartbeat_fresh_s) or max(
            2.0, 4.0 * cfg.resource_report_period_ms / 1000.0)
        while not self._closing:
            try:
                cur = self._read_json(self._lease_path())
                if cur and int(cur.get("epoch", 0)) > self.epoch:
                    # We were frozen/partitioned long enough for the
                    # standby to take over: we are history.
                    self._fence(int(cur["epoch"]))
                    return
                if self._heartbeat_majority_ok(fresh_window):
                    self._renew_lease(ttl)
                else:
                    logger.warning(
                        "withholding GCS lease renewal: no fresh "
                        "heartbeat majority (partitioned from agents?)")
            except asyncio.CancelledError:
                raise
            except Exception:
                logger.exception("lease renewal pass failed")
            await asyncio.sleep(ttl / 3.0)

    def _fence(self, successor_epoch: int) -> None:
        """A successor bumped the epoch past ours: refuse every write
        from now on and signal the hosting process to exit (the
        subprocess main watches fenced_event; in-process tests assert on
        it directly)."""
        if self._fenced:
            return
        self._fenced = True
        self._failover_count += 1
        logger.error(
            "GCS FENCED: successor epoch %d > ours %d — refusing all "
            "writes and exiting", successor_epoch, self.epoch)
        self._ingest_anomaly({
            "kind": "gcs_fenced", "daemon": "gcs",
            "epoch": self.epoch, "successor_epoch": successor_epoch})
        self.fenced_event.set()

    def _check_writable(self, p: Optional[dict] = None) -> None:
        """Mutation fencing: a fenced ex-primary accepts no state
        mutation at all, and ANY primary rejects a mutation stamped
        with an epoch older than its own (a grant-holder acting on a
        pre-failover decision)."""
        if self._fenced:
            raise rpc.RpcError(
                f"stale_epoch: this GCS instance is fenced "
                f"(epoch {self.epoch})")
        if p:
            e = p.get(protocol.EPOCH_KEY)
            if e is not None and int(e) and int(e) < self.epoch:
                raise rpc.RpcError(
                    f"stale_epoch: mutation carries epoch {e} < "
                    f"current {self.epoch}")

    # ------------------------------------------------------------------ KV --
    async def h_kv_put(self, conn, p):
        self._check_writable(p)
        ns = self.kv.setdefault(p.get("ns", ""), {})
        key = p["key"]
        if not p.get("overwrite", True) and key in ns:
            return False
        ns[key] = p["value"]
        if p.get("ns", "") not in _EPHEMERAL_NS:
            self._log("kv_put", {"ns": p.get("ns", ""), "key": key,
                                 "value": p["value"]})
        return True

    async def h_kv_get(self, conn, p):
        return self.kv.get(p.get("ns", ""), {}).get(p["key"])

    async def h_kv_exists(self, conn, p):
        return p["key"] in self.kv.get(p.get("ns", ""), {})

    async def h_kv_del(self, conn, p):
        self._check_writable(p)
        ns = self.kv.get(p.get("ns", ""), {})
        prefix = p.get("prefix", False)
        if p.get("ns", "") not in _EPHEMERAL_NS:
            self._log("kv_del", {"ns": p.get("ns", ""), "key": p["key"],
                                 "prefix": prefix})
        if prefix:
            n = 0
            for k in [k for k in ns if k.startswith(p["key"])]:
                del ns[k]
                n += 1
            return n
        return 1 if ns.pop(p["key"], None) is not None else 0

    async def h_kv_keys(self, conn, p):
        ns = self.kv.get(p.get("ns", ""), {})
        pref = p.get("prefix") or ""
        return [k for k in ns if k.startswith(pref)]

    # ---------------------------------------------------------------- nodes --
    async def h_register_node(self, conn, p):
        self._check_writable(p)
        node = NodeInfo(p["node_id"], p["address"], p["resources"],
                        p.get("labels", {}), p.get("store_path", ""),
                        p.get("session_dir", ""))
        prev = self.nodes.get(node.node_id)
        if prev is not None:
            # Re-registration after a connection blip or GCS restart:
            # running leases still consume resources, so keep the last
            # reported availability (the next heartbeat refreshes it) and
            # retire the stale gcs->agent connection.
            node.resources_available = dict(prev.resources_available)
            if prev.conn is not None and not prev.conn.closed:
                await prev.conn.close()
        node.client_conn = conn
        self.nodes[node.node_id] = node
        self._node_epoch += 1
        self._addr_index = None
        self._mark_view_dirty(node)
        self._log("node", {
            "node_id": node.node_id, "address": list(node.address),
            "resources": node.resources_total, "labels": node.labels,
            "store_path": node.store_path,
            "session_dir": node.session_dir})
        rpc.spawn(self._connect_agent(node))
        self._publish(protocol.CH_NODE, {"event": "alive", "node": node.view()})
        if not p.get("view", True):
            # Registrants that don't consume the cluster view (agents,
            # the soak harness) skip the O(N) reply: a wave of N
            # registrations otherwise does O(N^2) view-building on this
            # loop, which is exactly the mass-(re)registration moment
            # the GCS can least afford it.
            return {"node_id": node.node_id, "num_nodes": len(self.nodes),
                    protocol.EPOCH_KEY: self.epoch}
        return {"cluster_nodes": [n.view() for n in self.nodes.values()],
                protocol.EPOCH_KEY: self.epoch}

    async def _connect_agent(self, node: NodeInfo):
        try:
            node.conn = await rpc.connect(node.address, name="gcs->agent")
        except rpc.ConnectionLost:
            logger.warning("cannot connect to agent %s", node.address)

    async def h_get_nodes(self, conn, p):
        """Full node views, or — with {"since": epoch} — only the views
        whose SCHEDULING-RELEVANT state changed after `epoch` (see
        _mark_view_dirty; pass since=-1 for a full delta-form bootstrap).
        Observability-only fields (runtime gauges, transfer counters,
        rtt/clock) do not dirty a view: dashboards and the CLI use the
        full form, scheduling clients (core_worker's 2s-cached view) use
        deltas so N pollers cost O(changes), not O(N) each."""
        since = (p or {}).get("since")
        if since is None:
            return [n.view() for n in self.nodes.values()]
        if since > self._view_epoch:
            since = -1      # GCS restarted with a fresh epoch: resend all
        return {"epoch": self._view_epoch,
                "changed": [n.view() for n in self.nodes.values()
                            if n.view_version > since],
                "total": len(self.nodes)}

    async def h_report_resources(self, conn, p):
        node = self.nodes.get(p["node_id"])
        if node is None or not node.alive:
            # The reporter was marked dead (health-check false positive —
            # e.g. a GC pause on the agent outlived the failure budget) or
            # predates a journal wipe.  Death is permanent for consumers
            # (its actors were restarted, its primaries written off), so
            # tell the agent its reports are going nowhere: it re-registers
            # under a FRESH node id and rejoins instead of zombieing.
            return False
        if node.resources_available != p["available"]:
            self._mark_view_dirty(node)
        node.resources_available = p["available"]
        node.last_heartbeat = time.monotonic()
        if p.get("runtime"):
            # Runtime gauges (lease queue depth, arena occupancy, ...):
            # straight into the node view for the CLI summary / dashboard;
            # the agent separately exports the same numbers as metrics.
            node.runtime = p["runtime"]
        if p.get("transfer"):
            node.transfer = p["transfer"]
            total = int(node.transfer.get("bytes_served") or 0) + \
                int(node.transfer.get("bytes_pulled") or 0)
            now_ts = time.monotonic()
            dt = now_ts - node._transfer_prev_ts
            if node._transfer_prev_ts and 0.0 < dt < 60.0:
                node.bulk_rate = max(0, total - node._transfer_prev) / dt
            node._transfer_prev = total
            node._transfer_prev_ts = now_ts
        peer_stats = p.get("peer_stats")
        if peer_stats:
            # Fold the reporter's per-peer link observations into each
            # TARGET node's evidence: multiple independent reporters
            # seeing high RTT to one node is the strongest gray signal
            # there is (differential observability).
            now = time.monotonic()
            by_addr = self._alive_by_addr()
            for addr_s, st in peer_stats.items():
                target = by_addr.get(addr_s)
                if target is None or target.node_id == p["node_id"]:
                    continue
                ts = now - float(st.get("age_s") or 0.0)
                rtt = st.get("rtt")
                if rtt is not None:
                    target.peer_rtts[p["node_id"]] = (float(rtt), ts)
                rate = st.get("rate")
                if rate is not None:
                    target.peer_rates[p["node_id"]] = (float(rate), ts)
        # Dict (truthy) keeps the legacy `ok is False` rejection check
        # working while carrying the cluster epoch: the heartbeat is how
        # every agent LEARNS a failover happened (and starts fencing
        # grants minted under the old epoch).
        return {"ok": True, protocol.EPOCH_KEY: self.epoch}

    async def h_drain_node(self, conn, p):
        """Two-phase graceful drain (reference: autoscaler.proto DrainNode;
        Pathways-style preemption handling — planned departure is distinct
        from abrupt death).  Phase 1 marks the node DRAINING: the scheduler
        and spillback stop targeting it, its ALIVE actors restart elsewhere
        through the normal restart path (before teardown, with a
        NodePreemptedError cause), and the agent migrates sole primary
        object copies to a peer.  Phase 2 — only at the deadline, or once
        the agent reports the drain complete — falls back to the hard-kill
        death path.  Payload: node_id, reason (preemption|idle|manual),
        deadline_s, wait (block until the node is dead)."""
        node = self.nodes.get(p["node_id"])
        if node is None:
            return False
        if not node.alive:
            return True          # already dead: drain is trivially done
        reason = p.get("reason") or protocol.DRAIN_MANUAL
        from .config import get_config
        d = p.get("deadline_s")   # explicit 0 = hard-kill now, not default
        deadline_s = float(get_config().node_drain_deadline_s
                           if d is None else d)
        if node.draining is None:
            node.draining = {"reason": reason,
                             "deadline": time.monotonic() + deadline_s}
            node.drain_reason = reason
            self._mark_view_dirty(node)     # schedulable flipped off
            logger.warning("node %s draining (reason=%s, deadline=%.1fs)",
                           node.node_id.hex()[:8], reason, deadline_s)
            self._publish(protocol.CH_NODE, {
                "event": "draining", "node": node.view(),
                "reason": reason, "deadline_s": deadline_s})
            node.drain_task = rpc.spawn(
                self._drain_node(node, reason, deadline_s))
        if p.get("wait") and node.drain_task is not None:
            try:
                await asyncio.wait_for(asyncio.shield(node.drain_task),
                                       deadline_s + 10.0)
            except asyncio.TimeoutError:
                return False
        return True

    async def _drain_node(self, node: NodeInfo, reason: str,
                          deadline_s: float):
        deadline = time.monotonic() + deadline_s
        cause = (f"NodePreemptedError: node {node.node_id.hex()[:8]} is "
                 f"being drained (reason={reason})")
        # Restart ALIVE actors elsewhere BEFORE teardown — _pick_node no
        # longer offers the draining node, so the existing restart path
        # lands them on a peer while the old incarnations keep serving
        # in-flight calls until the node exits.  Runs concurrently with
        # the agent-side object migration below; both are bounded by the
        # drain deadline (a restart that cannot place in time continues in
        # the background and is skipped by the final hard-kill pass, which
        # only death-handles actors still ALIVE on this node).
        pending = [rpc.spawn(self._handle_actor_death(actor, cause))
                   for actor in list(self.actors.values())
                   if actor.node_id == node.node_id
                   and actor.state == protocol.ACTOR_ALIVE]
        if node.conn is not None and not node.conn.closed:
            remaining = max(0.5, deadline - time.monotonic())
            pending.append(rpc.spawn(node.conn.call(
                "drain", {"reason": reason, "deadline_s": remaining},
                timeout=remaining + 5.0)))
        for t in pending:
            # Tasks may outlive the deadline wait below; retrieve their
            # exceptions (e.g. an agent drain RPC racing the node's death)
            # so they don't log as never-retrieved at GC.
            t.add_done_callback(lambda t: t.cancelled() or t.exception())
        if pending:
            # asyncio.wait, NOT wait_for(gather(...)): the latter CANCELS
            # the children at the deadline, which would kill an actor
            # restart mid create_actor_worker and strand the actor in
            # RESTARTING.  Slow restarts must keep running past the
            # deadline (the hard-kill pass below skips RESTARTING actors).
            await asyncio.wait(pending,
                               timeout=max(0.1, deadline - time.monotonic()))
        await self._mark_node_dead(node.node_id,
                                   f"drained (reason={reason})")
        # Graceful teardown: the agent SIGTERMs its workers (closing actor
        # connections so clients fail over to the restarted incarnations),
        # unlinks its shm arena and exits.
        live = self.nodes.get(node.node_id)
        if live is not None and live.conn is not None \
                and not live.conn.closed:
            try:
                live.conn.notify("shutdown", {"graceful": True})
            except rpc.ConnectionLost:
                pass

    async def h_report_demand(self, conn, p):
        """Core workers report unfulfilled lease shapes so the autoscaler
        can see cluster-wide pending demand (reference: autoscaler state
        aggregation in gcs_autoscaler_state_manager.cc)."""
        shapes = p.get("shapes") or []
        if shapes:
            self.demand[p["reporter"]] = {"shapes": shapes,
                                          "ts": time.monotonic()}
        else:
            self.demand.pop(p["reporter"], None)
        return True

    async def h_get_demand(self, conn, p):
        """Aggregate non-expired demand: task shapes from workers, plus
        pending actors and pending placement-group bundles."""
        ttl = p.get("ttl_s", 15.0)
        now = time.monotonic()
        shapes: list = []
        for reporter, entry in list(self.demand.items()):
            if now - entry["ts"] > ttl:
                del self.demand[reporter]
                continue
            shapes.extend(entry["shapes"])
        pending_actors = [
            a.spec.get("resources", {}) for a in self.actors.values()
            if a.state in (protocol.ACTOR_PENDING,
                           protocol.ACTOR_RESTARTING)]
        pending_bundles: list = []
        for pg in self.placement_groups.values():
            if pg["state"] == "PENDING":
                pending_bundles.append({"strategy": pg["strategy"],
                                        "bundles": pg["bundle_specs"]})
        return {"task_shapes": shapes,
                "pending_actors": [r for r in pending_actors if r],
                "pending_pgs": pending_bundles}

    async def _health_loop(self):
        """Active health checking (reference: gcs_health_check_manager.h —
        FailNode after `health_check_failure_threshold` missed periods),
        plus the gray-failure scorer: every period the GCS RTT-probes each
        agent, folds in peers' heartbeat-carried observations, and updates
        a per-node suspicion score.  Crash detection (silence) and gray
        detection (lateness) deliberately share this loop — a node can be
        sliding from one to the other."""
        from .config import get_config
        cfg = get_config()
        period = cfg.health_check_period_ms / 1000.0
        threshold = cfg.health_check_failure_threshold
        tick = time.monotonic()
        while True:
            await asyncio.sleep(period)
            try:
                now = time.monotonic()
                # A paused observer cannot judge silence.  When this tick
                # itself is late — the loop was busy, or the whole host
                # froze (creating a TPU client stalls every process on the
                # machine for seconds) — the heartbeats sent meanwhile are
                # still queued behind it, so the pause is taken out of
                # every node's silence instead of being read as its death.
                late, tick = now - tick - period, now
                if late > period:
                    for node in self.nodes.values():
                        node.last_heartbeat = min(
                            now, node.last_heartbeat + late)
                for node in list(self.nodes.values()):
                    if node.alive and \
                            now - node.last_heartbeat > period * threshold:
                        await self._mark_node_dead(node.node_id,
                                                   "health check failed")
                for node in self.nodes.values():
                    if not node.alive:
                        continue
                    if node.conn is None or node.conn.closed:
                        # The probe dial is made once at registration
                        # and is not self-healing: re-dial here so a
                        # transient reset can't permanently blind
                        # probe-based gray detection (and rtt_ms
                        # observability) for a node that stays ALIVE
                        # on its own agent->gcs heartbeat dial.
                        rpc.spawn(self._redial_and_probe(
                            node, period * threshold))
                        continue
                    # Concurrent probes: a slow node must not delay
                    # the scoring (or probing) of its siblings.
                    rpc.spawn(self._probe_node(node,
                                               period * threshold))
                self._update_suspicion(cfg, period, threshold)
            except Exception:
                logger.exception("health check pass failed")

    async def _redial_and_probe(self, node: NodeInfo, bound: float) -> None:
        """Re-establish a dropped gcs→agent probe dial, then probe.  A
        refused dial while the node's heartbeats keep flowing is the
        asymmetric-partition signature — fold it in as the same
        worst-case sample a timed-out probe produces."""
        await self._connect_agent(node)
        if node.conn is None or node.conn.closed:
            rtt = max(bound, 1.0)
            node.rtt_ema = rtt if node.rtt_ema is None \
                else 0.7 * node.rtt_ema + 0.3 * rtt
            node.rtt_ts = time.monotonic()
            return
        await self._probe_node(node, bound)

    async def _probe_node(self, node: NodeInfo, bound: float) -> None:
        """One timed ping of an agent; folds the RTT into its EMA.  A
        failed/timed-out probe folds the full bound in as a worst-case
        sample rather than recording nothing: the documented
        asymmetric-partition case is exactly a GCS→node direction gone
        dark while node→GCS heartbeats keep flowing — silence THERE
        must raise suspicion (the EMA and the sustained window still
        require it to persist before anything drains).  Death from
        total silence stays the heartbeat detector's job.

        The same round trip doubles as the clock-alignment probe: the
        agent's ping reply carries its receive/transmit wall stamps
        (t1, t2), and with our own send/receive stamps (t0, t3) the
        NTP sample theta = ((t1-t0)+(t2-t3))/2 estimates that node's
        clock offset — min-RTT filtered and smoothed in
        node.clock (clocks.OffsetEstimator), exported via the node
        view and the per-node skew gauge, applied read-side by
        timeline rendering.  No extra RPC: measurement rides the
        health loop that already exists."""
        t0_mono = time.monotonic()
        t0 = clocks.wall()
        reply = None
        try:
            reply = await node.conn.call("ping", {},
                                         timeout=max(bound, 1.0))
            rtt = time.monotonic() - t0_mono
        except Exception:
            rtt = max(bound, 1.0)
        else:
            t3 = clocks.wall()
            if isinstance(reply, dict) and "t1" in reply \
                    and "t2" in reply:
                from .config import get_config as _gc
                if _gc().clock_align_enabled:
                    try:
                        node.clock.add(t0, float(reply["t1"]),
                                       float(reply["t2"]), t3)
                    except (TypeError, ValueError):
                        pass  # malformed stamps: RTT evidence still counts
        node.rtt_ema = rtt if node.rtt_ema is None \
            else 0.7 * node.rtt_ema + 0.3 * rtt
        node.rtt_ts = time.monotonic()

    def _update_suspicion(self, cfg, period: float, threshold: int) -> None:
        """Score each alive node against the cluster: suspicion rises
        when its probe/peer RTT exceeds both an absolute floor
        (gray_min_rtt_ms) and a multiple of its PEERS' median RTT
        (gray_rtt_ratio — shared load on the host running the GCS moves
        the median, not the ratio), or when its heartbeats arrive with
        gray-zone staleness.  Sustained suspicion past
        gray_suspicion_threshold auto-triggers the PR-3 two-phase drain
        with reason='gray' — closing detect -> avoid -> evacuate."""
        alive = [n for n in self.nodes.values() if n.alive]
        if not alive:
            return
        now = time.monotonic()
        # Same 30s freshness bar as each node's own obs below: a frozen
        # EMA (probe conn died) must not keep skewing the cluster
        # baseline its PEERS are measured against.
        rtt_pairs = [(n, n.rtt_ema) for n in alive
                     if n.rtt_ema is not None and now - n.rtt_ts < 30.0]
        susp_threshold = float(cfg.gray_suspicion_threshold)
        min_rtt = float(cfg.gray_min_rtt_ms) / 1000.0
        ratio = float(cfg.gray_rtt_ratio)
        report_s = cfg.resource_report_period_ms / 1000.0
        death_bound = period * threshold

        # Evict long-stale peer evidence: reporters die and re-register
        # under fresh node ids forever (PR-3 rejoin path), so without a
        # sweep these dicts grow monotonically for the cluster's
        # lifetime (the agent-side twin, _peer_stats, evicts at the
        # same horizon for the same reason).
        for node in alive:
            for d in (node.peer_rtts, node.peer_rates):
                for rid in [r for r, (_v, ts) in d.items()
                            if now - ts > 900.0]:
                    del d[rid]

        def _fresh_vals(d):
            """Fresh (<30s) values from healthy reporters only: a gray
            reporter measures every peer through its own sick link."""
            return sorted(
                v for rid, (v, ts) in d.items()
                if now - ts < 30.0 and v is not None
                and getattr(self.nodes.get(rid), "suspicion",
                            1.0) < susp_threshold)

        # Per-node peer-observed transfer rate (upper median — the
        # healthier read; rate has no own-probe to exonerate a node, so
        # a lone reporter never counts).
        rate_med = {}
        for node in alive:
            rates = _fresh_vals(node.peer_rates)
            if len(rates) >= 2:
                rate_med[node.node_id] = rates[len(rates) // 2]

        def _loo_median(sorted_vals, own, lower=False):
            """Median of sorted_vals with ONE occurrence of `own`
            removed (leave-one-out; own=None removes nothing).  Sorting
            once and bisecting here keeps the tick O(N log N) — a
            per-node re-sort is O(N^2 log N) of event-loop stall at
            fleet size, and a slow scorer tick would feed back into its
            own heartbeat-staleness evidence."""
            n = len(sorted_vals)
            if own is None:
                if not n:
                    return None
                return sorted_vals[(n - 1) // 2 if lower else n // 2]
            if n <= 1:
                return None
            m = (n - 2) // 2 if lower else (n - 1) // 2
            if m >= bisect.bisect_left(sorted_vals, own):
                m += 1
            return sorted_vals[m]

        own_rtt = {m.node_id: r for m, r in rtt_pairs}
        rtts_sorted = sorted(own_rtt.values())
        rates_sorted = sorted(rate_med.values())

        for node in alive:
            # Baseline = median RTT of the OTHER nodes: including a
            # node's own RTT in its baseline lets the slow node of a
            # 2-node cluster (or the slow half of any even one) set its
            # own floor and never look suspect.
            baseline = _loo_median(rtts_sorted,
                                   own_rtt.get(node.node_id))
            # Stale probe evidence is no evidence: if the gcs->agent
            # probe conn died, rtt_ema freezes at its last value — a
            # node that was briefly slow must not stay suspect forever
            # on a frozen reading (peer_rtts below age out the same way).
            obs = node.rtt_ema if now - node.rtt_ts < 30.0 else None
            fresh = _fresh_vals(node.peer_rtts)
            # Lower median across reporters, and never a LONE reporter
            # overriding a fresh healthy probe: a genuinely slow node
            # looks slow to every reporter, so the lower median stays
            # high — but one accuser (flaky, or itself sub-threshold
            # gray) can't defame a node the GCS's own probe exonerates.
            if fresh and (obs is None or len(fresh) >= 2):
                med = fresh[(len(fresh) - 1) // 2]
                obs = med if obs is None else max(obs, med)
            raw = 0.0
            if obs is not None and baseline is not None:
                floor = max(min_rtt, ratio * baseline)
                if obs > floor:
                    raw = min(1.0, (obs - floor) / floor)
            # Bandwidth deficit: peers pull from this node at least
            # gray_rtt_ratio slower than from the rest of the cluster —
            # the one signal a throttled/half-duplex-sick link shows
            # while its small-frame ping RTT still looks clean.
            rm = rate_med.get(node.node_id)
            base_r = _loo_median(rates_sorted, rm, lower=True) \
                if rm is not None else None
            if rm is not None and base_r is not None:
                if rm * ratio < base_r:
                    raw = max(raw, min(1.0, (base_r / max(rm, 1.0)
                                             - ratio) / ratio))
            hb_age = now - node.last_heartbeat
            if hb_age > max(3.0 * report_s, 1.0):
                # Heartbeats late but not yet fatal: the gray zone
                # between healthy and the crash detector's verdict.
                raw = max(raw, min(1.0, hb_age / death_bound))
            was_suspect = node.suspicion >= policy.SUSPECT_THRESHOLD
            node.suspicion = 0.7 * node.suspicion + 0.3 * raw
            if (node.suspicion >= policy.SUSPECT_THRESHOLD) != was_suspect:
                # Trust-tier flip is what delta-view consumers
                # (prefer_trusted) act on; sub-threshold EMA drift is not.
                self._mark_view_dirty(node)
            if node.suspicion >= susp_threshold:
                if node.suspect_since is None:
                    node.suspect_since = now
                    logger.warning(
                        "node %s gray-suspect: suspicion=%.2f "
                        "(rtt=%s, baseline=%s, hb_age=%.2fs)",
                        node.node_id.hex()[:8], node.suspicion,
                        f"{obs * 1000:.0f}ms" if obs else "n/a",
                        f"{baseline * 1000:.1f}ms" if baseline else "n/a",
                        hb_age)
                self._maybe_gray_drain(node, alive, now,
                                       float(cfg.gray_sustained_s),
                                       bool(cfg.gray_auto_drain),
                                       susp_threshold)
            elif node.suspicion < 0.8 * susp_threshold:
                node.suspect_since = None       # hysteresis

    def _maybe_gray_drain(self, node: NodeInfo, alive, now: float,
                          sustained_s: float, auto: bool,
                          susp_threshold: float) -> None:
        if not auto or node.draining is not None or not node.alive:
            return
        if node.suspect_since is None \
                or now - node.suspect_since < sustained_s:
            return
        from .config import get_config as _gc
        exempt = float(_gc().gray_bulk_drain_exempt_bytes_per_s)
        if exempt > 0 and node.bulk_rate >= exempt:
            # Mid-broadcast/bulk-serving node: its probe RTT inflates for
            # exactly as long as the transfer runs (PR 4's "bulk transfer
            # != RTT" principle, applied to the GCS's own probes, which
            # queue behind the agent's chunk serving).  Placement already
            # deprioritizes it via suspicion; EVACUATING it would kill
            # the very transfer that made it look slow.  The auto-drain
            # resumes the first sustained-suspect window after the bulk
            # flow stops.
            node.suspect_since = now
            return
        # Never evacuate INTO nothing: require at least one other
        # schedulable, non-suspect node to receive the work — if the
        # whole cluster looks gray, the problem is the observer (or the
        # fabric), not this node.
        others = [m for m in alive
                  if m is not node and m.schedulable
                  and m.suspicion < susp_threshold]
        if not others:
            return
        logger.warning(
            "auto-draining gray node %s (suspicion %.2f sustained %.1fs)",
            node.node_id.hex()[:8], node.suspicion,
            now - node.suspect_since)
        rpc.spawn(self.h_drain_node(None, {
            "node_id": node.node_id, "reason": protocol.DRAIN_GRAY}))

    def _on_client_close(self, conn):
        """A registered agent's inbound connection closed: for a crashed
        or SIGKILL'd agent the kernel closes the socket immediately, so
        mark the node dead NOW instead of waiting health_check_period_ms ×
        health_check_failure_threshold.  Netsplits send no FIN/RST and
        still take the heartbeat-timeout path; an agent-side reconnect
        re-registers (fresh NodeInfo), so a stale conn's close can never
        kill the successor (identity check below)."""
        if self._closing:
            return
        for node in self.nodes.values():
            if node.client_conn is conn and node.alive:
                rpc.spawn(self._mark_node_dead(
                    node.node_id, "agent connection closed"))
                break

    async def _mark_node_dead(self, node_id: bytes, reason: str):
        node = self.nodes.get(node_id)
        if not node or not node.alive:
            return
        node.alive = False
        node.draining = None
        self._addr_index = None
        self._mark_view_dirty(node)
        logger.warning("node %s dead: %s", node_id.hex()[:8], reason)
        self._publish(protocol.CH_NODE, {"event": "dead", "node": node.view(),
                                         "reason": reason})
        # Fail actors on that node; restart if allowed.
        for actor in list(self.actors.values()):
            if actor.node_id == node_id and actor.state == protocol.ACTOR_ALIVE:
                await self._handle_actor_death(actor, f"node died: {reason}")

    # --------------------------------------------------------------- pubsub --
    async def h_subscribe(self, conn, p):
        # Idempotent per (channel, conn): a client whose subscribe RPC
        # raced a GCS restart retries it AFTER its reconnect hook already
        # re-subscribed — appending blindly would double every notify.
        subs = self._subscribers.setdefault(p["channel"], [])
        if conn not in subs:
            subs.append(conn)
        return True

    async def h_publish(self, conn, p):
        self._publish(p["channel"], p["message"])
        return True

    def _publish(self, channel: str, message):
        subs = self._subscribers.get(channel, [])
        dead = []
        for c in subs:
            if c.closed:
                dead.append(c)
                continue
            try:
                c.notify("pubsub", {"channel": channel, "message": message})
            except rpc.ConnectionLost:
                dead.append(c)
        for c in dead:
            subs.remove(c)

    # ----------------------------------------------------------------- jobs --
    async def h_next_job_id(self, conn, p):
        self._check_writable(p)
        self._job_counter += 1
        self._log("job_counter", self._job_counter)
        return self._job_counter

    async def h_register_job(self, conn, p):
        self._check_writable(p)
        self.jobs[p["job_id"]] = {"job_id": p["job_id"],
                                  "driver_addr": p.get("driver_addr"),
                                  "start_time": time.time(), "alive": True}
        self._log("job", self.jobs[p["job_id"]])
        return True

    async def h_get_jobs(self, conn, p):
        return list(self.jobs.values())

    # --------------------------------------------------------------- actors --
    async def h_register_actor(self, conn, p):
        """Register + schedule an actor (reference: gcs_actor_manager.cc
        RegisterActor/CreateActor; scheduling in gcs_actor_scheduler.cc)."""
        self._check_writable(p)
        spec = p["spec"]
        actor_id = spec["actor_id"]
        name = spec.get("name")
        if name:
            existing_id = self.named_actors.get(name)
            if existing_id is not None and existing_id != actor_id:
                existing = self.actors.get(existing_id)
                if existing and existing.state != protocol.ACTOR_DEAD:
                    if spec.get("get_if_exists"):
                        return {"existing": True, "actor": existing.view()}
                    raise ValueError(f"actor name {name!r} already taken")
        existing = self.actors.get(actor_id)
        if existing is not None:
            # Retried register (e.g. driver reconnected after a GCS
            # restart that already replayed this actor) — idempotent.
            return {"existing": True, "actor": existing.view()}
        actor = ActorInfo(actor_id, spec)
        self.actors[actor_id] = actor
        if name:
            self.named_actors[name] = actor_id
        self._log_actor(actor, with_spec=True)
        if actor_id in self._pending_kills:
            self._pending_kills.pop(actor_id, None)
            actor.max_restarts = 0
            actor.state = protocol.ACTOR_DEAD
            actor.death_cause = "killed before registration completed"
            self._log_actor(actor)
            return {"existing": False, "actor": actor.view()}
        # Placement runs in the background: RegisterActor replies once the
        # actor is recorded, the creation task proceeds asynchronously
        # (reference: gcs_actor_manager.cc RegisterActor vs CreateActor —
        # clients poll/get with wait_alive).  Keeping PENDING visible also
        # lets the autoscaler see the actor as demand and bring capacity
        # before the scheduling deadline.  Fast placements (warm worker
        # pool) get a short grace so the common path replies ALIVE with
        # the address inline — first-call latency matters.
        rpc.spawn(self._schedule_or_bury(actor))
        start = time.monotonic()
        while actor.state == protocol.ACTOR_PENDING \
                and time.monotonic() - start < 0.4:
            await asyncio.sleep(
                0.01 if time.monotonic() - start < 0.2 else 0.05)
        return {"existing": False, "actor": actor.view()}

    async def _schedule_or_bury(self, actor: ActorInfo):
        ok = await self._schedule_actor(actor)
        if not ok and actor.state == protocol.ACTOR_PENDING:
            actor.state = protocol.ACTOR_DEAD
            # Keep a more specific cause if the scheduler recorded one
            # (e.g. a runtime-env install failure).
            actor.death_cause = (actor.death_cause
                                 or "scheduling failed: no feasible node")
            if actor.name and \
                    self.named_actors.get(actor.name) == actor.actor_id:
                del self.named_actors[actor.name]
            self._log_actor(actor)
            self._publish(protocol.CH_ACTOR,
                          {"event": "dead", "actor": actor.view()})

    def _pick_node(self, resources: Dict[str, float],
                   strategy: Optional[dict],
                   locality: Optional[Dict] = None) -> Optional[NodeInfo]:
        """Feasibility + best-fit over the live resource view. Honors
        node-affinity and placement-group strategies; falls back to
        most-available (spread-ish, mirroring hybrid policy's behavior
        below the packing threshold).

        `locality` (addr -> hinted arg bytes, from the spec's replica-
        directory hints) biases the DEFAULT policy toward nodes already
        holding the bytes — strictly below feasibility, explicit
        strategies, labels, and trusted-first ordering."""
        if strategy and strategy.get("type") == "node_affinity":
            node = self.nodes.get(strategy["node_id"])
            if node and node.schedulable:
                return node
            if not strategy.get("soft"):
                return None
        if strategy and strategy.get("type") == "placement_group":
            pg = self.placement_groups.get(strategy["pg_id"])
            if pg and pg["state"] == "CREATED":
                idx = strategy.get("bundle_index", 0)
                if idx < 0:
                    # any-bundle: rotate across live bundle nodes so retries
                    # reach a bundle with room (the GCS does not track
                    # per-bundle usage; agents reject exhausted bundles)
                    live = [b for b in pg["bundles"]
                            if (n := self.nodes.get(b["node_id"]))
                            and n.schedulable]
                    if not live:
                        return None
                    self._pg_rr[pg["pg_id"]] = (
                        self._pg_rr.get(pg["pg_id"], -1) + 1)
                    b = live[self._pg_rr[pg["pg_id"]] % len(live)]
                    return self.nodes[b["node_id"]]
                bundle = pg["bundles"][idx]
                node = self.nodes.get(bundle["node_id"])
                if node and node.schedulable:
                    return node
            return None
        live = [n for n in self.nodes.values() if n.schedulable]
        if strategy and strategy.get("type") == "node_label":
            keep = set(policy.label_filter(
                [(n.node_id, n.labels or {}) for n in live],
                strategy.get("hard") or None))
            live = [n for n in live if n.node_id in keep]
            if not live:
                return None
            soft = strategy.get("soft")
            if soft:
                # Soft preference: place within the preferred subset when
                # any of it is feasible, falling back to all hard matches
                # (reference: node_label policy's soft reordering).
                preferred = [n for n in live
                             if all((n.labels or {}).get(a) == b
                                    for a, b in soft.items())]
                if preferred:
                    pick = policy.hybrid_pick(
                        [(n, n.resources_total, n.resources_available)
                         for n in preferred], resources)
                    if pick is not None:
                        return pick
        def _pick(cand_nodes):
            cands = [(n, n.resources_total, n.resources_available)
                     for n in cand_nodes]
            if strategy and strategy.get("type") == "spread":
                # Least-utilized feasible node (reference:
                # spread_scheduling_policy.h round-robins; least-utilized
                # is the stateless equivalent under a live resource view).
                feas = [(n, policy.critical_utilization(t, a, resources))
                        for n, t, a in cands
                        if policy.feasible(a, resources)]
                return min(feas, key=lambda nu: nu[1])[0] if feas else None
            if locality:
                # Bytes-already-local tiebreak (within this trust tier;
                # feasibility checked inside): a node holding the spec's
                # large args saves their whole transfer.  Same min_bytes
                # floor as the submitter/spillback paths — a feasible
                # node holding only a tiny arg must not override
                # pack/spread.
                from .config import get_config as _gc2
                best = policy.pick_by_locality(
                    [(n, n.address, n.resources_total,
                      n.resources_available) for n in cand_nodes],
                    resources, locality,
                    min_bytes=_gc2().object_locality_min_bytes)
                if best is not None:
                    return best
            # Default: hybrid top-k pack-then-spread
            # (reference: hybrid_scheduling_policy.h:50).
            return policy.hybrid_pick(cands, resources)

        # Gray-failure deprioritization AFTER constraint filtering (so a
        # hard label match on a suspect node stays feasible): place on
        # the non-suspect subset when it fits, else fall back to every
        # live node — a suspect node is a last resort, never excluded.
        trusted = [n for n in live
                   if n.suspicion < policy.SUSPECT_THRESHOLD]
        if trusted and len(trusted) < len(live):
            pick = _pick(trusted)
            if pick is not None:
                return pick
        return _pick(live)

    async def _schedule_actor(self, actor: ActorInfo,
                              timeout_s: float | None = None) -> bool:
        """Queue-until-feasible scheduling (reference: GcsActorScheduler keeps
        pending actors and reschedules as resources free up).

        The deadline restarts whenever a new node registers: cloud TPU
        provisioning routinely exceeds the base timeout, and a node arriving
        means the autoscaler is actively delivering the capacity this actor
        is waiting for."""
        spec = actor.spec
        if timeout_s is None:
            from .config import get_config
            timeout_s = float(get_config().actor_scheduling_timeout_s)
        deadline = time.monotonic() + timeout_s
        epoch = self._node_epoch
        node = None
        from .config import get_config as _get_config
        locality = policy.arg_locality(spec.get("args")) \
            if _get_config().object_locality_scheduling_enabled else None
        if locality and max(locality.values()) < \
                _get_config().object_locality_min_bytes:
            locality = None
        while time.monotonic() < deadline:
            if self._node_epoch != epoch:
                epoch = self._node_epoch
                deadline = time.monotonic() + timeout_s
            if actor.state not in (protocol.ACTOR_PENDING,
                                   protocol.ACTOR_RESTARTING):
                return False        # killed while pending/restarting
            node = self._pick_node(spec.get("resources", {}),
                                   spec.get("scheduling_strategy"),
                                   locality=locality)
            if node is not None and node.conn is not None and not node.conn.closed:
                try:
                    result = await node.conn.call("create_actor_worker", spec,
                                                  timeout=120)
                    break
                except (rpc.RpcError, asyncio.TimeoutError) as e:
                    msg = str(e)
                    if "runtime env setup failed" in msg:
                        # A broken env spec never succeeds by retrying —
                        # bury the actor with the installer's error (the
                        # task path fails fast the same way); retrying
                        # would livelock: each fresh install's "in
                        # progress" polls keep the deadline alive.
                        actor.death_cause = msg.split("\n")[0]
                        return False
                    if protocol.LEASE_REFUSED in msg or \
                            protocol.ACTOR_INIT_RAISED in msg:
                        # Likewise: a request the node can never grant
                        # (a fraction of a real chip) or a constructor
                        # that raised.  The whole remote traceback is the
                        # cause — it names what the actor found.
                        actor.death_cause = msg
                        return False
                    if "setup in progress" in msg:
                        # The node is actively materializing this actor's
                        # runtime env (pip installs can take minutes) —
                        # that's forward progress, not a stall: keep the
                        # deadline fresh like a new-capacity event.
                        deadline = time.monotonic() + timeout_s
                    logger.warning("actor creation on %s failed: %s; retrying",
                                   node.node_id.hex()[:8], msg.split("\n")[0])
            await asyncio.sleep(0.2)
        else:
            logger.warning(
                "actor scheduling timed out: resources=%s node view=%s",
                spec.get("resources"),
                [(n.node_id.hex()[:8], n.alive,
                  n.resources_available,
                  n.conn is not None and not n.conn.closed)
                 for n in self.nodes.values()])
            return False
        actor.state = protocol.ACTOR_ALIVE
        actor.address = result["worker_addr"]
        actor.node_id = node.node_id
        self._log_actor(actor)
        self._publish(protocol.CH_ACTOR, {"event": "alive", "actor": actor.view()})
        return True

    async def h_get_actor(self, conn, p):
        actor = None
        if p.get("actor_id"):
            actor = self.actors.get(p["actor_id"])
        elif p.get("name"):
            aid = self.named_actors.get(p["name"])
            actor = self.actors.get(aid) if aid else None
        if actor is None:
            return None
        if p.get("wait_alive") and actor.state == protocol.ACTOR_PENDING:
            start = time.monotonic()
            while actor.state == protocol.ACTOR_PENDING \
                    and time.monotonic() - start < 30.0:
                await asyncio.sleep(
                    0.01 if time.monotonic() - start < 0.3 else 0.05)
        return actor.view()

    async def h_list_actors(self, conn, p):
        return [a.view() for a in self.actors.values()]

    async def h_kill_actor(self, conn, p):
        self._check_writable(p)
        actor = self.actors.get(p["actor_id"])
        if actor is None:
            # Client-minted handles can be killed before their background
            # registration lands; remember the kill so registration buries
            # the actor instead of scheduling an unreachable orphan.
            now = time.monotonic()
            for aid, ts in list(self._pending_kills.items()):
                if now - ts > 600.0:
                    del self._pending_kills[aid]
                else:
                    break   # insertion-ordered: rest are fresher
            self._pending_kills[p["actor_id"]] = now
            return False
        actor.max_restarts = 0  # explicit kill is permanent
        if actor.state == protocol.ACTOR_ALIVE and actor.address:
            try:
                c = await rpc.connect(tuple(actor.address), retries=1)
                c.notify("kill", {"no_restart": True})
                await c.close()
            except rpc.ConnectionLost:
                pass
        await self._handle_actor_death(actor, "killed via kill_actor")
        return True

    async def h_actor_failed(self, conn, p):
        self._check_writable(p)
        actor = self.actors.get(p["actor_id"])
        if actor is None:
            return False
        await self._handle_actor_death(actor, p.get("reason", "worker died"))
        return True

    async def _handle_actor_death(self, actor: ActorInfo, reason: str):
        """Restart-or-bury (reference: gcs_actor_manager.cc OnActorWorkerDead;
        restart counting at :283)."""
        if actor.state == protocol.ACTOR_DEAD:
            return
        if actor.restarts < actor.max_restarts or actor.max_restarts < 0:
            actor.restarts += 1
            actor.state = protocol.ACTOR_RESTARTING
            actor.address = None
            self._publish(protocol.CH_ACTOR,
                          {"event": "restarting", "actor": actor.view()})
            ok = await self._schedule_actor(actor)
            if ok:
                return
            # Keep a specific cause the scheduler recorded during the
            # failed restart (e.g. a runtime-env install error) — that is
            # the actionable diagnosis, not the original death reason.
            if actor.death_cause:
                reason = f"{reason}; restart failed: {actor.death_cause}"
            else:
                reason = f"{reason}; restart failed"
        actor.state = protocol.ACTOR_DEAD
        actor.death_cause = reason
        actor.address = None
        if actor.name and self.named_actors.get(actor.name) == actor.actor_id:
            del self.named_actors[actor.name]
        self._log_actor(actor)
        self._publish(protocol.CH_ACTOR, {"event": "dead", "actor": actor.view()})

    # ----------------------------------------------------- placement groups --
    async def h_create_placement_group(self, conn, p):
        """Register a PG in PENDING state and place it asynchronously with a
        two-phase bundle reservation across agents (reference:
        gcs_placement_group_manager.cc pending queue +
        gcs_placement_group_scheduler.cc prepare/commit;
        node_manager.proto:471-476).  Returns immediately; clients poll
        get_placement_group / wait on the CH_PG channel."""
        self._check_writable(p)
        pg_id = p["pg_id"]
        if pg_id in self.placement_groups:
            # Retried create (reply lost across a GCS restart): keep the
            # replayed entry and any bundles already committed.
            return {"ok": True, "pg_id": pg_id}
        entry = {
            "pg_id": pg_id,
            "strategy": p.get("strategy", "PACK"),
            "bundle_specs": p["bundles"],     # list of resource dicts
            "bundles": [],                    # filled once placed
            "name": p.get("name", ""),
            "state": "PENDING",
        }
        self.placement_groups[pg_id] = entry
        self._log("pg", entry)
        rpc.spawn(self._place_pg(entry))
        return {"ok": True, "pg_id": pg_id}

    async def _place_pg(self, entry: dict):
        """Retry placement until feasible or the PG is removed (the
        reference keeps infeasible PGs pending forever too)."""
        pg_id = entry["pg_id"]
        bundles = entry["bundle_specs"]

        async def _return(idx, node):
            try:
                await node.conn.call("return_bundle", {
                    "pg_id": pg_id, "bundle_index": idx})
            except (rpc.RpcError, AttributeError, asyncio.TimeoutError):
                pass

        async def _finalize(chosen) -> None:
            """Every bundle is reserved on its node: flip to CREATED — or,
            if the PG was removed mid-placement, hand everything back."""
            if entry["state"] != "PENDING":
                await asyncio.gather(
                    *[_return(i, n) for i, n in enumerate(chosen)])
                return
            entry["bundles"] = [
                {"node_id": n.node_id, "resources": b,
                 "node_addr": list(n.address)}
                for b, n in zip(bundles, chosen)]
            entry["state"] = "CREATED"
            self._log("pg", entry)
            self._pg_event(pg_id).set()
            self._publish(protocol.CH_PG,
                          {"event": "created", "pg_id": pg_id})

        while entry["state"] == "PENDING":
            chosen = self._place_bundles(bundles, entry["strategy"])
            if chosen is None:
                await asyncio.sleep(0.2)
                continue
            if len({n.node_id for n in chosen}) == 1:
                # Single-node placement: prepare+commit collapse into ONE
                # agent RPC (no cross-node atomicity to coordinate) — the
                # dominant shape for small PGs and single-host gangs.
                node = chosen[0]
                try:
                    ok = await node.conn.call("reserve_bundles", {
                        "pg_id": pg_id,
                        "bundles": [{"bundle_index": i, "resources": b}
                                    for i, b in enumerate(bundles)]},
                        timeout=30)
                except (rpc.RpcError, AttributeError, asyncio.TimeoutError):
                    ok = False
                if not ok:
                    await asyncio.sleep(0.2)
                    continue
                await _finalize(chosen)
                return
            # Phase 1: prepare on every node IN PARALLEL; roll back on any
            # failure (a 64-bundle Train worker group pays one agent round
            # trip, not 64).
            async def _prepare(idx, bundle, node):
                try:
                    return await node.conn.call("prepare_bundle", {
                        "pg_id": pg_id, "bundle_index": idx,
                        "resources": bundle}, timeout=30)
                except (rpc.RpcError, AttributeError, asyncio.TimeoutError):
                    return False

            oks = await asyncio.gather(
                *[_prepare(i, b, n)
                  for i, (b, n) in enumerate(zip(bundles, chosen))])
            prepared = [(i, n) for i, (ok, n) in
                        enumerate(zip(oks, chosen)) if ok]
            if not all(oks):
                await asyncio.gather(*[_return(i, n) for i, n in prepared])
                await asyncio.sleep(0.2)
                continue
            # Phase 2: commit (parallel); on any failure return every
            # bundle and retry placement from scratch (a node died between
            # prepare and commit).
            async def _commit(idx, node):
                return await node.conn.call(
                    "commit_bundle", {"pg_id": pg_id, "bundle_index": idx})

            # return_exceptions: every commit must SETTLE before any
            # return_bundle goes out — a plain gather raises on the first
            # failure while sibling commits are still in flight, and a
            # commit processed after its bundle's return would leak the
            # node's resources on the retry.
            outcomes = await asyncio.gather(
                *[_commit(i, n) for i, n in prepared],
                return_exceptions=True)
            if any(isinstance(o, BaseException) for o in outcomes):
                await asyncio.gather(*[_return(i, n) for i, n in prepared])
                await asyncio.sleep(0.2)
                continue
            await _finalize(chosen)
            return

    def _place_bundles(self, bundles, strategy,
                       nodes=None) -> Optional[List[NodeInfo]]:
        alive = (nodes if nodes is not None else
                 [n for n in self.nodes.values() if n.schedulable])
        if not alive:
            return None
        if nodes is None:
            # Gray-failure deprioritization: try the gang on the
            # non-suspect subset first; suspect nodes host new bundles
            # only when the placement cannot succeed without them.
            trusted = [n for n in alive
                       if n.suspicion < policy.SUSPECT_THRESHOLD]
            if trusted and len(trusted) < len(alive):
                got = self._place_bundles(bundles, strategy, nodes=trusted)
                if got is not None:
                    return got
        remaining = {n.node_id: dict(n.resources_available) for n in alive}

        def fits(node, bundle):
            avail = remaining[node.node_id]
            return all(avail.get(k, 0.0) >= v for k, v in bundle.items() if v > 0)

        def take(node, bundle):
            avail = remaining[node.node_id]
            for k, v in bundle.items():
                avail[k] = avail.get(k, 0.0) - v

        chosen: List[NodeInfo] = []
        if strategy in ("PACK", "STRICT_PACK"):
            order = sorted(alive, key=lambda n: -sum(n.resources_available.values()))
            for bundle in bundles:
                placed = None
                for node in (chosen[-1:] if chosen else []) + order:
                    if fits(node, bundle):
                        placed = node
                        break
                if placed is None:
                    return None
                if strategy == "STRICT_PACK" and chosen and placed is not chosen[0]:
                    if fits(chosen[0], bundle):
                        placed = chosen[0]
                    else:
                        return None
                take(placed, bundle)
                chosen.append(placed)
        else:  # SPREAD / STRICT_SPREAD
            used: set = set()
            for bundle in bundles:
                order = sorted(alive, key=lambda n: (n.node_id in used,
                               -sum(remaining[n.node_id].values())))
                placed = None
                for node in order:
                    if strategy == "STRICT_SPREAD" and node.node_id in used:
                        continue
                    if fits(node, bundle):
                        placed = node
                        break
                if placed is None:
                    return None
                take(placed, bundle)
                used.add(placed.node_id)
                chosen.append(placed)
        return chosen

    async def h_remove_placement_group(self, conn, p):
        self._check_writable(p)
        pg = self.placement_groups.pop(p["pg_id"], None)
        if pg is None:
            return False
        pg["state"] = "REMOVED"         # stops a pending _place_pg loop
        ev = self._pg_events.pop(p["pg_id"], None)
        if ev is not None:
            ev.set()                    # wake pending waiters (-> None)
        self._log("pg_del", p["pg_id"])

        async def _return(idx, bundle):
            node = self.nodes.get(bundle["node_id"])
            if node and node.conn and not node.conn.closed:
                try:
                    await node.conn.call(
                        "return_bundle",
                        {"pg_id": p["pg_id"], "bundle_index": idx})
                except rpc.RpcError:
                    pass

        # Bundles return in parallel — removal latency is one agent round
        # trip, not one per bundle.
        await asyncio.gather(*[_return(i, b)
                               for i, b in enumerate(pg["bundles"])])
        return True

    def _pg_event(self, pg_id) -> asyncio.Event:
        ev = self._pg_events.get(pg_id)
        if ev is None:
            ev = self._pg_events[pg_id] = asyncio.Event()
        return ev

    async def h_get_placement_group(self, conn, p):
        entry = self.placement_groups.get(p["pg_id"])
        if entry is None or not p.get("wait_created"):
            return entry
        # Server-side event wait: the waiter wakes the moment _place_pg
        # publishes CREATED (or removal fires the event) — no poll loop
        # (reference: clients block on the CreatePlacementGroup reply /
        # ready future).
        if entry["state"] == "PENDING":
            try:
                await asyncio.wait_for(
                    self._pg_event(p["pg_id"]).wait(),
                    min(p.get("timeout_s", 10.0), 60.0))
            except asyncio.TimeoutError:
                pass
        # Removal during the wait pops the table; honor the None-means-
        # removed contract rather than returning the orphaned entry.
        return self.placement_groups.get(p["pg_id"])

    async def h_list_placement_groups(self, conn, p):
        return list(self.placement_groups.values())

    async def h_get_cluster_info(self, conn, p):
        return {
            "nodes": [n.view() for n in self.nodes.values()],
            "num_actors": len(self.actors),
            "num_jobs": len(self.jobs),
            protocol.EPOCH_KEY: self.epoch,
            "failovers": self._failover_count,
        }


class GcsStandby:
    """Warm-standby GCS: tails the primary's journal (shared-path follow
    mode — the single-host equivalent of a `journal_tail` streaming RPC),
    keeps hot replicas of every journaled table in an unstarted
    GcsServer, and holds back from serving until the primary's lease has
    gone a full TTL without renewal.  Takeover then: drain the un-tailed
    journal suffix, bump the cluster epoch (journaled before a single
    request is served), take over the advertised address, and start
    serving — clients re-home through resolve_gcs_address() on their
    next reconnect attempt (docs/control_plane.md §8)."""

    def __init__(self, journal_path: str, ha_dir: str,
                 host: str = "127.0.0.1", port: int = 0):
        self.journal_path = journal_path
        self.ha_dir = ha_dir
        self.server = GcsServer(host, port, journal_path, ha_dir=ha_dir)
        self.tailer = JournalTailer(journal_path)
        self.promoted = False
        self._stop = False

    def stop(self) -> None:
        self._stop = True

    def _apply(self, records, reset: bool) -> None:
        if reset:
            # Compaction replaced the journal under us: the new file is
            # self-contained (snapshot + suffix), so rebuild from zero.
            self.server._reset_tables()
        self.server._replay(records)

    async def run_until_takeover(self) -> Optional[GcsServer]:
        """Tail until the lease expires, then take over and return the
        (started) server; returns None if stop() was called first."""
        cfg = get_config()
        poll = cfg.gcs_standby_poll_ms / 1000.0
        lease_path = os.path.join(self.ha_dir, protocol.GCS_LEASE_FILE)
        sb_path = os.path.join(self.ha_dir, protocol.GCS_STANDBY_FILE)
        last_progress = 0.0
        while not self._stop:
            records, reset = self.tailer.poll()
            if records or reset:
                self._apply(records, reset)
            now = time.time()
            if now - last_progress >= 1.0:
                # Tail-progress breadcrumb: the PRIMARY exports it as
                # the standby-lag gauges (it owns the metrics endpoint).
                GcsServer._write_json_atomic(sb_path, {
                    "lag_bytes": self.tailer.lag_bytes(),
                    "ts": now, "pid": os.getpid()})
                last_progress = now
            lease = GcsServer._read_json(lease_path)
            if lease is not None:
                age = now - float(lease.get("renewed", 0.0))
                ttl = float(lease.get("ttl_s",
                                      cfg.gcs_lease_ttl_s))
                if age > ttl:
                    await self._take_over(lease, age)
                    return self.server
            await asyncio.sleep(poll)
        self.tailer.close()
        return None

    async def _take_over(self, stale_lease: dict, lease_age_s: float):
        # Final drain: whatever the dead primary flushed before the
        # lease lapsed must be in the replica before we bump the epoch.
        for _ in range(8):
            records, reset = self.tailer.poll()
            if not records and not reset:
                break
            self._apply(records, reset)
        self.tailer.close()
        srv = self.server
        prev_epoch = srv.epoch
        srv.epoch = max(srv.epoch, int(stale_lease.get("epoch", 0))) + 1
        srv._failover_count += 1
        srv._replayed = True        # tables are hot; start() must not re-replay
        logger.warning(
            "GCS standby taking over: lease stale %.2fs, epoch %d -> %d",
            lease_age_s, prev_epoch, srv.epoch)
        addr = await srv.start()    # journals the epoch bump, claims the
        self.promoted = True        # lease, rewrites the address file
        # Failover is an anomaly by definition: capture a black-box
        # bundle (diag-gcs_failover-*) with the takeover context.
        srv._ingest_anomaly({
            "kind": "gcs_failover", "daemon": "gcs",
            "epoch": srv.epoch, "prev_epoch": prev_epoch,
            "lease_age_s": round(lease_age_s, 3),
            "ex_primary_pid": int(stale_lease.get("owner_pid") or 0),
            "address": list(addr)})
        return addr


async def _amain(args):
    rpc.enable_eager_tasks()
    from .config import Config, get_config as _gcfg, set_config
    if args.system_config:
        set_config(Config(json.loads(args.system_config)))
    chaos_spec = _gcfg().rpc_chaos
    if chaos_spec:
        rpc.enable_chaos(chaos_spec)
    rpc.enable_link_chaos(_gcfg().link_chaos)
    rpc.enable_native_framer(_gcfg().rpc_native_framer)
    rpc.set_default_call_timeout(_gcfg().control_call_timeout_s)

    def _ready(payload: dict) -> None:
        if not args.ready_file:
            return
        tmp = args.ready_file + ".tmp"
        with open(tmp, "w") as f:
            json.dump(payload, f)
        os.replace(tmp, args.ready_file)

    if args.standby:
        if not args.journal:
            raise SystemExit("--standby requires --journal")
        ha_dir = args.ha_dir or os.path.dirname(args.journal)
        standby = GcsStandby(args.journal, ha_dir, port=args.port)
        # Readiness for a standby means "tailing", not "serving".
        _ready({"standby": True, "pid": os.getpid()})
        server = await standby.run_until_takeover()
        if server is None:
            return
        _ready({"address": list(server.address), "promoted": True,
                protocol.EPOCH_KEY: server.epoch, "pid": os.getpid()})
    else:
        server = GcsServer(port=args.port,
                           journal_path=args.journal or None,
                           ha_dir=args.ha_dir or None)
        addr = await server.start()
        # Signal readiness to the parent via a file it watches.
        _ready({"address": list(addr), protocol.EPOCH_KEY: server.epoch,
                "pid": os.getpid()})
    # Serve until fenced: a successor epoch in the lease file means a
    # standby took over while this process was frozen/partitioned — the
    # only correct move left is to stop touching the world and exit.
    await server.fenced_event.wait()
    logger.error("exiting: fenced by a newer-epoch primary")
    try:
        await asyncio.wait_for(server.close(), 5)
    except asyncio.TimeoutError:
        pass
    os._exit(3)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--port", type=int, default=0)
    parser.add_argument("--ready-file", default="")
    parser.add_argument("--journal", default="")
    parser.add_argument("--ha-dir", default="",
                        help="shared dir for the HA lease + advertised-"
                             "address files; empty disables the lease")
    parser.add_argument("--standby", action="store_true",
                        help="run as warm standby: tail the journal, "
                             "serve only after lease-expiry takeover")
    parser.add_argument("--log-level", default="INFO")
    parser.add_argument("--system-config", default="")
    args = parser.parse_args()
    logging.basicConfig(level=args.log_level, format="%(asctime)s %(levelname)s %(name)s %(message)s")
    from .node import install_daemon_profiler
    install_daemon_profiler("gcs")
    from .auth import require_process_token
    require_process_token("gcs")
    try:
        asyncio.run(_amain(args))
    except KeyboardInterrupt:
        pass


if __name__ == "__main__":
    main()
