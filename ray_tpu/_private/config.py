"""Runtime config/flag registry.

TPU-native equivalent of the reference's RAY_CONFIG X-macro registry
(reference: src/ray/common/ray_config_def.h — 234 entries, each overridable by
an `RAY_<name>` env var and cluster-wide via the `_system_config` JSON passed
to init). Here every flag is declared once with a typed default, overridable by
`RAY_TPU_<name>` in the process environment and by the `_system_config` dict
passed to `ray_tpu.init`.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict

_REGISTRY: Dict[str, tuple] = {}


def _define(name: str, default: Any, doc: str = ""):
    _REGISTRY[name] = (type(default), default, doc)


# ---- core runtime -----------------------------------------------------------
_define("object_store_memory_bytes", 0, "0 = auto (30% of system RAM, capped)")
_define("object_store_auto_fraction", 0.3)
_define("object_store_max_auto_bytes", 16 * 1024**3)
_define("object_store_table_slots", 1 << 16)
_define("max_direct_call_object_size", 100 * 1024,
        "results <= this are returned inline to the owner's memory store "
        "(reference: RAY_CONFIG max_direct_call_object_size, 100KB)")
_define("memory_store_max_bytes", 512 * 1024 * 1024)
_define("worker_register_timeout_s", 60.0)
_define("cgroup_enabled", False,
        "place worker processes in a cgroup v2 group (reference: "
        "common/cgroup2 system/application split); no-op when cgroup2 "
        "is unavailable or read-only")
_define("cgroup_memory_max_bytes", 0, "0 = no kernel memory cap")
_define("memory_usage_threshold", 0.95,
        "node memory fraction above which the agent's memory monitor kills "
        "a worker (reference: RAY_memory_usage_threshold); >=1 disables")
_define("memory_monitor_refresh_ms", 250,
        "memory monitor poll period (reference: "
        "RAY_memory_monitor_refresh_ms); 0 disables the monitor")
_define("worker_lease_timeout_s", 30.0)
_define("num_workers_soft_limit", 0, "0 = num_cpus")
_define("max_leases_per_scheduling_key", 64,
        "client-side cap on concurrent worker leases per scheduling key "
        "(reference: normal_task_submitter lease pool; queue-bounded anyway)")
_define("max_tasks_in_flight_per_worker", 64,
        "ceiling of the ADAPTIVE per-lease submit window: the pipeline "
        "deepens toward this while observed push->complete latency stays "
        "low and shrinks back on backpressure/loss (reference: "
        "normal_task_submitter.cc max_tasks_in_flight_per_worker)")
_define("submit_batch_ack_timeout_s", 15.0,
        "how long a submitter waits for a submit_batch enqueue-ack before "
        "resending the still-unfinished tasks (the worker dedups by task "
        "id, so a dropped ack is harmless); 4 lost acks recycle the "
        "connection")
_define("worker_prestart_count", 2,
        "workers spawned at agent boot so first leases don't pay process "
        "startup (reference: worker_pool.cc prestart)")
_define("worker_fork_server", True,
        "fork default-env CPU workers from a warm pre-imported zygote "
        "process (~100ms) instead of exec+reimport (~seconds); TPU and "
        "runtime-env workers always exec fresh (zygote.py)")
_define("worker_niceness", 0)
_define("maximum_gcs_destroyed_actor_cached_count", 100_000)
_define("task_max_retries_default", 3)
_define("actor_max_restarts_default", 0)
_define("actor_scheduling_timeout_s", 90.0,
        "how long a pending actor waits for a feasible node before failing; "
        "the deadline restarts whenever a new node registers, so autoscaler "
        "provisioning slower than this does not kill pending actors "
        "(reference: GcsActorScheduler queues indefinitely)")
_define("health_check_period_ms", 1000,
        "reference: gcs_health_check_manager.h health_check_period_ms")
_define("health_check_failure_threshold", 5)
_define("gcs_lease_ttl_s", 3.0,
        "primary GCS advertised-address lease TTL: the primary renews "
        "the on-disk lease every ttl/3 while it holds agent-heartbeat "
        "majority; a warm standby takes over (bumping the cluster "
        "epoch) only after the lease has been stale for a full TTL")
_define("gcs_standby_poll_ms", 100,
        "warm-standby journal-tail and lease-check poll interval")
_define("gcs_lease_heartbeat_fresh_s", 0.0,
        "heartbeat freshness window for the lease-renewal majority "
        "condition; 0 = auto (4x resource_report_period_ms, min 2s). "
        "A primary that cannot see fresh heartbeats from a majority of "
        "alive agents stops renewing — so a primary partitioned from "
        "the cluster yields, while a standby partitioned from a "
        "healthy primary never steals the lease (split-brain guard)")
_define("journal_snapshot_every_bytes", 64 * 1024 * 1024,
        "GCS journal compaction threshold: when the journal file "
        "exceeds this many bytes, the primary writes a full-table "
        "snapshot record to a fresh file and atomically replaces the "
        "journal (replay = snapshot + suffix); 0 disables compaction. "
        "Standby tailers detect the replacement by inode change and "
        "re-read from the snapshot")
_define("resource_report_period_ms", 250,
        "ray_syncer-equivalent periodic resource view broadcast")
_define("lineage_max_entries", 100_000,
        "owner-side lineage cap (reference: task_manager.h max_lineage_bytes)")
_define("object_spill_dir", "", "empty = <session_dir>/spill")
_define("object_spill_external_uri", "",
        "external/cloud spill tier (reference: _private/external_storage"
        ".py:398 smart_open impl): when set (file:///shared/mount, "
        "mock://bucket/prefix, or a registered custom scheme), every "
        "local spill also uploads a durable copy and registers its URI "
        "in the GCS KV, so any node can restore a dead node's spilled "
        "objects without lineage re-execution")
_define("object_spill_threshold", 0.8,
        "fraction of store capacity above which pinned primaries spill "
        "proactively (reference: local_object_manager.h spill threshold)")
_define("object_transfer_chunk_bytes", 8 * 1024 * 1024,
        "inter-node object transfer chunk size "
        "(reference: object_manager chunked push, default 5MiB chunks)")
_define("max_concurrent_pulls", 16,
        "per-node cap on simultaneous inbound object pulls "
        "(reference: pull_manager.cc bundle admission)")
_define("object_transfer_max_inflight_chunks", 8,
        "chunk requests kept in flight per object pull — pipelines the "
        "source's shm read / spill-file read under the wire transfer "
        "(reference: object_manager.cc overlapping chunked push)")
_define("object_transfer_chunk_timeout_s", 30.0,
        "per-chunk fetch deadline during a pull; an expired chunk retries "
        "on the same source then fails over to alternates")
_define("task_arg_fetch_timeout_s", 600.0,
        "bound on an executing task's by-reference arg fetch; a freed or "
        "unrecoverable arg fails the task instead of wedging the worker")
_define("create_backpressure_timeout_s", 30.0,
        "how long a plasma put waits for spill/eviction to make room before "
        "failing (reference: plasma create_request_queue semantics)")
_define("create_queue_depth", 32,
        "bound on the agent's FIFO create-admission queue (the "
        "CreateRequestQueue analogue): puts/seals that cannot reserve "
        "arena headroom park here while eviction/spill makes room; a "
        "full queue or an expired deadline fails the create TYPED as "
        "ObjectStoreFullError(retry_after_s) — never a raw arena "
        "exception (reference: plasma create_request_queue.h)")
_define("eviction_pinned_bytes_floor", 0,
        "pressure sweeps never spill arena-resident pinned primaries "
        "below this many bytes (0 = no floor): keeps a hot working set "
        "resident even under admission pressure; re-fetchable "
        "secondaries are always dropped first regardless of the floor")
_define("lease_shed_pressure_threshold", 0.95,
        "when the node's shared memory-pressure signal (max of arena "
        "occupancy, node RAM, KV pool, chaos squeeze) is at or above "
        "this fraction, lease granting prefers spilling tasks back to a "
        "feasible peer over granting locally — memory_monitor feeds the "
        "same signal the create queue drains.  Only sheds when a "
        "spillback target exists; a sole node always grants")
_define("kv_cache_demotion_enabled", True,
        "LRU-evicted prefix-cache pages demote into host/NVMe KV parts "
        "(the external-KV part format) instead of being freed; a later "
        "prefix hit promotes them back via direct re-install + "
        "device_put, so cache hit rate survives page-pool pressure")
_define("kv_demoted_bytes_limit", 256 * 1024 * 1024,
        "byte bound on the demoted prefix-cache tier; the host window "
        "holds the hot tail and overflows to NVMe files under the spill "
        "dir, oldest demoted entries are dropped past the bound")
_define("mem_chaos", "",
        "memory-pressure chaos: 'arena=frac:period_s[,pool=frac]' — "
        "squeeze the EFFECTIVE arena budget to frac of capacity (and "
        "optionally the KV page pool to pool-frac) during alternate "
        "half-periods, then restore it; drives spill/eviction/"
        "backpressure and KV demotion under load, composing with "
        "process/link chaos (empty = off)")
_define("rpc_connect_retries", 10)
_define("rpc_connect_retry_delay_s", 0.2)
_define("rpc_native_framer", True,
        "run RPC wire framing through the _rpcframe.so C extension "
        "(src/rpcframe): C stream scanner + raw chunks recv'd straight "
        "into the shm arena + vectored writev frame waves (reference: "
        "Ray keeps its whole rpc/object-transfer plane in C++, "
        "src/ray/rpc + object_manager).  Per process/node; the wire "
        "format is identical to the pure-Python framer, so clusters may "
        "mix modes freely.  Off, a missing compiler, or a corrupt .so "
        "all fall back to pure Python (warn once, never an error)")
_define("daemon_io_shards", -1,
        "I/O shards for the daemon RPC planes (GCS and node agents): "
        "accepted connections are distributed round-robin across this "
        "many per-shard event-loop THREADS, each running the full wire "
        "path (framing, msgpack codec, native-framer recv/writev) for "
        "its connections; handlers that only touch the arena/io run "
        "entirely on their shard, state-mutating handlers hop to the "
        "daemon's main loop in ONE batched call_soon_threadsafe per "
        "ready-wave.  -1 = auto (min(4, cpu cores)); 0 = single-loop "
        "mode (everything on the main loop, exactly the pre-shard "
        "behavior).  The wire format is identical in both modes, so "
        "clusters may mix sharded and unsharded daemons freely "
        "(reference: Ray's GCS and raylet run their gRPC services on "
        "dedicated C++ executor thread pools)")
_define("control_call_timeout_s", 60.0,
        "default deadline for unary control-plane RPCs whose call site "
        "passes no timeout: a half-open connection (gray peer, asymmetric "
        "partition) can then never hang a caller forever.  Streaming-ish "
        "calls that legitimately block (actor pushes, stream "
        "backpressure, object long-polls) opt out with explicit "
        "timeout=0; 0 here disables the default entirely")
_define("replica_directory_enabled", True,
        "owners track EVERY holder of a plasma object (primary + pulled "
        "secondaries, reference: the ownership table tracks all object "
        "locations, Ownership NSDI'21): pulls stamp the full from_addrs "
        "set (hedging/failover get real alternates), concurrent pulls "
        "stripe chunks across holders (Cornet-style swarm broadcast), "
        "and the scheduler scores bytes-already-local placement")
_define("replica_directory_max_secondaries", 8,
        "per-object cap on tracked secondary holders (oldest registration "
        "dropped first; secondaries are evictable caches, so a dropped "
        "entry only costs the swarm a source)")
_define("object_locality_scheduling_enabled", True,
        "score default-strategy placement by bytes already local to each "
        "candidate node (task-spec ref-arg location hints); never "
        "overrides feasibility, labels, or trusted-first ordering")
_define("object_locality_min_bytes", 1024 * 1024,
        "ignore locality below this many hinted arg bytes — tiny args "
        "re-fetch faster than a misplaced lease costs")
_define("arg_prefetch_enabled", True,
        "on lease grant the agent immediately starts pulling the lease's "
        "missing large by-reference args, overlapping the fetch with "
        "worker dispatch/queueing (reference: the raylet pulls task args "
        "during lease setup, pull_manager task-arg bundles)")
_define("arg_prefetch_min_bytes", 1024 * 1024,
        "only prefetch args at least this large: small args resolve "
        "through the owner faster than a pull round-trip")
_define("pull_hedge_enabled", True,
        "race a backup source for a pull chunk once the primary exceeds "
        "its observed p95 latency (Dean & Barroso hedged requests); "
        "needs >=2 sources (from_addrs) to engage")
_define("pull_hedge_delay_ms", 0,
        "hedge delay override; 0 = adaptive (per-peer p95 of recent "
        "chunk fetches, 200ms until enough samples)")
_define("pull_hedge_budget_fraction", 0.1,
        "cap on hedged fetches as a fraction of total chunk fetches "
        "(plus a small burst) so hedging cannot amplify load on an "
        "already-throttled cluster")
_define("gray_bulk_drain_exempt_bytes_per_s", 8 * 1024 * 1024,
        "hold the gray AUTO-DRAIN (placement deprioritization still "
        "applies) while a suspect node is moving at least this much "
        "object-plane data per second between heartbeats — a node "
        "serving a weight broadcast is busy, not gray, and evacuating "
        "it would kill the transfer that inflated its probe RTT; 0 "
        "disables the exemption")
_define("gray_suspicion_threshold", 0.6,
        "per-node suspicion score (0..1, EMA of RTT-vs-cluster-baseline "
        "and heartbeat-staleness evidence) above which a node is "
        "treated as gray-suspect: placement deprioritizes it and, "
        "sustained, it is auto-drained")
_define("gray_sustained_s", 5.0,
        "how long suspicion must stay above the threshold before the "
        "GCS auto-drains the node with reason='gray' (0 disables the "
        "sustain requirement, not the drain)")
_define("gray_auto_drain", True,
        "auto-trigger drain_node(reason='gray') for a sustained-suspect "
        "node (detect -> avoid -> evacuate); never drains the last "
        "healthy node")
_define("gray_min_rtt_ms", 100.0,
        "absolute RTT floor below which a node is never gray-suspect "
        "(pure ratio-to-baseline would flag healthy microsecond-RTT "
        "nodes on an idle cluster)")
_define("gray_rtt_ratio", 3.0,
        "probe/peer RTT must also exceed this multiple of the cluster "
        "median RTT to count as gray evidence")
_define("rpc_chaos", "",
        "deterministic RPC fault injection: 'Method=N:req%:resp%' "
        "(reference: src/ray/rpc/rpc_chaos.cc RAY_testing_rpc_failure)")
_define("link_chaos", "",
        "deterministic link-level fault injection on the RPC byte "
        "stream: '[match/]kind=fields,...' with kind in out_delay|"
        "in_delay|out_bw|in_bw|out_drop|in_drop — per-peer delay+jitter, "
        "bandwidth throttling, and ASYMMETRIC partitions (out_drop "
        "blackholes A->B while B->A flows); enabling it process-wide on "
        "one node is slow-node mode (_private/chaos.py LinkChaos)")
_define("process_chaos", "",
        "deterministic process-kill fault injection for cluster fixtures: "
        "'class=N:period_s[:delay_s]' with class in worker|agent|gcs — "
        "SIGKILLs N processes of that class, one every period_s seconds "
        "(first after delay_s); mirrors the rpc_chaos spec style but "
        "exercises the CRASH paths message drops never reach "
        "(_private/chaos.py ProcessChaos)")
_define("node_drain_deadline_s", 30.0,
        "default deadline for the two-phase graceful node drain "
        "(drain_node without an explicit deadline_s): the GCS stops "
        "scheduling onto the node, restarts its actors elsewhere and "
        "migrates sole primary object copies off it, then falls back to "
        "the hard-kill death path when the deadline expires "
        "(reference: autoscaler.proto DrainNode deadline_timestamp_ms)")
_define("grant_or_reject_spillback", True)
_define("scheduler_top_k_fraction", 0.2,
        "hybrid policy: pick among best-k nodes "
        "(reference: hybrid_scheduling_policy.h)")
_define("scheduler_spread_threshold", 0.5,
        "node utilization below which hybrid policy packs "
        "(reference: RAY_scheduler_spread_threshold)")
_define("put_small_object_in_memory_store", True)
_define("metrics_report_interval_ms", 2000)
_define("event_buffer_max_events", 10_000)
_define("task_event_flush_interval_s", 1.0,
        "task-event + metric buffer flush period "
        "(reference: task_event_buffer.h report interval)")
_define("gcs_task_events_max", 100_000,
        "GCS-side ring buffer cap on retained task events "
        "(reference: RAY_task_events_max_num_task_in_gcs)")
_define("log_rotation_bytes", 100 * 1024 * 1024)

# ---- observability: flight recorder + clock alignment -----------------------
_define("flight_recorder_enabled", True,
        "per-process flight recorder: preallocated ring of plane-level "
        "events (lease lifecycle, object-transfer timelines) flushed "
        "over the existing heartbeat/telemetry batching — no new "
        "per-event RPCs (reference: Ray's task_event_buffer + "
        "src/ray/util/event.h bounded in-memory event rings)")
_define("flight_recorder_capacity", 4096,
        "flight-recorder ring slots per process; overflow drops the "
        "OLDEST record and counts it (exported as "
        "ray_tpu_flight_recorder_dropped_total)")
_define("flight_recorder_categories", "",
        "comma-separated category gate for the flight recorder "
        "(lease,transfer,request,anomaly); empty = all "
        "categories on")
_define("flight_recorder_sample_n", 1,
        "record 1 of every N instant events per category (spans are "
        "never sampled away); 1 = record everything")
_define("clock_align_enabled", True,
        "estimate per-node wall-clock offsets from the GCS health-loop "
        "RTT probes (NTP-style theta = ((t1-t0)+(t2-t3))/2, min-RTT "
        "filtered + smoothed), stamp them into node views, and apply "
        "them in timeline rendering so cross-node spans nest correctly")
_define("clock_skew_s", 0.0,
        "CHAOS: shift this process's telemetry wall clock by this many "
        "seconds (clocks.wall()).  Set per node via _system_config to "
        "fake disagreeing host clocks; the agent forwards it to its "
        "workers' env so the whole node skews coherently")
_define("metrics_export_enabled", True,
        "ship each daemon's util.metrics registry + runtime gauges "
        "(arena occupancy, lease queue depth, io_stats, copy-audit, "
        "recorder drops) to the GCS on its heartbeat/telemetry tick; "
        "the dashboard /metrics exposition then carries node_id-labeled "
        "series for every node")

# ---- observability: diagnosis plane (watchdogs + black-box capture) ---------
_define("diagnosis_enabled", True,
        "run the per-daemon hung-work watchdogs (wedged event loops, "
        "tasks RUNNING past their historical p95, leases "
        "granted-but-never-RUNNING, serving requests "
        "admitted-but-token-silent); each detector emits a typed "
        "`anomaly` flight-recorder event and a ray_tpu_anomaly_total "
        "counter (reference spirit: Google-Wide Profiling / Tail at "
        "Scale always-on anomaly capture)")
_define("diagnosis_poll_ms", 500,
        "watchdog thread poll period; detectors are O(tracked work) "
        "dict scans, so this bounds detection latency, not overhead")
_define("diagnosis_loop_wedge_s", 5.0,
        "a loopmon entry stale at least this long while its thread is "
        "still alive is a WEDGED loop (not a stopped one) -> dump its "
        "stack via sys._current_frames from the watchdog thread")
_define("diagnosis_task_hang_multiple", 20.0,
        "a task RUNNING longer than this multiple of its function's "
        "historical p95 (EMA over completed runs) is flagged hung")
_define("diagnosis_task_hang_min_s", 10.0,
        "floor on the per-function hang threshold so short functions "
        "with microsecond p95s don't flap")
_define("diagnosis_task_hang_default_s", 120.0,
        "hang threshold for functions with no completion history yet")
_define("diagnosis_lease_stall_s", 15.0,
        "a lease granted this long ago whose worker has started zero "
        "tasks since the grant (and runs none now) is a stalled lease "
        "-> the owner likely wedged or the push never arrived")
_define("diagnosis_serving_silence_s", 15.0,
        "a serving request admitted into a decode batch but token-silent "
        "this long is flagged (decode loop wedged or request starved)")
_define("anomaly_capture_enabled", True,
        "when a detector fires, the GCS snapshots the implicated nodes "
        "(recorder drain, stacks, CPU profile, metrics, node views) "
        "into a diag-<kind>-<ts>/ black-box bundle")
_define("diagnosis_capture_dir", "",
        "bundle output directory; empty = <session_dir>/diagnosis")
_define("diagnosis_capture_min_interval_s", 60.0,
        "per-anomaly-kind rate limit on bundle capture: a flapping "
        "detector keeps counting but cannot DoS the cluster with "
        "bundle I/O inside this window")
_define("diagnosis_capture_profile_s", 2.0,
        "CPU-profile sampling window captured into each bundle")
_define("diagnosis_capture_max_bundles", 20,
        "oldest bundles are pruned beyond this many (disk bound)")
_define("diagnosis_chaos_enabled", False,
        "CHAOS: expose debug handlers that wedge daemon loops on "
        "purpose (tests only; never enable in production)")


class Config:
    """Resolved config: defaults < env (RAY_TPU_<name>) < _system_config."""

    def __init__(self, system_config: Dict[str, Any] | None = None):
        self._values: Dict[str, Any] = {}
        for name, (typ, default, _doc) in _REGISTRY.items():
            val = default
            env = os.environ.get(f"RAY_TPU_{name}")
            if env is not None:
                val = _parse(typ, env)
            self._values[name] = val
        for k, v in (system_config or {}).items():
            if k not in _REGISTRY:
                raise ValueError(f"unknown _system_config key: {k}")
            self._values[k] = v

    def __getattr__(self, name):
        try:
            return self._values[name]
        except KeyError:
            raise AttributeError(name)

    def to_dict(self) -> Dict[str, Any]:
        return dict(self._values)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "Config":
        cfg = cls.__new__(cls)
        cfg._values = dict(d)
        return cfg


def _parse(typ, s: str):
    if typ is bool:
        return s.lower() in ("1", "true", "yes")
    if typ in (int, float):
        return typ(s)
    if typ in (dict, list):
        return json.loads(s)
    return s


def resolve_io_shards(cfg: "Config" | None = None) -> int:
    """Effective daemon I/O shard count: the configured value, with -1
    (auto) resolving to min(4, cpu cores).  0 disables sharding."""
    cfg = cfg or get_config()
    n = int(cfg.daemon_io_shards)
    if n < 0:
        n = min(4, os.cpu_count() or 1)
    return max(0, n)


_global: Config | None = None


def get_config() -> Config:
    global _global
    if _global is None:
        _global = Config()
    return _global


def set_config(cfg: Config):
    global _global
    _global = cfg
