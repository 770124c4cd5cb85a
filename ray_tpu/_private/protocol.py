"""Wire-protocol message shapes shared by driver, workers, agent, and GCS.

Plays the role of the reference's protobuf schemas (reference:
src/ray/protobuf/{common,gcs_service,core_worker,node_manager}.proto), but as
msgpack-friendly plain dicts: the control plane is Python asyncio, so a
schema-compiler adds latency without type safety we can't get anyway. Field
names below are the single source of truth; every service cites these helpers.
"""

from __future__ import annotations

import hashlib
from typing import Any, Dict, List, Optional, Tuple

# Address = [host, port] (TCP) or "path" (unix socket); msgpack-safe.
Address = Any


def concat_parts(parts) -> bytes:
    """Join serialized parts (see serialization.py) into one bytes payload.
    bytes.join consumes buffer-protocol parts (memoryviews) directly, so
    this is a single-allocation single-pass copy — no per-part bytes()."""
    return b"".join(parts)


def function_id(pickled: bytes) -> bytes:
    return hashlib.sha1(pickled).digest()[:16]


def ref_locations(raw) -> List[Tuple]:
    """Normalize a ref entry's location hint (`e["ref"][2]`) to a list of
    address tuples, primary first.

    Accepts every shape that has ever been on the wire: None (no hint),
    a single `[host, port]` address (pre-directory peers published one
    primary), or the replica-directory list `[[host, port], ...]`."""
    if not raw:
        return []
    first = raw[0]
    if isinstance(first, (list, tuple)):
        return [tuple(a) for a in raw]
    return [tuple(raw)]


def make_task_spec(
    *,
    task_id: bytes,
    job_id: bytes,
    fn_id: bytes,
    args: List[dict],
    nreturns: int,
    owner_addr: Address,
    resources: Dict[str, float],
    retries_left: int = 0,
    actor_id: Optional[bytes] = None,
    method: Optional[str] = None,
    seq: int = 0,
    scheduling_strategy: Optional[dict] = None,
    runtime_env: Optional[dict] = None,
    name: str = "",
    streaming: Optional[dict] = None,
    deadline: Optional[float] = None,
) -> dict:
    """Equivalent of the reference's TaskSpecification (common/task/).

    args entries:
      {"v": bytes}                          — inline serialized value
      {"ref": [id_bytes, owner_addr, locations], "sz": nbytes}
                                            — by-reference
    `locations` is the owner's replica-directory snapshot at submit time
    (list of node addresses holding a copy, PRIMARY FIRST) or None when
    unknown; legacy peers sent a single address — use `ref_locations` to
    consume either shape.  `sz` (optional) is the serialized size of the
    referenced object: together they feed the locality-aware scheduler's
    bytes-already-local score and the agent's arg prefetch.
    """
    return {
        "task_id": task_id,
        "job_id": job_id,
        "fn_id": fn_id,
        "args": args,
        "nreturns": nreturns,
        "owner_addr": owner_addr,
        "resources": resources,
        "retries_left": retries_left,
        "actor_id": actor_id,
        "method": method,
        "seq": seq,
        "scheduling_strategy": scheduling_strategy,
        "runtime_env": runtime_env,
        "name": name,
        # {"bp": N} for streaming-generator tasks (num_returns="streaming");
        # absent/None for regular tasks.
        "streaming": streaming,
        # Absolute wall-clock (time.time()) end-to-end deadline from
        # .options(timeout_s=...), or None.  Travels with the spec across
        # every hop (driver -> agent -> worker -> nested submits) so the
        # remaining budget composes instead of stacking per-hop constants;
        # enforced owner-side (DeadlineExceededError on the return refs)
        # and checked worker-side before execution.
        "deadline": deadline,
        # {"trace_id", "span_id"} of the submitting span when tracing is
        # enabled (reference: remote_function.py:344 — tracing context
        # injected into every submit; workers chain execution spans to
        # it).  make_task_spec is the single choke point every task and
        # actor call flows through, so injection lives here.
        "trace": _trace_inject(),
    }


# --------------------------------------------------------------------------
# Pre-encoded spec prefixes (submit/complete fast path; see
# docs/control_plane.md).  A task spec splits into a STABLE prefix — every
# field that is constant across calls of one RemoteFunction / actor handle
# (fn_id, resources, owner, scheduling strategy, runtime env, job) — and a
# small per-call DELTA (task id, args, retries, seq, ...).  The prefix is
# msgpack-encoded ONCE and shipped as an opaque blob inside each
# submit_batch frame; the receiver decodes it once per batch (and caches
# the decode by blob), then reconstructs each spec as {**prefix, **delta}.
# This removes the per-call serialize/deserialize of the ~16 stable fields
# that dominated control-plane CPU under task fan-out.
# --------------------------------------------------------------------------

# Fields that may differ between two tasks sharing a prefix.  Everything
# else MUST be byte-identical across the batch (guaranteed by grouping:
# normal tasks batch per scheduling key + owner, actor tasks per handle).
SPEC_VOLATILE = ("retries_left", "nreturns", "streaming", "trace",
                 "method", "seq", "name", "deadline")


def spec_prefix_of(spec: dict) -> dict:
    """Normalize one sample spec into the stable prefix every delta is
    applied on top of: per-call fields reset to their cheapest defaults so
    a large inline arg (or a task id) can never be frozen into the blob."""
    p = dict(spec)
    p["task_id"] = b""
    p["args"] = []
    p["retries_left"] = 0
    p["seq"] = 0
    p["trace"] = None
    p["streaming"] = None
    p["deadline"] = None
    return p


def spec_delta(prefix: dict, spec: dict) -> dict:
    """Per-call wire delta: task id + args always, plus any volatile field
    that differs from the prefix.  {**prefix, **delta} == spec exactly."""
    d = {"task_id": spec["task_id"], "args": spec["args"]}
    for k in SPEC_VOLATILE:
        v = spec.get(k)
        if v != prefix.get(k):
            d[k] = v
    return d


def encode_prefix(prefix: dict) -> bytes:
    """Pack the stable prefix once; the blob is reused verbatim on every
    submit_batch frame (and is the receiver's decode-cache key)."""
    import msgpack
    return msgpack.packb(prefix, use_bin_type=True)


def decode_prefix(blob: bytes) -> dict:
    import msgpack
    return msgpack.unpackb(blob, raw=False, strict_map_key=False)


_tracing = None


def _trace_inject():
    # Module cached on first use (util.tracing has no _private imports, but
    # a top-level import would still cycle through ray_tpu/__init__): the
    # per-submit cost is one contextvar read.
    global _tracing
    if _tracing is None:
        from ..util import tracing as _t
        _tracing = _t
    return _tracing.inject()


def scheduling_key(fn_id: bytes, resources: Dict[str, float],
                   strategy: Optional[dict],
                   runtime_env: Optional[dict] = None) -> bytes:
    """Tasks with the same key can share leased workers (reference:
    NormalTaskSubmitter lease caching by SchedulingKey — which includes
    the runtime env, since envs shape the worker process)."""
    h = hashlib.sha1(fn_id)
    for k in sorted(resources):
        h.update(k.encode())
        h.update(str(resources[k]).encode())
    if strategy:
        h.update(repr(sorted(strategy.items())).encode())
    if runtime_env:
        from .runtime_env import runtime_env_hash
        h.update(runtime_env_hash(runtime_env))
    return h.digest()[:16]


# Actor states (reference: gcs.proto ActorTableData.ActorState)
ACTOR_PENDING = "PENDING_CREATION"
ACTOR_ALIVE = "ALIVE"
ACTOR_RESTARTING = "RESTARTING"
ACTOR_DEAD = "DEAD"

# Node states (reference: gcs.proto GcsNodeInfo.GcsNodeState + the
# autoscaler's DRAINING drain protocol, autoscaler.proto DrainNode).
# DRAINING is the two-phase departure state: the node finishes what it
# has (in-flight leases, actor hand-off, primary-object migration) but
# receives no new work; at the drain deadline it transitions to DEAD.
NODE_ALIVE = "ALIVE"
NODE_DRAINING = "DRAINING"
NODE_DEAD = "DEAD"

# Drain reasons (reference: autoscaler.proto DrainNodeReason —
# preemption carries a deadline the cloud enforces; idle drains come
# from the autoscaler; manual from operators/tests).
DRAIN_PREEMPTION = "preemption"
DRAIN_IDLE = "idle"
DRAIN_MANUAL = "manual"
# Gray-failure evacuation: the health scorer found the node alive but
# sustained-suspect (slow links, lossy NIC, asymmetric partition) and
# auto-triggered the drain — detect -> avoid -> evacuate.
DRAIN_GRAY = "gray"

# Pubsub channels (reference: pubsub channel types in gcs.proto)
CH_ACTOR = "actor"
CH_NODE = "node"
CH_ERROR = "error"
CH_LOG = "log"
CH_PG = "placement_group"

# Cluster epoch — the fencing token of GCS high availability
# (reference: Raft terms, Ongaro & Ousterhout; leader leases with
# monotonic epochs).  The epoch is a journaled monotonic integer bumped
# exactly once per failover, BEFORE the new primary serves a single
# request.  It is stamped into every lease grant, node registration and
# actor-placement decision under the EPOCH_KEY field; agents and core
# workers reject grants minted under an older epoch (StaleEpochError)
# and the primary rejects mutations carrying a stale one.  EPOCH_NONE
# marks a participant that has not yet learned any epoch (accepts the
# first one it sees).
EPOCH_KEY = "cluster_epoch"
EPOCH_NONE = 0

# Typed-rejection marker used on the wire when an agent refuses a
# stale-epoch lease operation: replies carry {"granted": False,
# "reject": REJECT_STALE_EPOCH, EPOCH_KEY: <current>} so owners can
# distinguish fencing from plain resource exhaustion.
REJECT_STALE_EPOCH = "stale_epoch"

# Reasons a lease / actor creation is refused for good: the submitter (or
# the GCS actor scheduler) fails the work instead of retrying.  Matched as
# substrings of the refusal text, like "runtime env setup failed".
LEASE_REFUSED = "lease refused"
ACTOR_INIT_RAISED = "actor __init__ raised"

# GCS high-availability files, all under the session dir (the shared
# path both the primary and the warm standby can reach):
#   GCS_ADDRESS_FILE — the ADVERTISED address: {"address": [h, p],
#     "cluster_epoch": e}.  Atomically replaced by whichever instance
#     currently holds the lease; every client re-reads it through
#     resolve_gcs_address() on every reconnect attempt, so failover
#     re-homing rides the existing jittered dial backoff.
#   GCS_LEASE_FILE — the primary's liveness lease: {"epoch",
#     "renewed" (wall), "ttl_s", "owner_pid", "address"}.  Renewed
#     every ttl/3 while the primary holds agent-heartbeat majority; a
#     standby takes over only once the lease has gone a full TTL
#     without renewal, and an ex-primary that observes a HIGHER epoch
#     in this file is fenced (refuses writes and exits).
#   GCS_STANDBY_FILE — the standby's tail progress: {"lag_bytes",
#     "ts", "pid"}; the primary exports it as the standby-lag gauges.
GCS_ADDRESS_FILE = "gcs_address.json"
GCS_LEASE_FILE = "gcs_lease.json"
GCS_STANDBY_FILE = "gcs_standby.json"


def resolve_gcs_address(session_dir: Optional[str], fallback=None):
    """Current advertised GCS address for a session, or `fallback`.

    The ONE address-resolution helper every reconnect path routes
    through (agents, core workers, drivers): reading the session's
    address file at dial time — instead of trusting the address cached
    from init()/argv forever — is what lets a failover (or a plain
    address change) re-home clients without process restarts."""
    if session_dir:
        import json
        import os
        try:
            with open(os.path.join(session_dir, GCS_ADDRESS_FILE)) as f:
                info = json.load(f)
            addr = info.get("address")
            if addr and len(addr) >= 2:
                return (addr[0], int(addr[1]))
        except (OSError, ValueError, TypeError):
            pass
    return fallback
