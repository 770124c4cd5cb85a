"""Collective communication groups over actors/workers.

Reference: python/ray/util/collective/collective.py — GroupManager (:76),
init_collective_group (:182), declarative create_collective_group (:222),
ops allreduce (:339) / reduce (:392) / broadcast (:454) / allgather (:504)
/ reducescatter (:553) / send-recv (:612/:675) / barrier (:379), with
NCCL/GLOO backends (collective_group/nccl_collective_group.py:121).

TPU-native backends (SURVEY.md §2.4 XlaCollectiveGroup plan):
- "host": CPU/numpy collectives rendezvoused through the GCS KV store —
  the DCN/control-plane tier, standing in for the reference's gloo group.
  Each op is a (group, seq) round: members publish contributions and read
  peers' (reference: NCCL Rendezvous shares its unique id through the
  internal KV the same way, nccl_collective_group.py:29-120).
- "xla": in-graph collectives over ICI for jax arrays — compiled psum /
  all_gather over the process's mesh; the heavy-data tier.  Requires the
  jax.distributed world the Train backend forms (train/backend.py).

Collective calls must be issued in the same order by every member of a
group (the reference's NCCL semantics carry the same requirement).
"""

from __future__ import annotations

import pickle
import time
from typing import Any, Dict, List, Optional

import numpy as np

import ray_tpu

_POLL_S = 0.002
_NS = "collective"


class _KV:
    """Thin sync KV client on the GCS (namespaced)."""

    @staticmethod
    def put(key: str, value: bytes, overwrite: bool = True) -> bool:
        return ray_tpu._core().gcs_call(
            "kv_put", {"ns": _NS, "key": key, "value": value,
                       "overwrite": overwrite})

    @staticmethod
    def get(key: str) -> Optional[bytes]:
        return ray_tpu._core().gcs_call("kv_get", {"ns": _NS, "key": key})

    @staticmethod
    def wait(key: str, timeout: float) -> bytes:
        deadline = time.monotonic() + timeout
        poll = _POLL_S
        while True:
            v = _KV.get(key)
            if v is not None:
                return v
            if time.monotonic() > deadline:
                raise TimeoutError(f"collective rendezvous timed out on "
                                   f"{key!r}")
            time.sleep(poll)
            poll = min(poll * 1.5, 0.05)

    @staticmethod
    def delete_prefix(key: str) -> int:
        return ray_tpu._core().gcs_call(
            "kv_del", {"ns": _NS, "key": key, "prefix": True})


REDUCE_OPS = {
    "sum": lambda arrs: np.sum(arrs, axis=0),
    "product": lambda arrs: np.prod(arrs, axis=0),
    "min": lambda arrs: np.min(arrs, axis=0),
    "max": lambda arrs: np.max(arrs, axis=0),
}


class HostCollectiveGroup:
    """KV-rendezvous collectives for host (numpy) data."""

    def __init__(self, group_name: str, world_size: int, rank: int,
                 timeout_s: float = 60.0):
        self.name = group_name
        self.world_size = world_size
        self.rank = rank
        self.timeout_s = timeout_s
        self._seq = 0
        self._p2p_seq: Dict[tuple, int] = {}

    # ------------------------------------------------------------ internals

    def _round(self, payload: bytes, op_tag: str) -> List[bytes]:
        """All-to-all publish + collect for one collective round."""
        self._seq += 1
        base = f"{self.name}/{self._seq}/{op_tag}"
        _KV.put(f"{base}/{self.rank}", payload)
        out = []
        for r in range(self.world_size):
            out.append(payload if r == self.rank else
                       _KV.wait(f"{base}/{r}", self.timeout_s))
        # Round N-2 is globally complete once every rank entered round N
        # (all contributions for N are only written after N-1 was read by
        # that rank), so lag-2 cleanup never races slow readers.
        if self.rank == 0 and self._seq >= 3:
            _KV.delete_prefix(f"{self.name}/{self._seq - 2}/")
        return out

    # ------------------------------------------------------------------ ops

    def allreduce(self, tensor: np.ndarray, op: str = "sum") -> np.ndarray:
        parts = self._round(pickle.dumps(np.asarray(tensor)), "ar")
        return REDUCE_OPS[op]([pickle.loads(p) for p in parts])

    def reduce(self, tensor: np.ndarray, dst_rank: int = 0,
               op: str = "sum") -> np.ndarray:
        """Binomial-tree reduce toward dst_rank: each rank reads at most
        log2(W) partials and writes one, vs the W-reads-per-rank of a
        full allreduce (reference: collective.py:392 reduce is a true
        rooted reduction, not allreduce-at-everyone)."""
        if self.world_size == 1:
            return np.asarray(tensor)
        self._seq += 1
        base = f"{self.name}/{self._seq}/rd"
        acc = np.asarray(tensor)
        # Virtual ranks place dst at 0 so the standard binomial recursion
        # roots there.
        vr = (self.rank - dst_rank) % self.world_size
        mask = 1
        while mask < self.world_size:
            if vr & mask:
                # Leaf for this level: ship the partial up and stop
                # combining.
                _KV.put(f"{base}/{self.rank}", pickle.dumps(acc))
                break
            child_vr = vr + mask
            if child_vr < self.world_size:
                child = (child_vr + dst_rank) % self.world_size
                part = pickle.loads(
                    _KV.wait(f"{base}/{child}", self.timeout_s))
                acc = REDUCE_OPS[op]([acc, part])
            mask <<= 1
        if vr == 0:
            out = acc
            # Completion marker: non-dst ranks block on it, which (a)
            # keeps all ranks in lockstep rounds and (b) proves every
            # rank wrote this round before anyone advances — the
            # precondition the lag-2 cleanup relies on.
            _KV.put(f"{base}/done", b"1")
        else:
            _KV.wait(f"{base}/done", self.timeout_s)
            out = np.asarray(tensor)
        if self.rank == 0 and self._seq >= 3:
            _KV.delete_prefix(f"{self.name}/{self._seq - 2}/")
        return out

    def allgather(self, tensor: np.ndarray) -> List[np.ndarray]:
        parts = self._round(pickle.dumps(np.asarray(tensor)), "ag")
        return [pickle.loads(p) for p in parts]

    def broadcast(self, tensor: np.ndarray,
                  src_rank: int = 0) -> np.ndarray:
        self._seq += 1
        base = f"{self.name}/{self._seq}/bc"
        if self.rank == src_rank:
            _KV.put(f"{base}/src", pickle.dumps(np.asarray(tensor)))
            out = np.asarray(tensor)
        else:
            out = pickle.loads(_KV.wait(f"{base}/src", self.timeout_s))
        # confirmation half-round so src can't race ahead and delete
        self._round(b"", "bc_ack")
        return out

    def reducescatter(self, tensor: np.ndarray,
                      op: str = "sum") -> np.ndarray:
        """Chunked reduce-scatter: rank r publishes chunk j of its local
        tensor to rank j and reads only chunk r from each peer — O(N)
        bytes moved per rank instead of the O(W·N) an
        allreduce-then-slice pays (reference: collective.py:553)."""
        x = np.asarray(tensor)
        w = self.world_size
        if w == 1:
            return x
        self._seq += 1
        base = f"{self.name}/{self._seq}/rs"
        chunks = np.array_split(x, w, axis=0)
        for j in range(w):
            if j != self.rank:
                _KV.put(f"{base}/{self.rank}-{j}", pickle.dumps(chunks[j]))
        mine = [chunks[self.rank]]
        for r in range(w):
            if r != self.rank:
                mine.append(pickle.loads(
                    _KV.wait(f"{base}/{r}-{self.rank}", self.timeout_s)))
        # Symmetric round (every rank reads a write from every peer), so
        # the same lag-2 cleanup argument as _round applies.
        if self.rank == 0 and self._seq >= 3:
            _KV.delete_prefix(f"{self.name}/{self._seq - 2}/")
        return REDUCE_OPS[op](mine)

    def barrier(self) -> None:
        self._round(b"", "bar")

    def send(self, tensor: np.ndarray, dst_rank: int) -> None:
        key = (self.rank, dst_rank)
        self._p2p_seq[key] = self._p2p_seq.get(key, 0) + 1
        _KV.put(f"{self.name}/p2p/{self.rank}-{dst_rank}/"
                f"{self._p2p_seq[key]}",
                pickle.dumps(np.asarray(tensor)))

    def recv(self, src_rank: int) -> np.ndarray:
        key = (src_rank, self.rank)
        self._p2p_seq[key] = self._p2p_seq.get(key, 0) + 1
        k = f"{self.name}/p2p/{src_rank}-{self.rank}/{self._p2p_seq[key]}"
        v = _KV.wait(k, self.timeout_s)
        ray_tpu._core().gcs_call("kv_del", {"ns": _NS, "key": k,
                                            "prefix": False})
        return pickle.loads(v)

    def destroy(self) -> None:
        if self.rank == 0:
            _KV.delete_prefix(f"{self.name}/")


class XlaCollectiveGroup:
    """In-graph XLA collectives over the local (or jax.distributed-global)
    device set — the ICI tier.  Arrays are jax arrays; the reduction runs
    as a compiled psum/all_gather, so on a TPU slice it rides the
    interconnect exactly like pjit's collectives (SURVEY.md §5.8)."""

    def __init__(self, group_name: str, world_size: int, rank: int):
        import jax
        self.name = group_name
        self.world_size = world_size
        self.rank = rank
        self._bridge: Optional[HostCollectiveGroup] = None
        if world_size > 1 and jax.process_count() != world_size:
            raise RuntimeError(
                f"XlaCollectiveGroup({group_name}) needs a formed "
                f"jax.distributed world of {world_size} processes; this "
                f"process sees {jax.process_count()} (form it with the "
                "Train JaxConfig backend or jax.distributed.initialize)")

    def _global_mesh(self):
        import jax
        from jax.sharding import Mesh
        return Mesh(np.array(jax.devices()), ("p",))

    def allreduce(self, tensor, op: str = "sum"):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        mesh = self._global_mesh()
        n = len(mesh.devices)
        # Stack each process's contribution along a leading device axis,
        # psum it in-graph, read back the (replicated) result.
        x = jnp.asarray(tensor)
        if self.world_size == 1:
            return x
        from jax.experimental import multihost_utils
        stacked = multihost_utils.process_allgather(x)
        red = {"sum": jnp.sum, "product": jnp.prod, "min": jnp.min,
               "max": jnp.max}[op]
        return jax.jit(lambda s: red(s, axis=0))(stacked)

    def allgather(self, tensor):
        import jax.numpy as jnp
        if self.world_size == 1:
            return jnp.asarray(tensor)[None]
        from jax.experimental import multihost_utils
        return multihost_utils.process_allgather(jnp.asarray(tensor))

    def broadcast(self, tensor, src_rank: int = 0):
        import jax.numpy as jnp
        if self.world_size == 1:
            return jnp.asarray(tensor)
        from jax.experimental import multihost_utils
        return multihost_utils.broadcast_one_to_all(
            jnp.asarray(tensor), is_source=self.rank == src_rank)

    def barrier(self) -> None:
        if self.world_size == 1:
            return
        from jax.experimental import multihost_utils
        multihost_utils.sync_global_devices(f"ray_tpu:{self.name}")

    def reducescatter(self, tensor, op: str = "sum"):
        """In-graph psum_scatter over the process axis when the layout
        allows (sum, 1 device/process, divisible length): the reduction
        and the scatter ride ICI in one fused XLA collective, O(N)
        per-link instead of allgather's O(W·N).  Other shapes fall back
        to allreduce + slice."""
        import jax
        import jax.numpy as jnp
        x = jnp.asarray(tensor)
        if self.world_size == 1:
            return x
        if (op == "sum" and jax.local_device_count() == 1
                and x.shape[0] % self.world_size == 0):
            from jax.experimental import multihost_utils
            from jax.sharding import Mesh, PartitionSpec as P
            mesh = self._global_mesh()
            g = multihost_utils.host_local_array_to_global_array(
                x, mesh, P("p"))
            out = jax.jit(jax.shard_map(
                lambda s: jax.lax.psum_scatter(
                    s, "p", scatter_dimension=0, tiled=True),
                mesh=mesh, in_specs=P("p"), out_specs=P("p")))(g)
            return multihost_utils.global_array_to_host_local_array(
                out, mesh, P("p"))
        full = self.allreduce(tensor, op)
        return np.array_split(np.asarray(full), self.world_size,
                              axis=0)[self.rank]

    def reduce(self, tensor, dst_rank: int = 0, op: str = "sum"):
        out = self.allreduce(tensor, op)
        return out if self.rank == dst_rank else tensor

    # ------------------------------------------------------------------ p2p

    def _host_bridge(self) -> HostCollectiveGroup:
        # Lazily-built host-plane twin of this group: device arrays are
        # staged through host memory and the GCS KV (the DCN tier).
        # In-graph device-to-device transfers belong in lax.ppermute
        # inside a shard_map — this bridge covers the control-plane and
        # cross-mesh cases (reference: collective.py:612/:675 send/recv).
        if self._bridge is None:
            self._bridge = HostCollectiveGroup(
                f"{self.name}@xla-p2p", self.world_size, self.rank)
        return self._bridge

    def send(self, tensor, dst_rank: int):
        self._host_bridge().send(np.asarray(tensor), dst_rank)

    def recv(self, src_rank: int):
        import jax.numpy as jnp
        return jnp.asarray(self._host_bridge().recv(src_rank))

    def destroy(self) -> None:
        # Unconditional on rank 0: peers create the p2p bridge lazily, so
        # rank 0 may have no bridge while unconsumed sends from other
        # ranks still sit under the bridge namespace in the KV.
        if self.rank == 0:
            _KV.delete_prefix(f"{self.name}@xla-p2p/")
        self._bridge = None


BACKENDS = {"host": HostCollectiveGroup, "xla": XlaCollectiveGroup,
            "gloo": HostCollectiveGroup}


class GroupManager:
    """Per-process registry (reference: collective.py:76)."""

    def __init__(self):
        self._groups: Dict[str, Any] = {}

    def create(self, backend: str, group_name: str, world_size: int,
               rank: int):
        if group_name in self._groups:
            raise ValueError(f"group {group_name!r} already initialized "
                             "in this process")
        cls = BACKENDS[backend]
        g = cls(group_name, world_size, rank)
        self._groups[group_name] = g
        return g

    def get(self, group_name: str):
        g = self._groups.get(group_name)
        if g is None:
            g = self._lookup_declared(group_name)
        if g is None:
            raise RuntimeError(
                f"collective group {group_name!r} is not initialized in "
                "this process; call init_collective_group() or declare it "
                "with create_collective_group()")
        return g

    def _lookup_declared(self, group_name: str):
        """Declarative path: the driver stored membership in the KV keyed
        by actor id; first op inside the actor resolves its rank lazily
        (reference: create_collective_group + _check_inside_actor)."""
        me = ray_tpu.get_runtime_context().get_actor_id()
        if me is None:
            return None
        decl = _KV.get(f"decl/{group_name}")
        if decl is None:
            return None
        info = pickle.loads(decl)
        try:
            rank = info["actor_ids"].index(me)
        except ValueError:
            return None
        g = BACKENDS[info["backend"]](group_name, info["world_size"], rank)
        self._groups[group_name] = g
        return g

    def destroy(self, group_name: str):
        g = self._groups.pop(group_name, None)
        if g is not None:
            g.destroy()


_manager = GroupManager()


# -------------------------------------------------------------- public API


def init_collective_group(world_size: int, rank: int,
                          backend: str = "host",
                          group_name: str = "default"):
    """Imperative init, called by every member (reference:
    collective.py:182)."""
    return _manager.create(backend, group_name, world_size, rank)


def create_collective_group(actors: List[Any], world_size: int,
                            ranks: Optional[List[int]] = None,
                            backend: str = "host",
                            group_name: str = "default") -> None:
    """Declarative init from the driver (reference: collective.py:222):
    membership is stored in the KV; each actor resolves its rank on first
    op."""
    if len(actors) != world_size:
        raise ValueError("len(actors) must equal world_size")
    ranks = ranks or list(range(world_size))
    ordered = [None] * world_size
    for a, r in zip(actors, ranks):
        ordered[r] = a._actor_id
    _KV.put(f"decl/{group_name}", pickle.dumps({
        "backend": backend, "world_size": world_size,
        "actor_ids": ordered}))


def is_group_initialized(group_name: str = "default") -> bool:
    return group_name in _manager._groups


def destroy_collective_group(group_name: str = "default") -> None:
    _manager.destroy(group_name)


def get_rank(group_name: str = "default") -> int:
    return _manager.get(group_name).rank


def get_collective_group_size(group_name: str = "default") -> int:
    return _manager.get(group_name).world_size


def allreduce(tensor, group_name: str = "default", op: str = "sum"):
    return _manager.get(group_name).allreduce(tensor, op)


def reduce(tensor, dst_rank: int = 0, group_name: str = "default",
           op: str = "sum"):
    return _manager.get(group_name).reduce(tensor, dst_rank, op)


def broadcast(tensor, src_rank: int = 0, group_name: str = "default"):
    return _manager.get(group_name).broadcast(tensor, src_rank)


def allgather(tensor, group_name: str = "default"):
    return _manager.get(group_name).allgather(tensor)


def reducescatter(tensor, group_name: str = "default", op: str = "sum"):
    return _manager.get(group_name).reducescatter(tensor, op)


def barrier(group_name: str = "default") -> None:
    _manager.get(group_name).barrier()


def send(tensor, dst_rank: int, group_name: str = "default") -> None:
    _manager.get(group_name).send(tensor, dst_rank)


def recv(src_rank: int, group_name: str = "default"):
    return _manager.get(group_name).recv(src_rank)
