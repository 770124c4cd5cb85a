"""One process per chip: what the agent puts in a worker's environment, how
it hands out and takes back chip ids, and the refusals that keep a request
from waiting on something no node can grant.  No TPU here: chips are a
faked id list on a skeletal agent (injected `TPU` counts, as elsewhere in
the suite, mean a host WITHOUT chips)."""

import asyncio
import collections
import os
import subprocess
import sys
import types

import pytest

from ray_tpu._private import rpc
from ray_tpu._private.agent import NodeAgent, WorkerHandle
from ray_tpu._private.compile_cache import ENV as CACHE_ENV
from ray_tpu.tpu import accelerator
from ray_tpu.tpu.accelerator import TPUAcceleratorManager


def _agent(chips=()):
    a = NodeAgent.__new__(NodeAgent)
    a._host_chips = tuple(chips)
    a._free_chips = list(chips)
    a.workers, a.idle_workers, a.leases, a.bundles = {}, [], {}, {}
    a.resources_total = {"TPU": float(len(chips))}
    a.resources_available = {"TPU": float(len(chips))}
    a._parked_leases = collections.deque()
    a._park_event = asyncio.Event()
    return a


@pytest.mark.parametrize("parent", [None, "tpu", "tpu,cpu", "cpu"])
@pytest.mark.parametrize("chips", [(), (0, 1, 2, 3)])
def test_cpu_worker_and_zygote_are_pinned_to_cpu(monkeypatch, parent, chips):
    if parent is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", parent)
    env = _agent(chips)._worker_env(None, needs_tpu=False)  # zygote's too
    assert env["JAX_PLATFORMS"] == "cpu"
    assert "TPU_VISIBLE_CHIPS" not in env


def test_one_chip_leases_get_disjoint_chips_and_tpu_only_env(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    a = _agent((0, 1, 2, 3))
    first, second = a._take_chips({"TPU": 1.0}), a._take_chips({"TPU": 1.0})
    assert len(first) == len(second) == 1 and first != second
    for ids in (first, second):
        env = a._worker_env(None, needs_tpu=True, chip_ids=ids)
        assert env["JAX_PLATFORMS"] == "tpu"
        assert env["TPU_VISIBLE_CHIPS"] == str(ids[0])
        assert env["RAY_TPU_LEASED_CHIPS"] == str(ids[0])
        assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
        assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"
    assert a._free_chips == [2, 3]
    # A lease over the whole host keeps libtpu's defaults.
    whole = _agent((0,))
    env = whole._worker_env(None, True, whole._take_chips({"TPU": 1.0}))
    assert env["JAX_PLATFORMS"] == "tpu" and "TPU_VISIBLE_CHIPS" not in env
    with pytest.raises(ValueError, match="1, 2 or all"):
        TPUAcceleratorManager.worker_env([0, 1, 2], [0, 1, 2, 3])


def test_fractional_chip_is_refused_and_overdraw_is_not_granted():
    a = _agent((0, 1))
    with pytest.raises(rpc.RpcError, match="lease refused"):
        a._take_chips({"TPU": 0.5})
    a._take_chips({"TPU": 2.0})
    with pytest.raises(rpc.RpcError, match="not exited"):
        a._take_chips({"TPU": 1.0})
    from ray_tpu.llm.serve_patterns import build_llm_app
    with pytest.raises(ValueError, match="whole chips"):
        build_llm_app("tiny", num_tpus=0.5)


@pytest.mark.parametrize("parent", [None, "cpu"])
def test_injected_tpu_counts_leave_the_environment_alone(monkeypatch, parent):
    if parent is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", parent)
    a = _agent(())
    assert a._take_chips({"TPU": 4.0, "TPU-v5litepod-4-head": 1.0}) == ()
    env = a._worker_env(None, needs_tpu=True)
    assert env.get("JAX_PLATFORMS") == parent
    assert not any(k.startswith("TPU_") or k == "RAY_TPU_LEASED_CHIPS"
                   for k in env.keys() - os.environ.keys())


def test_tpu_lease_is_credited_back_only_when_the_process_has_exited():
    """The chip outlives the lease: capacity and chip ids come back when
    the worker process is gone, not when the lease is returned."""
    async def main():
        a = _agent((0,))
        ids = a._take_chips({"TPU": 1.0})
        a.resources_available["TPU"] = 0.0
        proc = subprocess.Popen([sys.executable, "-c",
                                 "import time; time.sleep(60)"])
        wh = WorkerHandle(b"w" * 16, proc)
        wh.needs_tpu, wh.chip_ids = True, ids
        wh.lease_id, wh.lease_resources = b"l" * 16, {"TPU": 1.0}
        wh.lease_owner_conn = types.SimpleNamespace(closed=False)
        a.workers[wh.worker_id] = a.leases[wh.lease_id] = wh
        a._reclaim_lease(wh.lease_id, wh)
        # Lease returned, process still up (SIGTERM is in flight).
        assert a.resources_available["TPU"] == 0.0 and a._free_chips == []
        assert wh not in a.idle_workers and not a.leases
        for _ in range(500):
            if a.resources_available["TPU"] == 1.0:
                break
            await asyncio.sleep(0.02)
        assert proc.poll() is not None
        assert a.resources_available["TPU"] == 1.0 and a._free_chips == [0]
        assert wh.worker_id not in a.workers

    asyncio.run(main())


def test_rejoin_after_a_false_death_terminates_orphaned_actor_workers():
    """A node the GCS wrongly declared dead rejoins under a fresh id; the
    GCS already buried its actors and will never kill their processes —
    the agent must, or a chip-holding orphan keeps the chip for good."""
    async def main():
        a = _agent((0,))
        a.node_id, a.gcs, ids = b"n" * 16, object(), []

        async def register(_gcs):
            ids.append(a.node_id)
        a._register_gcs = register
        sleeper = [sys.executable, "-c", "import time; time.sleep(60)"]
        actor = WorkerHandle(b"a" * 16, subprocess.Popen(sleeper))
        actor.is_actor, actor.actor_id = True, b"A" * 16
        task = WorkerHandle(b"t" * 16, subprocess.Popen(sleeper))
        a.workers = {actor.worker_id: actor, task.worker_id: task}
        try:
            await a._rejoin_with_fresh_id()
            assert ids == [a.node_id] and a.node_id != b"n" * 16
            assert actor.proc.wait(timeout=10) is not None
            assert actor.actor_id is None       # no death report is sent
            assert task.proc.poll() is None     # leased task workers stay
        finally:
            task.proc.kill()
            actor.proc.kill()

    asyncio.run(main())


def test_gcs_does_not_read_its_own_pause_as_a_nodes_death():
    """Creating a TPU client froze every process on the chip machine for
    ~7.5 s; the GCS woke, saw >5 s without a heartbeat and buried the node
    that held the chip.  A tick that is itself late must discount the
    pause — and a node that is silent while the GCS is awake still dies."""
    import time

    from ray_tpu._private import gcs as gcs_mod
    from ray_tpu._private.config import Config, get_config, set_config

    async def main():
        g = gcs_mod.GcsServer.__new__(gcs_mod.GcsServer)
        node = gcs_mod.NodeInfo(b"n" * 16, ("127.0.0.1", 1), {}, {}, "", "")
        g.nodes, dead = {node.node_id: node}, []

        async def mark_dead(node_id, reason):
            g.nodes[node_id].alive = False
            dead.append(reason)

        async def no_probe(*_a):
            pass
        g._mark_node_dead, g._redial_and_probe = mark_dead, no_probe
        g._update_suspicion = lambda *a: None
        loop = asyncio.ensure_future(g._health_loop())
        try:
            for _ in range(4):                  # heartbeats flow: alive
                await asyncio.sleep(0.05)
                node.last_heartbeat = time.monotonic()
            time.sleep(0.6)                     # everything freezes
            await asyncio.sleep(0.08)           # ... the GCS ticks first
            assert node.alive and not dead
            node.last_heartbeat = time.monotonic()
            await asyncio.sleep(0.5)            # real silence, GCS awake
            assert not node.alive and dead == ["health check failed"]
        finally:
            loop.cancel()

    old = get_config()
    set_config(Config({"health_check_period_ms": 50,
                       "health_check_failure_threshold": 5}))
    try:
        asyncio.run(main())
    finally:
        set_config(old)


def test_chip_discovery_reads_env_and_device_files_only(monkeypatch):
    monkeypatch.setattr(TPUAcceleratorManager, "_cached_chip_ids", None)
    monkeypatch.setenv("TPU_VISIBLE_CHIPS", "2,3")
    assert TPUAcceleratorManager.chip_ids() == [2, 3]
    monkeypatch.setattr(TPUAcceleratorManager, "_cached_chip_ids", None)
    monkeypatch.setenv("TPU_VISIBLE_CHIPS", "two")
    with pytest.raises(ValueError):     # never read as "no chips"
        TPUAcceleratorManager.chip_ids()
    monkeypatch.delenv("TPU_VISIBLE_CHIPS")
    files = {"/dev/accel*": [],
             "/dev/vfio/[0-9]*": ["/dev/vfio/7", "/dev/vfio/9"]}
    monkeypatch.setattr(accelerator.glob, "glob", files.__getitem__)
    assert TPUAcceleratorManager.chip_ids() == [0, 1]
    assert TPUAcceleratorManager.num_chips() == 2


def test_metadata_probe_is_bounded_and_cached(monkeypatch):
    """No metadata server (the sealed chip machine, this sandbox): the
    first lookup gives up within its bound and later paths are not tried."""
    import time
    import urllib.request
    calls = []

    def hang(*a, **k):
        calls.append(a)
        time.sleep(30)

    accelerator._gce_metadata.cache_clear()
    monkeypatch.setattr(accelerator, "_metadata_unreachable", [])
    monkeypatch.setattr(urllib.request, "urlopen", hang)
    t0 = time.monotonic()
    assert accelerator._gce_metadata("instance/attributes/topology") is None
    assert accelerator._gce_metadata("instance/attributes/other") is None
    assert time.monotonic() - t0 < 5 and len(calls) == 1
    accelerator._gce_metadata.cache_clear()


def test_compile_cache_dir_is_the_variable_or_one_fixed_checkout_path(
        monkeypatch, tmp_path):
    from ray_tpu._private import compile_cache, node
    monkeypatch.setenv(CACHE_ENV, str(tmp_path))
    assert compile_cache.compile_cache_dir() == str(tmp_path)
    assert node.child_env()[CACHE_ENV] == str(tmp_path)
    # Unset: two processes agree on one path inside the checkout.
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items() if k != CACHE_ENV}
    env["PYTHONPATH"] = root
    seen = {subprocess.run(
        [sys.executable, "-c",
         "from ray_tpu._private.compile_cache import compile_cache_dir;"
         "print(compile_cache_dir())"],
        env=env, cwd=cwd, check=True, capture_output=True,
        text=True).stdout.strip() for cwd in (root, str(tmp_path))}
    assert seen == {os.path.join(root, ".jax_cache")}
    monkeypatch.delenv(CACHE_ENV)
    assert node.child_env()[CACHE_ENV] == os.path.join(root, ".jax_cache")


def test_flash_attention_raises_on_a_tpu_for_a_shape_it_cannot_tile(
        monkeypatch):
    import importlib

    import jax.numpy as jnp

    fa = importlib.import_module("ray_tpu.ops.flash_attention")
    monkeypatch.setattr(fa.jax, "devices",
                        lambda: [types.SimpleNamespace(platform="tpu")])
    q = jnp.zeros((1, 48, 6, 64), jnp.float32)
    kv = jnp.zeros((1, 48, 4, 64), jnp.float32)
    with pytest.raises(ValueError, match="head_dim 64") as e:
        fa.flash_attention(q, kv, kv)
    assert "6 query heads" in str(e.value)
    with pytest.raises(ValueError, match="seq 48"):
        fa.flash_attention(q, q, q, block_q=32, block_k=32)
