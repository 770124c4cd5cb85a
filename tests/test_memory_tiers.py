"""Tiered cluster memory: unified spill/eviction/admission with KV
offload, put backpressure, and the memory-pressure chaos mode.

Reference model: raylet LocalObjectManager spill tier as a directory
location, plasma CreateRequestQueue admission (queue for headroom, fail
typed past the deadline), and vLLM-style KV page offload — all drained
by one shared node pressure signal.
"""

import asyncio
import os
import threading
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.exceptions import ObjectStoreFullError


# ----------------------------------------------------------- unit layer ---
def test_pressure_signal_max_of_fresh_sources():
    from ray_tpu._private.memory_monitor import PressureSignal
    sig = PressureSignal()
    assert sig.level() == 0.0
    sig.report("arena", 0.4)
    sig.report("kv_pool", 0.9)
    assert sig.level() == pytest.approx(0.9)
    sig.clear("kv_pool")
    assert sig.level() == pytest.approx(0.4)
    sig.report("chaos", 7.0)          # clamped into [0, 1]
    assert sig.level() == 1.0
    sig.clear("chaos")
    # Stale reports age out of level() past the freshness horizon.
    sig.report("node", 0.8)
    assert sig.level(fresh_s=0.0) == 0.0


def test_parse_mem_spec_and_square_wave():
    from ray_tpu._private.chaos import MemChaos, parse_mem_spec
    spec = parse_mem_spec("arena=0.5:2,pool=0.25")
    assert spec["arena"] == pytest.approx(0.5)
    assert spec["pool"] == pytest.approx(0.25)
    assert spec["period"] == pytest.approx(2.0)
    for bad in ("", "arena=1.5:2", "arena=0:2", "bogus=0.5:2",
                "arena=0.5:0", "pool=-1"):
        with pytest.raises(ValueError):
            parse_mem_spec(bad)
    mc = MemChaos("arena=0.5:10")
    t0 = mc._t0
    # First half-period: restored; second half: squeezed.
    assert not mc.squeezing(now=t0 + 1.0)
    assert mc.arena_frac(now=t0 + 1.0) == pytest.approx(1.0)
    assert mc.squeezing(now=t0 + 6.0)
    assert mc.arena_frac(now=t0 + 6.0) == pytest.approx(0.5)
    assert mc.pool_frac(now=t0 + 6.0) == pytest.approx(1.0)  # pool unset
    assert not mc.squeezing(now=t0 + 11.0)   # next cycle restores
    assert mc.squeezes >= 1


def test_arg_locality_scores_disk_tier_between_arena_and_remote():
    from ray_tpu._private.scheduling_policy import (DISK_TIER_WEIGHT,
                                                    arg_locality)
    arena = ("10.0.0.1", 1)
    spilled = ("10.0.0.2", 1)
    dev = ("10.0.0.3", 1)
    args = [{"ref": [b"o" * 20, ["w", 0], [list(arena), list(spilled)]],
             "sz": 1000, "dsk": [list(spilled)], "dev": [list(dev)]}]
    out = arg_locality(args)
    assert out[arena] == 1000
    # A holder in BOTH the location list and the dsk hint (a spilled
    # primary) counts ONCE, at disk weight — its arena copy is gone.
    assert out[spilled] == int(1000 * DISK_TIER_WEIGHT)
    assert out[dev] == 2000
    assert out[arena] > out[spilled] > 0


def test_memory_store_disk_tier_directory():
    from ray_tpu._private.memory_store import MemoryStore
    ms = MemoryStore()
    oid = b"x" * 20
    prim, sec, dsk = ("h1", 1), ("h2", 1), ("h3", 1)
    ms.put_plasma_location(oid, list(prim), size=64)
    ms.add_location(oid, sec)
    ms.add_location(oid, dsk, disk=True)
    # Disk holders are real pull sources: in locations(), ranked LAST.
    assert ms.locations(oid) == [prim, sec, dsk]
    assert ms.disk_locations(oid) == [dsk]
    # disk=True retract removes ONLY the tier marking.
    ms.add_location(oid, sec, disk=True)
    assert sec in ms.disk_locations(oid)
    ms.remove_location(oid, sec, disk=True)
    assert ms.disk_locations(oid) == [dsk]
    assert sec in ms.locations(oid)          # secondary record stands
    # Plain remove drops every tier.
    ms.remove_location(oid, dsk)
    assert ms.disk_locations(oid) == []


# ------------------------------------------- agent sweep / spill interleave ---
def _shell_agent(tmp_path, capacity=8 << 20):
    """A NodeAgent shell exposing only the spill/eviction surface — the
    sweep machinery is testable without a cluster (same pattern as
    test_data_plane's _mini_agent)."""
    from ray_tpu._private.agent import NodeAgent
    from ray_tpu._private.shm_store import ShmStore
    path = f"/dev/shm/rts_tiers_{os.getpid()}_{os.urandom(4).hex()}"
    store = ShmStore.create(path, capacity)
    a = NodeAgent.__new__(NodeAgent)
    a.store = store
    a.address = ("127.0.0.1", 0)
    a.pinned = {}
    a.spilled = {}
    a._spilling = set()
    a._spill_dir = str(tmp_path / "spill")
    a._spilled_bytes_total = 0
    a._restored_bytes_total = 0
    a._pinned_owner = {}
    a._replica_owner = {}
    a._pinned_floor = 0
    a._ext = None
    return a, store, path


def test_spill_aborts_when_pin_count_moves_mid_write(tmp_path, monkeypatch):
    """Satellite bugfix regression: a pin_transfer landing while the
    spill write runs off-loop makes the snapshotted pin count STALE —
    the spill must abort (object stays resident, no file, accounting
    intact), not commit a release_n for the old count."""
    from ray_tpu._private import agent as agent_mod
    a, store, path = _shell_agent(tmp_path)
    try:
        oid = os.urandom(20)
        store.put(oid, [b"z" * (1 << 20)], keep_pin=True)
        a.pinned[oid] = 1

        release = threading.Event()
        real_write = agent_mod._write_file

        def gated_write(p, view):
            release.wait(10)
            return real_write(p, view)

        monkeypatch.setattr(agent_mod, "_write_file", gated_write)

        async def main():
            task = asyncio.ensure_future(a._spill_one(oid))
            await asyncio.sleep(0.3)         # write parked off-loop
            a.pinned[oid] = 2                # pin_transfer lands mid-write
            release.set()
            return await task

        freed = asyncio.run(main())
        assert freed == 0, "stale-pin spill must abort"
        assert store.contains(oid)
        assert store.refcount(oid) == 1      # the pin survives, no leak
        assert oid not in a.spilled and oid not in a._spilling
        assert not os.path.exists(a._spill_path(oid))

        # A later sweep (pin count stable now) spills normally.
        async def retry():
            return await a._spill_one(oid)
        a.pinned[oid] = 1
        assert asyncio.run(retry()) == 1 << 20
        assert oid in a.spilled and not store.contains(oid)
    finally:
        store.close()
        try:
            os.unlink(path)
        except FileNotFoundError:
            pass


def test_eviction_drops_secondaries_before_spilling_primaries(tmp_path):
    """Eviction ordering (test-pinned): re-fetchable secondaries are
    DROPPED (no disk write) before any sole pinned primary spills; the
    pinned floor keeps a hot working set arena-resident."""
    a, store, path = _shell_agent(tmp_path)
    try:
        sec = os.urandom(20)
        store.put(sec, [b"s" * (1 << 20)])           # refcount 0 replica
        a._replica_owner[sec] = ("10.0.0.9", 1)
        prim = os.urandom(20)
        store.put(prim, [b"p" * (1 << 20)], keep_pin=True)
        a.pinned[prim] = 1
        a._pinned_owner[prim] = ("10.0.0.9", 2)

        async def sweep(need):
            return await a._free_space(need)

        # A small need is met ENTIRELY by dropping the secondary.
        freed = asyncio.run(sweep(1 << 20))
        assert freed >= 1 << 20
        assert not store.contains(sec)
        assert store.contains(prim) and prim not in a.spilled
        assert sec not in a._replica_owner

        # Floor: the sweep refuses to spill below the pinned floor.
        a._pinned_floor = 1 << 30
        assert asyncio.run(sweep(1 << 20)) == 0
        assert store.contains(prim) and prim not in a.spilled

        # Floor lifted: the primary spills (disk tier, file on NVMe).
        a._pinned_floor = 0
        freed = asyncio.run(sweep(1 << 20))
        assert freed == 1 << 20
        assert prim in a.spilled and not store.contains(prim)
        assert os.path.exists(a.spilled[prim][0])
        assert a._spilled_bytes_total == 1 << 20
    finally:
        store.close()
        try:
            os.unlink(path)
        except FileNotFoundError:
            pass


# -------------------------------------------------------- cluster layer ---
@pytest.fixture
def small_store():
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(num_cpus=2, object_store_memory=32 << 20)
    yield
    ray_tpu.shutdown()


def test_spilled_primary_registers_disk_tier_and_restores_identical(
        small_store):
    """Tentpole flow: spilling a primary registers a storage-tier
    location in the owner's replica directory; restore retracts it; the
    bytes round-trip identical through the directory-resolved path."""
    core = ray_tpu._core()
    arrays = [np.full(4 << 20, i, dtype=np.uint8) for i in range(16)]
    refs = [ray_tpu.put(a) for a in arrays]         # 64 MiB: early spill
    # At least one early object's spill must surface as a disk-tier
    # directory entry at the owner (async notify: poll briefly).
    deadline = time.monotonic() + 30
    marked = None
    while time.monotonic() < deadline and marked is None:
        for r in refs[:8]:
            if core.memory_store.disk_locations(r.binary()):
                marked = r
                break
        if marked is None:
            time.sleep(0.2)
    assert marked is not None, "no spilled primary registered a disk tier"
    # Every object restores byte-identical, spilled or not.  The marked
    # one is read LAST and its value HELD: an alive zero-copy view is an
    # active reader, so the pressure sweep cannot re-spill it while we
    # watch its tier marking retract (read pins now release on GC — a
    # dropped value would make re-spill/re-mark a legitimate race).
    held = None
    for i, r in enumerate(refs):
        if r is marked:
            continue
        got = np.asarray(ray_tpu.get(r, timeout=60))
        assert got.tobytes() == arrays[i].tobytes()
        del got
    held = np.asarray(ray_tpu.get(marked, timeout=60))
    assert held.tobytes() == arrays[refs.index(marked)].tobytes()
    # The restored object's tier marking is retracted (restore notified
    # the owner with disk=True remove).
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline and \
            core.memory_store.disk_locations(marked.binary()):
        time.sleep(0.2)
    assert core.memory_store.disk_locations(marked.binary()) == []
    del held


def test_put_past_deadline_raises_typed_with_accounting_intact():
    """Admission contract: a put that can neither reserve arena space
    nor reach the spill tier fails TYPED (ObjectStoreFullError with a
    retry_after_s hint) — never a raw arena exception — and the failed
    create leaves accounting intact (freeing room makes later puts
    succeed)."""
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    # /dev/null/x can never become a directory, even for root: both the
    # agent sweep and the worker's direct-disk fallback lose the tier.
    ray_tpu.init(num_cpus=1, object_store_memory=16 << 20,
                 _system_config={"object_spill_dir": "/dev/null/x",
                                 "create_backpressure_timeout_s": 2.0})
    try:
        store = ray_tpu._core().store
        keep = [ray_tpu.put(np.full(4 << 20, i, dtype=np.uint8))
                for i in range(3)]                   # 12 of 16 MiB pinned
        before = store.stats()
        with pytest.raises(ObjectStoreFullError) as ei:
            ray_tpu.put(np.zeros(8 << 20, dtype=np.uint8))
        assert ei.value.retry_after_s > 0
        # Accounting intact: the failed create left no reservation, no
        # pin, no partially-written region in the arena...
        after = store.stats()
        assert after["bytes_in_use"] == before["bytes_in_use"]
        assert after["num_objects"] == before["num_objects"]
        # ...and the residents still read back fine.
        for i in range(len(keep)):
            assert int(np.asarray(ray_tpu.get(keep[i], timeout=30))[0]) == i
        # A later small put is admitted to the ARENA through the same
        # path (backing off by the error's own retry_after_s hint — the
        # contract callers are sold; below the oversized threshold that
        # shortcuts straight to the broken disk tier).
        deadline = time.monotonic() + 60
        while True:
            try:
                ref = ray_tpu.put(np.full(2 << 20, 7, dtype=np.uint8))
                break
            except ObjectStoreFullError as e:
                assert time.monotonic() < deadline, \
                    "arena never admitted a fitting put"
                time.sleep(min(max(e.retry_after_s, 0.1), 1.0))
        got = np.asarray(ray_tpu.get(ref, timeout=30))
        assert got[0] == 7 and got.nbytes == 2 << 20
    finally:
        ray_tpu.shutdown()


# ------------------------------------------------------------ KV offload ---
def _tiny_engine(**kw):
    from ray_tpu.llm import LLMEngine
    from ray_tpu.models import PRESETS
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("page_size", 8)
    kw.setdefault("prefix_cache", True)
    kw.setdefault("seed", 0)
    return LLMEngine(PRESETS["tiny"], **kw)


def test_kv_demote_promote_token_parity():
    """LRU-evicted prefix pages demote to the host window and promote
    back on reuse — generated tokens are identical to the never-evicted
    run, and the round-trip is visible in the stats counters."""
    from ray_tpu.llm import SamplingParams
    eng = _tiny_engine(kv_pages=12)
    prompt = list(range(1, 33))                      # 4 full pages
    sp = SamplingParams(max_tokens=4)
    first = eng.generate([prompt], sp)[0]
    # Force every cache entry out through the demotion hook.
    while eng._cache._entries:
        eng._cache.evict_lru(eng._decref, eng._demote_entry)
    st = eng.prefix_cache_stats()
    assert st["demoted_pages"] > 0 and st["demoted_entries"] > 0
    assert not eng._cache._entries
    again = eng.generate([prompt], sp)[0]
    st = eng.prefix_cache_stats()
    assert st["promoted_pages"] > 0, "reuse must promote, not re-prefill"
    assert again == first, "promoted KV must be token-exact"


def test_kv_demote_overflows_to_nvme_parts(tmp_path):
    """Past the host-window byte budget, demoted entries overflow to
    NVMe part files ({k, v, len} npz) and still promote token-exact."""
    from ray_tpu.llm import SamplingParams
    from ray_tpu.llm.kv_cache import _KVDemoteStore
    eng = _tiny_engine(kv_pages=12)
    # Swap in a near-zero host window over a temp dir: every demotion
    # overflows to disk immediately.
    eng._demote = _KVDemoteStore(1, str(tmp_path / "kv"))
    prompt = list(range(1, 33))
    sp = SamplingParams(max_tokens=4)
    first = eng.generate([prompt], sp)[0]
    while eng._cache._entries:
        eng._cache.evict_lru(eng._decref, eng._demote_entry)
    st = eng.prefix_cache_stats()
    assert st["demoted_disk_entries"] > 0 and st["demoted_disk_spills"] > 0
    assert any(f.startswith("kvdemote-")
               for f in os.listdir(tmp_path / "kv"))
    again = eng.generate([prompt], sp)[0]
    assert again == first
    assert eng.prefix_cache_stats()["promoted_pages"] > 0


def test_kv_pool_squeeze_parks_and_restores_pages():
    """apply_pool_pressure is the mem_chaos pool hook: free pages park
    on the ballast list under a squeeze and return on restore — decode
    correctness is unaffected."""
    from ray_tpu.llm import SamplingParams
    eng = _tiny_engine(kv_pages=16)
    total_free = len(eng._free_pages)
    eng.apply_pool_pressure(0.25)
    assert len(eng._ballast_pages) > 0
    assert len(eng._free_pages) < total_free
    out = eng.generate([[1, 2, 3, 4]], SamplingParams(max_tokens=3))[0]
    eng.apply_pool_pressure(1.0)
    assert not eng._ballast_pages
    # Page 0 is the engine's reserved null page: usable = n_pages - 1.
    assert len(eng._free_pages) + len(eng._page_refs) == eng.n_pages - 1
    eng2 = _tiny_engine(kv_pages=16)
    assert eng2.generate([[1, 2, 3, 4]],
                         SamplingParams(max_tokens=3))[0] == out


# ------------------------------------------------------------ chaos soak ---
@pytest.mark.slow
def test_oversubscription_soak_under_mem_chaos():
    """4x arena oversubscription under the mem_chaos square wave: every
    failure is the TYPED backpressure error (none expected with a live
    spill tier — zero untyped failures is the acceptance bar) and every
    object reads back byte-identical.  Verification runs in WORKER
    tasks: a worker's arg pins release when the task completes, so the
    soak measures the tiering machinery, not the driver's zero-copy
    read views accumulating in the arena."""
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(num_cpus=2, object_store_memory=32 << 20,
                 _system_config={"mem_chaos": "arena=0.5:2",
                                 "create_backpressure_timeout_s": 10.0})

    @ray_tpu.remote
    def fingerprint(a):
        return (int(a[0]), int(a[-1]), int(a.nbytes))

    try:
        untyped = []
        for round_no in range(3):
            fills = [(round_no * 32 + i) % 251 for i in range(32)]
            refs = []
            for f in fills:              # 32 x 4 MiB = 4x the 32 MiB arena
                try:
                    refs.append(ray_tpu.put(np.full(4 << 20, f,
                                                    dtype=np.uint8)))
                except ObjectStoreFullError:
                    refs.append(None)    # typed shedding: acceptable
                except Exception as e:   # noqa: BLE001
                    untyped.append(repr(e))
                    refs.append(None)
            live = [(f, r) for f, r in zip(fills, refs) if r is not None]
            assert live, f"round {round_no}: every single put was shed"
            try:
                outs = ray_tpu.get(
                    [fingerprint.remote(r) for _, r in live], timeout=300)
            except ObjectStoreFullError:
                outs = None              # typed, whole-round: acceptable
            except Exception as e:       # noqa: BLE001
                untyped.append(repr(e))
                outs = None
            if outs is not None:
                for (f, _), out in zip(live, outs):
                    assert out == (f, f, 4 << 20), \
                        f"corrupt restore in round {round_no}: {out} != {f}"
            del refs, live
        assert not untyped, f"untyped failures under mem_chaos: {untyped[:3]}"
    finally:
        ray_tpu.shutdown()
