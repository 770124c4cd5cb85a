"""Long-context engine: sequence-parallel prefill attention +
cross-host paged KV.

Parity discipline: the SP kernels (ring attention with rotating KV
blocks + running log-sum-exp rescaling; Ulysses all-to-all) and the
streamed paged-KV path must match the engine's single-device
`_prefill_fn` / closed-loop decode EXACTLY (greedy tokens) and to fp32
tolerance (logits) at every shard count — online softmax is associative
in fp32, so any mismatch is a bug, not noise.  Everything runs the tiny
TransformerConfig on the conftest 8-device CPU mesh
(XLA_FLAGS=--xla_force_host_platform_device_count).

Failure discipline: a KV part whose holder dies mid-decode surfaces
typed (KVGatherError inside the engine, StreamBrokenError at the
serving surface) and NEVER a wrong token; pool + window accounting
return to exact zero leak.
"""

import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.exceptions import KVGatherError, StreamBrokenError
from ray_tpu.llm import LLMEngine, LongContextApp, SamplingParams
from ray_tpu.llm.kv_cache import _KVWindow
from ray_tpu.llm.programs import _prefill_fn
from ray_tpu.models import PRESETS

pytestmark = pytest.mark.sp

CFG = PRESETS["tiny"]


def _prompt(n, seed=0):
    return list(np.random.default_rng(seed).integers(1, CFG.vocab_size, n))


# ------------------------------------------------------------- SP parity ---

@pytest.mark.parametrize("degree", [2, 4])
@pytest.mark.parametrize("strategy", ["ring", "ulysses"])
def test_sp_prefill_fn_parity(degree, strategy):
    """sp_prefill_fn == _prefill_fn to fp32 tolerance: logits AND the
    full KV it returns for install, at odd (non-bucket) lengths so the
    padded tail crosses shard boundaries."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.llm.sequence_parallel import sp_mesh, sp_prefill_fn
    from ray_tpu.models.transformer import init_params

    params = init_params(CFG, jax.random.key(0))
    mesh = sp_mesh(degree)
    # Odd lengths only: the padded tail crossing shard boundaries is the
    # hard case; exact-bucket lengths ride the engine parity tests.
    for S, Sb in ((37, 64), (111, 128)):
        toks = np.zeros((1, Sb), np.int32)
        toks[0, :S] = _prompt(S, seed=S)
        toks = jnp.asarray(toks)
        ref_lg, ref_k, ref_v = jax.jit(
            lambda p, t, n: _prefill_fn(p, t, n, CFG))(params, toks, S)
        sp_lg, sp_k, sp_v = jax.jit(
            lambda p, t, n: sp_prefill_fn(p, t, n, CFG, mesh, strategy)
        )(params, toks, S)
        np.testing.assert_allclose(np.asarray(sp_lg), np.asarray(ref_lg),
                                   rtol=2e-4, atol=2e-4)
        # Only the REAL positions must match: padded-tail rows are
        # garbage by contract on both paths (decode masks them).
        np.testing.assert_allclose(np.asarray(sp_k)[:, :S],
                                   np.asarray(ref_k)[:, :S],
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(sp_v)[:, :S],
                                   np.asarray(ref_v)[:, :S],
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("degree,strategy",
                         [(1, "ring"), (2, "ring"), (4, "ulysses")])
def test_engine_sp_generate_parity(degree, strategy):
    # Engine-level dispatch at degrees {1,2,4}; the remaining
    # degree x strategy grid is covered at fn level above (tier-1
    # budget: each engine pair here costs ~2.5s of compiles).
    """End-to-end greedy tokens through the engine match the sp_degree=1
    engine at every degree/strategy (the admission path installs the SP
    kernel's KV into the same paged pool decode reads)."""
    prompts = [_prompt(40), _prompt(23, seed=1)]
    sp = SamplingParams(max_tokens=6)
    base = LLMEngine(CFG, max_batch=2, max_len=128, seed=0)
    expect = base.generate(prompts, sp)
    eng = LLMEngine(CFG, max_batch=2, max_len=128, seed=0,
                    sp_degree=degree, sp_strategy=strategy)
    assert eng.generate(prompts, sp) == expect
    if degree > 1:
        # Per-shard stripe accounting: every admitted request records
        # which pages each SP shard installed (the handoff unit).
        eng2 = LLMEngine(CFG, max_batch=1, max_len=128, seed=0,
                         sp_degree=degree, sp_strategy=strategy,
                         page_size=8)
        rid = eng2.add_request(_prompt(40), sp)
        eng2.step()
        req = eng2._requests[rid]
        assert req.sp_stripes is not None
        flat = [p for stripe in req.sp_stripes for p in stripe]
        n_pages = -(-40 // 8)
        assert sorted(flat) == sorted(
            int(p) for p in eng2._tables[req.slot][:n_pages])


def test_engine_sp_prefix_cache_suffix_parity():
    """Prefix-cache hit + SP: the second request's SUFFIX prefill runs
    sequence-parallel (ring seeded by the resident prefix) and still
    skips the shared span's compute; tokens match the non-SP engine."""
    shared = _prompt(32, seed=7)
    p1 = shared + _prompt(9, seed=8)
    p2 = shared + _prompt(13, seed=9)
    sp = SamplingParams(max_tokens=5)

    base = LLMEngine(CFG, max_batch=2, max_len=128, seed=0,
                     page_size=16, prefix_cache=True)
    e1 = base.generate([p1], sp)
    e2 = base.generate([p2], sp)
    assert base.prefix_cache_stats()["hits"] >= 1

    eng = LLMEngine(CFG, max_batch=2, max_len=128, seed=0,
                    page_size=16, prefix_cache=True, sp_degree=2)
    assert eng.generate([p1], sp) == e1
    assert eng.generate([p2], sp) == e2
    st = eng.prefix_cache_stats()
    assert st["hits"] >= 1 and st["hit_pages"] >= 2
    # sp-tagged cache namespace: keys are per-SP-layout by construction.
    assert eng._cache.tag == b"sp2"


def test_sp_engine_rejects_bad_layouts():
    with pytest.raises(ValueError, match="power of two"):
        LLMEngine(CFG, sp_degree=3)
    with pytest.raises(ValueError, match="divisible by sp_degree"):
        # _bucket clamps to max_len: an indivisible max_len would reach
        # shard_map as an unsplittable axis — must fail at construction.
        LLMEngine(CFG, max_len=90, sp_degree=4)
    with pytest.raises(ValueError, match="divisible"):
        LLMEngine(CFG, sp_degree=8, sp_strategy="ulysses")


# ------------------------------------------------------- chunked prefill ---

def test_chunked_prefill_parity_and_tick_bound():
    """A huge prompt advances ONE chunk per tick: no giant XLA bucket is
    ever compiled, an already-decoding request keeps emitting a token
    every tick (no starvation), and the final tokens match the
    unchunked engine exactly."""
    long_p = _prompt(120, seed=3)
    short_p = _prompt(6, seed=4)
    sp = SamplingParams(max_tokens=24)

    base = LLMEngine(CFG, max_batch=2, max_len=256, seed=0)
    expect_long = base.generate([long_p], sp)[0]
    expect_short = base.generate([short_p], sp)[0]

    eng = LLMEngine(CFG, max_batch=2, max_len=256, seed=0,
                    page_size=16, prefill_chunk=32)
    out = {}
    rid_s = eng.add_request(short_p, sp)
    eng.step()                                   # short admitted
    for r, tok, _fin in eng.take_tick_events():
        out.setdefault(r, []).append(tok)
    rid_l = eng.add_request(long_p, sp)
    short_tokens_during_prefill = 0
    while eng.has_unfinished():
        eng.step()
        prefilling = bool(eng._prefilling)
        for r, tok, _fin in eng.take_tick_events():
            out.setdefault(r, []).append(tok)
            if r == rid_s and prefilling:
                short_tokens_during_prefill += 1
    # Parity: chunked == unchunked for both requests.
    assert out[rid_s] == expect_short
    assert out[rid_l] == expect_long
    # The decoding request never starved while the long prompt chunked.
    assert short_tokens_during_prefill >= 3
    # Tick-latency bound: only chunk-sized prefill buckets were
    # compiled; the 128-token bucket the whole prompt would need never
    # exists (suffix chunks compile at the chunk bucket, 32).
    buckets = [k[-1] if isinstance(k, tuple) else k
               for k in eng._prefill_jit]
    assert max(buckets) <= 32, buckets
    # And wall-clock: with everything warm, a tick that advances one
    # chunk stays bounded (generous CI bound; the structural pin above
    # is the real guarantee).
    rid2 = eng.add_request(long_p, sp)
    eng.step()
    t0 = time.perf_counter()
    eng.step()                                   # one warm chunk tick
    assert time.perf_counter() - t0 < 2.0
    eng.cancel_request(rid2)


# ------------------------------------------------- streamed paged KV -------

def test_paged_prefill_decode_parity_and_accounting():
    """prefill_paged → decode_paged matches the closed-loop engine: the
    context never touches the decode pool (only the decode tail), and
    pool + window accounting return to zero after completion."""
    prompt = _prompt(100, seed=5)
    sp = SamplingParams(max_tokens=6)
    base = LLMEngine(CFG, max_batch=1, max_len=256, seed=0)
    expect = base.generate([prompt], sp)[0]

    # max_len=64 < context 100: the paged path is the only way this
    # engine can serve it at all.
    pre = LLMEngine(CFG, max_batch=1, max_len=64, page_size=16,
                    kv_pages=4, seed=0)
    dec = LLMEngine(CFG, max_batch=1, max_len=64, page_size=16,
                    kv_pages=4, seed=0, kv_gather_window=2)
    handoff = pre.prefill_paged(prompt, sp, span=32)
    assert len(handoff["parts"]) == 4 and handoff["len"] == 100
    out = dec.decode_paged(handoff, sp)
    assert out == expect
    assert dec.kv_pages_free() == dec.kv_pages_total      # zero leak
    st = dec.kv_gather_stats()
    assert st["resident"] == 0 and st["fetches"] > 0
    # window (2) < parts (4): degraded to re-fetching — counted, never
    # silent.
    assert st["refetches"] > 0


def test_kv_window_refetch_counting_and_typed_failure():
    calls = []

    def fetch(handle):
        calls.append(handle)
        if handle == "boom":
            raise OSError("holder died")
        return {"k": np.zeros(2), "v": np.zeros(2), "len": 2}

    w = _KVWindow(1, fetch)
    w.get("a", "ha")
    w.get("b", "hb")                  # evicts a
    w.get("a", "ha")                  # re-fetch: counted
    assert w.fetches == 3 and w.refetches == 1
    with pytest.raises(KVGatherError) as ei:
        w.get("c", "boom")
    assert isinstance(ei.value.__cause__, OSError)
    # Malformed part payloads are typed too, not AttributeErrors later.
    w2 = _KVWindow(1, lambda h: "junk")
    with pytest.raises(KVGatherError, match="expected"):
        w2.get("x", "hx")


def test_paged_decode_gather_failure_is_typed_and_leak_free():
    """Mid-decode loss of a KV part's holder: the request retires typed
    (finish_reason 'error', KVGatherError), other requests in the same
    batch are unaffected, and every page returns to the pool."""
    prompt = _prompt(64, seed=6)
    sp = SamplingParams(max_tokens=8)
    pre = LLMEngine(CFG, max_batch=1, max_len=64, page_size=16,
                    kv_pages=4, seed=0)
    handoff = pre.prefill_paged(prompt, sp, span=32)

    alive = {"ok": True}
    parts_data = {i: p["handle"] for i, p in enumerate(handoff["parts"])}

    def fetch(handle):
        if not alive["ok"]:
            raise ConnectionError("KV holder SIGKILLed")
        return handle

    dec = LLMEngine(CFG, max_batch=2, max_len=64, page_size=16,
                    kv_pages=6, seed=0, kv_gather_window=1,
                    kv_fetch=fetch)
    rid = dec.add_paged_request(handoff["parts"], handoff["len"],
                                handoff["first"], sp)
    other = dec.add_request(_prompt(5, seed=8), SamplingParams(max_tokens=12))
    free_before_any = dec.kv_pages_total
    dec.step()                        # both admitted; paged emits token
    dec.step()
    alive["ok"] = False               # the holding "host" dies
    errored = None
    while dec.has_unfinished():
        for done in dec.step():
            if done.req_id == rid:
                errored = done
    assert errored is not None and errored.finish_reason == "error"
    assert isinstance(errored.error, KVGatherError)
    assert isinstance(errored.error.__cause__, ConnectionError)
    # The colocated request decoded to completion, unaffected.
    assert len(dec._requests) == 0
    assert dec.kv_pages_free() == free_before_any          # exact zero leak
    assert dec.kv_gather_stats()["resident"] == 0
    del parts_data


# ------------------------------------------------ cluster + chaos tier ----

@pytest.fixture
def lc_cluster():
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(num_cpus=6)
    yield
    ray_tpu.shutdown()


@pytest.mark.slow
def test_cluster_context_exceeds_single_node_pool(lc_cluster):
    """Serve a context that CANNOT fit any single replica's KV page pool
    (pools sized to prove it: kv_pages=4 x page 16 = 64 tokens + scratch
    per node, context = 160 tokens), through N=2 sequence-parallel
    prefill shards handing stripes to one decode replica.  Mechanics
    pinned (the CPU box makes GiB/s meaningless): per-shard stripe
    publication counts, decode-side gather counters, refs-only handoff,
    and exact-token parity with the single closed-loop engine."""
    prompt = _prompt(160, seed=11)
    sp_opts = {"max_tokens": 6}
    ref = LLMEngine(CFG, max_batch=1, max_len=256, seed=0)
    expect = ref.generate([prompt], SamplingParams(max_tokens=6))[0]

    app = LongContextApp("tiny", prefill_shards=2, decode_replicas=1,
                         span=32, max_len=64, page_size=16, kv_pages=4,
                         kv_gather_window=3, max_tokens=6, seed=0)
    try:
        handoff = app.prefill(prompt, sp_opts, timeout=300)
        # 160 tokens / span 32 = 5 stripes, round-robined 3/2 across
        # the two shards — no single arena holds the whole context.
        assert len(handoff["parts"]) == 5
        assert all(not isinstance(p["handle"], dict)
                   for p in handoff["parts"]), "bytes leaked into handoff"
        dec = app.decodes[0]
        rid = ray_tpu.get(dec.admit_paged.remote(handoff), timeout=120)
        gen = dec.collect_stream.options(
            num_returns="streaming").remote(rid)
        toks = []
        for item_ref in gen:
            item = ray_tpu.get(item_ref, timeout=120)
            if isinstance(item, dict):
                assert item["finish_reason"] == "length"
                break
            toks.append(item)
        assert toks == expect
        st = app.debug_stats(timeout=60)
        d = st["decodes"][0]
        # Gather mechanics: the decode pulled remote stripes (window 3 <
        # 5 parts → counted refetches, never silent), and its own pool
        # shows zero leak after completion.
        assert d["kv_gather"]["fetches"] >= 5
        assert d["kv_gather"]["refetches"] > 0
        assert d["kv_gather"]["bytes"] > 0
        assert d["kv_pages_free"] == d["kv_pages_total"]
        # Per-shard install counts: both shards computed + published
        # stripes (3 and 2 chunks' worth of sp:gather spans ran there).
        for s in st["shards"]:
            assert s["kv_pages_free"] == s["kv_pages_total"]
        # OPEN-loop on the same pool-exceeding context: requests are
        # offered on schedule regardless of completions, each through
        # the full shard-prefill → paged-decode path, and none breaks.
        from ray_tpu.llm import run_open_loop
        rep = run_open_loop(
            lambda p: app.stream(p, sp_opts, timeout=240),
            rate_hz=1.0, duration_s=3.0,
            prompt_fn=lambda i: _prompt(160, seed=20 + i),
            num_replicas=1, request_timeout_s=240.0)
        assert rep["completed"] == rep["offered"] >= 3, rep
        assert rep["broken"] == 0 and not rep["errors"], rep
        assert rep["tokens_total"] >= 3 * 6
    finally:
        app.shutdown()


@pytest.mark.chaos
@pytest.mark.slow
def test_kv_holding_host_sigkill_mid_decode_typed(lc_cluster):
    """SIGKILL the shard actor holding remote KV stripes mid-decode: the
    affected stream fails TYPED (StreamBrokenError carrying
    tokens_emitted, KVGatherError cause) — never a wrong token — pages
    reclaim to exact zero, and the decode replica keeps serving fresh
    local requests."""
    import os
    import signal

    prompt = _prompt(128, seed=13)
    app = LongContextApp("tiny", prefill_shards=2, decode_replicas=1,
                         span=32, max_len=64, page_size=16, kv_pages=4,
                         kv_gather_window=1,   # every step re-pulls: the
                         max_tokens=40,        # kill is observed promptly
                         seed=0)
    try:
        # 40 decode-tail tokens fit the 4-page pool (ceil(41/16) = 3
        # pages) while leaving plenty of stream for the kill to land in.
        handoff = app.prefill(prompt, {"max_tokens": 40}, timeout=300)
        dec = app.decodes[0]
        rid = ray_tpu.get(dec.admit_paged.remote(handoff), timeout=120)
        gen = dec.collect_stream.options(
            num_returns="streaming").remote(rid)
        it = iter(gen)
        got = [ray_tpu.get(next(it), timeout=120) for _ in range(3)]
        assert all(isinstance(t, int) for t in got)
        # Kill the shard holding stripe 0 (chunk 0 went to shard 0).
        pid = ray_tpu.get(app.shards[0].pid.remote(), timeout=30)
        os.kill(pid, signal.SIGKILL)
        with pytest.raises(StreamBrokenError) as ei:
            for item_ref in it:
                item = ray_tpu.get(item_ref, timeout=180)
                assert not isinstance(item, dict), \
                    "stream finished cleanly despite KV loss"
        assert ei.value.tokens_emitted >= 3
        # Accounting returns to exact zero on the decode replica, and it
        # still serves fresh (non-paged) requests.
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            d = ray_tpu.get(dec.debug_stats.remote(), timeout=30)
            if d["active"] == 0 and d["queue_depth"] == 0:
                break
            time.sleep(0.5)
        assert d["kv_broken"] >= 1
        assert d["kv_pages_free"] == d["kv_pages_total"]
        assert d["kv_gather"]["resident"] == 0
        out = ray_tpu.get(
            dec.generate.remote(_prompt(5, seed=14), {"max_tokens": 3}),
            timeout=120)
        assert len(out["tokens"]) == 3
    finally:
        app.shutdown()
