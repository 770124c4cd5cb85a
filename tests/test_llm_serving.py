"""Production LLM serving subsystem: continuous batching, token
streaming, KV-prefix cache, queue-driven autoscaling, load shedding.

Reference model: Orca iteration-level scheduling (admission per decode
tick) + vLLM PagedAttention block sharing, behind the Serve
router/controller with typed failure surfaces (OverloadedError,
StreamBrokenError, DeadlineExceededError).  Everything runs the tiny
TransformerConfig on CPU; the open-loop load test stays small-scale.
"""

import asyncio
import threading
import time

import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu._private import flight_recorder
from ray_tpu.exceptions import (DeadlineExceededError, OverloadedError,
                                StreamBrokenError)
from ray_tpu.llm import (EngineReplica, LLMEngine, SamplingParams,
                         build_llm_app, run_open_loop)
from ray_tpu.models import PRESETS

pytestmark = pytest.mark.serving

CFG = PRESETS["tiny"]


@pytest.fixture
def serve_cluster():
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(num_cpus=8)
    serve.start()
    yield
    serve.shutdown()
    ray_tpu.shutdown()


# ---------------------------------------------------------------- engine ---

def test_admission_sampling_is_one_transfer_per_tick(captured_recorder):
    """A 3-request admission wave samples its first tokens in ONE
    device->host pull (one `sample_sync` span per tick, batch=3), not
    one blocking pull per request."""
    with captured_recorder() as rec:
        eng = LLMEngine(CFG, max_batch=4, max_len=64, seed=0, page_size=8)
        for i in range(3):
            eng.add_request([i + 1, i + 2, i + 3],
                            SamplingParams(max_tokens=3))
        eng.step()
        rows = [r for r in rec.rows() if r["cat"] == "request"]
        samples = [r for r in rows if r["name"] == "sample_sync"]
        prefills = [r for r in rows if r["name"] == "prefill"]
        assert len(samples) == 1, samples
        assert samples[0]["args"]["batch"] == 3
        assert len(prefills) == 3
        while eng.has_unfinished():
            eng.step()


def test_prefix_cache_hit_parity_eviction_and_accounting(captured_recorder):
    """Page-granular prefix reuse: a shared-prefix request skips
    prefill for the shared pages (page-pool accounting asserted), tokens
    stay IDENTICAL to an uncached engine, and LRU entries evict under
    pool pressure."""
    prefix = list(range(5, 25))              # 2 full pages of 8
    pA, pB = prefix + [30, 31], prefix + [40, 41, 42]
    sp = SamplingParams(max_tokens=5)
    ref = LLMEngine(CFG, max_batch=2, max_len=64, seed=0, page_size=8)
    eng = LLMEngine(CFG, max_batch=2, max_len=64, seed=0, page_size=8,
                    prefix_cache=True)
    assert eng.generate([pA], sp)[0] == ref.generate([pA], sp)[0]
    assert eng.generate([pB], sp)[0] == ref.generate([pB], sp)[0]
    st = eng.prefix_cache_stats()
    assert st["hits"] == 1 and st["hit_pages"] == 2, st
    # Shared pages were NOT re-allocated: B borrowed A's 2 prefix pages.
    with captured_recorder() as rec:
        eng.generate([pA], sp)               # full prompt cached now
        rows = [r for r in rec.rows()
                if r["cat"] == "request" and r["name"] == "prefill"]
    assert rows and rows[-1]["args"]["cached_tokens"] == 16

    # Eviction under pool pressure: 4-page pool, 1 cached page per
    # retired request -> the cache must shed LRU entries to keep fitting.
    small = LLMEngine(CFG, max_batch=2, max_len=64, seed=0, page_size=8,
                      kv_pages=4, prefix_cache=True)
    for i in range(6):
        out = small.generate([[i * 7 + 1, i * 7 + 2] * 6],
                             SamplingParams(max_tokens=4))
        assert len(out[0]) == 4
    st = small.prefix_cache_stats()
    assert st["evictions"] >= 1, st
    assert st["free_pages"] + st["allocated_pages"] == 4

    # P/D: decode_from with prompt_tokens learns the prefix; the second
    # blob install hits the decode-side cache.
    pre = LLMEngine(CFG, max_batch=1, max_len=64, seed=0, page_size=8,
                    prefix_cache=True)
    dec = LLMEngine(CFG, max_batch=2, max_len=64, seed=0, page_size=8,
                    prefix_cache=True)
    blob, first = pre.prefill_only(pA, sp)
    assert dec.decode_from(blob, first, sp, prompt_tokens=pA) \
        == ref.generate([pA], sp)[0]
    blob2, first2 = pre.prefill_only(pB, sp)
    assert dec.decode_from(blob2, first2, sp, prompt_tokens=pB) \
        == ref.generate([pB], sp)[0]
    # BOTH sides reuse the prefix: the prefill-only engine populates its
    # cache from prefill_only itself (no admission ever runs there), so
    # the second prefill skipped the shared span's compute too.
    assert pre.prefix_cache_stats()["hits"] >= 1, pre.prefix_cache_stats()
    assert dec.prefix_cache_stats()["hits"] >= 1


def test_engine_replica_streams_batches_and_cancels():
    """In-process EngineReplica: a late arrival is admitted while an
    earlier request is still decoding; tokens stream incrementally; an
    abandoned stream cancels its request and frees pages mid-decode;
    eos produces finish_reason='stop'."""

    async def main():
        er = EngineReplica("tiny", max_batch=4, max_len=64, page_size=8,
                           max_tokens=16)

        async def consume(prompt, delay=0.0, take=None, opts=None):
            await asyncio.sleep(delay)
            toks, reason, stamps = [], None, []
            gen = er.stream_generate(prompt, opts or {"max_tokens": 16})
            try:
                async for item in gen:
                    if isinstance(item, dict):
                        reason = item["finish_reason"]
                        break
                    stamps.append(time.monotonic())
                    toks.append(item)
                    if take and len(toks) >= take:
                        break
            finally:
                await gen.aclose()
            return toks, reason, stamps

        a = asyncio.ensure_future(consume([1, 2, 3, 4, 5]))
        b = asyncio.ensure_future(consume([9, 8, 7], delay=0.05))
        (ta, ra, sa), (tb, rb, sb) = await asyncio.gather(a, b)
        assert len(ta) == 16 and ra == "length"
        assert len(tb) == 16 and rb == "length"
        st = await er.debug_stats()
        assert st["max_active"] >= 2, st          # batched concurrently
        # incremental: first token arrived well before the last
        assert sa[0] < sa[-1]
        # parity with the closed-loop engine
        ref = LLMEngine(CFG, max_batch=4, max_len=64, seed=0)
        assert ta == ref.generate([[1, 2, 3, 4, 5]],
                                  SamplingParams(max_tokens=16))[0]

        # abandoned stream -> typed cancel, pages freed mid-decode
        await consume([11, 12, 13], take=3)
        await asyncio.sleep(0.3)
        st = await er.debug_stats()
        assert st["cancelled"] >= 1, st
        assert st["kv_pages_free"] == st["kv_pages_total"], st
        assert st["active"] == 0 and st["queue_depth"] == 0

        # eos -> finish_reason "stop"
        free_run, _, _ = await consume([3, 17, 42])
        eos = free_run[2]
        toks, reason, _ = await consume(
            [3, 17, 42], opts={"max_tokens": 16, "eos_id": eos})
        assert reason == "stop" and toks[-1] == eos

    asyncio.run(main())


def test_a_caller_waiting_for_the_lock_holds_the_next_step_back():
    """The replica tells its engine whether anyone waits for the lock (a
    request about to be enqueued, a cancellation): while someone does, no
    decode step leaves ahead of the call that asks for it, so the waiting
    caller's prefill never queues behind one."""

    async def main():
        er = EngineReplica("tiny", max_batch=2, max_len=64, page_size=8,
                           max_tokens=4)
        hold = er.engine.hold_ahead
        assert hold() is False
        async with er._lock:
            waiter = asyncio.ensure_future(er._lock.acquire())
            await asyncio.sleep(0)
            assert hold() is True
        await waiter
        assert hold() is False
        er._lock.release()
        out = await er.generate([1, 2, 3], {"max_tokens": 4})
        assert len(out["tokens"]) == 4
        assert (await er.debug_stats())["tick"]["ns"]["ahead"] > 0

    asyncio.run(main())


def test_while_somebody_waits_for_the_lock_no_second_step_is_queued():
    """A reply decoded with nobody at the lock runs with one step running
    and one queued; the same reply decoded while a caller stands at the
    lock all the time has no step queued behind another and none sent
    ahead, and the same tokens."""

    async def main():
        er = EngineReplica("tiny", max_batch=2, max_len=64, page_size=8,
                           max_tokens=12)
        alone = await er.generate([1, 2, 3])
        free = await er.debug_stats()
        assert free["decode"]["steps_queued"] >= 6

        # What `hold_ahead` reads (the test above: a caller at the lock is
        # counted): one who stands there for as long as the reply takes.
        er._lock.waiting += 1
        try:
            held_reply = await er.generate([1, 2, 3])
        finally:
            er._lock.waiting -= 1
        held = await er.debug_stats()
        assert held_reply["tokens"] == alone["tokens"]
        assert held["decode"]["steps"] == 2 * free["decode"]["steps"]
        assert held["decode"]["steps_queued"] \
            == free["decode"]["steps_queued"]
        assert held["tick"]["ns"]["ahead"] == free["tick"]["ns"]["ahead"]

    asyncio.run(main())


def test_queued_deadline_expires_typed():
    """A request whose deadline passes while parked in the admission
    queue fails typed (DeadlineExceededError) without occupying a slot,
    and its (never-reserved) pages don't leak."""

    async def main():
        from ray_tpu._private import deadlines
        # ~480 decode ticks keep the pool busy far past the short
        # deadline below even with warm compile caches.
        er = EngineReplica("tiny", max_batch=2, max_len=512, page_size=16,
                           kv_pages=31, max_tokens=480, max_queue=16)

        async def consume(prompt, opts):
            toks = []
            gen = er.stream_generate(prompt, opts)
            try:
                async for item in gen:
                    if isinstance(item, dict):
                        break
                    toks.append(item)
            finally:
                await gen.aclose()
            return toks

        long_task = asyncio.ensure_future(
            consume([1, 2, 3], {"max_tokens": 480}))
        await asyncio.sleep(0.5)              # admitted; pool exhausted
        assert (await er.debug_stats())["kv_pages_free"] == 0
        tok = deadlines.set_current(time.time() + 0.2)
        try:
            with pytest.raises(DeadlineExceededError, match="queue"):
                await consume([7, 8, 9], {"max_tokens": 4})
        finally:
            deadlines.reset(tok)
        assert len(await long_task) == 480    # unharmed by the expiry
        st = await er.debug_stats()
        assert st["expired"] == 1 and st["kv_pages_free"] == 31

    asyncio.run(main())


# ----------------------------------------------------------------- serve ---

def test_open_loop_harness_sustains_load_and_streams(serve_cluster):
    """Acceptance: the open-loop harness sustains an arrival rate with
    >=2 concurrent in-flight requests per replica, streams incrementally
    (first item observed before the stream ends), and continuous
    batching is visible in recorder spans (a late arrival's prefill ran
    while another request was mid-decode)."""
    h = serve.run(build_llm_app(
        "tiny", min_replicas=1, max_replicas=1, max_batch=4, max_len=64,
        page_size=8, max_tokens=40), name="llm-tiny")
    opts = {"max_tokens": 40}

    def submit(p):
        return h.options(stream=True,
                         method_name="stream_generate").remote(p, opts)

    for _ in submit([1, 2, 3]):
        pass                                  # warmup: compile + admit
    rep = run_open_loop(
        submit, rate_hz=40.0, duration_s=2.0,
        prompt_fn=lambda i: [(i % 37) + 1, (i % 11) + 2, 7],
        num_replicas=1)
    assert rep["completed"] == rep["offered"], rep
    assert not rep["errors"] and rep["unfinished"] == 0, rep
    assert rep["max_inflight"] >= 2, rep      # open-loop concurrency
    assert rep["tokens_per_s_per_replica"] > 0
    # streams incrementally: first token lands before the stream ends
    assert 0 < rep["ttft_p50_ms"] < rep["total_p50_ms"], rep

    # Continuous batching, asserted via recorder spans that rode the
    # telemetry flush to the GCS sink: some request was PREFILLED while
    # >=1 other request was actively decoding.
    core = ray_tpu._core()
    deadline = time.monotonic() + 30
    seen = None
    while time.monotonic() < deadline:
        rows = [e for e in core.gcs_call("get_task_events",
                                         {"limit": 100_000})
                if e.get("event") == "SPAN" and e.get("cat") == "request"]
        admits = [e for e in rows if e["name"] == "request:admit"]
        joined = [e for e in rows if e["name"] == "prefill"
                  and (e.get("args") or {}).get("active", 0) >= 1]
        decodes = [e for e in rows if e["name"] == "decode"
                   and (e.get("args") or {}).get("batch", 0) >= 2]
        seen = (len(admits), len(joined), len(decodes))
        if admits and joined and decodes:
            break
        time.sleep(1.0)
    assert seen and all(seen), \
        f"no continuous-batching evidence in recorder spans: {seen}"
    serve.delete("llm-tiny")


def test_autoscales_on_queue_depth_and_back_to_zero(serve_cluster):
    """Queue-driven autoscaling: sustained streaming load grows 1 -> N
    replicas (load = queue depth x page occupancy via __serve_load__);
    idle decays to ZERO; a new request revives the deployment through
    router-reported demand."""
    h = serve.run(build_llm_app(
        "tiny", name="llm-auto", min_replicas=0, max_replicas=3,
        target_load=1.0, downscale_delay_s=2.0, max_batch=2,
        max_len=64, page_size=8, kv_pages=7, max_tokens=48),
        name="llm-auto")
    ctl = ray_tpu.get_actor("SERVE_CONTROLLER")

    def replicas():
        return ray_tpu.get(ctl.debug_state.remote(),
                           timeout=30)["deployments"]["llm-auto"]

    assert replicas() == 1                    # starts at one, not zero
    stop = threading.Event()

    def pump():
        while not stop.is_set():
            try:
                h.remote([1, 2, 3], {"max_tokens": 48}).result(
                    timeout_s=60)
            except Exception:
                pass

    pumps = [threading.Thread(target=pump, daemon=True)
             for _ in range(6)]
    for t in pumps:
        t.start()
    try:
        deadline = time.monotonic() + 60
        grew = False
        while time.monotonic() < deadline:
            if replicas() >= 2:
                grew = True
                break
            time.sleep(0.5)
        assert grew, "never scaled up under queued streaming load"
    finally:
        stop.set()
    for t in pumps:
        t.join(timeout=90)
    # Idle: decays all the way to zero.
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline and replicas() != 0:
        time.sleep(0.5)
    assert replicas() == 0, "never scaled to zero when idle"
    # Demand revives 0 -> 1 and the request completes.
    out = h.remote([4, 5, 6], {"max_tokens": 4}).result(timeout_s=90)
    assert len(out) == 4
    assert replicas() >= 1
    serve.delete("llm-auto")


def test_shed_returns_typed_overloaded_never_hangs(serve_cluster):
    """Once the admission queue exceeds its bound the replica sheds with
    a typed OverloadedError carrying retry_after_s — surfaced unwrapped
    through the serve handle, and nothing hangs."""
    dep = serve.deployment(EngineReplica, name="llm-shed",
                           num_replicas=1,
                           ray_actor_options={"num_cpus": 1})
    h = serve.run(dep.bind("tiny", max_batch=1, max_len=64, page_size=8,
                           kv_pages=4, max_tokens=24, max_queue=2),
                  name="llm-shed")
    h.remote([1, 2, 3], {"max_tokens": 2}).result(timeout_s=120)  # warm
    results, errs = [], []

    def one(i):
        try:
            results.append(h.remote([i + 1, i + 2, i + 3],
                                    {"max_tokens": 24}).result(
                                        timeout_s=120))
        except OverloadedError as e:
            errs.append(e)

    threads = [threading.Thread(target=one, args=(i,), daemon=True)
               for i in range(10)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads), "shed path hung"
    assert errs, "overload never shed"
    assert all(isinstance(e, OverloadedError) and e.retry_after_s > 0
               for e in errs)
    assert results, "every request shed — queue bound too tight"
    serve.delete("llm-shed")


def test_openai_sse_stream_and_finish_reasons(serve_cluster):
    """stream=true serves SSE through the HTTP proxy: incremental data:
    chunks, a final chunk with finish_reason, then [DONE]; non-streaming
    responses carry real finish_reasons too."""
    import json
    import socket
    import urllib.request

    from ray_tpu.llm import build_openai_app
    from ray_tpu.serve import api as serve_api
    serve.start(http_port=0)
    serve.run(build_openai_app(preset="tiny", model_name="tiny-chat",
                               max_len=64),
              name="openai_tiny-chat", route_prefix="/v1")
    port = ray_tpu.get(serve_api._proxy.ready.remote(), timeout=60)

    def sse(path, payload):
        body = json.dumps(payload).encode()
        s = socket.create_connection(("127.0.0.1", port), timeout=120)
        s.sendall(
            f"POST {path} HTTP/1.1\r\nHost: x\r\n"
            f"Content-Type: application/json\r\n"
            f"Content-Length: {len(body)}\r\n\r\n".encode() + body)
        buf = b""
        while b"data: [DONE]" not in buf:
            c = s.recv(65536)
            if not c:
                break
            buf += c
        s.close()
        text = buf.decode(errors="replace")
        head, _, rest = text.partition("\r\n\r\n")
        events = [l[6:] for l in rest.replace("\r\n", "\n").split("\n")
                  if l.startswith("data: ")]
        return head, events

    head, events = sse("/v1/completions",
                       {"prompt": "hello", "max_tokens": 8,
                        "stream": True})
    assert "200 OK" in head and "text/event-stream" in head
    assert "chunked" in head.lower()
    assert events[-1] == "[DONE]"
    parsed = [json.loads(e) for e in events if e != "[DONE]"]
    deltas = [p for p in parsed
              if p["choices"][0].get("text")
              and not p["choices"][0]["finish_reason"]]
    finals = [p["choices"][0]["finish_reason"] for p in parsed
              if p["choices"][0]["finish_reason"]]
    assert len(deltas) >= 2, "tokens did not stream incrementally"
    assert finals == ["length"], finals

    head, events = sse("/v1/chat/completions",
                       {"messages": [{"role": "user", "content": "hi"}],
                        "max_tokens": 5, "stream": True})
    assert any("chat.completion.chunk" in e for e in events)
    assert events[-1] == "[DONE]"

    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/completions",
        data=json.dumps({"prompt": "hey", "max_tokens": 4}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as r:
        res = json.loads(r.read())
    assert res["choices"][0]["finish_reason"] in ("length", "stop")


# ----------------------------------------------------------------- chaos ---

@pytest.mark.chaos
def test_replica_sigkill_mid_stream_breaks_typed_and_recovers(
        serve_cluster):
    """Process chaos: SIGKILL the engine replica mid-decode.  The
    in-flight stream fails TYPED (StreamBrokenError carrying
    tokens-emitted-so-far, never a silent replay), the controller
    replaces the replica, and fresh requests succeed."""
    import os
    import signal

    dep = serve.deployment(EngineReplica, name="llm-kill",
                           num_replicas=1,
                           ray_actor_options={"num_cpus": 1})
    h = serve.run(dep.bind("tiny", max_batch=2, max_len=256,
                           page_size=16, max_tokens=200),
                  name="llm-kill")
    pid = h.pid.remote().result(timeout_s=120)
    # Tight backpressure parks the producer mid-decode, so the kill
    # lands while the stream is genuinely in flight.
    s = h.options(stream=True, method_name="stream_generate",
                  stream_backpressure=2).remote([1, 2, 3],
                                                {"max_tokens": 200})
    it = iter(s)
    got = [next(it), next(it)]
    assert all(isinstance(t, int) for t in got)
    os.kill(pid, signal.SIGKILL)
    with pytest.raises(StreamBrokenError) as ei:
        for _ in it:
            pass
    assert ei.value.tokens_emitted >= 2
    # The controller's reconcile loop replaces the dead replica; a new
    # request (transparently re-routed by the handle) succeeds.
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        try:
            out = h.remote([4, 5, 6], {"max_tokens": 3}).result(
                timeout_s=30)
            assert len(out) == 3
            break
        except Exception:
            time.sleep(1.0)
    else:
        raise AssertionError("deployment never recovered after SIGKILL")
    serve.delete("llm-kill")


@pytest.mark.chaos
def test_pd_split_deadline_through_queue_under_link_chaos():
    """P/D under link chaos: prefill on a SHARDED engine, the KV blob
    moves across shardings to an unsharded decode actor over a link with
    injected latency; a decode whose deadline expires while queued
    behind a pool-exhausting request fails typed
    (`.options(timeout_s=)` propagation through the admission queue),
    and a well-budgeted decode still matches the closed-loop
    reference."""
    import jax

    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(num_cpus=6,
                 _system_config={"link_chaos": "out_delay=0.05"})
    try:
        prompt = [4, 8, 15, 16, 23]
        sp = SamplingParams(max_tokens=4)
        ref = LLMEngine(CFG, max_batch=1, max_len=64, seed=0)
        expect = ref.generate([prompt], sp)[0]

        # Prefill on a tp-sharded engine (driver-side mesh): the blob is
        # gathered to host — the cross-sharding KV move.
        if len(jax.devices()) >= 2:
            from ray_tpu.parallel import MeshSpec, build_mesh
            mesh = build_mesh(MeshSpec(tp=2), devices=jax.devices()[:2])
            pre = LLMEngine(CFG, max_batch=1, max_len=64, seed=0,
                            mesh=mesh)
        else:                                 # pragma: no cover
            pre = LLMEngine(CFG, max_batch=1, max_len=64, seed=0)
        blob, first = pre.prefill_only(prompt, sp)

        Dec = ray_tpu.remote(EngineReplica)
        # Pool sized so ONE long request exhausts it: 3+480+1 tokens ->
        # 31 pages of 16; ~480 decode ticks keep the pool busy far past
        # the short deadline below even on a fast host.
        dec = Dec.remote("tiny", max_batch=2, max_len=512, page_size=16,
                         kv_pages=31, max_tokens=480, prefix_cache=False)
        busy = dec.stream_generate.options(
            num_returns="streaming").remote([1, 2, 3],
                                            {"max_tokens": 480})
        it = iter(busy)
        ray_tpu.get(next(it))                 # admitted: pool now full
        t0 = time.monotonic()
        with pytest.raises(DeadlineExceededError):
            ray_tpu.get(dec.decode.options(timeout_s=0.4).remote(
                blob, first, {"max_tokens": 4}, prompt), timeout=60)
        assert time.monotonic() - t0 < 30
        # The busy stream is unharmed; drain it.
        drained = sum(1 for _ in it)
        assert drained >= 400
        # With a real budget the queued decode admits once pages free,
        # and the tokens match the closed-loop reference exactly.
        res = ray_tpu.get(dec.decode.options(timeout_s=120).remote(
            blob, first, {"max_tokens": 4}, prompt), timeout=180)
        assert res["tokens"] == expect, (res, expect)
        st = ray_tpu.get(dec.debug_stats.remote(), timeout=30)
        assert st["expired"] >= 1 and st["kv_pages_free"] == 31, st
    finally:
        ray_tpu.shutdown()
