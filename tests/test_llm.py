"""LLM library: engine parity, continuous batching, batch processor,
serving patterns (reference model: python/ray/llm tests over the vLLM
engine; here the native JAX engine)."""

import numpy as np
import pytest

import ray_tpu
from ray_tpu.llm import (LLMEngine, ProcessorConfig, SamplingParams,
                         build_dp_deployment, build_llm_processor,
                         run_pd_app)
from ray_tpu.models import PRESETS, forward

CFG = PRESETS["tiny"]


def _ref_greedy(params, prompt, n):
    """Reference continuation: full re-forward argmax each step."""
    import jax.numpy as jnp
    toks = list(prompt)
    out = []
    for _ in range(n):
        logits = forward(params, jnp.asarray([toks], jnp.int32), CFG)
        nxt = int(jnp.argmax(logits[0, -1]))
        out.append(nxt)
        toks.append(nxt)
    return out


def test_engine_matches_full_forward_greedy():
    eng = LLMEngine(CFG, max_batch=2, max_len=64, seed=0)
    prompt = [3, 17, 42, 7, 99, 5, 23]
    got = eng.generate([prompt], SamplingParams(max_tokens=8))[0]
    want = _ref_greedy(eng.params, prompt, 8)
    assert got == want


def test_continuous_batching_mixed_lengths_and_slot_reuse():
    eng = LLMEngine(CFG, max_batch=2, max_len=64, seed=1)
    prompts = [[1, 2, 3], [4, 5, 6, 7, 8, 9, 10, 11], [12, 13]]
    outs = eng.generate(prompts, SamplingParams(max_tokens=5))
    assert len(outs) == 3
    assert all(len(o) == 5 for o in outs)
    # 3 requests through 2 slots: per-request results must still match
    # the full-forward reference (batching can't cross-contaminate).
    for p, o in zip(prompts, outs):
        assert o == _ref_greedy(eng.params, p, 5)


def test_eos_stops_generation():
    eng = LLMEngine(CFG, max_batch=1, max_len=64, seed=0)
    prompt = [3, 17, 42]
    free_run = eng.generate([prompt], SamplingParams(max_tokens=10))[0]
    eos = free_run[3]
    eng2 = LLMEngine(CFG, max_batch=1, max_len=64, seed=0)
    stopped = eng2.generate(
        [prompt], SamplingParams(max_tokens=10, eos_id=eos))[0]
    assert stopped == free_run[:4]
    assert stopped[-1] == eos


def test_prefill_decode_disaggregation_parity():
    pre = LLMEngine(CFG, max_batch=1, max_len=64, seed=0)
    dec = LLMEngine(CFG, max_batch=2, max_len=64, seed=0)
    ref = LLMEngine(CFG, max_batch=1, max_len=64, seed=0)
    prompt = [9, 8, 7, 6, 5]
    sp = SamplingParams(max_tokens=6)
    kv, first = pre.prefill_only(prompt, sp)
    assert kv["len"] == len(prompt)
    got = dec.decode_from(kv, first, sp)
    want = ref.generate([prompt], sp)[0]
    assert got == want


def test_batch_processor_over_data(ray_start_regular):
    from ray_tpu import data as rdata
    rows = []
    rng = np.random.default_rng(0)
    for i in range(6):
        n = int(rng.integers(2, 10))
        toks = np.zeros(16, np.int32)
        toks[:n] = rng.integers(1, CFG.vocab_size, n)
        rows.append({"prompt_tokens": toks, "prompt_len": np.int32(n)})
    proc = build_llm_processor(ProcessorConfig(
        preset="tiny", max_tokens=4, batch_size=3, concurrency=1,
        max_len=64))
    out = proc(rdata.from_items(rows)).take_all()
    assert len(out) == 6
    eng = LLMEngine(CFG, max_batch=4, max_len=64, seed=0)
    for row in out:
        n = int(row["prompt_len"])
        prompt = list(map(int, np.asarray(row["prompt_tokens"])[:n]))
        want = eng.generate([prompt], SamplingParams(max_tokens=4))[0]
        got = list(map(int, np.asarray(
            row["generated_tokens"])[:int(row["generated_tokens_len"])]))
        assert got == want


@pytest.fixture
def serve_cluster():
    from ray_tpu import serve
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(num_cpus=6)
    serve.start()
    yield
    serve.shutdown()
    ray_tpu.shutdown()


def test_dp_serving_pattern(serve_cluster):
    from ray_tpu import serve
    handle = serve.run(build_dp_deployment(
        "tiny", num_replicas=2, max_tokens=4, max_len=64, seed=0))
    prompt = [11, 22, 33, 44]
    got = handle.remote(prompt).result(timeout_s=120)
    eng = LLMEngine(CFG, max_batch=4, max_len=64, seed=0)
    assert got == eng.generate([prompt], SamplingParams(max_tokens=4))[0]


def test_pd_disaggregation_serving_pattern(serve_cluster):
    handle = run_pd_app("tiny", max_len=64, seed=0)
    prompt = [5, 4, 3, 2]
    got = handle.remote(prompt, 5).result(timeout_s=180)
    eng = LLMEngine(CFG, max_batch=4, max_len=64, seed=0)
    assert got == eng.generate([prompt], SamplingParams(max_tokens=5))[0]


def test_openai_compatible_api(ray_start_regular):
    """OpenAI surface over the native engine (reference:
    llm/_internal/serve build_openai_app): /v1/models, /v1/completions,
    /v1/chat/completions with the standard JSON shapes, end-to-end
    through the Serve HTTP proxy."""
    import json
    import urllib.request

    from ray_tpu import serve
    from ray_tpu.llm import build_openai_app

    serve.start(http_port=0)
    from ray_tpu.serve import api as serve_api
    serve.run(build_openai_app(preset="tiny", model_name="tiny-chat"),
              name="openai_tiny-chat", route_prefix="/v1")
    import ray_tpu as rt
    proxy_port = rt.get(serve_api._proxy.ready.remote(), timeout=60)
    base = f"http://127.0.0.1:{proxy_port}/v1"

    try:
        _run_openai_assertions(base)
    finally:
        serve.shutdown()


def _run_openai_assertions(base):
    import json
    import urllib.request

    def call(path, payload=None):
        if payload is None:
            req = urllib.request.Request(base + path)
        else:
            req = urllib.request.Request(
                base + path, data=json.dumps(payload).encode(),
                headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as r:
            return json.loads(r.read())

    models = call("/models")
    assert models["object"] == "list"
    assert models["data"][0]["id"] == "tiny-chat"

    comp = call("/completions", {"prompt": "hello", "max_tokens": 4})
    assert comp["object"] == "text_completion"
    assert len(comp["choices"]) == 1
    assert comp["usage"]["completion_tokens"] > 0
    assert isinstance(comp["choices"][0]["text"], str)

    chat = call("/chat/completions", {
        "messages": [{"role": "user", "content": "hi"}],
        "max_tokens": 4})
    assert chat["object"] == "chat.completion"
    assert chat["choices"][0]["message"]["role"] == "assistant"
    assert chat["usage"]["total_tokens"] > 0

    # Error contract: bad requests return REAL HTTP statuses (OpenAI
    # SDKs key exception types off them), not 200 + error body.
    import urllib.error
    try:
        call("/chat/completions", {"messages": []})
        assert False, "expected HTTP 400"
    except urllib.error.HTTPError as e:
        assert e.code == 400
        assert "messages" in json.loads(e.read())["error"]["message"]


def test_tp_sharded_engine_identical_tokens():
    """VERDICT r3 item 2: a GSPMD tp-sharded decode produces the same
    tokens as the single-device engine (weights sharded heads/kv/mlp over
    tp, KV pool sharded on kv_heads)."""
    import jax
    from ray_tpu.parallel import MeshSpec, build_mesh

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    mesh = build_mesh(MeshSpec(tp=4), devices=jax.devices()[:4])
    prompts = [[5, 6, 7], [9, 10, 11, 12, 13], [21, 22]]
    sp = SamplingParams(max_tokens=8)
    ref = LLMEngine(CFG, max_batch=2, max_len=64, seed=0)
    out_ref = ref.generate(prompts, sp)
    shd = LLMEngine(CFG, max_batch=2, max_len=64, seed=0, mesh=mesh)
    wq = shd.params["layers"]["attn"]["wq"]
    assert "tp" in str(wq.sharding.spec), wq.sharding.spec
    assert "tp" in str(shd._pk.sharding.spec), shd._pk.sharding.spec
    out_shd = shd.generate(prompts, sp)
    assert out_shd == out_ref, (out_shd, out_ref)


def test_paged_kv_oversubscribed_pool_queues_and_completes():
    """A pool smaller than max_batch*max_len still serves every request:
    admission waits for pages, retirement recycles them."""
    eng = LLMEngine(CFG, max_batch=4, max_len=64, seed=0,
                    page_size=16, kv_pages=6)
    assert eng.n_pages == 7            # 6 usable + scratch
    sp = SamplingParams(max_tokens=6)
    # each request needs ceil((3+6+1)/16)=1 page; 8 requests through 6 pages
    prompts = [[i + 1, i + 2, i + 3] for i in range(8)]
    outs = eng.generate(prompts, sp)
    assert len(outs) == 8 and all(len(o) == 6 for o in outs)
    assert eng.kv_pages_free() == 6    # all recycled
    # parity with an uncontended engine
    ref = LLMEngine(CFG, max_batch=4, max_len=64, seed=0)
    assert outs == ref.generate(prompts, sp)


def test_pd_kv_transfer_across_sharding_layouts():
    """P/D disaggregation moves KV between engines with different
    shardings: unsharded prefill -> tp-sharded decode and back."""
    import jax
    from ray_tpu.parallel import MeshSpec, build_mesh

    if len(jax.devices()) < 2:
        pytest.skip("needs 2 virtual devices")
    mesh = build_mesh(MeshSpec(tp=2), devices=jax.devices()[:2])
    sp = SamplingParams(max_tokens=6)
    prompt = [4, 8, 15, 16, 23]
    ref = LLMEngine(CFG, max_batch=1, max_len=64, seed=0)
    expect = ref.generate([prompt], sp)[0]

    pre = LLMEngine(CFG, max_batch=1, max_len=64, seed=0)
    dec_shd = LLMEngine(CFG, max_batch=2, max_len=64, seed=0, mesh=mesh)
    blob, first = pre.prefill_only(prompt, sp)
    assert dec_shd.decode_from(blob, first, sp) == expect

    pre_shd = LLMEngine(CFG, max_batch=1, max_len=64, seed=0, mesh=mesh)
    dec = LLMEngine(CFG, max_batch=2, max_len=64, seed=0)
    blob2, first2 = pre_shd.prefill_only(prompt, sp)
    assert dec.decode_from(blob2, first2, sp) == expect


def test_unserviceable_request_rejected_up_front():
    eng = LLMEngine(CFG, max_batch=1, max_len=64, seed=0,
                    page_size=16, kv_pages=2)
    with pytest.raises(ValueError, match="KV pages"):
        eng.add_request(list(range(1, 41)), SamplingParams(max_tokens=20))


# ---- the decode step reads live pages only (ops/paged_attention.py) ------

def _wide_head_cfg():
    """128-wide heads (what the Pallas kernel tiles), small enough for the
    CPU: 4 query heads over 2 KV heads, bf16 as it is served."""
    import jax.numpy as jnp
    from ray_tpu.models import TransformerConfig
    return TransformerConfig(
        vocab_size=256, hidden_size=256, intermediate_size=512, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=128, max_seq_len=256,
        dtype=jnp.bfloat16)


def _narrow_head_cfg(kv=2):
    """A dense decoder with 64-wide heads whose KV heads fill whole lane
    rows: the pool holds a token's row as lanes (`pool_row`).  float32, so
    that greedy tokens can be compared."""
    import jax.numpy as jnp
    from ray_tpu.models import TransformerConfig
    return TransformerConfig(
        vocab_size=256, hidden_size=128, intermediate_size=256, num_layers=2,
        num_heads=2 * kv, num_kv_heads=kv, head_dim=64, max_seq_len=256,
        dtype=jnp.float32)


@pytest.mark.parametrize("path", ["reference", "kernel", "lanes2", "lanes8"])
def test_decode_logits_through_cache_match_float32_forward(path, monkeypatch):
    """Prefill, then 2 x page + 3 decode steps through the paged cache: each
    step's logits against a float32 forward pass over the whole sequence.
    `tiny` takes the plain function; 128-wide heads take the Pallas kernel,
    here interpreted (steered in the test: there is no TPU); 64-wide heads
    (KV x D = 128 and 512) the plain function over rows of lanes."""
    import dataclasses
    import functools

    import jax
    import jax.numpy as jnp
    from ray_tpu.llm import programs as E
    from ray_tpu.ops import paged_attention as pa

    page = 16
    if path == "kernel":
        cfg, tol = _wide_head_cfg(), 0.08
        monkeypatch.setattr(pa, "decode_path", lambda *shapes: "pallas")
        monkeypatch.setattr(pa, "_paged_decode_pallas", functools.partial(
            pa._paged_decode_pallas, interpret=True))
        monkeypatch.setattr(pa, "_CHUNK_ROWS", 2 * page * cfg.num_kv_heads)
    elif path.startswith("lanes"):
        cfg, tol = _narrow_head_cfg(int(path[5:])), 2e-4
    else:
        cfg, tol = CFG, 2e-4
    steps = 2 * page + 3
    eng = LLMEngine(cfg, max_batch=2, max_len=4 * page, page_size=page,
                    seed=0)
    assert eng.decode_stats()["pool_row"] == (
        "lanes" if path.startswith("lanes") else "heads")
    assert eng._pk.ndim == (4 if path.startswith("lanes") else 5)
    prompt = [3, 17, 42, 7, 99, 5, 23, 11, 2]
    eng.add_request(prompt, SamplingParams(max_tokens=steps + 2))
    assert eng._admit() == 1                     # prefill; slot 0 holds it
    slot = next(iter(eng._slots))
    f32 = dataclasses.replace(cfg, dtype=jnp.float32)
    params32 = jax.tree.map(lambda a: a.astype(jnp.float32), eng.params)
    width = len(prompt) + steps + 1               # one program: causal
    full = jax.jit(lambda toks, n: forward(params32, toks, f32)[0, n - 1])
    decode = jax.jit(lambda pk, pv, tb, lt, ln, ac: E._decode_logits_fn(
        eng.params, pk, pv, tb, lt, ln, ac, cfg, eng.page, None))
    active = np.zeros(eng.max_batch, bool)
    active[slot] = True
    toks = prompt + [int(eng._last[slot])]
    pk, pv, worst = eng._pk, eng._pv, 0.0
    for i in range(steps):
        lengths = eng._lengths.copy()
        lengths[slot] = len(toks) - 1
        last = eng._last.copy()
        last[slot] = toks[-1]
        pk, pv, logits = decode(pk, pv, jnp.asarray(eng._tables),
                                jnp.asarray(last), jnp.asarray(lengths),
                                jnp.asarray(active))
        want = np.asarray(full(jnp.asarray(
            [toks + [0] * (width - len(toks))], jnp.int32), len(toks)))
        got = np.asarray(logits[slot])
        worst = max(worst, np.abs(got - want).max())
        assert worst < tol, (i, worst)
        toks.append(int(np.argmax(want)))


def _narrow_engine(**kw):
    kw = {"max_batch": 2, "max_len": 128, "page_size": 16, "seed": 0, **kw}
    return LLMEngine(_narrow_head_cfg(), **kw)


@pytest.mark.parametrize("what", ["greedy", "prefix_hit", "chunked",
                                  "demotion", "shipped", "streamed", "tp2"])
def test_narrow_heads_dense_decoder_through_every_pool_path(what):
    """A dense decoder with 64-wide heads (KV 2 x 64: the pool's row is 128
    lanes) through every path that writes or reads the pool: the tokens are
    those of the plain engine, and the plain engine's those of a forward
    pass over the whole sequence."""
    import jax
    import jax.numpy as jnp
    cfg = _narrow_head_cfg()
    rng = np.random.default_rng(1)
    doc = rng.integers(1, cfg.vocab_size, 50).tolist()
    first, second = doc + [7, 8, 9], doc + [11, 12, 13, 14]
    sp = SamplingParams(max_tokens=20)       # past a page's end
    plain = _narrow_engine()
    assert plain.decode_stats()["pool_row"] == "lanes"
    assert plain._pk.shape == (2, 17, 16, 128)
    want = plain.generate([second], sp)[0]
    if what == "greedy":
        toks = list(second)
        for tok in want:
            logits = forward(plain.params, jnp.asarray([toks], jnp.int32), cfg)
            assert int(jnp.argmax(logits[0, -1])) == tok
            toks.append(tok)
        return
    if what == "prefix_hit":                 # the XLA suffix arm
        eng = _narrow_engine(prefix_cache=True)
        eng.generate([first], sp)
        assert eng.generate([second], sp)[0] == want
        assert eng.prefix_cache_stats()["hit_pages"] == 3
    elif what == "chunked":                  # suffix prefills, chunk by chunk
        eng = _narrow_engine(prefill_chunk=16)
        assert eng.generate([second], sp)[0] == want
    elif what == "demotion":                 # pool -> host -> pool
        eng = _narrow_engine(prefix_cache=True, kv_pages=12)
        assert eng._demote is not None
        eng.generate([second], sp)
        while eng._cache._entries:
            eng._cache.evict_lru(eng._decref, eng._demote_entry)
        part = next(iter(eng._demote._host.values()))
        assert part["k"].shape[-2:] == (2, 64)      # stored by heads
        assert eng.generate([second], sp)[0] == want
        assert eng.prefix_cache_stats()["promoted_pages"] > 0
    elif what == "shipped":                  # prefill here, decode there
        pre = _narrow_engine(prefix_cache=True)
        pre.prefill_only(first, sp)          # leaves the document's pages
        blob, tok = pre.prefill_only(second, sp)    # gathers them back
        assert blob["k"].shape == (2, len(second), 2, 64)
        assert _narrow_engine().decode_from(blob, tok, sp) == want
    elif what == "streamed":                 # the tail in the pool
        pre = _narrow_engine(kv_pages=4)
        handoff = pre.prefill_paged(second, sp, span=32)
        dec = _narrow_engine(kv_pages=6)
        rid = dec.add_paged_request(handoff["parts"], handoff["len"],
                                    handoff["first"], sp)
        out = None
        while dec.has_unfinished():
            for done in dec.step():
                if done.req_id == rid:
                    out = done.out
        assert out == want
    elif what == "tp2":                      # a shard's row: one head, 64
        from ray_tpu.parallel import MeshSpec, build_mesh
        if len(jax.devices()) < 2:
            pytest.skip("needs 2 virtual devices")
        mesh = build_mesh(MeshSpec(tp=2), devices=jax.devices()[:2])
        eng = _narrow_engine(mesh=mesh, prefix_cache=True)
        assert "tp" in str(eng._pk.sharding.spec)
        eng.generate([first], sp)
        assert eng.generate([second], sp)[0] == want


def test_tokens_do_not_depend_on_what_is_addressable():
    """The same requests under max_len 256 and 1,024: the step reads what
    is live, so the tokens are the same."""
    prompts = [[5, 6, 7], list(range(9, 49)), [21, 22]]
    sp = SamplingParams(max_tokens=24)
    outs = [LLMEngine(CFG, max_batch=2, max_len=n, page_size=16,
                      seed=0).generate(prompts, sp) for n in (256, 1024)]
    assert outs[0] == outs[1]
    assert all(len(o) == 24 for o in outs[0])


def test_decode_step_writes_the_pool_in_place():
    """The compiled decode step aliases both pools and its resident state
    (the slots' rows: tables, last tokens, lengths, active mask,
    temperatures; and the key) to its outputs, the packed update to none,
    and, where the backend says, needs less temporary memory than one
    pool."""
    import re

    import jax
    eng = LLMEngine(CFG, max_batch=2, max_len=64, page_size=16,
                    kv_pages=256, seed=0)
    compiled = eng._decode_jit.lower(
        eng.params, eng._pk, eng._pv, eng._dev, eng._no_rows).compile()
    n_params = len(jax.tree.leaves(eng.params))
    n_state = len(jax.tree.leaves(eng._dev))
    assert n_state == 2                       # the slots' rows and the key
    head = compiled.as_text().split("\n", 1)[0]
    aliases = {int(o): int(i) for o, i in
               re.findall(r"\{(\d+)\}: \((\d+), \{\}", head)}
    # Outputs: pool_k, pool_v, the state's leaves, next tokens (not aliased).
    assert aliases == {o: n_params + o for o in range(2 + n_state)}, head
    mem = compiled.memory_analysis()
    if mem is not None and mem.temp_size_in_bytes:
        assert mem.temp_size_in_bytes < eng._pk.nbytes, mem


# ---- the decode step's resident state (llm/programs.py:_decode_fn) ---------

def _engine(mesh_axes, **kw):
    """An engine on one device, or on a forced-CPU mesh of `mesh_axes`."""
    if mesh_axes is None:
        return LLMEngine(CFG, **kw)
    import jax
    from ray_tpu.parallel import MeshSpec, build_mesh
    n = int(np.prod(list(mesh_axes.values())))
    assert len(jax.devices()) >= n
    return LLMEngine(CFG, mesh=build_mesh(MeshSpec(**mesh_axes),
                                          devices=jax.devices()[:n]), **kw)


def _check_against_host_rebuilt_steps(eng, seed):
    """Wrap the engine's decode step: before each one, the parent's step is
    run beside it, on inputs rebuilt from the HOST's mirrors and with the
    key split on the host, and the tokens must agree; after it, the state
    on the device must be what the mirrors say.  Returns the list the
    checked steps are counted in."""
    import jax
    from ray_tpu.llm import programs as E

    cfg, page, kv_shd = eng.cfg, eng.page, eng._kv_shd
    parent_step = jax.jit(
        lambda p, pk, pv, tb, lt, ln, ac, tp, key: E._sample_fn(
            E._decode_logits_fn(p, pk, pv, tb, lt, ln, ac, cfg, page,
                                kv_shd)[2], ac, tp, key))
    host = {"rng": jax.random.key(seed + 1)}
    resident, first_tokens, checked = eng._decode_jit, eng._sample_batch, []

    def sample_batch(logits_list, params_list):
        if any(p.temperature > 0 for p in params_list):   # the parent's rule
            host["rng"], _ = jax.random.split(host["rng"])
        return first_tokens(logits_list, params_list)

    def step(params, pk, pv, state, update):
        active = np.zeros(eng.max_batch, bool)
        active[[s for s, r in eng._slots.items() if not r.kv_paged]] = True
        host["rng"], key = jax.random.split(host["rng"])
        want = np.asarray(parent_step(
            params, pk, pv, eng._tables, eng._last, eng._lengths, active,
            eng._temps, key))
        mirrors = E._pack_rows(
            eng._tables, np.where(active, want, eng._last),
            eng._lengths + active, active, eng._temps, False)[:, :-1]
        pk, pv, state, nxt = resident(params, pk, pv, state, update)
        np.testing.assert_array_equal(np.asarray(nxt), want)
        np.testing.assert_array_equal(np.asarray(state["slots"]), mirrors)
        np.testing.assert_array_equal(
            jax.random.key_data(state["rng"]),
            jax.random.key_data(host["rng"]))
        checked.append(int(active.sum()))
        return pk, pv, state, nxt

    eng._decode_jit, eng._sample_batch = step, sample_batch
    return checked


@pytest.mark.parametrize("mesh_axes", [None, {"tp": 2}], ids=["one", "tp2"])
@pytest.mark.parametrize("temps", [(0.0,), (0.8, 0.0, 1.3)],
                         ids=["greedy", "sampled"])
def test_resident_decode_state_gives_the_host_rebuilt_steps_tokens(
        temps, mesh_axes):
    """Admissions, retirements, slot reuse, a prefix-cache hit, a cancelled
    request and a chunked prefill: in every decode step the tokens from the
    state that lives on the device equal those of the parent's step on
    inputs rebuilt from the host's mirrors."""
    seed, page = 3, 16
    eng = _engine(mesh_axes, max_batch=3, max_len=128, page_size=page,
                  prefix_cache=True, prefill_chunk=2 * page, seed=seed)
    checked = _check_against_host_rebuilt_steps(eng, seed)
    rng = np.random.default_rng(0)
    doc = rng.integers(1, CFG.vocab_size, 3 * page).tolist()
    prompts = [rng.integers(1, CFG.vocab_size, n).tolist()
               for n in (5, 5 * page + 3, 9, 12, 7, 20)]
    prompts[2] = doc + prompts[2]            # leaves the document's pages
    prompts[3] = doc + prompts[3]            # ... for this one to hit
    arrive = {0: [0, 1, 2], 3: [3, 4], 9: [5]}
    ids, outs, chunked, steps, cancelled = {}, {}, 0, 0, False
    while steps == 0 or eng.has_unfinished():
        for i in arrive.get(steps, ()):
            ids[i] = eng.add_request(prompts[i], SamplingParams(
                max_tokens=6 + 3 * i, temperature=temps[i % len(temps)]))
        req = eng._requests.get(ids.get(4))
        if req is not None and len(req.out) == 3:   # mid-decode, slot live
            assert eng._slots.get(req.slot) is req
            cancelled = eng.cancel_request(ids[4])
        chunked += bool(eng._prefilling)
        for req in eng.step():
            outs[req.req_id] = req.out
        steps += 1
        assert steps < 200
    assert cancelled and chunked >= 2 and eng._cache.hits >= 1
    assert sorted(outs) == sorted(ids[i] for i in (0, 1, 2, 3, 5))
    assert all(len(outs[ids[i]]) == 6 + 3 * i for i in (0, 1, 2, 3, 5))
    st = eng.decode_stats()
    assert st["steps"] == len(checked) > 20 and max(checked) == 3
    # Six requests took a slot, five of them retired and one was cancelled:
    # each is a row to write (one row, where a slot was freed and taken
    # between two steps), and most steps write none.
    assert 6 <= st["state_syncs"] < st["steps"] // 2
    assert st["state_syncs"] <= st["state_rows"] <= 12
    assert not eng._touched.any() or not eng._slots


def test_decode_state_accepts_only_the_marked_rows():
    """`_pack_rows` -> `_accept_rows`: the rows the host marks replace the
    device's, bit for bit (a temperature too); the others stay."""
    import jax
    from ray_tpu.llm import programs as E
    B, P = 5, 7
    rng = np.random.default_rng(1)
    draw = lambda: (rng.integers(0, 99, (B, P)), rng.integers(0, 99, B),
                    rng.integers(0, 99, B), rng.random(B) < 0.5,
                    rng.random(B).astype(np.float32) * 2)
    old, new = draw(), draw()
    take = np.array([True, False, False, True, False])
    slots = E._pack_rows(*old, False)[:, :-1]
    got = np.asarray(jax.jit(E._accept_rows)(slots, E._pack_rows(*new, take)))
    assert got.dtype == np.int32 and got.shape == (B, P + 4)
    want = [np.where(take.reshape((B,) + (1,) * (o.ndim - 1)), n, o)
            for o, n in zip(old, new)]
    np.testing.assert_array_equal(got[:, :P], want[0])
    for col, w in zip((E._COL_LAST, E._COL_LENGTH, E._COL_ACTIVE), want[1:]):
        np.testing.assert_array_equal(got[:, P + col], w)
    np.testing.assert_array_equal(
        got[:, P + E._COL_TEMP].view(np.float32), want[4])


@pytest.mark.parametrize("mesh_axes", [None, {"tp": 2}], ids=["one", "tp2"])
def test_step_with_no_slot_touched_uploads_nothing(mesh_axes):
    """A decode step before which the host touched no slot moves nothing
    from host to device; a step after an admission or a retirement writes
    the touched rows, and only them; neither kind compiles again."""
    import jax
    from ray_tpu._private.compile_cache import compile_cache_stats
    eng = _engine(mesh_axes, max_batch=4, max_len=64, page_size=16, seed=0)
    count = lambda: [eng.decode_stats()[k] for k in (
        "steps", "state_syncs", "state_rows", "step_state_rows")]
    sampled = SamplingParams(max_tokens=12, temperature=0.9)
    eng.add_request([1, 2, 3], SamplingParams(max_tokens=3))
    eng.step()
    assert count() == [1, 1, 1, 1]
    eng.add_request([4, 5, 6, 7], sampled)
    assert [len(r.out) for r in eng.step()] == [3]   # the first one retires
    assert count() == [2, 2, 2, 1]
    eng.step()                      # its freed slot is a row to write
    assert count() == [3, 3, 3, 1]
    compiles = compile_cache_stats()["requests"]
    with jax.transfer_guard_host_to_device("disallow_explicit"):
        for _ in range(4):
            eng.step()
    assert count() == [7, 3, 3, 0]
    eng.add_request([8, 9, 10], sampled)
    eng.step()
    assert count() == [8, 4, 4, 1]
    assert compile_cache_stats()["requests"] == compiles


def _script(eng, events, steps):
    """Drive `eng` by a script: at call i, `events.get(i)` is run with the
    engine before `step()`.  Returns every request's tokens, by id."""
    outs = {}
    for i in range(steps):
        if i in events:
            events[i](eng)
        for r in eng.step():
            outs[r.req_id] = list(r.out)
    assert not eng.has_unfinished()
    return outs


@pytest.mark.parametrize("owner", ["never_holds", "always_holds"])
def test_a_step_sent_ahead_changes_no_token(owner):
    """With an owner who can say that nobody waits (`hold_ahead`), `step()`
    sends the next decode step off before it returns where that step needs
    nothing of the host, and the one after it before that one is read.
    Requests that end by length and by eos, one that arrives while steps
    are out, one cancelled while steps are out and its slot taken by the
    next: every token is the one the engine without an owner gives, and
    the stats count the steps that were read.  An owner for whom somebody
    always waits gets the lockstep order, step for step."""
    make = lambda: _engine(None, max_batch=2, max_len=64, page_size=16,
                           seed=0)
    first = make().generate([[5, 6, 7]], SamplingParams(max_tokens=9))[0]
    eos = SamplingParams(max_tokens=9, eos_id=first[4])
    events = {
        0: lambda e: (e.add_request([5, 6, 7], eos),
                      e.add_request([1, 2, 3, 4], SamplingParams(max_tokens=14))),
        3: lambda e: e.add_request([9, 8, 7], SamplingParams(max_tokens=6)),
        8: lambda e: e.cancel_request(2),
        9: lambda e: e.add_request([2, 4, 6, 8], SamplingParams(max_tokens=5)),
    }
    plain = make()
    want = _script(plain, events, 26)
    assert len(want) == 3 and len(want[0]) == 5
    eng = make()
    eng.hold_ahead = {"never_holds": lambda: False,
                      "always_holds": lambda: True}[owner]
    assert _script(eng, events, 26) == want
    ns = eng.phases.snapshot()["ns"]
    stats = eng.decode_stats()
    if owner == "always_holds":
        assert ns["ahead"] == 0 and stats == plain.decode_stats()
        assert stats["steps_queued"] == 0
        return
    assert ns["ahead"] > 0
    # a step that was out when its last row went is dropped unread
    assert stats["steps"] == plain.decode_stats()["steps"]
    assert 0 < stats["steps_queued"] < stats["steps"]
    assert stats["pages_read"] == plain.decode_stats()["pages_read"]


def test_a_reply_that_ends_by_length_has_no_step_queued_behind_it():
    """The host counts a reply's tokens, so the call that will retire it
    queues no step behind the one it reads (and sends none ahead after
    it): the caller who comes back finds at most one running step in front
    of its prefill."""
    eng = _engine(None, max_batch=2, max_len=64, page_size=16, seed=0)
    eng.hold_ahead = lambda: False
    eng.add_request([1, 2, 3, 4], SamplingParams(max_tokens=8))
    out = []                    # per call: (steps queued so far, a step out)
    while eng.has_unfinished():
        done = eng.step()
        out.append((eng.decode_stats()["steps_queued"],
                    eng._ahead is not None, bool(done)))
    # first token + 7 decode steps: the first call dispatches and sends one
    # ahead, five calls queue, the last reads with nothing behind it
    assert [o[1] for o in out] == [True] * 6 + [False]
    assert [o[2] for o in out] == [False] * 6 + [True]
    assert out[-1][0] == 5 and eng.decode_stats()["steps"] == 7


def test_an_eos_rows_dead_step_writes_its_own_page_alone(captured_recorder):
    """A reply that ends by eos is seen one step late: the step queued
    behind the one that sampled the eos still holds its row.  That dead
    step writes the row's own page and no other, its token is dropped, and
    the `decode` spans count the rows the lockstep order counts."""
    make = lambda: _engine(None, max_batch=2, max_len=64, page_size=16,
                           seed=0)
    first = make().generate([[5, 6, 7]], SamplingParams(max_tokens=9))[0]
    params = [SamplingParams(max_tokens=9, eos_id=first[4]),
              SamplingParams(max_tokens=12)]
    pools, batches, outs, own = [], [], [], []
    for hold in (None, lambda: False):
        with captured_recorder() as rec:
            eng = make()
            eng.hold_ahead = hold
            for prompt, p in zip(([5, 6, 7], [1, 2, 3, 4]), params):
                eng.add_request(prompt, p)
            done, dead = {}, 0
            while eng.has_unfinished():
                done.update((r.req_id, list(r.out)) for r in eng.step())
                if not own:
                    own = list(eng._requests[0].pages)
                flight = eng._ahead
                dead += flight is not None and any(
                    eng._slots.get(s) is not r
                    for s, r in flight.batch.items())
            batches.append([r["args"]["batch"] for r in rec.rows()
                            if r["name"] == "decode"])
        assert (dead > 0) == (hold is not None)
        outs.append(done)
        pools.append((np.asarray(eng._pk), np.asarray(eng._pv)))
    assert outs[0] == outs[1] and outs[0][0] == first[:5]
    assert batches[0] == batches[1] and min(batches[0]) == 1
    others = [p for p in range(1, pools[0][0].shape[1]) if p not in own]
    for plain, queued in zip(*pools):
        np.testing.assert_array_equal(plain[:, others], queued[:, others])
    # ... and it did write there: the eos token's keys, one row past the
    # last the lockstep order wrote
    assert any((a[:, own] != b[:, own]).any() for a, b in zip(*pools))


def test_a_tick_admits_one_slots_worth_of_prompt():
    """Two long prompts that arrive in one tick are admitted a tick apart,
    in their order, and end a tick apart (callers of a closed loop do not
    come back together); short ones still take their slots in one tick; and
    nobody's tokens change."""
    make = lambda: _engine(None, max_batch=4, max_len=64, page_size=16,
                           seed=0)
    long_a, long_b, short = list(range(1, 41)), list(range(41, 81)), [7, 8, 9]
    sp = SamplingParams(max_tokens=6)
    eng = make()
    for p in (long_a, long_b, short):
        eng.add_request(p, sp)
    ends, active = {}, []
    for tick in range(12):
        for r in eng.step():
            ends[r.req_id] = tick
        active.append(eng.active_requests + len(ends))
    # 40 + 40 > 64: the second waits a tick, and the short one behind it
    # goes with it (40 + 3 <= 64)
    assert active[:2] == [1, 3] and not eng.has_unfinished()
    assert ends[1] == ends[0] + 1 == ends[2]
    assert eng.phases.admitting == 2
    alone = [make().generate([p], sp)[0] for p in (long_a, long_b, short)]
    again = make()
    assert again.generate([long_a, long_b, short], sp) == alone
    # three short prompts: one tick
    eng = make()
    for p in ([1, 2], [3, 4, 5], short):
        eng.add_request(p, sp)
    eng.step()
    assert eng.active_requests == 3 and eng.phases.admitting == 1


def test_debug_stats_count_pages_read():
    """`debug_stats()["decode"]`: pages the decode steps read (live ones:
    lengths // page + 1 of each active slot) beside what the tables
    address, and the path taken."""
    import asyncio

    from ray_tpu.llm.serving import EngineReplica

    page, n_prompt, n_out = 16, 30, 12

    async def run():
        er = EngineReplica("tiny", max_batch=2, max_len=128, page_size=page,
                           seed=0)
        out = await er.generate(list(range(1, n_prompt + 1)),
                                {"max_tokens": n_out})
        assert len(out["tokens"]) == n_out
        return await er.debug_stats()

    stats = asyncio.run(run())
    # One whole-prompt prefill of 30 tokens in a 32 bucket, on the CPU.
    assert stats["prefill"] == {"path": "xla", "kernel_calls": 0,
                                "xla_calls": 1, "kv_blocks_run": 1,
                                "kv_blocks_dense": 1, "row_blocks_run": 1,
                                "row_blocks_dense": 1}
    st = stats["decode"]
    # Prefill gives the first token; decode step i attends n_prompt + i.
    lengths = [n_prompt + i for i in range(n_out - 1)]
    assert st["path"] == "reference" and st["pool_row"] == "heads"
    assert st["steps"] == len(lengths)
    assert st["pages_read"] == sum(n // page + 1 for n in lengths)
    assert st["pages_addressable"] == len(lengths) * 2 * (128 // page)
    assert st["step_pages_read"] == lengths[-1] // page + 1
    assert st["step_pages_addressable"] == 2 * (128 // page)


# ---- prefill without the S x S scores (ops/prefill_attention.py) ---------

@pytest.fixture
def prefill_kernel(monkeypatch):
    """Call it to steer the prefill bodies onto the kernel, interpreted, in
    blocks of 128 from 128 padded rows up (there is no TPU here; the chooser
    itself is in tests/test_prefill_attention.py)."""
    import functools

    from ray_tpu.ops import prefill_attention as pfa

    def steer():
        monkeypatch.setattr(pfa, "_BLOCK", 128)
        monkeypatch.setattr(
            pfa, "prefill_path", lambda q, kv, dtype, **kw: "kernel"
            if q[0] >= 128 and pfa.kernel_tiles(q, kv, dtype, **kw)
            else "xla")
        monkeypatch.setattr(pfa, "_prefill_attention_pallas",
                            functools.partial(pfa._prefill_attention_pallas,
                                              interpret=True))
    return steer


def _tiny_wide():
    """`tiny` with 128-wide heads, which the prefill kernel tiles; float32
    as `tiny` is, so the two forms agree to rounding."""
    import dataclasses
    return dataclasses.replace(CFG, head_dim=128, max_seq_len=512)


@pytest.mark.parametrize("form", ["whole", "suffix"])
def test_prefill_bodies_through_the_kernel_match_xla(form, prefill_kernel):
    """`_prefill_fn`, whole and over cached pages, with the kernel against their
    XLA expression: last-token logits and the rows to install below
    `length` (rows past it are garbage under either)."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.llm import programs as E

    cfg, page, rows, length = _tiny_wide(), 16, 256, 150
    rng = np.random.default_rng(0)
    eng = LLMEngine(cfg, max_batch=2, max_len=512, page_size=page, seed=0)
    toks = np.zeros((1, rows), np.int32)
    toks[0, :length] = rng.integers(1, cfg.vocab_size, length)
    if form == "whole":
        fn = lambda: jax.jit(lambda p, t, n: E._prefill_fn(p, t, n, cfg))(
            eng.params, jnp.asarray(toks), length)
    else:
        # Slot 0 holds a 100-token prompt; its first 4 pages are the prefix.
        eng.add_request(rng.integers(1, cfg.vocab_size, 100).tolist(),
                        SamplingParams(max_tokens=4))
        assert eng._admit() == 1
        row = jnp.asarray(eng._tables[next(iter(eng._slots))])
        fn = lambda: jax.jit(
            lambda p, pk, pv, pg, t, pl, n: E._prefill_fn(
                p, t, n, cfg, cached=(pk, pv, pg, pl, page)))(
            eng.params, eng._pk, eng._pv, row, jnp.asarray(toks), 4 * page,
            length)
    want = [np.asarray(a) for a in fn()]
    prefill_kernel()
    assert E._prefill_path(cfg, rows, None, *(
        (page, eng.pages_per_slot) if form == "suffix" else ())) == "kernel"
    got = [np.asarray(a) for a in fn()]
    assert all(np.isfinite(a).all() for a in got)
    assert np.abs(got[0] - want[0]).max() < 2e-4 * np.abs(want[0]).max()
    for g, w in zip(got[1:], want[1:]):                    # ks, vs
        assert g.shape == w.shape == (cfg.num_layers, rows,
                                      cfg.num_kv_heads, 128)
        assert np.abs(g[:, :length] - w[:, :length]).max() < 1e-4


def test_prefix_hit_through_the_kernel_gives_the_whole_prompts_tokens(
        prefill_kernel):
    """Greedy tokens of a prefix-cache hit (suffix form over 4 cached pages)
    equal those of the same prompt prefilled whole, both through the
    kernel; `prefill_stats()` (served as `debug_stats()["prefill"]`) counts
    the calls of each form and the key blocks run beside the dense ones."""
    cfg, page = _tiny_wide(), 16
    rng = np.random.default_rng(1)
    first = rng.integers(1, cfg.vocab_size, 200).tolist()
    second = first[:4 * page] + rng.integers(1, cfg.vocab_size, 150).tolist()
    sp = SamplingParams(max_tokens=6)
    xla = LLMEngine(cfg, max_batch=2, max_len=512, page_size=page,
                    seed=0).generate([second], sp)[0]
    prefill_kernel()
    whole = LLMEngine(cfg, max_batch=2, max_len=512, page_size=page, seed=0)
    assert whole.generate([second], sp)[0] == xla
    assert whole.prefill_stats() == {
        "path": "kernel", "kernel_calls": 1, "xla_calls": 0,
        "kv_blocks_run": 3, "kv_blocks_dense": 4,   # 214 rows in 2 x 128
        "row_blocks_run": 1, "row_blocks_dense": 1}
    hit = LLMEngine(cfg, max_batch=2, max_len=512, page_size=page, seed=0,
                    prefix_cache=True)
    hit.generate([first], sp)
    assert hit.generate([second], sp)[0] == xla
    assert hit.prefix_cache_stats()["hit_pages"] == 4
    st = hit.prefill_stats()
    # 200 rows whole: 3 of 4; 150 suffix rows after 64 cached tokens: 3 + 2
    # of the 2 x (512 + 256) / 128 a gathered page row would cover.
    assert (st["path"], st["kernel_calls"], st["xla_calls"]) == \
        ("kernel", 2, 0)
    assert (st["kv_blocks_run"], st["kv_blocks_dense"]) == (3 + 5, 4 + 12)
    hit.generate([[5, 6, 7]], sp)                       # an 8-row bucket
    st = hit.prefill_stats()
    assert (st["path"], st["kernel_calls"], st["xla_calls"]) == ("xla", 2, 1)
    assert st["kv_blocks_run"] <= st["kv_blocks_dense"]
