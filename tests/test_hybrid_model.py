"""A stack that is a pattern of block kinds (models/transformer.py: `M`
Mamba-2, `E` latent routed experts, `*` attention), its state in the engine
(llm/engine.py: per-slot recurrent state, state checkpoints in the prefix
cache), and the benchmark family that holds it to a plain float32 reference
(benchmark/families/nemotron_h.py).  CPU, tiny sizes, seeded weights,
float32: program and reference then decide alike, and every tolerance below
is rounding of float32 sums in another order (1e-4 on values of order 1).
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import refcheck, selftest
from benchmark.families import nemotron_h as family
from benchmark.run import load_cell
from ray_tpu.llm.engine import LLMEngine, SamplingParams
from ray_tpu.models import mamba2, routed
from ray_tpu.models.transformer import (PRESETS, TransformerConfig,
                                        attention_block, attn_out, block_out,
                                        block_qkv, forward, init_params,
                                        rms_norm)

DIMS = mamba2.Mamba2Dims(num_heads=8, head_dim=4, state=8, groups=2,
                         conv_kernel=4, chunk=8)
ROUTED = routed.RoutedDims(experts=16, held=4, held_from=4, top_k=3,
                           latent=16, width=24, shared_width=40, scale=5.0)
TOL = dict(rtol=1e-4, atol=1e-4)


# ---- the Mamba-2 recurrence ------------------------------------------------

def _token_by_token(x, dt, A, Bm, Cm, h0):
    """h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T; y_t = h_t C_t, one token
    at a time, in numpy."""
    x, dt, A, Bm, Cm, h = (np.asarray(a, np.float64)
                           for a in (x, dt, A, Bm, Cm, h0))
    H, G = x.shape[2], Bm.shape[2]
    ys = np.zeros(x.shape)
    for b in range(x.shape[0]):
        hb = h[b].copy()
        for t in range(x.shape[1]):
            for head in range(H):
                g = head // (H // G)
                hb[head] = np.exp(dt[b, t, head] * A[head]) * hb[head] \
                    + dt[b, t, head] * np.outer(x[b, t, head], Bm[b, t, g])
                ys[b, t, head] = hb[head] @ Cm[b, t, g]
        h[b] = hb
    return ys, h


@pytest.mark.parametrize("start", ["zero", "given"])
@pytest.mark.parametrize("length", [16, 37, 64, 5])
def test_chunked_scan_is_the_token_by_token_recurrence(length, start):
    k = jax.random.split(jax.random.key(length), 6)
    H, P, G, N = DIMS.num_heads, DIMS.head_dim, DIMS.groups, DIMS.state
    x = jax.random.normal(k[0], (2, length, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[1], (2, length, H)))
    A = -jnp.exp(jax.random.normal(k[2], (H,)))
    Bm = jax.random.normal(k[3], (2, length, G, N))
    Cm = jax.random.normal(k[4], (2, length, G, N))
    h0 = jnp.zeros((2, H, P, N)) if start == "zero" \
        else jax.random.normal(k[5], (2, H, P, N))
    y, last, kept = mamba2.ssd(x, dt, A, Bm, Cm, h0, DIMS.chunk, every=16)
    want_y, want_h = _token_by_token(x, dt, A, Bm, Cm, h0)
    np.testing.assert_allclose(np.asarray(y), want_y, **TOL)
    np.testing.assert_allclose(np.asarray(last), want_h, **TOL)
    assert kept.shape[1] == length // 16
    for i in range(length // 16):           # the state after every 16 tokens
        _, at = _token_by_token(x[:, :16 * (i + 1)], dt[:, :16 * (i + 1)], A,
                                Bm[:, :16 * (i + 1)], Cm[:, :16 * (i + 1)], h0)
        np.testing.assert_allclose(np.asarray(kept[:, i]), at, **TOL)


@pytest.mark.parametrize("length", [16, 37])
def test_mixer_in_a_padded_bucket_and_from_a_checkpoint(length):
    """What the engine asks of the mixer: rows past `length` move no state,
    a decode step is a chunk of one token, and the state kept at a boundary
    carries on to the same answer."""
    lp = mamba2.init_layer(jax.random.key(0), 32, DIMS, jnp.float32)
    u = jax.random.normal(jax.random.key(1), (1, length, 32))
    zero = mamba2.zero_state(DIMS, 1, jnp.float32)
    y, end, kept = mamba2.mixer(lp, u, zero, DIMS, every=16)
    padded = jnp.pad(u, ((0, 0), (0, 64 - length), (0, 0)))
    yp, endp, keptp = mamba2.mixer(lp, padded, zero, DIMS, length=length,
                                   every=16)
    np.testing.assert_allclose(yp[:, :length], y, **TOL)
    for key in ("ssm", "tail"):
        np.testing.assert_allclose(endp[key], end[key], **TOL)
        np.testing.assert_allclose(keptp[key][:, :length // 16], kept[key],
                                   **TOL)
    state, rows = zero, []
    for t in range(length):                 # one token at a time
        row, state, _ = mamba2.mixer(lp, u[:, t:t + 1], state, DIMS)
        rows.append(row)
    np.testing.assert_allclose(jnp.concatenate(rows, 1), y, **TOL)
    np.testing.assert_allclose(state["ssm"], end["ssm"], **TOL)
    at16 = {key: kept[key][:, 0] for key in kept}
    rest, end16, _ = mamba2.mixer(lp, u[:, 16:], at16, DIMS) \
        if length > 16 else (y[:, 16:], end, None)
    np.testing.assert_allclose(rest, y[:, 16:], **TOL)
    np.testing.assert_allclose(end16["ssm"], end["ssm"], **TOL)
    live = jnp.asarray([False])             # a slot that is not live
    _, same, _ = mamba2.mixer(lp, u[:, :1], end, DIMS, live=live)
    for key in ("ssm", "tail"):
        np.testing.assert_array_equal(same[key], end[key])


# ---- the routed layer ------------------------------------------------------

def _routed_layer(dims=ROUTED, hidden=32, seed=0):
    return routed.init_layer(jax.random.key(seed), hidden, dims, jnp.float32)


def _plain_routed(lp, x, dims, first, held):
    """The layer in plain numpy: every held expert in turn."""
    lp = jax.tree.map(lambda a: np.asarray(a, np.float64), lp)
    x = np.asarray(x, np.float64)
    s = 1 / (1 + np.exp(-(x @ lp["router"])))
    order = np.argsort(-(s + lp["router_bias"]), -1, kind="stable")
    take = order[:, :dims.top_k]
    w = np.take_along_axis(s, take, -1)
    w = w / w.sum(-1, keepdims=True) * dims.scale
    u = x @ lp["w_down"]
    mix = np.zeros_like(u)
    for e in range(held):
        mine = np.where(take == first + e, w, 0).sum(-1)
        mix += mine[:, None] * (np.maximum(u @ lp["w1"][e], 0) ** 2
                                @ lp["w2"][e])
    shared = np.maximum(x @ lp["ws1"], 0) ** 2 @ lp["ws2"]
    return mix @ lp["w_up"], shared


def test_routed_layer_shapes_with_nothing_dropped():
    lp = _routed_layer()
    x = jax.random.normal(jax.random.key(1), (2, 9, 32))
    y, counts, chosen = jax.jit(
        lambda p, x: routed.mixer(p, x, ROUTED))(lp, x)
    assert y.shape == x.shape and chosen.shape == (2, 9, ROUTED.top_k)
    held = (np.asarray(chosen) >= 4) & (np.asarray(chosen) < 8)
    assert int(counts[1]) == held.sum()     # every held (token, expert) row
    assert int(counts[0]) == len(set(np.asarray(chosen)[held].tolist()))
    mix, shared = _plain_routed(lp, x.reshape(18, 32), ROUTED, 4, 4)
    np.testing.assert_allclose(y.reshape(18, 32), mix + shared, **TOL)


def test_routed_layer_is_dropless_when_every_token_meets_one_expert():
    """A batch sent wholly to one held expert: a capacity would drop most of
    it; every row must be computed."""
    lp = _routed_layer()
    lp["router"] = jnp.zeros_like(lp["router"])
    lp["router_bias"] = jnp.zeros(16).at[5].set(1.0).at[0].set(0.5) \
        .at[1].set(0.25)                    # expert 5 (held), 0 and 1 (not)
    x = jax.random.normal(jax.random.key(2), (1, 40, 32))
    y, counts, chosen = routed.mixer(lp, x, ROUTED)
    assert (np.sort(np.asarray(chosen), -1) == [0, 1, 5]).all()
    assert counts.tolist() == [1, 40]
    mix, shared = _plain_routed(lp, x[0], ROUTED, 4, 4)
    np.testing.assert_allclose(y[0], mix + shared, **TOL)
    _, padded, _ = routed.mixer(lp, x, ROUTED, jnp.arange(40)[None] < 7)
    assert padded.tolist() == [1, 7]        # rows that are not real: none


def test_one_held_expert_is_the_plain_feed_forward():
    dims = dataclasses.replace(ROUTED, experts=1, held=1, held_from=0, top_k=1,
                               scale=1.0)
    lp = _routed_layer(dims)
    x = jax.random.normal(jax.random.key(3), (2, 5, 32))
    y, counts, _ = routed.mixer(lp, x, dims)
    assert counts.tolist() == [1, 10]
    xf = x.reshape(10, 32)
    ffn = jnp.square(jax.nn.relu(xf @ lp["w_down"] @ lp["w1"][0])) \
        @ lp["w2"][0] @ lp["w_up"]
    shared = jnp.square(jax.nn.relu(xf @ lp["ws1"])) @ lp["ws2"]
    np.testing.assert_allclose(y.reshape(10, 32), ffn + shared, **TOL)


def _plain_gated(lp, x, dims):
    """The gated layer of `benchmark/families/lfm2_moe.py` in plain numpy:
    every expert of the model in turn, no latent space; with a shared width
    `benchmark/families/deepseek_v3.py`'s, whose shared expert is one more
    SwiGLU on every row.  -> (the routed sum, the shared expert's part)."""
    lp = jax.tree.map(lambda a: np.asarray(a, np.float64), lp)
    x = np.asarray(x, np.float64)
    s = 1 / (1 + np.exp(-(x @ lp["router"])))
    take = np.argsort(-(s + lp["router_bias"]), -1, kind="stable")[:, :dims.top_k]
    w = np.take_along_axis(s, take, -1)
    w = w / w.sum(-1, keepdims=True) * dims.scale
    out = np.zeros_like(x)
    for e in range(dims.experts):
        mine = np.where(take == e, w, 0).sum(-1)
        gate, up = np.split(x @ lp["w1"][e], 2, -1)
        out += mine[:, None] * ((gate / (1 + np.exp(-gate)) * up) @ lp["w2"][e])
    if not dims.shared_width:
        return out, 0.0
    gate, up = np.split(x @ lp["ws1"], 2, -1)
    return out, (gate / (1 + np.exp(-gate)) * up) @ lp["ws2"]


@pytest.mark.parametrize("form", ["latent_relu2", "gated", "gated_shared"])
def test_the_four_shares_add_up_to_the_uncut_layer(form):
    """The deployment a held share stands for: 4 chips hold a quarter of the
    experts each.  Their routed parts, with the shared expert (which every
    chip computes alike) counted once, are the whole layer: 4 of 16 latent
    relu^2 experts with a shared one (the hybrid), 16 of 64 gated experts at
    top 4 with none (LFM2, whose cell holds all 64), and two halves of 32
    gated experts at top 6 with a gated shared one (Moonlight's layer)."""
    chips = 4
    if form == "gated":
        share = routed.RoutedDims(experts=64, held=16, held_from=0, top_k=4,
                                  latent=0, width=24, shared_width=0,
                                  scale=1.0, gated=True)
    elif form == "gated_shared":
        chips = 2
        share = routed.RoutedDims(experts=64, held=32, held_from=0, top_k=6,
                                  latent=0, width=24, shared_width=48,
                                  scale=2.446, gated=True)
    else:
        share = ROUTED
    n = share.held
    whole = dataclasses.replace(share, held=chips * n, held_from=0)
    lp = _routed_layer(whole)
    x = jax.random.normal(jax.random.key(4), (1, 12, 32))
    mix, shared = _plain_gated(lp, x[0], whole) if form != "latent_relu2" \
        else _plain_routed(lp, x[0], whole, 0, 16)
    parts = []
    for chip in range(chips):
        dims = dataclasses.replace(share, held_from=n * chip)
        mine = dict(lp, w1=lp["w1"][n * chip:n * chip + n],
                    w2=lp["w2"][n * chip:n * chip + n])
        y, _, _ = routed.mixer(mine, x, dims)
        parts.append(np.asarray(y[0], np.float64) - shared)
    np.testing.assert_allclose(sum(parts) + shared, mix + shared, **TOL)
    y, _, _ = routed.mixer(lp, x, whole)
    np.testing.assert_allclose(y[0], mix + shared, **TOL)


# ---- the configuration -----------------------------------------------------

def _file():
    return load_cell("serve_doc_reask_hybrid")["config"]


def test_published_parameter_count_from_the_keys():
    cut = _file()
    pub = {k: v for k, v in cut["published"].items() if k != "parameters"}
    whole = dict(cut, **pub)
    assert round(family.param_count(whole) / 1e9, 2) == 120.67
    assert round(family.param_count(whole, active=True) / 1e9, 2) == 12.77
    assert round(family.param_count(cut) / 1e9, 3) == 4.648
    assert family.program_config(cut).param_count() == family.param_count(cut)
    assert cut["hybrid_override_pattern"] == \
        pub["hybrid_override_pattern"][27:38]
    assert round(family.state_bytes(cut) / 1e6, 1) == 21.3


def test_program_config_refuses_what_the_kinds_cannot_express():
    for key, value in (("n_group", 8), ("mlp_hidden_act", "silu"),
                       ("num_nextn_predict_layers", 1),
                       ("hybrid_override_pattern", "MEMEMEMEM-E")):
        with pytest.raises(ValueError):
            family.program_config(dict(_file(), **{key: value}))


def test_seeded_routers_are_balanced_as_training_would_leave_them():
    """`init_params` sets each routed layer's correction bias so that its
    experts are about equally loaded on fresh seeded tokens: the load of a
    chip's share must not be the seed's to decide."""
    from ray_tpu.models.transformer import (balance_routers, embed_tokens,
                                            mamba_block)
    cfg, pc = _tiny()
    pc = dataclasses.replace(pc, routed=dataclasses.replace(
        pc.routed, experts=32, held=8, held_from=0, top_k=4))
    for seed in (0, 1):
        params = init_params(pc, jax.random.key(seed))
        flat = dict(params, layers=tuple(
            dict(lp, router_bias=jnp.zeros(32)) if "router" in lp else lp
            for lp in params["layers"]))
        again = balance_routers(flat, pc, jax.random.key(9))
        toks = jax.random.randint(jax.random.key(50 + seed), (2, 512), 0, 512)
        spread = []
        for tree in (flat, again, params):
            x = embed_tokens(tree, toks, pc)        # pattern MEM*E: layer 1
            x = mamba_block(tree["layers"][0], x, mamba2.zero_state(
                pc.mamba, 2, pc.dtype), pc)[0]
            chosen = routed.mixer(tree["layers"][1], rms_norm(
                x, tree["layers"][1]["ln"], 1e-5), pc.routed)[2]
            load = np.bincount(np.asarray(chosen).ravel(), minlength=32)
            spread.append(load.std() / load.mean())
        assert spread[1] < 0.2 and spread[2] < 0.2 < spread[0], spread
        assert jax.tree.structure(again) == jax.tree.structure(params)


# ---- the engine ------------------------------------------------------------

def _tiny():
    cell = load_cell("serve_doc_reask_hybrid")
    selftest.shrink(cell)
    return cell["config"], family.program_config(cell["config"],
                                                 max_seq_len=512)


def _engine(pc, seed, **kw):
    kw = {"max_batch": 2, "max_len": 512, "page_size": 16, "kv_pages": 64,
          "prefix_cache": True, **kw}
    return LLMEngine(pc, seed=seed, **kw)


def _prompt(cfg, seed, n=75):
    return np.random.default_rng([seed, 5]).integers(
        1, cfg["vocab_size"], n).tolist()


@pytest.mark.parametrize("seed", [1, 2])
def test_prefill_then_decode_through_the_slot_state_is_the_full_forward(seed):
    cfg, pc = _tiny()
    eng = _engine(pc, seed)
    prompt = _prompt(cfg, seed)
    out = eng.generate([prompt], SamplingParams(max_tokens=8))[0]
    got = eng.trace_logits(prompt, out[:-1])
    toks = jnp.asarray([prompt + out[:-1]], jnp.int32)
    ref = family.reference_logits(eng.params, toks, cfg)[0, len(prompt) - 1:]
    np.testing.assert_allclose(got["logits"], ref, **TOL)
    assert np.asarray(ref).argmax(-1).tolist() == out       # greedy, served
    assert got["chosen"].shape == (2, len(prompt) + 7, 4)
    st = eng.routed_stats()
    assert st["steps"] == 7 and max(st["step_rows"]) <= 4   # one live slot


@pytest.mark.parametrize("cell", ["serve_doc_reask_hybrid",
                                  "serve_doc_reask_moe",
                                  "serve_doc_reask_retention"])
def test_a_step_sent_ahead_advances_the_slot_state_all_the_same(cell):
    """An engine whose owner can say that nobody waits (`hold_ahead`) sends
    the next decode step off before `step()` returns, and the one after it
    before that one is read: with two steps out the recurrent state (the
    hybrid's Mamba-2, LFM2's convolution tails, Brumby's retention) and the
    routed counts come out as from steps read at once, with a second
    request admitted while steps are out, whose prefill installs its
    state into a slot row BEHIND them on the device."""
    loaded = load_cell(cell)
    selftest.shrink(loaded)
    cfg = loaded["config"]
    pc = loaded["family"].program_config(cfg, max_seq_len=512)
    outs, stats = [], []
    for hold in (None, lambda: False):
        eng = _engine(pc, 3)
        eng.hold_ahead = hold
        eng.add_request(_prompt(cfg, 3), SamplingParams(max_tokens=10))
        done = {}
        for i in range(16):
            if i == 3:
                eng.add_request(_prompt(cfg, 4, 40),
                                SamplingParams(max_tokens=6))
            done.update((r.req_id, list(r.out)) for r in eng.step())
        assert not eng.has_unfinished() and len(done) == 2
        outs.append(done)
        stats.append((eng.routed_stats(), eng.decode_stats(),
                      eng.retention_stats()))
    assert outs[0] == outs[1]
    assert eng.phases.snapshot()["ns"]["ahead"] > 0
    # the second request joined a step later, inside the first one's nine
    (routed0, decode0, ret0), (routed1, decode1, ret1) = stats
    assert routed0.get("rows") == routed1.get("rows")
    assert decode0["steps"] == decode1["steps"] == 9
    assert decode0["steps_queued"] == 0 < decode1["steps_queued"]
    assert ret0.get("rows_stepped") == ret1.get("rows_stepped")


def test_a_hit_is_cut_back_to_a_checkpoint_and_answers_like_a_cold_prompt():
    cfg, pc = _tiny()
    eng = _engine(pc, 3)
    doc = _prompt(cfg, 3, 150)              # checkpoints every 64 tokens
    first, second = doc + _prompt(cfg, 4, 9), doc + _prompt(cfg, 5, 12)
    eng.generate([first], SamplingParams(max_tokens=4))
    st = eng.state_stats()
    assert st["every"] == 64 and st["rows_in_use"] == 2 \
        and st["checkpoints_kept"] == 2
    warm = eng.generate([second], SamplingParams(max_tokens=4))[0]
    st, pc_st = eng.state_stats(), eng.prefix_cache_stats()
    assert pc_st["hits"] == 1 and pc_st["hit_pages"] == 8       # 128 tokens
    assert st["tokens_recomputed"] == 144 - 128 \
        and st["hit_prompt_tokens"] == len(second)
    cold = _engine(pc, 3, prefix_cache=False)
    assert cold.generate([second], SamplingParams(max_tokens=4))[0] == warm
    # The same logits, to rounding: the hit's prefill against a cold one.
    hit = eng._run_suffix(second, 128, np.r_[
        eng._cache.lookup(second)[1], np.zeros(24, np.int32)],
        from_row=eng._cache.lookup(second)[2])[0]
    np.testing.assert_allclose(hit, cold._run_prefill(second)[0], **TOL)


def test_eviction_frees_pages_and_checkpoint_rows_together():
    cfg, pc = _tiny()
    eng = _engine(pc, 6, kv_pages=24)       # 384 tokens: 6 checkpoint rows
    assert eng.state_stats()["rows_total"] == 6
    for seed in range(4):                   # 4 x 150 tokens do not fit
        eng.generate([_prompt(cfg, 10 + seed, 150)],
                     SamplingParams(max_tokens=2))
    st, pc_st = eng.state_stats(), eng.prefix_cache_stats()
    assert pc_st["evictions"] > 0 and st["checkpoints_evicted"] > 0
    assert st["rows_in_use"] == st["checkpoints_kept"] \
        - st["checkpoints_evicted"]
    cache = eng._cache
    while cache.evict_lru(eng._decref):
        pass
    assert eng.state_stats()["rows_in_use"] == 0 \
        and sorted(cache.free_rows) == list(range(2, 8))
    assert eng.kv_pages_free() == 24 and not cache._rows


def test_a_pattern_is_served_on_one_device_whole_prompts_at_a_time():
    _, pc = _tiny()
    with pytest.raises(ValueError, match="pattern"):
        _engine(pc, 0, prefill_chunk=64)
    with pytest.raises(ValueError, match="pattern"):
        _engine(pc, 0).prefill_only([1, 2, 3])
    with pytest.raises(ValueError, match="pattern"):
        forward(init_params(pc, jax.random.key(0)), jnp.zeros((1, 4), int), pc)


# ---- the check the family owns --------------------------------------------

def _served(seed):
    cfg, pc = _tiny()
    eng = _engine(pc, seed)
    prompt = _prompt(cfg, seed)
    served = [eng.generate([prompt], SamplingParams(max_tokens=8))[0]
              for _ in range(2)]
    return cfg, eng, prompt, served


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_check_passes_sound_seeds(seed):
    cfg, eng, prompt, served = _served(seed)
    assert eng.prefix_cache_stats()["hits"] == 1    # the second was a hit
    r = refcheck.report(eng, family, cfg, prompt, served)
    assert r["ok"] and r["owned_by"].endswith("nemotron_h"), r
    assert r["forgiven"]["outside_zone"] == 0 and r["logit_max"] < 1e-3
    assert r["traced_from"] == [0, 64]      # cold, then from the checkpoint
    assert "prefill_logit_max" in r["plain"]


def test_check_fails_fp8_rounded_weights():
    cfg, eng, prompt, _ = _served(1)
    low = jax.tree.map(
        lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype)
        if a.ndim > 1 else a, eng.params)
    rounded = LLMEngine(eng.cfg, low, max_batch=2, max_len=512, page_size=16,
                        kv_pages=64)
    served = [rounded.generate([prompt], SamplingParams(max_tokens=8))[0]] * 2

    class Control:                  # the program in fp8, held to the weights
        params = eng.params
        trace_logits = rounded.trace_logits
        _run_prefill = rounded._run_prefill
    r = refcheck.report(Control, family, cfg, prompt, served)
    assert not r["ok"] and r["logit_rms"] > family.TOLERANCE["logit_rms"], r


def test_check_fails_an_altered_token():
    cfg, eng, prompt, served = _served(2)
    worst = int(np.argmin(np.asarray(eng._run_prefill(prompt)[0])))
    r = refcheck.report(eng, family, cfg, prompt,
                        [[worst] + served[0][1:], served[1]])
    assert not r["ok"] and r["margin"] > family.TOLERANCE["margin"], r


def test_check_fails_a_decision_outside_the_zone():
    cfg, eng, prompt, served = _served(3)

    class Flipped:
        params = eng.params
        _run_prefill = eng._run_prefill

        @staticmethod
        def trace_logits(p, toks, cached=False):
            got = eng.trace_logits(p, toks, cached)
            scores = jax.nn.sigmoid(
                rms_norm(eng.params["embed"][jnp.asarray(p[:1])],
                         eng.params["layers"][1]["ln"], 1e-5)
                @ eng.params["layers"][1]["router"])
            last = int(jnp.argmin(scores[0]))       # the worst expert
            if not cached:
                got["chosen"] = got["chosen"].at[0, 0, 0].set(last)
            return got
    r = refcheck.report(Flipped, family, cfg, prompt, served)
    assert not r["ok"] and r["forgiven"]["outside_zone"] > 0, r


# ---- the dense decoder is the pattern of one kind --------------------------

def test_the_dense_decoder_is_the_pattern_of_one_kind():
    cfg = PRESETS["tiny"]
    assert cfg.pattern == "" and cfg.kinds == "D" * cfg.num_layers
    assert cfg.count("*") == 0 and cfg.count("M") == 0
    params = init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (1, 24), 1, cfg.vocab_size)
    eng = LLMEngine(cfg, params, max_batch=2, max_len=64, page_size=16)
    assert eng._every == 0 and "rec" not in eng._dev \
        and eng.state_stats() == {"enabled": False} \
        and eng.routed_stats() == {"enabled": False}
    want = forward(params, tokens, cfg)[0, -1]
    np.testing.assert_allclose(eng._run_prefill(tokens[0].tolist())[0], want,
                               **TOL)
    # The dense block is the attention kind and then its feed-forward.
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    x = jax.random.normal(jax.random.key(2), (1, 8, cfg.hidden_size))
    from ray_tpu.models.transformer import rope_angles
    cos, sin = rope_angles(jnp.arange(8), cfg)

    def attend(q, k, v):
        return q, None
    q, _, _ = block_qkv(lp, x, cos, sin, cfg)
    half, _ = attention_block(lp, x, cos, sin, attend, cfg)
    np.testing.assert_array_equal(half, attn_out(lp, x, q, cfg))
    assert block_out(lp, x, q, cfg).shape == x.shape
